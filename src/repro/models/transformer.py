"""Decoder-only transformer LM covering the dense / MoE / VLM families.

Layer-stacked params under ``jax.lax.scan`` (compact HLO even at 61 layers /
1T params), capture-aware linears everywhere, three entry points:

  * ``loss_fn``     — next-token CE (+ MoE aux), returns KV-capture stats
  * ``prefill_fn``  — populate a KV cache, return last-position logits
  * ``decode_fn``   — one token in, logits + updated cache out

VLM/audio archs (``input_is_embeds``) take precomputed frontend embeddings
for train/prefill (the modality frontend is a stub per assignment) and fall
back to the token embedding table for decode.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import kv as kvlib
from repro.kernels import attention as kattention
from repro.models import module as M
from repro.models.attention import attention_block, attention_spec
from repro.models.layers import embed, embed_spec, linear, linear_spec, make_norm, mlp, mlp_spec
from repro.models.moe import moe_apply, moe_spec
from repro.sharding.constraints import shard_activations


def _remat_policy(name: str):
    """The ``jax.checkpoint`` policy of ``cfg.remat``: ``'full'`` saves
    nothing; ``'dots'`` saves what the MXU produced, the matmul outputs and
    the attention kernel's output and log-sum-exp
    (``kernels.attention.RESIDUAL_NAMES``), whose only rebuild is a second
    ``flash_fwd`` launch; ``'none'`` (None) does not checkpoint.  The
    pure-JAX attention path (``models/flash.py``: the CPU, and the TPU
    where the kernel cannot run) names no residual, so there ``'dots'``
    keeps recomputing its forward and spends no memory on it."""
    cp = jax.checkpoint_policies
    if name == 'full':
        return cp.nothing_saveable
    if name == 'dots':
        return cp.save_from_both_policies(
            cp.dots_with_no_batch_dims_saveable,
            cp.save_only_these_names(*kattention.RESIDUAL_NAMES))
    return None


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean next-token CE in f32 without materializing one-hots."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


class TransformerLM:
    """Families: dense, moe, vlm."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    # -- specs ------------------------------------------------------------

    def block_spec(self) -> dict:
        cfg = self.cfg
        norm_spec, _ = make_norm(cfg.norm)
        spec = {
            'norm1': norm_spec(cfg.d_model, cfg.pdtype),
            'attn': attention_spec(cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                   cfg.head_dim, cfg.pdtype, cfg.qkv_bias),
            'norm2': norm_spec(cfg.d_model, cfg.pdtype),
        }
        if cfg.n_experts:
            spec['moe'] = moe_spec(cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.pdtype)
            if cfg.n_shared_experts:
                spec['shared_mlp'] = mlp_spec(cfg.d_model,
                                              cfg.d_ff * cfg.n_shared_experts,
                                              cfg.pdtype)
        else:
            spec['mlp'] = mlp_spec(cfg.d_model, cfg.d_ff, cfg.pdtype)
        return spec

    def param_specs(self) -> dict:
        cfg = self.cfg
        norm_spec, _ = make_norm(cfg.norm)
        specs = {
            'embed': embed_spec(cfg.vocab, cfg.d_model, cfg.pdtype),
            'blocks': M.stack_specs(self.block_spec(), cfg.n_layers),
            'norm_f': norm_spec(cfg.d_model, cfg.pdtype),
        }
        if not cfg.tie_embeddings:
            specs['lm_head'] = linear_spec(cfg.d_model, cfg.vocab,
                                           ('embed', 'vocab'), cfg.pdtype)
        return specs

    def precon_paths(self) -> set[str]:
        cfg = self.cfg
        paths = set()
        for sub in ('q', 'k', 'v', 'o'):
            paths.add(f'blocks/attn/{sub}/w')
        if cfg.n_experts:
            paths |= {'blocks/moe/router/w', 'blocks/moe/gate/w',
                      'blocks/moe/up/w', 'blocks/moe/down/w'}
            if cfg.n_shared_experts:
                paths |= {f'blocks/shared_mlp/{s}/w' for s in ('gate', 'up', 'down')}
        else:
            paths |= {f'blocks/mlp/{s}/w' for s in ('gate', 'up', 'down')}
        if not cfg.tie_embeddings:
            paths.add('lm_head/w')
        return paths

    # -- block ------------------------------------------------------------

    def _block(self, p, x, *, positions, col, taps, capture,
               cache=None, cache_pos=None):
        cfg = self.cfg
        _, norm = make_norm(cfg.norm)
        kw = dict(col=col, taps=taps, capture=capture, compute_dtype=cfg.cdtype)
        h = norm(p['norm1'], x)
        att, new_cache = attention_block(
            p['attn'], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, positions=positions, causal=True,
            rope=True, rope_theta=cfg.rope_theta, impl=cfg.attn_impl,
            q_chunk=cfg.q_chunk, k_chunk=cfg.k_chunk,
            cache=cache, cache_pos=cache_pos, path='attn', **kw)
        x = x + att
        h2 = norm(p['norm2'], x)
        if cfg.n_experts:
            ff, aux = moe_apply(p['moe'], h2, top_k=cfg.top_k,
                                capacity_factor=cfg.capacity_factor,
                                norm_topk=cfg.norm_topk, path='moe',
                                aux_coef=cfg.moe_aux_coef, **kw)
            if cfg.n_shared_experts:
                ff = ff + mlp(p['shared_mlp'], h2, path='shared_mlp', **kw)
        else:
            ff, aux = mlp(p['mlp'], h2, path='mlp', **kw), jnp.zeros((), jnp.float32)
        return x + ff, new_cache, aux

    # -- forward (train / prefill share the stacked scan) ------------------

    def _forward(self, params, x, positions, *, taps=None, capture=None,
                 cache=None, cache_pos=None):
        cfg = self.cfg
        block_taps = M.subtree(taps, 'blocks') or {}
        has_cache = cache is not None

        def body(carry, xs):
            h = shard_activations(carry)
            if has_cache:
                bp, bt, bc = xs
            else:
                bp, bt = xs
                bc = None
            bcol: dict = {}
            h, new_bc, aux = self._block(
                bp, h, positions=positions, col=bcol, taps=bt or None,
                capture=capture, cache=bc, cache_pos=cache_pos)
            ys = (bcol, new_bc, aux) if has_cache else (bcol, aux)
            return h, ys

        policy = _remat_policy(cfg.remat)
        if policy is not None or cfg.remat == 'full':
            body = jax.checkpoint(body, policy=policy)

        if has_cache:
            xs = (params['blocks'], block_taps, cache['blocks'])
            x, (cols, new_caches, auxs) = jax.lax.scan(
                body, x, xs, unroll=cfg.scan_unroll)
            new_cache = dict(cache)
            new_cache['blocks'] = new_caches
        else:
            xs = (params['blocks'], block_taps)
            x, (cols, auxs) = jax.lax.scan(body, x, xs, unroll=cfg.scan_unroll)
            new_cache = None
        col = M.add_prefix(cols, 'blocks')
        return x, col, jnp.sum(auxs), new_cache

    def _logits(self, params, x, col, taps, capture):
        cfg = self.cfg
        _, norm = make_norm(cfg.norm)
        x = norm(params['norm_f'], x)
        if cfg.tie_embeddings:
            return x.astype(cfg.cdtype) @ params['embed']['table'].T.astype(cfg.cdtype)
        return linear(params['lm_head'], x, path='lm_head', col=col,
                      taps=taps, capture=capture, compute_dtype=cfg.cdtype)

    def _embed_in(self, params, batch):
        cfg = self.cfg
        if cfg.input_is_embeds and 'embeds' in batch:
            return batch['embeds'].astype(cfg.cdtype)
        return embed(params['embed'], batch['tokens'], cfg.cdtype)

    # -- entry points -------------------------------------------------------

    def loss_fn(self, params, taps, batch, capture: Optional[kvlib.CaptureConfig]):
        cfg = self.cfg
        x = self._embed_in(params, batch)
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x, col, aux, _ = self._forward(x=x, params=params, positions=positions,
                                       taps=taps, capture=capture)
        logits = self._logits(params, x, col, taps, capture)
        loss = cross_entropy(logits, batch['labels']) + aux
        return loss, {'stats': col, 'n_tokens': b * s}

    def init_cache(self, batch_size: int, max_seq: int, abstract: bool = False):
        cfg = self.cfg
        mk = (lambda shp, dt: jax.ShapeDtypeStruct(shp, dt)) if abstract else \
             (lambda shp, dt: jnp.zeros(shp, dt))
        dt = jnp.dtype(cfg.cache_dtype)
        blocks = {'k': mk((cfg.n_layers, batch_size, max_seq, cfg.n_kv_heads,
                           cfg.head_dim), dt),
                  'v': mk((cfg.n_layers, batch_size, max_seq, cfg.n_kv_heads,
                           cfg.head_dim), dt)}
        return {'blocks': blocks}

    def prefill_fn(self, params, batch):
        cfg = self.cfg
        x = self._embed_in(params, batch)
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        cache = self.init_cache(b, s)
        x, col, _, cache = self._forward(x=x, params=params, positions=positions,
                                         cache=cache)
        logits = self._logits(params, x[:, -1:, :], col, None, None)
        return logits[:, 0], cache

    def decode_fn(self, params, cache, tokens, pos):
        """tokens: (B,) int32; pos: scalar int32 — write position."""
        cfg = self.cfg
        x = embed(params['embed'], tokens[:, None], cfg.cdtype)
        positions = jnp.full((tokens.shape[0], 1), pos)
        x, col, _, new_cache = self._forward(x=x, params=params,
                                             positions=positions,
                                             cache=cache, cache_pos=pos)
        logits = self._logits(params, x, col, None, None)
        return logits[:, 0], new_cache
