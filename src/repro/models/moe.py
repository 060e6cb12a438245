"""Mixture-of-Experts with sort-based capacity dispatch (MaxText-style).

Tokens' top-k expert assignments are sorted by expert id, positioned within
each expert's segment, and scattered into a dense ``(E, C, D)`` buffer
(capacity ``C = ceil(T·k·cf / E)``); overflow drops.  Expert FFNs are a
single stacked einsum — with the expert axis sharded over the 'model' mesh
axis this is expert parallelism, and XLA inserts the dispatch/combine
all-to-alls from the sharding constraints.

Eva-for-MoE (beyond-paper): each expert weight gets a per-expert tap
``(E, d_out)`` and masked per-expert input means, so the rank-one
preconditioner applies vmapped over experts.  The router is an ordinary
preconditioned linear.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import kv as kvlib
from repro.models.layers import linear, linear_spec
from repro.sharding.constraints import constrain
from repro.models.module import ParamSpec


def moe_spec(d: int, d_ff: int, n_experts: int, dtype=jnp.float32) -> dict:
    def w(shape, axes):
        return ParamSpec(shape, dtype, axes, init='scaled')
    return {
        'router': linear_spec(d, n_experts, ('embed', None), dtype, bias=False),
        'gate': {'w': w((n_experts, d, d_ff), ('expert', 'embed', 'mlp'))},
        'up': {'w': w((n_experts, d, d_ff), ('expert', 'embed', 'mlp'))},
        'down': {'w': w((n_experts, d_ff, d), ('expert', 'mlp', 'embed'))},
    }


def capacity(n_tokens: int, top_k: int, n_experts: int, factor: float) -> int:
    c = int(math.ceil(n_tokens * top_k * factor / n_experts))
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def _expert_linear(w: jnp.ndarray, x: jnp.ndarray, *, wpath: str, col,
                   taps, capture, mask) -> jnp.ndarray:
    """x: (E, ..., d_in) @ w: (E, d_in, d_out) with per-expert stats/taps.
    mask: (E, ...) slot validity."""
    if capture is not None and capture.a is not None:
        xf = x.reshape(x.shape[0], -1, x.shape[-1])
        mf = mask.reshape(mask.shape[0], -1)
        col[wpath] = kvlib.fwd_stats_masked(xf, mf, capture)
    y = jnp.einsum('e...d,edf->e...f', x, w)
    if taps is not None and wpath in taps:
        tap = taps[wpath].reshape((taps[wpath].shape[0],) + (1,) * (y.ndim - 2)
                                  + (taps[wpath].shape[-1],))
        y = y + tap.astype(y.dtype)
    return y


def _n_data_shards() -> int:
    """Data-axis size of the current mesh (1 outside a mesh context)."""
    from repro.sharding.constraints import current_mesh
    m = current_mesh()
    if m is None:
        return 1
    n = 1
    for a in ('pod', 'data'):
        if a in m.shape:
            n *= m.shape[a]
    return n


def moe_apply(p: dict, x: jnp.ndarray, *, top_k: int, capacity_factor: float,
              norm_topk: bool = True, path: str = '', col=None,
              taps=None, capture=None, compute_dtype=None,
              aux_coef: float = 0.0):
    """x: (B, S, D) -> (y, aux_loss).  Dropless up to capacity; overflow drops."""
    col = col if col is not None else {}
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    n_experts = p['gate']['w'].shape[0]

    logits = linear(p['router'], xt, path=f'{path}/router', col=col,
                    taps=taps, capture=capture, compute_dtype=compute_dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)     # (T, E)
    gate_vals, expert_ids = jax.lax.top_k(probs, top_k)             # (T, k)
    if norm_topk:
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # --- load-balancing auxiliary loss (Switch-style) ---
    if aux_coef:
        me = jnp.mean(probs, axis=0)                                 # (E,)
        ce = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], n_experts), axis=0)
        aux = aux_coef * n_experts * jnp.sum(me * ce)
    else:
        aux = jnp.zeros((), jnp.float32)

    # --- group-local sort-based dispatch (hierarchical all-to-all) ---
    # Tokens are routed *within their data shard's group* (G = number of
    # data shards; per-group capacity C_l).  Dispatch/combine gathers are
    # then shard-local, and the only cross-device movement is resharding
    # the (E, G, C_l, D) slot tensor from token-major (G over data axes) to
    # expert-major (E over model axis) — a clean all-to-all of slot volume,
    # instead of the (T, D)-sized all-reduce per layer SPMD emits for
    # global gathers/scatters (§Perf iterations 2–3, EXPERIMENTS.md).
    # Only int32 index tables go through scatters.
    groups = _n_data_shards()
    if t % groups or (t // groups) < top_k:
        groups = 1
    tg = t // groups
    cap = capacity(tg, top_k, n_experts, capacity_factor)

    ids_g = expert_ids.reshape(groups, tg * top_k)                   # (G,T_l*k)
    ok_shape = (groups, tg, top_k)

    def route(flat_e):
        """Per-group slot assignment from (T_l*k,) expert ids."""
        sort_idx = jnp.argsort(flat_e)
        counts = jnp.zeros((n_experts,), jnp.int32).at[flat_e].add(1)
        seg_start = jnp.cumsum(counts) - counts
        inv_rank = jnp.zeros((tg * top_k,), jnp.int32).at[sort_idx].set(
            jnp.arange(tg * top_k, dtype=jnp.int32))
        pos_tk = inv_rank - seg_start[flat_e]
        ok_tk = pos_tk < cap
        safe_pos = jnp.where(ok_tk, pos_tk, cap)
        tk_token = jnp.arange(tg * top_k, dtype=jnp.int32) // top_k
        slot_token = jnp.zeros((n_experts, cap + 1), jnp.int32).at[
            flat_e, safe_pos].set(tk_token)[:, :cap]
        slot_mask = jnp.zeros((n_experts, cap + 1), jnp.float32).at[
            flat_e, safe_pos].set(ok_tk.astype(jnp.float32))[:, :cap]
        flat_slot = flat_e * cap + jnp.minimum(pos_tk, cap - 1)
        return slot_token, slot_mask, flat_slot, ok_tk

    slot_token, slot_mask, flat_slot, ok_tk = jax.vmap(route)(ids_g)
    slot_mask = jnp.moveaxis(slot_mask, 0, 1)                        # (E,G,C)

    xd = xt.astype(compute_dtype) if compute_dtype is not None else xt
    xg = constrain(xd.reshape(groups, tg, d), 'data', None, None)
    disp = jax.vmap(lambda xs, idx: jnp.take(xs, idx, axis=0))(
        xg, slot_token)                                              # (G,E,C,D)
    disp = jnp.moveaxis(disp, 0, 1)                                  # (E,G,C,D)
    disp = disp * slot_mask[..., None].astype(disp.dtype)
    disp = constrain(disp, 'model', 'data', None, None)

    # --- expert FFN (E = expert parallelism, G = data parallelism) ---
    wd = (lambda w: w.astype(compute_dtype)) if compute_dtype is not None else (lambda w: w)
    kw = dict(col=col, taps=taps, capture=capture, mask=slot_mask)
    g = _expert_linear(wd(p['gate']['w']), disp, wpath=f'{path}/gate/w', **kw)
    u = _expert_linear(wd(p['up']['w']), disp, wpath=f'{path}/up/w', **kw)
    h = jax.nn.silu(g) * u
    out_e = _expert_linear(wd(p['down']['w']), h, wpath=f'{path}/down/w', **kw)
    out_e = constrain(out_e, 'model', 'data', None, None)

    # --- combine: gather + weighted top-k sum, all group-local ---
    out_g = jnp.moveaxis(out_e, 1, 0).reshape(groups, n_experts * cap, d)
    out_g = constrain(out_g, 'data', None, None)
    w_g = (gate_vals.reshape(groups, tg, top_k)
           * ok_tk.reshape(groups, tg, top_k)).astype(jnp.float32)

    def combine(os, idx, wg):
        y_tk = jnp.take(os, idx, axis=0).reshape(tg, top_k, d)
        return jnp.einsum('tkd,tk->td', y_tk.astype(jnp.float32), wg)

    y_g = jax.vmap(combine)(out_g, flat_slot, w_g)                   # (G,T_l,D)
    y_g = constrain(y_g, 'data', None, None)
    return y_g.reshape(b, s, d).astype(x.dtype), aux
