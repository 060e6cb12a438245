"""Attention core with a custom VJP: a compiled Pallas flash kernel on the
TPU, the pure-JAX flash below everywhere else.

``flash_attention`` picks its path at trace time by what it observes
(``attention_plan``): the TPU backend, arrays whole on one device (no
enclosing Auto mesh of more than one device; inside ``shard_map`` they
are per device), S_q equal to S_k, a head size the kernel takes and S a
multiple of the kernel's block.  There it runs ``kernels/attention.py``,
which skips causal tiles above the diagonal in every pass and builds the
mask per tile inside the kernel.  Each trace adds its plan to the lists
``collect`` holds open; the trainer writes the plans of a step's trace
as one ``attention_plan`` record.

The pure-JAX path (``flash_attention_xla``) is the CPU path and the
kernel's reference.  It never saves the S×S probability tensor (the
naive composition's residual, 112 GiB/layer at 32k context):

  forward : online-softmax over K/V chunks (scan), saving only
            (out, q, k, v, lse) — O(S·d), never O(S²);
  backward: recomputes P chunk-by-chunk exactly (via the saved LSE) and
            accumulates dQ, dK, dV — the standard flash-attention-2 split:
            dQ with a scan over KV chunks, dK/dV with a scan over Q chunks.

GQA is native in both: queries are grouped (B, S, KV, G, Dh) and K/V are
never repeated.  The pure-JAX path masks every (q_chunk × k_chunk) tile
and computes fully masked ones too.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import attention as kattention
from repro.kernels import dispatch
from repro.sharding.constraints import current_mesh

NEG_INF = -1e30
KERNEL_HEAD_DIMS = (64, 128)
_COLLECTORS: list = []  # the open ``collect`` lists, innermost last


@dataclasses.dataclass(frozen=True)
class AttentionPlan:
    """What one call site of ``flash_attention`` runs, per (batch, head):
    ``tiles`` of the ``tiles_total`` (block_q × block_k) tiles of S×S."""
    site: str           # 'B x S x H/KV x D/causal|full', per device
    path: str           # 'pallas' | 'xla'
    rule: str           # why this path
    block_q: int
    block_k: int
    tiles: int
    tiles_total: int


def kernel_block(s: int) -> Optional[int]:
    """The kernel's square block for sequence ``s``: the largest of 512,
    256 and 128 that divides it, else None.  On a v5e at S 2048, 512 beat
    256 and 1024 for head sizes 64 and 128 alike (PERF.md), so the head
    size does not enter."""
    return next((b for b in (512, 256, 128) if s % b == 0), None)


def attention_plan(q_shape, k_shape, causal: bool, q_chunk: int,
                   k_chunk: int) -> AttentionPlan:
    """The path ``flash_attention`` takes for these shapes, by backend
    and shape alone."""
    b, sq, h, dh = q_shape
    sk, kvh = k_shape[1], k_shape[2]
    site = f'{b}x{sq}x{h}/{kvh}x{dh}/{"causal" if causal else "full"}'
    mesh = current_mesh()
    block = kernel_block(sq)
    if dispatch.backend() != 'tpu':
        rule = f'backend {dispatch.backend()}'
    elif mesh is not None and mesh.size > 1:
        rule = f'arrays split over an Auto mesh of {mesh.size} devices'
    elif sq != sk:
        rule = f'S_q {sq} != S_k {sk}'
    elif dh not in KERNEL_HEAD_DIMS:
        rule = f'head_dim {dh} not in {KERNEL_HEAD_DIMS}'
    elif block is None:
        rule = f'S {sq} not a multiple of 128'
    else:
        n = sq // block
        tiles = n * (n + 1) // 2 if causal else n * n
        return AttentionPlan(site, 'pallas', f'tpu, S {sq} = {n} x {block}, '
                             f'head_dim {dh}', block, block, tiles, n * n)
    qc, kc = min(q_chunk, sq), min(k_chunk, sk)
    n = (sq // qc) * (sk // kc)
    return AttentionPlan(site, 'xla', rule, qc, kc, n, n)


@contextlib.contextmanager
def collect():
    """A list that gathers the plan of every ``flash_attention`` call
    traced while the context is open."""
    plans: list = []
    _COLLECTORS.append(plans)
    try:
        yield plans
    finally:
        _COLLECTORS.remove(plans)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, q_chunk: int = 512,
                    k_chunk: int = 1024) -> jnp.ndarray:
    """q: (B,S,H,Dh); k/v: (B,S,KV,Dh) -> (B,S,H,Dh), by the path
    ``attention_plan`` picks; ``q_chunk``/``k_chunk`` tile the pure-JAX
    path only."""
    plan = attention_plan(q.shape, k.shape, causal, q_chunk, k_chunk)
    for plans in _COLLECTORS:
        plans.append(plan)
    if plan.path == 'xla':
        return flash_attention_xla(q, k, v, causal, q_chunk, k_chunk)
    heads_major = lambda x: jnp.swapaxes(x, 1, 2)
    out = kattention.flash_attention(heads_major(q), heads_major(k),
                                     heads_major(v), causal, plan.block_q,
                                     plan.block_k)
    return heads_major(out)


def _chunk(x: jnp.ndarray, axis: int, size: int) -> jnp.ndarray:
    """(…, S, …) -> (…, S/size, size, …) with the chunk axis moved to 0."""
    n = x.shape[axis] // size
    new_shape = x.shape[:axis] + (n, size) + x.shape[axis + 1:]
    return jnp.moveaxis(x.reshape(new_shape), axis, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_xla(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, q_chunk: int = 512,
                        k_chunk: int = 1024) -> jnp.ndarray:
    """q: (B,S,H,Dh); k/v: (B,S,KV,Dh) -> (B,S,H,Dh)."""
    out, _ = _flash_fwd_inner(q, k, v, causal, q_chunk, k_chunk)
    return out


def _flash_fwd_inner(q, k, v, causal, q_chunk, k_chunk):
    b, sq, h, dh = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    q_chunk = min(q_chunk, sq)
    k_chunk = min(k_chunk, sk)
    nq, nk = sq // q_chunk, sk // k_chunk
    scale = 1.0 / math.sqrt(dh)

    qg = q.reshape(b, nq, q_chunk, kvh, g, dh)
    kc = _chunk(k, 1, k_chunk)      # (nk, b, kc, kvh, dh)
    vc = _chunk(v, 1, k_chunk)

    def one_q(qi, q_blk):
        def kv_step(carry, xs):
            m, l, acc = carry
            ki, k_blk, v_blk = xs
            s = jnp.einsum('bqkgd,bskd->bkgqs', q_blk.astype(jnp.float32),
                           k_blk.astype(jnp.float32)) * scale
            if causal:
                qp = qi * q_chunk + jnp.arange(q_chunk)
                kp = ki * k_chunk + jnp.arange(k_chunk)
                s = jnp.where((qp[:, None] >= kp[None, :])[None, None, None],
                              s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                'bkgqs,bskd->bkgqd', p, v_blk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, q_chunk, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kc, vc))
        l = jnp.maximum(l, 1e-30)
        out = acc / l[..., None]
        lse = m + jnp.log(l)                          # (b,kvh,g,qc)
        return jnp.moveaxis(out, 3, 1), lse           # (b,qc,kvh,g,dh)

    outs, lses = jax.lax.map(lambda args: one_q(*args),
                             (jnp.arange(nq), jnp.moveaxis(qg, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, dh).astype(q.dtype)
    # lses: (nq, b, kvh, g, qc) -> (b, kvh, g, nq, qc) -> (b, kvh, g, sq)
    lse = jnp.moveaxis(lses, 0, 3).reshape(b, kvh, g, sq)
    return out, lse


def _flash_fwd(q, k, v, causal, q_chunk, k_chunk):
    out, lse = _flash_fwd_inner(q, k, v, causal, q_chunk, k_chunk)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, q_chunk, k_chunk, res, dout):
    q, k, v, out, lse = res
    b, sq, h, dh = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    q_chunk = min(q_chunk, sq)
    k_chunk = min(k_chunk, sk)
    nq, nk = sq // q_chunk, sk // k_chunk
    scale = 1.0 / math.sqrt(dh)

    qg = q.reshape(b, sq, kvh, g, dh)
    og = out.reshape(b, sq, kvh, g, dh)
    dog = dout.reshape(b, sq, kvh, g, dh)
    # delta = rowsum(dO ⊙ O)  (b,kvh,g,sq)
    delta = jnp.einsum('bskgd,bskgd->bkgs', dog.astype(jnp.float32),
                       og.astype(jnp.float32))

    qc = _chunk(qg, 1, q_chunk)       # (nq, b, qc, kvh, g, dh)
    doc = _chunk(dog, 1, q_chunk)
    kc = _chunk(k, 1, k_chunk)        # (nk, b, kc, kvh, dh)
    vc = _chunk(v, 1, k_chunk)
    lse_c = _chunk(lse, 3, q_chunk)   # (nq, b, kvh, g, qc)
    delta_c = _chunk(delta, 3, q_chunk)

    def p_tile(qi, ki, q_blk, k_blk, lse_blk):
        s = jnp.einsum('bqkgd,bskd->bkgqs', q_blk.astype(jnp.float32),
                       k_blk.astype(jnp.float32)) * scale
        if causal:
            qp = qi * q_chunk + jnp.arange(q_chunk)
            kp = ki * k_chunk + jnp.arange(k_chunk)
            s = jnp.where((qp[:, None] >= kp[None, :])[None, None, None],
                          s, NEG_INF)
        return jnp.exp(s - lse_blk[..., None])        # (b,kvh,g,qc,kc)

    # --- dQ: for each q chunk, scan kv chunks ---
    def dq_one(qi, q_blk, do_blk, lse_blk, delta_blk):
        def step(dq_acc, xs):
            ki, k_blk, v_blk = xs
            p = p_tile(qi, ki, q_blk, k_blk, lse_blk)
            dp = jnp.einsum('bqkgd,bskd->bkgqs', do_blk.astype(jnp.float32),
                            v_blk.astype(jnp.float32))
            ds = p * (dp - delta_blk[..., None])
            dq_acc = dq_acc + jnp.einsum('bkgqs,bskd->bqkgd', ds,
                                         k_blk.astype(jnp.float32)) * scale
            return dq_acc, None

        dq0 = jnp.zeros((b, q_chunk, kvh, g, dh), jnp.float32)
        dq, _ = jax.lax.scan(step, dq0, (jnp.arange(nk), kc, vc))
        return dq

    dqs = jax.lax.map(lambda a: dq_one(*a),
                      (jnp.arange(nq), qc, doc, lse_c, delta_c))
    dq = jnp.moveaxis(dqs, 0, 1).reshape(b, sq, h, dh).astype(q.dtype)

    # --- dK/dV: for each kv chunk, scan q chunks ---
    def dkv_one(ki, k_blk, v_blk):
        def step(carry, xs):
            dk_acc, dv_acc = carry
            qi, q_blk, do_blk, lse_blk, delta_blk = xs
            p = p_tile(qi, ki, q_blk, k_blk, lse_blk)
            dv_acc = dv_acc + jnp.einsum('bkgqs,bqkgd->bskd', p,
                                         do_blk.astype(jnp.float32))
            dp = jnp.einsum('bqkgd,bskd->bkgqs', do_blk.astype(jnp.float32),
                            v_blk.astype(jnp.float32))
            ds = p * (dp - delta_blk[..., None])
            dk_acc = dk_acc + jnp.einsum('bkgqs,bqkgd->bskd', ds,
                                         q_blk.astype(jnp.float32)) * scale
            return (dk_acc, dv_acc), None

        z = jnp.zeros((b, k_chunk, kvh, dh), jnp.float32)
        (dk, dv), _ = jax.lax.scan(
            step, (z, z), (jnp.arange(nq), qc, doc, lse_c, delta_c))
        return dk, dv

    dks, dvs = jax.lax.map(lambda a: dkv_one(*a), (jnp.arange(nk), kc, vc))
    dk = jnp.moveaxis(dks, 0, 1).reshape(b, sk, kvh, dh).astype(k.dtype)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(b, sk, kvh, dh).astype(v.dtype)
    return dq, dk, dv


flash_attention_xla.defvjp(_flash_fwd, _flash_bwd)
