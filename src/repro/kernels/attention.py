"""Pallas TPU flash attention with native GQA: a forward kernel and the
two backward kernels (dQ; dK and dV), under one custom VJP.

Layout: q (B, H, S, D), k/v (B, KV, S, D) with H = KV·G; query head
``kv·G + g`` reads KV head ``kv``.  A grid step holds one (q block, k
block) tile of every query head of one KV head: the step loads its K/V
block once and loops over the G heads, so K/V are never repeated.

The grid is (B, KV, T) over the T tiles that hold an unmasked entry: a
causal tile above the diagonal is neither loaded nor computed nor counted
as a step.  Which tile each step takes comes from three scalar-prefetched
tables (``tile_tables``): its q block, its k block and flags saying
whether it opens or closes its row (its column in the dK/dV kernel) and
whether it crosses the diagonal.  Only a crossing tile builds the causal
mask, from iota, inside the kernel, once for its G heads.

Numerics match the pure-JAX path in ``models/flash.py`` as the TPU runs
it: q·kᵀ and dO·vᵀ take the inputs' dtype into the MXU with f32
accumulation; the 1/√D scale multiplies the f32 scores; max, sums,
exponentials and the log-sum-exp are f32; P and dS enter their matmuls in
the inputs' dtype.  The log-sum-exp (the forward's residual) and
rowsum(dO·O) travel as (B, H, 1, S) f32 rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
NEG_INF = -1e30
FIRST, LAST, MASKED = 1, 2, 4          # tile flags
NN = (((1,), (0,)), ((), ()))          # x @ y
NT = (((1,), (1,)), ((), ()))          # x @ yᵀ
VMEM_LIMIT = 96 * 2 ** 20    # 16 heads of 128 outgrow the 16 MiB default


def tile_tables(n_q: int, n_k: int, block_q: int, block_k: int,
                causal: bool, by: str = 'q') -> np.ndarray:
    """(3, T) int32: q block, k block and flags of every tile with an
    unmasked entry, row by row (``by='q'``) or column by column
    (``by='k'``).  ``FIRST``/``LAST`` mark a row's (column's) first and
    last tile; ``MASKED`` a tile crossing the causal diagonal."""
    tiles = [(i, j) for i in range(n_q) for j in range(n_k)
             if not causal or j * block_k <= i * block_q + block_q - 1]
    if by == 'k':
        tiles.sort(key=lambda t: (t[1], t[0]))
    outer = 0 if by == 'q' else 1
    rows = []
    for n, (i, j) in enumerate(tiles):
        flags = 0
        if n == 0 or tiles[n - 1][outer] != tiles[n][outer]:
            flags |= FIRST
        if n == len(tiles) - 1 or tiles[n + 1][outer] != tiles[n][outer]:
            flags |= LAST
        if causal and j * block_k + block_k - 1 > i * block_q:
            flags |= MASKED
        rows.append((i, j, flags))
    return np.asarray(rows, np.int32).T


def _lanes(x, n: int):
    """A (rows, 128) lane-replicated column, as (rows, n)."""
    if n <= LANE:
        return x[:, :n]
    return jnp.tile(x, (1, n // LANE))


def _each_tile(flags, body, shape, q0, k0, k_rows: bool = False):
    """``body(keep)`` under the tile's flag: on a tile that crosses the
    diagonal ``keep`` is its causal mask (True where the key does not
    follow the query; ``k_rows``: keys run down the rows), built once for
    the tile's G heads; elsewhere None."""
    masked = (flags & MASKED) != 0

    @pl.when(masked)
    def _masked():
        rows = lax.broadcasted_iota(jnp.int32, shape, 0)
        cols = lax.broadcasted_iota(jnp.int32, shape, 1)
        body((cols - rows if k_rows else rows - cols) >= k0 - q0)

    pl.when(jnp.logical_not(masked))(lambda: body(None))


def _scores(a, b, scale, keep):
    """a·bᵀ in f32, scaled, with the masked entries at ``NEG_INF``."""
    s = lax.dot_general(a, b, NT, preferred_element_type=jnp.float32) * scale
    return s if keep is None else jnp.where(keep, s, NEG_INF)


def _fwd_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, scale, groups, bq, bk):
    t = pl.program_id(2)
    i, j, flags = qi_ref[t], ki_ref[t], fl_ref[t]
    dh = acc_ref.shape[-1]

    @pl.when((flags & FIRST) != 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(keep):
        k, v = k_ref[...], v_ref[...]

        def head(g, _):
            s = _scores(q_ref[g], k, scale, keep)
            m_prev, l_prev = m_ref[g], l_ref[g]
            m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - _lanes(m_next, bk))
            alpha = jnp.exp(m_prev - m_next)
            l_ref[g] = alpha * l_prev + lax.broadcast_in_dim(
                p.sum(axis=-1), l_prev.shape, (0,))
            m_ref[g] = m_next
            pv = lax.dot_general(p.astype(v.dtype), v, NN,
                                 preferred_element_type=jnp.float32)
            acc_ref[g] = _lanes(alpha, dh) * acc_ref[g] + pv
            return None

        lax.fori_loop(0, groups, head, None)

    _each_tile(flags, body, (bq, bk), i * bq, j * bk)

    @pl.when((flags & LAST) != 0)
    def _end():
        def head(g, _):
            l = l_ref[g]
            o_ref[g] = (acc_ref[g] / _lanes(l, dh)).astype(o_ref.dtype)
            lse_ref[g] = jnp.transpose(m_ref[g] + jnp.log(l))[:1]
            return None

        lax.fori_loop(0, groups, head, None)


def _dq_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, acc_ref, *, scale, groups, bq, bk):
    t = pl.program_id(2)
    i, j, flags = qi_ref[t], ki_ref[t], fl_ref[t]

    @pl.when((flags & FIRST) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(keep):
        k, v = k_ref[...], v_ref[...]

        def head(g, _):
            s = _scores(q_ref[g], k, scale, keep)
            p = jnp.exp(s - lse_ref[g, 0][:, None])
            dp = lax.dot_general(do_ref[g], v, NT,
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[g, 0][:, None])
            acc_ref[g] += lax.dot_general(ds.astype(k.dtype), k, NN,
                                          preferred_element_type=jnp.float32)
            return None

        lax.fori_loop(0, groups, head, None)

    _each_tile(flags, body, (bq, bk), i * bq, j * bk)

    @pl.when((flags & LAST) != 0)
    def _end():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale, groups,
                bq, bk):
    t = pl.program_id(2)
    i, j, flags = qi_ref[t], ki_ref[t], fl_ref[t]

    @pl.when((flags & FIRST) != 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(keep):
        k, v = k_ref[...], v_ref[...]

        def head(g, _):
            q, do = q_ref[g], do_ref[g]
            st = _scores(k, q, scale, keep)
            pt = jnp.exp(st - lse_ref[g])
            dv_acc[...] += lax.dot_general(pt.astype(do.dtype), do, NN,
                                           preferred_element_type=jnp.float32)
            dpt = lax.dot_general(v, do, NT,
                                  preferred_element_type=jnp.float32)
            dst = pt * (dpt - delta_ref[g])
            dk_acc[...] += lax.dot_general(dst.astype(q.dtype), q, NN,
                                           preferred_element_type=jnp.float32)
            return None

        lax.fori_loop(0, groups, head, None)

    _each_tile(flags, body, (bk, bq), i * bq, j * bk, k_rows=True)

    @pl.when((flags & LAST) != 0)
    def _end():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=('parallel', 'parallel', 'arbitrary'),
        vmem_limit_bytes=VMEM_LIMIT)


def _specs(groups, bq, bk, dh):
    """BlockSpecs by role: the G heads' q-shaped block, the KV head's
    k-shaped block, the G heads' rows of (B, H, 1, S) statistics."""
    q = pl.BlockSpec((None, groups, bq, dh),
                     lambda b, h, t, qi, ki, fl: (b, h, qi[t], 0))
    kv = pl.BlockSpec((None, None, bk, dh),
                      lambda b, h, t, qi, ki, fl: (b, h, ki[t], 0))
    row = pl.BlockSpec((None, groups, 1, bq),
                       lambda b, h, t, qi, ki, fl: (b, h, 0, qi[t]))
    return q, kv, row


def _forward(q, k, v, causal, block_q, block_k, interpret):
    b, h, s, dh = q.shape
    kvh = k.shape[1]
    groups = h // kvh
    tabs = tile_tables(s // block_q, s // block_k, block_q, block_k, causal)
    q_spec, kv_spec, row_spec = _specs(groups, block_q, block_k, dh)
    kernel = functools.partial(_fwd_kernel, scale=dh ** -0.5, groups=groups,
                               bq=block_q, bk=block_k)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, kvh, tabs.shape[1]),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[q_spec, row_spec],
            scratch_shapes=[pltpu.VMEM((groups, block_q, LANE), jnp.float32),
                            pltpu.VMEM((groups, block_q, LANE), jnp.float32),
                            pltpu.VMEM((groups, block_q, dh), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name='flash_fwd',
    )(*tabs, q, k, v)
    return o, lse


def _backward(q, k, v, o, lse, do, causal, block_q, block_k, interpret):
    b, h, s, dh = q.shape
    kvh = k.shape[1]
    groups = h // kvh
    scale = dh ** -0.5
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)[:, :, None, :]
    q_spec, kv_spec, row_spec = _specs(groups, block_q, block_k, dh)
    ins = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
    grid = functools.partial(pltpu.PrefetchScalarGridSpec,
                             num_scalar_prefetch=3, in_specs=ins)
    nq, nk = s // block_q, s // block_k
    tabs = tile_tables(nq, nk, block_q, block_k, causal)
    kw = dict(scale=scale, groups=groups, bq=block_q, bk=block_k)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid_spec=grid(grid=(b, kvh, tabs.shape[1]), out_specs=q_spec,
                       scratch_shapes=[pltpu.VMEM((groups, block_q, dh),
                                                  jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_params(), interpret=interpret, name='flash_dq',
    )(*tabs, q, k, v, do, lse, delta)
    tabs = tile_tables(nq, nk, block_q, block_k, causal, by='k')
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid_spec=grid(grid=(b, kvh, tabs.shape[1]),
                       out_specs=[kv_spec, kv_spec],
                       scratch_shapes=[pltpu.VMEM((block_k, dh), jnp.float32),
                                       pltpu.VMEM((block_k, dh), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_params(), interpret=interpret, name='flash_dkv',
    )(*tabs, q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool, block_q: int, block_k: int,
                    interpret: bool = False):
    """q: (B, H, S, D); k/v: (B, KV, S, D) -> (B, H, S, D).  S must be a
    multiple of both blocks; ``block_k`` a multiple of 128."""
    return _forward(q, k, v, causal, block_q, block_k, interpret)[0]


def _vjp_fwd(q, k, v, causal, block_q, block_k, interpret):
    o, lse = _forward(q, k, v, causal, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _vjp_bwd(causal, block_q, block_k, interpret, res, do):
    return _backward(*res, do, causal, block_q, block_k, interpret)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
