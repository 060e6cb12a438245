"""Pallas kernels: fused Eva precondition -> update epilogue, one launch.

The composed bucket hot path costs ~4 gradient-sized HBM round trips after
the stats are ready: ``bilinear`` reads G, ``rank1_update`` reads G and
writes P, the momentum trace reads (m, P) and writes m, and the KL trust
region reads (m, G) again for the inner product.  These kernels do all of
it in ONE pass over G per bucket:

  phase 0  accumulate the reduction (aᵀGb for Eva / aᵀG for Eva-f) into a
           tiny VMEM-resident output, visiting tiles in exactly the same
           order as the standalone ``bilinear``/``matvec`` kernels — the
           reduction is bit-identical to the composed path;
  phase 1  re-stream G: compute the rank-one tile P = s·(G − c·abᵀ)
           (bit-identical to ``rank1_update``), optionally fold the
           heavy-ball momentum ``out = μ·m + P``, write the f32 output
           tile, and accumulate the epilogue partials
           ``aux = [⟨out,G⟩, ⟨out,out⟩, ⟨G,G⟩]`` per stack item.

The trust-region scale ν (Eq. 16) depends on the GLOBAL ⟨u,g⟩ across every
parameter, so it cannot be applied inside a per-bucket launch; the aux
partials make the remaining host-side tail a scalar reduction plus one
cheap elementwise scale.  ``aux``'s tile-major accumulation order differs
from the composed ``tree_vdot`` (which reduces each leaf fully first), so
the folded tail agrees with the composed chain to f32 reduction tolerance
(~1e-6 relative).

Both kernels use a two-phase grid ``(L, 2, ...)``: TPU grid iterations are
sequential per core, so every phase-0 tile of a stack item completes before
its phase-1 tiles read the reduction back.  G is read twice from HBM — the
reduction output is far too small to carry tile partials for a one-read
formulation — so the win over the composed path is the dropped P/m/vdot
round trips, not the G reads.  The reduction lives in VMEM scratch (a
revisited *output* block is not re-read from HBM on the TPU), the per-item
scalars in SMEM, and ``aux`` in lanes 0..2 of a (1, 128) block; the output
and momentum blocks stay parked on tile (0, 0) through phase 0, so phase 0
neither fetches m nor writes back an untouched output tile.

"Bit-identical" above holds per tile formula; across a whole launch the
in-kernel coeff division (``dot/denom``) can contract differently from the
host-side division of the composed path, so end-to-end agreement with the
composed chain is within 1 f32 ulp of the update scale (γ·|Δ| < 1e-6),
not universally bit-exact — see tests/test_fused.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bilinear import _tile_bilinear, as_col, as_row, pad_mat
from repro.kernels.matvec import _tile_matvec
from repro.kernels.rank1_update import SMEM_SPEC, _rank1_tile
from repro.kernels.tiles import LANE, fit_tiles


def _epilogue_tile(g, p, m, mu, fold, o_ref, aux_ref):
    """Shared phase-1 tail: momentum fold + output write + aux partials
    accumulated into lanes 0..2 of the item's (1, 128) aux block."""
    out = mu * m + p if fold else p
    o_ref[0] = out
    lane = jax.lax.broadcasted_iota(jnp.int32, aux_ref.shape[1:], 1)
    sums = (jnp.sum(out * g), jnp.sum(out * out), jnp.sum(g * g))
    aux_ref[0] += jnp.where(lane == 0, sums[0],
                            jnp.where(lane == 1, sums[1],
                                      jnp.where(lane == 2, sums[2], 0.0)))


def _make_eva_fused_kernel(fold: bool):
    def kernel(g_ref, a_ref, b_ref, sc_ref, m_ref, o_ref, aux_ref, dot_ref):
        l = pl.program_id(0)
        ph = pl.program_id(1)
        i = pl.program_id(2)
        j = pl.program_id(3)

        @pl.when((ph == 0) & (i == 0) & (j == 0))
        def _init():
            dot_ref[...] = jnp.zeros_like(dot_ref)
            aux_ref[...] = jnp.zeros_like(aux_ref)

        g = g_ref[0].astype(jnp.float32)
        a = a_ref[0]
        b = b_ref[0]

        @pl.when(ph == 0)
        def _reduce():
            dot_ref[...] += _tile_bilinear(g, a, b)

        @pl.when(ph == 1)
        def _emit():
            coeff = dot_ref[:, :1] / sc_ref[l, 0]
            p = _rank1_tile(g, a, b, coeff, sc_ref[l, 1])
            _epilogue_tile(g, p, m_ref[0], sc_ref[l, 2], fold, o_ref,
                           aux_ref)

    return kernel


def _make_eva_f_fused_kernel(fold: bool, bn: int, n_col_blocks: int):
    def u_block(u_ref, j):
        if n_col_blocks == 1:
            return u_ref
        return u_ref.at[:, pl.ds(pl.multiple_of(j * bn, bn), bn)]

    def kernel(g_ref, a_ref, sc_ref, m_ref, o_ref, aux_ref, u_ref):
        l = pl.program_id(0)
        ph = pl.program_id(1)
        j = pl.program_id(2)
        i = pl.program_id(3)

        @pl.when((ph == 0) & (j == 0) & (i == 0))
        def _init():
            u_ref[...] = jnp.zeros_like(u_ref)
            aux_ref[...] = jnp.zeros_like(aux_ref)

        g = g_ref[0].astype(jnp.float32)
        a = a_ref[0]
        u = u_block(u_ref, j)

        @pl.when(ph == 0)
        def _reduce():
            u[...] += _tile_matvec(g, a)

        @pl.when(ph == 1)
        def _emit():
            p = _rank1_tile(g, a, u[...], 1.0 / sc_ref[l, 0], sc_ref[l, 1])
            _epilogue_tile(g, p, m_ref[0], sc_ref[l, 2], fold, o_ref,
                           aux_ref)

    return kernel


def _scalars(denom, gamma, mu):
    """(L, 3) SMEM operand: [denom, 1/γ, μ] per stack item."""
    L = denom.shape[0]
    return jnp.stack([denom,
                      jnp.full((L,), 1.0 / gamma, jnp.float32),
                      jnp.full((L,), mu, jnp.float32)], axis=-1)


def _prep(g, m, block_in, block_out, interpret):
    d_in, d_out = g.shape[1:]
    bm, bn = fit_tiles(d_in, d_out, block_in, block_out, g.dtype.itemsize,
                       interpret)
    pad_in, pad_out = (-d_in) % bm, (-d_out) % bn
    return (pad_mat(g, pad_in, pad_out),
            pad_mat(m.astype(jnp.float32), pad_in, pad_out),
            bm, bn, pad_in, pad_out)


def _tile_spec(bm, bn, phase_axis):
    """G-shaped block that parks on tile (0, 0) while phase 0 runs
    (``phase_axis`` picks which of the two inner grid indices is i)."""
    if phase_axis == 'ij':
        return pl.BlockSpec((1, bm, bn), lambda l, p, i, j: (l, i * p, j * p))
    return pl.BlockSpec((1, bm, bn), lambda l, p, j, i: (l, i * p, j * p))


@functools.partial(jax.jit, static_argnames=('gamma', 'mu', 'fold_momentum',
                                             'block_in', 'block_out',
                                             'interpret'))
def eva_fused_stacked(g, a, b, gamma: float, m, mu: float,
                      fold_momentum: bool = True,
                      block_in: int = 512, block_out: int = 512,
                      interpret: bool = True):
    """Fused Eva (Eq. 13) + epilogue.  g: (L, d_in, d_out); a: (L, d_in);
    b: (L, d_out); m: (L, d_in, d_out) f32 momentum buffer.

    Returns ``(out, aux)``: out (L, d_in, d_out) f32 = μ·m + P (P only when
    ``fold_momentum=False``); aux (L, 3) f32 = [⟨out,g⟩, ⟨out,out⟩, ⟨g,g⟩].
    """
    L, d_in, d_out = g.shape
    a32 = a.astype(jnp.float32)
    b32 = b.astype(jnp.float32)
    sc = _scalars(gamma + jnp.sum(a32 * a32, -1) * jnp.sum(b32 * b32, -1),
                  gamma, mu)
    g, m, bm, bn, pad_in, pad_out = _prep(g, m, block_in, block_out,
                                          interpret)
    mp, np_ = g.shape[1:]
    out, aux = pl.pallas_call(
        _make_eva_fused_kernel(fold_momentum),
        grid=(L, 2, mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda l, p, i, j: (l, i, j)),
            pl.BlockSpec((1, bm, 1), lambda l, p, i, j: (l, i, 0)),
            pl.BlockSpec((1, 1, bn), lambda l, p, i, j: (l, 0, j)),
            SMEM_SPEC,
            _tile_spec(bm, bn, 'ij'),
        ],
        out_specs=[
            _tile_spec(bm, bn, 'ij'),
            pl.BlockSpec((1, 1, LANE), lambda l, p, i, j: (l, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L, mp, np_), jnp.float32),
            jax.ShapeDtypeStruct((L, 1, LANE), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, LANE), jnp.float32)],
        interpret=interpret,
    )(g, as_col(a32, pad_in), as_row(b32, pad_out), sc, m)
    if (mp, np_) != (d_in, d_out):
        out = out[:, :d_in, :d_out]
    return out, aux[:, 0, :3]


@functools.partial(jax.jit, static_argnames=('gamma', 'mu', 'fold_momentum',
                                             'block_in', 'block_out',
                                             'interpret'))
def eva_f_fused_stacked(g, a, gamma: float, m, mu: float,
                        fold_momentum: bool = True,
                        block_in: int = 512, block_out: int = 512,
                        interpret: bool = True):
    """Fused Eva-f (Eq. 21) + epilogue; same contract as
    :func:`eva_fused_stacked` with u = aᵀG accumulated in phase 0 into a
    full-width VMEM row (each column block revisits its slice in phase 1)."""
    L, d_in, d_out = g.shape
    a32 = a.astype(jnp.float32)
    sc = _scalars(gamma + jnp.sum(a32 * a32, -1), gamma, mu)
    g, m, bm, bn, pad_in, pad_out = _prep(g, m, block_in, block_out,
                                          interpret)
    mp, np_ = g.shape[1:]
    out, aux = pl.pallas_call(
        _make_eva_f_fused_kernel(fold_momentum, bn, np_ // bn),
        grid=(L, 2, np_ // bn, mp // bm),
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda l, p, j, i: (l, i, j)),
            pl.BlockSpec((1, bm, 1), lambda l, p, j, i: (l, i, 0)),
            SMEM_SPEC,
            _tile_spec(bm, bn, 'ji'),
        ],
        out_specs=[
            _tile_spec(bm, bn, 'ji'),
            pl.BlockSpec((1, 1, LANE), lambda l, p, j, i: (l, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L, mp, np_), jnp.float32),
            jax.ShapeDtypeStruct((L, 1, LANE), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, np_), jnp.float32)],
        interpret=interpret,
    )(g, as_col(a32, pad_in), sc, m)
    if (mp, np_) != (d_in, d_out):
        out = out[:, :d_in, :d_out]
    return out, aux[:, 0, :3]
