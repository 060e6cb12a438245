"""Pallas TPU kernel: tiled vector–matrix product  u = aᵀ G.

Used by Eva-f (Eq. 21: u = āᵀG) and by the bilinear form (Eq. 13's
b̄ᵀGā = u·b̄).  Memory-bound: each G tile is read once; partial products
accumulate in the f32 VMEM output block across the reduction grid axis
(TPU grid iterations are sequential, so the j-major accumulation is safe).

The (bm × bn) G tile multiplies a (bm, 1) column slice of ``a`` and
accumulates into a (1, bn) output row — blocks whose last two dims Mosaic
accepts (see ``tiles.fit_tiles``).  The tile product is an elementwise
multiply + axis reduction (not ``a @ g``) so the lowering — and therefore
the accumulation order — is identical inside and outside grid loops; this
is what lets ``matvec_stacked`` (stack folded into the leading grid axis,
one launch per parameter bucket) match per-item calls bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bilinear import as_col, pad_mat
from repro.kernels.tiles import fit_tiles


def _tile_matvec(g, a_col):
    """(bm, bn) tile × (bm, 1) slice -> (1, bn) partial products, f32."""
    return jnp.sum(a_col * g, axis=0, keepdims=True)


def _matvec_kernel(g_ref, a_ref, o_ref):
    i = pl.program_id(1)  # reduction index (d_in blocks)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    g = g_ref[...].astype(jnp.float32)
    o_ref[...] += _tile_matvec(g, a_ref[...])


def _matvec_stacked_kernel(g_ref, a_ref, o_ref):
    i = pl.program_id(2)  # reduction index (d_in blocks)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    g = g_ref[0].astype(jnp.float32)
    o_ref[0] += _tile_matvec(g, a_ref[0])


def _prep(g, a, block_in, block_out, interpret):
    d_in, d_out = g.shape[-2:]
    bm, bn = fit_tiles(d_in, d_out, block_in, block_out, g.dtype.itemsize,
                       interpret)
    pad_in, pad_out = (-d_in) % bm, (-d_out) % bn
    return pad_mat(g, pad_in, pad_out), as_col(a, pad_in), bm, bn


@functools.partial(jax.jit, static_argnames=('block_in', 'block_out', 'interpret'))
def matvec(g: jnp.ndarray, a: jnp.ndarray, block_in: int = 512,
           block_out: int = 512, interpret: bool = True) -> jnp.ndarray:
    """u = aᵀ G.  g: (d_in, d_out); a: (d_in,) -> (d_out,) f32."""
    d_out = g.shape[1]
    g, a, bm, bn = _prep(g, a, block_in, block_out, interpret)
    m, n = g.shape
    out = pl.pallas_call(
        _matvec_kernel,
        # out-block-major order: j outer, i inner -> accumulate over i
        grid=(n // bn, m // bm),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
            pl.BlockSpec((bm, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(g, a)
    return out[0, :d_out]


@functools.partial(jax.jit, static_argnames=('block_in', 'block_out', 'interpret'))
def matvec_stacked(g: jnp.ndarray, a: jnp.ndarray, block_in: int = 512,
                   block_out: int = 512, interpret: bool = True) -> jnp.ndarray:
    """Stacked u = aᵀ G.  g: (L, d_in, d_out); a: (L, d_in) -> (L, d_out)
    f32.  One launch; the stack rides the leading grid axis."""
    d_out = g.shape[2]
    g, a, bm, bn = _prep(g, a, block_in, block_out, interpret)
    L, m, n = g.shape
    out = pl.pallas_call(
        _matvec_stacked_kernel,
        grid=(L, n // bn, m // bm),
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda l, j, i: (l, i, j)),
            pl.BlockSpec((1, bm, 1), lambda l, j, i: (l, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bn), lambda l, j, i: (l, 0, j)),
        out_shape=jax.ShapeDtypeStruct((L, 1, n), jnp.float32),
        interpret=interpret,
    )(g, a)
    return out[:, 0, :d_out]


def _matvec_cols_kernel(g_ref, a_ref, o_ref):
    i = pl.program_id(2)  # reduction index (band-row blocks)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    g = g_ref[...].astype(jnp.float32)
    o_ref[0] += _tile_matvec(g, a_ref[0])


@functools.partial(jax.jit, static_argnames=('block_in', 'block_out', 'interpret'))
def matvec_cols(g: jnp.ndarray, a: jnp.ndarray, block_in: int = 512,
                block_out: int = 512, interpret: bool = True) -> jnp.ndarray:
    """Column-blocked partial matvec  U_w = A_w G_w  for factor sharding.

    ``g``: (m, n) — one worker's contiguous row band of a symmetric (n, n)
    factor B (m = band rows; symmetry makes the row band the transposed
    column block, so the band partial is the column-block partial).
    ``a``: (R, m) — the matching owned columns of R stacked vectors.
    Returns (R, n) f32 *partials*: full output width, 1/W of the FLOPs;
    summing the partials over all bands (one zero-padded psum) reconstructs
    ``A B`` exactly — zero pad rows contribute zero.

    Same tile product as :func:`matvec` (elementwise multiply + axis-0
    reduction), so per-band partials summed on the host match the unsharded
    kernel bit-for-bit in f32 accumulation order per tile.
    """
    R, m = a.shape
    m_g, n = g.shape
    if m != m_g:
        raise ValueError(f'matvec_cols: a {a.shape} does not match g {g.shape}')
    g, a, bm, bn = _prep(g, a, block_in, block_out, interpret)
    mp, np_ = g.shape
    out = pl.pallas_call(
        _matvec_cols_kernel,
        # vectors ride the leading grid axis; j outer, i inner accumulation
        grid=(R, np_ // bn, mp // bm),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda r, j, i: (i, j)),
            pl.BlockSpec((1, bm, 1), lambda r, j, i: (r, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bn), lambda r, j, i: (r, 0, j)),
        out_shape=jax.ShapeDtypeStruct((R, 1, np_), jnp.float32),
        interpret=interpret,
    )(g, a)
    return out[:, 0, :n]


def _matvec_cols_stacked_kernel(g_ref, a_ref, o_ref):
    i = pl.program_id(3)  # reduction index (band-row blocks)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    g = g_ref[0].astype(jnp.float32)
    o_ref[0, 0] += _tile_matvec(g, a_ref[0, 0])


@functools.partial(jax.jit, static_argnames=('block_in', 'block_out', 'interpret'))
def matvec_cols_stacked(g: jnp.ndarray, a: jnp.ndarray, block_in: int = 512,
                        block_out: int = 512,
                        interpret: bool = True) -> jnp.ndarray:
    """Stacked :func:`matvec_cols`: one launch per parameter bucket.

    ``g``: (L, m, n) row bands of L factors; ``a``: (L, R, m) owned columns
    of R vectors per factor -> (L, R, n) f32 partials.  The factor stack
    rides the leading grid axis exactly like :func:`matvec_stacked`."""
    L, R, m = a.shape
    Lg, m_g, n = g.shape
    if (L, m) != (Lg, m_g):
        raise ValueError(
            f'matvec_cols_stacked: a {a.shape} does not match g {g.shape}')
    g, a, bm, bn = _prep(g, a, block_in, block_out, interpret)
    mp, np_ = g.shape[1:]
    out = pl.pallas_call(
        _matvec_cols_stacked_kernel,
        grid=(L, R, np_ // bn, mp // bm),
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda l, r, j, i: (l, i, j)),
            pl.BlockSpec((1, 1, bm, 1), lambda l, r, j, i: (l, r, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, bn), lambda l, r, j, i: (l, r, 0, j)),
        out_shape=jax.ShapeDtypeStruct((L, R, 1, np_), jnp.float32),
        interpret=interpret,
    )(g, a)
    return out[:, :, 0, :n]
