"""Pallas TPU kernel: bilinear form  d = aᵀ G b  (Eq. 13 numerator).

Single pass over G: each (bm × bn) tile contracts against its a- and
b-slices and accumulates into a lane-broadcast (1, 128) f32 VMEM block
across the whole sequential grid (Mosaic stores vectors, not scalars, to
VMEM).  Combined with ``rank1_update`` this gives the two-pass fused Eva
step: 2 reads + 1 write of G total (vs ≥4 G-sized transfers for the
unfused jnp composition).

Vector operands ride in TPU-legal blocks: ``a`` as a (d_in, 1) column
(block (bm, 1)), ``b`` as a (1, d_out) row (block (1, bn)), so the tile
product is a plain broadcast.

``bilinear_stacked`` folds a leading stack of L independent (G, a, b)
problems into the grid as its leading axis — one kernel launch for a whole
parameter bucket (layers of identical shape, see ``core/bucketing``).  The
per-tile program and the (i, j) iteration order within each stack entry are
identical to the unstacked kernel, so stacked and per-item results agree
bit-for-bit.  The tile contraction is written as an elementwise
multiply + reduction (not ``jnp.dot``): reduction lowering is stable across
grid-loop contexts, where dot_general on CPU may pick different blocked
algorithms inside vs outside a loop and break that bit-equality.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiles import LANE, fit_tiles


def _tile_bilinear(g, a_col, b_row):
    """Contract one (bm, bn) tile against its (bm, 1) / (1, bn) slices ->
    scalar f32."""
    return jnp.sum((a_col * g) * b_row)


def _bilinear_kernel(g_ref, a_ref, b_ref, o_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    g = g_ref[...].astype(jnp.float32)
    o_ref[...] += _tile_bilinear(g, a_ref[...], b_ref[...])


def _bilinear_stacked_kernel(g_ref, a_ref, b_ref, o_ref):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when((i == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    g = g_ref[0].astype(jnp.float32)
    o_ref[...] += _tile_bilinear(g, a_ref[0], b_ref[0])


def as_col(v, pad=0):
    """(..., d) vector -> (..., d + pad, 1) f32 column, zero-padded."""
    v = v.astype(jnp.float32)
    if pad:
        v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, pad)])
    return v[..., :, None]


def as_row(v, pad=0):
    """(..., d) vector -> (..., 1, d + pad) f32 row, zero-padded."""
    v = v.astype(jnp.float32)
    if pad:
        v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, pad)])
    return v[..., None, :]


def pad_mat(g, pad_in, pad_out):
    """Zero-pad the trailing (d_in, d_out) dims of ``g``."""
    if pad_in or pad_out:
        g = jnp.pad(g, [(0, 0)] * (g.ndim - 2) + [(0, pad_in), (0, pad_out)])
    return g


def fit_and_pad(g, a, b, block_in, block_out, interpret):
    """Fit the tiles to ``g``'s trailing (d_in, d_out) and zero-pad ``g``,
    ``a`` (as a column) and ``b`` (as a row) to whole tiles:
    ``(g, a_col, b_row, bm, bn)``."""
    d_in, d_out = g.shape[-2:]
    bm, bn = fit_tiles(d_in, d_out, block_in, block_out, g.dtype.itemsize,
                       interpret)
    pad_in, pad_out = (-d_in) % bm, (-d_out) % bn
    return (pad_mat(g, pad_in, pad_out), as_col(a, pad_in),
            as_row(b, pad_out), bm, bn)


@functools.partial(jax.jit, static_argnames=('block_in', 'block_out', 'interpret'))
def bilinear(g: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
             block_in: int = 512, block_out: int = 512,
             interpret: bool = True) -> jnp.ndarray:
    """aᵀ G b -> () f32.  g: (d_in, d_out); a: (d_in,); b: (d_out,)."""
    g, a, b, bm, bn = fit_and_pad(g, a, b, block_in, block_out,
                                    interpret)
    m, n = g.shape
    out = pl.pallas_call(
        _bilinear_kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, LANE), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, LANE), jnp.float32),
        interpret=interpret,
    )(g, a, b)
    return out[0, 0]


@functools.partial(jax.jit, static_argnames=('block_in', 'block_out', 'interpret'))
def bilinear_stacked(g: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                     block_in: int = 512, block_out: int = 512,
                     interpret: bool = True) -> jnp.ndarray:
    """Stacked aᵀ G b -> (L,) f32.  g: (L, d_in, d_out); a: (L, d_in);
    b: (L, d_out).  One launch; the stack rides the leading grid axis."""
    g, a, b, bm, bn = fit_and_pad(g, a, b, block_in, block_out,
                                    interpret)
    L, m, n = g.shape
    out = pl.pallas_call(
        _bilinear_stacked_kernel,
        grid=(L, m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda l, i, j: (l, i, j)),
            pl.BlockSpec((1, bm, 1), lambda l, i, j: (l, i, 0)),
            pl.BlockSpec((1, 1, bn), lambda l, i, j: (l, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, LANE), lambda l, i, j: (l, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((L, 1, LANE), jnp.float32),
        interpret=interpret,
    )(g, a, b)
    return out[:, 0, 0]
