"""Pallas TPU kernel: fused rank-one update  P = s·(G − c·a bᵀ).

This is the hot half of Eva's Sherman–Morrison step (Eq. 13): a purely
memory-bound pass over the gradient (read G once, write P once, ~3 flops per
element).  The roofline goal is streaming G at HBM bandwidth, so:

  * G is tiled (block_in × block_out) in blocks Mosaic accepts
    (``tiles.fit_tiles``: lane tiles of 128, sublane tiles of 8 for f32 /
    16 for bf16, or the full dim);
  * the KV slices ride as a (bm, 1) column of a and a (1, bn) row of b —
    tiny VMEM residents;
  * coeff/scale ride in SMEM as scalars (computed on the host side of the
    op — see ops.eva_precondition).

Grid iteration order is (d_in/bm, d_out/bn), sequential per TPU core;
the fused multiply-sub runs on the VPU while the next G tile streams in.
``rank1_update_stacked`` folds a leading stack of L problems into the grid
(one launch per parameter bucket); the body is purely elementwise, so
stacked and per-item results agree bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bilinear import fit_and_pad

SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _rank1_tile(g, a_col, b_row, coeff, scale):
    return scale * (g - coeff * (a_col * b_row))


def _rank1_kernel(g_ref, a_ref, b_ref, cs_ref, o_ref):
    g = g_ref[...].astype(jnp.float32)
    o_ref[...] = _rank1_tile(g, a_ref[...], b_ref[...], cs_ref[0],
                             cs_ref[1]).astype(o_ref.dtype)


def _rank1_stacked_kernel(g_ref, a_ref, b_ref, cs_ref, o_ref):
    l = pl.program_id(0)
    g = g_ref[0].astype(jnp.float32)
    o_ref[0] = _rank1_tile(g, a_ref[0], b_ref[0], cs_ref[l, 0],
                           cs_ref[l, 1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=('block_in', 'block_out', 'interpret'))
def rank1_update(g: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                 coeff: jnp.ndarray, scale: jnp.ndarray,
                 block_in: int = 512, block_out: int = 512,
                 interpret: bool = True) -> jnp.ndarray:
    """P = scale·(G − coeff·a bᵀ).  g: (d_in, d_out); a: (d_in,); b: (d_out,).

    Shapes not divisible by the block are padded (the pad region computes
    garbage that is sliced off — cheaper than ragged BlockSpecs).
    """
    d_in, d_out = g.shape
    g, a, b, bm, bn = fit_and_pad(g, a, b, block_in, block_out,
                                    interpret)
    m, n = g.shape
    cs = jnp.stack([jnp.asarray(coeff, jnp.float32),
                    jnp.asarray(scale, jnp.float32)])
    out = pl.pallas_call(
        _rank1_kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), g.dtype),
        interpret=interpret,
    )(g, a, b, cs)
    if (m, n) != (d_in, d_out):
        out = out[:d_in, :d_out]
    return out


@functools.partial(jax.jit, static_argnames=('block_in', 'block_out', 'interpret'))
def rank1_update_stacked(g: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                         coeff: jnp.ndarray, scale: jnp.ndarray,
                         block_in: int = 512, block_out: int = 512,
                         interpret: bool = True) -> jnp.ndarray:
    """Stacked P = scale·(G − coeff·a bᵀ); one launch for the whole stack.

    g: (L, d_in, d_out); a: (L, d_in); b: (L, d_out); coeff/scale: (L,).
    """
    L, d_in, d_out = g.shape
    g, a, b, bm, bn = fit_and_pad(g, a, b, block_in, block_out,
                                    interpret)
    m, n = g.shape[1:]
    cs = jnp.stack([jnp.asarray(coeff, jnp.float32),
                    jnp.asarray(scale, jnp.float32)], axis=-1)   # (L, 2)
    out = pl.pallas_call(
        _rank1_stacked_kernel,
        grid=(L, m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda l, i, j: (l, i, j)),
            pl.BlockSpec((1, bm, 1), lambda l, i, j: (l, i, 0)),
            pl.BlockSpec((1, 1, bn), lambda l, i, j: (l, 0, j)),
            SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda l, i, j: (l, i, j)),
        out_shape=jax.ShapeDtypeStruct((L, m, n), g.dtype),
        interpret=interpret,
    )(g, a, b, cs)
    if (m, n) != (d_in, d_out):
        out = out[:, :d_in, :d_out]
    return out
