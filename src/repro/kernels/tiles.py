"""Tile fitting shared by the kernels and the dispatch layer.

The kernels pad each operand up to a multiple of the block size and slice
the pad back off.  Two rules, by whether the kernel is compiled for the TPU:

* interpret mode (``align=1``): keep the tile *count* implied by the
  requested block but shrink the block to the smallest size covering the
  dim in that many tiles, so the pad is at most ``tiles - 1`` elements::

      d=520, block=512  ->  2 tiles of 260 (pad 0)
      d=1000, block=512 ->  2 tiles of 500 (pad 0)
      d<=block          ->  1 tile of d    (pad 0)

* compiled (``align>1``): Mosaic requires the last two dims of every block
  to be multiples of the hardware tiling (lanes: 128; sublanes: 8 for f32,
  16 for bf16) or the full array dim.  A pad here is a whole extra HBM copy
  of the operand, so among the aligned blocks in ``[block/4, block]`` the
  one with the least padded size wins (ties: the larger block)::

      d=4864, block=512, align=128 -> 256 (19 tiles, pad 0)
      d=896,  block=512, align=16  -> 448 (2 tiles, pad 0)

Kept dependency-free (no jax import) so both the kernel modules and
``dispatch`` can use it without an import cycle.
"""
from __future__ import annotations

LANE = 128


def fit_block(d: int, block: int, align: int = 1) -> int:
    """Block size for a dim of size ``d`` at requested ``block``.

    ``align`` > 1 returns either ``d`` itself or a multiple of ``align``.
    """
    if d <= 0:
        raise ValueError(f'fit_block: dim must be positive, got {d}')
    if block <= 0:
        raise ValueError(f'fit_block: block must be positive, got {block}')
    if align > 1:
        block = max(align, block - block % align)
    if d <= block:
        return d
    if align == 1:
        tiles = -(-d // block)      # ceil: tile count at the requested block
        return -(-d // tiles)       # smallest block covering d in that many
    lo = max(align, (block // 4) - (block // 4) % align)
    return min(range(lo, block + 1, align),
               key=lambda b: (-(-d // b) * b, -b))


def sublane_align(itemsize: int) -> int:
    """Second-minor tiling of a dtype: 8 rows of 32-bit, packed for narrower
    types (16 for bf16, 32 for int8)."""
    return 8 * max(1, 4 // int(itemsize))


def fit_tiles(d_in: int, d_out: int, block_in: int, block_out: int,
              itemsize: int, interpret: bool) -> tuple[int, int]:
    """(bm, bn) for a (d_in, d_out) operand of ``itemsize``-byte elements:
    d_in rides the sublanes, d_out the lanes."""
    if interpret:
        return fit_block(d_in, block_in), fit_block(d_out, block_out)
    return (fit_block(d_in, block_in, sublane_align(itemsize)),
            fit_block(d_out, block_out, LANE))
