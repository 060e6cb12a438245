"""Tile-size / impl autotuner feeding the kernel dispatch cache.

For each (op, shape, dtype) the tuner benchmarks a small block_in/block_out
grid of the Pallas kernel plus the pure-XLA ``ref.py`` path and records the
winner in a JSON cache keyed on (backend, op, shape, dtype) — the format
``dispatch.install_cache`` consumes and ``tile_defaults.json`` ships as
warm-start defaults:

    {"version": 1,
     "backend": "cpu",
     "entries": {"cpu/bilinear/float32/512x384":
                 {"impl": "xla", "block_in": 512, "block_out": 384,
                  "us": 12.3}}}

Determinism: given identical measurements the output bytes are identical —
entries are emitted with ``json.dumps(sort_keys=True, indent=2)``, the
candidate list is a fixed-order dedup, and ties break toward (lower time,
'xla' before 'pallas', smaller blocks).  Tests inject a fake ``bench`` to
pin the measurements and assert byte-stable output.

CLI: ``scripts/autotune.py``; programmatic warm-start:
``dispatch.install_cache(tune([...]))``.
"""
from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Callable, Iterable, Optional

import jax
import jax.numpy as jnp

from repro.kernels import bilinear as _bil
from repro.kernels import fused as _fused
from repro.kernels import matvec as _mv
from repro.kernels import rank1_update as _r1
from repro.kernels import ref
from repro.kernels.dispatch import DEFAULT_BLOCK, backend, cache_key
from repro.kernels.tiles import fit_tiles

OPS = ('bilinear', 'matvec', 'rank1_update')
FUSED_OPS = ('eva_fused', 'eva_f_fused')
DEFAULT_GRID = ((128, 128), (256, 256), (512, 512))
_IMPL_RANK = {'xla': 0, 'pallas': 1}


def default_bench(fn: Callable[[], object], reps: int = 3,
                  warmup: int = 1) -> float:
    """Median wall µs of ``fn()`` (must block on its result)."""
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e6)
    times.sort()
    return times[len(times) // 2]


def _operands(op: str, d_in: int, d_out: int, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    g = jax.random.normal(ks[0], (d_in, d_out), jnp.float32).astype(dtype)
    a = jax.random.normal(ks[1], (d_in,), jnp.float32).astype(dtype)
    b = jax.random.normal(ks[2], (d_out,), jnp.float32).astype(dtype)
    m = jnp.zeros((1, d_in, d_out), jnp.float32)
    return g, a, b, m


def _candidate_fn(op: str, impl: str, g, a, b, m, bm: int, bn: int,
                  interpret: bool):
    """A no-arg, result-blocking callable running one op instance."""
    coeff = jnp.float32(0.37)
    scale = jnp.float32(2.5)
    if impl == 'xla':
        table = {
            'bilinear': lambda: ref.bilinear_ref(g, a, b),
            'matvec': lambda: ref.matvec_ref(g, a),
            'rank1_update': lambda: ref.rank1_update_ref(g, a, b, coeff,
                                                         scale),
            'eva_fused': lambda: ref.eva_fused_ref(g[None], a[None], b[None],
                                                   0.03, m, 0.9, True)[0],
            'eva_f_fused': lambda: ref.eva_f_fused_ref(g[None], a[None],
                                                       0.03, m, 0.9, True)[0],
        }
    else:
        kw = dict(block_in=bm, block_out=bn, interpret=interpret)
        table = {
            'bilinear': lambda: _bil.bilinear(g, a, b, **kw),
            'matvec': lambda: _mv.matvec(g, a, **kw),
            'rank1_update': lambda: _r1.rank1_update(g, a, b, coeff, scale,
                                                     **kw),
            'eva_fused': lambda: _fused.eva_fused_stacked(
                g[None], a[None], b[None], 0.03, m, 0.9, **kw)[0],
            'eva_f_fused': lambda: _fused.eva_f_fused_stacked(
                g[None], a[None], 0.03, m, 0.9, **kw)[0],
        }
    fn = table[op]
    jitted = jax.jit(fn)
    return lambda: jax.block_until_ready(jitted())


def _candidates(op: str, d_in: int, d_out: int, grid, impls,
                itemsize: int = 4, interpret: bool = True):
    """Fixed-order (impl, block_in, block_out) list; fitted duplicates
    collapse to the first occurrence so the sweep stays deterministic."""
    seen, out = set(), []
    for impl in impls:
        if impl == 'xla':
            pairs = ((DEFAULT_BLOCK, DEFAULT_BLOCK),)
        else:
            pairs = grid
        for bi, bo in pairs:
            bm, bn = fit_tiles(d_in, d_out, bi, bo, itemsize, interpret)
            key = (impl, bm, bn)
            if key not in seen:
                seen.add(key)
                out.append(key)
    return out


def tune(shapes: Iterable[tuple[int, int]], *, ops=OPS,
         dtypes=('float32',), grid=DEFAULT_GRID, impls=('xla', 'pallas'),
         bench: Optional[Callable[[Callable[[], object]], float]] = None,
         backend_name: Optional[str] = None) -> dict:
    """Benchmark the candidate grid per (op, shape, dtype); return the
    cache dict (see module docstring).  ``bench(fn) -> µs`` is injectable
    (tests pin it for determinism); ``backend_name`` overrides the key
    prefix (the measurements still run on the current backend)."""
    bench = bench or default_bench
    be = backend_name or backend()
    interpret = backend() != 'tpu'
    entries = {}
    for d_in, d_out in shapes:
        for dtype in dtypes:
            dt = jnp.dtype(dtype)
            for op in ops:
                g, a, b, m = _operands(op, d_in, d_out, dt)
                best = None
                for impl, bm, bn in _candidates(op, d_in, d_out, grid,
                                                impls, dt.itemsize,
                                                interpret):
                    fn = _candidate_fn(op, impl, g, a, b, m, bm, bn,
                                       interpret)
                    us = float(bench(fn))
                    rank = (us, _IMPL_RANK[impl], bm, bn)
                    if best is None or rank < best[0]:
                        best = (rank, impl, bm, bn, us)
                _, impl, bm, bn, us = best
                entries[cache_key(op, d_in, d_out, dt, be)] = {
                    'impl': impl, 'block_in': bm, 'block_out': bn,
                    'us': round(us, 3)}
    return {'version': 1, 'backend': be, 'entries': entries}


def dumps(cache: dict) -> str:
    """Canonical byte-stable serialization of a tune() result."""
    return json.dumps(cache, sort_keys=True, indent=2) + '\n'


def write(cache: dict, path) -> Path:
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(dumps(cache))
    return path


def merge(base: dict, new: dict) -> dict:
    """New entries win; version/backend from ``new``."""
    entries = dict(base.get('entries', {}))
    entries.update(new.get('entries', {}))
    out = dict(new)
    out['entries'] = entries
    return out
