"""Backend-aware kernel dispatch: per-(op, backend, shape, dtype) impl
selection + tile lookup for the Eva hot-path kernels.

Three implementations per op:

  * ``'pallas'``           — the Pallas kernels; compiled on TPU, interpret
                             (Python semantics) everywhere else.  This is
                             the historical ``use_pallas=True`` behavior.
  * ``'pallas_interpret'`` — Pallas forced into interpret mode on every
                             backend (tests pin this to exercise the kernel
                             bodies deterministically).
  * ``'xla'``              — the pure-jnp ``ref.py`` path, one fused XLA
                             region.  On CPU this is orders of magnitude
                             faster than interpret-mode Pallas (see
                             ``benchmarks/table5_itertime.py --kernels``).
  * ``'auto'``             — resolve per call site: an autotune-cache entry
                             for (backend, op, shape, dtype) wins if
                             present; otherwise ``'pallas'`` on TPU and
                             ``'xla'`` everywhere else.

The default impl is a **runtime** setting (``set_default_impl`` /
``impl_override``), replacing the old import-time ``ops.INTERPRET``
constant — tests and benchmarks flip backends without module reloads.
Per-call overrides thread through ``Extras.kernel`` (a ``KernelConfig``)
or the explicit ``impl=`` argument on each wrapper.

Tile sizes come from the autotune cache (``kernels/autotune.py``; shipped
defaults in ``tile_defaults.json`` warm-start it), else the 512-tile
default, fitted to the shape (and, compiled, to the TPU tiling) by
``tiles.fit_tiles``.  Every
resolution is recorded and exposed via ``choices_snapshot()`` so the
trainer can emit the chosen impl + tiles as optional obs fields.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
from pathlib import Path
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.kernels import bilinear as _bil
from repro.kernels import matvec as _mv
from repro.kernels import rank1_update as _r1
from repro.kernels import ref
from repro.kernels import tiles

IMPLS = ('auto', 'pallas', 'pallas_interpret', 'xla')
DEFAULT_BLOCK = 512
_DEFAULTS_FILE = Path(__file__).with_name('tile_defaults.json')


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """The launcher/trainer-level kernel knobs, threaded via ``Extras``.

    ``impl`` overrides the process default for every dispatch inside the
    step; ``autotune_cache`` is a JSON cache path installed at step-build
    time (``install_cache``); ``autotune`` marks that the launcher ran the
    tuner this session (informational, for obs).
    """
    impl: str = 'auto'
    autotune_cache: Optional[str] = None
    autotune: bool = False


@dataclasses.dataclass(frozen=True)
class Choice:
    """One resolved dispatch decision."""
    impl: str            # 'pallas' | 'xla'
    interpret: bool      # meaningful only for impl='pallas'
    block_in: int        # requested blocks (kernels fit them per shape)
    block_out: int


_state: dict[str, Any] = {'impl': 'auto', 'cache': None}
_choices: dict[str, str] = {}


def backend() -> str:
    return jax.default_backend()


def default_impl() -> str:
    return _state['impl']


def set_default_impl(impl: str) -> None:
    """Set the process-wide default impl at runtime (no reload needed)."""
    _check_impl(impl)
    _state['impl'] = impl


@contextlib.contextmanager
def impl_override(impl: str):
    """Temporarily force an impl (tests/benchmarks)."""
    _check_impl(impl)
    prev = _state['impl']
    _state['impl'] = impl
    try:
        yield
    finally:
        _state['impl'] = prev


def _check_impl(impl: str) -> None:
    if impl not in IMPLS:
        raise ValueError(f'unknown kernel impl {impl!r}; have {IMPLS}')


def impl_from_extras(extras, default: Optional[str] = None) -> Optional[str]:
    """The per-step impl request threaded through ``Extras.kernel``.

    A present ``KernelConfig`` wins over the preconditioner's own default —
    including ``'auto'``, which engages the dispatch layer's cache/backend
    resolution.  No config -> ``default`` (``None`` keeps callers on their
    historical inline-jnp path)."""
    cfg = getattr(extras, 'kernel', None) if extras is not None else None
    if cfg is not None:
        return cfg.impl
    return default


# ---------------------------------------------------------------------------
# Autotune-cache plumbing


def cache_key(op: str, d_in: int, d_out: int, dtype,
              backend_name: Optional[str] = None) -> str:
    return (f'{backend_name or backend()}/{op}/'
            f'{jnp.dtype(dtype).name}/{d_in}x{d_out}')


def _shipped_defaults() -> dict:
    if _DEFAULTS_FILE.exists():
        return dict(json.loads(_DEFAULTS_FILE.read_text()).get('entries', {}))
    return {}


def _cache() -> dict:
    if _state['cache'] is None:
        _state['cache'] = _shipped_defaults()
    return _state['cache']


def install_cache(cache) -> int:
    """Install autotune winners on top of the shipped defaults.

    ``cache`` is a path to an ``autotune.py`` JSON file or an already-loaded
    ``{'entries': {...}}``/plain-entries mapping.  Returns the entry count.
    """
    if isinstance(cache, (str, Path)):
        cache = json.loads(Path(cache).read_text())
    entries = cache.get('entries', cache) if isinstance(cache, dict) else {}
    base = _shipped_defaults()
    base.update(entries)
    _state['cache'] = base
    return len(base)


def reset_cache() -> None:
    _state['cache'] = None


# ---------------------------------------------------------------------------
# Resolution


def resolve(op: str, d_in: int, d_out: int, dtype,
            impl: Optional[str] = None) -> Choice:
    """Pick (impl, tiles) for one op instance.

    Order: explicit ``impl`` arg > process default; ``'auto'`` consults the
    autotune cache for this (backend, op, shape, dtype) and falls back to
    the backend rule (TPU -> pallas, else xla).  Blocks: cache entry, else
    the 512 default — the requested sizes, which each kernel fits to the
    shape and (compiled) to the TPU tiling via ``tiles.fit_tiles``.
    """
    req = impl or _state['impl']
    _check_impl(req)
    entry = _cache().get(cache_key(op, d_in, d_out, dtype)) or {}
    if req == 'auto':
        concrete = entry.get('impl') or \
            ('pallas' if backend() == 'tpu' else 'xla')
    else:
        concrete = req
    interpret = True if concrete == 'pallas_interpret' \
        else backend() != 'tpu'
    if concrete == 'pallas_interpret':
        concrete = 'pallas'
    block_in = int(entry.get('block_in', DEFAULT_BLOCK))
    block_out = int(entry.get('block_out', DEFAULT_BLOCK))
    # the kernels fit the requested blocks themselves (same rule), so the
    # recorded tiles are the ones that run
    bm, bn = tiles.fit_tiles(d_in, d_out, block_in, block_out,
                             jnp.dtype(dtype).itemsize, interpret)
    choice = Choice(impl=concrete, interpret=interpret,
                    block_in=block_in, block_out=block_out)
    label = concrete + ('/interpret' if concrete == 'pallas' and interpret
                        else '')
    _choices[op] = f'{label} {bm}x{bn} @ {d_in}x{d_out}'
    return choice


def choices_snapshot() -> dict[str, str]:
    """Latest resolved (impl, tiles) per op — the obs ``kernel_tiles``."""
    return dict(_choices)


# ---------------------------------------------------------------------------
# Op wrappers (the only call sites the rest of the repo should use)


def bilinear(g, a, b, impl: Optional[str] = None):
    c = resolve('bilinear', *g.shape[-2:], g.dtype, impl)
    if c.impl == 'xla':
        return ref.bilinear_ref(g, a, b)
    return _bil.bilinear(g, a, b, block_in=c.block_in, block_out=c.block_out,
                         interpret=c.interpret)


def bilinear_stacked(g, a, b, impl: Optional[str] = None):
    c = resolve('bilinear', *g.shape[-2:], g.dtype, impl)
    if c.impl == 'xla':
        return ref.bilinear_ref(g, a, b)
    return _bil.bilinear_stacked(g, a, b, block_in=c.block_in,
                                 block_out=c.block_out, interpret=c.interpret)


def matvec(g, a, impl: Optional[str] = None):
    c = resolve('matvec', *g.shape[-2:], g.dtype, impl)
    if c.impl == 'xla':
        return ref.matvec_ref(g, a)
    return _mv.matvec(g, a, block_in=c.block_in, block_out=c.block_out,
                      interpret=c.interpret)


def matvec_stacked(g, a, impl: Optional[str] = None):
    c = resolve('matvec', *g.shape[-2:], g.dtype, impl)
    if c.impl == 'xla':
        return ref.matvec_ref(g, a)
    return _mv.matvec_stacked(g, a, block_in=c.block_in,
                              block_out=c.block_out, interpret=c.interpret)


def matvec_cols(g, a, impl: Optional[str] = None):
    c = resolve('matvec_cols', *g.shape[-2:], g.dtype, impl)
    if c.impl == 'xla':
        return ref.matvec_cols_ref(g, a)
    return _mv.matvec_cols(g, a, block_in=c.block_in, block_out=c.block_out,
                           interpret=c.interpret)


def matvec_cols_stacked(g, a, impl: Optional[str] = None):
    c = resolve('matvec_cols', *g.shape[-2:], g.dtype, impl)
    if c.impl == 'xla':
        return ref.matvec_cols_ref(g, a)
    return _mv.matvec_cols_stacked(g, a, block_in=c.block_in,
                                   block_out=c.block_out,
                                   interpret=c.interpret)


def rank1_update(g, a, b, coeff, scale, impl: Optional[str] = None):
    c = resolve('rank1_update', *g.shape[-2:], g.dtype, impl)
    if c.impl == 'xla':
        return ref.rank1_update_ref(g, a, b, coeff, scale)
    return _r1.rank1_update(g, a, b, coeff, scale, block_in=c.block_in,
                            block_out=c.block_out, interpret=c.interpret)


def rank1_update_stacked(g, a, b, coeff, scale, impl: Optional[str] = None):
    c = resolve('rank1_update', *g.shape[-2:], g.dtype, impl)
    if c.impl == 'xla':
        return ref.rank1_update_ref(g, a, b, coeff, scale)
    return _r1.rank1_update_stacked(g, a, b, coeff, scale,
                                    block_in=c.block_in,
                                    block_out=c.block_out,
                                    interpret=c.interpret)


def eva_fused_stacked(g, a, b, gamma: float, m, mu: float,
                      fold_momentum: bool = True,
                      impl: Optional[str] = None):
    """One-launch Eva precondition + epilogue (see ``kernels/fused.py``).

    Returns ``(out, aux)``: ``out`` = μ·m + P (or P when ``fold_momentum``
    is off), f32; ``aux`` (L, 3) per-item partials [⟨out,g⟩, ⟨out,out⟩,
    ⟨g,g⟩] for the KL/graft scalar tails.
    """
    from repro.kernels import fused
    c = resolve('eva_fused', *g.shape[-2:], g.dtype, impl)
    if c.impl == 'xla':
        return ref.eva_fused_ref(g, a, b, gamma, m, mu, fold_momentum)
    return fused.eva_fused_stacked(g, a, b, gamma, m, mu,
                                   fold_momentum=fold_momentum,
                                   block_in=c.block_in,
                                   block_out=c.block_out,
                                   interpret=c.interpret)


def eva_f_fused_stacked(g, a, gamma: float, m, mu: float,
                        fold_momentum: bool = True,
                        impl: Optional[str] = None):
    """One-launch Eva-f precondition + epilogue; same contract as
    :func:`eva_fused_stacked`."""
    from repro.kernels import fused
    c = resolve('eva_f_fused', *g.shape[-2:], g.dtype, impl)
    if c.impl == 'xla':
        return ref.eva_f_fused_ref(g, a, gamma, m, mu, fold_momentum)
    return fused.eva_f_fused_stacked(g, a, gamma, m, mu,
                                     fold_momentum=fold_momentum,
                                     block_in=c.block_in,
                                     block_out=c.block_out,
                                     interpret=c.interpret)
