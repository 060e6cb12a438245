"""The backend rule that picks the kernel on the chip's paths.

``backend()`` is the one thing read: ``models/flash.py`` takes the Pallas
attention kernel on the TPU from it, and ``eva_fused``/``eva_f_fused``
below take Eva's fused rank-one update from it.  On the TPU they run the
compiled Pallas kernels of ``kernels/fused.py`` with 512-wide requested
blocks (``tiles.fit_tiles`` fits them to each shape and to the chip's
tiling); on any other backend they run ``ref.py``'s jnp twins, which XLA
fuses.  Eva's unfused path never comes here: it runs the inline jnp of
``core/precondition.py`` on every backend.  No flag chooses either.
"""
from __future__ import annotations

import dataclasses

import jax

from repro.kernels import fused, ref

BLOCK = 512


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Selects nothing.  The benchmark's traffic files name
    ``"kernel_impl": "auto"`` and ``bench/harness.py`` builds this from it
    and hands it to ``Trainer(kernel=...)``, so the name stays and accepts
    only that value; the kernel follows ``backend()`` either way."""
    impl: str = 'auto'

    def __post_init__(self):
        if self.impl != 'auto':
            raise ValueError(f"kernel impl {self.impl!r}: only 'auto' is "
                             'accepted; the backend picks the kernel')


def backend() -> str:
    return jax.default_backend()


def _route(kernel, twin, g, vecs, gamma, m, mu, fold_momentum):
    """Fold the leading stack dims of g, m and the vectors into one axis,
    run ``kernel`` compiled on the TPU or ``twin`` elsewhere, unfold."""
    lead, n = g.shape[:-2], g.ndim - 2
    gs, ms = g.reshape((-1,) + g.shape[n:]), m.reshape((-1,) + m.shape[n:])
    vs = [v.reshape((-1,) + v.shape[n:]) for v in vecs]
    if backend() == 'tpu':
        out, aux = kernel(gs, *vs, gamma, ms, mu, fold_momentum=fold_momentum,
                          block_in=BLOCK, block_out=BLOCK, interpret=False)
    else:
        out, aux = twin(gs, *vs, gamma, ms, mu, fold_momentum)
    return out.reshape(lead + out.shape[1:]), aux.reshape(lead + (3,))


def eva_fused(g, a, b, gamma: float, m, mu: float,
              fold_momentum: bool = True):
    """Eq. 13 + momentum/epilogue in one launch per call.

    g, m: (..., d_in, d_out); a: (..., d_in); b: (..., d_out); any leading
    stack dims fold into the kernel grid's one stack axis.  Returns
    ``(out, aux)``: ``out`` f32 shaped like g, = μ·m + P (P alone when
    ``fold_momentum`` is off); ``aux`` (..., 3) f32 per-item partials
    [⟨out,g⟩, ⟨out,out⟩, ⟨g,g⟩] for the KL/graft scalar tails.
    """
    return _route(fused.eva_fused_stacked, ref.eva_fused_ref, g, (a, b),
                  gamma, m, mu, fold_momentum)


def eva_f_fused(g, a, gamma: float, m, mu: float,
                fold_momentum: bool = True):
    """Eq. 21 + momentum/epilogue in one launch; same contract as
    :func:`eva_fused` with u = aᵀG."""
    return _route(fused.eva_f_fused_stacked, ref.eva_f_fused_ref, g, (a,),
                  gamma, m, mu, fold_momentum)
