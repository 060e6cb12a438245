"""Pure-jnp twins of the fused Pallas kernels (``kernels/fused.py``).

``kernels/dispatch.py`` runs these on every backend but the TPU, and the
tests hold the kernels to them.  Every op accepts any leading stack dims
via ellipsis einsums.  Layouts match ``repro.core.precondition``: g
(..., d_in, d_out), a (..., d_in), b (..., d_out).  Reductions are f32
regardless of input dtype, like the kernels.
"""
from __future__ import annotations

import jax.numpy as jnp


def matvec_ref(g: jnp.ndarray, a: jnp.ndarray) -> jnp.ndarray:
    """u = aᵀ G — contraction over d_in.  (..., d_in, d_out),(..., d_in)
    -> (..., d_out) f32."""
    return jnp.einsum('...io,...i->...o', g.astype(jnp.float32),
                      a.astype(jnp.float32))


def bilinear_ref(g: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """aᵀ G b — scalar per leading index.  -> (...) f32."""
    return jnp.einsum('...io,...i,...o->...', g.astype(jnp.float32),
                      a.astype(jnp.float32), b.astype(jnp.float32))


def _fused_epilogue(g32, p, m, mu, fold_momentum):
    out = mu * m.astype(jnp.float32) + p if fold_momentum else p
    aux = jnp.stack([jnp.sum(out * g32, (-2, -1)),
                     jnp.sum(out * out, (-2, -1)),
                     jnp.sum(g32 * g32, (-2, -1))], axis=-1)
    return out, aux


def eva_fused_ref(g, a, b, gamma: float, m, mu: float,
                  fold_momentum: bool = True):
    """XLA twin of ``kernels/fused.py::eva_fused_stacked``.

    Returns ``(out, aux)``: out (..., d_in, d_out) f32 = μ·m + P (or P when
    ``fold_momentum`` is off); aux (..., 3) f32 = [⟨out,g⟩, ⟨out,out⟩,
    ⟨g,g⟩] per leading index.
    """
    g32 = g.astype(jnp.float32)
    a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
    dot = bilinear_ref(g, a, b)
    denom = gamma + jnp.sum(a32 * a32, -1) * jnp.sum(b32 * b32, -1)
    coeff = (dot / denom)[..., None, None]
    # multiply by the precomputed reciprocal, matching _rank1_tile's
    # scale operand bit-for-bit (x/gamma rounds differently)
    p = (1.0 / gamma) * (g32 - coeff * (a32[..., :, None] * b32[..., None, :]))
    return _fused_epilogue(g32, p, m, mu, fold_momentum)


def eva_f_fused_ref(g, a, gamma: float, m, mu: float,
                    fold_momentum: bool = True):
    """XLA twin of ``kernels/fused.py::eva_f_fused_stacked``; same contract
    as :func:`eva_fused_ref` with u = aᵀG."""
    g32 = g.astype(jnp.float32)
    a32 = a.astype(jnp.float32)
    u = matvec_ref(g, a)
    coeff = (1.0 / (gamma + jnp.sum(a32 * a32, -1)))[..., None, None]
    p = (1.0 / gamma) * (g32 - coeff * (a32[..., :, None] * u[..., None, :]))
    return _fused_epilogue(g32, p, m, mu, fold_momentum)
