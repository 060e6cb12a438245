"""KL clipping (Eq. 16), KL normalization (§4.1) and grafting (§4.2).

All three consume both the preconditioned updates (the incoming ``updates``)
and the raw gradients (``extras.raw_grads``) threaded by ``chain``.

``kl_clip_trace`` fuses the KL trust region with heavy-ball momentum: the
reference implementation clips the preconditioned gradient and *then* feeds
a torch-SGD momentum buffer, whose 1/(1-μ) steady-state gain re-amplifies
the clipped update up to 10× outside the trust region — on quadratic-ish
tasks this produced a limit cycle where momentum *hurt* (the seed's failing
§5 momentum ablation).  Fusing the two — accumulate first, clip the
momentum-included update, store the clipped buffer — keeps every applied
step inside the region while preserving heavy-ball smoothing, and reduces
exactly to ``kl_clip`` at momentum = 0.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Union

import jax
import jax.numpy as jnp

from repro.core.transform import (Extras, GradientTransformation, TraceState,
                                  _unit_init, tree_vdot)

Schedule = Union[float, Callable]
_tree_map = jax.tree_util.tree_map


def _lr_at(lr: Schedule, step) -> jnp.ndarray:
    if callable(lr):
        return jnp.asarray(lr(step), jnp.float32)
    return jnp.asarray(lr, jnp.float32)


def kl_clip(kappa: float = 1e-3, lr: Schedule = 0.1) -> GradientTransformation:
    """ν = min(1, sqrt(κ / (α² Σ_l p_lᵀ g_l))); scales all updates by ν.

    ``p`` are the (preconditioned) incoming updates, ``g`` the raw gradients.
    (C+γI)^{-1} is PD so pᵀg ≥ 0; we clamp for numerical safety.
    """

    def update(updates, state, params=None, extras: Extras | None = None):
        del params
        alpha = _lr_at(lr, extras.step)
        kl = jnp.maximum(tree_vdot(updates, extras.raw_grads), 0.0)
        nu = jnp.minimum(1.0, jnp.sqrt(kappa / jnp.maximum(alpha * alpha * kl, 1e-20)))
        return jax.tree_util.tree_map(lambda u: u * nu, updates), state

    return GradientTransformation(_unit_init, update)


def kl_clip_trace(kappa: float = 1e-3, lr: Schedule = 0.1,
                  momentum: float = 0.9,
                  nesterov: bool = False) -> GradientTransformation:
    """Momentum-aware KL trust region (see module docstring).

    m ← μ·m + p;  u = p + μ·m if nesterov else m;
    ν = min(1, √(κ / (α² uᵀg)));  output = ν·u;  store = ν·m.

    Storing the clipped buffer is what makes the transform self-stabilizing:
    in the clipped regime the buffer cannot accumulate past the trust
    region; once ν = 1 it is plain heavy-ball, and any incipient overshoot
    grows uᵀg until the clip re-engages.
    """

    def init(params):
        return TraceState(trace=jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params))

    def update(updates, state, params=None, extras: Extras | None = None):
        del params
        with jax.named_scope('kl_clip'):
            m = jax.tree_util.tree_map(
                lambda mm, g: momentum * mm + g.astype(jnp.float32),
                state.trace, updates)
            if nesterov:
                u = jax.tree_util.tree_map(
                    lambda g, mm: g.astype(jnp.float32) + momentum * mm,
                    updates, m)
            else:
                u = m
            alpha = _lr_at(lr, extras.step)
            kl = jnp.maximum(tree_vdot(u, extras.raw_grads), 0.0)
            nu = jnp.minimum(1.0, jnp.sqrt(
                kappa / jnp.maximum(alpha * alpha * kl, 1e-20)))
            out = jax.tree_util.tree_map(lambda x: x * nu, u)
            stored = out if not nesterov else jax.tree_util.tree_map(
                lambda x: x * nu, m)
        return out, TraceState(trace=stored)

    return GradientTransformation(init, update)


def kl_normalize(eps: float = 1e-12) -> GradientTransformation:
    """p / sqrt(Σ_l p_lᵀ g_l) — the hyper-parameter-free Eva-f stabilizer."""

    def update(updates, state, params=None, extras: Extras | None = None):
        del params
        kl = jnp.maximum(tree_vdot(updates, extras.raw_grads), eps)
        s = jax.lax.rsqrt(kl)
        return jax.tree_util.tree_map(lambda u: u * s, updates), state

    return GradientTransformation(_unit_init, update)


# ---------------------------------------------------------------------------
# Fused update tails.  The finish helpers below are the SINGLE source of the
# scalar epilogues shared by (a) the fused-kernel optimizer paths, which get
# the inner products as per-bucket kernel partials (``kernels/fused.py``),
# and (b) ``fused_tail``, the one-transform jnp replacement for the composed
# [clip/normalize/graft] + [momentum] tail of the solve-based optimizers.
# The math is identical to the composed transforms above; only the number of
# tree traversals changes.


def finish_kl_clip(u, kl, step, kappa: float, lr: Schedule, m=None):
    """The Eq. 16 trust-region scale given a precomputed uᵀg.

    ``u`` is the momentum-included update tree (f32); ``kl`` the global
    ⟨u, raw_grads⟩ scalar.  Returns ``(out, stored)`` = (ν·u, ν·(m or u))
    — exactly ``kl_clip_trace``'s tail (``m`` only differs under nesterov).
    """
    with jax.named_scope('kl_clip'):
        alpha = _lr_at(lr, step)
        kl = jnp.maximum(kl, 0.0)
        nu = jnp.minimum(1.0, jnp.sqrt(
            kappa / jnp.maximum(alpha * alpha * kl, 1e-20)))
        out = _tree_map(lambda x: x * nu, u)
        stored = out if m is None else _tree_map(lambda x: x * nu, m)
    return out, stored


def ema_finish(x, trace, momentum: float, step):
    """``ema_trace`` semantics on an already-built tree: m ← μ·m + (1−μ)·x;
    out = m / (1−μ^(t+1)).  Returns ``(out, new trace)`` (trace kept f32)."""
    gain = 1.0 - momentum
    m = _tree_map(lambda mm, xx: momentum * mm.astype(jnp.float32)
                  + gain * xx.astype(jnp.float32), trace, x)
    if momentum:
        corr = 1.0 - jnp.asarray(momentum, jnp.float32) \
            ** (jnp.asarray(step).astype(jnp.float32) + 1.0)
        return _tree_map(lambda mm: mm / corr, m), m
    return m, m


def finish_normalized_ema(p, pg, trace, momentum: float, step,
                          eps: float = 1e-12):
    """``kl_normalize`` + ``ema_trace`` tail given a precomputed ⟨p, g⟩."""
    s = jax.lax.rsqrt(jnp.maximum(pg, eps))
    return ema_finish(_tree_map(lambda u: u * s, p), trace, momentum, step)


def finish_graft_ema(p, pp, gg, trace, momentum: float, step,
                     eps: float = 1e-12):
    """``graft_to_grad_magnitude`` + ``ema_trace`` tail given per-leaf
    ⟨p,p⟩ / ⟨g,g⟩ trees of scalars."""
    scaled = _tree_map(
        lambda u, a, b: u * jnp.sqrt(b / jnp.maximum(a, eps)), p, pp, gg)
    return ema_finish(scaled, trace, momentum, step)


@dataclasses.dataclass(frozen=True)
class Epilogue:
    """Declarative description of an optimizer's update tail.

    kind: 'kl_clip' (trust region + heavy-ball, the eva/kfac tail) |
    'kl_normalize' (global rescale + EMA momentum, eva_f/foof) |
    'graft' (per-leaf SGD-magnitude graft + EMA momentum, eva_s/shampoo).
    """
    kind: str
    kappa: float = 1e-3
    lr: Schedule = 0.1
    momentum: float = 0.9
    nesterov: bool = False
    eps: float = 1e-12


def fused_tail(epi: Epilogue) -> GradientTransformation:
    """One-transform (single-traversal) replacement for the composed
    [kl_clip_trace] / [kl_normalize + ema_trace] / [graft + ema_trace]
    chain tails — same math, same state shape (one f32 ``TraceState``)."""
    if epi.kind not in ('kl_clip', 'kl_normalize', 'graft'):
        raise ValueError(f'unknown epilogue kind {epi.kind!r}')

    def init(params):
        return TraceState(trace=_tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params))

    def update(updates, state, params=None, extras: Extras | None = None):
        del params
        p32 = _tree_map(lambda u: u.astype(jnp.float32), updates)
        if epi.kind == 'kl_clip':
            m = _tree_map(lambda mm, g: epi.momentum * mm + g,
                          state.trace, p32)
            u = _tree_map(lambda g, mm: g + epi.momentum * mm, p32, m) \
                if epi.nesterov else m
            out, stored = finish_kl_clip(
                u, tree_vdot(u, extras.raw_grads), extras.step,
                epi.kappa, epi.lr, m=m if epi.nesterov else None)
        elif epi.kind == 'kl_normalize':
            out, stored = finish_normalized_ema(
                p32, tree_vdot(p32, extras.raw_grads), state.trace,
                epi.momentum, extras.step, epi.eps)
        else:  # graft
            pp = _tree_map(lambda u: jnp.sum(u * u), p32)
            gg = _tree_map(
                lambda g: jnp.sum(jnp.square(g.astype(jnp.float32))),
                extras.raw_grads)
            out, stored = finish_graft_ema(p32, pp, gg, state.trace,
                                           epi.momentum, extras.step, epi.eps)
        return out, TraceState(trace=stored)

    return GradientTransformation(init, update)


def graft_to_grad_magnitude(eps: float = 1e-12) -> GradientTransformation:
    """Per-layer scale sqrt(gᵀg / pᵀp): preconditioned *direction* with SGD
    *magnitude* (the Eva-s stabilizer, after [Anil et al. 2021])."""

    def update(updates, state, params=None, extras: Extras | None = None):
        del params

        def leaf(u, g):
            u32 = u.astype(jnp.float32)
            g32 = g.astype(jnp.float32)
            s = jnp.sqrt(jnp.sum(g32 * g32) / jnp.maximum(jnp.sum(u32 * u32), eps))
            return (u32 * s).astype(u.dtype)

        return jax.tree_util.tree_map(leaf, updates, extras.raw_grads), state

    return GradientTransformation(_unit_init, update)
