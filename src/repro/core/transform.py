"""Minimal optax-like gradient-transformation algebra with a side-channel.

Second-order methods need more than (grads, state, params): Eva needs the
Kronecker-vector statistics captured during the forward/backward pass, KL
clipping needs the *raw* gradients alongside the preconditioned ones, and
grafting needs both magnitudes.  We thread all of that through an ``Extras``
record so individual transforms stay tiny and composable.

Every transform is a pair of pure functions ``(init, update)`` over pytrees,
which makes the whole optimizer state shardable, checkpointable and donatable
under ``pjit``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Types


@dataclasses.dataclass(frozen=True)
class Extras:
    """Side-channel values available to every transform in a chain.

    Attributes:
      raw_grads: the unmodified gradients (before any preconditioning).
      stats: KV/KF statistics captured by the model forward/backward
        (see ``repro.core.kv``); a dict keyed by parameter path.
      loss: scalar loss value for logging-style transforms.
      step: current step (filled in by ``chain``).
      plan: optional ``repro.core.bucketing.BucketPlan`` built once at
        ``init_opt_state`` time; bucketed preconditioners use it instead of
        re-deriving the grouping (the fallback is a memoized re-derivation,
        so omitting it is always correct, just redundant work at trace time).
      sched: optional ``repro.schedule.RefreshRuntime`` — the curvature
        refresh runtime threaded next to the plan: default refresh policy
        and the worker-sharded-ownership switch.  Omitting it leaves each
        preconditioner on its own ``policy``/``interval`` arguments.
      comm: optional ``repro.comm.ExchangeConfig`` — which codec each
        cross-device exchange family (gradients / statistics / refresh)
        uses and whether the refresh exchange is the owned-slice
        all-gather or the legacy full-stack psum.  Omitting it means the
        defaults (f32 stats/refresh, owned-slice refresh exchange).
      factor: optional ``repro.core.factor_sharded.FactorShardConfig`` —
        the per-factor execution policy for oversized Kronecker factors
        (``head_policy='shard'|'exclude'|'dense'``, the sub-slice
        ``shard_threshold`` and the iterative-solver knobs).  Omitting it
        keeps every factor on the dense legacy path, bit-exactly.
    """

    raw_grads: Any = None
    stats: Any = None
    loss: Any = None
    step: Any = None
    plan: Any = None
    sched: Any = None
    comm: Any = None
    factor: Any = None


class GradientTransformation(NamedTuple):
    init: Callable[[Any], Any]
    update: Callable[..., tuple[Any, Any]]  # (updates, state, params, extras)


class EmptyState(NamedTuple):
    pass


def _unit_init(params, extras=None):
    del params, extras
    return EmptyState()


def stateless(fn: Callable[[Any, Any, Extras], Any]) -> GradientTransformation:
    """Build a stateless transform from ``fn(updates, params, extras)``."""

    def update(updates, state, params=None, extras: Extras | None = None):
        return fn(updates, params, extras), state

    return GradientTransformation(_unit_init, update)


# ---------------------------------------------------------------------------
# Tree utilities


def tree_map(fn, *trees):
    return jax.tree_util.tree_map(fn, *trees)


def tree_vdot(a, b):
    """Global inner product <a, b> over two pytrees.

    Elementwise multiply + full reduce (NOT jnp.vdot: its 1-D flatten breaks
    sharding and forces a full all-gather of every gradient under SPMD).
    """
    leaves = jax.tree_util.tree_map(
        lambda x, y: jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32)), a, b
    )
    return jax.tree_util.tree_reduce(jnp.add, leaves, jnp.zeros((), jnp.float32))


def tree_norm_sq(a):
    return tree_vdot(a, a)


def tree_scale(tree, s):
    return jax.tree_util.tree_map(lambda x: (x.astype(jnp.float32) * s).astype(x.dtype), tree)


def tree_zeros_like(tree, dtype=None):
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, dtype or x.dtype), tree
    )


# ---------------------------------------------------------------------------
# Chain


class ChainState(NamedTuple):
    step: jnp.ndarray
    inner: tuple


def chain(*transforms: GradientTransformation) -> GradientTransformation:
    """Compose transforms left-to-right; maintains a shared step counter.

    The ``Extras`` record is augmented with ``raw_grads`` (the incoming
    updates) and ``step`` before the first transform runs.
    """

    def init(params, extras: Extras | None = None):
        inner = []
        for t in transforms:
            try:
                inner.append(t.init(params, extras))
            except TypeError:
                inner.append(t.init(params))
        return ChainState(step=jnp.zeros((), jnp.int32), inner=tuple(inner))

    def update(updates, state: ChainState, params=None, extras: Extras | None = None):
        extras = extras or Extras()
        extras = dataclasses.replace(extras, raw_grads=updates, step=state.step)
        new_inner = []
        for t, s in zip(transforms, state.inner):
            updates, s = t.update(updates, s, params=params, extras=extras)
            new_inner.append(s)
        return updates, ChainState(step=state.step + 1, inner=tuple(new_inner))

    return GradientTransformation(init, update)


def apply_updates(params, updates):
    """``w <- w + Δw`` preserving dtypes (master math in f32)."""
    return jax.tree_util.tree_map(
        lambda p, u: (p.astype(jnp.float32) + u.astype(jnp.float32)).astype(p.dtype),
        params,
        updates,
    )


# ---------------------------------------------------------------------------
# First-order building blocks


class TraceState(NamedTuple):
    trace: Any


def trace(momentum: float = 0.9, nesterov: bool = False,
          dtype: Optional[jnp.dtype] = None,
          dampening: float = 0.0,
          bias_correction: bool = False) -> GradientTransformation:
    """Heavy-ball momentum (torch-SGD convention: m <- mu*m + (1-dampening)·g).

    ``dampening=momentum`` + ``bias_correction=True`` gives the EMA form
    ``m̂ = (mu·m + (1-mu)·g) / (1-mu^t)``: same smoothing direction as
    heavy-ball but unit steady-state gain instead of 1/(1-mu).  The
    second-order optimizers use this form so that momentum composes with the
    KL trust region — undamped heavy-ball multiplies the clipped update by
    up to 1/(1-mu) (10× at mu=0.9), stepping far outside the region the clip
    just enforced (the paper's §5 momentum ablation regressed without this).
    ``momentum=0`` reduces to the identity in both conventions.
    """

    def init(params):
        return TraceState(trace=jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, dtype or p.dtype), params))

    def update(updates, state, params=None, extras=None):
        del params
        gain = 1.0 - dampening
        new_trace = jax.tree_util.tree_map(
            lambda m, g: momentum * m.astype(jnp.float32)
            + gain * g.astype(jnp.float32),
            state.trace, updates)
        out = new_trace
        if bias_correction and momentum:
            step = extras.step if extras is not None and extras.step is not None \
                else jnp.zeros((), jnp.int32)
            corr = 1.0 - jnp.asarray(momentum, jnp.float32) \
                ** (step.astype(jnp.float32) + 1.0)
            out = jax.tree_util.tree_map(lambda m: m / corr, new_trace)
        if nesterov:
            out = jax.tree_util.tree_map(
                lambda g, m: gain * g.astype(jnp.float32) + momentum * m,
                updates, out)
        stored = jax.tree_util.tree_map(
            lambda m, old: m.astype(old.dtype), new_trace, state.trace)
        return out, TraceState(trace=stored)

    return GradientTransformation(init, update)


def ema_trace(momentum: float = 0.9, nesterov: bool = False) -> GradientTransformation:
    """Bias-corrected EMA momentum — the trust-region-compatible form used by
    the second-order optimizer chains (see ``trace``)."""
    return trace(momentum, nesterov=nesterov, dampening=momentum,
                 bias_correction=True)


def scale(factor) -> GradientTransformation:
    return stateless(lambda u, p, e: tree_map(lambda x: x * factor, u))


class ScheduleState(NamedTuple):
    pass


def scale_by_schedule(schedule: Callable[[jnp.ndarray], jnp.ndarray],
                      negate: bool = True) -> GradientTransformation:
    """Multiply updates by ``-schedule(step)`` (learning-rate schedule)."""

    def update(updates, state, params=None, extras: Extras | None = None):
        lr = schedule(extras.step if extras is not None else 0)
        s = -lr if negate else lr
        return tree_map(lambda x: x * s, updates), state

    return GradientTransformation(_unit_init, update)


def add_decayed_weights(weight_decay: float,
                        mask: Callable[[Any], Any] | None = None) -> GradientTransformation:
    def fn(updates, params, extras):
        if weight_decay == 0.0 or params is None:
            return updates
        wd = jax.tree_util.tree_map(
            lambda g, p: g + weight_decay * p.astype(g.dtype), updates, params)
        if mask is not None:
            m = mask(params)
            wd = jax.tree_util.tree_map(
                lambda use, a, b: a if use else b, m, wd, updates)
        return wd

    return stateless(fn)


def clip_by_global_norm(max_norm: float) -> GradientTransformation:
    def fn(updates, params, extras):
        gn = jnp.sqrt(tree_norm_sq(updates) + 1e-16)
        s = jnp.minimum(1.0, max_norm / gn)
        return tree_map(lambda x: x * s, updates)

    return stateless(fn)


class AdamState(NamedTuple):
    mu: Any
    nu: Any
    count: jnp.ndarray


def scale_by_adam(b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8) -> GradientTransformation:
    def init(params):
        z = lambda: jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return AdamState(mu=z(), nu=z(), count=jnp.zeros((), jnp.int32))

    def update(updates, state, params=None, extras=None):
        del params, extras
        count = state.count + 1
        mu = jax.tree_util.tree_map(
            lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32), state.mu, updates)
        nu = jax.tree_util.tree_map(
            lambda v, g: b2 * v + (1 - b2) * jnp.square(g.astype(jnp.float32)),
            state.nu, updates)
        mu_hat = tree_scale(mu, 1.0 / (1 - b1 ** count.astype(jnp.float32)))
        nu_hat = tree_scale(nu, 1.0 / (1 - b2 ** count.astype(jnp.float32)))
        out = jax.tree_util.tree_map(
            lambda m, v: m / (jnp.sqrt(v) + eps), mu_hat, nu_hat)
        return out, AdamState(mu=mu, nu=nu, count=count)

    return GradientTransformation(init, update)


class AdagradState(NamedTuple):
    accum: Any


def scale_by_adagrad(eps: float = 1e-10, initial_accum: float = 0.1) -> GradientTransformation:
    def init(params):
        return AdagradState(accum=jax.tree_util.tree_map(
            lambda p: jnp.full(p.shape, initial_accum, jnp.float32), params))

    def update(updates, state, params=None, extras=None):
        del params, extras
        accum = jax.tree_util.tree_map(
            lambda a, g: a + jnp.square(g.astype(jnp.float32)), state.accum, updates)
        out = jax.tree_util.tree_map(
            lambda g, a: g.astype(jnp.float32) / (jnp.sqrt(a) + eps), updates, accum)
        return out, AdagradState(accum=accum)

    return GradientTransformation(init, update)
