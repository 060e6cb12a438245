"""Eva-s (paper §4.2): vectorized Shampoo — per-mode gradient-mean KVs +
grafting to the SGD magnitude.

Needs **no** capture: the KVs are the gradient's own row/col means
(v_i = mean_{-i}(G)), EMA'd over steps (the vectorized analogue of Shampoo's
statistic accumulation; documented deviation — the paper does not specify the
temporal treatment of v, we mirror Eq. 14-15).

Bucketed: the (v_in, v_out) running means live bucket-stacked (in the
``a_mean``/``b_mean`` LayerStats slots) and both the EMA and the rank-one
update run once per (shape, dtype) bucket via ``precondition_tree``.

Pipelining: eva_s performs **no curvature collective** (its KVs are local
gradient means and data-parallel gradient averaging already happened in the
grad psum), so ``RefreshRuntime(pipeline='onestep')`` is an exact no-op here
— the state carries no ``pipe`` buffers and sync/onestep are bit-identical.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import bucketing
from repro.core import kv as kvlib
from repro.core import precondition as pre
from repro.core.clipping import finish_graft_ema, graft_to_grad_magnitude
from repro.core.eva import (_eva_cached_init, _refresh_snapshot,
                            _zeros_like_spec)
from repro.core.transform import (Extras, GradientTransformation, chain,
                                  add_decayed_weights, ema_trace,
                                  scale_by_schedule)
from repro.schedule import policy as schedpol, runtime as schedrt


def default_precon_predicate(path: str, leaf) -> bool:
    """Precondition every >=2-D weight; skip biases/norms/scalars."""
    return hasattr(leaf, 'ndim') and leaf.ndim >= 2


class EvaSState(NamedTuple):
    running: kvlib.RunningStats
    cached: Any
    sched: schedpol.SchedState
    # fused path only: the f32 EMA momentum buffer (else in ema_trace state)
    trace: Any = None


def _kv_init_s(params, extras, policy, interval, predicate):
    flat = kvlib.flatten_params(params)
    plan = bucketing.build_plan(flat, predicate)
    zeros = {
        b.key: kvlib.LayerStats(
            a_mean=jnp.zeros((len(b.paths),) + b.shape[:-1], jnp.float32),
            b_mean=jnp.zeros((len(b.paths),) + b.shape[:-2] + b.shape[-1:],
                             jnp.float32))
        for b in plan.buckets}
    pol = schedrt.from_extras(extras).resolve(policy, interval)
    return dict(running=kvlib.init_running(zeros),
                cached=_eva_cached_init(pol, zeros),
                sched=schedpol.init_state(pol, zeros))


def _kv_step_s(state, updates, extras, *, policy, interval, kv_decay,
               predicate):
    """eva_s per-step stats: fresh (v_in, v_out) from the gradients' own
    means, bucket-level EMA, snapshot refresh."""
    pol = schedrt.from_extras(extras).resolve(policy, interval)
    flat = kvlib.flatten_params(updates)
    plan = bucketing.build_plan(flat, predicate)
    g_b = bucketing.gather(plan, {p: flat[p] for p in plan.paths})
    fresh = {}
    for b in plan.buckets:
        vi, vo = pre.grad_kvs(g_b[b.key])
        fresh[b.key] = kvlib.LayerStats(a_mean=vi, b_mean=vo)
    stats, running = kvlib.update_running(state.running, fresh, kv_decay)
    used, sched, cached = _refresh_snapshot(pol, state.sched, stats,
                                            state.cached)
    return flat, plan, used, dict(running=running, cached=cached, sched=sched)


def eva_s_preconditioner(gamma: float = 0.03, kv_decay: float = 0.95,
                         interval: int = 1,
                         policy: Optional[schedpol.RefreshPolicy] = None,
                         predicate=default_precon_predicate
                         ) -> GradientTransformation:

    def init(params, extras: Extras | None = None):
        return EvaSState(**_kv_init_s(params, extras, policy, interval,
                                      predicate))

    def update(updates, state: EvaSState, params=None, extras: Extras | None = None):
        del params
        flat, plan, used, parts = _kv_step_s(
            state, updates, extras, policy=policy, interval=interval,
            kv_decay=kv_decay, predicate=predicate)
        out = pre.precondition_tree(flat, used, 'eva_s', gamma, plan=plan)
        return kvlib.unflatten_params(out), EvaSState(**parts)

    return GradientTransformation(init, update)


def eva_s_fused_update(gamma: float = 0.03, kv_decay: float = 0.95,
                       momentum: float = 0.9, fold_graft: bool = True,
                       interval: int = 1,
                       policy: Optional[schedpol.RefreshPolicy] = None,
                       predicate=default_precon_predicate
                       ) -> GradientTransformation:
    """Preconditioner + SGD-magnitude graft + EMA momentum as ONE transform.

    The ``eva_fused`` kernel emits P and the per-leaf [⟨p,g⟩, ⟨p,p⟩, ⟨g,g⟩]
    partials in a single launch per bucket; the graft scale is exactly
    √(⟨g,g⟩/⟨p,p⟩) from those partials, so the separate per-leaf reduction
    pass of ``graft_to_grad_magnitude`` disappears.  ``fold_graft=False``
    (weight decay upstream — kernel g ≠ raw_grads) recomputes the ⟨g,g⟩
    side from ``extras.raw_grads``.
    """

    def init(params, extras: Extras | None = None):
        return EvaSState(**_kv_init_s(params, extras, policy, interval,
                                      predicate),
                         trace=_zeros_like_spec(params))

    def update(updates, state: EvaSState, params=None, extras: Extras | None = None):
        del params
        flat, plan, used, parts = _kv_step_s(
            state, updates, extras, policy=policy, interval=interval,
            kv_decay=kv_decay, predicate=predicate)
        out_flat, partials = pre.precondition_tree_fused(
            flat, used, 'eva_s', gamma, plan=plan, fold_momentum=False)
        pp = {p: partials[p][1] for p in partials}
        if fold_graft:
            gg = {p: partials[p][2] for p in partials}
        else:
            raw = kvlib.flatten_params(extras.raw_grads)
            gg = {p: jnp.sum(jnp.square(raw[p].astype(jnp.float32)))
                  for p in partials}
        out_flat, stored_flat = finish_graft_ema(
            out_flat, pp, gg, kvlib.flatten_params(state.trace), momentum,
            extras.step)
        return kvlib.unflatten_params(out_flat), EvaSState(
            **parts, trace=kvlib.unflatten_params(stored_flat))

    return GradientTransformation(init, update)


def eva_s(lr=0.1, gamma: float = 0.03, kv_decay: float = 0.95,
          momentum: float = 0.9, weight_decay: float = 0.0,
          interval: int = 1,
          policy: Optional[schedpol.RefreshPolicy] = None,
          fused: bool = False) -> GradientTransformation:
    parts = []
    if weight_decay:
        parts.append(add_decayed_weights(weight_decay))
    if fused:
        parts.append(eva_s_fused_update(
            gamma, kv_decay, momentum, fold_graft=(weight_decay == 0.0),
            interval=interval, policy=policy))
    else:
        parts.append(eva_s_preconditioner(gamma, kv_decay,
                                          interval=interval, policy=policy))
        parts.append(graft_to_grad_magnitude())
        parts.append(ema_trace(momentum))
    parts.append(scale_by_schedule(lr if callable(lr) else (lambda _: lr)))
    return chain(*parts)


CAPTURE = kvlib.NO_CAPTURE
