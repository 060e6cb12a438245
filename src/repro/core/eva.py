"""Eva (paper §3): rank-one Kronecker-vector preconditioning.

``eva_preconditioner`` is the composable transform (running-average KVs +
Sherman–Morrison update, Eq. 13-15); ``eva`` is the full paper optimizer:
``precondition → KL clip → momentum → (weight decay) → -lr``.

Preconditioning is *bucketed* (``core/bucketing``): parameter paths group by
(shape, dtype) and each bucket runs ONE broadcast/grid-folded call through
``precondition.precondition_tree`` — no per-path Python loop.  KV running
stats live bucket-stacked in state and EMA at bucket level; when a
data-parallel mesh axis is live (shard_map/pmap), fresh statistics are
psum-averaged across ('pod','data') first, making them batch-global as in
the paper's multi-GPU setup.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import bucketing
from repro.core import kv as kvlib
from repro.core import precondition as pre
from repro.core.clipping import finish_kl_clip, kl_clip_trace
from repro.core.transform import (Extras, GradientTransformation, chain,
                                  add_decayed_weights, ema_trace,
                                  scale_by_schedule, tree_vdot)
from repro.schedule import (pipeline as pipemod, policy as schedpol,
                            runtime as schedrt)


class EvaState(NamedTuple):
    running: kvlib.RunningStats
    cached: Any                   # KV snapshot applied at the last refresh
    sched: schedpol.SchedState
    # pipeline='onestep': {'stats': PipelineState} — the reduced fresh-KV
    # tree exchanged this step, applied (fed to the EMA) next step.  None
    # in sync mode (no extra leaves, same checkpoints as before).
    pipe: Any = None
    # fused path only (``eva(fused=True)``): the f32 heavy-ball buffer that
    # the composed chain keeps in kl_clip_trace's TraceState.  None for the
    # composed path — state layout/checkpoints there are unchanged.
    trace: Any = None


def _zeros_like_spec(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), tree)


def _extract(stats: dict, fields: tuple[str, ...]) -> dict:
    """Keep only the requested LayerStats fields (None elsewhere)."""
    out = {}
    for path, st in stats.items():
        out[path] = kvlib.LayerStats(**{f: getattr(st, f) for f in fields})
    return out


def _stats_plan(flat_updates: dict, stats: dict,
                extras: Optional[Extras]) -> bucketing.BucketPlan:
    """The bucket plan over the preconditioned (= captured) paths; uses the
    plan built at init_opt_state time when threaded through Extras, else
    re-derives it (memoized on the shape signature)."""
    if extras is not None and extras.plan is not None:
        return extras.plan
    return bucketing.build_plan({p: flat_updates[p] for p in stats
                                 if p in flat_updates})


def _eva_cached_init(pol, zeros):
    """The eva-family applied-snapshot slot: None when the policy itself
    keeps a snapshot (adaptive) — both follow the identical
    where(refresh, fresh, old) update from identical zeros, so storing the
    tree twice would double the KV bytes in state and every checkpoint."""
    return None if pol.wants_snapshot else zeros


def _refresh_snapshot(pol, sched, stats, cached):
    """Shared eva-family refresh: the KV snapshot actually *applied* is the
    bias-corrected EMA at the last refresh.  With ``every_k(1)`` the
    ``jnp.where`` selects the fresh stats every step — bit-identical to the
    historical always-fresh behavior (the select copies values exactly).
    The EMA itself still advances every step, mirroring how K-FAC refreshes
    factors every step but inverses on the interval.

    Returns ``(applied stats, new SchedState, new cached slot)``; snapshot
    policies read/maintain the applied tree inside SchedState instead of a
    duplicate ``cached`` (see ``_eva_cached_init``)."""
    refresh, staleness = pol.decide(sched, stats)
    base = sched.snapshot if pol.wants_snapshot else cached
    used = jax.tree_util.tree_map(
        lambda f, c: jnp.where(refresh, f, c), stats, base)
    new_sched = schedpol.commit(pol, sched, stats, refresh, staleness)
    return used, new_sched, (None if pol.wants_snapshot else used)


def _kv_init(params, extras, fields, policy, interval):
    """Shared eva-family init: bucket plan + zeroed running stats + sched."""
    if extras is None or extras.stats is None:
        raise ValueError('eva-family preconditioner init needs example stats '
                         '(pass Extras(stats=...) — see train.make_train_step)')
    flat = kvlib.flatten_params(params)
    plan = _stats_plan(flat, extras.stats, extras)
    zeros = bucketing.gather_tree(
        plan, _zeros_like_spec(_extract(extras.stats, fields)))
    rt = schedrt.from_extras(extras)
    pol = rt.resolve(policy, interval)
    pipe = ({'stats': pipemod.init_state(zeros)}
            if rt.pipeline == 'onestep' else None)
    return dict(running=kvlib.init_running(zeros),
                cached=_eva_cached_init(pol, zeros),
                sched=schedpol.init_state(pol, zeros), pipe=pipe)


def _kv_step(state, updates, extras, *, fields, site, policy, interval,
             kv_decay):
    """Shared eva-family per-step stats plumbing: EMA the fresh KVs (with
    the staged cross-replica mean) and pick the applied snapshot.

    Returns ``(flat updates, plan, applied stats, new-state field dict)``.
    """
    rt = schedrt.from_extras(extras)
    pol = rt.resolve(policy, interval)
    pipe = schedrt.resolve_pipe(rt, state.pipe)
    flat = kvlib.flatten_params(updates)
    fresh_flat = _extract(extras.stats, fields)
    plan = _stats_plan(flat, fresh_flat, extras)
    with jax.named_scope('kv'):
        fresh, pipe_stats = pipemod.staged_pmean(
            bucketing.gather_tree(plan, fresh_flat),
            None if pipe is None else pipe['stats'], site=site)
        stats, running = kvlib.update_running(state.running, fresh,
                                              kv_decay)
        used, sched, cached = _refresh_snapshot(pol, state.sched, stats,
                                                state.cached)
    return flat, plan, used, dict(
        running=running, cached=cached, sched=sched,
        pipe=None if pipe is None else {'stats': pipe_stats})


def eva_preconditioner(gamma: float = 0.03, kv_decay: float = 0.95,
                       interval: int = 1,
                       policy: Optional[schedpol.RefreshPolicy] = None
                       ) -> GradientTransformation:
    """Bucketed P = (G − (b̄ᵀGā)/(γ+‖ā‖²‖b̄‖²)·āb̄ᵀ)/γ with EMA'd KVs.

    Eva is cheap enough to refresh every step (the paper's argument), but
    the refresh runtime gives it the same policy knob as the baselines —
    the Fig. 6 grid needs eva × {every_k, adaptive} cells too.
    """

    fields = ('a_mean', 'b_mean')

    def init(params, extras: Extras | None = None):
        return EvaState(**_kv_init(params, extras, fields, policy, interval))

    def update(updates, state: EvaState, params=None, extras: Extras | None = None):
        del params
        flat, plan, used, parts = _kv_step(
            state, updates, extras, fields=fields, site='stats/eva',
            policy=policy, interval=interval, kv_decay=kv_decay)
        out = pre.precondition_tree(flat, used, 'eva', gamma, plan=plan)
        return kvlib.unflatten_params(out), EvaState(**parts)

    return GradientTransformation(init, update)


def eva_fused_update(lr=0.1, gamma: float = 0.03, kv_decay: float = 0.95,
                     kl_kappa: float = 1e-3, momentum: float = 0.9,
                     fold_kl: bool = True, interval: int = 1,
                     policy: Optional[schedpol.RefreshPolicy] = None
                     ) -> GradientTransformation:
    """Preconditioner + KL trust region + heavy-ball as ONE transform.

    Each bucket runs a single ``eva_fused`` launch (``kernels/dispatch.py``)
    that preconditions, folds ``m ← μ·m + P``, and emits the ⟨u,g⟩ partials
    the Eq. 16 clip needs — the separate kl_clip_trace tree passes
    disappear.  ``fold_kl=False`` (set when weight decay runs before the
    preconditioner, making the kernel's g ≠ raw_grads) keeps the kernel
    fusion but recomputes the global uᵀg against ``extras.raw_grads``.
    Math matches ``eva_preconditioner + kl_clip_trace`` (non-nesterov) to
    f32 reduction tolerance; the momentum buffer lives in
    ``EvaState.trace`` instead of a chained TraceState.
    """
    fields = ('a_mean', 'b_mean')

    def init(params, extras: Extras | None = None):
        return EvaState(**_kv_init(params, extras, fields, policy, interval),
                        trace=_zeros_like_spec(params))

    def update(updates, state: EvaState, params=None, extras: Extras | None = None):
        del params
        flat, plan, used, parts = _kv_step(
            state, updates, extras, fields=fields, site='stats/eva',
            policy=policy, interval=interval, kv_decay=kv_decay)
        out_flat, partials = pre.precondition_tree_fused(
            flat, used, 'eva', gamma, plan=plan,
            trace=kvlib.flatten_params(state.trace), momentum=momentum,
            fold_momentum=True)
        u = kvlib.unflatten_params(out_flat)
        if fold_kl:
            kl = sum(partials[p][0] for p in sorted(partials))
        else:
            kl = tree_vdot(u, extras.raw_grads)
        out, stored = finish_kl_clip(u, kl, extras.step, kl_kappa, lr)
        return out, EvaState(**parts, trace=stored)

    return GradientTransformation(init, update)


def eva(lr=0.1, gamma: float = 0.03, kv_decay: float = 0.95,
        kl_kappa: float = 1e-3, momentum: float = 0.9,
        weight_decay: float = 0.0, nesterov: bool = False,
        interval: int = 1,
        policy: Optional[schedpol.RefreshPolicy] = None,
        fused: bool = False) -> GradientTransformation:
    """The full Eva optimizer as evaluated in the paper (§5).

    ``fused=True`` collapses preconditioner + KL clip + momentum into one
    kernel launch per bucket (``eva_fused_update``); it requires the
    non-nesterov trust-region tail, so nesterov / ``kl_kappa=None`` configs
    fall back to the composed chain.  The backend picks the kernel
    (``kernels/dispatch.py``).
    """
    parts = []
    if weight_decay:
        # L2 regularization enters the gradient *before* preconditioning,
        # matching the reference implementation (grad += wd * w pre-hook).
        parts.append(add_decayed_weights(weight_decay))
    if fused and kl_kappa is not None and not nesterov:
        parts.append(eva_fused_update(
            lr, gamma, kv_decay, kl_kappa, momentum,
            fold_kl=(weight_decay == 0.0), interval=interval,
            policy=policy))
        parts.append(scale_by_schedule(lr if callable(lr) else (lambda _: lr)))
        return chain(*parts)
    parts.append(eva_preconditioner(gamma, kv_decay, interval=interval,
                                    policy=policy))
    if kl_kappa is not None:
        # momentum lives INSIDE the trust region (see clipping.kl_clip_trace)
        parts.append(kl_clip_trace(kl_kappa, lr, momentum, nesterov=nesterov))
    else:
        # unit-gain momentum: same equal-lr step-scale convention as every
        # other chain in the registry (see transform.ema_trace)
        parts.append(ema_trace(momentum, nesterov=nesterov))
    parts.append(scale_by_schedule(lr if callable(lr) else (lambda _: lr)))
    return chain(*parts)


CAPTURE = kvlib.EVA_CAPTURE
