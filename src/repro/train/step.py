"""Train-step factory: loss → (grads, tap-grads) → KV stats → optimizer.

The returned ``train_step(params, opt_state, batch)`` is a pure function —
jit/pjit it, donate params/opt_state, shard it with the production mesh.
``abstract_opt_state`` mirrors the same wiring under ``eval_shape`` so the
dry-run can lower a 1T-param step without allocating anything.
"""
from __future__ import annotations

import functools
import inspect
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import bucketing
from repro.core import kv as kvlib
from repro.core import factor_sharded as fsh
from repro.core.transform import Extras, GradientTransformation, apply_updates
from repro.schedule import pipeline as pipemod, runtime as schedrt


def _plan_for_stats(params_or_grads, stats) -> Optional[bucketing.BucketPlan]:
    """The bucket plan over captured (= preconditioned) paths — built once
    here at init time and threaded to the optimizer through ``Extras.plan``
    (re-derivations inside jitted updates hit the memo cache)."""
    if stats is None:
        return None
    flat = kvlib.flatten_params(params_or_grads)
    return bucketing.build_plan({p: flat[p] for p in stats if p in flat})


def taps_caller(taps_fn: Optional[Callable]) -> Callable:
    """Normalize a taps factory to ``(params, batch) -> taps``.

    Legacy callers close over the global batch size
    (``lambda p: model.make_taps(32, capture)``), which breaks under the
    explicit-DP step where each worker sees ``batch/W`` rows — a
    batch-aware ``taps_fn(params, batch)`` sizes the taps from the batch it
    is actually handed (global under ``make_train_step``, the local shard
    under ``make_dp_step``).  Arity is inspected once at factory time, not
    per trace."""
    if taps_fn is None:
        return lambda params, batch: None
    try:
        n_args = len(inspect.signature(taps_fn).parameters)
    except (TypeError, ValueError):
        n_args = 1
    if n_args >= 2:
        return taps_fn
    return lambda params, batch: taps_fn(params)


def _default_make_taps(model, params, capture: kvlib.CaptureConfig):
    if not capture.needs_taps:
        return None
    if hasattr(model, 'make_taps'):
        # simple models: batch-size-dependent full taps are bound later
        raise ValueError('models with custom make_taps need explicit taps '
                         '(use make_train_step(..., taps_fn=...))')
    if capture.b == 'outer':
        # K-FAC needs the z-shaped cotangent; a silent vector-tap fallback
        # here folded the scan path dim into the token axis (wrong stats
        # AND shape-mismatched lax.cond branches in sharded_refresh)
        raise ValueError("capture.b='outer' needs full z-shaped taps — "
                         "pass taps_fn (see kv.make_full_taps)")
    flat = kvlib.flatten_params(params)
    return kvlib.make_vector_taps(params, set(model.precon_paths()) & set(flat))


def compute_grads_and_stats(model, params, batch,
                            capture: kvlib.CaptureConfig,
                            taps: Optional[dict] = None):
    """Shared by train_step and abstract shape derivation.

    The loss runs under ``named_scope('forward')`` inside the
    differentiated function, so the compiled step names its forward ops
    ``jvp(forward)/...`` and their transposes (the backward pass, ``remat``
    recompute included) ``transpose(jvp(forward))/...``; the statistics'
    finalization is ``capture/...``.  These names, with the
    ``optimizer``/``kv``/``precondition``/``kl_clip``/``apply``/``metrics``
    /``exchange`` scopes of the step factories and ``core/``, are what the
    benchmark's trace reduction groups device time by."""
    if capture.needs_taps:
        if taps is None:
            taps = _default_make_taps(model, params, capture)

        def lf(p, t):
            with jax.named_scope('forward'):
                return model.loss_fn(p, t, batch, capture)

        (loss, aux), (grads, tap_grads) = jax.value_and_grad(
            lf, argnums=(0, 1), has_aux=True)(params, taps)
    else:
        def lf(p):
            with jax.named_scope('forward'):
                return model.loss_fn(p, None, batch, capture)

        (loss, aux), grads = jax.value_and_grad(lf, has_aux=True)(params)
        tap_grads = None

    stats = None
    if capture.active:
        with jax.named_scope('capture'):
            stats = kvlib.finalize_stats(
                aux['stats'], tap_grads, capture,
                n_tokens=jnp.asarray(aux['n_tokens'], jnp.float32))
    return loss, grads, stats


def _step_metrics(loss, grads, new_opt_state) -> dict:
    """The step's metrics: loss, global gradient norm, and the refresh,
    pipeline and sharded-factor telemetry of the new state."""
    with jax.named_scope('metrics'):
        grad_norm = jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree_util.tree_leaves(grads)))
        metrics = {'loss': loss, 'grad_norm': grad_norm}
        # refresh-runtime observability: cumulative refreshes / staleness
        # of every scheduled transform in the state ({} for unscheduled
        # opts)
        metrics.update(schedrt.schedule_metrics(new_opt_state))
        # realized pipeline staleness per exchange site ({} in sync mode)
        metrics.update(pipemod.pipeline_metrics(new_opt_state))
        # sharded-factor telemetry ({} unless a factor policy tripped)
        metrics.update(fsh.step_metrics(new_opt_state))
    return metrics


def make_train_step(model, opt: GradientTransformation,
                    capture: kvlib.CaptureConfig,
                    taps_fn: Optional[Callable] = None,
                    donate: bool = True,
                    microbatches: int = 1,
                    sched: Optional[schedrt.RefreshRuntime] = None,
                    comm: Optional[Any] = None,
                    factor: Optional[Any] = None,
                    kernel: Optional[Any] = None) -> Callable:
    """Build the pure train step.  ``taps_fn(params)`` overrides tap creation
    (needed for full-tap K-FAC on the simple models).

    ``sched`` is the curvature refresh runtime threaded through ``Extras``
    next to the bucket plan (train-level default policy + worker-sharded
    refresh switch); pass the same runtime to ``init_opt_state`` so the
    scheduling state is allocated for the policy that will actually run.

    ``comm`` is the train-level ``repro.comm.ExchangeConfig`` threaded
    through ``Extras.comm``: which codec the statistics reduction and the
    owned-slice curvature-refresh exchange use under a live data-parallel
    mesh (None = defaults: f32 wire, owned-slice all-gather refresh).

    ``factor`` is the ``repro.core.factor_sharded.FactorShardConfig``
    threaded through ``Extras.factor``: the per-factor oversized-Kronecker
    policy (``head_policy='shard'|'exclude'|'dense'``).  None keeps every
    factor on the dense legacy path, bit-exactly.

    ``kernel`` is a ``repro.kernels.dispatch.KernelConfig`` threaded
    through ``Extras.kernel``: the per-step kernel impl request
    (auto/pallas/xla dispatch + autotune-cache tiles).  None keeps the
    optimizers on their own ``use_pallas``/``kernel_impl`` defaults.

    ``microbatches > 1`` runs gradient accumulation: the global batch is
    split on dim 0 and scanned, summing grads (f32) and averaging KV stats.
    This is what bounds activation memory at the 1T-param shape cells —
    saved-residual and MoE-dispatch peaks shrink by the microbatch factor
    (§Perf memory iteration)."""
    sched = sched if sched is not None else schedrt.RefreshRuntime()
    make_taps = taps_caller(taps_fn)

    def grads_of(params, batch):
        return compute_grads_and_stats(model, params, batch, capture,
                                       make_taps(params, batch))

    def train_step(params, opt_state, batch):
        if microbatches > 1:
            split = jax.tree_util.tree_map(
                lambda x: x.reshape((microbatches, x.shape[0] // microbatches)
                                    + x.shape[1:]), batch)

            def acc(carry, mb):
                g_acc, s_acc, l_acc = carry
                loss, grads, stats = grads_of(params, mb)
                g_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(a.dtype), g_acc, grads)
                if stats is not None:
                    s_acc = jax.tree_util.tree_map(
                        lambda a, s: a + s.astype(jnp.float32), s_acc, stats)
                return (g_acc, s_acc, l_acc + loss), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            s_shapes = jax.eval_shape(
                lambda p, b: grads_of(p, b)[2], params,
                jax.tree_util.tree_map(lambda x: x[0], split))
            s0 = (jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, jnp.float32), s_shapes)
                if capture.active else None)
            (g_sum, s_sum, l_sum), _ = jax.lax.scan(
                acc, (g0, s0, jnp.zeros((), jnp.float32)), split)
            inv = 1.0 / microbatches
            grads = jax.tree_util.tree_map(lambda g: g * inv, g_sum)
            stats = (jax.tree_util.tree_map(lambda s: s * inv, s_sum)
                     if s_sum is not None else None)
            loss = l_sum * inv
        else:
            loss, grads, stats = grads_of(params, batch)

        with jax.named_scope('optimizer'):
            updates, new_opt_state = opt.update(
                grads, opt_state, params=params,
                extras=Extras(stats=stats, loss=loss,
                              plan=_plan_for_stats(grads, stats),
                              sched=sched, comm=comm, factor=factor,
                              kernel=kernel))
        with jax.named_scope('apply'):
            new_params = apply_updates(params, updates)
        return new_params, new_opt_state, _step_metrics(
            loss, grads, new_opt_state)

    return train_step


def make_dp_step(model, opt: GradientTransformation,
                 capture: kvlib.CaptureConfig, mesh,
                 taps_fn: Optional[Callable] = None,
                 sched: Optional[schedrt.RefreshRuntime] = None,
                 comm: Optional[Any] = None,
                 factor: Optional[Any] = None,
                 kernel: Optional[Any] = None) -> Callable:
    """Explicit data-parallel train step over ``mesh``'s ``'data'`` axis —
    the elastic trainer's engine (``train/trainer.py::Trainer.fit_elastic``).

    Params/opt-state replicated, the global batch split over ``'data'``:
    the loss is ``pmean``'d and the gradients mean-all-reduced in f32
    (site ``grads/dp``), KV statistics likewise (site ``stats/dp``, axes
    passed explicitly for the same false-negative-probe reason as
    ``train/compression.py`` — the optimizer's own ``staged_pmean`` over
    already-identical values is then exact and idempotent).  The
    optimizer's update runs with the ``'data'`` axis bound, so
    worker-sharded refresh and the owned-slice exchange see
    ``world = mesh 'data' size`` — re-jitting this step under a resized
    mesh *is* the ownership reshard (``schedule/reshard.py``).

    At W=1 every collective reduces over a size-1 axis (``psum`` of one
    shard, divide by 1 — exact), so the trajectory is bit-identical to
    ``make_train_step``: the non-elastic trainer is the W=1 special case,
    not a separate code path.  Same metrics contract as
    ``make_train_step``."""
    sched = sched if sched is not None else schedrt.RefreshRuntime()
    make_taps = taps_caller(taps_fn)
    from jax.sharding import PartitionSpec as P

    from repro.comm import exchange

    def local_step(params, opt_state, batch):
        # NOTE: batch here is the per-worker shard — a batch-aware taps_fn
        # (see taps_caller) sizes full taps to batch/W rows
        loss, grads, stats = compute_grads_and_stats(
            model, params, batch, capture, make_taps(params, batch))
        loss = jax.lax.pmean(loss, 'data')

        def mean_over_workers(tree, site):
            # the mean is taken in f32; handing it back in each leaf's own
            # dtype keeps the optimizer's bucket keys (which name the dtype)
            # those of the state built from bf16 params
            mean, _, _ = exchange.allreduce_mean_tree(
                tree, codec='f32', axes=('data',), site=site)
            return jax.tree_util.tree_map(lambda m, x: m.astype(x.dtype),
                                          mean, tree)

        with jax.named_scope('exchange'):
            grads = mean_over_workers(grads, 'grads/dp')
            if stats is not None:
                stats = mean_over_workers(stats, 'stats/dp')
        with jax.named_scope('optimizer'):
            updates, new_opt_state = opt.update(
                grads, opt_state, params=params,
                extras=Extras(stats=stats, loss=loss,
                              plan=_plan_for_stats(grads, stats),
                              sched=sched, comm=comm, factor=factor,
                              kernel=kernel))
        with jax.named_scope('apply'):
            new_params = apply_updates(params, updates)
        return new_params, new_opt_state, _step_metrics(
            loss, grads, new_opt_state)

    return jax.shard_map(local_step, mesh=mesh,
                         in_specs=(P(), P(), P('data')),
                         out_specs=(P(), P(), P()), check_vma=False)


def init_opt_state(model, opt: GradientTransformation,
                   capture: kvlib.CaptureConfig, params, batch,
                   taps_fn: Optional[Callable] = None,
                   sched: Optional[schedrt.RefreshRuntime] = None,
                   comm: Optional[Any] = None,
                   factor: Optional[Any] = None,
                   kernel: Optional[Any] = None):
    """Materialized optimizer state (examples/trainer).  ``batch`` may be
    arrays or ShapeDtypeStructs — stats shapes come from eval_shape."""
    sched = sched if sched is not None else schedrt.RefreshRuntime()
    if not capture.active:
        return opt.init(params, Extras(sched=sched, comm=comm,
                                       factor=factor, kernel=kernel))
    make_taps = taps_caller(taps_fn)

    def stats_of(p, b):
        _, _, stats = compute_grads_and_stats(model, p, b, capture,
                                              make_taps(p, b))
        return stats

    stats_shapes = jax.eval_shape(stats_of, params, batch)
    zero_stats = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), stats_shapes)
    return opt.init(params, Extras(stats=zero_stats,
                                   plan=_plan_for_stats(params, zero_stats),
                                   sched=sched, comm=comm, factor=factor,
                                   kernel=kernel))


def stats_plan_of(model, capture: kvlib.CaptureConfig, params, batch,
                  taps_fn: Optional[Callable] = None
                  ) -> Optional[bucketing.BucketPlan]:
    """The bucket plan over preconditioned paths, without materializing any
    state (trainer logging: the refresh-ownership map is keyed by it)."""
    if not capture.active:
        return None
    make_taps = taps_caller(taps_fn)

    def stats_of(p, b):
        return compute_grads_and_stats(model, p, b, capture,
                                       make_taps(p, b))[2]

    stats_shapes = jax.eval_shape(stats_of, params, batch)
    return _plan_for_stats(params, stats_shapes)


def abstract_opt_state(model, opt: GradientTransformation,
                       capture: kvlib.CaptureConfig, params_abstract, batch_specs,
                       taps_fn: Optional[Callable] = None,
                       sched: Optional[schedrt.RefreshRuntime] = None,
                       comm: Optional[Any] = None,
                       factor: Optional[Any] = None,
                       kernel: Optional[Any] = None):
    """ShapeDtypeStruct pytree of the optimizer state (dry-run path)."""
    def init_fn(p, b):
        return init_opt_state(model, opt, capture, p, b, taps_fn, sched=sched,
                              comm=comm, factor=factor, kernel=kernel)
    return jax.eval_shape(init_fn, params_abstract, batch_specs)
