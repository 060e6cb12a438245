"""RefreshRuntime: the façade the optimizers and the train step talk to.

One object owns the three scheduling concerns the optimizers used to
re-implement ad hoc:

* **policy resolution** — an optimizer's explicit ``policy=`` wins, else a
  train-level default threaded through ``Extras.sched``, else the legacy
  ``interval`` kwarg as ``every_k(interval)``;
* **gated, worker-sharded recomputation** — :func:`sharded_refresh` wraps
  the whole refresh in one ``lax.cond`` (skipped steps cost nothing) and,
  under a live data-parallel mesh, flattens each bucket's stack × leading
  scan dims into slices and gates each slice on ownership with an inner
  ``lax.cond`` inside the ``lax.map`` (``lax.map`` lowers to ``scan``, so
  non-owned slices really skip the inverse) before the codec-aware
  owned-slice exchange (``repro.comm.exchange``, per-worker traffic ~1/W;
  the legacy full-stack psum stays available via
  ``ExchangeConfig(exchange='psum')``);
* **observability** — :func:`schedule_metrics` pulls refresh counts /
  staleness out of any optimizer state so the trainer can log them without
  knowing optimizer internals; the comm layer counts exchange bytes per
  call-site.

Bit-identity contract: with ``every_k(1)`` and/or a single worker, outputs
are bit-identical (atol=0) to always-fresh recomputation.  With W workers
the two exchange modes are bit-identical to each other under the f32 codec
(owned-slice copies / x+0 psums are both exact); vs a single worker only
the LAPACK batching of the slice-granular inverses can move the last float
ulp (see ``recompute_sharded``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Optional

import jax
import jax.numpy as jnp

from repro.comm import exchange, metrics
from repro.comm import codec as exchange_codec
from repro.core.bucketing import Bucket, BucketPlan
from repro.schedule import ownership
from repro.schedule import pipeline as pipeline_mod
from repro.schedule import policy as policy_mod
from repro.sharding.constraints import bound_axis_sizes, psum_tree


@dataclasses.dataclass(frozen=True)
class RefreshRuntime:
    """Train-level refresh configuration (static, not a pytree).

    Attributes:
      policy: default policy for optimizers built without an explicit one
        (their legacy ``interval`` kwarg still wins over this default only
        when it was explicitly set ≠ 1 — see :meth:`resolve`).
      shard_refresh: gate worker-sharded ownership; turning it off makes
        every worker recompute everything (the redundant pre-runtime
        behavior, kept for A/B benchmarks).
      pipeline: 'sync' (default — every exchange result is applied in the
        step that issued it, the exact legacy behavior) or 'onestep' (the
        double-buffered pipeline: step t applies the stats / refreshed
        inverses exchanged at t−1 so step t's collectives can overlap its
        compute; see ``repro.schedule.pipeline``).  Must match between
        ``init_opt_state`` and the train step — 'onestep' allocates
        pipeline buffers in optimizer state.
    """

    policy: Optional[policy_mod.RefreshPolicy] = None
    shard_refresh: bool = True
    pipeline: str = 'sync'

    def __post_init__(self):
        if self.pipeline not in ('sync', 'onestep'):
            raise ValueError("pipeline must be 'sync' or 'onestep', "
                             f'got {self.pipeline!r}')

    def resolve(self, local: Optional[policy_mod.RefreshPolicy],
                interval: int = 1) -> policy_mod.RefreshPolicy:
        if local is not None:
            return local
        if interval != 1:
            # an explicitly-tuned legacy interval beats a train-level default
            return policy_mod.every_k(interval)
        return self.policy if self.policy is not None \
            else policy_mod.every_k(1)


_DEFAULT = RefreshRuntime()


def from_extras(extras) -> RefreshRuntime:
    """The runtime threaded through ``Extras.sched`` (next to the bucket
    plan), or the default runtime when the caller drives the transform
    directly."""
    rt = getattr(extras, 'sched', None) if extras is not None else None
    return rt if rt is not None else _DEFAULT


def resolve_pipe(rt: RefreshRuntime, state_pipe):
    """The pipe dict an optimizer update should thread this step (None in
    sync mode), with a static consistency check: the pipeline mode is baked
    into the state structure at init, so init and update must agree."""
    if rt.pipeline == 'onestep':
        if state_pipe is None:
            raise ValueError(
                "pipeline='onestep' but the optimizer state has no pipeline "
                'buffers — pass the same RefreshRuntime(pipeline=...) to '
                'init_opt_state and the train step')
        return state_pipe
    if state_pipe is not None:
        raise ValueError(
            "pipeline='sync' but the optimizer state carries pipeline "
            'buffers — pass the same RefreshRuntime(pipeline=...) to '
            'init_opt_state and the train step')
    return None


# ---------------------------------------------------------------------------
# Gated, worker-sharded refresh


def sharded_refresh(plan: BucketPlan, refresh: jnp.ndarray,
                    item_fn: Callable[[Bucket, Any], Any],
                    args_b: Mapping[str, Any], old_b: Mapping[str, Any],
                    *, cost: Callable[[Bucket], float],
                    shard: bool = True,
                    comm: Optional[exchange.ExchangeConfig] = None,
                    site: str = 'refresh',
                    pipe: Optional[pipeline_mod.PipelineState] = None):
    """Recompute cached per-bucket values under a refresh decision.

    Args:
      plan: the bucket plan whose stacked state is being refreshed.
      refresh: traced scalar bool — the policy decision (replicated across
        workers, so every worker takes the same cond branch).
      item_fn: ``(bucket, per_item_args) -> per_item_out`` — the expensive
        recomputation for ONE stack item (e.g. a damped-inverse pair).
        Must broadcast over leading dims: single-worker it receives a whole
        stack row (with any scan/expert lead dims), under a W>1 mesh one
        (lead-flattened) slice at a time.
      args_b: {bucket_key: stacked-args pytree} (leading axis = stack).
      old_b: {bucket_key: stacked cached values} returned unchanged on
        non-refresh steps; also supplies output shapes/dtypes.
      cost: per-item FLOP estimate for ownership weighting.
      shard: disable to force every worker to recompute everything.
      comm: exchange config (``Extras.comm``): which codec the refreshed
        slices travel in and whether the exchange is the owned-slice
        all-gather (default; per-worker traffic ~1/W of the stack) or the
        legacy full-stack zero-padded psum.
      site: call-site label for the ``repro.comm.metrics`` byte counters.
      pipe: ``None`` (sync — the refreshed values are applied in this step,
        the legacy behavior and return shape) or this site's
        ``PipelineState`` (one-step pipeline).  The cond/exchange graph is
        IDENTICAL in both modes; what changes is the consumer: pipelined
        callers precondition with the returned ``applied`` caches (the
        values refreshed in an earlier step — ``old_b``, which doubles as
        the in-flight buffer, so no second cache copy exists) and store the
        fresh result, keeping this step's exchange out of this step's
        compute cone.

    Returns {bucket_key: refreshed stacked values} with ``old_b``'s
    structure when ``pipe is None``; otherwise the staged triple
    ``(applied, fresh, new_pipe)`` where ``applied`` is ``old_b`` (what
    this step preconditions with) and ``fresh`` is the cond output (what
    the caller must store for the next step).
    """
    axes = ownership.data_axes_in_scope() if shard else ()
    world, rank = ownership.world_and_rank(axes) if shard else (1, None)
    cfg = exchange.from_extras(None) if comm is None else comm

    def recompute_single(_):
        # the exact legacy single-worker structure: one fused lax.map per
        # bucket over stack ROWS, item_fn broadcasting over any leading
        # scan/expert dims — this is the path the atol=0 every_k(1)-vs-
        # legacy contracts compare (tests/test_schedule.py)
        out = {}
        for b in plan.buckets:
            out[b.key] = jax.lax.map(lambda a, b=b: item_fn(b, a),
                                     args_b[b.key])
        # W=1: nothing moves, but the site still reports the stack's
        # logical payload so telemetry breakdowns compare across worlds
        metrics.record(site, bytes_per_call=sum(
            exchange.tree_payload_bytes(v, exchange_codec.F32)
            for v in out.values()), codec='f32', mode='local')
        return out

    def recompute_sharded(_):
        # W > 1: ownership at SLICE granularity — the stack axis and the
        # leading scan/expert dims flatten into one (N·lead) slice axis, so
        # refresh FLOPs and exchange traffic both scale ~1/W even when the
        # model has few (huge, scan-stacked) parameter paths.  Caveat: a
        # slice inverse runs LAPACK on one (d, d) matrix where the
        # single-worker path batches (lead, d, d), which can move the last
        # float ulp (~1e-6; batched-vs-single getrf) — the two exchange
        # MODES below stay bit-identical to each other because they share
        # this compute.
        # topology='pod': pod-local ownership so the slice gather stays on
        # the intra-pod (ICI) axis; needs both ('pod','data') axes live and
        # the gather exchange (the full-stack psum has no gather stage)
        pods = None
        if cfg.topology == 'pod' and cfg.exchange == 'gather' \
                and len(axes) == 2:
            sizes = bound_axis_sizes()
            pods = (int(sizes.get(axes[0], 1)), int(sizes.get(axes[1], 1)))
            if pods[0] <= 1 or pods[0] * pods[1] != world:
                pods = None
        owners = (ownership.assign_pod_slice_owners(plan, cost, pods)
                  if pods is not None
                  else ownership.assign_slice_owners(plan, cost, world))
        out = {}
        for b in plan.buckets:
            nlead = len(b.shape) - 2
            n_slices = len(b.paths) * ownership.lead_size(b)

            def flat(x, nlead=nlead, n_slices=n_slices):
                return x.reshape((n_slices,) + x.shape[1 + nlead:])

            fargs = jax.tree_util.tree_map(flat, args_b[b.key])
            fold = jax.tree_util.tree_map(flat, old_b[b.key])
            own = jnp.asarray(owners[b.key])

            def one(t, b=b, own=own, fold=fold):
                idx, a = t
                zeros = jax.tree_util.tree_map(
                    lambda x: jnp.zeros(x.shape[1:], x.dtype), fold)
                return jax.lax.cond(own[idx] == rank,
                                    lambda a: item_fn(b, a),
                                    lambda a: zeros, a)

            idx = jnp.arange(n_slices, dtype=jnp.int32)
            out[b.key] = jax.lax.map(one, (idx, fargs))
        # exchange: owners computed real slices, everyone else zeros.
        # 'gather' ships only each worker's owned slices (static-shape
        # padded gather, per-worker traffic ~1/W of the stack) and
        # reconstructs every slice as an exact copy of its owner's value;
        # 'psum' is the legacy full-stack zero-padded sum (x+0 exact).
        # Exact copies and x+0 sums are both bit-exact, so the two modes
        # agree atol=0 under the f32 codec.
        if cfg.exchange == 'psum':
            out = psum_tree(out, axes)
            metrics.record(site, bytes_per_call=sum(
                exchange.tree_payload_bytes(v, exchange_codec.F32)
                for v in out.values()), codec='f32', mode='psum')
        else:
            out = exchange.allgather_owned_slices(
                plan, owners, world, rank, out, codec=cfg.codec,
                axes=axes, site=site, pods=pods)
        return {k: jax.tree_util.tree_map(
            lambda y, o: y.reshape(o.shape), out[k], old_b[k])
            for k in out}

    recompute = recompute_single if world == 1 else recompute_sharded

    def keep(_):
        return {b.key: old_b[b.key] for b in plan.buckets}

    fresh = jax.lax.cond(refresh, recompute, keep, operand=None)
    if pipe is None:
        return fresh
    applied = {b.key: old_b[b.key] for b in plan.buckets}
    return applied, fresh, pipeline_mod.tick(pipe, refresh)


# ---------------------------------------------------------------------------
# Observability


def sched_states(opt_state: Any) -> list[policy_mod.SchedState]:
    """All SchedState nodes in an optimizer-state pytree (works on traced
    and concrete states — the walk is over static Python structure)."""
    found: list[policy_mod.SchedState] = []

    def walk(x):
        if isinstance(x, policy_mod.SchedState):
            found.append(x)
            return
        if isinstance(x, dict):
            for v in x.values():
                walk(v)
        elif isinstance(x, (list, tuple)):
            for v in x:
                walk(v)

    walk(opt_state)
    return found


# Step-metric fields this module contributes, declared next to their
# producer so the telemetry schema (repro.obs.events) stays in sync with
# the code that emits them: name -> (kind in {'int','num'}, unit).
METRIC_FIELDS = {
    'refreshes': ('int', 'cumulative refreshes'),
    'refresh_since': ('int', 'steps since last refresh'),
    'staleness': ('num', 'policy staleness proxy'),
}


def ownership_event(plan: Optional[BucketPlan],
                    world: Optional[int] = None) -> Optional[dict]:
    """Typed ``refresh_ownership`` record body ({'world','owners'}) for a
    bucket plan under a ``world``-worker mesh — what the trainer emits at
    startup through ``repro.obs`` (None when nothing is preconditioned)."""
    if plan is None or not plan.buckets:
        return None
    world = world if world is not None else max(1, jax.device_count())
    return {'world': int(world),
            'owners': ownership.describe_ownership(plan, world)}


def schedule_metrics(opt_state: Any) -> dict[str, jnp.ndarray]:
    """{'refreshes', 'refresh_since', 'staleness'} aggregated over every
    scheduled transform in the state; {} when nothing is scheduled.  Usable
    inside jit (returns traced scalars) and on concrete states."""
    sts = sched_states(opt_state)
    if not sts:
        return {}
    return {
        'refreshes': sum((s.n_refresh for s in sts),
                         jnp.zeros((), jnp.int32)),
        'refresh_since': jnp.max(jnp.stack([s.since for s in sts])),
        'staleness': jnp.max(jnp.stack([s.staleness for s in sts])),
    }
