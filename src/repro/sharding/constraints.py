"""Activation sharding constraints, mesh-aware but model-agnostic.

XLA's sharding propagation is weak through ``while`` loops: without anchors,
loop carries (the residual stream, flash-attention accumulators) silently
replicate — the dry-run showed 112 GiB/device attention residuals on qwen2.
``shard_activations(x)`` pins the batch dim of (B, S, D)-like activations to
the data axes of whatever mesh is current (no-op outside a mesh context or
when batch doesn't divide), which is enough of an anchor for propagation to
shard the loops.  Sequence parallelism (seq → 'model' in the norm/elementwise
regions) is available as ``shard_activations(x, seq='model')`` — a §Perf lever.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P


def current_mesh():
    """The mesh of the enclosing ``jax.set_mesh`` context when every axis
    is Auto (constraints are legal), else None — outside any mesh, and
    inside ``shard_map`` bodies, whose mapped axes are Manual."""
    m = jax.sharding.get_abstract_mesh()
    if m.empty or any(t != AxisType.Auto for t in m.axis_types):
        return None
    return m


def bound_axis_sizes() -> dict:
    """{axis name: size} for the mesh axes bound in the current tracing
    scope (the Manual axes of an enclosing ``shard_map`` body); {} at top
    level."""
    m = jax.sharding.get_abstract_mesh()
    return {str(a): int(m.shape[a]) for a in m.manual_axes}


def _apply(x: jnp.ndarray, spec: list) -> jnp.ndarray:
    """``with_sharding_constraint`` by spec under ``jit``; an eager
    (uncommitted, single-device) array needs the concrete context mesh
    to be placed rather than refused."""
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(x, P(*spec))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(jax.sharding.get_mesh(), P(*spec)))


def constrain(x: jnp.ndarray, *axes: Optional[str]) -> jnp.ndarray:
    """with_sharding_constraint by logical role per dim: each entry is
    'data' (→ (pod,data)), 'model', or None; silently dropped when the axis
    is missing, doesn't divide, or we're inside shard_map."""
    mesh = current_mesh()
    if mesh is None:
        return x
    daxes = tuple(a for a in ('pod', 'data') if a in mesh.shape)
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]
    spec: list = [None] * x.ndim
    for i, role in enumerate(axes[:x.ndim]):
        if role == 'data' and daxes and x.shape[i] % dsize == 0 and x.shape[i] > 0:
            spec[i] = daxes if len(daxes) > 1 else daxes[0]
        elif role == 'model' and 'model' in mesh.shape and \
                x.shape[i] % mesh.shape['model'] == 0:
            spec[i] = 'model'
    if all(s is None for s in spec):
        return x
    return _apply(x, spec)


def data_axes_in_scope() -> tuple[str, ...]:
    """The subset of the data-parallel axes ('pod', 'data') bound in the
    current tracing scope (inside shard_map/pmap bodies); () elsewhere."""
    bound = bound_axis_sizes()
    return tuple(a for a in ('pod', 'data') if a in bound)


class _InFlightPmean:
    """An issued-but-not-collected statistics reduction (one of: the raw
    tree when no data axis is bound, a dtype-preserving psum'd tree plus
    its static divisor, or a ``repro.comm`` :class:`InFlightMean`).  Lives
    within one trace — the pipeline stores the *collected* tree."""

    __slots__ = ('tree', 'n', 'kind')

    def __init__(self, tree, n, kind):
        self.tree = tree
        self.n = n
        self.kind = kind   # 'raw' | 'passthrough' | 'codec'


def issue_pmean_stats(tree, codec=None, site: Optional[str] = None
                      ) -> _InFlightPmean:
    """Collective half of :func:`pmean_stats`: fire the psums (or the
    codec'd all-reduce issue) over the live data-parallel axes.  The
    passthrough divisor is the trace-time axis size — exactly what
    ``lax.pmean`` divides by internally (``psum`` of a non-traced 1), so
    composing with :func:`collect_pmean_stats` stays bit-exact and
    dtype-preserving."""
    axes = data_axes_in_scope()
    if not axes or tree is None:
        if site is not None and tree is not None:
            from repro.comm import exchange, get_codec, metrics
            c = get_codec(codec)
            # No data axis bound (single-host pjit): nothing moves on the
            # wire, but the site still carries its logical payload so the
            # telemetry breakdown stays comparable across world sizes.
            metrics.record(site, bytes_per_call=exchange.tree_payload_bytes(
                tree, c), codec=c.name, mode='local')
        return _InFlightPmean(tree, None, 'raw')
    from repro.comm import exchange, get_codec, metrics
    arg = axes if len(axes) > 1 else axes[0]
    if get_codec(codec).passthrough:
        if site is not None:
            c = get_codec(codec)
            metrics.record(site, bytes_per_call=exchange.tree_payload_bytes(
                tree, c), codec=c.name, mode='psum')
        return _InFlightPmean(
            jax.tree_util.tree_map(lambda x: jax.lax.psum(x, arg), tree),
            jax.lax.psum(1, arg), 'passthrough')
    return _InFlightPmean(
        exchange.issue_allreduce_mean_tree(tree, codec=codec, axes=axes,
                                           site=site), None, 'codec')


def collect_pmean_stats(fl: _InFlightPmean):
    """Local finishing half of :func:`pmean_stats` (divide / decode)."""
    if fl.kind == 'raw':
        return fl.tree
    if fl.kind == 'passthrough':
        return jax.tree_util.tree_map(lambda v: v / fl.n, fl.tree)
    from repro.comm import exchange
    return exchange.collect_allreduce_mean_tree(fl.tree)[0]


def pmean_stats(tree, codec=None, site: Optional[str] = None):
    """psum-average a pytree of per-bucket KV/KF statistics across the live
    data-parallel axes, making Eva's statistics batch-global as in the
    paper's multi-GPU setup (§3.3).

    ``codec`` ('f32' | 'bf16' | 'int8' | a ``repro.comm.Codec``) selects
    the wire format — the K-FAC/FOOF ``a_outer``/``b_outer`` factor
    reduction moves O(d²) per layer (4-5× the gradient volume on the
    roofline), so compressing it matters where Eva's O(d) KVs don't.
    ``codec=None`` or 'f32' keeps the exact legacy ``lax.pmean`` ops, which
    is what the atol=0 scheduling contracts compare against.

    No-op when no data axis is bound (single-host pjit path — there XLA's
    sharding propagation already reduces the stats with the gradients).
    The f32/None path is idempotent under repetition (pmean of
    already-averaged replicated values returns them unchanged), so
    composing it with an outer explicit reduction (e.g.
    ``train/compression.py``) is safe; the bf16/int8 paths re-quantize on
    every application and must run exactly once per fresh statistic.

    Synchronous composition of the staged halves (issue the collectives,
    finish locally) — see ``repro.schedule.pipeline`` for the one-step
    staged caller.
    """
    return collect_pmean_stats(issue_pmean_stats(tree, codec=codec,
                                                 site=site))


def psum_tree(tree, axes: Optional[tuple[str, ...]] = None):
    """psum a pytree across the live data-parallel axes — the exchange step
    of worker-sharded curvature refresh (``repro.schedule.ownership``): each
    worker contributes its owned, zero-padded slices and the sum
    reconstructs the full bucket stack on every worker (adding zeros is
    exact in IEEE arithmetic, so the exchange preserves bit-identity with a
    single-host refresh).  No-op when no data axis is bound.
    """
    if axes is None:
        axes = data_axes_in_scope()
    if not axes or tree is None:
        return tree
    return jax.tree_util.tree_map(
        lambda x: jax.lax.psum(x, axes if len(axes) > 1 else axes[0]), tree)


def shard_activations(x: jnp.ndarray, seq: Optional[str] = None) -> jnp.ndarray:
    """Constrain dim0 (batch) to (pod,data); optionally dim1 (seq) to model.
    Falls back to sharding the sequence dim over 'data' for batch=1 cells."""
    mesh = current_mesh()
    if mesh is None or x.ndim < 2:
        return x
    daxes = tuple(a for a in ('pod', 'data') if a in mesh.shape)
    if not daxes:
        return x
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]
    spec: list = [None] * x.ndim
    if x.shape[0] % dsize == 0 and x.shape[0] >= dsize:
        spec[0] = daxes if len(daxes) > 1 else daxes[0]
        if seq and seq in mesh.shape and x.ndim >= 3 and \
                x.shape[1] % mesh.shape[seq] == 0:
            spec[1] = seq
    elif x.ndim >= 2 and 'data' in mesh.shape and \
            x.shape[1] % mesh.shape['data'] == 0:
        spec[1] = 'data'
    else:
        return x
    return _apply(x, spec)
