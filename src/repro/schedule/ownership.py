"""Worker-sharded refresh ownership: which data-parallel worker recomputes
which bucket item.

Every worker holding identical (psum-averaged) curvature statistics and
redundantly inverting every bucket item is exactly the waste distributed
K-FAC-style layer assignment eliminates (cf. MKOR's distributed factor
maintenance).  This module assigns work to the workers of the live
``('pod','data')`` mesh deterministically at two granularities: per stack
row (:func:`assign_owners`, the original cost-weighted LPT greedy — no
production caller since the runtime went slice-granular; kept as the
simple reference the ownership tests compare against) and per
(row × lead-dim) slice (:func:`assign_slice_owners`, what the refresh
runtime shards at; :func:`assign_pod_slice_owners` for pod-local
topology), so
refresh FLOPs scale 1/W with world size even on scan-stacked models with
few parameter paths.  The refreshed slices are then exchanged through
``repro.comm.exchange`` — by default an owned-slice all-gather whose
per-worker traffic also scales ~1/W (each slice arrives as an exact copy
of its owner's value), or the legacy bucket-stacked zero-padded ``psum``
(``x + 0 == x`` is exact) — both bit-identical reconstructions.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bucketing import Bucket, BucketPlan
from repro.sharding.constraints import bound_axis_sizes, data_axes_in_scope


# ---------------------------------------------------------------------------
# Per-item cost model


def inverse_cost(sides: str = 'both') -> Callable[[Bucket], float]:
    """FLOP estimate for refreshing ONE item of a bucket: dense
    factorizations are cubic in the factor dim, and scan-stacked leading
    dims multiply (an item of a ``(L, d_in, d_out)`` bucket refreshes L
    factor pairs).

    sides: 'left' (FOOF: input factor only) or 'both' (K-FAC / Shampoo).
    """
    if sides not in ('left', 'both'):
        raise ValueError(f"sides must be 'left' or 'both', got {sides!r}")

    def cost(bucket: Bucket) -> float:
        d_in, d_out = bucket.shape[-2], bucket.shape[-1]
        lead = 1
        for d in bucket.shape[:-2]:
            lead *= d
        c = float(d_in) ** 3
        if sides == 'both':
            c += float(d_out) ** 3
        return lead * c

    return cost


# ---------------------------------------------------------------------------
# Assignment


@functools.lru_cache(maxsize=256)
def _assign_cached(plan: BucketPlan, costs: tuple, world: int,
                   counts: tuple) -> dict:
    owners = {b.key: np.zeros(n, np.int64)
              for b, n in zip(plan.buckets, counts)}
    if world > 1:
        items = [(costs[bi], b.key, i)
                 for bi, b in enumerate(plan.buckets)
                 for i in range(counts[bi])]
        # LPT greedy = weighted round-robin: biggest items first, each to the
        # least-loaded worker; ties broken by (key, item) so the map is a
        # pure function of (plan, cost, world) on every host.
        items.sort(key=lambda t: (-t[0], t[1], t[2]))
        loads = np.zeros(world, np.float64)
        for c, key, i in items:
            w = int(np.argmin(loads))
            owners[key][i] = w
            loads[w] += c
    return owners


def assign_owners(plan: BucketPlan, cost: Callable[[Bucket], float],
                  world: int) -> dict[str, np.ndarray]:
    """{bucket_key: (N,) int array of owner ranks in [0, world)} — static
    (numpy) metadata, deterministic across hosts.  One entry per stack ROW
    (parameter path); the refresh runtime and the exchange accounting use
    the finer :func:`assign_slice_owners` — this row-level form has no
    production caller and survives as the reference in the tests."""
    costs = tuple(cost(b) for b in plan.buckets)
    counts = tuple(len(b.paths) for b in plan.buckets)
    return _assign_cached(plan, costs, world, counts)


def lead_size(bucket: Bucket) -> int:
    """Product of a bucket's leading (scan/expert-stack) dims — the number
    of factor pairs one stack row carries."""
    lead = 1
    for d in bucket.shape[:-2]:
        lead *= int(d)
    return lead


@functools.lru_cache(maxsize=256)
def _assign_slices_cached(plan: BucketPlan, costs: tuple, world: int,
                          counts: tuple) -> dict:
    owners = {b.key: np.zeros(n, np.int64)
              for b, n in zip(plan.buckets, counts)}
    if world > 1:
        order = sorted(range(len(plan.buckets)),
                       key=lambda bi: (-costs[bi], plan.buckets[bi].key))
        loads = np.zeros(world, np.float64)
        for bi in order:
            key = plan.buckets[bi].key
            per = np.zeros(world, np.int64)
            for i in range(counts[bi]):
                # per-bucket balance first (counts differ by <= 1, which is
                # what minimizes the padded all-gather), global cost load as
                # the tie-break; first-min ties keep the map deterministic
                cand = np.flatnonzero(per == per.min())
                w = int(cand[np.argmin(loads[cand])])
                owners[key][i] = w
                per[w] += 1
                loads[w] += costs[bi]
    return owners


def assign_slice_owners(plan: BucketPlan, cost: Callable[[Bucket], float],
                        world: int) -> dict[str, np.ndarray]:
    """{bucket_key: (N·lead,) owner ranks} — ownership at the finest stack
    granularity: (row, lead-slice), row-major.

    Row-level assignment caps parallelism at the path count, which on
    scan-stacked models is tiny (qwen2-0.5b: 7 paths for 168 layer-factor
    pairs) — one 2 GB row then has a single owner and the exchange can't
    shrink.  Slicing the leading dims makes refresh FLOPs *and* the
    owned-slice exchange genuinely scale ~1/W.

    Within a bucket every slice costs the same (``cost(bucket)/lead``), so
    the assignment balances each bucket's slice COUNT across workers first
    (per-worker counts differ by at most 1 — exactly what minimizes the
    padded all-gather size, since the exchange pads every worker to the
    bucket max) and breaks count ties by global cost load (the LPT
    objective; buckets are visited biggest-slice-first).  Deterministic on
    every host, like :func:`assign_owners`.
    """
    costs = tuple(cost(b) / lead_size(b) for b in plan.buckets)
    counts = tuple(len(b.paths) * lead_size(b) for b in plan.buckets)
    return _assign_slices_cached(plan, costs, world, counts)


@functools.lru_cache(maxsize=256)
def _assign_pod_cached(plan: BucketPlan, costs: tuple, pods: tuple,
                       counts: tuple) -> dict:
    n_pods, per_pod = pods
    owners = {b.key: np.zeros(n, np.int64)
              for b, n in zip(plan.buckets, counts)}
    if n_pods * per_pod > 1:
        # LPT of whole buckets over pods: biggest total first to the
        # least-loaded pod — every slice of a bucket lands in ONE pod, so
        # the slice-granular gather stays on that pod's ICI links.
        order = sorted(range(len(plan.buckets)),
                       key=lambda bi: (-costs[bi] * counts[bi],
                                       plan.buckets[bi].key))
        pod_loads = np.zeros(n_pods, np.float64)
        for bi in order:
            key = plan.buckets[bi].key
            pod = int(np.argmin(pod_loads))
            pod_loads[pod] += costs[bi] * counts[bi]
            # within the pod: balance slice counts over its workers (the
            # same objective as the flat assignment — per-worker counts
            # differ by <= 1, minimizing the padded gather)
            for i in range(counts[bi]):
                owners[key][i] = pod * per_pod + i % per_pod
    return owners


def assign_pod_slice_owners(plan: BucketPlan, cost: Callable[[Bucket], float],
                            pods: tuple[int, int]) -> dict[str, np.ndarray]:
    """Slice owners under a ``(n_pods, per_pod)`` topology: every bucket's
    slices are owned by workers of a single pod (buckets LPT-balanced over
    pods by total inverse cost, slices count-balanced within the pod).

    Global ranks are row-major over ('pod', intra-pod) — matching
    ``world_and_rank`` over the ('pod','data') axes — so the same owner
    map drives both the cond-gated recompute and the two-stage exchange
    (``repro.comm.exchange.allgather_owned_slices(pods=...)``).
    """
    costs = tuple(cost(b) / lead_size(b) for b in plan.buckets)
    counts = tuple(len(b.paths) * lead_size(b) for b in plan.buckets)
    return _assign_pod_cached(plan, costs, tuple(pods), counts)


def describe_ownership(plan: BucketPlan, world: int,
                       sides: str = 'both') -> dict[str, list[int]]:
    """JSON-able per-worker owned-slice counts per bucket (trainer
    logging): {bucket_key: [slices owned by worker 0, 1, ...]}."""
    owners = assign_slice_owners(plan, inverse_cost(sides), world)
    return {k: np.bincount(v, minlength=world).tolist()
            for k, v in owners.items()}


# ---------------------------------------------------------------------------
# Sub-slice (column-block) ownership — granularity BELOW one slice
#
# Slice-granular ownership bottoms out at one (lead-slice, d, d) factor per
# owner: a single un-stackable oversized factor (glm4-9b's 151552-wide vocab
# head) is then owned whole by ONE worker and caps the W=4 exchange
# reduction at 1.71x.  These helpers partition the rows/columns of one such
# factor across ALL workers as contiguous row bands, which the matrix-free
# apply path (repro.core.factor_sharded) turns into per-worker partial
# matvecs completed by a single zero-padded psum.


def factor_block(d: int, world: int) -> int:
    """Rows per worker when one (d, d) factor is column-block partitioned:
    ``ceil(d / world)``.  Worker ``w`` holds the contiguous row band
    ``[w*B, (w+1)*B)`` of the zero-padded ``(world*B, d)`` factor.  Every
    row of a single symmetric factor costs the same, so the uniform
    contiguous split IS the LPT partition at this granularity (per-worker
    loads differ by at most one row) — no greedy pass needed."""
    return -(-int(d) // int(world))


def assign_subslice_owners(d: int, world: int) -> np.ndarray:
    """(world,) int64: row band ``b`` of the factor is owned by worker
    ``b`` — the uniform LPT map below slice granularity, returned as an
    explicit owner array so describe/logging paths treat factor bands like
    any other ownership map."""
    return np.arange(int(world), dtype=np.int64)


def subslice_trips(bucket: Bucket, threshold: int) -> tuple[bool, bool]:
    """(in_side, out_side): which factor sides of ``bucket`` exceed the
    sub-slice ``shard_threshold`` (factor dim >= threshold).  The policy
    knob (``repro.core.factor_sharded.FactorShardConfig``) decides WHAT to
    do with a tripped side ('shard' | 'exclude' | keep 'dense'); this is
    only the structural trigger."""
    d_in, d_out = int(bucket.shape[-2]), int(bucket.shape[-1])
    return d_in >= int(threshold), d_out >= int(threshold)


def describe_subslices(plan: BucketPlan, world: int,
                       threshold: int) -> dict[str, list[int]]:
    """JSON-able per-worker row-band sizes for every tripped factor side
    (trainer logging, alongside :func:`describe_ownership`):
    ``{'<bucket_key>/<in|out>': [rows owned by worker 0, 1, ...]}``."""
    out: dict[str, list[int]] = {}
    for b in plan.buckets:
        trips = subslice_trips(b, threshold)
        for side, tripped, d in (('in', trips[0], int(b.shape[-2])),
                                 ('out', trips[1], int(b.shape[-1]))):
            if tripped:
                blk = factor_block(d, world)
                out[f'{b.key}/{side}'] = [
                    max(0, min(blk, d - w * blk)) for w in range(world)]
    return out


# ---------------------------------------------------------------------------
# Mesh introspection (trace-time)


def world_and_rank(axes: Optional[tuple[str, ...]] = None):
    """(world, rank) over the data-parallel axes bound in the current
    tracing scope.  ``world`` is a static int; ``rank`` is a traced scalar
    (row-major over the bound axes), or None when single-worker.

    Outside any shard_map/pmap body this is (1, None): refresh sharding
    quietly disables itself and every worker (the only worker) owns
    everything — which is what makes single-host behavior the W=1 special
    case of the same code path rather than a separate branch.
    """
    if axes is None:
        axes = data_axes_in_scope()
    if not axes:
        return 1, None
    sizes = bound_axis_sizes()
    world = 1
    for a in axes:
        world *= int(sizes.get(a, 1))
    if world <= 1:
        return 1, None
    rank = jnp.zeros((), jnp.int32)
    for a in axes:
        rank = rank * int(sizes.get(a, 1)) + jax.lax.axis_index(a)
    return world, rank
