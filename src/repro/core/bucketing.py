"""Bucketed parameter grouping for vectorized preconditioning.

The paper's central claim (§3, §4) is that second-order updates become
*vectorizable*: the Sherman–Morrison/Kronecker-vector formulas broadcast
over any leading dims.  A per-path Python dict loop throws that away — a
40-layer model pays 40 kernel launches per step.  This module groups
parameter paths by ``(shape, dtype)`` into **buckets**, stacks each bucket
into one ``(N, *shape)`` array, and lets the caller run ONE broadcast (or
grid-folded Pallas) preconditioning call per bucket before scattering the
results back.

Layout contract
---------------
* A plan is a deterministic pure function of the flat ``{path: leaf}``
  mapping's shapes/dtypes: paths are sorted, buckets are keyed
  ``"<dtype>_<d0>x<d1>..."`` and emitted in sorted-key order.  Determinism
  is what lets optimizer *state* (EMA'd statistics, cached inverses) live
  bucketed: the plan rebuilt from the same tree always aligns with it.
* Stacking axis is a NEW leading axis 0; entry ``i`` of a bucket is
  ``bucket.paths[i]``.  Scan-stacked leaves (leading layer/expert dims) keep
  those dims *inside* the bucket shape — a bucket of ``(L, d_in, d_out)``
  leaves stacks to ``(N, L, d_in, d_out)``, which the broadcast formulas
  and the grid-folded kernels handle unchanged.
* ``build_plan`` is memoized on the shape signature, so deriving the plan
  at ``init_opt_state`` time and re-deriving it inside a jitted ``update``
  costs one dict walk, not a recomputation.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Mapping, NamedTuple, Optional

import jax
import jax.numpy as jnp


# Buckets with fewer than this many members skip the stack/unstack copies in
# ``precondition.precondition_tree`` and take the broadcast per-path calls
# instead: the table5 CPU numbers showed gather/scatter copies for N<=2
# buckets costing more than the one launch they save (ROADMAP "bucket gather
# cost").  State layout is unaffected — optimizer state stays bucket-stacked
# for every bucket (``gather_tree``/``gather`` ignore the flag), so the
# threshold is purely an execution-path choice and outputs stay bit-identical
# either way (proven in tests/test_bucketing.py).
DEFAULT_MIN_BUCKET_SIZE = 3


class Bucket(NamedTuple):
    key: str                    # "<dtype>_<d0>x<d1>..."
    paths: tuple[str, ...]      # sorted; index in this tuple == stack index
    shape: tuple[int, ...]      # per-leaf shape (without the stack axis)
    dtype: Any                  # jnp dtype
    stacked: bool = True        # False: small bucket, broadcast path


class BucketPlan(NamedTuple):
    buckets: tuple[Bucket, ...]

    @property
    def paths(self) -> tuple[str, ...]:
        return tuple(p for b in self.buckets for p in b.paths)

    def __len__(self) -> int:
        return len(self.buckets)


def bucket_key(shape: tuple[int, ...], dtype) -> str:
    return f"{jnp.dtype(dtype).name}_{'x'.join(map(str, shape))}"


@functools.lru_cache(maxsize=512)
def _plan_from_sig(sig: tuple, min_bucket_size: int) -> BucketPlan:
    groups: dict[str, list] = {}
    meta: dict[str, tuple] = {}
    for path, shape, dtype_name in sig:
        key = bucket_key(shape, dtype_name)
        groups.setdefault(key, []).append(path)
        meta[key] = (shape, dtype_name)
    buckets = tuple(
        Bucket(key=k, paths=tuple(sorted(groups[k])),
               shape=meta[k][0], dtype=jnp.dtype(meta[k][1]),
               stacked=len(groups[k]) >= min_bucket_size)
        for k in sorted(groups))
    return BucketPlan(buckets=buckets)


def build_plan(flat: Mapping[str, Any],
               predicate: Optional[Callable[[str, Any], bool]] = None,
               min_bucket_size: Optional[int] = None) -> BucketPlan:
    """Group ``{path: leaf}`` (arrays / ShapeDtypeStructs / tracers) into a
    deterministic BucketPlan; ``predicate(path, leaf)`` filters paths.
    Buckets smaller than ``min_bucket_size`` (default
    ``DEFAULT_MIN_BUCKET_SIZE``) are marked unstacked — same grouping and
    state layout, but ``precondition_tree`` skips their gather/scatter."""
    if min_bucket_size is None:
        min_bucket_size = DEFAULT_MIN_BUCKET_SIZE
    sig = tuple(sorted(
        (p, tuple(x.shape), jnp.dtype(x.dtype).name)
        for p, x in flat.items()
        if predicate is None or predicate(p, x)))
    return _plan_from_sig(sig, min_bucket_size)


def gather(plan: BucketPlan, flat: Mapping[str, Any]) -> dict[str, jnp.ndarray]:
    """Stack each bucket's leaves along a new axis 0: {key: (N, *shape)}."""
    return {b.key: jnp.stack([flat[p] for p in b.paths]) for b in plan.buckets}


def scatter(plan: BucketPlan, bucketed: Mapping[str, jnp.ndarray]) -> dict[str, Any]:
    """Inverse of ``gather``: {path: (*shape)} in plan order."""
    out = {}
    for b in plan.buckets:
        stacked = bucketed[b.key]
        for i, p in enumerate(b.paths):
            out[p] = stacked[i]
    return out


def gather_tree(plan: BucketPlan, flat: Mapping[str, Any]) -> dict[str, Any]:
    """``gather`` for per-path *pytrees* (e.g. ``kv.LayerStats``): each leaf
    position is stacked across the bucket's paths; None leaves stay None.

    All paths in a bucket must share the pytree structure (true by
    construction: one capture config per optimizer)."""
    out = {}
    for b in plan.buckets:
        trees = [flat[p] for p in b.paths]
        out[b.key] = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *trees)
    return out


def is_bucketed(plan: BucketPlan, mapping: Mapping[str, Any]) -> bool:
    """True when ``mapping`` is keyed by this plan's bucket keys (already
    gathered) rather than by parameter paths."""
    keys = {b.key for b in plan.buckets}
    return bool(mapping) and set(mapping) <= keys


def kernel_shapes(plan: BucketPlan) -> list[tuple[int, int, int]]:
    """``(L, d_in, d_out)`` of each bucket as one kernel launch sees it: the
    stack axis and any leading (scan) dims folded into L."""
    out = []
    for b in plan.buckets:
        lead = len(b.paths)
        for d in b.shape[:-2]:
            lead *= d
        out.append((lead,) + tuple(b.shape[-2:]))
    return out
