"""Matrix-free sharded application of oversized Kronecker factors.

The owned-slice refresh exchange bottoms out at ONE owner per factor: a
single un-stackable oversized factor (glm4-9b's 151552-wide vocab-head
K-FAC/Shampoo factor) then caps the W=4 exchange reduction at 1.71x vs the
4.00x the rest of the model achieves.  MKOR ducks the problem with
``exclude_vocabulary_size``; the paper's Sherman–Morrison identity (Eq. 13)
makes the better fix obvious — the *inverse never needs materializing*.
This module extends that matrix-free view from rank-one Eva updates to
dense Kronecker factors: the damped inverse (or inverse 4th root) is
*applied* to the gradient through an iterative solve whose only primitive
is ``Y @ M`` — and that matvec distributes perfectly over row bands of the
factor (``ownership.factor_block``), each worker contributing a full-width
partial completed by one gradient-shaped psum
(``exchange.psum_partials``).  Nothing (d, d)-sized is ever inverted,
eigendecomposed, or exchanged.

Per-factor policy knob (threaded via ``Extras.factor``):

  'dense'    — legacy path, bit-exact (the module is a structural no-op).
  'exclude'  — MKOR-style guard: the oversized side becomes the identity,
               the remaining side keeps plain-γ damping (π-split damping
               needs both factors).  Zero cost, zero exchange.
  'shard'    — matrix-free: per-worker band matvecs (FLOPs 1/W) + one psum
               per solve iteration.  The factor EMA stays replicated (state
               layout unchanged); only the *work* and the refresh exchange
               shrink — the oversized factor leaves the refresh roofline
               entirely and its per-step traffic is gradient-shaped.

Solvers: 'binomial' — the generalized binomial (Neumann) series for
(M+γI)^{-p} after a Gershgorin rescale, valid for any p>0 (K-FAC p=1,
Shampoo p=1/4); 'cg' — conjugate gradients, p=1 only (exact in ≤ d
iterations on a small factor, which is what the equivalence tests use).
Small factors below ``shard_threshold`` keep the dense cached-inverse
fallback (``_damped_inv`` / ``_inv_proot_psd``) recomputed replicated under
the same refresh schedule.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import exchange
from repro.core import bucketing
from repro.core import precondition as pre
from repro.schedule import ownership


POLICIES = ('dense', 'exclude', 'shard')
SOLVERS = ('binomial', 'cg')


@dataclasses.dataclass(frozen=True)
class FactorShardConfig:
    """Per-factor execution policy for oversized Kronecker factors.

    head_policy: what to do with a factor side whose dim trips
      ``shard_threshold`` — 'dense' (legacy, default), 'exclude' (identity
      guard) or 'shard' (matrix-free distributed solve).
    shard_threshold: factor dim at/above which a side trips
      (``ownership.subslice_trips``).  The 65536 default targets
      vocab-scale factors only: glm4-9b's 151552 head trips, its 13696
      d_ff (the largest block side) does not.  Callers size it to their
      arch; the launcher exposes ``--head-threshold``.
    solver / solve_iters: iterative scheme for 'shard' ('cg' valid for
      power −1 only; Shampoo's −1/4 root always takes the binomial series).
    """
    head_policy: str = 'dense'
    shard_threshold: int = 65536
    solver: str = 'cg'
    solve_iters: int = 32

    def __post_init__(self):
        if self.head_policy not in POLICIES:
            raise ValueError(f'head_policy must be one of {POLICIES}, '
                             f'got {self.head_policy!r}')
        if self.solver not in SOLVERS:
            raise ValueError(f'solver must be one of {SOLVERS}, '
                             f'got {self.solver!r}')
        if self.shard_threshold < 2:
            raise ValueError('shard_threshold must be >= 2')
        if self.solve_iters < 1:
            raise ValueError('solve_iters must be >= 1')


def from_extras(extras) -> FactorShardConfig:
    """The factor policy threaded through ``Extras.factor`` (a
    FactorShardConfig or a kwargs mapping); default keeps every factor
    dense — the exact legacy path."""
    f = getattr(extras, 'factor', None) if extras is not None else None
    if f is None:
        return FactorShardConfig()
    if isinstance(f, FactorShardConfig):
        return f
    return FactorShardConfig(**dict(f))


# ---------------------------------------------------------------------------
# Plan split: which buckets leave the dense refresh path


@functools.lru_cache(maxsize=256)
def _split_cached(plan: bucketing.BucketPlan, policy: str,
                  threshold: int):
    head: dict[str, tuple[str, str]] = {}
    dense = []
    for b in plan.buckets:
        t_in, t_out = ownership.subslice_trips(b, threshold)
        if policy != 'dense' and (t_in or t_out):
            head[b.key] = (policy if t_in else 'dense',
                           policy if t_out else 'dense')
        else:
            dense.append(b)
    if not head:
        # return the ORIGINAL plan object: callers hit the legacy code path
        # with the same lru-cached ownership maps — bit-exact by identity
        return plan, head
    return bucketing.BucketPlan(buckets=tuple(dense)), head


def split_plan(plan: bucketing.BucketPlan, cfg: FactorShardConfig):
    """(dense_plan, {bucket_key: (in_policy, out_policy)}).

    Buckets with a tripped side are removed from the dense plan — and with
    it from ``sharded_refresh`` and the owned-slice exchange; sides below
    the threshold inside a head bucket stay 'dense' (cached small inverse,
    recomputed replicated).  When nothing trips (or head_policy='dense')
    the original plan object is returned with an empty policy map: the
    optimizer takes the legacy path unchanged."""
    return _split_cached(plan, cfg.head_policy, int(cfg.shard_threshold))


# ---------------------------------------------------------------------------
# Distributed band matvec: the ONE primitive of the matrix-free path


def _band(m: jnp.ndarray, world: int, rank) -> jnp.ndarray:
    """This worker's contiguous row band of factor ``m`` (..., d, d) ->
    (..., B, d) with B = ceil(d/world); rows past d are zero (padding), so
    band partials sum exactly to the unsharded matvec."""
    if world <= 1 or rank is None:
        return m
    d = m.shape[-2]
    blk = ownership.factor_block(d, world)
    pad = world * blk - d
    if pad:
        width = [(0, 0)] * (m.ndim - 2) + [(0, pad), (0, 0)]
        m = jnp.pad(m, width)
    return jax.lax.dynamic_slice_in_dim(m, rank * blk, blk, axis=-2)


def _matvec_partial(band: jnp.ndarray, y: jnp.ndarray, world: int,
                    rank) -> jnp.ndarray:
    """Partial of ``y @ M`` from this worker's row band (M symmetric, so
    the row band is the transposed column block): contracts only the owned
    columns of ``y`` — FLOPs 1/W — and returns a full-width (..., R, d)
    partial that ``exchange.psum_partials`` completes."""
    if world <= 1 or rank is None:
        return jnp.einsum('...ri,...ij->...rj', y, band)
    blk = band.shape[-2]
    d = y.shape[-1]
    pad = world * blk - d
    if pad:
        width = [(0, 0)] * (y.ndim - 1) + [(0, pad)]
        y = jnp.pad(y, width)
    y_blk = jax.lax.dynamic_slice_in_dim(y, rank * blk, blk, axis=-1)
    return jnp.einsum('...ri,...ij->...rj', y_blk, band)


# ---------------------------------------------------------------------------
# Iterative damped-inverse application:  Y (M + γI)^{-power}


@functools.lru_cache(maxsize=64)
def _binomial_coeffs(power: float, iters: int) -> tuple[float, ...]:
    """Series coefficients of (1-x)^{-power} = Σ a_k x^k:
    a_0 = 1, a_{k+1} = a_k (k + power) / (k + 1)."""
    a = [1.0]
    for k in range(iters):
        a.append(a[-1] * (k + power) / (k + 1))
    return tuple(a)


def solve_damped_power(m: jnp.ndarray, y: jnp.ndarray, gamma, power: float,
                       *, cfg: FactorShardConfig, axes, world: int, rank,
                       site: Optional[str] = None) -> jnp.ndarray:
    """Matrix-free ``Y (M + γI)^{-power}`` for PSD ``m`` (..., d, d) and
    ``y`` (..., R, d); ``gamma`` broadcasts over the leading batch dims.

    Every ``Y @ M`` is a per-worker band partial + one psum; the factor is
    never inverted.  'binomial': Gershgorin-rescaled generalized binomial
    series, any power > 0 — convergence rate (1 - γ/c)^k with
    c = max_j Σ_i |M_ij| + γ, so heavier damping converges faster.
    'cg': conjugate gradients on the SPD system, power −1 only (Shampoo's
    −1/4 root silently takes the series).  W=1 runs the identical code
    minus the collective.
    """
    f32 = jnp.float32
    m = m.astype(f32)
    y = y.astype(f32)
    gam = jnp.asarray(gamma, f32)
    band = _band(m, world, rank)
    iters = int(cfg.solve_iters)
    shard_bytes = float(int(np.prod(band.shape)) * 4)
    extra = {'solve_iters': iters, 'factor_shard_bytes': int(shard_bytes)}

    def mv(v):
        part = _matvec_partial(band, v, world, rank)
        return exchange.psum_partials(part, axes, world, site=site,
                                      calls=iters, extra=extra)

    if cfg.solver == 'cg' and power == 1.0:
        # CG on (M + γI) xᵀ = yᵀ, vectorized over the R rows of y (each row
        # an independent RHS; α/β are per-row scalars).
        def dot(u, v):
            return jnp.sum(u * v, axis=-1)

        x = jnp.zeros_like(y)
        r = y
        p = r
        rs = dot(r, r)

        def body(carry, _):
            x, r, p, rs = carry
            ap = mv(p) + gam[..., None, None] * p
            denom = dot(p, ap)
            alpha = jnp.where(denom > 0, rs / jnp.maximum(denom, 1e-30), 0.0)
            x = x + alpha[..., None] * p
            r = r - alpha[..., None] * ap
            rs_new = dot(r, r)
            beta = jnp.where(rs > 0, rs_new / jnp.maximum(rs, 1e-30), 0.0)
            p = r + beta[..., None] * p
            return (x, r, p, rs_new), None

        (x, _, _, _), _ = jax.lax.scan(body, (x, r, p, rs), None,
                                       length=iters)
        return x

    # Generalized binomial series.  Scale c ≥ λmax(M) + γ via the
    # Gershgorin column-abs-sum bound — itself assembled from band partials
    # with one small psum (the bands partition the rows exactly).
    col_part = jnp.sum(jnp.abs(band), axis=-2)
    col = exchange.psum_partials(col_part, axes, world, site=None)
    c = jnp.max(col, axis=-1) + gam                       # (...,) per item
    coeffs = _binomial_coeffs(float(power), iters)

    def t_step(v):
        # V ← V T  with  T = I - (M + γI)/c   (spectral radius < 1)
        return v - (mv(v) + gam[..., None, None] * v) / c[..., None, None]

    def body(carry, a_k):
        v, acc = carry
        v = t_step(v)
        return (v, acc + a_k * v), None

    acc = coeffs[0] * y
    (_, acc), _ = jax.lax.scan(body, (y, acc),
                               jnp.asarray(coeffs[1:], f32))
    return acc * (c ** (-float(power)))[..., None, None]


# ---------------------------------------------------------------------------
# Head state: cached dense-side operators + refresh-time dampings


class HeadState(NamedTuple):
    """Sharded-factor bucket state.  ``buckets`` maps bucket key ->
    {'inv_in', 'inv_out' (cached dense-side operator, or () when that side
    is excluded/sharded), 'gam_in', 'gam_out' (refresh-time dampings — the
    sharded side solves against the LIVE factor EMA but keeps the legacy
    frozen-γ staleness semantics)}.  The two scalars are static-valued
    telemetry surfaced as step metrics."""
    buckets: dict
    solve_iters: jnp.ndarray    # () int32
    shard_bytes: jnp.ndarray    # () float32 — per-step partial-psum bytes


def _plain_gamma(m: jnp.ndarray, gamma) -> jnp.ndarray:
    batch = m.shape[:-2]
    return jnp.broadcast_to(jnp.asarray(gamma, jnp.float32), batch)


def _entry_shapes(policies: tuple[str, str], m_in, m_out, gamma,
                  dense_op, method: str) -> dict:
    p_in, p_out = policies
    if method == 'kfac' and 'exclude' not in policies:
        gam_in, gam_out = pre.kfac_pi_damping(m_in, m_out, gamma)
    else:
        # identity on one side makes the π trace split meaningless (and
        # Shampoo never π-splits): plain γ on whatever sides remain
        gam_in, gam_out = _plain_gamma(m_in, gamma), _plain_gamma(m_out, gamma)
    return {
        'inv_in': dense_op(m_in, gam_in) if p_in == 'dense' else (),
        'inv_out': dense_op(m_out, gam_out) if p_out == 'dense' else (),
        'gam_in': gam_in, 'gam_out': gam_out,
    }


def _damped_inv(m: jnp.ndarray, gamma) -> jnp.ndarray:
    d = m.shape[-1]
    eye = jnp.eye(d, dtype=jnp.float32)
    gam = jnp.asarray(gamma, jnp.float32)[..., None, None]
    return jnp.linalg.inv(m.astype(jnp.float32) + gam * eye)


def _dense_op(method: str):
    if method == 'kfac':
        return _damped_inv
    # _inv_proot_psd adds gamma to the (..., d) eigenvalues — broadcast the
    # (batch,) damping to (batch, 1)
    return lambda m, gam: pre._inv_proot_psd(m.astype(jnp.float32),
                                             gam[..., None], 0.25)


def shard_psum_bytes(plan: bucketing.BucketPlan, policies: dict,
                     cfg: FactorShardConfig) -> float:
    """Static per-step f32 partial-psum bytes of the sharded-factor apply
    (one worker's contribution): ``solve_iters`` gradient-shaped psums per
    sharded side of every head bucket.  Callable on specs — this is the
    figure roofline reports next to the refresh-exchange reduction."""
    total = 0.0
    for b in plan.buckets:
        pol = policies.get(b.key)
        if pol is None:
            continue
        n = len(b.paths) * ownership.lead_size(b)
        d_in, d_out = int(b.shape[-2]), int(b.shape[-1])
        elems = n * d_in * d_out
        for p in pol:
            if p == 'shard':
                total += 4.0 * elems * cfg.solve_iters
    return total


def init_head(stats: dict, policies: dict,
              cfg: FactorShardConfig, plan: bucketing.BucketPlan,
              method: str) -> Optional[HeadState]:
    """Zero-initialized HeadState matching what ``refresh_head`` produces;
    None when no bucket tripped — state layout stays bit-identical to
    legacy (``pipe``-field precedent)."""
    if not policies:
        return None
    buckets = {}
    for k, (p_in, p_out) in policies.items():
        m_in, m_out = stats[k]
        batch = m_in.shape[:-2]
        buckets[k] = {
            'inv_in': (jnp.zeros_like(m_in, dtype=jnp.float32)
                       if p_in == 'dense' else ()),
            'inv_out': (jnp.zeros_like(m_out, dtype=jnp.float32)
                        if p_out == 'dense' else ()),
            'gam_in': jnp.zeros(batch, jnp.float32),
            'gam_out': jnp.zeros(batch, jnp.float32),
        }
    sharded = any(p == 'shard' for pol in policies.values() for p in pol)
    return HeadState(
        buckets=buckets,
        solve_iters=jnp.asarray(cfg.solve_iters if sharded else 0, jnp.int32),
        shard_bytes=jnp.asarray(shard_psum_bytes(plan, policies, cfg),
                                jnp.float32))


def refresh_head(refresh, stats: dict, head: Optional[HeadState],
                 policies: dict, gamma: float, *, cfg: FactorShardConfig,
                 plan: bucketing.BucketPlan, method: str
                 ) -> Optional[HeadState]:
    """Recompute head-bucket operators under the same refresh gate as the
    dense plan: dense-side damped inverses (replicated — the side is small
    by construction, so no exchange) + the frozen dampings.  ``stats``:
    {bucket_key: (m_in, m_out)} live factor EMAs."""
    if not policies:
        return None
    dense_op = _dense_op(method)

    def fresh():
        return {k: _entry_shapes(policies[k], stats[k][0], stats[k][1],
                                 gamma, dense_op, method)
                for k in policies}

    buckets = jax.lax.cond(refresh, fresh, lambda: head.buckets)
    return HeadState(buckets=buckets, solve_iters=head.solve_iters,
                     shard_bytes=head.shard_bytes)


# ---------------------------------------------------------------------------
# Apply: the per-step matrix-free preconditioning of head buckets


def _apply_one(g: jnp.ndarray, entry: dict, policies: tuple[str, str],
               m_in: jnp.ndarray, m_out: jnp.ndarray, *, power: float,
               cfg: FactorShardConfig, axes, world: int, rank,
               site: Optional[str]) -> jnp.ndarray:
    p_in, p_out = policies
    g32 = g.astype(jnp.float32)
    if p_in == 'dense':
        g32 = jnp.einsum('...ij,...jo->...io', entry['inv_in'], g32)
    elif p_in == 'shard':
        gt = jnp.swapaxes(g32, -1, -2)
        gt = solve_damped_power(m_in, gt, entry['gam_in'], power, cfg=cfg,
                                axes=axes, world=world, rank=rank, site=site)
        g32 = jnp.swapaxes(gt, -1, -2)
    # 'exclude': identity — the guard costs nothing
    if p_out == 'dense':
        g32 = jnp.einsum('...io,...oj->...ij', g32, entry['inv_out'])
    elif p_out == 'shard':
        g32 = solve_damped_power(m_out, g32, entry['gam_out'], power,
                                 cfg=cfg, axes=axes, world=world, rank=rank,
                                 site=site)
    return g32.astype(g.dtype)


def apply_tree(flat: dict, plan: bucketing.BucketPlan, policies: dict,
               head: HeadState, factors: dict, *, power: float,
               cfg: FactorShardConfig, site: str) -> dict:
    """Precondition the head buckets of ``flat`` ({path: grad}) in place of
    the dense cached-operator path.  ``factors``: {bucket_key: (m_in,
    m_out)} live EMAs (bucket-stacked); dense buckets pass through
    untouched.  One vectorized apply per stacked bucket, mirroring
    ``precondition_tree``'s engine contract."""
    if not policies:
        return flat
    from repro.sharding.constraints import data_axes_in_scope
    axes = data_axes_in_scope()
    world, rank = ownership.world_and_rank(axes)
    out = dict(flat)
    for b in plan.buckets:
        if b.key not in policies:
            continue
        entry = head.buckets[b.key]
        m_in, m_out = factors[b.key]
        kw = dict(power=power, cfg=cfg, axes=axes, world=world, rank=rank,
                  site=site)
        if b.stacked:
            g = jnp.stack([flat[p] for p in b.paths])
            res = _apply_one(g, entry, policies[b.key], m_in, m_out, **kw)
            for i, p in enumerate(b.paths):
                out[p] = res[i]
        else:
            for i, p in enumerate(b.paths):
                e_i = jax.tree_util.tree_map(lambda x, i=i: x[i], entry)
                out[p] = _apply_one(flat[p], e_i, policies[b.key],
                                    m_in[i], m_out[i], **kw)
    return out


# ---------------------------------------------------------------------------
# Step metrics (repro.obs contract: declared fields, walked from opt state)


METRIC_FIELDS = {
    'factor_solve_iters': ('int', 'iterations of one sharded-factor solve'),
    'factor_shard_bytes': ('num', 'per-step sharded-factor partial-psum B'),
}


def head_states(opt_state):
    """Every HeadState in an optimizer state tree (chains nest states in
    tuples/dicts — mirror ``schedule.runtime.sched_states``)."""
    found = []

    def walk(x):
        if isinstance(x, HeadState):
            found.append(x)
        elif isinstance(x, dict):
            for v in x.values():
                walk(v)
        elif isinstance(x, (list, tuple)):
            for v in x:
                walk(v)

    walk(opt_state)
    return found


def step_metrics(opt_state) -> dict:
    """{declared field: scalar} for the step event — empty when no factor
    is sharded (fields are optional; no schema bump)."""
    out = {}
    for hs in head_states(opt_state):
        out['factor_solve_iters'] = hs.solve_iters
        out['factor_shard_bytes'] = hs.shard_bytes
    return out
