"""Sherman–Morrison rank-one preconditioning math (paper Eq. 13/21/23).

All weights use the (..., d_in, d_out) layout (einsum '...i,...io->...o');
leading dims are layer stacks / experts and every formula broadcasts over
them, which is what lets a whole ``lax.scan``-stacked model be preconditioned
in one fused XLA region instead of a per-layer Python loop.

Kernel routing: ``impl=`` hands the two hot operations (bilinear form +
rank-1 update) to the dispatch layer (``repro.kernels.dispatch``), which
picks compiled Pallas / interpret Pallas / the pure-XLA ``ref.py`` path per
(op, backend, shape, dtype).  ``use_pallas=True`` is the historical alias
for ``impl='pallas'``.  ``impl=None`` keeps the inline broadcast-jnp path
below — mathematically identical (the kernels are asserted against these
functions in tests).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp


def _f32(x):
    # promote low-precision grads to f32 for the math; keep f64 under x64
    return x.astype(jnp.promote_types(x.dtype, jnp.float32))


# ---------------------------------------------------------------------------
# Eva (Eq. 13): P = (G - (b̄ᵀGā)/(γ + ‖ā‖²‖b̄‖²) · ā b̄ᵀ) / γ
# (paper layout ΔW ∝ b̄ āᵀ is for (d_out,d_in) weights; ours is transposed)


def _kernel_impl(use_pallas: bool, impl: Optional[str]) -> Optional[str]:
    """Back-compat shim: ``use_pallas=True`` is ``impl='pallas'``."""
    return impl or ('pallas' if use_pallas else None)


def eva_precondition(g: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                     gamma: float, use_pallas: bool = False,
                     impl: Optional[str] = None) -> jnp.ndarray:
    """g: (..., d_in, d_out); a: (..., d_in); b: (..., d_out)."""
    impl = _kernel_impl(use_pallas, impl)
    if impl:
        from repro.kernels import ops as kops
        return kops.eva_precondition(g, a, b, gamma, impl=impl)
    g32, a32, b32 = _f32(g), _f32(a), _f32(b)
    dot = jnp.einsum('...io,...i,...o->...', g32, a32, b32)
    denom = gamma + jnp.sum(a32 * a32, -1) * jnp.sum(b32 * b32, -1)
    coeff = dot / denom
    p = (g32 - coeff[..., None, None] * (a32[..., :, None] * b32[..., None, :])) / gamma
    return p.astype(g.dtype)


# ---------------------------------------------------------------------------
# Eva-f (Eq. 21): P = (G - ā (āᵀ G) / (γ + ‖ā‖²)) / γ


def eva_f_precondition(g: jnp.ndarray, a: jnp.ndarray, gamma: float,
                       use_pallas: bool = False,
                       impl: Optional[str] = None) -> jnp.ndarray:
    """g: (..., d_in, d_out); a: (..., d_in)."""
    impl = _kernel_impl(use_pallas, impl)
    if impl:
        from repro.kernels import ops as kops
        return kops.eva_f_precondition(g, a, gamma, impl=impl)
    g32, a32 = _f32(g), _f32(a)
    u = jnp.einsum('...io,...i->...o', g32, a32)          # āᵀG  (..., d_out)
    denom = gamma + jnp.sum(a32 * a32, -1)
    p = (g32 - (a32[..., :, None] * u[..., None, :]) / denom[..., None, None]) / gamma
    return p.astype(g.dtype)


# ---------------------------------------------------------------------------
# Eva-s (Eq. 23, k=2): KVs are the gradient's own row/col means


def grad_kvs(g: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """v_in = mean over d_out of G; v_out = mean over d_in of G."""
    g32 = _f32(g)
    return jnp.mean(g32, axis=-1), jnp.mean(g32, axis=-2)


def eva_s_precondition(g: jnp.ndarray, v_in: jnp.ndarray, v_out: jnp.ndarray,
                       gamma: float, use_pallas: bool = False,
                       impl: Optional[str] = None) -> jnp.ndarray:
    """Same rank-one form as Eva with (v_in, v_out) in place of (ā, b̄)."""
    impl = _kernel_impl(use_pallas, impl)
    if impl:
        from repro.kernels import ops as kops
        return kops.eva_precondition(g, v_in, v_out, gamma, impl=impl)
    g32, vi, vo = _f32(g), _f32(v_in), _f32(v_out)
    dot = jnp.einsum('...io,...i,...o->...', g32, vi, vo)
    denom = gamma + jnp.sum(vi * vi, -1) * jnp.sum(vo * vo, -1)
    coeff = dot / denom
    p = (g32 - coeff[..., None, None] * (vi[..., :, None] * vo[..., None, :])) / gamma
    return p.astype(g.dtype)


# ---------------------------------------------------------------------------
# Explicit-inverse baselines (K-FAC Eq. 5, FOOF Eq. 6, Shampoo Eq. 8)


def _damped_solve(m: jnp.ndarray, rhs: jnp.ndarray, gamma) -> jnp.ndarray:
    """(M + γI)^{-1} rhs for PSD M (..., d, d); batched over leading dims."""
    d = m.shape[-1]
    eye = jnp.eye(d, dtype=m.dtype)
    gam = jnp.asarray(gamma, m.dtype)[..., None, None]   # scalar -> (1,1)
    return jnp.linalg.solve(m + gam * eye, rhs)


def kfac_pi_damping(a_outer: jnp.ndarray, b_outer: jnp.ndarray,
                    gamma: float) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Martens-Grosse π-scaled split damping: γ_R = π√γ, γ_Q = √γ/π."""
    d_in = a_outer.shape[-1]
    d_out = b_outer.shape[-1]
    tr_a = jnp.trace(a_outer, axis1=-2, axis2=-1) / d_in
    tr_b = jnp.trace(b_outer, axis1=-2, axis2=-1) / d_out
    pi = jnp.sqrt(jnp.maximum(tr_a, 1e-12) / jnp.maximum(tr_b, 1e-12))
    root = jnp.sqrt(jnp.asarray(gamma, jnp.float32))
    return pi * root, root / pi  # (γ_R for A-side, γ_Q for B-side)


def kfac_precondition(g: jnp.ndarray, a_outer: jnp.ndarray, b_outer: jnp.ndarray,
                      gamma: float) -> jnp.ndarray:
    """(R+γ_R I)^{-1} G (Q+γ_Q I)^{-1} in our (d_in, d_out) layout."""
    g32 = _f32(g)
    gamma_r, gamma_q = kfac_pi_damping(a_outer, b_outer, gamma)
    left = _damped_solve(_f32(a_outer), g32, gamma_r)
    # right-side solve: X (Q+γI)^{-1}  ==  solve((Q+γI)ᵀ, Xᵀ)ᵀ ; Q symmetric.
    right = _damped_solve(_f32(b_outer), jnp.swapaxes(left, -1, -2), gamma_q)
    return jnp.swapaxes(right, -1, -2).astype(g.dtype)


def foof_precondition(g: jnp.ndarray, a_outer: jnp.ndarray, gamma: float) -> jnp.ndarray:
    """(R + γI)^{-1} G — FOOF preconditions the input side only."""
    return _damped_solve(_f32(a_outer), _f32(g), gamma).astype(g.dtype)


def _inv_proot_psd(m: jnp.ndarray, gamma: float, power: float) -> jnp.ndarray:
    """(M + γI)^{-power} for PSD M via eigh; batched."""
    w, v = jnp.linalg.eigh(_f32(m))
    w = jnp.maximum(w, 0.0) + gamma
    return jnp.einsum('...ij,...j,...kj->...ik', v, w ** (-power), v)


def shampoo_precondition(g: jnp.ndarray, m_in: jnp.ndarray, m_out: jnp.ndarray,
                         gamma: float) -> jnp.ndarray:
    """G ×_in (M_in+γI)^{-1/4} ×_out (M_out+γI)^{-1/4} (k=2 modes)."""
    g32 = _f32(g)
    p_in = _inv_proot_psd(m_in, gamma, 0.25)
    p_out = _inv_proot_psd(m_out, gamma, 0.25)
    out = jnp.einsum('...ij,...jo->...io', p_in, g32)
    out = jnp.einsum('...io,...oj->...ij', out, p_out)
    return out.astype(g.dtype)


# ---------------------------------------------------------------------------
# Bucketed tree preconditioning — the vectorized engine entry point


def precondition_tree(updates: dict, aux: dict, method: str, gamma: float, *,
                      plan=None, use_pallas: bool = False,
                      impl: Optional[str] = None) -> dict:
    """Precondition a flat ``{path: grad}`` tree with ONE vectorized call
    per parameter bucket (paper §3-§4: the formulas broadcast, so same-shape
    layers batch into a single launch instead of a per-path Python loop).

    Args:
      updates: flat ``{path: (..., d_in, d_out)}`` gradient dict (paths
        absent from ``aux``/``plan`` pass through untouched).
      aux: per-path ``kv.LayerStats`` (``{path: LayerStats}``) **or** the
        already-bucketed form (``{bucket_key: LayerStats}`` with stacked
        fields, as stored in optimizer state — detected via ``plan``).
        Field conventions per method:
          eva      — a_mean=ā, b_mean=b̄            (Eq. 13)
          eva_f    — a_mean=ā                       (Eq. 21)
          eva_s    — a_mean=v_in, b_mean=v_out      (Eq. 23)
          foof     — a_outer=AAᵀ  [or a_outer=(AAᵀ+γI)^{-1} for foof_cached]
          kfac     — a_outer, b_outer  [kfac_cached: the damped inverses]
          shampoo  — a_outer=M_in, b_outer=M_out  [shampoo_cached: the
                     cached inverse 4th roots]
      method: one of eva | eva_f | eva_s | foof | kfac | shampoo, or the
        ``*_cached`` variant applying precomputed operators.
      plan: ``bucketing.BucketPlan`` built at ``init_opt_state`` time;
        derived (memoized) from ``aux``'s paths when omitted.
      use_pallas: route the rank-one methods through the grid-folded Pallas
        kernels (one launch per bucket, ``kernels/ops.py``) — alias for
        ``impl='pallas'``.
      impl: kernel dispatch request for the rank-one methods
        (``kernels/dispatch.py``: 'auto' | 'pallas' | 'pallas_interpret' |
        'xla'); ``None`` keeps the inline broadcast-jnp formulas above.

    Bucket layout & version support: buckets group paths by (shape, dtype)
    with a new stacking axis 0 (``bucketing.build_plan``); scan-stacked
    leaves keep their leading layer/expert dims inside the bucket shape.
    Small buckets (``Bucket.stacked == False``, below the plan's
    min-bucket-size) skip the stack/unstack copies entirely and run the
    same formulas per path — on CPU the gather/scatter for an N<=2 bucket
    costs more than the single launch it saves.  For the rank-one methods
    and the ``*_cached`` operator application (everything the optimizers
    run) outputs are bit-identical to the per-path loop over the formulas
    above at ANY threshold: broadcast batching is used exactly where XLA
    guarantees per-item reduction order.  The direct solve/eigh methods
    (foof/kfac/shampoo) use one fused ``lax.map`` per stacked bucket —
    bit-identical to per-item calls of the same form, but the stacked
    (compiled scan body) and unstacked (eager) paths may differ in the
    last ulp, so across *different* thresholds they only agree to float
    tolerance (see tests/test_bucketing.py).
    """
    from repro.core import bucketing

    if plan is None:
        sel = {p: updates[p] for p in aux if p in updates}
        if aux and not sel:
            # bucket keys ('float32_16x32') never match gradient paths; a
            # silent empty plan would return the gradients unpreconditioned
            raise ValueError(
                'precondition_tree: no aux key matches an update path — '
                'bucket-keyed aux requires an explicit plan=')
        plan = bucketing.build_plan(sel)
    aux_is_bucketed = bucketing.is_bucketed(plan, aux)

    def one_bucket(bucket, g, st, stacked):
        """g/st carry a leading stack axis when ``stacked``; the rank-one
        and cached-operator formulas broadcast over it, the LAPACK methods
        fuse it with one ``lax.map`` (or apply directly per item)."""
        if method == 'eva':
            return eva_precondition(g, st.a_mean, st.b_mean, gamma,
                                    use_pallas=use_pallas, impl=impl)
        if method == 'eva_f':
            return eva_f_precondition(g, st.a_mean, gamma,
                                      use_pallas=use_pallas, impl=impl)
        if method == 'eva_s':
            return eva_s_precondition(g, st.a_mean, st.b_mean, gamma,
                                      use_pallas=use_pallas, impl=impl)
        if method == 'foof':
            if not stacked:
                return foof_precondition(g, st.a_outer, gamma)
            return jax.lax.map(
                lambda t: foof_precondition(t[0], t[1], gamma),
                (g, st.a_outer))
        if method == 'kfac':
            if not stacked:
                return kfac_precondition(g, st.a_outer, st.b_outer, gamma)
            return jax.lax.map(
                lambda t: kfac_precondition(t[0], t[1], t[2], gamma),
                (g, st.a_outer, st.b_outer))
        if method == 'shampoo':
            if not stacked:
                return shampoo_precondition(g, st.a_outer, st.b_outer, gamma)
            return jax.lax.map(
                lambda t: shampoo_precondition(t[0], t[1], t[2], gamma),
                (g, st.a_outer, st.b_outer))
        if method == 'foof_cached':
            return apply_left(g, st.a_outer)
        if method in ('kfac_cached', 'shampoo_cached'):
            return apply_two_sided(g, st.a_outer, st.b_outer)
        raise ValueError(f'unknown method {method!r}')

    with jax.named_scope('precondition'):
        out = dict(updates)
        big = [b for b in plan.buckets if b.stacked]
        if big:
            sub = bucketing.BucketPlan(buckets=tuple(big))
            aux_b = {b.key: aux[b.key] for b in big} if aux_is_bucketed \
                else bucketing.gather_tree(sub, aux)
            g_b = bucketing.gather(sub, {p: updates[p] for p in sub.paths})
            out_b = {b.key: one_bucket(b, g_b[b.key], aux_b[b.key], True)
                     for b in big}
            out.update(bucketing.scatter(sub, out_b))
        for b in plan.buckets:
            if b.stacked:
                continue
            for i, p in enumerate(b.paths):
                st = jax.tree_util.tree_map(lambda x, i=i: x[i], aux[b.key]) \
                    if aux_is_bucketed else aux[p]
                out[p] = one_bucket(b, updates[p], st, False)
        return out


def precondition_tree_fused(updates: dict, aux: dict, method: str,
                            gamma: float, *, plan=None, trace=None,
                            momentum: float = 0.0,
                            fold_momentum: bool = False,
                            impl: Optional[str] = None):
    """Fused precondition → update-epilogue over a flat gradient tree.

    One ``eva_fused``/``eva_f_fused`` dispatch per bucket instead of the
    bilinear + rank1_update pair plus separate momentum/inner-product tree
    passes (``kernels/fused.py``).  Rank-one methods only ('eva' | 'eva_f' |
    'eva_s'); paths outside the plan pass through with the same epilogue
    applied in jnp.

    Args:
      trace: flat ``{path: f32 momentum buffer}`` matching ``updates``
        (missing paths get zeros); only read when ``fold_momentum``.
      momentum: heavy-ball μ folded into the output when ``fold_momentum``.
      fold_momentum: emit ``out = μ·trace + P`` (the kl_clip_trace
        accumulate step) instead of the bare preconditioned ``P``.

    Returns ``(out, partials)``: ``out`` — flat ``{path: f32 array}``;
    ``partials`` — flat ``{path: (3,) f32}`` per-leaf epilogue sums
    ``[⟨out,g⟩, ⟨out,out⟩, ⟨g,g⟩]`` (``g`` = the *incoming* updates, i.e.
    the preconditioner input — equal to the raw gradients only when no
    transform ran before the preconditioner; callers gate the KL fold on
    that, see ``core/eva.py``).
    """
    from repro.core import bucketing
    from repro.kernels import ops as kops

    if method not in ('eva', 'eva_f', 'eva_s'):
        raise ValueError(f'precondition_tree_fused: rank-one methods only, '
                         f'got {method!r}')
    if plan is None:
        sel = {p: updates[p] for p in aux if p in updates}
        if aux and not sel:
            raise ValueError(
                'precondition_tree_fused: no aux key matches an update path '
                '— bucket-keyed aux requires an explicit plan=')
        plan = bucketing.build_plan(sel)
    aux_is_bucketed = bucketing.is_bucketed(plan, aux)
    trace = trace or {}
    mu = momentum if fold_momentum else 0.0

    def m_for(p):
        m = trace.get(p)
        return jnp.zeros(updates[p].shape, jnp.float32) if m is None \
            else m.astype(jnp.float32)

    def run(g, st, m):
        if method == 'eva_f':
            return kops.eva_f_fused(g, st.a_mean, gamma, m, mu,
                                    fold_momentum=fold_momentum, impl=impl)
        return kops.eva_fused(g, st.a_mean, st.b_mean, gamma, m, mu,
                              fold_momentum=fold_momentum, impl=impl)

    with jax.named_scope('precondition'):
        out, partials = {}, {}
        big = [b for b in plan.buckets if b.stacked]
        if big:
            sub = bucketing.BucketPlan(buckets=tuple(big))
            aux_b = {b.key: aux[b.key] for b in big} if aux_is_bucketed \
                else bucketing.gather_tree(sub, aux)
            g_b = bucketing.gather(sub, {p: updates[p] for p in sub.paths})
            m_b = bucketing.gather(sub, {p: m_for(p) for p in sub.paths})
            for b in big:
                o, ax = run(g_b[b.key], aux_b[b.key], m_b[b.key])
                for i, p in enumerate(b.paths):
                    out[p] = o[i]
                    # scan-stacked leaves carry (S, 3) partials; the epilogue
                    # scalars are per *tree leaf*, so sum the item dims away
                    partials[p] = ax[i].reshape(-1, 3).sum(axis=0)
        for b in plan.buckets:
            if b.stacked:
                continue
            for i, p in enumerate(b.paths):
                st = jax.tree_util.tree_map(lambda x, i=i: x[i], aux[b.key]) \
                    if aux_is_bucketed else aux[p]
                o, ax = run(updates[p], st, m_for(p))
                out[p] = o
                partials[p] = ax.reshape(-1, 3).sum(axis=0)
        pre_paths = set(plan.paths)
        for p, g in updates.items():
            if p in pre_paths:
                continue
            g32 = g.astype(jnp.float32)
            o = mu * m_for(p) + g32 if fold_momentum else g32
            out[p] = o
            partials[p] = jnp.stack([jnp.sum(o * g32), jnp.sum(o * o),
                                     jnp.sum(g32 * g32)])
        return out, partials


def apply_left(g: jnp.ndarray, op_in: jnp.ndarray) -> jnp.ndarray:
    """op_in @ G — batched application of a cached input-side operator."""
    out = jnp.einsum('...ij,...jo->...io', op_in, _f32(g))
    return out.astype(g.dtype)


def apply_two_sided(g: jnp.ndarray, op_in: jnp.ndarray,
                    op_out: jnp.ndarray) -> jnp.ndarray:
    """op_in @ G @ op_out — batched two-sided cached-operator application."""
    out = jnp.einsum('...ij,...jo->...io', op_in, _f32(g))
    out = jnp.einsum('...io,...oj->...ij', out, op_out)
    return out.astype(g.dtype)


def map_bucket(fn, *args):
    """One fused ``lax.map`` over a bucket's stack axis — used where the
    batched LAPACK path (solve/inv/eigh) would change per-item numerics."""
    return jax.lax.map(lambda t: fn(*t), tuple(args))


# ---------------------------------------------------------------------------
# Reference dense forms (tests only): build the full (C + γI)^{-1} g


def eva_explicit(g: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                 gamma: float) -> jnp.ndarray:
    """Literal (C+γI)^{-1} vec(G) with C = (b̄b̄ᵀ)⊗(āāᵀ) — O(d⁴), tests only.

    vec() follows the paper: row-major flatten of the (d_out, d_in) weight;
    with our (d_in, d_out) layout that is ``g.T.reshape(-1)`` and
    ``C = kron(b̄b̄ᵀ, āāᵀ)``.
    """
    d_in, d_out = g.shape[-2], g.shape[-1]
    vec = g.astype(jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)
    vec = jnp.swapaxes(vec, -1, -2).reshape(d_out * d_in)
    c = jnp.kron(jnp.outer(b, b), jnp.outer(a, a))
    p = jnp.linalg.solve(c + gamma * jnp.eye(d_out * d_in, dtype=c.dtype), vec)
    return jnp.swapaxes(p.reshape(d_out, d_in), -1, -2).astype(g.dtype)
