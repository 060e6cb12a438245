"""Bucketed ``precondition_tree`` must be BIT-IDENTICAL (atol=0) to the
per-layer loop over the ``precondition`` formulas, for every method, on
mixed-shape trees, scan-stacked leading dims, and the fused route.

This is the contract that lets the optimizers batch same-shape layers into
one launch without changing a single ulp of the training trajectory.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bucketing
from repro.core import kv as kvlib
from repro.core import precondition as pre
from repro.kernels import dispatch

GAMMA = 0.03

# mixed shapes: two 3-path buckets, one singleton, one scan-stacked bucket
SHAPES = {
    'blk0/mlp/w': (16, 8),
    'blk1/mlp/w': (16, 8),
    'blk2/mlp/w': (16, 8),
    'head/w': (16, 4),
    'stack/attn/w': (3, 12, 8),   # lax.scan-stacked layers
    'stack/mlp/w': (3, 12, 8),
    'odd/w': (7, 5),              # non-128-aligned (kernel padding path)
}


def _psd(key, *shape):
    m = jax.random.normal(key, shape)
    return m @ jnp.swapaxes(m, -1, -2) + 0.1 * jnp.eye(shape[-1])


def _make_tree(seed=0):
    key = jax.random.PRNGKey(seed)
    grads, aux = {}, {}
    for i, (path, shape) in enumerate(SHAPES.items()):
        k = jax.random.fold_in(key, i)
        ks = jax.random.split(k, 5)
        lead, d_in, d_out = shape[:-2], shape[-2], shape[-1]
        grads[path] = jax.random.normal(ks[0], shape)
        aux[path] = kvlib.LayerStats(
            a_mean=jax.random.normal(ks[1], lead + (d_in,)),
            b_mean=jax.random.normal(ks[2], lead + (d_out,)),
            a_outer=_psd(ks[3], *lead, d_in, d_in),
            b_outer=_psd(ks[4], *lead, d_out, d_out))
    return grads, aux


PER_LAYER = {
    'eva': lambda g, st: pre.eva_precondition(
        g, st.a_mean, st.b_mean, GAMMA),
    'eva_f': lambda g, st: pre.eva_f_precondition(g, st.a_mean, GAMMA),
    'eva_s': lambda g, st: pre.eva_s_precondition(
        g, st.a_mean, st.b_mean, GAMMA),
    'foof': lambda g, st: pre.foof_precondition(g, st.a_outer, GAMMA),
    'kfac': lambda g, st: pre.kfac_precondition(
        g, st.a_outer, st.b_outer, GAMMA),
    'shampoo': lambda g, st: pre.shampoo_precondition(
        g, st.a_outer, st.b_outer, GAMMA),
}

ALL_METHODS = sorted(PER_LAYER)


def _assert_bit_identical(out, ref):
    for path in ref:
        a, b = np.asarray(out[path]), np.asarray(ref[path])
        assert a.dtype == b.dtype, path
        np.testing.assert_array_equal(a, b, err_msg=path)  # atol=0


@pytest.mark.parametrize('method', ALL_METHODS)
def test_bucketed_matches_per_layer_loop(method):
    grads, aux = _make_tree()
    ref = {p: PER_LAYER[method](grads[p], aux[p]) for p in grads}
    out = pre.precondition_tree(grads, aux, method, GAMMA)
    _assert_bit_identical(out, ref)


@pytest.mark.parametrize('method', ['eva', 'eva_f', 'eva_s'])
@pytest.mark.parametrize('min_size', [1, 2, 99])
def test_bucketed_fused_matches_per_layer_loop(method, min_size):
    """The fused route (``precondition_tree_fused``) gives per path what one
    ``dispatch.eva_fused``/``eva_f_fused`` call per path gives, whichever
    buckets are stacked.  Here that is the jnp twin, whose CPU reductions
    XLA may order differently for a stack than for one item (1 f32 ulp);
    the compiled kernels are bit-identical stacked and per item
    (tests/test_kernels.py)."""
    grads, aux = _make_tree(seed=1)
    fold = method == 'eva'
    trace = {p: jax.random.normal(jax.random.PRNGKey(i), g.shape)
             for i, (p, g) in enumerate(sorted(grads.items()))}
    plan = bucketing.build_plan(grads, min_bucket_size=min_size)
    out, partials = pre.precondition_tree_fused(
        grads, aux, method, GAMMA, plan=plan, trace=trace, momentum=0.9,
        fold_momentum=fold)
    mu = 0.9 if fold else 0.0
    for p, g in grads.items():
        st = aux[p]
        if method == 'eva_f':
            o, ax = dispatch.eva_f_fused(g, st.a_mean, GAMMA, trace[p], mu,
                                         fold_momentum=fold)
        else:
            o, ax = dispatch.eva_fused(g, st.a_mean, st.b_mean, GAMMA,
                                       trace[p], mu, fold_momentum=fold)
        np.testing.assert_allclose(np.asarray(out[p]), np.asarray(o),
                                   rtol=1e-6, atol=1e-5, err_msg=p)
        np.testing.assert_allclose(
            np.asarray(partials[p]),
            np.asarray(ax.reshape(-1, 3).sum(axis=0)), rtol=1e-6,
            err_msg=p)


@pytest.mark.parametrize('method', ['eva', 'kfac'])
def test_cached_operator_path(method):
    """The *_cached application (what the interval-cached optimizers run)
    equals the per-path einsum loop."""
    grads, aux = _make_tree(seed=2)
    ops = {p: kvlib.LayerStats(a_outer=aux[p].a_outer, b_outer=aux[p].b_outer)
           for p in grads}
    out = pre.precondition_tree(grads, ops, 'kfac_cached', GAMMA)
    ref = {p: pre.apply_two_sided(grads[p], aux[p].a_outer, aux[p].b_outer)
           for p in grads}
    _assert_bit_identical(out, ref)


def test_non_preconditioned_paths_pass_through():
    grads, aux = _make_tree()
    grads['bias/b'] = jnp.arange(4.0)
    out = pre.precondition_tree(grads, aux, 'eva', GAMMA)
    np.testing.assert_array_equal(np.asarray(out['bias/b']), np.arange(4.0))


def test_dtype_segregation():
    """Same shape, different dtype -> different buckets; dtypes preserved."""
    key = jax.random.PRNGKey(3)
    grads = {
        'a/w': jax.random.normal(key, (8, 4), jnp.float32),
        'b/w': jax.random.normal(key, (8, 4)).astype(jnp.bfloat16),
    }
    aux = {p: kvlib.LayerStats(a_mean=jnp.ones((8,)), b_mean=jnp.ones((4,)))
           for p in grads}
    plan = bucketing.build_plan(grads)
    assert len(plan.buckets) == 2
    out = pre.precondition_tree(grads, aux, 'eva', GAMMA, plan=plan)
    assert out['a/w'].dtype == jnp.float32
    assert out['b/w'].dtype == jnp.bfloat16
    ref = {p: PER_LAYER['eva'](grads[p], aux[p]) for p in grads}
    _assert_bit_identical(out, ref)


def test_plan_determinism_and_layout():
    grads, _ = _make_tree()
    plan = bucketing.build_plan(grads)
    plan2 = bucketing.build_plan(dict(reversed(list(grads.items()))))
    assert plan == plan2  # insertion order must not matter
    assert plan is plan2  # memoized on the shape signature
    # the three (16, 8) paths share one bucket, sorted
    by_key = {b.key: b for b in plan.buckets}
    b = by_key[bucketing.bucket_key((16, 8), jnp.float32)]
    assert b.paths == ('blk0/mlp/w', 'blk1/mlp/w', 'blk2/mlp/w')


def test_gather_scatter_roundtrip():
    grads, _ = _make_tree()
    plan = bucketing.build_plan(grads)
    back = bucketing.scatter(plan, bucketing.gather(plan, grads))
    _assert_bit_identical(back, grads)


def test_bucketed_aux_equals_flat_aux():
    """State-resident (pre-gathered) aux must give the same result as flat
    per-path aux — this is the optimizer fast path."""
    grads, aux = _make_tree(seed=4)
    plan = bucketing.build_plan(grads)
    aux_b = bucketing.gather_tree(plan, aux)
    out_flat = pre.precondition_tree(grads, aux, 'eva', GAMMA, plan=plan)
    out_bucketed = pre.precondition_tree(grads, aux_b, 'eva', GAMMA, plan=plan)
    _assert_bit_identical(out_bucketed, out_flat)


def test_min_bucket_size_marks_small_buckets_unstacked():
    """Default threshold: N<=2 buckets skip the stack/unstack copies (the
    table5 CPU numbers — ROADMAP 'bucket gather cost'); grouping, keys and
    state layout are unchanged."""
    grads, _ = _make_tree()
    plan = bucketing.build_plan(grads)
    by_key = {b.key: b for b in plan.buckets}
    assert by_key[bucketing.bucket_key((16, 8), jnp.float32)].stacked  # N=3
    assert not by_key[bucketing.bucket_key((16, 4), jnp.float32)].stacked  # N=1
    assert not by_key[bucketing.bucket_key((3, 12, 8), jnp.float32)].stacked  # N=2
    # explicit threshold overrides
    all_stacked = bucketing.build_plan(grads, min_bucket_size=1)
    assert all(b.stacked for b in all_stacked.buckets)
    none_stacked = bucketing.build_plan(grads, min_bucket_size=99)
    assert not any(b.stacked for b in none_stacked.buckets)
    # same grouping either way
    assert [b.paths for b in plan.buckets] == \
        [b.paths for b in all_stacked.buckets]


@pytest.mark.parametrize('method', ['eva', 'eva_f', 'eva_s', 'eva_cached',
                                    'kfac_cached'])
@pytest.mark.parametrize('min_size', [1, 2, 99])
def test_min_bucket_size_output_bit_identical(method, min_size):
    """For every path the OPTIMIZERS actually run (rank-one broadcast +
    cached-operator application), the threshold is invisible: any
    min_bucket_size gives bit-identical outputs to the per-layer loop."""
    grads, aux = _make_tree(seed=7)
    plan = bucketing.build_plan(grads, min_bucket_size=min_size)
    if method.endswith('_cached'):
        ops = {p: kvlib.LayerStats(a_outer=aux[p].a_outer,
                                   b_outer=aux[p].b_outer) for p in grads}
        out = pre.precondition_tree(grads, ops, 'kfac_cached', GAMMA,
                                    plan=plan)
        ref = {p: pre.apply_two_sided(grads[p], aux[p].a_outer,
                                      aux[p].b_outer) for p in grads}
    else:
        out = pre.precondition_tree(grads, aux, method, GAMMA, plan=plan)
        ref = {p: PER_LAYER[method](grads[p], aux[p]) for p in grads}
    _assert_bit_identical(out, ref)


@pytest.mark.parametrize('method', ['foof', 'kfac', 'shampoo'])
@pytest.mark.parametrize('min_size', [1, 99])
def test_min_bucket_size_lapack_methods_allclose(min_size, method):
    """The direct solve/eigh methods flip between a compiled ``lax.map``
    body (stacked) and eager per-path calls (unstacked), which — like
    jit-vs-eager (see test_under_jit) — may differ in the last ulp; they
    must still agree to float tolerance at every threshold.  (The
    optimizers themselves only use the *_cached application, which is
    exact — see test_min_bucket_size_output_bit_identical.)"""
    grads, aux = _make_tree(seed=7)
    ref = {p: PER_LAYER[method](grads[p], aux[p]) for p in grads}
    plan = bucketing.build_plan(grads, min_bucket_size=min_size)
    out = pre.precondition_tree(grads, aux, method, GAMMA, plan=plan)
    for p in ref:
        np.testing.assert_allclose(np.asarray(out[p]), np.asarray(ref[p]),
                                   rtol=1e-5, atol=1e-6, err_msg=p)


def test_min_bucket_size_with_bucketed_state_aux():
    """Optimizer state stays bucket-stacked for ALL buckets; the small-
    bucket path must slice it per item and still match."""
    grads, aux = _make_tree(seed=8)
    plan = bucketing.build_plan(grads, min_bucket_size=99)  # all unstacked
    aux_b = bucketing.gather_tree(plan, aux)  # state layout: always stacked
    out = pre.precondition_tree(grads, aux_b, 'eva', GAMMA, plan=plan)
    ref = {p: PER_LAYER['eva'](grads[p], aux[p]) for p in grads}
    _assert_bit_identical(out, ref)


def test_under_jit():
    """The whole engine must trace cleanly (plans are static metadata)."""
    grads, aux = _make_tree(seed=5)

    @jax.jit
    def run(g, a):
        return pre.precondition_tree(g, a, 'eva', GAMMA)

    out = run(grads, aux)
    eager = pre.precondition_tree(grads, aux, 'eva', GAMMA)
    for p in eager:
        # jit fuses differently than eager -> last-ulp differences only
        np.testing.assert_allclose(np.asarray(out[p]), np.asarray(eager[p]),
                                   rtol=1e-5, atol=1e-6)
