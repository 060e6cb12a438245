"""Curvature refresh runtime (repro.schedule).

Contracts proven here:
  * with ``every_k(1)`` (and with ``every_k(k)`` for the interval methods)
    the scheduled optimizers are BIT-IDENTICAL (atol=0) to the legacy
    per-optimizer behavior — the references below replicate the exact
    pre-runtime update structure (``count % interval`` under ``lax.cond``,
    always-fresh KV snapshots for the eva family);
  * single-host refresh ≡ W-worker ownership-sharded refresh under
    shard_map (subprocess with 4 host devices) to float tolerance — the
    exchange itself is bit-exact (see tests/test_comm_exchange.py), the
    slice-granular compute batches LAPACK differently (last-ulp);
  * policy semantics: every_k counts, warmup_then_k, adaptive drift
    triggering;
  * ownership assignment is deterministic, covers every item, and balances
    weighted cost;
  * the train-level default policy threads through ``Extras.sched``.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bucketing
from repro.core import kv as kvlib
from repro.core import precondition as pre
from repro.core.eva import (_extract, _stats_plan, _zeros_like_spec,
                            eva_preconditioner)
from repro.core.eva_f import eva_f_preconditioner
from repro.core.eva_s import eva_s_preconditioner
from repro.core.foof import foof_preconditioner
from repro.core.kfac import _damped_inv, kfac_preconditioner
from repro.core.shampoo import shampoo_preconditioner
from repro.core.transform import Extras
from repro.schedule import ownership, runtime as schedrt
from repro.schedule.policy import (SchedState, adaptive, every_k, named_policy,
                                   warmup_then_k)
from repro.sharding.constraints import pmean_stats

GAMMA = 0.03

SHAPES = {
    'blk0/w': (8, 4),
    'blk1/w': (8, 4),
    'blk2/w': (8, 4),
    'head/w': (8, 3),          # singleton bucket (broadcast path)
    'stack/w': (2, 6, 4),      # scan-stacked leading dim
}


def _psd(key, *shape):
    m = jax.random.normal(key, shape)
    return m @ jnp.swapaxes(m, -1, -2) + 0.1 * jnp.eye(shape[-1])


def _grads(seed):
    key = jax.random.PRNGKey(seed)
    return {p: jax.random.normal(jax.random.fold_in(key, i), s)
            for i, (p, s) in enumerate(SHAPES.items())}


def _capture_stats(seed):
    """Per-path LayerStats as the forward/backward capture would emit."""
    key = jax.random.PRNGKey(1000 + seed)
    out = {}
    for i, (p, s) in enumerate(SHAPES.items()):
        ks = jax.random.split(jax.random.fold_in(key, i), 4)
        lead, d_in, d_out = s[:-2], s[-2], s[-1]
        out[p] = kvlib.LayerStats(
            a_mean=jax.random.normal(ks[0], lead + (d_in,)),
            b_mean=jax.random.normal(ks[1], lead + (d_out,)),
            a_outer=_psd(ks[2], *lead, d_in, d_in),
            b_outer=_psd(ks[3], *lead, d_out, d_out))
    return out


def _params():
    return kvlib.unflatten_params(_grads(0))


def _assert_trees_equal(a, b, msg=''):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)


# ---------------------------------------------------------------------------
# Legacy references: the exact pre-runtime update structure


def _legacy_kfac_run(steps, interval, kf_decay=0.9):
    """The pre-runtime K-FAC preconditioner: count % interval under cond,
    recompute via one fused lax.map per bucket."""
    fields = ('a_outer', 'b_outer')
    params = _params()
    flat = kvlib.flatten_params(params)
    stats0 = _capture_stats(0)
    plan = _stats_plan(flat, stats0, None)
    zeros = bucketing.gather_tree(plan, _zeros_like_spec(_extract(stats0, fields)))
    run = kvlib.init_running(zeros)
    a_inv = {k: jnp.zeros_like(st.a_outer) for k, st in run.stats.items()}
    b_inv = {k: jnp.zeros_like(st.b_outer) for k, st in run.stats.items()}
    count = jnp.zeros((), jnp.int32)
    outs = []
    for t in range(steps):
        g = _grads(t)
        fresh = pmean_stats(bucketing.gather_tree(
            plan, _extract(_capture_stats(t), fields)))
        stats, run = kvlib.update_running(run, fresh, kf_decay)

        def one(ao, bo):
            gamma_r, gamma_q = pre.kfac_pi_damping(ao, bo, GAMMA)
            return _damped_inv(ao, gamma_r), _damped_inv(bo, gamma_q)

        def recompute(_):
            ai, bi = {}, {}
            for k, st in stats.items():
                ai[k], bi[k] = pre.map_bucket(one, st.a_outer, st.b_outer)
            return ai, bi

        refresh = (count % interval) == 0
        a_inv, b_inv = jax.lax.cond(refresh, recompute,
                                    lambda _: (a_inv, b_inv), operand=None)
        ops = {k: kvlib.LayerStats(a_outer=a_inv[k], b_outer=b_inv[k])
               for k in a_inv}
        outs.append(pre.precondition_tree(g, ops, 'kfac_cached', GAMMA,
                                          plan=plan))
        count = count + 1
    return outs


def _legacy_foof_run(steps, interval, kf_decay=0.9):
    fields = ('a_outer',)
    params = _params()
    flat = kvlib.flatten_params(params)
    stats0 = _capture_stats(0)
    plan = _stats_plan(flat, stats0, None)
    zeros = bucketing.gather_tree(plan, _zeros_like_spec(_extract(stats0, fields)))
    run = kvlib.init_running(zeros)
    a_inv = {k: jnp.zeros_like(st.a_outer) for k, st in run.stats.items()}
    count = jnp.zeros((), jnp.int32)
    outs = []
    for t in range(steps):
        g = _grads(t)
        fresh = pmean_stats(bucketing.gather_tree(
            plan, _extract(_capture_stats(t), fields)))
        stats, run = kvlib.update_running(run, fresh, kf_decay)

        def recompute(_):
            return {k: pre.map_bucket(lambda m: _damped_inv(m, GAMMA),
                                      st.a_outer)
                    for k, st in stats.items()}

        refresh = (count % interval) == 0
        a_inv = jax.lax.cond(refresh, recompute, lambda _: a_inv, operand=None)
        ops = {k: kvlib.LayerStats(a_outer=a_inv[k]) for k in a_inv}
        outs.append(pre.precondition_tree(g, ops, 'foof_cached', GAMMA,
                                          plan=plan))
        count = count + 1
    return outs


def _legacy_shampoo_run(steps, interval, eps_init=1e-6):
    params = _params()
    flat = kvlib.flatten_params(params)
    plan = bucketing.build_plan(flat)
    m_in, m_out = {}, {}
    for b in plan.buckets:
        lead = (len(b.paths),) + b.shape[:-2]
        d_in, d_out = b.shape[-2], b.shape[-1]
        m_in[b.key] = eps_init * jnp.broadcast_to(
            jnp.eye(d_in, dtype=jnp.float32), lead + (d_in, d_in))
        m_out[b.key] = eps_init * jnp.broadcast_to(
            jnp.eye(d_out, dtype=jnp.float32), lead + (d_out, d_out))
    p_in = jax.tree_util.tree_map(jnp.zeros_like, m_in)
    p_out = jax.tree_util.tree_map(jnp.zeros_like, m_out)
    count = jnp.zeros((), jnp.int32)
    outs = []
    for t in range(steps):
        g = _grads(t)
        g_b = bucketing.gather(plan, g)
        for b in plan.buckets:
            gg = g_b[b.key].astype(jnp.float32)
            m_in[b.key] = m_in[b.key] + jnp.einsum('...io,...jo->...ij', gg, gg)
            m_out[b.key] = m_out[b.key] + jnp.einsum('...io,...ij->...oj', gg, gg)

        def recompute(_):
            return ({k: pre.map_bucket(
                        lambda m: pre._inv_proot_psd(m, 1e-4, 0.25), m_in[k])
                     for k in m_in},
                    {k: pre.map_bucket(
                        lambda m: pre._inv_proot_psd(m, 1e-4, 0.25), m_out[k])
                     for k in m_out})

        refresh = (count % interval) == 0
        p_in, p_out = jax.lax.cond(refresh, recompute,
                                   lambda _: (p_in, p_out), operand=None)
        ops = {k: kvlib.LayerStats(a_outer=p_in[k], b_outer=p_out[k])
               for k in p_in}
        outs.append(pre.precondition_tree(g, ops, 'shampoo_cached', 1e-4,
                                          plan=plan))
        count = count + 1
    return outs


def _legacy_eva_family_run(method, steps, kv_decay=0.9):
    """Pre-runtime eva/eva_f: always-fresh bias-corrected KV snapshot."""
    fields = {'eva': ('a_mean', 'b_mean'), 'eva_f': ('a_mean',)}[method]
    params = _params()
    flat = kvlib.flatten_params(params)
    stats0 = _capture_stats(0)
    plan = _stats_plan(flat, stats0, None)
    run = kvlib.init_running(bucketing.gather_tree(
        plan, _zeros_like_spec(_extract(stats0, fields))))
    outs = []
    for t in range(steps):
        g = _grads(t)
        fresh = pmean_stats(bucketing.gather_tree(
            plan, _extract(_capture_stats(t), fields)))
        stats, run = kvlib.update_running(run, fresh, kv_decay)
        outs.append(pre.precondition_tree(g, stats, method, GAMMA, plan=plan))
    return outs


def _legacy_eva_s_run(steps, kv_decay=0.9):
    params = _params()
    flat = kvlib.flatten_params(params)
    plan = bucketing.build_plan(flat)
    zeros = {
        b.key: kvlib.LayerStats(
            a_mean=jnp.zeros((len(b.paths),) + b.shape[:-1], jnp.float32),
            b_mean=jnp.zeros((len(b.paths),) + b.shape[:-2] + b.shape[-1:],
                             jnp.float32))
        for b in plan.buckets}
    run = kvlib.init_running(zeros)
    outs = []
    for t in range(steps):
        g = _grads(t)
        g_b = bucketing.gather(plan, g)
        fresh = {}
        for b in plan.buckets:
            vi, vo = pre.grad_kvs(g_b[b.key])
            fresh[b.key] = kvlib.LayerStats(a_mean=vi, b_mean=vo)
        stats, run = kvlib.update_running(run, fresh, kv_decay)
        outs.append(pre.precondition_tree(g, stats, 'eva_s', GAMMA, plan=plan))
    return outs


# ---------------------------------------------------------------------------
# Scheduled runs


def _scheduled_run(method, steps, sched=None, **kw):
    maker = {
        'eva': lambda: eva_preconditioner(GAMMA, 0.9, **kw),
        'eva_f': lambda: eva_f_preconditioner(GAMMA, 0.9, **kw),
        'eva_s': lambda: eva_s_preconditioner(GAMMA, 0.9, **kw),
        'foof': lambda: foof_preconditioner(GAMMA, 0.9, **kw),
        'kfac': lambda: kfac_preconditioner(GAMMA, 0.9, **kw),
        'shampoo': lambda: shampoo_preconditioner(1e-4, **kw),
    }[method]
    opt = maker()
    params = _params()
    needs_stats = method in ('eva', 'eva_f', 'foof', 'kfac')
    extras0 = Extras(stats=_capture_stats(0) if needs_stats else None,
                     sched=sched)
    state = opt.init(params, extras0)
    outs = []
    for t in range(steps):
        ex = Extras(stats=_capture_stats(t) if needs_stats else None,
                    sched=sched)
        out, state = opt.update(_grads(t), state, extras=ex)
        outs.append(kvlib.flatten_params(out))
    return outs, state


STEPS = 6

LEGACY = {
    'eva': lambda k: _legacy_eva_family_run('eva', STEPS),
    'eva_f': lambda k: _legacy_eva_family_run('eva_f', STEPS),
    'eva_s': lambda k: _legacy_eva_s_run(STEPS),
    'foof': lambda k: _legacy_foof_run(STEPS, k),
    'kfac': lambda k: _legacy_kfac_run(STEPS, k),
    'shampoo': lambda k: _legacy_shampoo_run(STEPS, k),
}

ALL_METHODS = sorted(LEGACY)
INTERVAL_METHODS = ['foof', 'kfac', 'shampoo']


@pytest.mark.parametrize('method', ALL_METHODS)
def test_every_1_bit_identical_to_legacy(method):
    """every_k(1) == the historical always-fresh/interval=1 behavior,
    atol=0, for all six methods."""
    ref = LEGACY[method](1)
    outs, _ = _scheduled_run(method, STEPS, policy=every_k(1))
    for t in range(STEPS):
        _assert_trees_equal(outs[t], ref[t], msg=f'{method} step {t}')


@pytest.mark.parametrize('method', ALL_METHODS)
def test_pipeline_sync_bit_identical_to_legacy(method):
    """An explicit ``RefreshRuntime(pipeline='sync')`` is the staged
    issue/collect composition of every exchange — proven atol=0 against the
    pre-pipeline legacy references, state included (``pipe=None`` adds no
    leaves, so the state trees match the default-runtime run exactly)."""
    ref = LEGACY[method](1)
    sync = schedrt.RefreshRuntime(pipeline='sync')
    outs, state = _scheduled_run(method, STEPS, policy=every_k(1), sched=sync)
    for t in range(STEPS):
        _assert_trees_equal(outs[t], ref[t], msg=f'{method} step {t}')
    _, state_default = _scheduled_run(method, STEPS, policy=every_k(1))
    _assert_trees_equal(state, state_default, msg=f'{method} state')
    assert (jax.tree_util.tree_structure(state)
            == jax.tree_util.tree_structure(state_default))


@pytest.mark.parametrize('method', INTERVAL_METHODS)
def test_every_k_bit_identical_to_legacy_interval(method):
    """every_k(3) == the historical ``count % 3`` branch, atol=0 —
    mid-interval cached-inverse steps included."""
    ref = LEGACY[method](3)
    outs, _ = _scheduled_run(method, STEPS, policy=every_k(3))
    for t in range(STEPS):
        _assert_trees_equal(outs[t], ref[t], msg=f'{method} step {t}')


@pytest.mark.parametrize('method', INTERVAL_METHODS)
def test_interval_kwarg_equals_policy(method):
    """The legacy ``interval=`` kwarg is exactly ``every_k(interval)``."""
    a, sa = _scheduled_run(method, STEPS, interval=3)
    b, sb = _scheduled_run(method, STEPS, policy=every_k(3))
    for t in range(STEPS):
        _assert_trees_equal(a[t], b[t], msg=f'{method} step {t}')
    _assert_trees_equal(sa, sb, msg=f'{method} state')


# ---------------------------------------------------------------------------
# Policy semantics


def _sched_of(state) -> SchedState:
    sts = schedrt.sched_states(state)
    assert len(sts) == 1
    return sts[0]


def test_every_k_refresh_count():
    _, state = _scheduled_run('kfac', STEPS, policy=every_k(3))
    s = _sched_of(state)
    assert int(s.count) == STEPS
    assert int(s.n_refresh) == 2          # steps 0 and 3
    assert int(s.since) == STEPS - 1 - 3  # last refresh at step 3


def test_warmup_then_k():
    _, state = _scheduled_run('kfac', STEPS, policy=warmup_then_k(3, 10))
    s = _sched_of(state)
    # steps 0,1,2 warm up; step 3 fires ((3-3) % 10 == 0); 4,5 do not
    assert int(s.n_refresh) == 4


def test_adaptive_triggers_on_drift():
    """An unreachable threshold refreshes only at the forced step 0; a
    ~zero threshold refreshes every step (the stats stream moves every
    step) and must then equal every_k(1) bit-exactly."""
    _, state = _scheduled_run('kfac', STEPS, policy=adaptive(threshold=1e6))
    s = _sched_of(state)
    assert int(s.n_refresh) == 1          # only the forced step-0 refresh
    eager, state = _scheduled_run('kfac', STEPS,
                                  policy=adaptive(threshold=1e-9))
    s = _sched_of(state)
    assert int(s.n_refresh) == STEPS      # drift always exceeds ~0
    # and an eager adaptive run equals every-step refresh bit-exactly
    ref, _ = _scheduled_run('kfac', STEPS, policy=every_k(1))
    for t in range(STEPS):
        _assert_trees_equal(eager[t], ref[t], msg=f'step {t}')


def test_adaptive_max_interval_bound():
    _, state = _scheduled_run('kfac', STEPS,
                              policy=adaptive(threshold=1e6, max_interval=2))
    s = _sched_of(state)
    assert int(s.n_refresh) == 3          # steps 0, 2, 4 (since >= 1 forces)


def test_named_policy_registry():
    assert named_policy('every_k', k=4).name == 'every_k(4)'
    assert named_policy('adaptive', threshold=0.1).wants_snapshot
    with pytest.raises(KeyError):
        named_policy('nope')


def test_extras_sched_default_policy():
    """A train-level default policy (Extras.sched) applies to optimizers
    built without an explicit policy/interval."""
    rt = schedrt.RefreshRuntime(policy=every_k(3))
    opt = kfac_preconditioner(GAMMA, 0.9)
    params = _params()
    state = opt.init(params, Extras(stats=_capture_stats(0), sched=rt))
    for t in range(STEPS):
        _, state = opt.update(_grads(t), state,
                              extras=Extras(stats=_capture_stats(t), sched=rt))
    assert int(_sched_of(state).n_refresh) == 2
    # an explicitly-tuned local interval beats the train-level default
    opt = kfac_preconditioner(GAMMA, 0.9, interval=2)
    state = opt.init(params, Extras(stats=_capture_stats(0), sched=rt))
    for t in range(STEPS):
        _, state = opt.update(_grads(t), state,
                              extras=Extras(stats=_capture_stats(t), sched=rt))
    assert int(_sched_of(state).n_refresh) == 3


def test_schedule_metrics():
    _, state = _scheduled_run('foof', STEPS, policy=every_k(2))
    m = schedrt.schedule_metrics(state)
    assert int(m['refreshes']) == 3
    assert schedrt.schedule_metrics({'no': 'sched'}) == {}


# ---------------------------------------------------------------------------
# Ownership


def test_ownership_assignment_covers_and_balances():
    plan = bucketing.build_plan(_grads(0))
    cost = ownership.inverse_cost('both')
    owners = ownership.assign_owners(plan, cost, world=3)
    per_worker = np.zeros(3)
    for b in plan.buckets:
        assert owners[b.key].shape == (len(b.paths),)
        assert set(owners[b.key].tolist()) <= {0, 1, 2}
        for i, w in enumerate(owners[b.key]):
            per_worker[w] += cost(b)
    assert (per_worker > 0).all()          # nobody idle at this item count
    # deterministic (and cached) across calls
    again = ownership.assign_owners(plan, cost, world=3)
    for k in owners:
        np.testing.assert_array_equal(owners[k], again[k])
    # W=1: everything owned by rank 0
    solo = ownership.assign_owners(plan, cost, world=1)
    for k in solo:
        assert (solo[k] == 0).all()


def test_inverse_cost_model():
    plan = bucketing.build_plan(_grads(0))
    by_key = {b.key: b for b in plan.buckets}
    b84 = by_key[bucketing.bucket_key((8, 4), jnp.float32)]
    assert ownership.inverse_cost('both')(b84) == 8 ** 3 + 4 ** 3
    assert ownership.inverse_cost('left')(b84) == 8 ** 3
    bstack = by_key[bucketing.bucket_key((2, 6, 4), jnp.float32)]
    assert ownership.inverse_cost('both')(bstack) == 2 * (6 ** 3 + 4 ** 3)
    with pytest.raises(ValueError):
        ownership.inverse_cost('up')


def test_world_and_rank_single_host():
    world, rank = ownership.world_and_rank()
    assert world == 1 and rank is None


# ---------------------------------------------------------------------------
# Single-host ≡ W-worker ownership under shard_map (subprocess: the forced
# 4-device flag must not leak into this test process)

_SHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import kv as kvlib
    from repro.core.kfac import kfac_preconditioner
    from repro.core.transform import Extras
    from repro.schedule.policy import every_k
    from repro.launch.mesh import make_mesh

    SHAPES = {'blk0/w': (8, 4), 'blk1/w': (8, 4), 'blk2/w': (8, 4),
              'head/w': (8, 3), 'stack/w': (2, 6, 4)}

    def psd(key, *shape):
        m = jax.random.normal(key, shape)
        return m @ jnp.swapaxes(m, -1, -2) + 0.1 * jnp.eye(shape[-1])

    def grads(seed):
        key = jax.random.PRNGKey(seed)
        return {p: jax.random.normal(jax.random.fold_in(key, i), s)
                for i, (p, s) in enumerate(SHAPES.items())}

    def stats(seed):
        key = jax.random.PRNGKey(1000 + seed)
        out = {}
        for i, (p, s) in enumerate(SHAPES.items()):
            ks = jax.random.split(jax.random.fold_in(key, i), 2)
            lead, d_in, d_out = s[:-2], s[-2], s[-1]
            out[p] = kvlib.LayerStats(
                a_outer=psd(ks[0], *lead, d_in, d_in),
                b_outer=psd(ks[1], *lead, d_out, d_out))
        return out

    STEPS = 4
    opt = kfac_preconditioner(0.03, 0.9, policy=every_k(2))
    params = kvlib.unflatten_params(grads(0))
    from repro.schedule.runtime import RefreshRuntime

    def run_single():
        state = opt.init(params, Extras(stats=stats(0)))
        outs = []
        for t in range(STEPS):
            out, state = opt.update(grads(t), state,
                                    extras=Extras(stats=stats(t)))
            outs.append(out)
        return outs, state

    def run_meshed(shard):
        rt = RefreshRuntime(shard_refresh=shard)
        mesh = make_mesh((4,), ('data',))
        state = opt.init(params, Extras(stats=stats(0), sched=rt))

        def body(g, s, st):
            return opt.update(g, s, extras=Extras(stats=st, sched=rt))

        step = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(), P(), P()), out_specs=(P(), P()),
            check_vma=False))
        outs = []
        for t in range(STEPS):
            out, state = step(grads(t), state, stats(t))
            outs.append(out)
        return outs, state

    def maxdiff(a, b):
        return max(float(np.max(np.abs(np.asarray(x).astype(np.float64)
                                       - np.asarray(y).astype(np.float64))))
                   for x, y in zip(jax.tree_util.tree_leaves(a),
                                   jax.tree_util.tree_leaves(b)))

    (o1, s1) = run_single()
    (o2, s2) = run_meshed(shard=True)      # ownership-sharded refresh
    (o3, s3) = run_meshed(shard=False)     # every worker recomputes all
    print(json.dumps({
        'devices': jax.device_count(),
        # ownership mechanism alone: sharded vs redundant on the SAME mesh
        'shard_vs_redundant_out': maxdiff(o2, o3),
        'shard_vs_redundant_state': maxdiff(s2, s3),
        # cross-world: only the pmean of replicated stats may round
        'shard_vs_single_out': maxdiff(o2, o1),
        'shard_vs_single_state': maxdiff(s2, s1),
    }))
""")


@pytest.mark.multihost
def test_sharded_refresh_matches_single_host():
    out = subprocess.run(
        [sys.executable, '-c', _SHARD_SCRIPT],
        capture_output=True, text=True, timeout=600,
        # JAX_PLATFORMS pinned: the scrubbed env must not fall through to
        # accelerator discovery (libtpu-on-a-TPU-less-host hangs forever)
        env={'PYTHONPATH': 'src', 'PATH': '/usr/bin:/bin', 'HOME': '/root',
             'JAX_PLATFORMS': 'cpu'},
        cwd=Path(__file__).resolve().parent.parent)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec['devices'] == 4
    # W-worker sharded refresh vs W-worker redundant refresh on the same
    # mesh: the EXCHANGE is bit-exact (owned-slice copies / x+0 psums —
    # tests/test_comm_exchange.py proves allgather ≡ psum atol=0 for all
    # six methods), but since the comm layer the sharded path owns stack
    # slices at (row × lead-dim) granularity, so its LAPACK inverses run
    # per (d, d) slice where the redundant worker batches (lead, d, d) —
    # batched-vs-single getrf moves the last float ulp (~1e-6,
    # data-dependent; see the lax.map note in test_bucketing).
    assert rec['shard_vs_redundant_out'] < 1e-4
    assert rec['shard_vs_redundant_state'] < 1e-4
    # Against a single host: additionally the pre-existing pmean_stats
    # reduction of replicated statistics (a psum of four equal f32 values
    # can round in the last ulp); the trajectory must still agree to float
    # tolerance.
    assert rec['shard_vs_single_out'] < 1e-4
    assert rec['shard_vs_single_state'] < 1e-4
