"""Double-buffered curvature pipeline (repro.schedule.pipeline).

Contracts proven here:
  * ``PipelineState`` slot semantics: zeros cold start at age 0, swap on
    ``stage``, refresh-gated age on ``tick``;
  * ``pipeline='onestep'`` EXACT semantics (atol=0, single host): the
    stats-only optimizers (eva, eva_f) equal a sync run fed the
    one-step-shifted stats stream ``[0, s_0, s_1, …]``; the interval
    methods (kfac, foof, shampoo) equal hand-rolled double-buffered
    references (precondition with the PREVIOUS caches, store this step's
    refresh); eva_s has no exchange so onestep ≡ sync trivially;
  * init/update pipeline-mode agreement is statically enforced
    (``resolve_pipe`` raises on mismatch);
  * observability: ``pipe_entries`` / ``pipeline_metrics`` report realized
    per-site staleness;
  * under a live 4-device mesh (subprocess) the onestep trajectory matches
    the single-host onestep trajectory to float tolerance, with the same
    exchange/LAPACK caveats as the sync sharded-refresh test.
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
from repro.schedule import pipeline as pipemod, runtime as schedrt
from repro.schedule.policy import adaptive, every_k

GAMMA = 0.03
STEPS = 6

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


def _zero_stats():
    return jax.tree_util.tree_map(jnp.zeros_like, _capture_stats(0))


def _params():
    return kvlib.unflatten_params(_grads(0))


def _assert_trees_equal(a, b, msg=''):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)


_MAKERS = {
    'eva': lambda **kw: eva_preconditioner(GAMMA, 0.9, **kw),
    'eva_f': lambda **kw: eva_f_preconditioner(GAMMA, 0.9, **kw),
    'eva_s': lambda **kw: eva_s_preconditioner(GAMMA, 0.9, **kw),
    'foof': lambda **kw: foof_preconditioner(GAMMA, 0.9, **kw),
    'kfac': lambda **kw: kfac_preconditioner(GAMMA, 0.9, **kw),
    'shampoo': lambda **kw: shampoo_preconditioner(1e-4, **kw),
}
_NEEDS_STATS = ('eva', 'eva_f', 'foof', 'kfac')


def _run(method, steps, sched=None, stats_fn=_capture_stats, **kw):
    """Scheduled run with an explicit RefreshRuntime and stats stream."""
    opt = _MAKERS[method](**kw)
    params = _params()
    needs = method in _NEEDS_STATS
    state = opt.init(params, Extras(stats=stats_fn(0) if needs else None,
                                    sched=sched))
    outs = []
    for t in range(steps):
        ex = Extras(stats=stats_fn(t) if needs else None, sched=sched)
        out, state = opt.update(_grads(t), state, extras=ex)
        outs.append(kvlib.flatten_params(out))
    return outs, state


_ONESTEP = schedrt.RefreshRuntime(pipeline='onestep')


# ---------------------------------------------------------------------------
# PipelineState slot semantics


def test_pipeline_state_slots():
    tmpl = {'a': jnp.ones((2, 3))}
    p = pipemod.init_state(tmpl)
    _assert_trees_equal(p.inflight, {'a': jnp.zeros((2, 3))})
    assert int(p.age) == 0

    applied, p1 = pipemod.stage(p, {'a': jnp.full((2, 3), 5.0)})
    _assert_trees_equal(applied, {'a': jnp.zeros((2, 3))})  # cold zeros out
    _assert_trees_equal(p1.inflight, {'a': jnp.full((2, 3), 5.0)})
    assert int(p1.age) == 1
    applied, p2 = pipemod.stage(p1, {'a': jnp.full((2, 3), 7.0)})
    _assert_trees_equal(applied, {'a': jnp.full((2, 3), 5.0)})

    # refresh-site slot: buffer lives elsewhere, only the age is carried
    r = pipemod.init_state()
    assert r.inflight is None and int(r.age) == 0
    r = pipemod.tick(r, jnp.asarray(True))
    assert int(r.age) == 1
    r = pipemod.tick(r, jnp.asarray(False))
    r = pipemod.tick(r, jnp.asarray(False))
    assert int(r.age) == 3
    r = pipemod.tick(r, jnp.asarray(True))
    assert int(r.age) == 1


def test_staged_pmean_sync_is_identity_composition():
    tree = {'x': jnp.arange(6.0).reshape(2, 3)}
    fresh, pipe = pipemod.staged_pmean(tree, None)
    assert pipe is None
    _assert_trees_equal(fresh, tree)          # W=1, raw passthrough


def test_resolve_pipe_mode_mismatch_raises():
    """init and update must agree on the pipeline mode — a checkpoint from
    one mode fed to a step of the other is a config bug, caught statically."""
    with pytest.raises(ValueError, match='onestep'):
        _, state = _run('kfac', 1, sched=None)  # sync state (pipe=None)
        opt = _MAKERS['kfac']()
        opt.update(_grads(0), state,
                   extras=Extras(stats=_capture_stats(0), sched=_ONESTEP))
    with pytest.raises(ValueError, match='sync'):
        opt = _MAKERS['kfac']()
        state = opt.init(_params(), Extras(stats=_capture_stats(0),
                                           sched=_ONESTEP))
        opt.update(_grads(0), state,
                   extras=Extras(stats=_capture_stats(0), sched=None))


# ---------------------------------------------------------------------------
# Exact onestep semantics, single host (atol=0)


@pytest.mark.parametrize('method', ['eva', 'eva_f'])
@pytest.mark.parametrize('policy', [every_k(1), adaptive(threshold=0.05)])
def test_onestep_equals_shifted_stream(method, policy):
    """For the stats-only optimizers the one-step-stale pipeline IS the sync
    optimizer fed yesterday's statistics: onestep on [s_0, s_1, …] equals
    sync on [0, s_0, …, s_{n-2}] bit-exactly (the EMA count advances
    identically, only the consumed stream shifts)."""
    onestep, _ = _run(method, STEPS, sched=_ONESTEP, policy=policy)

    def shifted(t):
        return _zero_stats() if t == 0 else _capture_stats(t - 1)

    sync, _ = _run(method, STEPS, sched=None, stats_fn=shifted, policy=policy)
    for t in range(STEPS):
        _assert_trees_equal(onestep[t], sync[t], msg=f'{method} step {t}')


def test_onestep_eva_s_is_noop():
    """eva_s performs no curvature collective → onestep ≡ sync exactly."""
    a, sa = _run('eva_s', STEPS, sched=_ONESTEP)
    b, sb = _run('eva_s', STEPS, sched=None)
    for t in range(STEPS):
        _assert_trees_equal(a[t], b[t], msg=f'step {t}')
    _assert_trees_equal(sa, sb)


def _ref_kfac_onestep(steps, interval, kf_decay=0.9):
    """Hand-rolled double-buffered K-FAC: the EMA consumes LAST step's
    reduced factors (zeros at t=0) and preconditioning uses LAST step's
    inverses; this step's gated recompute lands in state only."""
    fields = ('a_outer', 'b_outer')
    flat = kvlib.flatten_params(_params())
    stats0 = _capture_stats(0)
    plan = _stats_plan(flat, stats0, None)
    zeros = bucketing.gather_tree(plan, _zeros_like_spec(_extract(stats0, fields)))
    run = kvlib.init_running(zeros)
    a_inv = {k: jnp.zeros_like(st.a_outer) for k, st in run.stats.items()}
    b_inv = {k: jnp.zeros_like(st.b_outer) for k, st in run.stats.items()}
    prev_fresh = zeros
    outs = []
    for t in range(steps):
        applied, prev_fresh = prev_fresh, bucketing.gather_tree(
            plan, _extract(_capture_stats(t), fields))
        stats, run = kvlib.update_running(run, applied, kf_decay)

        def one(ao, bo):
            gamma_r, gamma_q = pre.kfac_pi_damping(ao, bo, GAMMA)
            return _damped_inv(ao, gamma_r), _damped_inv(bo, gamma_q)

        def recompute(_):
            ai, bi = {}, {}
            for k, st in stats.items():
                ai[k], bi[k] = pre.map_bucket(one, st.a_outer, st.b_outer)
            return ai, bi

        used_a, used_b = a_inv, b_inv
        a_inv, b_inv = jax.lax.cond(t % interval == 0, recompute,
                                    lambda _: (a_inv, b_inv), operand=None)
        ops = {k: kvlib.LayerStats(a_outer=used_a[k], b_outer=used_b[k])
               for k in used_a}
        outs.append(pre.precondition_tree(_grads(t), ops, 'kfac_cached',
                                          GAMMA, plan=plan))
    return outs


def _ref_foof_onestep(steps, interval, kf_decay=0.9):
    fields = ('a_outer',)
    flat = kvlib.flatten_params(_params())
    stats0 = _capture_stats(0)
    plan = _stats_plan(flat, stats0, None)
    zeros = bucketing.gather_tree(plan, _zeros_like_spec(_extract(stats0, fields)))
    run = kvlib.init_running(zeros)
    a_inv = {k: jnp.zeros_like(st.a_outer) for k, st in run.stats.items()}
    prev_fresh = zeros
    outs = []
    for t in range(steps):
        applied, prev_fresh = prev_fresh, bucketing.gather_tree(
            plan, _extract(_capture_stats(t), fields))
        stats, run = kvlib.update_running(run, applied, kf_decay)

        def recompute(_):
            return {k: pre.map_bucket(lambda m: _damped_inv(m, GAMMA),
                                      st.a_outer)
                    for k, st in stats.items()}

        used = a_inv
        a_inv = jax.lax.cond(t % interval == 0, recompute, lambda _: a_inv,
                             operand=None)
        ops = {k: kvlib.LayerStats(a_outer=used[k]) for k in used}
        outs.append(pre.precondition_tree(_grads(t), ops, 'foof_cached',
                                          GAMMA, plan=plan))
    return outs


def _ref_shampoo_onestep(steps, interval, eps_init=1e-6):
    """Shampoo's accumulators are local (no stats exchange); only the root
    refresh is pipelined — apply last step's roots, store this step's."""
    flat = kvlib.flatten_params(_params())
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

        used_in, used_out = p_in, p_out
        p_in, p_out = jax.lax.cond(t % interval == 0, recompute,
                                   lambda _: (p_in, p_out), operand=None)
        ops = {k: kvlib.LayerStats(a_outer=used_in[k], b_outer=used_out[k])
               for k in used_in}
        outs.append(pre.precondition_tree(g, ops, 'shampoo_cached', 1e-4,
                                          plan=plan))
    return outs


_ONESTEP_REFS = {
    'kfac': _ref_kfac_onestep,
    'foof': _ref_foof_onestep,
    'shampoo': _ref_shampoo_onestep,
}


@pytest.mark.parametrize('method', sorted(_ONESTEP_REFS))
@pytest.mark.parametrize('interval', [1, 3])
def test_onestep_equals_double_buffered_reference(method, interval):
    ref = _ONESTEP_REFS[method](STEPS, interval)
    outs, _ = _run(method, STEPS, sched=_ONESTEP, policy=every_k(interval))
    for t in range(STEPS):
        _assert_trees_equal(
            kvlib.flatten_params(ref[t]), outs[t],
            msg=f'{method} interval={interval} step {t}')


# ---------------------------------------------------------------------------
# Observability


def test_pipe_entries_and_metrics():
    _, state = _run('kfac', STEPS, sched=_ONESTEP, policy=every_k(2))
    entries = pipemod.pipe_entries(state)
    assert sorted(k for k, _ in entries) == ['refresh', 'stats']
    by_key = dict(entries)
    assert int(by_key['stats'].age) == 1       # re-exchanged every step
    # refreshes fired at steps 0, 2, 4 → after step 5 the in-flight
    # inverses were computed at step 4: age 2
    assert int(by_key['refresh'].age) == 2
    m = pipemod.pipeline_metrics(state)
    assert int(m['pipeline_lag']) == 2
    assert int(m['pipeline_lag/stats']) == 1
    assert int(m['pipeline_lag/refresh']) == 2

    # sync state: no pipeline, no metrics
    _, state = _run('kfac', 1, sched=None)
    assert pipemod.pipe_entries(state) == []
    assert pipemod.pipeline_metrics(state) == {}


def test_sync_state_structure_has_no_pipe_leaves():
    """pipe=None must contribute zero leaves — sync checkpoints stay
    loadable across the refactor."""
    _, state = _run('foof', 2, sched=schedrt.RefreshRuntime(pipeline='sync'))
    _, legacy = _run('foof', 2, sched=None)
    assert (jax.tree_util.tree_structure(state)
            == jax.tree_util.tree_structure(legacy))


# ---------------------------------------------------------------------------
# HLO overlap checker (launch.hlo_analysis.collective_overlap)

_HLO_DIRECT = textwrap.dedent("""
    HloModule m

    ENTRY %main (p0: f32[4,4], p1: f32[4,4]) -> (f32[4,4], f32[4,4]) {
      %p0 = f32[4,4]{1,0} parameter(0)
      %p1 = f32[4,4]{1,0} parameter(1)
      %ar = f32[4,4]{1,0} all-reduce(%p0), replica_groups=[1,4]
      %dep = f32[4,4]{1,0} dot(%ar, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %indep = f32[4,4]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      ROOT %out = (f32[4,4], f32[4,4]) tuple(%dep, %indep)
    }
""")

_HLO_FUSION = textwrap.dedent("""
    HloModule m

    %fused (fp0: f32[4,4], fp1: f32[4,4]) -> f32[4,4] {
      %fp0 = f32[4,4]{1,0} parameter(0)
      %fp1 = f32[4,4]{1,0} parameter(1)
      ROOT %d = f32[4,4]{1,0} dot(%fp0, %fp1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
    }

    ENTRY %main (p0: f32[4,4], p1: f32[4,4]) -> f32[4,4] {
      %p0 = f32[4,4]{1,0} parameter(0)
      %p1 = f32[4,4]{1,0} parameter(1)
      %ags = f32[4,4]{1,0} all-gather-start(%p0), replica_groups=[1,4]
      %agd = f32[4,4]{1,0} all-gather-done(%ags)
      ROOT %f = f32[4,4]{1,0} fusion(%agd, %p1), kind=kLoop, calls=%fused
    }
""")

_HLO_WHILE_CARRY = textwrap.dedent("""
    HloModule m

    %cond (cp: (s32[], f32[4,4])) -> pred[] {
      %cp = (s32[], f32[4,4]) parameter(0)
      %i = s32[] get-tuple-element(%cp), index=0
      %n = s32[] constant(3)
      ROOT %lt = pred[] compare(%i, %n), direction=LT
    }

    %body (bp: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
      %bp = (s32[], f32[4,4]) parameter(0)
      %i = s32[] get-tuple-element(%bp), index=0
      %x = f32[4,4]{1,0} get-tuple-element(%bp), index=1
      %one = s32[] constant(1)
      %ip = s32[] add(%i, %one)
      %ar = f32[4,4]{1,0} all-reduce(%x), replica_groups=[1,4]
      ROOT %t = (s32[], f32[4,4]) tuple(%ip, %ar)
    }

    ENTRY %main (p0: f32[4,4], p1: f32[4,4]) -> f32[4,4] {
      %p0 = f32[4,4]{1,0} parameter(0)
      %p1 = f32[4,4]{1,0} parameter(1)
      %init = (s32[], f32[4,4]) tuple-hack(%p0)
      %w = (s32[], f32[4,4]) while(%init), condition=%cond, body=%body
      %wx = f32[4,4]{1,0} get-tuple-element(%w), index=1
      ROOT %d = f32[4,4]{1,0} dot(%wx, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
    }
""").replace('tuple-hack', 'tuple')


def test_overlap_checker_direct_dependence():
    from repro.launch import hlo_analysis
    rep = hlo_analysis.collective_overlap(_HLO_DIRECT)
    assert rep.collective_count == 1
    assert rep.blocking_collectives == 1
    assert rep.total_dots == 2
    assert rep.dependent_dots == 1
    # both dots are 2*16*4 = 128 flops; exactly half the flops must wait
    assert rep.dependent_fraction == pytest.approx(0.5)
    assert rep.dot_flops_independent == pytest.approx(rep.dot_flops_dependent)


def test_overlap_checker_through_fusion_and_async_pair():
    from repro.launch import hlo_analysis
    rep = hlo_analysis.collective_overlap(_HLO_FUSION)
    # -start and -done both count as collective sources; the dot INSIDE the
    # fusion computation is reached through the caller-operand→parameter edge
    assert rep.collective_count == 2
    assert rep.blocking_collectives == 2
    assert rep.total_dots == 1
    assert rep.dependent_dots == 1
    assert rep.dependent_fraction == 1.0


def test_overlap_checker_while_loop_carry():
    from repro.launch import hlo_analysis
    rep = hlo_analysis.collective_overlap(_HLO_WHILE_CARRY)
    # the all-reduce inside the while body reaches the downstream dot via
    # body-root → while-op → consumer
    assert rep.collective_count == 1
    assert rep.blocking_collectives == 1
    assert rep.dependent_dots == 1 and rep.total_dots == 1


def test_overlap_checker_no_collectives():
    from repro.launch import hlo_analysis
    rep = hlo_analysis.collective_overlap(
        _HLO_DIRECT.replace('all-reduce(%p0), replica_groups=[1,4]',
                            'negate(%p0)'))
    assert rep.collective_count == 0
    assert rep.dependent_fraction == 0.0
    assert rep.total_dots == 2


def test_overlap_checker_nonblocking_collective():
    """A collective whose output feeds only a state-like output (no dot in
    its cone) must not count as blocking — the onestep signature."""
    from repro.launch import hlo_analysis
    hlo = _HLO_DIRECT.replace('dot(%ar, %p1)', 'dot(%p0, %p1)')
    rep = hlo_analysis.collective_overlap(hlo)
    assert rep.collective_count == 1
    assert rep.blocking_collectives == 0
    assert rep.dependent_dots == 0


# ---------------------------------------------------------------------------
# 4-device mesh (subprocess: the forced device-count flag must not leak)

_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import kv as kvlib
    from repro.core.kfac import kfac_preconditioner
    from repro.core.transform import Extras
    from repro.schedule import pipeline as pipemod
    from repro.schedule.policy import every_k
    from repro.schedule.runtime import RefreshRuntime
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

    STEPS = 5
    opt = kfac_preconditioner(0.03, 0.9, policy=every_k(2))
    params = kvlib.unflatten_params(grads(0))

    def run(rt, meshed):
        state = opt.init(params, Extras(stats=stats(0), sched=rt))
        if meshed:
            mesh = make_mesh((4,), ('data',))

            def body(g, s, st):
                return opt.update(g, s, extras=Extras(stats=st, sched=rt))

            step = jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=(P(), P(), P()),
                out_specs=(P(), P()), check_vma=False))
        else:
            def step(g, s, st):
                return opt.update(g, s, extras=Extras(stats=st, sched=rt))
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

    one_rt = lambda shard: RefreshRuntime(pipeline='onestep',
                                          shard_refresh=shard)
    o_single, s_single = run(one_rt(False), meshed=False)
    o_mesh, s_mesh = run(one_rt(True), meshed=True)
    lag = {k: int(v) for k, v in pipemod.pipeline_metrics(s_mesh).items()}

    # structural overlap: dependent dot-FLOP fraction per pipeline mode
    from repro.launch import hlo_analysis
    frac = {}
    for mode in ('sync', 'onestep'):
        rt = RefreshRuntime(pipeline=mode, shard_refresh=True)
        st = opt.init(params, Extras(stats=stats(0), sched=rt))
        mesh = make_mesh((4,), ('data',))

        def body(g, s, stt):
            return opt.update(g, s, extras=Extras(stats=stt, sched=rt))

        step = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(), P(), P()),
            out_specs=(P(), P()), check_vma=False))
        txt = step.lower(grads(0), st, stats(0)).compile().as_text()
        frac[mode] = hlo_analysis.collective_overlap(txt).dependent_fraction

    print(json.dumps({
        'devices': jax.device_count(),
        'mesh_vs_single_out': maxdiff(o_mesh, o_single),
        'mesh_vs_single_state': maxdiff(
            [l for l in jax.tree_util.tree_leaves(s_mesh)],
            [l for l in jax.tree_util.tree_leaves(s_single)]),
        'lag': lag,
        'dep_frac': frac,
    }))
""")


@pytest.mark.multihost
def test_onestep_sharded_matches_single_host():
    out = subprocess.run(
        [sys.executable, '-c', _MESH_SCRIPT],
        capture_output=True, text=True, timeout=600,
        # JAX_PLATFORMS pinned: the scrubbed env must not fall through to
        # accelerator discovery (libtpu-on-a-TPU-less-host hangs forever)
        env={'PYTHONPATH': 'src', 'PATH': '/usr/bin:/bin', 'HOME': '/root',
             'JAX_PLATFORMS': 'cpu'},
        cwd=Path(__file__).resolve().parent.parent)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec['devices'] == 4
    # same tolerance rationale as the sync sharded-refresh test: the
    # exchange is bit-exact, slice-granular LAPACK batching moves the last
    # float ulp, replicated-stats psum rounding likewise
    assert rec['mesh_vs_single_out'] < 1e-4
    assert rec['mesh_vs_single_state'] < 1e-4
    # refreshes fired at steps 0, 2, 4; after step 4 the in-flight
    # inverses are 1 step old, the stats buffer always 1
    assert rec['lag'] == {'pipeline_lag': 1, 'pipeline_lag/refresh': 1,
                          'pipeline_lag/stats': 1}
    # the point of the pipeline: in sync mode the preconditioning dots sit
    # in the collectives' dependence cone; in onestep they all leave it
    assert rec['dep_frac']['sync'] > 0.5
    assert rec['dep_frac']['onestep'] == 0.0
