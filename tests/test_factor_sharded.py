"""Sharded vocab-head factors (repro.core.factor_sharded): policy routing,
matrix-free solve accuracy, and state compatibility.

Contracts proven here:
  * ``head_policy='dense'`` — and an untripped threshold under any policy —
    reproduce the legacy K-FAC/Shampoo outputs AND state bit-exactly
    (atol=0): the split returns the original plan object and ``head=None``
    keeps the state pytree structure unchanged;
  * ``'exclude'`` changes only the tripped head path (identity on the
    oversized side), non-head buckets stay bit-exact;
  * ``'shard'`` matches the dense damped inverse within the iterative
    tolerance (CG at power −1; binomial series at Shampoo's −1/4), with
    the non-head buckets again bit-exact;
  * on a real 4-device host mesh (subprocess) the distributed partial-psum
    solve agrees with the legacy dense run to the same tolerance, and the
    ``factor/*`` call-site is recorded with the partial-psum mode;
  * checkpoint save/restore mid-run with sharded head state resumes
    bit-exactly (frozen dampings + cached dense-side operators roundtrip);
  * the sub-slice ownership helpers partition factor rows exactly once;
  * ``step_metrics`` surfaces the declared ``repro.obs`` fields.
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
from repro.core import factor_sharded as fsh
from repro.core import kv as kvlib
from repro.core.factor_sharded import FactorShardConfig
from repro.core.transform import Extras
from repro.schedule import ownership
from repro.train import checkpoint as ckpt


# ---------------------------------------------------------------------------
# Config + plan split


def test_config_validation():
    assert FactorShardConfig().head_policy == 'dense'
    with pytest.raises(ValueError):
        FactorShardConfig(head_policy='drop')
    with pytest.raises(ValueError):
        FactorShardConfig(solver='chebyshev')
    assert fsh.from_extras(None) == FactorShardConfig()
    assert fsh.from_extras(Extras()) == FactorShardConfig()
    cfg = FactorShardConfig(head_policy='shard', shard_threshold=128)
    assert fsh.from_extras(Extras(factor=cfg)) is cfg
    kw = fsh.from_extras(Extras(factor={'head_policy': 'exclude'}))
    assert kw.head_policy == 'exclude'


def _toy_plan():
    return bucketing.build_plan({'blk/w': jnp.zeros((8, 6)),
                                 'head/w': jnp.zeros((8, 40))})


def test_split_plan_identity_when_nothing_trips():
    plan = _toy_plan()
    for cfg in (FactorShardConfig(),                       # policy dense
                FactorShardConfig(head_policy='shard',     # threshold high
                                  shard_threshold=64)):
        dense, pol = fsh.split_plan(plan, cfg)
        assert dense is plan and pol == {}


def test_split_plan_trips_per_side():
    plan = _toy_plan()
    dense, pol = fsh.split_plan(
        plan, FactorShardConfig(head_policy='shard', shard_threshold=32))
    dense_keys = {b.key for b in dense.buckets}
    head_keys = set(pol)
    assert len(head_keys) == 1 and not (dense_keys & head_keys)
    (policies,) = pol.values()
    assert policies == ('dense', 'shard')   # only the 40-dim out side trips


def test_subslice_ownership_partitions_rows():
    assert ownership.factor_block(40, 4) == 10
    assert ownership.factor_block(41, 4) == 11
    np.testing.assert_array_equal(ownership.assign_subslice_owners(40, 4),
                                  np.arange(4))
    plan = _toy_plan()
    desc = ownership.describe_subslices(plan, 4, 32)
    # only the tripped side appears; band sizes cover the dim exactly once
    (key,) = [k for k in desc if k.endswith('/out')]
    assert sum(desc[key]) == 40 and max(desc[key]) == 10
    assert not any(k.endswith('/in') for k in desc)


# ---------------------------------------------------------------------------
# Optimizer-level equivalence (single device; the solve's psum is a no-op)


def _paths():
    return {'blk/w': (8, 6), 'head/w': (8, 40)}


def _tree(seed):
    rng = np.random.default_rng(seed)
    return {p: jnp.asarray(rng.normal(size=s), jnp.float32)
            for p, s in _paths().items()}


def _stats(seed=10):
    rng = np.random.default_rng(seed)

    def psd(d):
        m = rng.normal(size=(d, d))
        return jnp.asarray(m @ m.T / d + 0.5 * np.eye(d), jnp.float32)

    return {p: kvlib.LayerStats(a_outer=psd(s[0]), b_outer=psd(s[1]))
            for p, s in _paths().items()}


def _run(opt_factory, factor, steps=3):
    params, grads, stats = _tree(0), _tree(1), _stats()
    opt = opt_factory()
    ex = Extras(stats=stats, factor=factor)
    state = opt.init(params, ex)
    out = None
    for _ in range(steps):
        out, state = opt.update(grads, state, params=params, extras=ex)
    return kvlib.flatten_params(out), state


def _md(a, b, p):
    return float(jnp.max(jnp.abs(np.asarray(a[p], np.float64)
                                 - np.asarray(b[p], np.float64))))


def _kf():
    import importlib
    mod = importlib.import_module('repro.core.kfac')
    return mod.kfac_preconditioner(gamma=0.5, interval=1)


def _sp():
    import importlib
    mod = importlib.import_module('repro.core.shampoo')
    return mod.shampoo_preconditioner(gamma=0.5, interval=1)


@pytest.mark.parametrize('factory', [_kf, _sp], ids=['kfac', 'shampoo'])
def test_dense_policy_is_legacy_bit_exact(factory):
    legacy_out, legacy_st = _run(factory, None)
    dense_out, dense_st = _run(
        factory, FactorShardConfig(head_policy='dense', shard_threshold=32))
    for p in legacy_out:
        assert _md(legacy_out, dense_out, p) == 0.0, p
    # state structure AND values identical (head=None keeps the pytree)
    la, da = (jax.tree_util.tree_leaves(s) for s in (legacy_st, dense_st))
    assert len(la) == len(da)
    for x, y in zip(la, da):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_exclude_touches_only_head_path():
    legacy, _ = _run(_kf, None)
    excl, _ = _run(_kf, FactorShardConfig(head_policy='exclude',
                                          shard_threshold=32))
    assert _md(excl, legacy, 'blk/w') == 0.0
    assert _md(excl, legacy, 'head/w') > 0.0   # the guard changes the head


def test_shard_cg_matches_dense_within_tolerance():
    legacy, st = _run(_kf, None)
    shard, st_shard = _run(_kf, FactorShardConfig(
        head_policy='shard', shard_threshold=32, solver='cg',
        solve_iters=60))
    assert _md(shard, legacy, 'blk/w') == 0.0  # dense bucket untouched
    assert _md(shard, legacy, 'head/w') < 1e-5
    # the sharded state carries the declared obs fields
    m = fsh.step_metrics(st_shard)
    assert set(m) == set(fsh.METRIC_FIELDS)
    assert float(m['factor_solve_iters']) == 60
    assert float(m['factor_shard_bytes']) > 0
    assert fsh.step_metrics(st) == {}          # legacy state: no fields


def test_shard_binomial_matches_shampoo_root():
    legacy, _ = _run(_sp, None)
    shard, _ = _run(_sp, FactorShardConfig(
        head_policy='shard', shard_threshold=32, solver='binomial',
        solve_iters=600))
    assert _md(shard, legacy, 'blk/w') == 0.0
    assert _md(shard, legacy, 'head/w') < 1e-4


def test_obs_declares_factor_fields():
    from repro.obs import events
    assert 'factor_solve_iters' in events.SCHEMAS['step']
    assert 'factor_shard_bytes' in events.SCHEMAS['step']
    assert 'solve_iters' in events._SITE_FIELDS
    assert 'factor_shard_bytes' in events._SITE_FIELDS


# ---------------------------------------------------------------------------
# Checkpoint resume: sharded head state (frozen dampings + cached dense-side
# operators) must roundtrip bit-exactly, including mid-interval


def _factor_train(steps, tmp_path=None, save_at=None, factor=None):
    from repro.core.registry import make_optimizer
    from repro.data.synthetic import ClassStream
    from repro.models import module as M
    from repro.models.simple import MLP, classifier_loss_fn
    from repro.train.step import init_opt_state, make_train_step

    stream = ClassStream(batch=32, dim=8, classes=3, seed=0)
    model = MLP([8, 32, 16, 3])
    model.loss_fn = classifier_loss_fn(model)
    params = M.init_params(model.param_specs(), jax.random.PRNGKey(0))
    opt, capture = make_optimizer('kfac', lr=0.05, interval=3)
    taps_fn = lambda p: model.make_taps(32, capture)  # noqa: E731
    state = init_opt_state(model, opt, capture, params, stream.batch_at(0),
                           taps_fn=taps_fn, factor=factor)
    step = jax.jit(make_train_step(model, opt, capture, taps_fn=taps_fn,
                                   factor=factor))
    for i in range(steps):
        if save_at is not None and i == save_at:
            ckpt.save(tmp_path, i, {'params': params, 'opt_state': state},
                      {'next_step': i})
            template = jax.tree_util.tree_map(
                jnp.zeros_like, {'params': params, 'opt_state': state})
            restored, meta = ckpt.restore(tmp_path, i, template)
            params, state = restored['params'], restored['opt_state']
            assert meta['next_step'] == i
        params, state, _ = step(params, state, stream.batch_at(i))
    return params, state


def test_sharded_head_state_resume_bit_exact(tmp_path):
    # threshold 32: the (8,32) layer trips its out side, (32,16) its in
    # side, (16,3) stays dense — head + dense buckets in one state; save at
    # step 4 = mid-interval for k=3 (frozen dampings must survive)
    factor = FactorShardConfig(head_policy='shard', shard_threshold=32,
                               solver='cg', solve_iters=20)
    heads = fsh.head_states(_factor_train(1, factor=factor)[1])
    assert heads and len(heads[0].buckets) == 2   # the premise above holds
    p_ref, s_ref = _factor_train(7, factor=factor)
    p_res, s_res = _factor_train(7, tmp_path=tmp_path, save_at=4,
                                 factor=factor)
    for x, y in zip(jax.tree_util.tree_leaves((p_ref, s_ref)),
                    jax.tree_util.tree_leaves((p_res, s_res))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# 4-device proof (subprocess: the forced device flag must not leak): the
# distributed partial-psum solve ≡ the legacy dense run within iterative
# tolerance, dense buckets bit-exact, and the factor site is recorded

_SHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    import importlib
    kfac_mod = importlib.import_module('repro.core.kfac')
    sh_mod = importlib.import_module('repro.core.shampoo')
    from repro.comm import metrics
    from repro.core import kv as kvlib
    from repro.core.factor_sharded import FactorShardConfig
    from repro.core.transform import Extras
    from repro.schedule.runtime import RefreshRuntime
    from repro.launch.mesh import make_mesh

    PATHS = {'blk/w': (8, 6), 'head/w': (8, 40)}
    rng = np.random.default_rng(0)
    params = {p: jnp.asarray(rng.normal(size=s), jnp.float32)
              for p, s in PATHS.items()}
    grads = {p: jnp.asarray(rng.normal(size=s), jnp.float32)
             for p, s in PATHS.items()}

    def psd(d):
        m = rng.normal(size=(d, d))
        return jnp.asarray(m @ m.T / d + 0.5 * np.eye(d), jnp.float32)

    stats = {p: kvlib.LayerStats(a_outer=psd(s[0]), b_outer=psd(s[1]))
             for p, s in PATHS.items()}
    mesh = make_mesh((4,), ('data',))
    rt = RefreshRuntime(shard_refresh=True)

    def run(opt_factory, factor, steps=3):
        opt = opt_factory()
        state = opt.init(params, Extras(stats=stats, factor=factor,
                                        sched=rt))

        def body(g, s, st):
            return opt.update(g, s, extras=Extras(stats=st, factor=factor,
                                                  sched=rt))

        step = jax.jit(jax.shard_map(body, mesh=mesh,
                                        in_specs=(P(), P(), P()),
                                        out_specs=(P(), P()), check_vma=False))
        out = None
        for _ in range(steps):
            out, state = step(grads, state, stats)
        return kvlib.flatten_params(out), state

    def md(a, b, p):
        return float(jnp.max(jnp.abs(
            np.asarray(a[p], np.float64) - np.asarray(b[p], np.float64))))

    kf = lambda: kfac_mod.kfac_preconditioner(gamma=0.5, interval=1)
    sp = lambda: sh_mod.shampoo_preconditioner(gamma=0.5, interval=1)
    cg = FactorShardConfig(head_policy='shard', shard_threshold=32,
                           solver='cg', solve_iters=60)
    bino = FactorShardConfig(head_policy='shard', shard_threshold=32,
                             solver='binomial', solve_iters=600)

    rec = {'devices': jax.device_count()}
    legacy, _ = run(kf, None)
    dense, _ = run(kf, FactorShardConfig(head_policy='dense',
                                         shard_threshold=32))
    shard, st = run(kf, cg)
    rec['kfac_dense_blk'] = md(dense, legacy, 'blk/w')
    rec['kfac_dense_head'] = md(dense, legacy, 'head/w')
    rec['kfac_shard_blk'] = md(shard, legacy, 'blk/w')
    rec['kfac_shard_head'] = md(shard, legacy, 'head/w')

    sp_legacy, _ = run(sp, None)
    sp_shard, _ = run(sp, bino)
    rec['shampoo_shard_blk'] = md(sp_shard, sp_legacy, 'blk/w')
    rec['shampoo_shard_head'] = md(sp_shard, sp_legacy, 'head/w')

    sites = metrics.snapshot()
    rec['sites'] = {k: {'mode': v['mode'],
                        'bytes_per_call': v['bytes_per_call']}
                    for k, v in sites.items() if k.startswith('factor/')}
    print(json.dumps(rec))
""")


@pytest.mark.multihost
def test_shard_solve_matches_dense_on_4_devices():
    out = subprocess.run(
        [sys.executable, '-c', _SHARD_SCRIPT],
        capture_output=True, text=True, timeout=1800,
        # JAX_PLATFORMS pinned: the scrubbed env must not fall through to
        # accelerator discovery (libtpu-on-a-TPU-less-host hangs forever)
        env={'PYTHONPATH': 'src', 'PATH': '/usr/bin:/bin', 'HOME': '/root',
             'JAX_PLATFORMS': 'cpu'},
        cwd=Path(__file__).resolve().parent.parent)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec['devices'] == 4
    # dense policy ≡ legacy, bit-exact, even on the live mesh
    assert rec['kfac_dense_blk'] == 0.0 and rec['kfac_dense_head'] == 0.0
    # sharded solve: dense buckets bit-exact, head within CG tolerance
    assert rec['kfac_shard_blk'] == 0.0
    assert rec['kfac_shard_head'] < 1e-4, rec
    assert rec['shampoo_shard_blk'] == 0.0
    assert rec['shampoo_shard_head'] < 1e-3, rec
    # the distributed solve recorded its partial-psum call-sites
    modes = {k: v['mode'] for k, v in rec['sites'].items()}
    assert modes.get('factor/kfac') == 'psum-partial', modes
    assert modes.get('factor/shampoo') == 'psum-partial', modes
    assert all(v['bytes_per_call'] > 0 for v in rec['sites'].values())
