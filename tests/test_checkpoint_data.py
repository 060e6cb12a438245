"""Checkpoint roundtrip/async/GC/elastic-reshard + data determinism tests."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh

from repro.data.memmap_loader import MemmapLM, write_tokens
from repro.data.pipeline import Prefetcher
from repro.data.synthetic import AEStream, ClassStream, LMStream
from repro.train import checkpoint as ckpt


def _tree():
    return {'a': {'w': jnp.arange(12.0).reshape(3, 4)},
            'opt': (jnp.zeros(()), {'m': jnp.ones((5,)) * 2})}


def test_roundtrip(tmp_path):
    t = _tree()
    ckpt.save(tmp_path, 3, t, {'next_step': 3})
    template = jax.tree_util.tree_map(jnp.zeros_like, t)
    restored, meta = ckpt.restore(tmp_path, 3, template)
    assert meta['next_step'] == 3
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
        t, restored)


def test_async_and_gc(tmp_path):
    c = ckpt.AsyncCheckpointer(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        c.save(s, _tree(), {'next_step': s})
    c.wait()
    assert ckpt.available_steps(tmp_path) == [3, 4]
    assert ckpt.latest_step(tmp_path) == 4


def test_atomicity_incomplete_ignored(tmp_path):
    ckpt.save(tmp_path, 1, _tree())
    # simulate a crashed save: directory without the commit marker
    (tmp_path / 'step_00000009').mkdir()
    assert ckpt.latest_step(tmp_path) == 1


def test_elastic_reshard_restore(tmp_path):
    """Restore onto an explicit sharding (single-device 'mesh')."""
    t = _tree()
    ckpt.save(tmp_path, 1, t)
    mesh = make_mesh((1,), ('data',))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    restored, _ = ckpt.restore(tmp_path, 1,
                               jax.tree_util.tree_map(jnp.zeros_like, t),
                               shardings=sh)
    assert restored['a']['w'].sharding.is_equivalent_to(sh, 2)


def test_restore_missing_leaf_raises(tmp_path):
    """A template leaf absent from the manifest is a structural mismatch
    (different optimizer / pipeline mode), not silently zero-filled."""
    ckpt.save(tmp_path, 1, {'a': jnp.zeros(3)})
    with pytest.raises(KeyError, match='missing leaf'):
        ckpt.restore(tmp_path, 1, {'a': jnp.zeros(3), 'b': jnp.zeros(2)})


def test_restore_shape_mismatch_names_the_leaf(tmp_path):
    ckpt.save(tmp_path, 1, {'a': {'w': jnp.zeros((3, 4))}})
    with pytest.raises(ValueError, match=r"\['a'\]\['w'\]"):
        ckpt.restore(tmp_path, 1, {'a': {'w': jnp.zeros((4, 3))}})


def test_restore_missing_step_raises(tmp_path):
    ckpt.save(tmp_path, 1, {'a': jnp.zeros(3)})
    with pytest.raises(FileNotFoundError):
        ckpt.restore(tmp_path, 99, {'a': jnp.zeros(3)})


def test_gc_keep_zero_disables_gc(tmp_path):
    """keep <= 0 means 'never delete' — NOT 'delete everything' (the
    steps[:-0] == [] footgun is guarded explicitly)."""
    for s in (1, 2, 3):
        ckpt.save(tmp_path, s, {'a': jnp.zeros(2)})
    ckpt.gc_old(tmp_path, keep=0)
    assert ckpt.available_steps(tmp_path) == [1, 2, 3]
    ckpt.gc_old(tmp_path, keep=-1)
    assert ckpt.available_steps(tmp_path) == [1, 2, 3]


def test_gc_keep_larger_than_available(tmp_path):
    for s in (1, 2):
        ckpt.save(tmp_path, s, {'a': jnp.zeros(2)})
    ckpt.gc_old(tmp_path, keep=5)
    assert ckpt.available_steps(tmp_path) == [1, 2]


def test_gc_missing_dir_is_noop(tmp_path):
    ckpt.gc_old(tmp_path / 'never_created', keep=2)  # must not raise
    assert ckpt.available_steps(tmp_path / 'never_created') == []


def test_gc_skips_incomplete_dirs(tmp_path):
    """GC counts only committed checkpoints; a crashed save's tmp/partial
    dir neither counts toward keep-K nor gets deleted by gc_old."""
    for s in (1, 2, 3):
        ckpt.save(tmp_path, s, {'a': jnp.zeros(2)})
    (tmp_path / 'step_00000009').mkdir()  # no .complete marker
    ckpt.gc_old(tmp_path, keep=1)
    assert ckpt.available_steps(tmp_path) == [3]
    assert (tmp_path / 'step_00000009').exists()


def test_lm_stream_seekable_deterministic():
    s = LMStream(vocab=64, seq_len=16, batch=4, seed=3)
    b1 = s.batch_at(7)
    b2 = s.batch_at(7)
    np.testing.assert_array_equal(np.asarray(b1['tokens']),
                                  np.asarray(b2['tokens']))
    # labels are next-token shifted views of the same sample
    np.testing.assert_array_equal(np.asarray(b1['tokens'][:, 1:]),
                                  np.asarray(b1['labels'][:, :-1]))
    assert s.bigram_ce < s.uniform_ce  # structure present


def test_memmap_rank_disjoint(tmp_path):
    toks = np.arange(10_000) % 251
    write_tokens(tmp_path / 'corpus', toks)
    world = 4
    seen = []
    for r in range(world):
        ds = MemmapLM(str(tmp_path / 'corpus'), seq_len=32, batch=2,
                      rank=r, world=world, seed=0)
        b = ds.batch_at(0)
        seen.append(np.asarray(b['tokens']))
    flat = np.concatenate([s.reshape(-1) for s in seen])
    # same step across ranks covers disjoint windows (first tokens differ)
    firsts = [s[:, 0] for s in seen]
    assert len({tuple(f.tolist()) for f in firsts}) == world
    # deterministic
    ds0 = MemmapLM(str(tmp_path / 'corpus'), seq_len=32, batch=2,
                   rank=0, world=world, seed=0)
    np.testing.assert_array_equal(np.asarray(ds0.batch_at(0)['tokens']),
                                  seen[0])


def test_prefetcher_matches_stream_and_seeks():
    s = ClassStream(batch=4, dim=8, classes=3, seed=1)
    p = Prefetcher(s, depth=2)
    try:
        for i in range(3):
            got = p.batch_at(i)
            want = s.batch_at(i)
            np.testing.assert_allclose(np.asarray(got['x']),
                                       np.asarray(want['x']))
        got = p.batch_at(10)  # seek
        np.testing.assert_allclose(np.asarray(got['x']),
                                   np.asarray(s.batch_at(10)['x']))
    finally:
        p.close()


def test_ae_stream_range():
    b = AEStream(batch=3).batch_at(0)
    x = np.asarray(b['x'])
    assert x.min() >= 0.0 and x.max() <= 1.0 and x.shape == (3, 784)


# ---------------------------------------------------------------------------
# Refresh-runtime state must checkpoint: resume at step s is bit-exact with
# an uninterrupted run, including a mid-interval phase (cached inverses +
# counters) and adaptive-policy state (drift snapshot).


def _sched_train(name, steps, tmp_path=None, save_at=None, sched=None,
                 **opt_kw):
    import jax.numpy as jnp

    from repro.core.registry import make_optimizer
    from repro.models import module as M
    from repro.models.simple import MLP, classifier_loss_fn
    from repro.train.step import init_opt_state, make_train_step

    stream = ClassStream(batch=32, dim=8, classes=3, seed=0)
    model = MLP([8, 16, 3])
    model.loss_fn = classifier_loss_fn(model)
    params = M.init_params(model.param_specs(), jax.random.PRNGKey(0))
    opt, capture = make_optimizer(name, lr=0.05, **opt_kw)
    taps_fn = (lambda p: model.make_taps(32, capture)) \
        if capture.needs_taps else None
    state = init_opt_state(model, opt, capture, params, stream.batch_at(0),
                           taps_fn=taps_fn, sched=sched)
    step = jax.jit(make_train_step(model, opt, capture, taps_fn=taps_fn,
                                   sched=sched))
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


def _assert_bit_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize('name,kw,save_at', [
    # save at step 4 = mid-interval for k=3 (last refresh at 3, cached
    # inverses + since-counter must survive the roundtrip)
    ('kfac', {'interval': 3}, 4),
    ('shampoo', {'interval': 2}, 3),
    # adaptive policy: the drift snapshot is part of the checkpoint
    ('eva', {}, 4),
])
def test_refresh_state_resume_bit_exact(tmp_path, name, kw, save_at):
    from repro.schedule.policy import adaptive

    if name == 'eva':
        kw = dict(kw, policy=adaptive(threshold=0.05))
    steps = 7
    p_ref, s_ref = _sched_train(name, steps, **kw)
    p_res, s_res = _sched_train(name, steps, tmp_path=tmp_path,
                                save_at=save_at, **kw)
    _assert_bit_equal(p_ref, p_res)
    _assert_bit_equal(s_ref, s_res)


@pytest.mark.parametrize('name,kw,save_at', [
    # onestep pipeline: the checkpoint lands at a step boundary with a
    # buffer IN FLIGHT (the stats exchanged at step save_at-1 not yet
    # applied, a mid-interval inverse age) — PipelineState must roundtrip
    ('kfac', {'interval': 3}, 4),
    ('eva', {}, 4),
])
def test_pipeline_state_resume_bit_exact(tmp_path, name, kw, save_at):
    from repro.schedule.runtime import RefreshRuntime

    rt = RefreshRuntime(pipeline='onestep')
    steps = 7
    p_ref, s_ref = _sched_train(name, steps, sched=rt, **kw)
    p_res, s_res = _sched_train(name, steps, tmp_path=tmp_path,
                                save_at=save_at, sched=rt, **kw)
    _assert_bit_equal(p_ref, p_res)
    _assert_bit_equal(s_ref, s_res)
