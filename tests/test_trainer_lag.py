"""``Trainer.fit`` reads each step back one step late: the same trajectory
and records as a synchronous loop, step n+1 dispatched before step n's
read-back wherever the host does not need step n's state, and a ``loop``
record that counts both kinds of read-back exactly."""
import jax
import numpy as np
import pytest

from repro.core.registry import make_optimizer
from repro.data.synthetic import ClassStream
from repro.models import module as M
from repro.models.simple import MLP, classifier_loss_fn
from repro.obs import report
from repro.train import checkpoint as ckpt
from repro.train.step import init_opt_state
from repro.train.trainer import Trainer, TrainerConfig

STEPS = 6


def _trainer(tmp_path, opt_name='eva', opt_kw=None, **cfg_kw):
    stream = ClassStream(batch=16, dim=8, classes=4, spread=1.5, seed=0)
    model = MLP([8, 16, 4])
    model.loss_fn = classifier_loss_fn(model)
    params = M.init_params(model.param_specs(), jax.random.PRNGKey(0))
    opt, capture = make_optimizer(opt_name, lr=0.05, **(opt_kw or {}))
    taps_fn = (lambda p: model.make_taps(16, capture)) \
        if capture.needs_taps else None
    cfg = TrainerConfig(**{'total_steps': STEPS, 'log_every': 10 ** 6,
                           'ckpt_every': 0, 'out_dir': str(tmp_path),
                           **cfg_kw})
    return Trainer(model, opt, capture, cfg, taps_fn=taps_fn), params, stream


def _records(tr, event):
    return [r for r in report.load_records(str(tr.metrics_path))
            if r.get('event') == event]


def _copy(tree):
    return jax.tree_util.tree_map(
        lambda x: x + 0 if hasattr(x, 'dtype') else x, tree)


def _host(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _assert_same(a, b):
    la, lb = _host(a), _host(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and np.array_equal(x, y)


def _sync_loop(tr, params, data, steps):
    """The plain loop: each step's loss read back before the next
    dispatch, over the trainer's own jitted step.  Returns the final
    state, the losses and the state after every step, on the host."""
    p = _copy(params)
    s = _copy(init_opt_state(tr.model, tr.opt, tr.capture, params,
                             data.batch_at(0), taps_fn=tr.taps_fn,
                             sched=tr.sched))
    losses, after = [], []
    for step in range(steps):
        p, s, m = tr.step_fn(p, s, data.batch_at(step))
        losses.append(float(m['loss']))
        after.append((_host(p), _host(s)))
    return p, s, losses, after


@pytest.mark.parametrize('case', ['plain', 'ckpt_every=2', 'profile'])
def test_fit_is_bit_identical_to_a_synchronous_loop(tmp_path, case):
    kw = {'plain': {}, 'ckpt_every=2': {'ckpt_every': 2},
          'profile': {'profile': True, 'log_every': 2}}[case]
    tr, params, data = _trainer(tmp_path, **kw)
    p, s, hist = tr.fit(params, data, resume=False)
    p_ref, s_ref, h_ref, after = _sync_loop(tr, params, data, STEPS)
    assert hist == h_ref
    _assert_same(p, p_ref)
    _assert_same(s, s_ref)
    if case == 'ckpt_every=2':
        assert ckpt.available_steps(tmp_path / 'ckpt') == [2, 4, 6]
        for step in (2, 4, 6):
            tree, meta = ckpt.restore(tmp_path / 'ckpt', step,
                                      {'params': p, 'opt_state': s})
            assert meta['next_step'] == step
            want_p, want_s = after[step - 1]
            for x, y in zip(_host(tree['params']) + _host(tree['opt_state']),
                            want_p + want_s):
                assert np.array_equal(x, y)


class _ReadBack:
    """A loss that logs when the loop reads it to the host."""

    def __init__(self, value, step, log):
        self.value, self.step, self.log = value, step, log

    def __array__(self, dtype=None, copy=None):
        self.log.append(('read', self.step))
        return np.asarray(self.value)


def _spied(tr, log):
    jitted = tr.step_fn
    n = {'step': 0}

    def spy(params, opt_state, batch):
        step = n['step']
        n['step'] += 1
        log.append(('dispatch', step))
        params, opt_state, metrics = jitted(params, opt_state, batch)
        return params, opt_state, {**metrics,
                                   'loss': _ReadBack(metrics['loss'], step,
                                                     log)}

    spy.lower = jitted.lower
    tr.step_fn = spy


@pytest.mark.parametrize('kw, drained', [
    ({}, {5}),
    ({'ckpt_every': 3}, {2, 5}),
    ({'profile': True, 'log_every': 2}, {0, 2, 4, 5}),
    ({'log_every': 2}, {5}),
])
def test_next_dispatch_precedes_each_read_back(tmp_path, kw, drained):
    """Step n+1 is dispatched before step n is read back, except where
    step n drains; every step is read back once, in order; the ``loop``
    record counts both kinds exactly."""
    tr, params, data = _trainer(tmp_path, **kw)
    log = []
    _spied(tr, log)
    _, _, hist = tr.fit(params, data, resume=False)
    assert len(hist) == STEPS
    reads = [s for kind, s in log if kind == 'read']
    assert reads == list(range(STEPS))
    for step in range(STEPS - 1):
        before = log.index(('dispatch', step + 1)) < log.index(('read', step))
        assert before == (step not in drained), (step, log)
    assert _records(tr, 'loop') == [{
        'event': 'loop', 'v': 1, 'steps': STEPS,
        'overlapped': STEPS - len(drained), 'drained': len(drained)}]
    text = report.render(report.breakdown(report.load_records(
        str(tr.metrics_path))))
    assert (f'read-backs overlapped with the next step: '
            f'{STEPS - len(drained)} of {STEPS}') in text
    assert len(tr._watchdog.times) == STEPS
    assert all(dt > 0 for dt in tr._watchdog.times)


def test_preemption_drains_the_step_it_lands_in(tmp_path):
    """A preemption flag raised during step 3's dispatch drains step 3,
    checkpoints at 4 and stops: no step 4 is dispatched."""
    tr, params, data = _trainer(tmp_path, total_steps=100)
    log = []
    _spied(tr, log)
    inner, calls = tr.step_fn, []

    def flagging(*a):
        calls.append(1)
        if len(calls) == 4:
            tr._preempted = True  # simulate SIGTERM delivery
        return inner(*a)

    tr.step_fn = flagging
    _, _, hist = tr.fit(params, data, resume=False)
    assert len(hist) == 4
    assert max(s for kind, s in log if kind == 'dispatch') == 3
    assert ckpt.latest_step(tmp_path / 'ckpt') == 4
    assert _records(tr, 'loop')[-1]['drained'] == 1
    assert _records(tr, 'loop')[-1]['overlapped'] == 3


def test_records_match_a_fully_drained_run(tmp_path):
    """Step and refresh records keep their steps and fields: a lagged run
    writes what a run that drains every step (``ckpt_every=1``) writes,
    up to the step times."""
    def run(sub, **kw):
        tr, params, data = _trainer(tmp_path / sub, opt_name='kfac',
                                    opt_kw={'interval': 2}, log_every=1,
                                    **kw)
        tr.fit(params, data, resume=False)
        out = {}
        for ev in ('step', 'refresh'):
            out[ev] = [{k: v for k, v in r.items() if k != 'step_time_s'}
                       for r in _records(tr, ev)]
        return out, _records(tr, 'loop')[0]

    lagged, loop_l = run('lagged')
    drained, loop_d = run('drained', ckpt_every=1)
    assert lagged == drained
    assert [r['step'] for r in lagged['step']] == list(range(STEPS))
    assert [r['step'] for r in lagged['refresh']] == [0, 2, 4]
    assert (loop_l['overlapped'], loop_d['overlapped']) == (STEPS - 1, 0)
