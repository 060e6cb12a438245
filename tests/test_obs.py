"""Unified telemetry layer (``repro.obs``): schema validation of every
record type, the run-scoped Recorder, span nesting, the straggler watchdog,
the golden-file report/diff contract, and the training loops' named phases
on the profiler's trace."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import metrics as comm_metrics
from repro.core import kv as kvlib
from repro.core.registry import make_optimizer
from repro.data.synthetic import ClassStream
from repro.models import module as M
from repro.models.simple import MLP, classifier_loss_fn
from repro.obs import events, report, spans
from repro.train.step import init_opt_state, make_train_step

DATA = Path(__file__).parent / 'data'
FIX_A = str(DATA / 'obs_fixture_a.jsonl')
FIX_B = str(DATA / 'obs_fixture_b.jsonl')


# ---------------------------------------------------------------------------
# Schema: one valid + one corrupted example per record type


VALID = {
    'step': {'step': 3, 'loss': 1.5, 'grad_norm': 0.2, 'step_time_s': 0.01,
             'refreshes': 2, 'refresh_since': 1, 'staleness': 1.0,
             'pipeline_lag': 1, 'pipeline_lag/stats': 1,
             'exchanged_mb_cum': 4.5},
    'refresh': {'step': 4, 'refreshes': 2, 'step_time_s': 0.02},
    'refresh_ownership': {'world': 4, 'owners': {'float32_4x8x8': [1, 1, 1, 1]}},
    'reshard': {'world_from': 4, 'world_to': 2, 'pipeline': 'drained',
                'source': 'checkpoint', 'step': 7, 'slices_total': 5,
                'slices_moved': 3},
    'comm_exchange': {'sites': {'stats/eva': {
        'traces': 1, 'bytes_per_call': 1024, 'codec': 'f32',
        'mode': 'psum'}}},
    'straggler': {'step': 9, 'step_time_s': 0.9, 'median_s': 0.01,
                  'factor': 3.0},
    'span': {'name': 'grad', 'ms': 12.5, 'step': 2, 'seq': 0, 'depth': 1,
             'parent': 'step'},
    'loop': {'steps': 6, 'overlapped': 5, 'drained': 1},
    'optimizer_plan': {'buckets': {
        'bfloat16_4x64x64': {'leaves': 3, 'stacked': True,
                             'g_bytes': 98304},
        'bfloat16_64x512': {'leaves': 1, 'stacked': False,
                            'g_bytes': 65536}}},
    'profile': {'step': 0, 'live_buffer_mb': 8.0, 'device_bytes_in_use': 123,
                'fns': {'grad': {'flops': 1}}},
    'bench': {'name': 'table5/x', 'us_per_call': 10.0, 'derived': 'a=1',
              'fields': {'a': '1'}},
}


@pytest.mark.parametrize('event', sorted(events.SCHEMAS))
def test_schema_accepts_valid_record(event):
    rec = {'event': event, 'v': events.SCHEMA_VERSION, **VALID[event]}
    assert events.validate_record(rec) == []


@pytest.mark.parametrize('event', sorted(events.SCHEMAS))
def test_schema_rejects_missing_required(event):
    required = [k for k, f in events.SCHEMAS[event].items() if f.required]
    assert required, event
    rec = {'event': event, **VALID[event]}
    del rec[required[0]]
    errs = events.validate_record(rec)
    assert any(required[0] in e for e in errs), errs


@pytest.mark.parametrize('event', sorted(events.SCHEMAS))
def test_schema_rejects_unknown_field_and_bad_type(event):
    rec = {'event': event, **VALID[event], 'not_a_field': 1}
    assert any('not_a_field' in e for e in events.validate_record(rec))
    required = [k for k, f in events.SCHEMAS[event].items() if f.required]
    bad = {'event': event, **VALID[event], required[0]: object}
    # an un-JSON-able junk value never matches any accepted type set
    bad[required[0]] = [[]] if event != 'comm_exchange' else 'oops'
    assert events.validate_record(bad), event


def test_schema_version_and_bool_rules():
    rec = {'event': 'refresh', 'v': events.SCHEMA_VERSION + 1,
           'step': 1, 'refreshes': 1}
    assert any('schema version' in e for e in events.validate_record(rec))
    # bool is an int subclass in Python but never a valid numeric field
    rec = {'event': 'refresh', 'step': True, 'refreshes': 1}
    assert events.validate_record(rec)


def test_legacy_envelope_less_step_records_validate():
    # pre-obs trainer lines had no 'event'/'v' — still valid step records
    legacy = {'step': 5, 'loss': 2.0, 'grad_norm': 0.1, 'step_time_s': 0.02}
    assert events.infer_event(legacy) == 'step'
    assert events.validate_record(legacy) == []


def test_site_validation_catches_corruption():
    rec = {'event': 'comm_exchange',
           'sites': {'stats/eva': {'bytes_per_call': 'lots',
                                   'codec': 'f32'}}}
    errs = events.validate_record(rec)
    assert any('bytes_per_call' in e for e in errs)      # wrong type
    assert any("missing required field 'mode'" in e for e in errs)
    # the pod gather extras are typed: pods is the (n_pods, pod_size) pair
    ok = {'event': 'comm_exchange',
          'sites': {'refresh/kfac': {'bytes_per_call': 8, 'codec': 'f32',
                                     'mode': 'gather-pod', 'pods': [2, 2],
                                     'ici_bytes': 6, 'dcn_bytes': 2}}}
    assert events.validate_record(ok) == []


def test_bucket_validation_catches_corruption():
    rec = {'event': 'optimizer_plan',
           'buckets': {'bfloat16_64x512': {'leaves': 1, 'stacked': 1}}}
    errs = events.validate_record(rec)
    assert any('stacked' in e for e in errs)             # an int, not a bool
    assert any("missing required field 'g_bytes'" in e for e in errs)
    # a bool is still no count
    rec['buckets']['bfloat16_64x512'] = {'leaves': True, 'stacked': False,
                                         'g_bytes': 8}
    assert any('leaves' in e for e in events.validate_record(rec))


def test_plan_fields_count_each_bucket_of_a_plan():
    from repro.core import bucketing
    flat = {'a/w': jnp.zeros((4, 64, 64), jnp.bfloat16),
            'b/w': jnp.zeros((4, 64, 64), jnp.bfloat16),
            'c/w': jnp.zeros((4, 64, 64), jnp.bfloat16),
            'head/w': jnp.zeros((64, 512), jnp.bfloat16)}
    body = events.plan_fields(bucketing.build_plan(flat))
    assert body == VALID['optimizer_plan']
    assert events.validate_record({'event': 'optimizer_plan', **body}) == []


# ---------------------------------------------------------------------------
# Recorder


def test_recorder_writes_validates_and_scopes(tmp_path):
    path = tmp_path / 'metrics.jsonl'
    with events.Recorder(path) as rec:
        comm_metrics.record('stats/test_obs', bytes_per_call=64,
                            codec='f32', mode='local')
        rec.emit('refresh', step=1, refreshes=1)
        with pytest.raises(events.SchemaError):
            rec.emit('refresh', step=1)                  # missing required
        with pytest.raises(events.SchemaError):
            rec.emit('no_such_event', x=1)
        # the recorder's comm scope saw the site traced while it was open
        assert rec.comm_sites()['stats/test_obs']['bytes_per_call'] == 64
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert lines == [{'event': 'refresh', 'v': events.SCHEMA_VERSION,
                      'step': 1, 'refreshes': 1}]
    # a recorder opened after the trace does NOT see the old site...
    with events.Recorder(None) as rec2:
        assert 'stats/test_obs' not in rec2.comm_sites()
    # ...but the process-global table still has it (roofline contract)
    assert comm_metrics.snapshot()['stats/test_obs']['traces'] == 1


# ---------------------------------------------------------------------------
# Spans + watchdog


def test_span_nesting_order_and_fence(monkeypatch):
    """Spans nest and close in order, and none waits on the device: the
    fence of the old phased profile is gone."""
    def no_wait(*a, **k):
        raise AssertionError('a span waited on the device')

    monkeypatch.setattr(jax, 'block_until_ready', no_wait)
    clock = iter(range(100))
    rec = events.Recorder(None)
    tracker = spans.SpanTracker(rec, clock=lambda: float(next(clock)))
    with tracker.span('step', step=2):
        with tracker.span('dispatch', step=2):
            pending = jnp.ones((2, 2)) * 2          # enqueued, not awaited
        with tracker.span('host', step=2):
            pass
    names = [r['name'] for r in tracker.records]
    assert names == ['dispatch', 'host', 'step']         # closed-in order
    by = {r['name']: r for r in tracker.records}
    assert by['dispatch']['depth'] == 1 and by['dispatch']['parent'] == 'step'
    assert by['step']['depth'] == 0 and by['step']['parent'] is None
    assert [r['seq'] for r in tracker.records] == [0, 1, 2]
    assert all(r['step'] == 2 for r in tracker.records)
    assert all(events.validate_record({'event': 'span', **r}) == []
               for r in tracker.records)
    assert [r['name'] for r in rec.records] == names
    assert by['dispatch']['ms'] == 1000.0               # one clock tick
    assert float(pending[0, 0]) == 2.0


def test_span_tracker_keeps_nothing_without_a_recorder():
    """The default mode's tracker only annotates the profiler's trace."""
    tracker = spans.SpanTracker()
    for step in range(3):
        with tracker.span('data', step=step):
            with tracker.span('dispatch', step=step):
                pass
    assert tracker.records == []


def test_straggler_watchdog_flags_injected_slow_step():
    rec = events.Recorder(None)
    dog = spans.StragglerWatchdog(factor=3.0, recorder=rec, min_history=8)
    for i in range(7):
        assert not dog.observe(i, 0.010)     # below min_history: never fires
    assert not dog.observe(7, 0.012)
    assert dog.observe(8, 0.100)             # 10x the median
    flag = rec.records[-1]
    assert flag['event'] == 'straggler' and flag['step'] == 8
    assert flag['step_time_s'] == pytest.approx(0.1)
    assert events.validate_record(flag) == []
    assert not dog.observe(9, 0.011)


# ---------------------------------------------------------------------------
# Golden-file report contract (checked-in fixtures; B is A +15% slower)


def test_breakdown_golden_numbers():
    bd = report.breakdown(report.load_records(FIX_A))
    assert bd['n_step_records'] == 6 and bd['step_range'] == (0, 10)
    # warm mean drops the (compile) first step: [18,24,18,24,16] -> 20.0
    assert bd['mean_step_ms'] == pytest.approx(20.0)
    # spans: the step-0 (compile) spans are dropped from phase means
    assert bd['phases']['grad']['mean_ms'] == pytest.approx(12.0)
    assert bd['phases']['step']['mean_ms'] == pytest.approx(18.0)
    # refresh differential: firing [24,24] vs cached [18,18,16]
    r = bd['refresh']
    assert r['count'] == 2
    assert r['extra_ms_per_refresh'] == pytest.approx(24.0 - 52 / 3)
    assert r['amortized_ms_per_step'] == pytest.approx(
        r['extra_ms_per_refresh'] * 2 / 5)
    # exchange split: per-step vs per-refresh sites, ICI/DCN byte split
    ex = bd['exchange']
    assert ex['step_bytes'] == 1048576 and ex['refresh_bytes'] == 2097152
    assert ex['ici_bytes'] == 1572864 and ex['dcn_bytes'] == 524288
    assert bd['ownership']['world'] == 4
    # HLO costs merge forward from the step-0 one-shot profile record
    assert bd['profile']['step'] == 10
    assert bd['profile']['fns']['grad']['flops'] == 1000000


def test_render_contains_breakdown_sections():
    text = report.render(report.breakdown(report.load_records(FIX_A)), 'A')
    assert 'mean step time: 20.00 ms' in text
    assert 'stats/kfac' in text and 'refresh/kfac' in text
    assert 'ici 1.50 MiB / dcn 0.50 MiB' in text
    assert 'refresh ownership (world=4' in text
    assert 'grad' in text and 'GFLOP' in text


def test_diff_gates_on_mean_step_time():
    bd_a = report.breakdown(report.load_records(FIX_A))
    bd_b = report.breakdown(report.load_records(FIX_B))
    text, worst = report.diff(bd_a, bd_b)
    assert worst == pytest.approx(15.0)
    assert '[gate]' in text and '+15.0%' in text


def test_cli_exit_codes(capsys):
    assert report.main([FIX_A, FIX_B, '--validate']) == 0
    assert report.main([FIX_A, FIX_B, '--diff', '--max-regress', '20']) == 0
    assert report.main([FIX_A, FIX_B, '--diff', '--max-regress', '10']) == 2
    capsys.readouterr()


def test_cli_validate_catches_corruption(tmp_path, capsys):
    bad = tmp_path / 'metrics.jsonl'
    bad.write_text('{"event": "step", "loss": 1.0}\n'     # missing step
                   'not json at all\n'
                   '{"event": "wat", "x": 1}\n')
    assert report.main([str(bad), '--validate']) == 1
    out = capsys.readouterr().out
    assert '3 schema error' in out


def test_bench_rows_load_and_gate(tmp_path, capsys):
    def bench(path, us):
        rows = [{'event': 'bench', 'v': events.SCHEMA_VERSION,
                 'name': 'cell/x', 'us_per_call': us, 'derived': 'n=1'}]
        Path(path).write_text(json.dumps(rows))
    a, b = tmp_path / 'a.json', tmp_path / 'b.json'
    bench(a, 100.0)
    bench(b, 140.0)
    assert report.main([str(a), str(b), '--validate']) == 0
    assert report.main([str(a), str(b), '--diff', '--max-regress', '50']) == 0
    assert report.main([str(a), str(b), '--diff', '--max-regress', '25']) == 2
    capsys.readouterr()


# ---------------------------------------------------------------------------
# Trainer profile mode end-to-end (tiny MLP, CPU-fast)


def _mlp_trainer(tmp_path, optimizer='eva', **cfg_kw):
    from repro.train import Trainer, TrainerConfig
    stream = ClassStream(batch=16, dim=8, classes=4, spread=1.5, seed=0)
    model = MLP([8, 16, 4])
    model.loss_fn = classifier_loss_fn(model)
    params = M.init_params(model.param_specs(), jax.random.PRNGKey(0))
    opt, capture = make_optimizer(optimizer, lr=0.05)
    taps_fn = (lambda p: model.make_taps(16, capture)) \
        if capture.needs_taps else None
    cfg = TrainerConfig(total_steps=3, log_every=1, ckpt_every=0,
                        out_dir=str(tmp_path / 'run'), **cfg_kw)
    return Trainer(model, opt, capture, cfg, taps_fn=taps_fn), params, stream


def _by_event(tmp_path):
    recs = report.load_records(str(tmp_path / 'run' / 'metrics.jsonl'))
    assert report.validate_records(recs) == []
    by_event = {}
    for r in recs:
        by_event.setdefault(events.infer_event(r), []).append(r)
    return by_event


def test_trainer_profile_mode_emits_valid_telemetry(tmp_path):
    """Profile mode runs the production step, donated, in the same loop,
    and adds the loop's span records and the step's profile samples."""
    tr, params, stream = _mlp_trainer(tmp_path, profile=True)
    fed = []

    def spy(p, s, b):
        fed.append(jax.tree_util.tree_leaves(p)[0])
        return jitted(p, s, b)

    jitted, tr.step_fn = tr.step_fn, spy
    spy.lower = jitted.lower
    tr.fit(params, stream)
    assert len(fed) == 3 and all(x.is_deleted() for x in fed)  # donated

    by_event = _by_event(tmp_path)
    assert len(by_event['step']) == 3
    names = [s['name'] for s in by_event['span']]
    # a profile-mode log step (here every step) drains: its own wait and
    # host follow its dispatch, before the next step's data
    assert names == ['data', 'dispatch', 'wait', 'host'] * 3
    assert [s['step'] for s in by_event['span']] == [0] * 4 + [1] * 4 + [2] * 4
    assert [(r['overlapped'], r['drained']) for r in by_event['loop']] == [
        (0, 3)]
    assert by_event['profile'], 'profile mode must emit profile records'
    assert [list(p.get('fns', {})) for p in by_event['profile']] == [
        ['train_step'], [], []]
    text = report.render(report.breakdown(report.load_records(
        str(tmp_path / 'run' / 'metrics.jsonl'))))
    table = text.split('phase ')[1].split('\n\n')[0].splitlines()[1:]
    assert [ln.split()[0] for ln in table] == [
        'data', 'dispatch', 'wait', 'refresh', 'exchange', 'host']
    shares = [float(ln.split()[2].rstrip('%')) for ln in table
              if ln.split()[0] in ('data', 'dispatch', 'wait', 'host')]
    assert sum(shares) == pytest.approx(100.0, abs=0.5)
    # eva exchanges its KV stats every step — the site must be attributed
    assert any('stats/eva' in r['sites'] for r in by_event['comm_exchange'])
    # the step record is a superset of the legacy fields
    step0 = by_event['step'][0]
    assert {'step', 'loss', 'grad_norm', 'step_time_s'} <= set(step0)


@pytest.mark.parametrize('optimizer', ['eva', 'sgd'])
def test_fit_records_the_optimizer_plan_once_per_call(tmp_path, optimizer):
    """Each ``fit`` call records the optimizer's bucket plan once, before
    its first step: every preconditioned leaf in one bucket, with the
    bytes of its gradient.  A first-order optimizer has no plan."""
    tr, params, stream = _mlp_trainer(tmp_path, optimizer=optimizer)
    params, state, _ = tr.fit(params, stream)
    tr.cfg.total_steps = 5
    tr.fit(params, stream, start_step=3, opt_state=state)
    recs = report.load_records(str(tmp_path / 'run' / 'metrics.jsonl'))
    assert report.validate_records(recs) == []
    kinds = [events.infer_event(r) for r in recs]
    plans = [r for r in recs if r['event'] == 'optimizer_plan']
    if optimizer == 'sgd':
        assert plans == [] and kinds.count('loop') == 2
        return
    # one call's records end at its loop record
    ends = [i for i, k in enumerate(kinds) if k == 'loop']
    for a, b in zip([0] + [e + 1 for e in ends], ends):
        call = kinds[a:b]
        assert call.count('optimizer_plan') == 1
        assert call.index('optimizer_plan') < call.index('step')
    assert plans[0] == plans[1]
    precon = {p: x for p, x in kvlib.flatten_params(params).items()
              if x.ndim == 2}
    assert sum(b['leaves'] for b in plans[0]['buckets'].values()) == \
        len(precon)
    assert sum(b['g_bytes'] for b in plans[0]['buckets'].values()) == \
        sum(x.size * x.dtype.itemsize for x in precon.values())


def _host_events(trace_dir) -> list:
    """(name, start, end, stats) of every host event of a profiler capture
    whose name is ``train`` or ``drain`` or starts ``train.``."""
    path = sorted(Path(trace_dir).glob('**/*.xplane.pb'))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith('/host'):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in ('train', 'drain') \
                        or e.name.startswith('train.'):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                {k: v for k, v in e.stats}))
    return sorted(out, key=lambda e: e[1])


@pytest.mark.parametrize('loop', ['fit', 'fit_elastic'])
def test_loop_names_its_phases_on_the_profiler_trace(tmp_path, loop):
    """Under any jax.profiler capture each step of either loop is a
    ``train`` step event holding train.data and train.dispatch of its step,
    then train.wait and train.host: in ``fit_elastic`` of the same step, in
    ``fit`` of the step before (none at the first), the last step's in a
    ``drain`` event after it.  Each span carries the step it belongs to;
    without profile mode nothing is recorded beside the trace."""
    tr, params, stream = _mlp_trainer(tmp_path)
    with jax.profiler.trace(str(tmp_path / 'trace')):
        getattr(tr, loop)(params, stream)
    evs = _host_events(tmp_path / 'trace')
    steps = [e for e in evs if e[0] == 'train']
    assert [int(e[3]['step_num']) for e in steps] == [0, 1, 2]
    drains = [e for e in evs if e[0] == 'drain']
    if loop == 'fit':
        assert [int(e[3]['step']) for e in drains] == [2]
    else:
        assert drains == []
    for i, (_, t0, t1, _) in enumerate(steps + drains):
        inside = [e for e in evs if e[0] not in ('train', 'drain')
                  and t0 <= e[1] and e[2] <= t1]
        if loop == 'fit_elastic':
            want = [('train.data', i), ('train.dispatch', i),
                    ('train.wait', i), ('train.host', i)]
        elif i == len(steps):       # the drain
            want = [('train.wait', 2), ('train.host', 2)]
        else:
            want = [('train.data', i), ('train.dispatch', i)]
            if i:
                want += [('train.wait', i - 1), ('train.host', i - 1)]
        assert [(e[0], int(e[3]['step'])) for e in inside] == want
    assert 'span' not in _by_event(tmp_path)


# ---------------------------------------------------------------------------
# K-FAC scan-stacked capture regression (the bug this PR fixed: the vector-
# tap fallback collapsed scan lead dims into the token axis, so the stacked
# b_outer lost the path dim and the refresh cond branches disagreed)


def test_kfac_full_taps_keep_scan_lead_dims():
    from repro.configs.registry import demo_lm
    from repro.models import build_model
    from repro.train.step import compute_grads_and_stats
    cfg = demo_lm('small')
    model = build_model(cfg)
    params = M.init_params(model.param_specs(), jax.random.PRNGKey(0))
    _, capture = make_optimizer('kfac', lr=0.05)
    paths = set(model.precon_paths()) & set(kvlib.flatten_params(params))
    batch_shape = (2, 8)
    taps_fn = lambda p: kvlib.make_full_taps(p, paths, batch_shape)
    from repro.data.synthetic import LMStream
    batch = LMStream(vocab=cfg.vocab, seq_len=8, batch=2,
                     seed=0).batch_at(0)

    def stats_of(p):
        return compute_grads_and_stats(model, p, batch, capture,
                                       taps_fn(p))[2]

    shapes = jax.eval_shape(stats_of, params)
    flat = kvlib.flatten_params(params)
    for path, st in shapes.items():
        lead = flat[path].shape[:-2]
        d_out = flat[path].shape[-1]
        # b_outer must keep the scan path dims in front, matching a_outer
        assert st.b_outer.shape == lead + (d_out, d_out), path
        assert st.a_outer.shape[:-2] == lead, path


def test_kfac_scan_stacked_step_runs():
    from repro.configs.registry import demo_lm
    from repro.models import build_model
    cfg = demo_lm('small')
    model = build_model(cfg)
    params = M.init_params(model.param_specs(), jax.random.PRNGKey(0))
    opt, capture = make_optimizer('kfac', lr=0.05)
    paths = set(model.precon_paths()) & set(kvlib.flatten_params(params))
    taps_fn = lambda p: kvlib.make_full_taps(p, paths, (2, 8))
    from repro.data.synthetic import LMStream
    batch = LMStream(vocab=cfg.vocab, seq_len=8, batch=2,
                     seed=0).batch_at(0)
    state = init_opt_state(model, opt, capture, params, batch,
                           taps_fn=taps_fn)
    step = jax.jit(make_train_step(model, opt, capture, taps_fn=taps_fn))
    for _ in range(2):
        params, state, m = step(params, state, batch)
    assert np.isfinite(float(m['loss']))
