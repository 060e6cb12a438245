"""The reduction from a profiler trace to the per-layer metrics, against a
small trace in the TPU profiler's layout (``fixtures/trace_small.pbtxt``)
whose numbers are worked out by hand in the fixture's header."""
import math
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).resolve().parent / 'fixtures' / 'trace_small.pbtxt'
DEV = '/device:TPU:0'


@pytest.fixture
def small_trace(tmp_path):
    from jax._src.profiler import ProfileData
    from bench import trace
    path = tmp_path / 'small.xplane.pb'
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        FIXTURE.read_text()))
    return trace.load(str(path))


def test_window_from_the_loops_first_request(small_trace):
    """The window opens at the request for its second step: the first holds
    fit's second request for its first batch."""
    from bench import trace
    assert trace.window(small_trace) == (10000.0, 100000.0)


def test_busy_gaps_and_idle_share(small_trace):
    from bench import trace
    ops = small_trace.ops[DEV]
    w0, w1 = trace.window(small_trace)
    # the op before the window is left out; the loop and its body count once
    assert trace.busy_ns(ops, w0, w1) == 60000.0
    assert trace.gaps(ops, w0, w1) == [(10000.0, 12000.0),
                                       (42000.0, 58000.0),
                                       (88000.0, 100000.0)]
    labels = [trace.host_label(small_trace, a, b)
              for a, b in trace.gaps(ops, w0, w1)]
    assert labels == ['host: waiting for the next batch',
                      'host: waiting for the next batch',
                      'host: step loop (dispatch, loss readback)']


def test_self_times_exclude_nested_ops(small_trace):
    from bench import trace
    w0, w1 = trace.window(small_trace)
    assert trace.self_times(small_trace.ops[DEV], w0, w1) == {
        'while.1': 2000.0, 'fusion.2': 8000.0,
        'eva_fused_stacked.5': 20000.0, 'convolution.3': 30000.0}


def test_kernel_found_by_name(small_trace):
    from bench import trace
    w0, w1 = trace.window(small_trace)
    spans = trace.kernel_spans(small_trace.ops[DEV], 'eva_fused_stacked',
                               w0, w1)
    assert [(s.start, s.end) for s in spans] == [(20000.0, 40000.0)]
    assert trace.kernel_spans(small_trace.ops[DEV], 'eva_fused', w0, w1) == []


def _view(small_trace, **kw):
    from bench import harness, trace
    w0, w1 = trace.window(small_trace)
    cell = harness.load_cell('qwen2-0.5b.eva-fused.b1s2048')
    args = dict(cell=cell, peaks=harness.read_peaks('TPU v5 lite'), chips=1,
                steps=2, window_s=(w1 - w0) / 1e9,
                busy_s=trace.busy_ns(small_trace.ops[DEV], w0, w1) / 1e9,
                ops=[small_trace.ops[DEV]],
                modules=[small_trace.modules[DEV]], w0=w0, w1=w1,
                data_wait_s=[0.002, 0.013], hbm_peak_bytes=6_000_000_000,
                flops_per_token=1.0e3)
    args.update(kw)
    return cell, harness.View(**args)


def test_metric_readers_on_the_small_trace(small_trace):
    from bench.kernels import eva_fused
    cell, view = _view(small_trace)
    read = {m['name']: cell.metric_reader(m['name']).read(view)
            for m in cell.per_layer}
    assert read['device_idle_share'] == pytest.approx(100 * 30 / 90)
    assert read['hbm_peak_gb'] == 6.0
    assert read['data_wait_ms'] == pytest.approx(7.5)
    # two runs of the step inside the window, 30 us each; the run of the
    # other program before the window is left out
    assert read['step_mfu'] == pytest.approx(
        100 * 1.0e3 * 2048 * 2 / (60e-6 * 197e12))
    assert read['eva_fused_ms_per_step'] == pytest.approx(0.01)
    flops, nbytes = eva_fused.cost(4, 256, 128, 2)
    least = max(flops / 197e12, nbytes / 819e9)
    assert nbytes / 819e9 > flops / 197e12          # bound by bytes
    assert read['eva_fused_roofline'] == pytest.approx(100 * least / 20e-6)


def test_readers_return_nothing_when_nothing_to_read(small_trace):
    cell, view = _view(small_trace, ops=[[]], modules=[[]],
                       hbm_peak_bytes=None, data_wait_s=[])
    for name in ('eva_fused_ms_per_step', 'eva_fused_roofline',
                 'hbm_peak_gb', 'data_wait_ms', 'step_mfu'):
        assert cell.metric_reader(name).read(view) is None


def test_peaks_table_refuses_an_unknown_device():
    from bench import harness
    assert harness.read_peaks('TPU v5 lite')['bf16_flops'] == 197e12
    with pytest.raises(SystemExit):
        harness.read_peaks('TPU v9 imaginary')
    assert math.isfinite(harness.read_peaks('TPU v5 lite')['hbm_bytes_per_s'])
