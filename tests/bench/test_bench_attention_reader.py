"""``attention_ms_per_step``: the attention kernel's forward, dQ and dK/dV
launches found by instruction name, per step of the traced window."""
import pytest


def _span(name, start, end, call='custom-call(%p.1), custom_call_target'):
    from bench import trace
    return trace.Span(f'%{name} = bf16[4,14,2048,64] {call}', start, end)


def _view(ops, steps=2):
    from bench import harness
    cell = harness.load_cell('qwen2-0.5b.eva.b4s2048')
    return cell, harness.View(
        cell=cell, peaks={}, chips=1, steps=steps, window_s=1e-4, busy_s=0.0,
        ops=[ops], modules=[[]], w0=10_000.0, w1=110_000.0, data_wait_s=[],
        hbm_peak_bytes=None, flops_per_token=1.0)


def test_reads_the_three_kernels_by_name():
    ops = [_span('flash_fwd.13', 10_000.0, 14_000.0),
           _span('flash_fwd.14', 20_000.0, 24_000.0),
           _span('flash_dq.10', 30_000.0, 36_000.0),
           _span('flash_dkv.10', 40_000.0, 48_000.0),
           # not the kernel: a fusion named after it, an op outside the
           # window, a kernel of another name
           _span('flash_fwd_fusion.2', 50_000.0, 90_000.0, 'fusion(%a)'),
           _span('flash_dq.11', 120_000.0, 130_000.0),
           _span('eva_fused_stacked.5', 60_000.0, 70_000.0)]
    cell, view = _view(ops)
    read = cell.metric_reader('attention_ms_per_step').read(view)
    assert read == pytest.approx((4 + 4 + 6 + 8) / 1e3 / 2)


def test_reads_nothing_without_the_kernel():
    cell, view = _view([_span('fusion.530', 10_000.0, 20_000.0, 'fusion(%a)')])
    assert cell.metric_reader('attention_ms_per_step').read(view) is None
