"""Device time of the ``eva_fused`` Pallas launches per step of the traced
window, found by kernel name (summed over the chips used, then averaged)."""
from bench import trace


def read(view):
    spans = [s for ops in view.ops
             for s in trace.kernel_spans(ops, 'eva_fused_stacked', view.w0,
                                         view.w1)]
    if not spans or not view.steps:
        return None
    total = sum(s.end - s.start for s in spans) / len(view.ops)
    return total / 1e6 / view.steps
