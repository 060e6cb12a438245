"""Device time of the attention kernel's launches per step of the traced
window: its forward, dQ and dK/dV kernels, found by kernel name (summed
over the chips used, then averaged).  A program without the kernel reads
nothing."""
from bench import trace

KERNELS = ('flash_fwd', 'flash_dq', 'flash_dkv')


def read(view):
    spans = [s for ops in view.ops for kernel in KERNELS
             for s in trace.kernel_spans(ops, kernel, view.w0, view.w1)]
    if not spans or not view.steps:
        return None
    total = sum(s.end - s.start for s in spans) / len(view.ops)
    return total / 1e6 / view.steps
