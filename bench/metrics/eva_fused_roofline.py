"""``eva_fused``'s share of its roofline: the least time the chip could take
for the launches in the traced window (per launch, the larger of FLOPs over
peak FLOP/s and bytes over HBM bandwidth, from ``bench/kernels/eva_fused``)
over the time they took.  At 15 FLOPs per 10 bytes the bytes bound it."""
from bench import trace
from bench.kernels import eva_fused


def read(view):
    least, took = 0.0, 0.0
    for ops in view.ops:
        for s in trace.kernel_spans(ops, 'eva_fused_stacked', view.w0,
                                    view.w1):
            flops, nbytes = eva_fused.cost_of_event(s.name)
            least += max(flops / view.peaks['bf16_flops'],
                         nbytes / view.peaks['hbm_bytes_per_s'])
            took += (s.end - s.start) / 1e9
    if took <= 0:
        return None
    return 100.0 * least / took
