"""The compiled train step's share of the chip's bf16 peak while it runs on
the device: model FLOPs of the step's runs in the traced window over their
device time (the ``XLA Modules`` events of the program that took the most
device time there), summed over the chips used.  Every kernel of the step
runs inside those runs, so this bounds each kernel's share from above: a
kernel taken off the path leaves its own roofline silent, not this."""


def read(view):
    flops, took = 0.0, 0.0
    per_run = view.flops_per_token * view.cell.tokens_per_step / view.chips
    for mods in view.modules:
        runs: dict = {}
        for s in mods:
            if view.w0 <= s.start and s.end <= view.w1:
                runs.setdefault(s.name, []).append(s.end - s.start)
        if not runs:
            continue
        step = max(runs.values(), key=sum)
        flops += per_run * len(step)
        took += sum(step) / 1e9
    if took <= 0:
        return None
    return 100.0 * flops / (took * view.peaks['bf16_flops'])
