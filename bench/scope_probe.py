"""One traced run of a cell (``bench/run.py --trace 1``), read by the parts
the program names: device time per named scope of the compiled step, the
training loop's ``train.*`` host spans, and what each long idle gap was.

    python3 bench/scope_probe.py --workload <name> --seed <n>

The harness hands its per-layer readers neither the compiled step's text
nor the program's host spans: its ``View`` has no field for them.  For this
one run the probe gives it both, as the harness would: it keeps
``compiled.as_text()`` from the compile that follows the window
(``_compiled_peak``), reads the ``train.*`` spans from the trace the
harness loads, passes them to ``View`` as ``op_scopes`` and ``program``,
labels idle gaps by the program's spans first (``scopes.program_label``),
and adds the readers of ``PROBED`` to the cell's per-layer metrics.  The
timed window, the checks and every other reading are the harness's own.
It prints the harness's result line with those metrics in it, and beside
it (``probe``) the device time of every scope, the scoped share of the
step's device time and the median traced step (from the ``bench.data``
request times).  Against a program without the names the new readers
read nothing.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]

# (name, unit, source, layer) of the readers under bench/metrics/ that read
# the program's names
PROBED = (('forward_ms_per_step', 'ms', 'device_trace', 'model forward'),
          ('backward_ms_per_step', 'ms', 'device_trace', 'model backward'),
          ('optimizer_ms_per_step', 'ms', 'device_trace', 'optimizer'),
          ('precondition_ms_per_step', 'ms', 'device_trace', 'optimizer'),
          ('dispatch_ms', 'ms', 'program_span', 'trainer loop'),
          ('loop_host_ms', 'ms', 'program_span', 'trainer loop'))


def probe(cell, seed: int, t_process: float, **run_kw) -> dict:
    """``harness.run_cell(cell, seed, ..., trace=True)`` with the compiled
    step's scopes and the program's spans handed to its readers."""
    import jax
    from bench import harness, scopes
    from bench import trace as tr

    kept: dict = {}
    compiled_peak, load = harness._compiled_peak, tr.load
    host_label, view_cls = tr.host_label, harness.View

    def keep_text(program, params, state, batch):
        spec = lambda t: jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), t)
        compiled = program.jitted_step.lower(
            spec(params), spec(state), spec(batch)).compile()
        kept['scopes'] = scopes.op_scopes(compiled.as_text())
        mem = compiled.memory_analysis()
        return None if mem is None else int(mem.peak_memory_in_bytes)

    def load_program_too(path):
        trace = load(path)
        kept['trace'] = trace
        kept['program'] = scopes.program_spans(path)
        return trace

    def label(trace, t0, t1):
        return (scopes.program_label(kept['program'], t0, t1)
                or host_label(trace, t0, t1))

    @dataclasses.dataclass
    class View(view_cls):
        op_scopes: dict = dataclasses.field(
            default_factory=lambda: kept.get('scopes', {}))
        program: list = dataclasses.field(
            default_factory=lambda: kept.get('program', []))

    cell = dataclasses.replace(cell, per_layer=list(cell.per_layer) + [
        {'name': n, 'unit': u, 'better': 'lower', 'source': s, 'layer': la,
         'moves': 'tokens_per_s'} for n, u, s, la in PROBED])
    harness._compiled_peak, tr.load = keep_text, load_program_too
    tr.host_label, harness.View = label, View
    try:
        result = harness.run_cell(cell, seed, harness.TRACE_SECONDS, True,
                                  t_process, **run_kw)
    finally:
        harness._compiled_peak, tr.load = compiled_peak, load
        tr.host_label, harness.View = host_label, view_cls
    result['probe'] = summary(kept)
    return result


def summary(kept: dict) -> dict:
    """Device ms per traced step by scope (chip 0), the scoped share of the
    step's device time, and the traced window's step times."""
    from bench import scopes
    from bench import trace as tr
    trace = kept['trace']
    w0, w1 = tr.window(trace)
    ops = trace.ops[sorted(trace.ops)[0]]
    starts = sorted(s.start for s in trace.host if s.name == 'bench.data'
                    and w0 <= s.start <= w1)
    steps = max(len(starts), 1)
    names = kept.get('scopes', {})
    times = scopes.scope_times(ops, w0, w1, names)
    total = sum(times.values())
    unscoped = sorted(((t, n) for n, t in tr.self_times(ops, w0, w1).items()
                       if scopes.scope_of(names.get(n)) == scopes.UNSCOPED),
                      reverse=True)[:10]
    return {
        'scope_ms_per_step': {k: v / 1e6 / steps
                              for k, v in sorted(times.items())},
        'scoped_share': (1.0 - times.get(scopes.UNSCOPED, 0.0) / total
                         if total else None),
        'steps': len(starts),
        'step_ms_median': 1e3 * statistics.median(
            (b - a) / 1e9 for a, b in zip(starts, starts[1:]))
        if len(starts) > 1 else None,
        'program_spans_ms': {
            n: statistics.fmean(ms) for n in
            ('train.data', 'train.dispatch', 'train.wait', 'train.host')
            if (ms := scopes.span_ms(kept['program'], n, w0, w1))},
        'unscoped_top': [[n, names.get(n), t / 1e6 / steps]
                         for t, n in unscoped],
        'gap_split_ms': gap_split(kept['program'], ops, w0, w1)}


def gap_split(spans, ops, w0: float, w1: float) -> dict:
    """The device's idle time in the traced window by the loop's span it
    falls in (ms per step); idle time inside ``train.wait`` is split into
    ``wait.head`` (before the step's first op: launch), ``wait.tail``
    (after its last op: the loss read-back) and ``wait.mid``."""
    from bench import trace as tr
    idle = tr.gaps(ops, w0, w1)
    out: dict = {}
    steps = 0
    for s in spans:
        if not s.name.startswith('train.') or not w0 <= s.start < w1:
            continue
        steps += s.name == 'train.wait'
        for a, b in idle:
            c = min(b, s.end) - max(a, s.start)
            if c <= 0:
                continue
            key = s.name
            if s.name == 'train.wait':
                key += ('.head' if a <= s.start else
                        '.tail' if b >= s.end else '.mid')
            out[key] = out.get(key, 0.0) + c
    covered = sum(out.values())
    out['outside spans'] = sum(b - a for a, b in idle) - covered
    return {k: v / 1e6 / max(steps, 1) for k, v in sorted(out.items())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import harness

    cell = harness.load_cell(args.workload)
    import jax
    jax.config.update('jax_compilation_cache_dir', str(ROOT / '.jax_cache'))
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    try:
        result = probe(cell, args.seed, T_PROCESS)
    except harness.NoChip as e:
        print(f'bench: {e}', file=sys.stderr)
        return 3
    print(json.dumps(result))
    return 0


if __name__ == '__main__':
    sys.exit(main())
