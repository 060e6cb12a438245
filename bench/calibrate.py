"""Readings that set a cell's correctness limits, many seeds in one process.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--faults half_batch,dropped_leaf] \
        [--fault-seeds 7,8,9]

For each seed: the program through the checked first steps of the timed
path (the same trainer for every seed), the plain reference, and the
numbers that the run compares.  The control is the reference at the
precision below the configuration's, put in the program's place; a fault is
the program with one planted (``bench/faults.py``).  One JSON line per
reading, with its widest leaves, then a summary: per number the largest
sound reading (the lower one), and the smallest of the control and of each
fault.  Exits non-zero without a TPU: limits come from the chip.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _seeds(s):
    return [int(x) for x in s.split(',') if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', type=_seeds, required=True)
    ap.add_argument('--control-seeds', type=_seeds, default=[])
    ap.add_argument('--faults', default='')
    ap.add_argument('--fault-seeds', type=_seeds, default=[])
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / 'src'))
    import jax
    from bench import compare, faults, harness
    from bench.traffic import TokenStream

    if jax.devices()[0].platform != 'tpu':
        print('calibrate: JAX finds no TPU', file=sys.stderr)
        return 3
    jax.config.update('jax_compilation_cache_dir', str(ROOT / '.jax_cache'))
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    cell = harness.load_cell(args.workload)
    out_dir = ROOT / '.bench' / 'runs' / cell.name
    reference = harness.reference(cell)
    streams, refs = {}, {}

    def stream_of(seed):
        if seed not in streams:
            streams.clear()
            streams[seed] = TokenStream.from_traffic(
                cell.traffic, cell.cfg['vocab'], seed)
        return streams[seed]

    def ref_of(seed):
        if seed not in refs:
            refs[seed] = harness.reference_run(cell, reference, seed,
                                               stream_of(seed))
        return refs[seed]

    from repro.data import Prefetcher

    def program_reading(program, seed):
        data = harness.TimedSource(Prefetcher(stream_of(seed)))
        try:
            prog, _ = harness.checked_steps(cell, program, data, seed)
        finally:
            data.inner.close()
        return prog

    summary = {}

    def emit(kind, seed, prog):
        ref = ref_of(seed)
        values = compare.readings(prog, ref)
        print(json.dumps({'kind': kind, 'seed': seed, 'readings': values,
                          'worst': compare.worst_leaves(prog, ref),
                          'losses': prog['losses']}), flush=True)
        summary.setdefault(kind, []).append(values)

    program = harness.Program(cell, out_dir)
    for seed in args.seeds:
        emit('program', seed, program_reading(program, seed))
    del program
    if args.control_seeds:
        control = harness.reference(cell, 'fp8')
        for seed in args.control_seeds:
            emit('control', seed, harness.reference_run(
                cell, control, seed, stream_of(seed)))
    for name in [f for f in args.faults.split(',') if f]:
        program = harness.Program(cell, out_dir, fault=faults.FAULTS[name])
        for seed in args.fault_seeds:
            emit(f'fault:{name}', seed, program_reading(program, seed))
        del program
    agg = {}
    for kind, rows in summary.items():
        pick = max if kind == 'program' else min
        agg[kind] = {k: pick(r[k] for r in rows) for k in rows[0]}
    print(json.dumps({'summary': agg}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
