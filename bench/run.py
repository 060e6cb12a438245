"""Run one benchmark cell and print its result as the last line of stdout.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it loads the cell, sets up the program's trainer, measures for
``--seconds`` (``--trace 1``: a shorter window under the profiler, which
reports the cell's per-layer metrics instead of its end-to-end ones),
checks what the timed path produced against the plain reference, and
prints one JSON object.  It needs a TPU with as many chips as the cell asks
for; without one it exits non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / 'src' / 'repro').is_dir():
        print(f'bench: no program sources at {ROOT / "src"}', file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT))
    from bench import harness

    cell = harness.load_cell(args.workload)
    import jax
    # the persistent compilation cache lives at a fixed path in the checkout
    jax.config.update('jax_compilation_cache_dir', str(ROOT / '.jax_cache'))
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_PROCESS)
    except harness.NoChip as e:
        print(f'bench: {e}', file=sys.stderr)
        return 3
    print(json.dumps(result))
    return 0


if __name__ == '__main__':
    sys.exit(main())
