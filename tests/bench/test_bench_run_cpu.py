"""A whole run of the harness on the CPU at a test's size, with the look
for a chip skipped: the timed path drives the program's trainer, nothing
compiles inside the window, and the comparison with the reference passes;
the fp8 control put in the program's place does not.  The same for a cell
trained by SGD, whose reference is another file under bench/optimizers."""
import json
import math
import time
from pathlib import Path

FIXTURES = Path(__file__).resolve().parent / 'fixtures'
SEED = json.loads((FIXTURES / 'tiny_cell.json').read_text())['seed']


def test_sound_run_is_correct(tiny_cell, tmp_path):
    from bench import harness
    lines = []
    result = harness.run_cell(tiny_cell, SEED, 0.5, False,
                              time.perf_counter(), require_tpu=False,
                              work_dir=tmp_path, log=lines.append)
    assert result['correct'] is True, result['checks']
    assert result['failed'] == 0 and result['attempted'] >= 2
    assert 'compilations in the window: 0' in lines
    assert list(result)[-1] == 'checks'
    assert set(result['metrics']) == {'tokens_per_s', 'mfu', 'step_ms_p90',
                                      'setup_s'}
    assert result['metrics']['tokens_per_s']['value'] > 0
    assert result['metrics']['setup_s']['value'] > 0
    for name, c in result['checks'].items():
        assert lines[-4:][list(result['checks']).index(name)] == \
            f'check {name} {c["value"]!r} limit {c["limit"]!r}'
    json.dumps(result)


def test_fp8_control_is_not_correct(tiny_cell, tmp_path):
    from bench import harness
    result = harness.run_cell(tiny_cell, SEED, 0.5, False,
                              time.perf_counter(), require_tpu=False,
                              work_dir=tmp_path, log=lambda s: None,
                              program_as_reference='fp8')
    assert result['correct'] is False
    over = [k for k, c in result['checks'].items() if c['value'] > c['limit']]
    assert over and all(math.isfinite(c['value'])
                        for c in result['checks'].values())


def test_sgd_cell_is_correct(tiny_sgd_cell, tmp_path):
    from bench import harness
    result = harness.run_cell(tiny_sgd_cell, SEED, 0.5, False,
                              time.perf_counter(), require_tpu=False,
                              work_dir=tmp_path, log=lambda s: None)
    assert result['correct'] is True, result['checks']
    assert result['metrics']['tokens_per_s']['value'] > 0


def test_sgd_fp8_control_is_not_correct(tiny_sgd_cell, tmp_path):
    from bench import harness
    result = harness.run_cell(tiny_sgd_cell, SEED, 0.5, False,
                              time.perf_counter(), require_tpu=False,
                              work_dir=tmp_path, log=lambda s: None,
                              program_as_reference='fp8')
    assert result['correct'] is False
