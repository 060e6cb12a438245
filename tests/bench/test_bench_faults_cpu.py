"""Each fault a one-chip training cell can have, planted under the timed
path of a whole harness run (look for a chip skipped): ``correct`` comes
out false -- also where the leaf that loses its update is a small one."""
import json
import time
from pathlib import Path

import pytest

FIXTURES = Path(__file__).resolve().parent / 'fixtures'
SEED = json.loads((FIXTURES / 'tiny_cell.json').read_text())['seed']


@pytest.mark.parametrize('fault', ['frozen', 'half_batch', 'dropped_leaf',
                                   'dropped_scale'])
def test_planted_fault_is_not_correct(fault, tiny_cell, tmp_path):
    from bench import faults, harness
    result = harness.run_cell(tiny_cell, SEED, 0.3, False,
                              time.perf_counter(), require_tpu=False,
                              work_dir=tmp_path, log=lambda s: None,
                              fault=faults.FAULTS[fault])
    assert result['correct'] is False, result['checks']


def test_planted_fault_under_sgd_is_not_correct(tiny_sgd_cell, tmp_path):
    from bench import faults, harness
    result = harness.run_cell(tiny_sgd_cell, SEED, 0.3, False,
                              time.perf_counter(), require_tpu=False,
                              work_dir=tmp_path, log=lambda s: None,
                              fault=faults.FAULTS['half_batch'])
    assert result['correct'] is False, result['checks']
