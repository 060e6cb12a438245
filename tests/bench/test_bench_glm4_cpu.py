"""A whole run of the harness on the CPU at a test's size, for a cell
shaped as glm4-9b-l4v8 is: its configuration file with the widths cut,
so the head stays untied, the attention has no QKV bias, and eight query
heads share one KV head.  The sound run passes the comparison with the
reference, with the untied head among the leaves that Eva preconditions
and moves; the fp8 control put in the program's place does not."""
import json
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / 'fixtures'
TINY = json.loads((FIXTURES / 'tiny_glm4_cell.json').read_text())
HEAD = 'lm_head/w'


@pytest.fixture
def tiny_glm4_cell():
    from bench import harness
    cfg = json.loads((ROOT / 'bench' / 'configs' / 'glm4-9b-l4v8.json')
                     .read_text())
    cfg.update(TINY['config'])
    traffic = json.loads((ROOT / 'bench' / 'traffic' / 'eva.s2048.json')
                         .read_text())
    traffic.update(TINY['traffic'])
    bench = json.loads((ROOT / 'BENCHMARK.json').read_text())
    return harness.Cell(name='tiny-glm4', chips=1, cfg=cfg, traffic=traffic,
                        limits=TINY['limits'],
                        end_to_end=bench['end_to_end'], per_layer=[])


def test_glm4_shaped_cell_keeps_what_the_share_forces(tiny_glm4_cell):
    cfg = tiny_glm4_cell.cfg
    assert cfg['tie_embeddings'] is False and cfg['qkv_bias'] is False
    assert cfg['n_heads'] // cfg['n_kv_heads'] > 2      # qwen2's tiny: 4/2
    assert cfg['n_heads'] * cfg['head_dim'] == cfg['d_model']


def test_glm4_sound_run_is_correct_and_moves_the_head(tiny_glm4_cell,
                                                      tmp_path, monkeypatch):
    from bench import compare, harness
    seen = []
    readings = compare.readings

    def spy(prog, ref):
        seen.append((prog, ref))
        return readings(prog, ref)

    monkeypatch.setattr(compare, 'readings', spy)
    result = harness.run_cell(tiny_glm4_cell, TINY['seed'], 0.5, False,
                              time.perf_counter(), require_tpu=False,
                              work_dir=tmp_path, log=lambda s: None)
    assert result['correct'] is True, result['checks']
    assert result['failed'] == 0 and result['attempted'] >= 2
    (prog, ref), = seen
    assert HEAD not in compare.worst_leaves(prog, ref)['left_out']
    assert prog['update1'][HEAD] > 0 and prog['change'][HEAD] > 0
    # Eva preconditions the head: its own bucket in the trainer's plan
    records = [json.loads(line) for line in
               (tmp_path / 'runs' / 'tiny-glm4' / 'metrics.jsonl')
               .read_text().splitlines()]
    plan = next(r for r in records if r['event'] == 'optimizer_plan')
    d, v = tiny_glm4_cell.cfg['d_model'], tiny_glm4_cell.cfg['vocab']
    assert plan['buckets'][f'bfloat16_{d}x{v}'] == {
        'leaves': 1, 'stacked': False, 'g_bytes': 2 * d * v}


def test_glm4_fp8_control_is_not_correct(tiny_glm4_cell, tmp_path):
    from bench import harness
    result = harness.run_cell(tiny_glm4_cell, TINY['seed'], 0.5, False,
                              time.perf_counter(), require_tpu=False,
                              work_dir=tmp_path, log=lambda s: None,
                              program_as_reference='fp8')
    assert result['correct'] is False
    assert any(c['value'] > c['limit'] for c in result['checks'].values())
