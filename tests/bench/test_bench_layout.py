"""BENCHMARK.json against the benchmark's contract, and every file a cell
needs found by name; FLOP and byte counts against hand counts."""
import json
import math
import re
import shutil

import pytest

from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

BENCH = json.loads((ROOT / 'BENCHMARK.json').read_text())
NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.-]{1,16}$')
WIDTH = re.compile(r'(_dim|_rank)$|^(d_model|d_ff|head_dim|hidden|intermediate'
                   r'|n_heads|n_kv_heads|top_k)')
CELLS = [w['name'] for w in BENCH['workloads']]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {'command', 'paths', 'run_seconds', 'configs',
                          'workloads', 'end_to_end', 'per_layer'}
    assert 1 <= BENCH['run_seconds'] <= 51
    assert isinstance(BENCH['run_seconds'], int)
    assert 1 <= len(BENCH['paths']) <= 16
    for p in BENCH['paths']:
        assert re.fullmatch(r'[A-Za-z0-9_./-]{1,200}', p) and '..' not in p
        assert (ROOT / p).is_dir()
    assert len(BENCH['command']) <= 32
    for word in BENCH['command']:
        assert not word.startswith('/') and '..' not in word
        if word.endswith('.py'):
            assert any(word.startswith(p + '/') for p in BENCH['paths'])
    assert len((ROOT / 'BENCHMARK.json').read_bytes()) <= 64 * 1024


def test_names_units_and_entry_keys():
    metrics = BENCH['end_to_end'] + BENCH['per_layer']
    names = [m['name'] for m in metrics] + CELLS + \
        [c['name'] for c in BENCH['configs']]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m['unit']), m['unit']
        assert m['better'] in ('lower', 'higher')
    for m in BENCH['end_to_end']:
        assert set(m) <= {'name', 'unit', 'better', 'bound', 'source',
                          'workloads'}
        assert m['source'] in ('host_clock', 'device_trace')
        assert 0.01 <= m['bound'] <= 0.25
    assert any(m['name'] == 'setup_s' for m in BENCH['end_to_end'])
    e2e = {m['name'] for m in BENCH['end_to_end']}
    layers = {}
    for m in BENCH['per_layer']:
        assert set(m) <= {'name', 'unit', 'better', 'source', 'layer', 'moves',
                          'workloads'}
        assert m['source'] in ('device_trace', 'program_span',
                               'program_counter', 'host_clock')
        assert m['moves'] in e2e
        assert 1 <= len(m['layer']) <= 200 and '\n' not in m['layer']
        layers.setdefault(m['layer'], []).append(m['name'])
        if m['name'].endswith('_roofline') or 'mfu' in m['name']:
            assert m['unit'] == '%'
        for w in m.get('workloads', []):
            assert w in CELLS
    for w in BENCH['workloads']:
        assert set(w) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert w['chips'] in (1, 4) and len(w['why']) <= 200
        assert NAME.match(w['traffic'])
    pairs = [(w['config'], w['traffic']) for w in BENCH['workloads']]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize('entry', BENCH['configs'], ids=lambda c: c['name'])
def test_config_entry(entry):
    assert set(entry) == {'name', 'source', 'file', 'reduced', 'why'}
    assert entry['source'].startswith('https://')
    assert any(entry['file'].startswith(p + '/') for p in BENCH['paths'])
    cfg = json.loads((ROOT / entry['file']).read_text())
    assert cfg['name'] == entry['name']
    assert cfg['reduced'] == entry['reduced']
    for k in entry['reduced']:
        assert NAME.match(k) and not WIDTH.search(k), k
    assert any(w['config'] == entry['name'] for w in BENCH['workloads'])


def test_every_limits_and_traffic_file_has_its_keys():
    for path in (ROOT / 'bench/limits').glob('*.json'):
        limits = json.loads(path.read_text())['limits']
        assert set(limits) == {'loss_gap', 'update1_gap', 'change_gap',
                               'unmoved_leaves'}, path
        assert limits['unmoved_leaves'] == 0, path
    for path in (ROOT / 'bench/traffic').glob('*.json'):
        t = json.loads(path.read_text())
        assert t['name'] == path.stem and NAME.match(t['name'])
        assert t['checked_steps'] >= 3
        assert (ROOT / 'bench/optimizers'
                / f"{t['optimizer']['name']}.py").is_file()


@pytest.mark.parametrize('cell', CELLS)
def test_cell_files_found_by_name(cell):
    from bench import harness
    c = harness.load_cell(cell)
    assert set(c.limits) == {'loss_gap', 'update1_gap', 'change_gap',
                             'unmoved_leaves'}
    assert c.traffic['checked_steps'] >= 3
    assert hasattr(c.model_reference(), 'Reference')
    assert callable(c.optimizer().program_first_update)
    assert harness.reference(c).opt.__class__.__name__ == 'Reference'
    for m in c.per_layer:
        assert callable(c.metric_reader(m['name']).read)
    assert {m['name'] for m in c.end_to_end} >= {'setup_s', 'tokens_per_s'}


@pytest.mark.parametrize('path', sorted((ROOT / 'bench/configs').glob('*.json')),
                         ids=lambda p: p.stem)
def test_program_runs_the_configuration_as_filed(path, tmp_path):
    """Every configuration file: the program's registry config with the
    file's cuts is the file, and its parameters are what the reference
    makes."""
    from bench import harness
    cfg = json.loads(path.read_text())
    traffic = json.loads((ROOT / 'bench/traffic/eva.b4s2048.json')
                         .read_text())
    cell = harness.Cell(name=cfg['name'], chips=1, cfg=cfg, traffic=traffic,
                        limits={}, end_to_end=[], per_layer=[])
    program = harness.Program(cell, tmp_path)
    layout = cell.model_reference().param_layout(cfg)
    assert program.param_shapes == {
        p: (s, cfg['param_dtype']) for p, (s, _) in layout.items()}


def test_flops_per_token_by_hand():
    from bench import harness
    qwen = harness.load_cell('qwen2-0.5b.eva.b4s2048')
    ref = qwen.model_reference()
    layer = 896 * 896 * 2 + 896 * 128 * 2 + 896 * 4864 * 3
    hand = 6 * (24 * layer + 896 * 151936) + 6 * 24 * 14 * 64 * 2048
    assert ref.flops_per_token(qwen.cfg, 2048) == hand
    assert round(hand / 1e9, 2) == 3.23
    glm = json.loads((ROOT / 'bench/configs/glm4-9b-l4v8.json').read_text())
    layer = 4096 * 4096 * 2 + 4096 * 256 * 2 + 4096 * 13696 * 3
    hand = 6 * (4 * layer + 4096 * 18944) + 6 * 4 * 32 * 128 * 2048
    assert ref.flops_per_token(glm, 2048) == hand
    assert round(hand / 1e9, 2) == 5.56
    counts = glm['parameters']
    assert 4 * (layer + 2 * 4096) == counts['layers']
    assert sum(math.prod(s) for s, _ in ref.param_layout(glm).values()) \
        == counts['total']


def test_eva_fused_bytes_and_flops():
    from bench.kernels import eva_fused
    flops, nbytes = eva_fused.cost(24, 896, 4864, 2)
    n = 24 * 896 * 4864
    assert flops == 15 * n
    assert nbytes == n * (2 + 4 + 4) + 4 * 24 * (896 + 4864)
    text = ('%eva_fused_stacked.1 = (f32[24,4864,896]{2,1,0:T(8,128)}, '
            'f32[24,1,128]{2,1,0}) custom-call(bf16[24,4864,896]{2,1,0} '
            '%while.210, f32[24,4864,1]{2,1,0} %copy.227)')
    assert eva_fused.cost_of_event(text) == eva_fused.cost(24, 4864, 896, 2)
    assert eva_fused.cost_of_event(text.replace('(bf16', '(f32'))[1] \
        == eva_fused.cost(24, 4864, 896, 4)[1]


def test_new_cell_and_metric_are_new_files_only(tmp_path):
    """A configuration, traffic mixes (one trained by another optimizer
    than Eva), their cells and a per-layer metric are added as files plus
    BENCHMARK.json entries: no file of the harness changes, and the harness
    finds each by its name."""
    from bench import harness
    shutil.copytree(ROOT / 'bench', tmp_path / 'bench')
    before = {p: p.read_bytes() for p in (tmp_path / 'bench').rglob('*')
              if p.is_file()}
    bench = json.loads((ROOT / 'BENCHMARK.json').read_text())
    cfg = json.loads((ROOT / 'bench/configs/qwen2-0.5b.json').read_text())
    cfg.update(name='qwen2-0.5b-l12', n_layers=12, reduced=['n_layers'])
    (tmp_path / 'bench/configs/qwen2-0.5b-l12.json').write_text(
        json.dumps(cfg))
    traffic = json.loads((ROOT / 'bench/traffic/eva.b4s2048.json')
                         .read_text())
    traffic.update(name='eva.b8s1024', batch=8, seq_len=1024)
    (tmp_path / 'bench/traffic/eva.b8s1024.json').write_text(
        json.dumps(traffic))
    cell = 'qwen2-0.5b-l12.eva.b8s1024'
    (tmp_path / f'bench/limits/{cell}.json').write_text(json.dumps(
        {'limits': {'loss_gap': 1e-3, 'update1_gap': 1e-2,
                    'change_gap': 1e-2, 'unmoved_leaves': 0}}))
    sgd = dict(traffic, name='sgd.b8s1024',
               optimizer={'name': 'sgd', 'lr': 0.05, 'momentum': 0.9})
    (tmp_path / 'bench/traffic/sgd.b8s1024.json').write_text(json.dumps(sgd))
    sgd_cell = 'qwen2-0.5b-l12.sgd.b8s1024'
    (tmp_path / f'bench/limits/{sgd_cell}.json').write_text(json.dumps(
        {'limits': {'loss_gap': 1e-3, 'update1_gap': 1e-2,
                    'change_gap': 1e-2, 'unmoved_leaves': 0}}))
    (tmp_path / 'bench/metrics/steps_traced.py').write_text(
        'def read(view):\n    return float(view.steps) or None\n')
    bench['configs'].append({'name': cfg['name'], 'source': 'https://x',
                             'file': 'bench/configs/qwen2-0.5b-l12.json',
                             'reduced': ['n_layers'], 'why': 'test'})
    bench['workloads'].append({'name': cell, 'config': cfg['name'],
                               'traffic': 'eva.b8s1024', 'chips': 1,
                               'why': 'test'})
    bench['workloads'].append({'name': sgd_cell, 'config': cfg['name'],
                               'traffic': 'sgd.b8s1024', 'chips': 1,
                               'why': 'test'})
    bench['per_layer'].append({'name': 'steps_traced', 'unit': 'steps',
                               'better': 'higher', 'source': 'host_clock',
                               'layer': 'trainer loop',
                               'moves': 'tokens_per_s',
                               'workloads': [cell]})
    (tmp_path / 'BENCHMARK.json').write_text(json.dumps(bench))
    c = harness.load_cell(cell, root=tmp_path)
    assert c.cfg['n_layers'] == 12 and c.tokens_per_step == 8 * 1024
    assert [m['name'] for m in c.per_layer][-1] == 'steps_traced'
    assert c.metric_reader('steps_traced').read(
        type('V', (), {'steps': 7})()) == 7.0
    assert c.model_reference().flops_per_token(c.cfg, 1024) > 0
    s = harness.load_cell(sgd_cell, root=tmp_path)
    assert s.optimizer_kwargs == {'lr': 0.05, 'momentum': 0.9}
    assert s.optimizer().__file__.endswith('optimizers/sgd.py')
    assert harness.reference(s).opt.factor(0.0) == 1.0
    for w in BENCH['workloads']:
        assert harness.load_cell(w['name'], root=tmp_path).name == w['name']
    after = {p: p.read_bytes() for p in before}
    assert after == before
