"""The benchmark's own tests: CPU only, small.  They import the benchmark
package from the checkout's root."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

FIXTURES = Path(__file__).resolve().parent / 'fixtures'


def _tiny(optimizer=None):
    from bench import harness
    cfg = json.loads((ROOT / 'bench' / 'configs' / 'qwen2-0.5b.json')
                     .read_text())
    tiny = json.loads((FIXTURES / 'tiny_cell.json').read_text())
    cfg.update(tiny['config'])
    traffic = json.loads((ROOT / 'bench' / 'traffic' / 'eva.b4s2048.json')
                         .read_text())
    traffic.update(tiny['traffic'])
    limits = tiny['limits']
    if optimizer:
        traffic['optimizer'] = tiny[f'{optimizer}_optimizer']
        limits = tiny[f'{optimizer}_limits']
    bench = json.loads((ROOT / 'BENCHMARK.json').read_text())
    return harness.Cell(name='tiny', chips=1, cfg=cfg, traffic=traffic,
                        limits=limits,
                        end_to_end=bench['end_to_end'], per_layer=[])


@pytest.fixture
def tiny_cell():
    """A cell of the qwen2-0.5b family cut to a size a test can run on the
    CPU (the real cells keep published widths), with its own limits."""
    return _tiny()


@pytest.fixture
def tiny_sgd_cell():
    """The same, trained by SGD: a cell whose optimizer is not Eva."""
    return _tiny('sgd')
