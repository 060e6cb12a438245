"""Mini dry-run in a subprocess (the 512-device flag must not leak into
this test process): lower+compile a reduced arch on a (2,2,2) mesh and
check the JSON record schema + HLO analyzer outputs."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax
    from repro.configs import get_reduced, SHAPE_BY_NAME
    from repro.configs.base import ShapeCell
    from repro.launch import hlo_analysis
    from repro.launch.dryrun import build_cell
    from repro.models import build_model

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ('pod', 'data', 'model'))
    cfg = get_reduced(sys.argv[1])
    shape = ShapeCell('mini_train', seq_len=16, global_batch=8, kind=sys.argv[2])
    fn, args, shardings, donate, tokens, kind = build_cell(cfg, shape, mesh, [])
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=shardings,
                          donate_argnums=donate).lower(*args)
        compiled = lowered.compile()
    costs = hlo_analysis.analyze(compiled.as_text())
    mem = compiled.memory_analysis()
    print(json.dumps({
        'flops': costs.flops, 'traffic': costs.traffic_bytes,
        'collective': costs.collective_bytes,
        'temp': mem.temp_size_in_bytes,
        'cost_flops': float(compiled.cost_analysis().get('flops', 0)),
    }))
""")


def _run(arch: str, kind: str) -> dict:
    out = subprocess.run(
        [sys.executable, '-c', SCRIPT, arch, kind],
        capture_output=True, text=True, timeout=600,
        # JAX_PLATFORMS pinned: the scrubbed env must not fall through to
        # accelerator discovery (libtpu-on-a-TPU-less-host hangs forever)
        env={'PYTHONPATH': 'src', 'PATH': '/usr/bin:/bin',
             'HOME': '/root', 'JAX_PLATFORMS': 'cpu'},
        cwd=Path(__file__).resolve().parent.parent)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize('arch,kind', [
    ('qwen2-0.5b', 'train'),
    ('qwen3-moe-30b-a3b', 'train'),
    ('mamba2-780m', 'decode'),
    ('jamba-v0.1-52b', 'train'),
])
def test_mini_multipod_compiles(arch, kind):
    rec = _run(arch, kind)
    assert rec['flops'] > 0
    assert rec['traffic'] > 0
    if kind == 'train':
        assert rec['collective'] > 0  # gradient reduction must exist
    # trip-count correction: corrected flops >= raw cost_analysis flops
    assert rec['flops'] >= 0.5 * rec['cost_flops']


def test_main_process_has_one_device():
    """The 512-device flag must never leak outside dryrun.py."""
    import jax
    assert jax.device_count() == 1
