"""The command without a chip, and outside a checkout: it exits non-zero
and prints no result."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(root: Path):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    return subprocess.run(
        [sys.executable, 'bench/run.py', '--workload',
         'qwen2-0.5b.eva.b4s2048', '--seed', '3000000007', '--seconds', '1',
         '--trace', '0'], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ''
    assert 'no TPU' in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / 'BENCHMARK.json', tmp_path)
    shutil.copytree(ROOT / 'bench', tmp_path / 'bench',
                    ignore=shutil.ignore_patterns('__pycache__'))
    shutil.copytree(ROOT / 'tests' / 'bench', tmp_path / 'tests' / 'bench',
                    ignore=shutil.ignore_patterns('__pycache__'))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ''
