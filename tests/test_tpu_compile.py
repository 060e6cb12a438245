"""Compile rehearsal for TPU v5e: the fused Eva kernels and the Eva train steps
of qwen2-0.5b and glm4-9b-l4v8, compiled by the TPU compiler for a
described (not attached) v5e:2x2 topology.  Nothing runs; a compile that passes is not a chip run.

The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.  Keep every such compile in this one
file (a second file could land on another worker, where its fixture would
skip in silence).
"""
import collections
import importlib.util
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused as kfused

ROOT = Path(__file__).resolve().parents[1]

# qwen2-0.5b's preconditioned buckets as one kernel launch sees them
# (L, d_in, d_out); test_bucket_shapes_are_qwen2s keeps this list honest
QWEN_SHAPES = [(24, 4864, 896), (48, 896, 128), (48, 896, 4864),
               (48, 896, 896)]
HBM_BYTES = 15.75 * 2 ** 30   # what the v5e compiler lets a program use


def _smoke():
    spec = importlib.util.spec_from_file_location('chip_smoke',
                                                  ROOT / 'chip_smoke.py')
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope='module')
def topo():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')


@pytest.fixture(scope='module')
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope='module', autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    cc.reset_cache()
    yield
    jax.config.update('jax_enable_compilation_cache', prev)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_call(op, L, m, n, dtype, sh, block=512):
    kw = dict(block_in=block, block_out=block, interpret=False)
    g = _spec(sh, (L, m, n), dtype)
    a, b = _spec(sh, (L, m)), _spec(sh, (L, n))
    mom = _spec(sh, (L, m, n))
    calls = {
        'eva_fused_stacked': (lambda g, a, b, m_: kfused.eva_fused_stacked(
            g, a, b, 0.03, m_, 0.9, **kw), (g, a, b, mom)),
        'eva_f_fused_stacked': (lambda g, a, m_: kfused.eva_f_fused_stacked(
            g, a, 0.03, m_, 0.9, **kw), (g, a, mom)),
    }
    fn, args = calls[op]
    return jax.jit(fn).lower(*args).compile()


KERNELS = ['eva_fused_stacked', 'eva_f_fused_stacked']


def test_bucket_shapes_are_qwen2s():
    assert sorted(_smoke().qwen_kernel_shapes()) == sorted(QWEN_SHAPES)


@pytest.mark.parametrize('shape', QWEN_SHAPES, ids=lambda s: 'x'.join(map(str, s)))
@pytest.mark.parametrize('op', KERNELS)
def test_kernel_compiles_at_qwen2_shape(op, shape, one_chip):
    compiled = _kernel_call(op, *shape, jnp.bfloat16, one_chip)
    assert 'tpu_custom_call' in compiled.as_text()
    assert op in _kernel_names(compiled)


@pytest.mark.parametrize('op', KERNELS)
def test_largest_tuned_tile_fits_vmem(op, one_chip):
    """The largest tile the 512 request can give (512x512) in f32, the
    widest operands: Mosaic refuses a kernel whose buffers exceed VMEM."""
    compiled = _kernel_call(op, 2, 1024, 1024, jnp.float32, one_chip)
    assert 'tpu_custom_call' in compiled.as_text()


ATTENTION_KERNELS = ('flash_fwd', 'flash_dq', 'flash_dkv')


def _kernel_counts(compiled) -> collections.Counter:
    """How often each custom call appears in a compiled program, by name
    without the ``.<n>`` the compiler appends (the profiler's trace names
    ops so)."""
    names = re.findall(r'%([\w.-]+) = [^\n]*custom-call\(',
                       compiled.as_text())
    return collections.Counter(re.sub(r'\.\d+$', '', n) for n in names)


def _kernel_names(compiled) -> set:
    return set(_kernel_counts(compiled))


def _compile_train_step(arch, batch_size, one_chip, monkeypatch, fused):
    """The Eva train step at ``batch_size`` x 2048, donated params and
    optimizer state, compiled for one v5e.  The backend is steered to the
    TPU (dispatch sees the CPU here), so the step takes the chip's paths:
    the attention kernel, and compiled Pallas ``eva_fused`` when fused."""
    from repro.core import make_optimizer
    from repro.kernels import dispatch
    from repro.models import build_model
    from repro.models import module as M
    from repro.schedule.runtime import RefreshRuntime
    from repro.train.step import abstract_opt_state, make_train_step

    monkeypatch.setattr(dispatch, 'backend', lambda: 'tpu')
    model = build_model(arch)
    params = jax.eval_shape(lambda: M.init_params(model.param_specs(),
                                                  jax.random.PRNGKey(0)))
    opt, capture = make_optimizer('eva', lr=0.05, fused=fused)
    tok = jax.ShapeDtypeStruct((batch_size, 2048), jnp.int32)
    batch = {'tokens': tok, 'labels': tok}
    sched = RefreshRuntime()
    state = abstract_opt_state(model, opt, capture, params, batch,
                               sched=sched)
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda x: _spec(one_chip, x.shape, x.dtype), t)
    step = make_train_step(model, opt, capture, sched=sched)
    return jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(state), on_chip(batch)).compile()


def _check_step(compiled, fused):
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert peak <= HBM_BYTES, f'{peak / 2**30:.2f} GiB > 15.75 GiB'
    counts = _kernel_counts(compiled)
    names = set(counts)
    assert set(ATTENTION_KERNELS) <= names, names
    assert ('eva_fused_stacked' in names) == fused, names
    # remat='dots' keeps the forward's o and lse: the backward's layer scan
    # launches no second flash_fwd
    assert counts['flash_fwd'] == 1, counts


@pytest.mark.parametrize('path', ['default', 'fused'])
def test_qwen2_eva_train_step_fits_hbm(path, one_chip, monkeypatch):
    """The whole qwen2-0.5b Eva step at the smoke's batch x 2048 within
    one v5e's HBM, the attention kernel in it, ``eva_fused`` only on the
    fused path."""
    from repro.configs import get_config
    compiled = _compile_train_step(get_config('qwen2-0.5b'),
                                   _smoke().SMOKE_BATCH, one_chip,
                                   monkeypatch, path == 'fused')
    _check_step(compiled, path == 'fused')


def test_glm4_eva_train_step_fits_hbm(one_chip, monkeypatch):
    """glm4-9b-l4v8's step as its benchmark cell runs it (batch 3 x 2048,
    Eva's default path), heads of 128 through the attention kernel."""
    import json
    from repro.configs import get_config
    cfg = json.loads((ROOT / 'bench' / 'configs' / 'glm4-9b-l4v8.json')
                     .read_text())
    arch = get_config(cfg['program_arch']).replace(
        **{k: cfg[k] for k in cfg['reduced']})
    _check_step(_compile_train_step(arch, 3, one_chip, monkeypatch, False),
                False)
