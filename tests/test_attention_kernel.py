"""The Pallas attention kernel (``kernels/attention.py``), run in interpret
mode on the CPU against the pure-JAX ``flash_attention_xla`` on outputs and
on dq, dk, dv; its tile tables; the forward residuals the models' ``remat``
policy keeps; and the rule by which ``flash_attention`` picks it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.kernels import attention as kattention
from repro.kernels import dispatch
from repro.models import flash
from repro.models.transformer import _remat_policy

# (batch, S, heads, kv heads, head_dim, block): qwen2-0.5b's GQA 14/2 at
# head_dim 64 and glm4-9b's 32/2 at head_dim 128
SHAPES = {'qwen2': (1, 512, 14, 2, 64, 256), 'glm4': (1, 256, 32, 2, 128, 128)}


def _inputs(b, s, h, kvh, dh, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, s, h, dh), dtype)
    k = jax.random.normal(ks[1], (b, s, kvh, dh), dtype)
    v = jax.random.normal(ks[2], (b, s, kvh, dh), dtype)
    do = jax.random.normal(ks[3], (b, s, h, dh), dtype)
    return q, k, v, do


def _kernel(causal, block):
    t = lambda x: jnp.swapaxes(x, 1, 2)
    return lambda q, k, v: t(kattention.flash_attention(
        t(q), t(k), t(v), causal, block, block, True))


@pytest.mark.parametrize('causal', [True, False], ids=['causal', 'full'])
@pytest.mark.parametrize('shape', sorted(SHAPES))
def test_kernel_matches_pure_flash(shape, causal):
    b, s, h, kvh, dh, block = SHAPES[shape]
    q, k, v, do = _inputs(b, s, h, kvh, dh, jnp.float32)
    ref = lambda q, k, v: flash.flash_attention_xla(q, k, v, causal, 128, 128)
    o_ref, vjp_ref = jax.vjp(ref, q, k, v)
    o, vjp = jax.vjp(_kernel(causal, block), q, k, v)
    for got, want in zip((o,) + vjp(do), (o_ref,) + vjp_ref(do)):
        np.testing.assert_allclose(got, want, rtol=2e-5,
                                   atol=2e-5 * float(jnp.max(jnp.abs(want))))


def test_kernel_in_bf16_rounds_p_and_ds_once():
    """bf16 in: P and dS enter their matmuls in bf16, where the CPU's pure
    path keeps them in f32; the gap is bf16 rounding, not an error."""
    b, s, h, kvh, dh, block = SHAPES['qwen2']
    q, k, v, do = _inputs(b, s, h, kvh, dh, jnp.bfloat16)
    ref = lambda q, k, v: flash.flash_attention_xla(q, k, v, True, 128, 128)
    o_ref, vjp_ref = jax.vjp(ref, q, k, v)
    o, vjp = jax.vjp(_kernel(True, block), q, k, v)
    for got, want in zip((o,) + vjp(do), (o_ref,) + vjp_ref(do)):
        assert got.dtype == jnp.bfloat16
        err = jnp.max(jnp.abs(got.astype(jnp.float32) - want))
        assert float(err) <= 2e-2 * float(jnp.max(jnp.abs(want)))


def _kernel_calls(jaxpr) -> list:
    """Names of the ``pallas_call``s in a jaxpr and its sub-jaxprs, in
    order."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'pallas_call':
            names.append(eqn.params['name'])
            continue
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    names += _kernel_calls(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    names += _kernel_calls(sub)
    return names


def _remat_grad(policy):
    """Gradient of a checkpointed kernel call (GQA 4/2, S 256, blocks of
    128) under the models' ``remat`` policy ``policy``."""
    f = lambda q, k, v: jnp.sum(jnp.sin(kattention.flash_attention(
        q, k, v, True, 128, 128, True).astype(jnp.float32)))
    return jax.grad(jax.checkpoint(f, policy=_remat_policy(policy)),
                    argnums=(0, 1, 2))


def _remat_inputs():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    return (jax.random.normal(ks[0], (1, 4, 256, 64), jnp.bfloat16),
            jax.random.normal(ks[1], (1, 2, 256, 64), jnp.bfloat16),
            jax.random.normal(ks[2], (1, 2, 256, 64), jnp.bfloat16))


@pytest.mark.parametrize('policy,forwards', [('dots', 1), ('full', 2)])
def test_remat_policy_keeps_the_kernels_residuals(policy, forwards):
    """``'dots'`` saves o and lse under ``RESIDUAL_NAMES``, so the backward
    launches no second ``flash_fwd``; ``'full'`` saves nothing and rebuilds
    them with one."""
    jaxpr = jax.make_jaxpr(_remat_grad(policy))(*_remat_inputs()).jaxpr
    calls = _kernel_calls(jaxpr)
    assert calls.count('flash_fwd') == forwards, calls
    assert calls.count('flash_dq') == calls.count('flash_dkv') == 1, calls


def test_saved_residuals_give_the_recomputed_gradients():
    """Saving o and lse hands the backward the values the recompute
    would: dq, dk and dv are bitwise those of ``'full'``."""
    inputs = _remat_inputs()
    for got, want in zip(_remat_grad('dots')(*inputs),
                         _remat_grad('full')(*inputs)):
        assert got.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


@pytest.mark.parametrize('by', ['q', 'k'])
def test_tile_tables_skip_the_masked_half(by):
    qi, ki, fl = kattention.tile_tables(4, 4, 512, 512, True, by=by)
    assert len(qi) == 10 and all(k <= q for q, k in zip(qi, ki))
    outer = qi if by == 'q' else ki
    for n in range(len(qi)):
        opens = n == 0 or outer[n - 1] != outer[n]
        closes = n == len(qi) - 1 or outer[n + 1] != outer[n]
        assert bool(fl[n] & kattention.FIRST) == opens
        assert bool(fl[n] & kattention.LAST) == closes
        assert bool(fl[n] & kattention.MASKED) == (qi[n] == ki[n])
    # full attention: every tile, none masked; unequal blocks
    qi, ki, fl = kattention.tile_tables(4, 2, 256, 512, False)
    assert len(qi) == 8 and not any(fl & kattention.MASKED)
    # causal with k blocks twice the q blocks: a q block's last k block
    # holds its diagonal
    qi, ki, fl = kattention.tile_tables(4, 2, 256, 512, True)
    assert list(zip(qi, ki)) == [(0, 0), (1, 0), (2, 0), (2, 1), (3, 0),
                                 (3, 1)]
    assert [bool(f & kattention.MASKED) for f in fl] == [
        True, True, False, True, False, True]


QWEN = ((4, 2048, 14, 64), (4, 2048, 2, 64))


@pytest.mark.parametrize('q_shape,k_shape,causal,path', [
    (*QWEN, True, 'pallas'),
    (*QWEN, False, 'pallas'),
    ((3, 2048, 32, 128), (3, 2048, 2, 128), True, 'pallas'),
    ((4, 2000, 14, 64), (4, 2000, 2, 64), True, 'xla'),       # odd S
    ((4, 1024, 14, 64), (4, 2048, 2, 64), False, 'xla'),      # S_q != S_k
    ((4, 2048, 14, 80), (4, 2048, 2, 80), True, 'xla'),       # head_dim
], ids=['qwen2-causal', 'qwen2-full', 'glm4', 'odd-S', 'cross', 'dh80'])
def test_selection_rule(q_shape, k_shape, causal, path, monkeypatch):
    assert flash.attention_plan(q_shape, k_shape, causal, 512,
                                1024).path == 'xla'   # the CPU
    monkeypatch.setattr(dispatch, 'backend', lambda: 'tpu')
    plan = flash.attention_plan(q_shape, k_shape, causal, 512, 1024)
    assert plan.path == path, plan.rule
    if path == 'pallas':
        n = q_shape[1] // plan.block_q
        assert plan.tiles_total == n * n
        assert plan.tiles == (n * (n + 1) // 2 if causal else n * n)
    else:
        assert plan.tiles == plan.tiles_total


def test_selection_keeps_pure_path_on_a_split_mesh(monkeypatch):
    """Under an Auto mesh of two devices the arrays are not per device:
    the custom call would be replicated, so the pure path stays."""
    monkeypatch.setattr(dispatch, 'backend', lambda: 'tpu')
    mesh = jax.sharding.AbstractMesh((2,), ('data',))
    with jax.sharding.use_abstract_mesh(mesh):
        plan = flash.attention_plan(*QWEN, True, 512, 1024)
    assert plan.path == 'xla' and 'mesh' in plan.rule


def test_flash_attention_records_the_kernel_it_traces(monkeypatch):
    """On the TPU backend the dispatch traces the kernel (shapes only
    here) and appends its plan, forward and backward."""
    monkeypatch.setattr(dispatch, 'backend', lambda: 'tpu')
    q = jax.ShapeDtypeStruct(QWEN[0], jnp.bfloat16)
    k = jax.ShapeDtypeStruct(QWEN[1], jnp.bfloat16)
    loss = lambda q, k, v: jnp.sum(flash.flash_attention(q, k, v))
    with flash.collect() as plans:
        grads = jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), q, k, k)
    assert [g.shape for g in grads] == [QWEN[0], QWEN[1], QWEN[1]]
    assert plans and {p.path for p in plans} == {'pallas'}
    assert plans[0].site == '4x2048x14/2x64/causal'
    assert (plans[0].tiles, plans[0].tiles_total) == (10, 16)
