"""The backend rule of ``kernels/dispatch.py`` and the tile fitting of
``kernels/tiles.py``: the TPU takes the compiled fused kernels at
512-wide requested blocks, every other backend the jnp twins."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch, fused, ref
from repro.kernels.tiles import fit_block, fit_tiles

GAMMA = 0.03
MU = 0.9


def _mk(lead, shape=(24, 16), key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    g = jax.random.normal(ks[0], lead + shape, jnp.float32)
    a = jax.random.normal(ks[1], lead + shape[:1], jnp.float32)
    b = jax.random.normal(ks[2], lead + shape[1:], jnp.float32)
    m = jax.random.normal(ks[3], lead + shape, jnp.float32)
    return g, a, b, m


def _route(kind, g, a, b, m):
    if kind == 'eva':
        return dispatch.eva_fused(g, a, b, GAMMA, m, MU)
    return dispatch.eva_f_fused(g, a, GAMMA, m, MU)


# ---------------------------------------------------------------------------
# tiles.fit_block (satellite: waste-aware clamp)


def test_fit_block_small_dim_is_dim():
    assert fit_block(48, 512) == 48
    assert fit_block(512, 512) == 512


def test_fit_block_balances_tiles():
    # 520 @ 512: min() clamp would pad to 1024 (49% waste); fit_block keeps
    # the 2 tiles but shrinks them to 260 (zero pad)
    assert fit_block(520, 512) == 260
    assert fit_block(1000, 512) == 500
    assert fit_block(513, 512) == 257


def test_fit_block_alignment_rounds_up():
    # 1001 has no 8-aligned divisor: the least-padded aligned block wins
    b = fit_block(1001, 512, align=8)
    assert b % 8 == 0 and b >= 500
    # align never exceeds max(block, align)
    assert fit_block(7, 4, align=8) <= 8
    # an aligned exact divisor beats a padded larger block (a pad is an
    # extra HBM copy of the operand on the chip)
    assert fit_block(1000, 512, align=8) == 200
    assert fit_block(4864, 512, align=128) == 256
    assert fit_block(896, 512, align=16) == 448


def test_fit_block_rejects_nonpositive():
    with pytest.raises(ValueError):
        fit_block(0, 512)
    with pytest.raises(ValueError):
        fit_block(64, 0)


# the compiled tiles of qwen2-0.5b's four bucket shapes, bf16 and f32 alike:
# what the fused cell runs on the chip at the 512 request
QWEN_TILES = {(4864, 896): (304, 128), (896, 128): (448, 128),
              (896, 4864): (448, 256), (896, 896): (448, 128)}


@pytest.mark.parametrize('itemsize', [2, 4])
@pytest.mark.parametrize('shape', sorted(QWEN_TILES))
def test_fit_tiles_compiled_at_qwen2_shapes(shape, itemsize):
    bm, bn = fit_tiles(*shape, dispatch.BLOCK, dispatch.BLOCK, itemsize,
                       interpret=False)
    assert (bm, bn) == QWEN_TILES[shape]
    assert shape[0] % bm == 0 and shape[1] % bn == 0     # no pad copy


# ---------------------------------------------------------------------------
# the backend rule


@pytest.mark.parametrize('lead', [(4,), (2, 3)])
@pytest.mark.parametrize('kind', ['eva', 'eva_f'])
def test_cpu_route_is_ref_twin_with_stack_folded(kind, lead, monkeypatch):
    """Off the TPU the route is the jnp twin over the folded stack, bit for
    bit, unfolded back to the leading dims."""
    monkeypatch.setattr(dispatch, 'backend', lambda: 'cpu')
    g, a, b, m = _mk(lead)
    out, aux = _route(kind, g, a, b, m)
    fold = lambda x: x.reshape((-1,) + x.shape[len(lead):])
    if kind == 'eva':
        r_out, r_aux = ref.eva_fused_ref(fold(g), fold(a), fold(b), GAMMA,
                                         fold(m), MU)
    else:
        r_out, r_aux = ref.eva_f_fused_ref(fold(g), fold(a), GAMMA, fold(m),
                                           MU)
    assert out.shape == g.shape and aux.shape == lead + (3,)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(r_out).reshape(g.shape))
    np.testing.assert_array_equal(np.asarray(aux),
                                  np.asarray(r_aux).reshape(lead + (3,)))


@pytest.mark.parametrize('kind', ['eva', 'eva_f'])
def test_tpu_route_calls_compiled_kernel(kind, monkeypatch):
    """On the TPU the route calls the fused kernel compiled (not
    interpreted) at 512-wide requested blocks, over the folded stack."""
    calls = []

    def record(name, twin):
        def kernel(*args, **kw):
            calls.append((name, args[0].shape, kw))
            return twin(*args, fold_momentum=kw['fold_momentum'])
        return kernel

    monkeypatch.setattr(dispatch, 'backend', lambda: 'tpu')
    monkeypatch.setattr(fused, 'eva_fused_stacked',
                        record('eva', ref.eva_fused_ref))
    monkeypatch.setattr(fused, 'eva_f_fused_stacked',
                        record('eva_f', ref.eva_f_fused_ref))
    g, a, b, m = _mk((2, 3))
    _route(kind, g, a, b, m)
    assert calls == [(kind, (6, 24, 16),
                      dict(fold_momentum=True, block_in=512, block_out=512,
                           interpret=False))]


def test_kernel_config_accepts_only_auto():
    assert dispatch.KernelConfig().impl == 'auto'
    assert dispatch.KernelConfig(impl='auto').impl == 'auto'
    for impl in ('pallas', 'pallas_interpret', 'xla', None):
        with pytest.raises(ValueError):
            dispatch.KernelConfig(impl=impl)
