"""The fused Eva Pallas kernels (``kernels/fused.py``) in interpret mode
against their jnp twins (``kernels/ref.py``): shape × dtype sweeps and the
tile-boundary grids."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import fused, ref

GAMMA = 0.03
MU = 0.9
SHAPES = [(8, 8), (64, 48), (128, 128), (200, 136), (512, 384), (1000, 513)]
DTYPES = [jnp.float32, jnp.bfloat16]
KERNELS = ['eva', 'eva_f']


def _mk(L, shape, dtype=jnp.float32, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    g = jax.random.normal(ks[0], (L,) + shape, jnp.float32).astype(dtype)
    a = jax.random.normal(ks[1], (L, shape[0]), jnp.float32).astype(dtype)
    b = jax.random.normal(ks[2], (L, shape[1]), jnp.float32).astype(dtype)
    m = jax.random.normal(ks[3], (L,) + shape, jnp.float32)
    return g, a, b, m


def _kernel(kind, g, a, b, m, fold=True, block=128):
    kw = dict(fold_momentum=fold, block_in=block, block_out=block)
    if kind == 'eva':
        return fused.eva_fused_stacked(g, a, b, GAMMA, m, MU, **kw)
    return fused.eva_f_fused_stacked(g, a, GAMMA, m, MU, **kw)


def _twin(kind, g, a, b, m, fold=True):
    if kind == 'eva':
        return ref.eva_fused_ref(g, a, b, GAMMA, m, MU, fold_momentum=fold)
    return ref.eva_f_fused_ref(g, a, GAMMA, m, MU, fold_momentum=fold)


def _assert_close(got, want):
    (out, aux), (r_out, r_aux) = got, want
    np.testing.assert_allclose(np.asarray(out), np.asarray(r_out),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(aux), np.asarray(r_aux),
                               rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize('shape', SHAPES)
@pytest.mark.parametrize('dtype', DTYPES)
@pytest.mark.parametrize('fold', [False, True])
@pytest.mark.parametrize('kind', KERNELS)
def test_fused_matches_ref_twin(kind, fold, dtype, shape):
    """Each fused kernel, momentum folded and not, as an L=1 stack."""
    g, a, b, m = _mk(1, shape, dtype)
    _assert_close(_kernel(kind, g, a, b, m, fold),
                  _twin(kind, g, a, b, m, fold))


# ---------------------------------------------------------------------------
# tile-boundary edge cases: non-divisible dims + stack depths.  The hard
# guarantee is stacked ≡ per-item (identical tile programs, bit-exact);
# agreement with the einsum twins is tight-tolerance — XLA contracts the
# broadcast formulas with different FMA/reduction order, so bit-identity
# with ref.py does not hold even for single-tile launches.

ODD_SHAPES = [(7, 5), (65, 33), (129, 127)]
BLOCKS = [32, 512]  # multi-tile with padding remainder / single padded tile


@pytest.mark.parametrize('shape', ODD_SHAPES)
@pytest.mark.parametrize('block', BLOCKS)
@pytest.mark.parametrize('kind', KERNELS)
def test_tile_boundary_vs_ref(kind, block, shape):
    g, a, b, m = _mk(1, shape, key=11)
    _assert_close(_kernel(kind, g, a, b, m, block=block),
                  _twin(kind, g, a, b, m))


@pytest.mark.parametrize('shape', ODD_SHAPES)
@pytest.mark.parametrize('block', BLOCKS)
@pytest.mark.parametrize('L', [1, 3])
@pytest.mark.parametrize('kind', KERNELS)
def test_tile_boundary_stacked_bit_identical_to_per_item(kind, L, block,
                                                         shape):
    g, a, b, m = _mk(L, shape, key=7)
    out, aux = _kernel(kind, g, a, b, m, block=block)
    for l in range(L):
        s = slice(l, l + 1)
        one_out, one_aux = _kernel(kind, g[s], a[s], b[s], m[s], block=block)
        np.testing.assert_array_equal(np.asarray(out[s]), np.asarray(one_out))
        np.testing.assert_array_equal(np.asarray(aux[s]), np.asarray(one_aux))


@pytest.mark.parametrize('shape', ODD_SHAPES)
@pytest.mark.parametrize('kind', KERNELS)
def test_tile_boundary_block_size_invariance(kind, shape):
    """Padding remainder tiles must not leak into the result: the same
    kernel at block 32 and as one padded tile agrees to f32 reduction
    order."""
    g, a, b, m = _mk(1, shape, key=13)
    _assert_close(_kernel(kind, g, a, b, m, block=32),
                  _kernel(kind, g, a, b, m, block=512))
