"""Fused precondition→update epilogue (kernels/fused.py + the optimizer
``fused=True`` paths): the fused single-launch chain must reproduce the
composed chain — ``core/precondition.py``'s inline jnp, then the
clip/momentum tail.

Tolerance contract (see the fused.py module docstring): the fused output
and the aux partials differ from the composed chain only by f32
reduction/FMA order and the rounding of the 1/γ scale, within 1 f32 ulp of
the update scale (γ·|Δ| < 1e-6).  The kernels against their jnp twins are
in tests/test_kernels.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kv as kvlib
from repro.core import precondition as pre
from repro.core.eva import eva
from repro.core.eva_f import eva_f
from repro.core.eva_s import eva_s
from repro.core.foof import foof
from repro.core.kfac import kfac
from repro.core.shampoo import shampoo
from repro.core.transform import Extras
from repro.kernels import fused

GAMMA = 0.03
MU = 0.9
SHAPES = [(3, 64, 48), (2, 129, 127), (1, 200, 136)]


def _mk_stacked(shape, key=0):
    L, d_in, d_out = shape
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    g = jax.random.normal(ks[0], shape, jnp.float32)
    a = jax.random.normal(ks[1], (L, d_in), jnp.float32)
    b = jax.random.normal(ks[2], (L, d_out), jnp.float32)
    m = jax.random.normal(ks[3], shape, jnp.float32)
    return g, a, b, m


def _composed_eva_p(g, a, b):
    """The composed chain the fused launch replaces: the unfused path's
    inline jnp."""
    return pre.eva_precondition(g, a, b, GAMMA)


# ---------------------------------------------------------------------------
# kernel level


@pytest.mark.parametrize('shape', SHAPES)
def test_eva_fused_foldoff_matches_composed(shape):
    """The deviations left are f32 reduction order, how XLA contracts the
    in-kernel coeff division against the composed one, and 1/γ as a
    multiply against a divide — ≤1 f32 ulp at the update's O(1/γ) scale.
    γ·diff stays under 1e-6."""
    g, a, b, m = _mk_stacked(shape)
    out, _ = fused.eva_fused_stacked(g, a, b, GAMMA, m, MU,
                                     fold_momentum=False,
                                     block_in=128, block_out=128)
    comp = _composed_eva_p(g, a, b)
    np.testing.assert_allclose(GAMMA * np.asarray(out),
                               GAMMA * np.asarray(comp),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize('shape', SHAPES)
def test_eva_fused_foldon_matches_jnp_tail(shape):
    """The folded momentum and the aux partials against the jnp tail over
    the kernel's own P (P against the composed chain is the test above)."""
    g, a, b, m = _mk_stacked(shape)
    out, aux = fused.eva_fused_stacked(g, a, b, GAMMA, m, MU,
                                       fold_momentum=True,
                                       block_in=128, block_out=128)
    p, _ = fused.eva_fused_stacked(g, a, b, GAMMA, m, MU,
                                   fold_momentum=False,
                                   block_in=128, block_out=128)
    want = MU * m + p
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-6, rtol=1e-6)
    want_aux = jnp.stack([jnp.sum(want * g, (-2, -1)),
                          jnp.sum(want * want, (-2, -1)),
                          jnp.sum(g * g, (-2, -1))], axis=-1)
    np.testing.assert_allclose(np.asarray(aux), np.asarray(want_aux),
                               rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize('fn', ['eva', 'eva_f'])
def test_fused_single_vs_multi_tile_agree(fn):
    """Tile count must not change the result beyond f32 reduction order."""
    g, a, b, m = _mk_stacked((2, 129, 127))
    if fn == 'eva':
        one = fused.eva_fused_stacked(g, a, b, GAMMA, m, MU,
                                      block_in=512, block_out=512)
        many = fused.eva_fused_stacked(g, a, b, GAMMA, m, MU,
                                       block_in=32, block_out=32)
    else:
        one = fused.eva_f_fused_stacked(g, a, GAMMA, m, MU,
                                        block_in=512, block_out=512)
        many = fused.eva_f_fused_stacked(g, a, GAMMA, m, MU,
                                         block_in=32, block_out=32)
    np.testing.assert_allclose(np.asarray(one[0]), np.asarray(many[0]),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(one[1]), np.asarray(many[1]),
                               rtol=2e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# optimizer level: fused=True ≡ fused=False for all six optimizers


def _params():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    return {'l1': {'w': jax.random.normal(ks[0], (32, 16)),
                   'b': jax.random.normal(ks[1], (16,))},
            'l2': {'w': jax.random.normal(ks[2], (32, 16))},
            'l3': {'w': jax.random.normal(ks[3], (16, 8))}}


def _grads(step):
    ks = jax.random.split(jax.random.PRNGKey(100 + step), 4)
    return {'l1': {'w': jax.random.normal(ks[0], (32, 16)),
                   'b': jax.random.normal(ks[1], (16,))},
            'l2': {'w': jax.random.normal(ks[2], (32, 16))},
            'l3': {'w': jax.random.normal(ks[3], (16, 8))}}


def _stats(kind, step):
    """Per-layer curvature stats of the shape each optimizer family
    captures (kv.LayerStats): rank-1 vectors for eva/eva_f, PSD outer
    products for the solve-based families."""
    ks = jax.random.split(jax.random.PRNGKey(200 + step), 12)

    def ls(i, din, dout):
        if kind == 'eva':
            return kvlib.LayerStats(
                a_mean=jax.random.normal(ks[i], (din,)),
                b_mean=jax.random.normal(ks[i + 1], (dout,)))
        if kind == 'eva_f':
            return kvlib.LayerStats(a_mean=jax.random.normal(ks[i], (din,)))
        if kind == 'foof':
            a = jax.random.normal(ks[i], (din, din))
            return kvlib.LayerStats(a_outer=a @ a.T / din)
        a = jax.random.normal(ks[i], (din, din))
        b = jax.random.normal(ks[i + 1], (dout, dout))
        return kvlib.LayerStats(a_outer=a @ a.T / din, b_outer=b @ b.T / dout)

    return {'l1/w': ls(0, 32, 16), 'l2/w': ls(3, 32, 16),
            'l3/w': ls(6, 16, 8)}


def _run(factory, kind, steps=4, **kw):
    params = _params()
    opt = factory(lr=0.1, **kw)
    state = opt.init(params, Extras(stats=_stats(kind, 0)))
    outs = []
    for t in range(steps):
        upd, state = opt.update(_grads(t), state, params=params,
                                extras=Extras(stats=_stats(kind, t)))
        outs.append(upd)
    return outs


@pytest.mark.parametrize('name,factory', [
    ('eva', eva), ('eva_f', eva_f), ('eva_s', eva_s),
    ('kfac', kfac), ('foof', foof), ('shampoo', shampoo)])
def test_optimizer_fused_matches_composed(name, factory):
    base = _run(factory, name, fused=False)
    fusd = _run(factory, name, fused=True)
    for t, (u0, u1) in enumerate(zip(base, fusd)):
        for x, y in zip(jax.tree_util.tree_leaves(u0),
                        jax.tree_util.tree_leaves(u1)):
            np.testing.assert_allclose(
                np.asarray(x, np.float32), np.asarray(y, np.float32),
                atol=1e-6, rtol=1e-6, err_msg=f'{name} step {t}')
