"""Kernel dispatch layer: impl resolution, runtime flips, tile fitting,
autotune cache install + determinism (PR: backend-aware dispatch)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, dispatch, ref
from repro.kernels.tiles import fit_block


@pytest.fixture(autouse=True)
def _clean_dispatch_state():
    prev = dispatch.default_impl()
    yield
    dispatch.set_default_impl(prev)
    dispatch.reset_cache()


def _mk(shape, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    g = jax.random.normal(ks[0], shape, jnp.float32)
    a = jax.random.normal(ks[1], (shape[0],), jnp.float32)
    b = jax.random.normal(ks[2], (shape[1],), jnp.float32)
    return g, a, b


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


# ---------------------------------------------------------------------------
# resolution rules


def test_resolve_auto_cpu_is_xla():
    # shape absent from the shipped cache -> pure backend rule (cpu: xla)
    c = dispatch.resolve('bilinear', 96, 80, jnp.float32, 'auto')
    if dispatch.backend() == 'cpu':
        assert c.impl == 'xla'


def test_resolve_auto_reads_shipped_cache():
    # 64x48 ships with a measured pallas winner in tile_defaults.json
    key = dispatch.cache_key('bilinear', 64, 48, jnp.float32)
    entry = dispatch._cache().get(key)
    if entry is not None:
        c = dispatch.resolve('bilinear', 64, 48, jnp.float32, 'auto')
        assert c.impl == entry['impl']


def test_resolve_explicit_pallas_interprets_off_tpu():
    c = dispatch.resolve('bilinear', 64, 48, jnp.float32, 'pallas')
    assert c.impl == 'pallas'
    assert c.interpret == (dispatch.backend() != 'tpu')
    ci = dispatch.resolve('bilinear', 64, 48, jnp.float32, 'pallas_interpret')
    assert ci.impl == 'pallas' and ci.interpret


def test_resolve_unknown_impl_raises():
    with pytest.raises(ValueError):
        dispatch.resolve('bilinear', 64, 48, jnp.float32, 'cuda')


def test_runtime_default_flip_no_reload():
    """set_default_impl / impl_override replace the old import-time
    ops.INTERPRET constant — flipping needs no module reload."""
    dispatch.set_default_impl('xla')
    assert dispatch.resolve('matvec', 64, 48, jnp.float32).impl == 'xla'
    with dispatch.impl_override('pallas_interpret'):
        c = dispatch.resolve('matvec', 64, 48, jnp.float32)
        assert c.impl == 'pallas' and c.interpret
    assert dispatch.resolve('matvec', 64, 48, jnp.float32).impl == 'xla'


def test_choices_snapshot_records_resolution():
    dispatch.resolve('bilinear', 200, 136, jnp.float32, 'pallas_interpret')
    snap = dispatch.choices_snapshot()
    assert 'bilinear' in snap and '@ 200x136' in snap['bilinear']


def test_impl_from_extras_config_wins():
    from repro.core.transform import Extras

    cfg = dispatch.KernelConfig(impl='xla')
    assert dispatch.impl_from_extras(Extras(kernel=cfg), 'pallas') == 'xla'
    # a present config wins even at 'auto' (engages the dispatch cache)
    auto = dispatch.KernelConfig(impl='auto')
    assert dispatch.impl_from_extras(Extras(kernel=auto), None) == 'auto'
    # no config -> caller default (None keeps the inline-jnp path)
    assert dispatch.impl_from_extras(Extras(), 'pallas') == 'pallas'
    assert dispatch.impl_from_extras(None, None) is None


# ---------------------------------------------------------------------------
# cache install / winner routing


def test_install_cache_routes_auto(tmp_path):
    key = dispatch.cache_key('bilinear', 64, 48, jnp.float32)
    cache = {'version': 1, 'entries': {
        key: {'impl': 'pallas', 'block_in': 32, 'block_out': 16, 'us': 1.0}}}
    path = tmp_path / 'cache.json'
    path.write_text(json.dumps(cache))
    assert dispatch.install_cache(str(path)) >= 1
    c = dispatch.resolve('bilinear', 64, 48, jnp.float32, 'auto')
    assert c.impl == 'pallas'
    assert (c.block_in, c.block_out) == (32, 16)
    # other shapes keep the backend rule
    assert dispatch.resolve('bilinear', 65, 48, jnp.float32, 'auto').impl \
        in ('xla', 'pallas')
    dispatch.reset_cache()
    # after reset, shipped defaults govern again (entry gone unless shipped)
    c2 = dispatch.resolve('bilinear', 64, 48, jnp.float32, 'auto')
    assert (c2.block_in, c2.block_out) != (32, 16) or c2.impl != 'pallas'


def test_shipped_defaults_exist_and_validate():
    """The warm-start file ships with the repo and parses into entries of
    the documented shape."""
    assert dispatch._DEFAULTS_FILE.exists()
    data = json.loads(dispatch._DEFAULTS_FILE.read_text())
    assert data['version'] == 1 and data['entries']
    for key, e in data['entries'].items():
        assert set(e) >= {'impl', 'block_in', 'block_out'}, key
        assert e['impl'] in ('xla', 'pallas')


# ---------------------------------------------------------------------------
# op wrappers: xla path is ref.py bit-for-bit; pallas path agrees tightly


@pytest.mark.parametrize('shape', [(64, 48), (200, 136)])
def test_xla_path_is_ref_bit_exact(shape):
    g, a, b = _mk(shape)
    np.testing.assert_array_equal(
        np.asarray(dispatch.bilinear(g, a, b, impl='xla')),
        np.asarray(ref.bilinear_ref(g, a, b)))
    np.testing.assert_array_equal(
        np.asarray(dispatch.matvec(g, a, impl='xla')),
        np.asarray(ref.matvec_ref(g, a)))
    np.testing.assert_array_equal(
        np.asarray(dispatch.rank1_update(g, a, b, 0.37, 2.5, impl='xla')),
        np.asarray(ref.rank1_update_ref(g, a, b, 0.37, 2.5)))


@pytest.mark.parametrize('shape', [(64, 48), (200, 136)])
def test_xla_vs_interpret_agree(shape):
    g, a, b = _mk(shape)
    for op, args in [('bilinear', (g, a, b)), ('matvec', (g, a)),
                     ('rank1_update', (g, a, b, jnp.float32(0.37),
                                       jnp.float32(2.5)))]:
        fn = getattr(dispatch, op)
        x = fn(*args, impl='xla')
        p = fn(*args, impl='pallas_interpret')
        np.testing.assert_allclose(np.asarray(x), np.asarray(p),
                                   atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# autotuner: deterministic output given pinned measurements


def _fake_bench():
    calls = {'n': 0}

    def bench(fn):
        del fn
        calls['n'] += 1
        return float(calls['n'])
    return bench


def test_autotune_deterministic_bytes():
    """Same shapes + same (injected) measurements -> identical JSON bytes;
    the CI determinism contract for the persisted cache."""
    shapes = [(64, 48), (200, 136)]
    s1 = autotune.dumps(autotune.tune(shapes, bench=_fake_bench()))
    s2 = autotune.dumps(autotune.tune(shapes, bench=_fake_bench()))
    assert s1 == s2
    data = json.loads(s1)
    assert data['version'] == 1
    assert len(data['entries']) == len(shapes) * len(autotune.OPS)


def test_autotune_first_candidate_wins_fixed_order():
    """The injected bench returns strictly increasing times, so the first
    candidate (xla, fixed candidate order) must win everywhere."""
    cache = autotune.tune([(64, 48)], bench=_fake_bench())
    for e in cache['entries'].values():
        assert e['impl'] == 'xla'


def test_autotune_winner_installs_and_resolves(tmp_path):
    def pallas_wins(fn):
        del fn
        # called in candidate order: xla first -> make it slow
        pallas_wins.n = getattr(pallas_wins, 'n', 0) + 1
        return 1e6 if pallas_wins.n % 7 == 1 else float(pallas_wins.n)

    cache = autotune.tune([(64, 48)], ops=('bilinear',), bench=pallas_wins)
    (entry,) = cache['entries'].values()
    assert entry['impl'] == 'pallas'
    path = autotune.write(cache, tmp_path / 'win.json')
    dispatch.install_cache(path)
    c = dispatch.resolve('bilinear', 64, 48, jnp.float32, 'auto')
    assert c.impl == 'pallas'
    assert (c.block_in, c.block_out) == (entry['block_in'],
                                         entry['block_out'])


def test_autotune_merge_new_wins():
    base = {'version': 1, 'entries': {'k1': {'impl': 'xla'},
                                      'k2': {'impl': 'xla'}}}
    new = {'version': 1, 'backend': 'cpu',
           'entries': {'k2': {'impl': 'pallas'}}}
    merged = autotune.merge(base, new)
    assert merged['entries']['k1']['impl'] == 'xla'
    assert merged['entries']['k2']['impl'] == 'pallas'
