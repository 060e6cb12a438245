"""Mesh helpers on the installed jax: ``launch.mesh.make_mesh`` (all-Auto
axes) and the mesh-context queries in ``sharding.constraints``."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.launch.mesh import make_mesh
from repro.sharding.constraints import (bound_axis_sizes, constrain,
                                        current_mesh, data_axes_in_scope,
                                        pmean_stats, shard_activations)


def test_make_mesh_installed_api():
    mesh = make_mesh((1, 1), ('data', 'model'))
    assert tuple(mesh.axis_names) == ('data', 'model')
    assert all(t == AxisType.Auto for t in mesh.axis_types)


def test_current_mesh_none_outside_context():
    assert current_mesh() is None


def test_current_mesh_inside_context():
    mesh = make_mesh((1,), ('data',))
    with jax.set_mesh(mesh):
        m = current_mesh()
        assert m is not None
        assert 'data' in m.shape


def test_constrain_noop_outside_mesh():
    x = jnp.ones((4, 4))
    np.testing.assert_array_equal(np.asarray(constrain(x, 'data')), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(shard_activations(x)), np.asarray(x))


def test_constrain_under_mesh_context():
    mesh = make_mesh((1, 1), ('data', 'model'))
    x = jnp.ones((2, 4, 8))
    with jax.set_mesh(mesh):
        y = shard_activations(x)
        z = constrain(x, 'data', None, 'model')
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(z), np.asarray(x))


def test_bound_axis_names_and_pmean_stats():
    assert bound_axis_sizes() == {}
    assert data_axes_in_scope() == ()
    # pmean_stats is the identity outside any shard_map scope
    tree = {'b': jnp.arange(3.0)}
    out = pmean_stats(tree)
    np.testing.assert_array_equal(np.asarray(out['b']), np.asarray(tree['b']))
    assert pmean_stats(None) is None


def test_pmean_stats_inside_shard_map():
    mesh = make_mesh((1,), ('data',))
    from jax.sharding import PartitionSpec as P

    def body(x):
        assert data_axes_in_scope() == ('data',)
        assert bound_axis_sizes() == {'data': 1}
        # Manual axes: constraints are illegal here, so no mesh is current
        assert current_mesh() is None
        return pmean_stats({'s': x})['s']

    f = jax.shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                      check_vma=False)
    out = jax.jit(f)(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0))
