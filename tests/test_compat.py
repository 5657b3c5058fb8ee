"""Tests for the mesh / sharding surface in repro.compat (the JAX symbols
that moved in past releases, wrapped in one file)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.compat import Mesh, PartitionSpec as P


class TestShardMap:
    def test_identity_on_singleton_mesh(self):
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
        x = jnp.arange(8.0).reshape(2, 4)
        out = compat.shard_map(
            lambda t: t * 2.0,
            mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False,
        )(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 2.0)

    def test_collective_inside_body(self):
        mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
        x = jnp.ones((4,))
        out = compat.shard_map(
            lambda t: jax.lax.psum(t, "pod"),
            mesh=mesh, in_specs=(P(),), out_specs=P(),
        )(x)
        np.testing.assert_allclose(np.asarray(out), np.ones(4))

    def test_axis_size_concrete_inside_body(self):
        mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))

        def body(t):
            # must be usable in Python control flow at trace time
            assert int(compat.axis_size("pod")) == 1
            return t

        out = compat.shard_map(
            body, mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False,
        )(jnp.arange(3.0))
        np.testing.assert_allclose(np.asarray(out), [0.0, 1.0, 2.0])

    def test_default_check_flag_jittable(self):
        mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
        f = jax.jit(compat.shard_map(
            lambda t: t + 1.0, mesh=mesh, in_specs=(P(),), out_specs=P(),
        ))
        np.testing.assert_allclose(np.asarray(f(jnp.zeros(3))), np.ones(3))


class TestAbstractMesh:
    def test_construction_and_shape(self):
        mesh = compat.abstract_mesh((4, 2), ("data", "model"))
        assert tuple(mesh.axis_names) == ("data", "model")
        assert mesh.shape["data"] == 4
        assert mesh.shape["model"] == 2

    def test_three_axes(self):
        mesh = compat.abstract_mesh((2, 4, 2), ("pod", "data", "model"))
        assert dict(mesh.shape) == {"pod": 2, "data": 4, "model": 2}

    def test_mismatched_lengths_raise(self):
        with pytest.raises(ValueError, match="disagree"):
            compat.abstract_mesh((4, 2), ("data",))

    def test_usable_for_spec_resolution(self):
        from repro.runtime import sharding as shd

        mesh = compat.abstract_mesh((4, 2), ("data", "model"))
        with shd.use_rules(mesh):
            spec = shd.resolve_spec((8, 16), ("batch", "heads"))
        assert spec == P(("data",), "model")


class TestMakeMesh:
    def test_make_mesh_singleton(self):
        mesh = compat.make_mesh((1, 1), ("data", "model"))
        assert tuple(mesh.axis_names) == ("data", "model")
        assert mesh.devices.size == 1

    def test_axes_default_to_auto(self):
        mesh = compat.make_mesh((1, 1), ("data", "model"))
        assert tuple(mesh.axis_types) == (compat.AxisType.Auto,) * 2

    def test_make_mesh_explicit_axis_types(self):
        mesh = compat.make_mesh((1,), ("data",), axis_types=(compat.AxisType.Auto,))
        assert mesh.shape["data"] == 1
