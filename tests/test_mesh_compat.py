"""`parallel/mesh.py::shard_map_compat` on the one installation.

The shim once accepted whichever shard_map the running jax exposed —
`jax.shard_map` (`check_vma=`) or the 0.4.x
`jax.experimental.shard_map.shard_map` (`check_rep=`). The repo runs on
one installation (jax 0.9), so the 0.4.x branch is gone; what stays
asserted is the call shape the shim hands `jax.shard_map` and that the
wrapped kernel computes across a real multi-device mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from predictionio_tpu.parallel import mesh as mesh_mod


def _psum_through(compat_result):
    """Run the wrapped kernel on a 1-device mesh and return the sum."""
    return np.asarray(compat_result(jnp.arange(8, dtype=jnp.float32)))


def _kernel(x):
    return jax.lax.psum(jnp.sum(x), "block")


def test_shard_map_compat_native_spelling(monkeypatch):
    """`jax.shard_map` present -> used, with the check_vma spelling."""
    calls = {}

    def fake_shard_map(f, mesh, in_specs, out_specs, **kwargs):
        calls.update(kwargs, mesh=mesh, in_specs=in_specs)
        # delegate to the real implementation so the wrapped kernel is
        # genuinely executable — the fake only asserts the call shape
        from jax.experimental.shard_map import shard_map
        return shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_rep=False)

    monkeypatch.setattr(jax, "shard_map", fake_shard_map, raising=False)
    m = mesh_mod.get_mesh(1)
    wrapped = mesh_mod.shard_map_compat(_kernel, m, (P("block"),), P())
    assert calls["check_vma"] is False          # the new-API spelling
    assert "check_rep" not in calls
    assert calls["mesh"] is m
    assert calls["in_specs"] == (P("block"),)   # sequence normalized
    assert _psum_through(wrapped) == pytest.approx(28.0)


def test_shard_map_compat_never_needs_the_experimental_module(monkeypatch):
    """The 0.4.x spelling is not a fallback any more: with
    `jax.experimental.shard_map` unusable the shim still works."""
    import jax.experimental.shard_map as exp_mod

    def boom(*_a, **_k):
        raise AssertionError("the 0.4.x shard_map spelling was called")

    monkeypatch.setattr(exp_mod, "shard_map", boom)
    m = mesh_mod.get_mesh(1)
    wrapped = mesh_mod.shard_map_compat(_kernel, m, [P("block")], P())
    assert _psum_through(wrapped) == pytest.approx(28.0)


def test_shard_map_compat_across_the_virtual_mesh():
    """The same kernel over all 8 virtual devices: each device sums its
    slice, the psum gives every device the total; list and tuple
    in_specs are the same call."""
    m = mesh_mod.get_mesh(8)
    via_tuple = _psum_through(
        mesh_mod.shard_map_compat(_kernel, m, (P("block"),), P()))
    via_list = _psum_through(
        mesh_mod.shard_map_compat(_kernel, m, [P("block")], P()))
    assert via_tuple == pytest.approx(28.0)
    np.testing.assert_array_equal(via_tuple, via_list)


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
