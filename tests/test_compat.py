"""src/repro/compat.py: the three wrappers pass the settings the repo relies
on (replication checking off, Auto axis types) to the jax 0.9 API.

Each wrapper looks its jax symbol up at call time, so a recording stub
installed with monkeypatch sees exactly what compat passes."""
import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import compat


def _mesh1():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))


def test_shard_map_new_api_branch(monkeypatch):
    """compat must call ``jax.shard_map`` with ``check_vma=False``."""
    calls = {}
    real = jax.shard_map

    def fake_shard_map(f, *, mesh, in_specs, out_specs, check_vma):
        calls["check_vma"] = check_vma
        return real(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                    check_vma=check_vma)

    monkeypatch.setattr(jax, "shard_map", fake_shard_map)
    fn = compat.shard_map(lambda x: x * 2, mesh=_mesh1(), in_specs=(P(),),
                          out_specs=P())
    out = fn(jnp.arange(4))
    assert calls == {"check_vma": False}
    assert (np.asarray(out) == 2 * np.arange(4)).all()


def test_axis_size_new_api_branch(monkeypatch):
    """compat must return ``lax.axis_size``'s answer."""
    sentinel = jnp.int32(12345)
    monkeypatch.setattr(lax, "axis_size", lambda name: sentinel)
    assert int(compat.axis_size("data")) == 12345


def test_make_mesh_new_api_branch(monkeypatch):
    """compat must request Auto axis types for every axis."""
    class FakeAxisType:
        Auto = "auto-sentinel"

    calls = {}

    def fake_make_mesh(axis_shapes, axis_names, axis_types=None):
        calls["axis_types"] = axis_types
        return "mesh-sentinel"

    monkeypatch.setattr(jax.sharding, "AxisType", FakeAxisType)
    monkeypatch.setattr(jax, "make_mesh", fake_make_mesh)
    assert compat.make_mesh((1, 1), ("a", "b")) == "mesh-sentinel"
    assert calls == {"axis_types": ("auto-sentinel", "auto-sentinel")}


def test_make_mesh_builds_a_real_mesh():
    """End to end on the installed jax: a usable 1-device mesh."""
    mesh = compat.make_mesh((1,), ("data",))
    assert mesh.shape["data"] == 1
