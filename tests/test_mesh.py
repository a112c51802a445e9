import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_MODEL,
    MeshSpec,
    batch_sharding,
    build_mesh,
    local_batch_size,
    mesh_summary,
)


def test_default_mesh_all_data(devices8):
    mesh = build_mesh()
    assert mesh.shape[AXIS_DATA] == 8
    assert mesh.devices.size == 8


def test_mesh_spec_resolve():
    spec = MeshSpec(model=2, seq=2).resolve(8)
    assert spec.data == 2
    assert spec.model == 2 and spec.seq == 2


def test_mesh_spec_bad_divisibility():
    with pytest.raises(ValueError):
        MeshSpec(model=3).resolve(8)
    with pytest.raises(ValueError):
        MeshSpec(data=4, model=4).resolve(8)


def test_mesh_spec_from_dict_rejects_unknown():
    with pytest.raises(ValueError):
        MeshSpec.from_dict({"tensor": 2})


def test_build_mesh_2d(devices8):
    mesh = build_mesh(MeshSpec(data=2, model=4))
    assert mesh.shape[AXIS_DATA] == 2
    assert mesh.shape[AXIS_MODEL] == 4


def test_batch_sharding_puts_batch_on_data(devices8):
    mesh = build_mesh(MeshSpec(data=4, fsdp=2))
    x = np.arange(16 * 3, dtype=np.float32).reshape(16, 3)
    xs = jax.device_put(x, batch_sharding(mesh, extra_dims=1))
    # batch dim sharded over dcn*data*fsdp*expert = 8 (size-1 axes free;
    # expert is a batch axis so EP meshes don't duplicate dense compute)
    assert xs.sharding.spec == P(("dcn", AXIS_DATA, "fsdp", "expert"), None)
    np.testing.assert_array_equal(np.asarray(xs), x)


def test_local_batch_size(devices8):
    mesh = build_mesh(MeshSpec(data=4, fsdp=2))
    assert local_batch_size(mesh, 32) == 4
    with pytest.raises(ValueError):
        local_batch_size(mesh, 30)


def test_mesh_summary(devices8):
    s = mesh_summary(build_mesh(MeshSpec(data=8)))
    assert "data=8" in s


class TestDcnAxis:
    """Multislice: the outer `dcn` axis (SURVEY §2.5 "DCN
    across slices")."""

    def test_dcn_in_resolve_and_batch_axes(self):
        from kubeflow_tpu.parallel.mesh import AXIS_DCN, BATCH_AXES

        spec = MeshSpec(dcn=2, model=2).resolve(8)
        assert spec.data == 2
        assert spec.axis_sizes()[AXIS_DCN] == 2
        assert BATCH_AXES == (AXIS_DCN, AXIS_DATA, "fsdp", "expert")
        assert spec.batch_axes == BATCH_AXES

    def test_build_mesh_dcn_outermost_contiguous_ranks(self, devices8):
        """CPU fallback: ranks [0..3] form dcn group 0, [4..7] group 1 —
        the contiguous-rank layout the JAXJob controller assigns
        slice_id = rank // per_slice by."""
        from kubeflow_tpu.parallel.mesh import AXIS_DCN

        mesh = build_mesh(MeshSpec(dcn=2, data=2, model=2))
        assert mesh.shape[AXIS_DCN] == 2
        devs = mesh.devices  # shape (dcn, data, fsdp, pipe, expert, seq, model)
        slice0 = {d.id for d in devs[0].flat}
        slice1 = {d.id for d in devs[1].flat}
        assert slice0 == {0, 1, 2, 3} and slice1 == {4, 5, 6, 7}

    def test_local_batch_counts_dcn(self, devices8):
        mesh = build_mesh(MeshSpec(dcn=2, data=2, model=2))
        assert local_batch_size(mesh, 32) == 8  # 32 / (2 dcn * 2 data)

    def test_dcn_step_executes_with_psum_over_slices(self, devices8):
        """A jitted step sharded over (dcn, data) must produce the same
        global gradient sum as single-device math — the all-reduce
        crosses the dcn axis."""
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        from kubeflow_tpu.parallel.mesh import batch_spec

        mesh = build_mesh(MeshSpec(dcn=2, data=4))
        x = jnp.arange(16.0).reshape(16, 1)

        def loss(w, x):
            return jnp.mean((x @ w) ** 2)

        w = jnp.ones((1, 1))
        with mesh:
            g = jax.jit(
                jax.grad(loss),
                in_shardings=(NamedSharding(mesh, P()),
                              NamedSharding(mesh, batch_spec(mesh, 1))),
            )(w, x)
        ref = jax.grad(loss)(w, x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(ref), rtol=1e-6)
