"""Device mesh construction for TPU slices.

The reference platform's only notion of topology is "N replica pods, each
asking for `nvidia.com/gpu: 1`" (tf-controller-examples/tf-cnn/
create_job_specs.py:165-170). On TPU the topology is first-class: a slice
is a 2D/3D torus of chips wired by ICI, and XLA lowers collectives onto
that torus. This module owns the mapping from a logical parallelism spec
(dp/fsdp/tp/pp/sp/ep axis sizes) to a physical `jax.sharding.Mesh`.

Axis vocabulary (used by models, trainer, and kernels throughout):

- ``dcn``      — the cross-slice axis: data parallelism over the
                 data-center network on multislice deployments (one
                 gradient all-reduce per step; the only collective slow
                 enough for DCN).
- ``data``     — pure data parallelism (gradient all-reduce).
- ``fsdp``     — data parallelism with parameter/optimizer sharding
                 (all-gather params, reduce-scatter grads).
- ``model``    — tensor parallelism (Megatron-style row/col sharding).
- ``pipe``     — pipeline stages.
- ``seq``      — sequence/context parallelism (ring attention axis).
- ``expert``   — expert parallelism for MoE (all-to-all dispatch).

Collectives for `dcn`/`data`/`fsdp` are cheap and tolerate DCN;
`model`/`seq` collectives are per-layer and must ride ICI. `build_mesh`
therefore puts the fastest-varying (innermost, ICI-adjacent) device
dimension on `model`/`seq` and the outermost on `dcn` then `data`,
matching the scaling-book recipe of "model-parallel inner, data-parallel
outer, slices outermost". On real multislice hardware the ``dcn`` axis is
placed with `mesh_utils.create_hybrid_device_mesh` so each slice's
devices stay ICI-contiguous.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_DCN = "dcn"
AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_PIPELINE = "pipe"
AXIS_EXPERT = "expert"
AXIS_SEQ = "seq"
AXIS_MODEL = "model"

# Outer-to-inner physical placement order. Inner axes get ICI-adjacent
# devices; the outermost (dcn) spans slices on multi-slice deployments.
_AXIS_ORDER = (AXIS_DCN, AXIS_DATA, AXIS_FSDP, AXIS_PIPELINE, AXIS_EXPERT,
               AXIS_SEQ, AXIS_MODEL)

# The canonical axis vocabulary, public. tpulint's sharding-consistency
# rules (TPU105/TPU106, kubeflow_tpu/analysis/rules_sharding.py) resolve
# every PartitionSpec axis name against this tuple — a new axis must be
# added here (the lint's mirror is AST-pinned to _AXIS_ORDER in
# tests/test_tpulint.py) before any spec may name it.
AXIS_NAMES: tuple[str, ...] = _AXIS_ORDER

# Every batch-sharded PartitionSpec uses this tuple; size-1 axes are free,
# so single-slice meshes pay nothing for carrying the dcn name.
# `expert` is a batch axis too (GShard-style): outside MoE layers the
# expert dimension has nothing to shard, and leaving tokens replicated
# across it would duplicate every dense block's compute ep-fold. Inside
# an MoE layer the token<->expert regrouping is exactly the all-to-all
# over this axis (ops/moe.py).
BATCH_AXES = (AXIS_DCN, AXIS_DATA, AXIS_FSDP, AXIS_EXPERT)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical parallelism specification.

    Any axis set to 1 is still present in the mesh (size-1 axes are free),
    so a single `PartitionSpec` vocabulary works for every configuration.
    ``data = -1`` means "whatever is left over" and is resolved against the
    device count at mesh-build time.
    """

    dcn: int = 1
    data: int = -1
    fsdp: int = 1
    pipe: int = 1
    expert: int = 1
    seq: int = 1
    model: int = 1

    def resolve(self, n_devices: int) -> "MeshSpec":
        """Resolve data=-1 against the device count; validate divisibility."""
        fixed = (self.dcn * self.fsdp * self.pipe * self.expert * self.seq
                 * self.model)
        data = self.data
        if data == -1:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"device count {n_devices} not divisible by non-data axes "
                    f"product {fixed} (spec={self})"
                )
            data = n_devices // fixed
        total = data * fixed
        if total != n_devices:
            raise ValueError(
                f"mesh spec {self} needs {total} devices, have {n_devices}"
            )
        return dataclasses.replace(self, data=data)

    def axis_sizes(self) -> dict[str, int]:
        return {
            AXIS_DCN: self.dcn,
            AXIS_DATA: self.data,
            AXIS_FSDP: self.fsdp,
            AXIS_PIPELINE: self.pipe,
            AXIS_EXPERT: self.expert,
            AXIS_SEQ: self.seq,
            AXIS_MODEL: self.model,
        }

    @property
    def batch_axes(self) -> tuple[str, ...]:
        """Axes the global batch is sharded over."""
        return BATCH_AXES

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "MeshSpec":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown mesh axes {sorted(unknown)}; known: {sorted(known)}")
        return cls(**{k: int(v) for k, v in d.items()})


def build_mesh(
    spec: MeshSpec | Mapping[str, Any] | None = None,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a `jax.sharding.Mesh` from a logical spec.

    Uses `mesh_utils.create_device_mesh`: on TPU devices the physical
    assignment follows the slice's ICI topology (it understands TPU
    coords) and an impossible shape raises; on CPU devices it is a plain
    reshape, which keeps the dcn axis outermost — the contiguous-rank
    layout the JAXJob controller assigns slices by.
    """
    if devices is None:
        devices = jax.devices()
    if spec is None:
        spec = MeshSpec()
    if not isinstance(spec, MeshSpec):
        spec = MeshSpec.from_dict(spec)
    spec = spec.resolve(len(devices))
    sizes = spec.axis_sizes()
    shape = tuple(sizes[a] for a in _AXIS_ORDER)
    dev_np = np.asarray(devices, dtype=object)
    if spec.dcn > 1 and all(
            getattr(d, "slice_index", None) is not None for d in devices):
        # real multislice hardware: the dcn axis must fall on slice
        # boundaries so inner axes stay ICI-contiguous. Errors here (dcn
        # not matching the actual slice count, per-slice shape mismatch)
        # MUST propagate — a silent reshape would put per-layer
        # collectives on DCN, an order-of-magnitude slowdown.
        ici_shape = (1,) + shape[1:]
        dcn_shape = (spec.dcn,) + (1,) * (len(shape) - 1)
        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=dev_np)
        return Mesh(dev_array, _AXIS_ORDER)
    return Mesh(mesh_utils.create_device_mesh(shape, devices=dev_np),
                _AXIS_ORDER)


def batch_spec(mesh: Mesh, extra_dims: int = 0) -> P:
    """PartitionSpec for a batch-major array: shard dim 0 over data axes."""
    del mesh
    return P(BATCH_AXES, *([None] * extra_dims))


def batch_sharding(mesh: Mesh, extra_dims: int = 0) -> NamedSharding:
    return NamedSharding(mesh, batch_spec(mesh, extra_dims))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def local_batch_size(mesh: Mesh, global_batch: int) -> int:
    n = 1
    for a in BATCH_AXES:
        n *= mesh.shape[a]
    if global_batch % n:
        raise ValueError(f"global batch {global_batch} not divisible by dp={n}")
    return global_batch // n


def mesh_summary(mesh: Mesh) -> str:
    axes = ", ".join(f"{k}={v}" for k, v in mesh.shape.items() if v > 1) or "single-device"
    kinds = {d.device_kind for d in mesh.devices.flat}
    return f"Mesh({axes}) on {mesh.devices.size}x {'/'.join(sorted(kinds))}"


def pad_to_multiple(x: int, m: int) -> int:
    return int(math.ceil(x / m) * m)


def current_mesh() -> Mesh | None:
    """The mesh installed by the ambient `with mesh:` context, if any."""
    env = jax._src.mesh.thread_resources.env
    m = env.physical_mesh
    return None if m.empty else m


def shard_constraint(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint that is a no-op outside a mesh context.

    Mesh presence is checked explicitly (rather than try/except) so real
    sharding errors — rank mismatch, indivisible dims — still propagate."""
    if current_mesh() is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)
