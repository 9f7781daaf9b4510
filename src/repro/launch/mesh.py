"""Production meshes (TPU v5e pods) + the sampler shard layout (``ShardSpec``).

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run entrypoint sets XLA_FLAGS *before* any jax import.
``ShardSpec`` is the one exception to the functions-only rule: it is a
frozen, hashable *description* of a layout (mesh shape + axis names + which
axis carries the client dimension) — building it touches no device state
either; the mesh is materialized lazily by ``ShardSpec.mesh()``.
"""
from __future__ import annotations

import dataclasses

import jax

__all__ = [
    "ShardSpec",
    "make_mesh",
    "make_production_mesh",
    "make_host_mesh",
    "fsdp_axes",
    "batch_axes",
]


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding-constraint
    and ``shard_map`` code in this repo names mesh axes in
    ``with_sharding_constraint``, which only ``Auto`` axes accept (the
    installed JAX defaults to ``Explicit``).  ``devices`` defaults to all of
    this process's devices."""
    from jax.sharding import AxisType

    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Declarative layout of a sampler's (N,) client axis over a mesh.

    The sampler stack is configured with a ``ShardSpec`` (not a live
    ``Mesh``) so the frozen ``Sampler`` dataclasses stay hashable and
    JSON-describable: ``axes`` is the full mesh shape as
    ``((name, size), ...)`` pairs and ``axis`` names the mesh axis the
    (N,) client dimension is split over (every other axis replicates it).
    Two processes agreeing on a ``ShardSpec`` agree on the layout — which
    is why checkpoint manifests record ``to_manifest()`` and why restoring
    onto a *different* mesh shape is legal: the arrays round-trip through
    host numpy and are re-laid-out by the restoring process's own spec.
    """

    axes: tuple = (("data", 1),)  # ((axis_name, size), ...) — the mesh shape
    axis: str = "data"  # which axis carries the (N,) client dimension

    def __post_init__(self):
        object.__setattr__(
            self, "axes", tuple((str(n), int(s)) for n, s in self.axes)
        )
        names = [n for n, _ in self.axes]
        if self.axis not in names:
            raise ValueError(
                f"ShardSpec.axis {self.axis!r} is not a mesh axis; have {names}"
            )

    @classmethod
    def from_mesh(cls, mesh, axis: str = "data") -> "ShardSpec":
        return cls(
            axes=tuple(zip(mesh.axis_names, mesh.devices.shape)), axis=axis
        )

    @property
    def num_shards(self) -> int:
        return dict(self.axes)[self.axis]

    def mesh(self):
        """Materialize the described mesh over this process's devices."""
        return make_mesh(
            tuple(s for _, s in self.axes), tuple(n for n, _ in self.axes)
        )

    def named_sharding(self, mesh=None):
        """NamedSharding splitting a leading (N,) axis over ``self.axis``."""
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(mesh or self.mesh(), PartitionSpec(self.axis))

    def to_manifest(self) -> dict:
        """JSON-ready record for checkpoint manifests (provenance, not a
        restore constraint — see class docstring)."""
        return {"axes": [[n, s] for n, s in self.axes], "axis": self.axis}

    @classmethod
    def from_manifest(cls, data: dict) -> "ShardSpec":
        return cls(
            axes=tuple((n, s) for n, s in data["axes"]), axis=data["axis"]
        )


def _override_mesh():
    """REPRO_MESH_SHAPE env override, e.g. "4,4" or "2,4,4" (CI / host runs)."""
    import os

    override = os.environ.get("REPRO_MESH_SHAPE")
    if not override:
        return None
    shape = tuple(int(x) for x in override.split(","))
    axes = ("pod", "data", "model") if len(shape) == 3 else ("data", "model")
    return make_mesh(shape, axes)


def make_production_mesh(*, multi_pod: bool = False):
    mesh = _override_mesh()
    if mesh is not None:
        return mesh
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """(data, model) mesh over whatever devices THIS host exposes.

    REPRO_MESH_SHAPE overrides (same contract as ``make_production_mesh``);
    otherwise every device goes on the ``data`` axis, which carries the
    cohort (``client_parallel``) and the sampler's (N,) client axis — a
    four-chip host gives ``(data=4, model=1)``.  One device yields the
    degenerate (1, 1) mesh, so the mesh-parallel code path is exercised
    everywhere the tests run."""
    mesh = _override_mesh()
    if mesh is not None:
        return mesh
    return make_mesh((len(jax.devices()), 1), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Mesh axes carrying the batch/client dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def fsdp_axes(mesh) -> tuple:
    """Mesh axes over which fully-sharded parameters are scattered."""
    return batch_axes(mesh)
