"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — ``pod``
composes with ``data`` into the batch/FSDP axis; ``model`` (TP/EP) stays
intra-pod on ICI.  Scaling to N pods changes one integer here.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS first.
"""

from __future__ import annotations

import jax


def _axis_type_kwargs(n_axes: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_type_kwargs(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh helper (tests use small fake-device meshes)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), **_axis_type_kwargs(len(axes)))


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def named(mesh, spec_tree):
    """Convert a tree of PartitionSpecs to NamedShardings for this mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.tree.map(
        lambda sp: NamedSharding(mesh, sp) if isinstance(sp, P) else sp,
        spec_tree,
        is_leaf=lambda x: isinstance(x, P) or x is None)
