"""Production and serving mesh definitions.

``make_production_mesh`` / ``make_serving_mesh`` are FUNCTIONS (not module
constants) so importing this module never touches jax device state —
mandatory because the dry-run must set XLA_FLAGS before any jax
initialization.  Every mesh is built with ``AxisType.Auto`` axes (GSPMD
propagation plus explicit ``shard_map`` regions — the sharding model the
model code is written against).
"""
from __future__ import annotations

import jax
import numpy as np


def _mk_mesh(shape, axes):
    """``jax.make_mesh`` over the first ``prod(shape)`` devices with Auto
    axes (``make_mesh`` itself defaults to Explicit)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
    a second data-parallel axis crossing the DCN/ICI boundary."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk_mesh(shape, axes)


def make_serving_mesh(dp: int, tp: int):
    """Serving mesh ``(data=dp, model=tp)`` over the first ``dp * tp``
    devices: the ``model`` axis tensor-parallelizes attention heads and
    the paged KV pools inside each engine replica; the ``data`` axis
    indexes data-parallel engine replicas (request queues are partitioned
    host-side — see ``launch/engine.py: ReplicatedEngine``)."""
    if dp < 1 or tp < 1:
        raise ValueError(f"mesh axes must be >= 1, got dp={dp} tp={tp}")
    return _mk_mesh((dp, tp), ("data", "model"))


def replica_meshes(mesh, n: int = None) -> list:
    """One single-axis ``("model",)`` sub-mesh per ``data`` row of a
    serving mesh — each data-parallel engine replica runs its
    tensor-parallel attention over its OWN row of devices, so replicas
    never share a collective.

    ``mesh=None`` with ``n`` set is the MESHLESS fleet: ``n`` unsharded
    engine replicas time-slicing the default device (disjoint page pools,
    no collectives — exactly the replica topology, minus the placement).
    That is how the HA suite exercises replica loss and live-request
    migration on a single-device CPU host."""
    if mesh is None:
        if n is None or n < 1:
            raise ValueError("replica_meshes: mesh=None needs an explicit "
                             f"replica count n >= 1, got {n!r}")
        return [None] * n
    devs = np.asarray(mesh.devices)
    if mesh.axis_names == ("model",):
        return [mesh]
    if mesh.axis_names != ("data", "model"):
        raise ValueError(f"expected a (data, model) serving mesh, got "
                         f"axes {mesh.axis_names}")
    subs = [jax.sharding.Mesh(devs[i], ("model",))
            for i in range(devs.shape[0])]
    if n is not None and n != len(subs):
        raise ValueError(f"mesh data axis has {len(subs)} replicas but "
                         f"replicas={n} was requested")
    return subs


def dp_axes_of(mesh) -> tuple:
    """The batch-sharding axes of a production mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
