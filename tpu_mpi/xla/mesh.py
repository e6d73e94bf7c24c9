"""Mesh construction: binding communicators to device grids.

Reference analog: the launcher + libmpi fix ranks at MPI_Init
(/root/reference/src/environment.jl:80-89); Cartesian topology maps ranks to
grids (src/topology.jl:30-49). On TPU the device grid is primary:
``jax.sharding.Mesh`` built by ``mesh_utils.create_device_mesh`` honors the
physical ICI torus so that neighboring mesh coordinates are neighboring chips
(SURVEY.md §2.3 topology row) — the analog of mapping Cart ranks onto the
interconnect for bandwidth.
"""

from __future__ import annotations

from typing import Mapping, Optional, Sequence, Union

import numpy as np


def local_device_count() -> int:
    import jax
    return len(jax.devices())


def make_mesh(axes: Union[Mapping[str, int], Sequence[int]],
              names: Optional[Sequence[str]] = None, devices=None):
    """Build a Mesh from {axis: size} (or a shape plus names).

    Uses ``mesh_utils.create_device_mesh`` when the device count matches the
    full grid so TPU ICI topology is respected (a shape the topology cannot
    host raises); a subset of the devices is laid out in C order.
    """
    import jax
    from jax.sharding import Mesh
    from jax.experimental import mesh_utils

    if isinstance(axes, Mapping):
        names = tuple(axes.keys())
        shape = tuple(int(s) for s in axes.values())
    else:
        shape = tuple(int(s) for s in axes)
        if names is None:
            names = tuple(f"ax{i}" for i in range(len(shape)))
        names = tuple(names)
    if devices is None:
        devices = jax.devices()
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(f"mesh {dict(zip(names, shape))} needs {n} devices, "
                         f"have {len(devices)}")
    if n == len(devices) and devices == jax.devices():
        return Mesh(mesh_utils.create_device_mesh(shape), names)
    dev_array = np.array(devices[:n]).reshape(shape)
    return Mesh(dev_array, names)


def world_mesh(axis: str = "world"):
    """A 1-d mesh over all local devices — the COMM_WORLD of the in-graph
    layer."""
    return make_mesh({axis: local_device_count()})


def comm_mesh(comm, axis: str = "comm"):
    """A Mesh over a host-side communicator's devices.

    Bridges the two faces: the classic ``Comm`` (an ordered rank set, each
    rank owning one device) becomes a 1-d mesh whose axis order is the comm's
    rank order, so in-graph collectives over ``axis`` line up with host-side
    rank numbering. For a ``CartComm`` the grid shape and per-dimension axis
    names (``cart0``, ``cart1``, …) are preserved.
    """
    import jax
    from jax.sharding import Mesh

    ctx = comm.ctx
    devs = [ctx.device_for(w) for w in comm.group]
    dims = getattr(comm, "dims", None)
    if dims is not None:
        names = tuple(f"cart{i}" for i in range(len(dims)))
        return Mesh(np.array(devs).reshape(tuple(dims)), names)
    return Mesh(np.array(devs), (axis,))
