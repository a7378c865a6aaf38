"""Production meshes.

Topology-aware axis placement (the paper's rule applied to mesh design): the
`model` (TP) axis — one collective per layer — maps to the innermost,
fastest device dimension; `data` spans a pod's ICI; `pod` is the outermost
DCN level and carries exactly one (multilevel-decomposed) gradient exchange
per step.  No tensor-parallel collective ever crosses a pod boundary.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_test_mesh",
           "mesh_topology", "dp_topology", "dp_decomposition",
           "mesh_communicator"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the model code relies on
    GSPMD propagation (``jax.make_mesh`` defaults to ``Explicit`` axes, under
    which its gathers, sorts and ragged dots refuse to trace)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(pods: int = 1, data: int = 2, model: int = 2):
    """(pod, data, model) mesh over the first ``pods*data*model`` devices;
    the pod axis appears only when ``pods > 1``."""
    if pods > 1:
        return make_mesh((pods, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def mesh_topology(mesh) -> "object":
    """The core.Topology matching a mesh: strata = [pod, data-row]; used to
    build the paper's explicit trees over the flattened device order."""
    import numpy as np
    from repro.core.topology import Topology, DCN, ICI_FAR, ICI

    pods = mesh.shape.get("pod", 1)
    data = mesh.shape.get("data", 1)
    model = mesh.shape.get("model", 1)
    P = pods * data * model
    idx = np.arange(P)
    coords = np.stack([idx // (data * model), idx // model], axis=1)
    return Topology(coords, [DCN, ICI_FAR, ICI])


def dp_topology(mesh) -> "object":
    """The core.Topology over the DATA-PARALLEL ranks only (pod x data),
    matching the jax backend's flat (slow, *fast) index space — model-axis
    peers hold distinct parameter shards and are not collective members."""
    import numpy as np
    from repro.core.topology import Topology, DCN, ICI

    pods = mesh.shape.get("pod", 1)
    data = mesh.shape.get("data", 1)
    coords = (np.arange(pods * data) // data)[:, None]
    return Topology(coords, [DCN, ICI])


def dp_decomposition(mesh) -> tuple:
    """(slow_axis, fast_axes) of the data-parallel axes: the multilevel
    gradient exchange reduce-scatters over ``fast_axes`` and crosses
    ``slow_axis`` (the DCN) exactly once per step."""
    slow = "pod" if "pod" in mesh.shape else None
    fast = ("data",) if "data" in mesh.shape else ()
    return slow, fast


def mesh_communicator(mesh, *, backend: str = "jax", policy="paper", **kw):
    """The :class:`repro.core.Communicator` for a device mesh.

    backend "jax": axis-decomposed collectives over the dp axes.
    backend "ppermute": explicit tree rounds over a single flattened axis
        (pass ``axis=``, or use a 1-axis mesh).
    backend "sim": postal-model planning/estimation on the mesh's topology.
    """
    from repro.core import Communicator

    topo = mesh_topology(mesh)
    if backend == "jax":
        # rank space = (pod, data) only: use the dp-scoped topology so
        # member/root indices agree with the backend's axis_index space
        topo = dp_topology(mesh)
        slow, fast = dp_decomposition(mesh)
        kw.setdefault("slow_axis", slow)
        kw.setdefault("fast_axes", fast)
    elif backend == "ppermute" and "axis" not in kw:
        if len(mesh.axis_names) != 1:
            raise ValueError("ppermute backend needs axis= on multi-axis "
                             "meshes")
        kw["axis"] = mesh.axis_names[0]
    return Communicator(topo, backend=backend, policy=policy, **kw)
