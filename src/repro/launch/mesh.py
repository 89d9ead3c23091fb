"""Production meshes.

Functions (not module-level constants) so importing never touches jax device
state.  The dry-run forces 512 host devices via XLA_FLAGS before any import
(see dryrun.py); real deployments get the same shapes from the TPU topology.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh_for(n_devices: int, model_parallel: int = 1, pods: int = 1):
    """Elastic-scaling entry point: build the best mesh for the devices that
    are actually alive (used by repro.ft on restart after failures)."""
    assert n_devices % (model_parallel * pods) == 0, (n_devices, model_parallel, pods)
    data = n_devices // (model_parallel * pods)
    if pods > 1:
        return jax.make_mesh((pods, data, model_parallel),
                             ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
    return jax.make_mesh((data, model_parallel), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_description(mesh) -> dict:
    return {"axis_names": list(mesh.axis_names),
            "shape": [int(mesh.shape[a]) for a in mesh.axis_names],
            "n_devices": int(mesh.size)}
