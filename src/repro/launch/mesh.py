"""Production meshes (functions — importing this module never touches jax
device state; jax.make_mesh is only called when the launcher asks).

single-pod: (16, 16)    -> ('data', 'model')      256 chips
multi-pod : (2, 16, 16) -> ('pod', 'data', 'model') 512 chips

Hardware model (TPU v5e-like, used by the roofline):
  197 TFLOP/s bf16 / chip, 819 GB/s HBM / chip, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import warnings

import jax
from jax.sharding import AxisType

PEAK_FLOPS = 197e12       # bf16 per chip
HBM_BW = 819e9            # bytes/s per chip
ICI_BW = 50e9             # bytes/s per link


def _mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-propagated
    shardings), the semantics this code is written for; newer JAX
    defaults to ``Explicit`` axes, which type every array's sharding."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — used by tests."""
    return _mesh((data, model), ("data", "model"))


def make_worker_mesh(workers: int, *, model_parallel: int = 1,
                     axis_name: str = "worker", model: int | None = None):
    """Mesh for comm='axis' decentralized execution: one slot of
    ``axis_name`` per worker (the optimizer's ppermute gossip runs over
    it), optionally crossed with an inner 'model' axis
    (``model_parallel=M``) so each worker is itself an M-device
    model-parallel group — the packed optimizer state is then sharded
    ``P('worker', 'model')``, gossip still crosses only the worker axis,
    and grads are computed model-parallel within each worker
    (``make_optimizer(comm='axis', mesh=...)`` picks M up from the mesh).
    Needs ``workers * model_parallel`` devices. ``model=`` is the
    deprecated spelling of ``model_parallel``."""
    if model is not None:
        if model_parallel != 1:
            raise ValueError(
                "pass either model_parallel= or the deprecated model=, "
                f"not both (got model_parallel={model_parallel}, "
                f"model={model})")
        warnings.warn("make_worker_mesh(model=...) is deprecated; use "
                      "model_parallel=", DeprecationWarning, stacklevel=2)
    m = model_parallel if model is None else model
    if m > 1:
        return _mesh((workers, m), (axis_name, "model"))
    return _mesh((workers,), (axis_name,))


def n_chips(mesh) -> int:
    return mesh.devices.size
