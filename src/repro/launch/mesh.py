"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state. The dry-run sets XLA_FLAGS before any jax import to get
512 host devices; smoke tests and benches see the real devices.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) data x model single pod; (2, 16, 16) pod x data x model for
    the 512-chip two-pod dry-run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_worker_mesh(n_workers: int, n_model: int = 1, devices=None):
    """One device per USEC worker (x ``n_model`` model shards).

    Every axis is Auto: the executors shard_map the worker axis by hand
    and leave any other axis to GSPMD. Raises naming both counts when the
    host has too few devices: a worker is one device, so one chip runs
    N=1."""
    devices = devices if devices is not None else jax.devices()
    need = n_workers * n_model
    if len(devices) < need:
        raise ValueError(
            f"{n_workers} workers x {n_model} model shards need {need} "
            f"devices (one per worker); found {len(devices)} "
            f"{devices[0].platform if devices else 'no'} device(s)")
    shape, axes = (n_workers, n_model), ("data", "model")
    if n_model == 1:
        shape, axes = (n_workers,), ("data",)
    return jax.make_mesh(shape, axes, devices=devices[:need],
                         axis_types=(AxisType.Auto,) * len(axes))
