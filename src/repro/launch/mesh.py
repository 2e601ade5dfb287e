"""Production mesh construction.

A *function*, not a module-level constant — importing this module never
touches jax device state, so tests and benches keep seeing 1 CPU device.
The dry-run entrypoint sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
before any jax import; everything here just consumes ``jax.devices()``.

Topology (TPU v5e target):
    single-pod : (data=16, model=16)            = 256 chips
    multi-pod  : (pod=2, data=16, model=16)     = 512 chips

``model`` is the high-bandwidth inner axis (TP/EP); ``data``/(``pod``,``data``)
carry batch + FSDP.  ``make_slice_mesh`` builds sub-meshes for HPO trials.
Every axis is ``AxisType.Auto``: the sharding rules here speak GSPMD
(``with_sharding_constraint``), which Explicit axes — ``jax.make_mesh``'s
default — reject.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"production mesh {shape} needs {n} devices, found {len(devices)}; "
            "run under launch/dryrun.py which forces 512 host devices"
        )
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_trial_mesh(
    n_devices: int,
    axes: Tuple[str, ...] = ("data", "model"),
    shape: Optional[Tuple[int, ...]] = None,
    devices: Optional[Sequence] = None,
) -> jax.sharding.Mesh:
    """Mesh for a single HPO trial on a slice of the pod (or the CPU container)."""
    devices = list(devices) if devices is not None else jax.devices()[:n_devices]
    if shape is None:
        # favour the model axis: (1, n) for tiny trials, squarish otherwise
        d = 1
        while d * d <= n_devices:
            d += 1
        d -= 1
        while n_devices % d:
            d -= 1
        shape = (d, n_devices // d)
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))
