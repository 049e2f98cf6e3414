"""Decode-serving mesh builders.

The serving mesh is always 2-D ``(data, model)``: KV pages (and so plan
subtasks) shard over ``data``, KV heads over ``model`` (TP-aligned with
``launch.sharding``'s param scheme).  Unlike ``launch.mesh`` these
builders make *plain* meshes (no GSPMD auto axis types): the sharded
decode step is traced manually under ``shard_map``, which owns both
axes explicitly.

A mesh takes the devices present: TPU chips, or on the CPU host devices
emulated with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
(the pattern the tests use); a ``1x1`` mesh exercises the full SPMD
code path on a single device.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def parse_mesh(spec: str) -> Tuple[int, int]:
    """``"DxM"`` -> ``(data, model)`` sizes (e.g. ``"2x2"``)."""
    try:
        d, m = spec.lower().split("x")
        d, m = int(d), int(m)
    except ValueError:
        raise ValueError(f"mesh spec must look like '2x1', got {spec!r}")
    if d < 1 or m < 1:
        raise ValueError(f"mesh sizes must be >= 1, got {spec!r}")
    return d, m


def decode_mesh(data: int = 1, model: int = 1):
    """Build a ``(data, model)`` mesh over the first ``data*model``
    devices.  ``data`` must be a power of two (the cross-device POR
    merge is a recursive-doubling butterfly)."""
    import jax

    if data & (data - 1):
        raise ValueError(f"data axis must be a power of two, got {data}")
    n = data * model
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(
            f"mesh {data}x{model} needs {n} devices, have {len(devs)} "
            f"(on the CPU, emulate them with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} set before jax "
            f"initialises)")
    return jax.sharding.Mesh(
        np.array(devs[:n]).reshape(data, model), ("data", "model"))


def mesh_shape(mesh) -> Tuple[int, int]:
    return mesh.shape["data"], mesh.shape["model"]
