"""Mesh construction. FUNCTIONS (not module-level state) so importing
this module never touches jax device initialization.

`make_mesh` is the one constructor every mesh in the repository goes
through, tests included. Its axes are `AxisType.Auto`: GSPMD propagates
shardings and `with_sharding_constraint` (dist/context.py) may name any
axis. `jax.make_mesh` alone makes Explicit axes, which refuse those
constraints."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """A mesh of `shape` over `axes`, every axis Auto. `devices`
    defaults to the first prod(shape) visible devices."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.
    Axes: data = FSDP/ZeRO + batch, model = TP/EP, pod = pure DP."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-process CPU mesh for tests/examples (1 device)."""
    return make_mesh((1, 1), ("data", "model"))


def make_serve_mesh(*, data: int | None = None, model: int = 1):
    """Serving mesh over the visible devices: `data` page-pool shards
    (each holding an equal block of the paged-KV pool and an equal
    slice of the batch) x `model` tensor-parallel ways. Defaults to all
    devices on the data axis. On CPU, pair with `XLA_FLAGS=
    --xla_force_host_platform_device_count=N` (or `launch.serve
    --devices N`) to rehearse multi-device serving; on a TPU host the
    chips are the devices."""
    n = len(jax.devices())
    if data is None:
        data = max(n // model, 1)
    if data * model > n:
        raise ValueError(f"mesh {data}x{model} needs {data * model} "
                         f"devices, only {n} visible")
    return make_mesh((data, model), ("data", "model"))
