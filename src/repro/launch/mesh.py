"""Production meshes (DESIGN.md §5).

Functions, not module constants: importing this module must never touch
jax device state (smoke tests run with 1 CPU device; only dryrun.py sets
XLA_FLAGS for 512 host devices).

Every mesh is built with Auto axis types: the sharding rules and the
serving programs are written for GSPMD propagation, while
`jax.make_mesh` defaults to Explicit axes (sharding in types), under
which e.g. the embedding gather refuses a vocab-sharded operand.

Hardware model (TPU v5e-class, used by the roofline):
  197 TFLOP/s bf16 per chip; 819 GB/s HBM; ~50 GB/s/link ICI.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

SINGLE_POD = (16, 16)        # 256 chips
MULTI_POD = (2, 16, 16)      # 512 chips


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh over the single local device (smoke scale)."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_serving_mesh(data: int, model: int):
    """REAL (data, model) mesh over the first data*model local devices:
    the chips of a TPU host, or virtual CPU devices for sharded-serving
    validation off the chip (launch/shard_serve.py, tests). Unlike the
    abstract device-duplicating test meshes, every position is a
    distinct addressable device, so programs actually SPMD-partition
    and execute. On the CPU the process needs that many virtual devices
    before jax initializes (shard_serve.main sets jax_num_cpu_devices);
    raises a clear error otherwise instead of building a broken mesh."""
    need = data * model
    have = len(jax.devices())
    if have < need:
        raise RuntimeError(
            f"make_serving_mesh({data}, {model}) needs {need} devices "
            f"but only {have} are visible (on the CPU, set "
            f"jax_num_cpu_devices >= {need} before jax initializes — "
            f"see launch/shard_serve.py).")
    return _auto_mesh((data, model), ("data", "model"),
                      devices=jax.devices()[:need])


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def num_chips(mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n
