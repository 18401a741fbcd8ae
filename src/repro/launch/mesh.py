"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to get 512 placeholder host devices.
"""
from __future__ import annotations

import jax

from repro.distributed.api import (
    RULES_2D, RULES_2D_DEC, RULES_2D_SP, RULES_3D, RULES_3D_DEC, RULES_3D_SP,
    AxisRules,
)

__all__ = ["make_mesh", "make_production_mesh", "make_rules", "make_elastic_mesh"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in Auto mode (GSPMD propagation
    from the ``constrain`` annotations, not explicit sharding types)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_rules(mesh, *, seq_parallel: bool = False,
               decode_opt: bool = False) -> AxisRules:
    if "pod" in mesh.axis_names:
        table = RULES_3D_SP if seq_parallel else (
            RULES_3D_DEC if decode_opt else RULES_3D)
    else:
        table = RULES_2D_SP if seq_parallel else (
            RULES_2D_DEC if decode_opt else RULES_2D)
    return AxisRules(mesh, table)


def make_custom_mesh(data: int, model: int):
    """Arbitrary (data, model) factorization of one pod (hillclimb lever)."""
    return make_mesh((data, model), ("data", "model"))


def make_elastic_mesh(model_parallel: int = 16):
    """Best mesh for *whatever devices are currently alive* (elastic restart).

    Keeps the tensor axis fixed (weights shard layout unchanged) and gives
    every remaining device to data parallelism — restoring a checkpoint onto
    this mesh is a pure re-shard (tests/test_checkpoint.py exercises it).
    """
    n = len(jax.devices())
    mp = min(model_parallel, n)
    while n % mp:
        mp -= 1
    return make_mesh((n // mp, mp), ("data", "model"))
