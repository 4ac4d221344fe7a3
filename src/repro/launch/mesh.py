"""Production mesh builders (TPU v5e target).

Functions — never module-level constants — so importing this module never
touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import (see dryrun.py) to build these meshes on the CPU container.
"""

from __future__ import annotations

import jax

# TPU v5e hardware constants (roofline denominators, EXPERIMENTS.md §Roofline).
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Mesh with Auto axes: the one place meshes are built.

    ``jax.make_mesh`` defaults to Explicit axes, on which
    ``with_sharding_constraint`` (``ShardingCtx.constrain``) refuses to
    run; the GSPMD rule tables in ``repro.distributed.sharding`` need Auto.
    """
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Whatever devices exist on this host (smoke tests, examples)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))
