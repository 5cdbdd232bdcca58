"""Production meshes (DESIGN.md §6).

Target: TPU v5e.  Single pod = 16x16 = 256 chips, axes ("data", "model").
Multi-pod = 2 pods = 512 chips, axes ("pod", "data", "model") — the "pod"
axis carries only data parallelism (DCN-friendly: one gradient/params
reduction per step crosses pods).

``make_production_mesh`` is a function (not a module constant) so importing
this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import and only then builds the mesh.
"""

from __future__ import annotations

import jax

# v5e hardware constants used by the roofline (benchmarks/roofline.py)
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (~per-chip effective)


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto: in JAX 0.9 its default is
    Explicit axes, which the ``with_sharding_constraint`` hints of
    ``models/hints.py`` and the fed round's ``shard_map`` reject."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_cohort_mesh(num_devices: int | None = None):
    """1-D mesh carrying the cohort engine's client axis (DESIGN.md §3.5):
    each device runs cohort_size / num_devices clients of the padded cohort
    buffer.  Uses all local devices by default.  Bucket sizes from
    ``SamplingSchedule.bucket_ladder`` are powers of two *except the top
    bucket M itself*, so a power-of-two device count divides every bucket
    below full participation; full-participation rounds on a non-power-of-two
    M belong on the oracle path (the server dispatches them there)."""
    import numpy as np
    devs = jax.devices()
    if num_devices is not None:
        devs = devs[:num_devices]
    return jax.sharding.Mesh(np.asarray(devs), ("clients",))


def data_axes(mesh) -> tuple:
    """The batch/FSDP axes: everything except 'model'."""
    return tuple(a for a in mesh.axis_names if a != "model")


def num_chips(mesh) -> int:
    return int(mesh.devices.size)
