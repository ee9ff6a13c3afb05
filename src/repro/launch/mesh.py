"""Production meshes.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16) — the "pod"
axis carries pure data parallelism (and FSDP for the largest models) over
the inter-pod DCN/optical links; "model" stays within a pod's ICI.

Defined as functions (never module-level constants) so importing this module
never touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax import.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

# TPU v5e-class hardware constants used by the roofline analysis.
# Single source of truth: repro.core.hw (shared with the tile autotuner).
from repro.core.hw import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

__all__ = ["HBM_BW", "ICI_BW", "PEAK_FLOPS_BF16", "make_mesh",
           "make_production_mesh", "make_local_mesh"]


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with Auto axes.

    Every mesh in this repo goes through here.  The model code annotates
    activations with ``with_sharding_constraint`` (parallel/logical.py) and
    leaves the rest to GSPMD propagation, which is what Auto axes mean;
    ``jax.make_mesh`` defaults to Explicit axes, under which those
    annotations and ``jnp.take`` on a sharded table are rejected."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Smoke-test mesh over whatever devices exist (CPU: 1 device)."""
    n = len(jax.devices())
    return make_mesh((n // model_axis, model_axis), ("data", "model"))
