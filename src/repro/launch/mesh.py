"""Production mesh definitions (TPU v5e).

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods = 512 chips as (pod=2, data=16, model=16); the "pod"
axis carries only data parallelism (gradient all-reduce over DCI).

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).

Every mesh here has ``Auto`` axes: the partitioner places what the
``sharding/partition.py`` rules leave open, and ``with_sharding_constraint``
may name any axis.  That is the semantics this code was written for;
``jax.make_mesh`` alone now defaults to ``Explicit`` axes, which refuse
those constraints.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with ``Auto`` axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Every device this host has as a flat ("data",) miner mesh."""
    return make_mesh((len(jax.devices()),), ("data",))


# v5e hardware constants for the roofline (EXPERIMENTS.md §Roofline)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
