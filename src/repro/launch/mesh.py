"""Production mesh construction.

Single pod: 256 chips as (data=16, model=16). Multi-pod: 2 pods = 512 chips
as (pod=2, data=16, model=16) — the pod axis is pure data parallelism
(gradient all-reduce crosses the inter-pod links; the model axis stays
intra-pod, mirroring the paper's "TP stays intra-node" placement rule).

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices are available, its axes
    in auto mode: the compiler propagates the parameter and batch
    shardings (``sharding.partition``) through the step."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
