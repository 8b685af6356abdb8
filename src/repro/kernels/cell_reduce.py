"""Fused simulator reduction kernel (Pallas) — one launch per evaluation.

:class:`repro.cluster.simulator.TrainingSimulator`'s vectorized fast path
derives an iteration time from the cached per-cell measurements with ~10
separate numpy reductions (TP ring minima, stage-time formula, per-column
stage maxima, DP ring minima, activation-hop sums, pipeline max, DP
all-reduce bottleneck). This module fuses that whole reduction tree —
per-cell ring-min, stage-max, hop-path sum and the final critical-path
max — into a single Pallas kernel launch, so on a compiled backend the
entire evaluation runs out of VMEM with no HBM round-trips between passes.

It backs the ``pallas`` entry of the simulator's ``ReductionBackend``
registry (see docs/kernels.md). Inputs are the simulator's *measured*
arrays (cell speed minima and raw ring/hop edge bandwidths — incremental
event-scoped maintenance stays on the numpy side); the kernel owns every
reduction after measurement. ``cell_reduce`` (the Pallas launch) and
``cell_reduce_reference`` (the same traced math without ``pallas_call``)
share one function, so interpret-mode kernel output is bit-identical to
the reference by construction; versus the float64 numpy oracle the float32
kernel carries the documented ~1e-5 relative tolerance.

The kernel requires a full hybrid shape (tp > 1, dp > 1, pp > 1);
:func:`repro.cluster.simulator.resolve_reduction_backend` keeps
degenerate topologies on the numpy path, as a reported choice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_compiled


def _fused_reduce(cell_speed, tp_edge, dp_edge, hop_bw, alloc_off,
                  c_flops, c_speed, c_tp, pp_vol, c_dp):
    """The simulator's full post-measurement reduction tree (pure jnp).

    Shapes: ``cell_speed`` (pp, dp); ``tp_edge``/``dp_edge`` (pp, dp, tp);
    ``hop_bw`` (pp - 1, dp); ``alloc_off`` (1, dp) — ``allocation + pp - 1``
    as floats. Scalars are 0-d arrays (the factored formula constants of
    ``_Cells``). Returns ``(t, stage_max, tp_bw, dp_bw)`` with ``t`` (1, 1)
    the iteration time, ``stage_max`` (1, dp), ``tp_bw`` (pp, dp) and
    ``dp_bw`` (pp, tp) the per-group bottlenecks ``profile_groups`` needs.
    """
    tp_bw = jnp.min(tp_edge, axis=2)                      # TP ring minima
    stage = c_flops / (c_speed * cell_speed) + c_tp / tp_bw
    stage_max = jnp.max(stage, axis=0, keepdims=True)     # per-DP-group
    dp_bw = jnp.min(dp_edge, axis=1)                      # DP ring minima
    hop2 = 2.0 * jnp.sum(pp_vol / hop_bw, axis=0, keepdims=True)
    pipe = alloc_off * stage_max + hop2                   # 1F1B + hops
    t = jnp.max(pipe) + c_dp / jnp.min(dp_bw)             # + DP all-reduce
    return t.reshape(1, 1), stage_max, tp_bw, dp_bw


def _reduce_kernel(
    params_ref, cell_speed_ref, tp_edge_ref, dp_edge_ref, hop_bw_ref,
    alloc_ref, t_out, stage_max_out, tp_bw_out, dp_bw_out,
):
    p = params_ref
    outs = _fused_reduce(
        cell_speed_ref[:], tp_edge_ref[:], dp_edge_ref[:], hop_bw_ref[:],
        alloc_ref[:],
        c_flops=p[0, 0], c_speed=p[0, 1], c_tp=p[0, 2],
        pp_vol=p[0, 3], c_dp=p[0, 4],
    )
    for ref, val in zip(
        (t_out, stage_max_out, tp_bw_out, dp_bw_out), outs
    ):
        ref[:] = val


@functools.partial(jax.jit, static_argnames=("interpret",))
def cell_reduce(
    cell_speed, tp_edge, dp_edge, hop_bw, alloc_off,
    c_flops, c_speed, c_tp, pp_vol, c_dp, *, interpret=None,
):
    """The full reduction tree as a single ``pallas_call`` launch.

    Array shapes/dtypes as in :func:`_fused_reduce` (``alloc_off`` may be
    1-D; constants may be python floats — traced, so allocation changes
    don't recompile). Returns ``(t, stage_max, tp_bw, dp_bw)``.
    """
    if interpret is None:
        interpret = not pallas_compiled()
    dt = cell_speed.dtype
    pp, dp, tp = tp_edge.shape
    alloc_off = alloc_off.astype(dt).reshape(1, dp)
    params = jnp.stack([
        jnp.asarray(c_flops, dt), jnp.asarray(c_speed, dt),
        jnp.asarray(c_tp, dt), jnp.asarray(pp_vol, dt),
        jnp.asarray(c_dp, dt), jnp.zeros((), dt),
    ]).reshape(1, 6)
    vec = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _reduce_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((1, 1), dt),      # iteration time
            jax.ShapeDtypeStruct((1, dp), dt),     # stage_max
            jax.ShapeDtypeStruct((pp, dp), dt),    # tp_bw
            jax.ShapeDtypeStruct((pp, tp), dt),    # dp_bw
        ),
        in_specs=[smem] + [vec] * 5,
        out_specs=(vec,) * 4,
        interpret=interpret,
    )(params, cell_speed, tp_edge, dp_edge, hop_bw, alloc_off)


@jax.jit
def cell_reduce_reference(
    cell_speed, tp_edge, dp_edge, hop_bw, alloc_off,
    c_flops, c_speed, c_tp, pp_vol, c_dp,
):
    """The kernel's math as a plain traced function (no ``pallas_call``) —
    the bit-match oracle for interpret-mode parity tests."""
    dt = cell_speed.dtype
    pp, dp, tp = tp_edge.shape
    alloc_off = alloc_off.astype(dt).reshape(1, dp)
    return _fused_reduce(
        cell_speed, tp_edge, dp_edge, hop_bw, alloc_off,
        jnp.asarray(c_flops, dt), jnp.asarray(c_speed, dt),
        jnp.asarray(c_tp, dt), jnp.asarray(pp_vol, dt),
        jnp.asarray(c_dp, dt),
    )
