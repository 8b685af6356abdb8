"""Micro-batched training steps.

Two variants:

* ``make_train_step`` — fixed even micro-batching: ``lax.scan`` over the
  slot axis accumulating gradients, then AdamW. This is what the multi-pod
  dry-run lowers (the roofline baseline).

* ``make_adaptive_train_step`` — the FALCON S2-integrated step: a
  ``jax.shard_map`` manual over the DP axes (model axis left auto for
  GSPMD) runs a ``lax.while_loop`` whose trip count is each DP group's
  *own* micro-batch allocation ``m_i``, so slow groups genuinely execute
  fewer micro-batches inside one SPMD program. Gradients are combined with
  the paper's weighted aggregation: sum of per-micro-batch gradients psum'd
  over DP and divided by the global micro-batch count. Model-axis
  collectives stay consistent because every member of a model group shares
  the same DP index, hence the same trip count.

Batch layout: ``(slots, global_microbatch, S, ...)`` — see data/pipeline.py.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import model as model_lib
from repro.optim import adamw
from repro.sharding import partition


#: the loss metrics a step returns besides the loss: per MoE layer and held
#: expert the tokens routed to it, the held assignments not computed, and
#: the rows the expert layers' dispatches ran
MOE_COUNTERS = ("moe_counts", "moe_dropped", "moe_rows")


def _microbatch_loss(params, mb, cfg: ArchConfig, use_kernel: bool):
    return model_lib.loss_fn(params, mb, cfg, use_kernel=use_kernel)


def make_train_step(
    cfg: ArchConfig,
    opt_cfg: adamw.AdamWConfig,
    *,
    use_kernel: bool = False,
) -> Callable:
    """Even micro-batching: scan over all slots."""

    def train_step(params, opt_state, batch):
        slots = jax.tree.leaves(batch)[0].shape[0]

        def body(carry, i):
            gsum, lsum = carry
            mb = _take_slot(batch, i, cfg)
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: _microbatch_loss(p, mb, cfg, use_kernel), has_aux=True
            )(params)
            counters = {k: metrics[k] for k in MOE_COUNTERS if k in metrics}
            return (jax.tree.map(jnp.add, gsum, grads), lsum + loss), counters

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gsum, lsum), counters = jax.lax.scan(
            body, (g0, jnp.float32(0)), jnp.arange(slots)
        )
        grads = jax.tree.map(lambda g: g / slots, gsum)
        params, opt_state = adamw.update(opt_cfg, grads, opt_state, params)
        # MoE routing counts ride out with the loss, summed over the slots.
        counters = {k: jnp.sum(v, axis=0) for k, v in counters.items()}
        return params, opt_state, {"loss": lsum / slots, **counters}

    return train_step


def _take_slot(batch: dict, i, cfg: ArchConfig) -> dict:
    out = {}
    for k, v in batch.items():
        if k == "positions":  # (3, B, S) — shared across slots
            out[k] = v
        else:
            out[k] = jax.lax.dynamic_index_in_dim(v, i, axis=0, keepdims=False)
    return out


def make_adaptive_train_step(
    cfg: ArchConfig,
    opt_cfg: adamw.AdamWConfig,
    mesh: Mesh,
    *,
    use_kernel: bool = False,
) -> Callable:
    """FALCON S2 step: per-DP-group dynamic trip counts + weighted grads."""
    ba = partition.batch_axes(mesh)

    def grad_fn(params, batch, counts):
        m = counts[0]  # local DP group's allocation

        def cond(carry):
            return carry[0] < m

        def body(carry):
            i, gsum, lsum = carry
            mb = _take_slot(batch, i, cfg)
            (loss, _), grads = jax.value_and_grad(
                lambda p: _microbatch_loss(p, mb, cfg, use_kernel), has_aux=True
            )(params)
            return (i + 1, jax.tree.map(jnp.add, gsum, grads), lsum + loss)

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        _, gsum, lsum = jax.lax.while_loop(
            cond, body, (jnp.int32(0), g0, jnp.float32(0))
        )
        # Weighted gradient aggregation (paper §5.3 / ref [5]): each group
        # contributes its gradient *sum*; dividing by the global micro-batch
        # count gives weights m_i / M.
        gsum = jax.lax.psum(gsum, ba)
        total = jax.lax.psum(m, ba).astype(jnp.float32)
        grads = jax.tree.map(lambda g: g / total, gsum)
        loss = jax.lax.psum(lsum, ba) / total
        return grads, loss

    def batch_in_specs(batch_spec_tree):
        # shard_map is manual only over the DP axes: drop other axis names.
        keep = set(ba)

        def strip(spec: P) -> P:
            out = []
            for s in spec:
                if s is None:
                    out.append(None)
                elif isinstance(s, tuple):
                    t = tuple(a for a in s if a in keep)
                    out.append(t if t else None)
                else:
                    out.append(s if s in keep else None)
            return P(*out)

        return jax.tree.map(strip, batch_spec_tree, is_leaf=lambda x: isinstance(x, P))

    bspecs = batch_in_specs(partition.train_batch_specs(cfg, mesh))
    param_specs0 = jax.tree.map(
        lambda _: P(), model_lib.param_shapes(cfg)
    )  # params replicated over DP axes (model axis stays auto)

    sharded_grad = jax.shard_map(
        grad_fn,
        mesh=mesh,
        in_specs=(param_specs0, bspecs, P(ba)),
        out_specs=(param_specs0, P()),
        axis_names=frozenset(ba),
        check_vma=False,
    )

    def train_step(params, opt_state, batch, counts):
        grads, loss = sharded_grad(params, batch, counts)
        params, opt_state = adamw.update(opt_cfg, grads, opt_state, params)
        return params, opt_state, {"loss": loss}

    return train_step
