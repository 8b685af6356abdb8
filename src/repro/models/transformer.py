"""Decoder stack assembly: scan over repeating periods of sub-layers.

Heterogeneous architectures (jamba's 1-attention:7-mamba interleave with
alternating MoE) are expressed as a *period* — a fixed tuple of sub-layers —
and the full stack is `lax.scan` over ``n_periods`` with parameters stacked
on a leading axis. This keeps the lowered HLO small (one period body) even
for 80-layer models, which matters for 512-device dry-run compile times.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, SubLayer
from repro.models import attention, layers, moe, ssm
from repro.models.schema import Schema, stack


def period_schema(cfg: ArchConfig) -> Schema:
    out: Schema = {}
    for j, sub in enumerate(cfg.period):
        entry: Schema = {}
        if sub.mixer == "attn":
            entry["attn"] = attention.attn_schema(cfg)
        else:
            entry["mamba"] = ssm.mamba_schema(cfg)
        if sub.mlp == "mlp":
            entry["mlp"] = layers.mlp_schema(cfg)
        elif sub.mlp == "moe":
            entry["moe"] = moe.moe_schema(cfg)
        out[f"sub{j}"] = entry
    return out


def blocks_schema(cfg: ArchConfig) -> Schema:
    return stack(period_schema(cfg), cfg.n_periods)


def _scaled(y: jax.Array, cfg: ArchConfig) -> jax.Array:
    """A sub-layer's output as the residual adds it (Granite scales it)."""
    r = cfg.residual_multiplier
    return y if r == 1.0 else y * jnp.asarray(r, y.dtype)


def apply_sublayer(
    x: jax.Array,
    p: dict,
    sub: SubLayer,
    cfg: ArchConfig,
    positions: jax.Array | None,
    window: int,
    use_kernel: bool,
    *,
    want_cache: bool = False,
) -> tuple[jax.Array, jax.Array, dict | None, dict | None]:
    """Residual sub-layer application: ``x + r * mixer(norm(x))``, then
    ``x + r * ffn(norm(x))`` (``r`` = ``cfg.residual_multiplier``; the MoE's
    ffn is its routed experts plus its shared ones). The mixers run under
    the ``attn`` / ``mamba`` named scopes. Returns (x, aux_loss, MoE stats
    or None, the mixer's prefill cache when ``want_cache`` else None)."""
    aux = jnp.zeros((), jnp.float32)
    stats = cache = None
    if sub.mixer == "attn":
        with jax.named_scope("attn"):
            y = attention.apply_attention(
                p["attn"], x, cfg, positions, window=window,
                use_kernel=use_kernel, return_kv=want_cache,
            )
    else:
        with jax.named_scope("mamba"):
            y = ssm.apply_mamba(
                p["mamba"], x, cfg, use_kernel=use_kernel, return_cache=want_cache
            )
    if want_cache:
        y, cache = y
    x = x + _scaled(y, cfg)
    if sub.mlp == "mlp":
        x = x + _scaled(layers.apply_mlp(p["mlp"], x, cfg), cfg)
    elif sub.mlp == "moe":
        y, aux, stats = moe.apply_moe(p["moe"], x, cfg)
        x = x + _scaled(y, cfg)
    return x, aux, stats, cache


def apply_blocks(
    blocks: dict,
    x: jax.Array,
    cfg: ArchConfig,
    positions: jax.Array | None,
    *,
    window: int = 0,
    use_kernel: bool = False,
    remat: bool = True,
) -> tuple[jax.Array, jax.Array, dict]:
    """Run the full stack. Returns (hidden (B,S,D), total aux loss, MoE
    stats): with MoE sub-layers ``{"moe_counts": (n_moe_layers,
    held_experts) int32, "moe_dropped": int32, "moe_rows": int32}``, else
    ``{}``."""

    def sublayer(h, p, sub):
        return apply_sublayer(h, p, sub, cfg, positions, window, use_kernel)[:3]

    if remat:
        # Each sub-layer is its own remat boundary, so the backward pass
        # recomputes one sub-layer's forward at a time (a whole period of
        # ten would hold ten layers' recomputed activations at once). Save
        # matmul outputs across it (they're what the backward pass actually
        # needs); recompute only the cheap elementwise/norm chains.
        # Full-recompute remat costs ~25-30 % extra FLOPs, and train_4k
        # peaks far below HBM — memory is the cheaper currency here
        # (EXPERIMENTS §Perf, granite-3-8b iteration 1).
        sublayer = jax.checkpoint(
            sublayer, static_argnums=(2,),
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )

    def period_body(carry, period_params):
        h, aux_sum = carry
        counts, dropped, rows = [], jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)
        for j, sub in enumerate(cfg.period):
            h, aux, stats = sublayer(h, period_params[f"sub{j}"], sub)
            aux_sum = aux_sum + aux
            if stats is not None:
                counts.append(stats["counts"])
                dropped = dropped + stats["dropped"]
                rows = rows + stats["rows"]
        out = None
        if counts:
            out = {"counts": jnp.stack(counts), "dropped": dropped, "rows": rows}
        return (h, aux_sum), out

    (x, aux_total), per_period = jax.lax.scan(
        period_body, (x, jnp.zeros((), jnp.float32)), blocks
    )
    if per_period is None:
        return x, aux_total, {}
    counts = per_period["counts"]  # (n_periods, moe sub-layers, held)
    return x, aux_total, {
        "moe_counts": counts.reshape(-1, counts.shape[-1]),
        "moe_dropped": jnp.sum(per_period["dropped"]),
        "moe_rows": jnp.sum(per_period["rows"]),
    }


# ----------------------------------------------------------------- decode
def init_caches(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    out: dict = {}
    for j, sub in enumerate(cfg.period):
        if sub.mixer == "attn":
            c = attention.init_kv_cache(cfg, batch, max_len)
        else:
            c = ssm.init_ssm_cache(cfg, batch)
        out[f"sub{j}"] = jax.tree.map(
            lambda leaf: jnp.broadcast_to(leaf, (cfg.n_periods, *leaf.shape)), c
        )
    return out


def grow_caches(caches: dict, cfg: ArchConfig, max_len: int) -> dict:
    """Pad prefill-produced KV caches out to the serving context length.

    Prefill returns caches sized to the prompt; decode writes into a fixed
    ``max_len`` buffer indexed by ``pos``. SSM caches are O(1) in context
    length and pass through unchanged.
    """
    out: dict = {}
    for j, sub in enumerate(cfg.period):
        key = f"sub{j}"
        c = caches[key]
        if sub.mixer == "attn":
            pad = max_len - c["k"].shape[2]  # (periods, B, S, kv, hd)
            widths = [(0, 0), (0, 0), (0, max(pad, 0)), (0, 0), (0, 0)]
            c = {
                "k": jnp.pad(c["k"], widths),
                "v": jnp.pad(c["v"], widths),
            }
        out[key] = c
    return out


def cache_shapes(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    out: dict = {}
    for j, sub in enumerate(cfg.period):
        if sub.mixer == "attn":
            c = attention.kv_cache_shape(cfg, batch, max_len)
        else:
            c = ssm.ssm_cache_shape(cfg, batch)
        out[f"sub{j}"] = jax.tree.map(
            lambda leaf: jax.ShapeDtypeStruct(
                (cfg.n_periods, *leaf.shape), leaf.dtype
            ),
            c,
        )
    return out


def decode_blocks(
    blocks: dict,
    x: jax.Array,
    caches: dict,
    pos: jax.Array,
    cfg: ArchConfig,
    *,
    window: int = 0,
    use_kernel: bool = False,
) -> tuple[jax.Array, dict]:
    """One-token decode through the stack. Returns (hidden, new caches)."""

    def period_body(h, scanned):
        period_params, cache = scanned
        new_cache = {}
        for j, sub in enumerate(cfg.period):
            key = f"sub{j}"
            if sub.mixer == "attn":
                dh, nc = attention.decode_attention(
                    period_params[key]["attn"], h, cache[key], pos, cfg,
                    window=window, use_kernel=use_kernel,
                )
            else:
                dh, nc = ssm.decode_mamba(
                    period_params[key]["mamba"], h, cache[key], cfg
                )
            h = h + _scaled(dh, cfg)
            new_cache[key] = nc
            if sub.mlp == "mlp":
                h = h + _scaled(layers.apply_mlp(period_params[key]["mlp"], h, cfg), cfg)
            elif sub.mlp == "moe":
                y, _, _ = moe.apply_moe(period_params[key]["moe"], h, cfg)
                h = h + _scaled(y, cfg)
        return h, new_cache

    x, new_caches = jax.lax.scan(period_body, x, (blocks, caches))
    return x, new_caches
