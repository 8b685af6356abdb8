"""Top-level language model: embed -> blocks -> head, plus loss and decode.

Input conventions per modality (the VLM/audio carve-out):
  * text:          batch["tokens"] (B, S) int32
  * vision_embeds: batch["embeds"] (B, S, D) + batch["positions"] (3, B, S)
  * audio_codes:   batch["tokens"] (B, S, K) int32 (K EnCodec codebooks)
Training batches additionally carry batch["labels"] (same layout as tokens).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers, transformer
from repro.models.schema import (
    ParamDef,
    Schema,
    axes_tree,
    init_tree,
    shape_tree,
)

AUX_LOSS_COEF = 0.01


def model_schema(cfg: ArchConfig) -> Schema:
    out = {
        "embed": layers.embed_schema(cfg),
        "blocks": transformer.blocks_schema(cfg),
        "final_norm": layers.rmsnorm_schema(cfg.d_model),
    }
    if not cfg.tie_word_embeddings:
        out["head"] = layers.head_schema(cfg)
    return out


def init_params(cfg: ArchConfig, seed: int = 0) -> dict:
    return init_tree(model_schema(cfg), jax.random.key(seed))


def param_shapes(cfg: ArchConfig) -> dict:
    return shape_tree(model_schema(cfg))


def param_axes(cfg: ArchConfig) -> dict:
    return axes_tree(model_schema(cfg))


def _embed_inputs(params: dict, batch: dict, cfg: ArchConfig) -> jax.Array:
    if cfg.modality == "vision_embeds":
        return batch["embeds"].astype(cfg.activation_dtype)
    return layers.apply_embed(params["embed"], batch["tokens"], cfg)


def _positions(batch: dict, cfg: ArchConfig, seq_len: int) -> jax.Array | None:
    if cfg.pos_encoding == "none":
        return None
    if cfg.pos_encoding == "mrope":
        return batch["positions"]
    bsz = (
        batch["embeds"].shape[0]
        if cfg.modality == "vision_embeds"
        else batch["tokens"].shape[0]
    )
    return jnp.broadcast_to(jnp.arange(seq_len)[None, :], (bsz, seq_len))


def _forward(params, batch, cfg, *, window, use_kernel, remat):
    x = _embed_inputs(params, batch, cfg)
    positions = _positions(batch, cfg, x.shape[1])
    x, aux, moe_stats = transformer.apply_blocks(
        params["blocks"], x, cfg, positions,
        window=window, use_kernel=use_kernel, remat=remat,
    )
    x = layers.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return layers.apply_head(layers.head_params(params, cfg), x, cfg), aux, moe_stats


def forward(
    params: dict,
    batch: dict,
    cfg: ArchConfig,
    *,
    window: int = 0,
    use_kernel: bool = False,
    remat: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Full forward pass. Returns (logits, aux_loss)."""
    logits, aux, _ = _forward(
        params, batch, cfg, window=window, use_kernel=use_kernel, remat=remat
    )
    return logits, aux


def loss_fn(
    params: dict,
    batch: dict,
    cfg: ArchConfig,
    *,
    window: int = 0,
    use_kernel: bool = False,
    remat: bool = True,
) -> tuple[jax.Array, dict]:
    """Mean next-token cross-entropy (+ MoE aux). Returns (loss, metrics);
    with MoE layers the metrics also hold ``moe_counts`` (tokens routed to
    each held expert, per MoE layer) and ``moe_dropped`` (held-expert
    assignments left uncomputed: 0 on the dropless path)."""
    logits, aux, moe_stats = _forward(
        params, batch, cfg, window=window, use_kernel=use_kernel, remat=remat
    )
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = jnp.mean(logz - ll)
    loss = ce + AUX_LOSS_COEF * aux
    return loss, {"ce": ce, "aux": aux, **moe_stats}


# ----------------------------------------------------------------- decode
def decode_step(
    params: dict,
    tokens: jax.Array,
    caches: dict,
    pos: jax.Array,
    cfg: ArchConfig,
    *,
    window: int = 0,
    use_kernel: bool = False,
) -> tuple[jax.Array, dict]:
    """Generate logits for ONE new token given the cache state.

    tokens: (B, 1) int32 (or (B, 1, K) audio / (B, 1, D) vision embeds).
    Returns (logits (B, 1, V[, K]), new caches).
    """
    if cfg.modality == "vision_embeds":
        x = tokens.astype(cfg.activation_dtype)  # already embeddings
    else:
        x = layers.apply_embed(params["embed"], tokens, cfg)
    x, new_caches = transformer.decode_blocks(
        params["blocks"], x, caches, pos, cfg, window=window,
        use_kernel=use_kernel,
    )
    x = layers.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return layers.apply_head(layers.head_params(params, cfg), x, cfg), new_caches
