"""Shared layers: RMSNorm, SwiGLU MLP, RoPE / M-RoPE, embeddings."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.schema import ParamDef, Schema


# --------------------------------------------------------------- RMSNorm
def rmsnorm_schema(d: int) -> ParamDef:
    return ParamDef((d,), (None,), init="ones")


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    norm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (norm * weight.astype(jnp.float32)).astype(dtype)


# ----------------------------------------------------------- SwiGLU MLP
def mlp_schema(cfg: ArchConfig, d_ff: int | None = None) -> Schema:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "norm": rmsnorm_schema(d),
        "wi_gate": ParamDef((d, f), (None, "model")),
        "wi_up": ParamDef((d, f), (None, "model")),
        "wo": ParamDef((f, d), ("model", None)),
    }


def apply_mlp(params: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    h = rmsnorm(x, params["norm"], cfg.norm_eps)
    gate = h @ params["wi_gate"]
    up = h @ params["wi_up"]
    return (jax.nn.silu(gate) * up) @ params["wo"]


# ------------------------------------------------------------- RoPE(s)
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (..., S, H, hd); positions: broadcastable (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]  # head axis
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def mrope_sections(head_dim: int) -> tuple[int, int, int]:
    """Qwen2-VL M-RoPE: split the hd/2 rotary pairs into (t, h, w) sections
    with the 16/24/24-style 1:1.5:1.5 proportion."""
    half = head_dim // 2
    t = half // 4
    h = (half - t) // 2
    w = half - t - h
    return t, h, w


def apply_mrope(x: jax.Array, positions3: jax.Array, theta: float) -> jax.Array:
    """Multimodal RoPE. positions3: (3, ..., S) = (temporal, height, width)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    secs = mrope_sections(hd)
    parts = []
    start = 0
    for i, sec in enumerate(secs):
        pos = positions3[i]
        parts.append(pos[..., None].astype(jnp.float32) * freqs[start : start + sec])
        start += sec
    angles = jnp.concatenate(parts, axis=-1)[..., None, :]  # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------- embeddings
def embed_schema(cfg: ArchConfig) -> Schema:
    v, d = cfg.padded_vocab, cfg.d_model
    if cfg.modality == "audio_codes":
        return {"tok": ParamDef((cfg.num_codebooks, v, d), (None, "model", None))}
    return {"tok": ParamDef((v, d), ("model", None))}


def apply_embed(params: dict, tokens: jax.Array, cfg: ArchConfig) -> jax.Array:
    if cfg.modality == "audio_codes":
        # tokens: (B, S, K) -> sum of the K per-codebook embeddings
        # (MusicGen's delay-pattern interleave is the data stub's job).
        out = sum(
            jnp.take(params["tok"][k], tokens[..., k], axis=0)
            for k in range(cfg.num_codebooks)
        )
        return out.astype(cfg.activation_dtype)
    x = jnp.take(params["tok"], tokens, axis=0).astype(cfg.activation_dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def head_params(params: dict, cfg: ArchConfig) -> dict:
    """The output head's parameters: with tied embeddings, the embedding
    table read as a ``(d, vocab)`` matrix (its vocab dim keeps the
    model-axis sharding the untied head has)."""
    if cfg.tie_word_embeddings:
        return {"w": params["embed"]["tok"].T}
    return params["head"]


def head_schema(cfg: ArchConfig) -> Schema:
    """The untied head (tied configs have none: :func:`head_params`)."""
    v, d = cfg.padded_vocab, cfg.d_model
    if cfg.modality == "audio_codes":
        return {"w": ParamDef((cfg.num_codebooks, d, v), (None, None, "model"))}
    return {"w": ParamDef((d, v), (None, "model"))}


def apply_head(params: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Returns logits over the padded vocab: (B,S,Vp) or (B,S,K,Vp).

    Padding columns are masked to a large negative so softmax/argmax/logsumexp
    never select them; the width stays ``padded_vocab`` so the model-axis
    sharding survives through the loss.
    """
    if cfg.modality == "audio_codes":
        logits = jnp.einsum("bsd,kdv->bskv", x, params["w"])
    else:
        logits = x @ params["w"]
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    vp = cfg.padded_vocab
    if vp != cfg.vocab_size:
        mask = jnp.arange(vp) < cfg.vocab_size
        logits = jnp.where(mask, logits, jnp.asarray(-1e9, logits.dtype))
    return logits
