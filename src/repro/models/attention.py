"""GQA attention: blocked (flash-style) training path, cached decode path.

The training/prefill path is a pure-jnp *blocked online-softmax* attention
(`lax.scan` over KV blocks) so the full (S x S) score matrix is never
materialized — this is what the multi-pod dry-run lowers. The Pallas TPU
kernel in ``repro.kernels.flash_attention`` implements the same blocking for
real hardware and is validated against ``repro.kernels.ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers
from repro.models.schema import ParamDef, Schema

NEG_INF = -1e30


def attn_schema(cfg: ArchConfig) -> Schema:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    return {
        "norm": layers.rmsnorm_schema(d),
        "wq": ParamDef((d, h * hd), (None, "model")),
        "wk": ParamDef((d, kv * hd), (None, "model")),
        "wv": ParamDef((d, kv * hd), (None, "model")),
        "wo": ParamDef((h * hd, d), ("model", None)),
    }


# ------------------------------------------------------------------ core
def blocked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    kv_block: int = 1024,
    scale: float | None = None,
) -> jax.Array:
    """Online-softmax attention over KV blocks.

    q: (B, Sq, H, hd); k, v: (B, Skv, KVH, hd) with H a multiple of KVH.
    ``window`` > 0 restricts attention to the last ``window`` keys
    (sliding-window). ``q_offset`` is the absolute position of q[0]
    (for decode/prefill continuation). ``scale`` multiplies the scores
    (default ``hd ** -0.5``; a config's is ``ArchConfig.attn_scale``).
    """
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    if scale is None:
        scale = hd**-0.5

    nblk = -(-skv // kv_block)
    pad = nblk * kv_block - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    # (B, KVH, rep, Sq, hd) grouped query layout.
    qg = q.reshape(b, sq, kvh, rep, hd).transpose(0, 2, 3, 1, 4) * scale
    kb = k.reshape(b, nblk, kv_block, kvh, hd) if pad == 0 else k.reshape(
        b, nblk, kv_block, kvh, hd
    )
    vb = v.reshape(b, nblk, kv_block, kvh, hd)
    kb = kb.transpose(1, 0, 3, 2, 4)  # (nblk, B, KVH, blk, hd)
    vb = vb.transpose(1, 0, 3, 2, 4)

    q_pos = q_offset + jnp.arange(sq)

    def body(carry, blk):
        m_prev, l_prev, acc = carry
        kblk, vblk, blk_idx = blk
        # scores: (B, KVH, rep, Sq, blk)
        s = jnp.einsum(
            "bgrsd,bgkd->bgrsk", qg.astype(jnp.float32), kblk.astype(jnp.float32)
        )
        k_pos = blk_idx * kv_block + jnp.arange(kv_block)
        mask = jnp.ones((sq, kv_block), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        mask &= (k_pos < skv)[None, :]
        s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bgrsk,bgkd->bgrsd", p, vblk.astype(jnp.float32)
        )
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, kvh, rep, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, rep, sq), jnp.float32)
    acc0 = jnp.zeros((b, kvh, rep, sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (kb, vb, jnp.arange(nblk))
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd).astype(q.dtype)


def _apply_positions(
    q: jax.Array, k: jax.Array, positions: jax.Array | None, cfg: ArchConfig
) -> tuple[jax.Array, jax.Array]:
    if cfg.pos_encoding == "rope":
        assert positions is not None
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    elif cfg.pos_encoding == "mrope":
        assert positions is not None and positions.shape[0] == 3
        q = layers.apply_mrope(q, positions, cfg.rope_theta)
        k = layers.apply_mrope(k, positions, cfg.rope_theta)
    return q, k


def apply_attention(
    params: dict,
    x: jax.Array,
    cfg: ArchConfig,
    positions: jax.Array | None,
    *,
    window: int = 0,
    use_kernel: bool = False,
    return_kv: bool = False,
):
    """Training/prefill self-attention. x: (B, S, D). With ``return_kv``
    returns ``(out, {"k", "v"})``, the prefill's cache."""
    b, s, _ = x.shape
    hd, h, kv = cfg.resolved_head_dim, cfg.num_heads, cfg.num_kv_heads
    hn = layers.rmsnorm(x, params["norm"], cfg.norm_eps)
    q = (hn @ params["wq"]).reshape(b, s, h, hd)
    k = (hn @ params["wk"]).reshape(b, s, kv, hd)
    v = (hn @ params["wv"]).reshape(b, s, kv, hd)
    q, k = _apply_positions(q, k, positions, cfg)
    if use_kernel:
        from repro.kernels import ops as kernel_ops

        if cfg.attention_multiplier:  # the kernel scales by hd ** -0.5
            q = q * jnp.asarray(cfg.attn_scale * hd**0.5, q.dtype)
        out = kernel_ops.flash_attention(q, k, v, causal=True, window=window)
    else:
        out = blocked_attention(q, k, v, causal=True, window=window,
                                scale=cfg.attn_scale)
    out = out.reshape(b, s, h * hd) @ params["wo"]
    return (out, {"k": k, "v": v}) if return_kv else out


# ----------------------------------------------------------------- decode
def init_kv_cache(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    hd, kv = cfg.resolved_head_dim, cfg.num_kv_heads
    shape = (batch, max_len, kv, hd)
    dt = cfg.activation_dtype
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def kv_cache_shape(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    hd, kv = cfg.resolved_head_dim, cfg.num_kv_heads
    shape = (batch, max_len, kv, hd)
    dt = cfg.activation_dtype
    return {
        "k": jax.ShapeDtypeStruct(shape, dt),
        "v": jax.ShapeDtypeStruct(shape, dt),
    }


def decode_attention(
    params: dict,
    x: jax.Array,
    cache: dict,
    pos: jax.Array,
    cfg: ArchConfig,
    positions_full: jax.Array | None = None,
    *,
    window: int = 0,
    use_kernel: bool = False,
) -> tuple[jax.Array, dict]:
    """One-token decode. x: (B, 1, D); cache k/v: (B, S_max, KVH, hd);
    pos: scalar int32 — current position. Returns (out, new_cache).

    With ``window`` > 0, only the trailing ``window`` cache entries are
    attended (sliding-window decode — the sub-quadratic long_500k path for
    full-attention architectures). ``use_kernel`` routes the cache read
    through the Pallas flash-decode kernel (TPU target; interpret on CPU).
    """
    b, _, _ = x.shape
    hd, h, kv = cfg.resolved_head_dim, cfg.num_heads, cfg.num_kv_heads
    s_max = cache["k"].shape[1]
    hn = layers.rmsnorm(x, params["norm"], cfg.norm_eps)
    q = (hn @ params["wq"]).reshape(b, 1, h, hd)
    k_new = (hn @ params["wk"]).reshape(b, 1, kv, hd)
    v_new = (hn @ params["wv"]).reshape(b, 1, kv, hd)

    if cfg.pos_encoding == "rope":
        pos_arr = jnp.full((b, 1), pos, jnp.int32)
        q = layers.apply_rope(q, pos_arr, cfg.rope_theta)
        k_new = layers.apply_rope(k_new, pos_arr, cfg.rope_theta)
    elif cfg.pos_encoding == "mrope":
        pos_arr = jnp.full((3, b, 1), pos, jnp.int32)
        q = layers.apply_mrope(q, pos_arr, cfg.rope_theta)
        k_new = layers.apply_mrope(k_new, pos_arr, cfg.rope_theta)

    k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, pos, axis=1)

    if window and window < s_max:
        # Slide: attend to the `window` keys ending at pos (static size).
        start = jnp.maximum(pos - window + 1, 0)
        k_att = jax.lax.dynamic_slice_in_dim(k_cache, start, window, axis=1)
        v_att = jax.lax.dynamic_slice_in_dim(v_cache, start, window, axis=1)
        k_pos = start + jnp.arange(window)
        valid = k_pos <= pos
    else:
        k_att, v_att = k_cache, v_cache
        k_pos = jnp.arange(s_max)
        valid = k_pos <= pos

    if use_kernel:
        from repro.kernels import ops as kernel_ops

        # valid positions form a prefix of k_att in both branches:
        # full cache -> pos+1; sliding window -> pos+1-start.
        valid_len = jnp.sum(valid).astype(jnp.int32)
        if cfg.attention_multiplier:  # the kernel scales by hd ** -0.5
            q = q * jnp.asarray(cfg.attn_scale * hd**0.5, q.dtype)
        out = kernel_ops.flash_decode(
            q.reshape(b, h, hd), k_att, v_att, valid_len
        )
        out = out.reshape(b, 1, h * hd).astype(x.dtype)
        return out @ params["wo"], {"k": k_cache, "v": v_cache}

    rep = h // kv
    qg = q.reshape(b, kv, rep, hd).astype(jnp.float32) * cfg.attn_scale
    s = jnp.einsum("bgrd,bkgd->bgrk", qg, k_att.astype(jnp.float32))
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrk,bkgd->bgrd", p, v_att.astype(jnp.float32))
    out = out.reshape(b, 1, h * hd).astype(x.dtype)
    return out @ params["wo"], {"k": k_cache, "v": v_cache}
