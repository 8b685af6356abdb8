"""Mamba2 SSD (state-space duality) layer — chunked dual form.

TPU adaptation (DESIGN.md): the selective scan is evaluated in the SSD
*dual* form — per-chunk matmuls (MXU-friendly) plus a short inter-chunk
recurrence via `lax.scan` — instead of the element-wise CUDA scan of the
original. The Pallas kernel in ``repro.kernels.ssd_scan`` implements the
same chunking with explicit VMEM tiles; this module is the pure-jnp path
(also the oracle the kernel is validated against).

Shapes follow the Mamba2 paper: H heads of dim P, state size N, G groups
for B/C (shared across H//G heads).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers
from repro.models.schema import ParamDef, Schema


def mamba_schema(cfg: ArchConfig) -> Schema:
    d = cfg.d_model
    inner, h = cfg.ssm_inner, cfg.ssm_heads
    g, n, w = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv_width
    bias: Schema = {}
    if cfg.ssm_conv_bias:
        bias = {
            "conv_x_bias": ParamDef((inner,), ("model",), init="zeros"),
            "conv_bc_bias": ParamDef((2 * g * n,), (None,), init="zeros"),
        }
    return {
        **bias,
        "norm": layers.rmsnorm_schema(d),
        "w_z": ParamDef((d, inner), (None, "model")),
        "w_x": ParamDef((d, inner), (None, "model")),
        "w_bc": ParamDef((d, 2 * g * n), (None, None)),
        "w_dt": ParamDef((d, h), (None, "model")),
        "dt_bias": ParamDef((h,), ("model",), init="zeros"),
        "a_log": ParamDef((h,), ("model",), init="zeros"),
        "d_skip": ParamDef((h,), ("model",), init="ones"),
        "conv_x": ParamDef((w, inner), (None, "model"), scale=0.1),
        "conv_bc": ParamDef((w, 2 * g * n), (None, None), scale=0.1),
        "out_norm": ParamDef((inner,), ("model",), init="ones"),
        "w_out": ParamDef((inner, d), ("model", None)),
    }


def causal_conv(x: jax.Array, w: jax.Array, bias: jax.Array | None = None) -> jax.Array:
    """Depthwise causal conv. x: (B, S, C); w: (W, C); bias: (C,), added in
    float32 so that its gradient, a sum over every token, accumulates in
    float32."""
    width = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    s = x.shape[1]
    out = sum(xp[:, i : i + s, :] * w[i] for i in range(width))
    if bias is None:
        return out
    return (out.astype(jnp.float32) + bias.astype(jnp.float32)).astype(out.dtype)


def ssd_scan(
    x: jax.Array,  # (B, S, H, P) — dt-scaled inputs NOT yet applied
    dt: jax.Array,  # (B, S, H) — softplus'd step sizes
    a: jax.Array,  # (H,) — negative decay rates (-exp(a_log))
    b_mat: jax.Array,  # (B, S, G, N)
    c_mat: jax.Array,  # (B, S, G, N)
    chunk: int,
    initial_state: jax.Array | None = None,  # (B, H, P, N)
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD. Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    assert s % chunk == 0, f"seq {s} not divisible by chunk {chunk}"
    nc = s // chunk
    rep = h // g

    xc = x.reshape(bsz, nc, chunk, h, p)
    dtc = dt.reshape(bsz, nc, chunk, h)
    bc = b_mat.reshape(bsz, nc, chunk, g, n)
    cc = c_mat.reshape(bsz, nc, chunk, g, n)

    da = dtc * a  # (B, nc, Q, H), negative
    cum = jnp.cumsum(da, axis=2)  # within-chunk cumulative log-decay

    # ---- intra-chunk (dual/attention-like form) -------------------------
    # L[q, k] = exp(cum[q] - cum[k]) for q >= k else 0. The mask goes in
    # before the exp: above the diagonal rel is positive and can overflow
    # (a 256-step chunk reaches exp(177)), and inf there makes the
    # gradient NaN even where the forward masks it.
    rel = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,Q,K,H)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    l_mat = jnp.exp(jnp.where(tri[None, None, :, :, None], rel, -jnp.inf))
    scores = jnp.einsum("bcqgn,bckgn->bcqkg", cc, bc)  # (B,nc,Q,K,G)
    scores = jnp.repeat(scores, rep, axis=-1)  # G -> H
    m = scores * l_mat * dtc[:, :, None, :, :]  # dt applied at source step k
    y_intra = jnp.einsum("bcqkh,bckhp->bcqhp", m, xc)

    # ---- chunk states ----------------------------------------------------
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # (B,nc,Q,H)
    xbar = xc * (dtc * decay_to_end)[..., None]  # (B,nc,Q,H,P)
    b_h = jnp.repeat(bc, rep, axis=3)  # (B,nc,Q,H,N)
    states = jnp.einsum("bcqhn,bcqhp->bchpn", b_h, xbar)

    # ---- inter-chunk recurrence -----------------------------------------
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (B,nc,H)

    s0 = (
        initial_state
        if initial_state is not None
        else jnp.zeros((bsz, h, p, n), x.dtype)
    )

    def step(carry, inp):
        st, dec = inp  # (B,H,P,N), (B,H)
        prev = carry
        new = prev * dec[:, :, None, None] + st
        return new, prev  # emit the state *entering* this chunk

    final_state, prev_states = jax.lax.scan(
        step,
        s0.astype(jnp.float32),
        (states.swapaxes(0, 1).astype(jnp.float32), chunk_decay.swapaxes(0, 1)),
    )
    prev_states = prev_states.swapaxes(0, 1)  # (B,nc,H,P,N)

    c_h = jnp.repeat(cc, rep, axis=3)  # (B,nc,Q,H,N)
    decay_from_start = jnp.exp(cum)  # (B,nc,Q,H)
    y_inter = (
        jnp.einsum("bcqhn,bchpn->bcqhp", c_h, prev_states.astype(x.dtype))
        * decay_from_start[..., None]
    )

    y = (y_intra + y_inter).reshape(bsz, s, h, p)
    return y.astype(x.dtype), final_state.astype(x.dtype)


def apply_mamba(
    params: dict,
    x: jax.Array,
    cfg: ArchConfig,
    *,
    use_kernel: bool = False,
    return_cache: bool = False,
):
    """Training/prefill Mamba2 block. x: (B, S, D). With ``return_cache``
    returns ``(out, cache)``, the prefill's state and conv tails."""
    bsz, s, _ = x.shape
    h, p = cfg.ssm_heads, cfg.ssm_head_dim
    g, n, w = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv_width

    hn = layers.rmsnorm(x, params["norm"], cfg.norm_eps)
    z = hn @ params["w_z"]
    xin_raw = hn @ params["w_x"]
    bc_raw = hn @ params["w_bc"]
    # dt_bias and d_skip enter in float32: their gradients are sums over
    # every token (and head dim), which would otherwise accumulate in the
    # activations' bfloat16.
    dt = jax.nn.softplus((hn @ params["w_dt"]).astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))

    xin = jax.nn.silu(causal_conv(xin_raw, params["conv_x"], params.get("conv_x_bias")))
    bc = jax.nn.silu(causal_conv(bc_raw, params["conv_bc"], params.get("conv_bc_bias")))
    b_mat, c_mat = jnp.split(bc, 2, axis=-1)

    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    xh = xin.reshape(bsz, s, h, p)
    b_mat = b_mat.reshape(bsz, s, g, n)
    c_mat = c_mat.reshape(bsz, s, g, n)

    if use_kernel:
        from repro.kernels import ops as kernel_ops

        y, state = kernel_ops.ssd_scan(xh, dt, a, b_mat, c_mat, chunk=cfg.ssm_chunk)
    else:
        y, state = ssd_scan(xh, dt, a, b_mat, c_mat, chunk=cfg.ssm_chunk)
    y = y + (params["d_skip"].astype(jnp.float32)[:, None] * xh).astype(y.dtype)
    y = y.reshape(bsz, s, h * p)
    y = layers.rmsnorm(y * jax.nn.silu(z), params["out_norm"], cfg.norm_eps)
    out = y @ params["w_out"]
    if not return_cache:
        return out
    return out, {"state": state, "conv_x": xin_raw[:, -(w - 1):],
                 "conv_bc": bc_raw[:, -(w - 1):]}


# ----------------------------------------------------------------- decode
def init_ssm_cache(cfg: ArchConfig, batch: int) -> dict:
    h, p = cfg.ssm_heads, cfg.ssm_head_dim
    g, n, w = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv_width
    dt = cfg.activation_dtype
    return {
        "state": jnp.zeros((batch, h, p, n), dt),
        "conv_x": jnp.zeros((batch, w - 1, cfg.ssm_inner), dt),
        "conv_bc": jnp.zeros((batch, w - 1, 2 * g * n), dt),
    }


def ssm_cache_shape(cfg: ArchConfig, batch: int) -> dict:
    h, p = cfg.ssm_heads, cfg.ssm_head_dim
    g, n, w = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv_width
    dt = cfg.activation_dtype
    return {
        "state": jax.ShapeDtypeStruct((batch, h, p, n), dt),
        "conv_x": jax.ShapeDtypeStruct((batch, w - 1, cfg.ssm_inner), dt),
        "conv_bc": jax.ShapeDtypeStruct((batch, w - 1, 2 * g * n), dt),
    }


def decode_mamba(
    params: dict, x: jax.Array, cache: dict, cfg: ArchConfig
) -> tuple[jax.Array, dict]:
    """One-token Mamba2 step. x: (B, 1, D)."""
    bsz = x.shape[0]
    h, p = cfg.ssm_heads, cfg.ssm_head_dim
    g, n = cfg.ssm_groups, cfg.ssm_state
    rep = h // g

    hn = layers.rmsnorm(x, params["norm"], cfg.norm_eps)
    z = hn @ params["w_z"]  # (B,1,inner)
    xin = hn @ params["w_x"]
    bc = hn @ params["w_bc"]
    dt = jax.nn.softplus(hn @ params["w_dt"] + params["dt_bias"])  # (B,1,H)

    # Rolling conv caches.
    xin_hist = jnp.concatenate([cache["conv_x"], xin], axis=1)  # (B,W,inner)
    bc_hist = jnp.concatenate([cache["conv_bc"], bc], axis=1)
    xin = jnp.einsum("bwc,wc->bc", xin_hist, params["conv_x"])
    bc_c = jnp.einsum("bwc,wc->bc", bc_hist, params["conv_bc"])
    if cfg.ssm_conv_bias:
        xin = xin + params["conv_x_bias"]
        bc_c = bc_c + params["conv_bc_bias"]
    xin = jax.nn.silu(xin)[:, None]
    bc_c = jax.nn.silu(bc_c)[:, None]
    b_mat, c_mat = jnp.split(bc_c, 2, axis=-1)

    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    xh = xin.reshape(bsz, h, p)
    b_h = jnp.repeat(b_mat.reshape(bsz, g, n), rep, axis=1)  # (B,H,N)
    c_h = jnp.repeat(c_mat.reshape(bsz, g, n), rep, axis=1)
    dt1 = dt[:, 0, :]  # (B,H)

    decay = jnp.exp(dt1 * a)  # (B,H)
    state = cache["state"].astype(jnp.float32)
    state = state * decay[:, :, None, None] + jnp.einsum(
        "bh,bhp,bhn->bhpn", dt1, xh.astype(jnp.float32), b_h.astype(jnp.float32)
    )
    y = jnp.einsum("bhn,bhpn->bhp", c_h.astype(jnp.float32), state)
    y = y + params["d_skip"][:, None].astype(jnp.float32) * xh
    y = y.reshape(bsz, 1, h * p).astype(x.dtype)
    y = layers.rmsnorm(y * jax.nn.silu(z), params["out_norm"], cfg.norm_eps)
    new_cache = {
        "state": state.astype(cache["state"].dtype),
        "conv_x": xin_hist[:, 1:],
        "conv_bc": bc_hist[:, 1:],
    }
    return y @ params["w_out"], new_cache
