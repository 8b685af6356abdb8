"""Plain reference of the Granite-4.0-H (Mamba-2 + GQA + MoE) model's loss,
for the CPU tests that hold the program to it.

Straightforward ``jax.numpy`` that shares no code with ``repro.models``:
token embedding times ``embedding_multiplier``; per layer ``x + r *
mixer(RMSNorm(x))`` and ``x + r * (moe(RMSNorm(x)) + shared(RMSNorm(x)))``
with ``r = residual_multiplier``; final RMSNorm; the tied embedding table
as the output head, logits divided by ``logits_scaling``; mean next-token
cross-entropy plus the load-balance term at ``program_aux_loss_coef``.

* Mamba-2 mixer: the scan in its quadratic (dual) form over the whole
  sequence, ``y_t = sum_{s<=t} (C_t . B_s) exp(cum_t - cum_s) dt_s x_s +
  D x_t`` with ``cum`` the running sum of ``dt A`` (no chunks, nothing of
  ``ssm.ssd_scan``).
* Attention: GQA with no positional encoding, scaled by
  ``attention_multiplier``.
* MoE: softmax over each token's ``num_experts_per_tok`` best router
  logits; a dense sum of every held expert over every token, weighted by
  its gate (no dispatch, no capacity).

Activations are float32 and every product runs at ``HIGHEST`` precision.
The configuration is a plain dict of the Hugging Face config's keys
(:func:`config_dict` makes one from an ``ArchConfig``). The benchmark
keeps its own copy, ``chipbench/reference/granite_hybrid.py``, which adds
the optimizer steps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
#: query positions per block of the Mamba-2 quadratic form ((Q, S, heads)
#: float32 decays) and of attention
MAMBA_Q_BLOCK = 128
ATTN_Q_BLOCK = 256
#: tokens per block of the output head
HEAD_BLOCK = 1024


def _rounder(operand_dtype):
    if operand_dtype is None:
        return lambda x: x.astype(F32)
    dt = jnp.dtype(operand_dtype)
    return lambda x: x.astype(dt).astype(F32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def period_types(cfg: dict) -> list[str]:
    """The mixer of each layer of one period: the shortest prefix of the
    run's ``layer_types`` that repeats over its layers."""
    types = cfg["layer_types"][: cfg["num_hidden_layers"]]
    for n in range(1, len(types) + 1):
        if len(types) % n == 0 and types == types[:n] * (len(types) // n):
            return types[:n]
    return types


def _blocks(n, size):
    """Split ``n`` positions into blocks of ``size`` (or one block)."""
    size = size if n % size == 0 else n
    return n // size, size


def _mamba(p, x, cfg, rnd, mm):
    """One Mamba-2 mixer over one sequence ``x`` (S, d)."""
    eps = cfg["rms_norm_eps"]
    heads, hdim = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    s = x.shape[0]
    h = _rmsnorm(x, p["norm"], eps)
    z = mm(h, p["w_z"])
    xi = mm(h, p["w_x"])
    bc = mm(h, p["w_bc"])
    dt = jax.nn.softplus(mm(h, p["w_dt"]) + p["dt_bias"].astype(F32))  # (S, H)

    def conv(u, w, b):
        # out[t] = b + sum_j u[t - j] * w[W - 1 - j]
        width = w.shape[0]
        out = u * w[width - 1].astype(F32)
        for j in range(1, width):
            shifted = jnp.concatenate([jnp.zeros((j, u.shape[1]), F32), u[: s - j]])
            out = out + shifted * w[width - 1 - j].astype(F32)
        return out if b is None else out + b.astype(F32)

    xi = jax.nn.silu(conv(xi, p["conv_x"], p.get("conv_x_bias")))
    bc = jax.nn.silu(conv(bc, p["conv_bc"], p.get("conv_bc_bias")))
    bm = bc[:, : g * n].reshape(s, g, n)
    cm = bc[:, g * n:].reshape(s, g, n)
    a = -jnp.exp(p["a_log"].astype(F32))
    xh = xi.reshape(s, heads, hdim)
    cum = jnp.cumsum(dt * a, axis=0)  # (S, H)
    group_of_head = np.arange(heads) // (heads // g)
    keys = np.arange(s)

    @jax.checkpoint
    def block(args):
        cq, cumq, tq = args  # (Q, g, n), (Q, H), (Q,)
        sc = jnp.einsum("qgn,kgn->qkg", rnd(cq), rnd(bm), precision=HIGHEST)
        sc = sc[:, :, group_of_head]  # (Q, S, H)
        causal = (tq[:, None] >= keys[None, :])[:, :, None]
        decay = jnp.exp(jnp.where(causal, cumq[:, None, :] - cum[None, :, :], -jnp.inf))
        w = sc * decay * dt[None, :, :]
        return jnp.einsum("qkh,khp->qhp", rnd(w), rnd(xh), precision=HIGHEST)

    nb, qb = _blocks(s, MAMBA_Q_BLOCK)
    y = jax.lax.map(block, (cm.reshape(nb, qb, g, n), cum.reshape(nb, qb, heads),
                            jnp.asarray(keys.reshape(nb, qb))))
    y = y.reshape(s, heads, hdim) + p["d_skip"].astype(F32)[:, None] * xh
    y = _rmsnorm(y.reshape(s, heads * hdim) * jax.nn.silu(z), p["out_norm"], eps)
    return mm(y, p["w_out"])


def _attention(p, x, cfg, rnd, mm):
    """Causal GQA with no positional encoding over one sequence (S, d)."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // heads
    s = x.shape[0]
    h = _rmsnorm(x, p["norm"], cfg["rms_norm_eps"])
    q = mm(h, p["wq"]).reshape(s, heads, hd) * cfg["attention_multiplier"]
    k = jnp.repeat(mm(h, p["wk"]).reshape(s, kv, hd), heads // kv, axis=1)
    v = jnp.repeat(mm(h, p["wv"]).reshape(s, kv, hd), heads // kv, axis=1)
    keys = np.arange(s)

    @jax.checkpoint
    def block(args):
        qc, tq = args
        sc = jnp.einsum("qhd,khd->hqk", rnd(qc), rnd(k), precision=HIGHEST)
        sc = jnp.where((tq[:, None] >= keys[None, :])[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", rnd(pr), rnd(v), precision=HIGHEST)

    nb, qb = _blocks(s, ATTN_Q_BLOCK)
    o = jax.lax.map(block, (q.reshape(nb, qb, heads, hd), jnp.asarray(keys.reshape(nb, qb))))
    return mm(o.reshape(s, heads * hd), p["wo"])


def _moe(p, x, cfg, rnd, mm):
    """The MoE layer and shared expert over tokens ``x`` (T, d). Returns
    (output, load-balance term)."""
    e, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    held = cfg["expert_offset"] + np.arange(cfg["num_local_experts"])
    t = x.shape[0]
    h = _rmsnorm(x, p["norm"], cfg["rms_norm_eps"])
    logits = mm(h, p["router"])  # (T, E)
    top_logits, top_idx = jax.lax.top_k(logits, k)
    top_gates = jax.nn.softmax(top_logits, axis=-1)
    # gate of each held expert for each token (0 where it is not chosen)
    gate = jnp.sum(jnp.where(top_idx[:, :, None] == held[None, None, :],
                             top_gates[:, :, None], 0.0), axis=1)  # (T, held)
    frac = jnp.sum(jax.nn.one_hot(top_idx, e, dtype=F32), axis=(0, 1)) / (t * k)
    aux = e * jnp.sum(frac * jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0))
    up = jnp.einsum("td,edf->tef", rnd(h), rnd(p["wi_up"]), precision=HIGHEST)
    act = jax.nn.silu(jnp.einsum("td,edf->tef", rnd(h), rnd(p["wi_gate"]),
                                 precision=HIGHEST)) * up * gate[:, :, None]
    y = jnp.einsum("tef,efd->td", rnd(act), rnd(p["wo"]), precision=HIGHEST)
    shared = mm(jax.nn.silu(mm(h, p["shared_wi_gate"])) * mm(h, p["shared_wi_up"]),
                p["shared_wo"])
    return y + shared, aux


def slot_loss(params, tokens, labels, cfg: dict, operand_dtype=None):
    """The loss of one slot (``tokens``, ``labels``: (sequences, S)): mean
    cross-entropy over its tokens plus the load-balance term, which the
    MoE layers take over all of the slot's tokens together."""
    rnd = _rounder(operand_dtype)

    def mm(a, w):
        return jnp.dot(rnd(a), rnd(w), precision=HIGHEST, preferred_element_type=F32)

    n_seq, s = tokens.shape
    d = cfg["hidden_size"]
    r = cfg["residual_multiplier"]
    eps = cfg["rms_norm_eps"]
    table = params["embed"]["tok"]
    x = rnd(table[tokens]) * cfg["embedding_multiplier"]  # (n, S, d)
    types = period_types(cfg)
    n_periods = cfg["num_hidden_layers"] // len(types)

    def layer(x, lp, mixer):
        fn = _mamba if mixer == "mamba" else _attention
        y = jax.lax.map(jax.checkpoint(lambda xs: fn(lp[mixer], xs, cfg, rnd, mm)), x)
        x = x + r * y
        y, aux = _moe(lp["moe"], x.reshape(n_seq * s, d), cfg, rnd, mm)
        return x + r * y.reshape(n_seq, s, d), aux

    aux_total = jnp.zeros((), F32)
    for i in range(n_periods):
        for j, kind in enumerate(types):
            mixer = "attn" if kind == "attention" else "mamba"
            lp = jax.tree.map(lambda a: a[i], params["blocks"][f"sub{j}"])
            x, aux = jax.checkpoint(layer, static_argnums=(2,))(x, lp, mixer)
            aux_total = aux_total + aux

    x = _rmsnorm(x, params["final_norm"], eps).reshape(n_seq * s, d)
    vocab = cfg["vocab_size"]

    @jax.checkpoint
    def head(args):
        xc, lc = args
        logits = mm(xc, table[:vocab].T) / cfg["logits_scaling"]
        ll = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - ll

    nb, tb = _blocks(n_seq * s, HEAD_BLOCK)
    ce = jax.lax.map(head, (x.reshape(nb, tb, d), labels.reshape(nb, tb)))
    return jnp.mean(ce) + cfg["program_aux_loss_coef"] * aux_total


def config_dict(cfg) -> dict:
    """The reference's configuration for an ``ArchConfig`` of this
    family (every layer MoE with one shared expert, NoPE attention)."""
    from repro.models.model import AUX_LOSS_COEF

    types = ["attention" if s.mixer == "attn" else "mamba" for s in cfg.period]
    return {
        "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "vocab_size": cfg.vocab_size,
        "rms_norm_eps": cfg.norm_eps,
        "num_hidden_layers": cfg.num_layers,
        "layer_types": types * cfg.n_periods,
        "mamba_n_heads": cfg.ssm_heads,
        "mamba_d_head": cfg.ssm_head_dim,
        "mamba_d_state": cfg.ssm_state,
        "mamba_n_groups": cfg.ssm_groups,
        "router_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.top_k,
        "num_local_experts": cfg.held_experts,
        "expert_offset": cfg.expert_offset,
        "attention_multiplier": cfg.attn_scale,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "logits_scaling": cfg.logits_scaling,
        "program_aux_loss_coef": AUX_LOSS_COEF,
    }
