"""Plain reference of a dense pre-norm decoder's training step.

Straightforward ``jax.numpy``: token embedding, per layer RMSNorm -> GQA
causal attention with rotary positions (rotate-half over the two halves of
each head) -> residual, RMSNorm -> SwiGLU MLP -> residual; final RMSNorm,
output head over the real vocabulary, mean next-token cross-entropy. Then
AdamW with global-norm clipping and a warm-up/cosine schedule, parameters
kept in their stated dtype (updated in float32, stored back rounded).

Activations are float32 and every product runs at ``HIGHEST`` precision.
``operand_dtype`` rounds every matmul operand (weights, activations, the
embedding table) to a lower type first: that is the control, computed one
precision below what the configuration states.

The parameter tree follows the layout the benchmark hands the program
(``embed/tok``, ``blocks/sub0/{attn,mlp}/...`` stacked over layers,
``final_norm``, ``head/w``); rows and columns past the real vocabulary are
padding that no token reaches.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _rounder(operand_dtype):
    if operand_dtype is None:
        return lambda x: x.astype(F32)
    dt = jnp.dtype(operand_dtype)
    return lambda x: x.astype(dt).astype(F32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, theta):
    """x: (S, heads, hd); rotate-half rotary embedding at positions 0..S-1."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), F32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def sequence_loss(params, tokens, labels, cfg: dict, operand_dtype=None,
                  q_chunk: int = 512):
    """Mean cross-entropy of one sequence (``tokens``, ``labels``: (S,))."""
    rnd = _rounder(operand_dtype)
    d = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // heads
    rep = heads // kv
    vocab = cfg["vocab_size"]
    eps = cfg["rms_norm_eps"]
    theta = cfg["rope_theta"]
    s = tokens.shape[0]

    def mm(a, w):
        return jnp.dot(rnd(a), rnd(w), precision=HIGHEST, preferred_element_type=F32)

    x = rnd(params["embed"]["tok"][tokens])
    blocks = params["blocks"]["sub0"]
    n_chunks = max(1, s // q_chunk)
    causal = np.tril(np.ones((s, s), dtype=bool)).reshape(n_chunks, s // n_chunks, s)

    def layer(x, lp):
        a, m = lp["attn"], lp["mlp"]
        h = _rmsnorm(x, a["norm"], eps)
        q = _rope(mm(h, a["wq"]).reshape(s, heads, hd), theta) * (hd ** -0.5)
        k = _rope(mm(h, a["wk"]).reshape(s, kv, hd), theta)
        v = mm(h, a["wv"]).reshape(s, kv, hd)
        k = jnp.repeat(k, rep, axis=1)  # head j reads kv group j // rep
        v = jnp.repeat(v, rep, axis=1)

        @jax.checkpoint
        def attend(args):
            qc, mask = args
            sc = jnp.einsum("qhd,khd->hqk", rnd(qc), rnd(k), precision=HIGHEST)
            sc = jnp.where(mask[None], sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1)
            return jnp.einsum("hqk,khd->qhd", rnd(p), rnd(v), precision=HIGHEST)

        o = jax.lax.map(attend, (q.reshape(n_chunks, s // n_chunks, heads, hd),
                                 jnp.asarray(causal)))
        x = x + mm(o.reshape(s, heads * hd), a["wo"])
        h = _rmsnorm(x, m["norm"], eps)
        x = x + mm(jax.nn.silu(mm(h, m["wi_gate"])) * mm(h, m["wi_up"]), m["wo"])
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, blocks)
    x = _rmsnorm(x, params["final_norm"], eps)
    logits = mm(x, params["head"]["w"][:, :vocab])
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - ll)


def _grad_fn(cfg: dict, operand_dtype):
    """Loss and gradient of one sequence. The gradient is taken with
    respect to the stored parameters, so it comes out in their dtype, as
    the program's does; every product inside runs in float32."""
    return jax.jit(jax.value_and_grad(
        lambda p, t, l: sequence_loss(p, t, l, cfg, operand_dtype)))


def lr_at(opt: dict, step: int) -> float:
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    cos = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * cos


def _norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                      for x in jax.tree.leaves(tree)])


def _adamw_fn(opt: dict):
    b1, b2, eps, wd = opt["beta1"], opt["beta2"], opt["eps"], opt["weight_decay"]

    def f(params, grads, mu, nu, step, lr):
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
        bc1 = 1 - b1 ** step
        bc2 = 1 - b2 ** step

        def one(p, g, m, n):
            g = g * scale
            m = b1 * m + (1 - b1) * g
            n = b2 * n + (1 - b2) * g * g
            p32 = p.astype(F32)
            p32 = p32 - lr * ((m / bc1) / (jnp.sqrt(n / bc2) + eps) + wd * p32)
            return p32.astype(p.dtype), m, n

        flat_p, tdef = jax.tree.flatten(params)
        outs = [one(*a) for a in zip(flat_p, jax.tree.leaves(grads),
                                     jax.tree.leaves(mu), jax.tree.leaves(nu))]
        new = [jax.tree.unflatten(tdef, [o[i] for o in outs]) for i in range(3)]
        return (*new, _norms(grads), _norms(grads) * scale)

    return jax.jit(f, donate_argnums=(0, 1, 2, 3))


def leaf_norms(tree) -> np.ndarray:
    return np.asarray(jax.jit(_norms)(tree), dtype=np.float64)


def train_readings(make_params, batches, cfg: dict, opt: dict,
                   operand_dtype=None) -> dict:
    """Run the reference's first ``len(batches)`` steps from the weights
    ``make_params()`` gives (called twice: to start, and for the change).

    ``batches[i]`` is ``(tokens, labels)`` of shape (slots, sequences, S):
    the step's loss and gradient are means over every sequence, slot by
    slot. Returns each step's loss, the per-leaf norms of the first step's
    clipped gradient (what AdamW's first moment receives) and of the raw
    one, and the per-leaf norms of the parameters' change after the last
    step.
    """
    grad = _grad_fn(cfg, operand_dtype)
    adamw = _adamw_fn(opt)
    add = jax.jit(lambda a, b: jax.tree.map(lambda x, y: x + y.astype(F32), a, b),
                  donate_argnums=(0,))
    params = make_params()
    mu = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    nu = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    losses, first_clipped, first_raw = [], None, None
    for step, (tokens, labels) in enumerate(batches, start=1):
        slots, seqs = tokens.shape[:2]
        gsum = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
        slot_losses = []
        for i in range(slots):
            seq_losses = []
            for j in range(seqs):
                loss, g = grad(params, jnp.asarray(tokens[i, j]), jnp.asarray(labels[i, j]))
                gsum = add(gsum, g)
                del g
                seq_losses.append(float(loss))
            slot_losses.append(float(np.mean(seq_losses)))
        losses.append(float(np.mean(slot_losses)))
        n = slots * seqs
        gmean = jax.jit(lambda t: jax.tree.map(lambda x: x / n, t),
                        donate_argnums=(0,))(gsum)
        params, mu, nu, raw, clipped = adamw(
            params, gmean, mu, nu, jnp.float32(step), jnp.float32(lr_at(opt, step)))
        if step == 1:
            first_raw = np.asarray(raw, np.float64)
            first_clipped = np.asarray(clipped, np.float64)
    del mu, nu
    p0 = make_params()
    change = leaf_norms(jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: x.astype(F32) - y.astype(F32), a, b))(params, p0))
    return {"losses": losses, "grad_norms": first_clipped,
            "raw_grad_norms": first_raw, "change_norms": change}
