"""Plain reference of a Granite-4.0-H (Mamba-2 + GQA + MoE) training step.

Straightforward ``jax.numpy`` from the configuration's own keys: token
embedding times ``embedding_multiplier``; per layer ``x + r * mixer(
RMSNorm(x))`` and ``x + r * (moe(RMSNorm(x)) + shared(RMSNorm(x)))`` with
``r = residual_multiplier``; final RMSNorm; the tied embedding table as
the output head, logits divided by ``logits_scaling``; mean next-token
cross-entropy plus the load-balance term at the configuration's
``program_aux_loss_coef``. Then AdamW as ``dense_decoder`` steps it.

* Mamba-2 mixer: in-projections (z, x, B/C, dt), depthwise causal conv
  with bias, SiLU, ``dt = softplus(. + dt_bias)``, ``A = -exp(a_log)``;
  the scan in its quadratic (dual) form over the whole sequence,
  ``y_t = sum_{s<=t} (C_t . B_s) exp(cum_t - cum_s) dt_s x_s + D x_t`` with
  ``cum`` the running sum of ``dt A``, one block of query positions at a
  time; then ``RMSNorm(y * SiLU(z))`` and the out-projection.
* Attention: GQA with no positional encoding, scores scaled by
  ``attention_multiplier``.
* MoE: the router scores all ``router_experts``; each token keeps its
  ``num_experts_per_tok`` best logits and softmaxes them. The layer sums
  every held expert (``num_local_experts`` from ``expert_offset``) over
  every token, each weighted by its gate (0 where not chosen): no
  dispatch, no capacity. The load-balance term is ``E * sum_e f_e * p_e``
  over all the router's experts, over the slot's tokens, per layer.

Activations are float32 and every product runs at ``HIGHEST`` precision;
``operand_dtype`` rounds every matmul operand to a lower type first (the
control). The parameter tree follows the layout the benchmark hands the
program (``embed/tok``, ``blocks/sub<j>/{mamba|attn, moe}/...`` stacked
over periods of ``layer_types``, ``final_norm``).

On several devices the reference's state is spread over them by
``NamedSharding`` (each leaf split along a dimension the device count
divides); the compiler partitions the computation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.dense_decoder import _adamw_fn, lr_at

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


def _mm_fns(operand_dtype):
    rnd = _rounder(operand_dtype)

    def mm(a, w):
        return jnp.dot(rnd(a), rnd(w), precision=HIGHEST, preferred_element_type=F32)

    return rnd, mm


def embed(table, tokens, cfg: dict, operand_dtype=None):
    """The scaled token embeddings (sequences, S, d)."""
    rnd, _ = _mm_fns(operand_dtype)
    return rnd(table[tokens]) * cfg["embedding_multiplier"]


def layer(x, lp, cfg: dict, mixer: str, operand_dtype=None):
    """One layer over ``x`` (sequences, S, d): the mixer (``"mamba"`` or
    ``"attn"``) and the MoE, each added to the residual scaled by
    ``residual_multiplier``. Returns (x, load-balance term)."""
    rnd, mm = _mm_fns(operand_dtype)
    n_seq, s, d = x.shape
    r = cfg["residual_multiplier"]
    fn = _mamba if mixer == "mamba" else _attention
    y = jax.lax.map(jax.checkpoint(lambda xs: fn(lp[mixer], xs, cfg, rnd, mm)), x)
    x = x + r * y
    y, aux = _moe(lp["moe"], x.reshape(n_seq * s, d), cfg, rnd, mm)
    return x + r * y.reshape(n_seq, s, d), aux


def head_loss(x, table, final_norm, labels, cfg: dict, operand_dtype=None):
    """Mean next-token cross-entropy of ``x`` (sequences, S, d) through the
    final RMSNorm and the tied head over the real vocabulary, logits
    divided by ``logits_scaling``."""
    _, mm = _mm_fns(operand_dtype)
    n_seq, s, d = x.shape
    x = _rmsnorm(x, final_norm, cfg["rms_norm_eps"]).reshape(n_seq * s, d)
    vocab = cfg["vocab_size"]

    @jax.checkpoint
    def head(args):
        xc, lc = args
        logits = mm(xc, table[:vocab].T) / cfg["logits_scaling"]
        ll = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - ll

    nb, tb = _blocks(n_seq * s, HEAD_BLOCK)
    return jnp.mean(jax.lax.map(head, (x.reshape(nb, tb, d), labels.reshape(nb, tb))))


def layer_plan(cfg: dict) -> list[tuple[int, int, str]]:
    """``(period, index in the period, mixer)`` of each layer in order."""
    types = period_types(cfg)
    return [(i, j, "attn" if kind == "attention" else "mamba")
            for i in range(cfg["num_hidden_layers"] // len(types))
            for j, kind in enumerate(types)]


def slot_loss(params, tokens, labels, cfg: dict, operand_dtype=None):
    """The loss of one slot (``tokens``, ``labels``: (sequences, S)): mean
    cross-entropy over its tokens plus the load-balance term, which the
    MoE layers take over all of the slot's tokens together."""
    table = params["embed"]["tok"]
    x = embed(table, tokens, cfg, operand_dtype)
    aux_total = jnp.zeros((), F32)
    for i, j, mixer in layer_plan(cfg):
        lp = jax.tree.map(lambda a: a[i], params["blocks"][f"sub{j}"])
        x, aux = layer(x, lp, cfg, mixer, operand_dtype)
        aux_total = aux_total + aux
    ce = head_loss(x, table, params["final_norm"], labels, cfg, operand_dtype)
    return ce + cfg["program_aux_loss_coef"] * aux_total


class LayerwiseGrad:
    """:func:`slot_loss` and its gradient, one layer at a time: the forward
    keeps each layer's input, and the backward recomputes one layer's
    forward under ``jax.vjp`` at a time. Each kind of layer, the head and
    the embedding compile once, whatever the depth; the gradient is that of
    ``jax.value_and_grad(slot_loss)``, in the parameters' dtype."""

    def __init__(self, cfg: dict, operand_dtype=None) -> None:
        self.cfg = cfg
        coef = cfg["program_aux_loss_coef"]

        def fwd(x, lp, mixer):
            return layer(x, lp, cfg, mixer, operand_dtype)

        def bwd(x, lp, g, mixer):
            _, vjp = jax.vjp(lambda x, lp: layer(x, lp, cfg, mixer, operand_dtype), x, lp)
            return vjp((g, jnp.asarray(coef, F32)))

        def head(x, table, final_norm, labels):
            return jax.value_and_grad(
                lambda x, t, n: head_loss(x, t, n, labels, cfg, operand_dtype),
                argnums=(0, 1, 2))(x, table, final_norm)

        def embed_bwd(table, tokens, g):
            _, vjp = jax.vjp(lambda t: embed(t, tokens, cfg, operand_dtype), table)
            return vjp(g)[0]

        self._embed = jax.jit(lambda t, tok: embed(t, tok, cfg, operand_dtype))
        self._fwd = jax.jit(fwd, static_argnums=(2,))
        self._bwd = jax.jit(bwd, static_argnums=(3,))
        self._head = jax.jit(head)
        self._embed_bwd = jax.jit(embed_bwd)
        self._add = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
        self._put = jax.jit(lambda a, i, g: a.at[i].add(g), donate_argnums=(0,))

    def __call__(self, params, tokens, labels):
        plan = layer_plan(self.cfg)
        table = params["embed"]["tok"]

        def layer_params(i, j):
            return jax.tree.map(lambda a: a[i], params["blocks"][f"sub{j}"])

        xs, aux_total = [self._embed(table, tokens)], 0.0
        for i, j, mixer in plan:
            x, aux = self._fwd(xs[-1], layer_params(i, j), mixer)
            xs.append(x)
            aux_total += float(aux)
        ce, (g, g_table, g_norm) = self._head(xs.pop(), table, params["final_norm"], labels)
        blocks = jax.tree.map(jnp.zeros_like, params["blocks"])
        for i, j, mixer in reversed(plan):
            g, g_lp = self._bwd(xs.pop(), layer_params(i, j), g, mixer)
            blocks[f"sub{j}"] = jax.tree.map(lambda a, b: self._put(a, i, b),
                                             blocks[f"sub{j}"], g_lp)
        g_table = self._add(g_table, self._embed_bwd(table, tokens, g))
        loss = float(ce) + self.cfg["program_aux_loss_coef"] * aux_total
        return loss, {"embed": {"tok": g_table}, "blocks": blocks, "final_norm": g_norm}


#: the MoE's expert weight leaves, (periods, held experts, ...): their
#: norms are taken per period and held expert
EXPERT_LEAVES = ("wi_gate", "wi_up", "wo")


def _is_expert_leaf(path) -> bool:
    keys = [getattr(k, "key", None) for k in path]
    return len(keys) >= 2 and keys[-2] == "moe" and keys[-1] in EXPERT_LEAVES


def unit_sq(tree):
    """The squared norm of each unit of ``tree``, in flattening order: a
    unit is a leaf, or one period's held expert of an expert weight leaf
    (so one expert's share is compared on its own)."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x2 = jnp.square(x.astype(F32))
        if _is_expert_leaf(path):
            out.append(jnp.sum(x2, axis=tuple(range(2, x.ndim))).reshape(-1))
        else:
            out.append(jnp.sum(x2)[None])
    return jnp.concatenate(out)


def unit_norms(tree) -> np.ndarray:
    return np.sqrt(np.asarray(jax.jit(unit_sq)(tree), np.float64))


def unit_names(template) -> list[str]:
    """A name for each unit of :func:`unit_sq`, in its order."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(template)[0]:
        name = jax.tree_util.keystr(path)
        if _is_expert_leaf(path):
            out += [f"{name}[{i}, expert {e}]" for i in range(x.shape[0])
                    for e in range(x.shape[1])]
        else:
            out.append(name)
    return out


def spread(tree, devices):
    """``NamedSharding`` of each leaf of ``tree`` over ``devices``: split
    along its largest dimension the device count divides (else kept
    whole on every device). Placement only."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("d",))
    n = len(devices)

    def one(leaf):
        dims = [i for i in np.argsort(leaf.shape)[::-1] if leaf.shape[i] % n == 0]
        spec = [None] * len(leaf.shape)
        if dims:
            spec[dims[0]] = "d"
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, tree)


def train_readings(make_params, template, batches, cfg: dict, opt: dict,
                   operand_dtype=None, devices=None) -> dict:
    """Run the reference's first ``len(batches)`` steps from the weights
    ``make_params(shardings)`` gives (called twice: to start, and for the
    change), its state spread over ``devices`` (None: one device).

    ``batches[i]`` is ``(tokens, labels)`` of shape (slots, sequences, S):
    a step's loss and gradient are means over its slots. Returns each
    step's loss, the norms by unit (:func:`unit_sq`) of the first step's
    clipped gradient (what AdamW's first moment receives) and of the raw
    one, and those of the parameters' change after the last step.
    """
    sh = spread(template, devices) if devices is not None and len(devices) > 1 else None
    grad = LayerwiseGrad(cfg, operand_dtype)
    adamw = _adamw_fn(opt)
    add = jax.jit(lambda a, b: jax.tree.map(lambda x, y: x + y.astype(F32), a, b),
                  donate_argnums=(0,), out_shardings=sh)
    zeros = jax.jit(lambda: jax.tree.map(lambda p: jnp.zeros(p.shape, F32), template),
                    out_shardings=sh)
    as_f32 = jax.jit(lambda t: jax.tree.map(lambda x: x.astype(F32), t), out_shardings=sh)
    params = make_params(sh)
    mu, nu = zeros(), zeros()
    losses, first_clipped, first_raw = [], None, None
    for step, (tokens, labels) in enumerate(batches, start=1):
        slots = tokens.shape[0]
        gsum = None
        slot_losses = []
        for i in range(slots):
            loss, g = grad(params, jnp.asarray(tokens[i]), jnp.asarray(labels[i]))
            gsum = as_f32(g) if gsum is None else add(gsum, g)
            del g
            slot_losses.append(loss)
        losses.append(float(np.mean(slot_losses)))
        gmean = jax.jit(lambda t: jax.tree.map(lambda x: x / slots, t),
                        donate_argnums=(0,))(gsum)
        if step == 1:
            first_raw = unit_norms(gmean)
            gnorm = float(np.sqrt(np.sum(np.square(first_raw))))
            first_clipped = first_raw * min(1.0, opt["clip_norm"] / max(gnorm, 1e-9))
        params, mu, nu, _, _ = adamw(
            params, gmean, mu, nu, jnp.float32(step), jnp.float32(lr_at(opt, step)))
    del mu, nu
    p0 = make_params(sh)
    change = np.sqrt(np.asarray(jax.jit(lambda a, b: unit_sq(jax.tree.map(
        lambda x, y: x.astype(F32) - y.astype(F32), a, b)))(params, p0), np.float64))
    return {"losses": losses, "grad_norms": first_clipped,
            "raw_grad_norms": first_raw, "change_norms": change}
