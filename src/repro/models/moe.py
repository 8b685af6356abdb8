"""Mixture-of-Experts with sort-based, dropless token dispatch.

Tokens are routed over every expert, their (token, expert) assignments to
the experts this host holds are sorted by expert, and each held expert's
SwiGLU FFN runs as one grouped product (``jax.lax.ragged_dot``) over its
contiguous run of rows, combined back with the router gates. No capacity
buffer: every assignment to a held expert is computed, however unbalanced
the routing (at most ``min(top_k, held)`` rows a token, a static bound).

Held experts: ``cfg.held_experts`` of them from ``cfg.expert_offset``
(all by default). An expert outside that share belongs to another host;
the router still scores it, and what it would add is left out.

Sharding modes (resolved against the model axis):
  * ``experts``: expert-parallel — the held-expert dim of the expert
    weights is sharded; each model rank computes its own experts from every
    token and one psum combines the partial outputs.
  * ``ff``: tensor-parallel experts — the per-expert FF dim is sharded
    (used when the experts do not divide the axis).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers
from repro.models.schema import ParamDef, Schema


def moe_schema(cfg: ArchConfig) -> Schema:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.held_experts
    if cfg.moe_shard == "experts":
        ax: tuple = ("model", None, None)
    else:  # "ff": shard the per-expert hidden dim
        ax = (None, None, "model")
    out: Schema = {
        "norm": layers.rmsnorm_schema(d),
        "router": ParamDef((d, cfg.padded_experts), (None, None)),
        "wi_gate": ParamDef((e, d, f), ax),
        "wi_up": ParamDef((e, d, f), ax),
        "wo": ParamDef((e, f, d), (ax[0], ax[2], None)),
    }
    if cfg.num_shared_experts:
        fs = cfg.shared_d_ff * cfg.num_shared_experts
        out["shared_wi_gate"] = ParamDef((d, fs), (None, "model"))
        out["shared_wi_up"] = ParamDef((d, fs), (None, "model"))
        out["shared_wo"] = ParamDef((fs, d), ("model", None))
    return out


def route(
    logits: jax.Array, top_k: int, n_real: int | None = None
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing. Returns (gates (T,k), expert_idx (T,k), aux_loss).

    The gates are the top-k of the softmax over every expert, renormalised
    to sum to one: the same numbers as a softmax over the top-k logits
    alone (Granite's and Mixtral's form), since both are
    ``exp(l_i) / sum_{j in top-k} exp(l_j)``.

    ``n_real``: number of real experts when the expert dim is padded —
    dummy columns are masked so they are never routed to."""
    if n_real is not None and n_real < logits.shape[-1]:
        mask = jnp.arange(logits.shape[-1]) < n_real
        logits = jnp.where(mask, logits, jnp.asarray(-1e9, logits.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e.
    e = logits.shape[-1]
    pe = probs.mean(axis=0)  # (E,)
    fe = jnp.zeros(e).at[idx.reshape(-1)].add(1.0) / idx.size
    aux = e * jnp.sum(fe * pe)
    return gates, idx, aux


def expert_ffn(params: dict, xs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """Per-expert SwiGLU over rows sorted by expert: rows
    ``[sum(sizes[:e]), sum(sizes[:e+1]))`` go through expert ``e``. Rows
    past ``sum(sizes)`` belong to no expert and come out undefined (the
    TPU's grouped product leaves them unwritten): callers mask them."""
    gate = jax.lax.ragged_dot(xs, params["wi_gate"], group_sizes)
    up = jax.lax.ragged_dot(xs, params["wi_up"], group_sizes)
    return jax.lax.ragged_dot(jax.nn.silu(gate) * up, params["wo"], group_sizes)


def _moe_core(
    params: dict,
    xf: jax.Array,
    cfg: ArchConfig,
    e_offset,
    e_local: int,
) -> tuple[jax.Array, jax.Array, dict]:
    """Route + sort-dispatch + grouped SwiGLU for experts
    [e_offset, e_offset + e_local). Returns the *partial* combined output
    (T, D) f32 (contributions of those experts only), the aux loss and
    ``{"counts": (e_local,) tokens each expert computed, "dropped":
    assignments to these experts left uncomputed}``.

    The expert-parallel path calls it per model-axis shard with that
    shard's experts, so dispatch and combine stay device-local (the
    cross-shard reduction is one psum of the partial output — see
    apply_moe).
    """
    t, d = xf.shape
    k = cfg.top_k
    gates, idx, aux = route(xf @ params["router"], k, n_real=cfg.num_experts)

    flat_e = idx.reshape(-1)  # (T*k,)
    flat_g = gates.reshape(-1)
    # Each assignment's group: its local expert, or e_local (sorted last,
    # never computed) for an expert outside this share.
    local = flat_e - e_offset
    held_assignment = (local >= 0) & (local < e_local)
    keys = jnp.where(held_assignment, local, e_local)
    # A token's top-k experts are distinct, so at most min(k, e_local) of
    # its assignments are held: the held ones sort into these rows.
    rows = t * min(k, e_local)
    order = jnp.argsort(keys, stable=True)[:rows]
    group_sizes = jnp.bincount(keys, length=e_local + 1)[:e_local].astype(jnp.int32)
    token_of = order // k
    # Rows past the held assignments are padding, which the grouped
    # product leaves undefined: they are masked with ``where`` on the way
    # in and before any use of the output (a zero gate would not do: NaN *
    # 0 is NaN, in the output and in the gates' gradient alike).
    valid = (keys[order] < e_local)[:, None]
    xs = jnp.where(valid, xf[token_of], 0.0)
    out = jnp.where(valid, expert_ffn(params, xs, group_sizes).astype(jnp.float32), 0.0)
    y = jnp.zeros((t, d), jnp.float32).at[token_of].add(out * flat_g[order][:, None])
    held = jnp.sum(held_assignment).astype(jnp.int32)
    stats = {"counts": group_sizes, "dropped": held - jnp.sum(group_sizes)}
    return y, aux, stats


def _combine(y: jax.Array) -> jax.Array:
    """The expert-parallel layer's one exchange between chips: the sum of
    every model rank's partial output."""
    return jax.lax.psum(y, "model")


def _shared(params: dict, xf: jax.Array) -> jax.Array:
    with jax.named_scope("shared_mlp"):
        shg = jax.nn.silu(xf @ params["shared_wi_gate"]) * (xf @ params["shared_wi_up"])
        return shg @ params["shared_wo"]


def _ep_axes(cfg: ArchConfig):
    """(mesh, batch_axes, model_axis_size) when the expert-parallel
    shard_map path applies under the ambient mesh, else None.

    Expert parallelism needs held experts % model == 0; the GSPMD fallback
    handles the rest. On meshless hosts (CPU smoke tests) the mesh is empty
    -> None.
    """
    mesh = jax.sharding.get_abstract_mesh()
    names = mesh.axis_names
    if "model" not in names:
        return None
    tp = mesh.shape["model"]
    if tp <= 1 or cfg.moe_shard != "experts" or cfg.held_experts % tp:
        return None
    ba = tuple(a for a in ("pod", "data") if a in names)
    return mesh, ba, tp


def apply_moe(
    params: dict, x: jax.Array, cfg: ArchConfig
) -> tuple[jax.Array, jax.Array, dict]:
    """Returns (output (B,S,D), aux_loss scalar, stats): ``stats["counts"]``
    (held_experts,) tokens routed to each held expert, ``stats["dropped"]``
    held assignments not computed (0: the dispatch is dropless). The routed
    experts run under the ``moe`` named scope, the shared expert under
    ``shared_mlp``.

    Under a mesh with a model axis dividing the held experts (and
    ``moe_shard="experts"``), dispatch/combine run *shard-locally* inside a
    shard_map: each model rank sorts its own experts' assignments from its
    own tokens, and the only cross-shard communication is one
    activation-sized psum of the partial outputs over the model axis — the
    same collective the dense TP MLP already pays — instead of GSPMD's
    replicated-scatter all-reduces (EXPERIMENTS §Perf, jamba/olmoe
    iterations). Otherwise falls back to the plain GSPMD formulation.
    """
    b, s, d = x.shape
    hn = layers.rmsnorm(x, params["norm"], cfg.norm_eps)

    ep = _ep_axes(cfg)
    if ep is None:
        xf = hn.reshape(b * s, d)
        with jax.named_scope("moe"):
            y, aux, stats = _moe_core(
                params, xf, cfg, cfg.expert_offset, cfg.held_experts
            )
            y = y.astype(x.dtype)
        if cfg.num_shared_experts:
            y = y + _shared(params, xf).astype(x.dtype)
        return y.reshape(b, s, d), aux, stats

    mesh, ba, tp = ep
    e_local = cfg.held_experts // tp
    from jax.sharding import PartitionSpec as P

    dsize = 1
    for a in ba:
        dsize *= mesh.shape[a]
    # A batch that doesn't divide the DP axes (long_500k decode has B=1) is
    # replicated across them: every DP rank then routes the same tokens.
    split = bool(ba) and b % dsize == 0
    bspec = P(ba if split else None, None, None)
    wspec = {
        "norm": jax.tree.map(lambda _: P(), params["norm"]),
        "router": P(),
        "wi_gate": P("model", None, None),
        "wi_up": P("model", None, None),
        "wo": P("model", None, None),
    }
    if cfg.num_shared_experts:
        wspec["shared_wi_gate"] = P(None, "model")
        wspec["shared_wi_up"] = P(None, "model")
        wspec["shared_wo"] = P("model", None)

    def ep_body(p, h):
        bl, sl, _ = h.shape
        xf = h.reshape(bl * sl, d)
        r = jax.lax.axis_index("model")
        with jax.named_scope("moe"):
            y, aux, stats = _moe_core(
                p, xf, cfg, cfg.expert_offset + r * e_local, e_local
            )
        if cfg.num_shared_experts:
            # Shared experts are column/row tensor-parallel over the same
            # axis; their row-parallel partial rides the same psum.
            y = y + _shared(p, xf).astype(jnp.float32)
        y = _combine(y)
        aux = jax.lax.pmean(aux, ("model", *ba))  # identical across manual ranks
        counts, dropped = stats["counts"], jax.lax.psum(stats["dropped"], "model")
        if split:
            counts = jax.lax.psum(counts, ba)
            dropped = jax.lax.psum(dropped, ba)
        return y.astype(h.dtype).reshape(bl, sl, d), aux, counts, dropped

    manual = frozenset(("model", *ba))
    shmapped = jax.shard_map(
        ep_body,
        mesh=mesh,
        in_specs=(wspec, bspec),
        out_specs=(bspec, P(), P("model"), P()),
        axis_names=manual,
        check_vma=False,
    )
    y, aux, counts, dropped = shmapped({k: params[k] for k in wspec}, hn)
    return y, aux, {"counts": counts, "dropped": dropped}
