"""Mixture-of-Experts with sort-based, dropless token dispatch.

Tokens are routed over every expert, their (token, expert) assignments to
the experts this host holds are sorted by expert, and each held expert's
SwiGLU FFN runs as one grouped product (``jax.lax.ragged_dot``) over its
contiguous run of rows, combined back with the router gates. No capacity
buffer: every assignment to a held expert is computed, however unbalanced
the routing. The rows a dispatch runs come from a static ladder
(:func:`dispatch_rungs`): the bound ``t * min(top_k, held)`` (a token's
top-k are distinct), then halves of it down to the load uniform routing
would bring; each layer takes the smallest rung that holds its assignments.

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

import functools
import math

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


def dispatch_rungs(t: int, k: int, e_local: int, num_experts: int) -> tuple[int, ...]:
    """The row counts a dispatch of ``t`` tokens to ``e_local`` of
    ``num_experts`` experts may run, largest first. The first is the
    dropless bound ``t * min(k, e_local)``; each next one halves it, rounded
    up to a multiple of 128, while the half still holds the assignments
    uniform routing brings, ``t * k * e_local / num_experts``. With every
    expert held that is the bound itself: one rung."""
    full = t * min(k, e_local)
    expected = t * k * e_local / num_experts
    rungs = [full]
    half = full / 2
    while 0 < expected <= half:
        rung = min(full, math.ceil(half / 128) * 128)
        if rung < rungs[-1]:
            rungs.append(rung)
        half /= 2
    return tuple(rungs)


def rung_index(rungs: tuple[int, ...], held: jax.Array) -> jax.Array:
    """Index into ``rungs`` (largest first) of the smallest that holds
    ``held`` rows; the first, the dropless bound, always does."""
    return jnp.sum(jnp.asarray(rungs[1:]) >= held).astype(jnp.int32)


def _dispatch(rows, k, w, xf, flat_g, order, group_sizes):
    """Gather, grouped SwiGLU and gated scatter-add combine over the first
    ``rows`` entries of ``order`` (the held assignments sorted first, by
    expert): the partial output (T, D) f32."""
    t, d = xf.shape
    order = order[:rows]
    token_of = order // k
    # Rows past the held assignments are padding, which the grouped
    # product leaves undefined: they are masked with ``where`` on the way
    # in and before any use of the output (a zero gate would not do: NaN *
    # 0 is NaN, in the output and in the gates' gradient alike).
    valid = (jnp.arange(rows) < jnp.sum(group_sizes))[:, None]
    xs = jnp.where(valid, xf[token_of], 0.0)
    out = jnp.where(valid, expert_ffn(w, xs, group_sizes).astype(jnp.float32), 0.0)
    return jnp.zeros((t, d), jnp.float32).at[token_of].add(out * flat_g[order][:, None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _laddered(rungs, k, w, xf, flat_g, order, group_sizes, rung):
    """:func:`_dispatch` at ``rungs[rung]`` rows, one branch a rung. Its
    own VJP switches on the same rung and keeps only the inputs, the order
    and the rung: differentiated through ``lax.switch``, every branch's
    residuals would be kept, those of the branches not taken zero-filled
    at their full sizes."""
    branches = [functools.partial(_dispatch, r, k) for r in rungs]
    return jax.lax.switch(rung, branches, w, xf, flat_g, order, group_sizes)


def _laddered_fwd(rungs, k, w, xf, flat_g, order, group_sizes, rung):
    y = _laddered(rungs, k, w, xf, flat_g, order, group_sizes, rung)
    return y, (w, xf, flat_g, order, group_sizes, rung)


def _laddered_bwd(rungs, k, res, dy):
    w, xf, flat_g, order, group_sizes, rung = res

    def grads(rows, dy, w, xf, flat_g):
        _, vjp = jax.vjp(
            lambda w, xf, g: _dispatch(rows, k, w, xf, g, order, group_sizes), w, xf, flat_g
        )
        return vjp(dy)

    branches = [functools.partial(grads, r) for r in rungs]
    dw, dxf, dg = jax.lax.switch(rung, branches, dy, w, xf, flat_g)
    return dw, dxf, dg, None, None, None


_laddered.defvjp(_laddered_fwd, _laddered_bwd)


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
    assignments to these experts left uncomputed, "rows": rows the
    dispatch ran}``.

    The expert-parallel path calls it per model-axis shard with that
    shard's experts, so dispatch and combine stay device-local (the
    cross-shard reduction is one psum of the partial output — see
    apply_moe).
    """
    t = xf.shape[0]
    k = cfg.top_k
    gates, idx, aux = route(xf @ params["router"], k, n_real=cfg.num_experts)

    flat_e = idx.reshape(-1)  # (T*k,)
    flat_g = gates.reshape(-1)
    # Each assignment's group: its local expert, or e_local (sorted last,
    # never computed) for an expert outside this share.
    local = flat_e - e_offset
    held_assignment = (local >= 0) & (local < e_local)
    keys = jnp.where(held_assignment, local, e_local)
    order = jnp.argsort(keys, stable=True)
    group_sizes = jnp.bincount(keys, length=e_local + 1)[:e_local].astype(jnp.int32)
    w = {name: params[name] for name in ("wi_gate", "wi_up", "wo")}
    rungs = dispatch_rungs(t, k, e_local, cfg.num_experts)
    if len(rungs) == 1:
        y = _dispatch(rungs[0], k, w, xf, flat_g, order, group_sizes)
        rows = jnp.int32(rungs[0])
    else:
        rung = rung_index(rungs, jnp.sum(group_sizes))
        y = _laddered(rungs, k, w, xf, flat_g, order, group_sizes, rung)
        rows = jnp.asarray(rungs, jnp.int32)[rung]
    held = jnp.sum(held_assignment).astype(jnp.int32)
    stats = {"counts": group_sizes, "dropped": held - jnp.sum(group_sizes), "rows": rows}
    return y, aux, stats


def _rows(stats: dict, t: int, cfg: ArchConfig, e_local: int):
    """The rows a core's dispatch ran; the dropless bound for a core that
    does not say."""
    return stats.get("rows", jnp.int32(t * min(cfg.top_k, e_local)))


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
    held assignments not computed (0: the dispatch is dropless),
    ``stats["rows"]`` rows the dispatch ran, summed over the model ranks.
    The routed experts run under the ``moe`` named scope, the shared expert
    under ``shared_mlp``.

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
        stats = dict(stats, rows=_rows(stats, b * s, cfg, cfg.held_experts))
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
        counts = stats["counts"]
        dropped = jax.lax.psum(stats["dropped"], "model")
        rows = jax.lax.psum(_rows(stats, bl * sl, cfg, e_local), "model")
        if split:
            counts, dropped, rows = jax.lax.psum((counts, dropped, rows), ba)
        return y.astype(h.dtype).reshape(bl, sl, d), aux, counts, dropped, rows

    manual = frozenset(("model", *ba))
    shmapped = jax.shard_map(
        ep_body,
        mesh=mesh,
        in_specs=(wspec, bspec),
        out_specs=(bspec, P(), P("model"), P(), P()),
        axis_names=manual,
        check_vma=False,
    )
    y, aux, counts, dropped, rows = shmapped({k: params[k] for k in wspec}, hn)
    return y, aux, {"counts": counts, "dropped": dropped, "rows": rows}
