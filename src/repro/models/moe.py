"""Mixture-of-Experts with sort-based token dispatch (dropping, capacity C).

FLOP-exact formulation: tokens are sorted by routed expert, packed into an
(E, C, D) capacity buffer, processed by per-expert SwiGLU FFNs, and combined
back with router gates — so HLO FLOPs reflect *active* experts only (the
dense all-experts einsum would inflate the roofline by E/k).

Sharding modes (resolved against the model axis):
  * ``experts``: expert-parallel — the E dim of expert weights and of the
    capacity buffer is sharded; dispatch/combine induce all-to-all traffic.
  * ``ff``: tensor-parallel experts — the per-expert FF dim is sharded
    (used when E does not divide the axis, e.g. 60 experts on 16 devices).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers
from repro.models.schema import ParamDef, Schema


def moe_schema(cfg: ArchConfig) -> Schema:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.padded_experts
    if cfg.moe_shard == "experts":
        ax: tuple = ("model", None, None)
    else:  # "ff": shard the per-expert hidden dim
        ax = (None, None, "model")
    out: Schema = {
        "norm": layers.rmsnorm_schema(d),
        "router": ParamDef((d, e), (None, None)),
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


def _moe_core(
    params: dict,
    xf: jax.Array,
    cfg: ArchConfig,
    e_offset,
    e_local: int,
) -> tuple[jax.Array, jax.Array]:
    """Route + sort-dispatch + per-expert SwiGLU for experts
    [e_offset, e_offset + e_local). Returns the *partial* combined output
    (T, D) f32 (contributions of those experts only) and the aux loss.

    With (e_offset=0, e_local=E) this is the full dense-host computation;
    the expert-parallel path calls it per model-axis shard so dispatch and
    combine stay device-local (the cross-shard reduction is one psum of the
    activation-sized partial output — see apply_moe).
    """
    t, d = xf.shape
    k, e = cfg.top_k, cfg.padded_experts
    # Capacity is sized for the REAL expert count: tokens only ever route to
    # real experts, so padded columns get none.
    cap = int(t * k / cfg.num_experts * cfg.capacity_factor) + 1

    gates, idx, aux = route(xf @ params["router"], k, n_real=cfg.num_experts)

    # ---- sort-based dispatch into the (e_local, C, D) capacity buffer ---
    flat_e = idx.reshape(-1)  # (T*k,)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    token_of = order // k
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.cumsum(counts) - counts
    slot = jnp.arange(t * k) - starts[sorted_e]
    local_e = sorted_e - e_offset
    keep = (slot < cap) & (local_e >= 0) & (local_e < e_local)
    slot_c = jnp.where(keep, slot, 0)
    local_c = jnp.where(keep, local_e, 0)

    buf = jnp.zeros((e_local, cap, d), xf.dtype)
    contrib = jnp.where(keep[:, None], xf[token_of], 0.0)
    buf = buf.at[local_c, slot_c].add(contrib)

    # ---- per-expert SwiGLU ---------------------------------------------
    gate_h = jnp.einsum("ecd,edf->ecf", buf, params["wi_gate"])
    up_h = jnp.einsum("ecd,edf->ecf", buf, params["wi_up"])
    act = jax.nn.silu(gate_h) * up_h
    out_buf = jnp.einsum("ecf,efd->ecd", act, params["wo"])

    # ---- combine ---------------------------------------------------------
    y_sorted = out_buf[local_c, slot_c] * jnp.where(keep, flat_g[order], 0.0)[:, None]
    y = jnp.zeros((t, d), jnp.float32).at[token_of].add(y_sorted.astype(jnp.float32))
    return y, aux


def _ep_axes(cfg: ArchConfig):
    """(batch_axes, model_axis_size) when the expert-parallel shard_map path
    applies under the ambient mesh, else None.

    Expert parallelism needs E % model == 0; the GSPMD fallback handles the
    rest. On meshless hosts (CPU smoke tests) the mesh is empty -> None.
    """
    mesh = jax.sharding.get_abstract_mesh()
    names = mesh.axis_names
    if "model" not in names:
        return None
    tp = mesh.shape["model"]
    if tp <= 1 or cfg.moe_shard != "experts" or cfg.padded_experts % tp:
        return None
    ba = tuple(a for a in ("pod", "data") if a in names)
    return mesh, ba, tp


def apply_moe(params: dict, x: jax.Array, cfg: ArchConfig) -> tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,D), aux_loss scalar).

    Under a mesh with a model axis dividing E (and ``moe_shard="experts"``),
    dispatch/combine run *shard-locally* inside a shard_map: each model rank
    builds the capacity buffer for its own experts from its own tokens, and
    the only cross-shard communication is one activation-sized psum of the
    partial outputs over the model axis — the same collective the dense TP
    MLP already pays — instead of GSPMD's replicated-scatter all-reduces
    (EXPERIMENTS §Perf, jamba/olmoe iterations). Otherwise falls back to the
    plain GSPMD formulation.
    """
    b, s, d = x.shape
    hn = layers.rmsnorm(x, params["norm"], cfg.norm_eps)

    ep = _ep_axes(cfg)
    if ep is None:
        xf = hn.reshape(b * s, d)
        y, aux = _moe_core(params, xf, cfg, 0, cfg.padded_experts)
        y = y.astype(x.dtype)
        if cfg.num_shared_experts:
            shg = jax.nn.silu(xf @ params["shared_wi_gate"]) * (
                xf @ params["shared_wi_up"]
            )
            y = y + (shg @ params["shared_wo"]).astype(x.dtype)
        return y.reshape(b, s, d), aux

    mesh, ba, tp = ep
    e_local = cfg.padded_experts // tp
    from jax.sharding import PartitionSpec as P

    dsize = 1
    for a in ba:
        dsize *= mesh.shape[a]
    if b % dsize:
        # Batch doesn't divide the DP axes (long_500k decode has B=1): go
        # manual over the model axis only; tokens are replicated across DP.
        ba = ()
    bspec = P(ba if ba else None, None, None)
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
        y, aux = _moe_core(p, xf, cfg, r * e_local, e_local)
        if cfg.num_shared_experts:
            # Shared experts are column/row tensor-parallel over the same
            # axis; their row-parallel partial rides the same psum.
            shg = jax.nn.silu(xf @ p["shared_wi_gate"]) * (xf @ p["shared_wi_up"])
            y = y + (shg @ p["shared_wo"]).astype(jnp.float32)
        y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, ("model", *ba))  # identical across manual ranks
        return y.astype(h.dtype).reshape(bl, sl, d), aux

    manual = frozenset(("model", *ba))
    shmapped = jax.shard_map(
        ep_body,
        mesh=mesh,
        in_specs=(wspec, bspec),
        out_specs=(bspec, P()),
        axis_names=manual,
        check_vma=False,
    )
    y, aux = shmapped(
        {k: params[k] for k in wspec}, hn
    )
    return y, aux
