"""Operations a Granite-4.0-H (Mamba-2 + GQA + MoE) training step requires,
computed from the configuration's shapes alone.

Like ``chipbench.flops``, these are the benchmark's own counts: the
program's arithmetic does not enter them. The configuration is the
cell's file (Hugging Face keys), at the host's share: its
``num_local_experts`` experts of the router's ``router_experts`` live on
the host, so a token meets ``num_experts_per_tok * held / router_experts``
of them on average.
"""
from __future__ import annotations


def _layer_kinds(cfg: dict) -> list[str]:
    return list(cfg["layer_types"][: int(cfg["num_hidden_layers"])])


def expected_held_experts_per_token(cfg: dict) -> float:
    """Held experts a token is routed to on average: its top-k spread over
    all the router's experts, of which the host holds its share."""
    return (int(cfg["num_experts_per_tok"]) * int(cfg["num_local_experts"])
            / int(cfg["router_experts"]))


def hybrid_matmul_params(cfg: dict) -> float:
    """Parameters that enter a matrix multiplication per token: each Mamba-2
    mixer's in-projection (z, x, B and C, dt) and out-projection, each
    attention layer's Q, K, V and O, each layer's router, shared SwiGLU
    expert and the expected held experts' SwiGLU, and the tied output head
    over the real vocabulary. The embedding lookup, norms, convolutions
    and the scan's elementwise decay are left out."""
    d = int(cfg["hidden_size"])
    heads, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd = d // heads
    inner = int(cfg["mamba_expand"]) * d
    gn = int(cfg["mamba_n_groups"]) * int(cfg["mamba_d_state"])
    mamba = d * (2 * inner + 2 * gn + int(cfg["mamba_n_heads"])) + inner * d
    attn = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    moe = (d * int(cfg["router_experts"])
           + 3 * d * int(cfg["shared_intermediate_size"])
           + 3 * d * int(cfg["intermediate_size"]) * expected_held_experts_per_token(cfg))
    kinds = _layer_kinds(cfg)
    n_attn = kinds.count("attention")
    return ((len(kinds) - n_attn) * mamba + n_attn * attn + len(kinds) * moe
            + d * int(cfg["vocab_size"]))


def attention_fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward FLOPs of the score and value products per token over the
    causal half of a sequence (diagonal included), summed over the
    attention layers."""
    n_attn = _layer_kinds(cfg).count("attention")
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return n_attn * 2 * (2 * heads * (d // heads)) * (seq_len + 1) / 2


def ssd_fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward FLOPs of the Mamba-2 scan's products per token in its chunked
    (SSD) form at ``mamba_chunk_size``, summed over the Mamba layers:
    intra-chunk scores ``C_q . B_k`` and outputs over the causal half of a
    chunk, the chunk states ``B_k x_k``, and the inter-chunk outputs
    ``C_q . state``."""
    n_mamba = _layer_kinds(cfg).count("mamba")
    chunk = min(int(cfg["mamba_chunk_size"]), seq_len)
    keys = (chunk + 1) / 2
    h, p = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    n, g = int(cfg["mamba_d_state"]), int(cfg["mamba_n_groups"])
    scores = keys * 2 * n * g
    outputs = keys * 2 * p * h
    states = 2 * n * p * h
    inter = 2 * n * p * h
    return n_mamba * (scores + outputs + states + inter)


def hybrid_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward FLOPs per trained token: 6 per matmul
    parameter, and three times the attention and scan products' forward.
    No recomputed operation counts."""
    return (6.0 * hybrid_matmul_params(cfg)
            + 3.0 * (attention_fwd_flops_per_token(cfg, seq_len)
                     + ssd_fwd_flops_per_token(cfg, seq_len)))
