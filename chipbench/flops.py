"""Operations and bytes that the work requires, computed from shapes.

These are the benchmark's own counts: the program's arithmetic does not
enter them, so a change to the program cannot change the yardstick.
"""
from __future__ import annotations


def _dims(cfg: dict) -> tuple[int, int, int, int, int, int, int]:
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    kv = int(cfg["num_key_value_heads"])
    hd = int(cfg.get("head_dim") or d // heads)
    return (int(cfg["num_hidden_layers"]), d, heads, kv, hd,
            int(cfg["intermediate_size"]), int(cfg["vocab_size"]))


def dense_matmul_params(cfg: dict) -> int:
    """Parameters that enter a matrix multiplication per token of a dense
    decoder (Q, K, V, O, SwiGLU gate/up/down per layer, and the output
    head over the real vocabulary). The input embedding is a lookup, not
    a matmul, and norms are elementwise: both are left out."""
    layers, d, heads, kv, hd, ff, vocab = _dims(cfg)
    attn = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    mlp = 3 * d * ff
    return layers * (attn + mlp) + d * vocab


def attention_fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward FLOPs of the score (Q K^T) and value (P V) products per
    token of a causal sequence: each is 2 * heads * head_dim per key, over
    (seq_len + 1) / 2 keys on average (the causal half, diagonal included)."""
    layers, _, heads, _, hd, _, _ = _dims(cfg)
    return layers * 2 * (2 * heads * hd) * (seq_len + 1) / 2


def dense_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward FLOPs per trained token: 6 per matmul
    parameter, and three times attention's forward products. No
    recomputed operation counts."""
    return (6.0 * dense_matmul_params(cfg)
            + 3.0 * attention_fwd_flops_per_token(cfg, seq_len))


def bocd_step_bytes(slots: int, streams: int, itemsize: int = 4) -> int:
    """HBM bytes one BOCD step over a ``slots x streams`` state needs: the
    three per-stream state arrays (log run-length posterior, mean, scale)
    read and written once, plus one sample in and Pr(r=0) out per stream.
    Counted the same whatever implements the step."""
    return 6 * slots * streams * itemsize + 2 * streams * itemsize
