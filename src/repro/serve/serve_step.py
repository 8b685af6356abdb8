"""Serve steps: prefill (fill caches, return last-token logits) and decode
(one new token against a seq_len cache) — the shapes the decode dry-runs
lower.

Sliding-window policy: architectures with ``long_context == "sliding"`` use
their configured window for the long_500k decode (sub-quadratic per-token
cost AND bounded attention reads); SSM/hybrid archs run natively.
"""
from __future__ import annotations

from typing import Callable

import jax

from repro.configs.base import ArchConfig
from repro.models import layers, model as model_lib, transformer


def serve_window(cfg: ArchConfig, seq_len: int) -> int:
    """The attention window used when serving at this context length."""
    if cfg.long_context == "sliding" and cfg.sliding_window and seq_len > 65536:
        return cfg.sliding_window
    return 0


def make_decode_step(
    cfg: ArchConfig, seq_len: int, *, use_kernel: bool = False
) -> Callable:
    window = serve_window(cfg, seq_len)

    def decode_step(params, tokens, caches, pos):
        return model_lib.decode_step(
            params, tokens, caches, pos, cfg, window=window,
            use_kernel=use_kernel,
        )

    return decode_step


# ------------------------------------------------------------------ prefill
def make_prefill_step(cfg: ArchConfig, seq_len: int) -> Callable:
    """Forward over the prompt, returning (last-token logits, filled caches)."""
    window = serve_window(cfg, seq_len)

    def prefill(params, batch):
        x = (
            batch["embeds"].astype(cfg.activation_dtype)
            if cfg.modality == "vision_embeds"
            else layers.apply_embed(params["embed"], batch["tokens"], cfg)
        )
        positions = model_lib._positions(batch, cfg, x.shape[1])

        def period_body(h, period_params):
            cache_out = {}
            for j, sub in enumerate(cfg.period):
                h, _, _, cache_out[f"sub{j}"] = transformer.apply_sublayer(
                    h, period_params[f"sub{j}"], sub, cfg, positions, window,
                    False, want_cache=True,
                )
            return h, cache_out

        h, caches = jax.lax.scan(period_body, x, params["blocks"])
        h = layers.rmsnorm(h, params["final_norm"], cfg.norm_eps)
        logits = layers.apply_head(layers.head_params(params, cfg), h[:, -1:], cfg)
        return logits, caches

    return prefill
