"""Batched serving driver with FALCON latency monitoring.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b --smoke \
        --requests 8 --prompt-len 32 --gen 16 [--inject gpu:1:0.5:5:200]

Serves a batch of requests through the real prefill + decode path (smoke
configs on CPU; the full configs are exercised via the dry-run). FALCON's
detector watches the per-token decode latency exactly as it watches training
iteration time — serving is iterative too, so the same ACF/BOCD stack
applies; mitigation for serving is placement adjustment (S3) or re-schedule
(S4), surfaced here as detection reports.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.cluster.injector import FailSlowInjector
from repro.cluster.simulator import JobSpec, TrainingSimulator
from repro.cluster.spec import ClusterSpec, ModelSpec
from repro.configs.base import get_config
from repro.core.detector import FalconDetect
from repro.launch import init_compile_cache
from repro.launch.train import parse_injection
from repro.models import model as model_lib, transformer
from repro.serve.serve_step import make_decode_step, make_prefill_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--inject", action="append", default=[])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    init_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    b, s0, total = args.requests, args.prompt_len, args.prompt_len + args.gen
    print(f"serving {b} requests x ({s0} prompt + {args.gen} new) on {cfg.name}")

    params = model_lib.init_params(cfg, args.seed)
    rng = np.random.default_rng(args.seed)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s0)), jnp.int32)
    if cfg.modality == "audio_codes":
        prompt = prompt[..., None].repeat(cfg.num_codebooks, -1)

    # Performance model for latency signal + optional fail-slow injection.
    sim = TrainingSimulator(
        cluster=ClusterSpec(n_nodes=1, gpus_per_node=8),
        job=JobSpec(
            model=ModelSpec(layers=cfg.num_layers, hidden=max(cfg.d_model, 1024),
                            seq_len=total, vocab=cfg.vocab_size),
            tp=2, dp=4, pp=1, micro_batches=8,
        ),
    )
    injector = FailSlowInjector([parse_injection(t) for t in args.inject])
    detector = FalconDetect(cluster=sim, verify_window=6)

    prefill = jax.jit(make_prefill_step(cfg, s0))
    decode = jax.jit(make_decode_step(cfg, total, use_kernel=args.use_kernel))

    t0 = time.monotonic()
    logits, caches = prefill(params, {"tokens": prompt})
    jax.block_until_ready(logits)
    t_prefill = time.monotonic() - t0
    caches = transformer.grow_caches(caches, cfg, total)

    tok = jnp.argmax(logits[:, -1], axis=-1).reshape(b, 1).astype(jnp.int32)
    if cfg.modality == "audio_codes":
        tok = tok[..., 0:1] if tok.ndim == 3 else tok[..., None].repeat(cfg.num_codebooks, -1)
    pos = jnp.asarray(s0, jnp.int32)
    out_tokens = []
    wall = 0.0
    for step in range(args.gen):
        t1 = time.monotonic()
        logits, caches = decode(params, tok, caches, pos)
        jax.block_until_ready(logits)
        measured = time.monotonic() - t1
        injector.apply(sim.state, wall)
        latency = sim.iteration_time() if injector.injections else measured
        wall += latency
        ev = detector.observe(latency, wall)
        if ev is not None:
            print(f"  token {step}: FALCON flags {ev.root_cause.value} "
                  f"on {ev.components} ({ev.t_healthy:.3f}s -> {ev.t_slow:.3f}s)")
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        if cfg.modality == "audio_codes":
            tok = nxt.reshape(b, 1, cfg.num_codebooks).astype(jnp.int32)
            out_tokens.append(np.asarray(nxt)[..., 0])
        else:
            tok = nxt.reshape(b, 1).astype(jnp.int32)
            out_tokens.append(np.asarray(nxt))
        pos = pos + 1

    gen = np.stack(out_tokens, axis=1)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    print(f"prefill: {t_prefill:.2f}s   decode: {args.gen} tokens/seq, "
          f"{b * args.gen / max(wall, 1e-9):.1f} tok/s (modeled)"
          if injector.injections else
          f"prefill: {t_prefill:.2f}s   decode throughput "
          f"{b * args.gen / max(wall, 1e-9):.1f} tok/s")
    print(f"sample continuation: {gen[0].tolist()}")


if __name__ == "__main__":
    main()
