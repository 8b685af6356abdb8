"""The monitored training step: ``FalconTrainer`` on the configuration's
model, wired as the train CLI wires it (``repro.launch.train.build_trainer``:
a simulated TP=2 x DP x PP=2 job whose fail-slows FALCON screens).

Set-up builds the trainer, hands it weights made from the seed, and drives
its first ``reference_steps`` steps through the same call and feed as the
window (``FalconTrainer.run`` for one step, each step on its own rows),
recording what the comparison needs: each step's loss, the first gradient
as AdamW received it (its first moment after one step over 1 - beta1) and
the parameters' change after the last of those steps. The window then
continues the same trainer step by step until ``--seconds`` have passed.
Once the window has closed and the trainer is freed, the plain reference
runs the same steps from the same weights on the same rows.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import time

import numpy as np

from chipbench import harness
from chipbench.harness import Check, Job, Outcome
from chipbench.reference import dense_decoder
from chipbench.tracing import WINDOW_SPAN
from chipbench.generators import train_data_seed, train_tokens
from chipbench.weights import change_norms, make_weights

#: the trainer's fields that the configuration's ``optimizer`` states
OPTIMIZER_FIELDS = ("lr", "beta1", "beta2", "eps", "weight_decay", "clip_norm",
                    "warmup_steps", "min_lr_frac")


def program_arch(cfg: dict):
    """The program's ArchConfig at the file's sizes: every size comes from
    the file, the layer pattern and code path from the program's entry."""
    from repro.configs.base import get_config

    heads = cfg["num_attention_heads"]
    return dataclasses.replace(
        get_config(cfg["program_arch"]),
        num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"],
        dtype=cfg["torch_dtype"],
    )


def _check_optimizer(trainer, opt: dict, total_steps: int) -> None:
    have = {f: getattr(trainer.opt_cfg, f) for f in OPTIMIZER_FIELDS}
    want = {f: opt[f] for f in OPTIMIZER_FIELDS}
    if have != want or trainer.opt_cfg.total_steps != total_steps:
        raise ValueError(f"the trainer's AdamW {have} (total_steps "
                         f"{trainer.opt_cfg.total_steps}) is not the "
                         f"configuration's {want} (total_steps {total_steps})")


def run(job: Job) -> Outcome:
    import jax

    from repro.data.pipeline import DataConfig
    from repro.launch.train import build_trainer, parse_injection

    cfg, traffic = job.config(), job.traffic("train_tokens")
    opt = dict(cfg["optimizer"], total_steps=traffic["schedule_steps"])
    arch = program_arch(cfg)
    slots, groups = traffic["slots"], traffic["dp_groups"]
    seqs = traffic["sequences_per_group"]
    seq_len = traffic["seq_len"]
    data0 = DataConfig(seq_len=seq_len, global_batch=slots * groups * seqs,
                       slots=slots, dp_groups=groups,
                       seed=train_data_seed(job.seed, 1))
    trainer = build_trainer(
        arch, data0, steps=traffic["schedule_steps"],
        falcon_enabled=traffic["falcon"],
        ckpt_dir=os.path.join(harness.WORK_DIR, "ckpt"),
    )
    _check_optimizer(trainer, opt, traffic["schedule_steps"])
    template = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            trainer.params)
    std = cfg["initializer_range"]
    trainer.params = make_weights(template, job.seed, std)
    n_ref = traffic["reference_steps"]
    fault = traffic.get("failslow")
    if fault:
        start = (n_ref + fault["at_window_step"]) * trainer.perf_model.healthy_iteration_time()
        trainer.injector.add(parse_injection(
            f"{fault['kind']}:{fault['target']}:{fault['severity']}:{start!r}:1e9"))

    def step(k: int):
        trainer.data = dataclasses.replace(data0, seed=train_data_seed(job.seed, k))
        return trainer.run(1)[-1]

    losses, grad_norms = [], None
    for k in range(1, n_ref + 1):
        rec = step(k)
        losses.append(rec.loss)
        if k == 1:
            mu = trainer.opt_state.mu
            grad_norms = dense_decoder.leaf_norms(mu) / (1.0 - opt["beta1"])
            del mu
    change = np.asarray(change_norms(trainer.params, template, job.seed, std), np.float64)

    tokens_per_step = slots * groups * seqs * seq_len
    mark = job.compiles.mark() if job.compiles else None
    records = []
    job.tracer.start()
    with job.tracer.span(WINDOW_SPAN):
        t0 = time.monotonic()
        setup_s = t0 - job.t_start
        k = n_ref
        while True:
            k += 1
            with job.tracer.span("chipbench.train_step"):
                records.append(step(k))
            if time.monotonic() - t0 >= job.seconds:
                break
        window_s = time.monotonic() - t0
    job.tracer.stop()
    in_window = job.compiles.since(mark) if mark else {}
    peak = harness.peak_bytes(jax.devices()[0])
    strategies = [(i, r.strategy) for i, r in enumerate(records, start=1) if r.strategy]
    finite = [math.isfinite(r.loss) for r in records]
    counters = {
        "steps": len(records),
        "window_s": window_s,
        "tokens_per_step": tokens_per_step,
        "measured_s": sum(r.measured for r in records),
        "seq_len": seq_len,
        "compiles_in_window": in_window.get("compiles", 0),
        "strategies": strategies,
    }
    harness.log(f"setup_s={setup_s!r} window_s={window_s!r} steps={len(records)} "
                f"measured_s={counters['measured_s']!r} strategies={strategies} "
                f"window_compiles={in_window} peak_bytes={peak}")
    trace_events = job.tracer.events()
    del trainer, records
    gc.collect()

    program = {"losses": losses, "grad_norms": grad_norms, "change_norms": change}
    batches = [train_tokens(train_data_seed(job.seed, k), slots, groups * seqs,
                            seq_len, cfg["vocab_size"]) for k in range(1, n_ref + 1)]
    t_ref = time.monotonic()
    ref = dense_decoder.train_readings(
        lambda: make_weights(template, job.seed, std), batches, cfg, opt)
    harness.log(f"reference_s={time.monotonic() - t_ref!r} "
                f"program_losses={losses} reference_losses={ref['losses']}")
    paths = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(template)[0]]
    for name in ("grad_norms", "change_norms"):
        harness.log(f"{name} by leaf (program, reference): " + ", ".join(
            f"{p}={a!r}/{b!r}" for p, a, b in zip(paths, program[name], ref[name])))
    limits = job.limits()
    checks = [Check(name, value, float(limits[name]))
              for name, value in readings_gaps(program, ref).items()]
    if fault:
        checks.append(Check("strategies_dispatched", float(len(strategies)),
                            float(limits["strategies_dispatched"]), at_least=True))
    return Outcome(
        setup_s=setup_s, window_s=window_s, attempted=len(finite),
        failed=finite.count(False),
        end_to_end={"train_tokens_per_s": len(finite) * tokens_per_step / window_s},
        counters=counters, memory_peak_bytes=peak, checks=checks,
        trace_events=trace_events,
    )


def readings_gaps(program: dict, ref: dict) -> dict:
    """The compared numbers: the worst step's relative loss gap, the worst
    leaf's gap of first-gradient norms, and the worst leaf's gap of change
    norms over the leaves whose reference gradient is not nought to
    rounding (at least a thousandth of the median leaf's)."""
    lp = np.asarray(program["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    raw = np.asarray(ref["raw_grad_norms"], np.float64)
    moved = raw >= 1e-3 * np.median(raw)
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_norm_gap": harness.norm_gap(program["grad_norms"], ref["grad_norms"]),
        "change_norm_gap": harness.norm_gap(program["change_norms"],
                                            ref["change_norms"], moved),
    }

