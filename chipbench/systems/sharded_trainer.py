"""The monitored training step of a hybrid Mamba-2 / attention / MoE model
on a four-chip host: ``FalconTrainer`` over a ``(data, model)`` mesh, wired
as the train CLI wires it (``repro.launch.train.build_trainer``).

The run follows ``systems/trainer.py`` step for step: set-up builds the
trainer on the mesh, hands it weights made from the seed in the shardings
the trainer placed its own in, and drives its first ``reference_steps``
steps as the window does, recording each step's loss, the first gradient
as AdamW received it and the parameters' change after the last of those
steps. The window then continues the same trainer until ``--seconds``
have passed. Once the window has closed and the trainer is freed, the
plain reference (``reference/granite_hybrid.py``) runs the same steps from
the same weights on the same rows, its float32 state spread over the
host's chips.

The comparison takes the dense cells' gradient and change gaps, with a
held expert's slice of the expert weights as a unit of its own, and
checks that the expert layer dropped no assignment
(``moe_dropped_tokens``, the trainer's counter over every step); the
routing counters' window deltas and the mesh's size go among the
counters.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import time

import numpy as np

from chipbench import harness
from chipbench.generators import train_data_seed, train_tokens
from chipbench.harness import Check, Job, Outcome
from chipbench.reference import granite_hybrid
from chipbench.systems.trainer import _check_optimizer, readings_gaps
from chipbench.tracing import WINDOW_SPAN
from chipbench.weights import _as_stored, weight_builder

#: the routing counters ``FalconTrainer`` keeps for a model with MoE layers
ROUTING_COUNTERS = ("moe_routed_tokens", "moe_expert_load_max", "moe_dropped_tokens")


def program_arch(cfg: dict):
    """The program's ArchConfig at the file's sizes: every size comes from
    the file, the code path from the program's entry. Settings the program
    does not implement are refused, not ignored."""
    from repro.configs.base import SubLayer, get_config

    fixed = {"hidden_act": "silu", "attention_bias": False, "mamba_proj_bias": False,
             "normalization_function": "rmsnorm", "position_embedding_type": "nope"}
    off = {k: cfg[k] for k, v in fixed.items() if cfg[k] != v}
    if off:
        raise ValueError(f"the program implements {fixed}, not {off}")
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    if cfg["mamba_n_heads"] * cfg["mamba_d_head"] != cfg["mamba_expand"] * d:
        raise ValueError("mamba_n_heads * mamba_d_head must be mamba_expand * hidden_size")
    subs = {"mamba": SubLayer("mamba", "moe"), "attention": SubLayer("attn", "moe")}
    return dataclasses.replace(
        get_config(cfg["program_arch"]),
        num_layers=cfg["num_hidden_layers"],
        d_model=d,
        num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=d // heads,
        vocab_size=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"],
        dtype=cfg["torch_dtype"],
        period=tuple(subs[k] for k in granite_hybrid.period_types(cfg)),
        num_experts=cfg["router_experts"],
        experts_held=cfg["num_local_experts"],
        expert_offset=cfg["expert_offset"],
        top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["intermediate_size"],
        num_shared_experts=1,
        shared_d_ff=cfg["shared_intermediate_size"],
        ssm_state=cfg["mamba_d_state"],
        ssm_head_dim=cfg["mamba_d_head"],
        ssm_expand=cfg["mamba_expand"],
        ssm_groups=cfg["mamba_n_groups"],
        ssm_conv_width=cfg["mamba_d_conv"],
        ssm_chunk=cfg["mamba_chunk_size"],
        ssm_conv_bias=cfg["mamba_conv_bias"],
        pos_encoding="none",
        attention_multiplier=cfg["attention_multiplier"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=float(cfg["logits_scaling"]),
        tie_word_embeddings=cfg["tie_word_embeddings"],
    )


def _check_aux_coef(cfg: dict) -> None:
    from repro.models.model import AUX_LOSS_COEF

    if AUX_LOSS_COEF != cfg["program_aux_loss_coef"]:
        raise ValueError(f"the program's load-balance coefficient {AUX_LOSS_COEF} is "
                         f"not the configuration's {cfg['program_aux_loss_coef']}")


def sharded_weights(template, shardings, seed: int, init_std: float):
    """The weights of ``seed`` (``chipbench.weights``' values), made on the
    devices directly in ``shardings``."""
    import jax
    import jax.numpy as jnp

    lo, hi = harness.seed_words(seed)
    return jax.jit(weight_builder(template, init_std),
                   out_shardings=shardings)(jnp.uint32(lo), jnp.uint32(hi))


def sharded_change_norms(params, template, shardings, seed: int, init_std: float):
    """Norms by unit (``granite_hybrid.unit_sq``) of ``params`` minus the
    seed's starting weights, the start remade inside the call in
    ``shardings`` (no device holds a whole copy)."""
    import jax
    import jax.numpy as jnp

    build = weight_builder(template, init_std)
    lo, hi = harness.seed_words(seed)

    def f(p, lo, hi):
        start = jax.lax.with_sharding_constraint(build(lo, hi), shardings)
        return jnp.sqrt(granite_hybrid.unit_sq(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - _as_stored(b), p, start)))

    return jax.jit(f)(params, jnp.uint32(lo), jnp.uint32(hi))


@dataclasses.dataclass
class Setup:
    """A trainer built for a job, holding the seed's weights, and what its
    first steps and the reference need."""
    trainer: object
    data0: object
    template: object
    opt: dict
    devices: list


def build(job: Job) -> Setup:
    """The cell's trainer on its mesh, wired by ``build_trainer``, its
    parameters replaced by the seed's weights in the trainer's shardings."""
    import jax

    from repro.data.pipeline import DataConfig
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import build_trainer

    cfg, traffic = job.config(), job.traffic("train_tokens")
    opt = dict(cfg["optimizer"], total_steps=traffic["schedule_steps"])
    arch = program_arch(cfg)
    _check_aux_coef(cfg)
    mesh = make_host_mesh(cfg["mesh"]["data"], cfg["mesh"]["model"])
    slots, groups = traffic["slots"], traffic["dp_groups"]
    data0 = DataConfig(seq_len=traffic["seq_len"],
                       global_batch=slots * groups * traffic["sequences_per_group"],
                       slots=slots, dp_groups=groups, seed=train_data_seed(job.seed, 1))
    trainer = build_trainer(
        arch, data0, steps=traffic["schedule_steps"],
        falcon_enabled=traffic["falcon"],
        ckpt_dir=os.path.join(harness.WORK_DIR, "ckpt"), mesh=mesh,
    )
    _check_optimizer(trainer, opt, traffic["schedule_steps"])
    template = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            trainer.params)
    trainer.params = sharded_weights(template, trainer.param_shardings, job.seed,
                                     cfg["initializer_range"])
    return Setup(trainer, data0, template, opt, list(mesh.devices.flat))


def train_step(setup: Setup, job: Job, k: int):
    """Step ``k`` of the run (1-based): the trainer's ``run(1)`` on the
    rows of data seed ``k``."""
    setup.trainer.data = dataclasses.replace(setup.data0, seed=train_data_seed(job.seed, k))
    return setup.trainer.run(1)[-1]


def first_steps(setup: Setup, job: Job) -> dict:
    """The program's readings over the first ``reference_steps`` steps:
    each step's loss, the first gradient as AdamW received it, and the
    parameters' change after the last, by unit."""
    n_ref = job.traffic("train_tokens")["reference_steps"]
    trainer, losses, grad_norms = setup.trainer, [], None
    for k in range(1, n_ref + 1):
        losses.append(train_step(setup, job, k).loss)
        if k == 1:
            grad_norms = (granite_hybrid.unit_norms(trainer.opt_state.mu)
                          / (1.0 - setup.opt["beta1"]))
    change = np.asarray(sharded_change_norms(
        trainer.params, setup.template, trainer.param_shardings, job.seed,
        job.config()["initializer_range"]), np.float64)
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}


def reference_readings(job: Job, template, devices, operand_dtype=None) -> dict:
    """The plain reference's readings over the same first steps, from the
    same weights on the same rows, its state spread over ``devices``."""
    cfg, traffic = job.config(), job.traffic("train_tokens")
    opt = dict(cfg["optimizer"], total_steps=traffic["schedule_steps"])
    seqs = traffic["dp_groups"] * traffic["sequences_per_group"]
    batches = [train_tokens(train_data_seed(job.seed, k), traffic["slots"], seqs,
                            traffic["seq_len"], cfg["vocab_size"])
               for k in range(1, traffic["reference_steps"] + 1)]
    return granite_hybrid.train_readings(
        lambda sh: sharded_weights(template, sh, job.seed, cfg["initializer_range"]),
        template, batches, cfg, opt, operand_dtype=operand_dtype, devices=devices)


def run(job: Job) -> Outcome:
    from repro.launch.train import parse_injection

    cfg, traffic = job.config(), job.traffic("train_tokens")
    setup = build(job)
    trainer, template, devices = setup.trainer, setup.template, setup.devices
    slots, groups = traffic["slots"], traffic["dp_groups"]
    seqs = traffic["sequences_per_group"]
    seq_len = traffic["seq_len"]
    n_ref = traffic["reference_steps"]
    fault = traffic.get("failslow")
    if fault:
        start = (n_ref + fault["at_window_step"]) * trainer.perf_model.healthy_iteration_time()
        trainer.injector.add(parse_injection(
            f"{fault['kind']}:{fault['target']}:{fault['severity']}:{start!r}:1e9"))

    def routing() -> dict:
        return {name: getattr(trainer, name) for name in ROUTING_COUNTERS}

    program = first_steps(setup, job)

    tokens_per_step = slots * groups * seqs * seq_len
    mark = job.compiles.mark() if job.compiles else None
    before = routing()
    records = []
    job.tracer.start()
    with job.tracer.span(WINDOW_SPAN):
        t0 = time.monotonic()
        setup_s = t0 - job.t_start
        k = n_ref
        while True:
            k += 1
            with job.tracer.span("chipbench.train_step"):
                records.append(train_step(setup, job, k))
            if time.monotonic() - t0 >= job.seconds:
                break
        window_s = time.monotonic() - t0
    job.tracer.stop()
    after = routing()
    in_window = job.compiles.since(mark) if mark else {}
    peak = max(harness.peak_bytes(d) for d in devices)
    strategies = [(i, r.strategy) for i, r in enumerate(records, start=1) if r.strategy]
    finite = [math.isfinite(r.loss) for r in records]
    counters = {
        "steps": len(records),
        "window_s": window_s,
        "tokens_per_step": tokens_per_step,
        "measured_s": sum(r.measured for r in records),
        "seq_len": seq_len,
        "chips": len(devices),
        "compiles_in_window": in_window.get("compiles", 0),
        "strategies": strategies,
        # routing in the window; a (layer, expert) pair's mean load is
        # moe_routed_tokens / moe_layer_experts
        **{name: after[name] - before[name] for name in ROUTING_COUNTERS},
        "moe_layer_experts": cfg["num_hidden_layers"] * cfg["num_local_experts"],
    }
    dropped = after["moe_dropped_tokens"]
    harness.log(f"setup_s={setup_s!r} window_s={window_s!r} steps={len(records)} "
                f"measured_s={counters['measured_s']!r} strategies={strategies} "
                f"window_compiles={in_window} peak_bytes={peak} routing={after}")
    trace_events = job.tracer.events()
    del trainer, setup, records
    gc.collect()

    t_ref = time.monotonic()
    ref = reference_readings(job, template, devices)
    harness.log(f"reference_s={time.monotonic() - t_ref!r} "
                f"program_losses={program['losses']} reference_losses={ref['losses']}")
    paths = granite_hybrid.unit_names(template)
    for name in ("grad_norms", "change_norms"):
        harness.log(f"{name} by unit (program, reference): " + ", ".join(
            f"{p}={a!r}/{b!r}" for p, a, b in zip(paths, program[name], ref[name])))
    limits = job.limits()
    checks = [Check(name, value, float(limits[name]))
              for name, value in readings_gaps(program, ref).items() if name in limits]
    checks.append(Check("moe_dropped_tokens", float(dropped),
                        float(limits["moe_dropped_tokens"])))
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
