"""Drive FALCON's main path once on one TPU chip and check what comes out.

    python3 chip_smoke.py [--out DIR]          # one chip: the three phases
    python3 chip_smoke.py --four-chips         # four chips: the S2 step only

One process runs every phase; it starts no child process.

1. **Trainer.** ``FalconTrainer`` with FALCON on, wired to its simulated
   cluster by :func:`repro.launch.train.build_trainer`, trains granite-3-8b
   at its published widths with the depth cut to 2 layers, while an
   ``--inject``-style GPU fail-slow makes the control plane dispatch a
   strategy. Losses must be finite and a strategy must be dispatched.
2. **Fleet screen.** The ``mixed_fleet`` campaign runs twice: on its
   default path (the shared-prefix engine with auto-selected backends:
   Pallas on a TPU) and fresh with ``batched`` / ``vectorized``. Both must
   raise the same flags and diagnoses.
3. **Kernel check.** ``bocd_step`` and ``cell_reduce``, compiled at the
   shapes those phases used, must contain ``tpu_custom_call``: they
   compiled to Mosaic and did not run in interpret mode.

``--four-chips`` runs instead only the S2 adaptive train step on a
(data=2, model=2) mesh, compared against the even ``make_train_step`` on
the same mesh at counts ``[s, s]`` and ``[s, s // 2]``.

The script refuses to run where JAX's first device is not a TPU. Its last
line of standard output is one JSON object, ``{"ok": true, "device":
{...}}``, printed only when every phase passed; any failure exits non-zero.
Reports go under ``--out`` (default ``chiprun_out/chip_smoke``). Where
``JAX_COMPILATION_CACHE_DIR`` is unset, compiled programs are cached in
``.jax_cache`` of this checkout.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import ArchConfig, get_config  # noqa: E402
from repro.core import bocd  # noqa: E402
from repro.data.pipeline import DataConfig, make_batch  # noqa: E402
from repro.launch import init_compile_cache  # noqa: E402
from repro.launch.train import build_trainer, parse_injection  # noqa: E402

#: the published widths; only the depth is cut to fit one chip
ARCH = "granite-3-8b"
LAYERS = 2
#: one 2,048-token sequence per DP group and micro-batch, two DP groups:
#: 4,096 tokens per micro-batch. A 4,096-token sequence needs 10.16 GB of
#: step temporaries (the f32 attention-score residuals of the KV-block
#: scan) next to 7.47 GB of params + AdamW state: 17.6 GB of 15.75 GB.
CHIP_DATA = DataConfig(seq_len=2048, global_batch=4, slots=2, dp_groups=2)
TRAIN_STEPS = 30
INJECT_STEP = 10  # the GPU fail-slow starts at this step's simulated time
FLEET_PRESET, FLEET_JOBS, FLEET_TICKS = "mixed_fleet", 8, 600


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


#: the jitted kernel wrappers whose compiled shapes the kernel check replays
KERNELS = ("bocd_step", "cell_reduce")
#: jitted function -> (the kernel it launches, the argument whose shape is
#: recorded); ``_advance`` is ``PallasBOCD``'s per-tick launch of
#: ``bocd_step``'s kernel
LAUNCHES = {"jit(bocd_step)": ("bocd_step", 1), "jit(_advance)": ("bocd_step", 2),
            "jit(cell_reduce)": ("cell_reduce", 1)}


class CompileLog(logging.Handler):
    """Counts backend compiles, their seconds, and persistent-cache hits
    through ``jax.monitoring`` (listeners stay registered; read deltas),
    and records the shape of every compile that launches ``bocd_step`` /
    ``cell_reduce`` (:data:`LAUNCHES`: the ``(K, B)`` state or the
    ``(pp, dp, tp)`` edges) from the per-compile record jax's ``pxla``
    logger writes at DEBUG."""

    def __init__(self) -> None:
        super().__init__(logging.DEBUG)
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.kernel_shapes: dict[str, set[tuple]] = {k: set() for k in KERNELS}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        pxla = logging.getLogger("jax._src.interpreters.pxla")
        pxla.setLevel(logging.DEBUG)
        pxla.propagate = False
        pxla.addHandler(self)

    def emit(self, record: logging.LogRecord) -> None:
        if not record.msg.startswith("Compiling %s with global shapes"):
            return
        name, avals = record.args[0], record.args[1]
        if name in LAUNCHES:
            kernel, arg = LAUNCHES[name]
            self.kernel_shapes[kernel].add(tuple(avals[arg].shape))

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> tuple[float, int, int]:
        return self.compile_s, self.compiles, self.cache_hits

    def since(self, mark: tuple[float, int, int]) -> str:
        s, n, h = mark
        return (
            f"compile_s={self.compile_s - s:.3f} compiles={self.compiles - n} "
            f"persistent_cache_hits={self.cache_hits - h}"
        )


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def chip_config() -> ArchConfig:
    return dataclasses.replace(get_config(ARCH), num_layers=LAYERS)


# --------------------------------------------------------------- phase 1
def trainer_phase(
    cfg: ArchConfig, data: DataConfig, *, steps: int, inject_step: int,
    out_dir: str, compiles: CompileLog,
) -> dict:
    """FalconTrainer with FALCON on, wired as the train CLI wires it (a
    full hybrid TP=2 x DP=data.dp_groups x PP=2 simulated job, so the
    reduction kernel applies)."""
    trainer = build_trainer(
        cfg, data, steps=steps, ckpt_dir=os.path.join(out_dir, "ckpt"),
    )
    sim = trainer.perf_model
    start = inject_step * sim.healthy_iteration_time()
    trainer.injector.add(parse_injection(f"gpu:1:0.5:{start!r}:1e9"))
    shape = jax.tree.map(np.shape, make_batch(cfg, data, 0))
    log("trainer", f"arch={cfg.name} layers={cfg.num_layers} "
        f"d_model={cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"params={cfg.total_params() / 1e9:.3f}B")
    log("trainer", f"batch={shape} (slots, sequences, tokens); "
        f"tokens/step={data.global_batch * data.seq_len}")
    job = sim.job
    log("trainer", f"simulated job tp={job.tp} dp={job.dp} pp={job.pp}; "
        f"screening={trainer.detector.backend} (FalconDetect per-job path) "
        f"reduction={sim.reduction_name}")
    mark = compiles.mark()
    hist = trainer.run(steps)
    for r in hist:
        log("trainer", f"step={r.step} loss={r.loss!r} measured_s={r.measured!r} "
            f"sim_iter_s={r.iter_time!r} strategy={r.strategy or '-'}")
    log("trainer", compiles.since(mark))
    losses = [r.loss for r in hist]
    strategies = [r.strategy for r in hist if r.strategy]
    peak = peak_bytes(jax.devices()[0])
    log("trainer", f"peak_bytes_in_use={peak} strategies={strategies}")
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"non-finite training loss: {losses}")
    if not strategies:
        raise AssertionError("the injected fail-slow dispatched no strategy")
    return {
        "steps": len(hist),
        "reduction": sim.reduction_name,
        "strategies": strategies,
    }


# --------------------------------------------------------------- phase 2
def _flags_and_diagnoses(runs: dict, report: dict) -> tuple[list, list]:
    flags = sorted(
        (mode, ev.job_id, ev.time)
        for mode in ("ckpt", "falcon")
        for ev in runs[mode].events if type(ev).__name__ == "Flag"
    )
    diags = [
        (d["job_id"], d["time_s"], d["cause"], tuple(d["components"]))
        for d in report["diagnoses"]
    ]
    return flags, diags


def fleet_phase(
    *, n_jobs: int, max_ticks: int, out_dir: str, compiles: CompileLog,
) -> dict:
    """The fleet campaign on its default path (the shared-prefix engine,
    backends auto-selected: the Pallas screen on a TPU, as ``python -m
    repro.launch.campaign`` runs it) and on fresh runs with the numpy
    backends; the two must raise identical flags and diagnoses
    (docs/kernels.md)."""
    from repro.scenarios import run_and_score, write_report

    results = {}
    for label, screening, reduction in (
        ("auto", None, None), ("numpy", "batched", "vectorized"),
    ):
        mark = compiles.mark()
        t0 = time.monotonic()
        spec, runs, report = run_and_score(
            FLEET_PRESET, n_jobs=n_jobs, seed=0, max_ticks=max_ticks,
            screening_backend=screening, reduction_backend=reduction,
        )
        secs = time.monotonic() - t0
        path = write_report(report, os.path.join(out_dir, label))
        sims = {p.job_id: p.make_sim() for p in spec.jobs}
        if reduction is not None:
            for s in sims.values():
                s.reduction = reduction
        red = {
            j: f"{s.reduction_name}(tp={s.job.tp},dp={s.job.dp},pp={s.job.pp})"
            for j, s in sims.items()
        }
        det, mit = report["detection"]["overall"], report["mitigation"]
        log("fleet", f"{label}: path={'engine' if screening is None else 'fresh'} "
            f"screening={bocd.select_backend(screening).name} "
            f"reductions={red}")
        log("fleet", f"{label}: wall_s={secs!r} ticks={report['campaign']['ticks_run']} "
            f"precision={det['precision']} recall={det['recall']} "
            f"latency_mean_s={det['latency_mean_s']} "
            f"slowdown_mitigated_pct={mit['slowdown_mitigated_pct']} "
            f"{compiles.since(mark)} report={path}")
        results[label] = _flags_and_diagnoses(runs, report)
    flags_a, diags_a = results["auto"]
    flags_n, diags_n = results["numpy"]
    log("fleet", f"flags={len(flags_a)} vs {len(flags_n)}, "
        f"diagnoses={len(diags_a)} vs {len(diags_n)}")
    if flags_a != flags_n or diags_a != diags_n:
        raise AssertionError(
            f"backends disagree:\nauto flags {flags_a}\nnumpy flags {flags_n}"
            f"\nauto diagnoses {diags_a}\nnumpy diagnoses {diags_n}"
        )
    if not diags_a:
        raise AssertionError("the fleet campaign raised no diagnosis")
    return {"n_jobs": n_jobs, "flags": len(flags_a), "diagnoses": len(diags_a)}


# --------------------------------------------------------------- phase 3
def kernel_phase(kernel_shapes: dict[str, set[tuple]]) -> dict[str, bool]:
    """Compile each kernel, with its default ``interpret`` choice, at every
    shape the earlier phases compiled it at; report whether the compiled
    program holds the Mosaic custom call (True on a TPU, False in
    interpret mode)."""
    from repro.kernels.bocd_step import bocd_step
    from repro.kernels.cell_reduce import cell_reduce

    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    lowered = {}
    for k, b in sorted(kernel_shapes["bocd_step"]):
        lowered[f"bocd_step(K={k},B={b})"] = bocd_step.lower(
            sds((b,), f32), sds((k, b), f32), sds((k, b), f32),
            sds((k, b), f32), sds((k, 1), f32), sds((k, 1), f32),
            sds((k, 1), jnp.int32), sds((b,), f32), sds((), f32),
        )
    for pp, dp, tp in sorted(kernel_shapes["cell_reduce"]):
        lowered[f"cell_reduce(pp={pp},dp={dp},tp={tp})"] = cell_reduce.lower(
            sds((pp, dp), f32), sds((pp, dp, tp), f32), sds((pp, dp, tp), f32),
            sds((pp - 1, dp), f32), sds((dp,), f32), *(sds((), f32),) * 5,
        )
    out = {}
    for name, low in lowered.items():
        out[name] = "tpu_custom_call" in low.compile().as_text()
        log("kernels", f"{name} tpu_custom_call={out[name]}")
    return out


# ------------------------------------------------------------ four chips
#: limits of the four-chip comparison (their reasons in four_chip_phase)
LOSS_TOL, GRAD_TOL = 1e-3, 5e-2


def _even_batch_of(batch: dict, counts: list[int]) -> dict:
    """The batch on which ``make_train_step`` must equal the S2 step at
    ``counts``: every micro-batch the S2 step runs (group ``g``'s first
    ``counts[g]`` slots), each once per DP group, so that the even step's
    mean weighs them alike. Needs one sequence per group and slot."""
    picks = [(i, g) for g, c in enumerate(counts) for i in range(c)]
    order = np.asarray(picks * len(counts)).reshape(-1, len(counts), 2)
    return {k: v[order[..., 0], order[..., 1]] for k, v in batch.items()}


def four_chip_phase(
    cfg: ArchConfig, data: DataConfig, devices: list, compiles: CompileLog,
) -> dict:
    """The S2 adaptive step on a (data=2, model=2) mesh vs the even step,
    each from the same params and AdamW state, at counts ``[s, s]`` (on
    the same batch) and ``[s, s // 2]`` (the even step on the micro-batches
    the S2 step ran, :func:`_even_batch_of`). Loss and gradient must agree
    within ``LOSS_TOL`` / ``GRAD_TOL``, and the gradient limit must flag
    the skewed step against the full even step (a dropped micro-batch)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.models import model as model_lib
    from repro.optim import adamw
    from repro.sharding import partition
    from repro.train import train_step as ts_lib

    mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2), ("data", "model"))
    dp = mesh.shape["data"]
    if data.dp_groups != dp or data.mb_sequences != 1:
        raise ValueError("the comparison needs one sequence per DP group "
                         "and slot, one DP group per data shard")
    pspecs = partition.param_specs(cfg, mesh)
    pshard = partition.named(pspecs, mesh)
    oshard = partition.named(
        adamw.opt_state_specs(pspecs, model_lib.param_shapes(cfg), mesh), mesh
    )
    bshard = partition.named(partition.train_batch_specs(cfg, mesh), mesh)
    opt_cfg = adamw.AdamWConfig()
    params = jax.jit(
        lambda: model_lib.init_params(cfg, 0), out_shardings=pshard
    )()
    opt = jax.jit(adamw.init, out_shardings=oshard)(params)
    host_batch = make_batch(cfg, data, 0)
    batch = jax.device_put(host_batch, bshard)
    counts_shard = NamedSharding(mesh, P("data"))
    adaptive = jax.jit(ts_lib.make_adaptive_train_step(cfg, opt_cfg, mesh))
    even = jax.jit(ts_lib.make_train_step(cfg, opt_cfg))

    def spread(tree) -> set[int]:
        return {len(x.sharding.device_set) for x in jax.tree.leaves(tree)}

    placed = {"params": spread(params), "opt": spread(opt),
              "batch": spread(batch)}

    def run(name, step, *args):
        # Keep only what is compared: the loss and AdamW's first moment,
        # (1 - beta1) * the clipped gradient of this first step.
        p, o, m = step(params, opt, *args)
        placed[name] = spread((p, o))
        return float(m["loss"]), o.mu

    def rel_l2(a, b) -> float:
        num = sum(float(jnp.sum((x - y) ** 2)) for x, y in
                  zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True))
        den = sum(float(jnp.sum(y ** 2)) for y in jax.tree.leaves(b))
        return math.sqrt(num / den)

    s = data.slots
    mark = compiles.mark()
    results = {}
    even_full = run("even", even, batch)
    for counts in ([s, s], [s, s // 2]):
        got = run(f"adaptive{counts}", adaptive, batch,
                  jax.device_put(jnp.array(counts, jnp.int32), counts_shard))
        want = even_full if counts == [s, s] else run(
            f"even{counts}", even,
            jax.device_put(_even_batch_of(host_batch, counts), bshard),
        )
        # Loss: the same tokens through the same bf16 forward; only the
        # f32 reduction order of the mean differs. Gradient: the same sum
        # in another order over bf16 activations.
        results[tuple(counts)] = (
            got, want, abs(got[0] - want[0]) / abs(want[0]),
            rel_l2(got[1], want[1]),
        )
    log("4chip", f"mesh={dict(mesh.shape)} batch={jax.tree.map(np.shape, batch)} "
        f"{compiles.since(mark)}")
    for counts, (got, want, loss_rel, grad_rel) in results.items():
        log("4chip", f"counts {list(counts)}: loss S2={got[0]!r} "
            f"even={want[0]!r} rel={loss_rel!r} (limit {LOSS_TOL}); "
            f"grad rel L2={grad_rel!r} (limit {GRAD_TOL})")
    # The fault the limits must catch: the skewed S2 step against the even
    # step on the full batch, i.e. one micro-batch dropped unnoticed.
    fault = rel_l2(results[(s, s // 2)][0][1], even_full[1])
    log("4chip", f"fault reading (counts [{s},{s // 2}] vs the full even "
        f"step): grad rel L2={fault!r}")
    log("4chip", f"devices holding each array: {placed}")
    for d in devices[:4]:
        log("4chip", f"device {d.id} peak_bytes_in_use={peak_bytes(d)}")
    if any(v != {4} for v in placed.values()):
        raise AssertionError(f"arrays not spread over all 4 devices: {placed}")
    for counts, (_, _, loss_rel, grad_rel) in results.items():
        if not (loss_rel <= LOSS_TOL and grad_rel <= GRAD_TOL):
            raise AssertionError(f"S2 step at counts {list(counts)} does not "
                                 "match make_train_step")
    if not fault > GRAD_TOL:
        raise AssertionError("the gradient limit does not separate a dropped "
                             f"micro-batch ({fault} <= {GRAD_TOL})")
    return {"loss_rel": results[(s, s)][2], "grad_rel": results[(s, s)][3],
            "fault_grad_rel": fault}


# ------------------------------------------------------------------ main
def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "chip_smoke"))
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the S2 adaptive step on a 2x2 mesh")
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX's first device is {platform!r}, not a TPU; "
              "this smoke runs only on the chip", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 2
    cache = init_compile_cache()
    compiles = CompileLog()
    os.makedirs(args.out, exist_ok=True)
    log("smoke", f"device={devices[0].device_kind} count={len(devices)} "
        f"jax={jax.__version__} compile_cache={cache}")
    cfg = chip_config()
    phase = "setup"
    try:
        if args.four_chips:
            phase = "4chip"
            four_chip_phase(cfg, CHIP_DATA, devices, compiles)
        else:
            phase = "trainer"
            trainer_phase(
                cfg, CHIP_DATA, steps=TRAIN_STEPS, inject_step=INJECT_STEP,
                out_dir=args.out, compiles=compiles,
            )
            phase = "fleet"
            fleet_phase(n_jobs=FLEET_JOBS, max_ticks=FLEET_TICKS,
                        out_dir=args.out, compiles=compiles)
            phase = "kernels"
            shapes = compiles.kernel_shapes
            log("kernels", f"shapes the phases compiled: {shapes}")
            missing = [k for k, v in shapes.items() if not v]
            if missing:
                raise AssertionError(f"no phase launched {missing}")
            ok = kernel_phase(shapes)
            if not all(ok.values()):
                raise AssertionError(f"a kernel did not compile to Mosaic: {ok}")
    except Exception:
        traceback.print_exc()
        log("smoke", f"FAILED in phase {phase}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
