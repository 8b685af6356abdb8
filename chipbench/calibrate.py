"""Readings that set the correctness limits: the control and the faults.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 [--ticks N]
    python3 chipbench/calibrate.py --workload <fleet cell> --seeds 1,2,3 \
        --plant half_streams --seconds 10

Not part of a benchmark run. For each seed it prints each compared number
of the control, the plain reference computed one precision below what the
configuration states and put in the program's place, against the
reference at the stated precision, and of the faults the cell can have:

* training cells: the control computes every matmul operand in float8
  (e4m3) for the configuration's bfloat16; the fault read here is the mean
  taken over half of each step's sequences, in the reference; a step that
  returns its state unchanged reads 1 on the gradient by construction;
* the fleet cell: the control runs the posterior in bfloat16 for the
  configuration's float32 screen (``--ticks``: the window's ticks after
  set-up). ``--plant`` instead runs the whole benchmark run, window and
  comparison, with the timed path broken underneath (``FLEET_FAULTS``:
  the screening backend's step or the flags the screen returns), and
  prints its result line.

The lower readings come from the benchmark's own sound runs (their
``checks``). ``--rehearse-cpu`` runs at the rehearsal sizes on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


from chipbench import harness  # noqa: E402


def train_readings(job: harness.Job) -> dict:
    """Control and half-batch readings of a training cell for one seed."""
    import jax.numpy as jnp

    from chipbench.generators import train_data_seed, train_tokens
    from chipbench.reference import dense_decoder
    from chipbench.systems.trainer import readings_gaps
    from chipbench.weights import make_weights

    cfg, traffic = job.config(), job.traffic("train_tokens")
    opt = dict(cfg["optimizer"], total_steps=traffic["schedule_steps"])
    template = _param_template(cfg)
    seqs = traffic["dp_groups"] * traffic["sequences_per_group"]
    batches = [train_tokens(train_data_seed(job.seed, k), traffic["slots"], seqs,
                            traffic["seq_len"], cfg["vocab_size"])
               for k in range(1, traffic["reference_steps"] + 1)]

    def weights():
        return make_weights(template, job.seed, cfg["initializer_range"])

    ref = dense_decoder.train_readings(weights, batches, cfg, opt)
    ctrl = dense_decoder.train_readings(weights, batches, cfg, opt,
                                        operand_dtype=jnp.float8_e4m3fn)
    half = dense_decoder.train_readings(
        weights, [(t[:, : seqs // 2], lab[:, : seqs // 2]) for t, lab in batches], cfg, opt)
    return {"control": readings_gaps(ctrl, ref), "half_batch": readings_gaps(half, ref),
            "reference_losses": ref["losses"]}


def _param_template(cfg: dict):
    """The parameter shapes the program trains for this configuration."""
    import jax

    from chipbench.systems.trainer import program_arch
    from repro.models import model as model_lib

    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                        model_lib.param_shapes(program_arch(cfg)))


def fleet_readings(job: harness.Job, ticks: int) -> dict:
    """The control's readings on the fleet cell for one seed: ``ticks``
    ticks after set-up stand for the window, then the untimed tail."""
    import ml_dtypes

    from chipbench.generators import FleetTraffic
    from chipbench.reference import fleet_screen as reference
    from chipbench.systems.fleet_screen import late_window, readings

    cfg, traffic = job.config(), job.traffic("fleet")
    n = cfg["streams"]
    gen = FleetTraffic(traffic, n, job.seed)
    ref = reference.Screen(n, cfg)
    ctrl = reference.Screen(n, cfg, dtype=ml_dtypes.bfloat16)
    last = traffic["setup_ticks"] + ticks - 1
    for t in range(last + 1 + traffic["tail_ticks"]):
        x = gen.sample(t)
        ref.tick(x)
        ctrl.tick(x)
    late = late_window(ref.t)
    out = {"control": readings(ctrl.flags, ctrl.post.p_within(late), ref, last),
           "reference_flags": len(ref.flags)}
    ctrl.close()
    ref.close()
    return out


class Unchanged:
    """A screening backend whose step returns its state unchanged."""

    def __init__(self, inner) -> None:
        self.inner = inner

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def update(self, x):
        return np.exp(np.asarray(self.inner._log_r[0], np.float64))


class HalfLeftOut:
    """A screening backend whose step advances only the first half of the
    streams; the second half keeps its first state."""

    def __init__(self, make, n: int, **kw) -> None:
        mu0 = np.broadcast_to(kw.pop("mu0"), (n,))
        self.h = n // 2
        self.a = make(self.h, mu0=mu0[: self.h], **kw)
        self.b = make(n - self.h, mu0=mu0[self.h:], **kw)

    def __getattr__(self, name):
        return getattr(self.a, name)

    def update(self, x):
        held = np.exp(np.asarray(self.b._log_r[0], np.float64))
        return np.concatenate([self.a.update(x[: self.h]), held])

    def p_recent_change(self, window=2):
        return np.concatenate([self.a.p_recent_change(window), self.b.p_recent_change(window)])

    def map_runlength(self):
        return np.concatenate([self.a.map_runlength(), self.b.map_runlength()])


def _wrap_backend(wrap):
    """A fault that wraps every backend the auto-selected factory makes."""

    def plant(set_attr) -> None:
        from repro.core import bocd

        real = bocd.select_backend("auto")

        class Planted(bocd.ScreeningBackendFactory):
            name = "planted"

            def make(self, n_series, **kw):
                return wrap(real.make, n_series, **kw)

        set_attr(bocd, "select_backend", lambda name=None: Planted())

    return plant


def _alter_flags(set_attr) -> None:
    """Every flag the screen returns names the next stream."""
    from repro.core.detector import FleetDetect, FleetFlag

    real_tick = FleetDetect.tick

    def tick(self, times):
        return [FleetFlag(worker=(f.worker + 1) % self.n_workers, change_point=f.change_point)
                for f in real_tick(self, times)]

    set_attr(FleetDetect, "tick", tick)


#: faults of the fleet screen's timed path, each planted by a function of
#: ``set_attr``
FLEET_FAULTS = {
    "state_unchanged": _wrap_backend(lambda make, n, **kw: Unchanged(make(n, **kw))),
    "half_streams": _wrap_backend(lambda make, n, **kw: HalfLeftOut(make, n, **kw)),
    "flag_altered": _alter_flags,
}


def plant_fleet_fault(fault: str, set_attr=setattr) -> None:
    """Break the fleet screen's timed path underneath with
    ``FLEET_FAULTS[fault]``. ``set_attr`` is ``setattr`` or a test's
    ``monkeypatch.setattr``."""
    FLEET_FAULTS[fault](set_attr)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--ticks", type=int, default=1500)
    ap.add_argument("--plant", choices=sorted(FLEET_FAULTS))
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.plant:
        from chipbench import run

        plant_fleet_fault(args.plant)
        for seed in seeds:
            run.main(["--workload", args.workload, "--seed", str(seed),
                      "--seconds", str(args.seconds), "--trace", "0"]
                     + (["--rehearse-cpu"] if args.rehearse_cpu else []))
        return 0
    cell = harness.resolve_cell(harness.load_manifest(), args.workload)
    if not args.rehearse_cpu:
        from repro.launch import init_compile_cache

        init_compile_cache()
    for seed in seeds:
        job = harness.Job(cell=cell, seed=seed, seconds=0.0, tracer=None,
                          compiles=None, t_start=0.0, rehearsal=args.rehearse_cpu)
        if cell.config["system"] == "trainer":
            got = train_readings(job)
        else:
            got = fleet_readings(job, args.ticks)
        print(json.dumps({"workload": args.workload, "seed": seed, **got},
                         default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
