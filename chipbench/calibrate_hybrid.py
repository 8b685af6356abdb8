"""Readings that set the hybrid training cell's correctness limits: the
control and the faults planted in the program.

    python3 chipbench/calibrate_hybrid.py --workload <cell> --seeds 1,2

Not part of a benchmark run. For each seed it takes every reading in one
process, as a run of the cell takes them (the trainer's first
``reference_steps`` steps against the plain reference), without the
window: the reference at the stated precision once; the control, the
reference with every matmul operand in float8 (e4m3) for the
configuration's bfloat16, put in the program's place; then the sound
program and each fault of :data:`FAULTS`, planted in the program's timed
path. The programs are traced while the references run and compiled side
by side, so a chip call pays about one compilation for all of them. One
JSON line each. A test plants the same faults under a whole benchmark
run with :func:`plant`.

The faults:

* ``residual_ignored``: each sub-layer's output is added to the residual
  unscaled, as if ``residual_multiplier`` were 1;
* ``expert_dropped``: the first held expert's output is dropped (its
  down-projection reads zero), wherever on the mesh it lives;
* ``capacity_buffer``: the expert layer packs each expert's assignments
  into a capacity buffer of 1.25 times the mean load and drops the rest,
  as the program did before its dispatch became dropless;
* ``half_batch``: each step's loss, and so its gradient, is the mean over
  the first half of the step's sequences only;
* ``no_psum``: the expert layer's exchange between chips is left out, so
  each chip goes on with its own experts' and its own shard of the shared
  expert's partial output.

The lower readings come from the benchmark's own sound runs (their
``checks``) and the sweep's sound program. ``--rehearse-cpu`` runs at the
rehearsal sizes on the CPU, which needs four devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness  # noqa: E402

#: the capacity factor of the expert layer's capacity buffer (the fault)
CAPACITY_FACTOR = 1.25


# ------------------------------------------------------------------ faults
def _residual_ignored(set_attr) -> None:
    from repro.models import transformer

    set_attr(transformer, "_scaled", lambda y, cfg: y)


def _expert_dropped(set_attr) -> None:
    import jax.numpy as jnp

    from repro.models import moe

    real = moe._moe_core

    def core(params, xf, cfg, e_offset, e_local):
        keep = (e_offset + jnp.arange(e_local)) != cfg.expert_offset
        wo = params["wo"] * keep[:, None, None].astype(params["wo"].dtype)
        return real(dict(params, wo=wo), xf, cfg, e_offset, e_local)

    set_attr(moe, "_moe_core", core)


def _capacity_buffer(set_attr) -> None:
    import jax
    import jax.numpy as jnp

    from repro.models import moe

    def core(params, xf, cfg, e_offset, e_local):
        t, d = xf.shape
        k = cfg.top_k
        cap = int(t * k / cfg.num_experts * CAPACITY_FACTOR) + 1
        gates, idx, aux = moe.route(xf @ params["router"], k, n_real=cfg.num_experts)
        flat_e, flat_g = idx.reshape(-1), gates.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        token_of = order // k
        counts = jnp.bincount(flat_e, length=cfg.padded_experts)
        slot = jnp.arange(t * k) - (jnp.cumsum(counts) - counts)[sorted_e]
        local_e = sorted_e - e_offset
        held = (local_e >= 0) & (local_e < e_local)
        keep = held & (slot < cap)
        slot_c, local_c = jnp.where(keep, slot, 0), jnp.where(keep, local_e, 0)
        buf = jnp.zeros((e_local, cap, d), xf.dtype).at[local_c, slot_c].add(
            jnp.where(keep[:, None], xf[token_of], 0.0))
        act = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["wi_gate"]))
               * jnp.einsum("ecd,edf->ecf", buf, params["wi_up"]))
        out = jnp.einsum("ecf,efd->ecd", act, params["wo"])
        y_sorted = out[local_c, slot_c] * jnp.where(keep, flat_g[order], 0.0)[:, None]
        y = jnp.zeros((t, d), jnp.float32).at[token_of].add(y_sorted.astype(jnp.float32))
        kept = jnp.bincount(jnp.where(keep, local_e, e_local), length=e_local + 1)
        stats = {"counts": kept[:e_local].astype(jnp.int32),
                 "dropped": (jnp.sum(held) - jnp.sum(keep)).astype(jnp.int32)}
        return y, aux, stats

    set_attr(moe, "_moe_core", core)


def _half_batch(set_attr) -> None:
    from repro.train import train_step

    real = train_step._microbatch_loss

    def loss(params, mb, cfg, use_kernel):
        return real(params, {k: v[: v.shape[0] // 2] for k, v in mb.items()}, cfg,
                    use_kernel)

    set_attr(train_step, "_microbatch_loss", loss)


def _no_psum(set_attr) -> None:
    from repro.models import moe

    set_attr(moe, "_combine", lambda y: y)


#: faults of the hybrid model's timed path, each planted by a function of
#: ``set_attr``
FAULTS = {
    "residual_ignored": _residual_ignored,
    "expert_dropped": _expert_dropped,
    "capacity_buffer": _capacity_buffer,
    "half_batch": _half_batch,
    "no_psum": _no_psum,
}


def plant(fault: str, set_attr=setattr) -> None:
    """Break the program's timed path underneath with ``FAULTS[fault]``.
    ``set_attr`` is ``setattr`` or a test's ``monkeypatch.setattr``."""
    FAULTS[fault](set_attr)


def sweep(job: harness.Job, faults=tuple(FAULTS)):
    """Yield the readings of one seed: the reference's losses, the
    control's gaps, then the sound program's and each fault's gaps with
    the expert layer's dropped assignments, each as soon as it is read."""
    import concurrent.futures
    import gc
    import threading
    import time

    import jax
    import jax.numpy as jnp

    from chipbench.systems import sharded_trainer as st
    from chipbench.systems.trainer import readings_gaps
    from repro.data.pipeline import make_batch

    t0 = time.monotonic()
    setup = st.build(job)
    trainer, template, devices = setup.trainer, setup.template, setup.devices
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          (trainer.params, trainer.opt_state,
                           make_batch(trainer.cfg, trainer.data, 0)))
    trainer.params = trainer.opt_state = None  # the references need the memory
    del setup
    gc.collect()
    variants = ["sound", *faults]
    pool = concurrent.futures.ThreadPoolExecutor(len(variants))
    compiled = {}

    def lower_all():
        # The references import nothing of the program, so planting here
        # leaves what the main thread traces meanwhile alone.
        for name in variants:
            undo = []

            def set_attr(obj, attr, value):
                undo.append((obj, attr, getattr(obj, attr)))
                setattr(obj, attr, value)

            try:
                if name != "sound":
                    plant(name, set_attr)
                with jax.set_mesh(trainer.mesh):
                    lowered = trainer.jit_step().lower(*shapes)
                compiled[name] = pool.submit(lowered.compile)
            except Exception as err:  # noqa: BLE001
                compiled[name] = concurrent.futures.Future()
                compiled[name].set_exception(err)
            finally:
                for obj, attr, value in reversed(undo):
                    setattr(obj, attr, value)
            compiled[name].add_done_callback(lambda _, name=name: harness.log(
                f"{name} compiled at {time.monotonic() - t0:.1f} s"))

    def failed(name: str, err: Exception) -> dict:
        # one reading that fails leaves the others of the call standing
        return {"variant": name, "error": repr(err), "at_s": time.monotonic() - t0}

    lowering = threading.Thread(target=lower_all)
    lowering.start()
    try:
        ref = st.reference_readings(job, template, devices)
        yield {"variant": "reference", "losses": ref["losses"], "at_s": time.monotonic() - t0}
        try:
            ctrl = st.reference_readings(job, template, devices, jnp.float8_e4m3fn)
            yield {"variant": "control", **readings_gaps(ctrl, ref),
                   "losses": ctrl["losses"], "at_s": time.monotonic() - t0}
        except Exception as err:  # noqa: BLE001
            yield failed("control", err)
    finally:
        lowering.join()
    del trainer
    for name in variants:
        try:
            setup = st.build(job)
            setup.trainer._step_fn = compiled.pop(name).result()
            program = st.first_steps(setup, job)
            yield {"variant": name, **readings_gaps(program, ref),
                   "moe_dropped_tokens": setup.trainer.moe_dropped_tokens,
                   "losses": program["losses"], "at_s": time.monotonic() - t0}
        except Exception as err:  # noqa: BLE001
            yield failed(name, err)
        setup = program = None
        gc.collect()
    pool.shutdown()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.resolve_cell(harness.load_manifest(), args.workload)
    if not args.rehearse_cpu:
        from repro.launch import init_compile_cache

        init_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        job = harness.Job(cell=cell, seed=seed, seconds=0.0, tracer=None,
                          compiles=None, t_start=0.0, rehearsal=args.rehearse_cpu)
        for reading in sweep(job):
            print(json.dumps({"workload": args.workload, "seed": seed, **reading},
                             default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
