"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it finds the cell in ``BENCHMARK.json``, refuses to run where
JAX finds no TPU or fewer chips than the cell asks for (exit 2, no result),
builds the system the cell's configuration names, warms it up (set-up, from
process start), measures for ``--seconds`` seconds, checks what the timed
path produced against the plain reference, and prints one JSON object as
its last line of standard output. With ``--trace 0`` its metrics are the
cell's end-to-end metrics; with ``--trace 1`` the window runs under the
profiler and the metrics are the cell's per-layer metrics, each read by
``chipbench/metrics/<metric>.py``. The numbers compared with the reference
are printed beside their limits as the last lines of standard error and
under ``checks``, the last key of the result line.

``--rehearse-cpu`` runs the same flow on JAX's CPU at the tiny sizes of
the files' ``rehearsal`` sections. It prints no device metric.

Compiled programs are cached in ``<checkout>/.jax_cache`` (or where
``JAX_COMPILATION_CACHE_DIR`` points).
"""
from __future__ import annotations

import time

T_START = time.monotonic()  # set-up is measured from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness  # noqa: E402


def parse_args(argv: list[str] | None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run on JAX's CPU at the tiny rehearsal sizes")
    return ap.parse_args(argv)


def _per_layer(cell, outcome, summary, peaks_row) -> dict:
    """Each per-layer metric of the cell, read by its reader from the run's
    counters, the reduced trace, the chip's peaks and the configuration;
    a reader that finds nothing to read leaves its metric out."""
    import types

    ctx = types.SimpleNamespace(counters=outcome.counters, trace=summary,
                                peaks=peaks_row, config=cell.config)
    out = {}
    for m in cell.per_layer:
        value = harness.load_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv: list[str] | None = None, t_start: float = T_START) -> int:
    args = parse_args(argv)
    manifest = harness.load_manifest()
    cell = harness.resolve_cell(manifest, args.workload)
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse_cpu and platform != "tpu":
        print(f"chipbench: JAX's first device is {platform!r}, not a TPU; "
              "the benchmark measures only on the chip", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from chipbench import peaks, tracing

    peaks_row = None
    if not args.rehearse_cpu:
        peaks_row = peaks.peaks(devices[0].device_kind)
        from repro.launch import init_compile_cache

        harness.log(f"compile cache: {init_compile_cache()}")
    trace_dir = (os.path.join(harness.WORK_DIR, "trace", args.workload)
                 if args.trace else None)
    job = harness.Job(
        cell=cell, seed=args.seed, seconds=args.seconds,
        tracer=tracing.Tracer(trace_dir), compiles=harness.CompileLog(),
        t_start=t_start, rehearsal=args.rehearse_cpu,
    )
    system = harness.load_system(cell.config["system"])
    outcome = system.run(job)

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": outcome.memory_peak_bytes}
    breakdown = None
    if args.rehearse_cpu:
        metrics: dict = {}
        device["rehearsal"] = True
    elif args.trace:
        summary = tracing.reduce(outcome.trace_events)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = summary.breakdown()
        metrics = _per_layer(cell, outcome, summary, peaks_row)
    else:
        values = dict(outcome.end_to_end, setup_s=outcome.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    correct = all(c.ok for c in outcome.checks)
    for c in outcome.checks:
        harness.log(c.line())
    print(harness.result_line(
        correct=correct, attempted=outcome.attempted, failed=outcome.failed,
        metrics=metrics, device=device, checks=outcome.checks,
        breakdown=breakdown,
    ), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
