"""Benchmark harness — one module per paper table/figure (deliverable d).

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--smoke]

Each module exposes ``run() -> list[dict]``; results are printed as aligned
tables and persisted to ``results/bench/<name>.json``. ``--smoke`` runs
every benchmark at toy scale (modules whose ``run`` accepts a ``smoke``
keyword); it exists so CI can execute the full suite end-to-end in minutes
— perf entry points that don't run, rot.
"""
from __future__ import annotations

import argparse
import importlib
import inspect
import sys
import time
import traceback

from benchmarks.common import print_table
from repro.launch import init_compile_cache

#: (module, paper artifact)
SUITE = [
    ("validation_cost", "Fig. 9 — O(1) communicator validation"),
    ("iteration_estimation", "Fig. 12 — ACF iteration-time estimation"),
    ("detection_accuracy", "Tables 4-5 — detector accuracy"),
    ("microbatch_solver", "Table 6 — micro-batch solver time"),
    ("mitigation_s2", "Figs. 13-14 — S2 micro-batch adjustment"),
    ("mitigation_s3", "Figs. 15-16 — S3 topology adjustment"),
    ("topology_overhead", "Fig. 19 — topology-adjust overhead M vs D"),
    ("characterization", "Table 1 / Fig. 1 — characterization campaign"),
    ("detector_overhead", "Fig. 18 — detector overhead (real JAX steps)"),
    ("end_to_end", "Fig. 20 / Table 7 — 64-GPU end-to-end"),
    ("roofline", "Roofline — dry-run derived terms (deliverable g)"),
    ("fleet_scale", "Fleet-scale fast path — batched detection + vector sim"),
    ("event_rate", "Event rate — event-scoped incremental recompute cost"),
    ("controlplane_overhead", "Control plane — per-tick overhead at 1-64 jobs"),
    ("campaign_throughput", "Scenario campaigns — engine ticks/s vs fleet size"),
    ("whatif_replay", "What-if engine — replay cost vs fresh re-runs"),
    ("campaign_reuse", "Campaign reuse — shared-prefix engine vs fresh runs"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="run a single benchmark")
    ap.add_argument(
        "--smoke", action="store_true",
        help="toy-scale pass over every benchmark (CI rot check)",
    )
    args = ap.parse_args()
    init_compile_cache()

    if args.only and args.only not in {name for name, _ in SUITE}:
        ap.error(
            f"unknown benchmark {args.only!r}; choose from: "
            + ", ".join(name for name, _ in SUITE)
        )

    if args.smoke:
        # Toy-scale numbers must not clobber the tracked full-scale results.
        import tempfile

        from benchmarks import common

        common.RESULTS_DIR = tempfile.mkdtemp(prefix="bench_smoke_")

    failures = []
    for name, title in SUITE:
        if args.only and args.only != name:
            continue
        mod = importlib.import_module(f"benchmarks.{name}")
        kwargs = {}
        if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
            kwargs["smoke"] = True
        t0 = time.monotonic()
        try:
            rows = mod.run(**kwargs)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures.append(name)
            continue
        dt = time.monotonic() - t0
        if name == "roofline":
            rows = [
                {k: r[k] for k in (
                    "arch", "shape", "compute_s", "memory_s", "collective_s",
                    "dominant", "model_over_hlo", "peak_gib_dev",
                )}
                for r in rows
            ]
        print_table(f"{title}  [{dt:.1f}s]", rows)
    if failures:
        print(f"\nFAILED benchmarks: {failures}")
        sys.exit(1)
    print("\nALL BENCHMARKS COMPLETED")


if __name__ == "__main__":
    main()
