"""Plumbing shared by every cell: the manifest, name lookup, seeds, compile
counting, the window clock and the result line.

Nothing here knows a configuration, a traffic mix or a metric by name: a
cell names its configuration file and traffic mix in ``BENCHMARK.json``,
the configuration file names its ``system`` (``chipbench/systems/<system>.py``)
and each per-layer metric has a reader ``chipbench/metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import sys
from typing import Any, Callable

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "chipbench")
#: git-ignored scratch of a run: traces and the trainer's checkpoint dir
WORK_DIR = os.path.join(BENCH_DIR, ".work")


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, resolved."""

    name: str
    chips: int
    config: dict
    traffic_name: str
    traffic: dict
    #: end-to-end metric entries this cell reports (manifest order)
    end_to_end: list[dict]
    #: per-layer metric entries this cell reports (manifest order)
    per_layer: list[dict]
    #: correctness limits by compared number (``chipbench/limits/<cell>.json``)
    limits: dict


def traffic_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "traffic", f"{name}.json")


def limits_path(workload: str) -> str:
    return os.path.join(BENCH_DIR, "limits", f"{workload}.json")


def metric_reader_path(metric: str) -> str:
    return os.path.join(BENCH_DIR, "metrics", f"{metric}.py")


def _reports(entry: dict, cell: str, e2e_names: set[str]) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    # An end-to-end metric without a list is in every cell; a per-layer
    # one is in every cell that reports the metric it moves.
    return "moves" not in entry or entry["moves"] in e2e_names


def resolve_cell(manifest: dict, name: str, root: str = ROOT) -> Cell:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    centry = configs[w["config"]]
    e2e = [m for m in manifest["end_to_end"] if _reports(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=load_json(os.path.join(root, centry["file"])),
        traffic_name=w["traffic"],
        traffic=load_json(traffic_path(w["traffic"])),
        end_to_end=e2e,
        per_layer=per_layer,
        limits=load_json(limits_path(name)),
    )


def load_reader(metric: str) -> Callable:
    """The ``read(ctx)`` function of a per-layer metric's reader file."""
    path = metric_reader_path(metric)
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_system(name: str):
    return importlib.import_module(f"chipbench.systems.{name}")


# ------------------------------------------------------------------ seeds
def seed_words(seed: int) -> list[int]:
    """Any whole number as non-negative 32-bit words (for SeedSequence and
    jax keys): seeds may exceed 32 signed bits and may be negative."""
    s = int(seed) % (1 << 64)
    return [s & 0xFFFFFFFF, s >> 32]


# --------------------------------------------------------------- compiles
class CompileLog:
    """Backend compiles, their seconds and persistent-cache hits, through
    ``jax.monitoring`` (listeners stay registered; read deltas). Copied
    from the program's ``chip_smoke.py``."""

    def __init__(self) -> None:
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> tuple[float, int, int]:
        return self.compile_s, self.compiles, self.cache_hits

    def since(self, mark: tuple[float, int, int]) -> dict:
        s, n, h = mark
        return {"compile_s": self.compile_s - s, "compiles": self.compiles - n,
                "cache_hits": self.cache_hits - h}


# ------------------------------------------------------------------ output
def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit: the run
    is correct only if ``value <= limit`` (``value >= limit`` for an
    ``at_least`` check)."""

    name: str
    value: float
    limit: float
    at_least: bool = False

    @property
    def ok(self) -> bool:
        if not math.isfinite(self.value):
            return False
        return self.value >= self.limit if self.at_least else self.value <= self.limit

    def line(self) -> str:
        sense = ">=" if self.at_least else "<="
        return (f"check {self.name} = {self.value!r} (limit {sense} "
                f"{self.limit!r}) {'ok' if self.ok else 'FAILED'}")


def result_line(
    *, correct: bool, attempted: int, failed: int, metrics: dict,
    device: dict, checks: list[Check], breakdown: dict | None = None,
) -> str:
    out: dict = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    # The compared numbers come last, each with its limit.
    out["checks"] = {
        c.name: {"value": _finite_or_text(c.value), "limit": c.limit,
                 "sense": ">=" if c.at_least else "<="}
        for c in checks
    }
    return json.dumps(out)


def _finite_or_text(v: float) -> float | str:
    """JSON has no NaN or infinity: such a reading is written as text."""
    return v if math.isfinite(v) else repr(v)


# ------------------------------------------------------------------- runs
@dataclasses.dataclass
class Job:
    """What a system driver is given for one run."""

    cell: Cell
    seed: int
    seconds: float
    tracer: Any
    compiles: CompileLog | None
    #: monotonic time at which the process started: set-up is measured from it
    t_start: float
    #: CPU rehearsal at the tiny sizes of the files' ``rehearsal`` sections
    rehearsal: bool = False

    def config(self) -> dict:
        cfg = dict(self.cell.config)
        if self.rehearsal:
            cfg.update(cfg.get("rehearsal", {}))
        return cfg

    def traffic(self, generator: str) -> dict:
        """The cell's traffic mix, which must name ``generator``."""
        tr = dict(self.cell.traffic)
        if tr.get("generator") != generator:
            raise ValueError(f"traffic {self.cell.traffic_name!r} is for generator "
                             f"{tr.get('generator')!r}, not {generator!r}")
        if self.rehearsal:
            tr.update(tr.get("rehearsal", {}))
        return tr

    def limits(self) -> dict:
        lim = dict(self.cell.limits["limits"])
        if self.rehearsal:
            lim.update(self.cell.limits.get("rehearsal", {}))
        return lim


@dataclasses.dataclass
class Outcome:
    """What a system driver returns: the window's numbers, the counters the
    per-layer readers take, the peak memory and the comparison."""

    setup_s: float
    window_s: float
    attempted: int
    failed: int
    #: end-to-end metric values by name (``setup_s`` is added by the harness)
    end_to_end: dict
    counters: dict
    memory_peak_bytes: int
    checks: list
    trace_events: dict | None = None


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def norm_gap(prog, ref, keep=None) -> float:
    """Worst leaf's gap between two per-leaf norms, against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    import numpy as np

    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    keep = np.ones(ref.shape, bool) if keep is None else np.asarray(keep)
    med = float(np.median(ref[keep]))
    gaps = np.abs(prog - ref) / np.maximum(ref, med)
    return float(np.max(gaps[keep]))
