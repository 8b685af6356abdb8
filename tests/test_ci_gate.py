"""The CI workflow's own contracts, covered by tier-1.

The regression gate (`.github/workflows/ci.yml` sweep-gate job) only
protects the repo if the committed baseline actually parses, matches the
schema `repro.launch.sweep` expects, and the gate arithmetic does what the
workflow believes — all of which would otherwise only fail *in* CI, after
the fact.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.launch import sweep as sweep_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(
    REPO, "results", "sweeps", "single_gpu_throttle-j1.baseline.json"
)
HANG_BASELINE = os.path.join(
    REPO, "results", "sweeps", "collective_hang-j2.baseline.json"
)
WORKFLOW = os.path.join(REPO, ".github", "workflows", "ci.yml")


def load_baseline() -> dict:
    with open(BASELINE) as f:
        return json.load(f)


def test_committed_baseline_parses_and_matches_gate_schema():
    baseline = load_baseline()
    for key in sweep_mod.GATE_SCHEMA_KEYS:
        assert key in baseline, f"baseline missing {key!r}"
    gate = baseline["gate"]
    metric = gate["metric"]
    assert metric in dict(sweep_mod.METRICS), metric
    assert float(gate["max_drop_pct_points"]) > 0
    m = baseline["metrics"][metric]
    assert m["mean"] is not None
    assert m["n"] == baseline["seeds"] > 1
    # The gated preset/shape must match what the workflow runs.
    assert baseline["preset"] == "single_gpu_throttle"
    assert baseline["jobs"] == 1


def test_gate_arithmetic_passes_identity_and_fails_regression():
    baseline = load_baseline()
    identity = {"metrics": baseline["metrics"]}
    passed, _ = sweep_mod.check_gate(identity, baseline)
    assert passed
    metric = baseline["gate"]["metric"]
    allowed = baseline["gate"]["max_drop_pct_points"]
    regressed = {
        "metrics": {
            metric: {
                "mean": baseline["metrics"][metric]["mean"] - allowed - 0.01
            }
        }
    }
    passed, verdict = sweep_mod.check_gate(regressed, baseline)
    assert not passed
    assert metric in verdict


def test_workflow_invokes_the_gate_against_the_committed_baseline():
    with open(WORKFLOW) as f:
        text = f.read()
    assert "repro.launch.sweep" in text
    assert "results/sweeps/single_gpu_throttle-j1.baseline.json" in text
    assert "repro.launch.campaign" in text  # determinism job
    assert "results/campaigns/single_gpu_throttle-j1-s0.json" in text
    assert "benchmarks.run --smoke" in text
    assert "pytest -x -q" in text
    # Robustness gates: hang determinism + sweep gate, flaky-exec smoke.
    assert "results/campaigns/collective_hang-j2-s0.json" in text
    assert "results/campaigns/flaky_executor-j2-s0.json" in text
    assert "results/sweeps/collective_hang-j2.baseline.json" in text
    assert 'run_and_score("flaky_executor", seed=0)' in text
    # Observability gates: sidecar byte-determinism + dashboard artifact.
    assert "results/campaigns/mixed_fleet-j8-s0.$ext" in text
    assert "collective_hang-j2-s0" in text and "--obs" in text
    assert "repro.launch.obs" in text
    assert "mixed_fleet-dashboard.html" in text


def test_committed_obs_sidecars_exist_for_the_ci_diff():
    for base in ("collective_hang-j2-s0", "mixed_fleet-j8-s0"):
        trace_path = os.path.join(
            REPO, "results", "campaigns", f"{base}.trace.json"
        )
        with open(trace_path) as f:
            doc = json.load(f)
        assert doc["displayTimeUnit"] == "ms"
        assert any(e["ph"] == "X" for e in doc["traceEvents"])
        metrics_path = os.path.join(
            REPO, "results", "campaigns", f"{base}.metrics.json"
        )
        with open(metrics_path) as f:
            snap = json.load(f)
        assert {c["name"] for c in snap["counters"]} >= {
            "events_total", "diagnoses_total"
        }


def test_committed_hang_baseline_parses_and_matches_gate_schema():
    with open(HANG_BASELINE) as f:
        baseline = json.load(f)
    for key in sweep_mod.GATE_SCHEMA_KEYS:
        assert key in baseline, f"hang baseline missing {key!r}"
    gate = baseline["gate"]
    assert gate["metric"] in dict(sweep_mod.METRICS)
    assert float(gate["max_drop_pct_points"]) > 0
    m = baseline["metrics"][gate["metric"]]
    assert m["mean"] is not None
    assert m["n"] == baseline["seeds"] > 1
    assert baseline["preset"] == "collective_hang"
    assert baseline["jobs"] == 2
    # Every seed must have watchdog-detected every injected hang.
    wd = baseline["metrics"]["hang_detection_rate"]
    assert wd["mean"] == 1.0 and wd["n"] == baseline["seeds"]


def test_committed_hang_and_flaky_reports_exist_for_the_ci_diff():
    for preset in ("collective_hang", "flaky_executor"):
        path = os.path.join(
            REPO, "results", "campaigns", f"{preset}-j2-s0.json"
        )
        with open(path) as f:
            report = json.load(f)
        assert report["campaign"]["preset"] == preset
        assert report["campaign"]["n_jobs"] == 2
        assert "robustness" in report


def test_committed_determinism_report_exists_for_the_ci_diff():
    path = os.path.join(
        REPO, "results", "campaigns", "single_gpu_throttle-j1-s0.json"
    )
    with open(path) as f:
        report = json.load(f)
    assert report["campaign"]["preset"] == "single_gpu_throttle"
    assert report["campaign"]["seed"] == 0
    assert report["campaign"]["n_jobs"] == 1


@pytest.mark.slow
def test_sweep_cli_gate_mode_end_to_end(tmp_path):
    """The exact command CI runs, end to end, including the exit code."""
    out = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.sweep",
            "--preset", "single_gpu_throttle", "--jobs", "1", "--seeds", "3",
            "--out", str(tmp_path), "--gate", BASELINE, "--quiet",
        ],
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
        capture_output=True, text=True, timeout=1200,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "GATE PASS" in out.stdout


@pytest.mark.slow
def test_hang_sweep_cli_gate_mode_end_to_end(tmp_path):
    """The collective_hang gate command CI runs, end to end."""
    out = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.sweep",
            "--preset", "collective_hang", "--jobs", "2", "--seeds", "3",
            "--out", str(tmp_path), "--gate", HANG_BASELINE, "--quiet",
        ],
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
        capture_output=True, text=True, timeout=1200,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "GATE PASS" in out.stdout


def test_parallel_sweep_is_byte_identical_to_serial(tmp_path):
    """--workers fans seeds out over processes; the sweep table must be
    byte-identical to the serial run (each seed's report is a pure
    function of its inputs, and map keeps seed order)."""
    serial = sweep_mod.run_sweep(
        "single_gpu_throttle", seeds=2, max_ticks=160, workers=1
    )
    fanned = sweep_mod.run_sweep(
        "single_gpu_throttle", seeds=2, max_ticks=160, workers=2
    )
    assert serial == fanned
    p1 = sweep_mod.write_sweep(serial, str(tmp_path / "serial"))
    p2 = sweep_mod.write_sweep(fanned, str(tmp_path / "fanned"))
    with open(p1, "rb") as f1, open(p2, "rb") as f2:
        assert f1.read() == f2.read()


def test_sweep_pool_refused_off_cpu(monkeypatch):
    """One process per chip: on an accelerator backend a worker pool is
    refused before any campaign runs; on the CPU it stays available."""
    import jax

    assert sweep_mod.pool_refusal(4) is None  # CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sweep_mod.pool_refusal(1) is None
    assert "'tpu'" in sweep_mod.pool_refusal(2)
    monkeypatch.setattr(sweep_mod, "run_and_score", None)  # never reached
    with pytest.raises(ValueError, match="--workers 2 refused"):
        sweep_mod.run_sweep("single_gpu_throttle", seeds=2, workers=2)
