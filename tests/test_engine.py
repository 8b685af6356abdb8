"""Shared-prefix campaign engine: forked-vs-fresh byte identity, snapshot
completeness, lazy-job reconstruction, and the decision-trace memo.

The engine's headline invariant is *exactness*: every run it serves —
recorded completion, snapshot fork, knob bundle, decision hook — must be
byte-identical to a fresh :func:`run_campaign` execution. The tests pin
that equality at the RunResult level (typed events, per-job outcomes,
tick counts), so any state the fork snapshot fails to carry shows up as
an event or outcome diff; the tamper tests additionally prove each
snapshot surface is *load-bearing* (corrupting it changes the branch),
which is what guarantees a newly added mutable field cannot silently be
omitted from :meth:`ControlPlane.snapshot`.
"""
import numpy as np
import pytest

from repro.core.planner import PlannerKnobs
from repro.scenarios.campaign import MODES, build_campaign, run_campaign
from repro.scenarios.engine import CampaignEngine
from repro.scenarios.presets import PRESETS
from repro.scenarios.scoring import run_and_score, score_campaign


def assert_same_run(fresh, got):
    """Full RunResult equality: events bit-for-bit, outcomes field-wise."""
    assert fresh.ticks_run == got.ticks_run
    assert len(fresh.events) == len(got.events)
    for i, (a, b) in enumerate(zip(fresh.events, got.events)):
        assert type(a) is type(b) and a.__dict__ == b.__dict__, (
            f"event {i}: {a!r} != {b!r}"
        )
    assert list(fresh.outcomes) == list(got.outcomes)
    for job_id, a in fresh.outcomes.items():
        b = got.outcomes[job_id]
        for f in ("join_time", "end_time", "iters_done", "steps",
                  "overhead_paid", "stalled_ticks", "mitigations"):
            va, vb = getattr(a, f), getattr(b, f)
            assert va == vb and repr(va) == repr(vb), (job_id, f, va, vb)


def assert_engine_matches_fresh(spec):
    engine = CampaignEngine(spec)
    for mode in MODES:
        assert_same_run(run_campaign(spec, mode), engine.run(mode))
    return engine


# ---------------------------------------------------------------- identity
@pytest.mark.parametrize("preset", [
    "single_gpu_throttle",   # one job, clean fork
    "collective_hang",       # watchdog/hang path through the prefix
    "flaky_executor",        # executor-fault verdicts post-fork
    "mixed_fleet",           # churn + adaptive retunes + every strategy
])
def test_forked_equals_fresh(preset):
    spec = build_campaign(preset, seed=0)
    engine = assert_engine_matches_fresh(spec)
    # The plane modes actually exercised the fork machinery (a campaign
    # whose plane never intervenes would vacuously pass the equality).
    assert engine.stats["forked_runs"] + engine.stats["reused_runs"] >= 2


def test_forked_equals_fresh_other_seed():
    assert_engine_matches_fresh(build_campaign("mixed_fleet", seed=1))


@pytest.mark.slow
@pytest.mark.parametrize("preset", sorted(PRESETS))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forked_equals_fresh_all_presets(preset, seed):
    assert_engine_matches_fresh(build_campaign(preset, seed=seed))


def test_run_and_score_engine_report_matches_fresh():
    _, _, via_engine = run_and_score("collective_hang", seed=0)
    _, _, via_fresh = run_and_score("collective_hang", seed=0, fresh=True)
    assert via_engine == via_fresh


def test_shared_engine_across_scoring_calls():
    spec = build_campaign("single_gpu_throttle", seed=0)
    engine = CampaignEngine(spec)
    _, _, first = run_and_score("single_gpu_throttle", engine=engine)
    _, _, second = run_and_score("single_gpu_throttle", engine=engine)
    assert first == second
    # The second pass is served entirely from the mode tree.
    assert engine.stats["memo_hits"] >= 2


# ------------------------------------------------------ per-job divergence
def test_untouched_jobs_ride_the_recording():
    spec = build_campaign("mixed_fleet", seed=0)
    engine = CampaignEngine(spec)
    faults = engine.run("faults")
    falcon = engine.run("falcon")
    touched = falcon.touched_jobs
    assert touched is not None and touched
    assert touched < set(falcon.outcomes)  # some jobs stayed virtual
    # Every job the plane acted on is in touched_jobs...
    acted = {
        ev.job_id for ev in falcon.events
        if getattr(ev, "job_id", "") and type(ev).__name__ not in
        ("Observation", "ScreenTuning", "Membership")
    }
    assert acted == touched
    # ...and a job the plane never touched keeps its faults-leg outcome
    # bit-for-bit (it never left the recording).
    for job_id in set(falcon.outcomes) - touched:
        a, b = faults.outcomes[job_id], falcon.outcomes[job_id]
        for f in ("end_time", "iters_done", "stalled_ticks", "overhead_paid"):
            assert repr(getattr(a, f)) == repr(getattr(b, f)), (job_id, f)
        assert not b.mitigations


def test_batched_rng_fast_forward_is_bitwise():
    """Lazy materialization fast-forwards a job's jitter stream with ONE
    batched draw; the stream state afterwards must be bitwise identical
    to the per-tick scalar draws the real run made."""
    for k in (1, 7, 304):
        a = np.random.default_rng([0, 7, 3])
        b = np.random.default_rng([0, 7, 3])
        batched = a.normal(1.0, 0.02, size=k)
        scalars = [float(b.normal(1.0, 0.02)) for _ in range(k)]
        assert [repr(float(v)) for v in batched] == [repr(v) for v in scalars]
        assert repr(float(a.normal(1.0, 0.02))) == repr(float(b.normal(1.0, 0.02)))


# ------------------------------------------------- snapshot completeness
def _tampered_branch(preset, mutate):
    """Run the falcon branch from a fork whose snapshot was corrupted by
    ``mutate(blob)``; returns (fresh falcon, tampered branch result)."""
    spec = build_campaign(preset, seed=0)
    engine = CampaignEngine(spec)
    engine._ensure_base()
    kind, fork = engine._falcon_plan()
    assert kind == "fork" and fork is not None
    mutate(fork.blob)
    return run_campaign(spec, "falcon"), engine._full_leg("falcon", fork=fork)


def _runs_differ(a, b):
    if a.ticks_run != b.ticks_run or len(a.events) != len(b.events):
        return True
    if any(
        type(x) is not type(y) or x.__dict__ != y.__dict__
        for x, y in zip(a.events, b.events)
    ):
        return True
    return any(
        repr(a.outcomes[j].iters_done) != repr(b.outcomes[j].iters_done)
        or a.outcomes[j].end_time != b.outcomes[j].end_time
        for j in a.outcomes
    )


def _swap_fleet_cols(blob):
    (ja, sa), (jb, sb) = list(blob["jobs"].items())[:2]
    sa["_fleet_col"], sb["_fleet_col"] = sb["_fleet_col"], sa["_fleet_col"]


@pytest.mark.parametrize("surface,preset,mutate", [
    # Representative mutable surfaces the fork snapshot carries must be
    # load-bearing: corrupting them has to change the branch. A surface
    # whose corruption were invisible could silently be dropped from
    # snapshot() — this test is what makes a missed field fail.
    ("fleet-screen sample history", "mixed_fleet",
     lambda blob: blob["fleet"].__setitem__(
         "history",
         (blob["fleet"]["history"][0] * 1.5, blob["fleet"]["history"][1]))),
    ("fleet drift baseline (ewma)", "mixed_fleet",
     lambda blob: blob["fleet"].__setitem__(
         "ewma", blob["fleet"]["ewma"] * 3.0)),
    ("watchdog cadence", "collective_hang",
     lambda blob: blob["watchdog"]["last"].update(
         {j: t - 100.0 for j, t in blob["watchdog"]["last"].items()})),
    ("per-job screen routing", "mixed_fleet", _swap_fleet_cols),
    ("incident-gap counters", "mixed_fleet",
     lambda blob: blob.__setitem__("watched_s", 0.0)),
])
def test_tampered_snapshot_changes_the_branch(surface, preset, mutate):
    fresh, tampered = _tampered_branch(preset, mutate)
    assert _runs_differ(fresh, tampered), (
        f"corrupting the {surface} snapshot did not change the branch — "
        "the surface is dead weight or the fork is not actually using it"
    )


def test_untampered_fork_blob_roundtrips():
    """Control for the tamper matrix: the same fork, un-corrupted, must
    reproduce the fresh run exactly."""
    fresh, branch = _tampered_branch("mixed_fleet", lambda blob: None)
    assert not _runs_differ(fresh, branch)
    assert_same_run(fresh, branch)


# ------------------------------------------------------------------ memo
def test_memo_identical_knobs_return_cached_run():
    spec = build_campaign("single_gpu_throttle", seed=0)
    engine = CampaignEngine(spec)
    knobs = PlannerKnobs(breakeven_scale=1.3)
    first = engine.run("falcon", planner_knobs=knobs)
    again = engine.run("falcon", planner_knobs=knobs)
    assert again is first
    assert engine.stats["memo_hits"] == 1
    # None normalizes to the default bundle — same memo slot.
    base = engine.run("falcon")
    assert engine.run("falcon", planner_knobs=PlannerKnobs()) is base


def test_memo_decision_trace_serves_equivalent_knobs():
    """A knob bundle that reprices every recorded break-even consult to
    the same decision reuses the scored leg outright — and the served
    result is still byte-identical to a fresh run under those knobs."""
    spec = build_campaign("mixed_fleet", seed=0)
    engine = CampaignEngine(spec)
    engine.run("falcon")
    near = PlannerKnobs(breakeven_scale=1.0 + 1e-9)
    served = engine.run("falcon", planner_knobs=near)
    assert engine.stats["trace_hits"] == 1
    assert_same_run(run_campaign(spec, "falcon", planner_knobs=near), served)


def test_memo_distinct_decisions_run_fresh():
    spec = build_campaign("mixed_fleet", seed=0)
    engine = CampaignEngine(spec)
    base = engine.run("falcon")
    harsh = engine.run("falcon", planner_knobs=PlannerKnobs(breakeven_scale=25.0))
    assert engine.stats["trace_hits"] == 0
    assert _runs_differ(base, harsh)
    assert_same_run(
        run_campaign(
            spec, "falcon", planner_knobs=PlannerKnobs(breakeven_scale=25.0)
        ),
        harsh,
    )


def test_decision_hooks_fork_but_never_memoize():
    class Suppress:
        def __init__(self, jobs):
            self.jobs = jobs

        def allow(self, job_id, strategy, now):
            return job_id not in self.jobs

        def allow_relief(self, job_id, now):
            return True

        def forced(self, job_id, now):
            return []

    spec = build_campaign("mixed_fleet", seed=0)
    engine = CampaignEngine(spec)
    touched = engine.run("falcon").touched_jobs
    victim = sorted(touched)[0]
    fresh = run_campaign(spec, "falcon", decision_hook=Suppress({victim}))
    got = engine.run("falcon", decision_hook=Suppress({victim}))
    assert_same_run(fresh, got)
    assert engine._memo.keys() == {("falcon", PlannerKnobs())}


# ------------------------------------------------------------- reporting
def test_scored_report_identical_from_engine_runs():
    spec = build_campaign("flaky_executor", seed=0)
    engine = CampaignEngine(spec)
    runs_fresh = {m: run_campaign(spec, m) for m in MODES}
    runs_eng = {m: engine.run(m) for m in MODES}
    assert score_campaign(spec, runs_fresh) == score_campaign(spec, runs_eng)


# ------------------------------------------------ the default path on a TPU
def test_engine_forks_the_pallas_screen(monkeypatch):
    """Where the platform is a TPU, ``"auto"`` picks the Pallas screen and
    ``run_and_score``'s default path still runs on the engine: its
    snapshots carry ``PallasBOCD`` state, and every mode equals a fresh
    run on the same backends. The platform decision is patched to report
    a TPU; the kernels, which read the real platform, stay in interpret
    mode on the CPU."""
    import repro.kernels
    from repro.core import bocd
    from repro.kernels import bocd_step as bk

    monkeypatch.setattr(repro.kernels, "pallas_compiled", lambda: True)
    assert bocd.select_backend("auto").name == "pallas"
    snaps = []
    take = bk.PallasBOCD.snapshot
    monkeypatch.setattr(
        bk.PallasBOCD, "snapshot", lambda self: snaps.append(1) or take(self)
    )
    spec = build_campaign("single_gpu_throttle", seed=0, max_ticks=150)
    engine = assert_engine_matches_fresh(spec)
    assert snaps
    assert engine.stats["forked_runs"] + engine.stats["reused_runs"] >= 2
