"""Fleet fast-path equivalence tests.

Pins the vectorized implementations to their scalar/loop references:

* ``BatchedBOCD`` change-point indices match scalar ``BOCD`` per column
  (uncapped mode is per-column exact; the capped shared frontier equals the
  scalar cap rule at B=1).
* The vectorized ``TrainingSimulator`` fast path matches the nested-loop
  reference to 1e-9 across randomized placements, allocations and injected
  slowdowns, and its memo invalidates on every mutation surface.
"""
import numpy as np
import pytest

from repro.cluster.injector import FailSlowInjector, Injection, InjectionKind
from repro.cluster.simulator import JobSpec, TrainingSimulator
from repro.cluster.spec import ClusterSpec, ModelSpec
from repro.core import bocd
from repro.core.detector import FalconDetect, FleetDetect
from repro.core.ringbuf import MatrixRingBuffer, RingBuffer
from repro.kernels import pallas_compiled

MODEL = ModelSpec(layers=24, hidden=4096, seq_len=2048, vocab=50257)


# --------------------------------------------------------- batched BOCD
def fleet_matrix(n_workers=24, n_ticks=400, seed=0):
    """Per-column step changes at varied onsets/levels/jumps."""
    x = np.empty((n_ticks, n_workers))
    for col in range(n_workers):
        r = np.random.default_rng(seed * 1000 + col)
        lvl = 1.0 + 0.5 * (col % 3)
        jump = 1.0 + 0.15 + 0.02 * (col % 7)
        cp = (100 + 7 * col) % (n_ticks // 2) + 50
        x[:, col] = np.concatenate([
            r.normal(lvl, 0.01 * lvl, cp),
            r.normal(lvl * jump, 0.01 * lvl * jump, n_ticks - cp),
        ])
    return x


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_batched_indices_match_scalar_per_column(seed):
    x = fleet_matrix(seed=seed)
    batched = bocd.detect_change_points_batch(x)
    for col in range(x.shape[1]):
        assert batched[col] == bocd.detect_change_points(x[:, col]), col


def test_batched_posterior_matches_scalar_uncapped():
    x = fleet_matrix(n_workers=8, n_ticks=200)
    scale = bocd.noise_scale_batch(x)
    det = bocd.BatchedBOCD(8, mu0=x[0] / scale)
    scalars = [
        bocd.BOCD(mu0=float(x[0, c] / scale[c])) for c in range(8)
    ]
    for t in range(x.shape[0]):
        det.update(x[t] / scale)
        for c, s in enumerate(scalars):
            s.update(float(x[t, c] / scale[c]))
            live = np.isfinite(det._log_r[:, c])
            assert np.array_equal(det._rl[live], s._rl), (t, c)
            np.testing.assert_allclose(
                det._log_r[live, c], s._log_r, atol=1e-9
            )


def test_capped_batched_equals_capped_scalar_at_b1():
    """The shared truncation frontier degenerates to the scalar cap rule."""
    r = np.random.default_rng(3)
    x = np.concatenate([r.normal(1.0, 0.01, 250), r.normal(1.4, 0.014, 250)])
    s = bocd.BOCD(mu0=float(x[0]), max_hypotheses=32)
    b = bocd.BatchedBOCD(1, mu0=x[:1], max_hypotheses=32)
    for t in range(x.size):
        s.update(float(x[t]))
        b.update(x[t : t + 1])
        assert b.n_hypotheses <= 32
        assert np.array_equal(b._rl, s._rl), t
        np.testing.assert_allclose(b._log_r[:, 0], s._log_r, atol=1e-9)


def test_scalar_cap_bounds_hypotheses():
    det = bocd.BOCD(hazard=0.01, mu0=1.0, max_hypotheses=24)
    r = np.random.default_rng(1)
    for _ in range(800):
        det.update(float(r.normal(1.0, 0.01)))
        assert det._log_r.size <= 24
    # detection still works through the cap
    x = np.concatenate([r.normal(1.0, 0.01, 80), r.normal(1.5, 0.015, 80)])
    scale = bocd.noise_scale(x)
    det2 = bocd.BOCD(mu0=float(x[0] / scale), max_hypotheses=24)
    fired = []
    for i, xi in enumerate(x):
        det2.update(float(xi / scale))
        if i > 2 and det2.p_recent_change() > 0.9:
            fired.append(i - det2.map_runlength())
    assert any(abs(i - 80) <= 3 for i in fired)


def test_noise_scale_batch_matches_scalar():
    x = fleet_matrix(n_workers=6, n_ticks=100)
    batch = bocd.noise_scale_batch(x)
    for c in range(6):
        assert batch[c] == pytest.approx(bocd.noise_scale(x[:, c]), rel=0, abs=0)


# ----------------------------------------------------------- FleetDetect
def test_fleet_detect_flags_exactly_the_stragglers():
    n, t_total, onset = 256, 160, 100
    rng = np.random.default_rng(5)
    x = rng.normal(1.0, 0.01, (t_total, n))
    bad = sorted(rng.choice(n, 6, replace=False).tolist())
    x[onset:, bad] *= 1.35
    fd = FleetDetect(n_workers=n)
    hits = {}
    for t in range(t_total):
        for flag in fd.tick(x[t]):
            hits.setdefault(flag.worker, flag.change_point)
    assert sorted(hits) == bad
    for cp in hits.values():
        assert abs(cp.index - onset) <= 5
        assert cp.relative_change > 0.2


def test_fleet_detect_no_false_flags_on_healthy_fleet():
    rng = np.random.default_rng(11)
    fd = FleetDetect(n_workers=128)
    flags = [f for t in range(200) for f in fd.tick(rng.normal(1.0, 0.01, 128))]
    assert flags == []


def test_fleet_detect_flags_once_per_change():
    rng = np.random.default_rng(7)
    x = rng.normal(1.0, 0.01, (200, 32))
    x[80:, 3] *= 1.5
    fd = FleetDetect(n_workers=32)
    flags = [f for t in range(200) for f in fd.tick(x[t])]
    assert len([f for f in flags if f.worker == 3]) == 1


# ------------------------------------------------------------ ring buffer
def test_ring_buffer_absolute_indexing():
    rb = RingBuffer(4)
    for i in range(10):
        rb.append(float(i))
    assert len(rb) == 10
    assert rb.start == 6
    assert rb.view(6, 10).tolist() == [6.0, 7.0, 8.0, 9.0]
    assert rb.view(0, 8).tolist() == [6.0, 7.0]  # clamped to retained
    assert rb.last(2).tolist() == [8.0, 9.0]
    assert rb[7] == 7.0
    with pytest.raises(IndexError):
        rb[5]


def test_matrix_ring_buffer_columns():
    mb = MatrixRingBuffer(3, 2)
    for i in range(5):
        mb.append(np.array([i, 10 + i], dtype=float))
    assert mb.column(0, 2, 5).tolist() == [2.0, 3.0, 4.0]
    assert mb.column(1, 0, 5).tolist() == [12.0, 13.0, 14.0]
    assert mb.rows(3).shape == (2, 2)


def test_falcon_detect_bounded_history_still_detects():
    """Detection works far beyond the ring capacity (O(1) per observe)."""
    class _Stub:  # pinpoint sees no groups -> CPU_CONTENTION root cause
        def profile_groups(self):
            return {}
        def group_ranks(self, g):
            return []
        def benchmark_compute(self, ranks):
            return {}
        def measure_link(self, pair):
            return 0.0
    det = FalconDetect(cluster=_Stub(), history_cap=128)
    rng = np.random.default_rng(0)
    event = None
    for i in range(2000):
        t = 1.0 if i < 1500 else 1.6
        t *= float(rng.normal(1, 0.004))
        event = det.observe(t, float(i)) or event
    assert det._series.capacity == 128  # bounded storage
    assert event is not None
    assert event.t_slow > event.t_healthy * 1.4


# ------------------------------------------------- vectorized simulator
def random_sim(rng):
    tp = int(rng.choice([1, 2, 4]))
    pp = int(rng.choice([1, 2, 4]))
    dp = int(rng.choice([1, 2, 4, 8]))
    n = tp * dp * pp
    gpn = int(rng.choice([2, 4, 8]))
    nodes = max(1, (n + gpn - 1) // gpn)
    spec = ClusterSpec(n_nodes=nodes, gpus_per_node=gpn)
    if n > spec.n_devices:
        return None
    job = JobSpec(model=MODEL, tp=tp, dp=dp, pp=pp, micro_batches=4 * dp)
    sim = TrainingSimulator(cluster=spec, job=job)
    sim.apply_placement(rng.permutation(n).tolist())
    if dp > 1:
        alloc = [4] * dp
        alloc[0] += 2
        alloc[1] -= 2
        sim.set_allocation(alloc)
    for _ in range(int(rng.integers(0, 4))):
        kind = rng.choice(["gpu", "host", "link", "nic"])
        if kind == "gpu":
            sim.state.devices[int(rng.integers(n))].compute_speed = float(
                rng.uniform(0.3, 0.9)
            )
        elif kind == "host":
            sim.state.devices[int(rng.integers(n))].host_speed = float(
                rng.uniform(0.5, 0.9)
            )
        elif kind == "link":
            a, b = rng.choice(spec.n_devices, 2, replace=False)
            sim.state.degrade_link(int(a), int(b), float(rng.uniform(0.05, 0.8)))
        else:
            sim.state.degrade_nic(int(rng.integers(nodes)), float(rng.uniform(0.2, 0.8)))
    return sim


def test_vectorized_simulator_matches_reference_randomized():
    rng = np.random.default_rng(42)
    tried = 0
    while tried < 40:
        sim = random_sim(rng)
        if sim is None:
            continue
        tried += 1
        fast, ref = sim.iteration_time(), sim.iteration_time_reference()
        assert fast == pytest.approx(ref, rel=1e-9, abs=0.0)
        assert sim.profile_groups() == sim.profile_groups_reference()
        assert sim.per_microbatch_times() == pytest.approx(
            sim.per_microbatch_times_reference(), rel=1e-9
        )


def make_sim(tp=2, dp=2, pp=2, nodes=2, gpn=4, micro_batches=8):
    job = JobSpec(model=MODEL, tp=tp, dp=dp, pp=pp, micro_batches=micro_batches)
    return TrainingSimulator(
        cluster=ClusterSpec(n_nodes=nodes, gpus_per_node=gpn), job=job
    )


def test_memo_invalidates_on_every_mutation_surface():
    sim = make_sim()

    def check():
        assert sim.iteration_time() == pytest.approx(
            sim.iteration_time_reference(), rel=1e-12
        )

    check()
    sim.state.devices[0].compute_speed = 0.5
    check()
    sim.state.devices[1].host_speed = 0.7
    check()
    sim.state.degrade_link(0, 4, 0.2)
    check()
    sim.state.degrade_nic(1, 0.5)
    check()
    sim.state.restore_link(0, 4)
    check()
    sim.state.restore_nic(1)
    check()
    sim.state.reset()
    check()
    sim.set_allocation([6, 2])
    check()
    sim.apply_placement(list(reversed(range(sim.job.n_devices))))
    check()
    sim.placement = list(range(sim.job.n_devices))  # direct assignment
    check()
    sim.restart()
    check()


def test_memoized_healthy_steps_hit_cache():
    sim = make_sim()
    inj = FailSlowInjector([
        Injection(start=5.0, duration=10.0, kind=InjectionKind.GPU_SLOW,
                  target=(0,), severity=0.5),
    ])
    inj.apply(sim.state, 0.0)
    t0 = sim.iteration_time()
    v0 = sim.state.version
    inj.apply(sim.state, 1.0)  # same (empty) active set: no reset, no bump
    assert sim.state.version == v0
    assert sim.iteration_time() == t0
    inj.apply(sim.state, 6.0)  # episode starts: state changes
    assert sim.state.version != v0
    t1 = sim.iteration_time()
    assert t1 > t0
    v1 = sim.state.version
    inj.apply(sim.state, 7.0)  # steady episode: no re-apply
    assert sim.state.version == v1
    inj.apply(sim.state, 20.0)  # episode over: reset back to healthy
    assert sim.iteration_time() == pytest.approx(t0)


def test_external_mutation_between_applies_is_not_lost():
    """The injector's steady-state skip must notice third-party mutations."""
    sim = make_sim()
    inj = FailSlowInjector([
        Injection(start=0.0, duration=100.0, kind=InjectionKind.GPU_SLOW,
                  target=(0,), severity=0.5),
    ])
    inj.apply(sim.state, 1.0)
    t_ep = sim.iteration_time()
    sim.state.devices[0].compute_speed = 1.0  # external meddling
    inj.apply(sim.state, 2.0)  # version moved: full reset + re-apply
    assert sim.iteration_time() == pytest.approx(t_ep)


# ------------------------------------------- dynamic membership (churn)
def _posterior(batch, col):
    """One column's live (run_length, posterior) pairs, sorted."""
    live = np.isfinite(batch._log_r[:, col])
    return sorted(
        zip(batch._rl[live].tolist(), batch._log_r[live, col].tolist())
    )


def test_batched_take_columns_equals_fresh_run():
    """Sub-slicing mid-stream leaves each survivor's posterior exactly what
    a fresh (uncapped) recursion over the surviving columns would hold."""
    x = fleet_matrix(n_workers=10, n_ticks=200, seed=3)
    scale = bocd.noise_scale_batch(x)
    keep = [0, 2, 5, 9]
    full = bocd.BatchedBOCD(10, mu0=x[0] / scale)
    for t in range(120):
        full.update(x[t] / scale)
    full.take_columns(np.array(keep))
    fresh = bocd.BatchedBOCD(len(keep), mu0=x[0, keep] / scale[keep])
    for t in range(200):
        if t >= 120:
            full.update(x[t, keep] / scale[keep])
        fresh.update(x[t, keep] / scale[keep])
    for c in range(len(keep)):
        a, b = _posterior(full, c), _posterior(fresh, c)
        assert [rl for rl, _ in a] == [rl for rl, _ in b]
        assert np.allclose([p for _, p in a], [p for _, p in b])
    assert np.array_equal(full.map_runlength(), fresh.map_runlength())


def test_fleet_remove_worker_matches_fresh_detector():
    """Flags after a mid-stream leave match a fresh detector that never saw
    the departed stream (sub-slice equivalence at the FleetDetect level)."""
    n_t = 200
    rng = np.random.default_rng(21)
    x = np.asarray(rng.normal(1.0, 0.01, (n_t, 6)))
    x[150:, 4] *= 1.4  # onset after the leave, on a surviving stream
    keep = [0, 1, 3, 4, 5]
    a = FleetDetect(n_workers=6, max_hypotheses=None)
    b = FleetDetect(n_workers=5, max_hypotheses=None)
    flags_a, flags_b = [], []
    for t in range(n_t):
        if t == 100:
            a.remove_worker(2)
        row = x[t, keep]
        if t < 100:
            flags_a += a.tick(x[t])
        else:
            flags_a += [f for f in a.tick(row)]
        flags_b += b.tick(row)
    assert [(f.worker, f.change_point.index) for f in flags_a] == [
        (f.worker, f.change_point.index) for f in flags_b
    ]
    assert any(f.worker == 3 for f in flags_b)  # old column 4, shifted


def test_fleet_add_worker_warms_and_detects():
    """A stream joining mid-flight is screened after its own warmup and its
    fail-slow is flagged; established streams are unaffected."""
    rng = np.random.default_rng(9)
    fd = FleetDetect(n_workers=3)
    for t in range(60):
        fd.tick(rng.normal(1.0, 0.01, 3))
    w = fd.add_worker()
    assert (w, fd.n_workers, fd.n_cohorts) == (3, 4, 2)
    hits = {}
    for t in range(80):
        row = np.empty(4)
        row[:3] = rng.normal(1.0, 0.01, 3)
        row[3] = rng.normal(2.0 if t < 40 else 2.9, 0.02)
        for f in fd.tick(row):
            hits.setdefault(f.worker, t)
    assert list(hits) == [3]
    assert abs(hits[3] - 40) <= 4


def test_fleet_consolidate_matches_fresh_window_detector():
    """Re-warming cohorts into one frontier equals a fresh detector fed the
    common retained history window, flag for flag."""
    rng = np.random.default_rng(4)
    fd = FleetDetect(n_workers=3, max_cohorts=None)
    hist = []
    for t in range(40):
        row = rng.normal(1.0, 0.01, 3)
        hist.append(row)
        fd.tick(row)
    fd.add_worker()
    for t in range(30):
        row = np.empty(4)
        row[:3] = rng.normal(1.0, 0.01, 3)
        row[3] = rng.normal(1.5, 0.015)
        hist.append(row)
        fd.tick(row)
    assert fd.n_cohorts == 2
    fd.consolidate()
    assert fd.n_cohorts == 1
    # Fresh detector over the common window (the join tick onward).
    window = np.asarray([h for h in hist if len(h) == 4])
    fresh = FleetDetect(n_workers=4, max_cohorts=None)
    for row in window:
        fresh.tick(row)
    onset = 70
    flags_a, flags_b = [], []
    for t in range(40):
        row = np.empty(4)
        row[:3] = rng.normal(1.0, 0.01, 3)
        row[3] = rng.normal(1.5, 0.015)
        if t >= 10:
            row[1] *= 1.45
        flags_a += fd.tick(row)
        flags_b += fresh.tick(row)
    assert [f.worker for f in flags_a] == [f.worker for f in flags_b]
    # Absolute indices differ by the 40 pre-join ticks the fresh one skipped.
    assert [f.change_point.index - 40 for f in flags_a] == [
        f.change_point.index for f in flags_b
    ]


def test_fleet_drift_screen_catches_ramped_onset():
    """A gradual ramp (invisible to the run-length rule — each step is
    barely surprising) is flagged by the lagged drift screen."""
    rng = np.random.default_rng(0)
    n_t = 250
    prof = np.concatenate([
        np.zeros(100), np.linspace(0.0, 0.3, 40), np.full(n_t - 140, 0.3)
    ])
    fd = FleetDetect(n_workers=1)
    hits = []
    for t in range(n_t):
        hits += [
            (t, f.change_point.relative_change)
            for f in fd.tick(np.array([(1 + prof[t]) * rng.normal(1, 0.003)]))
        ]
    assert hits, "ramp missed"
    t0, rel = hits[0]
    assert 100 < t0 < 140  # confirmed during the ramp
    assert rel > 0.1


def test_long_horizon_screen_catches_subthreshold_creep():
    """A creep below threshold/drift_ref per 40 ticks is invisible to both
    BOCD and the lagged drift screen; the long-horizon EWMA baseline
    catches it (ROADMAP: e.g. a 10 %/hour ramp on a fleet-monitor tick)."""
    rng = np.random.default_rng(0)
    fd = FleetDetect(n_workers=4, ewma_min_age=32)
    flags = []
    for t in range(900):
        x = rng.normal(1.0, 0.004, 4)
        if t >= 100:  # worker 2: +0.05 %/tick, ~2 %/40 ticks — sub-threshold
            x[2] *= 1.0 + 0.0005 * (t - 100)
        flags += [(t, f.worker, f.change_point) for f in fd.tick(x)]
    mine = [f for f in flags if f[1] == 2]
    assert mine, "creep missed"
    t0, _, cp = mine[0]
    assert cp.relative_change > 0.09
    assert not [f for f in flags if f[1] != 2], "healthy workers flagged"
    # the confirmed drift re-estimates the stream's jitter scale
    assert np.isfinite(fd._scale[2])


def test_long_horizon_screen_stays_quiet_on_step_faults():
    """Step changes are BOCD's: the baseline re-anchors on the confirmed
    flag, so the same physical fault never double-fires through the
    long-horizon screen."""
    rng = np.random.default_rng(1)
    fd = FleetDetect(n_workers=2, ewma_min_age=32)
    flags = []
    for t in range(400):
        x = rng.normal(1.0, 0.004, 2)
        if t >= 120:
            x[1] *= 1.35
        flags += [(t, f.worker) for f in fd.tick(x)]
    hits = [t for t, w in flags if w == 1]
    assert hits and hits[0] <= 125  # BOCD got it promptly
    assert len(hits) <= 2  # no EWMA re-fire on the anchored level


def test_adaptive_knobs_retune_from_observed_change_rate():
    """adapt_every derives the hazard (and the shared frontier cap) from
    the observed confirmed-flag rate; a quiet fleet drifts toward the rare
    end, a churny one toward the frequent end, both within bounds."""
    rng = np.random.default_rng(2)
    quiet = FleetDetect(n_workers=8, adapt_every=50)
    for _ in range(200):
        quiet.tick(rng.normal(1.0, 0.004, 8))
    assert quiet.last_tuning is not None
    assert quiet.hazard < 1.0 / 100.0  # rarer than the prior
    assert quiet.hazard >= quiet.hazard_bounds[0]
    assert quiet.max_hypotheses >= 32
    for cohort in quiet._cohorts:  # propagated into the live batches
        assert cohort.batch.hazard == quiet.hazard
        assert cohort.batch.max_hypotheses == quiet.max_hypotheses

    churny = FleetDetect(n_workers=8, adapt_every=50, ewma_span=0)
    level = np.ones(8)
    for t in range(400):
        if t % 25 == 0:  # a real level shift somewhere, every 25 ticks
            level[int(rng.integers(8))] *= float(rng.choice([1.3, 1 / 1.3]))
        churny.tick(level * rng.normal(1.0, 0.004, 8))
    assert churny.last_tuning is not None
    assert churny.hazard > quiet.hazard
    assert churny.hazard <= churny.hazard_bounds[1]

    fixed = FleetDetect(n_workers=8)  # default: constants stay put
    for _ in range(200):
        fixed.tick(rng.normal(1.0, 0.004, 8))
    assert fixed.last_tuning is None and fixed.hazard == 1.0 / 100.0


def test_screen_tuning_event_in_typed_log():
    """The control plane mirrors adaptive re-tunes into the event log."""
    from repro.cluster.simulator import JobSpec, TrainingSimulator
    from repro.cluster.spec import ClusterSpec, ModelSpec
    from repro.controlplane import ControlPlane, ScreenTuning

    sim = TrainingSimulator(
        cluster=ClusterSpec(n_nodes=1, gpus_per_node=4),
        job=JobSpec(
            model=ModelSpec(layers=8, hidden=1024, seq_len=512, vocab=1000),
            tp=1, dp=4, pp=1, micro_batches=8,
        ),
    )
    plane = ControlPlane(fleet_kwargs={"adapt_every": 40})
    plane.register_job("j0", sim)
    rng = np.random.default_rng(3)
    t = sim.iteration_time()
    for k in range(100):
        plane.tick({"j0": t * float(rng.normal(1, 0.004))}, float(k))
    tunings = [e for e in plane.events if isinstance(e, ScreenTuning)]
    assert tunings, "no ScreenTuning emitted"
    assert tunings[0].job_id == "" and tunings[0].hazard > 0
    assert tunings[0].worker_ticks > 0
    # one event per distinct retune, not one per tick
    assert len(tunings) <= 100 // 40

    # default plane: no adaptive events, log shape unchanged
    plane2 = ControlPlane()
    plane2.register_job("j0", sim)
    for k in range(100):
        plane2.tick({"j0": t * float(rng.normal(1, 0.004))}, float(k))
    assert not [e for e in plane2.events if isinstance(e, ScreenTuning)]


# ------------------------------------------------- backend registries
# Satellite of the ScreeningBackend/ReductionBackend API redesign: every
# registry entry must be interchangeable within its documented tolerance
# (scalar fan-out is the per-column oracle; batched numpy is exact;
# Pallas carries the float32 kernel tolerance from docs/kernels.md).


def _screen_traces(b, t_max, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(0.0, 1.0, (t_max, b))
    x[t_max // 2:, :: max(b // 3, 1)] += 6.0  # strong breaks, scaled units
    return x


@pytest.mark.parametrize("b", [1, 7, 64, 1000])
def test_screening_backends_equivalent_probabilities(b):
    """scalar / batched / pallas report the same change probabilities per
    stream (registry promise), at fleet sizes from one stream to 1k."""
    t_max = 16 if b == 1000 else 24
    x = _screen_traces(b, t_max, seed=b)
    dets = {
        name: bocd.SCREENING_BACKENDS[name].make(
            b, mu0=x[0], max_hypotheses=32
        )
        for name in ("scalar", "batched", "pallas")
    }
    for t in range(t_max):
        p = {name: det.update(x[t]) for name, det in dets.items()}
        np.testing.assert_allclose(   # numpy paths: same recursion exactly
            p["batched"], p["scalar"], rtol=1e-9, atol=1e-12
        )
        np.testing.assert_allclose(   # float32 kernel: documented drift
            p["pallas"], p["batched"], rtol=1e-4, atol=1e-4
        )
    np.testing.assert_array_equal(
        dets["batched"].map_runlength(), dets["scalar"].map_runlength()
    )
    np.testing.assert_allclose(
        dets["pallas"].p_recent_change(), dets["batched"].p_recent_change(),
        rtol=1e-4, atol=1e-4,
    )


def test_fleet_detect_backend_flag_parity():
    """FleetDetect raises identical flags whichever registry backend runs
    the screen — the end-to-end guarantee the CI kernels job smoke-tests."""
    b, t_max = 48, 60
    rng = np.random.default_rng(11)
    x = rng.normal(1.0, 0.01, (t_max, b))
    x[30:, [3, 17, 40]] *= 1.35
    flags = {}
    for name in ("scalar", "batched", "pallas"):
        fleet = FleetDetect(n_workers=b, backend=name)
        flags[name] = sorted(
            (t, f.worker) for t in range(t_max) for f in fleet.tick(x[t])
        )
    assert flags["batched"] == flags["scalar"]
    assert flags["pallas"] == flags["batched"]
    assert {w for _, w in flags["batched"]} == {3, 17, 40}


def test_screening_backend_registry_resolution():
    assert bocd.select_backend("batched").name == "batched"
    auto = bocd.select_backend(None)
    assert auto.name == ("pallas" if pallas_compiled() else "batched")
    with pytest.raises(ValueError, match="unknown screening backend"):
        bocd.select_backend("fpga")
    # factory instances pass through
    fac = bocd.SCREENING_BACKENDS["scalar"]
    assert bocd.resolve_screening_backend(fac) is fac


def _faulted_sim(n_devices=512, seed=0):
    tp, pp = 4, 4
    dp = n_devices // (tp * pp)
    model = ModelSpec(layers=16, hidden=2048, seq_len=1024, vocab=32000)
    job = JobSpec(model=model, tp=tp, dp=dp, pp=pp, micro_batches=2 * dp)
    sim = TrainingSimulator(
        cluster=ClusterSpec(n_nodes=n_devices // 8), job=job
    )
    rng = np.random.default_rng(seed)
    for d in rng.choice(n_devices, 5, replace=False):
        sim.state.devices[int(d)].compute_speed = 0.7
    sim.state.degrade_nic(int(rng.integers(n_devices // 8)), 0.5)
    return sim


def test_reduction_backends_equivalent():
    """Every ReductionBackend registry entry agrees with the reference
    nested-loop oracle on a faulted hybrid topology, within its own
    documented tolerance, across the whole read API."""
    from repro.cluster.simulator import REDUCTION_BACKENDS

    sim = _faulted_sim()
    want_t = sim.iteration_time_reference()
    want_pm = np.asarray(sim.per_microbatch_times_reference())
    want_pg = sim.profile_groups_reference()
    for name, cls in REDUCTION_BACKENDS.items():
        rb = cls()
        tol = max(rb.tolerance, 1e-12)
        got_t = float(rb.iteration_time(sim))
        np.testing.assert_allclose(got_t, want_t, rtol=tol, err_msg=name)
        got_pm = np.asarray(rb.per_microbatch_times(sim))
        np.testing.assert_allclose(got_pm, want_pm, rtol=tol, err_msg=name)
        got_pg = rb.profile_groups(sim)
        assert got_pg.keys() == want_pg.keys(), name
        for k in want_pg:
            np.testing.assert_allclose(
                got_pg[k], want_pg[k], rtol=tol, err_msg=f"{name}:{k}"
            )


def test_reduction_backend_resolution_and_sim_knob():
    from repro.cluster import simulator as S

    sim = _faulted_sim(seed=3)
    job = sim.job
    # the hot path stays inline for the defaults (no indirection object)
    auto = S.resolve_reduction_backend(None, job)
    assert auto is None if not pallas_compiled() else auto.name == "pallas"
    assert S.resolve_reduction_backend("vectorized", job) is None
    assert S.resolve_reduction_backend("reference", job).name == "reference"
    with pytest.raises(ValueError, match="unknown reduction backend"):
        S.select_reduction_backend("abacus")
    with pytest.raises(TypeError):
        S.resolve_reduction_backend(42, job)

    # the TrainingSimulator knob swaps backends and stays consistent
    t_vec = sim.iteration_time()
    sim.reduction = "reference"
    t_ref = sim.iteration_time()
    np.testing.assert_allclose(t_vec, t_ref, rtol=1e-9)
    sim.reduction = "pallas"
    t_pal = sim.iteration_time()
    np.testing.assert_allclose(t_pal, t_ref, rtol=1e-4)
    # and the memo keeps tracking mutations across backend switches
    sim.state.devices[0].compute_speed = 0.4
    t_after = sim.iteration_time()
    assert t_after > t_pal
    np.testing.assert_allclose(
        t_after, sim.iteration_time_reference(), rtol=1e-4
    )


def test_reduction_backend_degenerate_topology_is_reported(monkeypatch):
    """A job with a parallel axis of 1 runs the inline path even where
    Pallas compiles, says so, and refuses an explicit pallas backend."""
    import jax

    from repro.cluster import simulator as S

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = ModelSpec(layers=8, hidden=1024, seq_len=512, vocab=32000)
    flat = TrainingSimulator(
        cluster=ClusterSpec(n_nodes=2, gpus_per_node=4),
        job=JobSpec(model=model, tp=2, dp=4, pp=1, micro_batches=16),
    )
    assert not S.fits_pallas_reduction(flat.job)
    assert S.resolve_reduction_backend("auto", flat.job) is None
    assert flat.reduction_name == "vectorized"
    with pytest.raises(ValueError, match="pp=1"):
        S.resolve_reduction_backend("pallas", flat.job)
    with pytest.raises(ValueError, match="pp=1"):
        S.resolve_reduction_backend(S.PallasReduction(), flat.job)
    hybrid = _faulted_sim()
    assert S.fits_pallas_reduction(hybrid.job)
    assert hybrid.reduction_name == "pallas"
    # reassigning the job re-resolves the backend
    hybrid.job = flat.job
    assert hybrid.reduction_name == "vectorized"


# ------------------------------------------- snapshot / restore, factories
def _steady_step(fd, level, t):
    """Step faults on a fixed membership."""
    if t in (90, 150, 230):
        level[(t // 30) % fd.n_workers] *= 1.5
    return level


def _churn_step(fd, level, t):
    """Joins at 120 and 180, a leave at 260, step faults up and down."""
    if t in (120, 180):
        fd.add_worker()
        level = np.append(level, 1.0)
    if t == 260 and fd.n_workers > 4:
        fd.remove_worker(2)
        level = np.delete(level, 2)
    if t in (90, 150, 230, 300, 360):
        level[(t // 30) % fd.n_workers] *= 1.6
    if t == 330:
        level[0] *= 0.6
    return level


def _drive(fd, step, level, rng, t0, t1, out):
    """Tick ``fd`` over ``[t0, t1)``, logging every flag; returns the
    level vector and the most cohorts seen at once."""
    peak = fd.n_cohorts
    for t in range(t0, t1):
        level = step(fd, level, t)
        x = level * (1.0 + 0.02 * rng.standard_normal(fd.n_workers))
        out.append([
            (f.worker, f.change_point.index, f.change_point.probability,
             f.change_point.mean_before, f.change_point.mean_after)
            for f in fd.tick(x)
        ])
        peak = max(peak, fd.n_cohorts)
    return level, peak


@pytest.mark.parametrize("adapt", [0, 50])
@pytest.mark.parametrize(
    "schedule, seed, cut, end, max_cohorts",
    [
        ("steady", 9, 100, 180, 4),
        ("churn", 3, 200, 420, 2),
        ("churn", 7, 200, 420, 2),
    ],
)
def test_fleet_snapshot_restore_tail_equivalence(
    schedule, seed, cut, end, max_cohorts, adapt
):
    """A fresh FleetDetect restored from snapshot() continues bitwise
    identically to the instance that kept running — on the churn schedule
    also after joins and a consolidation before the cut and a leave after
    it (the campaign engine's forks rely on this)."""
    step = _steady_step if schedule == "steady" else _churn_step
    kw = dict(backend="batched", adapt_every=adapt, max_cohorts=max_cohorts)
    rng = np.random.default_rng(seed)
    fd = FleetDetect(n_workers=6, **kw)
    level, peak = _drive(fd, step, np.ones(6), rng, 0, cut, [])
    if schedule == "churn":
        assert fd.n_cohorts < peak  # consolidated before the snapshot
    snap = fd.snapshot()
    rng_state = rng.bit_generator.state
    level_snap = level.copy()
    cont_a: list = []
    _drive(fd, step, level, rng, cut, end, cont_a)

    fd2 = FleetDetect(n_workers=6, **kw)
    fd2.restore(snap)
    rng2 = np.random.default_rng(seed)
    rng2.bit_generator.state = rng_state
    cont_b: list = []
    _drive(fd2, step, level_snap, rng2, cut, end, cont_b)
    assert any(cont_a)
    assert cont_a == cont_b
    assert np.array_equal(fd._scale, fd2._scale, equal_nan=True)
    assert np.array_equal(fd._ewma, fd2._ewma, equal_nan=True)
    assert (fd.hazard, fd.max_hypotheses) == (fd2.hazard, fd2.max_hypotheses)


class _CountingFactory:
    """A duck-typed screening factory, no ScreeningBackendFactory base
    (like the benchmark's recording factory): keeps what it makes."""

    name = "counting"

    def __init__(self) -> None:
        self.made: list = []

    def make(self, n_series: int, **kwargs):
        inst = bocd.BatchedBOCD(n_series, **kwargs)
        self.made.append(inst)
        return inst


def test_fleet_detect_makes_every_cohort_through_its_factory():
    """A joining cohort's warm-up, consolidate() and restore() each build
    their batch through the caller's factory, and the restored copy ticks
    on like the original."""
    rng = np.random.default_rng(5)
    fac = _CountingFactory()
    fd = FleetDetect(n_workers=4, backend=fac, max_cohorts=None)
    for _ in range(40):
        fd.tick(1.0 + 0.02 * rng.standard_normal(fd.n_workers))
    assert [b.n_series for b in fac.made] == [4]
    fd.add_worker()  # warms 8 ticks later
    for _ in range(8):
        fd.tick(1.0 + 0.02 * rng.standard_normal(fd.n_workers))
    assert [b.n_series for b in fac.made] == [4, 1]
    assert [c.batch for c in fd._cohorts] == fac.made

    fd.consolidate()
    assert [b.n_series for b in fac.made] == [4, 1, 5]
    assert fd.n_cohorts == 1 and fd._cohorts[0].batch is fac.made[-1]

    fac2 = _CountingFactory()
    fd2 = FleetDetect(n_workers=5, backend=fac2, max_cohorts=None)
    fd2.restore(fd.snapshot())
    assert [b.n_series for b in fac2.made] == [5]
    assert fd2._cohorts[0].batch is fac2.made[0]
    level = np.ones(5)
    flags: list = []
    for t in range(60):
        if t == 20:
            level[3] *= 1.5
        x = level * (1.0 + 0.02 * rng.standard_normal(5))
        got = [(f.worker, f.change_point.index) for f in fd.tick(x)]
        assert got == [(f.worker, f.change_point.index) for f in fd2.tick(x)]
        flags += got
    assert [w for w, _ in flags] == [3]
    assert len(fac.made) == 3 and len(fac2.made) == 1


def test_resolve_screening_backend_rejects_a_class():
    """Only names and factory instances select a backend: a backend or
    factory class raises instead of being wrapped."""
    for cls in (bocd.BatchedBOCD, bocd.BOCD, bocd.BatchedScreening):
        with pytest.raises(TypeError, match="a name or a factory"):
            bocd.resolve_screening_backend(cls)
    with pytest.raises(TypeError):
        FleetDetect(n_workers=2, backend=bocd.BatchedBOCD)


def test_watchdog_snapshot_roundtrip():
    from repro.core.detector import Watchdog

    wd = Watchdog()
    for i in range(10):
        wd.beat("j1", i * 1.0)
        wd.beat("j2", i * 1.7)
    snap = wd.snapshot()
    wd.beat("j1", 99.0)  # post-snapshot divergence must not leak back
    wd2 = Watchdog()
    wd2.restore(snap)
    assert wd2._last == {"j1": 9.0, "j2": 9 * 1.7}
    assert wd2._beats == {"j1": 10, "j2": 10}
    # the continued instance moved on; the restored one holds the snapshot
    assert wd._last["j1"] == 99.0 and wd._beats["j1"] == 11
