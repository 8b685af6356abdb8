"""Wall-clock spans and counters inside the program (``repro.obs.host``):
they land on the profiler's timeline with their tick or step ids, nest
where the work happens, change no output, and cost nothing to a process
that never imports JAX. ``PallasBOCD`` counts the bytes that cross."""
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from repro.cluster.injector import FailSlowInjector
from repro.cluster.simulator import JobSpec, ModelSpec, TrainingSimulator
from repro.cluster.spec import ClusterSpec
from repro.configs.base import get_config
from repro.controlplane import ControlPlane, MitigationResult
from repro.core import bocd
from repro.core.detector import FleetDetect
from repro.core.events import Strategy
from repro.data.pipeline import DataConfig
from repro.kernels.bocd_step import PallasBOCD
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import FalconTrainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from chipbench import harness, spans, tracing  # noqa: E402

FLEET_SPANS = {"fleet.tick", "fleet.ewma", "fleet.warm", "fleet.bocd_update",
               "fleet.posterior", "fleet.flags", "fleet.drift", "fleet.retune",
               "fleet.consolidate"}
TRAIN_SPANS = {"train.step", "train.batch", "train.dispatch", "train.loss_sync",
               "train.simulate", "controlplane.observe", "train.mitigate"}


def _profiled(trace_dir, fn):
    """``fn()`` under the profiler, and the spans of its trace."""
    jax.profiler.start_trace(str(trace_dir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    ev = spans.load(str(trace_dir))
    found = [(n, s, s + d) for n, s, d in ev["host"] if spans.is_program_span(n)]
    ids = {(n, s): i for n, s, i in ev["span_ids"]}
    return out, found, ids


def _parent(spans, child, name):
    """The innermost span called ``name`` around ``child``."""
    _, s, e = child
    around = [x for x in spans if x[0] == name and x[1] <= s and e <= x[2]]
    return min(around, key=lambda x: x[2] - x[1]) if around else None


def _run_fleet(backend, n=256, ticks=90, slow=3, onset=60):
    """A screen with churn and adaptive retuning on, so every phase runs:
    a join at tick 20 warms a second cohort, which ``max_cohorts=1``
    consolidates; one stream steps up x1.5 at ``onset``."""
    fleet = FleetDetect(n_workers=n, backend=backend, max_cohorts=1,
                        adapt_every=25, max_hypotheses=8)
    rng = np.random.default_rng(7)
    flags = []
    for t in range(ticks):
        if t == 20:
            fleet.add_worker()
        x = 1.0 + 0.01 * rng.standard_normal(fleet.n_workers)
        if t >= onset:
            x[slow] *= 1.5
        flags += [(t, f.worker, f.change_point) for f in fleet.tick(x)]
    post = [np.asarray(c.batch.p_recent_change(2)) for c in fleet._cohorts]
    return flags, post, fleet


def test_fleet_screen_is_identical_under_the_profiler_and_spans_nest(tmp_path):
    want_flags, want_post, _ = _run_fleet("batched")
    (flags, post, fleet), spans, ids = _profiled(
        tmp_path, lambda: _run_fleet("batched"))
    assert flags == want_flags and any(w == 3 for _, w, _ in flags)
    assert all(np.array_equal(a, b) for a, b in zip(post, want_post))
    assert fleet.verify_attempts >= fleet.verify_confirmed >= 1
    assert FLEET_SPANS <= {n for n, _, _ in spans}
    ticks = [x for x in spans if x[0] == "fleet.tick"]
    assert sorted(ids[(n, s)]["tick"] for n, s, _ in ticks) == list(range(90))
    for x in spans:
        if x[0] == "fleet.tick":
            continue
        tick = _parent(spans, x, "fleet.tick")
        assert tick is not None, x
        assert ids[(x[0], x[1])]["tick"] == ids[(tick[0], tick[1])]["tick"], x


def test_backend_copies_are_spans_inside_the_screen(tmp_path):
    backend = bocd.PallasScreening(interpret=True)
    want_flags, want_post, _ = _run_fleet(backend, n=128, ticks=70, onset=50)
    (flags, post, _), spans, ids = _profiled(
        tmp_path, lambda: _run_fleet(backend, n=128, ticks=70, onset=50))
    assert flags == want_flags and flags
    assert all(np.array_equal(a, b) for a, b in zip(post, want_post))
    names = {n for n, _, _ in spans}
    assert {"bocd.upload", "bocd.readback"} <= names
    last_tick = max(e for n, _, e in spans if n == "fleet.tick")
    for x in spans:
        if x[0].startswith("bocd.") and x[1] < last_tick:  # not the final read
            assert _parent(spans, x, "fleet.tick") is not None, x
            assert "step" in ids[(x[0], x[1])], x
    # the posterior's reads fall inside its phase
    reads = [x for x in spans if x[0] == "bocd.readback"]
    assert any(_parent(spans, x, "fleet.posterior") for x in reads)
    assert any(_parent(spans, x, "fleet.flags") for x in reads)


def test_readers_see_the_backend_and_screen_counters_in_a_real_trace(tmp_path):
    """A window of ticks traced as the harness traces it: the read-backs'
    ``bytes`` add up to ``d2h_bytes``'s delta, the ticks carry the verify
    counters, and every fleet reader finds its metric."""
    backend = bocd.PallasScreening(interpret=True)
    n, ticks, window = 128, 70, range(40, 70)
    fleet = FleetDetect(n_workers=n, backend=backend, max_hypotheses=8)
    rng = np.random.default_rng(3)
    xs = 1.0 + 0.01 * rng.standard_normal((ticks, n))
    xs[window.start + 4:, 5] *= 1.5
    for x in xs[:window.start]:
        fleet.tick(x)
    (batch,) = [c.batch for c in fleet._cohorts]
    before = (batch.d2h_bytes, fleet.verify_attempts, fleet.verify_confirmed)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        for t in window:
            if t == window.stop - 1:
                last = (fleet.verify_attempts, fleet.verify_confirmed)
            with jax.profiler.TraceAnnotation("chipbench.tick"):
                fleet.tick(xs[t])
    jax.profiler.stop_trace()
    ev = spans.load(str(tmp_path))
    run = spans.WindowSpans.of(ev)
    assert run.id_sum("bocd.readback", "bytes") == batch.d2h_bytes - before[0] > 0
    assert run.id_delta("fleet.tick", "verify_attempts") == last[0] - before[1] > 0
    assert run.id_delta("fleet.tick", "verify_confirmed") == last[1] - before[2]
    w0, w1 = tracing.window_bounds(ev["host"])
    ctx = types.SimpleNamespace(counters={"ticks": len(window)}, peaks=None, config={},
                                trace=types.SimpleNamespace(window_s=(w1 - w0) * 1e-9))
    setattr(ctx, spans.CTX_ATTR, run)
    got = {m: harness.load_reader(m)(ctx) for m in (
        "screen_posterior_ms_per_tick", "screen_flags_ms_per_tick",
        "screen_drift_ms_per_tick", "screen_readback_ms_per_tick",
        "screen_d2h_bytes_per_tick", "screen_verify_yield")}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["screen_d2h_bytes_per_tick"] == (batch.d2h_bytes - before[0]) / len(window)


def _train(tmp_path):
    cfg = get_config("falcon-demo-100m").smoke()
    data = DataConfig(seq_len=32, global_batch=4, slots=2, dp_groups=2)
    sim = TrainingSimulator(
        cluster=ClusterSpec(n_nodes=2, gpus_per_node=4),
        job=JobSpec(model=ModelSpec(layers=8, hidden=1024, seq_len=512,
                                    vocab=32000),
                    tp=2, dp=2, pp=2, micro_batches=8),
    )
    trainer = FalconTrainer(
        cfg=cfg, data=data, opt_cfg=AdamWConfig(total_steps=2),
        perf_model=sim, injector=FailSlowInjector([]), falcon_enabled=True,
        ckpt_dir=str(tmp_path),
    )
    observe = trainer.control.observe

    def observe_and_ignore(job_id, iter_time, now):
        # One dispatched strategy on the second step, so the mitigation
        # phase runs: S1 leaves the JAX-side state as it is.
        out = observe(job_id, iter_time, now)
        if len(trainer.history) == 1:
            out.append(MitigationResult(job_id=job_id, time=now,
                                        strategy=Strategy.IGNORE, applied=True))
        return out

    trainer.control.observe = observe_and_ignore
    hist = trainer.run(2)
    params = [np.asarray(x) for x in jax.tree.leaves(trainer.params)]
    return [(r.loss, r.iter_time, r.strategy) for r in hist], params, hist


def test_trainer_is_identical_under_the_profiler_and_spans_nest(tmp_path):
    want_hist, want_params, _ = _train(tmp_path / "a")
    (hist, params, records), spans, ids = _profiled(
        tmp_path / "trace", lambda: _train(tmp_path / "b"))
    assert hist == want_hist and hist[1][2] == "IGNORE"
    assert all(np.array_equal(a, b) for a, b in zip(params, want_params))
    assert TRAIN_SPANS <= {n for n, _, _ in spans}
    steps = [x for x in spans if x[0] == "train.step"]
    assert sorted(ids[(n, s)]["step_num"] for n, s, _ in steps) == [0, 1]
    for x in spans:
        if x[0] == "train.step":
            continue
        step = _parent(spans, x, "train.step")
        assert step is not None, x
        assert ids[(x[0], x[1])]["step"] == ids[(step[0], step[1])]["step_num"], x
    # dispatch and loss sync cover what StepRecord.measured times
    timed = sum(e - s for n, s, e in spans
                if n in ("train.dispatch", "train.loss_sync")) * 1e-9
    measured = sum(r.measured for r in records)
    assert timed == pytest.approx(measured, rel=0.05)


def test_control_plane_tick_is_a_span_with_its_tick(tmp_path):
    def run():
        plane = ControlPlane(screening_backend="batched")
        for j in range(3):
            plane.register_job(f"job{j}", TrainingSimulator(
                cluster=ClusterSpec(n_nodes=1, gpus_per_node=4),
                job=JobSpec(model=ModelSpec(layers=4, hidden=512, seq_len=256,
                                            vocab=1000), tp=2, dp=2, pp=1,
                            micro_batches=4)))
        for t in range(12):
            plane.tick([1.0, 1.0, 1.0], float(t))

    _, spans, ids = _profiled(tmp_path, run)
    ticks = [x for x in spans if x[0] == "controlplane.tick"]
    assert sorted(ids[(n, s)]["tick"] for n, s, _ in ticks) == list(range(12))
    for x in spans:
        if x[0] == "fleet.tick":
            assert _parent(spans, x, "controlplane.tick") is not None


def test_pallas_backend_counts_the_bytes_that_cross():
    k, b = 8, 128
    be = PallasBOCD(b, max_hypotheses=k, interpret=True)
    priors = 5 * 4  # hazard, kappa0, alpha0, beta0, truncation: the params row
    assert be.h2d_bytes == priors + b * 4 + k * b * 4  # and mu0 and log_r
    assert (be.d2h_bytes, be.host_reads) == (0, 0)
    rng = np.random.default_rng(0)
    for i in range(3):
        p0 = be.update(1.0 + 0.01 * rng.standard_normal(b))
    assert be.h2d_bytes == priors + b * 4 + k * b * 4 + 3 * b * 4  # x alone
    assert (be.d2h_bytes, be.host_reads) == (0, 0)  # p0 stays on the device
    assert isinstance(p0, jax.Array) and p0.shape == (b,)
    h2d = be.h2d_bytes
    be.p_recent_change(2)
    assert be.d2h_bytes == b * 4 and be.host_reads == 1
    assert be.h2d_bytes == h2d + 4  # the window, once per value
    be.map_runlength()
    assert be.d2h_bytes == 2 * b * 4 and be.host_reads == 2
    # asked again unchanged: served from JAX's host copy, not counted
    be.map_runlength()
    be.p_recent_change(2)
    assert be.d2h_bytes == 2 * b * 4 and be.host_reads == 2
    _ = be.n_hypotheses  # not per tick: reads the whole log_r
    assert be.d2h_bytes == 2 * b * 4 + k * b * 4 and be.host_reads == 3
    be.update(np.ones(b))
    be.map_runlength()
    be.p_recent_change(2)
    assert be.d2h_bytes == 4 * b * 4 + k * b * 4 and be.host_reads == 5
    assert be.h2d_bytes == h2d + 4 + b * 4
    h2d = be.h2d_bytes
    be.retune(hazard=0.02)
    assert be.h2d_bytes == h2d + priors
    be.take_columns(np.arange(b // 2))
    assert be.h2d_bytes == h2d + priors + (b // 2) * 4  # the int32 index


def test_spans_are_free_and_import_no_jax_without_jax():
    code = (
        "import sys\n"
        "import numpy as np\n"
        "import repro.controlplane, repro.core.detector\n"
        "from repro.core.detector import FleetDetect\n"
        "from repro.obs import host\n"
        "fleet = FleetDetect(n_workers=16, backend='batched')\n"
        "for t in range(20):\n"
        "    fleet.tick(np.ones(16))\n"
        "assert host.span('fleet.tick', tick=1) is host.span('x')\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_a_span_is_a_profiler_annotation_once_jax_is_imported():
    from repro.obs import host

    assert isinstance(host.span("fleet.tick", tick=3), jax.profiler.TraceAnnotation)
    assert isinstance(host.step("train.step", 3), jax.profiler.StepTraceAnnotation)
