"""The fleet screen: ``FleetDetect.tick`` over every stream of a fleet, with
the screening backend auto-selected (the Pallas ``bocd_step`` kernel on a
TPU), as the control plane builds it.

Closed loop: each tick's samples are generated (outside the timed call),
then ``tick`` runs on them; the next tick starts when it returns. Set-up
feeds ``setup_ticks`` healthy ticks, which warm the screen, compile the
kernel at the fleet's one shape and mature the drift and long-horizon
screens, so every screen is live in the window. Faults start after set-up.

``correct`` compares what the timed path produced (every confirmed flag,
and the final run-length posterior read through the backend's own
interface) with the plain reference fed the same samples. After the
window closes, ``tail_ticks`` more ticks (untimed) let both sides flag
the changes that happened up to the window's last tick.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import harness
from chipbench.harness import Check, Job, Outcome
from chipbench.reference import fleet_screen as reference
from chipbench.tracing import WINDOW_SPAN
from chipbench.generators import FleetTraffic

#: FleetDetect's constructor arguments that the configuration states
DETECT_FIELDS = ("hazard", "cp_threshold", "verify_threshold", "verify_window",
                 "verify_windows", "drift_ref", "drift_ref_window",
                 "drift_cur_window", "drift_hold", "ewma_span", "ewma_min_age",
                 "ewma_hold", "warmup", "min_gap", "recent_window",
                 "history_cap", "max_hypotheses", "max_cohorts", "adapt_every")
#: the backend's priors that the configuration states
PRIOR_FIELDS = ("kappa0", "alpha0", "beta0", "truncation", "max_hypotheses")


class RecordingFactory:
    """A screening-backend factory that makes what the auto-selected one
    makes and keeps every instance, so the run can read their posteriors
    afterwards through the backend interface."""

    def __init__(self, inner) -> None:
        self.inner = inner
        self.name = getattr(inner, "name", "auto")
        self.made: list = []

    def make(self, n_series: int, **kwargs):
        inst = self.inner.make(n_series, **kwargs)
        self.made.append(inst)
        return inst


def detector_kwargs(cfg: dict) -> dict:
    kw = {f: cfg[f] for f in DETECT_FIELDS}
    kw["verify_windows"] = tuple(kw["verify_windows"])
    return kw


def run(job: Job) -> Outcome:
    import jax

    from repro.core import bocd
    from repro.core.detector import FleetDetect

    cfg, traffic = job.config(), job.traffic("fleet")
    n = cfg["streams"]
    factory = RecordingFactory(bocd.select_backend("auto"))
    fleet = FleetDetect(n_workers=n, backend=factory, **detector_kwargs(cfg))
    gen = FleetTraffic(traffic, n, job.seed)
    flags: list[tuple[int, int, int]] = []

    def tick(t: int, x: np.ndarray) -> None:
        for f in fleet.tick(x):
            flags.append((t, f.worker, f.change_point.index))

    setup_ticks = traffic["setup_ticks"]
    for t in range(setup_ticks):
        tick(t, gen.sample(t))
    _check_priors(factory.made, cfg)

    mark = job.compiles.mark() if job.compiles else None
    tick_s: list[float] = []
    gen_s = 0.0
    job.tracer.start()
    with job.tracer.span(WINDOW_SPAN):
        t0 = time.monotonic()
        setup_s = t0 - job.t_start
        t = setup_ticks
        while True:
            g0 = time.monotonic()
            with job.tracer.span("chipbench.generate"):
                x = gen.sample(t)
            g1 = time.monotonic()
            with job.tracer.span("chipbench.tick"):
                tick(t, x)
            g2 = time.monotonic()
            gen_s += g1 - g0
            tick_s.append(g2 - g1)
            t += 1
            if g2 - t0 >= job.seconds:
                break
        window_s = time.monotonic() - t0
    job.tracer.stop()
    in_window = job.compiles.since(mark) if mark else {}
    peak = harness.peak_bytes(jax.devices()[0])
    # Answers due in the window may come after it: feed the tail, untimed,
    # and compare the changes that happened up to the window's last tick.
    last = t - 1
    for t in range(t, t + traffic["tail_ticks"]):
        tick(t, gen.sample(t))
    if len(factory.made) != 1:
        raise RuntimeError(f"expected one screening batch, the screen made {len(factory.made)}")
    batch = factory.made[0]
    p_prog = np.asarray(batch.p_recent_change(cfg["recent_window"]), np.float64)
    rl_prog = np.asarray(batch.map_runlength(), np.int64)
    n_ticks = len(tick_s)
    total_ticks = last + 1 + traffic["tail_ticks"]
    p_late = np.asarray(batch.p_recent_change(late_window(total_ticks)), np.float64)
    counters = {
        "ticks": n_ticks,
        "streams": n,
        "window_s": window_s,
        "tick_s_sum": float(sum(tick_s)),
        "generate_s": gen_s,
        "slots": cfg["max_hypotheses"],
        "compiles_in_window": in_window.get("compiles", 0),
        "flags_in_window": sum(1 for f in flags if setup_ticks <= f[0] <= last),
        "backend": type(batch).__name__,
    }
    harness.log(f"setup_s={setup_s!r} window_s={window_s!r} ticks={n_ticks} "
                f"generate_s={gen_s!r} backend={counters['backend']} "
                f"flags={len(flags)} window_compiles={in_window} peak_bytes={peak}")
    trace_events = job.tracer.events()
    del fleet, batch, factory
    gc.collect()

    t_ref = time.monotonic()
    screen = reference.Screen(n, cfg)
    for i in range(total_ticks):
        screen.tick(gen.sample(i))
    harness.log(f"reference_s={time.monotonic() - t_ref!r} reference_flags={len(screen.flags)}")
    _log_differences(flags, p_prog, rl_prog, screen, gen)
    limits = job.limits()
    checks = [Check(name, value, float(limits[name]))
              for name, value in readings(flags, p_late, screen, last).items()]
    screen.close()
    ms = np.asarray(tick_s) * 1e3
    return Outcome(
        setup_s=setup_s, window_s=window_s, attempted=n_ticks, failed=0,
        end_to_end={
            "screen_stream_updates_per_s": n * n_ticks / window_s,
            "screen_tick_p99_ms": float(np.percentile(ms, 99)),
        },
        counters=counters, memory_peak_bytes=peak, checks=checks,
        trace_events=trace_events,
    )


def _log_differences(flags, p_prog, rl_prog, screen, gen: FleetTraffic) -> None:
    """Where the program and the reference part: the flags only one has, and
    the stream of the widest posterior gap with its fault, if any."""
    only_prog = sorted(set(flags) - set(screen.flags))
    only_ref = sorted(set(screen.flags) - set(flags))
    p_ref, rl_ref = screen.post.p_recent(), screen.post.map_runlength()
    w = int(np.argmax(np.abs(p_prog - p_ref)))
    fault = ""
    hit = np.flatnonzero(gen.faulty == w)
    if hit.size:
        k = int(hit[0])
        fault = (f" faulty: onset {int(gen.onset[k])} severity {gen.severity[k]!r} "
                 f"ramp {int(gen.ramp[k])}")
    harness.log(f"flags (tick, stream, index) only in the program {only_prog[:40]}, "
                f"only in the reference {only_ref[:40]}")
    harness.log(f"widest Pr(recent) gap {abs(p_prog[w] - p_ref[w])!r} at stream {w}: "
                f"p {p_prog[w]!r} vs {p_ref[w]!r}, MAP run length {int(rl_prog[w])} vs "
                f"{int(rl_ref[w])}{fault}; MAP run lengths differ on a share "
                f"{float(np.mean(rl_prog != rl_ref))!r}")


def _check_priors(made: list, cfg: dict) -> None:
    for inst in made:
        have = {f: getattr(inst, f) for f in PRIOR_FIELDS}
        want = {f: cfg[f] for f in PRIOR_FIELDS}
        if have != want:
            raise ValueError(f"the screening backend's priors {have} are not "
                             f"the configuration's {want}")


def readings(flags, p_late, screen: reference.Screen, last: int) -> dict:
    """The compared numbers: the share of streams that one side flags for a
    change up to tick ``last`` and the other does not (which streams a
    monitor reports slow), and the mean over streams of the gap between
    the two final posteriors' mass on a change in the later half of the
    run, Pr(run length <= ``late_window(screen.t)``); ``p_late`` is the
    program's.

    Not compared, because rounding alone moves them (see PERF.md): the
    change index and tick of each flag, which slide along a gradual onset
    between float32 and float64; the MAP run length, which the shared
    frontier's eviction ties turn on up to two fifths of the streams; and
    the widest gap of a posterior over thousands of streams, where the
    hard mass truncation lets one stream take another path."""
    ref_late = screen.post.p_within(late_window(screen.t))
    flagged_prog = {f[1] for f in flags if f[2] <= last}
    flagged_ref = {f[1] for f in screen.flags if f[2] <= last}
    return {
        "flag_disagreement_share": len(flagged_prog ^ flagged_ref) / screen.n,
        "late_change_mass_gap": float(np.mean(np.abs(np.asarray(p_late) - ref_late))),
    }


def late_window(ticks: int) -> int:
    """The run lengths of a change in the later half of ``ticks`` ticks."""
    return ticks // 2
