"""The program's spans in a traced window (``chipbench.spans``) and the
per-layer metrics that read them (CPU only)."""
from __future__ import annotations

import json
import os
import types

import numpy as np
import pytest

from chipbench import harness, spans, tracing

TESTDATA = os.path.join(harness.BENCH_DIR, "testdata")
MS = 1e6  # ns per ms


def _harness_trace() -> dict:
    """The harness's spans alone, as a program without spans leaves them."""
    return {
        "device": {"/device:TPU:0": [
            ["fusion.1", 1 * MS, 1.5 * MS],
            ["bocd_step.1", 2 * MS, 2 * MS],
            ["copy.2", 6 * MS, 1 * MS],
        ]},
        "host": [
            [tracing.WINDOW_SPAN, 0.0, 10 * MS],
            ["chipbench.tick", 0.0, 5 * MS],
            ["chipbench.generate", 4 * MS, 2 * MS],  # outlasts chipbench.tick
        ],
    }


def _span_trace() -> dict:
    """Window 2-20 ms. One tick starts before the window and one ends after
    it; a device op runs under a read-back and one under the second tick."""
    return {
        "device": {"/device:TPU:0": [
            ["bocd_step.1", 3.5 * MS, 1 * MS],
            ["copy.1", 12.5 * MS, 1.5 * MS],
            ["outside", 21 * MS, 1 * MS],
        ]},
        "host": [
            [tracing.WINDOW_SPAN, 2 * MS, 18 * MS],
            ["chipbench.tick", 0.0, 10 * MS],
            ["$detector.py:1 tick", 1 * MS, 8 * MS],  # not a span
            ["fleet.tick", 1 * MS, 8 * MS],
            ["fleet.posterior", 2.5 * MS, 2.5 * MS],
            ["bocd.readback", 3 * MS, 1 * MS],
            ["fleet.drift", 6 * MS, 2 * MS],
            ["chipbench.generate", 10 * MS, 2 * MS],
            ["chipbench.tick", 12 * MS, 9 * MS],
            ["fleet.tick", 13 * MS, 12 * MS],
        ],
        "span_ids": [
            ["fleet.tick", 1 * MS, {"tick": 7, "verify_attempts": 10, "verify_confirmed": 1}],
            ["fleet.posterior", 2.5 * MS, {"tick": 7}],
            ["bocd.readback", 3 * MS, {"step": 7, "bytes": 1_048_576}],
            ["fleet.drift", 6 * MS, {"tick": 7}],
            ["fleet.tick", 13 * MS, {"tick": 8, "verify_attempts": 18, "verify_confirmed": 3}],
        ],
    }


def test_reduce_spans_of_a_hand_made_trace():
    got = spans.reduce_spans(_span_trace())
    want = {  # name: (count, inclusive, self, idle) in ms
        "chipbench.tick": (1, 16, 2, 1.5),
        "fleet.tick": (1, 14, 9.5, 8.5),
        "fleet.posterior": (1, 2.5, 1.5, 1.0),
        "bocd.readback": (1, 1, 1, 0.5),
        "fleet.drift": (1, 2, 2, 2),
        "chipbench.generate": (1, 2, 2, 2),
    }
    assert set(got) == set(want)  # every instant is under some span
    for name, (count, incl, own, idle) in want.items():
        s = got[name]
        assert s.count == count, name
        assert s.inclusive_s == pytest.approx(incl * 1e-3), name
        assert s.self_s == pytest.approx(own * 1e-3), name
        assert s.idle_s == pytest.approx(idle * 1e-3), name
    assert sum(s.self_s for s in got.values()) == pytest.approx(18e-3)
    summary = tracing.reduce(_span_trace())
    assert sum(s.idle_s for s in got.values()) == pytest.approx(
        summary.window_s - summary.busy_s)


def test_reduce_spans_keys_idle_under_no_span_as_none():
    got = spans.reduce_spans(_harness_trace())
    assert not any(spans.is_program_span(k) for k in got if k)
    # chipbench.generate (4-6 ms) outlasts chipbench.tick (0-5) and is cut
    # at 5: no span is over 5-10, idle in 5-6 and 7-10
    assert got[None].self_s == pytest.approx(5e-3)
    assert got[None].idle_s == pytest.approx(4e-3)


def test_a_span_that_outlasts_its_parent_is_cut_at_the_parent():
    ev = {"device": {"/device:TPU:0": [["op.1", 30 * MS, 1 * MS]]},
          "host": [[tracing.WINDOW_SPAN, 0.0, 20 * MS],
                   ["train.step", 0.0, 10 * MS],
                   ["train.batch", 5 * MS, 10 * MS]]}
    got = spans.reduce_spans(ev)
    assert got["train.batch"].inclusive_s == pytest.approx(10e-3)
    assert got["train.batch"].self_s == pytest.approx(5e-3)
    assert got["train.step"].self_s == pytest.approx(5e-3)
    assert got[None].self_s == pytest.approx(10e-3)


def test_window_spans_keep_the_ids_of_spans_that_start_in_the_window():
    run = spans.WindowSpans.of(_span_trace())
    assert [i["tick"] for _, i in run.ids["fleet.tick"]] == [8]  # tick 7 starts before
    assert run.id_sum("bocd.readback", "bytes") == 1_048_576
    assert run.id_sum("fleet.drift", "bytes") is None
    assert run.id_delta("fleet.tick", "verify_attempts") is None  # one tick
    assert run.inclusive_s("fleet.drift", "fleet.posterior") == pytest.approx(4.5e-3)
    assert run.inclusive_s("fleet.flags") is None


SPAN_READERS = {
    # metric: its value on _span_trace at 2 window ticks / steps
    "screen_posterior_ms_per_tick": 1.25,
    "screen_flags_ms_per_tick": None,  # no fleet.flags span in that trace
    "screen_drift_ms_per_tick": 1.0,
    "screen_readback_ms_per_tick": 0.5,
    "train_batch_ms_per_step": None,
    "train_monitor_ms_per_step": None,
}
ID_READERS = {
    # metric: its value on _span_trace with the window widened to 0-30 ms,
    # so that both ticks start in it
    "screen_d2h_bytes_per_tick": 524_288,
    "screen_verify_yield": 25.0,
}


def _ctx(events, counters):
    """A reader context whose run's spans are ``events``'."""
    w0, w1 = tracing.window_bounds(events["host"])
    ctx = types.SimpleNamespace(counters=counters, peaks=None, config={},
                                trace=types.SimpleNamespace(window_s=(w1 - w0) * 1e-9))
    setattr(ctx, spans.CTX_ATTR, spans.WindowSpans.of(events))
    return ctx


@pytest.mark.parametrize("metric", sorted(SPAN_READERS) + sorted(ID_READERS))
def test_new_readers_leave_their_metric_out_without_program_spans(metric):
    """On a program without spans, or a run that was not traced, every
    reader returns None and raises nothing."""
    read = harness.load_reader(metric)
    assert read(_ctx(_harness_trace(), {"ticks": 2, "steps": 2})) is None
    untraced = types.SimpleNamespace(counters={"ticks": 2, "steps": 2}, trace=None,
                                     peaks=None, config={})
    assert read(untraced) is None


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_span_readers_per_tick(metric):
    got = harness.load_reader(metric)(_ctx(_span_trace(), {"ticks": 2, "steps": 2}))
    want = SPAN_READERS[metric]
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("metric", sorted(ID_READERS))
def test_id_readers(metric):
    ev = _span_trace()
    ev["host"][0] = [tracing.WINDOW_SPAN, 0.0, 30 * MS]
    got = harness.load_reader(metric)(_ctx(ev, {"ticks": 2}))
    assert got == pytest.approx(ID_READERS[metric])


def test_of_run_reads_the_newest_trace_once_and_only_if_its_window_matches(
        tmp_path, monkeypatch):
    calls = []

    def load(trace_dir):
        calls.append(trace_dir)
        return _span_trace()

    monkeypatch.setattr(spans, "load", load)
    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    ctx = types.SimpleNamespace(counters={"ticks": 2},
                                trace=types.SimpleNamespace(window_s=18 * MS * 1e-9))
    assert spans.ms_per(ctx, "ticks", "fleet.posterior") == pytest.approx(1.25)
    assert spans.ms_per(ctx, "ticks", "bocd.readback") == pytest.approx(0.5)
    assert calls == [str(tmp_path)]
    other = types.SimpleNamespace(counters={"ticks": 2},
                                  trace=types.SimpleNamespace(window_s=17e-3))
    assert spans.of_run(other) is None  # another run's window
    monkeypatch.setattr(spans, "load", _raise_not_found)
    fresh = types.SimpleNamespace(counters={}, trace=types.SimpleNamespace(window_s=1.0))
    assert spans.of_run(fresh) is None  # no trace on disk


def _raise_not_found(trace_dir):
    raise FileNotFoundError(trace_dir)


def test_reduce_spans_of_a_recorded_chip_trace():
    """The span reduction against a plain recount of a recorded window with
    the program's spans: on a 100 ns grid each instant goes to the shortest
    span over it, and idle instants are those no device op covers. (The
    file is not a ``trace_*`` one: many of its ops last under 20 ns, which
    that test's busy-time grid rounds out to 100-200 ns, beyond its 2 %
    tolerance.)"""
    with open(os.path.join(TESTDATA, "spans_v5e_fleet_screen.json")) as f:
        ev = json.load(f)
    got = spans.reduce_spans(ev)
    assert {"fleet.tick", "fleet.posterior", "bocd.readback", "bocd.upload",
            "chipbench.tick"} <= set(got)
    w0, w1 = tracing.window_bounds(ev["host"])
    n = int((w1 - w0) / 100)
    label = np.full(n, -1)
    found = sorted((x for x in ev["host"] if spans.is_span(x[0])), key=lambda x: -x[2])
    names = sorted({x[0] for x in found})
    for name, s, d in found:  # shorter spans paint over longer ones
        lo, hi = max(0, int(round((s - w0) / 100))), min(n, int(round((s + d - w0) / 100)))
        label[lo:hi] = names.index(name)
    busy = np.zeros(n, bool)
    (ops,) = ev["device"].values()
    for _, s, d in ops:
        lo, hi = max(0, int((s - w0) / 100)), min(n, int(np.ceil((s + d - w0) / 100)))
        busy[lo:hi] = True
    for i, name in enumerate(names):
        mine = label == i
        assert got[name].self_s == pytest.approx(mine.sum() * 100e-9, rel=0.02, abs=2e-6), name
        assert got[name].idle_s == pytest.approx((mine & ~busy).sum() * 100e-9,
                                                 rel=0.02, abs=2e-6), name
    s = tracing.reduce(ev)
    assert sum(v.idle_s for v in got.values()) == pytest.approx(s.window_s - s.busy_s, rel=1e-9)
    # every program span carries its tick (the screen) or step (the backend)
    for name, _, ids in ev["span_ids"]:
        assert ("tick" if name.startswith("fleet.") else "step") in ids, name
    # each whole tick reads p0, log_r and rl once (32 x 8,192 float32 slots,
    # 32 int32 run lengths); map_runlength's reads are served from JAX's
    # host copy and carry 0 bytes
    run = spans.WindowSpans.of(ev)
    by_step: dict = {}
    for _, ids in run.ids["bocd.readback"]:
        by_step.setdefault(ids["step"], []).append(ids["bytes"])
    assert len(by_step) >= 5
    for step, read in list(by_step.items())[1:-1]:
        assert sorted(b for b in read if b) == [128, 32_768, 1_048_576], step
    assert {0} >= {b for read in by_step.values() for b in read} - {128, 32_768, 1_048_576}
    attempts = [ids["verify_attempts"] for _, ids in run.ids["fleet.tick"]]
    assert attempts == sorted(attempts) and run.id_delta("fleet.tick", "verify_attempts") >= 0
