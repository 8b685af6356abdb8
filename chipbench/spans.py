"""The program's wall-clock spans in a traced window, for the per-layer
metrics that read them.

The program marks its host phases with ``jax.profiler`` annotations
(``repro.obs.host``: ``fleet.*``, ``bocd.*``, ``train.*``,
``controlplane.*``). They land in the same ``.xplane.pb`` as the device ops
and the harness's own spans (``chipbench.*``), on one clock, and carry
their ids (the tick or step number, and where a span reports a program
counter, its value) as the event's stats.

* :func:`load` reads the newest trace under a directory: every chip's ops,
  the window, every span, and each program span's ids.
* :func:`reduce_spans` turns that into numbers per span name: count,
  inclusive and self seconds in the window, and the device idle each is the
  innermost span over. Pure, so hand-made and recorded traces check it on
  the CPU.
* :func:`of_run` gives a reader the spans of the run it reads: the newest
  trace under the harness's trace directory, taken only if its window is
  the one the reduced trace (``ctx.trace``) holds, read once a run and kept
  on the reader context. On a program without spans only the harness's are
  there, and the readers leave their metrics out.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

from chipbench import harness, tracing

#: the first word of each program span's name (``fleet.tick``, ...)
PROGRAM_LAYERS = ("fleet", "bocd", "train", "controlplane")
#: the first word of the harness's own spans
HARNESS_LAYER = "chipbench"
#: where ``run.py`` has the profiler write each cell's trace
TRACE_ROOT = os.path.join(harness.WORK_DIR, "trace")
#: the reader context's attribute that keeps the run's spans once read
CTX_ATTR = "program_spans"


def is_program_span(name: str) -> bool:
    return "." in name and name.partition(".")[0] in PROGRAM_LAYERS


def is_span(name: str) -> bool:
    """A program span or one of the harness's (not its window)."""
    return is_program_span(name) or (
        name.startswith(HARNESS_LAYER + ".") and name != tracing.WINDOW_SPAN)


def load(trace_dir: str) -> dict:
    """The newest trace under ``trace_dir`` as ``{"device": {chip: [[name,
    start_ns, dur_ns], ...]}, "host": [[name, start_ns, dur_ns], ...],
    "span_ids": [[name, start_ns, {id: value}], ...]}``; ``host`` holds the
    window and the spans (:func:`is_span`) only, ``span_ids`` the program
    spans'."""
    import warnings

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    device: dict[str, list] = {}
    host: list = []
    span_ids: list = []
    for plane in pd.planes:
        for line in plane.lines:
            if (plane.name.startswith("/device:TPU:")
                    and line.name == tracing.DEVICE_OPS_LINE):
                device[plane.name] = [
                    [tracing.short_name(ev.name), float(ev.start_ns), float(ev.duration_ns)]
                    for ev in line.events
                ]
            elif plane.name == "/host:CPU":
                for ev in line.events:
                    name = ev.name
                    if name != tracing.WINDOW_SPAN and not is_span(name):
                        continue
                    host.append([name, float(ev.start_ns), float(ev.duration_ns)])
                    if is_program_span(name):
                        # jaxlib warns that the stats' builtin type has no
                        # __module__ as it builds that type: nothing to act on
                        with warnings.catch_warnings():
                            warnings.simplefilter("ignore", DeprecationWarning)
                            span_ids.append([name, float(ev.start_ns), dict(ev.stats)])
    return {"device": device, "host": host, "span_ids": span_ids}


@dataclasses.dataclass
class SpanStats:
    """One span name's share of a window (seconds)."""

    #: spans that start inside the window
    count: int = 0
    #: their time inside the window, children included
    inclusive_s: float = 0.0
    #: time inside the window in which this span is the innermost span
    self_s: float = 0.0
    #: device idle time in which this span is the innermost span, divided
    #: by the number of chips
    idle_s: float = 0.0


def _innermost(spans: list[tuple[float, float, str]], w0: float, w1: float):
    """Cut ``[w0, w1]`` into ``(start, end, name)`` pieces, each named by
    the innermost span over it (``None`` where no span is). Spans nest, as
    they do on one thread; one that outlasts its parent is cut at the
    parent's end."""
    pieces: list[tuple[float, float, str | None]] = []
    stack: list[tuple[float, str]] = []  # (end, name) of open spans
    cursor = w0
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            if end > cursor:
                pieces.append((cursor, end, top))
                cursor = end
        if s > cursor:
            pieces.append((cursor, s, stack[-1][1] if stack else None))
            cursor = s
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    while stack:
        end, top = stack.pop()
        if end > cursor:
            pieces.append((cursor, end, top))
            cursor = end
    if w1 > cursor:
        pieces.append((cursor, w1, None))
    return pieces


def reduce_spans(events: dict) -> dict:
    """Per span name (:func:`is_span`: the program's and the harness's),
    its :class:`SpanStats` over the harness's window span, clipped to it.
    Device idle is the window less the union of each chip's ops, as
    :func:`tracing.reduce` counts it. The window's time under no span is
    keyed ``None`` (``self_s`` and ``idle_s`` alone)."""
    w0, w1 = tracing.window_bounds(events["host"])
    out: dict = defaultdict(SpanStats)
    clipped = []
    for name, s, d in events["host"]:
        if not is_span(name):
            continue
        if w0 <= s < w1:
            out[name].count += 1
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            out[name].inclusive_s += (b - a) * 1e-9
            clipped.append((a, b, name))
    pieces = _innermost(clipped, w0, w1)
    for a, b, name in pieces:
        out[name].self_s += (b - a) * 1e-9
    chips = events["device"]
    for ops in chips.values():
        busy = tracing._union([(max(s, w0), min(s + d, w1)) for _, s, d in ops
                               if min(s + d, w1) > max(s, w0)])
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2]) if ge > gs]
        j = 0
        for gs, ge in gaps:  # gaps and pieces both in time order
            while j < len(pieces) and pieces[j][1] <= gs:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < ge:
                a, b, name = pieces[k]
                out[name].idle_s += (min(b, ge) - max(a, gs)) * 1e-9 / len(chips)
                k += 1
    return dict(out)


@dataclasses.dataclass
class WindowSpans:
    """The spans of one traced window."""

    #: per span name, its :class:`SpanStats` (:func:`reduce_spans`)
    stats: dict
    #: ``(start_ns, {id: value})`` of each program span that starts in the
    #: window, by name, in time order
    ids: dict

    @classmethod
    def of(cls, events: dict) -> WindowSpans:
        w0, w1 = tracing.window_bounds(events["host"])
        ids: dict = defaultdict(list)
        for name, s, i in sorted(events.get("span_ids", ()), key=lambda x: x[1]):
            if w0 <= s < w1:
                ids[name].append((s, i))
        return cls(stats=reduce_spans(events), ids=dict(ids))

    def inclusive_s(self, *names: str) -> float | None:
        """Seconds under the spans ``names`` in the window, children
        included; None where none of them is in it."""
        found = [self.stats[n] for n in names if n in self.stats]
        return sum(s.inclusive_s for s in found) if found else None

    def id_sum(self, name: str, key: str) -> int | None:
        """The sum of id ``key`` over the window's ``name`` spans; None
        where no such span carries it."""
        vals = [i[key] for _, i in self.ids.get(name, ()) if key in i]
        return sum(vals) if vals else None

    def id_delta(self, name: str, key: str) -> int | None:
        """How far id ``key``, a program counter as of each ``name`` span's
        start, moved from the window's first such span to its last; None
        where fewer than two carry it."""
        vals = [i[key] for _, i in self.ids.get(name, ()) if key in i]
        return vals[-1] - vals[0] if len(vals) > 1 else None


def of_run(ctx) -> WindowSpans | None:
    """The spans of the run whose reader context is ``ctx``: None where the
    run was not traced or its trace is not on disk."""
    if getattr(ctx, "trace", None) is None:
        return None
    if not hasattr(ctx, CTX_ATTR):
        setattr(ctx, CTX_ATTR, _read_run(ctx.trace.window_s))
    return getattr(ctx, CTX_ATTR)


def _read_run(window_s: float) -> WindowSpans | None:
    try:
        events = load(TRACE_ROOT)
        w0, w1 = tracing.window_bounds(events["host"])
    except (FileNotFoundError, ValueError):
        return None
    if (w1 - w0) * 1e-9 != window_s:  # not this run's trace
        return None
    return WindowSpans.of(events)


def ms_per(ctx, unit: str, *names: str) -> float | None:
    """Milliseconds under the spans ``names`` in the run's window, children
    included, per ``ctx.counters[unit]`` (``ticks``, ``steps``); None where
    none of them is there."""
    run, n = of_run(ctx), ctx.counters.get(unit)
    seconds = run.inclusive_s(*names) if run else None
    if seconds is None or not n:
        return None
    return seconds / n * 1e3
