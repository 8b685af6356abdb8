"""From a profiler trace to the benchmark's device numbers.

Two halves:

* :func:`load_xplane` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
  into plain event lists: ``device`` ops per chip (the ``XLA Ops`` line of
  each ``/device:TPU:<n>`` plane) and ``host`` events (every line of
  ``/host:CPU``, the harness's own ``TraceAnnotation`` spans among them),
  as ``[name, start_ns, duration_ns]`` on the trace's one timeline.
* :func:`reduce` turns such lists into numbers: device busy time as the
  union of op intervals inside the window, idle share, a kernel's device
  time by its stable name, the ops that took most time, and idle gaps by
  what the host was doing in them. It is pure, so a small recorded trace
  (``testdata/``) checks it on the CPU.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

#: the harness's span around the whole measured window; its extent is the
#: traced window on the trace's timeline
WINDOW_SPAN = "chipbench.window"
#: the line of a device plane whose events are the executed XLA ops
DEVICE_OPS_LINE = "XLA Ops"


def load_xplane(trace_dir: str) -> dict:
    """Device ops and host events of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    device: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        for line in plane.lines:
            if plane.name.startswith("/device:TPU:") and line.name == DEVICE_OPS_LINE:
                device[plane.name] = [
                    [short_name(ev.name), float(ev.start_ns), float(ev.duration_ns)]
                    for ev in line.events
                ]
            elif plane.name == "/host:CPU":
                host.extend(
                    [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                    for ev in line.events
                )
    return {"device": device, "host": host}


def short_name(hlo: str) -> str:
    """An XLA op event is named by its whole HLO instruction; keep the
    instruction's name (``%bocd_step.1 = (...) custom-call(...)`` ->
    ``bocd_step.1``)."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def base_name(name: str) -> str:
    """An instruction's name without XLA's numeric suffix: the stable name
    a kernel keeps across compiles (``bocd_step.1`` -> ``bocd_step``)."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def _self_seconds(ops: list[tuple[str, float, float]]) -> dict[str, float]:
    """Seconds per op name excluding nested ops: a loop or call op on the
    ops line contains the ops of its body, which count for themselves."""
    out: dict[str, float] = defaultdict(float)
    stack: list[tuple[float, str]] = []  # (end, name) of open ancestors
    for name, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= (min(b, stack[-1][0]) - a) * 1e-9
        out[name] += (b - a) * 1e-9
        stack.append((b, name))
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class TraceSummary:
    """The reduced trace of one window (seconds, averaged over chips)."""

    window_s: float
    busy_s: float
    #: device seconds per op name (self time: nested ops excluded), summed
    #: over the chips and divided by their number
    op_s: dict[str, float]
    #: idle seconds inside the window by the host activity that covered
    #: them, divided by the number of chips
    idle_by_host_s: dict[str, float]
    n_chips: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, name: str) -> float:
        """Device seconds of every op whose stable name is ``name``."""
        return sum(v for k, v in self.op_s.items() if base_name(k) == name)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_host_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def window_bounds(host: list) -> tuple[float, float]:
    spans = [(s, s + d) for name, s, d in host if name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found {len(spans)}")
    return spans[0]


def _label_gaps(gaps: list[tuple[float, float]], host: list) -> list[str]:
    """For each gap, the most specific host event that covers its middle:
    the shortest one containing it, or ``idle (no host span)``. One sweep
    over events and gap middles, both in time order."""
    import heapq

    events = sorted(
        (s, s + d, name) for name, s, d in host if d > 0 and name != WINDOW_SPAN
    )
    order = sorted(range(len(gaps)), key=lambda i: gaps[i][0])
    labels = ["idle (no host span)"] * len(gaps)
    active: list[tuple[float, float, str]] = []  # (duration, end, name)
    j = 0
    for i in order:
        gs, ge = gaps[i]
        mid = 0.5 * (gs + ge)
        while j < len(events) and events[j][0] <= mid:
            s, e, name = events[j]
            heapq.heappush(active, (e - s, e, name))
            j += 1
        while active and active[0][1] < mid:
            heapq.heappop(active)  # ended before this middle, and before every later one
        if active:
            labels[i] = active[0][2]
    return labels


def reduce(events: dict) -> TraceSummary:
    """Reduce ``{"device": {chip: [[name, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...]}`` to a :class:`TraceSummary`
    over the harness's window span."""
    w0, w1 = window_bounds(events["host"])
    chips = events["device"]
    if not chips:
        raise ValueError("the trace holds no device ops")
    busy = 0.0
    op_s: dict[str, float] = defaultdict(float)
    gaps: list[tuple[float, float]] = []
    for ops in chips.values():
        clipped = []
        for name, s, d in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((name, a, b))
        for name, sec in _self_seconds(clipped).items():
            op_s[name] += sec
        merged = _union([(a, b) for _, a, b in clipped])
        busy += sum(b - a for a, b in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps.extend((gs, ge) for gs, ge in zip(edges[::2], edges[1::2]) if ge > gs)
    idle: dict[str, float] = defaultdict(float)
    for (gs, ge), label in zip(gaps, _label_gaps(gaps, events["host"])):
        idle[label] += (ge - gs) * 1e-9
    n = len(chips)
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy / n,
        op_s={k: v / n for k, v in op_s.items()},
        idle_by_host_s={k: v / n for k, v in idle.items()},
        n_chips=n,
    )


class Tracer:
    """Profiler control for one run: :meth:`start` / :meth:`stop` around the
    window, :meth:`span` for the harness's host spans, :meth:`events` for
    what the trace holds. With no ``trace_dir`` every call does nothing."""

    def __init__(self, trace_dir: str | None) -> None:
        self.trace_dir = trace_dir
        self._events: dict | None = None

    def start(self) -> None:
        if self.trace_dir is None:
            return
        import shutil

        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        jax.profiler.start_trace(self.trace_dir)

    def stop(self) -> None:
        if self.trace_dir is None:
            return
        import jax

        jax.profiler.stop_trace()

    def span(self, name: str):
        import contextlib

        if self.trace_dir is None:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def events(self) -> dict | None:
        if self.trace_dir is None:
            return None
        if self._events is None:
            self._events = load_xplane(self.trace_dir)
        return self._events
