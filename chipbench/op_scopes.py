"""Device time of the program's named scopes and of its collectives, from a
run's trace.

The program wraps its sub-layers in ``jax.named_scope`` (``mamba``,
``attn``, ``moe``, ``shared_mlp``). XLA keeps each op's scope path in its
metadata (``op_name``: ``jit(train_step)/.../transpose(jvp())/.../mamba/
dot_general`` for an op of a Mamba mixer's backward pass), and the profiler
writes it as a stat of the op's *event metadata* on each device plane.
``jax.profiler.ProfileData`` yields an event's own stats but not those of
its metadata, so :func:`event_metadata_stats` reads them from the
``.xplane.pb`` itself with a minimal protobuf wire-format reader (XSpace >
XPlane > ``event_metadata``, ``stat_metadata``). The generated
``xplane_pb2`` is installed only inside TensorFlow, whose import loads all
of TensorFlow into the process that holds the chips.

* :func:`reduce_ops` is pure: each chip's window-clipped ops, their scope
  paths and the op names of collectives give device self seconds per
  scope and of collectives, averaged over the chips. A collective is not
  counted under the scope it sits in, so the parts never overlap.
* :func:`of_run` gives a reader those seconds for the run it reads, read
  once a run and kept on the reader context.

    python3 chipbench/op_scopes.py <trace dir>

prints, for the newest trace there, which metadata stats carry a scope
path and each scope's device seconds.
"""
from __future__ import annotations

import glob
import os
import struct
import sys
from collections import defaultdict

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import spans, tracing  # noqa: E402

#: the program's named scopes whose device time the readers report
SCOPES = ("mamba", "attn", "moe", "shared_mlp")
#: HLO op names (their prefix) of the collectives
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
#: the reader context's attribute that keeps the run's reduction once read
CTX_ATTR = "op_scopes"


# ------------------------------------------------------------ wire format
def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """``(field number, wire type, value)`` of each field of one message:
    an int for varints and fixed widths, bytes for length-delimited."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val, i = struct.unpack_from("<Q", buf, i)[0], i + 8
        elif wt == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wt == 5:
            val, i = struct.unpack_from("<I", buf, i)[0], i + 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield num, wt, val


def _map_entry(buf: bytes) -> tuple[int, bytes]:
    key, value = 0, memoryview(b"")
    for num, _, val in _fields(buf):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def _stat(buf: bytes, stat_names: dict) -> tuple[int, object]:
    """An XStat's metadata id and value; a ``ref_value`` is the name of the
    stat metadata it refers to (the profiler's interned strings)."""
    mid, value = 0, None
    for num, _, val in _fields(buf):
        if num == 1:
            mid = val
        elif num == 2:
            value = struct.unpack("<d", struct.pack("<Q", val))[0]
        elif num in (3, 4):
            value = val
        elif num in (5, 6):
            value = bytes(val).decode("utf-8", "replace")
        elif num == 7:
            value = stat_names.get(val, val)
    return mid, value


def event_metadata_stats(data: bytes, plane_prefix: str = "/device:") -> dict:
    """``{plane name: {event metadata name: {stat name: value}}}`` for the
    planes whose name starts with ``plane_prefix``, from a serialized
    XSpace."""
    out: dict = {}
    for num, _, plane in _fields(memoryview(data)):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pnum, _, val in _fields(plane):
            if pnum == 2:
                name = bytes(val).decode("utf-8", "replace")
                if not name.startswith(plane_prefix):
                    break
            elif pnum == 4:
                metas.append(_map_entry(val)[1])
            elif pnum == 5:
                sid, smeta = _map_entry(val)
                for snum, _, sval in _fields(smeta):
                    if snum == 2:
                        stat_names[sid] = bytes(sval).decode("utf-8", "replace")
        if not name.startswith(plane_prefix):
            continue
        table: dict = {}
        for meta in metas:
            ename, raw = "", []
            for mnum, _, val in _fields(meta):
                if mnum == 2:
                    ename = bytes(val).decode("utf-8", "replace")
                elif mnum == 5:
                    raw.append(val)
            stats = {}
            for r in raw:
                mid, value = _stat(r, stat_names)
                stats[stat_names.get(mid, str(mid))] = value
            table[ename] = stats
        out[name] = table
    return out


# --------------------------------------------------------------- reduction
def scope_of(path: str) -> str | None:
    """The innermost of :data:`SCOPES` on an op's scope path, or None."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return None


def scope_path(stats: dict) -> tuple[str | None, str | None]:
    """``(stat name, scope path)`` of an op's metadata stats: the first
    string stat that holds a ``jit(...)/...`` path."""
    for key, value in stats.items():
        if isinstance(value, str) and value.startswith("jit(") and "/" in value:
            return key, value
    return None, None


def is_collective(op: str) -> bool:
    return op.startswith(COLLECTIVES)


def reduce_ops(chips: dict, paths: dict, w0: float, w1: float) -> dict:
    """Device self seconds in ``[w0, w1]`` (ns) by :data:`SCOPES` and of
    collectives (``"collective"``), and in all (``"busy"``, the self time of
    every op), summed over ``chips`` (``{chip: [[op, start_ns, dur_ns],
    ...]}``) and divided by their number. ``paths`` maps an op name to its
    scope path (missing: no scope)."""
    out: dict = defaultdict(float)
    for ops in chips.values():
        clipped = []
        for name, s, d in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((name, a, b))
        for name, sec in tracing._self_seconds(clipped).items():
            out["busy"] += sec
            if is_collective(name):
                out["collective"] += sec
                continue
            scope = scope_of(paths.get(name, ""))
            if scope:
                out[scope] += sec
    n = max(len(chips), 1)
    return {k: v / n for k, v in out.items()}


def load(trace_dir: str) -> tuple[dict, dict, tuple[float, float], dict]:
    """The newest trace under ``trace_dir``: each device chip's ops, each op
    name's scope path, the window and, by stat name, how many op names
    carry their path in it."""
    pths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)
    if not pths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events = spans.load(trace_dir)
    with open(pths[-1], "rb") as f:
        meta = event_metadata_stats(f.read())
    paths: dict = {}
    carriers: dict = defaultdict(int)
    for plane, table in meta.items():
        if plane not in events["device"]:
            continue
        for ename, stats in table.items():
            key, path = scope_path(stats)
            if path is not None:
                paths[tracing.short_name(ename)] = path
                carriers[key] += 1
    return events["device"], paths, tracing.window_bounds(events["host"]), dict(carriers)


def of_run(ctx) -> dict | None:
    """Seconds per window by scope, of collectives and busy, for the run
    whose reader context is ``ctx``: None where the run was not traced, its
    trace is not on disk, or no op carries a scope path."""
    if getattr(ctx, "trace", None) is None:
        return None
    if not hasattr(ctx, CTX_ATTR):
        setattr(ctx, CTX_ATTR, _read_run(ctx.trace.window_s))
    return getattr(ctx, CTX_ATTR)


def _read_run(window_s: float) -> dict | None:
    try:
        chips, paths, (w0, w1), _ = load(spans.TRACE_ROOT)
    except FileNotFoundError:  # no trace; one that does not decode raises
        return None
    if (w1 - w0) * 1e-9 != window_s or not paths:  # not this run's trace
        return None
    return reduce_ops(chips, paths, w0, w1)


def ms_per_step(ctx, key: str) -> float | None:
    """Milliseconds of device self time under ``key`` (a scope or
    ``collective``) per window step, averaged over the chips."""
    got, n = of_run(ctx), ctx.counters.get("steps")
    if got is None or not n:
        return None
    return got.get(key, 0.0) / n * 1e3


def main(argv: list[str]) -> int:
    chips, paths, (w0, w1), carriers = load(argv[0])
    print(f"chips={sorted(chips)} ops with a scope path={len(paths)} "
          f"carried by stat={carriers}")
    for scope in SCOPES:
        sample = next((p for p in paths.values() if scope_of(p) == scope), None)
        print(f"{scope}: e.g. {sample}")
    print({k: round(v, 6) for k, v in reduce_ops(chips, paths, w0, w1).items()})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
