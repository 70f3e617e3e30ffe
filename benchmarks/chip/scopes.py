"""Split a profiler trace (``.xplane.pb``) by what the program was doing:
device time by the program's named scopes, and idle time by its host
spans.

The program names its device work with ``jax.named_scope`` (``h2fed.*``:
local training, RSU aggregation, cloud blend, draws) and its host work
with ``jax.profiler.TraceAnnotation`` (``h2fed.*``: build, round, eval).
A scope is HLO metadata: the profiler stores it, with JAX's whole name
stack, in the ``tf_op`` stat of each XLA op's event metadata, beside its
``hlo_category``.  ``jax.profiler.ProfileData`` does not expose event
metadata, so this module decodes the XSpace protobuf itself, with a small
wire-format reader (no TensorFlow, no generated message classes).

``reduce_planes`` splits the traced window (the benchmark's
``bench.traced`` span) as ``trace_reduce`` clips it:

* ``device_s``: each op's self time (``trace_reduce.self_times``) goes to
  the innermost ``h2fed.*`` scope in its ``tf_op``, else to
  ``(unscoped)``; ``relayout_s``, the same for ops of category ``data
  formatting`` only.
* ``idle_by_span_s``: each idle gap's seconds go, instant by instant, to
  the innermost ``h2fed.*`` host span covering it, else to
  ``(outside)``; the parts sum to the idle time.

    python -m benchmarks.chip.scopes <trace.xplane.pb>   # the split
"""
from __future__ import annotations

import json
import struct
import sys
from collections import defaultdict
from typing import Dict, Iterator, List, NamedTuple, Sequence, Tuple

from benchmarks.chip import trace_reduce

PREFIX = "h2fed."
UNSCOPED = "(unscoped)"
OUTSIDE = "(outside)"
RELAYOUT = "data formatting"      # the hlo_category of layout copies

Span = Tuple[str, float, float]


# --------------------------------------------------------------------------
# protobuf wire format
# --------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of one message: an int for a
    varint, bytes for a length-delimited field, a double for a fixed64."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = struct.unpack_from("<d", buf, i)[0], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not supported")
        yield field, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


# --------------------------------------------------------------------------
# XSpace (tsl/profiler/protobuf/xplane.proto), the parts read here
# --------------------------------------------------------------------------

class Event(NamedTuple):
    name: str
    start_ns: float
    end_ns: float
    stats: Dict[str, object]


class Plane(NamedTuple):
    name: str
    lines: Dict[str, List[Event]]


def _stats(buf_list: Sequence[bytes], stat_names: Dict[int, str]
           ) -> Dict[str, object]:
    """XStat: metadata_id 1; double 2, uint64 3, int64 4, str 5, bytes 6,
    ref 7 (the id of a stat metadata whose name is the string)."""
    out = {}
    for buf in buf_list:
        mid, value = 0, None
        for f, v in _fields(buf):
            if f == 1:
                mid = v
            elif f == 2:
                value = v
            elif f == 3:
                value = v
            elif f == 4:
                value = _signed(v)
            elif f in (5, 6):
                value = bytes(v).decode("utf-8", "replace")
            elif f == 7:
                value = stat_names.get(v, "")
        out[stat_names.get(mid, str(mid))] = value
    return out


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _wanted(plane: str, line: str) -> bool:
    """The lines read: each device's XLA ops and every host thread."""
    return ((is_device(plane) and line == trace_reduce.OPS_LINE)
            or plane.startswith("/host:"))


def _plane(buf: bytes) -> Plane:
    """XPlane: name 2, lines 3, event_metadata 4, stat_metadata 5 (maps
    from id).  Only the lines ``_wanted`` names are decoded."""
    name, lines, ev_meta, st_meta = "", [], [], []
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            lines.append(v)
        elif f == 4:
            ev_meta.append(v)
        elif f == 5:
            st_meta.append(v)
    stat_names = {}
    for entry in st_meta:
        key, value = _map_entry(entry)
        stat_names[key] = next((bytes(v).decode() for f, v in _fields(value)
                                if f == 2), "")
    meta_raw = {}
    for entry in ev_meta:
        key, value = _map_entry(entry)
        meta_raw[key] = value
    meta: Dict[int, Tuple[str, Dict[str, object]]] = {}

    def event_meta(mid):
        if mid not in meta:
            ename, stats = "", []
            for f, v in _fields(meta_raw.get(mid, b"")):
                if f == 2:
                    ename = bytes(v).decode("utf-8", "replace")
                elif f == 5:
                    stats.append(v)
            meta[mid] = (ename, _stats(stats, stat_names))
        return meta[mid]

    out: Dict[str, List[Event]] = {}
    for buf in lines:
        lname, ts_ns, events = "", 0, []
        for f, v in _fields(buf):
            if f == 2:
                lname = bytes(v).decode()
            elif f == 3:
                ts_ns = _signed(v)
            elif f == 4:
                events.append(v)
        if not _wanted(name, lname):
            continue
        decoded = []
        for ev in events:
            mid = offset_ps = duration_ps = 0
            stats = []
            for f, v in _fields(ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    offset_ps = _signed(v)
                elif f == 3:
                    duration_ps = _signed(v)
                elif f == 4:
                    stats.append(v)
            ename, mstats = event_meta(mid)
            # whole nanoseconds, as jax.profiler.ProfileData gives them
            start = float(ts_ns + offset_ps // 1000)
            decoded.append(Event(ename, start, start + duration_ps // 1000,
                                 {**mstats, **_stats(stats, stat_names)}))
        out[lname] = decoded
    return Plane(name, out)


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def read_planes(path: str) -> List[Plane]:
    """The planes of an ``.xplane.pb``, with the lines ``_wanted`` names."""
    with open(path, "rb") as fh:
        data = fh.read()
    return [_plane(v) for f, v in _fields(data) if f == 1]


# --------------------------------------------------------------------------
# reductions
# --------------------------------------------------------------------------

def scope_of(tf_op: str) -> str:
    """The innermost ``h2fed.*`` component of an op's ``tf_op``, which is
    JAX's name stack and ``:<op type>``."""
    for part in reversed(tf_op.split("/")):
        if part.startswith(PREFIX):
            return part.split(":", 1)[0]
    return UNSCOPED


def innermost(spans: Sequence[Span], window: Tuple[float, float]
              ) -> List[Span]:
    """Disjoint (name, start, end) pieces covering ``window``: the
    innermost of ``spans`` (which nest, as one thread's spans do) at each
    instant, ``OUTSIDE`` where none covers it."""
    w0, w1 = window
    bounds = sorted({w0, w1} | {t for _, s, e in spans for t in (s, e)
                                if w0 < t < w1})
    opened = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    out: List[Span] = []
    stack: List[Span] = []
    j = 0
    for a, b in zip(bounds, bounds[1:]):
        while j < len(opened) and opened[j][1] <= a:
            stack.append(opened[j])
            j += 1
        while stack and stack[-1][2] <= a:
            stack.pop()
        live = [sp for sp in stack if sp[2] > a]
        name = live[-1][0] if live else OUTSIDE
        if out and out[-1][0] == name and out[-1][2] == a:
            out[-1] = (name, out[-1][1], b)
        else:
            out.append((name, a, b))
    return out


def split_by_overlap(intervals: Sequence[Tuple[float, float]],
                     pieces: Sequence[Span]) -> Dict[str, float]:
    """Seconds of ``intervals`` (sorted, disjoint) under each piece's name;
    ``pieces`` are sorted, disjoint and cover the intervals."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for s, e in intervals:
        while j < len(pieces) and pieces[j][2] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][1] < e:
            name, a, b = pieces[k]
            out[name] += max(0.0, min(e, b) - max(s, a))
            k += 1
    return dict(out)


def reduce_planes(planes: Sequence[Plane]) -> Dict:
    """The split of the traced window, in seconds per device (averaged
    over the devices, as ``trace_reduce.summarize`` averages busy time)."""
    host: List[Span] = []
    for p in planes:
        if p.name.startswith("/host:"):
            for events in p.lines.values():
                if any(e.name == trace_reduce.WINDOW_SPAN for e in events):
                    host = [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                            for e in events]
    windows = [(s, e) for n, s, e in host if n == trace_reduce.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {trace_reduce.WINDOW_SPAN!r} span on a host "
                         f"thread")
    window = w0, w1 = windows[-1]
    spans = [sp for sp in host
             if sp[0].startswith(PREFIX) and sp[2] > w0 and sp[1] < w1]
    pieces = innermost(spans, window)
    devices = [p for p in planes if is_device(p.name)
               and trace_reduce.OPS_LINE in p.lines]
    n_dev = max(len(devices), 1)
    scope_s: Dict[str, float] = defaultdict(float)
    relayout_s: Dict[str, float] = defaultdict(float)
    idle_s: Dict[str, float] = defaultdict(float)
    busy_s = 0.0
    for p in devices:
        clipped = [((scope_of(str(e.stats.get("tf_op", ""))),
                     e.stats.get("hlo_category") == RELAYOUT),
                    max(e.start_ns * 1e-9, w0), min(e.end_ns * 1e-9, w1))
                   for e in p.lines[trace_reduce.OPS_LINE]
                   if e.end_ns * 1e-9 > w0 and e.start_ns * 1e-9 < w1]
        for (scope, relayout), _, _, own in trace_reduce.self_times(clipped):
            scope_s[scope] += own / n_dev
            if relayout:
                relayout_s[scope] += own / n_dev
        busy = trace_reduce.union([(s, e) for _, s, e in clipped])
        busy_s += sum(e - s for s, e in busy) / n_dev
        for name, sec in split_by_overlap(
                trace_reduce.gaps(busy, window), pieces).items():
            idle_s[name] += sec / n_dev
    counts: Dict[str, int] = defaultdict(int)
    for name, _, _ in spans:
        counts[name] += 1
    return {
        "window_s": w1 - w0,
        "busy_s": busy_s,
        "idle_s": (w1 - w0) - busy_s,
        "device_s": dict(sorted(scope_s.items(), key=lambda kv: -kv[1])),
        "relayout_s": dict(sorted(relayout_s.items(),
                                  key=lambda kv: -kv[1])),
        "idle_by_span_s": dict(sorted(idle_s.items(),
                                      key=lambda kv: -kv[1])),
        "spans": dict(counts),
    }


def reduce_file(path: str) -> Dict:
    return reduce_planes(read_planes(path))


if __name__ == "__main__":
    print(json.dumps(reduce_file(sys.argv[1]), indent=1))
