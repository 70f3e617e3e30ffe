"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

The window is the host span the benchmark opens over the traced rounds
(``WINDOW_SPAN``).  On each device plane the operations of the XLA-ops line
are clipped to the window; busy time is the union of their intervals, and
the device idle share is one less busy over the window, averaged over the
devices.  Each idle gap inside the window is named by what the host's main
thread was doing at its middle: the innermost host event there, under the
innermost benchmark span.

    python -m benchmarks.chip.trace_reduce <trace.xplane.pb>   # summary
"""
from __future__ import annotations

import json
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench.traced"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The idle intervals of ``window`` between merged busy intervals."""
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, min(s, window[1])))
        t = max(t, e)
        if t >= window[1]:
            break
    if t < window[1]:
        out.append((t, window[1]))
    return [g for g in out if g[1] > g[0]]


def names_at(host: Sequence[Tuple[str, float, float]],
             times: Sequence[float]) -> List[str]:
    """For each time, ``span/event``: the innermost benchmark span and the
    innermost event of any name on the host's main thread there.  Events of
    one thread nest, so one sweep with a stack finds them."""
    events = sorted(host, key=lambda ev: (ev[1], -ev[2]))
    order = sorted(range(len(times)), key=lambda i: times[i])
    out = [""] * len(times)
    stack: List[Tuple[str, float, float]] = []
    j = 0
    for i in order:
        t = times[i]
        while j < len(events) and events[j][1] <= t:
            while stack and stack[-1][2] <= events[j][1]:
                stack.pop()
            stack.append(events[j])
            j += 1
        while stack and stack[-1][2] <= t:
            stack.pop()
        spans = [ev for ev in stack if ev[0].startswith(SPAN_PREFIX)]
        if not stack:
            out[i] = "(no host event)"
        elif not spans:
            out[i] = stack[-1][0]
        elif stack[-1] is spans[-1]:
            out[i] = spans[-1][0]
        else:
            out[i] = f"{spans[-1][0]}/{stack[-1][0]}"
    return out


def short_name(name: str) -> str:
    """An XLA op's instruction name: the trace names a TPU op by its whole
    HLO line, ``%fusion.3 = f32[...] fusion(...)``."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(ops: Sequence[Tuple[str, float, float]]
               ) -> List[Tuple[str, float, float, float]]:
    """(name, start, end, self seconds) of each op: its duration less the
    ops nested in it on the same line (a loop contains its body's ops)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [e - s for _, s, e in ops]
    stack: List[int] = []
    for i in order:
        s, e = ops[i][1], ops[i][2]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, ops[stack[-1]][2]) - s
        stack.append(i)
    return [(n, s, e, own[i]) for i, (n, s, e) in enumerate(ops)]


def summarize(device_ops: Dict[str, List[Tuple[str, float, float]]],
              host: Sequence[Tuple[str, float, float]],
              window: Interval, top: int = 10) -> Dict:
    """Reduce events (times in seconds) to the summary.

    ``device_ops``: per device, (op name, start, end); ``host``: the main
    thread's (name, start, end); ``window``: the traced window.  Per-op
    time is self time, so a loop op does not count its body twice."""
    w0, w1 = window
    op_s: Dict[str, float] = defaultdict(float)
    op_n: Dict[str, int] = defaultdict(int)
    busy_s, gap_s = [], defaultdict(float)
    for ops in device_ops.values():
        clipped = [(short_name(n), max(s, w0), min(e, w1)) for n, s, e in ops
                   if e > w0 and s < w1]
        for n, _, _, own in self_times(clipped):
            op_s[n] += own
            op_n[n] += 1
        busy = union([(s, e) for _, s, e in clipped])
        busy_s.append(sum(e - s for s, e in busy))
        idle = gaps(busy, window)
        names = names_at(host, [(s + e) / 2 for s, e in idle])
        for (s, e), name in zip(idle, names):
            gap_s[name] += (e - s) / len(device_ops)
    n_dev = max(len(device_ops), 1)
    ops_sorted = sorted(op_s.items(), key=lambda kv: -kv[1])
    return {
        "window_s": w1 - w0,
        "busy_s": sum(busy_s) / n_dev,
        "idle_share": 1.0 - sum(busy_s) / n_dev / (w1 - w0),
        "n_devices": len(device_ops),
        "ops": {k: v / n_dev for k, v in ops_sorted},
        "op_counts": {k: op_n[k] // n_dev for k, _ in ops_sorted},
        "gaps": sorted(gap_s.items(), key=lambda kv: -kv[1]),
        "top": top,
    }


def breakdown(summary: Dict) -> Dict:
    """The result line's ``breakdown``: the device operations that took
    most time, and the longest idle time by what the host was doing."""
    top = summary["top"]
    return {"device_ops": [[k, v] for k, v in
                           list(summary["ops"].items())[:top]],
            "idle_gaps": [[k, v] for k, v in summary["gaps"][:top]]}


def read_xplane(path: str) -> Tuple[Dict, List, Interval]:
    """(device ops, host main-thread events, window) from a trace file,
    times in seconds on the trace's clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    device_ops, host_lines = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ev = [(e.name, e.start_ns * 1e-9,
                       (e.start_ns + e.duration_ns) * 1e-9)
                      for e in line.events]
                if any(n == WINDOW_SPAN for n, _, _ in ev):
                    host_lines = ev
    windows = [(s, e) for n, s, e in host_lines if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span on a host thread")
    return device_ops, host_lines, windows[-1]


def reduce_file(path: str, top: int = 10) -> Dict:
    device_ops, host, window = read_xplane(path)
    if not device_ops:
        raise ValueError(f"{path}: no device plane with an {OPS_LINE!r} "
                         f"line")
    return summarize(device_ops, host, window, top)


def find_xplane(trace_dir: str) -> str:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return str(found[-1])


if __name__ == "__main__":
    s = reduce_file(sys.argv[1])
    s["ops"] = dict(list(s["ops"].items())[:25])
    s["gaps"] = s["gaps"][:25]
    print(json.dumps(s, indent=1))
