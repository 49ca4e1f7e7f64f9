"""From a profiler trace (``.xplane.pb``) to device busy time, idle share,
the device operations that took most time, and the longest idle gaps,
each labelled with what the host was doing then.

The host side is read from the harness's own ``jax.profiler``
``TraceAnnotation`` spans, all named ``bench/<what>``; the one named
``bench/window`` brackets the traced window. Device busy time is the union
of the intervals in which an operation ran on the device (line "XLA Ops"
of each ``/device:TPU:<n>`` plane), clipped to the window and averaged over
the devices.

    python bench/trace_reduce.py <trace dir or .xplane.pb>   # prints JSON
"""
from __future__ import annotations

import glob
import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench/window"
HOST_PREFIX = "bench/"
DEVICE_PREFIX = "/device:TPU:"
TOP = 10

Interval = Tuple[float, float]                    # (start_ns, end_ns)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def self_times(events: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Per op name, the time its events ran less the time of the events
    nested inside them (a loop op holds its body's ops)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [d for _, _, d in evs]
    stack: List[Tuple[float, int]] = []
    for i, (_, s, d) in enumerate(evs):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= d
        stack.append((s + d, i))
    out: Dict[str, float] = {}
    for (name, _, _), t in zip(evs, own):
        out[name] = out.get(name, 0.0) + max(t, 0.0)
    return out


def label_gap(gap: Interval, host: Sequence[Tuple[str, float, float]]) -> str:
    """The innermost host span (latest start) that covers the gap's middle;
    else the host span that ended last before it, as ``after <name>``."""
    mid = 0.5 * (gap[0] + gap[1])
    covering = [(s, n) for n, s, e in host if s <= mid <= e and n != WINDOW]
    if covering:
        return max(covering)[1]
    before = [(e, n) for n, s, e in host if e <= mid and n != WINDOW]
    return f"after {max(before)[1]}" if before else "no host span"


def summarize(devices: Dict[str, List[Tuple[str, float, float]]],
              host: List[Tuple[str, float, float]],
              window: Optional[Interval] = None) -> Dict:
    """``devices``: plane name -> [(op name, start_ns, duration_ns)];
    ``host``: [(span name, start_ns, end_ns)]; ``window``: the traced
    window (default: the ``bench/window`` span, else the extent of all
    device events). Returns seconds."""
    if window is None:
        spans = [(s, e) for n, s, e in host if n == WINDOW]
        if spans:
            window = (min(s for s, _ in spans), max(e for _, e in spans))
        else:
            allev = [(s, s + d) for evs in devices.values() for _, s, d in evs]
            if not allev:
                raise ValueError("the trace holds no device operation")
            window = (min(s for s, _ in allev), max(e for _, e in allev))
    lo, hi = window
    if not devices or not any(devices.values()):
        raise ValueError("the trace holds no device operation")
    busy_ns, per_op, gaps = [], {}, []
    for plane in sorted(devices):
        evs = devices[plane]
        busy = union(clip([(s, s + d) for _, s, d in evs], lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        inside = [(name, c[0][0], c[0][1] - c[0][0]) for name, s, d in evs
                  for c in [clip([(s, s + d)], lo, hi)] if c]
        for name, t in self_times(inside).items():
            per_op[name] = per_op.get(name, 0.0) + t
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append(((a, b), plane))
    n = len(busy_ns)
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy_ns) / n * 1e-9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    gaps.sort(key=lambda g: -(g[0][1] - g[0][0]))
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "n_devices": n,
        "device_ops": [[name, ns * 1e-9 / n] for name, ns in ops],
        "idle_gaps": [[label_gap(g, host), (g[1] - g[0]) * 1e-9]
                      for g, _ in gaps[:TOP]],
    }


def find_xplane(path: str) -> str:
    if path.endswith(".xplane.pb"):
        return path
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return files[-1]


def read(path: str):
    """``(devices, host)`` event lists of the trace at ``path``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(find_xplane(path))
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            # an op's event name is its HLO text: keep "%name" before " = "
            devices[plane.name] = [(e.name.split(" = ")[0], float(e.start_ns),
                                    float(e.duration_ns))
                                   for ln in ops for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIX):
                        s = float(e.start_ns)
                        host.append((e.name, s, s + float(e.duration_ns)))
    return devices, host


def reduce(path: str) -> Dict:
    devices, host = read(path)
    return summarize(devices, host)


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1]), indent=1))
