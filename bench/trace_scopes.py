"""From a profiler trace (``.xplane.pb``) to device time by the program's
own names: per layer scope, per program, and device-idle time per host
phase.

- Layer: each device op's ``tf_op`` metadata holds the JAX name stack
  (``jit(train_step)/transpose(jvp())/while/body/checkpoint/attention/...``);
  the op's layer is the innermost name of :data:`SCOPES` in it, once the
  transform wrappers (``jvp(...)``, ``transpose(...)``, ...) are stripped.
  An op with no scope is ``copy`` when it is a copy, else ``none``.
- Program: the execution on the device plane's "XLA Modules" line that
  holds the op (its ``program_id``); an op's metadata is found by program
  and name, since two programs can hold ops of the same name.
- Phase: the program's host spans ``gym/*`` and ``serve/*``
  (``jax.profiler.TraceAnnotation``). Each device-idle interval goes to
  the innermost span covering it, else to ``after <the span that ended
  last>``.
- Scope by the program's own names: the op's innermost name of the
  program's ``repro.telemetry.spans.SCOPES``, read when the trace is
  reduced (``scopes_s``), so that a scope a later program adds (an MoE's
  ``route`` inside ``mlp``) reads with no edit here. The bench's own copy,
  :data:`SCOPES`, keeps ``layers_s`` and its partitions as they are.
- Collective: an op whose name or category is an exchange between chips
  (all-gather, all-reduce, reduce-scatter, all-to-all, collective-permute,
  with their ``-start`` / ``-done`` parts; ``collectives_s``): the time the
  core spends in or waiting for one.

``jax.profiler.ProfileData`` gives the events but not their metadata's
stats, so :func:`op_metadata` decodes that part of the file itself: the
device planes' ``event_metadata`` and ``stat_metadata`` maps (protobuf wire
format, every other field skipped by its length).

A trace of a program without these names reads as no steps, no ticks and
no scoped op: every reader of this module then returns None.

    python bench/trace_scopes.py <trace dir or .xplane.pb>   # prints JSON
"""
from __future__ import annotations

import bisect
import functools
import json
import os
import re
import sys
import time
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))))

from bench.trace_reduce import (DEVICE_PREFIX, WINDOW, clip, find_xplane,  # noqa: E402
                                self_times, union)

#: the program's layer scopes (``repro.telemetry.spans.SCOPES``), kept here
#: too so that a program without them still reads
SCOPES = ("embed", "attention", "kv_read", "kv_write", "mlp", "ssm", "head",
          "loss", "optimizer", "sample")
PHASE_PREFIXES = ("gym/", "serve/")
#: engine spans that call the device, and the one that waits for arrivals
DEVICE_CALLS = ("serve/tick", "serve/prefill_chunk", "serve/first_token",
                "serve/slot_write", "serve/page_table")
IDLE = "serve/idle"
TICK = "serve/tick"
STEP = "gym/step"
DATA_WAIT = "gym/data_wait"
#: the train metrics' partition of the layers
TRAIN_GROUPS = {
    "attention": ("attention", "kv_read", "kv_write", "ssm"),
    "mlp": ("mlp",),
    "head_loss": ("embed", "head", "loss", "sample"),
    "optimizer": ("optimizer",),
    "unscoped": ("copy", "none"),
}
#: the decode tick's partition
TICK_GROUPS = {"kv": ("kv_read", "kv_write"), "copy": ("copy",)}

#: the exchanges between chips, as XLA names their ops and categories
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_WRAPPED = re.compile(r"[\w.-]+\((.*)\)")
_MODULE = re.compile(r"(.*)\((\d+)\)$")
_COPY_NAME = re.compile(r"%copy(-start|-done)?(\.\d+)?$")
_COLLECTIVE = re.compile(r"%?(" + "|".join(COLLECTIVES) + r")\b")

Interval = Tuple[float, float]


# -- layer of one op ----------------------------------------------------------
def scope_of(tf_op: str, names: Sequence[str] = SCOPES) -> Optional[str]:
    """The innermost of ``names`` in a JAX name stack, the program's own
    ``jit(...)`` left out; None when there is none."""
    found = None
    for part in tf_op.split("/")[1:]:
        m = _WRAPPED.fullmatch(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.fullmatch(part)
        if part in names:
            found = part
    return found


def is_collective(name: str, category: str = "") -> bool:
    """Whether an op exchanges data between chips, by its HLO name
    (``%all-gather-start.3``) or its category."""
    return bool(_COLLECTIVE.match(name) or _COLLECTIVE.search(category))


def layer_of(name: str, tf_op: str, category: str) -> str:
    """An op's layer: its scope, else ``copy`` for a copy, else ``none``."""
    s = scope_of(tf_op)
    if s is not None:
        return s
    if "copy" in category or _COPY_NAME.match(name):
        return "copy"
    return "none"


# -- the metadata the profiler API leaves out ---------------------------------
def _varint(b: memoryview, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: memoryview) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for varints, a slice of
    ``b`` for length-delimited and fixed-width fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _map_entry(b) -> Tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for f, v in _fields(b):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane_metadata(plane: memoryview
                    ) -> Tuple[str, Dict[Tuple[int, str], Tuple[str, str]]]:
    """A plane's name and, for a device plane, ``(program_id, op name)`` ->
    ``(tf_op, hlo_category)`` (XPlane: name 2, event_metadata 4,
    stat_metadata 5; XEventMetadata: name 2, stats 5; XStat: metadata_id 1,
    uint64 3, int64 4, str 5, ref 7; XStatMetadata: name 2)."""
    name, events, stat_names = "", [], {}
    for f, v in _fields(plane):
        if f == 2:
            name = bytes(v).decode()
        elif f == 4:
            events.append(v)
        elif f == 5:
            k, sm = _map_entry(v)
            stat_names[k] = next((bytes(x).decode() for g, x in _fields(sm)
                                  if g == 2), "")
    out: Dict[Tuple[int, str], Tuple[str, str]] = {}
    if not name.startswith(DEVICE_PREFIX):
        return name, out
    wanted = {"tf_op", "hlo_category", "program_id"}
    for entry in events:
        _, em = _map_entry(entry)
        op, stats = "", {}
        for f, v in _fields(em):
            if f == 2:
                op = bytes(v).decode(errors="replace").split(" = ")[0]
            elif f == 5:
                sid, val = 0, None
                for g, x in _fields(v):
                    if g == 1:
                        sid = x
                    elif g in (3, 4):
                        val = x
                    elif g == 5:
                        val = bytes(x).decode(errors="replace")
                    elif g == 7:
                        val = stat_names.get(x, "")
                stat = stat_names.get(sid)
                if stat in wanted:
                    stats[stat] = val
        if "program_id" in stats:
            out[(int(stats["program_id"]), op)] = (
                str(stats.get("tf_op", "")), str(stats.get("hlo_category", "")))
    return name, out


def op_metadata(path: str) -> Dict[str, Dict[Tuple[int, str], Tuple[str, str]]]:
    """Device plane name -> ``(program_id, "%op")`` -> ``(tf_op,
    hlo_category)`` from the ``.xplane.pb`` at ``path`` (XSpace: planes 1)."""
    with open(find_xplane(path), "rb") as f:
        space = memoryview(f.read())     # fields are sliced, never copied
    out = {}
    for field, plane in _fields(space):
        if field == 1:
            name, meta = _plane_metadata(plane)
            if meta:
                out[name] = meta
    return out


# -- events -------------------------------------------------------------------
def read(path: str):
    """``(ops, modules, host)`` of the trace at ``path``: per device plane
    the ops ``(program_id or None, layer, start_ns, dur_ns, name, scope,
    collective)`` (an unscoped op's name with its ``[hlo_category]``; its
    innermost name of the program's ``repro.telemetry.spans.SCOPES`` or
    None; whether it is an exchange between chips) and the program
    executions ``(program name, program_id, start_ns, dur_ns)``;
    the host's ``gym/*``, ``serve/*`` and ``bench/window`` spans
    ``(name, start_ns, end_ns)``.

    A TPU trace puts the device's events about a millisecond early against
    the host's (an execution starts before the host call that launched
    it). So each device plane is shifted onto the host clock by
    :func:`plane_shifts`."""
    import jax
    from repro.telemetry.spans import SCOPES as names

    xplane = find_xplane(path)
    meta = op_metadata(xplane)
    pd = jax.profiler.ProfileData.from_file(xplane)
    ops, modules, host = {}, {}, []
    ends: Dict[str, List[Tuple[int, float]]] = {}   # plane -> (run_id, end)
    completions: Dict[Tuple[int, int], float] = {}  # (device, run_id) -> t
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            mods = []
            for e in (lines["XLA Modules"].events
                      if "XLA Modules" in lines else ()):
                m = _MODULE.match(e.name)
                if m:
                    mods.append((m.group(1), int(m.group(2)),
                                 float(e.start_ns), float(e.duration_ns)))
                    run = _stat(e, "run_id")
                    if run is not None:
                        ends.setdefault(plane.name, []).append(
                            (int(run), float(e.start_ns + e.duration_ns)))
            mods.sort(key=lambda x: x[2])
            starts = [x[2] for x in mods]
            pm = meta.get(plane.name, {})
            evs = []
            for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
                s, d = float(e.start_ns), float(e.duration_ns)
                k = bisect.bisect_right(starts, s) - 1
                pid = (mods[k][1] if k >= 0 and s < mods[k][2] + mods[k][3]
                       else None)
                name = e.name.split(" = ")[0]
                tf_op, cat = pm.get((pid, name), ("", ""))
                layer = layer_of(name, tf_op, cat)
                tags = (scope_of(tf_op, names), is_collective(name, cat))
                if layer in ("copy", "none") and cat:
                    name = f"{name} [{cat}]"    # for the unscoped-op table
                evs.append((pid, layer, s, d, name) + tags)
            ops[plane.name], modules[plane.name] = evs, mods
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(PHASE_PREFIXES) or e.name == WINDOW:
                        s = float(e.start_ns)
                        host.append((e.name, s, s + float(e.duration_ns)))
                    elif e.name == "CompleteCallbacks":
                        run = _stat(e, "run_id")
                        if run is not None:
                            key = (int(_stat(e, "device_ordinal") or 0),
                                   int(run))
                            completions[key] = min(
                                float(e.start_ns),
                                completions.get(key, float("inf")))
    shifts = plane_shifts(ends, completions)
    for plane in ops:
        shift, n = shifts.get(plane, (0.0, 0))
        print(f"trace_scopes: {plane} shifted by {shift * 1e-6:.3f} ms onto "
              f"the host clock ({n} executions matched)",
              file=sys.stderr, flush=True)
        ops[plane] = [(p, la, s + shift, d, *tags)
                      for p, la, s, d, *tags in ops[plane]]
        modules[plane] = [(n, p, s + shift, d) for n, p, s, d in modules[plane]]
    return ops, modules, host


def plane_shifts(ends: Dict[str, List[Tuple[int, float]]],
                 completions: Dict[Tuple[int, int], float]
                 ) -> Dict[str, Tuple[float, int]]:
    """plane -> (shift, executions matched): the least time from an
    execution's end on ``/device:TPU:<k>`` to the host's
    ``CompleteCallbacks`` of the same ``run_id`` on device ``k`` (each
    device counts its own runs), so that after the shift no execution ends
    after the host heard that it ended. A plane with no match is not
    shifted."""
    out = {}
    for plane, runs in ends.items():
        device = int(plane.rsplit(":", 1)[1])
        lags = [completions[(device, r)] - end for r, end in runs
                if (device, r) in completions]
        out[plane] = (min(lags) if lags else 0.0, len(lags))
    return out


def _stat(event, name: str):
    return next((v for k, v in event.stats if k == name), None)


# -- idle time by phase ---------------------------------------------------------
def attribute(gaps: Sequence[Interval], spans: Sequence[Tuple[str, float, float]],
              outside: Sequence[str] = ()) -> Tuple[Dict[str, float], float]:
    """Split ``gaps`` (sorted, disjoint) by ``spans``: per label, the time
    under the innermost covering span (latest start), else ``after <name>``
    of the span that ended last before, else ``no span``; and the time
    covered by none of the spans named in ``outside``."""
    bounds = sorted({t for _, s, e in spans for t in (s, e)}
                    | {t for iv in gaps for t in iv})
    by_start = sorted(spans, key=lambda x: x[1])
    by_end = sorted(spans, key=lambda x: x[2])
    labels: Dict[str, float] = {}
    free = 0.0
    active: List[Tuple[float, float, str]] = []
    i = j = g = 0
    last = None
    for a, b in zip(bounds, bounds[1:]):
        while i < len(by_start) and by_start[i][1] <= a:
            n, s, e = by_start[i]
            active.append((s, -e, n))
            i += 1
        while j < len(by_end) and by_end[j][2] <= a:
            last = by_end[j][0]
            j += 1
        active = [x for x in active if -x[1] > a]
        while g < len(gaps) and gaps[g][1] <= a:
            g += 1
        if g == len(gaps) or gaps[g][0] >= b:
            continue
        if active:
            label = max(active)[2]
        else:
            label = f"after {last}" if last else "no span"
        labels[label] = labels.get(label, 0.0) + (b - a)
        if not any(x[2] in outside for x in active):
            free += b - a
    return labels, free


# -- the reduction ----------------------------------------------------------------
def summarize(ops, modules, host, window: Optional[Interval] = None) -> Dict:
    """Seconds, averaged over the device planes. ``window``: the traced
    window (default: the ``bench/window`` span, else the extent of the
    device ops). ``ops``, ``modules`` and ``host`` as :func:`read` gives
    them."""
    if window is None:
        w = [(s, e) for n, s, e in host if n == WINDOW]
        allev = [(s, s + d) for evs in ops.values()
                 for _, _, s, d, *_ in evs]
        if w:
            window = (min(s for s, _ in w), max(e for _, e in w))
        elif allev:
            window = (min(s for s, _ in allev), max(e for _, e in allev))
        else:
            raise ValueError("the trace holds no device operation")
    lo, hi = window
    if not any(ops.values()):
        raise ValueError("the trace holds no device operation")
    spans = [(n, s, e) for n, s, e in host
             if n.startswith(PHASE_PREFIXES) and e > lo and s < hi]
    names = {}
    for mods in modules.values():
        for name, pid, _, _ in mods:
            names[pid] = name
    tick_ids = _tick_programs(modules, spans)
    n = len(ops)
    layers: Dict[str, float] = {}
    scopes: Dict[str, float] = {}
    collectives = 0.0
    programs: Dict[str, Dict] = {}
    idle_labels: Dict[str, float] = {}
    idle = sched_free = busy = 0.0
    for plane in sorted(ops):
        evs = [((pid, layer, name, scope, coll), c[0][0], c[0][1] - c[0][0])
               for pid, layer, s, d, name, scope, coll in ops[plane]
               for c in [clip([(s, s + d)], lo, hi)] if c]
        for (pid, layer, name, scope, coll), t in self_times(evs).items():
            t /= n
            layers[layer] = layers.get(layer, 0.0) + t
            if scope is not None:
                scopes[scope] = scopes.get(scope, 0.0) + t
            if coll:
                collectives += t
            p = programs.setdefault(names.get(pid, "(no program)"),
                                    {"runs": 0, "module_s": 0.0, "ops_s": 0.0,
                                     "layers": {}, "unscoped": {},
                                     "tick": pid in tick_ids})
            p["ops_s"] += t
            p["layers"][layer] = p["layers"].get(layer, 0.0) + t
            if layer in ("copy", "none"):
                p["unscoped"][name] = p["unscoped"].get(name, 0.0) + t
        for name, pid, s, d in modules.get(plane, ()):
            if lo <= s + 0.5 * d <= hi and names.get(pid) in programs:
                p = programs[names[pid]]
                p["runs"] += 1.0 / n
                p["module_s"] += d / n
        on = union(clip([(s, s + d) for _, _, s, d, *_ in ops[plane]], lo,
                        hi))
        busy += sum(e - s for s, e in on) / n
        edges = [lo] + [x for iv in on for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        idle += sum(b - a for a, b in gaps) / n
        labels, free = attribute(gaps, spans, DEVICE_CALLS + (IDLE,))
        for k, v in labels.items():
            idle_labels[k] = idle_labels.get(k, 0.0) + v / n
        sched_free += free / n
    ns = 1e-9
    steps = sum(1 for x, _, _ in spans if x == STEP)
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy * ns,
        "idle_s": idle * ns,
        "layers_s": {k: v * ns for k, v in layers.items()},
        "scopes_s": {k: v * ns for k, v in scopes.items()},
        "collectives_s": collectives * ns,
        "programs": {k: {"runs": round(p["runs"]), "module_s": p["module_s"] * ns,
                         "ops_s": p["ops_s"] * ns, "tick": p["tick"],
                         "layers_s": {x: t * ns for x, t in p["layers"].items()},
                         "unscoped_s": {x: t * ns for x, t in p["unscoped"].items()}}
                     for k, p in programs.items()},
        "idle_by_phase_s": {k: v * ns for k, v in idle_labels.items()},
        "steps": steps,
        "data_wait_s": sum(min(e, hi) - max(s, lo) for x, s, e in spans
                           if x == DATA_WAIT) * ns,
        "ticks": round(sum(p["runs"] for p in programs.values() if p["tick"])),
        "sched_gap_s": sched_free * ns if any(x == TICK for x, _, _ in spans)
        else None,
    }


def _tick_programs(modules, spans) -> set:
    """The decode tick's program: the one run most often inside
    ``serve/tick`` spans (none without them)."""
    ticks = sorted((s, e) for n, s, e in spans if n == TICK)
    starts = [s for s, _ in ticks]
    counts: Dict[int, int] = {}
    for mods in modules.values():
        for _, pid, s, _ in mods:
            k = bisect.bisect_right(starts, s) - 1
            if k >= 0 and s <= ticks[k][1]:
                counts[pid] = counts.get(pid, 0) + 1
    return {max(counts, key=counts.get)} if counts else set()


# -- what the metric readers read -----------------------------------------------------
@functools.lru_cache(maxsize=2)
def _cached(xplane: str, mtime: float) -> Dict:
    t0 = time.perf_counter()
    s = summarize(*read(xplane))
    print(f"trace_scopes: {xplane} reduced in {time.perf_counter() - t0:.2f} s",
          file=sys.stderr, flush=True)
    log_tables(s)
    return s


def reduce(path: str) -> Dict:
    """:func:`summarize` of the trace at ``path``, parsed once per file."""
    xplane = find_xplane(path)
    return _cached(xplane, os.path.getmtime(xplane))


def trace_of(record: dict, metrics_file: str) -> Optional[Dict]:
    """For a metric reader at ``bench/metrics/<name>.py``: the reduction of
    the run's trace (``bench/.run/trace``), or None in a run the harness
    traced nothing in."""
    if not record.get("trace"):
        return None
    return reduce(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(metrics_file))), ".run", "trace"))


def scoped(s: Dict) -> bool:
    """Whether any device op carries a layer scope."""
    return any(k in SCOPES for k in s["layers_s"])


def train_ms_per_step(s: Optional[Dict], group: str) -> Optional[float]:
    """Device self-time of the layers of ``TRAIN_GROUPS[group]`` per
    ``gym/step`` span, in ms."""
    if s is None or not s["steps"] or not scoped(s):
        return None
    t = sum(s["layers_s"].get(k, 0.0) for k in TRAIN_GROUPS[group])
    return 1e3 * t / s["steps"]


def scope_ms_per_step(s: Optional[Dict], names: Sequence[str]
                      ) -> Optional[float]:
    """Device self-time of the program's scopes ``names`` (``scopes_s``)
    per ``gym/step`` span, in ms; None where none of them ran."""
    if s is None or not s["steps"]:
        return None
    got = [s["scopes_s"][k] for k in names if k in s["scopes_s"]]
    if not got:
        return None
    return 1e3 * sum(got) / s["steps"]


def collective_ms_per_step(s: Optional[Dict]) -> Optional[float]:
    """Device self-time of the collective ops (``collectives_s``) per
    ``gym/step`` span, in ms; None where no exchange ran (one chip)."""
    if s is None or not s["steps"] or not s["collectives_s"]:
        return None
    return 1e3 * s["collectives_s"] / s["steps"]


def tick_ms(s: Optional[Dict], part: str) -> Optional[float]:
    """The decode tick program's device self-time per tick, in ms:
    ``kv`` (``kv_read`` + ``kv_write``), ``copy`` (unscoped copies) or
    ``model`` (the rest)."""
    if s is None or not s["ticks"] or not scoped(s):
        return None
    prog = next(p for p in s["programs"].values() if p["tick"])
    lay = prog["layers_s"]
    if part == "model":
        t = sum(v for k, v in lay.items()
                if not any(k in g for g in TICK_GROUPS.values()))
    else:
        t = sum(lay.get(k, 0.0) for k in TICK_GROUPS[part])
    return 1e3 * t / s["ticks"]


def log_tables(s: Dict, out=sys.stderr) -> None:
    """The per-program, per-layer table and the idle-by-phase table."""
    def p(msg):
        print(msg, file=out, flush=True)

    p(f"trace_scopes: window {s['window_s']:.4f} s, device busy "
      f"{s['busy_s']:.4f} s, idle {s['idle_s']:.4f} s; {s['steps']} "
      f"gym/step spans, {s['ticks']} decode ticks")
    for name, prog in sorted(s["programs"].items(),
                             key=lambda kv: -kv[1]["ops_s"]):
        runs = max(prog["runs"], 1)
        p(f"trace_scopes: program {name}{' (decode tick)' if prog['tick'] else ''}"
          f": {prog['runs']} runs, {1e3 * prog['module_s'] / runs:.3f} ms "
          f"a run on the device, {1e3 * prog['ops_s'] / runs:.3f} ms of ops")
        for layer, t in sorted(prog["layers_s"].items(), key=lambda kv: -kv[1]):
            p(f"trace_scopes:   {layer:>10} {1e3 * t / runs:10.3f} ms a run "
              f"({100 * t / max(prog['ops_s'], 1e-30):5.1f} %)")
        cats: Dict[str, float] = {}
        for op, t in prog["unscoped_s"].items():
            cat = op.rsplit(" [", 1)[1][:-1] if op.endswith("]") else "?"
            cats[cat] = cats.get(cat, 0.0) + t
        for cat, t in sorted(cats.items(), key=lambda kv: -kv[1]):
            p(f"trace_scopes:     unscoped [{cat}] {1e3 * t / runs:.3f} ms a run")
        top = sorted(prog["unscoped_s"].items(), key=lambda kv: -kv[1])[:8]
        for op, t in top:
            p(f"trace_scopes:     unscoped {op} {1e3 * t / runs:.3f} ms a run")
    if s["collectives_s"]:
        p(f"trace_scopes: collectives {1e3 * s['collectives_s']:.3f} ms of "
          f"ops ({100 * s['collectives_s'] / max(s['busy_s'], 1e-30):.1f} % "
          f"of busy)")
    for label, t in sorted(s["idle_by_phase_s"].items(), key=lambda kv: -kv[1]):
        p(f"trace_scopes: idle under {label}: {1e3 * t:.3f} ms "
          f"({100 * t / max(s['idle_s'], 1e-30):.1f} % of idle)")
    if s["sched_gap_s"] is not None:
        p(f"trace_scopes: idle outside every device-calling engine span and "
          f"serve/idle: {1e3 * s['sched_gap_s']:.3f} ms")


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1]), indent=1))
