"""The harness: finds a cell's files by name, runs its job once, reads its
metrics and prints the result line.

Everything that belongs to one cell, configuration, traffic mix, job kind
or metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

- ``bench/workloads/<cell>.json``: job kind, shapes, engine settings and
  the limits of the correctness comparison;
- the configuration's ``file`` (``bench/configs/<config>.json``), whose
  ``family`` names its plain reference ``bench/reference/<family>.py`` and
  its program adapter ``bench/program/<family>.py``;
- ``bench/traffic/<traffic>.json``: the mix the one generator
  (``bench/traffic/generate.py``) reads;
- ``bench/jobs/<job>.py``: ``run(ctx)`` drives the program;
- ``bench/metrics/<metric>.py``: ``read(record)`` takes one metric from
  what the job recorded, or returns None when it finds nothing to read.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts: str) -> Any:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_file_module(path: str):
    """A module from a file whose name need not be an identifier
    (``bench/metrics/mfu.train.py``)."""
    name = "bench_file_" + os.path.relpath(path, ROOT).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_chips(chips: int):
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX finds no device: {e}") from None
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX's first device is on {devices[0].platform!r}: "
                     f"the benchmark measures the TPU and has no fallback")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def take_chips(bench: dict, cell: str):
    """The chips ``cell`` asks for, with JAX's persistent compilation cache
    on (the program's fixed directory in the checkout, or
    ``JAX_COMPILATION_CACHE_DIR``) for every program, however small."""
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}[cell]
    devices = require_chips(int(chips))
    from repro.run.cli import use_compile_cache

    import jax

    log(f"compile cache: {use_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return devices


class CompileCounter:
    """Counts programs lowered and compiled while ``active``."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.active = False
        self.lowered = 0
        self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active:
            if event == self.LOWER:
                self.lowered += 1
            elif event == self.COMPILE:
                self.compiled += 1


class Run:
    """One run of one cell: what the job reads, and what it records."""

    def __init__(self, root: str, workload: str, seed: int, seconds: float,
                 trace: bool, t_start: float, devices, hooks=()):
        self.root = root
        self.bench = load_json(root, "BENCHMARK.json")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no cell {workload!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
        self.cell = cells[workload]
        self.name = workload
        self.spec = load_json(root, "bench", "workloads", f"{workload}.json")
        confs = {c["name"]: c for c in self.bench["configs"]}
        self.config = load_json(root, confs[self.cell["config"]]["file"])
        self.traffic = load_json(root, "bench", "traffic",
                                 f"{self.cell['traffic']}.json")
        fam = self.config["family"]
        self.ref = importlib.import_module(f"bench.reference.{fam}")
        self.prog = importlib.import_module(f"bench.program.{fam}")
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.devices = devices
        self.t_start = t_start
        self.check_s = 0.0          # time spent capturing what is compared
        self.record: Dict[str, Any] = {"job": self.spec["job"],
                                       "chips": len(devices),
                                       "platform": devices[0].platform,
                                       "device_kind": devices[0].device_kind}
        self.checks: List[tuple] = []
        self.attempted = 0
        self.failed = 0
        self.scratch = os.path.join(root, "bench", ".run")
        os.makedirs(self.scratch, exist_ok=True)
        self.counter: Optional[CompileCounter] = None
        # called as hook(ctx, gym or engine) once the job has built it: the
        # harness's own tests break the timed path there
        self.hooks = list(hooks)
        self._trace_t0 = None
        # seconds the host spent starting and stopping the profiler: a job
        # that toggles it inside a timed call takes them out again
        self.record["trace_toggle_s"] = 0.0

    # -- weights ------------------------------------------------------------
    def weights(self) -> dict:
        """The seed's weights; on a cell of several chips drawn into
        :meth:`placement`, never whole on one chip."""
        from bench import weights

        across = self.devices if len(self.devices) > 1 else None
        return weights.make(self.ref.param_specs(self.config), self.seed,
                            devices=across)

    def placement(self) -> Optional[dict]:
        """Where :meth:`weights` puts each leaf: None (the default device)
        on one chip, else ``bench.weights.placement`` over the cell's."""
        from bench import weights

        if len(self.devices) == 1:
            return None
        return weights.placement(self.ref.param_specs(self.config),
                                 self.devices)

    def to_program(self, flat: dict) -> dict:
        tree: Dict[str, Any] = {}
        for name, arr in flat.items():
            path = self.prog.PATHS[name]
            node = tree
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = arr
        return tree

    def from_program(self, tree: dict) -> dict:
        flat = {}
        for name, path in self.prog.PATHS.items():
            node = tree
            for k in path:
                node = node.get(k) if isinstance(node, dict) else None
            if node is not None:
                flat[name] = node
        return flat

    # -- phases ---------------------------------------------------------------
    @contextlib.contextmanager
    def capture(self):
        """Time spent taking what the correctness check compares is not
        set-up: it is left out of ``setup_s``."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.check_s += time.perf_counter() - t0

    def window_begins(self) -> None:
        self.record["setup_s"] = (time.perf_counter() - self.t_start
                                  - self.check_s)
        if self.counter is not None:
            self.counter.active = True

    def window_ends(self) -> None:
        if self.counter is not None:
            self.counter.active = False
        stats = self.devices[0].memory_stats() or {}
        self.record["memory_peak_bytes"] = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in self.devices) if stats else None

    def span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"bench/{name}")

    def trace_start(self) -> None:
        import jax

        t0 = time.perf_counter()
        d = os.path.join(self.scratch, "trace")
        shutil.rmtree(d, ignore_errors=True)
        # host spans (TraceAnnotation) and device ops; no Python call
        # tracing, which slows the host and fills the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(d, profiler_options=opts)
        self._window_span = jax.profiler.TraceAnnotation("bench/window")
        self._window_span.__enter__()
        self._trace_t0 = time.perf_counter()
        self.record["trace_toggle_s"] += self._trace_t0 - t0

    def trace_stop(self) -> None:
        import jax

        self._window_span.__exit__(None, None, None)
        t1 = time.perf_counter()
        self.record["trace_host_s"] = t1 - self._trace_t0
        jax.profiler.stop_trace()
        self.record["trace_toggle_s"] += time.perf_counter() - t1

    def reduce_trace(self) -> None:
        """Reduce the traced window (after the run: parsing is slow). The
        trace stays in ``bench/.run/trace`` until the next traced run."""
        from bench import trace_reduce

        if "trace_host_s" not in self.record:
            return
        try:
            self.record["trace"] = trace_reduce.reduce(
                os.path.join(self.scratch, "trace"))
        except ValueError as e:
            if self.devices[0].platform == "tpu":
                raise
            log(f"trace: {e} (no accelerator plane on {self.devices[0].platform})")

    def log_memory(self, name: str, lowered) -> None:
        """The compiler's memory analysis of one timed program, on its own
        line: the allocator's ``memory_peak_bytes`` misses temporaries."""
        t0 = time.perf_counter()
        m = lowered.compile().memory_analysis()
        if m is None:
            return
        self.log_detail(
            f"memory_analysis {name}: arguments {m.argument_size_in_bytes} B, "
            f"outputs {m.output_size_in_bytes} B, aliased "
            f"{m.alias_size_in_bytes} B, temporaries {m.temp_size_in_bytes} B,"
            f" code {m.generated_code_size_in_bytes} B "
            f"({time.perf_counter() - t0:.2f} s to read)")

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append((name, float(value), float(limit)))

    def log_detail(self, msg: str) -> None:
        log(f"{self.name}: {msg}")


def _metric_names(bench: dict, section: str, cell: str) -> List[dict]:
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def result_line(run: Run, trace: bool) -> dict:
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in _metric_names(run.bench, section, run.name):
        reader = load_file_module(os.path.join(run.root, "bench", "metrics",
                                               f"{m['name']}.py"))
        v = reader.read(run.record)
        if v is None:
            if section == "end_to_end":
                raise RuntimeError(f"end-to-end metric {m['name']} read "
                                   f"nothing in cell {run.name}")
            continue
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    d = run.devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": run.record.get("memory_peak_bytes")}
    out = {"correct": bool(run.checks) and all(
               math.isfinite(v) and v <= lim for _, v, lim in run.checks),
           "attempted": run.attempted, "failed": run.failed,
           "metrics": metrics, "device": device}
    tr = run.record.get("trace")
    if trace and tr:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["checks"] = {n: {"value": _finite(v), "limit": lim}
                     for n, v, lim in run.checks}
    for m in metrics.values():
        m["value"] = _finite(m["value"])
    return out


def _finite(v: float) -> Optional[float]:
    """A reading for the JSON line: a number, or null where it is not
    finite (a run that produced no number)."""
    return v if math.isfinite(v) else None


def main(argv=None, *, t_start: Optional[float] = None, devices=None,
         root: str = ROOT, hooks=()) -> int:
    """Run one cell once. ``devices`` given skips the look for a chip, and
    ``hooks`` reach into the job (the harness's own tests on the CPU)."""
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        log(f"no cell {args.workload!r} in BENCHMARK.json")
        return 2
    if devices is None:
        try:
            devices = take_chips(bench, args.workload)
        except NoChip as e:
            log(f"bench: {e}")
            return 3
    run = Run(root, args.workload, args.seed, args.seconds,
              bool(args.trace), t_start, devices, hooks)
    run.counter = CompileCounter()
    job = importlib.import_module(f"bench.jobs.{run.spec['job']}")
    job.run(run)
    run.reduce_trace()
    log(f"programs lowered in the window: {run.counter.lowered}, "
        f"compiled: {run.counter.compiled}")
    out = result_line(run, bool(args.trace))
    for n, v, lim in run.checks:
        log(f"check {n}: {v!r} (limit {lim!r})")
    print(json.dumps(out), flush=True)
    return 0
