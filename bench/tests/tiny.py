"""A copy of the benchmark's data files at CPU size, for the harness's own
tests: the same cells, configurations and mixes with every width cut to a
few dozen.

Each family brings its own CPU size and limits: ``TINY = {"config": {...},
"limits": {"train": {...}, "serve": {...}}}`` in its reference module,
``bench/reference/<family>.py``. A config file's keys are replaced by
``TINY["config"]``'s, and a nested group's keys merged into it. A cell of
several chips runs in a subprocess on as many forced CPU devices.

    python -m bench.tests.tiny <root> <bench/run.py arguments>
"""
from __future__ import annotations

import importlib
import io
import json
import os
import shutil
import subprocess
import sys
import time
from contextlib import redirect_stdout

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: the job kinds whose limits a family's ``TINY["limits"]["serve"]`` gives
SERVE_JOBS = ("serve_online", "serve_batch")


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f)


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cut(cfg: dict, tiny: dict) -> dict:
    """``cfg`` with ``tiny``'s keys, a nested group merged key by key."""
    for k, v in tiny.items():
        if isinstance(v, dict) and isinstance(cfg.get(k), dict):
            cfg[k] = dict(cfg[k], **v)
        else:
            cfg[k] = v
    return cfg


def make(root: str) -> dict:
    """Write ``BENCHMARK.json`` and ``bench/{configs,workloads,traffic,
    metrics}`` under ``root``, cut to CPU size; returns the benchmark."""
    for d in ("configs", "workloads", "traffic"):
        os.makedirs(os.path.join(root, "bench", d), exist_ok=True)
    shutil.copytree(os.path.join(REPO, "bench", "metrics"),
                    os.path.join(root, "bench", "metrics"),
                    dirs_exist_ok=True)
    bench = _load(REPO, "BENCHMARK.json")
    tiny = {}
    for name in sorted(os.listdir(os.path.join(REPO, "bench", "configs"))):
        path = os.path.join("bench", "configs", name)
        cfg = _load(REPO, path)
        fam = cfg["family"]
        tiny[fam] = importlib.import_module(f"bench.reference.{fam}").TINY
        _dump(cut(cfg, tiny[fam]["config"]), os.path.join(root, path))
    families = {c["name"]: _load(REPO, c["file"])["family"]
                for c in bench["configs"]}
    for w in bench["workloads"]:
        s = _load(REPO, "bench", "workloads", f"{w['name']}.json")
        limits = tiny[families[w["config"]]]["limits"]
        if s["job"] == "train":
            s.update(global_batch=4, seq_len=64, data_steps=8, trace_steps=2,
                     limits=limits["train"])
        elif s["job"] in SERVE_JOBS:
            s.update(n_slots=4, max_len=256, prefill_chunk=32, max_output=64,
                     check_tokens=40, trace_seconds=0.5,
                     limits=limits["serve"])
        else:
            raise ValueError(f"no CPU size for job {s['job']!r}")
        _dump(s, os.path.join(root, "bench", "workloads", f"{w['name']}.json"))
    for t in {w["traffic"] for w in bench["workloads"]}:
        m = _load(REPO, "bench", "traffic", f"{t}.json")
        if m["kind"] == "requests":
            m["rate_per_s"] = 6.0
            if "system_prompts" in m:
                m["system_prompts"]["len"] = 32
            m["prompt_len"] = {"lognormal_median": 16, "sigma": 1.0,
                               "min": 4, "max": 64}
            m["output_len"] = {"uniform_min": 8, "uniform_max": 48}
        _dump(m, os.path.join(root, "bench", "traffic", f"{t}.json"))
    _dump(bench, os.path.join(root, "BENCHMARK.json"))
    return bench


def _in_process(root: str, argv, chips: int, hooks=()) -> str:
    """One run on the CPU (the look for a chip skipped); its stdout."""
    import jax

    from bench import harness

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = harness.main(list(argv), t_start=time.perf_counter(),
                          devices=jax.devices()[:chips], root=root,
                          hooks=hooks)
    assert rc == 0, rc
    return buf.getvalue()


def run(root: str, argv, hooks=()) -> dict:
    """One run of a cell of the tree at ``root``; the parsed result line. A
    cell of one chip runs in this process; one of several in a subprocess
    with as many forced CPU devices (no hooks reach it there)."""
    cell = argv[argv.index("--workload") + 1]
    chips = {w["name"]: w["chips"]
             for w in _load(root, "BENCHMARK.json")["workloads"]}[cell]
    if chips == 1:
        out = _in_process(root, argv, 1, hooks)
    else:
        assert not hooks, "hooks reach only a cell of one chip"
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}",
                   PYTHONPATH=os.pathsep.join([REPO, os.path.join(REPO, "src")]))
        p = subprocess.run([sys.executable, "-m", "bench.tests.tiny", root,
                            *argv], cwd=REPO, env=env, capture_output=True,
                           text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-4000:]
        out = p.stdout
    return json.loads(out.strip().splitlines()[-1])


if __name__ == "__main__":
    import jax

    _root, _argv = sys.argv[1], sys.argv[2:]
    print(_in_process(_root, _argv, len(jax.devices())), end="", flush=True)
