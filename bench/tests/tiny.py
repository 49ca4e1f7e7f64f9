"""A copy of the benchmark's data files at CPU size, for the harness's own
tests: the same cells, configurations and mixes with every width cut to a
few dozen."""
from __future__ import annotations

import io
import json
import os
import shutil
import time
from contextlib import redirect_stdout

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: Limits at this size, set between the program's readings and the
#: control's on seeds 1-4 (CPU, ``bench/calibrate.py``'s readings at the
#: tiny sizes): serving, program <= 0.19 and control >= 0.70; training,
#: loss 0.0046 / 0.0147 and gradient 0.023 / 0.023. The update norm does
#: not separate the control at this size (0.0135 / 0.010); its limit
#: catches a state left unchanged, which reads 1.
LIMITS = {
    "serve": {"logit_gap": 0.4},
    "dense_decoder": {"loss_gap": 0.009, "grad_norm_gap": 0.05,
                      "update_norm_gap": 0.5},
}


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f)


def make(root: str) -> dict:
    """Write ``BENCHMARK.json`` and ``bench/{configs,workloads,traffic,
    metrics}`` under ``root``; returns the benchmark."""
    for d in ("configs", "workloads", "traffic"):
        os.makedirs(os.path.join(root, "bench", d), exist_ok=True)
    shutil.copytree(os.path.join(REPO, "bench", "metrics"),
                    os.path.join(root, "bench", "metrics"),
                    dirs_exist_ok=True)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name in sorted(os.listdir(os.path.join(REPO, "bench", "configs"))):
        path = os.path.join("bench", "configs", name)
        with open(os.path.join(REPO, path)) as f:
            cfg = json.load(f)
        if cfg["family"] == "dense_decoder":
            # weights ten times the published scale: at this width the
            # served tokens then depend on their context, not only on the
            # token before them
            cfg.update(hidden_size=64, num_attention_heads=4,
                       num_key_value_heads=2, num_hidden_layers=2,
                       intermediate_size=128, vocab_size=512,
                       initializer_range=0.2)
        else:
            cfg.update(d_model=64, n_layer=2, vocab_size=509,
                       padded_vocab_size=512)
            cfg["mamba2_module"].update(headdim=16, d_state=16, chunk_size=32)
        _dump(cfg, os.path.join(root, path))
    families = {}
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            families[c["name"]] = json.load(f)["family"]
    for w in bench["workloads"]:
        with open(os.path.join(REPO, "bench", "workloads",
                               f"{w['name']}.json")) as f:
            s = json.load(f)
        if s["job"] == "train":
            s.update(global_batch=4, seq_len=64, data_steps=8, trace_steps=2,
                     limits=LIMITS[families[w["config"]]])
        else:
            s.update(n_slots=4, max_len=256, prefill_chunk=32, max_output=64,
                     check_tokens=40, trace_seconds=0.5,
                     limits=LIMITS["serve"])
        _dump(s, os.path.join(root, "bench", "workloads", f"{w['name']}.json"))
    for t in {w["traffic"] for w in bench["workloads"]}:
        with open(os.path.join(REPO, "bench", "traffic", f"{t}.json")) as f:
            m = json.load(f)
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


def run(root: str, argv, hooks=()) -> dict:
    """One in-process run on the CPU (the look for a chip skipped); the
    parsed result line."""
    import jax

    from bench import harness

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = harness.main(list(argv), t_start=time.perf_counter(),
                          devices=jax.devices()[:1], root=root,
                          hooks=hooks)
    assert rc == 0, rc
    return json.loads(buf.getvalue().strip().splitlines()[-1])
