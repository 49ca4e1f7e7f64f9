"""The harness at CPU size: every cell runs end to end; a cell and a
per-layer metric added only as new files are found by name and run; and
``bench/run.py`` ends non-zero with no result line where it finds no TPU
or no program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.tests import tiny

REPO = tiny.REPO
CELLS = ["qwen1.5-0.5b.train.seq2k", "qwen1.5-0.5b.serve.chat",
         "qwen1.5-0.5b.serve.batch"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = str(tmp_path_factory.mktemp("tiny"))
    tiny.make(r)
    return r


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(root, cell):
    out = tiny.run(root, ["--workload", cell, "--seed", "2147483649",
                          "--seconds", "2", "--trace", "0"])
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "setup_s" in out["metrics"]
    assert len(out["metrics"]) >= 2
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


def test_cell_and_metric_added_as_files_only(root, tmp_path):
    r = str(tmp_path / "copy")
    shutil.copytree(root, r)
    b = os.path.join(r, "bench")
    # a new traffic mix, a new cell of it and a new per-layer metric:
    # new files plus their entries in BENCHMARK.json, nothing else edited
    with open(os.path.join(b, "traffic", "chat.json")) as f:
        mix = json.load(f)
    mix["rate_per_s"] = 12.0
    with open(os.path.join(b, "traffic", "chat_fast.json"), "w") as f:
        json.dump(mix, f)
    shutil.copy(os.path.join(b, "workloads", "qwen1.5-0.5b.serve.chat.json"),
                os.path.join(b, "workloads",
                             "qwen1.5-0.5b.serve.chat-fast.json"))
    with open(os.path.join(b, "metrics", "completed.serve_online.py"),
              "w") as f:
        f.write("def read(record):\n"
                "    if record.get('job') != 'serve_online':\n"
                "        return None\n"
                "    return float(sum(1 for r in record['rows']"
                " if r.get('finish') == 'length'))\n")
    with open(os.path.join(r, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "qwen1.5-0.5b.serve.chat-fast"
    bench["workloads"].append({"name": cell, "config": "qwen1.5-0.5b",
                               "traffic": "chat_fast", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "qwen1.5-0.5b.serve.chat" in m.get("workloads", ()):
            m["workloads"].append(cell)
    bench["per_layer"].append({"name": "completed.serve_online",
                               "unit": "requests", "better": "higher",
                               "source": "program_counter",
                               "layer": "serve engine scheduler",
                               "moves": "serve_ttft_p95_s",
                               "workloads": [cell]})
    with open(os.path.join(r, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    assert tiny.run(r, ["--workload", cell, "--seed", "5", "--seconds", "2",
                        "--trace", "0"])["correct"]
    out = tiny.run(r, ["--workload", cell, "--seed", "5", "--seconds", "2",
                       "--trace", "1"])
    assert out["correct"]
    assert out["metrics"]["completed.serve_online"]["value"] == \
        out["attempted"] > 20
    assert "tick_ms.serve_online" in out["metrics"]


#: a third family, added as new files: a dense decoder under a name of its
#: own, with its CPU size and an active-parameter count
TWIN_REFERENCE = '''"""A dense decoder under a family name of its own."""
import math

from bench.reference.dense_decoder import (TINY, dims, head, hidden,  # noqa: F401
                                           logits, loss_sum, param_specs)


def active_params(c):
    return sum(math.prod(s) for s, _ in param_specs(c).values())
'''
TWIN_PROGRAM = ('"""The program side of the twin family."""\n'
                "from bench.program.dense_decoder import PATHS, arch_config"
                "  # noqa: F401\n")


def test_family_and_four_chip_cell_added_as_files_only(tmp_path):
    """A new family (reference, adapter, configuration) and a training cell
    of it with a plan and a mesh over four chips: new files plus entries in
    ``BENCHMARK.json`` in a copy of the tree, nothing else edited. The copy's
    tiny tree takes the family's CPU size from its module; the cell runs on
    four forced CPU devices and reads correct."""
    src = str(tmp_path / "src")
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(src, "bench"),
                    ignore=shutil.ignore_patterns(".run", ".dev",
                                                  "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), src)
    os.symlink(os.path.join(REPO, "src"), os.path.join(src, "src"))
    b = os.path.join(src, "bench")
    with open(os.path.join(b, "reference", "twin_decoder.py"), "w") as f:
        f.write(TWIN_REFERENCE)
    with open(os.path.join(b, "program", "twin_decoder.py"), "w") as f:
        f.write(TWIN_PROGRAM)
    with open(os.path.join(b, "configs", "qwen1.5-0.5b.json")) as f:
        cfg = json.load(f)
    cfg.update(name="twin-0.5b", family="twin_decoder")
    with open(os.path.join(b, "configs", "twin-0.5b.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "workloads",
                           "qwen1.5-0.5b.train.seq2k.json")) as f:
        spec = json.load(f)
    spec.update(plan="fsdp", mesh={"data": 4, "model": 1}, global_batch=8)
    cell = "twin-0.5b.train.fsdp4"
    with open(os.path.join(b, "workloads", f"{cell}.json"), "w") as f:
        json.dump(spec, f)
    with open(os.path.join(src, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "twin-0.5b", "source": "test",
                             "file": "bench/configs/twin-0.5b.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": "twin-0.5b",
                               "traffic": "pretrain_packed", "chips": 4,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "qwen1.5-0.5b.train.seq2k" in m.get("workloads", ()):
            m["workloads"].append(cell)
    with open(os.path.join(src, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    root = str(tmp_path / "tiny")
    argv = ["--workload", cell, "--seed", "2147483659", "--seconds", "2",
            "--trace", "0"]
    code = ("import json, sys; from bench.tests import tiny; "
            "tiny.make(sys.argv[1]); "
            "print(json.dumps(tiny.run(sys.argv[1], sys.argv[2:])))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([src, os.path.join(src, "src")]))
    p = subprocess.run([sys.executable, "-c", code, root, *argv], cwd=src,
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    with open(os.path.join(root, "bench", "configs", "twin-0.5b.json")) as f:
        assert json.load(f)["hidden_size"] == 64
    with open(os.path.join(root, "bench", "workloads", f"{cell}.json")) as f:
        assert json.load(f)["mesh"] == {"data": 4, "model": 1}
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"setup_s", "train_tokens_per_s"} <= set(out["metrics"])


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout):
    return any(line.strip().startswith("{") for line in stdout.splitlines())


def test_no_tpu_means_no_result():
    p = _run_cli(REPO)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "TPU" in p.stderr


def test_benchmark_files_alone_mean_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".run", ".dev",
                                                  "__pycache__"))
    env = {"PYTHONPATH": ""}
    p = _run_cli(str(tmp_path), env)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
