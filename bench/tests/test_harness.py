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
