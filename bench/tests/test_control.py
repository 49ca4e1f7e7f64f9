"""The control comes out not correct: the plain reference put in the
program's place at the precision below the one the configuration states
(float8 products for bfloat16 compute) fails at least one of a cell's
numbers, while the program passes all of them (CPU size, the tiny copy's
limits). On the chip the same readings, at the cells' own sizes, come from
``bench/calibrate.py``."""
import time

import jax
import pytest

from bench import calibrate, harness
from bench.tests import tiny

CELLS = ["qwen1.5-0.5b.train.seq2k", "qwen1.5-0.5b.serve.chat",
         "qwen1.5-0.5b.serve.batch"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = str(tmp_path_factory.mktemp("tiny"))
    tiny.make(r)
    return r


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_program_passes(root, cell):
    ctx = harness.Run(root, cell, 4, 2.0, False, time.perf_counter(),
                      jax.devices()[:1])
    fn = (calibrate.train_readings if ctx.spec["job"] == "train"
          else calibrate.serve_readings)
    out = fn(ctx, control=True)
    limits = ctx.spec["limits"]

    def fails(readings):
        return any(v > limits[k] for k, (v, _) in readings.items())

    assert not fails(out["program"]), out["program"]
    assert fails(out["control"]), out["control"]
    fault = out["half_batch" if "half_batch" in out else "token_altered"]
    assert fails(fault), fault
