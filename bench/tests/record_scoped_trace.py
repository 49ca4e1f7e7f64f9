#!/usr/bin/env python3
"""Record the small TPU trace ``data/tpu_v5e_scoped.xplane.pb`` that
``test_trace_scopes.py`` reads: one jitted function with two layer scopes
(``attention``, ``mlp``), called three times, each call inside the
program's ``serve/tick`` span, with host gaps between the calls, under
the harness's profiler options (``bench/harness.py``).

    python3 bench/tests/record_scoped_trace.py <out dir>   # on one TPU

Prints the path of the ``.xplane.pb`` it wrote.
"""
import glob
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.telemetry.spans import ATTENTION, MLP, scope, span  # noqa: E402


@jax.jit
def layer(x, w):
    with scope(ATTENTION):
        x = x + jnp.tanh(x @ w)
    with scope(MLP):
        return x + jax.nn.relu(x @ w.T)


def main(out: str) -> str:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: the fixture is a TPU trace")
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (1024, 1024), jnp.bfloat16)
    w = jax.random.normal(k2, (1024, 1024), jnp.bfloat16) * 0.03
    layer(x, w).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(3):
            with span("serve/tick"):
                x = layer(x, w)
                x.block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    return sorted(glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]


if __name__ == "__main__":
    print(main(sys.argv[1]))
