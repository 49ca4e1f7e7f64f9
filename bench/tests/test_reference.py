"""The plain references against the program's models at CPU size, on the
weights the benchmark makes from a seed: the same logits when the program
is run in float32, and the float8 control further from them than
bfloat16."""
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = str(tmp_path_factory.mktemp("tiny"))
    tiny.make(r)
    return r


def _setup(root, name):
    with open(os.path.join(root, "bench", "configs", f"{name}.json")) as f:
        c = json.load(f)
    ref = importlib.import_module(f"bench.reference.{c['family']}")
    prog = importlib.import_module(f"bench.program.{c['family']}")
    w = weights.make(ref.param_specs(c), 1234567890123)
    tree = {}
    for k, a in w.items():
        node = tree
        for p in prog.PATHS[k][:-1]:
            node = node.setdefault(p, {})
        node[prog.PATHS[k][-1]] = a
    from repro.models import build_model

    model = build_model(prog.arch_config(c))
    # the program's forward in float32 throughout
    model.embed_tokens = lambda params, tokens, dtype=None: \
        params["embed"][tokens]
    return c, ref, w, model, tree


@pytest.mark.parametrize("name", ["qwen1.5-0.5b", "mamba2-780m"])
def test_reference_logits_match_program(root, name):
    c, ref, w, model, tree = _setup(root, name)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 3, 500)
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply(tree, {"tokens": tokens})
        want = ref.logits(w, tokens, c)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("name", ["qwen1.5-0.5b", "mamba2-780m"])
def test_fp8_control_is_further_than_bf16(root, name):
    c, ref, w, _, _ = _setup(root, name)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 3, 500)
    f32 = np.asarray(ref.logits(w, tokens, c, "f32"))
    bf16 = np.asarray(ref.logits(w, tokens, c, "bf16"))
    fp8 = np.asarray(ref.logits(w, tokens, c, "fp8"))
    e_bf16 = np.max(np.abs(bf16 - f32))
    e_fp8 = np.max(np.abs(fp8 - f32))
    assert 0 < e_bf16 < e_fp8 / 3


def test_reference_loss_gradient_is_finite(root):
    c, ref, w, _, _ = _setup(root, "qwen1.5-0.5b")
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 65), 3, 500)
    g = jax.grad(lambda w: ref.loss_sum(w, tokens[:, :-1], tokens[:, 1:],
                                        c))(w)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in g.values())
    # a key bias moves only through the rotary positions: nonzero
    assert float(jnp.linalg.norm(g["layers/bk"])) > 0
