"""Layer scopes in the compiled programs: every matmul of the train step
and of the paged decode tick carries a name of the scope vocabulary in its
HLO ``op_name``, the name a profiler trace shows as ``tf_op``."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced
from repro.models import build_model
from repro.optim.adamw import AdamW
from repro.telemetry import spans as SP
from repro.train import steps as ST

OP_NAME = re.compile(r'op_name="([^"]*)"')


def _scope_of(op_name):
    """The innermost vocabulary scope of an HLO ``op_name`` (the program's
    own ``jit(...)`` component left out), or None."""
    found = None
    for part in op_name.split("/")[1:]:
        while re.fullmatch(r"\w+\((.*)\)", part):
            part = re.fullmatch(r"\w+\((.*)\)", part).group(1)
        if part in SP.SCOPES:
            found = part
    return found


def _scopes(hlo_text):
    """(scope of every dot, every scope any op carries)."""
    dots, seen = [], set()
    for line in hlo_text.splitlines():
        m = OP_NAME.search(line)
        if not m:
            continue
        s = _scope_of(m.group(1))
        seen.add(s)
        if " dot(" in line:
            dots.append((s, m.group(1)))
    return dots, seen


def _slots(n):
    return {"tokens": jnp.zeros((n,), jnp.int32),
            "pos": jnp.zeros((n,), jnp.int32),
            "active": jnp.ones((n,), bool),
            "n_gen": jnp.zeros((n,), jnp.int32),
            "max_gen": jnp.ones((n,), jnp.int32),
            "eos": jnp.full((n,), -1, jnp.int32),
            "key": jnp.zeros((n, 2), jnp.uint32),
            "temperature": jnp.ones((n,), jnp.float32),
            "top_k": jnp.zeros((n,), jnp.int32),
            "top_p": jnp.ones((n,), jnp.float32)}


def test_every_dot_of_train_step_and_paged_tick_is_scoped():
    model = build_model(get_reduced("qwen1p5_0p5b"))
    params = model.init(jax.random.PRNGKey(0))
    opt = AdamW(lr=1e-3)
    state = ST.init_train_state(model, opt, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    train = jax.jit(ST.make_train_step(model, opt)).lower(
        state, batch).compile().as_text()
    n_slots, max_pages = 2, 4
    tick = jax.jit(ST.make_engine_step(model, paged=True)).lower(
        params, model.init_paged_cache(8, 4), _slots(n_slots),
        jnp.zeros((n_slots, max_pages), jnp.int32)).compile().as_text()
    seen = set()
    for text in (train, tick):
        dots, scopes = _scopes(text)
        assert dots
        assert [d for d in dots if d[0] is None] == []
        seen |= scopes
    assert {"attention", "mlp", "head", "loss", "optimizer", "kv_read",
            "kv_write", "sample"} <= seen


@pytest.mark.parametrize("op_name,scope", [
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/bsd,df->bsf/dot_general", "mlp"),
    ("jit(train_step)/transpose(jvp(head))/bsd,dv->bsv/dot_general", "head"),
    ("jit(engine_step)/while/body/closed_call/attention/kv_read/gather",
     "kv_read"),
    ("jit(loss)/add", None),
])
def test_scope_of_op_name(op_name, scope):
    assert _scope_of(op_name) == scope


def test_unknown_scope_is_an_error():
    with pytest.raises(ValueError):
        SP.scope("attn")
