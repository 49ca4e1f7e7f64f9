"""A run with its timed path broken underneath comes out not correct: the
harness's look for a chip is skipped and the rest of the run is driven at
CPU size, once for each fault a cell can have (on one chip there is no
exchange between chips to leave out)."""
import jax
import jax.numpy as jnp
import pytest

from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = str(tmp_path_factory.mktemp("tiny"))
    tiny.make(r)
    return r


def state_unchanged(ctx, gym):
    """The step computes its metrics but returns its state unchanged."""
    raw = jax.jit(gym._build_step(None, ()))
    gym._step = lambda s, b: (s, raw(s, b)[1])


def half_batch(ctx, gym):
    """Half of every batch is left out; the mean is taken over the rest."""
    step = gym._step
    gym._step = lambda s, b: step(s, {k: v[:v.shape[0] // 2]
                                      for k, v in b.items()})


def token_altered(ctx, engine):
    """Every decoded token is moved by one id where the tick produces it."""
    tick, vocab = engine._tick, engine.model.cfg.vocab

    def altered(params, cache, slots, *rest):
        cache, slots, sampled, finished = tick(params, cache, slots, *rest)
        slots = dict(slots, tokens=(slots["tokens"] + 1) % vocab)
        return cache, slots, (sampled + 1) % vocab, finished

    engine._tick = altered


def cache_unchanged(ctx, engine):
    """The decode tick returns the KV cache it was given, unwritten."""
    tick = engine._tick

    def stale(params, cache, slots, *rest):
        kept = jax.tree_util.tree_map(jnp.copy, cache)
        _, slots, sampled, finished = tick(params, cache, slots, *rest)
        return kept, slots, sampled, finished

    engine._tick = stale


CASES = [
    ("qwen1.5-0.5b.train.seq2k", state_unchanged),
    ("qwen1.5-0.5b.train.seq2k", half_batch),
    ("qwen1.5-0.5b.serve.chat", token_altered),
    ("qwen1.5-0.5b.serve.chat", cache_unchanged),
    ("qwen1.5-0.5b.serve.batch", token_altered),
    ("qwen1.5-0.5b.serve.batch", cache_unchanged),
]


@pytest.mark.parametrize("cell,fault", CASES,
                         ids=[f"{c}-{f.__name__}" for c, f in CASES])
def test_fault_is_not_correct(root, cell, fault):
    out = tiny.run(root, ["--workload", cell, "--seed", "77", "--seconds",
                          "1", "--trace", "0"], hooks=[fault])
    assert out["correct"] is False, out["checks"]
