"""Per-layer device time from the program's own names: the scope rule, the
partitions and the idle-by-phase attribution on hand-made events, and the
metadata decoder on two traces recorded on one TPU v5e."""
import os

import pytest

from bench import trace_scopes as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PLANE = "/device:TPU:0"


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(train_step)/jvp()/while/body/closed_call/attention/"
     "bsd,dhk->bshk/dot_general", "attention"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/bsd,df->bsf/dot_general", "mlp"),
    ("jit(train_step)/transpose(jvp(head))/bsd,dv->bsv/dot_general", "head"),
    ("jit(f)/transpose(jvp(remat(mlp)))/mul", "mlp"),
    # nesting: the innermost scope wins
    ("jit(engine_step)/while/body/closed_call/attention/kv_read/gather",
     "kv_read"),
    ("jit(engine_step)/sample/vmap(jit(_gumbel))/log", "sample"),
    # the program's own name is not a scope, nor is a primitive
    ("jit(loss)/add", None),
    ("jit(<lambda>)/dot_general:", None),
    ("", None),
])
def test_scope_rule(tf_op, scope):
    assert T.scope_of(tf_op) == scope


def test_layer_of_unscoped_ops():
    assert T.layer_of("%copy.41", "", "data formatting") == "copy"
    assert T.layer_of("%fusion.3", "jit(f)/add", "copy") == "copy"
    assert T.layer_of("%fusion.3", "jit(f)/add", "loop fusion") == "none"
    assert T.layer_of("%copy.2", "jit(f)/mlp/copy", "") == "mlp"


def _op(pid, layer, start, dur, name, collective=False):
    """An op as ``read`` gives it: its program scope is its layer's."""
    return (pid, layer, start, dur, name,
            layer if layer in T.SCOPES else None, collective)


def _two_programs():
    """A train-like program (id 1) run twice, a while op holding its body,
    and a tick program (id 2) run twice with an unscoped copy; ns."""
    modules = {PLANE: [("jit_train_step", 1, 0.0, 100.0),
                       ("jit_train_step", 1, 200.0, 100.0),
                       ("jit_engine_step", 2, 400.0, 50.0),
                       ("jit_engine_step", 2, 500.0, 50.0)]}
    ops = {PLANE: []}
    for t0 in (0.0, 200.0):
        ops[PLANE] += [_op(1, "none", t0, 60.0, "%while"),
                       _op(1, "attention", t0 + 10, 20.0, "%fusion.1"),
                       _op(1, "mlp", t0 + 30, 30.0, "%fusion.2"),
                       _op(1, "optimizer", t0 + 60, 20.0, "%fusion.3"),
                       _op(1, "copy", t0 + 80, 20.0, "%copy.1")]
    for t0 in (400.0, 500.0):
        ops[PLANE] += [_op(2, "kv_read", t0, 10.0, "%fusion.1"),
                       _op(2, "copy", t0 + 10, 20.0, "%copy.1"),
                       _op(2, "mlp", t0 + 30, 15.0, "%fusion.2"),
                       _op(2, "sample", t0 + 45, 5.0, "%fusion.3")]
    return ops, modules


def test_partitions_of_busy_time():
    ops, modules = _two_programs()
    host = [("bench/window", 0.0, 600.0),
            ("gym/data_wait", 0.0, 5.0), ("gym/step", 5.0, 150.0),
            ("gym/data_wait", 150.0, 160.0), ("gym/step", 160.0, 350.0),
            ("serve/tick", 390.0, 460.0), ("serve/schedule", 460.0, 490.0),
            ("serve/tick", 490.0, 560.0)]
    s = T.summarize(ops, modules, host)
    assert s["busy_s"] == pytest.approx(300e-9)
    assert s["steps"] == 2 and s["ticks"] == 2
    assert s["data_wait_s"] == pytest.approx(15e-9)
    # the loop keeps 60 - 20 - 30 of its own time, in "none"
    train = s["programs"]["jit_train_step"]
    assert train["layers_s"] == pytest.approx(
        {"none": 20e-9, "attention": 40e-9, "mlp": 60e-9,
         "optimizer": 40e-9, "copy": 40e-9})
    assert train["runs"] == 2 and not train["tick"]
    tick = s["programs"]["jit_engine_step"]
    assert tick["tick"] and tick["module_s"] == pytest.approx(100e-9)
    # the five train groups partition every op's time, both programs
    parts = [T.train_ms_per_step(s, g) for g in T.TRAIN_GROUPS]
    assert sum(parts) == pytest.approx(1e3 * s["busy_s"] / 2)
    # the tick's three parts partition the tick program's time, per tick
    kv, copy, model = (T.tick_ms(s, p) for p in ("kv", "copy", "model"))
    assert (kv, copy, model) == pytest.approx((10e-6, 20e-6, 20e-6))
    assert kv + copy + model == pytest.approx(1e3 * tick["ops_s"] / 2)


def test_idle_by_phase_and_scheduler_gap():
    ops, modules = _two_programs()
    host = [("bench/window", 0.0, 600.0),
            ("serve/tick", 390.0, 460.0),
            ("serve/schedule", 460.0, 495.0),
            ("serve/page_table", 480.0, 490.0),     # nested in schedule
            ("serve/tick", 495.0, 560.0),
            ("serve/idle", 570.0, 590.0)]
    s = T.summarize(ops, modules, host)
    idle = s["idle_by_phase_s"]
    # busy [0, 100) [200, 300) [400, 450) [500, 550): idle [100, 200),
    # [300, 400), [450, 500), [550, 600)
    assert s["idle_s"] == pytest.approx(300e-9)
    assert idle == pytest.approx({
        "no span": 190e-9,               # before the first span opens
        "serve/tick": 35e-9,             # 390-400, 450-460, 495-500, 550-560
        "serve/schedule": 25e-9,         # 460-480, 490-495
        "serve/page_table": 10e-9,       # nested in schedule: innermost wins
        "after serve/tick": 10e-9,       # 560-570
        "serve/idle": 20e-9,
        "after serve/idle": 10e-9})
    # outside the device-calling spans and serve/idle: no span, schedule's
    # own part, and the two "after" pieces
    assert s["sched_gap_s"] == pytest.approx((190 + 25 + 10 + 10) * 1e-9)


def test_program_without_names_reads_nothing():
    """A trace of a program with no scopes and no phase spans (the parent
    of the change that added them) gives every reader None."""
    ops = {PLANE: [_op(1, "none", 0.0, 10.0, "%fusion"),
                   _op(1, "copy", 10.0, 5.0, "%copy.1")]}
    modules = {PLANE: [("jit_step", 1, 0.0, 15.0)]}
    s = T.summarize(ops, modules, [("bench/window", 0.0, 20.0),
                                   ("bench/tick", 0.0, 15.0)])
    assert s["steps"] == 0 and s["ticks"] == 0 and s["sched_gap_s"] is None
    assert all(T.train_ms_per_step(s, g) is None for g in T.TRAIN_GROUPS)
    assert T.tick_ms(s, "kv") is None
    assert T.trace_of({"job": "train"}, __file__) is None


def test_decoder_reads_the_first_fixture():
    """``tpu_v5e_steps.xplane.pb``: a program with no scopes."""
    meta = T.op_metadata(os.path.join(DATA, "tpu_v5e_steps.xplane.pb"))
    ops = meta[PLANE]
    fusion = [v for (pid, name), v in ops.items() if name == "%fusion"]
    assert fusion == [("jit(<lambda>)/dot_general:", "convolution fusion")]
    assert all(T.scope_of(tf) is None for tf, _ in ops.values())
    assert {cat for _, cat in ops.values()} >= {"copy-start", "copy-done"}
    assert len({pid for pid, _ in ops}) == 1


def test_scoped_fixture():
    """``tpu_v5e_scoped.xplane.pb`` (``record_scoped_trace.py``): one
    jitted function with an ``attention`` and an ``mlp`` scope, run three
    times inside ``serve/tick`` spans."""
    path = os.path.join(DATA, "tpu_v5e_scoped.xplane.pb")
    meta = T.op_metadata(path)[PLANE]
    scopes = {T.scope_of(tf) for tf, _ in meta.values()}
    assert {"attention", "mlp"} <= scopes
    s = T.reduce(path)
    assert s["ticks"] == 3 and s["steps"] == 0
    assert s["layers_s"]["attention"] > 0 and s["layers_s"]["mlp"] > 0
    prog = next(p for p in s["programs"].values() if p["tick"])
    assert prog["runs"] == 3
    parts = [T.tick_ms(s, p) for p in ("kv", "copy", "model")]
    assert parts[0] == 0.0
    assert sum(parts) == pytest.approx(1e3 * prog["ops_s"] / 3)
    assert prog["ops_s"] <= prog["module_s"] * 1.0001
    labels = s["idle_by_phase_s"]
    assert labels.get("after serve/tick", 0) > 0
    assert sum(labels.values()) == pytest.approx(s["idle_s"])
    assert s["sched_gap_s"] > 0


def test_program_scopes_and_collectives():
    """``scopes_s`` reads the program's own scope names, one that
    :data:`SCOPES` lacks among them, and ``collectives_s`` the exchanges
    between chips; both averaged over the device planes."""
    assert "experts" not in T.SCOPES
    assert T.scope_of("jit(f)/mlp/experts/dot_general",
                      T.SCOPES + ("experts",)) == "experts"
    assert T.scope_of("jit(f)/mlp/experts/dot_general") == "mlp"
    assert T.is_collective("%all-gather-start.3")
    assert T.is_collective("%all-to-all.1")
    assert T.is_collective("%fusion.7", "all-reduce fusion")
    assert not T.is_collective("%fusion.7", "loop fusion")
    assert not T.is_collective("%copy-start.2", "copy-start")
    modules, ops = {}, {}
    for k in range(2):          # two chips, the second twice as slow
        plane = f"/device:TPU:{k}"
        f = 1 + k
        modules[plane] = [("jit_train_step", 1, 0.0, 100.0 * f)]
        ops[plane] = [
            (1, "mlp", 0.0, 30.0 * f, "%fusion.1", "experts", False),
            (1, "mlp", 30.0 * f, 10.0 * f, "%fusion.2", "mlp", False),
            (1, "none", 40.0 * f, 20.0 * f, "%all-to-all.1 [all-to-all]",
             None, True),
            (1, "attention", 60.0 * f, 10.0 * f, "%all-gather-done.1",
             "attention", True),
            (1, "attention", 70.0 * f, 30.0 * f, "%fusion.3", "attention",
             False)]
    host = [("bench/window", 0.0, 200.0), ("gym/step", 0.0, 200.0)]
    s = T.summarize(ops, modules, host)
    assert s["scopes_s"] == pytest.approx(
        {"experts": 45e-9, "mlp": 15e-9, "attention": 60e-9})
    assert s["collectives_s"] == pytest.approx(45e-9)
    # the bench's own copy of the names folds experts into mlp
    assert s["layers_s"]["mlp"] == pytest.approx(60e-9)
    assert T.scope_ms_per_step(s, ("experts",)) == pytest.approx(45e-6)
    assert T.scope_ms_per_step(s, ("route",)) is None
    assert T.collective_ms_per_step(s) == pytest.approx(45e-6)


def test_scopes_equal_layers_without_new_names():
    """With the program's scopes the bench's own, ``scopes_s`` is
    ``layers_s`` without ``copy`` and ``none``; one chip has no exchange."""
    ops, modules = _two_programs()
    s = T.summarize(ops, modules, [("bench/window", 0.0, 600.0),
                                   ("gym/step", 0.0, 300.0)])
    assert s["scopes_s"] == {k: v for k, v in s["layers_s"].items()
                             if k in T.SCOPES}
    assert s["collectives_s"] == 0.0
    assert T.collective_ms_per_step(s) is None
    path = os.path.join(DATA, "tpu_v5e_scoped.xplane.pb")
    r = T.summarize(*T.read(path))
    assert r["scopes_s"] == {k: v for k, v in r["layers_s"].items()
                             if k in T.SCOPES}
    assert r["collectives_s"] == 0.0


def test_plane_shifts_match_runs_per_device():
    """Each device counts its own runs (as on a four-chip v5e, where chip 0
    was at run 53 while chips 1-3 were at 51): a plane is matched to its
    own device's completions, never to another's run of the same id."""
    step, lag = 220e6, 2e6
    ends, completions = {}, {}
    for dev, first in enumerate((53, 51, 51, 51)):
        plane = f"/device:TPU:{dev}"
        ends[plane] = [(first + k, 407e6 + k * step) for k in range(4)]
        for run, end in ends[plane]:
            completions[(dev, run)] = end + lag + dev * 1e3
    shifts = T.plane_shifts(ends, completions)
    for dev in range(4):
        shift, n = shifts[f"/device:TPU:{dev}"]
        assert n == 4 and shift == pytest.approx(lag + dev * 1e3)
    assert T.plane_shifts({"/device:TPU:1": [(99, 1.0)]}, completions) == {
        "/device:TPU:1": (0.0, 0)}
