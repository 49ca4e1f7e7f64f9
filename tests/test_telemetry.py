"""Unified telemetry: row schema, sinks, span determinism, MFU/goodput
accounting, profiler hook, the gym's and engine's phase spans on the
profiler clock, and the no-observer-effect contract (telemetry on vs. off
loss curves are bitwise identical)."""
import glob
import os

import jax
import pytest

import repro.core.components  # noqa: F401  (populates the registry)
import repro.run.kinds  # noqa: F401  (registers the run kinds)
from repro.config.registry import DEFAULT_REGISTRY
from repro.run import api as run_api
from repro.run.config import RunError, TelemetrySettings, TrainSettings
from repro.telemetry import (
    TelemetryRecorder,
    build_recorder,
    build_sink,
)
from repro.telemetry import accounting as ACC
from repro.telemetry.events import SchemaError, validate_row, validate_rows
from repro.telemetry.sinks import (
    CsvSink,
    JsonlSink,
    ListSink,
    MultiSink,
    read_csv,
    read_jsonl,
)
from repro.telemetry.spans import span


def _recorder(**kw):
    kw.setdefault("run", "t")
    kw.setdefault("kind", "train")
    kw.setdefault("fingerprint", "sha256:feed")
    return TelemetryRecorder(ListSink(), **kw)


# ---------------------------------------------------------------------------
# row schema
# ---------------------------------------------------------------------------
def test_validate_row_accepts_each_type():
    rec = _recorder()
    rec.metric(3, {"loss": 1.5, "ok": True})
    rec.event("run_start", steps=10)
    with rec.span("outer", step=1):
        with rec.span("inner"):
            pass
    assert validate_rows(rec.rows) == len(rec.rows) == 4


def test_validate_row_rejects_malformed():
    rec = _recorder()
    rec.metric(1, {"loss": 2.0})
    good = dict(rec.rows[0])

    for broken in (
        {**good, "v": 99},                      # wrong schema version
        {**good, "type": "gauge"},              # unknown row type
        {**good, "seq": "zero"},                # non-int seq
        {**good, "data": {"loss": [1, 2]}},     # non-scalar metric value
        {**good, "bogus": 1},                   # unknown envelope field
        {k: v for k, v in good.items() if k != "t_s"},   # missing required
    ):
        with pytest.raises(SchemaError):
            validate_row(broken)


def test_metric_coerces_values():
    rec = _recorder()
    import numpy as np

    rec.metric(1, {"b": True, "i": 7, "f": np.float32(2.5), "s": "x",
                   "n": None})
    data = rec.rows[0]["data"]
    assert data["b"] == 1 and isinstance(data["b"], int)
    assert data["i"] == 7 and data["s"] == "x" and data["n"] is None
    assert isinstance(data["f"], float) and data["f"] == 2.5
    validate_row(rec.rows[0])


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------
def _sample_rows():
    rec = _recorder()
    rec.event("run_start", steps=2)
    with rec.span("phase", step=1, label="a"):
        rec.metric(1, {"loss": 1.25, "note": "warm"})
    rec.metric(2, {"loss": 1.0})
    rec.event("run_end")
    return rec.rows


def test_jsonl_sink_round_trip(tmp_path):
    rows = _sample_rows()
    path = str(tmp_path / "telemetry.jsonl")
    sink = JsonlSink(path)
    for r in rows:
        sink.write(r)
    sink.close()
    assert read_jsonl(path, validate=True) == rows


def test_csv_sink_round_trip(tmp_path):
    rows = _sample_rows()
    path = str(tmp_path / "telemetry.csv")
    sink = CsvSink(path)
    for r in rows:
        sink.write(r)
    sink.close()
    back = read_csv(path, validate=True)
    assert len(back) == len(rows)
    for orig, rt in zip(rows, back):
        assert rt == orig, (orig, rt)


def test_multi_sink_fans_out(tmp_path):
    a, b = ListSink(), ListSink()
    multi = MultiSink([a, b])
    rec = TelemetryRecorder(multi, run="t", kind="train", fingerprint="f")
    rec.metric(1, {"x": 1.0})
    rec.close()
    assert a.rows == b.rows and len(a.rows) == 1


def test_sink_registry_components(tmp_path):
    mem = DEFAULT_REGISTRY.build("sink", "memory")
    assert isinstance(mem, ListSink)
    jl = DEFAULT_REGISTRY.build("sink", "jsonl",
                                path=str(tmp_path / "t.jsonl"))
    assert isinstance(jl, JsonlSink)
    jl.close()


def test_build_sink_variants(tmp_path):
    assert isinstance(build_sink("jsonl", output_dir=str(tmp_path)),
                      JsonlSink)
    # no destination -> in-memory fallback, never a crash
    assert isinstance(build_sink("jsonl"), ListSink)
    assert isinstance(build_sink("memory"), ListSink)
    m = build_sink("multi", sinks=["memory", {"sink": "memory"}])
    assert isinstance(m, MultiSink) and len(m.sinks) == 2
    with pytest.raises(ValueError):
        build_sink("carrier_pigeon")


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------
def _span_shape(rec):
    return [(r["name"], r["span_id"], r["parent_id"], r["depth"], r["seq"])
            for r in rec.rows if r["type"] == "span"]


def _emit_tree(rec):
    with rec.span("step", step=1):
        with rec.span("fwd"):
            pass
        with rec.span("bwd"):
            with rec.span("allreduce"):
                pass
    t = rec.now()
    rec.span_row("flush", t, t + 0.5, step=1)


def test_span_nesting_and_ordering_deterministic():
    a, b = _recorder(), _recorder()
    _emit_tree(a)
    _emit_tree(b)
    shape = _span_shape(a)
    assert shape == _span_shape(b)
    # ids are assigned at open, rows emitted at close: children precede
    # parents in the stream but carry the parent's (smaller) open-order id
    by_name = {s[0]: s for s in shape}
    assert by_name["step"][1] == 0 and by_name["step"][3] == 0
    assert by_name["fwd"][2] == 0 and by_name["fwd"][3] == 1
    assert by_name["allreduce"][2] == by_name["bwd"][1]
    assert by_name["allreduce"][3] == 2
    assert by_name["flush"][2] is None and by_name["flush"][3] == 0
    # close order: fwd, allreduce, bwd, step, flush
    assert [s[0] for s in shape] == ["fwd", "allreduce", "bwd", "step",
                                    "flush"]
    assert validate_rows(a.rows) == len(a.rows)


def test_span_row_explicit_parent_and_duration():
    rec = _recorder()
    t = rec.now()
    root = rec.span_row("serve/request", t, t + 1.0, rid=3)
    rec.span_row("serve/queued", t, t + 0.25, parent=root, rid=3)
    rows = [r for r in rec.rows if r["type"] == "span"]
    assert rows[1]["parent_id"] == root and rows[1]["depth"] == 1
    assert rows[0]["dur_s"] == pytest.approx(1.0)
    assert rows[1]["dur_s"] == pytest.approx(0.25)


# ---------------------------------------------------------------------------
# MFU / goodput accounting
# ---------------------------------------------------------------------------
def test_mfu_known_flops_arithmetic():
    # 1e12 FLOPs in 0.5s on 2 devices of 1e12 peak -> 1e12/(0.5*2e12) = 1.0
    assert ACC.mfu(1e12, 0.5, 2, peak_flops=1e12) == pytest.approx(1.0)
    assert ACC.mfu(1e12, 1.0, 1, peak_flops=4e12) == pytest.approx(0.25)
    assert ACC.mfu(1e12, 0.0, 1, peak_flops=1e12) == 0.0


class _Device:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_peak_table_lookup_by_device_kind():
    v5e = ACC.device_peaks(_Device("tpu", "TPU v5 lite"))
    assert v5e is ACC.V5E
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bytes_s"] == 819e9
    # the CPU takes no device metric: no entry, and no error
    assert ACC.device_peaks(_Device("cpu", "cpu")) is None
    # this suite runs on the CPU
    assert ACC.device_peaks(ACC.run_devices(None)[0]) is None
    # an accelerator missing from the table is an error, never a default
    with pytest.raises(ACC.UnknownDeviceError, match="TPU v9"):
        ACC.device_peaks(_Device("tpu", "TPU v9"))
    with pytest.raises(ACC.UnknownDeviceError):
        ACC.device_peaks(_Device("gpu", "NVIDIA H100"))
    info = ACC.device_info([_Device("tpu", "TPU v5 lite")] * 4)
    assert info == {"platform": "tpu", "device_kind": "TPU v5 lite",
                    "device_count": 4}


def test_goodput_clamped_ratio():
    assert ACC.goodput(10, 10) == 1.0
    assert ACC.goodput(8, 10) == pytest.approx(0.8)
    assert ACC.goodput(0, 0) == 1.0           # idle run is not a failure
    assert ACC.goodput(12, 10) == 1.0         # clamped


def test_flops_per_train_step_matches_toy_model():
    """6 * N_active * tokens, from a real (reduced) model's param count."""
    from repro.configs import get_reduced
    from repro.models import build_model

    cfg = get_reduced("qwen1p5_0p5b")
    model = build_model(cfg)

    class Loader:
        global_batch = 4

        class dataset:
            seq_len = 32

    flops = ACC.flops_per_train_step(model, Loader())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = ACC.count_param_leaves(params)
    assert flops == pytest.approx(6.0 * n * 4 * 32)
    # dryrun's historic entry point delegates to the same estimate
    from repro.configs.shapes import InputShape
    from repro.launch.dryrun import model_flops as dr_flops

    f2, n_total, n_active = dr_flops(cfg, InputShape("t", 32, 4, "train"))
    assert f2 == pytest.approx(flops) and n_total == n == n_active

    # geometry unknown -> None, never a guess
    assert ACC.flops_per_train_step(model, object()) is None


# ---------------------------------------------------------------------------
# run-document plumbing
# ---------------------------------------------------------------------------
def test_telemetry_settings_validation():
    s = TrainSettings(telemetry={"sink": "csv", "spans": False})
    assert s.telemetry.enabled and s.telemetry.sink == "csv"
    assert TrainSettings(telemetry=False).telemetry.enabled is False
    assert TrainSettings().telemetry.enabled is True   # default ON
    with pytest.raises(RunError):
        TrainSettings(telemetry={"sink": "bogus"})
    with pytest.raises(RunError):
        TrainSettings(telemetry={"sink": "multi"})   # multi needs sinks
    with pytest.raises(RunError):
        TrainSettings(telemetry={"profile": {"start_step": 0}})


def test_build_recorder_disabled_and_memory(tmp_path):
    assert build_recorder(TelemetrySettings(enabled=False),
                          output_dir=str(tmp_path), run="r", kind="train",
                          fingerprint="f") is None
    rec = build_recorder(None, output_dir="", run="r", kind="train",
                         fingerprint="f", write=False)
    rec.metric(1, {"x": 1.0})
    assert rec.summary()["metric_rows"] == 1 and "file" not in rec.summary()
    rec.close()


# ---------------------------------------------------------------------------
# end-to-end: train runs
# ---------------------------------------------------------------------------
def _train_doc(tmp_path, name, steps=4, *, train=None, gym=None):
    prefix = str(tmp_path / "data")
    return {
        "run": {"kind": "train", "name": name,
                "output_dir": str(tmp_path / name),
                "train": {"steps": steps, **(train or {})}},
        "arch": {"component_key": "arch_config",
                 "variant_key": "stablelm_1p6b",
                 "config": {"reduced": True, "n_layers": 1}},
        "model": {"component_key": "model", "variant_key": "auto",
                  "config": {"arch_config": {"instance_key": "arch"}}},
        "optimizer": {"component_key": "optimizer", "variant_key": "adamw",
                      "config": {"lr": 0.001}},
        "dataset": {"component_key": "dataset", "variant_key": "synthetic",
                    "config": {"n_tokens": 40000, "vocab": 512,
                               "prefix": prefix, "seq_len": 32, "seed": 0}},
        "loader": {"component_key": "loader", "variant_key": "sharded",
                   "config": {"dataset": {"instance_key": "dataset"},
                              "global_batch": 4}},
        "gym": {"component_key": "gym", "variant_key": "standard",
                "config": {"model": {"instance_key": "model"},
                           "optimizer": {"instance_key": "optimizer"},
                           "loader": {"instance_key": "loader"},
                           "log_every": 1, "prefetch": 0, **(gym or {})}},
    }


def test_train_run_emits_schema_valid_telemetry(tmp_path):
    result = run_api.execute_doc(_train_doc(tmp_path, "tele", steps=4))
    tel = result["telemetry"]
    rows = read_jsonl(tel["file"], validate=True)
    assert len(rows) == tel["rows"]
    types = {r["type"] for r in rows}
    assert types == {"metric", "span", "event"}
    # every row stamped with the run identity and monotonic seq
    assert [r["seq"] for r in rows] == list(range(len(rows)))
    assert all(r["run"] == "tele" and r["kind"] == "train" for r in rows)
    names = {r["name"] for r in rows if r["type"] == "span"}
    assert {"gym/data_wait", "gym/step", "gym/flush"} <= names
    events = [r["name"] for r in rows if r["type"] == "event"]
    assert events[0] == "run_start" and events[-1] == "run_end"
    # per-step metric rows carry the loss the history carries
    losses = {r["step"]: r["data"]["loss"] for r in rows
              if r["type"] == "metric" and "loss" in r["data"]}
    hist = {m["step"]: m["loss"] for m in result["history"] if "loss" in m}
    assert losses == hist
    # MFU/goodput land in the result
    assert result["goodput"] == 1.0
    assert result["steps_dispatched"] == 4
    # a CPU run measures no device metric: mfu is not written at all
    assert "mfu" not in result and result["model_flops_per_step"] > 0
    assert result["platform"] == "cpu" and result["device_count"] == 1


def test_telemetry_off_no_file_and_bitwise_identical_curves(tmp_path):
    on = run_api.execute_doc(_train_doc(tmp_path, "on", steps=4))
    off = run_api.execute_doc(
        _train_doc(tmp_path, "off", steps=4, train={"telemetry": False}))
    assert "telemetry" not in off
    assert not os.path.exists(str(tmp_path / "off" / "telemetry.jsonl"))
    on_hist = [(m["step"], m["loss"]) for m in on["history"] if "loss" in m]
    off_hist = [(m["step"], m["loss"]) for m in off["history"]
                if "loss" in m]
    assert on_hist == off_hist   # bitwise: floats compared exactly


def test_eval_metrics_reach_history_and_result(tmp_path):
    doc = _train_doc(tmp_path, "ev", steps=4, gym={"eval_every": 2})
    doc["evaluator"] = {
        "component_key": "evaluator", "variant_key": "perplexity",
        "config": {"dataset": {"instance_key": "dataset"}, "n_samples": 4},
    }
    result = run_api.execute_doc(doc)
    eval_rows = [m for m in result["history"]
                 if any(k.startswith("eval_") for k in m)]
    assert [m["step"] for m in eval_rows] == [2, 4]
    assert all("eval_loss" in m for m in eval_rows)
    assert result["eval_points"] == 2
    assert result["final_eval"]["eval_loss"] == eval_rows[-1]["eval_loss"]
    # eval rows flow through the sink too
    rows = read_jsonl(result["telemetry"]["file"], validate=True)
    tele_evals = [r for r in rows if r["type"] == "metric"
                  and "eval_loss" in r["data"]]
    assert [r["step"] for r in tele_evals] == [2, 4]


def test_wall_s_full_precision(tmp_path):
    result = run_api.execute_doc(_train_doc(tmp_path, "wall", steps=4))
    walls = [m["wall_s"] for m in result["history"] if "wall_s" in m]
    assert walls == sorted(walls) and len(walls) == 4
    # monotonic timestamps, not the old round(x, 2) grid
    assert any(w != round(w, 2) for w in walls)


def test_goodput_below_one_under_injected_rollback(tmp_path):
    result = run_api.execute_doc(_train_doc(
        tmp_path, "chaos", steps=8,
        train={"resilience": {"sentinel": True,
                              "faults": [{"kind": "nan_loss", "at": 5}]}},
        gym={"ckpt_every": 2}))
    assert result["rollback_count"] == 1
    assert result["steps_dispatched"] > 8
    assert result["goodput"] == pytest.approx(
        8 / result["steps_dispatched"])
    assert result["goodput"] < 1.0
    rows = read_jsonl(result["telemetry"]["file"], validate=True)
    names = [r["name"] for r in rows if r["type"] == "event"]
    assert "rollback" in names and "resilience/fault" in names


def test_profiler_hook_records_trace(tmp_path):
    result = run_api.execute_doc(_train_doc(
        tmp_path, "prof", steps=4,
        train={"telemetry": {"profile": {"start_step": 2,
                                         "num_steps": 1}}}))
    rows = read_jsonl(result["telemetry"]["file"], validate=True)
    names = [r["name"] for r in rows if r["type"] == "event"]
    assert "profile_start" in names and "profile_stop" in names
    assert os.path.isdir(result["profile_trace"])


def test_profiler_window_that_cannot_start_fails_the_run(tmp_path,
                                                         monkeypatch):
    def refuse(*a, **k):
        raise RuntimeError("no profiler on this backend")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError, match="no profiler"):
        run_api.execute_doc(_train_doc(
            tmp_path, "prof_fail", steps=3,
            train={"telemetry": {"profile": {"start_step": 2,
                                             "num_steps": 1}}}))


def _host_events(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert files, trace_dir
    pd = jax.profiler.ProfileData.from_file(files[0])
    return [e.name for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]


def test_gym_spans_in_profiler_trace_and_rows_unchanged(tmp_path):
    """The operator's path: a ``telemetry.profile`` window holds the gym's
    phases as host events, and the telemetry rows keep their tree."""
    result = run_api.execute_doc(_train_doc(
        tmp_path, "prof", steps=4,
        train={"telemetry": {"profile": {"start_step": 2, "num_steps": 2}}}))
    names = _host_events(result["profile_trace"])
    assert names.count("gym/step") == 2          # steps 2 and 3
    assert "gym/data_wait" in names and "gym/flush" in names
    rows = read_jsonl(result["telemetry"]["file"], validate=True)
    spans = [r for r in rows if r["type"] == "span"]
    # per step: data_wait then step, ids in open order, no parents; the
    # flush of the previous step's metrics follows from step 2 on
    shape = [(r["name"], r.get("step")) for r in spans]
    assert shape[:5] == [("gym/data_wait", 1), ("gym/step", 1),
                         ("gym/data_wait", 2), ("gym/step", 2),
                         ("gym/flush", 1)]
    assert [r["span_id"] for r in spans] == sorted(r["span_id"]
                                                   for r in spans)
    assert all(r["parent_id"] is None and r["depth"] == 0 for r in spans)
    assert sum(r["name"] == "gym/step" for r in spans) == 4


def test_csv_sink_through_run(tmp_path):
    result = run_api.execute_doc(_train_doc(
        tmp_path, "csvr", steps=2, train={"telemetry": {"sink": "csv"}}))
    path = result["telemetry"]["file"]
    assert path.endswith("telemetry.csv")
    rows = read_csv(path, validate=True)
    assert {r["type"] for r in rows} == {"metric", "span", "event"}


# ---------------------------------------------------------------------------
# end-to-end: serve engine spans
# ---------------------------------------------------------------------------
def test_serve_request_lifecycle_spans():
    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.serve import Request, ServeEngine

    cfg = get_reduced("qwen1p5_0p5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rec = _recorder(kind="serve")
    eng = ServeEngine(model, params, n_slots=2, max_len=32, block_len=0,
                      greedy=True, telemetry=rec)
    reqs = [Request(rid=i, prompt=tuple(range(1, 9)), max_new=4,
                    arrival_s=0.0, temperature=0.0, seed=i)
            for i in range(3)]
    result = eng.run(reqs, realtime=False, warmup=True)
    assert validate_rows(rec.rows) == len(rec.rows)
    spans = [r for r in rec.rows if r["type"] == "span"]
    roots = [s for s in spans if s["name"] == "serve/request"]
    assert len(roots) == 3
    for root in roots:
        kids = [s for s in spans if s["parent_id"] == root["span_id"]]
        assert sorted(k["name"] for k in kids) == [
            "serve/decode", "serve/prefill", "serve/queued"]
        assert all(k["depth"] == 1 for k in kids)
        phases = {k["name"]: k for k in kids}
        # lifecycle tiles the request span: queued -> prefill -> decode
        assert phases["serve/queued"]["t1_s"] == pytest.approx(
            phases["serve/prefill"]["t0_s"])
        assert root["dur_s"] >= phases["serve/prefill"]["dur_s"]
    # TTFT decomposes: queue_s + prefill_s == ttft_s (dense admission)
    for row in result["requests"]:
        assert row["queue_s"] + row["prefill_s"] == pytest.approx(
            row["ttft_s"], abs=2e-5)
    assert result["queue_s"] is not None and "p50" in result["queue_s"]
    # occupancy timeline: one sample per decode tick
    tl = result["timeline"]
    assert len(tl) == result["ticks"]
    assert all(set(t) >= {"t_s", "queue", "busy"} for t in tl)
    headline = [r for r in rec.rows if r["type"] == "metric"]
    assert headline and "tok_s" in headline[-1]["data"]


def test_engine_phase_spans_in_profiler_trace(tmp_path):
    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.serve import Request, ServeEngine

    model = build_model(get_reduced("qwen1p5_0p5b"))
    params = model.init(jax.random.PRNGKey(0))
    rec = _recorder(kind="serve")
    engine = ServeEngine(model, params, n_slots=2, max_len=32, block_len=4,
                         prefill_chunk=8, greedy=True, telemetry=rec)
    reqs = [Request(rid=i, prompt=tuple(range(1, 12)), max_new=4, seed=i)
            for i in range(3)]
    engine._warmup([r.prompt_len for r in reqs])
    with jax.profiler.trace(str(tmp_path)):
        res = engine.run(reqs, realtime=False, warmup=False)
    names = _host_events(str(tmp_path))
    assert names.count("serve/tick") == res["ticks"]
    for phase in ("serve/schedule", "serve/prefill_chunk",
                  "serve/first_token", "serve/slot_write",
                  "serve/page_table"):
        assert phase in names, phase
    # the phases are trace-only: the rows are the per-request lifecycle
    assert validate_rows(rec.rows) == len(rec.rows)
    assert {r["name"] for r in rec.rows if r["type"] == "span"} == {
        "serve/request", "serve/queued", "serve/prefill", "serve/decode"}
    assert len(res["tick_ms"]) == 3 and res["tick_ms"]["p50"] > 0


def test_span_rows_only_with_spans_on(tmp_path):
    on = _recorder()
    off = _recorder(spans=False)
    with jax.profiler.trace(str(tmp_path)):
        with span("gym/step", on, step=3) as sid:
            assert sid == 0
        with off.span("gym/step"):
            pass
        with span("serve/tick"):
            pass
    assert [(r["name"], r["step"]) for r in on.rows] == [("gym/step", 3)]
    assert off.rows == []
    assert _host_events(str(tmp_path)).count("gym/step") == 2


# ---------------------------------------------------------------------------
# sweep trials feed the sweep-level sink
# ---------------------------------------------------------------------------
def test_sweep_records_flow_to_telemetry(tmp_path, monkeypatch):
    from repro.sweep import runner as runner_mod
    from repro.sweep.runner import SweepRunner
    from repro.sweep.spec import SweepSpec

    spec = SweepSpec.from_dict({
        "name": "tsweep",
        "base": {"opt": {"lr": 0.1}, "arch": "a", "shape": "b"},
        "axes": [{"type": "grid",
                  "parameters": {"opt.lr": [0.1, 0.2, 0.3]}}],
        "output_dir": str(tmp_path / "sweep"),
    })

    def factory(s):
        def run(raw, trial=None):
            lr = raw["opt"]["lr"]
            if lr == 0.3:
                raise RuntimeError("boom")
            return {"final_loss": lr * 2, "wall_s": 0.0,
                    "collectives": {"all_gather": 3}}   # dict: must filter

        return run

    monkeypatch.setitem(runner_mod.BACKENDS, "gym", factory)
    rec = _recorder(kind="sweep")
    records = SweepRunner(spec, telemetry=rec).run()
    assert [r["status"] for r in records] == ["ok", "ok", "failed"]
    assert validate_rows(rec.rows) == len(rec.rows)
    metric_rows = [r for r in rec.rows if r["type"] == "metric"]
    assert len(metric_rows) == 2
    for r in metric_rows:
        assert r["attrs"]["status"] == "ok"
        assert "trial_wall_s" in r["data"] and "final_loss" in r["data"]
        assert "collectives" not in r["data"]   # non-scalar values dropped
    events = [r for r in rec.rows if r["type"] == "event"]
    assert [e["name"] for e in events] == ["trial_failed"]
    assert events[0]["attrs"]["error"] == "RuntimeError: boom"
