"""The declarative Run API: typed run documents, --set overrides, resolved
config + fingerprint artifacts, replay, the unified CLI, and the deprecation
shims."""
import json
import os

import pytest
import yaml

import repro.core.components  # noqa: F401  (populates the registry)
import repro.run.kinds  # noqa: F401  (registers the run kinds)
from repro.config.registry import DEFAULT_REGISTRY
from repro.config.resolver import load_yaml
from repro.run import api as run_api
from repro.run.config import RunError, parse_run_doc
from repro.run.fingerprint import fingerprint, materialize
from repro.run.legacy import legacy_dryrun_doc, legacy_train_doc
from repro.run.overrides import apply_overrides, parse_overrides

ROOT = os.path.join(os.path.dirname(__file__), "..")
QUICKSTART = os.path.join(ROOT, "examples", "configs", "quickstart.yaml")


def _tiny_train_doc(tmp_path, steps=2, log_every=1):
    """A minimal, fast train run document (synthetic data, bigram-scale)."""
    return {
        "run": {"kind": "train", "name": "tiny",
                "output_dir": str(tmp_path / "run"),
                "train": {"steps": steps}},
        "variables": {"seq_len": 32},
        "arch": {"component_key": "arch_config", "variant_key": "qwen1p5_0p5b",
                 "config": {"reduced": True}},
        "model": {"component_key": "model", "variant_key": "auto",
                  "config": {"arch_config": {"instance_key": "arch"}}},
        "optimizer": {"component_key": "optimizer", "variant_key": "adamw",
                      "config": {"lr": 0.001}},
        "dataset": {"component_key": "dataset", "variant_key": "synthetic",
                    "config": {"n_tokens": 30000, "vocab": 512,
                               "prefix": "/tmp/repro_runapi_test",
                               "seq_len": "${seq_len}"}},
        "loader": {"component_key": "loader", "variant_key": "sharded",
                   "config": {"dataset": {"instance_key": "dataset"},
                              "global_batch": 4}},
        "gym": {"component_key": "gym", "variant_key": "standard",
                "config": {"model": {"instance_key": "model"},
                           "optimizer": {"instance_key": "optimizer"},
                           "loader": {"instance_key": "loader"},
                           "log_every": log_every}},
    }


# ---------------------------------------------------------------------------
# parsing / normalization
# ---------------------------------------------------------------------------
def test_parse_typed_settings_and_defaults():
    cfg = parse_run_doc({"run": {"kind": "train", "train": {"steps": 7}},
                         "gym": {}})
    assert cfg.kind == "train"
    assert cfg.settings.steps == 7
    assert cfg.settings.gym_key == "gym"          # default filled
    assert cfg.output_dir == os.path.join("results", "runs", "run")
    assert "run" in cfg.doc and "gym" in cfg.graph


def test_parse_rejects_unknown_kind_and_settings():
    with pytest.raises(RunError, match="unknown run kind"):
        parse_run_doc({"run": {"kind": "teleport"}})
    with pytest.raises(RunError, match="unknown settings"):
        parse_run_doc({"run": {"kind": "train", "train": {"stepz": 1}}})
    with pytest.raises(RunError, match="other kinds"):
        parse_run_doc({"run": {"kind": "train", "train": {},
                               "serve": {"gen": 4}}})


def test_parse_kind_mismatch_flagged():
    with pytest.raises(RunError, match="launched as"):
        parse_run_doc({"run": {"kind": "train"}}, kind="serve")


def test_legacy_graph_infers_train():
    raw = load_yaml(QUICKSTART)
    raw.pop("run", None)
    cfg = parse_run_doc(raw, default_name="qs")
    assert cfg.kind == "train" and cfg.name == "qs"


def test_legacy_sweep_doc_infers_sweep():
    cfg = parse_run_doc({"sweep": {"name": "s", "backend": "dryrun",
                                   "base": {"arch": "a", "shape": "b"}}})
    assert cfg.kind == "sweep"
    assert cfg.settings["sweep"]["backend"] == "dryrun"


def test_sweep_output_dir_follows_spec():
    cfg = parse_run_doc({"sweep": {"name": "abl", "backend": "dryrun",
                                   "base": {"arch": "a", "shape": "b"},
                                   "output_dir": "results/sweeps/abl"}})
    assert cfg.output_dir == "results/sweeps/abl"


# ---------------------------------------------------------------------------
# --set overrides
# ---------------------------------------------------------------------------
def test_parse_overrides_yaml_typed():
    ov = dict(parse_overrides(["a.b=3", "c=0.5", "d=true", "e=null",
                               "f=[1, 2]", "g=text", "h="]))
    assert ov == {"a.b": 3, "c": 0.5, "d": True, "e": None, "f": [1, 2],
                  "g": "text", "h": ""}


def test_parse_overrides_rejects_missing_equals():
    with pytest.raises(RunError, match="path=value"):
        parse_overrides(["just-a-path"])


def test_apply_overrides_creates_leaf_but_not_intermediates():
    doc = {"run": {"train": {"steps": 1}}}
    out = apply_overrides(doc, [("run.train.steps", 9),
                                ("run.train.resume", True)])
    assert out["run"]["train"] == {"steps": 9, "resume": True}
    assert doc["run"]["train"]["steps"] == 1     # original untouched
    with pytest.raises(RunError, match="not found"):
        apply_overrides(doc, [("run.nope.deep", 1)])


def test_apply_overrides_list_index():
    doc = {"axes": [{"type": "grid"}, {"type": "zip"}]}
    out = apply_overrides(doc, [("axes.1.type", "list")])
    assert out["axes"][1]["type"] == "list"
    with pytest.raises(RunError, match="out of range"):
        apply_overrides(doc, [("axes.5.type", "x")])


# ---------------------------------------------------------------------------
# materialize + fingerprint
# ---------------------------------------------------------------------------
def test_materialize_fills_defaults_and_interpolates(tmp_path):
    doc = parse_run_doc(_tiny_train_doc(tmp_path)).doc
    resolved = materialize(doc)
    assert "variables" not in resolved
    assert resolved["dataset"]["config"]["seq_len"] == 32      # ${seq_len}
    opt = resolved["optimizer"]["config"]
    assert opt["lr"] == 0.001 and opt["weight_decay"] == 0.1   # default filled
    ref = resolved["model"]["config"]["arch_config"]
    assert ref == {"instance_key": "arch", "pass_type": "BY_REFERENCE"}


def test_materialize_is_a_fixpoint(tmp_path):
    doc = parse_run_doc(_tiny_train_doc(tmp_path)).doc
    once = materialize(doc)
    twice = materialize(once)
    assert once == twice
    assert fingerprint(once) == fingerprint(twice)


def test_fingerprint_tracks_content_not_key_order(tmp_path):
    doc = parse_run_doc(_tiny_train_doc(tmp_path)).doc
    reordered = dict(reversed(list(doc.items())))
    assert fingerprint(materialize(doc)) == fingerprint(materialize(reordered))
    changed = apply_overrides(doc, [("optimizer.config.lr", 0.01)])
    assert fingerprint(materialize(doc)) != fingerprint(materialize(changed))


# ---------------------------------------------------------------------------
# execution + artifacts + replay
# ---------------------------------------------------------------------------
def test_train_run_writes_artifacts_and_replays(tmp_path):
    doc = _tiny_train_doc(tmp_path)
    result = run_api.execute_doc(doc)
    assert result["final_loss"] > 0 and result["logged_points"] == 2
    run_dir = tmp_path / "run"
    assert (run_dir / "resolved.yaml").exists()
    manifest = json.loads((run_dir / "manifest.json").read_text())
    assert manifest["fingerprint"] == result["fingerprint"]
    on_disk = json.loads((run_dir / "result.json").read_text())
    assert on_disk["final_loss"] == pytest.approx(result["final_loss"])

    replayed = run_api.replay(str(run_dir))
    assert replayed["fingerprint"] == result["fingerprint"]
    assert replayed["final_loss"] == pytest.approx(result["final_loss"])


def test_replay_rejects_edited_artifact(tmp_path):
    run_api.execute_doc(_tiny_train_doc(tmp_path))
    run_dir = tmp_path / "run"
    doc = yaml.safe_load((run_dir / "resolved.yaml").read_text())
    doc["optimizer"]["config"]["lr"] = 0.9
    (run_dir / "resolved.yaml").write_text(yaml.safe_dump(doc))
    with pytest.raises(RunError, match="fingerprint mismatch"):
        run_api.replay(str(run_dir))


def test_train_empty_history_is_not_an_error(tmp_path):
    """Satellite: steps < log_every used to IndexError on the summary."""
    doc = _tiny_train_doc(tmp_path, steps=1, log_every=0)
    result = run_api.execute_doc(doc)
    assert result["logged_points"] == 0
    assert "final_loss" not in result


def test_dryrun_run_through_components(tmp_path):
    """A dryrun document with local mesh + custom shape compiles in-process
    (single CPU device) through the resolved components."""
    doc = {
        "run": {"kind": "dryrun", "name": "d",
                "output_dir": str(tmp_path / "d")},
        "arch": {"component_key": "arch_config", "variant_key": "qwen1p5_0p5b",
                 "config": {"reduced": True}},
        "shape": {"component_key": "shape", "variant_key": "custom",
                  "config": {"seq_len": 64, "global_batch": 2,
                             "kind": "train"}},
        "mesh": {"component_key": "mesh_provider", "variant_key": "local",
                 "config": {"dp": 1, "tp": 1}},
        "plan": {"component_key": "sharding_plan", "variant_key": "ddp"},
    }
    result = run_api.execute_doc(doc)
    assert result["chips"] == 1
    assert result["hlo_flops_per_dev"] > 0
    assert result["dominant_term"] in ("compute", "memory", "collective")
    assert (tmp_path / "d" / "resolved.yaml").exists()


def test_dryrun_skipped_combo_never_builds_the_mesh(tmp_path):
    """whisper-tiny x long_500k is a declared skip: the executor must return
    the skip record without constructing the production mesh (this process
    has one CPU device, so an eager build would RuntimeError)."""
    doc = {
        "run": {"kind": "dryrun", "name": "skip",
                "output_dir": str(tmp_path / "skip")},
        "arch": {"component_key": "arch_config",
                 "variant_key": "whisper_tiny"},
        "shape": {"component_key": "shape", "variant_key": "long_500k"},
        "mesh": {"component_key": "mesh_provider",
                 "variant_key": "production"},
    }
    result = run_api.execute_doc(doc)
    assert "skipped" in result


class _StubGym:
    ckpt_dir = ""
    loader = None

    def setup(self):
        return {"step": 0}

    def run(self, steps, state=None):
        return {"state": state, "history": [{"loss": 1.0}]}


def test_execute_with_custom_registry_falls_back_for_run_kinds(tmp_path):
    """A caller-supplied registry without run_kind entries still dispatches
    (built-in kinds are the fallback)."""
    from repro.config.registry import Registry

    reg = Registry()
    reg.register("gym", "stub", _StubGym)
    doc = {"run": {"kind": "train", "name": "custom",
                   "output_dir": str(tmp_path / "c"),
                   "train": {"steps": 3}},
           "gym": {"component_key": "gym", "variant_key": "stub"}}
    result = run_api.execute_doc(doc, registry=reg)
    assert result["steps"] == 3 and result["logged_points"] == 1


def test_run_kinds_are_registry_components():
    """New run kinds are a registry entry + settings schema, not a script."""
    assert set(DEFAULT_REGISTRY.variants("run_kind")) >= {
        "train", "bench", "dryrun", "serve", "trace", "sweep"}
    kind = DEFAULT_REGISTRY.build("run_kind", "train")
    assert callable(kind.execute)

    from repro.run.config import SETTINGS_SCHEMAS
    from repro.run.kinds import register_run_kind

    try:
        register_run_kind("export", None, lambda ctx: {"exported": True})
        assert "export" in DEFAULT_REGISTRY.variants("run_kind")
        cfg = parse_run_doc({"run": {"kind": "export"}})
        assert cfg.kind == "export"
    finally:  # the default registry is process-global: undo the demo kind
        DEFAULT_REGISTRY._entries.pop(("run_kind", "export"), None)
        SETTINGS_SCHEMAS.pop("export", None)


def test_sweep_trials_write_replayable_artifacts(tmp_path):
    base = _tiny_train_doc(tmp_path)
    base.pop("run")
    spec_doc = {
        "sweep": {
            "name": "mini", "backend": "gym", "steps": 1,
            "base": base, "output_dir": str(tmp_path / "sw"),
            "axes": [{"type": "grid",
                      "parameters": {"optimizer.config.lr": [0.001, 0.002]}}],
        }
    }
    result = run_api.execute_doc(spec_doc, default_name="mini")
    assert result["n_failed"] == 0 and result["n_records"] == 2
    trial_dir = tmp_path / "sw" / "trials" / "lr=0.001"
    assert (trial_dir / "resolved.yaml").exists()
    assert (trial_dir / "manifest.json").exists()
    records = [json.loads(line) for line in
               (tmp_path / "sw" / "records.jsonl").read_text().splitlines()]
    assert all(r["run_dir"].startswith("trials/") for r in records)
    replayed = run_api.replay(str(trial_dir))
    assert replayed["kind"] == "train"


# ---------------------------------------------------------------------------
# legacy converters
# ---------------------------------------------------------------------------
def test_legacy_dryrun_doc_maps_every_flag():
    doc = legacy_dryrun_doc({"arch": "stablelm-1.6b", "shape": "train_4k",
                             "plan_name": "fsdp_tp", "scan_block": 2,
                             "mla_absorb": True, "bf16_params": True,
                             "grad_accum": 4})
    assert doc["arch"]["variant_key"] == "stablelm_1p6b"
    assert doc["arch"]["config"] == {"scan_block_size": 2, "mla_absorb": True}
    assert doc["shape"]["variant_key"] == "train_4k"
    assert doc["plan"]["variant_key"] == "fsdp_tp"
    assert doc["precision"]["config"]["bf16_params"] is True
    assert doc["run"]["dryrun"]["grad_accum"] == 4
    parse_run_doc(doc)  # parses as a valid dryrun document


def test_legacy_dryrun_doc_mesh_split_and_errors():
    doc = legacy_dryrun_doc({"arch": "a", "shape": "s", "mesh_split": "32x8"})
    assert doc["mesh"] == {"component_key": "mesh_provider",
                           "variant_key": "split",
                           "config": {"dp": 32, "tp": 8}}
    with pytest.raises(RunError, match="unknown dryrun keys"):
        legacy_dryrun_doc({"arch": "a", "shape": "s", "warp": 9})
    with pytest.raises(RunError, match="needs 'shape'"):
        legacy_dryrun_doc({"arch": "a"})


def test_legacy_train_doc_reheads_existing_run_section():
    raw = {"run": {"kind": "dryrun", "dryrun": {"grad_accum": 2}},
           "gym": {}}
    doc = legacy_train_doc(raw, steps=5, resume=True, name="t")
    assert doc["run"]["kind"] == "train"
    assert doc["run"]["train"] == {"steps": 5, "resume": True}
    assert "dryrun" not in doc["run"]


def test_legacy_train_doc_without_flags_keeps_document_settings():
    """The shim must not clobber run.train of a new-style document when no
    explicit flag was passed (steps=None keeps the YAML's value)."""
    raw = {"run": {"kind": "train", "train": {"steps": 60, "resume": True}},
           "gym": {}}
    doc = legacy_train_doc(raw)
    assert doc["run"]["train"] == {"steps": 60, "resume": True}
    doc = legacy_train_doc(raw, steps=7)
    assert doc["run"]["train"] == {"steps": 7, "resume": True}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def test_cli_validate_examples(capsys):
    from repro.run.cli import main

    rc = main(["validate", os.path.join(ROOT, "examples", "configs")])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "FAIL" not in out
    assert "quickstart.yaml" in out and "ablation_dryrun.yaml" in out


def test_cli_validate_catches_bad_component(tmp_path, capsys):
    from repro.run.cli import main

    bad = tmp_path / "bad.yaml"
    bad.write_text(
        "run: {kind: train}\n"
        "gym: {component_key: gym, variant_key: warp_drive}\n")
    rc = main(["validate", str(bad)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "unknown variant" in out


def test_cli_train_and_replay(tmp_path, capsys):
    from repro.run.cli import main

    cfg_path = tmp_path / "run.yaml"
    cfg_path.write_text(yaml.safe_dump(_tiny_train_doc(tmp_path)))
    rc = main(["train", "--config", str(cfg_path),
               "--set", "run.train.steps=1"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "run artifact:" in out
    rc = main(["replay", str(tmp_path / "run")])
    assert rc == 0
    assert "replayed train run" in capsys.readouterr().out


def test_cli_rejects_kind_mismatch(tmp_path, capsys):
    from repro.run.cli import main

    cfg_path = tmp_path / "run.yaml"
    cfg_path.write_text("run: {kind: train}\ngym: {}\n")
    rc = main(["serve", "--config", str(cfg_path)])
    assert rc == 2
    assert "launched as" in capsys.readouterr().err


def test_compile_cache_dir_from_env_or_checkout(monkeypatch):
    import jax

    from repro.run import cli

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    key = ("jax_compilation_cache_include_metadata_in_key", True)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax")
    assert cli.use_compile_cache() == "/elsewhere/jax"
    assert calls == [key]        # JAX reads its own variable; no code path
    calls.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = cli.use_compile_cache()
    assert path == os.path.join(os.path.abspath(ROOT), ".jax_cache")
    assert calls == [key, ("jax_compilation_cache_dir", path)]


# ---------------------------------------------------------------------------
# normalized mesh providers (satellite)
# ---------------------------------------------------------------------------
def test_mesh_provider_components_are_providers():
    import repro.core.interfaces as IF

    for variant in ("single_device", "local", "production", "split"):
        kwargs = {"dp": 1, "tp": 1} if variant in ("local", "split") else {}
        provider = DEFAULT_REGISTRY.build("mesh_provider", variant, **kwargs)
        assert isinstance(provider, IF.MeshProviderIF), variant
        assert hasattr(provider, "build")
    assert DEFAULT_REGISTRY.build("mesh_provider", "single_device").build() is None


def test_gym_accepts_provider_without_callable_sniff():
    graph = {
        "mesh": {"component_key": "mesh_provider",
                 "variant_key": "single_device"},
    }
    from repro.config.resolver import resolve_config

    built = resolve_config(graph)
    from repro.core.components import _build_mesh

    assert _build_mesh(built["mesh"]) is None      # provider -> build()
    assert _build_mesh(None) is None               # passthrough
    sentinel = object()
    assert _build_mesh(sentinel) is sentinel       # raw mesh passthrough


def test_local_mesh_provider_builds_and_caches():
    provider = DEFAULT_REGISTRY.build("mesh_provider", "local", dp=1, tp=1)
    mesh = provider.build()
    assert mesh is provider.build()                # cached
    assert mesh.devices.size == 1


# ---------------------------------------------------------------------------
# bpe tokenizer factory (satellite)
# ---------------------------------------------------------------------------
def test_bpe_factory_trains_with_n_merges(tmp_path):
    corpus = tmp_path / "corpus.txt"
    corpus.write_text("aaabbb aaabbb aaabbb\n" * 50)
    tok = DEFAULT_REGISTRY.build("tokenizer", "bpe", corpus=str(corpus),
                                 n_merges=4)
    assert 0 < len(tok.merges) <= 4
    tok8 = DEFAULT_REGISTRY.build("tokenizer", "bpe", corpus=str(corpus),
                                  n_merges=8)
    assert len(tok8.merges) >= len(tok.merges)


def test_bpe_factory_flags_n_merges_without_corpus(tmp_path):
    with pytest.raises(ValueError, match="n_merges"):
        DEFAULT_REGISTRY.build("tokenizer", "bpe", n_merges=16)
    saved = tmp_path / "tok.json"
    DEFAULT_REGISTRY.build("tokenizer", "bpe").save(str(saved))
    with pytest.raises(ValueError, match="n_merges"):
        DEFAULT_REGISTRY.build("tokenizer", "bpe", path=str(saved),
                               n_merges=16)
    assert DEFAULT_REGISTRY.build("tokenizer", "bpe",
                                  path=str(saved)).merges == []
