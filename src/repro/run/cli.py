"""One CLI over the declarative run API.

  python -m repro train  --config run.yaml [--set path=value ...]
  python -m repro warmstart --config run.yaml [--source ckpt_dir] [--set ...]
  python -m repro sft    --config run.yaml [--set ...]
  python -m repro dpo    --config run.yaml [--set ...]
  python -m repro bench  --config run.yaml [--set ...]
  python -m repro dryrun --config run.yaml [--set ...] [--json out.json]
  python -m repro serve  --config run.yaml [--set ...]
  python -m repro trace  --config run.yaml [--set ...]
  python -m repro sweep  --config sweep.yaml [--list|--report-only|--redo|
                                              --max-trials N|--output-dir D]
  python -m repro replay <run_dir>
  python -m repro validate <yaml-or-dir> [...]

Legacy documents work unchanged: a bare component graph runs as ``train``, a
``sweep:`` document as ``sweep``.  ``--set`` patches the raw document before
parsing (dotted paths, YAML-typed values).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

#: kinds that compile on placeholder devices — the flag must be set before
#: JAX initialises its platform (harmless for in-process gym runs).
_FORCE_DEVICES_KINDS = ("dryrun", "trace", "sweep")
_XLA_FLAGS = "--xla_force_host_platform_device_count=512"
_CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is left
    to JAX. Otherwise the cache is ``.jax_cache/`` at the checkout root: a
    fixed path, so that the next process finds what this one compiled.
    The key includes the programs' metadata: without it a program compiled
    from other sources (other layer scopes, or none) with the same
    operations is a hit, and its traces show that build's names.
    Call it before the first compile.
    """
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _add_kind_parser(sub, kind: str, help_text: str):
    p = sub.add_parser(kind, help=help_text)
    p.add_argument("--config", required=True, help="run YAML document")
    p.add_argument("--set", dest="sets", action="append", default=[],
                   metavar="PATH=VALUE",
                   help="override a document path (YAML-typed value); "
                        "repeatable")
    return p


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro",
        description="Declarative run API: every entrypoint resolves through "
                    "the config graph.",
    )
    sub = ap.add_subparsers(dest="command", required=True)

    _add_kind_parser(sub, "train", "resolve the graph and drive the gym")
    w = _add_kind_parser(sub, "warmstart",
                         "train from another run's checkpoint under this "
                         "run's (possibly different) sharding plan/mesh")
    w.add_argument("--source", default="",
                   help="checkpoint dir (shorthand for "
                        "--set run.warmstart.source=...)")
    _add_kind_parser(sub, "sft",
                     "supervised finetuning: loss-masked prompt/response "
                     "batches, optionally through LoRA adapters")
    _add_kind_parser(sub, "dpo",
                     "direct preference optimization against a frozen "
                     "reference (static pairs or on-policy sampling)")
    _add_kind_parser(sub, "bench",
                     "measure compile / steady-state step time / tokens-sec "
                     "for a config; writes BENCH_<name>.json")
    d = _add_kind_parser(sub, "dryrun", "compile-time roofline analysis")
    d.add_argument("--json", default="", help="also write the result JSON here")
    _add_kind_parser(sub, "serve",
                     "continuous-batching engine / static-batch shim")
    _add_kind_parser(sub, "trace", "dump the compiled collective schedule")

    s = _add_kind_parser(sub, "sweep", "run a declarative ablation sweep")
    s.add_argument("--output-dir", default="",
                   help="override the spec's sweep directory")
    s.add_argument("--list", action="store_true",
                   help="print the expanded trials and exit (no execution)")
    s.add_argument("--report-only", action="store_true",
                   help="regenerate report from existing records and exit")
    s.add_argument("--redo", action="store_true",
                   help="ignore existing records, rerun every trial")
    s.add_argument("--max-trials", type=int, default=0,
                   help="cap how many new trials run this invocation")
    s.add_argument("--retry-failed", action="store_true",
                   help="on resume, re-run only transiently-failed trials "
                        "(IO/timeout); deterministic failures keep their "
                        "records")

    r = sub.add_parser("replay",
                       help="re-execute a run from its resolved.yaml artifact")
    r.add_argument("run_dir", help="directory holding resolved.yaml + "
                                   "manifest.json")

    v = sub.add_parser("validate",
                       help="schema + registry validation only, no execution")
    v.add_argument("paths", nargs="+",
                   help="run/sweep YAML files or directories of them")
    return ap


# ---------------------------------------------------------------------------
def _load_doc(path: str):
    from ..config.resolver import load_yaml

    doc = load_yaml(path)
    if doc is None:
        doc = {}
    return doc


def _parse_from_args(args, kind: str):
    from . import api
    from . import kinds as _kinds  # noqa: F401  (registers run kinds, e.g. warmstart)
    from .config import parse_run_doc
    from .overrides import apply_overrides, parse_overrides

    doc = _load_doc(args.config)
    stem = os.path.splitext(os.path.basename(args.config))[0]
    config_dir = os.path.dirname(os.path.abspath(args.config))
    cfg = parse_run_doc(doc, kind=kind, default_name=stem,
                        config_dir=config_dir)
    sets = parse_overrides(args.sets)
    if sets:
        # overrides address the NORMALIZED document, so paths like
        # run.train.steps work even when the YAML omits the section
        cfg = parse_run_doc(apply_overrides(cfg.doc, sets), kind=kind,
                            default_name=stem, config_dir=config_dir)
    return api, cfg


def _cmd_kind(args, kind: str) -> int:
    if kind == "warmstart" and getattr(args, "source", ""):
        args.sets.append(f"run.warmstart.source={args.source}")
    api, cfg = _parse_from_args(args, kind)
    log = lambda msg: print(msg, flush=True)  # noqa: E731
    options = {"verbose": True}
    result = api.execute(cfg, options=options, log=log)
    if kind in ("train", "warmstart", "sft", "dpo"):
        if result.get("logged_points"):
            print(f"done: {result['logged_points']} logged points; first loss "
                  f"{result['first_loss']:.4f} -> last "
                  f"{result['final_loss']:.4f}", flush=True)
        else:
            print(f"done: {result['steps']} steps, no logged points "
                  f"(steps < log_every)", flush=True)
    if kind == "bench":
        print(f"bench artifact: {result.get('bench_file', '(disabled)')}",
              flush=True)
    if kind == "dryrun" and getattr(args, "json", ""):
        with open(args.json, "w") as f:
            json.dump(result, f, indent=2, default=str)
    print(f"run artifact: {cfg.output_dir} ({result['fingerprint'][:15]}…)",
          flush=True)
    if result.get("status") == "preempted":
        # distinct resumable status (EX_TEMPFAIL): the scheduler should
        # relaunch this exact command with resume intact
        from ..resilience import PREEMPTED_EXIT_CODE

        print(f"preempted: resume with the same command "
              f"(exit {PREEMPTED_EXIT_CODE})", flush=True)
        return PREEMPTED_EXIT_CODE
    return 0


def _cmd_sweep(args) -> int:
    from .kinds import build_sweep_spec

    api, cfg = _parse_from_args(args, "sweep")
    if args.output_dir:
        # keep the run artifact (resolved.yaml/manifest) with the sweep output
        cfg.output_dir = args.output_dir
        cfg.doc["run"]["output_dir"] = args.output_dir

    if args.list:
        spec = build_sweep_spec(cfg, args.output_dir)
        trials = spec.trials()
        print(f"sweep {spec.name!r}: backend={spec.backend} "
              f"trials={len(trials)}")
        for t in trials:
            patches = dict(t.patches)
            if t.seed is not None:
                patches["<seed>"] = t.seed
            print(f"  [{t.index}] {t.trial_id}: {json.dumps(patches)}")
        return 0

    if args.report_only:
        from ..sweep.report import write_report
        from ..sweep.spec import SweepError

        spec = build_sweep_spec(cfg, args.output_dir)
        try:
            summary = write_report(spec)
        except SweepError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        _print_report(spec.output_dir, summary.get("best"),
                      spec.objective_mode, spec.objective_metric)
        return 0

    options = {"redo": args.redo, "max_trials": args.max_trials,
               "retry_failed": args.retry_failed}
    if args.output_dir:
        options["output_dir"] = args.output_dir
    result = api.execute(cfg, options=options,
                         log=lambda msg: print(msg, flush=True))
    _print_report(result["sweep_output_dir"], result.get("best"),
                  result["objective_mode"], result["objective_metric"])
    return 1 if result.get("n_failed") else 0


def _print_report(output_dir, best, mode, metric) -> None:
    with open(os.path.join(output_dir, "report.txt")) as f:
        print(f.read())
    if best:
        print(f"best trial: {best['trial_id']} "
              f"({mode} {metric} = {best['value']:.6g})")
    print(f"report: {os.path.join(output_dir, 'report.json')}")


def _cmd_replay(args) -> int:
    from . import api

    result = api.replay(args.run_dir, log=lambda m: print(m, flush=True))
    print(f"replayed {result['kind']} run: fingerprint "
          f"{result['fingerprint']}", flush=True)
    return 0


def _iter_yaml_paths(paths: List[str]):
    for p in paths:
        if os.path.isdir(p):
            for fn in sorted(os.listdir(p)):
                if fn.endswith((".yaml", ".yml")):
                    yield os.path.join(p, fn)
        else:
            yield p


def validate_path(path: str) -> str:
    """Validate one document; returns a human summary, raises on problems."""
    import repro.core.components  # noqa: F401
    import repro.run.kinds  # noqa: F401

    from ..config.resolver import validate_config
    from ..sweep.spec import SweepSpec
    from .config import parse_run_doc
    from .fingerprint import materialize

    doc = _load_doc(path)
    stem = os.path.splitext(os.path.basename(path))[0]
    cfg = parse_run_doc(doc, default_name=stem,
                        config_dir=os.path.dirname(os.path.abspath(path)))
    if cfg.kind == "sweep":
        spec = SweepSpec.from_dict(cfg.settings, config_dir=cfg.config_dir)
        n = len(spec.trials())
        if spec.backend == "gym" and isinstance(spec.base, dict) \
                and ("gym" in spec.base or "run" in spec.base):
            base = {k: v for k, v in spec.base.items() if k != "run"}
            validate_config(base)
        return f"kind=sweep backend={spec.backend} trials={n}"
    counts = validate_config(cfg.graph)
    materialize(cfg.doc)  # defaults must be expressible / variants known
    return (f"kind={cfg.kind} components={counts['components']} "
            f"top_level={counts['top_level']}")


def _cmd_validate(args) -> int:
    failures = 0
    for path in _iter_yaml_paths(args.paths):
        try:
            info = validate_path(path)
        except Exception as e:
            failures += 1
            print(f"FAIL {path}: {type(e).__name__}: {e}")
            continue
        print(f"ok   {path}  ({info})")
    if failures:
        print(f"{failures} config(s) failed validation", file=sys.stderr)
    return 1 if failures else 0


# ---------------------------------------------------------------------------
def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    command = args.command
    if command in _FORCE_DEVICES_KINDS:
        os.environ.setdefault("XLA_FLAGS", _XLA_FLAGS)
    if command != "validate":
        use_compile_cache()

    from ..config.resolver import ConfigError
    from ..sweep.spec import SweepError
    from .config import RunError

    try:
        if command == "validate":
            return _cmd_validate(args)
        if command == "replay":
            return _cmd_replay(args)
        if command == "sweep":
            return _cmd_sweep(args)
        return _cmd_kind(args, command)
    except (RunError, ConfigError, SweepError, FileNotFoundError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
