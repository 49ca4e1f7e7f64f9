"""The gym: a generic SPMD training driver (paper Fig. 1, right box).

The resolved object graph — model, optimizer, sharding plan, loader,
checkpointer, trackers — is injected; the gym only drives the loop. It owns
no architecture- or strategy-specific logic (that's the whole point).

Hot-path notes: the loader is wrapped in a :class:`PrefetchLoader` (a
background thread keeps the next ``prefetch`` batches on device, sharded per
the plan), metrics stay on device between log points — one
``jax.device_get`` per ``log_every`` window, flushed one window late so the
fetch never blocks dispatch of the current step — and checkpoints route
through the async engine (:mod:`repro.ckpt`): the loop pays only for the
overlapped device->host snapshot, serialization happens on a writer
thread.

Resilience (:mod:`repro.resilience`, all optional): a ``sentinel``
inspects every flushed metric point and an anomaly rolls the run back to
the newest committed checkpoint strictly *before* the anomaly step
(metrics flush one window late, so the latest checkpoint may already
hold corrupted state); a ``preempt_guard`` turns SIGTERM into one final
synchronous checkpoint and a resumable exit; a ``fault_injector``
schedules deterministic failures through the same paths the real ones
take."""
from __future__ import annotations

import dataclasses
import os
import shutil
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from ..data.prefetch import PrefetchLoader
from ..sharding import plans as PL
from ..train import checkpoint as CK
from ..train import steps as ST


@dataclasses.dataclass
class Gym:
    model: Any
    optimizer: Any
    loader: Any
    mesh: Any = None                      # None => single device
    plan: Any = None
    seed: int = 0
    grad_accum: int = 1
    log_every: int = 10
    eval_every: int = 0
    ckpt_every: int = 0
    ckpt_dir: str = ""
    checkpointer: Any = None              # CheckpointerIF (default: async)
    run_fingerprint: str = ""             # stamped into manifests; checked on restore
    prefetch: int = 2                     # device-prefetch depth (0 = sync)
    eval_fn: Optional[Callable] = None
    logger: Optional[Callable[[Dict[str, Any]], None]] = None
    # -- resilience (see repro.resilience; all optional) -------------------
    sentinel: Any = None                  # StepSentinel: anomaly detection
    preempt_guard: Any = None             # PreemptionGuard: graceful SIGTERM
    fault_injector: Any = None            # FaultInjector: scheduled chaos
    max_rollbacks: int = 3                # anomaly rollbacks before fatal
    skip_window: bool = False             # skip the anomalous data window
    ckpt_retry: Any = None                # RetryPolicy for checkpoint IO
    # -- telemetry (see repro.telemetry; both optional) --------------------
    telemetry: Any = None                 # TelemetryRecorder (unified sink)
    profiler: Any = None                  # ProfilerHook (jax.profiler window)

    def setup(self):
        if self.mesh is not None and self.plan is not None:
            mesh_ctx = PL.mesh_context(self.plan, self.mesh)
            storage_axes = self.plan.ep_storage_axes if self.plan.ep else ()
        else:
            mesh_ctx, storage_axes = None, ()
        self.mesh_ctx = mesh_ctx
        step_fn = self._build_step(mesh_ctx, storage_axes)
        if self.mesh is not None:
            state_sh, self.shard_warnings = PL.train_state_shardings(
                self.plan, self.mesh, self.model, self.optimizer,
                seed=self.seed,
            )
            self._state_sh = state_sh
            extra_sh = tuple(self._extra_step_shardings(state_sh))
            jitted = jax.jit(step_fn,
                             in_shardings=(state_sh, None) + extra_sh,
                             out_shardings=(state_sh, None),
                             donate_argnums=(0,))
        else:
            self.shard_warnings = []
            self._state_sh = None
            jitted = jax.jit(step_fn, donate_argnums=(0,))
        self._jit_step = jitted
        # extra step inputs (e.g. a DPO reference-params tree) are traced
        # arguments, NOT jit-closure constants: closing over them would bake
        # device buffers into the executable and double the weight memory
        self._step = lambda s, b: self._jit_step(s, b,
                                                 *self._step_extra_args())
        return self._init_state()

    def _init_state(self):
        """A fresh seed-initialized train state in this gym's layout — also
        the rollback fallback when no usable checkpoint predates an
        anomaly.  Requires :meth:`setup` to have run (shardings cached)."""
        key = jax.random.PRNGKey(self.seed)
        if self.mesh is not None:
            with self.mesh:
                return jax.jit(
                    lambda r: ST.init_train_state(self.model,
                                                  self.optimizer, r),
                    out_shardings=self._state_sh,
                )(key)
        return ST.init_train_state(self.model, self.optimizer, key)

    # -- subclass hooks ----------------------------------------------------
    # A Gym variant (e.g. the DPO gym) changes WHAT a step computes by
    # overriding these three; the loop, sharding, checkpointing, prefetch
    # and metrics machinery stay shared.
    def _build_step(self, mesh_ctx, storage_axes):
        """The (state, batch, *extras) -> (state, metrics) step function."""
        return ST.make_train_step(
            self.model, self.optimizer, mesh_ctx, storage_axes,
            grad_accum=self.grad_accum,
        )

    def _extra_step_shardings(self, state_sh) -> tuple:
        """in_shardings for the extra step arguments (sharded meshes only)."""
        return ()

    def _step_extra_args(self) -> tuple:
        """Extra positional arguments appended to every step call."""
        return ()

    # -- checkpointing -----------------------------------------------------
    def _ckpt(self):
        """The checkpointer this gym saves/restores through: the injected
        registry component, or a default async engine on ``ckpt_dir``."""
        ck = self.checkpointer
        if ck is None:
            if not self.ckpt_dir:
                return None
            from ..ckpt import AsyncCheckpointer

            ck = self.checkpointer = AsyncCheckpointer(self.ckpt_dir)
        # resilience knobs ride on the gym config; stamp them onto the
        # engine (injected registry checkpointers keep their own settings)
        if self.ckpt_retry is not None and hasattr(ck, "retry") \
                and ck.retry is None:
            ck.retry = self.ckpt_retry
        if self.fault_injector is not None \
                and hasattr(ck, "fault_injector") \
                and ck.fault_injector is None:
            ck.fault_injector = self.fault_injector
        return ck

    def save_policy(self, step: int) -> bool:
        """Does this step checkpoint? The ``ckpt_every`` knob (override for
        custom cadences — e.g. denser early saves)."""
        return bool(self.ckpt_every) and step % self.ckpt_every == 0

    def restore(self, state_like, source: str = "") -> Tuple[Any, Optional[int]]:
        """Restore the newest committed checkpoint into this gym's layout.

        ``source`` may be a checkpoint directory (either format), one
        committed ``step_XXXXXXXX`` dir, or a legacy ``.npz`` file; empty
        means the gym's own ``ckpt_dir``.  Returns ``(state, step)`` —
        unchanged ``(state_like, None)`` when there is nothing to restore.
        The restored leaves are laid out under THIS gym's plan/mesh, which
        need not match the topology the checkpoint was saved on.
        """
        from ..ckpt import elastic as EL
        from ..ckpt import format as CF

        ck = self._ckpt()
        if ck is not None and hasattr(ck, "wait"):
            ck.wait()  # queued saves must commit before "latest" is resolved
        src = source or self.ckpt_dir
        if not src:
            return state_like, None
        if os.path.isfile(src):
            path = src
        elif os.path.isdir(src) and CF.is_committed(src):
            path = src
        else:
            latest = CK.latest_checkpoint(src)
            if latest is None:
                return state_like, None
            path = latest[1]
        state_sh = getattr(self, "_state_sh", None)
        if os.path.isdir(path):
            saved_fp = CF.read_manifest(path).get("fingerprint", "")
            if saved_fp and self.run_fingerprint \
                    and saved_fp != self.run_fingerprint:
                # legitimate for elastic restores (a new plan/mesh changes
                # the fingerprint) but worth surfacing: the checkpoint was
                # written by a DIFFERENT resolved config
                import warnings

                warnings.warn(
                    f"restoring {path} saved under fingerprint "
                    f"{saved_fp[:22]}… into a run fingerprinted "
                    f"{self.run_fingerprint[:22]}… — the resolved configs "
                    f"differ", UserWarning, stacklevel=2)
            state = EL.restore(state_like, path, state_sh)
        else:
            state = CK.restore_checkpoint(state_like, path)
            if state_sh is not None:
                state = jax.device_put(state, state_sh)
        return state, int(jax.device_get(state["step"]))

    # -- input pipeline ----------------------------------------------------
    def _batch_shardings(self, batch):
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch
        )
        return PL.batch_shardings(self.plan, self.mesh, shapes)

    def _wrapped_loader(self):
        """The loader the loop actually drains: async device prefetch unless
        disabled or the injected loader already prefetches."""
        shardings = (self._batch_shardings
                     if self.mesh is not None and self.plan is not None
                     else None)
        if isinstance(self.loader, PrefetchLoader):
            # a YAML-wired loader/prefetch component knows nothing about the
            # mesh: drain a per-gym COPY carrying the plan's batch shardings
            # (the shared component instance is never mutated)
            if (self.loader.to_device and self.loader.shardings is None
                    and shardings is not None):
                return dataclasses.replace(self.loader, shardings=shardings)
            return self.loader
        if self.prefetch <= 0:
            return self.loader
        return PrefetchLoader(self.loader, depth=self.prefetch,
                              shardings=shardings)

    # -- training ----------------------------------------------------------
    def run(self, steps: int, state=None) -> Dict[str, Any]:
        """Train for ``steps`` steps.  Besides ``state`` and ``history`` the
        result carries the resilience record: ``events`` (anomaly /
        rollback / preempt / fault rows), ``rollbacks`` and ``preempted``
        — all empty/zero/False on a plain clean run."""
        if state is None:
            state = self.setup()
        start = int(state["step"])
        target = start + steps
        history: List[Dict[str, Any]] = []
        events: List[Dict[str, Any]] = []
        rollbacks = 0
        preempted = False
        dispatched = 0   # every step the loop issued, incl. replays
        data_offset = 0  # grows when skip_window drops anomalous batches
        t_run0 = time.perf_counter()  # full-precision monotonic epoch
        tel = self.telemetry
        do_spans = tel is not None and tel.spans
        inj = self.fault_injector
        guard = self.preempt_guard
        if guard is None and inj is not None and inj.pending("preempt"):
            # an injected preemption needs a flag holder even when no real
            # signal handler was wired; same polling path as the real thing
            from ..resilience.preempt import PreemptionGuard

            guard = PreemptionGuard()

        # the checkpointer is consulted through save_policy (not ckpt_every
        # directly) so a subclass can implement its own cadence
        ckpt = self._ckpt()
        ctx = self.mesh if self.mesh is not None else _nullctx()
        try:
            with ctx:
                while True:
                    current = int(jax.device_get(state["step"]))
                    if target - current <= 0:
                        break
                    pending: List[tuple] = []  # (step, device metrics, wall_s)

                    def flush(pending=pending):
                        if not pending:
                            return
                        t_f0 = time.perf_counter()
                        last_step = pending[-1][0]
                        fetched = jax.device_get([m for _, m, _ in pending])
                        rows = list(zip(list(pending), fetched))
                        pending.clear()
                        for (step, _, wall), vals in rows:
                            m = {k: float(v) for k, v in vals.items()}
                            if inj is not None and \
                                    inj.fire("nan_loss", step) is not None:
                                m["loss"] = float("nan")
                            m["step"] = step
                            m["wall_s"] = wall
                            if tel is not None:
                                # telemetry sees the observation even when
                                # the sentinel is about to trip on it
                                tel.metric(step, {k: v for k, v in m.items()
                                                  if k != "step"})
                            if self.sentinel is not None:
                                anomaly = self.sentinel.check(step, m)
                                if anomaly is not None:
                                    raise _Rollback(anomaly)
                            history.append(m)
                            if self.logger:
                                self.logger(m)
                        if do_spans:
                            tel.span_row("gym/flush", t_f0,
                                         time.perf_counter(), step=last_step)

                    loader = self._wrapped_loader()
                    batches = loader.batches(target - current,
                                             start_step=current + data_offset)
                    stop_step = 0
                    try:
                        it = iter(batches)
                        i = 0
                        while True:
                            # manual next() so the host-side wait for data
                            # is its own span, separated from dispatch
                            t_wait0 = time.perf_counter()
                            try:
                                batch = next(it)
                            except StopIteration:
                                break
                            t_wait1 = time.perf_counter()
                            step = current + i + 1
                            i += 1
                            if self.profiler is not None:
                                self.profiler.step_begin(step)
                            if inj is not None and \
                                    inj.fire("nan_params", step) is not None:
                                state = inj.corrupt_params(state)
                            state, metrics = self._step(state, batch)
                            dispatched += 1
                            if do_spans:
                                t_disp = time.perf_counter()
                                tel.span_row("gym/data_wait", t_wait0,
                                             t_wait1, step=step)
                                tel.span_row("gym/step", t_wait1, t_disp,
                                             step=step)
                            if self.log_every and (step % self.log_every == 0
                                                   or step == start + 1):
                                # fetch the PREVIOUS window now (long since
                                # computed — a cheap transfer), stash the
                                # current one: dispatch of the next step is
                                # never blocked on this step's metrics
                                flush()
                                pending.append((step, metrics,
                                                time.perf_counter() - t_run0))
                            if self.eval_every and self.eval_fn \
                                    and step % self.eval_every == 0:
                                ev = self.eval_fn(self.model, state["params"])
                                row = {"step": step,
                                       **{f"eval_{k}": float(v)
                                          for k, v in ev.items()}}
                                # eval points belong to the run record, not
                                # just the logger stream
                                history.append(row)
                                if tel is not None:
                                    tel.metric(step,
                                               {k: v for k, v in row.items()
                                                if k != "step"})
                                if self.logger:
                                    self.logger(row)
                            if ckpt is not None and self.save_policy(step):
                                # snapshot completes before the next step can
                                # donate the state buffers; serialization
                                # runs on the writer thread
                                t_ck0 = time.perf_counter()
                                ckpt.save(state, step,
                                          extra=self._ckpt_extra())
                                if do_spans:
                                    tel.span_row("gym/ckpt", t_ck0,
                                                 time.perf_counter(),
                                                 step=step)
                            if self.profiler is not None:
                                self.profiler.step_end(step, metrics)
                            if inj is not None and \
                                    inj.fire("preempt", step) is not None:
                                guard.request()
                            if guard is not None and guard.requested:
                                stop_step = step
                                break
                        flush()
                    except _Rollback as rb:
                        state, data_offset, rollbacks = self._rollback(
                            state, rb.event, events, history,
                            data_offset, rollbacks, ckpt)
                        continue
                    finally:
                        close = getattr(batches, "close", None)
                        if callable(close):
                            close()  # stop an abandoned prefetch worker
                    if stop_step:
                        # graceful preemption: one synchronous final save at
                        # the step boundary, then exit resumable
                        if ckpt is not None:
                            ckpt.save(state, stop_step,
                                      extra=self._ckpt_extra())
                            ckpt.wait()
                        events.append(guard.event(stop_step))
                        if tel is not None:
                            tel.event("preempt", step=stop_step)
                        if self.logger:
                            self.logger({"step": stop_step,
                                         "event": "preempt"})
                        preempted = True
                        guard.clear()
                    break
        finally:
            if self.profiler is not None:
                self.profiler.close()
            if ckpt is not None:
                # the run's last checkpoint must be committed and the writer
                # thread must not outlive the run (a sweep builds one gym per
                # trial) — even when the loop raised; close() drains first
                # and save() after close restarts the worker
                close = getattr(ckpt, "close", None)
                if callable(close):
                    close()
                else:
                    ckpt.wait()
        final_step = int(jax.device_get(state["step"]))
        return {"state": state, "history": history, "events": events,
                "rollbacks": rollbacks, "preempted": preempted,
                "steps_dispatched": dispatched,
                "productive_steps": max(0, final_step - start)}

    def _rollback(self, state, event, events, history, data_offset,
                  rollbacks, ckpt):
        """Recover from an anomaly: restore the newest committed checkpoint
        strictly BEFORE the anomaly step (detection lags one metrics
        window, so a checkpoint at/after it may hold corrupted state),
        falling back to a fresh seed init.  Checkpoints at/after the
        anomaly are deleted — they must never win a later "latest"
        resolution.  Returns the new ``(state, data_offset, rollbacks)``."""
        from ..ckpt import elastic as EL
        from ..ckpt import format as CF
        from ..resilience.sentinel import AnomalyError

        anomaly_step = int(event["step"])
        rollbacks += 1
        if rollbacks > self.max_rollbacks:
            events.append(dict(event, rollbacks=rollbacks, fatal=True))
            raise AnomalyError(
                f"anomaly at step {anomaly_step} ({event.get('reason')}): "
                f"rollback budget ({self.max_rollbacks}) exhausted", event)
        if ckpt is not None and hasattr(ckpt, "wait"):
            ckpt.wait()  # in-flight saves must commit before we pick one
        ckpt_dir = getattr(ckpt, "ckpt_dir", "") or self.ckpt_dir
        ckpts = CF.list_checkpoints(ckpt_dir) if ckpt_dir else []
        candidates = [(s, p) for s, p in ckpts if s < anomaly_step]
        if candidates:
            restored_step, path = max(candidates)
            state = EL.restore(state, path, getattr(self, "_state_sh", None))
        else:
            state = self._init_state()
            restored_step = int(jax.device_get(state["step"]))
        for s, p in ckpts:
            if s >= anomaly_step:
                shutil.rmtree(p, ignore_errors=True)
        history[:] = [m for m in history if m["step"] <= restored_step]
        if self.sentinel is not None:
            self.sentinel.reset()  # replayed steps re-observe their values
        if self.skip_window:
            data_offset += anomaly_step - restored_step
        events.append(dict(event, rollbacks=rollbacks,
                           restored_step=restored_step,
                           data_offset=data_offset))
        if self.telemetry is not None:
            self.telemetry.event("rollback", step=anomaly_step,
                                 reason=event.get("reason"),
                                 restored_step=restored_step,
                                 rollbacks=rollbacks)
        if self.logger:
            self.logger({"step": anomaly_step, "event": "rollback",
                         "reason": event.get("reason"),
                         "restored_step": restored_step})
        return state, data_offset, rollbacks

    def _ckpt_extra(self) -> Optional[Dict[str, Any]]:
        """Manifest extras: the run fingerprint, so a restore can tell when
        a checkpoint came from a different resolved config."""
        if not self.run_fingerprint:
            return None
        return {"fingerprint": self.run_fingerprint}

    # -- benchmarking ------------------------------------------------------
    def bench(self, steps: int = 20, warmup: int = 3,
              windows: int = 5) -> Dict[str, Any]:
        """Measure the hot path: compile time, steady-state step time,
        tokens/sec, and MFU on the device it ran on. The ONE timing
        implementation behind the ``bench`` run kind (``python -m repro
        bench``) and ``benchmarks/``.

        The ``steps`` are split into ``windows`` synchronized windows and
        ``steady_step_ms`` is the median of the per-window step times —
        a single long window lets one scheduler hiccup in a noisy
        container skew the whole figure (wall-clock swings of ~50% are
        documented in CHANGES.md); the median of several windows is
        robust to it.  Per-window rows ship in the result for
        inspection.  Steps are numbered from 1 (the compile step) for the
        ``profiler`` window: place it in the warm-up steps to keep the
        timed windows free of tracing cost.  ``losses`` holds every
        step's loss, compile and warm-up steps included.
        """
        import statistics

        from ..telemetry import accounting as ACC

        t0 = time.perf_counter()
        state = self.setup()
        setup_s = time.perf_counter() - t0
        start = int(state["step"])
        n_params = ACC.count_param_leaves(state["params"])
        tel = self.telemetry
        prof = self.profiler
        n_w = max(1, min(int(windows), steps))
        base, rem = divmod(steps, n_w)
        sizes = [base + (1 if w < rem else 0) for w in range(n_w)]
        sizes = [s for s in sizes if s > 0]
        losses: List[Any] = []   # device scalars, fetched once at the end
        ctx = self.mesh if self.mesh is not None else _nullctx()
        try:
            with ctx:
                loader = self._wrapped_loader()
                it = iter(loader.batches(1 + warmup + steps,
                                         start_step=start))

                def step(state):
                    n = start + len(losses) + 1
                    if prof is not None:
                        prof.step_begin(n)
                    state, m = self._step(state, next(it))
                    losses.append(m.get("loss", m.get("ce")))
                    if prof is not None:
                        prof.step_end(n, m)
                    return state, m

                t0 = time.perf_counter()
                state, m = step(state)
                jax.block_until_ready(m)
                compile_s = time.perf_counter() - t0  # trace+compile+run
                for _ in range(warmup):
                    state, m = step(state)
                jax.block_until_ready(m)
                window_rows: List[Dict[str, Any]] = []
                for k in sizes:
                    tw0 = time.perf_counter()
                    for _ in range(k):
                        state, m = step(state)
                    jax.block_until_ready(m)
                    tw1 = time.perf_counter()
                    window_rows.append({"steps": k,
                                        "wall_s": round(tw1 - tw0, 6),
                                        "step_ms": round((tw1 - tw0) / k
                                                         * 1000, 3)})
                    if tel is not None:
                        tel.metric(len(window_rows),
                                   {"bench_step_ms":
                                    window_rows[-1]["step_ms"],
                                    "bench_window_steps": k},
                                   phase="bench_window")
                jax.block_until_ready(state["step"])
        finally:
            if prof is not None:
                prof.close()
        wall = sum(r["wall_s"] for r in window_rows)
        steady_ms = statistics.median(r["step_ms"] for r in window_rows)
        losses = [float(v) for v in jax.device_get(losses)]
        devices = ACC.run_devices(self.mesh)
        result = {
            **ACC.device_info(devices),
            "n_params": n_params,
            "steps": steps,
            "warmup": warmup,
            "setup_s": round(setup_s, 3),
            "compile_s": round(compile_s, 3),
            "steady_step_ms": round(steady_ms, 3),
            "steady_step_ms_mean": round(wall / steps * 1000, 3),
            "windows": window_rows,
            "steps_per_s": round(steps / wall, 3) if wall > 0 else 0.0,
            "final_loss": round(losses[-1], 6),
            "losses": losses,
            "prefetch": self.prefetch,
            "grad_accum": self.grad_accum,
            # a clean bench dispatches every step productively by
            # construction (no rollback/preempt paths), so goodput is
            # exactly 1.0 — the CI schema guard asserts it
            "goodput": 1.0,
            "steps_dispatched": steps,
            "rollback_count": 0,
            "retry_count": int(getattr(self.checkpointer,
                                       "retry_count", 0) or 0),
            "graceful_exit": False,
        }
        # where the params and optimizer state live: bytes each device holds
        leaves = jax.tree_util.tree_leaves((state["params"], state["opt"]))
        per_dev: Dict[int, int] = {}
        for leaf in leaves:
            for shard in leaf.addressable_shards:
                per_dev[shard.device.id] = (per_dev.get(shard.device.id, 0)
                                            + shard.data.nbytes)
        result["state_bytes"] = sum(leaf.nbytes for leaf in leaves)
        result["state_bytes_per_device"] = per_dev
        stats = devices[0].memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            result["peak_bytes_in_use"] = int(stats["peak_bytes_in_use"])
        if prof is not None and prof.artifact:
            result["profile_trace"] = prof.artifact
        flops = ACC.flops_per_train_step(self.model, self.loader,
                                         self.grad_accum)
        peaks = ACC.device_peaks(devices[0])
        if flops:
            result["model_flops_per_step"] = flops
            if peaks is not None:
                result["mfu"] = ACC.mfu(flops, steady_ms / 1000.0,
                                        len(devices), peaks["flops_bf16"])
        gb = getattr(self.loader, "global_batch", None)
        seq = getattr(getattr(self.loader, "dataset", None), "seq_len", None)
        if gb and seq:
            result["global_batch"] = int(gb)
            result["seq_len"] = int(seq)
            result["tokens_per_s"] = int(gb * seq / (steady_ms / 1000.0)) \
                if steady_ms > 0 else 0
        if self.plan is not None and hasattr(self.plan, "describe"):
            from ..sharding import plans as PL

            result["plan"] = self.plan.describe()
            result["pipeline"] = PL.pipeline_info(
                self.plan, self.mesh,
                int(getattr(self.loader, "global_batch", 0) or 0))
        if tel is not None:
            tel.metric(None, {"steady_step_ms": result["steady_step_ms"],
                              "mfu": result.get("mfu"),
                              "tokens_per_s": result.get("tokens_per_s"),
                              "goodput": 1.0}, phase="bench_summary")
        return result


class _Rollback(Exception):
    """Internal control flow: the sentinel tripped mid-flush; unwind the
    current segment so :meth:`Gym._rollback` can restore and replay."""

    def __init__(self, event: Dict[str, Any]):
        super().__init__(event.get("reason", "anomaly"))
        self.event = event


class _nullctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False
