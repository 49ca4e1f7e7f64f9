"""Profiler hook: wrap a configured window of gym steps in
``jax.profiler.trace`` and record the artifact path as telemetry.

Configured declaratively on the Run API:

    telemetry:
      profile: {start_step: 5, num_steps: 2}

The hook is step-driven (``step_begin``/``step_end`` from the gym loop)
so it composes with resume/warmstart: a run resumed past ``start_step``
starts tracing at its first executed step at or beyond it.  A configured
window that cannot start or stop fails the run: a run asked for a trace
and must not report success without one.
"""
from __future__ import annotations

import os
from typing import Optional


class ProfilerHook:
    def __init__(self, start_step: int, num_steps: int, out_dir: str,
                 recorder=None) -> None:
        self.start_step = max(1, int(start_step))
        self.num_steps = max(1, int(num_steps))
        self.out_dir = str(out_dir)
        self.recorder = recorder
        self.active = False
        self.done = False
        self.artifact: Optional[str] = None
        self._stop_after = 0

    def step_begin(self, step: int) -> None:
        if self.done or self.active or step < self.start_step:
            return
        import jax

        os.makedirs(self.out_dir, exist_ok=True)
        jax.profiler.start_trace(self.out_dir)
        self.active = True
        self._stop_after = step + self.num_steps - 1
        if self.recorder is not None:
            self.recorder.event("profile_start", step=step,
                                path=self.out_dir)

    def step_end(self, step: int, outputs=None) -> None:
        """Close the window after its last step. ``outputs`` (that step's
        results) are waited for first, so the window's device work is in
        the trace and not cut off by the asynchronous dispatch."""
        if not self.active or step < self._stop_after:
            return
        if outputs is not None:
            import jax

            jax.block_until_ready(outputs)
        self._stop()
        if self.recorder is not None:
            self.recorder.event("profile_stop", step=step,
                                path=self.out_dir)

    def close(self) -> None:
        """Stop an open trace (preemption/rollback ended the run early)."""
        if self.active:
            self._stop()

    def _stop(self) -> None:
        import jax

        self.active = False
        self.done = True
        jax.profiler.stop_trace()
        self.artifact = self.out_dir
