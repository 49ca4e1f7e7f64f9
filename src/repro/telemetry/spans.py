"""Where time goes, named once: layer scopes on the device, phase spans on
the host, both visible on the profiler's clock (docs/observability.md).

- :func:`scope` is ``jax.named_scope`` restricted to :data:`SCOPES`, the
  model's layers. A scope changes HLO metadata only: every device op
  compiled under it carries the name in its ``op_name`` (``tf_op`` in a
  profiler trace), so a trace's device time splits by layer, forward and
  backward alike (``jvp(attention)``, ``transpose(jvp(attention))``).
- :func:`span` is one host phase: a ``jax.profiler.TraceAnnotation``
  around the work, so the phase shows in a profiler trace, and, when a
  recorder with spans on is given, the same interval as a telemetry span
  row. With no profiler running the annotation costs one ``TraceMe``
  check.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Any, Dict, Optional

import jax

#: the model's layers, innermost wins where scopes nest (``kv_read`` and
#: ``kv_write`` sit inside ``attention``)
EMBED = "embed"
ATTENTION = "attention"
KV_READ = "kv_read"
KV_WRITE = "kv_write"
MLP = "mlp"
SSM = "ssm"
HEAD = "head"
LOSS = "loss"
OPTIMIZER = "optimizer"
SAMPLE = "sample"
SCOPES = (EMBED, ATTENTION, KV_READ, KV_WRITE, MLP, SSM, HEAD, LOSS,
          OPTIMIZER, SAMPLE)


def scope(name: str):
    """``jax.named_scope(name)`` for a layer of :data:`SCOPES`."""
    if name not in SCOPES:
        raise ValueError(f"unknown layer scope {name!r} (known: {SCOPES})")
    return jax.named_scope(name)


def span(name: str, recorder: Any = None, step: Optional[int] = None,
         **attrs: Any):
    """One host phase on the profiler's clock, as a context manager; with
    ``recorder`` (spans on) also its telemetry row, emitted at close, and
    the row's span id as the ``with`` target."""
    if recorder is None or not recorder.spans:
        return jax.profiler.TraceAnnotation(name)
    return _recorded(name, recorder, step, attrs)


@contextmanager
def _recorded(name: str, recorder: Any, step: Optional[int],
              attrs: Dict[str, Any]):
    with jax.profiler.TraceAnnotation(name):
        sid, parent, depth = recorder.open_span()
        t_open = time.perf_counter()
        try:
            yield sid
        finally:
            recorder.close_span(name, sid, parent, depth, t_open,
                                time.perf_counter(), step, attrs)
