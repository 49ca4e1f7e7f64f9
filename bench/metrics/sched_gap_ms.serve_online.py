"""sched_gap_ms.serve_online: device-idle time outside every engine span that
calls the device (``serve/tick``, ``serve/prefill_chunk``,
``serve/first_token``, ``serve/slot_write``, ``serve/page_table``) and
outside ``serve/idle``, per decode tick of the traced window, in ms: the
time the chip waited for the Python scheduler (``bench/trace_scopes.py``)."""
from bench import trace_scopes


def read(record):
    if record.get("job") != "serve_online":
        return None
    s = trace_scopes.trace_of(record, __file__)
    if s is None or not s["ticks"] or s["sched_gap_s"] is None:
        return None
    return 1e3 * s["sched_gap_s"] / s["ticks"]
