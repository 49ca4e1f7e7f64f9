"""tick_kv_ms.serve_online: the decode tick program's device self-time under the
``kv_read`` and ``kv_write`` scopes (page gathers and KV scatters) per
tick of the traced window, in ms (``bench/trace_scopes.py``)."""
from bench import trace_scopes


def read(record):
    if record.get("job") != "serve_online":
        return None
    return trace_scopes.tick_ms(trace_scopes.trace_of(record, __file__), "kv")
