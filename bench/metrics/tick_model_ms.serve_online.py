"""tick_model_ms.serve_online: the decode tick program's device self-time
outside ``tick_kv_ms`` and ``tick_copy_ms`` (attention math, MLP, head,
sampling) per tick of the traced window, in ms; the three partition the
tick program's device time (``bench/trace_scopes.py``)."""
from bench import trace_scopes


def read(record):
    if record.get("job") != "serve_online":
        return None
    return trace_scopes.tick_ms(trace_scopes.trace_of(record, __file__),
                                "model")
