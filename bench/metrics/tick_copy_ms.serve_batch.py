"""tick_copy_ms.serve_batch: the decode tick program's device self-time in
copies under no layer scope per tick of the traced window, in ms
(``bench/trace_scopes.py``)."""
from bench import trace_scopes


def read(record):
    if record.get("job") != "serve_batch":
        return None
    return trace_scopes.tick_ms(trace_scopes.trace_of(record, __file__),
                                "copy")
