"""optimizer_ms.train: device self-time of the ops under the ``optimizer``
scope (the AdamW update) per ``gym/step`` span of the traced window, in ms
(``bench/trace_scopes.py``)."""
from bench import trace_scopes


def read(record):
    if record.get("job") != "train":
        return None
    return trace_scopes.train_ms_per_step(
        trace_scopes.trace_of(record, __file__), "optimizer")
