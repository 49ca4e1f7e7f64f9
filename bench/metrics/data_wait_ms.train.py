"""data_wait_ms.train: host time in the gym's ``gym/data_wait`` spans (the
loop waiting for its next batch) per ``gym/step`` span of the traced
window, in ms (``bench/trace_scopes.py``)."""
from bench import trace_scopes


def read(record):
    if record.get("job") != "train":
        return None
    s = trace_scopes.trace_of(record, __file__)
    if s is None or not s["steps"]:
        return None
    return 1e3 * s["data_wait_s"] / s["steps"]
