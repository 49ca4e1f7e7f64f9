"""queue_p95_s.serve_online: 95th percentile of the engine's per-request
``queue_s``, arrival to the start of admission (the scheduler's wait)."""
import numpy as np


def read(record):
    if record.get("job") != "serve_online":
        return None
    q = [r["queue_s"] for r in record["rows"] if "queue_s" in r]
    return float(np.percentile(q, 95)) if q else None
