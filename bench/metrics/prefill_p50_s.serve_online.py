"""prefill_p50_s.serve_online: median of the engine's per-request
``prefill_s``, admission to first token (chunked prefill, cache hits
included)."""
import numpy as np


def read(record):
    if record.get("job") != "serve_online":
        return None
    p = [r["prefill_s"] for r in record["rows"] if "prefill_s" in r]
    return float(np.percentile(p, 50)) if p else None
