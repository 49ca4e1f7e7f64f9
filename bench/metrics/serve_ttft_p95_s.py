"""serve_ttft_p95_s: 95th percentile, over every request that arrived in
the window, of scheduled arrival to first token (queueing included). A
request that never got its first token counts as infinitely late."""
import numpy as np


def read(record):
    if record.get("job") != "serve_online":
        return None
    t = [r.get("ttft_s", float("inf")) for r in record["rows"]]
    return float(np.percentile(np.asarray(t, np.float64), 95))
