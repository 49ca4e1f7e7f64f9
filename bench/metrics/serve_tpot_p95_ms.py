"""serve_tpot_p95_ms: 95th percentile, over the same requests, of the time
between output tokens as the user sees it, (completion - first token) /
(output tokens - 1); requests of one output token have none."""
import numpy as np


def read(record):
    if record.get("job") != "serve_online":
        return None
    t = [1000.0 * (r["done_s"] - r["arrival_s"] - r["ttft_s"]) / (r["n_gen"] - 1)
         if "done_s" in r else float("inf")
         for r in record["rows"] if r.get("n_gen", 2) > 1]
    return float(np.percentile(np.asarray(t, np.float64), 95))
