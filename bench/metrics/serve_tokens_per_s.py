"""serve_tokens_per_s: output tokens over the part of a batch run in which
requests still wait for a slot, from t = 0 to the first token of the last
request admitted. That is the throughput of a batch job far longer than one
window; the drain of the last slots, which such a job pays once, is left
out. Every token produced in that part counts: all of a request done by
its end, and of one still decoding, its first token and one per decode tick
ended since."""
import bisect


def read(record):
    if record.get("job") != "serve_batch":
        return None
    rows = [r for r in record["rows"] if "ttft_s" in r]
    end = max(r["arrival_s"] + r["ttft_s"] for r in rows)
    ticks = record["tick_ends_s"]
    tokens = 0
    for r in rows:
        first = r["arrival_s"] + r["ttft_s"]
        if r.get("done_s", float("inf")) <= end:
            tokens += r["n_gen"]
        elif first <= end:
            tokens += 1 + (bisect.bisect_right(ticks, end)
                           - bisect.bisect_right(ticks, first))
    return tokens / end
