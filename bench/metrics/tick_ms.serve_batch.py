"""tick_ms.serve_batch: the engine's mean decode tick, its own ``decode_s`` over
``ticks`` (dispatch to the tokens back on the host), net of the
profiler's start and stop in a traced run."""


def read(record):
    if record.get("job") != "serve_batch" or not record.get("ticks"):
        return None
    return 1000.0 * record["decode_s"] / record["ticks"]
