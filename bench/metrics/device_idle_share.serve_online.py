"""device_idle_share.serve_online: 1 - (union of device-operation intervals) /
traced window, from the profiler trace of a few seconds of the run, in
percent."""


def read(record):
    tr = record.get("trace")
    if record.get("job") != "serve_online" or not tr:
        return None
    return 100.0 * tr["idle_share"]
