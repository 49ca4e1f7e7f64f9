"""device_idle_share.train: 1 - (union of device-operation intervals) /
traced window, from the profiler trace of a few window steps, in
percent."""


def read(record):
    tr = record.get("trace")
    if record.get("job") != "train" or not tr:
        return None
    return 100.0 * tr["idle_share"]
