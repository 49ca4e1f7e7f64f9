"""setup_s: process start to the first timed step or request, compilation
included, the capture of what the correctness check compares left out."""


def read(record):
    return record.get("setup_s")
