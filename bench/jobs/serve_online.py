"""Online serving job: requests arrive on the traffic's open-loop
schedule and are timed from their scheduled arrival (``bench/serving.py``)."""
from bench import serving


def run(ctx):
    serving.run(ctx, realtime=True)
