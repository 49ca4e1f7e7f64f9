"""Batch generation job: every request is queued at t = 0 and the engine
works through them at full occupancy (``bench/serving.py``)."""
from bench import serving


def run(ctx):
    serving.run(ctx, realtime=False)
