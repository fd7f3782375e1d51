"""Median milliseconds per train step, from the sync-to-sync readings."""

import statistics

from benchmark import estimators


def read(obs, args, ctx):
    steps_per_s = estimators.sync_readings(obs["syncs"], obs["steps_per_sync"])
    return 1000.0 / statistics.median(steps_per_s) if steps_per_s else None
