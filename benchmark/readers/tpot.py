"""Milliseconds per output token after the first, pooled over every
token seen in the window (``estimators.pooled_tpot``)."""

from benchmark import estimators


def read(obs, args, ctx):
    s = estimators.pooled_tpot(estimators.stream_of(obs["records"]), obs["t0"], obs["t1"])
    return None if s is None else 1000.0 * s
