"""Tokens per second at the clients: the tokens that arrived inside the
window over its seconds (``estimators.plain_rate``). At a 12 ms round of
some 21 tokens the window's two edges cut 0.03% of 45 s."""

from benchmark import estimators


def read(obs, args, ctx):
    return estimators.plain_rate(estimators.stream_of(obs["records"]), obs["t0"], obs["t1"])
