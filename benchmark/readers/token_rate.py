"""Tokens per second at the clients: every token that arrived after the
window's first round instant up to its last, over the time between the
two (``estimators.aligned_rate``)."""

from benchmark import estimators


def read(obs, args, ctx):
    gap_s = float(obs["traffic"].get("round_gap_ms", 30)) / 1000.0
    return estimators.aligned_rate(
        estimators.stream_of(obs["records"]), obs["t0"], obs["t1"], gap_s
    )
