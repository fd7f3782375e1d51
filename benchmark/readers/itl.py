"""A quantile of the gaps between consecutive text events of a request,
pooled over the window, in ms: ``{"q": 0.95}``."""

from benchmark import estimators


def read(obs, args, ctx):
    gaps = estimators.token_gaps(
        estimators.stream_of(obs["records"]), obs["t0"], obs["t1"]
    )
    q = estimators.quantile(gaps, float(args["q"]))
    return None if q is None else 1000.0 * q
