"""Device milliseconds of the decode programs per token-step, with the
token-steps counted by the engine and not inferred.

The trace gives the seconds the decode programs ran in the traced
window; the engine's own counter of token-steps (K per harvested chunk)
gives the steps over the seconds of ``trace_counters`` around it. The
two windows are a few milliseconds apart in length and the counter
moves at harvest, so up to one chunk at each edge belongs to the other
side: each is taken per second of its own window.
``{"match": regex, "steps": counter name}``. Nothing where the program
has no such counter, or it did not move.
"""

from benchmark.readers import counter_ratio, trace_time


def read(obs, args, ctx):
    s, _ = trace_time.matched(obs, {"line": "modules", "match": args["match"]})
    tc = obs.get("trace_counters")
    if not s or not tc:
        return None
    steps = counter_ratio.delta(tc, [[args["steps"], "value"]])
    if steps <= 0:
        return None
    return 1000.0 * (s / obs["trace"]["window_s"]) / (steps / tc["seconds"])
