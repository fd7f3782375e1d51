"""``counter_ratio`` for a numerator that a program may lack while it has
the denominator (an older commit, measured with this reader): nothing
where either side did not move, so that a missing counter reads as
nothing and not as 0. Same arguments."""

from benchmark.readers import counter_ratio


def read(obs, args, ctx):
    counters = obs.get(args.get("counters", "counters"))
    if not counters or counter_ratio.delta(counters, args["num"]) <= 0:
        return None
    return counter_ratio.read(obs, args, ctx)
