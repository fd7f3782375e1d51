"""A ratio of counter deltas over the window:
``{"num": [[name, field], ...], "den": [[name, field], ...], "scale": x}``
with ``field`` one of ``value``, ``sum``, ``count``. Nothing where the
denominator did not move."""


def delta(counters, pairs):
    total = 0.0
    for name, field in pairs:
        after = counters["after"].get(name, {}).get(field, 0.0)
        before = counters["before"].get(name, {}).get(field, 0.0)
        total += after - before
    return total


def read(obs, args, ctx):
    counters = obs.get(args.get("counters", "counters"))
    if not counters:
        return None
    den = delta(counters, args["den"])
    if den <= 0:
        return None
    return float(args.get("scale", 1)) * delta(counters, args["num"]) / den
