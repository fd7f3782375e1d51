"""A kernel's share of its roofline, in percent, from the trace:
``{"match": regex, "cost": "flash_fwd" | "flash_bwd", "events_per_call": n}``.
Operations and bytes come from the shapes of one layer-call (the
per-chip batch x heads, sequence, head size); time is the device time of
the matching events, ``events_per_call`` of them to a layer-call."""

from benchmark import peaks
from benchmark.readers import trace_time

COSTS = {"flash_fwd": peaks.flash_fwd_cost, "flash_bwd": peaks.flash_bwd_cost}


def read(obs, args, ctx):
    s, n = trace_time.matched(obs, {"line": "ops", "match": args["match"]})
    if not s or not n:
        return None
    shape = obs["attention_shape"]
    cost = COSTS[args["cost"]](shape["bh"], shape["t"], shape["d"])
    per_call = s / (n / float(args.get("events_per_call", 1)))
    return peaks.roofline_share(cost, per_call, obs["device"]["kind"])["share"]
