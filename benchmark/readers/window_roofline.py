"""The window layers' ring attention's share of its roofline in the decode
programs, in percent: ``{"match": regex of the decode programs, "ops":
regex of the ring attention's device operations, "context": counter}``.

Time: the device seconds of the operations matching ``ops`` that start
inside a module event matching ``match``, from the trace itself
(``moe_roofline.seconds_inside``), per second of the traced window. Work:
the family's ``window_cost`` of the positions the live rows' windows held,
which the decode programs count on the device beside the sampled tokens,
over the seconds of ``trace_counters``, per second of those. The share is
the larger of bytes over bandwidth and operations over peak, over the
time. Nothing where the program has no such counter or operation, the
family counts no such cost, or there is no trace.
"""

from benchmark import harness, peaks
from benchmark import trace as trace_mod
from benchmark.readers import counter_ratio, moe_roofline


def read(obs, args, ctx):
    cost = getattr(harness.family(getattr(ctx, "family", None)), "window_cost", None)
    tc, trace_dir = obs.get("trace_counters"), obs.get("trace_dir")
    if cost is None or not tc or not trace_dir:
        return None
    path = trace_mod.find_xplane(trace_dir)
    if not path:
        return None
    busy_s, window_s = moe_roofline.seconds_inside(
        trace_mod.load_xplane(path), args["match"], args["ops"])
    context = counter_ratio.delta(tc, [[args["context"], "value"]])
    if not busy_s or not window_s or context <= 0:
        return None
    work = cost(obs["model"], context / tc["seconds"])
    peak = peaks.peak(obs["device"]["kind"])
    least = max(work["bytes"] / peak["hbm_bytes_per_s"], work["flops"] / peak["flops_bf16"])
    return 100.0 * least / (busy_s / window_s)
