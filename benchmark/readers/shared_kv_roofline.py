"""The share of its roofline of decode's attention over the ONE paged layer
that several layers read (``models/phi4flash.py``: the full layer and every
cross layer attend over the full layer's pages), in percent: ``{"match":
regex of the decode programs, "ops": regex of the page loops' device
operations, "context": counter}``.

Time: the device seconds of the operations matching ``ops`` that start
inside a module event matching ``match``, from the trace itself
(``moe_roofline.seconds_inside``), per second of the traced window. Work:
the family's ``shared_kv_cost`` of the positions the live rows attended
over, which the decode programs count on the device once a step beside the
sampled tokens (the cost function multiplies by the layers that read them),
over the seconds of ``trace_counters``, per second of those. The share is
the larger of bytes over bandwidth and operations over peak, over the time.
Nothing where the program has no such counter or operation, the family
counts no such cost, or there is no trace.
"""

from benchmark import harness, peaks
from benchmark import trace as trace_mod
from benchmark.readers import counter_ratio, moe_roofline


def read(obs, args, ctx):
    cost = getattr(harness.family(getattr(ctx, "family", None)), "shared_kv_cost", None)
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
