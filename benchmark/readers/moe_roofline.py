"""The expert layers' share of their roofline in the decode programs, in
percent: ``{"match": regex of the decode programs, "ops": regex of the
expert layer's device operations, "hit": counter, "pairs": counter}``.

Time: the device seconds of the operations matching ``ops`` that start
inside a module event matching ``match``, from the trace itself (the
reduced trace keeps no operation's module), per second of the traced
window. Work: the family's ``moe_cost`` of the experts hit and the
token-expert pairs the engine counted (on the device, beside the sampled
tokens) over the seconds of ``trace_counters``, per second of those. The
share is the larger of bytes over bandwidth and operations over peak,
over the time. Nothing where the program has no such counter or
operation, the family counts no cost, or there is no trace.

An operation's trace name is its HLO line; a ``jax.named_scope`` does not
reach it (PERF.md, PR 46), so the expert layer is known by its grouped
products, ``ragged-dot``: the sort, the gathers and the scatter-add
around them are left out of the time, a few percent of it.
"""

import bisect
import re

from benchmark import harness, peaks
from benchmark import trace as trace_mod
from benchmark.readers import counter_ratio


def seconds_inside(trace, modules: str, ops: str):
    """(device seconds of matching operations inside matching modules,
    window seconds), summed over chips and divided by their number."""
    win = trace_mod.window(trace)
    planes = trace_mod.device_planes(trace)
    if win is None or not planes:
        return None, None
    mod_rx, op_rx = re.compile(modules), re.compile(ops)
    total = 0.0
    for plane in planes:
        spans = sorted((s, s + d) for name, s, d in trace_mod._line(plane, trace_mod.MODULES_LINE)
                       if mod_rx.search(name))
        starts = [a for a, _ in spans]
        for name, s, d in trace_mod._line(plane, trace_mod.OPS_LINE):
            if not op_rx.search(name) or not win[0] <= s < win[1]:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < spans[i][1]:
                total += d
    return total * 1e-9 / len(planes), (win[1] - win[0]) * 1e-9


def read(obs, args, ctx):
    cost = getattr(harness.family(getattr(ctx, "family", None)), "moe_cost", None)
    tc, trace_dir = obs.get("trace_counters"), obs.get("trace_dir")
    if cost is None or not tc or not trace_dir:
        return None
    path = trace_mod.find_xplane(trace_dir)
    if not path:
        return None
    busy_s, window_s = seconds_inside(trace_mod.load_xplane(path), args["match"], args["ops"])
    hit = counter_ratio.delta(tc, [[args["hit"], "value"]])
    pairs = counter_ratio.delta(tc, [[args["pairs"], "value"]])
    if not busy_s or not window_s or hit <= 0 or pairs <= 0:
        return None
    work = cost(obs["model"], hit / tc["seconds"], pairs / tc["seconds"])
    peak = peaks.peak(obs["device"]["kind"])
    least = max(work["bytes"] / peak["hbm_bytes_per_s"], work["flops"] / peak["flops_bf16"])
    return 100.0 * least / (busy_s / window_s)
