"""The decode step's share of its roofline, in percent: the bytes a step
has to read (weights once in bf16, live K/V of the active rows) over the
chip's memory bandwidth, divided by the device time of a token-step."""

from benchmark import peaks
from benchmark.readers import counter_ratio, decode_step


def read(obs, args, ctx):
    step_ms = decode_step.read(obs, args, ctx)
    tc = obs.get("trace_counters")
    if not step_ms or not tc:
        return None
    fill_n = counter_ratio.delta(tc, [["rt_serve_batch_fill", "count"]])
    rows = counter_ratio.delta(tc, [["rt_serve_batch_fill", "sum"]]) / fill_n
    done = [r for r in obs["records"] if r["kind"] == "load" and r["usage"]]
    if not done:
        return None
    context = sum(
        r["usage"]["prompt_tokens"] + r["usage"]["completion_tokens"] / 2.0
        for r in done
    ) / len(done)
    return peaks.decode_roofline(
        step_ms / 1000.0, obs["model"], rows, context, obs["device"]["kind"]
    )
