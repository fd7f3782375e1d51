"""The whole decode step's share of the chip's peak, in percent: the bytes
a step has to read (by the family's ``decode_step_bytes``: weights once,
the live rows' caches) over the chip's memory bandwidth, which is the peak
that binds at one token a row, divided by the device time of a token-step
of the decode programs, whatever they are made of. A family that counts no
bytes reads nothing."""

from benchmark import harness, peaks
from benchmark.readers import counter_ratio, decode_step


def read(obs, args, ctx):
    step_bytes = getattr(harness.family(getattr(ctx, "family", None)),
                         "decode_step_bytes", None)
    step_ms = decode_step.read(obs, args, ctx)
    tc = obs.get("trace_counters")
    if step_bytes is None or not step_ms or not tc:
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
    return peaks.decode_step_mfu(
        step_ms / 1000.0, step_bytes(obs["model"], rows, context), obs["device"]["kind"]
    )
