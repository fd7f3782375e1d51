"""The whole decode step's share of the chip's peak, in percent: the bytes
a step has to read (by the family's ``decode_step_bytes``: weights once,
the live rows' caches) over the chip's memory bandwidth, which is the peak
that binds at one token a row, divided by the device time of a token-step
of the decode programs, whatever they are made of. A family that counts no
bytes reads nothing.

Of the routed experts a step reads the ones its rows reached and no other
(the grouped-matmul kernel visits no expert without a row), so where the
decode programs count them the family is handed the experts hit a
layer-step: ``rt_serve_moe_experts_hit_total`` over
``rt_serve_moe_expert_steps_total`` (the ratio ``moe_experts_hit`` reads)
times the experts held, from the same traced seconds' counters the rows
come from. A program without those series (no experts, or a tree before
PR 46) hands nothing, and a family of experts then falls back to the
experts expected under even routing."""

from benchmark import harness, peaks
from benchmark.readers import counter_ratio, decode_step, moe_load_skew

EXPERTS_HIT = "rt_serve_moe_experts_hit_total"
EXPERT_STEPS = "rt_serve_moe_expert_steps_total"


def experts_hit(tc, model, ctx):
    """Distinct held experts the rows reached in one layer of one step,
    the mean over the layer-steps of the traced seconds; None where the
    program counts none or nobody says how many are held."""
    steps = counter_ratio.delta(tc, [[EXPERT_STEPS, "value"]])
    hit = counter_ratio.delta(tc, [[EXPERTS_HIT, "value"]])
    held = moe_load_skew.held_experts(model or {}, ctx)
    if steps <= 0 or hit <= 0 or not held:
        return None
    return float(held) * hit / steps


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
    hit = experts_hit(tc, obs.get("model"), ctx)
    counted = {} if hit is None else {"experts_hit": hit}
    return peaks.decode_step_mfu(
        step_ms / 1000.0, step_bytes(obs["model"], rows, context, **counted),
        obs["device"]["kind"]
    )
