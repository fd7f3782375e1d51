"""The fullest held expert's tokens over the mean held expert's, over the
window: ``{"fullest": counter, "pairs": counter}``. The fullest is summed
over expert layers and decode steps, the pairs over the held experts too,
so the mean takes the experts held: the family's ``held_experts(model)``
where its file has one (``afmoe``'s key is ``num_experts``), else the
configuration's ``n_routed_experts``. Nothing where the counters are
absent or still, or nobody says how many experts are held."""

from benchmark import harness
from benchmark.readers import counter_ratio


def held_experts(model, ctx):
    """Routed experts the chip holds a layer, by the family or by the
    configuration's own key; None where neither says."""
    ask = getattr(harness.family(getattr(ctx, "family", None)), "held_experts", None)
    return ask(model) if ask else model.get("n_routed_experts")


def read(obs, args, ctx):
    counters, model = obs.get("counters"), obs.get("model") or {}
    held = held_experts(model, ctx)
    if not counters or not held:
        return None
    pairs = counter_ratio.delta(counters, [[args["pairs"], "value"]])
    if pairs <= 0:
        return None
    return counter_ratio.delta(counters, [[args["fullest"], "value"]]) * float(held) / pairs
