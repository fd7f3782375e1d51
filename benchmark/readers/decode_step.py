"""Device milliseconds of the decode programs per token-step (one new
token for every active row).

The trace gives the share of the traced window the decode programs ran;
the counters over the same seconds give the token-steps: tokens that
decode generated (all generated, less one first token per request, which
prefill samples) over the mean rows in a round. ``{"match": regex}``.
"""

from benchmark.readers import counter_ratio, trace_time


def token_steps_per_s(obs):
    tc = obs.get("trace_counters")
    if not tc:
        return None
    tokens = counter_ratio.delta(tc, [["rt_serve_tokens_generated_total", "value"]])
    firsts = counter_ratio.delta(tc, [["rt_serve_ttft_s", "count"]])
    fill_n = counter_ratio.delta(tc, [["rt_serve_batch_fill", "count"]])
    fill = counter_ratio.delta(tc, [["rt_serve_batch_fill", "sum"]])
    if fill_n <= 0 or fill <= 0 or tokens <= firsts:
        return None
    return (tokens - firsts) / (fill / fill_n) / tc["seconds"]


def read(obs, args, ctx):
    s, _ = trace_time.matched(obs, {"line": "modules", "match": args["match"]})
    rate = token_steps_per_s(obs)
    if not s or not rate:
        return None
    return 1000.0 * (s / obs["trace"]["window_s"]) / rate
