"""The metrics that read the engine's own spans and counters: each
metric file through its reader on observations made by hand. (Nothing
where the program has no such series: one case a listed metric in
``test_bench_readers.py``.)"""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import harness
from benchmark.readers import decode_step_counted

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TPU = SimpleNamespace(platform="tpu")

# a window's counters as the engine would leave them: 50 requests
# admitted after 120 s queued in all, 30 s of it refused for pages and
# 25 s from admission to the first token; 400 rounds of 2 s host time
# and 80 s blocked on the device; 900 token-steps in 300 dispatches,
# 8,460 row-steps; 20,000 prompt tokens of which
# 15,000 were resident; 6,000 tokens prefilled in calls padded to 9,600
BEFORE = {
    "rt_serve_engine_queue_wait_s": (10.0, 5), "rt_serve_engine_page_wait_s": (1.0, 5),
    "rt_serve_engine_first_token_s": (2.0, 5), "rt_serve_engine_round_host_s": (0.5, 100),
    "rt_serve_engine_round_blocked_s": (20.0, 100), "rt_serve_decode_row_steps_total": 900.0,
    "rt_serve_decode_steps_total": 100.0, "rt_serve_batch_fill": (900.0, 100),
    "rt_serve_prefix_tokens_reused_total": 1000.0, "rt_serve_prompt_tokens_total": 2000.0,
    "rt_serve_prefill_tokens_total": 500.0, "rt_serve_prefill_width": (800.0, 10),
}
AFTER = {
    "rt_serve_engine_queue_wait_s": (130.0, 55), "rt_serve_engine_page_wait_s": (31.0, 55),
    "rt_serve_engine_first_token_s": (27.0, 55), "rt_serve_engine_round_host_s": (2.5, 500),
    "rt_serve_engine_round_blocked_s": (100.0, 500), "rt_serve_decode_row_steps_total": 9360.0,
    "rt_serve_decode_steps_total": 1000.0, "rt_serve_batch_fill": (3600.0, 400),
    "rt_serve_prefix_tokens_reused_total": 16000.0, "rt_serve_prompt_tokens_total": 22000.0,
    "rt_serve_prefill_tokens_total": 6500.0, "rt_serve_prefill_width": (10400.0, 70),
}
EXPECTED = {
    "queue_wait_ms.decode": 2400.0, "queue_wait_ms.chat": 2400.0,
    "page_wait_ms.decode": 600.0, "page_wait_ms.chat": 600.0,
    "admit_to_first_ms": 500.0,
    "engine_host_ms.decode": 5.0, "engine_host_ms.chat": 5.0,
    "engine_blocked_ms.decode": 200.0, "engine_blocked_ms.chat": 200.0,
    "decode_rows_mean": 9.4,
    "decode_k_mean": 3.0, "prefix_token_share": 75.0, "prefill_useful_share": 62.5,
    # decode ran 3.5 of the trace's 4.0 s; 36 steps in 4.5 s of counters
    "decode_step_counted_ms.decode": 1000 * (3.5 / 4.0) / (36 / 4.5),
    "decode_step_counted_ms.chat": 1000 * (3.5 / 4.0) / (36 / 4.5),
}


def snap(d):
    return {k: ({"sum": v[0], "count": v[1]} if isinstance(v, tuple) else {"value": v})
            for k, v in d.items()}


def observations(before=BEFORE, after=AFTER):
    return {
        "counters": {"before": snap(before), "after": snap(after)},
        "trace": {"window_s": 4.0,
                  "modules": {"jit_decode_paged_and_sample": 3.0, "jit_decode_multi_paged": 0.5,
                              "jit_prefill_paged": 0.4},
                  "module_calls": {"jit_decode_paged_and_sample": 12.0,
                                   "jit_decode_multi_paged": 1.0, "jit_prefill_paged": 8.0}},
        "trace_counters": {"before": snap({"rt_serve_decode_steps_total": 400.0}),
                           "after": snap({"rt_serve_decode_steps_total": 436.0}),
                           "seconds": 4.5},
    }


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def reporting(b, end_to_end):
    """The accepted cells that report an end-to-end metric."""
    entry = next(m for m in b["end_to_end"] if m["name"] == end_to_end)
    return [w["name"] for w in b["workloads"]
            if w["name"] in entry.get("workloads", [w["name"]])]


# the engine's own series, which every family's rounds and admissions move
# (decode_step_counted_ms.decode beside them is the device's: no CPU reading)
ENGINE_SERIES = {"queue_wait_ms.decode", "page_wait_ms.decode", "engine_host_ms.decode",
                 "engine_blocked_ms.decode"}


def listed_once(b, cell):
    """The cell's entry and its configuration's, each held by its name:
    once in its list, wherever in it (a later PR appends behind them)."""
    (entry,) = [w for w in b["workloads"] if w["name"] == cell]
    (config,) = [c for c in b["configs"] if c["name"] == entry["config"]]
    return entry, config


def on_every_list_the_other_serving_cells_share(b, cell):
    """``cell`` is judged on serve_tok_s and stands on every per-layer list
    that every other such cell stands on, wherever in a list a later cell
    is appended."""
    serving = reporting(b, "serve_tok_s")
    assert cell in serving
    for m in b["per_layer"]:
        if set(serving) - {cell} <= set(m.get("workloads", serving)):
            assert cell in m.get("workloads", serving), m["name"]


def through_its_reader(name, obs):
    b = bench()
    with open(harness.find(b, "metrics", name)) as f:
        spec = json.load(f)
    return spec, harness.module(b, "readers", spec["reader"]).read(obs, spec.get("args", {}), TPU)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_file_reads_the_engines_series(name):
    spec, value = through_its_reader(name, observations())
    assert value == pytest.approx(EXPECTED[name])
    b = bench()
    entry = next(m for m in b["per_layer"] if m["name"] == name)
    assert entry["unit"] == spec["unit"]
    # the engine's series are every family's: a metric of them is listed
    # in exactly the accepted cells that report what its suffix moves, so a
    # cell a later PR appends to both lists fails nothing here
    moves = {"decode": "serve_tok_s", "chat": "tpot_ms"}.get(name.rsplit(".", 1)[-1], "tpot_ms")
    assert entry["moves"] == moves
    first = {"serve_tok_s": "xl-batch-decode", "tpot_ms": "xl-chat-sessions"}[moves]
    assert first in entry["workloads"]
    assert sorted(entry["workloads"]) == sorted(reporting(b, moves))


def test_decode_step_counted_needs_a_trace_counters_and_steps():
    args = {"match": "^jit_decode_(paged_and_sample|multi_paged)$",
            "steps": "rt_serve_decode_steps_total"}
    obs = observations()
    assert decode_step_counted.read(obs, args, TPU) == pytest.approx(109.375)
    assert decode_step_counted.read({"trace": obs["trace"]}, args, TPU) is None  # no counters
    assert decode_step_counted.read({"trace_counters": obs["trace_counters"]}, args, TPU) is None
    still = dict(obs, trace_counters=dict(obs["trace_counters"],
                                          after=obs["trace_counters"]["before"]))
    assert decode_step_counted.read(still, args, TPU) is None  # the counter did not move
    other = dict(obs, trace=dict(obs["trace"], modules={"jit_prefill_paged": 0.4}))
    assert decode_step_counted.read(other, args, TPU) is None  # no decode program ran


def test_span_gaps_names_idle_time_by_the_programs_own_spans():
    """10 ms window, the device idle from 4 to 5 ms and from 9 to 10: the
    first gap falls in the harvest inside a round (the innermost rt/ span
    wins over the round and over a Python frame), the second in the idle
    wait."""
    from benchmark.tools import span_gaps

    ms = 1e6
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [["fusion f32[8] 2in", 0, 4 * ms],
                                           ["fusion f32[8] 2in", 5 * ms, 4 * ms]]}]},
        {"name": "/host:CPU", "lines": [{"name": "llm-engine", "events": [
            ["bench/window", 0, 10 * ms], ["rt/engine/round", 0.5 * ms, 8 * ms],
            ["$llm.py:1 one_round", 0.5 * ms, 8 * ms], ["rt/engine/harvest", 3.8 * ms, 1.5 * ms],
            ["rt/engine/round", 8.6 * ms, 3 * ms], ["rt/engine/idle", 8.9 * ms, 2.6 * ms]]}]},
    ]}
    got = span_gaps.reduce_spans(trace)
    assert got["window_s"] == pytest.approx(0.010)
    assert got["spans"]["rt/engine/round"] == {"count": 2, "ms": pytest.approx(9.4)}
    assert got["spans"]["rt/engine/idle"] == {"count": 1, "ms": pytest.approx(1.1)}
    assert got["idle_s_by_span"] == {"rt/engine/harvest": pytest.approx(0.001),
                                     "rt/engine/idle": pytest.approx(0.001)}
