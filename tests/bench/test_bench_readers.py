"""Each reader on observations made by hand: what it reads, and that it
returns nothing where there is nothing to read."""

import json
import os
import re
from types import SimpleNamespace

import pytest

from benchmark import harness, peaks
from benchmark.readers import (
    compiles, counter_ratio, decode_step, decode_step_mfu, device_idle, exposed,
    gauge_share, gen_lag, hbm_used, itl, moe_load_skew, observed, step_ms, sync_rate, token_rate,
    tpot, trace_time, train_mfu, ttft,
)

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
GPT2 = os.path.join(os.path.dirname(peaks.__file__), "families", "gpt2.py")
TPU = SimpleNamespace(platform="tpu", family=GPT2)
CPU = SimpleNamespace(platform="cpu", family=GPT2)
XL = {"n_embd": 1600, "n_layer": 48, "n_head": 25, "n_positions": 1024, "vocab_size": 50257}


def rec(i, due, events, kind="load", sent=None, usage=None):
    return {"i": i, "kind": kind, "due": due, "sent": due if sent is None else sent,
            "events": events, "usage": usage}


def serve_obs():
    records = [
        rec(0, 10.0, [(10.5, 1), (10.6, 1), (10.7, 2)], usage={"prompt_tokens": 100, "completion_tokens": 4}),
        rec(1, 11.0, [(12.0, 1), (12.3, 1)], sent=11.002, usage={"prompt_tokens": 200, "completion_tokens": 2}),
        rec(2, 12.0, []),  # never showed text
        rec(3, 5.0, [(5.1, 1)]),  # due before the window
        rec(4, 10.0, [(10.2, 1)], kind="probe"),
    ]
    return {"records": records, "t0": 10.0, "t1": 20.0, "traffic": {"request_timeout_s": 120}}


def test_ttft_counts_due_turns_and_ranks_failures_largest():
    obs = serve_obs()
    good, failed = ttft.samples(obs)
    assert good == pytest.approx([500.0, 1000.0]) and failed == 1
    assert ttft.read(obs, {"q": 0.5}, TPU) == pytest.approx(1000.0)
    assert ttft.read(obs, {"q": 1.0}, TPU) == 120000.0
    obs["records"] = obs["records"][:2]
    assert ttft.read(obs, {"q": 0.5}, TPU) == pytest.approx(750.0)


def test_gen_lag_itl_tpot_and_rate_read_the_window_only():
    obs = serve_obs()
    assert gen_lag.read(obs, {"q": 1.0}, TPU) == pytest.approx(2.0)
    assert itl.read(obs, {"q": 1.0}, TPU) == pytest.approx(300.0)
    # pooled over tokens: (0.2 + 0.3) s over (3 + 1) tokens after the first
    assert tpot.read(obs, {}, TPU) == pytest.approx(125.0)
    # every token that arrived in the window, over its ten seconds
    assert token_rate.read(obs, {}, TPU) == pytest.approx(7 / 10.0)


def test_token_rate_is_the_plain_rate_the_generator_prints():
    from benchmark import estimators
    from benchmark.generators import serve_sessions

    obs = {**serve_obs(), "device": {}}
    plain = estimators.plain_rate(estimators.stream_of(obs["records"]), obs["t0"], obs["t1"])
    assert token_rate.read(obs, {}, TPU) == plain
    printed = next(n for n in serve_sessions._readings(obs) if n.startswith("tokens/s: plain"))
    assert printed.endswith(f"{plain:.4f}")


def counters(before, after):
    def snap(d):
        return {k: ({"sum": v[0], "count": v[1]} if isinstance(v, tuple) else {"value": v})
                for k, v in d.items()}
    return {"before": snap(before), "after": snap(after)}


def test_counter_ratio_reads_deltas():
    obs = {"counters": counters(
        {"rt_serve_batch_fill": (100.0, 10), "hits": 5.0, "misses": 5.0},
        {"rt_serve_batch_fill": (340.0, 20), "hits": 35.0, "misses": 15.0},
    )}
    fill = {"num": [["rt_serve_batch_fill", "sum"]], "den": [["rt_serve_batch_fill", "count"]]}
    assert counter_ratio.read(obs, fill, TPU) == pytest.approx(24.0)
    share = {"num": [["hits", "value"]], "den": [["hits", "value"], ["misses", "value"]],
             "scale": 100}
    assert counter_ratio.read(obs, share, TPU) == pytest.approx(75.0)
    assert counter_ratio.read(obs, {"num": [["x", "value"]], "den": [["y", "value"]]}, TPU) is None
    assert counter_ratio.read({}, fill, TPU) is None


def test_gauge_share_is_the_mean_of_the_samples():
    samples = [{"occ": {"value": 90.0}, "tot": {"value": 96.0}},
               {"occ": {"value": 96.0}, "tot": {"value": 96.0}}, {}]
    obs = {"counters": {"samples": samples}}
    assert gauge_share.read(obs, {"num": "occ", "den": "tot"}, TPU) == pytest.approx(
        (93.75 + 100.0) / 2
    )
    assert gauge_share.read({"counters": {"samples": []}}, {"num": "occ", "den": "tot"}, TPU) is None


def traced():
    return {
        "busy_s": 3.9, "window_s": 4.0,
        "modules": {"jit_decode_paged_and_sample": 3.0, "jit_decode_multi_paged": 0.5,
                    "jit_prefill_paged": 0.4},
        "module_calls": {"jit_decode_paged_and_sample": 12.0, "jit_decode_multi_paged": 1.0,
                         "jit_prefill_paged": 8.0},
        "ops": {"closed_call bf16[384,1024,64] 3in": 0.2}, "op_calls": {"closed_call bf16[384,1024,64] 3in": 100.0},
        "host_calls": {"PjRtCompile": 2.0},
        "exposed_s": {"all-reduce": 0.032},
    }


def test_trace_time_device_idle_and_exposed():
    obs = {"trace": traced(), "traced_steps": 16}
    per_call = {"line": "modules", "match": "^jit_prefill_paged$", "per": "call", "scale": 1000}
    assert trace_time.read(obs, per_call, TPU) == pytest.approx(50.0)
    assert trace_time.read(obs, {"line": "ops", "match": "^closed_call", "per": "window"}, TPU) == 0.2
    assert trace_time.read(obs, {"line": "modules", "match": "^nothing$"}, TPU) is None
    assert trace_time.read({}, per_call, TPU) is None
    assert device_idle.read(obs, {}, TPU) == pytest.approx(2.5)
    assert device_idle.read({}, {}, TPU) is None
    assert exposed.read(obs, {"collective": "all-reduce"}, TPU) == pytest.approx(2.0)
    assert exposed.read({"trace": traced()}, {"collective": "all-reduce"}, TPU) is None


def decode_obs():
    # 4 s of counters: 2,000 tokens of which 80 were first tokens, rows 24
    # a round: 80 token-steps, 20 a second; decode ran 3.5 of 4 s
    tc = counters(
        {"rt_serve_tokens_generated_total": 0.0, "rt_serve_ttft_s": (0.0, 0),
         "rt_serve_batch_fill": (0.0, 0)},
        {"rt_serve_tokens_generated_total": 2000.0, "rt_serve_ttft_s": (40.0, 80),
         "rt_serve_batch_fill": (1920.0, 80)},
    )
    tc["seconds"] = 4.0
    obs = {"trace": traced(), "trace_counters": tc, "model": XL,
           "device": {"kind": "TPU v5 lite"},
           "records": [rec(0, 0, [], usage={"prompt_tokens": 100, "completion_tokens": 100})]}
    return obs, {"match": "^jit_decode_(paged_and_sample|multi_paged)$"}


def test_decode_step_and_its_share_of_the_peak_by_hand():
    obs, args = decode_obs()
    assert decode_step.token_steps_per_s(obs) == pytest.approx(20.0)
    assert decode_step.read(obs, args, TPU) == pytest.approx(1000 * (3.5 / 4.0) / 20.0)
    # 2 x 1,557,611,200 bytes of weights, K and V of 24 rows at 150 tokens
    step_bytes = harness.family(GPT2).decode_step_bytes(XL, 24.0, 150.0)
    assert step_bytes == pytest.approx(3_115_222_400 + 1_105_920_000)
    assert decode_step_mfu.read(obs, args, TPU) == pytest.approx(
        peaks.decode_step_mfu(0.04375, step_bytes, "TPU v5 lite")
    )
    assert decode_step_mfu.read(obs, args, TPU) == pytest.approx(11.78, abs=0.01)
    assert decode_step.read({"trace": traced()}, args, TPU) is None


def test_decode_step_mfu_reads_nothing_where_the_family_counts_no_bytes(tmp_path):
    """Nothing, not 0: a family file without ``decode_step_bytes``, and a
    configuration that names no family, leave the metric out of the line."""
    obs, args = decode_obs()
    bare = tmp_path / "bare.py"
    bare.write_text("def context(model):\n    return 128\n")
    assert harness.family(str(bare)).context({}) == 128
    assert decode_step_mfu.read(obs, args, SimpleNamespace(platform="tpu", family=str(bare))) is None
    assert decode_step_mfu.read(obs, args, SimpleNamespace(platform="tpu", family=None)) is None
    assert decode_step_mfu.read(obs, args, SimpleNamespace(platform="tpu")) is None


def test_decode_step_mfu_is_left_out_of_a_line_off_the_chip():
    """Through the command's own ``read_metrics``: the share is the
    device's, so a run on the CPU prints none under its name (nor under
    ``decode_step_mfu.chat``), and the same observations on the chip do."""
    from benchmark import run

    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    obs, _ = decode_obs()
    for cell in ("xl-batch-decode", "xl-chat-sessions"):
        plan = run.resolve(bench, cell, True)
        names = {m["name"] for m in plan["metrics"] if m["name"].startswith("decode_step_mfu")}
        assert len(names) == 1
        plan["metrics"] = [m for m in plan["metrics"] if m["name"] in names]
        assert run.read_metrics(bench, plan, obs, CPU) == {}
        (got,) = run.read_metrics(bench, plan, obs, TPU).values()
        assert got == {"value": pytest.approx(11.78, abs=0.01), "unit": "%"}


MOE = [("trinity-mini-serve", "afmoe", 128, 127.97), ("kanana-2-30b-a3b-serve", "deepseek_v3", 128, 127.7),
       ("mimo-v2.5-serve", "mimo_v2", 16, 15.7)]


def moe_cell(config, family):
    """(model, family module, context) of a configuration of BENCHMARK.json."""
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    model = harness.load_json(os.path.join(harness.ROOT, entry["file"]))["model"]
    path = harness.find(BENCH, "families", family, ".py")
    return model, harness.family(path), SimpleNamespace(platform="tpu", family=path)


@pytest.mark.parametrize("config,family,held,expected", MOE)
def test_decode_step_mfu_counts_the_experts_the_rows_reached(config, family, held, expected):
    """The step's bytes take the experts HIT, as the decode programs
    counted them over the traced seconds (69% of those held: 960 layer-steps
    of ``held`` experts, 0.69 of them reached), and the experts EXPECTED
    under even routing only where the program has no such series."""
    model, fam, ctx = moe_cell(config, family)
    obs, args = decode_obs()
    obs["model"] = model
    even = decode_step_mfu.read(obs, args, ctx)
    assert fam.expected_experts_hit(model, 24.0) == pytest.approx(
        held * (1 - (1 - fam.expected_experts_hit(model, 1.0) / held) ** 24))
    assert even == pytest.approx(peaks.decode_step_mfu(
        0.04375, fam.decode_step_bytes(model, 24.0, 150.0), "TPU v5 lite"))
    for side, hit in (("before", 100.0), ("after", 100.0 + 0.69 * 960 * held)):
        obs["trace_counters"][side].update(
            {"rt_serve_moe_experts_hit_total": {"value": hit},
             "rt_serve_moe_expert_steps_total": {"value": 100.0 + (side == "after") * 960 * held}})
    assert decode_step_mfu.experts_hit(obs["trace_counters"], model, ctx) == pytest.approx(0.69 * held)
    counted = decode_step_mfu.read(obs, args, ctx)
    assert counted == pytest.approx(peaks.decode_step_mfu(
        0.04375, fam.decode_step_bytes(model, 24.0, 150.0, experts_hit=0.69 * held), "TPU v5 lite"))
    one_expert = 2.0 * 3 * model["hidden_size"] * model["moe_intermediate_size"]
    layers = {"afmoe": 4, "deepseek_v3": 4, "mimo_v2": 6}[family]
    gone = fam.expected_experts_hit(model, 24.0) - 0.69 * held
    assert (fam.decode_step_bytes(model, 24.0, 150.0)
            - fam.decode_step_bytes(model, 24.0, 150.0, experts_hit=0.69 * held)) == pytest.approx(
                layers * gone * one_expert)
    assert (counted < even) == (gone > 0)
    # at the cell's 127 rows even routing expects all but a sliver of the held experts
    assert fam.expected_experts_hit(model, 127.0) == pytest.approx(expected, rel=2e-3)
    # a series that stood, and a family without experts beside moving series: as before
    obs["trace_counters"]["after"]["rt_serve_moe_experts_hit_total"] = {"value": 100.0}
    assert decode_step_mfu.read(obs, args, ctx) == pytest.approx(even)
    obs["trace_counters"]["after"]["rt_serve_moe_experts_hit_total"] = {"value": 100.0 + 960 * held}
    obs["model"] = XL
    assert decode_step_mfu.experts_hit(obs["trace_counters"], XL, TPU) is None
    assert decode_step_mfu.read(obs, args, TPU) == pytest.approx(11.78, abs=0.01)


SKEW = {"fullest": "rt_serve_moe_max_load_total", "pairs": "rt_serve_moe_assignments_total"}


@pytest.mark.parametrize("config,family,held", [
    ("trinity-mini-serve", "afmoe", 128),            # the family answers from num_experts
    ("kanana-2-30b-a3b-serve", "deepseek_v3", 128),  # no such function: n_routed_experts
    ("mimo-v2.5-serve", "mimo_v2", 16),              # the 16 held of 256 routed over
])
def test_moe_load_skew_asks_the_family_for_the_experts_held(config, family, held):
    model, _, ctx = moe_cell(config, family)
    # 600 layer-steps: the fullest expert took 4,800 pairs of 38,400
    counted = counters({"rt_serve_moe_max_load_total": 500.0, "rt_serve_moe_assignments_total": 1000.0},
                       {"rt_serve_moe_max_load_total": 5300.0, "rt_serve_moe_assignments_total": 39400.0})
    assert moe_load_skew.read({"counters": counted, "model": model}, SKEW, ctx) == pytest.approx(
        4800.0 * held / 38400.0)
    # nothing, and no exception: no counters, counters that did not move, a
    # model nobody counts the experts of
    still = counters({"rt_serve_moe_assignments_total": 7.0}, {"rt_serve_moe_assignments_total": 7.0})
    assert moe_load_skew.read({"model": model}, SKEW, ctx) is None
    assert moe_load_skew.read({"counters": None, "model": model}, SKEW, ctx) is None
    assert moe_load_skew.read({"counters": still, "model": model}, SKEW, ctx) is None
    assert moe_load_skew.read({"counters": counted, "model": XL}, SKEW, TPU) is None
    assert moe_load_skew.read({"counters": counted}, SKEW, SimpleNamespace(platform="tpu")) is None


def test_compiles_counts_cache_entries_and_compile_events():
    obs = {"cache_entries": {"t0": 75, "t1": 76}, "trace": traced()}
    assert compiles.read(obs, {}, TPU) == 3.0  # one entry, two PjRtCompile events
    assert compiles.read({"cache_entries": {"t0": 5, "t1": 5}}, {}, TPU) == 0.0


def test_train_readers():
    stamps = [100.0 + 2.0 * i for i in range(11)]
    stamps[6:] = [s + 1.0 for s in stamps[6:]]  # one slow sync
    obs = {"syncs": stamps, "tokens_per_sync": 262144, "steps_per_sync": 8,
           "model": {"n_embd": 768, "n_layer": 12, "n_head": 12, "n_positions": 1024,
                     "vocab_size": 50257},
           "device": {"kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 15e9,
                      "memory_limit_bytes": 16e9}, "setup_s": 25.5}
    # the rate is over all the time between the first and last sync, the
    # slow one included; the step and the MFU are at the median reading
    assert sync_rate.read(obs, {}, TPU) == pytest.approx(262144 * 10 / 21.0)
    assert step_ms.read(obs, {}, TPU) == pytest.approx(250.0)
    assert step_ms.read({"syncs": [1.0], "steps_per_sync": 8}, {}, TPU) is None
    assert train_mfu.read(obs, {}, TPU) == pytest.approx(49.31, abs=0.01)
    assert train_mfu.read(obs, {}, CPU) is None
    assert hbm_used.read(obs, {}, TPU) == pytest.approx(93.75)
    assert hbm_used.read(obs, {}, CPU) is None
    assert observed.read(obs, {"key": "setup_s"}, TPU) == 25.5
    assert observed.read(obs, {"key": "missing"}, TPU) is None
    assert sync_rate.read({"syncs": [1.0]}, {}, TPU) is None


# -- a program without the series reads nothing ---------------------------
#
# One case a metric of BENCHMARK.json, so a metric a later PR appends is a
# case from the day it is listed. Three refusals (PR 28, 41 and 43) came
# from a reader that raised on a tree without its span or counter: the
# driver measures the parent with the change's benchmark files, and the
# parent is such a tree.

# readers of what the generator itself observed (the clients' records, the
# trainer's syncs, the compile cache, the allocator): no series of the
# program's is theirs to miss, so they are held to raising nothing
GENERATORS_OWN = {"observed", "compiles", "hbm_used", "ttft", "itl", "gen_lag", "tpot",
                  "token_rate", "step_ms", "sync_rate", "train_mfu"}
# series a reader reads by a name of its own and not by its file's arguments
READ_BY_NAME = {
    "decode_step": {"rt_serve_tokens_generated_total", "rt_serve_ttft_s", "rt_serve_batch_fill"},
    "decode_step_mfu": {"rt_serve_tokens_generated_total", "rt_serve_ttft_s",
                        "rt_serve_batch_fill"},
}
# the programs the benchmark has measured as parents, by the series they had
OLDER_PROGRAMS = {
    "before_pr24": ({"rt_serve_batch_fill": (900.0, 100), "rt_serve_tokens_generated_total": 5.0},
                    {"rt_serve_batch_fill": (3600.0, 400),
                     "rt_serve_tokens_generated_total": 900.0}, {}),
    "before_pr46": ({"rt_serve_decode_steps_total": 1.0}, {"rt_serve_decode_steps_total": 9.0},
                    {"rt_serve_kv_pages_total": 97.0}),
    "before_pr59": ({"rt_serve_engine_round_host_s": (0.5, 100),
                     "rt_serve_engine_round_blocked_s": (20.0, 100)},
                    {"rt_serve_engine_round_host_s": (2.5, 500),
                     "rt_serve_engine_round_blocked_s": (100.0, 500)}, {}),
}


def generators_side(model):
    """What a generator observes itself whatever the program counts: no
    request finished, no sync stamped, nothing compiled, no trace."""
    return {"records": [], "t0": 10.0, "t1": 20.0, "traffic": {}, "model": model,
            "cache_entries": {"start": 5, "t0": 5, "t1": 5}, "syncs": [], "steps_per_sync": 8,
            "tokens_per_sync": 8 * 32 * 1024, "traced_steps": 0, "trace": None, "trace_dir": None,
            "attention_shape": {"bh": 384, "t": 1024, "d": 64},
            "device": {"kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 0,
                       "memory_limit_bytes": 0}}


def series_read(spec):
    return (set(re.findall(r"rt_\w+", json.dumps(spec.get("args", {}))))
            | READ_BY_NAME.get(spec["reader"], set()))


def observations_without(spec, model):
    """(label, observations) whose program lacks every series ``spec``
    reads: no counters at all, counters that are None, and each older
    program that had none of them."""
    side = generators_side(model)
    yield "no_counters", side
    yield "counters_none", {**side, "counters": None, "trace_counters": None}
    for label, (before, after, sample) in OLDER_PROGRAMS.items():
        if series_read(spec) & (set(before) | set(sample)):
            continue
        moved = {**counters(before, after), "samples": [counters(sample, {})["before"]]}
        yield label, {**side, "counters": moved, "trace_counters": {**moved, "seconds": 4.5}}
        yield label + "_untraced", {**side, "counters": moved,
                                    "trace_counters": {"before": {}, "after": {}, "seconds": 4.0}}


def contexts(entry):
    """(family path, model) of every configuration a cell of the metric's
    list runs, and no family at all."""
    cells = [w for w in BENCH["workloads"]
             if w["name"] in entry.get("workloads", [w["name"]])]
    seen = {(None, None): (SimpleNamespace(platform="tpu"), XL)}
    for name in sorted({w["config"] for w in cells}):
        cfg = harness.load_json(os.path.join(
            harness.ROOT, next(c["file"] for c in BENCH["configs"] if c["name"] == name)))
        path = harness.find(BENCH, "families", cfg["family"], ".py")
        seen[path, name] = (SimpleNamespace(platform="tpu", family=path), cfg["model"])
    return list(seen.values())


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
def test_a_program_without_the_series_reads_nothing(name):
    """Every listed metric through its reader, in every family its cells
    run, on a trace and counters that lack what it reads: nothing (never
    0) and no exception."""
    entry = next(m for m in BENCH["end_to_end"] + BENCH["per_layer"] if m["name"] == name)
    spec = harness.load_json(harness.find(BENCH, "metrics", name))
    reader = harness.module(BENCH, "readers", spec["reader"])
    tried = 0
    for ctx, model in contexts(entry):
        for label, obs in observations_without(spec, model):
            got = reader.read(obs, spec.get("args", {}), ctx)
            tried += 1
            if spec["reader"] not in GENERATORS_OWN:
                assert got is None, (label, getattr(ctx, "family", None), got)
    assert tried >= 4


def test_every_reader_file_is_some_listed_metrics():
    """So the test above runs every reader under ``benchmark/readers/``."""
    used = {harness.load_json(harness.find(BENCH, "metrics", m["name"]))["reader"]
            for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    readers = {f[:-3] for f in os.listdir(os.path.join(harness.ROOT, "benchmark", "readers"))
               if f.endswith(".py") and f != "__init__.py"}
    assert readers == used


def test_a_program_that_counts_steps_and_no_row_steps_reads_no_rows():
    """``decode_rows_mean`` divides a series of PR 24 by one the engine
    had before it: on a tree between the two the ratio of what moved is 0,
    and a step of no rows does not exist, so the file takes the reader
    that wants its numerator to have moved."""
    spec = harness.load_json(harness.find(BENCH, "metrics", "decode_rows_mean"))
    reader = harness.module(BENCH, "readers", spec["reader"])
    before, after, _ = OLDER_PROGRAMS["before_pr46"]
    assert reader.read({"counters": counters(before, after)}, spec["args"], TPU) is None
    after = {**after, "rt_serve_decode_row_steps_total": 40.0}
    assert reader.read({"counters": counters(before, after)}, spec["args"], TPU) == 5.0
