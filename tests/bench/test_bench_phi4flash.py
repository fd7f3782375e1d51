"""The cell ``phi4flash-long-reasoning``: its configuration against the
catalog row, its arithmetic (the eight readings of one layer's pages among
it), its traffic, the metrics PR 57 brought through their readers, and the
whole command rehearsed on the CPU at the tiny twin."""

import json
import os
import re
from types import SimpleNamespace

import bench_rehearsal_file
import pytest
from test_bench_engine_metrics import (
    ENGINE_SERIES, listed_once, on_every_list_the_other_serving_cells_share, snap,
    through_its_reader,
)
from test_bench_rehearsal import rehearse, run

from benchmark import harness, traffic
from benchmark import trace as trace_mod
from benchmark.readers import moe_roofline, shared_kv_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["kv_state_share", "prefill_cross_share", "shared_kv_roofline"]
CELL = "phi4flash-long-reasoning"
CUT = ["max_position_embeddings"]
# the lists ISSUE 57 names for the cell, and those it keeps it off
JOINED = {"deploy_ready_s", "engine_load_s", "batch_fill.decode", "kv_pages_used.decode",
          "decode_step_ms.decode", "decode_step_counted_ms.decode", "compiles_in_window.decode",
          "decode_step_mfu", "device_idle.decode", "hbm_used.decode", "queue_wait_ms.decode",
          "page_wait_ms.decode", "engine_host_ms.decode", "engine_blocked_ms.decode",
          "prefill_ms.decode", "prefill_rows_mean", "kv_window_share", "window_context_share",
          "attn_loop_useful_share"}
EXCLUDED = {"moe_tokens_per_expert", "moe_experts_hit", "moe_load_skew", "moe_gmm_roofline",
            "mla_context_mean", "mla_paged_roofline", "kv_latent_token_bytes",
            "prefix_token_share.decode"}


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return load("benchmark/configs/phi-4-mini-flash-serve.json")


def test_published_is_the_catalog_row_and_the_declared_positions_alone_are_cut(cfg):
    model, published = cfg["model"], cfg["published"]
    cut = [k for k in model if model[k] != published[k]]
    assert cut == cfg["reduced"] == CUT
    assert (published["max_position_embeddings"], model["max_position_embeddings"]) == (
        262144, 16384)
    assert all(cfg[k] == model[k] for k in model)  # the top level says what runs
    kept = {"hidden_size": 2560, "intermediate_size": 10240, "num_hidden_layers": 32,
            "num_attention_heads": 40, "num_key_value_heads": 20, "sliding_window": 512,
            "vocab_size": 200064, "mb_per_layer": 2, "tie_word_embeddings": True,
            "layer_norm_eps": 1e-05}
    for key, value in kept.items():
        assert model[key] == published[key] == value, key
    assert cfg["held"]["vocab_rows"] == [0, 200064]
    assert "whole model" in cfg["deployment"] and len(cfg["assumed"]) >= 10
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == cfg["source"])
    assert row["name"] == "Phi-4-mini-flash-reasoning"
    assert published == row["config"] and list(published) == list(row["config"])


def test_the_program_runs_the_models_sizes_and_refuses_another_models(cfg):
    from benchmark.families import phi4flash as family

    assert family.program_sizes(cfg["model_id"]) == cfg["model"]
    tiny = load("tests/bench/configs/phi4flash-tiny-serve.json")
    assert family.program_sizes(tiny["model_id"]) == tiny["model"]
    assert list(tiny["model"]) == list(cfg["model"])
    # what the program has no switch for stands as the source says it
    for key, value in family.IMPLEMENTS.items():
        assert cfg["published"][key] == value, key
    assert (family.IMPLEMENTS["tie_word_embeddings"], family.IMPLEMENTS["mb_per_layer"],
            family.IMPLEMENTS["hidden_act"], family.IMPLEMENTS["mlp_bias"],
            family.IMPLEMENTS["lm_head_bias"], family.IMPLEMENTS["embd_pdrop"],
            family.IMPLEMENTS["resid_pdrop"]) == (True, 2, "silu", False, False, 0, 0)


def test_the_memory_and_a_steps_bytes_are_the_arithmetic_the_configuration_states(cfg):
    from benchmark.families import phi4flash as family

    model = cfg["model"]
    mix = family.mixer_params(model)
    assert mix == {"mamba": 41_241_600, "window": 19_668_864, "full": 19_668_864,
                   "gmu": 26_214_400, "cross": 13_112_704}
    assert family.params_count(model) == 3_852_562_944
    assert "3,852,562,944" in cfg["memory"]["parameters"]
    assert family.position_bytes(model) == 5120
    assert family.state_bytes(model) == 16 * 5120 * 4 + 3 * 5120 * 2 == 358_400
    assert family.shared_readers(model) == 8  # layer 17 and the seven cross layers
    # the full layer's pool: max_batch_size x the context's pages + the scratch page
    pages = cfg["engine"]["max_batch_size"] * (model["max_position_embeddings"] // 64) + 1
    assert pages == 8193 and "8,193 pages" in cfg["memory"]["pool"]
    assert pages * 64 * 5120 == pytest.approx(2.68e9, rel=5e-3)
    rows = 4 * cfg["engine"]["max_batch_size"]
    assert rows * 512 * 5120 * 8 == pytest.approx(2.68e9, rel=5e-3)          # the rings
    assert rows * 9 * family.state_bytes(model) == pytest.approx(0.41e9, rel=1e-2)
    # a step at 128 rows and a context of 1,250, by hand: every weight once,
    # the rows' embedding vectors, nine states read and written, eight rings'
    # 512 held positions, and layer 17's 1,250 positions EIGHT times
    weights = 2.0 * (3_852_562_944 + 128 * 2560)
    a_row = 2 * 9 * 358_400 + 8 * 512 * 5120 + 8 * 1250 * 5120
    assert family.decode_step_bytes(model, 128, 1250) == pytest.approx(weights + 128 * a_row)
    assert family.decode_step_bytes(model, 128, 1250) == pytest.approx(17.77e9, rel=1e-3)
    # inside the window the rings grow with the context, past it only the pages
    assert (family.decode_step_bytes(model, 128, 300)
            - family.decode_step_bytes(model, 128, 200)) == 128 * 100 * 16 * 5120
    assert (family.decode_step_bytes(model, 128, 2000)
            - family.decode_step_bytes(model, 128, 1000)) == 128 * 1000 * 8 * 5120
    assert "eight" in family.decode_step_bytes.__doc__.lower()
    cost = family.shared_kv_cost(model, 128 * 1250)
    assert cost == {"bytes": 128 * 1250 * 8 * 5120.0,
                    "flops": 128 * 1250 * 8 * 40 * 2.0 * (64 + 128)}
    assert family.window_cost(model, 128 * 512) == {
        "bytes": 128 * 512 * 8 * 5120.0, "flops": 128 * 512 * 8 * 40 * 2.0 * (64 + 128)}


def test_long_reasoning_sizes_are_what_the_cell_says(cfg):
    tr = load("benchmark/traffic/long-reasoning.json")
    assert (tr["users"], tr["system_prompt_tokens"], tr["max_turns"], tr["think_s"]) == (
        160, 0, 1, 0)
    assert (tr["endpoint"], tr["context_limit"], tr["session_pool"], tr["pool_seed"]) == (
        "/v1/completions", 3584, 1024, 5701)
    assert tr["turn_tokens"] == {"dist": "lognormal", "median": 512, "sigma": 0.8,
                                 "lo": 64, "hi": 2048}
    assert tr["reply_tokens"] == {"dist": "uniform", "lo": 768, "hi": 1536}
    assert 4 * cfg["engine"]["max_batch_size"] == 128 < tr["users"]  # a backlog from the start
    assert cfg["engine"]["max_new_tokens_cap"] >= 1536
    pool = traffic.session_pool(tr)
    assert len(pool) == 1024 and all(len(script) == 1 for script in pool)
    prompts = sorted(script[0]["prompt_tokens"] for script in pool)
    assert 450 < prompts[len(prompts) // 2] < 580
    assert 600 < sum(prompts) / len(prompts) < 760
    over = lambda n: sum(p > n for p in prompts) / len(prompts)  # noqa: E731
    assert 0.40 < over(512) < 0.60 and 0.02 < over(2047) < 0.08
    assert prompts[0] >= 64 and prompts[-1] == 2048
    for script in pool:
        turn = script[0]
        assert 768 <= turn["reply_tokens"] <= 1536
        assert turn["prompt_tokens"] + turn["reply_tokens"] <= 3584
    assert tr["warm"]["decode_k"] == list(range(1, 9)) and tr["warm"]["seconds"] in (60, 90)
    assert (tr["probe"], tr["trace_offset_s"], tr["trace_seconds"], tr["request_timeout_s"]) == (
        {"prompt_tokens": 40, "max_tokens": 17}, 6, 4, 180)


@pytest.mark.parametrize("name", ["long-reasoning", "tiny-reasoning"])
def test_the_new_mixes_are_reproducible_from_the_seed(name):
    tr = load(("benchmark" if name == "long-reasoning" else "tests/bench")
              + f"/traffic/{name}.json")
    big = 3_000_000_019
    a, b = traffic.plan(tr, big), traffic.plan(tr, big)
    assert a == b and a != traffic.plan(tr, 11) and a["system"] is None
    session = a["sessions"][0]
    body = traffic.turn_request(tr, "m", a, session, 0)
    assert len(body["prompt"].encode()) == session["script"][0]["prompt_tokens"]
    assert body["max_tokens"] == session["script"][0]["reply_tokens"]


GAUGES = {"rt_serve_kv_state_bytes": 0.4e9, "rt_serve_kv_window_bytes": 2.6e9,
          "rt_serve_kv_full_bytes": 2.0e9}
COUNTED = {
    "before": snap({"rt_serve_prefill_cross_positions_total": 300.0,
                    "rt_serve_prefill_tokens_total": 100_000.0}),
    "after": snap({"rt_serve_prefill_cross_positions_total": 300.0 + 400,
                   "rt_serve_prefill_tokens_total": 100_000.0 + 160_000}),
    "samples": [snap(GAUGES), snap(GAUGES)],
}


def test_the_data_only_metrics_read_the_engines_series():
    spec, got = through_its_reader("kv_state_share", {"counters": COUNTED})
    assert got == pytest.approx(8.0) and spec["reader"] == "gauge_part"
    spec, got = through_its_reader("prefill_cross_share", {"counters": COUNTED})
    assert got == pytest.approx(0.25) and spec["reader"] == "counter_ratio"
    for name in NEW:
        entry = next(m for m in load("BENCHMARK.json")["per_layer"] if m["name"] == name)
        spec = load(f"benchmark/metrics/{name}.json")
        assert entry["workloads"] == [CELL]
        assert (entry["moves"], entry["unit"]) == ("serve_tok_s", "%")
        assert spec["unit"] == "%" and len(spec["reads"]) > 200


def a_trace(pages=True):
    """Two decode programs and a prefill as the chip's trace names them
    since PR 58 (ledger, PR 61, ``breakdown``): in each decode program the
    kernel's calls over layer 17's pages, one a reading layer, beside a
    window layer's call of the same kernel under the ring's name and the
    K-step loop; ``pages=False`` is the tree before PR 58, the loops over
    pages (the carry opens with the running maximum) in the kernel's place."""
    page = ("paged_kv_attention f32[128,40,128] 9in" if pages
            else "while (s32[],f32[32,40,1],..) 1in")
    ring = "ring_kv_attention f32[128,40,128] 9in"
    ops = [[page, 1_000, 300_000],                                      # inside decode 1
           ["fusion bf16[32,512,1280] 2in", 302_000, 6_000],            # beside it: not its time
           [ring, 310_000, 80_000],                                     # a window layer's call
           [page, 600_000, 200_000],                                    # inside decode 1
           ["while (s32[],f32[2,40,512],..) 1in", 2_100_000, 900_000],  # prefill's attention
           ["while (s32[],f32[2,40,1],..) 1in", 3_050_000, 50_000],     # prefill's cross-decoder
           ["while (s32[],s32[128],..) 1in", 3_950_000, 900_000],       # the K-step loop
           [page, 4_000_000, 500_000]]                                  # inside decode 2
    modules = [["jit_decode_paged_and_sample", 0, 1_000_000],
               ["jit_prefill_paged", 2_000_000, 1_500_000],
               ["jit_decode_multi_paged", 3_900_000, 1_000_000]]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops}, {"name": "XLA Modules", "events": modules}]}]}


def test_shared_kv_roofline_counts_the_page_kernels_calls_inside_decode_programs_only(
        monkeypatch, cfg):
    """(``..._counts_the_page_loops_...`` until PR 62 pointed the file's
    ``ops`` at the kernel that replaced the loops in PR 58.)"""
    spec = load("benchmark/metrics/shared_kv_roofline.json")
    assert (spec["reader"], spec["args"]["ops"]) == ("shared_kv_roofline", "^paged_kv_attention")
    assert spec["args"]["context"] == "rt_serve_attn_context_tokens_total"
    assert "PR 62" in spec["reads"] and "mimo_v2, afmoe" in spec["reads"]
    busy, window = moe_roofline.seconds_inside(a_trace(), spec["args"]["match"],
                                               spec["args"]["ops"])
    assert busy == pytest.approx(1_000_000e-9)  # not the ring's calls, not the prefill's loops
    assert window == pytest.approx(4_850_000e-9 - 1_000e-9)
    # through the reader: 128 rows x 1,250 positions a step, 40 steps a traced second
    monkeypatch.setattr(trace_mod, "find_xplane", lambda d: "a.xplane.pb")
    monkeypatch.setattr(trace_mod, "load_xplane", lambda p: a_trace())
    held = 128 * 1250 * 40 * 4.0
    obs = {"model": cfg["model"], "trace_dir": "somewhere", "device": {"kind": "TPU v5 lite"},
           "trace_counters": {"before": snap({"rt_serve_attn_context_tokens_total": 7.0}),
                              "after": snap({"rt_serve_attn_context_tokens_total": 7.0 + held}),
                              "seconds": 4.0}}
    ctx = SimpleNamespace(platform="tpu", family=harness.find(
        load("BENCHMARK.json"), "families", "phi4flash", ".py"))
    got = shared_kv_roofline.read(obs, spec["args"], ctx)
    # 5,120 B a position a reading layer, eight of them, at 819 GB/s, over the loops' share
    assert got == pytest.approx(
        100 * (128 * 1250 * 8 * 5120 * 40 / 819e9) / (busy / window), rel=1e-3)
    assert 0 < got
    # a trace without the kernel (the loops of a tree before PR 58)
    monkeypatch.setattr(trace_mod, "load_xplane", lambda p: a_trace(pages=False))
    assert shared_kv_roofline.read(obs, spec["args"], ctx) is None
    monkeypatch.setattr(trace_mod, "load_xplane", lambda p: a_trace())
    # a family that counts no such cost, and a program without the counter
    assert shared_kv_roofline.read(obs, spec["args"], SimpleNamespace(platform="tpu")) is None
    other = SimpleNamespace(platform="tpu", family=harness.find(
        load("BENCHMARK.json"), "families", "afmoe", ".py"))
    assert shared_kv_roofline.read(obs, spec["args"], other) is None
    still = dict(obs, trace_counters=dict(obs["trace_counters"],
                                          after=obs["trace_counters"]["before"]))
    assert shared_kv_roofline.read(still, spec["args"], ctx) is None


def test_the_pattern_is_the_page_kernels_name_and_no_other_operations():
    """(``test_the_pattern_is_the_page_loops_name_and_no_other_loops`` until
    PR 62.) The name the chip's trace gives the kernel's calls over layer
    17's pages is what ``trace.short_op_name`` makes of their HLO line, a
    custom call under the kernel's own name
    (``ops/cached_attention.paged_attend``'s one-query form); the window
    layers' call of the same kernel under the ring's name, the loops of the
    tree before and prefill's loops are not matched."""
    rx = re.compile(load("benchmark/metrics/shared_kv_roofline.json")["args"]["ops"])
    line = ("%paged_kv_attention.9 = f32[128,40,128]{2,1,0:T(8,128)} custom-call("
            "s32[32768]{0:T(1024)} %bitcast.21, s32[128]{0:T(128)} %clamp.3, "
            "s32[129]{0:T(256)} %concatenate.5, s32[4096]{0:T(1024)} %fusion.91, "
            "bf16[128,40,64]{2,1,0:T(8,128)(2,1)} %fusion.402, "
            "bf16[64,1280]{1,0:T(8,128)(2,1)} %fusion.17, f32[40,1280]{1,0:T(8,128)} %fusion.18, "
            "bf16[8193,64,1280]{2,1,0:T(8,128)(2,1)} %get-tuple-element.61, "
            "bf16[8193,64,1280]{2,1,0:T(8,128)(2,1)} %get-tuple-element.62), "
            "custom_call_target=\"tpu_custom_call\", operand_layout_constraints={}")
    assert trace_mod.short_op_name(line) == "paged_kv_attention f32[128,40,128] 9in"
    assert rx.search(trace_mod.short_op_name(line))
    for other in ("ring_kv_attention f32[128,40,128] 9in", "while (s32[],f32[32,40,1],..) 1in",
                  "while (s32[],f32[32,40,1,128],..) 1in", "while (s32[],s32[128],..) 1in",
                  "while (s32[],f32[2,40,512],..) 1in", "paged_latent_attention bf16[128,32,640] 6in",
                  "fusion f32[128,40,128] 3in"):
        assert not rx.search(other), other


def the_cell_stands_on_its_lists(bench):
    """Of any ``bench``: the real file, and the copy with a cell appended
    that ``test_bench_contract.py`` makes. The cell is in ``workloads``
    once and its configuration in ``configs`` once, wherever: by name and
    by membership, never by place (PR 58 and PR 59 could append nothing to
    ``per_layer`` while this test pinned the lists' ends; PR 62)."""
    cell, entry = listed_once(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi-4-mini-flash-serve", "long-reasoning", 1)
    assert len(cell["why"]) <= 200
    on = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert set(NEW) <= on and JOINED <= on
    assert not EXCLUDED & on
    # the ring's loops are ops/cached_attention.ring_decode_attend's, Trinity's
    # carry shape, so the accepted reader matches them (PERF.md §6, PR 57)
    assert "window_attn_roofline" in on
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")["workloads"]
    on_every_list_the_other_serving_cells_share(bench, CELL)  # the engine's series among them
    assert entry["reduced"] == CUT and entry["source"] == load(entry["file"])["source"]
    # each of the entries PR 57 brought is listed once
    names = [m["name"] for m in bench["per_layer"]]
    assert all(names.count(name) == 1 for name in NEW)


def test_the_cell_stands_on_every_list_it_reports_and_on_none_it_is_kept_off(cfg):
    bench = load("BENCHMARK.json")
    the_cell_stands_on_its_lists(bench)
    assert listed_once(bench, CELL)[1]["source"] == cfg["source"]


@pytest.fixture(scope="module")
def reasoning(tmp_path_factory):
    bench_file = bench_rehearsal_file.write(tmp_path_factory.mktemp("rehearsal-phi4flash"))
    return rehearse(bench_file, "tiny-reasoning", 1), bench_file


def test_the_cell_resolves_dry():
    proc = run(["--workload", CELL, "--trace", "1", "--dry"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    plan = json.loads(proc.stdout.strip().splitlines()[-1])
    assert plan["family"] == "benchmark/families/phi4flash.py"
    assert set(NEW) <= set(plan["metrics"]) and plan["traffic"]["users"] == 160
    assert plan["metrics"]["shared_kv_roofline"] == "benchmark.readers.shared_kv_roofline"


def test_the_cell_rehearses_to_a_correct_line_with_its_counters_read(reasoning):
    (result, earlier), _ = reasoning
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 10
    got = result["metrics"]
    assert {"kv_state_share", "prefill_cross_share", "kv_window_share", "window_context_share",
            "attn_loop_useful_share", "prefill_rows_mean", "batch_fill.decode",
            "kv_pages_used.decode", "compiles_in_window.decode", "engine_load_s",
            "deploy_ready_s"} <= set(got)
    assert ENGINE_SERIES <= set(got)
    # no device metric from a CPU run
    assert not {"shared_kv_roofline", "window_attn_roofline", "decode_step_mfu",
                "prefill_ms.decode", "decode_step_ms.decode", "decode_step_counted_ms.decode",
                "hbm_used.decode", "device_idle.decode"} & set(got)
    # three Mamba layers' states beside two rings of 16 and one paged layer
    assert 0 < got["kv_state_share"]["value"] < 100
    # one position a row of a call through the cross-decoder, prompts of ~24
    assert 1 < got["prefill_cross_share"]["value"] < 15
    # prompts of ~24 and replies of 24-40 over a window of 16
    assert 20 < got["window_context_share"]["value"] < 90
    assert got["compiles_in_window.decode"]["value"] == 0.0
    assert any("family phi4flash" in line for line in earlier)
    gap = result["compared"]["decode_logit_gap"]
    assert gap["value"] <= gap["limit"]


def test_check_holds_the_tiny_twin_to_the_reference_through_its_family(reasoning):
    _, bench_file = reasoning
    proc = run(["--bench-file", bench_file, "--check", "phi4flash-tiny-serve",
                "--seed", "3000000019"])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
