"""The cell ``trinity-mixed-lengths``: its configuration against the catalog
row, its arithmetic, its traffic, the metrics PR 53 brought through their
readers, and the whole command rehearsed on the CPU at the tiny twin."""

import json
import os
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
from benchmark.readers import moe_roofline, window_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["window_context_share", "window_attn_roofline"]
CELL = "trinity-mixed-lengths"
CUT = ["layer_types", "max_position_embeddings", "num_dense_layers", "num_hidden_layers"]


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return load("benchmark/configs/trinity-mini-serve.json")


def test_published_is_the_catalog_row_and_depth_alone_is_cut(cfg):
    model, published = cfg["model"], cfg["published"]
    cut = [k for k in model if model[k] != published[k]]
    assert cut == cfg["reduced"] == CUT
    assert (published["num_hidden_layers"], model["num_hidden_layers"]) == (32, 5)
    assert (published["num_dense_layers"], model["num_dense_layers"]) == (2, 1)
    assert model["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert published["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 8
    assert all(cfg[k] == model[k] for k in model)  # the top level says what runs
    kept = {"hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4,
            "head_dim": 128, "sliding_window": 2048, "intermediate_size": 6144,
            "moe_intermediate_size": 1024, "num_experts": 128, "num_experts_per_tok": 8,
            "num_shared_experts": 1, "route_scale": 2.826, "vocab_size": 200192,
            "rope_theta": 10000, "rms_norm_eps": 1e-05, "mup_enabled": True}
    for key, value in kept.items():
        assert model[key] == published[key] == value, key
    assert cfg["held"]["experts"] == [0, 128] and cfg["held"]["vocab_rows"] == [0, 200192]
    assert "pipeline" in cfg["deployment"] and len(cfg["assumed"]) >= 10
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == cfg["source"])
    assert row["name"] == "Trinity-Mini"
    assert published == row["config"] and list(published) == list(row["config"])


def test_the_program_runs_the_models_sizes_and_refuses_another_models(cfg):
    from benchmark.families import afmoe as family

    assert family.program_sizes(cfg["model_id"]) == cfg["model"]
    tiny = load("tests/bench/configs/trinity-tiny-serve.json")
    assert family.program_sizes(tiny["model_id"]) == tiny["model"]
    assert list(tiny["model"]) == list(cfg["model"])
    # what the program has no switch for stands as the source says it
    for key, value in family.IMPLEMENTS.items():
        assert cfg["published"][key] == value, key
    assert (family.IMPLEMENTS["n_group"], family.IMPLEMENTS["num_expert_groups"],
            family.IMPLEMENTS["topk_group"], family.IMPLEMENTS["rope_scaling"],
            family.IMPLEMENTS["tie_word_embeddings"], family.IMPLEMENTS["score_func"]) == (
        1, 1, 1, None, False, "sigmoid")


@pytest.mark.parametrize("model_id", ["trinity-mini", "trinity-tiny"])
def test_params_count_is_the_parameter_trees_size(model_id):
    import jax

    from benchmark.families import afmoe as family
    from ray_tpu.models import afmoe

    tree = jax.eval_shape(lambda: afmoe.load_serving_params(afmoe.CONFIGS[model_id]))
    held = sum(a.size for a in jax.tree.leaves(tree))
    assert family.params_count(family.program_sizes(model_id)) == held


def test_the_cut_is_the_arithmetic_the_configuration_states(cfg):
    from benchmark.families import afmoe as family

    model = cfg["model"]
    # q, output and gate 2048 x 4096 each, k and v 2048 x 512, two norms of 128
    assert family.attention_params(model) == 3 * 2048 * 4096 + 2 * 2048 * 512 + 256
    assert family.attention_params(model) == pytest.approx(27.26e6, rel=1e-3)
    assert family.expert_params(model) == 3 * 2048 * 1024
    assert family.params_count(model) == pytest.approx(4241.5e6, rel=1e-4)
    assert "4,241.5 M" in cfg["memory"]["parameters"]
    assert family.position_bytes(model) == 2048
    # the full layer's pool: max_batch_size x the context's pages + the scratch page
    pages = cfg["engine"]["max_batch_size"] * (model["max_position_embeddings"] // 64) + 1
    assert pages == 8193 and "8,193 pages" in cfg["memory"]["pool"]
    assert pages * 64 * 2048 == pytest.approx(1.07e9, rel=5e-3)
    rows = 4 * cfg["engine"]["max_batch_size"]
    assert rows * 2048 * 2048 * 4 == pytest.approx(2.15e9, rel=2e-3)
    # a step at 128 rows: what lies outside the routed experts, every expert
    # of four layers (all 128 are reached), and the cache the rows hold
    outside = family.params_outside_experts(model)
    assert outside == pytest.approx(610.3e6, rel=1e-3)
    assert family.expected_experts_hit(model, 128) == pytest.approx(128.0, abs=0.05)
    experts = 4 * 128 * 3 * 2048 * 1024
    for context, seen in ((1000, 1000), (5000, 2048)):
        cache = 128 * (4 * seen + context) * 2048
        assert family.decode_step_bytes(model, 128, context) == pytest.approx(
            2.0 * (outside + 128 * 2048 + experts) + cache, rel=5e-4)
    assert family.decode_step_bytes(model, 128, 2700) == pytest.approx(10.52e9, rel=2e-3)
    # past the window only the full layer grows
    assert (family.decode_step_bytes(model, 128, 5000)
            - family.decode_step_bytes(model, 128, 4000)) == 128 * 1000 * 2048
    cost = family.window_cost(model, 128 * 2048)
    assert cost == {"bytes": 128 * 2048 * 4 * 2048.0, "flops": 128 * 2048 * 4 * 32 * 2.0 * 256}
    assert family.moe_cost(model, 128, 1024) == {"bytes": 2.0 * 128 * 3 * 2048 * 1024,
                                                 "flops": 2.0 * 1024 * 3 * 2048 * 1024}


def test_mixed_lengths_sizes_are_what_the_cell_says(cfg):
    tr = load("benchmark/traffic/mixed-lengths.json")
    assert (tr["users"], tr["system_prompt_tokens"], tr["max_turns"], tr["think_s"]) == (
        160, 0, 1, 0)
    assert (tr["endpoint"], tr["context_limit"], tr["session_pool"], tr["pool_seed"]) == (
        "/v1/completions", 9216, 1024, 5301)
    assert tr["turn_tokens"] == {"dist": "lognormal", "median": 1536, "sigma": 0.9,
                                 "lo": 128, "hi": 8192}
    assert tr["reply_tokens"] == {"dist": "uniform", "lo": 512, "hi": 1024}
    assert 4 * cfg["engine"]["max_batch_size"] == 128 < tr["users"]  # a backlog from the start
    assert cfg["engine"]["max_new_tokens_cap"] >= 1024
    pool = traffic.session_pool(tr)
    assert len(pool) == 1024 and all(len(script) == 1 for script in pool)
    prompts = sorted(script[0]["prompt_tokens"] for script in pool)
    assert 1400 < prompts[len(prompts) // 2] < 1700
    assert 2000 < sum(prompts) / len(prompts) < 2400
    over = lambda n: sum(p > n for p in prompts) / len(prompts)
    assert 0.30 < over(2048) < 0.45 and 0.09 < over(4096) < 0.19 and 0.01 < over(8191) < 0.06
    assert prompts[0] >= 128 and prompts[-1] == 8192
    for script in pool:
        turn = script[0]
        assert 512 <= turn["reply_tokens"] <= 1024
        assert turn["prompt_tokens"] + turn["reply_tokens"] <= 9216
    assert tr["warm"]["decode_k"] == list(range(1, 9)) and tr["warm"]["seconds"] == 60
    assert tr["warm"]["prefill_widths"] == [64, 128, 256, 512, 528, 544]
    assert (tr["probe"], tr["trace_offset_s"], tr["trace_seconds"]) == (
        {"prompt_tokens": 40, "max_tokens": 17}, 6, 4)


@pytest.mark.parametrize("name", ["mixed-lengths", "tiny-mixed"])
def test_the_new_mixes_are_reproducible_from_the_seed(name):
    tr = load(("benchmark" if name == "mixed-lengths" else "tests/bench") + f"/traffic/{name}.json")
    big = 3_000_000_019
    a, b = traffic.plan(tr, big), traffic.plan(tr, big)
    assert a == b and a != traffic.plan(tr, 11) and a["system"] is None
    session = a["sessions"][0]
    body = traffic.turn_request(tr, "m", a, session, 0)
    assert len(body["prompt"].encode()) == session["script"][0]["prompt_tokens"]
    assert body["max_tokens"] == session["script"][0]["reply_tokens"]


COUNTED = {
    "before": snap({"rt_serve_window_context_tokens_total": 3.0e6,
                    "rt_serve_attn_context_tokens_total": 5.0e6}),
    "after": snap({"rt_serve_window_context_tokens_total": 3.0e6 + 128 * 40 * 1600.0,
                   "rt_serve_attn_context_tokens_total": 5.0e6 + 128 * 40 * 2500.0}),
    "samples": [],
}


def test_window_context_share_reads_the_engines_series():
    spec, got = through_its_reader("window_context_share", {"counters": COUNTED})
    assert got == pytest.approx(64.0), spec
    assert spec["reader"] == "counter_ratio"  # data only: no code of this PR reads it
    for name in NEW:
        entry = next(m for m in load("BENCHMARK.json")["per_layer"] if m["name"] == name)
        spec = load(f"benchmark/metrics/{name}.json")
        assert CELL in entry["workloads"]
        assert (entry["moves"], entry["unit"]) == ("serve_tok_s", "%")
        assert spec["unit"] == "%" and len(spec["reads"]) > 200


def a_trace(rings=True):
    """Two decode programs and a prefill as the chip's trace names them
    since PR 58 (ledger, PR 61, ``breakdown``): in each decode program the
    ring kernel's calls, one a window layer, beside the full layer's call of
    the same kernel under its own name, an expert layer's kernel and the
    K-step loop; ``rings=False`` is the tree before PR 58, the loops over
    ring blocks (the carry opens with the weighted sum) in the kernel's place."""
    ring = ("ring_kv_attention f32[128,32,128] 9in" if rings
            else "while (s32[],f32[32,32,1,128],..) 1in")
    pages = "paged_kv_attention f32[128,32,128] 9in"
    ops = [[ring, 1_000, 300_000],                                      # inside decode 1
           ["fusion bf16[32,512,512] 2in", 302_000, 6_000],             # beside it: not its time
           [pages, 310_000, 80_000],                                    # the full layer's call
           ["grouped_matmul bf16[1024,1024] 7in", 400_000, 100_000],    # an expert layer's kernel
           [ring, 600_000, 200_000],                                    # inside decode 1
           ["while (s32[],f32[1,32,512],..) 1in", 2_100_000, 900_000],  # prefill's attention
           [ring, 3_050_000, 50_000],                                   # inside the prefill
           ["while (s32[],s32[128],..) 1in", 3_950_000, 900_000],       # the K-step loop
           [ring, 4_000_000, 500_000]]                                  # inside decode 2
    modules = [["jit_decode_paged_and_sample", 0, 1_000_000],
               ["jit_prefill_paged", 2_000_000, 1_500_000],
               ["jit_decode_multi_paged", 3_900_000, 1_000_000]]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops}, {"name": "XLA Modules", "events": modules}]}]}


def test_window_roofline_counts_the_ring_kernels_calls_inside_decode_programs_only(
        monkeypatch, cfg):
    """(``..._counts_the_ring_loops_...`` until PR 62 pointed the file's
    ``ops`` at the kernel that replaced the loops in PR 58.)"""
    spec = load("benchmark/metrics/window_attn_roofline.json")
    assert (spec["reader"], spec["args"]["ops"]) == ("window_roofline", "^ring_kv_attention")
    assert spec["args"]["context"] == "rt_serve_window_context_tokens_total"
    assert len(spec["reads"]) > 200 and "PR 62" in spec["reads"]
    busy, window = moe_roofline.seconds_inside(a_trace(), spec["args"]["match"], spec["args"]["ops"])
    assert busy == pytest.approx(1_000_000e-9)
    assert window == pytest.approx(4_850_000e-9 - 1_000e-9)
    # through the reader: 128 rows x 1,600 window positions a step, 40 steps a traced second
    monkeypatch.setattr(trace_mod, "find_xplane", lambda d: "a.xplane.pb")
    monkeypatch.setattr(trace_mod, "load_xplane", lambda p: a_trace())
    held = 128 * 1600 * 40 * 4.0
    obs = {"model": cfg["model"], "trace_dir": "somewhere", "device": {"kind": "TPU v5 lite"},
           "trace_counters": {"before": snap({"rt_serve_window_context_tokens_total": 7.0}),
                              "after": snap({"rt_serve_window_context_tokens_total": 7.0 + held}),
                              "seconds": 4.0}}
    ctx = SimpleNamespace(platform="tpu", family=harness.find(
        load("BENCHMARK.json"), "families", "afmoe", ".py"))
    got = window_roofline.read(obs, spec["args"], ctx)
    # 2,048 B a position a window layer, four of them, at 819 GB/s, over the loops' share
    assert got == pytest.approx(
        100 * (128 * 1600 * 4 * 2048 * 40 / 819e9) / (busy / window), rel=1e-3)
    assert 0 < got < 100
    # a trace without the kernel (another family's, or the loops of a tree before PR 58)
    monkeypatch.setattr(trace_mod, "load_xplane", lambda p: a_trace(rings=False))
    assert window_roofline.read(obs, spec["args"], ctx) is None
    monkeypatch.setattr(trace_mod, "load_xplane", lambda p: a_trace())
    # a family that counts no such cost, and a program without the counter
    assert window_roofline.read(obs, spec["args"], SimpleNamespace(platform="tpu")) is None
    other = SimpleNamespace(platform="tpu", family=harness.find(
        load("BENCHMARK.json"), "families", "mimo_v2", ".py"))
    assert window_roofline.read(obs, spec["args"], other) is None
    still = dict(obs, trace_counters=dict(obs["trace_counters"],
                                          after=obs["trace_counters"]["before"]))
    assert window_roofline.read(still, spec["args"], ctx) is None


def test_the_pattern_is_the_ring_kernels_name_and_no_other_operations():
    """(``test_the_pattern_is_the_ring_loops_name_and_no_other_loops`` until
    PR 62.) The name the chip's trace gives the kernel's calls is what
    ``trace.short_op_name`` makes of their HLO line, a custom call under the
    name ``ops/cached_attention.ring_decode_attend`` gives the kernel; the
    full layer's call of the same kernel, the loops of the tree before and
    every other kernel are not matched."""
    import re

    rx = re.compile(load("benchmark/metrics/window_attn_roofline.json")["args"]["ops"])
    line = ("%ring_kv_attention.7 = f32[128,32,128]{2,1,0:T(8,128)} custom-call("
            "s32[4096]{0:T(1024)} %bitcast.11, s32[128]{0:T(128)} %clamp.3, "
            "s32[129]{0:T(256)} %concatenate.5, s32[512]{0:T(512)} %fusion.91, "
            "bf16[128,32,128]{2,1,0:T(8,128)(2,1)} %fusion.402, "
            "bf16[128,512]{1,0:T(8,128)(2,1)} %fusion.17, f32[32,512]{1,0:T(8,128)} %fusion.18, "
            "bf16[512,512,512]{2,1,0:T(8,128)(2,1)} %get-tuple-element.61, "
            "bf16[512,512,512]{2,1,0:T(8,128)(2,1)} %get-tuple-element.62), "
            "custom_call_target=\"tpu_custom_call\", operand_layout_constraints={}")
    assert trace_mod.short_op_name(line) == "ring_kv_attention f32[128,32,128] 9in"
    assert rx.search(trace_mod.short_op_name(line))
    for other in ("paged_kv_attention f32[128,32,128] 9in", "while (s32[],f32[32,32,1,128],..) 1in",
                  "while (s32[],f32[32,32,1],..) 1in", "grouped_matmul bf16[1024,1024] 7in",
                  "paged_latent_attention bf16[128,32,640] 6in", "while (s32[],s32[128],..) 1in",
                  "fusion f32[128,32,128] 3in"):
        assert not rx.search(other), other


def the_cell_stands_on_its_lists(bench):
    """Of any ``bench``: the real file, and the copy with a cell appended
    that ``test_bench_contract.py`` makes. By name and by membership."""
    cell, entry = listed_once(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-mini-serve", "mixed-lengths", 1)
    assert len(cell["why"]) <= 200
    on = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    # what PR 53 brought, the expert layer's lists (moe_load_skew through the
    # family's held_experts: this configuration's key is num_experts), the
    # page loops' and the rows of a prefill call
    assert set(NEW) <= on
    assert {"moe_tokens_per_expert", "moe_experts_hit", "moe_load_skew", "moe_gmm_roofline",
            "kv_window_share", "attn_loop_useful_share", "prefill_rows_mean",
            "prefill_ms.decode"} <= on
    # no latent cache and no prefix hits in this family; its full layer calls
    # paged_kv_attention too, and afmoe counts no shared_kv_cost for it
    assert not {"mla_paged_roofline", "mla_context_mean", "kv_latent_token_bytes",
                "prefix_token_share.decode", "shared_kv_roofline"} & on
    assert not {"moe_roofline", "mla_roofline"} & {m["name"] for m in bench["per_layer"]}
    on_every_list_the_other_serving_cells_share(bench, CELL)  # the engine's series among them
    assert entry["reduced"] == CUT and entry["source"] == load(entry["file"])["source"]


def test_the_cell_stands_on_every_list_it_reports(cfg):
    bench = load("BENCHMARK.json")
    the_cell_stands_on_its_lists(bench)
    assert listed_once(bench, CELL)[1]["source"] == cfg["source"]


@pytest.fixture(scope="module")
def mixed(tmp_path_factory):
    bench_file = bench_rehearsal_file.write(tmp_path_factory.mktemp("rehearsal-trinity"))
    return rehearse(bench_file, "tiny-mixed", 1), bench_file


def test_the_cell_rehearses_to_a_correct_line_with_its_counters_read(mixed):
    (result, earlier), _ = mixed
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 10
    got = result["metrics"]
    assert {"window_context_share", "kv_window_share", "attn_loop_useful_share",
            "moe_tokens_per_expert", "moe_experts_hit", "prefill_rows_mean",
            "batch_fill.decode", "kv_pages_used.decode", "compiles_in_window.decode",
            "engine_load_s", "deploy_ready_s"} <= set(got)
    # the fullest expert over the mean of the 16 held, by the family's held_experts
    assert 1 <= got["moe_load_skew"]["value"] <= 16
    assert ENGINE_SERIES <= set(got)
    # no device metric from a CPU run
    assert not {"window_attn_roofline", "moe_gmm_roofline", "decode_step_mfu", "prefill_ms.decode",
                "decode_step_ms.decode", "decode_step_counted_ms.decode", "hbm_used.decode",
                "device_idle.decode"} & set(got)
    # prompts of ~30 and replies of 12-20 over a window of 16: most steps are past it
    assert 20 < got["window_context_share"]["value"] < 90
    # three rings of 16 positions a row beside one paged layer
    assert 0 < got["kv_window_share"]["value"] < 100
    assert 0 < got["moe_experts_hit"]["value"] <= 100
    assert got["compiles_in_window.decode"]["value"] == 0.0
    assert any("family afmoe" in line for line in earlier)
    assert result["compared"]["decode_logit_gap"]["value"] <= result["compared"]["decode_logit_gap"]["limit"]


def test_check_holds_the_tiny_twin_to_the_reference_through_its_family(mixed):
    _, bench_file = mixed
    proc = run(["--bench-file", bench_file, "--check", "trinity-tiny-serve", "--seed", "3000000019"])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["ok"] is True and (out["rows"], out["decode_steps"]) == (3, 32)
    assert 1e-4 < max(out["prefill_max_abs"], out["decode_max_abs"]) <= out["tolerance"]
    assert out["decode_judged"] > out["tokens_compared"] / 4
