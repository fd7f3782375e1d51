"""The cell ``mimo-reason-decode``: its configuration against the source's
widths, its traffic, the metrics PR 46 brought through their readers, and
the whole command rehearsed on the CPU at the tiny twin."""

import json
import os

import bench_rehearsal_file
import pytest
from test_bench_engine_metrics import (
    ENGINE_SERIES, listed_once, on_every_list_the_other_serving_cells_share, snap,
    through_its_reader,
)
from test_bench_rehearsal import rehearse, run

from benchmark import traffic
from benchmark.readers import moe_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# what PR 46 brought; the expert layers' roofline under the name of the kernel
# that replaced ragged_dot in PR 55 (``moe_roofline`` went out in PR 62)
NEW = ["moe_tokens_per_expert", "moe_experts_hit", "moe_load_skew", "kv_window_share",
       "moe_gmm_roofline"]
CELL = "mimo-reason-decode"


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_width_and_cuts_six_keys():
    cfg = load("benchmark/configs/mimo-v2.5-serve.json")
    model, published = cfg["model"], cfg["published"]
    kept = {"hidden_size": 4096, "num_attention_heads": 64, "head_dim": 192, "v_head_dim": 128,
            "swa_head_dim": 192, "swa_v_head_dim": 128, "num_key_value_heads": 4,
            "swa_num_key_value_heads": 8, "sliding_window": 128, "intermediate_size": 16384,
            "moe_intermediate_size": 2048, "num_experts_per_tok": 8,
            "partial_rotary_factor": 0.334, "rope_theta": 10000000, "swa_rope_theta": 10000,
            "attention_value_scale": 0.707}
    for key, value in kept.items():
        assert model[key] == published[key] == cfg[key] == value, key
    assert cfg["reduced"] == ["hybrid_layer_pattern", "max_position_embeddings",
                              "moe_layer_freq", "n_routed_experts", "num_hidden_layers",
                              "vocab_size"]
    assert (published["n_routed_experts"], model["n_routed_experts"]) == (256, 16)
    # the source's keys and no other: what the router scores stands under ``held``
    assert "router_experts" not in published and "router_experts" not in model
    assert cfg["held"]["router_experts"] == published["n_routed_experts"]
    assert (published["vocab_size"], model["vocab_size"]) == (152576, 152576 // 8)
    # the leading dense layer and one whole period, kinds in their published ratio
    assert model["hybrid_layer_pattern"] == published["hybrid_layer_pattern"][:1] + \
        published["hybrid_layer_pattern"][6:12]
    assert model["moe_layer_freq"] == published["moe_layer_freq"][:7]
    assert "16 chips" in cfg["deployment"] and len(cfg["assumed"]) >= 6


def test_the_cut_is_the_arithmetic_the_configuration_states():
    from benchmark.families import mimo_v2 as family

    model = load("benchmark/configs/mimo-v2.5-serve.json")["model"]
    assert family.expert_params(model) == 3 * 4096 * 2048 and family.routed_over(model) == 256
    assert family.params_count(model) == pytest.approx(3.430e9, rel=2e-3)
    # a step at 128 rows and ~640 positions: ~7.4 GB, of which the experts ~4.7
    step = family.decode_step_bytes(model, 128, 640)
    experts = 2.0 * 6 * family.expected_experts_hit(model, 128) * family.expert_params(model)
    assert family.expected_experts_hit(model, 128) == pytest.approx(15.72, abs=0.01)
    assert 7.0e9 < step < 7.8e9 and 0.6 < experts / step < 0.68
    # never more than the step must read: one row reaches at most 8 experts a layer
    assert family.decode_step_bytes(model, 1, 64) < 2.0 * (
        family.params_outside_experts(model) + 6 * 8 * family.expert_params(model)) + 1e6
    cost = family.moe_cost(model, experts_hit=16, assignments=64)
    assert cost == {"bytes": 2.0 * 16 * 3 * 4096 * 2048, "flops": 2.0 * 64 * 3 * 4096 * 2048}


def test_reason_decode_sizes_are_what_the_cell_says():
    tr = load("benchmark/traffic/reason-decode.json")
    assert (tr["users"], tr["max_turns"], tr["system_prompt_tokens"], tr["think_s"]) == (192, 1, 0, 0)
    pool = traffic.session_pool(tr)
    assert len(pool) == 1024 and all(len(script) == 1 for script in pool)
    prompts = sorted(s[0]["prompt_tokens"] for s in pool)
    replies = [s[0]["reply_tokens"] for s in pool]
    assert prompts[0] >= 64 and prompts[-1] <= 1024 and 220 < prompts[len(prompts) // 2] < 300
    assert min(replies) >= 512 and max(replies) <= 768
    assert all(s[0]["prompt_tokens"] + s[0]["reply_tokens"] <= tr["context_limit"] for s in pool)
    assert tr["warm"]["decode_k"] == list(range(1, 9))
    # longer than a chunk of 512: some prompts are prefilled in two calls
    assert sum(p > 512 for p in prompts) > 50


@pytest.mark.parametrize("name", ["reason-decode", "tiny-reason"])
def test_the_new_mixes_are_reproducible_from_the_seed(name):
    tr = load(("benchmark" if name == "reason-decode" else "tests/bench") + f"/traffic/{name}.json")
    big = 3_000_000_019
    a, b = traffic.plan(tr, big), traffic.plan(tr, big)
    assert a == b and a != traffic.plan(tr, 11)
    body = traffic.turn_request(tr, "m", a, a["sessions"][0], 0)
    assert len(body["prompt"].encode()) == a["sessions"][0]["script"][0]["prompt_tokens"]


COUNTED = {
    "before": snap({"rt_serve_moe_assignments_total": 1000.0,
                    "rt_serve_moe_expert_steps_total": 960.0,
                    "rt_serve_moe_experts_hit_total": 900.0,
                    "rt_serve_moe_max_load_total": 500.0}),
    "after": snap({"rt_serve_moe_assignments_total": 1000.0 + 38400.0,
                   "rt_serve_moe_expert_steps_total": 960.0 + 9600.0,
                   "rt_serve_moe_experts_hit_total": 900.0 + 9408.0,
                   "rt_serve_moe_max_load_total": 500.0 + 4800.0}),
    "samples": [snap({"rt_serve_kv_window_bytes": 400.0, "rt_serve_kv_full_bytes": 600.0})] * 3,
}


@pytest.mark.parametrize("name,want", [
    ("moe_tokens_per_expert", 4.0),       # 38,400 pairs over 9,600 expert-steps
    ("moe_experts_hit", 98.0),
    ("moe_load_skew", 4800.0 * 16 / 38400.0),  # 600 layer-steps: fullest 8, mean 4
    ("kv_window_share", 40.0),
])
def test_the_new_metrics_read_the_engines_series(name, want):
    spec, got = through_its_reader(name, {"counters": COUNTED, "model": {"n_routed_experts": 16}})
    assert got == pytest.approx(want), spec


def a_trace(kernel=True):
    """Two decode programs and a prefill as the chip's trace names them
    since PR 55 (ledger, PR 61, ``breakdown``): the grouped-matmul kernel's
    calls, two an expert layer; ``kernel=False`` is the tree before PR 55,
    ``jax.lax.ragged_dot``'s grouped products in their place."""
    up, down = (("grouped_matmul f32[1024,4096] 6in", "grouped_matmul bf16[1024,2048] 7in")
                if kernel else ("ragged-dot-none bf16[128,4096] 7in",
                                "ragged-dot-none bf16[128,2048] 7in"))
    ops = [[up, 1_000, 300_000],                                        # inside decode 1
           ["fusion bf16[128,4096] 2in", 400_000, 100_000],
           [down, 600_000, 200_000],                                    # inside decode 1
           [up, 2_100_000, 900_000],                                    # inside the prefill
           ["paged_kv_attention f32[128,64,128] 9in", 3_950_000, 40_000],  # a full layer's
           [down, 4_000_000, 500_000]]                                  # inside decode 2
    modules = [["jit_decode_paged_and_sample", 0, 1_000_000],
               ["jit_prefill_paged", 2_000_000, 1_500_000],
               ["jit_decode_multi_paged", 3_900_000, 1_000_000]]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops}, {"name": "XLA Modules", "events": modules}]}]}


def test_moe_gmm_roofline_counts_the_kernels_calls_inside_decode_programs_only(monkeypatch):
    """(``test_moe_roofline_counts_the_grouped_products_...`` until PR 62:
    the living file, and the kernel's name in the trace.) By hand: only
    the calls inside the decode programs' events are summed, and the share
    is the family's ``moe_cost`` of the experts hit and the pairs over it."""
    from types import SimpleNamespace

    from benchmark import harness, peaks
    from benchmark import trace as trace_mod

    spec = load("benchmark/metrics/moe_gmm_roofline.json")
    assert spec["reader"] == "moe_roofline" and spec["args"]["ops"] == "^grouped_matmul"
    assert not os.path.exists(os.path.join(ROOT, "benchmark/metrics/moe_roofline.json"))
    busy, window = moe_roofline.seconds_inside(a_trace(), spec["args"]["match"], spec["args"]["ops"])
    assert busy == pytest.approx(1_000_000e-9)
    assert window == pytest.approx(4_500_000e-9 - 1_000e-9)
    assert moe_roofline.seconds_inside({"planes": []}, "x", "y") == (None, None)
    # through the reader: six expert layers x 15.3 of 16 experts hit and 128 rows x
    # 8 pairs a step, 40 steps a traced second
    monkeypatch.setattr(trace_mod, "find_xplane", lambda d: "a.xplane.pb")
    monkeypatch.setattr(trace_mod, "load_xplane", lambda p: a_trace())
    model = load("benchmark/configs/mimo-v2.5-serve.json")["model"]
    hit, pairs = 6 * 15.3 * 40 * 4.0, 6 * 128 * 8 * 40 * 4.0
    obs = {"model": model, "trace_dir": "somewhere", "device": {"kind": "TPU v5 lite"},
           "trace_counters": {
               "before": snap({"rt_serve_moe_experts_hit_total": 5.0,
                               "rt_serve_moe_assignments_total": 9.0}),
               "after": snap({"rt_serve_moe_experts_hit_total": 5.0 + hit,
                              "rt_serve_moe_assignments_total": 9.0 + pairs}),
               "seconds": 4.0}}
    ctx = SimpleNamespace(platform="tpu", family=harness.find(
        load("BENCHMARK.json"), "families", "mimo_v2", ".py"))
    got = moe_roofline.read(obs, spec["args"], ctx)
    one_expert = 2.0 * 3 * model["hidden_size"] * model["moe_intermediate_size"]
    assert got == pytest.approx(
        100 * (6 * 15.3 * 40 * one_expert / peaks.peak("TPU v5 lite")["hbm_bytes_per_s"])
        / (busy / window), rel=1e-3)
    assert 0 < got
    # a tree whose expert layers hold ragged_dot and no kernel (before PR 55), a
    # family that counts no such cost, a program without the counters
    monkeypatch.setattr(trace_mod, "load_xplane", lambda p: a_trace(kernel=False))
    assert moe_roofline.read(obs, spec["args"], ctx) is None
    monkeypatch.setattr(trace_mod, "load_xplane", lambda p: a_trace())
    assert moe_roofline.read(obs, spec["args"], SimpleNamespace(platform="tpu")) is None
    still = dict(obs, trace_counters=dict(obs["trace_counters"],
                                          after=obs["trace_counters"]["before"]))
    assert moe_roofline.read(still, spec["args"], ctx) is None


def the_cell_stands_on_its_lists(bench):
    """Of any ``bench``: the real file, and the copy with a cell appended
    that ``test_bench_contract.py`` makes. By name and by membership."""
    cell, entry = listed_once(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mimo-v2.5-serve", "reason-decode", 1)
    on = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert set(NEW) <= on
    assert {"prefill_ms.decode", "attn_loop_useful_share", "prefill_rows_mean",
            "first_token_ahead_share.decode"} <= on
    # no latent cache, no prefix hits, no window's cost counted (window_attend's loop
    # stays in its window layers) and no layer whose pages several layers read
    assert not {"mla_paged_roofline", "mla_context_mean", "kv_latent_token_bytes",
                "prefix_token_share.decode", "window_attn_roofline", "window_context_share",
                "shared_kv_roofline", "kv_state_share"} & on
    assert "moe_roofline" not in {m["name"] for m in bench["per_layer"]}
    on_every_list_the_other_serving_cells_share(bench, CELL)
    assert entry["reduced"] == ["hybrid_layer_pattern", "max_position_embeddings",
                                "moe_layer_freq", "n_routed_experts", "num_hidden_layers",
                                "vocab_size"]
    assert entry["source"] == load(entry["file"])["source"]


def test_the_cell_stands_on_every_list_it_reports_and_on_none_it_is_kept_off():
    the_cell_stands_on_its_lists(load("BENCHMARK.json"))


@pytest.fixture(scope="module")
def reason(tmp_path_factory):
    bench_file = bench_rehearsal_file.write(tmp_path_factory.mktemp("rehearsal-mimo"))
    return rehearse(bench_file, "tiny-reason", 1), bench_file


def test_the_cell_rehearses_to_a_correct_line_with_its_counters_read(reason):
    (result, earlier), _ = reason
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 10
    got = result["metrics"]
    assert {"moe_tokens_per_expert", "moe_experts_hit", "moe_load_skew", "kv_window_share",
            "batch_fill.decode", "kv_pages_used.decode", "compiles_in_window.decode",
            "engine_load_s", "deploy_ready_s"} <= set(got)
    assert ENGINE_SERIES <= set(got)
    # no device metric from a CPU run
    assert not {"moe_gmm_roofline", "decode_step_mfu", "decode_step_ms.decode", "prefill_ms.decode",
                "decode_step_counted_ms.decode", "hbm_used.decode", "device_idle.decode"} & set(got)
    assert 0 < got["moe_experts_hit"]["value"] <= 100
    assert got["moe_tokens_per_expert"]["value"] > 0 and got["moe_load_skew"]["value"] >= 1
    assert 0 < got["kv_window_share"]["value"] < 100
    assert got["compiles_in_window.decode"]["value"] == 0.0
    assert any("family mimo_v2" in line for line in earlier)
    assert result["compared"]["decode_logit_gap"]["value"] <= 0.1


def test_check_holds_the_tiny_twin_to_the_reference_through_its_family(reason):
    _, bench_file = reason
    proc = run(["--bench-file", bench_file, "--check", "mimo-v2-tiny-serve", "--seed", "3000000019"])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["ok"] is True and (out["rows"], out["decode_steps"]) == (3, 32)
    assert 1e-4 < max(out["prefill_max_abs"], out["decode_max_abs"]) <= out["tolerance"] == 0.1
    assert 0 <= out["tokens_tied"] < out["tokens_compared"] / 2


@pytest.mark.parametrize("tied_draws, draws, prefill_judged", [(1, 2, True), (2, 2, False)],
                         ids=["drawn-again", "no-draw-left"])
def test_a_phase_whose_every_token_is_tied_is_never_judged_on_the_tied_ones(
        monkeypatch, tied_draws, draws, prefill_judged):
    """Seed 2060117546 of the driver's check tied all four prefill tokens
    at the published widths, the family judged all four, and one had
    rightly been ranked the other way: 0.722 against 0.15 on a sound
    program. Sequences that leave a phase nothing to judge are drawn
    again, by the reference's margins alone; a phase that ``DRAWS`` draws
    leave nothing reads 0 and says so. Here the reference's margins at
    the prefill calls' last positions read as ties in the first draws, and
    the program is off by far at every tied token."""
    import dataclasses

    import jax.numpy as jnp

    from benchmark.families import mimo_v2 as family
    from benchmark.reference import mimo_v2_ref
    from ray_tpu.models import mimo_v2

    cfg = dataclasses.replace(mimo_v2.CONFIGS["mimo-v2-tiny"], dtype=jnp.float32)
    params, model = mimo_v2.load_serving_params(cfg), family.program_sizes("mimo-v2-tiny")
    lens, steps, chunk = [40, 21], 3, 32
    ends = {40 + steps: [31, 39], 21 + steps: [20]}
    forward, calls = mimo_v2_ref.forward, []

    def tied_at_first(params, tokens, model, **kw):
        logits, margin = forward(params, tokens, model, **kw)
        calls.append(len(tokens))
        if len(calls) <= tied_draws * len(lens):
            at = jnp.asarray(ends[len(tokens)])
            margin = margin.at[at].set(0.0)
            logits = logits.at[at].add(5.0)  # a tied token read far off
        return logits, margin

    monkeypatch.setattr(mimo_v2_ref, "forward", tied_at_first)
    monkeypatch.setattr(family, "DRAWS", 2)
    out = family.compare_serve(cfg, model, params, 11, prompt_lens=lens, steps=steps,
                               page_tokens=16, chunk=chunk)
    assert out["draws"] == draws and len(calls) == draws * len(lens)
    assert out["decode_judged"] > 0 and 0 < out["decode_max_abs"] < 2e-3
    if prefill_judged:
        assert out["prefill_judged"] > 0 and 0 < out["prefill_max_abs"] < 2e-3
        assert out["tied_worst"] < 2e-3
    else:
        assert (out["prefill_judged"], out["prefill_max_abs"]) == (0, 0.0)
        assert out["tokens_tied"] >= 3 and out["tied_worst"] > 4.0
