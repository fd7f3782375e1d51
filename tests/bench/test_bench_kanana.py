"""The cell ``kanana-agent-sessions``: its configuration against the
catalog row, its arithmetic, its traffic, the metrics PR 48 brought through
their readers, and the whole command rehearsed on the CPU at the tiny twin."""

import json
import os
from types import SimpleNamespace

import bench_rehearsal_file
import pytest
from test_bench_engine_metrics import (
    ENGINE_SERIES, listed_once, on_every_list_the_other_serving_cells_share, reporting, snap,
    through_its_reader,
)
from test_bench_rehearsal import rehearse, run

from benchmark import harness, traffic
from benchmark import trace as trace_mod
from benchmark.readers import mla_roofline, moe_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# what PR 48 brought; the attention's roofline under the name of the kernel
# that replaced its loops in PR 56 (``mla_roofline`` went out in PR 62)
NEW = ["mla_paged_roofline", "mla_context_mean", "kv_latent_token_bytes",
       "prefix_token_share.decode", "prefill_ms.decode"]
CELL = "kanana-agent-sessions"


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return load("benchmark/configs/kanana-2-30b-a3b-serve.json")


def test_published_is_the_catalog_row_and_depth_alone_is_cut(cfg):
    model, published = cfg["model"], cfg["published"]
    cut = [k for k in model if model[k] != published[k]]
    assert cut == cfg["reduced"] == ["num_hidden_layers"]
    assert (published["num_hidden_layers"], model["num_hidden_layers"]) == (48, 5)
    assert all(cfg[k] == model[k] for k in model)  # the top level says what runs
    kept = {"hidden_size": 2048, "num_attention_heads": 32, "kv_lora_rank": 512,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "qk_head_dim": 192,
            "v_head_dim": 128, "intermediate_size": 6144, "moe_intermediate_size": 768,
            "n_routed_experts": 128, "n_shared_experts": 2, "num_experts_per_tok": 6,
            "routed_scaling_factor": 2.448, "vocab_size": 128256,
            "max_position_embeddings": 32768, "rope_theta": 1000000, "q_lora_rank": None}
    for key, value in kept.items():
        assert model[key] == published[key] == value, key
    assert cfg["held"]["experts"] == [0, 128] and cfg["held"]["vocab_rows"] == [0, 128256]
    assert "pipeline" in cfg["deployment"] and len(cfg["assumed"]) >= 7
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == cfg["source"])
    assert published == row["config"] and list(published) == list(row["config"])


def test_the_program_runs_the_models_sizes_and_refuses_another_models(cfg):
    from benchmark.families import deepseek_v3 as family

    assert family.program_sizes(cfg["model_id"]) == cfg["model"]
    # what the program has no switch for stands as the source says it
    for key, value in family.IMPLEMENTS.items():
        assert cfg["published"][key] == value, key
    # DeepSeek-V3 itself says otherwise on three of them: it is not claimed
    assert (family.IMPLEMENTS["q_lora_rank"], family.IMPLEMENTS["n_group"],
            family.IMPLEMENTS["rope_scaling"]) == (None, 1, None)


def test_the_cut_is_the_arithmetic_the_configuration_states(cfg):
    from benchmark.families import deepseek_v3 as family

    model = cfg["model"]
    assert family.attention_params(model) == pytest.approx(26.35e6, rel=1e-3)
    assert family.expert_params(model) == 3 * 2048 * 768
    assert family.params_count(model) == pytest.approx(3149.6e6, rel=1e-4)
    assert "3,149.6 M" in cfg["memory"]["parameters"]
    # the pool: max_batch_size x the context's pages + the scratch page
    pages = cfg["engine"]["max_batch_size"] * (model["max_position_embeddings"] // 64) + 1
    assert pages == 16385 and "16,385 pages" in cfg["memory"]["pool"]
    assert family.latent_token_bytes(model) == 1152
    assert pages * 64 * 5 * 1152 == pytest.approx(6.04e9, rel=2e-3)   # as it has to be
    assert pages * 64 * 5 * 640 * 2 == pytest.approx(6.71e9, rel=2e-3)  # as it is stored
    # K and V a head would be 17.8 times the latent row
    assert 32 * (192 + 128) * 2 / 1152 == pytest.approx(17.8, abs=0.03)
    # a step at 128 rows and a mean context of 5.2 k: 9.6 GB, 3.83 of them latent
    step = family.decode_step_bytes(model, 128, 5200)
    assert step == pytest.approx(9.6e9, rel=5e-3)
    assert family.expected_experts_hit(model, 128) == pytest.approx(127.7, abs=0.05)
    cost = family.mla_cost(model, 128 * 5200)
    assert cost["bytes"] == pytest.approx(3.83e9, rel=2e-3) == 128 * 5200 * 5 * 1152
    assert cost["flops"] == 128 * 5200 * 5 * 32 * 2 * (576 + 512)
    assert family.moe_cost(model, 128, 768) == {"bytes": 2.0 * 128 * 3 * 2048 * 768,
                                                "flops": 2.0 * 768 * 3 * 2048 * 768}


def test_agent_sessions_sizes_are_what_the_cell_says(cfg):
    tr = load("benchmark/traffic/agent-sessions.json")
    assert (tr["users"], tr["system_prompt_tokens"], tr["context_limit"], tr["think_s"]) == (
        160, 2048, 8192, 0)
    assert (tr["endpoint"], tr["stagger_depth"], tr["session_pool"], tr["pool_seed"]) == (
        "/v1/chat/completions", True, 512, 4801)
    assert 4 * cfg["engine"]["max_batch_size"] == 128 < tr["users"]  # a backlog from the start
    pool = traffic.session_pool(tr)
    assert len(pool) == 512
    turns = [len(script) for script in pool]
    assert 9 <= sorted(turns)[len(turns) // 2] <= 14  # about a dozen turns a session
    for script in pool:
        assert script[0]["prompt_tokens"] > 2048  # the shared system prompt leads every turn
        for k, turn in enumerate(script):
            assert 32 <= turn["turn_tokens"] <= 512 and 256 <= turn["reply_tokens"] <= 512
            assert turn["prompt_tokens"] + turn["reply_tokens"] <= 8192 or k == 0
    # what a turn prefills behind its prefix hit: the last reply and the new turn
    tails = [b["prompt_tokens"] - a["prompt_tokens"] // 64 * 64
             for script in pool for a, b in zip(script, script[1:])]
    assert 300 < sum(tails) / len(tails) < 700
    assert tr["warm"]["decode_k"] == list(range(1, 9))
    assert tr["warm"]["prefill_widths"] == [64, 128, 256, 512, 528, 544]


@pytest.mark.parametrize("name", ["agent-sessions", "tiny-agent"])
def test_the_new_mixes_are_reproducible_from_the_seed(name):
    tr = load(("benchmark" if name == "agent-sessions" else "tests/bench") + f"/traffic/{name}.json")
    big = 3_000_000_019
    a, b = traffic.plan(tr, big), traffic.plan(tr, big)
    assert a == b and a != traffic.plan(tr, 11)
    assert len(("<|system|>" + a["system"] + "\n").encode()) == tr["system_prompt_tokens"]
    session = a["sessions"][0]
    for k in (0, len(session["script"]) - 1):
        body = traffic.turn_request(tr, "m", a, session, k)
        assert traffic.chat_prompt_tokens(body["messages"]) == session["script"][k]["prompt_tokens"]
        assert body["messages"][0] == {"role": "system", "content": a["system"]}


COUNTED = {
    "before": snap({"rt_serve_mla_context_tokens_total": 5.0e6,
                    "rt_serve_decode_row_steps_total": 1000.0,
                    "rt_serve_prefix_tokens_reused_total": 100.0,
                    "rt_serve_prompt_tokens_total": 200.0}),
    "after": snap({"rt_serve_mla_context_tokens_total": 5.0e6 + 128 * 40 * 5200.0,
                   "rt_serve_decode_row_steps_total": 1000.0 + 128 * 40,
                   "rt_serve_prefix_tokens_reused_total": 100.0 + 9000.0,
                   "rt_serve_prompt_tokens_total": 200.0 + 10000.0}),
    "samples": [snap({"rt_serve_kv_latent_bytes": 16385 * 64 * 6400.0,
                      "rt_serve_kv_pages_total": 16384.0})] * 3,
}
TRACED = {"window_s": 4.0, "modules": {"jit_prefill_paged": 0.9, "jit_decode_multi_paged": 2.0},
          "module_calls": {"jit_prefill_paged": 30.0, "jit_decode_multi_paged": 12.0}}


@pytest.mark.parametrize("name,want", [
    ("mla_context_mean", 5200.0),
    ("kv_latent_token_bytes", 6400.0),
    ("prefix_token_share.decode", 90.0),
    ("prefill_ms.decode", 30.0),
])
def test_the_new_metrics_read_the_engines_series(name, want):
    spec, got = through_its_reader(name, {"counters": COUNTED, "trace": TRACED})
    assert got == pytest.approx(want), spec
    bench = load("BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert (entry["moves"], entry["unit"]) == ("serve_tok_s", spec["unit"])
    assert CELL in entry["workloads"] and set(entry["workloads"]) <= set(reporting(bench, "serve_tok_s"))


def a_trace(kernel=True):
    """Two decode programs and a prefill as the chip's trace names them
    since PR 56 (ledger, PR 61, ``breakdown``): the latent attention's
    kernel a layer in the decode programs, prefill's loops over rows, an
    expert layer's kernel and the K-step loop beside them; ``kernel=False``
    is the tree before PR 56, the loops whose carry opens with a running
    maximum a head in the kernel's place."""
    attend = ("paged_latent_attention bf16[128,32,640] 6in" if kernel
              else "while (s32[],f32[128,32],..) 1in")
    ops = [[attend, 1_000, 300_000],                                    # inside decode 1
           ["fusion bf16[512,64,640] 2in", 302_000, 90_000],            # beside it: not its time
           ["grouped_matmul bf16[768,768] 7in", 400_000, 100_000],      # an expert layer's kernel
           [attend, 600_000, 200_000],                                  # inside decode 1
           ["while (s32[],f32[32,512],..) 1in", 2_100_000, 900_000],    # prefill's attention
           [attend, 3_050_000, 50_000],                                 # inside the prefill
           ["while (s32[],s32[128],..) 1in", 3_950_000, 900_000],       # the K-step loop
           [attend, 4_000_000, 500_000]]                                # inside decode 2
    modules = [["jit_decode_paged_and_sample", 0, 1_000_000],
               ["jit_prefill_paged", 2_000_000, 1_500_000],
               ["jit_decode_multi_paged", 3_900_000, 1_000_000]]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops}, {"name": "XLA Modules", "events": modules}]}]}


def test_mla_paged_roofline_counts_the_kernels_calls_inside_decode_programs_only(monkeypatch, cfg):
    """(``test_mla_roofline_counts_the_attention_loops_...`` until PR 62:
    the living file, and the kernel's name in the trace.)"""
    spec = load("benchmark/metrics/mla_paged_roofline.json")
    assert spec["reader"] == "mla_roofline" and spec["args"]["ops"] == "^paged_latent_attention"
    # mla_roofline.json itself stays, listed by nothing, while a test outside the
    # benchmark's directories opens it (tests/test_paged_attention_forms.py: the old
    # pattern matches no loop of the compiled programs); its entry is out
    assert "mla_roofline" not in {m["name"] for m in load("BENCHMARK.json")["per_layer"]}
    busy, window = moe_roofline.seconds_inside(a_trace(), spec["args"]["match"], spec["args"]["ops"])
    assert busy == pytest.approx(1_000_000e-9)
    assert window == pytest.approx(4_850_000e-9 - 1_000e-9)
    # through the reader: 128 rows x 5,200 positions a step, 20 steps a traced second
    monkeypatch.setattr(trace_mod, "find_xplane", lambda d: "a.xplane.pb")
    monkeypatch.setattr(trace_mod, "load_xplane", lambda p: a_trace())
    context = 128 * 5200 * 20 * 4.0
    obs = {"model": cfg["model"], "trace_dir": "somewhere", "device": {"kind": "TPU v5 lite"},
           "trace_counters": {"before": snap({"rt_serve_mla_context_tokens_total": 7.0}),
                              "after": snap({"rt_serve_mla_context_tokens_total": 7.0 + context}),
                              "seconds": 4.0}}
    ctx = SimpleNamespace(platform="tpu", family=harness.find(
        load("BENCHMARK.json"), "families", "deepseek_v3", ".py"))
    got = mla_roofline.read(obs, spec["args"], ctx)
    # 3.83 GB a step x 20 steps a second at 819 GB/s, over the loops' share of the window
    assert got == pytest.approx(100 * (128 * 5200 * 5 * 1152 * 20 / 819e9) / (busy / window), rel=1e-3)
    assert 0 < got < 100
    # a tree whose decode programs hold the loops and no kernel (before PR 56)
    monkeypatch.setattr(trace_mod, "load_xplane", lambda p: a_trace(kernel=False))
    assert mla_roofline.read(obs, spec["args"], ctx) is None
    monkeypatch.setattr(trace_mod, "load_xplane", lambda p: a_trace())
    # a family that counts no such cost, and a program without the counter
    assert mla_roofline.read(obs, spec["args"], SimpleNamespace(platform="tpu")) is None
    still = dict(obs, trace_counters=dict(obs["trace_counters"],
                                          after=obs["trace_counters"]["before"]))
    assert mla_roofline.read(still, spec["args"], ctx) is None


def the_cell_stands_on_its_lists(bench):
    """Of any ``bench``: the real file, and the copy with a cell appended
    that ``test_bench_contract.py`` makes. By name and by membership."""
    cell, entry = listed_once(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kanana-2-30b-a3b-serve", "agent-sessions", 1)
    on = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    # what PR 48 brought, the expert layer's lists and the page loops' that
    # MiMo's cell opened, and no window's share: this family keeps no ring
    assert set(NEW) <= on and "kv_window_share" not in on
    assert {"moe_tokens_per_expert", "moe_experts_hit", "moe_load_skew", "moe_gmm_roofline",
            "attn_loop_useful_share", "prefill_rows_mean"} <= on
    # the two names that read nothing since PR 55 and PR 56 are on no list
    assert not {"moe_roofline", "mla_roofline"} & {m["name"] for m in bench["per_layer"]}
    on_every_list_the_other_serving_cells_share(bench, CELL)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == load(entry["file"])["source"]


def test_the_cell_stands_on_mimos_lists_but_the_window_share(cfg):
    bench = load("BENCHMARK.json")
    the_cell_stands_on_its_lists(bench)
    assert listed_once(bench, CELL)[1]["source"] == cfg["source"]


@pytest.fixture(scope="module")
def agent(tmp_path_factory):
    bench_file = bench_rehearsal_file.write(tmp_path_factory.mktemp("rehearsal-kanana"))
    return rehearse(bench_file, "tiny-agent", 1), bench_file


def test_the_cell_rehearses_to_a_correct_line_with_its_counters_read(agent):
    (result, earlier), _ = agent
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 10
    got = result["metrics"]
    assert {"mla_context_mean", "kv_latent_token_bytes", "prefix_token_share.decode",
            "moe_tokens_per_expert", "moe_experts_hit", "moe_load_skew",
            "batch_fill.decode", "kv_pages_used.decode", "compiles_in_window.decode",
            "engine_load_s", "deploy_ready_s"} <= set(got)
    assert ENGINE_SERIES <= set(got)
    # no device metric from a CPU run
    assert not {"mla_paged_roofline", "prefill_ms.decode", "moe_gmm_roofline", "decode_step_mfu",
                "decode_step_ms.decode", "decode_step_counted_ms.decode", "hbm_used.decode",
                "device_idle.decode"} & set(got)
    # three layers of 40 numbers, stored 128 wide, in bfloat16
    assert got["kv_latent_token_bytes"]["value"] == pytest.approx(3 * 128 * 2)
    # the shared system prompt and the session's history are prefix hits on latent pages
    assert 50 < got["prefix_token_share.decode"]["value"] < 100
    assert 128 < got["mla_context_mean"]["value"] < 400
    assert 0 < got["moe_experts_hit"]["value"] <= 100
    assert got["compiles_in_window.decode"]["value"] == 0.0
    assert any("family deepseek_v3" in line for line in earlier)
    assert result["compared"]["decode_logit_gap"]["value"] <= result["compared"]["decode_logit_gap"]["limit"]


def test_check_holds_the_tiny_twin_to_the_reference_through_its_family(agent):
    _, bench_file = agent
    proc = run(["--bench-file", bench_file, "--check", "kanana-2-tiny-serve", "--seed", "3000000019"])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["ok"] is True and (out["rows"], out["decode_steps"]) == (3, 32)
    assert 1e-4 < max(out["prefill_max_abs"], out["decode_max_abs"]) <= out["tolerance"]
    assert out["decode_judged"] > out["tokens_compared"] / 2
