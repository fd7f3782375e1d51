"""The cell ``qwen3next-long-documents``: its configuration against the
catalog row, its arithmetic (a step's bytes with the states read and written
among it), its traffic, the lists it stands on, and the whole command
rehearsed on the CPU at the tiny twin."""

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

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "qwen3next-long-documents"
CUT = ["max_position_embeddings", "num_experts", "num_hidden_layers", "vocab_size"]
# the lists ISSUE 63 names for the cell beside those every serving cell
# shares, and those whose reader finds nothing in this family's programs.
# ``kv_state_share`` is in neither: the cell runs that layer and the issue
# lists it, but ``test_bench_phi4flash.py`` holds that metric's list whole
# (``== [CELL]``) and a PR that adds a cell edits no file the benchmark has;
# the ``benchmark`` PR that frees it appends this cell (PERF.md section 7)
JOINED = {"deploy_ready_s", "engine_load_s", "decode_step_mfu", "prefill_ms.decode",
          "prefill_rows_mean", "attn_loop_useful_share", "first_token_ahead_share.decode",
          "moe_tokens_per_expert", "moe_experts_hit", "moe_load_skew", "moe_gmm_roofline",
          "hbm_used.decode", "device_idle.decode", "batch_fill.decode",
          "kv_pages_used.decode", "decode_step_ms.decode", "compiles_in_window.decode"}
EXCLUDED = {"kv_window_share", "window_context_share", "window_attn_roofline",
            "shared_kv_roofline", "prefill_cross_share", "mla_context_mean",
            "mla_paged_roofline", "kv_latent_token_bytes", "prefix_token_share.decode"}


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return load("benchmark/configs/qwen3-next-80b-a3b-serve.json")


def test_published_is_the_catalog_row_and_four_counts_alone_are_cut(cfg):
    model, published = cfg["model"], cfg["published"]
    cut = [k for k in model if model[k] != published[k]]
    assert cut == cfg["reduced"] == CUT
    assert [(published[k], model[k]) for k in CUT] == [
        (262144, 32768), (512, 128), (48, 8), (151936, 37984)]
    assert all(cfg[k] == model[k] for k in model)  # the top level says what runs
    kept = {"hidden_size": 2048, "head_dim": 256, "num_attention_heads": 16,
            "num_key_value_heads": 2, "moe_intermediate_size": 512,
            "shared_expert_intermediate_size": 512, "num_experts_per_tok": 10,
            "linear_num_key_heads": 16, "linear_num_value_heads": 32,
            "linear_key_head_dim": 128, "linear_value_head_dim": 128,
            "linear_conv_kernel_dim": 4, "full_attention_interval": 4,
            "partial_rotary_factor": 0.25, "intermediate_size": 5120, "rms_norm_eps": 1e-06}
    for key, value in kept.items():
        assert model[key] == published[key] == value, key
    # the floors: whole periods and four layers, eight experts, an eighth of the rows
    assert model["num_hidden_layers"] % model["full_attention_interval"] == 0
    assert model["num_experts"] >= 8 and 8 * model["vocab_size"] >= published["vocab_size"]
    held = cfg["held"]
    assert (held["experts"], held["router_experts"], held["vocab_rows"]) == (
        [0, 128], 512, [0, 37984])
    assert "0-7 of 48" in held["layers"] and "four chips share each layer" in held["deployment"]
    assert "four chips share each layer" in cfg["deployment"] and len(cfg["assumed"]) >= 10
    assert any("MTP" in a or "multi-token" in a for a in cfg["assumed"])
    assert any("A_log" in a and "0.9-0.999" in a for a in cfg["assumed"])
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == cfg["source"])
    assert row["name"] == "Qwen3-Next-80B-A3B-Instruct"
    assert published == row["config"] and list(published) == list(row["config"])


def test_the_program_runs_the_models_sizes_and_refuses_another_models(cfg):
    from benchmark.families import qwen3_next as family

    assert family.program_sizes(cfg["model_id"]) == cfg["model"]
    tiny = load("tests/bench/configs/qwen3next-tiny-serve.json")
    assert family.program_sizes(tiny["model_id"]) == tiny["model"]
    assert list(tiny["model"]) == list(cfg["model"])
    # what the program has no switch for stands as the source says it
    for key, value in family.IMPLEMENTS.items():
        assert cfg["published"][key] == value, key
    assert (family.routed_over(cfg["model"]), family.routed_over(tiny["model"])) == (512, 16)
    assert family.held_experts(cfg["model"]) == 128
    with pytest.raises(KeyError):
        family.routed_over({**cfg["model"], "num_experts": 64})


def test_the_memory_and_a_steps_bytes_are_the_arithmetic_the_configuration_states(cfg):
    from benchmark.families import qwen3_next as family

    model = cfg["model"]
    assert family.mixer_params(model) == {"linear": 33_718_464, "full": 27_263_488}
    assert family.params_count(model) == 3_667_251_328
    assert "3,667,251,328" in cfg["memory"]["parameters"]
    assert family.position_bytes(model) == 2 * 2 * 256 * 2 == 2048
    assert family.state_bytes(model) == 32 * 128 * 128 * 4 + 3 * 8192 * 2 == 2_146_304
    assert "2,146,304" in cfg["memory"]["states"]
    # the two paged layers' pool: max_batch_size x the context's pages + the scratch page
    pages = cfg["engine"]["max_batch_size"] * (model["max_position_embeddings"] // 64) + 1
    assert pages == 16385 and "16,385 pages" in cfg["memory"]["pool"]
    assert 2 * pages * 64 * 2048 == pytest.approx(4.29e9, rel=5e-3)
    rows = 4 * cfg["engine"]["max_batch_size"]
    assert rows * 6 * family.state_bytes(model) == pytest.approx(1.65e9, rel=5e-3)
    # a step at 128 rows and a context of 4,000, by hand, every held expert hit:
    # the weights outside the experts and the rows' embedding vectors, 8 x 128
    # experts, six states read AND written, two layers' 4,000 positions
    outside = (3_667_251_328 - 37_984 * 2048 - 8 * 128 * 3 * 2048 * 512)
    assert family.params_outside_experts(model) == outside
    by_hand = (2.0 * (outside + 128 * 2048) + 2.0 * 8 * 128 * 3 * 2048 * 512
               + 128 * (2 * 6 * 2_146_304 + 2 * 4000 * 2048))
    assert family.decode_step_bytes(model, 128, 4000, experts_hit=128) == pytest.approx(by_hand)
    assert by_hand == pytest.approx(12.6e9, rel=2e-2)
    # under even routing 128 rows reach 92.0% of the held experts a layer
    assert family.expected_experts_hit(model, 128) == pytest.approx(
        128 * (1 - (1 - 10 / 512) ** 128)) == pytest.approx(128 * 0.920, rel=1e-3)
    assert (family.decode_step_bytes(model, 128, 4000)
            < family.decode_step_bytes(model, 128, 4000, experts_hit=128))
    # the states do not grow with the context, the pages do
    assert (family.decode_step_bytes(model, 128, 5000)
            - family.decode_step_bytes(model, 128, 4000)) == 128 * 1000 * 2 * 2048
    assert (family.decode_step_bytes(model, 128, 4000)
            - family.decode_step_bytes(model, 64, 4000, experts_hit=family.expected_experts_hit(
                model, 128))) == pytest.approx(
        64 * (2.0 * 2048 + 2 * 6 * 2_146_304 + 2 * 4000 * 2048))
    assert "written" in family.decode_step_bytes.__doc__
    assert family.moe_cost(model, 100.0, 1000.0) == {
        "bytes": 2.0 * 100 * 3 * 2048 * 512, "flops": 2.0 * 1000 * 3 * 2048 * 512}


def test_the_check_holds_the_states_through_the_one_tolerance_the_harness_reads(cfg):
    """The family folds layer 0's state gap into the two numbers
    ``serve_sessions._check`` holds to ``logit_tolerance``, so the file's
    tolerance is the family's, the check decodes far enough for a state stored
    in bfloat16 to part from the sound runs, and the limit lies between the
    two readings ``check.why`` gives."""
    from benchmark.families import qwen3_next as family

    check = cfg["check"]
    assert check["logit_tolerance"] == family.LOGIT_LIMIT == 0.35
    assert (check["prompt_lens"], check["decode_steps"]) == ([1300, 600, 100], 128)
    assert 0.0043 * 1.5 < family.STATE_LIMIT < 0.0150 / 1.5
    for said in ("0.0039-0.0043", "0.0150-0.0161", "HOW MANY STEPS IT TAKES", "Layer 0"):
        assert said in check["why"], said
    tiny = load("tests/bench/configs/qwen3next-tiny-serve.json")["check"]
    assert "STATE_LIMIT" in tiny["why"]


def test_long_documents_sizes_are_what_the_cell_says(cfg):
    tr = load("benchmark/traffic/long-documents.json")
    assert (tr["users"], tr["system_prompt_tokens"], tr["max_turns"], tr["think_s"]) == (
        160, 0, 1, 0)
    assert (tr["endpoint"], tr["context_limit"], tr["session_pool"], tr["order"]) == (
        "/v1/completions", 16896, 512, "fixed")
    assert tr["turn_tokens"] == {"dist": "lognormal", "median": 3072, "sigma": 0.8,
                                 "lo": 512, "hi": 16384}
    assert tr["reply_tokens"] == {"dist": "uniform", "lo": 256, "hi": 512}
    others = {load(f"benchmark/traffic/{n}")["pool_seed"]
              for n in os.listdir(os.path.join(ROOT, "benchmark/traffic"))
              if n != "long-documents.json" and "pool_seed" in load(f"benchmark/traffic/{n}")}
    assert tr["pool_seed"] not in others  # a pool of its own
    assert 4 * cfg["engine"]["max_batch_size"] == 128 < tr["users"]  # a backlog from the start
    assert cfg["engine"]["max_new_tokens_cap"] >= 512
    assert cfg["model"]["max_position_embeddings"] >= tr["context_limit"]
    pool = traffic.session_pool(tr)
    assert len(pool) == 512 and all(len(script) == 1 for script in pool)
    prompts = sorted(script[0]["prompt_tokens"] for script in pool)
    assert 2700 < prompts[len(prompts) // 2] < 3500
    assert 3600 < sum(prompts) / len(prompts) < 4700
    assert prompts[0] >= 512 and prompts[-1] == 16384
    # an even draw: the cell is bound by prefill, its rate follows the mean prompt of
    # the ~230 sessions a window works through, and a window that lies on a slope of
    # that mean spreads the runs (PERF.md section 6, PR 63). In the users' order
    # (the pool's, round and round) no 230 consecutive sessions lie 5% from the pool's
    # mean (2.7% where the window lies; the first hand-in's pool 6301 read 10.8% there)
    order = [script[0]["prompt_tokens"] for script in pool] * 2
    mean = sum(order) / len(order)
    windows = [sum(order[i:i + 230]) / 230 for i in range(512)]
    assert max(abs(w / mean - 1) for w in windows) < 0.05
    for script in pool:
        turn = script[0]
        assert 256 <= turn["reply_tokens"] <= 512
        assert turn["prompt_tokens"] + turn["reply_tokens"] <= 16896
    warm = load("benchmark/traffic/long-reasoning.json")["warm"]
    assert tr["warm"] == warm  # warm-up as the sibling cell's
    assert (tr["probe"], tr["trace_offset_s"], tr["trace_seconds"]) == (
        {"prompt_tokens": 40, "max_tokens": 17}, 6, 4)


@pytest.mark.parametrize("name", ["long-documents", "tiny-documents"])
def test_the_new_mixes_are_reproducible_from_the_seed(name):
    tr = load(("benchmark" if name == "long-documents" else "tests/bench")
              + f"/traffic/{name}.json")
    big = 3_000_000_019
    a, b = traffic.plan(tr, big), traffic.plan(tr, big)
    assert a == b and a != traffic.plan(tr, 11) and a["system"] is None
    session = a["sessions"][0]
    body = traffic.turn_request(tr, "m", a, session, 0)
    assert len(body["prompt"].encode()) == session["script"][0]["prompt_tokens"]
    assert body["max_tokens"] == session["script"][0]["reply_tokens"]


def test_the_states_share_of_the_cache_reads_through_the_accepted_reader():
    """``kv_state_share``'s reader on this engine's gauges: 128 rows x six
    layers' states beside two layers' pages."""
    gauges = snap({"rt_serve_kv_state_bytes": 128 * 6 * 2_146_304.0,
                   "rt_serve_kv_window_bytes": 0.0, "rt_serve_kv_full_bytes": 4.29e9})
    _, got = through_its_reader("kv_state_share", {"counters": {"samples": [gauges, gauges]}})
    assert got == pytest.approx(100 * 1.648e9 / (1.648e9 + 4.29e9), rel=1e-3)


def the_cell_stands_on_its_lists(bench):
    """Of any ``bench``: the real file, and the copy with a cell appended
    that ``test_bench_contract.py`` makes. The cell is in ``workloads``
    once and its configuration in ``configs`` once, wherever: by name and
    by membership, never by place."""
    cell, entry = listed_once(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b-a3b-serve", "long-documents", 1)
    assert len(cell["why"]) <= 200 and "4x" in cell["why"]
    on = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert JOINED <= on and not EXCLUDED & on
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")["workloads"]
    on_every_list_the_other_serving_cells_share(bench, CELL)  # the engine's series among them
    assert entry["reduced"] == CUT and entry["source"] == load(entry["file"])["source"]


def test_the_cell_stands_on_every_list_it_reports_and_on_none_it_is_kept_off(cfg):
    bench = load("BENCHMARK.json")
    the_cell_stands_on_its_lists(bench)
    assert listed_once(bench, CELL)[1]["source"] == cfg["source"]


@pytest.fixture(scope="module")
def documents(tmp_path_factory):
    bench_file = bench_rehearsal_file.write(tmp_path_factory.mktemp("rehearsal-qwen3next"))
    return rehearse(bench_file, "tiny-documents", 1), bench_file


def test_the_cell_resolves_dry():
    proc = run(["--workload", CELL, "--trace", "1", "--dry"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    plan = json.loads(proc.stdout.strip().splitlines()[-1])
    assert plan["family"] == "benchmark/families/qwen3_next.py"
    assert JOINED <= set(plan["metrics"]) and plan["traffic"]["users"] == 160


def test_the_cell_rehearses_to_a_correct_line_with_its_counters_read(documents):
    (result, earlier), _ = documents
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 10
    got = result["metrics"]
    assert {"attn_loop_useful_share", "prefill_rows_mean", "moe_tokens_per_expert",
            "moe_experts_hit", "moe_load_skew", "first_token_ahead_share.decode",
            "batch_fill.decode", "kv_pages_used.decode", "compiles_in_window.decode",
            "engine_load_s", "deploy_ready_s"} <= set(got)
    assert ENGINE_SERIES <= set(got)
    # no device metric from a CPU run
    assert not {"moe_gmm_roofline", "decode_step_mfu", "prefill_ms.decode",
                "decode_step_ms.decode", "decode_step_counted_ms.decode", "hbm_used.decode",
                "device_idle.decode"} & set(got)
    assert 0 < got["moe_experts_hit"]["value"] <= 100
    assert got["compiles_in_window.decode"]["value"] == 0.0
    assert any("family qwen3_next" in line for line in earlier)
    gap = result["compared"]["decode_logit_gap"]
    assert gap["value"] <= gap["limit"]


def test_check_holds_the_tiny_twin_to_the_reference_through_its_family(documents):
    _, bench_file = documents
    proc = run(["--bench-file", bench_file, "--check", "qwen3next-tiny-serve",
                "--seed", "3000000019"])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
