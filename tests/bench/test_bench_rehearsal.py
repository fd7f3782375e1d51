"""The whole command, rehearsed on the CPU at gpt2-tiny with a 3 s window.

Every run is a process of its own, as the driver starts them. A rehearsal
reports the CPU it ran on and leaves every device metric out; a cell of
the real BENCHMARK.json refuses to run where there is no TPU.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import bench_rehearsal_file
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
DEVICE_ONLY = {"decode_step_ms.chat", "prefill_ms", "device_idle.chat", "device_idle.train",
               "flash_fwd_roofline", "flash_bwd_roofline", "train_mfu", "hbm_used.train"}


def run(args, root=ROOT, **env_extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT, "BENCH_RUN": "set-by-the-driver"})
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )


@pytest.fixture(scope="module")
def rehearsal_file(tmp_path_factory):
    return bench_rehearsal_file.write(tmp_path_factory.mktemp("rehearsal"))


def rehearse(bench_file, cell, trace, seed=3_000_000_019):
    proc = run(["--bench-file", bench_file, "--workload", cell, "--seed", str(seed),
                "--seconds", "3", "--trace", str(trace)])
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines[:-1]


@pytest.fixture(scope="module")
def decode(rehearsal_file):
    return rehearse(rehearsal_file, "tiny-decode", 0)


@pytest.fixture(scope="module")
def chat(rehearsal_file):
    return rehearse(rehearsal_file, "tiny-chat", 1)


@pytest.fixture(scope="module")
def train(rehearsal_file):
    return rehearse(rehearsal_file, "tiny-train", 1)


def test_the_last_line_is_one_json_object_with_the_contracts_keys(decode, chat, train):
    for result, _ in (decode, chat, train):
        assert set(result) == KEYS  # no breakdown without a device trace
        assert result["correct"] is True and result["failed"] == 0
        assert result["attempted"] > 0
        assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
        assert result["device"]["platform"] == "cpu" and result["device"]["count"] == 1
        for m in result["metrics"].values():
            assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
        # what `correct` was decided from comes last, each number beside its limit
        assert list(result)[-1] == "compared" and len(result["compared"]) == 5
        for c in result["compared"].values():
            assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]


def test_untraced_runs_report_the_cells_end_to_end_metrics(decode):
    result, earlier = decode
    assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    assert result["metrics"]["serve_tok_s"]["value"] > 0
    assert result["metrics"]["setup_s"]["unit"] == "s"
    text = "\n".join(earlier)
    # the phases of set-up and the plain reading beside the estimator
    assert "set-up: deploy_ready" in text and "set-up: warm_traffic" in text
    assert "plain (tokens in window / seconds)" in text and "median of 10 slices" in text


def test_traced_serving_rehearsal_reports_per_layer_metrics_but_no_device_metric(chat):
    result, _ = chat
    got = set(result["metrics"])
    assert {"router_wait_ms", "gen_lag_ms", "batch_fill.chat", "ttft_p50_ms", "itl_p95_ms",
            "deploy_ready_s", "engine_load_s", "compiles_in_window.chat"} <= got
    assert not got & DEVICE_ONLY
    assert "busy_s" not in result["device"]
    assert result["metrics"]["compiles_in_window.chat"]["value"] == 0.0


def test_traced_training_rehearsal(train):
    result, earlier = train
    got = set(result["metrics"])
    assert {"step_p50_ms", "input_wait", "trainer_ready_s", "compiles_in_window.train"} <= got
    assert not got & DEVICE_ONLY
    assert any("plain float32 reference" in line for line in earlier)
    assert any("sync-to-sync readings" in line for line in earlier)


def test_the_four_chip_cell_rehearses_on_four_virtual_devices(rehearsal_file):
    """One worker process drives four devices over a dp mesh: batch and
    probe sharded over them, the loss still the reference's."""
    result, earlier = rehearse(rehearsal_file, "tiny-train-dp4", 0)
    assert result["correct"] is True
    assert result["device"]["count"] == 4 and result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"train_tok_s", "setup_s"}
    assert any("plain float32 reference" in line for line in earlier)


def test_a_real_cell_without_a_tpu_exits_non_zero_and_prints_no_result():
    proc = run(["--workload", "xl-batch-decode", "--seed", "1", "--seconds", "3", "--trace", "0"])
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "keeps the program off" in proc.stderr


def test_a_rehearsal_refuses_to_run_off_the_cpu(rehearsal_file):
    proc = run(["--bench-file", rehearsal_file, "--workload", "tiny-decode", "--seconds", "3"],
               JAX_PLATFORMS="tpu,cpu")
    assert proc.returncode != 0 and "{" not in proc.stdout


def test_check_holds_a_serving_configuration_to_the_reference_through_its_family(rehearsal_file):
    """``--check <configuration>``, outside any run: the family's
    ``serve_params`` and ``compare_serve``, whichever family the file names."""
    proc = run(["--bench-file", rehearsal_file, "--check", "gpt2-tiny-serve", "--seed", "5"])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["ok"] is True and out["config"] == "gpt2-tiny-serve"
    assert (out["rows"], out["prompt_lens"], out["decode_steps"]) == (2, [70, 33], 8)
    assert 1e-5 < max(out["prefill_max_abs"], out["decode_max_abs"]) <= out["tolerance"] == 0.02
    assert out["device"]["platform"] == "cpu"


def copied_tree(tmp_path):
    """BENCHMARK.json and the files under ``paths``, as a checkout holds
    them, and what each file held."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in ("benchmark", "tests/bench"):
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    held = {}
    for d, _, files in os.walk(tmp_path):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                held[os.path.relpath(os.path.join(d, f), tmp_path)] = fh.read()
    return held


def assert_unedited(tmp_path, held, but=()):
    for rel, was in held.items():
        with open(tmp_path / rel, "rb") as f:
            assert f.read() == was or rel in but, rel


def test_alone_in_a_directory_the_benchmark_exits_non_zero(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program."""
    copied_tree(tmp_path)
    proc = run(["--workload", "small-pretrain", "--seed", "1", "--seconds", "3", "--trace", "0"],
               root=str(tmp_path), JAX_PLATFORMS="", PYTHONPATH="")
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_a_new_cell_config_mix_and_metric_need_no_edit_to_an_existing_file(tmp_path):
    """Driven by data: in a copy, add one configuration file, one traffic
    file, one metric file (read by a reader that is there) and one
    ``workloads`` entry; the command resolves and rehearses them."""
    held = copied_tree(tmp_path)
    bench = bench_rehearsal_file.build()
    with open(tmp_path / "tests/bench/configs/gpt2-tiny-serve.json") as f:
        config = json.load(f)
    config["engine"]["max_batch_size"] = 3
    (tmp_path / "benchmark/configs/tiny-wide-serve.json").write_text(json.dumps(config))
    with open(tmp_path / "tests/bench/traffic/tiny-decode.json") as f:
        mix = json.load(f)
    mix.update({"users": 4, "reply_tokens": {"dist": "uniform", "lo": 4, "hi": 6}})
    (tmp_path / "benchmark/traffic/tiny-short.json").write_text(json.dumps(mix))
    (tmp_path / "benchmark/metrics/shed_share.json").write_text(json.dumps({
        "unit": "%", "reader": "counter_ratio", "args": {
            "num": [["rt_serve_shed_total", "value"]],
            "den": [["rt_serve_router_requests_total", "value"]], "scale": 100},
    }))
    bench["configs"].append({"name": "tiny-wide-serve", "source": "tests", "reduced": [],
                             "file": "benchmark/configs/tiny-wide-serve.json", "why": "new"})
    bench["workloads"].append({"name": "tiny-wide.short", "config": "tiny-wide-serve",
                               "traffic": "tiny-short", "chips": 1, "why": "new"})
    bench["per_layer"].append({
        "name": "shed_share", "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "OpenAI ingress, proxy, router", "moves": "serve_tok_s",
        "workloads": ["tiny-wide.short"]})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tok_s":
            m["workloads"].append("tiny-wide.short")
    new_bench = tmp_path / "NEW_BENCHMARK.json"
    new_bench.write_text(json.dumps(bench))

    proc = run(["--bench-file", str(new_bench), "--workload", "tiny-wide.short", "--dry",
                "--trace", "1"], root=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-2000:]
    plan = json.loads(proc.stdout.strip().splitlines()[-1])
    assert plan["config"]["engine"]["max_batch_size"] == 3
    assert plan["traffic"]["users"] == 4
    assert plan["generator"] == "benchmark.generators.serve_sessions"
    assert plan["metrics"] == {"shed_share": "benchmark.readers.counter_ratio"}

    proc = run(["--bench-file", str(new_bench), "--workload", "tiny-wide.short", "--seed", "9",
                "--seconds", "2", "--trace", "0"], root=str(tmp_path))
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] and set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    assert_unedited(tmp_path, held)  # no file that was there was touched


TOY_FAMILY = '''"""GPT-2's adapter behind the key names of another family's config.json."""
from benchmark.families import gpt2

KEYS = {"hidden_size": "n_embd", "num_hidden_layers": "n_layer",
        "num_attention_heads": "n_head", "max_position_embeddings": "n_positions",
        "vocab_size": "vocab_size"}
serve_params, warm_row_updates = gpt2.serve_params, gpt2.warm_row_updates


def program_sizes(model_id):
    ours = gpt2.program_sizes(model_id)
    return {k: ours[v] for k, v in KEYS.items()}


def context(model):
    return int(model["max_position_embeddings"])


def compare_serve(mcfg, model, params, seed, prompt_lens, steps, page_tokens=64):
    return gpt2.compare_serve(mcfg, {KEYS[k]: v for k, v in model.items()}, params, seed,
                              prompt_lens, steps, page_tokens)
'''


def test_a_second_family_with_its_cell_config_mix_and_metric_is_files_alone(tmp_path):
    """Driven by data, down to the model's family: what the builder of the
    next ``model_config`` PR does, done by hand in a copy. Added, under
    ``paths``: a family file (GPT-2's adapter behind other key names, with
    no ``decode_step_bytes``), a configuration of that family with a depth
    cut listed in ``reduced`` and its tiny twin for the rehearsal, a mix
    and its tiny twin, a metric read by a reader that is there, and a file
    that says which tiny cell rehearses the new one; in BENCHMARK.json,
    entries. No file that was there is edited. Then the contract and
    traffic tests pass in the copy with a case for the new configuration
    and one for the new cell, ``--dry`` resolves the cell through the new
    family, and the CPU rehearsal runs it to a ``correct`` line whose
    reference check went through the new family's ``compare_serve``."""
    held = copied_tree(tmp_path)

    def add(rel, doc):
        assert rel not in held, rel
        os.makedirs(os.path.dirname(tmp_path / rel), exist_ok=True)
        (tmp_path / rel).write_text(doc if isinstance(doc, str) else json.dumps(doc, indent=1))

    def of(rel):
        return json.loads(held[rel])

    add("tests/bench/families/toy.py", TOY_FAMILY)
    sizes = {"hidden_size": 768, "num_hidden_layers": 12, "num_attention_heads": 12,
             "max_position_embeddings": 1024, "vocab_size": 50257}
    real = of("benchmark/configs/gpt2-xl-serve.json")
    real.update(source="tests", family="toy", model_id="gpt2-small", model=sizes,
                published={**sizes, "num_hidden_layers": 24}, reduced=["num_hidden_layers"])
    add("benchmark/configs/toy-small-serve.json", real)
    sizes = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
             "max_position_embeddings": 128, "vocab_size": 256}
    tiny = of("tests/bench/configs/gpt2-tiny-serve.json")
    tiny.update(family="toy", model=sizes, published={**sizes, "num_hidden_layers": 4},
                reduced=["num_hidden_layers"], engine={**tiny["engine"], "max_batch_size": 3})
    add("tests/bench/configs/toy-tiny-serve.json", tiny)
    add("benchmark/traffic/toy-decode.json",
        {**of("benchmark/traffic/batch-decode.json"), "users": 28})
    add("tests/bench/traffic/tiny-toy-decode.json",
        {**of("tests/bench/traffic/tiny-decode.json"), "users": 4,
         "reply_tokens": {"dist": "uniform", "lo": 4, "hi": 6}})
    add("benchmark/metrics/shed_share.json", {
        "unit": "%", "reader": "counter_ratio", "args": {
            "num": [["rt_serve_shed_total", "value"]],
            "den": [["rt_serve_router_requests_total", "value"]], "scale": 100}})
    add("tests/bench/rehearsal-toy.json", {
        "workloads": {"toy-small.decode": "tiny-toy.decode"},
        "configs": {"toy-small-serve": "toy-tiny-serve"},
        "traffic": {"toy-decode": "tiny-toy-decode"}})
    bench = of("BENCHMARK.json")
    bench["configs"].append({
        "name": "toy-small-serve", "source": "tests", "reduced": ["num_hidden_layers"],
        "file": "benchmark/configs/toy-small-serve.json", "why": "a second family"})
    bench["workloads"].append({"name": "toy-small.decode", "config": "toy-small-serve",
                               "traffic": "toy-decode", "chips": 1, "why": "new"})
    bench["per_layer"].append({
        "name": "shed_share", "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "OpenAI ingress, proxy, router", "moves": "serve_tok_s",
        "workloads": ["toy-small.decode"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("serve_tok_s", "decode_step_mfu"):
            m["workloads"].append("toy-small.decode")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=f"{tmp_path}{os.pathsep}{ROOT}")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/bench/test_bench_contract.py",
         "tests/bench/test_bench_traffic.py", "-q", "-rA", "-p", "no:cacheprovider"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    contract, traffic_ = "tests/bench/test_bench_contract.py", "tests/bench/test_bench_traffic.py"
    for case in (
        f"{contract}::test_configurations_keep_their_published_widths[BENCHMARK.json:toy-small-serve]",
        f"{contract}::test_configurations_keep_their_published_widths[rehearsal:toy-tiny-serve]",
        f"{traffic_}::test_set_up_warms_every_prefill_width_a_turn_can_meet[toy-small.decode]",
    ):
        assert f"PASSED {case}" in proc.stdout, case

    proc = run(["--workload", "toy-small.decode", "--dry", "--trace", "1"], root=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-2000:]
    plan = json.loads(proc.stdout.strip().splitlines()[-1])
    assert plan["family"] == "tests/bench/families/toy.py"
    assert plan["config"]["model"]["num_hidden_layers"] == 12
    assert plan["traffic"]["users"] == 28
    assert plan["generator"] == "benchmark.generators.serve_sessions"
    assert plan["metrics"]["shed_share"] == "benchmark.readers.counter_ratio"
    assert plan["metrics"]["decode_step_mfu"] == "benchmark.readers.decode_step_mfu"

    spec = importlib.util.spec_from_file_location(
        "copied_rehearsal_file", tmp_path / "tests/bench/bench_rehearsal_file.py")
    copied = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copied)
    new_bench = copied.write(tmp_path / "tests")
    proc = run(["--bench-file", new_bench, "--workload", "tiny-toy.decode", "--seed", "9",
                "--seconds", "2", "--trace", "0"], root=str(tmp_path))
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] and set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    assert "plain float32 reference (family toy)" in proc.stdout
    # the page table's width came from the new family's context(): 128 / 64
    assert "'max_pages': 2" in proc.stdout
    assert_unedited(tmp_path, held, but=("BENCHMARK.json",))  # which gained entries
