"""The whole command, rehearsed on the CPU at gpt2-tiny with a 3 s window.

Every run is a process of its own, as the driver starts them. A rehearsal
reports the CPU it ran on and leaves every device metric out; a cell of
the real BENCHMARK.json refuses to run where there is no TPU.
"""

import json
import os
import shutil
import subprocess
import sys

import bench_rehearsal_file
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
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


def test_alone_in_a_directory_the_benchmark_exits_non_zero(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in ("benchmark", "tests/bench"):
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = run(["--workload", "small-pretrain", "--seed", "1", "--seconds", "3", "--trace", "0"],
               root=str(tmp_path), JAX_PLATFORMS="", PYTHONPATH="")
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_a_new_cell_config_mix_and_metric_need_no_edit_to_an_existing_file(tmp_path):
    """Driven by data: in a copy, add one configuration file, one traffic
    file, one metric file (read by a reader that is there) and one
    ``workloads`` entry; the command resolves and rehearses them."""
    for p in ("benchmark", "tests/bench"):
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = {
        os.path.relpath(os.path.join(d, f), tmp_path): os.path.getmtime(os.path.join(d, f))
        for d, _, files in os.walk(tmp_path) for f in files
    }
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
    after = {
        rel: os.path.getmtime(tmp_path / rel) for rel in before
    }
    assert after == before  # no file that was there was touched
