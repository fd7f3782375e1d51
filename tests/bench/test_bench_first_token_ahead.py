"""``first_token_ahead_share.decode`` (PR 61): the share of first tokens that
reached their decode call from the device, before the host had fetched them.
The metric's file through its reader on counters made by hand, nothing (not
0) from a program or a decode module that never moves the counter,
``BENCHMARK.json``, which lists the entry since PR 62 (until then it stood
in ``data/first_token_ahead_entry.json``, written and not listed), held to
the contract and resolved by ``run.py``, and the tiny rehearsal's lines."""

import json
import os

import pytest
import test_bench_contract as contract
import test_bench_engine_metrics as engine_metrics

from benchmark import harness, run

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "first_token_ahead_share.decode"
ROWS = ("mimo-reason-decode", "kanana-agent-sessions", "trinity-mixed-lengths",
        "phi4flash-long-reasoning")  # the cells whose decode module takes rows
ONE_ROW = ("xl-batch-decode", "xl-chat-sessions")  # GPT-2's


def entry(b=None):
    """The entry as ``BENCHMARK.json`` lists it: once, by its name."""
    (e,) = [m for m in (b or engine_metrics.bench())["per_layer"] if m["name"] == NAME]
    return e


def through_its_reader(obs):
    b = engine_metrics.bench()
    spec = harness.load_json(harness.find(b, "metrics", NAME))
    value = harness.module(b, "readers", spec["reader"]).read(
        obs, spec.get("args", {}), engine_metrics.TPU)
    return spec, value


def counters(ahead, firsts):
    """A window in which ``firsts`` first tokens were delivered and ``ahead``
    of them (None: the program has no such counter) went to a decode call
    from the device."""
    before = {"rt_serve_ttft_s": (5.0, 40)}
    after = {"rt_serve_ttft_s": (5.0 + 0.2 * firsts, 40 + firsts)}
    if ahead is not None:
        before["rt_serve_first_tokens_ahead_total"] = 37.0
        after["rt_serve_first_tokens_ahead_total"] = 37.0 + ahead
    return {"counters": {"before": engine_metrics.snap(before),
                         "after": engine_metrics.snap(after)}}


def test_the_file_reads_the_share_of_first_tokens_that_went_ahead():
    spec, value = through_its_reader(counters(190, 200))
    assert value == pytest.approx(95.0)
    assert (spec["unit"], spec["reader"]) == ("%", "counter_ratio_moved")
    e = entry()
    assert (e["name"], e["unit"], e["better"]) == (NAME, "%", "higher")
    assert (e["source"], e["layer"], e["moves"]) == ("program_counter", "Engine", "serve_tok_s")
    # the four cells of rows: every accepted cell on serve_tok_s but GPT-2's
    b = engine_metrics.bench()
    assert sorted(e["workloads"]) == sorted(ROWS)
    assert set(engine_metrics.reporting(b, "serve_tok_s")) - set(ROWS) == {"xl-batch-decode"}
    assert not os.path.exists(os.path.join(HERE, "data", "first_token_ahead_entry.json"))


@pytest.mark.parametrize("obs", [counters(None, 200), counters(0, 200), counters(5, 0), {}],
                         ids=["no_counter", "counter_stood", "no_first_token", "no_counters"])
def test_a_program_that_sends_no_token_ahead_reads_nothing_not_zero(obs):
    """The parent has no such counter, and GPT-2's module of one row never
    moves it: the line leaves the metric out."""
    assert through_its_reader(obs)[1] is None


@pytest.mark.parametrize("rule", [
    "test_top_level_keys_and_limits", "test_names_units_and_entries",
    "test_cells_configs_and_moves_hang_together",
    "test_every_metric_traffic_and_generator_has_its_file",
])
def test_benchmark_json_with_the_entry_keeps_the_contract(rule):
    path = os.path.join(harness.ROOT, "BENCHMARK.json")
    b = harness.load_json(path)
    assert os.path.getsize(path) < 64 * 1024 and len(b["per_layer"]) <= 128
    assert entry(b)["name"] == NAME  # listed once, wherever in the list
    getattr(contract, rule)(b)


@pytest.mark.parametrize("cell", ROWS + ONE_ROW)
def test_run_resolves_it_in_the_cells_of_rows_and_in_no_other(cell, capsys):
    assert run.main(["--workload", cell, "--trace", "1", "--dry"]) == 0
    plan = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert (NAME in plan["metrics"]) == (cell in ROWS)
    if cell in ROWS:
        assert plan["metrics"][NAME] == "benchmark.readers.counter_ratio_moved"
    # an untraced run resolves no per-layer metric
    assert run.main(["--workload", cell, "--trace", "0", "--dry"]) == 0
    assert NAME not in json.loads(capsys.readouterr().out.strip().splitlines()[-1])["metrics"]


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """The rehearsal's BENCHMARK.json with the entry listed in the tiny
    twin of a cell of rows AND, to see what a module of one row reads, in
    GPT-2's tiny batch cell; both cells rehearsed with ``--trace 1``."""
    import bench_rehearsal_file
    import test_bench_rehearsal as rehearsal

    b, to = bench_rehearsal_file.build(), bench_rehearsal_file.names()["workloads"]
    listed = entry(b)  # the rehearsal's file is the real one renamed: the cells of rows
    assert to["mimo-reason-decode"] in listed["workloads"]
    listed["workloads"] = listed["workloads"] + [to["xl-batch-decode"]]
    path = tmp_path_factory.mktemp("rehearsal-ahead") / "BENCHMARK.json"
    path.write_text(json.dumps(b, indent=1))
    return {cell: rehearsal.rehearse(str(path), to[cell], 1)[0]
            for cell in ("mimo-reason-decode", "xl-batch-decode")}


def test_a_rehearsed_cell_of_rows_reads_every_first_token_ahead(rehearsed):
    """The whole way, on the CPU at mimo-v2-tiny: the engine's counter, the
    cluster's counters, the reader, the line. Every answer of the tiny mix
    is longer than one token, so every first token went ahead."""
    result = rehearsed["mimo-reason-decode"]
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"][NAME]
    # counted at the hand-over and at the landing behind it: a window's edge
    # may fall between the two of one token, of the few a 3 s window holds
    assert got["unit"] == "%" and 75.0 <= got["value"] <= 125.0
    assert result["metrics"]["compiles_in_window.decode"]["value"] == 0.0


def test_a_rehearsed_gpt2_cell_reads_nothing(rehearsed):
    result = rehearsed["xl-batch-decode"]
    assert result["correct"] is True and result["failed"] == 0
    assert NAME not in result["metrics"]
    assert "batch_fill.decode" in result["metrics"]  # the line is a traced one
