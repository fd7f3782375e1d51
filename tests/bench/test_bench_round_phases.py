"""The metrics that read the engine's round by phase and the seconds it
knew the device dry (PR 59): each of the sixteen files through its reader
on counters made by hand, and ``BENCHMARK.json``, which lists them since
PR 62 (until then they stood in ``data/round_phases_entries.json``, written
and not listed), held to the contract and resolved by ``run.py``. (Nothing
from a program without the series: one case a listed metric in
``test_bench_readers.py``.)"""

import json
import os
from types import SimpleNamespace

import pytest
import test_bench_contract as contract
import test_bench_engine_metrics as engine_metrics

from benchmark import harness, run
from benchmark.tools import round_phases

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TPU = SimpleNamespace(platform="tpu")
HOST = ("admit", "prefill", "dispatch", "harvest", "other")
SERVING = ("xl-batch-decode", "xl-chat-sessions", "mimo-reason-decode", "kanana-agent-sessions",
           "trinity-mixed-lengths", "phi4flash-long-reasoning")

# 400 rounds that worked. Seconds in each phase over the window, and of the
# host's own phases the seconds the device was known dry; then the same
# for 50 rounds of the traced seconds
ROUNDS, TRACED_ROUNDS = 400, 50
PHASE_S = {"admit": 0.4, "prefill": 0.8, "first_token_sync": 8.0, "dispatch": 1.2,
           "harvest_sync": 4.0, "harvest": 1.6, "other": 0.2}  # 16.2 s
DRY_S = {"admit": 0.04, "prefill": 0.08, "dispatch": 0.6, "harvest": 0.0, "other": 0.1}  # 0.82 s
TRACED_PHASE_S = {p: s / 8.1 for p, s in PHASE_S.items()}  # 2 s
TRACED_DRY_S = {"admit": 0.0, "prefill": 0.0125, "dispatch": 0.2, "harvest": 0.0,
                "other": 0.0875}  # 0.3 s
EXPECTED = {
    "round_admit_ms.decode": 1.0, "round_prefill_ms.decode": 2.0,
    "round_dispatch_ms.decode": 3.0, "round_harvest_ms.decode": 4.0,
    "round_other_ms.decode": 0.5, "round_first_sync_ms.decode": 20.0,
    "round_harvest_sync_ms.decode": 10.0,
    "dry_admit_ms.decode": 0.1, "dry_prefill_ms.decode": 0.2, "dry_dispatch_ms.decode": 1.5,
    "dry_harvest_ms.decode": 0.0, "dry_other_ms.decode": 0.25,
    "device_dry.decode": 100 * 0.82 / 16.2, "device_dry.chat": 100 * 0.82 / 16.2,
    "device_dry_traced.decode": 15.0, "device_dry_traced.chat": 15.0,
}


def series(phase_s, dry_s, rounds, base):
    """The account's series before and after: ``base`` seconds and rounds
    were on every one of them when the stretch began."""
    before, after = {}, {}
    for name, s in [*((f"rt_serve_engine_{p}_s", s) for p, s in phase_s.items()),
                    *((f"rt_serve_engine_dry_{p}_s", s) for p, s in dry_s.items())]:
        before[name] = (base, 100)
        after[name] = (base + s, 100 + rounds)
    return {"before": engine_metrics.snap(before), "after": engine_metrics.snap(after)}


def observations():
    return {"counters": series(PHASE_S, DRY_S, ROUNDS, 3.0),
            "trace_counters": {**series(TRACED_PHASE_S, TRACED_DRY_S, TRACED_ROUNDS, 5.0),
                               "seconds": 4.5}}


def entries(b=None):
    """The sixteen entries as ``BENCHMARK.json`` lists them, by name."""
    return [m for m in (b or engine_metrics.bench())["per_layer"] if m["name"] in EXPECTED]


def through_its_reader(name, obs):
    b = engine_metrics.bench()
    spec = harness.load_json(harness.find(b, "metrics", name))
    return spec, harness.module(b, "readers", spec["reader"]).read(obs, spec.get("args", {}), TPU)


def test_benchmark_json_lists_the_sixteen_once_each_and_the_data_file_is_gone():
    """(``test_the_data_file_holds_the_sixteen_and_no_other`` until PR 62.)"""
    assert sorted(e["name"] for e in entries()) == sorted(EXPECTED)
    assert not os.path.exists(os.path.join(HERE, "data", "round_phases_entries.json"))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_file_reads_the_accounts_series(name):
    spec, value = through_its_reader(name, observations())
    assert value == pytest.approx(EXPECTED[name])
    assert spec["reader"] == "counter_ratio"
    b = engine_metrics.bench()
    entry = next(e for e in entries() if e["name"] == name)
    assert (entry["unit"], entry["better"]) == (spec["unit"], "lower")
    assert (entry["source"], entry["layer"]) == ("program_span", "Engine")
    # the series are the engine's in every cell: a name stands in exactly
    # the accepted cells that report what its suffix moves, so a cell a
    # later PR adds fails here until the data file has it
    moves = {"decode": "serve_tok_s", "chat": "tpot_ms"}[name.rsplit(".", 1)[-1]]
    assert entry["moves"] == moves
    assert sorted(entry["workloads"]) == sorted(engine_metrics.reporting(b, moves))


def test_the_phases_add_up_to_what_the_accepted_metrics_read():
    """Host is the five phases of the thread's own code and blocked the
    two that wait: a program that observes them from the same readings
    reads the same through either family of files."""
    obs = observations()
    host = sum(PHASE_S[p] for p in HOST)
    blocked = PHASE_S["first_token_sync"] + PHASE_S["harvest_sync"]
    obs["counters"]["before"].update(engine_metrics.snap({
        "rt_serve_engine_round_host_s": (1.0, 100), "rt_serve_engine_round_blocked_s": (1.0, 100)}))
    obs["counters"]["after"].update(engine_metrics.snap({
        "rt_serve_engine_round_host_s": (1.0 + host, 100 + ROUNDS),
        "rt_serve_engine_round_blocked_s": (1.0 + blocked, 100 + ROUNDS)}))
    read = lambda name: through_its_reader(name, obs)[1]  # noqa: E731
    assert sum(read(f"round_{p}_ms.decode") for p in HOST) == pytest.approx(
        read("engine_host_ms.decode"))
    assert read("round_first_sync_ms.decode") + read("round_harvest_sync_ms.decode") == (
        pytest.approx(read("engine_blocked_ms.decode")))
    assert sum(read(f"dry_{p}_ms.decode") for p in HOST) <= read("engine_host_ms.decode")


@pytest.mark.parametrize("rule", [
    "test_top_level_keys_and_limits", "test_names_units_and_entries",
    "test_cells_configs_and_moves_hang_together", "test_every_metric_traffic_and_generator_has_its_file",
])
def test_benchmark_json_with_the_entries_keeps_the_contract(rule):
    path = os.path.join(ROOT, "BENCHMARK.json")
    b = harness.load_json(path)
    assert os.path.getsize(path) < 64 * 1024 and len(b["per_layer"]) <= 128
    assert len(entries(b)) == len(EXPECTED)  # each listed once, wherever in the list
    getattr(contract, rule)(b)


@pytest.mark.parametrize("cell", SERVING)
def test_run_resolves_them_in_every_serving_cell(cell, capsys):
    """(``test_run_resolves_every_serving_cell_of_the_merged_file`` until
    PR 62: the file the driver reads is the one that lists them.)"""
    assert run.main(["--workload", cell, "--trace", "1", "--dry"]) == 0
    plan = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    new = {n for n in plan["metrics"] if n in EXPECTED}
    suffix = ".chat" if cell == "xl-chat-sessions" else ".decode"
    assert new == {n for n in EXPECTED if n.endswith(suffix)}
    assert all(plan["metrics"][n] == "benchmark.readers.counter_ratio" for n in new)
    # an untraced run resolves none of them: they are per-layer metrics
    assert run.main(["--workload", cell, "--trace", "0", "--dry"]) == 0
    own = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert not set(own["metrics"]) & set(EXPECTED)


def test_round_phases_splits_a_gap_at_span_boundaries():
    """20 ms window. The device runs 0-4 and 11-20 ms: one gap of 7 ms that
    begins in a round's ``first_token_sync`` (3-5 ms), crosses a stretch of
    no inner span (5-6), the ``dispatch`` (6-8), the round's end (9), the
    space between rounds (9-10) and ends in the next round's ``admit``
    (10-12). ``attribute_gaps`` gives all of it to the round; here every
    instant goes to the span that covers it."""
    from benchmark.tools import span_gaps

    ms = 1e6
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [["fusion f32[8] 2in", 0, 4 * ms],
                                           ["fusion f32[8] 2in", 11 * ms, 9 * ms]]}]},
        {"name": "/host:CPU", "lines": [{"name": "llm-engine", "events": [
            ["bench/window", 0, 20 * ms], ["rt/engine/round", 1 * ms, 8 * ms],
            ["$llm.py:1 one_round", 1 * ms, 8 * ms],
            ["rt/engine/first_token_sync", 3 * ms, 2 * ms], ["rt/engine/dispatch", 6 * ms, 2 * ms],
            ["rt/engine/round", 10 * ms, 9 * ms], ["rt/engine/admit", 10 * ms, 2 * ms],
            ["rt/engine/harvest", 13 * ms, 3 * ms]]}]},
    ]}
    got = round_phases.reduce_phases(trace)
    assert got["window_s"] == pytest.approx(0.020) and got["rounds"] == 2
    assert got["idle_s"] == {
        "dispatch": pytest.approx(0.002), "other": pytest.approx(0.002),
        "first_token_sync": pytest.approx(0.001), "between_rounds": pytest.approx(0.001),
        "admit": pytest.approx(0.001), "harvest": 0.0,
    }
    assert got["idle_s_total"] == pytest.approx(0.007)
    assert got["spans"]["dispatch"] == {"count": 1, "ms": pytest.approx(2.0)}
    # the rounds' self time: 17 ms of rounds, 9 of them under an inner span
    assert got["spans"]["other"] == {"count": 2, "ms": pytest.approx(8.0)}
    assert got["ms_a_round"]["other"] == pytest.approx(4.0)
    # what the accepted reduction does with the same gap
    assert span_gaps.reduce_spans(trace)["idle_s_by_span"] == {
        "rt/engine/round": pytest.approx(0.007)}


def test_a_rehearsed_traced_line_carries_the_new_names_and_they_add_up(tmp_path):
    """The whole way, on the CPU at gpt2-tiny: the engine's account,
    the cluster's counters, the readers, the line. (No device number: a
    CPU's ``dry`` is the CPU's.)"""
    import bench_rehearsal_file
    import test_bench_rehearsal as rehearsal

    # the rehearsal's file is the real one renamed: it lists them too
    result, _ = rehearsal.rehearse(bench_rehearsal_file.write(tmp_path), "tiny-decode", 1)
    got = {n: m["value"] for n, m in result["metrics"].items()}
    assert {n for n in EXPECTED if n.endswith(".decode")} <= set(got)
    assert sum(got[f"round_{p}_ms.decode"] for p in HOST) == pytest.approx(
        got["engine_host_ms.decode"], rel=0.01)
    assert got["round_first_sync_ms.decode"] + got["round_harvest_sync_ms.decode"] == (
        pytest.approx(got["engine_blocked_ms.decode"], rel=0.01))
    assert all(0 <= got[f"dry_{p}_ms.decode"] <= got[f"round_{p}_ms.decode"] for p in HOST)
    assert 0 <= got["device_dry.decode"] <= 100 and 0 <= got["device_dry_traced.decode"] <= 100
