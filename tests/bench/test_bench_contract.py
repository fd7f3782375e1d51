"""BENCHMARK.json against the contract, and against the benchmark's files."""

import copy
import glob
import importlib
import json
import os
import re

import bench_rehearsal_file
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


@pytest.fixture(scope="module", params=["BENCHMARK.json", "rehearsal"])
def bench(request):
    if request.param == "rehearsal":
        return bench_rehearsal_file.build()
    return load(request.param)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert bench["paths"] == ["benchmark", "tests/bench"]
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["workloads"]) <= 24 and 1 <= len(bench["configs"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16 and 1 <= len(bench["per_layer"]) <= 128
    assert len(json.dumps(bench)) < 64 * 1024


def test_names_units_and_entries(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200 and len(c["source"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and 1 <= len(m["layer"]) <= 200
    metrics = bench["end_to_end"] + bench["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for group in (metrics, bench["workloads"], bench["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))


def test_cells_configs_and_moves_hang_together(bench):
    cells = {w["name"] for w in bench["workloads"]}
    assert {w["config"] for w in bench["workloads"]} == {c["name"] for c in bench["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in bench["end_to_end"]}
    assert e2e["setup_s"] == cells
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        # a per-layer metric moves one end-to-end metric that every one of
        # its cells reports
        assert set(m.get("workloads", cells)) <= e2e[m["moves"]], m["name"]
    for cell in cells:
        assert sum(cell in on for name, on in e2e.items() if name != "setup_s") >= 1
        assert any(cell in m.get("workloads", cells) for m in bench["per_layer"])
    layers = {}
    for m in bench["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(spellings) == 1 for spellings in layers.values())


@pytest.mark.parametrize("moved", ["serve_tok_s", "tpot_ms", "train_tok_s"])
def test_one_share_of_the_whole_steps_peak_bounds_each_claimable_metric(bench, moved):
    """Exactly one per-layer metric with ``mfu`` as a part of its name
    moves each metric a PR can claim, and it lists every cell that reports
    that metric: a kernel that silences its own ``<kernel>_roofline`` still
    has the whole step's share to bound its claim, in whichever cell."""
    cells = [w["name"] for w in bench["workloads"]]
    entry = next(m for m in bench["end_to_end"] if m["name"] == moved)
    whole = [m for m in bench["per_layer"]
             if m["moves"] == moved and "mfu" in re.split(r"[._\-]", m["name"])]
    assert len(whole) == 1, [m["name"] for m in whole]
    assert (whole[0]["unit"], whole[0]["better"]) == ("%", "higher")
    assert sorted(whole[0].get("workloads", cells)) == sorted(entry.get("workloads", cells))


def test_every_metric_traffic_and_generator_has_its_file(bench):
    from benchmark import harness

    for m in bench["end_to_end"] + bench["per_layer"]:
        spec = harness.load_json(harness.find(bench, "metrics", m["name"]))
        assert spec["unit"] == m["unit"], m["name"]
        assert callable(harness.module(bench, "readers", spec["reader"]).read)
    for w in bench["workloads"]:
        tr = harness.load_json(harness.find(bench, "traffic", w["traffic"]))
        assert callable(harness.module(bench, "generators", tr["generator"]).run)


def test_files_under_paths_are_named_from_a_names_characters():
    for p in ("benchmark", "tests/bench"):
        for d, _, files in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


# the source's own values, pinned here for the configurations this file has
# known since PR 23: the one place where a typo made in both a file and the
# program would show. A configuration that is not in the table is held to
# its own ``published`` block, which the reviewer holds against its source.
PINNED = {
    "gpt2-xl-serve": {"n_embd": 1600, "n_layer": 48, "n_head": 25,
                      "n_positions": 1024, "vocab_size": 50257},
    "gpt2-small-train": {"n_embd": 768, "n_layer": 12, "n_head": 12,
                         "n_positions": 1024, "vocab_size": 50257},
}
# what ``reduced`` may never name (the contract): a hidden, intermediate,
# latent, state or projection size, a head size, an expansion factor, the
# experts a token takes
WIDTH = re.compile(r"hidden_size|n_embd|d_model|intermediate|latent|state_size|proj|"
                   r"_dim$|_rank$|head_size|expand|experts_per_tok")


def configurations():
    out = []
    for label, bench in (("BENCHMARK.json", load("BENCHMARK.json")),
                         ("rehearsal", bench_rehearsal_file.build())):
        out += [pytest.param(label, bench, c, id=f"{label}:{c['name']}")
                for c in bench["configs"]]
    return out


@pytest.mark.parametrize("label,bench,entry", configurations())
def test_configurations_keep_their_published_widths(label, bench, entry):
    """``model`` is what runs and ``published`` what the source says, in
    the same keys; they differ on the keys ``reduced`` names and on no
    other, and the program's own sizes are ``model``."""
    from benchmark import harness

    cfg = load(entry["file"])
    model, published = cfg["model"], cfg["published"]
    assert list(model) == list(published)
    cut = [k for k in model if model[k] != published[k]]
    assert cut == entry["reduced"] == cfg["reduced"]
    assert not [k for k in cut if WIDTH.search(k)], "a width is never cut"
    assert all(NAME.match(k) for k in cut) and len(cut) <= 16
    assert published == PINNED.get(entry["name"], published)
    assert cfg["source"] == entry["source"]
    assert cfg["platform"] == ("tpu" if label == "BENCHMARK.json" else "cpu")
    # a catalog model's file repeats the catalog's keys at the top level,
    # where the driver compares them: they say what ``model`` says
    assert all(cfg[k] == model[k] for k in model if k in cfg)
    family = harness.family(harness.find(bench, "families", cfg["family"], ".py"))
    assert family.program_sizes(cfg.get("model_id") or cfg["train"]["model_id"]) == model
    assert family.context(model) > 0


# -- the lists take an addition ---------------------------------------------

APPENDED = {"config": "appended-tiny-serve", "cell": "appended-tiny-decode",
            "metric": "appended_share"}
RULES = ["test_top_level_keys_and_limits", "test_names_units_and_entries",
         "test_cells_configs_and_moves_hang_together",
         "test_one_share_of_the_whole_steps_peak_bounds_each_claimable_metric",
         "test_every_metric_traffic_and_generator_has_its_file",
         "test_configurations_keep_their_published_widths"]
LIST_CHECK = "the_cell_stands_on_its_lists"


def appended(bench):
    """A copy of ``bench`` as the next ``model_config`` PR would leave it: a
    configuration, a cell and a per-layer entry APPENDED to their lists
    (standing on the rehearsal's tiny files and on a metric file no
    BENCHMARK.json lists), and the cell appended to the list of
    ``serve_tok_s`` and to every per-layer list that all the cells on
    ``serve_tok_s`` already share (the engine's series are every family's;
    ``decode_step_mfu`` is among them). No accepted entry moves or changes
    otherwise."""
    b = copy.deepcopy(bench)
    serving = set(next(m for m in b["end_to_end"] if m["name"] == "serve_tok_s")["workloads"])
    for m in b["end_to_end"] + b["per_layer"]:
        if serving <= set(m.get("workloads", [])):
            m["workloads"].append(APPENDED["cell"])
    b["configs"].append({"name": APPENDED["config"], "source": "tests", "reduced": [],
                         "file": "tests/bench/configs/gpt2-tiny-serve.json",
                         "why": "a configuration a later PR appends"})
    b["workloads"].append({"name": APPENDED["cell"], "config": APPENDED["config"],
                           "traffic": "tiny-decode", "chips": 1,
                           "why": "a cell a later PR appends"})
    b["per_layer"].append({"name": APPENDED["metric"], "unit": "%", "better": "lower",
                           "source": "program_counter", "moves": "serve_tok_s",
                           "layer": "OpenAI ingress, proxy, router",
                           "workloads": [APPENDED["cell"]]})
    return b


def list_checks():
    """Every family's ``the_cell_stands_on_its_lists(bench)``, found by that
    name in the test files beside this one: a cell's test file brings its
    own, and nothing here is edited for it."""
    found = []
    for path in sorted(glob.glob(os.path.join(HERE, "test_bench_*.py"))):
        with open(path) as f:
            if f"def {LIST_CHECK}(" not in f.read():
                continue
        module = importlib.import_module(os.path.basename(path)[: -len(".py")])
        found.append(pytest.param(getattr(module, LIST_CHECK), id=module.CELL))
    return found


@pytest.fixture(scope="module")
def appended_bench(tmp_path_factory):
    """Written out and read back, as the driver would meet it."""
    path = tmp_path_factory.mktemp("appended") / "BENCHMARK.json"
    path.write_text(json.dumps(appended(load("BENCHMARK.json")), indent=1))
    assert os.path.getsize(path) < 64 * 1024
    return load(str(path))


def test_the_copy_appends_and_changes_no_accepted_entry(appended_bench):
    real = load("BENCHMARK.json")
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        was, now = real[kind], appended_bench[kind]
        grown = len(now) - len(was)
        assert grown == (0 if kind == "end_to_end" else 1), kind
        for old, new in zip(was, now):  # the accepted entries, in their order
            assert {k: v for k, v in new.items() if k != "workloads"} == {
                k: v for k, v in old.items() if k != "workloads"}
            assert new.get("workloads", [])[: len(old.get("workloads", []))] == old.get(
                "workloads", [])
    assert [appended_bench[k][-1]["name"] for k in ("configs", "workloads", "per_layer")] == [
        APPENDED["config"], APPENDED["cell"], APPENDED["metric"]]


@pytest.mark.parametrize("rule", RULES)
def test_a_copy_with_a_cell_a_configuration_and_a_metric_appended_keeps_every_rule(
        rule, appended_bench):
    """THE DEFINITION of "the harness takes a cell without an edit": every
    rule of this file, run against ``appended(BENCHMARK.json)``. With the
    cases of the next test it is what a PR that adds a cell runs before it
    hands in (``benchmark/families/gpt2.py``, PERF.md section 4): where it
    fails, some file of the benchmark holds an entry by its place, and the
    PR could not have appended its own."""
    check = globals()[rule]
    if rule == "test_one_share_of_the_whole_steps_peak_bounds_each_claimable_metric":
        for moved in ("serve_tok_s", "tpot_ms", "train_tok_s"):
            check(appended_bench, moved)
    elif rule == "test_configurations_keep_their_published_widths":
        for entry in appended_bench["configs"]:
            label = "appended" if entry["name"] == APPENDED["config"] else "BENCHMARK.json"
            check(label, appended_bench, entry)
    else:
        check(appended_bench)


@pytest.mark.parametrize("check", list_checks())
def test_every_familys_cell_still_stands_on_its_lists_in_that_copy(check, appended_bench):
    """Each family's "the cell stands on every list it reports and on none
    it is kept off", a function of a ``bench`` that its own test calls with
    the real file, called here with the copy: a cell's test holds its
    entries by name and by membership, never by place."""
    check(appended_bench)


def test_no_file_of_the_benchmarks_tests_indexes_the_real_lists_by_position():
    """``[-1]``, ``[-N:]`` or a number on ``workloads``, ``configs``,
    ``per_layer`` or ``end_to_end`` of a loaded BENCHMARK.json is the habit
    that kept three accepted PRs from appending (PR 58, 59, 61)."""
    habit = re.compile(r"""\[["'](workloads|configs|per_layer|end_to_end)["']\]\s*\[\s*[-\d:]""")
    for path in sorted(glob.glob(os.path.join(HERE, "*.py"))):
        with open(path) as f:
            for n, line in enumerate(f, 1):
                assert not habit.search(line), f"{os.path.basename(path)}:{n}: {line.strip()}"
