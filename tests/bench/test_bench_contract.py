"""BENCHMARK.json against the contract, and against the benchmark's files."""

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
