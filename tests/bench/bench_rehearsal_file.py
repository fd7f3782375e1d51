"""The rehearsal's BENCHMARK.json: the real one with its cells,
configurations and traffic mixes renamed to the tiny ones under
``tests/bench/`` and a 3 s window. ``rehearsal.json`` says which, and so
does every ``rehearsal-*.json`` beside it, so that a later PR's cell
rehearses by a file of its own; a cell that no file names is left out of
the rehearsal, with the configurations and metrics only it used. Every
metric, bound and layer is the real file's, so the rehearsal cannot drift
from the contract it rehearses."""

import glob
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load(path):
    with open(path) as f:
        return json.load(f)


def names():
    merged = load(os.path.join(HERE, "rehearsal.json"))
    for path in sorted(glob.glob(os.path.join(HERE, "rehearsal-*.json"))):
        for kind, renamed in load(path).items():
            merged[kind].update(renamed)
    return merged


def build():
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    to = names()
    bench["run_seconds"] = to["run_seconds"]
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] in to["workloads"]]
    used = {w["config"] for w in bench["workloads"]}
    bench["configs"] = [c for c in bench["configs"] if c["name"] in used]
    for c in bench["configs"]:
        c["name"] = to["configs"][c["name"]]
        c.update(source="tests", file=f"tests/bench/configs/{c['name']}.json",
                 why="CPU rehearsal")
        c["reduced"] = load(os.path.join(ROOT, c["file"]))["reduced"]
    for w in bench["workloads"]:
        w.update(why=f"CPU rehearsal of {w['name']}", name=to["workloads"][w["name"]],
                 config=to["configs"][w["config"]], traffic=to["traffic"][w["traffic"]])
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "workloads" in m:
                m["workloads"] = [to["workloads"][w] for w in m["workloads"]
                                  if w in to["workloads"]]
        bench[group] = [m for m in bench[group] if m.get("workloads", True)]
    return bench


def write(directory):
    path = os.path.join(str(directory), "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(build(), f, indent=1)
    return path
