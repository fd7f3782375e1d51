"""The rehearsal's BENCHMARK.json: the real one with its cells,
configurations and traffic mixes renamed to the gpt2-tiny ones under
``tests/bench/`` (``rehearsal.json`` says which) and a 3 s window. Every
metric, bound and layer is the real file's, so the rehearsal cannot
drift from the contract it rehearses."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def build():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(HERE, "rehearsal.json")) as f:
        names = json.load(f)
    bench["run_seconds"] = names["run_seconds"]
    for c in bench["configs"]:
        c["name"] = names["configs"][c["name"]]
        c.update(source="tests", file=f"tests/bench/configs/{c['name']}.json",
                 why="CPU rehearsal")
    for w in bench["workloads"]:
        w.update(why=f"CPU rehearsal of {w['name']}", name=names["workloads"][w["name"]],
                 config=names["configs"][w["config"]], traffic=names["traffic"][w["traffic"]])
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [names["workloads"][w] for w in m["workloads"]]
    return bench


def write(directory):
    path = os.path.join(str(directory), "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(build(), f, indent=1)
    return path
