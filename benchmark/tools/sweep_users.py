"""The user-count sweep of a closed-loop serving cell, run once on the chip.

    python3 benchmark/tools/sweep_users.py <cell> <seconds> <out.jsonl> 8 12 16 ...

For each count it writes a copy of the cell's traffic file with that many
users beside it (removed again afterwards), runs the cell once through the
ordinary command and records the tokens per second completed and the
latencies the run printed. The count the cell keeps is about four fifths
of the one at which tokens per second stop rising.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tools import run_sets  # noqa: E402


def main(cell: str, seconds: str, out_path: str, *counts: str) -> int:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    traffic_path = os.path.join(ROOT, "benchmark", "traffic", entry["traffic"] + ".json")
    with open(traffic_path) as f:
        traffic = json.load(f)
    made = []
    try:
        for n in counts:
            name = f"{entry['traffic']}-u{n}"
            path = os.path.join(ROOT, "benchmark", "traffic", name + ".json")
            with open(path, "w") as f:
                json.dump({**traffic, "users": int(n)}, f)
            made.append(path)
            sweep = dict(bench)
            sweep["workloads"] = [{**entry, "name": cell, "traffic": name}]
            bench_path = os.path.join(ROOT, "benchmark", "traffic", f"_sweep_{n}.json")
            with open(bench_path, "w") as f:
                json.dump(sweep, f)
            made.append(bench_path)
            row = run_sets.run_once(
                ["--bench-file", bench_path, "--workload", cell, "--seed", "5",
                 "--seconds", seconds, "--trace", "1"], users=int(n),
            )
            with open(out_path, "a") as f:
                f.write(json.dumps(row) + "\n")
    finally:
        for path in made:
            os.remove(path)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
