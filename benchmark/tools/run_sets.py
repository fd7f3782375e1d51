"""Run one cell several times, a new process each, and keep what each
run printed: the sets of runs the bounds are set from.

    python3 benchmark/tools/run_sets.py <cell> <seconds> <trace> <out.jsonl> <seed> [<seed> ...]
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_once(args, **row):
    """One run of the command; what it printed, and its result line."""
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *args],
        cwd=ROOT, capture_output=True, text=True,
    )
    lines = proc.stdout.strip().splitlines()
    row.update(rc=proc.returncode, wall_s=time.time() - t0, printed=lines[:-1],
               stderr=proc.stderr[-3000:])
    try:
        row["result"] = json.loads(lines[-1])
    except (IndexError, ValueError):
        row["printed"] = lines
    return row


def main(cell: str, seconds: str, trace: str, out_path: str, *seeds: str) -> int:
    for seed in seeds:
        row = run_once(
            ["--workload", cell, "--seed", seed, "--seconds", seconds, "--trace", trace],
            cell=cell, seed=int(seed), trace=int(trace),
        )
        with open(out_path, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(cell, seed, row["rc"], round(row["wall_s"], 1),
              {k: v["value"] for k, v in row.get("result", {}).get("metrics", {}).items()},
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
