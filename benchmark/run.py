"""Run one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time. Everything that belongs to one configuration,
one traffic mix or one metric is a file found by its name:

    configs/<config>.json      the entry's ``file``: sizes, deployment, family
    families/<family>.py       what the harness asks of a model (families/gpt2.py)
    traffic/<traffic>.json     generator name and parameters
    metrics/<metric>.json      unit, reader name and arguments
    generators/<name>.py       ``run(ctx) -> observations``
    readers/<name>.py          ``read(obs, args, ctx) -> number or None``

looked for under each directory of ``paths``; a new cell, configuration,
mix or metric needs no edit here. The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, traced ``breakdown``, and last ``compared``: every number
``correct`` was decided from, beside its limit. Earlier lines say what
set-up spent its time on and give the whole-window readings with the
sliced ones beside them.

A cell on the TPU waits for its chips before it starts and until they are
free again before it exits (``harness.wait_for_chips``): the run before
this one may still be letting go of them, and whatever runs next, an
older tree or an older benchmark, has to find them free. Neither wait is
part of any metric; the closing one speaks on standard error, after the
result line, and standard error ends with the numbers compared.

    --dry           resolve the cell and print the plan; run nothing
    --check <cfg>   compare the program with the plain reference at the
                    configuration's sizes, in this process (outside any run)
    --bench-file    another BENCHMARK.json (the CPU rehearsal's)
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.harness import find, load_json, module  # noqa: E402

# sources a CPU run may not report under: they describe the device
DEVICE_SOURCES = ("device_trace",)


def resolve(bench: Dict[str, Any], workload: str, trace: bool) -> Dict[str, Any]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        sys.exit(f"benchmark: no workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(find(bench, "traffic", cell["traffic"]))
    metrics = []
    for m in bench["per_layer" if trace else "end_to_end"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        spec = load_json(find(bench, "metrics", m["name"]))
        if spec["unit"] != m["unit"]:
            sys.exit(f"benchmark: metric {m['name']} is {m['unit']!r} in "
                     f"BENCHMARK.json and {spec['unit']!r} in its file")
        metrics.append({**m, "reader": spec["reader"], "args": spec.get("args", {})})
    fam = config.get("family")
    return {"cell": cell, "config": config, "traffic": traffic, "metrics": metrics,
            "family": find(bench, "families", fam, ".py") if fam else None}


def read_metrics(bench, plan, obs, ctx) -> Dict[str, Dict[str, Any]]:
    out: Dict[str, Dict[str, Any]] = {}
    for m in plan["metrics"]:
        if ctx.platform != "tpu" and m["source"] in DEVICE_SOURCES:
            continue  # no CPU number under a device metric's name
        value = module(bench, "readers", m["reader"]).read(obs, m["args"], ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def attach_trace(obs: Dict[str, Any]) -> None:
    """Reduce the trace a serving replica wrote; a trainer reduces its own."""
    from benchmark import trace as trace_mod

    if obs.get("trace") is None and obs.get("trace_dir"):
        path = trace_mod.find_xplane(obs["trace_dir"])
        if path:
            obs["trace"] = trace_mod.reduce(trace_mod.load_xplane(path))


def result_line(obs, metrics, traced: bool) -> Dict[str, Any]:
    dev = obs["device"]
    device = {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
              "memory_peak_bytes": dev["memory_peak_bytes"]}
    line: Dict[str, Any] = {
        "correct": not obs["problems"] and obs["failed"] == 0,
        "attempted": obs["attempted"], "failed": obs["failed"],
        "metrics": metrics, "device": device,
    }
    tr = obs.get("trace")
    if traced and tr:
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    # what `correct` compared, each number beside its limit; the last key
    line["compared"] = {name: {"value": float(v), "limit": float(limit)}
                        for name, (v, limit) in obs.get("compared", {}).items()}
    return line


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry", action="store_true")
    ap.add_argument("--check", metavar="CONFIG")
    ap.add_argument("--bench-file", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    bench = load_json(args.bench_file)

    if args.check:
        from benchmark.reference import check

        return check.main(bench, args.check, args.seed)
    if not args.workload:
        ap.error("--workload is required")
    plan = resolve(bench, args.workload, bool(args.trace))
    generator = module(bench, "generators", plan["traffic"]["generator"])
    readers = {m["reader"]: module(bench, "readers", m["reader"]) for m in plan["metrics"]}
    if args.dry:
        print(json.dumps({
            "workload": plan["cell"], "config": plan["config"],
            "traffic": plan["traffic"], "generator": generator.__name__,
            "family": plan["family"] and os.path.relpath(plan["family"], ROOT),
            "metrics": {m["name"]: readers[m["reader"]].__name__ for m in plan["metrics"]},
        }))
        return 0

    harness.prepare_env()  # before the program is imported: it reads its settings then
    platform = plan["config"].get("platform", "tpu")
    harness.require_platform(platform, int(plan["cell"]["chips"]))
    waited = harness.wait_for_chips(platform, "before the run")
    last_words: List[str] = []
    try:
        last_words = run_cell(bench, plan, generator, args, platform, waited)
        return 0
    finally:
        # also after a run that failed; on standard error, because the
        # result line stays the last of standard output
        with contextlib.redirect_stdout(sys.stderr):
            harness.wait_for_chips(platform, "after the run")
            for words in last_words:
                print(words, flush=True)


def run_cell(bench, plan, generator, args, platform: str, waited: float) -> List[str]:
    """The run itself, down to its result line. Set-up is counted from
    the process's start less the seconds it waited for the chips: they
    are the machine's state, not the program's set-up. Returns what
    standard error is to end with: ``correct`` and every number it was
    decided from, beside its limit."""
    seconds = float(args.seconds if args.seconds is not None else bench["run_seconds"])
    ctx = SimpleNamespace(
        cell=plan["cell"], config=plan["config"], traffic=plan["traffic"],
        seed=int(args.seed), seconds=seconds, trace=bool(args.trace),
        platform=platform, phases=harness.Phases(T_PROCESS_START + waited), root=ROOT,
        family=plan["family"],
    )
    harness.say(f"cell {args.workload}: seed {ctx.seed}, {seconds:g}s window, "
                f"trace {args.trace}, compile cache {harness.compile_cache_dir()} "
                f"({harness.cache_entries()} entries)")
    obs = generator.run(ctx)
    attach_trace(obs)
    for note in obs.get("notes", []):
        harness.say(note)
    for problem in obs["problems"]:
        harness.say(f"CHECK FAILED: {problem}")
    if obs["device"]["platform"] != platform or obs["device"]["count"] != int(
        plan["cell"]["chips"]
    ):
        sys.exit(f"benchmark: the cell wants {plan['cell']['chips']} x {platform}, "
                 f"the program computed on {obs['device']}")
    metrics = read_metrics(bench, plan, obs, ctx)
    line = result_line(obs, metrics, ctx.trace)
    print(json.dumps(line), flush=True)
    return [f"correct: {str(line['correct']).lower()}",
            *(f"compared: {name} {c['value']!r} limit {c['limit']!r}"
              for name, c in line["compared"].items())]


if __name__ == "__main__":
    sys.exit(main())
