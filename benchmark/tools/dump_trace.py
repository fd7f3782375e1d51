"""Look at one trace by hand: planes, lines, the names with most time on
each line, and the statistics the first events carry.

    python3 benchmark/tools/dump_trace.py <trace-dir> [<out-file> [<plain.json> <ms>]]

With a third and fourth argument it also records the trace's plain form,
cut to the first ``ms`` milliseconds of its window: the small recorded
trace the tests reduce.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import trace as trace_mod  # noqa: E402


def record(path: str, plain_path: str, ms: float) -> None:
    import json

    tr = trace_mod.load_xplane(path)
    lo = trace_mod.window(tr)[0]
    hi = lo + ms * 1e6
    for plane in tr["planes"]:
        for line in plane["lines"]:
            line["events"] = [
                [n, max(s, lo) - lo, min(s + d, hi) - max(s, lo)]
                for n, s, d in line["events"] if s + d > lo and s < hi
            ]
        plane["lines"] = [l for l in plane["lines"] if l["events"]]
    with open(plain_path, "w") as f:
        json.dump(tr, f, separators=(",", ":"))


def main(trace_dir: str, out_path: str = "", plain_path: str = "", ms: str = "0") -> int:
    from jax.profiler import ProfileData

    path = trace_mod.find_xplane(trace_dir)
    if not path:
        print(f"no .xplane.pb under {trace_dir}")
        return 1
    out = open(out_path, "w") if out_path else sys.stdout
    print(f"{path}: {os.path.getsize(path)} bytes", file=out)
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name}", file=out)
        for line in plane.lines:
            events = list(line.events)
            by_name = {}
            for ev in events:
                t = by_name.setdefault(trace_mod.short_op_name(ev.name) if ev.name.startswith('%') else ev.name, [0.0, 0])
                t[0] += ev.duration_ns
                t[1] += 1
            print(f"  LINE {line.name!r}: {len(events)} events", file=out)
            for name, (ns, n) in sorted(by_name.items(), key=lambda kv: -kv[1][0])[:40]:
                print(f"    {ns / 1e6:12.3f} ms {n:7d} x {name[:110]}", file=out)
            for ev in events[:2]:
                print(f"    stats of {ev.name[:60]!r}: "
                      f"{[(k, str(v)[:90]) for k, v in ev.stats]}", file=out)
    if plain_path:
        record(path, plain_path, float(ms))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:5]))
