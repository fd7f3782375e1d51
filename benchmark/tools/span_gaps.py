"""The program's own spans in one trace: how often and how long each
``rt/`` span ran in the traced window, and the device's idle seconds by
the innermost such span that overlaps each gap most, which is how
``breakdown`` names a gap too (``trace.attribute_gaps``, since PR 27); a
gap that no such span overlaps is "unattributed" here and goes to the
innermost host event of any kind there.

    python3 benchmark/tools/span_gaps.py <trace-dir> [<prefix>]
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import trace as trace_mod  # noqa: E402


def reduce_spans(tr, prefix: str = "rt/"):
    """On a trace in plain form: per span name its count and its
    milliseconds inside the window, and idle seconds by span."""
    win = trace_mod.window(tr)
    spans = [ev for ev in trace_mod.host_events(tr) if ev[0].startswith(prefix)]
    totals = {}
    for name, start, dur in spans:
        got = min(start + dur, win[1]) - max(start, win[0])
        if got > 0:
            t = totals.setdefault(name, [0, 0.0])
            t[0] += 1
            t[1] += got * 1e-6
    gaps = {}
    planes = trace_mod.device_planes(tr)
    for p in planes:
        idle = trace_mod.subtract([win], trace_mod.busy_intervals(p, win))
        idle = [g for g in idle if g[1] - g[0] >= 20_000]
        for name, s in trace_mod.attribute_gaps(idle, spans, prefer=prefix).items():
            gaps[name] = gaps.get(name, 0.0) + s / len(planes)
    return {
        "window_s": (win[1] - win[0]) * 1e-9,
        "spans": {n: {"count": c, "ms": ms} for n, (c, ms) in sorted(totals.items())},
        "idle_s_by_span": dict(sorted(gaps.items(), key=lambda kv: -kv[1])),
    }


def main(trace_dir: str, prefix: str = "rt/") -> int:
    path = trace_mod.find_xplane(trace_dir)
    if not path:
        print(f"no .xplane.pb under {trace_dir}")
        return 1
    tr = trace_mod.load_xplane(path)
    if trace_mod.window(tr) is None:
        print(f"{path}: no bench/window span and no device operation")
        return 1
    print(json.dumps(reduce_spans(tr, prefix)))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
