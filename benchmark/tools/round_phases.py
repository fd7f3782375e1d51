"""The engine's round by phase in one trace, and the device's idle
seconds split at span boundaries: every instant of a gap goes to the
``rt/engine/*`` span that covers that instant, to ``other`` under a
round and no inner span, to ``between_rounds`` under none. (``breakdown``
and ``span_gaps.py`` give a whole gap to the span that overlaps it most,
which for a gap that runs from one span into the next is the round.) The
trace's side of what the engine counts itself: ``rt_serve_engine_<phase>_s``
beside ``ms_a_round``, ``rt_serve_engine_dry_<phase>_s`` beside ``idle_s``,
which it may not pass by more than a phase boundary's worth.

    python3 benchmark/tools/round_phases.py <trace-dir>

A span shorter than 20 us is not in the loaded trace (``load_xplane``), so
its time and its gaps read as ``other``.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import trace as trace_mod  # noqa: E402

PREFIX = "rt/engine/"
ROUND = PREFIX + "round"


def covered(a: List[trace_mod.Interval], b: List[trace_mod.Interval]) -> List[trace_mod.Interval]:
    """Parts of the (merged) intervals ``a`` that the (merged) ``b`` cover."""
    return trace_mod.subtract(a, trace_mod.subtract(a, b))


def reduce_phases(tr: Dict[str, Any], min_gap_ns: float = 20_000) -> Dict[str, Any]:
    """On a trace in plain form. ``spans``: per phase its count and
    milliseconds inside the window, ``other`` being the rounds' self time;
    ``ms_a_round``: the same over the rounds; ``idle_s``: the gaps of
    ``min_gap_ns`` and more (what ``breakdown`` names) by the phase that
    covers each instant, averaged over the chips."""
    win = trace_mod.window(tr)
    by_name: Dict[str, List[trace_mod.Interval]] = {}
    counts: Dict[str, int] = {}
    for name, start, dur in trace_mod.host_events(tr):
        if name.startswith(PREFIX) and min(start + dur, win[1]) > max(start, win[0]):
            by_name.setdefault(name, []).append((start, start + dur))
            counts[name] = counts.get(name, 0) + 1
    spans = {name: trace_mod.union(trace_mod.clip(ivs, *win)) for name, ivs in by_name.items()}
    rounds = spans.pop(ROUND, [])
    inner = trace_mod.union(iv for ivs in spans.values() for iv in ivs)
    ms = {name[len(PREFIX):]: trace_mod.total(ivs) * 1e-6 for name, ivs in spans.items()}
    ms["other"] = (trace_mod.total(rounds) - trace_mod.total(covered(rounds, inner))) * 1e-6
    n_rounds = counts.get(ROUND, 0)
    idle: Dict[str, float] = {}
    planes = trace_mod.device_planes(tr)
    for p in planes:
        gaps = [g for g in trace_mod.subtract([win], trace_mod.busy_intervals(p, win))
                if g[1] - g[0] >= min_gap_ns]
        in_rounds = covered(gaps, rounds)
        parts = {name[len(PREFIX):]: trace_mod.total(covered(gaps, ivs))
                 for name, ivs in spans.items()}
        parts["other"] = trace_mod.total(in_rounds) - trace_mod.total(covered(in_rounds, inner))
        parts["between_rounds"] = trace_mod.total(gaps) - trace_mod.total(in_rounds)
        for name, ns in parts.items():
            idle[name] = idle.get(name, 0.0) + ns * 1e-9 / len(planes)
    return {
        "window_s": (win[1] - win[0]) * 1e-9,
        "rounds": n_rounds,
        "spans": {name: {"count": counts.get(PREFIX + name, n_rounds), "ms": v}
                  for name, v in sorted(ms.items())},
        "ms_a_round": {name: v / n_rounds for name, v in sorted(ms.items())} if n_rounds else {},
        "idle_s": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "idle_s_total": sum(idle.values()),
    }


def main(trace_dir: str) -> int:
    path = trace_mod.find_xplane(trace_dir)
    if not path:
        print(f"no .xplane.pb under {trace_dir}")
        return 1
    tr = trace_mod.load_xplane(path)
    if trace_mod.window(tr) is None:
        print(f"{path}: no bench/window span and no device operation")
        return 1
    print(json.dumps(reduce_phases(tr)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
