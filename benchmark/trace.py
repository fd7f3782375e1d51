"""Reduction of a profiler trace to busy time, per-operation time, idle
gaps and what the host was doing in them.

A trace, here, is plain data: ``{"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, duration_ns], ...]}]}]}``. ``load_xplane``
reads the profiler's ``.xplane.pb`` into that form with nothing but
JAX; everything else works on the plain form, so it is checked on the
small recorded trace kept with the tests.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench/window"
COLLECTIVES = {"all-reduce": r"^all-reduce", "all-gather": r"^all-gather"}
# host events that only wrap others say nothing about what the host did
_WRAPPERS = (WINDOW_SPAN, "$profiler.py", "$threading.py", "$<unknown>")


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    ))
    return files[-1] if files else None


def load_xplane(path: str, keep_host_events: int = 200_000) -> Dict[str, Any]:
    """Read an ``.xplane.pb``. Device planes are kept whole; of the host
    plane only events of 20 us and more, longest lines first, up to
    ``keep_host_events``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        lines = []
        budget = keep_host_events
        for line in plane.lines:
            events = []
            for ev in line.events:
                dur = float(ev.duration_ns)
                if not device and dur < 20_000:
                    continue
                events.append([_event_name(ev, device), float(ev.start_ns), dur])
            if not device:
                events = events[:budget]
                budget -= len(events)
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


_SHAPE = re.compile(r"([a-z]+\d+)\[([\d,]*)\]")


def short_op_name(text: str) -> str:
    """A device operation's trace name, which is its whole HLO line
    (``%fusion.12 = bf16[8,128]{1,0:T(8,128)} fusion(bf16[8,128]{...} %p0,
    ...)``), as ``fusion bf16[8,128] 2in``: the operation without its
    number, the shape of what it writes (the first two of a tuple) and
    how many operands it reads. Copies of different tensors then read as
    different operations, and a kernel's calls differ by their operands."""
    head, sep, rest = text.partition(" = ")
    op = re.sub(r"[.\d]+$", "", head.lstrip("%")) or head
    if not sep:
        return op
    if rest.startswith("("):
        depth, i = 0, 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        out_text, call = rest[: i + 1], rest[i + 1:]
        shapes = [f"{d}[{dims}]" for d, dims in _SHAPE.findall(out_text)]
        out = "(" + ",".join(shapes[:2]) + (",.." if len(shapes) > 2 else "") + ")"
    else:
        m = _SHAPE.match(rest)
        out = f"{m.group(1)}[{m.group(2)}]" if m else "?"
        call = re.sub(r"^\S+\[[\d,]*\](\{[^}]*\})?\s*", "", rest)
    args = call.partition("(")[2]
    depth, n = 0, 0
    for j, ch in enumerate(args):  # operands of the call itself, not of nested text
        if ch == "(":
            depth += 1
        elif ch == ")":
            if depth == 0:
                break
            depth -= 1
        elif ch == "%" and depth == 0 and (j == 0 or args[j - 1] in " ,"):
            n += 1
    return f"{op} {out} {n}in"


def _event_name(ev, device: bool) -> str:
    """Module events lose their run-specific id; device operations are
    named by ``short_op_name``."""
    name = re.sub(r"\(\d+\)$", "", ev.name)
    return short_op_name(name) if device and name.startswith("%") else name


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the (merged) intervals ``a`` that no interval of the
    (merged) ``b`` covers."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def _line(plane: Dict[str, Any], name: str) -> List[List[Any]]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def device_planes(trace: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


# a thread parked in one of these is waiting, not working
_WAITING = re.compile(
    r" (wait|acquire|get|sleep|select|poll|recv_into|recv_message|_recv_exact\w*|accept)$"
)
_DISPATCH = re.compile(r"^(PjitFunction|bench/)|ExecuteHelper")


def host_events(trace: Dict[str, Any], dispatching_only: bool = False) -> List[List[Any]]:
    """Events of the host's threads. With ``dispatching_only``, only of
    the threads that dispatch programs to the device (those with a
    ``PjitFunction``, an execute call or a ``bench/`` span of their own),
    if there is such a thread: what the others do does not hold the
    device up."""
    lines = [
        line["events"] for p in trace["planes"] if p["name"].startswith("/host:CPU")
        for line in p["lines"]
    ]
    if dispatching_only:
        chosen = [evs for evs in lines if any(_DISPATCH.search(e[0]) for e in evs)]
        lines = chosen or lines
    return [ev for evs in lines for ev in evs]


def window(trace: Dict[str, Any]) -> Optional[Interval]:
    """The traced window: the benchmark's own ``bench/window`` span if
    the host plane has it, else from the first device operation's start
    to the last one's end."""
    spans = [ev for ev in host_events(trace) if ev[0] == WINDOW_SPAN]
    if spans:
        ev = max(spans, key=lambda e: e[2])
        return (ev[1], ev[1] + ev[2])
    ops = [ev for p in device_planes(trace) for ev in _line(p, OPS_LINE)]
    if not ops:
        return None
    return (min(e[1] for e in ops), max(e[1] + e[2] for e in ops))


def busy_intervals(plane: Dict[str, Any], win: Interval) -> List[Interval]:
    ops = _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)
    return union(clip(((e[1], e[1] + e[2]) for e in ops), *win))


def op_totals(trace: Dict[str, Any], line: str,
              win: Interval) -> Tuple[Dict[str, float], Dict[str, float]]:
    """Per name on one line of the device planes, averaged over the
    chips: seconds inside the window, and events that start inside it."""
    planes = device_planes(trace)
    seconds: Dict[str, float] = {}
    calls: Dict[str, float] = {}
    for p in planes:
        for name, start, dur in _line(p, line):
            got = min(start + dur, win[1]) - max(start, win[0])
            if got > 0:
                seconds[name] = seconds.get(name, 0.0) + got * 1e-9 / len(planes)
            if win[0] <= start < win[1]:
                calls[name] = calls.get(name, 0.0) + 1.0 / len(planes)
    return seconds, calls


PROGRAM_SPANS = "rt/"  # the program's own spans (``tracing.span``)


def attribute_gaps(gaps: List[Interval], host: List[List[Any]],
                   prefer: str = PROGRAM_SPANS) -> Dict[str, float]:
    """Seconds of device idleness by what the host was doing: each gap
    goes to the host event that overlaps it most (the shorter event
    where two overlap it equally, so the innermost span wins). Where one
    of the program's own spans (a name that begins with ``prefer``)
    overlaps the gap, the gap goes to the innermost of those and not to a
    Python frame inside it: a frame's name carries a line number
    (``$llm.py:1455 run_round``) and changes with any edit above it."""
    named = sorted(
        (ev for ev in host
         if not ev[0].startswith(_WRAPPERS) and not _WAITING.search(ev[0])),
        key=lambda e: e[1],
    )
    starts = [ev[1] for ev in named]
    out: Dict[str, float] = {}
    for a, b in gaps:
        best, best_key = "unattributed", (0.0, 0.0)
        span, span_key = None, (0.0, 0.0)  # the best of the preferred
        hi = bisect.bisect_left(starts, b)
        # events that began before the gap ended, nearest first; the
        # scan is bounded, so a span that began very long ago is missed
        for name, start, dur in reversed(named[max(0, hi - 2000):hi]):
            ov = min(b, start + dur) - max(a, start)
            if ov > 0 and (ov, -dur) > best_key:
                best, best_key = name, (ov, -dur)
            if ov > 0 and (ov, -dur) > span_key and name.startswith(prefer):
                span, span_key = name, (ov, -dur)
        out[span or best] = out.get(span or best, 0.0) + (b - a) * 1e-9
    return out


def exposed_seconds(plane: Dict[str, Any], win: Interval, pattern: str) -> float:
    """Seconds inside the window in which an operation matching
    ``pattern`` runs on this device and no other operation does."""
    rx = re.compile(pattern)
    ops = _line(plane, OPS_LINE)
    # a loop's own event spans its body: it is not compute of its own
    hit = union(clip(((e[1], e[1] + e[2]) for e in ops if rx.search(e[0])), *win))
    rest = union(clip(
        ((e[1], e[1] + e[2]) for e in ops
         if not rx.search(e[0]) and not e[0].startswith(("while", "conditional"))),
        *win,
    ))
    return total(subtract(hit, rest)) * 1e-9


def reduce(trace: Dict[str, Any], top: int = 10,
           min_gap_ns: float = 20_000) -> Optional[Dict[str, Any]]:
    """Busy and window seconds (averaged over the chips), the device
    operations with most time, and the longest idle gaps by what the host
    was doing. None when no operation ran on a device."""
    win = window(trace)
    planes = device_planes(trace)
    if win is None or not planes:
        return None
    busy = [busy_intervals(p, win) for p in planes]
    busy_s = sum(total(b) for b in busy) * 1e-9 / len(planes)
    if busy_s <= 0:
        return None
    host = host_events(trace, dispatching_only=True)
    gaps: Dict[str, float] = {}
    for b in busy:
        idle = [g for g in subtract([win], b) if g[1] - g[0] >= min_gap_ns]
        for name, s in attribute_gaps(idle, host).items():
            gaps[name] = gaps.get(name, 0.0) + s / len(planes)
    modules, module_calls = op_totals(trace, MODULES_LINE, win)
    ops, op_calls = op_totals(trace, OPS_LINE, win)
    ranked = sorted(modules.items(), key=lambda kv: -kv[1])[:4]
    ranked = [(f"module {n}", s) for n, s in ranked]
    ranked += sorted(ops.items(), key=lambda kv: -kv[1])[: top - len(ranked)]
    compiles: Dict[str, float] = {}
    for name, start, _ in host_events(trace):
        # the runtime's compile events, not Python frames of a compiler.py
        if "ompil" in name and not name.startswith("$") and win[0] <= start < win[1]:
            compiles[name] = compiles.get(name, 0.0) + 1.0
    return {
        "busy_s": busy_s,
        "window_s": (win[1] - win[0]) * 1e-9,
        "chips": len(planes),
        "modules": modules,
        "module_calls": module_calls,
        "ops": ops,
        "op_calls": op_calls,
        "host_calls": compiles,
        "exposed_s": {
            name: sum(exposed_seconds(p, win, rx) for p in planes) / len(planes)
            for name, rx in COLLECTIVES.items()
        },
        "device_ops": [[n, s] for n, s in ranked],
        "idle_gaps": [
            [n, s] for n, s in sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
        ],
    }
