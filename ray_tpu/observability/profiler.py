"""Sampling profiler: fleet-wide wall-time attribution with no deps.

Parity target: `ray timeline`'s sibling tooling (py-spy dump / record
wired into the reference dashboard). Here the sampler is in-process —
a thread walking ``sys._current_frames()`` — so it needs no ptrace, no
external binary, and works identically in every spawned process.

Two modes share one sampling core:

- **On-demand capture** — ``rpc_profile(duration_s, hz)`` on the worker
  / node agent / control store runs :func:`capture` and returns folded
  stacks + per-subsystem sample counts; ``state.profile()`` fans the RPC
  across the fleet and :func:`merge` combines replies (deduped by
  per-process token — on a single-node ``init()`` the head, agent and
  driver share one process). ``rt profile`` renders the merge as a
  terminal table, folded-stacks text and a self-contained flamegraph
  HTML (:func:`flamegraph_html` — nested divs, no JS deps).
- **Continuous mode** — ``RT_PROFILER_HZ>0`` starts one low-rate daemon
  sampler per process (:class:`ContinuousSampler`, thread name
  ``rt-prof``) whose per-subsystem shares feed
  ``rt_profile_samples_total{subsystem}`` so history/alerts can trend
  CPU attribution. Default off; ``RT_OBSERVABILITY_ENABLED=0`` means
  zero extra threads.

Attribution walks each stack leaf -> root: the first frame inside a
``ray_tpu`` module maps through :data:`_FRAME_BUCKETS`
(rpc / scheduler / object-store / serve / engine / collective /
pipeline / user / obs); a frame outside both the stdlib and
site-packages is user code (``user``). Stacks that never leave the
stdlib (idle pool threads parked in ``queue.get``) fall back to a
thread-name map, so idle dispatcher threads attribute to their owning
subsystem instead of swamping ``other``.

Import discipline: only ``ray_tpu.utils.*`` imports allowed here.
"""

from __future__ import annotations

import html as _html
import os
import sys
import sysconfig
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

from ray_tpu.utils.config import config
from ray_tpu.utils.metrics import PROCESS_TOKEN

ENABLED = bool(config.observability_enabled)

SAMPLER_THREAD_NAME = "rt-prof"

# Leaf-to-root frame attribution: first matching path fragment wins.
# Order matters — specific prefixes before the ray_tpu/ catch-all.
_FRAME_BUCKETS: Tuple[Tuple[str, str], ...] = (
    ("ray_tpu/serve/llm", "engine"),
    ("ray_tpu/serve/models", "engine"),
    ("ray_tpu/serve/prefix_cache", "engine"),
    ("ray_tpu/serve/", "serve"),
    ("ray_tpu/collective/", "collective"),
    ("ray_tpu/parallel/", "pipeline"),
    ("ray_tpu/data/", "pipeline"),
    ("ray_tpu/train/", "pipeline"),
    ("ray_tpu/core/object_store", "object-store"),
    ("ray_tpu/core/device_objects", "object-store"),
    ("ray_tpu/core/channels", "object-store"),
    ("ray_tpu/utils/serialization", "object-store"),
    ("ray_tpu/core/control_store", "scheduler"),
    ("ray_tpu/core/scheduling", "scheduler"),
    ("ray_tpu/core/placement", "scheduler"),
    ("ray_tpu/core/node_agent", "scheduler"),
    ("ray_tpu/core/autoscaler", "scheduler"),
    ("ray_tpu/core/ha/", "scheduler"),
    ("ray_tpu/utils/rpc", "rpc"),
    ("ray_tpu/utils/gateway", "rpc"),
    ("ray_tpu/dashboard", "rpc"),
    ("ray_tpu/observability/", "obs"),
    # remaining ray_tpu/core frames are the task-execution machinery
    # (worker.py dispatch around user code) — attribute with the task
    ("ray_tpu/", "user"),
)

# Thread-name fallback for stacks that never leave the stdlib (a pool
# thread parked in queue.get has no ray_tpu frame, but its NAME says
# which subsystem owns it). Order matters: obs names before "cs-".
_THREAD_BUCKETS: Tuple[Tuple[str, str], ...] = (
    (SAMPLER_THREAD_NAME, "obs"),
    ("rt-blackbox", "obs"),
    ("cs-obs", "obs"),
    ("stall-watch", "obs"),
    ("-conn", "rpc"),
    ("-read", "rpc"),
    ("-accept", "rpc"),
    ("-disp", "rpc"),
    ("gw-", "rpc"),
    ("gateway", "rpc"),
    ("dashboard", "rpc"),
    ("cs-", "scheduler"),
    ("agent-", "scheduler"),
    ("autoscaler", "scheduler"),
    ("wal-group", "scheduler"),
    ("task-submit", "scheduler"),
    ("job-pump", "scheduler"),
    ("llm-engine", "engine"),
    ("serve-", "serve"),
    ("router-", "serve"),
    ("rt-rdt", "object-store"),
    ("data-", "pipeline"),
    ("streaming-", "pipeline"),
    ("actor-", "user"),
)

_STDLIB_DIR = sysconfig.get_paths().get("stdlib", "") or "<none>"
_SEP = os.sep


def _norm(path: str) -> str:
    return path.replace("\\", "/")


def classify_frames(filenames: Iterable[str],
                    thread_name: str = "") -> str:
    """Subsystem for one stack given its frame filenames LEAF FIRST."""
    for fn in filenames:
        nfn = _norm(fn)
        idx = nfn.rfind("ray_tpu/")
        if idx >= 0:
            sub = nfn[idx:]
            for fragment, bucket in _FRAME_BUCKETS:
                if sub.startswith(fragment):
                    return bucket
            return "user"
        if fn.startswith(_STDLIB_DIR) or fn.startswith("<"):
            continue  # stdlib / builtin frame: keep walking rootward
        if "site-packages" in nfn or "dist-packages" in nfn:
            continue  # third-party (jax/numpy): attribute to the caller
        return "user"  # a genuine user source file
    name = thread_name or ""
    for fragment, bucket in _THREAD_BUCKETS:
        if name.startswith(fragment) or fragment in name:
            return bucket
    return "other"


def _frame_label(frame) -> str:
    code = frame.f_code
    fn = _norm(code.co_filename)
    idx = fn.rfind("ray_tpu/")
    if idx >= 0:
        mod = fn[idx:-3] if fn.endswith(".py") else fn[idx:]
    else:
        mod = os.path.basename(fn)
        if mod.endswith(".py"):
            mod = mod[:-3]
    return f"{mod}:{code.co_name}"


_MAX_DEPTH = 64


def sample_stacks(
    skip_idents: Optional[Iterable[int]] = None,
) -> List[Tuple[str, str]]:
    """One snapshot of every live thread: ``(folded_stack, subsystem)``
    per thread, stack root-first as ``thread;mod:func;...;leaf``."""
    skip = set(skip_idents or ())
    names = {t.ident: t.name for t in threading.enumerate()}
    out: List[Tuple[str, str]] = []
    for ident, frame in sys._current_frames().items():
        if ident in skip:
            continue
        name = names.get(ident, f"tid-{ident}")
        labels: List[str] = []
        files: List[str] = []  # leaf first
        depth = 0
        while frame is not None and depth < _MAX_DEPTH:
            labels.append(_frame_label(frame))
            files.append(frame.f_code.co_filename)
            frame = frame.f_back
            depth += 1
        labels.reverse()  # root first for folding
        folded = name + ";" + ";".join(labels) if labels else name
        out.append((folded, classify_frames(files, name)))
    return out


def sample_subsystems(
    skip_idents: Optional[Iterable[int]] = None,
) -> Dict[str, int]:
    """Classification-only snapshot: subsystem -> thread count. The
    continuous sampler's per-tick path — skips the folded-label string
    work ``sample_stacks`` pays, and the lazy filename walk stops at
    the first frame that classifies (most stacks resolve in 1-2
    frames), which is what keeps always-on mode under 1% of a core."""
    skip = set(skip_idents or ())
    names = {t.ident: t.name for t in threading.enumerate()}

    def walk(frame):
        depth = 0
        while frame is not None and depth < _MAX_DEPTH:
            yield frame.f_code.co_filename
            frame = frame.f_back
            depth += 1

    out: Dict[str, int] = {}
    for ident, frame in sys._current_frames().items():
        if ident in skip:
            continue
        sub = classify_frames(walk(frame), names.get(ident, ""))
        out[sub] = out.get(sub, 0) + 1
    return out


def capture(duration_s: float = 5.0, hz: float = 99.0) -> Dict[str, Any]:
    """Sample this process for ``duration_s`` at ``hz`` and return the
    aggregated profile. Duration is clamped to
    ``profiler_max_duration_s`` server-side so an RPC caller can never
    pin a dispatcher thread indefinitely."""
    duration_s = min(max(float(duration_s), 0.05),
                     float(config.profiler_max_duration_s))
    hz = min(max(float(hz), 1.0), 1000.0)
    period = 1.0 / hz
    folded: Dict[str, int] = {}
    subsystems: Dict[str, int] = {}
    samples = 0
    ticks = 0
    me = {threading.get_ident()}
    t_start = time.monotonic()
    deadline = t_start + duration_s
    while True:
        t0 = time.monotonic()
        if t0 >= deadline:
            break
        for stack, subsystem in sample_stacks(skip_idents=me):
            folded[stack] = folded.get(stack, 0) + 1
            subsystems[subsystem] = subsystems.get(subsystem, 0) + 1
            samples += 1
        ticks += 1
        rest = min(period - (time.monotonic() - t0),
                   deadline - time.monotonic())
        if rest > 0:
            time.sleep(rest)
    return {
        "pid": os.getpid(),
        "token": PROCESS_TOKEN,
        "duration_s": duration_s,
        "hz": hz,
        "ticks": ticks,
        "samples": samples,
        "folded": folded,
        "subsystems": subsystems,
    }


def merge(profiles: Iterable[Optional[Dict[str, Any]]]) -> Dict[str, Any]:
    """Combine per-process capture replies into one fleet profile,
    deduping by per-process token (single-node init shares one process
    between head, agent and driver — each answers the fan-out)."""
    seen: set = set()
    folded: Dict[str, int] = {}
    subsystems: Dict[str, int] = {}
    pids: List[int] = []
    samples = 0
    ticks = 0
    for p in profiles:
        if not p:
            continue
        tok = p.get("token")
        if tok and tok in seen:
            continue
        if tok:
            seen.add(tok)
        pids.append(int(p.get("pid", -1)))
        samples += int(p.get("samples", 0))
        ticks += int(p.get("ticks", 0))
        for k, v in (p.get("folded") or {}).items():
            folded[k] = folded.get(k, 0) + int(v)
        for k, v in (p.get("subsystems") or {}).items():
            subsystems[k] = subsystems.get(k, 0) + int(v)
    return {
        "processes": len(pids),
        "pids": pids,
        "samples": samples,
        "ticks": ticks,
        "folded": folded,
        "subsystems": subsystems,
    }


def subsystem_rows(
    subsystems: Dict[str, int],
) -> List[Tuple[str, int, float]]:
    """``(subsystem, samples, pct)`` rows sorted by share, descending."""
    total = sum(subsystems.values()) or 1
    return [
        (name, n, 100.0 * n / total)
        for name, n in sorted(
            subsystems.items(), key=lambda kv: (-kv[1], kv[0])
        )
    ]


def subsystem_table(subsystems: Dict[str, int]) -> str:
    rows = subsystem_rows(subsystems)
    if not rows:
        return "(no samples)"
    width = max(len(r[0]) for r in rows)
    lines = [f"{'SUBSYSTEM':<{width}}  {'SAMPLES':>8}  {'%':>6}"]
    for name, n, pct in rows:
        lines.append(f"{name:<{width}}  {n:>8}  {pct:>5.1f}%")
    return "\n".join(lines)


def folded_text(folded: Dict[str, int]) -> str:
    """flamegraph.pl-compatible folded-stacks text (``stack count``)."""
    return "\n".join(
        f"{stack} {count}"
        for stack, count in sorted(
            folded.items(), key=lambda kv: (-kv[1], kv[0])
        )
    )


# --- flamegraph rendering (self-contained HTML, no JS deps) ----------------

_FG_COLORS = (
    "#e4574c", "#e8803f", "#ecae3b", "#c7c23e", "#8fbf4a",
    "#56b063", "#3fa98c", "#3f9cab", "#4a7fc1", "#7a6ccc",
)
_FG_ROW_PX = 17
_FG_MIN_FRAC = 0.0015  # nodes narrower than 0.15% are dropped


def _fg_color(label: str) -> str:
    return _FG_COLORS[hash(label) % len(_FG_COLORS)]


def flamegraph_html(folded: Dict[str, int],
                    title: str = "ray_tpu profile") -> str:
    """Render folded stacks as a static flamegraph: one absolutely
    positioned div per frame, width proportional to sample share, hover
    detail via the title attribute. Opens anywhere, no network."""
    total = sum(folded.values())
    root: Dict[str, Any] = {"n": total, "kids": {}}
    for stack, count in folded.items():
        node = root
        for part in stack.split(";"):
            kid = node["kids"].setdefault(part, {"n": 0, "kids": {}})
            kid["n"] += count
            node = kid
    divs: List[str] = []
    max_depth = 0

    def walk(node: Dict[str, Any], depth: int, x: float) -> None:
        nonlocal max_depth
        for label, kid in sorted(
            node["kids"].items(), key=lambda kv: (-kv[1]["n"], kv[0])
        ):
            frac = kid["n"] / total if total else 0.0
            if frac < _FG_MIN_FRAC:
                x += frac
                continue
            max_depth = max(max_depth, depth + 1)
            pct = 100.0 * frac
            esc = _html.escape(label)
            divs.append(
                f'<div class="f" title="{esc} — {kid["n"]} samples '
                f'({pct:.2f}%)" style="left:{100.0 * x:.3f}%;'
                f"top:{depth * _FG_ROW_PX}px;width:{pct:.3f}%;"
                f'background:{_fg_color(label)}">{esc}</div>'
            )
            walk(kid, depth + 1, x)
            x += frac

    walk(root, 0, 0.0)
    height = max(max_depth, 1) * _FG_ROW_PX
    esc_title = _html.escape(title)
    return f"""<!doctype html>
<html><head><meta charset="utf-8"><title>{esc_title}</title><style>
body{{font:13px sans-serif;margin:16px;background:#fff;color:#222}}
#fg{{position:relative;height:{height}px;border:1px solid #ddd}}
.f{{position:absolute;height:{_FG_ROW_PX - 1}px;overflow:hidden;
white-space:nowrap;font:11px monospace;color:#fff;
text-overflow:ellipsis;box-sizing:border-box;
border-right:1px solid rgba(255,255,255,.4);cursor:default}}
</style></head><body>
<h3>{esc_title}</h3>
<p>{total} samples · hover a frame for its share · width ∝ samples</p>
<div id="fg">{"".join(divs)}</div>
</body></html>
"""


# --- continuous mode -------------------------------------------------------

class ContinuousSampler(threading.Thread):
    """Low-rate per-process sampler feeding
    ``rt_profile_samples_total{subsystem}``. Tracks its own duty cycle
    (sampling time / wall time), so its overhead can be read without
    relying on A/B wall-clock noise."""

    def __init__(self, hz: float):
        super().__init__(name=SAMPLER_THREAD_NAME, daemon=True)
        self.hz = min(max(float(hz), 0.1), 1000.0)
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self.ticks = 0
        self.samples = 0
        self.busy_s = 0.0
        self.started_monotonic = time.monotonic()

    def run(self) -> None:
        from ray_tpu.observability import core_metrics

        period = 1.0 / self.hz
        me = {threading.get_ident()}
        while not self._stop.is_set():
            t0 = time.monotonic()
            batch = sample_subsystems(skip_idents=me)
            n = sum(batch.values())
            if core_metrics.ENABLED:
                for subsystem, count in batch.items():
                    core_metrics.profile_samples.inc(
                        count, tags={"subsystem": subsystem}
                    )
            busy = time.monotonic() - t0
            with self._lock:
                self.ticks += 1
                self.samples += n
                self.busy_s += busy
            self._stop.wait(max(period - busy, 0.001))

    def stop(self) -> None:
        self._stop.set()

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            wall = time.monotonic() - self.started_monotonic
            duty = self.busy_s / wall if wall > 0 else 0.0
            return {
                "hz": self.hz,
                "ticks": self.ticks,
                "samples": self.samples,
                "busy_s": self.busy_s,
                "wall_s": wall,
                "duty_pct": 100.0 * duty,
            }


_continuous: Optional[ContinuousSampler] = None
_continuous_lock = threading.Lock()


def maybe_start_continuous() -> Optional[ContinuousSampler]:
    """Start the per-process continuous sampler if configured
    (``RT_PROFILER_HZ`` > 0 and observability on). Idempotent."""
    global _continuous
    if not ENABLED:
        return None
    hz = float(config.profiler_hz)
    if hz <= 0:
        return None
    with _continuous_lock:
        if _continuous is not None and _continuous.is_alive():
            return _continuous
        from ray_tpu.observability import core_metrics

        sampler = ContinuousSampler(hz)
        sampler.start()
        _continuous = sampler
        if core_metrics.ENABLED:
            core_metrics.profiler_continuous_hz.set(sampler.hz)
        return sampler


def stop_continuous() -> None:
    global _continuous
    with _continuous_lock:
        if _continuous is not None:
            _continuous.stop()
            _continuous = None


def continuous_status() -> Dict[str, Any]:
    """For ``rt top``/bench: the in-process sampler state."""
    with _continuous_lock:
        sampler = _continuous
    if sampler is None or not sampler.is_alive():
        return {"running": False, "hz": 0.0}
    out = sampler.stats()
    out["running"] = True
    return out


def set_enabled(on: bool) -> None:
    global ENABLED
    ENABLED = bool(on)
    config.set("observability_enabled", bool(on))
