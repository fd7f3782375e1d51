"""Task lifecycle span stamping.

Parity target: the reference's task state transitions
(PENDING_ARGS_AVAIL → SUBMITTED_TO_WORKER → RUNNING → FINISHED) recorded
by task_event_buffer.cc and surfaced through `ray timeline` / the state
API. Here, owner-side lifecycle instants ("submitted", "lease_granted",
"dispatched") and executor-side execution slices share one bounded ring
per worker (CoreWorker._task_events); ``state.timeline()`` joins them by
task_id into Chrome-trace flow events across pids and
``state.task_summary()`` turns them into queue-wait / exec percentiles.

On top of the task lifecycle, the same ring carries:

- request spans (``"type": "request"``) — one per component a serve
  request crosses (proxy / router / replica / engine), all sharing the
  trace id minted at HTTP ingress (``x-rt-trace-id``), joined by
  ``state.timeline()`` into one cross-pid flow and rolled up by
  ``state.request_summary()``;
- pipeline slices (``"type": "pipeline"``) — per-stage fwd / bwd / idle
  slices from the compiled-pipeline exec loop, plus a per-step summary
  carrying the computed bubble fraction;
- collective spans (``"type": "collective"``) — one per host collective
  op, so the bytes counters in core_metrics get a timeline counterpart.

Beside the ring, ``span()`` puts a named host span into the profiler's
own trace (``jax.profiler.TraceAnnotation``), on the same clock as the
device operations; the ``ts_us`` argument of an ``rt/engine/round`` span
relates that clock to the ring's.

Timestamps: every stamp uses ``now_us()`` — a per-process wall-clock
anchor recorded ONCE at import plus a monotonic delta — so intra-run
ordering (and cross-pid joins within one run) survives NTP steps
mid-run. Different processes may disagree by their boot-time clock skew,
but no process's stamps ever jump backwards.

Hot-path contract: callers guard with the module-level ``ENABLED`` flag
(``if tracing.ENABLED: ...``) so ``RT_TRACE_EVENTS=0`` reduces every
stamp site to one attribute check — no dict building, no time syscall.

Import discipline: only ``ray_tpu.utils.*`` imports allowed here.
"""

from __future__ import annotations

import contextlib
import os
import sys
import time
import uuid
from typing import Any, Dict, Optional

from ray_tpu.utils.config import config

ENABLED = bool(config.trace_events)

# Lifecycle event phases (the "type": "lifecycle" events in the ring;
# executor execution slices carry no "type" key — the legacy shape).
SUBMITTED = "submitted"
LEASE_GRANTED = "lease_granted"
DISPATCHED = "dispatched"

# Request span components, in request order. The proxy mints the trace
# id; every downstream component reads it from the request headers under
# TRACE_HEADER and stamps its own span.
TRACE_HEADER = "x-rt-trace-id"
PROXY = "proxy"
ROUTER = "router"
REPLICA = "replica"
ENGINE = "engine"
# phases of the engine's leg, children of its span, tiling it: enqueued
# -> pages reserved -> first token sampled -> done
ENGINE_QUEUE = "engine.queue"
ENGINE_PREFILL = "engine.prefill"
ENGINE_DECODE = "engine.decode"

# Wall-clock anchor: recorded once per process so every later stamp is
# anchor + monotonic delta. An NTP step after import cannot reorder this
# process's events.
_WALL_ANCHOR = time.time() - time.monotonic()


def now_us() -> int:
    """Microsecond timestamp on the per-process monotonic-anchored
    wall clock."""
    return int((_WALL_ANCHOR + time.monotonic()) * 1e6)


def mono_us(t_monotonic: float) -> int:
    """Convert a ``time.monotonic()`` reading already taken by the
    caller onto the same anchored microsecond clock as ``now_us()``."""
    return int((_WALL_ANCHOR + t_monotonic) * 1e6)


def set_enabled(on: bool) -> None:
    global ENABLED
    ENABLED = bool(on)
    config.set("trace_events", bool(on))


_NO_SPAN = contextlib.nullcontext()


def span(name: str, **args: Any):
    """Context manager: a host span named ``name`` in the profiler's
    trace when this process has jax loaded, nothing otherwise. Never
    imports jax (proxy, router and load generators stay off it). With no
    profiler session open a ``TraceAnnotation`` is one flag check; with
    one open the span lands in the host plane of the same ``.xplane.pb``
    as the device operations."""
    if ENABLED:
        jax = sys.modules.get("jax")
        if jax is not None:
            return jax.profiler.TraceAnnotation(name, **args)
    return _NO_SPAN


def new_trace_id() -> str:
    """Mint a trace id at HTTP ingress (proxy)."""
    return uuid.uuid4().hex[:16]


def lifecycle_event(
    phase: str,
    task_id: str,
    name: str,
    worker_address: str,
    target: Optional[str] = None,
) -> Dict[str, Any]:
    """Build one lifecycle instant. Callers append it to their worker's
    event ring (CoreWorker._append_task_event)."""
    evt = {
        "type": "lifecycle",
        "phase": phase,
        "task_id": task_id,
        "name": name,
        "ts_us": now_us(),
        "worker": worker_address,
        "pid": os.getpid(),
    }
    if target is not None:
        evt["target"] = target
    return evt


def request_span(
    trace_id: str,
    component: str,
    deployment: str,
    ts_us: int,
    dur_us: int,
    worker_address: str = "",
    parent: Optional[str] = None,
    **extra: Any,
) -> Dict[str, Any]:
    """Build one request span (proxy/router/replica/engine leg of a
    serve request, or a phase of the engine's leg). ``ts_us`` comes from
    ``now_us()`` taken at span start; ``parent`` names the component
    whose span of the same trace encloses this one; extras (e.g.
    queue_us, status) ride along untyped."""
    evt = {
        "type": "request",
        "trace_id": trace_id,
        "component": component,
        "deployment": deployment,
        "ts_us": ts_us,
        "dur_us": dur_us,
        "worker": worker_address,
        "pid": os.getpid(),
    }
    if parent is not None:
        evt["parent"] = parent
    if extra:
        evt.update(extra)
    return evt


def pipeline_slice(
    stage: int,
    kind: str,
    ts_us: int,
    dur_us: int,
    step: int,
    microbatch: Optional[int] = None,
    worker_address: str = "",
    **extra: Any,
) -> Dict[str, Any]:
    """Build one compiled-pipeline stage slice. ``kind`` is one of
    "fwd" / "bwd" / "idle" / "step" (the per-step summary, which carries
    bubble_frac and schedule in extras)."""
    evt = {
        "type": "pipeline",
        "stage": stage,
        "kind": kind,
        "ts_us": ts_us,
        "dur_us": dur_us,
        "step": step,
        "worker": worker_address,
        "pid": os.getpid(),
    }
    if microbatch is not None:
        evt["microbatch"] = microbatch
    if extra:
        evt.update(extra)
    return evt


def collective_span(
    op: str,
    ts_us: int,
    dur_us: int,
    nbytes: int = 0,
    worker_address: str = "",
    **extra: Any,
) -> Dict[str, Any]:
    """Build one host-collective op span for the timeline (the byte and
    latency *metrics* are core_metrics' job; this is the trace slice)."""
    evt = {
        "type": "collective",
        "op": op,
        "ts_us": ts_us,
        "dur_us": dur_us,
        "nbytes": nbytes,
        "worker": worker_address,
        "pid": os.getpid(),
    }
    if extra:
        evt.update(extra)
    return evt


def emit(evt: Dict[str, Any]) -> None:
    """Append a pre-built event to this process's worker event ring, if
    a worker exists. Import-at-use keeps the utils-only import
    discipline for module import time."""
    from ray_tpu.core import worker as _worker_mod

    w = _worker_mod.global_worker_or_none()
    if w is not None:
        w._append_task_event(evt)
