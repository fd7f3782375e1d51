"""User-facing metrics API: Counter / Gauge / Histogram.

Parity: ray.util.metrics (reference python/ray/util/metrics.py:42).
Metrics register in a per-process registry; any process serves its
snapshot over the worker RPC (rpc_get_metrics) and the state API
aggregates across the cluster — the role the reference's OpenCensus →
dashboard-agent → Prometheus pipeline plays, without the Prometheus
dependency (a /metrics text formatter is provided for scraping).
"""

from __future__ import annotations

import bisect
import threading
import uuid
from typing import Callable, Dict, List, Optional, Sequence, Tuple

_lock = threading.Lock()
_registry: Dict[str, "_Metric"] = {}
# Modules holding module-level instrument references (e.g. the built-in
# core metrics) register a hook to re-create them after a registry wipe
# — a wiped registry would otherwise silently detach their instruments.
_reset_hooks: List[Callable[[], None]] = []

# Per-process identity for deduplicating scrapes: the head runs control
# store + node agent + driver in ONE process, so state.cluster_metrics
# must not sum that registry three times when it polls all three
# addresses.
PROCESS_TOKEN = uuid.uuid4().hex

_DEFAULT_BOUNDARIES = (
    0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 50.0, 100.0,
)


class _Metric:
    kind = "metric"

    def __init__(self, name: str, description: str = "",
                 tag_keys: Sequence[str] = ()):
        self.name = name
        self.description = description
        self.tag_keys = tuple(tag_keys)
        self._lock = threading.Lock()
        # tag-value tuple -> value state
        self._series: Dict[Tuple[str, ...], object] = {}
        with _lock:
            existing = _registry.get(name)
            if existing is not None:
                if existing.kind != self.kind:
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{existing.kind}"
                    )
                if existing.tag_keys != self.tag_keys:
                    raise ValueError(
                        f"metric {name!r} already registered with tag_keys="
                        f"{existing.tag_keys}"
                    )
                self._validate_rereg(existing)
                # per-name singleton series: re-constructing a metric
                # (e.g. inside a task that runs repeatedly on one worker)
                # must accumulate into the SAME series, not reset it
                self._series = existing._series
                self._lock = existing._lock
            else:
                _registry[name] = self

    def _validate_rereg(self, existing: "_Metric") -> None:
        """Kind-specific compatibility check on re-registration."""

    def _key(self, tags: Optional[Dict[str, str]]) -> Tuple[str, ...]:
        tags = tags or {}
        return tuple(str(tags.get(k, "")) for k in self.tag_keys)

    def snapshot(self) -> Dict:
        raise NotImplementedError


class Counter(_Metric):
    kind = "counter"

    def inc(self, value: float = 1.0,
            tags: Optional[Dict[str, str]] = None) -> None:
        if value < 0:
            raise ValueError("counters only increase")
        k = self._key(tags)
        with self._lock:
            self._series[k] = self._series.get(k, 0.0) + value

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "kind": self.kind,
                "description": self.description,
                "tag_keys": self.tag_keys,
                "series": {k: v for k, v in self._series.items()},
            }


class Gauge(_Metric):
    kind = "gauge"

    def set(self, value: float, tags: Optional[Dict[str, str]] = None) -> None:
        with self._lock:
            self._series[self._key(tags)] = float(value)

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "kind": self.kind,
                "description": self.description,
                "tag_keys": self.tag_keys,
                "series": {k: v for k, v in self._series.items()},
            }


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name: str, description: str = "",
                 boundaries: Sequence[float] = _DEFAULT_BOUNDARIES,
                 tag_keys: Sequence[str] = ()):
        self.boundaries = tuple(sorted(boundaries))
        # observe() sits on the RPC hot path: bisect over this prebuilt
        # list instead of rebuilding list(self.boundaries) per call
        self._bounds_list = list(self.boundaries)
        super().__init__(name, description, tag_keys)

    def _validate_rereg(self, existing: "_Metric") -> None:
        # a singleton's bucket arrays are sized for its boundaries —
        # adopting them under different boundaries would misbin counts
        if existing.boundaries != self.boundaries:
            raise ValueError(
                f"histogram {self.name!r} already registered with "
                f"boundaries={existing.boundaries}"
            )

    def observe(self, value: float,
                tags: Optional[Dict[str, str]] = None) -> None:
        k = self._key(tags)
        with self._lock:
            state = self._series.get(k)
            if state is None:
                state = {
                    "buckets": [0] * (len(self.boundaries) + 1),
                    "sum": 0.0,
                    "count": 0,
                }
                self._series[k] = state
            idx = bisect.bisect_left(self._bounds_list, value)
            state["buckets"][idx] += 1
            state["sum"] += value
            state["count"] += 1

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "kind": self.kind,
                "description": self.description,
                "tag_keys": self.tag_keys,
                "boundaries": self.boundaries,
                "series": {
                    k: dict(v, buckets=list(v["buckets"]))
                    for k, v in self._series.items()
                },
            }


def hist_quantile(
    bounds: Sequence[float],
    buckets: Sequence[float],
    q: float,
) -> Optional[float]:
    """Bucket-interpolated quantile from a (merged) histogram series:
    linear interpolation within the bucket holding the rank, Prometheus
    ``histogram_quantile`` style. None when bucket detail was dropped
    (divergent boundaries across workers) or the series is empty.

    The single shared implementation — state rollups, the ``rt top``
    renderer, the metrics-history store, and the alert engine all
    interpolate identically, so a client-vs-server percentile
    comparison never diverges on interpolation math.
    """
    total = sum(buckets)
    if not bounds or not total:
        return None
    rank = q * total
    cum = 0.0
    lo = 0.0
    for i, n in enumerate(buckets):
        hi = bounds[i] if i < len(bounds) else bounds[-1]
        if n and cum + n >= rank:
            return lo + (hi - lo) * ((rank - cum) / n)
        cum += n
        lo = hi
    return bounds[-1]


def hist_fraction_above(
    bounds: Sequence[float],
    buckets: Sequence[float],
    threshold: float,
) -> Optional[float]:
    """Fraction of observations above ``threshold``, interpolated within
    the bucket the threshold falls in (the SLO burn-rate numerator:
    "what share of requests exceeded the target"). None on an empty
    series or dropped bucket detail."""
    total = sum(buckets)
    if not bounds or not total:
        return None
    above = 0.0
    lo = 0.0
    for i, n in enumerate(buckets):
        hi = bounds[i] if i < len(bounds) else float("inf")
        if threshold <= lo:
            above += n
        elif threshold < hi and hi != float("inf"):
            # threshold splits this bucket: assume uniform density
            above += n * (hi - threshold) / (hi - lo)
        elif threshold < hi:
            # overflow bucket has no upper edge: no interpolation basis,
            # count the whole bucket as above (pessimistic)
            above += n
        lo = hi
    return min(above / total, 1.0)


def snapshot_all() -> Dict[str, Dict]:
    with _lock:
        metrics = list(_registry.values())
    return {m.name: m.snapshot() for m in metrics}


def _escape_label_value(v: str) -> str:
    """Prometheus exposition escaping for label values: backslash, double
    quote, and line feed must be escaped or the line is unparseable."""
    return (
        str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
    )


def _escape_help(s: str) -> str:
    """HELP text escaping per the exposition spec: backslash and LF."""
    return str(s).replace("\\", "\\\\").replace("\n", "\\n")


def prometheus_text(snapshots: Dict[str, Dict]) -> str:
    """Render aggregated snapshots in Prometheus exposition format."""
    lines: List[str] = []
    for name, snap in sorted(snapshots.items()):
        lines.append(
            f"# HELP {name} {_escape_help(snap.get('description', ''))}"
        )
        kind = snap["kind"]
        if kind == "histogram" and not snap.get("boundaries"):
            # bucket detail was dropped (divergent boundaries across
            # workers, state.cluster_metrics): only count/sum remain,
            # which is a summary, not a histogram
            kind = "summary"
        lines.append(f"# TYPE {name} {kind}")
        for tagvals, value in snap["series"].items():
            labels = ",".join(
                f'{k}="{_escape_label_value(v)}"'
                for k, v in zip(snap["tag_keys"], tagvals) if v
            )
            label_s = "{" + labels + "}" if labels else ""
            if snap["kind"] == "histogram":
                bounds = snap.get("boundaries", ())
                cum = 0
                for le, n in zip(list(bounds) + ["+Inf"], value["buckets"]):
                    cum += n
                    le_label = f'le="{le}"'
                    all_labels = f"{labels},{le_label}" if labels else le_label
                    lines.append(f"{name}_bucket{{{all_labels}}} {cum}")
                lines.append(f"{name}_count{label_s} {value['count']}")
                lines.append(f"{name}_sum{label_s} {value['sum']}")
            else:
                lines.append(f"{name}{label_s} {value}")
    return "\n".join(lines) + "\n"


def register_reset_hook(fn: Callable[[], None]) -> None:
    """Run fn after every registry reset (idempotent registration)."""
    if fn not in _reset_hooks:
        _reset_hooks.append(fn)


def _reset_for_tests() -> None:
    with _lock:
        _registry.clear()
    for fn in _reset_hooks:
        fn()
