"""What every generator needs around the program: where the compile
cache is, the cluster's counters as plain numbers, the phases of set-up.

The process that runs this never initialises a JAX backend: a chip
belongs to one process at a time, and the processes that compute are the
workers the node agent leases chips to.
"""

from __future__ import annotations

import errno
import functools
import glob
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, Iterable, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
# the longest a run waits for its chips, at its start and again at its
# end: a four-chip worker is gone 14-19 s after its SIGTERM, and 36 s was
# seen once (PERF.md section 6, PR 44)
CHIP_WAIT_LIMIT_S = 90.0
CHIP_WAIT_STEP_S = 0.5


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def find(bench: Dict[str, Any], kind: str, name: str, ext: str = ".json") -> str:
    """``<path>/<kind>/<name><ext>`` under the first of ``paths`` that has it."""
    tried = []
    for p in bench["paths"]:
        path = os.path.join(ROOT, p, kind, name + ext)
        if os.path.exists(path):
            return path
        tried.append(path)
    raise FileNotFoundError(f"no {kind} file for {name!r}: tried {tried}")


def module(bench: Dict[str, Any], kind: str, name: str):
    """The generator or reader ``name``, from whichever of ``paths`` holds it."""
    path = find(bench, kind, name, ".py")
    rel = os.path.relpath(path, ROOT)[: -len(".py")]
    return importlib.import_module(rel.replace(os.sep, "."))


@functools.lru_cache(maxsize=None)
def _module_at(path: str):
    name = "bench_family_" + os.path.basename(path)[: -len(".py")]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(path: Optional[str]):
    """The family adapter in the file at ``path``, which is what
    ``find(bench, "families", <the configuration's family>, ".py")`` gave
    the process that resolved the cell; None where the configuration names
    no family. Loaded from the file and not by a dotted name, because the
    replica's process is handed the path and imports ``benchmark`` from its
    own PYTHONPATH, which in a copied tree is another tree than the one the
    cell was resolved in. What a family file provides is listed in
    ``benchmark/families/gpt2.py``."""
    return _module_at(path) if path else None


def prepare_env() -> None:
    """Keep what the program writes under this run's own TMPDIR (the
    program's default is the fixed /tmp/ray_tpu, which two checkouts
    would share)."""
    tmp = os.environ.get("TMPDIR") or "/tmp"
    os.environ.setdefault("RT_TEMP_DIR", os.path.join(tmp, f"rt_bench_{os.getuid()}"))


def compile_cache_dir() -> str:
    from ray_tpu.accelerators import tpu as tpu_mod

    return os.environ.get(tpu_mod.COMPILE_CACHE_ENV) or tpu_mod.DEFAULT_COMPILE_CACHE_DIR


def cache_names() -> set:
    """The files of the compile cache: JAX names an entry after the
    program (``jit_prefill_paged-<key>``), so a name gained inside a
    window says what compiled there."""
    return {f for _, _, files in os.walk(compile_cache_dir()) for f in files}


def cache_entries() -> int:
    return len(cache_names())


def fresh_trace_dir() -> str:
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    os.makedirs(TRACE_DIR, exist_ok=True)
    return TRACE_DIR


def require_platform(platform: str, chips: int) -> None:
    """Exit non-zero, printing no result, unless this machine can give
    the cell its chips. A configuration that says ``"platform": "cpu"``
    (the rehearsal's) runs on the CPU and nowhere else."""
    from ray_tpu.accelerators import tpu as tpu_mod

    allowed = tpu_mod.tpu_allowed_by_env(os.environ)
    if platform == "cpu":
        if allowed:
            sys.exit("benchmark: a rehearsal configuration runs only under "
                     "an explicit JAX_PLATFORMS=cpu")
        return
    if not allowed:
        sys.exit(
            f"benchmark: this cell wants {chips} TPU chip(s), but JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r} keeps the program off them; "
            "no cell of BENCHMARK.json runs on the CPU"
        )
    found = tpu_mod.TPUAcceleratorManager.get_current_node_num_accelerators()
    if found < chips:
        sys.exit(f"benchmark: this cell wants {chips} TPU chip(s) and this "
                 f"machine exposes {found}")


def chip_files() -> List[str]:
    """The device files of this machine's chips: the list
    ``TPUAcceleratorManager.get_current_node_num_accelerators`` counts."""
    return sorted(glob.glob("/dev/accel*") + glob.glob("/dev/vfio/[0-9]*"))


def own_files() -> set:
    """What this process itself holds open, by path."""
    held = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            held.add(os.readlink(f"/proc/self/fd/{fd}"))
        except OSError:
            pass  # the descriptor that listed the directory is gone again
    return held


def open_and_close(path: str) -> None:
    os.close(os.open(path, os.O_RDWR))


def wait_for_chips(
    platform: str, when: str, *,
    files: Optional[Iterable[str]] = None, held: Optional[Iterable[str]] = None,
    opener: Callable[[str], None] = open_and_close,
    clock: Callable[[], float] = time.monotonic,
    sleep: Callable[[float], None] = time.sleep,
    limit_s: float = CHIP_WAIT_LIMIT_S,
) -> float:
    """Wait until every chip of this machine can be opened, and return
    the seconds that took by the clock. The process that held a chip
    before this run (a four-chip train worker) keeps its device file
    closed to everyone for 10-19 s after it was told to go, nothing in
    ``/proc/*/fd`` shows it, and a program that meets ``EBUSY`` there
    fails: only an ``open()`` can tell, so each file is opened and closed
    at once, and one that is busy is tried again every half second up to
    ``limit_s`` (a one-chip worker on its way out makes the ``open()``
    itself block for seconds instead). The wait is the machine's state
    and no part of any metric; when it runs out that is said and the run
    goes on. A rehearsal on the CPU opens nothing, a file this process
    holds itself is skipped, and a file that refuses for another reason
    than ``EBUSY`` is named and left alone (a wait changes nothing
    there)."""
    if platform != "tpu":
        return 0.0
    held = own_files() if held is None else set(held)
    left = []
    for path in chip_files() if files is None else files:
        if path in held:
            say(f"chips {when}: {path} is held by this process itself, not probed")
        else:
            left.append(path)
    n, t_start = len(left), clock()
    busy: Dict[str, float] = {}
    while left:
        for path in list(left):
            try:
                opener(path)
            except OSError as e:
                if e.errno == errno.EBUSY:
                    busy[path] = clock() - t_start
                    continue
                say(f"chips {when}: {path} cannot be probed ({e.strerror or e})")
            left.remove(path)
        if not left or clock() - t_start >= limit_s:
            break
        sleep(CHIP_WAIT_STEP_S)
    # by the clock, not by the sleeps: on one chip an open() that meets a
    # process still letting go blocks for seconds and then succeeds
    waited = clock() - t_start
    if not busy:
        say(f"chips {when}: {n} device file(s) probed, none busy, waited {waited:.2f}s")
        return waited
    seen = ", ".join(f"{p} last refused at {t:.2f}s" for p, t in sorted(busy.items()))
    say(f"chips {when}: waited {waited:.2f}s ({seen})"
        + (f"; STILL BUSY after the limit of {limit_s:g}s: {', '.join(left)}" if left else ""))
    return waited


def counters() -> Dict[str, Dict[str, float]]:
    """The cluster's metrics as ``{name: {"value"|"sum","count": x}}``,
    series of one metric added together."""
    from ray_tpu import state

    out: Dict[str, Dict[str, float]] = {}
    for name, m in state.cluster_metrics().items():
        if m["kind"] == "histogram":
            out[name] = {
                "sum": sum(s["sum"] for s in m["series"].values()),
                "count": sum(s["count"] for s in m["series"].values()),
            }
        else:
            out[name] = {"value": float(sum(m["series"].values()))}
    return out


class Phases:
    """Set-up phases on the host's clock, printed as they end."""

    def __init__(self, t_process_start: float):
        self.t0 = t_process_start
        self.marks: Dict[str, float] = {}
        self._last = t_process_start

    def mark(self, name: str, now: Optional[float] = None) -> float:
        now = time.time() if now is None else now
        self.marks[name] = now - self._last
        say(f"set-up: {name} {now - self._last:.2f}s (at {now - self.t0:.2f}s)")
        self._last = now
        return self.marks[name]

    def set(self, name: str, seconds: Any) -> None:
        if seconds is not None:
            self.marks[name] = float(seconds)
            say(f"set-up: {name} {float(seconds):.2f}s")


def with_memory(device: Dict[str, Any]) -> Dict[str, Any]:
    """Add the peak on the fullest chip, and its limit, to a device
    report, both from the allocator: the larger of its peak in use and
    its peak reserved. (A running program's temporaries are reserved, not
    "in use": a train step holds 1.9 GB in use and 14.5 GB reserved.)"""
    peaks = [max(m.get("peak_bytes_in_use", 0), m.get("peak_bytes_reserved", 0))
             for m in device["memory"]] or [0]
    limits = [m.get("bytes_limit", 0) for m in device["memory"]] or [0]
    device["memory_peak_bytes"] = int(max(peaks))
    device["memory_limit_bytes"] = int(max(limits))
    return device
