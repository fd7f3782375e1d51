"""TPU accelerator discovery + visibility management.

Parity: the reference's TPUAcceleratorManager
(python/ray/_private/accelerators/tpu.py:291): chip-count discovery, GCE
metadata pod-type/topology/worker-id detection (:450-563), the
``TPU_VISIBLE_CHIPS`` visibility env, and the per-pod-type head resource
used for whole-slice gang scheduling (util/tpu.py:225,460).

Discovery order for chip count:
  1. RT_NUM_TPUS (explicit override; the config.num_tpus dynamic flag)
  2. TPU_VISIBLE_CHIPS env (visibility restriction)
  3. /dev/accel* or /dev/vfio/<group> device files (local chips)
None found → 0 (CPU-only node). The count is what this host exposes as
device files: no JAX backend is initialised (the agent must stay off the
chip) and nothing is asked of the network. On the v5e sandbox machines
the PCI bus lists all four Google functions of the board while only the
leased chips have a /dev/vfio group, so the device files are the count.

A chip belongs to one process at a time. The node agent hands chip ids
to the workers it spawns (``visible_chips_env``), and a worker that was
leased chips checks what JAX found before computing
(``require_leased_platform``).

The RT_* overrides ride utils/config dynamic flags (re-read per call:
per-host inventory, never shipped in config snapshots). The TPU_* and
JAX_* names are external contracts with the TPU runtime and stay raw
env reads.
"""

from __future__ import annotations

import glob
import os
import urllib.request
from typing import Dict, Mapping, Optional, Sequence

from ray_tpu.utils.config import config

_GCE_METADATA_URL = "http://metadata.google.internal/computeMetadata/v1/instance/attributes/"

# chips per host for common TPU VM generations
_CHIPS_PER_HOST = {
    "v2": 4, "v3": 4, "v4": 4, "v5litepod": 4, "v5e": 4, "v5p": 4, "v6e": 4,
}

TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
NUM_TPUS_ENV = "RT_NUM_TPUS"

# Persistent XLA compile cache of the chip-holding processes. JAX reads
# JAX_COMPILATION_CACHE_DIR itself; where the outside did not set it,
# tpu workers get this one fixed directory inside the checkout (the
# path is part of the cache key, so it must never move with a session,
# a pid or the clock).
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def _metadata(key: str) -> Optional[str]:
    if os.environ.get("TPU_SKIP_MDS_QUERY"):
        return None
    try:
        req = urllib.request.Request(
            _GCE_METADATA_URL + key, headers={"Metadata-Flavor": "Google"}
        )
        with urllib.request.urlopen(req, timeout=0.5) as resp:
            return resp.read().decode()
    except Exception:
        return None


class TPUAcceleratorManager:
    """Static discovery/visibility helpers (mirrors the reference's API)."""

    @staticmethod
    def get_resource_name() -> str:
        return "TPU"

    @staticmethod
    def get_visible_accelerator_ids_env_var() -> str:
        return TPU_VISIBLE_CHIPS_ENV

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        explicit = config.num_tpus
        if explicit != "":
            return int(explicit)
        visible = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
        if visible:
            return len([c for c in visible.split(",") if c.strip()])
        return len(glob.glob("/dev/accel*") + glob.glob("/dev/vfio/[0-9]*"))

    @staticmethod
    def get_current_pod_type() -> Optional[str]:
        """e.g. 'v5litepod-16' — the accelerator-type of the slice."""
        env = config.tpu_pod_type
        if env:
            return env
        return _metadata("accelerator-type")

    @staticmethod
    def get_current_topology() -> Optional[str]:
        env = config.tpu_topology
        if env:
            return env
        return _metadata("tpu-env") and _parse_tpu_env("TOPOLOGY") or None

    @staticmethod
    def get_current_worker_id() -> Optional[int]:
        env = config.tpu_worker_id
        if env != "":
            return int(env)
        wid = _metadata("agent-worker-number")
        if wid is not None:
            try:
                return int(wid)
            except ValueError:
                return None
        return None

    @staticmethod
    def num_workers_in_slice(pod_type: str) -> int:
        """Hosts in a slice, from the pod type (e.g. v5litepod-16 → 4 hosts)."""
        try:
            gen, chips = pod_type.rsplit("-", 1)
            per_host = _CHIPS_PER_HOST.get(gen.split("_")[0], 4)
            return max(1, int(chips) // per_host)
        except (ValueError, KeyError):
            return 1


def _parse_tpu_env(key: str) -> Optional[str]:
    raw = _metadata("tpu-env")
    if not raw:
        return None
    try:
        for line in raw.splitlines():
            if line.startswith(key):
                return line.split(":", 1)[1].strip().strip("'\"")
    except Exception:
        return None
    return None


def tpu_allowed_by_env(env: Mapping[str, str]) -> bool:
    """False when JAX_PLATFORMS is set and leaves the TPU out (tier-1
    and cpu workers say ``cpu``): the one explicit way to keep an entry
    point off the chip."""
    platforms = env.get("JAX_PLATFORMS", "").strip()
    return not platforms or "tpu" in platforms.split(",")


def visible_chips_env(chip_ids: Sequence[int], host_chips: int) -> Dict[str, str]:
    """Spawn environment that shows libtpu exactly ``chip_ids`` of this
    host's ``host_chips`` chips. A subset also needs the process bounds:
    without them libtpu expects the whole host topology and refuses to
    start (the old TPU_*_HOST_BOUNDS names are set too, because TPU VM
    images export them with the host's values)."""
    env = {TPU_VISIBLE_CHIPS_ENV: ",".join(str(c) for c in chip_ids)}
    if len(chip_ids) < host_chips:
        if len(chip_ids) != 1:
            raise ValueError(
                f"a worker takes 1 chip or all {host_chips} of its host, "
                f"not {len(chip_ids)}"
            )
        for name in ("TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS",
                     "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS"):
            env[name] = "1,1,1"
    return env


def compile_cache_env(base_env: Mapping[str, str]) -> Dict[str, str]:
    """Compile-cache variables for a chip-holding worker: nothing where
    the outside already placed the cache, the fixed in-checkout
    directory otherwise. Every compile is cached (JAX's default skips
    those under a second) so that a second run adds no entries."""
    env = {"JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    if not base_env.get(COMPILE_CACHE_ENV):
        env[COMPILE_CACHE_ENV] = DEFAULT_COMPILE_CACHE_DIR
    return env


def require_leased_platform() -> None:
    """Raise unless a worker that was leased chips computes on them.

    Called at start-up by the processes that hold chips (LLM replicas,
    train workers). A ``tpu``-kind worker whose JAX came up on another
    platform must fail here, by name, rather than answer from the CPU."""
    from ray_tpu.core import worker as worker_mod

    w = worker_mod.global_worker_or_none()
    if w is None or getattr(w, "worker_kind", "cpu") != "tpu":
        return
    import jax

    leased = os.environ.get(TPU_VISIBLE_CHIPS_ENV, "")
    devices = jax.local_devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"worker was leased TPU chip(s) {leased or '?'} but JAX found "
            f"platform {devices[0].platform!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
        )
    chips = {tuple(d.coords) for d in devices}
    if leased and len(chips) != len(leased.split(",")):
        raise RuntimeError(
            f"worker was leased TPU chip(s) {leased} but JAX found "
            f"{len(chips)} chip(s) ({len(devices)} devices)"
        )


def get_tpu_coordinator_env_vars(
    coordinator_address: str, num_slices: int, slice_id: int
) -> dict:
    """MEGASCALE env for DCN multislice meshes.

    Parity: ray.util.tpu.get_tpu_coordinator_env_vars (util/tpu.py:198) —
    the env that makes XLA build a hierarchical ICI(inner)/DCN(outer) mesh.
    """
    return {
        "MEGASCALE_COORDINATOR_ADDRESS": coordinator_address,
        "MEGASCALE_NUM_SLICES": str(num_slices),
        "MEGASCALE_SLICE_ID": str(slice_id),
        "MEGASCALE_PORT": "8081",
    }
