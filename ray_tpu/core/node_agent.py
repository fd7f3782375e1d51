"""Node agent — per-host daemon: scheduler, worker pool, object store host.

Parity: the raylet (reference src/ray/raylet/node_manager.h:140 —
HandleRequestWorkerLease :290), WorkerPool (worker_pool.h:280), the
placement-group resource manager (placement_group_resource_manager.h:57-64,
PREPARE/COMMIT bundle carve-outs as named pools), and the plasma store host
(the ShmObjectStore bookkeeping lives here; workers mmap segments
directly).

TPU-first: node resources include "TPU" chips and slice-topology labels
discovered by ray_tpu.accelerators (parity: the reference's accelerator
plugin python/ray/_private/accelerators/tpu.py:291 which models TPU as a
schedulable resource + "TPU-<pod_type>-head" marker).

Lease protocol (hot path, mirrors §3.2 of SURVEY.md):
  owner → lease_worker(resources, bundle?) →
    {"granted": True, worker_address, lease_id}                  (local grant)
  | {"granted": False, "spillback": "<other agent address>"}      (spill)
  owner pushes tasks directly to the worker, then release_worker(lease_id).
"""

from __future__ import annotations

import logging
import math
import os
import socket
import struct
import subprocess
import sys
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.core import scheduling
from ray_tpu.core.object_store import ShmObjectStore
from ray_tpu.observability import core_metrics, forensics, profiler
from ray_tpu.utils import serialization
from ray_tpu.utils.config import config
from ray_tpu.utils.ids import NodeID
from ray_tpu.utils.rpc import RpcClient, RpcError, RpcServer

logger = logging.getLogger(__name__)

# Tolerance for resource-counter comparisons. Fractional requests (PG
# bundles like {"CPU": 0.01}) are not exactly representable in binary
# floating point, so long allocate/credit churn leaves ~1e-13 dust per
# cycle in the availability counters; an exact >= would then starve
# whole-unit requests on an idle node.
_RES_EPS = 1e-9
# SIGTERM -> SIGKILL grace for a worker that holds chips
_TPU_TERM_GRACE_S = 10.0


def worker_spawn_env(base_env, kind: str, chips=(),
                     host_chips: int = 0) -> Dict[str, str]:
    """Which device a spawned worker may touch, as environment. A ``tpu``
    worker sees exactly the chips it was started for and keeps its
    compile cache where accelerators/tpu.py says; JAX_PLATFORMS is left
    as the outside set it. Every other worker is pinned to the CPU: with
    libtpu installed, a process that imports JAX without that would take
    a chip away from the worker that was leased it."""
    from ray_tpu.accelerators import tpu as tpu_mod

    env = dict(base_env)
    if kind == "tpu":
        env.update(tpu_mod.visible_chips_env(chips, host_chips))
        env.update(tpu_mod.compile_cache_env(env))
    else:
        env["JAX_PLATFORMS"] = "cpu"
    return env


class _Worker:
    __slots__ = ("worker_id", "address", "pid", "proc", "state", "lease_id",
                 "kind", "env_hash", "log_base", "chips")

    def __init__(self, worker_id, address, pid, proc, kind="cpu",
                 env_hash="", log_base="", chips=()):
        self.worker_id = worker_id
        self.address = address
        self.pid = pid
        self.proc = proc  # subprocess.Popen or None (external)
        self.state = "idle"  # idle | leased | dead
        self.lease_id: Optional[str] = None
        self.kind = kind  # "cpu" | "tpu"
        self.log_base = log_base  # stdout/.err capture path prefix
        # Pool is keyed by (kind, env_hash), the way the reference keys
        # its pool by language + runtime-env hash (worker_pool.h:280):
        # repeated use of one runtime env lands on warm workers that
        # already booted with it, and heterogeneous jobs never share a
        # process. "" = the default (no-env) pool.
        self.env_hash = env_hash
        # chip ids this process was started with (tpu kind only). They
        # stay taken until the PROCESS is gone, whatever its lease does.
        self.chips: Tuple[int, ...] = tuple(chips)


class NodeAgent:
    def __init__(
        self,
        control_address: str,
        session_id: str,
        resources: Optional[Dict[str, float]] = None,
        labels: Optional[Dict[str, str]] = None,
        host: str = "127.0.0.1",
        port: int = 0,
        temp_dir: Optional[str] = None,
    ):
        self.node_id = NodeID.from_random()
        self.session_id = session_id
        self.control_address = control_address
        self._server = RpcServer("node_agent", host, port)
        self._server.register_instance(self)
        # raw (in-connection-order) handlers: a worker's oneway seal must
        # land before the recycle that chases it, and the recycle before
        # the next create_object, all on the same connection — dispatched
        # handlers would race and the create would miss the parked pages
        # every time in a put/delete loop. Both are lock-only (never
        # block), so inline execution in the read loop is safe.
        self._server.register_raw("seal_object", self._raw_seal_object)
        self._server.register_raw("recycle_object", self._raw_recycle_object)
        self._server.on_disconnect = self._owner_conn_closed

        from ray_tpu.accelerators import detect_node_resources_and_labels

        auto_res, auto_labels = detect_node_resources_and_labels()
        self.resources_total: Dict[str, float] = dict(auto_res)
        if resources:
            self.resources_total.update(resources)
        self.labels = {**auto_labels, **(labels or {})}

        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self.resources_available = dict(self.resources_total)
        # pg_id -> {"state": prepared|committed, "bundles": {idx: res},
        #            "available": {idx: res}}
        self._bundles: Dict[str, Dict[str, Any]] = {}

        self._workers: Dict[str, _Worker] = {}  # worker_id hex -> record
        self._leases: Dict[str, Dict[str, Any]] = {}  # lease_id -> info
        # Chip ids no live process was started with, and pid -> ids for
        # the processes that were. An id leaves the free list when a tpu
        # worker is spawned for it and returns only once that process
        # has been reaped, so two holders of a chip never overlap.
        self._tpu_chips_free: List[int] = list(
            range(int(self.resources_total.get("TPU", 0)))
        )
        self._proc_chips: Dict[int, Tuple[int, ...]] = {}
        self._pending_spawns = 0
        # lease requests currently waiting for resources (the autoscaler's
        # demand signal, carried on heartbeats — reference: resource_load
        # in the syncer's node snapshots)
        self._pending_leases = 0
        # resource shapes recently starved for (shape key -> last seen):
        # heartbeats report entries younger than the TTL
        self._starved_shapes: Dict[tuple, float] = {}
        # short-TTL cluster-view cache for the spillback consult
        # (_pick_target_node) — one fetch serves a whole lease storm
        self._view_cache_lock = threading.Lock()
        self._view_cache: Tuple[float, Any] = (0.0, None)
        # versioned-sync counters (observability for the delta protocol)
        self._hb_full = 0
        self._hb_light = 0

        self.temp_dir = temp_dir or os.path.join(
            config.temp_dir, f"session_{session_id[:8]}"
        )
        os.makedirs(os.path.join(self.temp_dir, "logs"), exist_ok=True)

        self.store = ShmObjectStore(
            session_id,
            self.node_id.hex(),
            int(config.object_store_memory_mb) * 1024 * 1024,
        )

        from ray_tpu.core.ha import head_resolver

        self._control = RpcClient(
            control_address, name="agent->cs", resolver=head_resolver()
        )
        self._stopped = threading.Event()
        self._threads: List[threading.Thread] = []
        # Data-plane listener (object transfer): whole segments stream
        # over a raw TCP socket via sendfile — the control RPC stack never
        # carries bulk object bytes (parity: reference object manager's
        # dedicated data port, src/ray/object_manager/object_manager.h).
        self._data_sock: Optional[socket.socket] = None
        self.data_port = 0
        # True when this agent is the whole process (node_main): being
        # declared dead exits the process; in-head agents just stop.
        self.standalone = False

    @property
    def address(self) -> str:
        return self._server.address

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def start(self) -> None:
        self._server.start()
        self._start_data_server()
        reply = self._control.call(
            "register_node",
            node_info={
                "node_id": self.node_id.hex(),
                "address": self.address,
                "resources_total": self.resources_total,
                "labels": self.labels,
                "object_store_capacity": self.store.usage()[1],
            },
            retryable=True,
        )
        config.load_snapshot(reply["config_snapshot"])
        # Session-scoped crash dir: this process's faulthandler + black
        # box re-point here, and spawned workers inherit it via
        # RT_CRASH_DIR (boot crashes landed in the temp_dir default).
        os.environ["RT_CRASH_DIR"] = os.path.join(self.temp_dir, "crash")
        forensics.install(forensics.current_role() or "driver")
        profiler.maybe_start_continuous()
        t = threading.Thread(target=self._heartbeat_loop, name="agent-hb", daemon=True)
        t.start()
        self._threads.append(t)
        tm = threading.Thread(
            target=self._memory_monitor_loop, name="agent-oom", daemon=True
        )
        tm.start()
        self._threads.append(tm)
        for _ in range(int(config.worker_pool_prestart)):
            self._spawn_worker()

    def stop(self) -> None:
        self._stopped.set()
        with self._lock:
            workers = list(self._workers.values())
            self._workers.clear()
        for w in workers:
            self._terminate_worker(w)
        if self._data_sock is not None:
            try:
                # wake any thread blocked in accept(2) — close alone
                # leaves it parked on a reusable fd number (see
                # RpcServer.stop)
                self._data_sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                self._data_sock.close()
            except OSError:
                pass
        self._server.stop()
        self._control.close()
        self.store.shutdown()

    # ------------------------------------------------------------------
    # data plane: whole-segment streaming over a raw TCP port (parity:
    # reference object manager's dedicated data port + chunked transfer,
    # src/ray/object_manager/object_manager.h — here one request streams
    # the whole segment via sendfile; native/src/store_core.cpp pumps it,
    # os.sendfile is the fallback)
    # ------------------------------------------------------------------

    _DATA_LOST = 0xFFFFFFFFFFFFFFFF

    def _start_data_server(self) -> None:
        try:
            host = self.address.rsplit(":", 1)[0]
            sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            sock.bind((host, 0))
            sock.listen(64)
        except OSError as e:
            # port-restricted environment: the node stays fully functional
            # on the chunked-RPC path (data_port=0 advertises exactly that)
            logger.warning("data-plane listener unavailable: %s", e)
            self.data_port = 0
            return
        self._data_sock = sock
        self.data_port = sock.getsockname()[1]
        t = threading.Thread(
            target=self._data_accept_loop, name="agent-data", daemon=True
        )
        t.start()
        self._threads.append(t)

    def _data_accept_loop(self) -> None:
        while not self._stopped.is_set():
            try:
                conn, _ = self._data_sock.accept()
            except OSError:
                return
            threading.Thread(
                target=self._serve_data_conn, args=(conn,),
                name="agent-data-conn", daemon=True,
            ).start()

    def _serve_data_conn(self, conn: socket.socket) -> None:
        try:
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            hdr = self._recv_exact(conn, 4)
            if hdr is None:
                return
            (path_len,) = struct.unpack("<I", hdr)
            if path_len > 4096:
                return
            req = self._recv_exact(conn, path_len + 16)
            if req is None:
                return
            path = req[:path_len].decode()
            offset, length = struct.unpack("<QQ", req[path_len:])
            try:
                opened = self.store.open_for_read(path)
            except ValueError:
                opened = None
            if opened is None:
                conn.sendall(struct.pack("<Q", self._DATA_LOST))
                return
            fd, size = opened
            try:
                if offset >= size:
                    conn.sendall(struct.pack("<Q", 0))
                    return
                total = min(length, size - offset)
                conn.sendall(struct.pack("<Q", total))
                from ray_tpu import native as native_mod

                lib = native_mod.store_lib()
                if lib is not None:
                    sent = lib.rt_sendfile_full(
                        conn.fileno(), fd, offset, total
                    )
                    if sent != total:
                        return  # peer gone or file truncated: drop conn
                else:
                    off = offset
                    remaining = total
                    while remaining > 0:
                        n = os.sendfile(
                            conn.fileno(), fd, off, min(remaining, 1 << 22)
                        )
                        if n <= 0:
                            return
                        off += n
                        remaining -= n
            finally:
                os.close(fd)
        except OSError:
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    @staticmethod
    def _recv_exact(conn: socket.socket, n: int) -> Optional[bytes]:
        buf = b""
        while len(buf) < n:
            part = conn.recv(n - len(buf))
            if not part:
                return None
            buf += part
        return buf

    def rpc_get_data_port(self, conn):
        return self.data_port

    def _update_pool_gauge_locked(self) -> None:
        """Refresh rt_worker_pool_size{state=...,node=...} from the live
        pool."""
        if not core_metrics.ENABLED:
            return
        counts: Dict[str, int] = {"idle": 0, "leased": 0, "dead": 0}
        for w in self._workers.values():
            counts[w.state] = counts.get(w.state, 0) + 1
        counts["spawning"] = self._pending_spawns
        node = self.node_id.hex()[:8]
        for state, n in counts.items():
            core_metrics.worker_pool_size.set(
                n, tags={"state": state, "node": node}
            )

    def _heartbeat_loop(self) -> None:
        # Versioned resource-view sync (reference ray_syncer.h:91 delta
        # protocol): a heartbeat carries the full resource payload only
        # when it CHANGED since the last acked beat (or as a periodic
        # anti-entropy refresh); unchanged beats are a light liveness ping
        # with the last version, so steady-state control-plane traffic is
        # O(nodes), not O(nodes x resource-dict size).
        last_sent = None
        version = 0
        since_full = 0
        while not self._stopped.wait(config.health_check_period_s):
            with self._lock:
                if core_metrics.ENABLED:
                    self._update_pool_gauge_locked()
                avail = dict(self.resources_available)
                pending = self._pending_leases
                busy = len(self._leases)
                now = time.monotonic()
                for k, ts in list(self._starved_shapes.items()):
                    if now - ts > 5.0:
                        del self._starved_shapes[k]
                shapes = [dict(k) for k in self._starved_shapes]
            payload = (tuple(sorted(avail.items())), pending, busy,
                       tuple(tuple(sorted(s.items())) for s in shapes))
            unchanged = payload == last_sent and since_full < 30
            try:
                if unchanged:
                    since_full += 1
                    self._hb_light += 1
                    reply = self._control.call(
                        "heartbeat", node_id=self.node_id.hex(),
                        resources_available=None, timeout_s=5.0,
                        view_version=version,
                    )
                    if reply.get("reattach"):
                        # head restarted: re-assert our state (or die if
                        # the store has explicitly declared us dead)
                        if not self._reattach_to_head():
                            return
                        last_sent = None
                        continue
                    if reply.get("resync"):
                        last_sent = None  # store lost our view: full next
                    if not reply.get("ok"):
                        self._declared_dead()
                        return
                    continue
                version += 1
                since_full = 0
                self._hb_full += 1
                reply = self._control.call(
                    "heartbeat", node_id=self.node_id.hex(),
                    resources_available=avail, timeout_s=5.0,
                    pending_leases=pending, active_leases=busy,
                    extra={"pending_shapes": shapes}, view_version=version,
                )
                last_sent = payload
                if reply.get("reattach"):
                    if not self._reattach_to_head():
                        return
                    last_sent = None
                    continue
                if not reply.get("ok"):
                    self._declared_dead()
                    return
            except RpcError:
                # the beat may not have landed: resend a full view next
                last_sent = None

    def _declared_dead(self) -> None:
        """Declared dead by the control plane: our actors may already be
        restarting elsewhere. Tear down (killing all local workers) so no
        split-brain actor instance keeps serving (reference: raylets exit
        when GCS declares them dead)."""
        logger.warning("control store declared this node dead; shutting down")
        self.stop()
        if self.standalone:
            os._exit(1)

    def _reattach_to_head(self) -> bool:
        """Re-assert this node's full state with a restarted head (HA
        reconciliation; parity: raylet reconnect under GCS FT). Reports
        live leases (tagged owner-bound vs store-managed), committed PG
        bundles, and pooled workers; the store replies with orphaned
        store-managed leases to release. Returns False when the store
        refuses (we are declared dead) — the caller must exit."""
        with self._lock:
            leases = {
                lid: {"bound": info.get("conn_id") is not None}
                for lid, info in self._leases.items()
            }
            bundles = {
                pg_id: sorted(rec["bundles"])
                for pg_id, rec in self._bundles.items()
                if rec["state"] == "committed"
            }
            workers = [
                w.address for w in self._workers.values()
                if w.state != "dead"
            ]
            node_info = {
                "node_id": self.node_id.hex(),
                "address": self.address,
                "resources_total": dict(self.resources_total),
                "labels": dict(self.labels),
                "object_store_capacity": self.store.usage()[1],
            }
        try:
            reply = self._control.call(
                "reattach_node", node_info=node_info, leases=leases,
                bundles=bundles, workers=workers, retryable=True,
            )
        except RpcError:
            logger.warning("re-attach RPC failed; retrying on next beat")
            return True  # transient: keep heartbeating, reattach re-asked
        if not reply.get("ok"):
            self._declared_dead()
            return False
        config.load_snapshot(reply["config_snapshot"])
        self.control_address = self._control.address
        orphans = reply.get("release_leases") or []
        for lid in orphans:
            # store-managed leases no live actor references (the head died
            # mid-creation): kill the half-created worker so the actor's
            # reschedule cannot double-place
            try:
                self.rpc_release_worker(None, lid, kill=True)
            except Exception:  # noqa: BLE001 — cleanup path
                logger.exception("orphan lease %s release failed", lid[:8])
        logger.info(
            "re-attached to head at %s (%d leases kept, %d orphans "
            "released)", self._control.address, len(leases) - len(orphans),
            len(orphans),
        )
        return True

    # ------------------------------------------------------------------
    # memory monitor / OOM killer (reference C19: MemoryMonitor
    # src/ray/common/memory_monitor.h:56 + WorkerKillingPolicy
    # worker_killing_policy.h:33)
    # ------------------------------------------------------------------

    @staticmethod
    def _memory_usage_fraction() -> float:
        """Host memory usage in [0, 1]. Test hook: the
        testing_memory_usage config (>=0) overrides the real reading."""
        injected = float(config.testing_memory_usage)
        if injected >= 0:
            return injected
        try:
            info = {}
            with open("/proc/meminfo") as f:
                for line in f:
                    k, v = line.split(":", 1)
                    info[k] = int(v.strip().split()[0])
            total = info.get("MemTotal", 0)
            avail = info.get("MemAvailable", info.get("MemFree", 0))
            if total <= 0:
                return 0.0
            return 1.0 - avail / total
        except (OSError, ValueError):
            return 0.0

    def _memory_monitor_loop(self) -> None:
        period = float(config.memory_monitor_period_s)
        threshold = float(config.memory_usage_threshold)
        while not self._stopped.wait(period):
            if self._memory_usage_fraction() < threshold:
                continue
            # Kill policy (reference worker_killing_policy: prefer
            # retriable / newest): the most recently LEASED worker — its
            # task is the newest work and the most likely to be retried
            # cleanly; idle pool workers are reaped first of all.
            victim = None
            with self._lock:
                idle = [w for w in self._workers.values() if w.state == "idle"]
                if idle:
                    victim = idle[0]
                    self._workers.pop(victim.worker_id, None)
                elif self._leases:
                    newest_lease = next(reversed(self._leases))
                    info = self._leases.get(newest_lease)
                    victim = self._workers.get(info["worker_id"]) if info else None
            if victim is not None:
                logger.warning(
                    "memory pressure (%.0f%% used >= %.0f%%): killing "
                    "worker pid=%s",
                    self._memory_usage_fraction() * 100, threshold * 100,
                    victim.pid,
                )
                self._terminate_worker(victim)

    # ------------------------------------------------------------------
    # worker pool (reference C6)
    # ------------------------------------------------------------------

    def _spawn_worker(self, kind: str = "cpu", env_spec=None,
                      env_hash: str = "", slot_reserved: bool = False,
                      chips: Tuple[int, ...] = ()) -> None:
        """slot_reserved: the caller already counted this spawn in
        _pending_spawns (under _lock, before the fork) so the spawn gate
        can't be double-passed during the ~100ms Popen window.
        chips: ids the caller took from _tpu_chips_free for this (tpu)
        worker; they come back in _reap_worker."""
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        env = worker_spawn_env(
            os.environ, kind, chips,
            host_chips=int(self.resources_total.get("TPU", 0)),
        )
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        env["RT_CONFIG_SNAPSHOT"] = config.snapshot()
        env["RT_CRASH_DIR"] = os.path.join(self.temp_dir, "crash")
        python = sys.executable
        if env_spec:
            # boot the worker INSIDE its runtime env: pip envs get the
            # env's interpreter; working_dir/py_modules/env_vars apply in
            # worker_main before the worker registers (reference: the
            # runtime-env agent prepares the env, then the pool forks the
            # worker into it)
            from ray_tpu.core import runtime_env as runtime_env_mod

            if env_spec.get("pip"):
                python = runtime_env_mod.ensure_pip_env(env_spec["pip"])
            import base64

            from ray_tpu.utils import serialization

            env["RT_BOOT_ENV"] = base64.b64encode(
                serialization.dumps(env_spec)
            ).decode()
        log_base = os.path.join(self.temp_dir, "logs", f"worker-{uuid.uuid4().hex[:8]}")
        stdout = open(log_base + ".out", "wb")
        stderr = open(log_base + ".err", "wb")
        proc = subprocess.Popen(
            [
                python, "-m", "ray_tpu.core.worker_main",
                "--node-address", self.address,
                "--control-address", self.control_address,
                "--node-id", self.node_id.hex(),
                "--session-id", self.session_id,
                "--kind", kind,
                "--env-hash", env_hash,
            ],
            env=env, stdout=stdout, stderr=stderr, start_new_session=True,
        )
        stdout.close()
        stderr.close()
        _PROC_REGISTRY[proc.pid] = proc
        _PROC_LOGS[proc.pid] = log_base
        with self._lock:
            if chips:
                self._proc_chips[proc.pid] = tuple(chips)
            if not slot_reserved:
                self._pending_spawns += 1
        threading.Thread(
            target=self._reap_worker, args=(proc,), name="agent-reap", daemon=True
        ).start()

    def _reap_worker(self, proc: subprocess.Popen) -> None:
        proc.wait()
        dead: Optional[_Worker] = None
        if _PROC_REGISTRY.pop(proc.pid, None) is not None:
            # Died before ever registering: release the spawn slot.
            _PROC_LOGS.pop(proc.pid, None)
            with self._lock:
                self._pending_spawns = max(0, self._pending_spawns - 1)
                self._cv.notify_all()
        freed_lease = False
        with self._lock:
            # the holder is gone: only now may its chips go to another
            self._return_chips_locked(self._proc_chips.pop(proc.pid, ()))
            self._cv.notify_all()
            for w in self._workers.values():
                if w.proc is proc:
                    dead = w
                    break
            if dead is not None:
                self._workers.pop(dead.worker_id, None)
                if dead.state == "leased" and dead.lease_id in self._leases:
                    info = self._leases.pop(dead.lease_id)
                    self._release_resources_locked(info)
                    freed_lease = True
                dead.state = "dead"
                self._cv.notify_all()
        if dead is not None and not self._stopped.is_set():
            if freed_lease:
                # only a leased worker's death frees capacity; an idle
                # worker crash-looping must not spam cluster-wide kicks
                self._notify_capacity_freed()
            try:
                self._control.call_oneway(
                    "report_worker_failure",
                    worker_address=dead.address,
                    node_id=self.node_id.hex(),
                    reason=f"worker process exited with code {proc.returncode}",
                )
            except RpcError:
                pass

    def rpc_register_worker(self, conn, worker_id: str, address: str, pid: int,
                            kind: str = "cpu", env_hash: str = ""):
        with self._lock:
            self._pending_spawns = max(0, self._pending_spawns - 1)
            w = _Worker(worker_id, address, pid, _PROC_REGISTRY.pop(pid, None),
                        kind=kind, env_hash=env_hash,
                        log_base=_PROC_LOGS.pop(pid, ""),
                        chips=self._proc_chips.get(pid, ()))
            self._workers[worker_id] = w
            self._cv.notify_all()
        # a fresh idle worker unparks zero-wait lease retries just like
        # freed resources do
        self._notify_capacity_freed()
        return {"node_id": self.node_id.hex(), "session_id": self.session_id}

    def _terminate_worker(self, w: _Worker) -> None:
        try:
            os.kill(w.pid, 15)
        except (ProcessLookupError, PermissionError):
            return
        if w.chips and w.proc is not None:
            # a chip holder that ignores SIGTERM (wedged in the device
            # runtime) would keep its chips from every later lease
            def _kill_if_alive(proc=w.proc):
                if proc.poll() is None:
                    proc.kill()

            t = threading.Timer(_TPU_TERM_GRACE_S, _kill_if_alive)
            t.daemon = True
            t.start()

    # ------------------------------------------------------------------
    # leases (reference C4/C5: HandleRequestWorkerLease + cluster scheduler)
    # ------------------------------------------------------------------

    def rpc_lease_worker(
        self,
        conn,
        resources: Dict[str, float],
        bundle=None,
        strategy=None,
        wait_s: float = 30.0,
        bind_to_conn: bool = True,
        runtime_env=None,
        spillback: bool = True,
    ):
        """bind_to_conn: a lease granted to a driver/executor (the lease
        cache) dies with its owner's RPC connection — an owner that exits
        without releasing (crash, no shutdown()) must not strand leased
        workers forever. The control store passes False: actor leases are
        store-managed (actor death/restart flows release them), and a
        transient store->agent reconnect must NOT kill every actor on the
        node.

        spillback=False: the control store's actor scheduler already
        picked this node from the GLOBAL cluster view, so re-consulting
        the store here would only amplify load — a capacity-freed kick
        retries every parked actor at once, and thousands of lease
        requests each calling get_cluster_view back to the store queue
        ahead of everything else on the store's dispatcher (ISSUE 14:
        the 10k kill-drain stalled 30s exactly this way)."""
        resources = {k: float(v) for k, v in (resources or {}).items() if v}
        if core_metrics.ENABLED:
            core_metrics.lease_requests.inc()
        # Cluster-level decision: can/should this run here? (spillback)
        if bundle is None:
            if spillback:
                target = self._pick_target_node(resources, strategy)
            else:
                # store-scheduled: the caller already picked this node
                # from the global view — treat it as the target
                target = {"node_id": self.node_id.hex()}
            if target is not None and target["node_id"] != self.node_id.hex():
                return {"granted": False, "spillback": target["address"]}
            if target is None and not self._feasible_locally(resources):
                # No live node's TOTALS fit: surface the error to the
                # caller fast, but record the shape so the autoscaler can
                # report truly-infeasible demand in `rt status`
                with self._lock:
                    shape_key = tuple(
                        sorted((k, float(v)) for k, v in resources.items())
                    )
                    self._starved_shapes[shape_key] = time.monotonic()
                return {"granted": False, "error": "infeasible"}
        else:
            # Bundle pinned to a PG: if this node doesn't host the
            # *requested bundle index* (it may host other bundles of a
            # SPREAD PG), spill back to the node that does (control store
            # records bundle_locations at COMMIT) rather than timing out
            # forever locally.
            with self._lock:
                rec = self._bundles.get(bundle[0])
                req_idx = bundle[1]
                have_pg = rec is not None and (
                    req_idx is None or req_idx < 0 or req_idx in rec["bundles"]
                )
            if not have_pg:
                target = self._pick_bundle_node(bundle)
                if target == "pending":
                    # PG exists but hasn't committed anywhere yet — let the
                    # caller retry (same contract as a lease timeout).
                    return {"granted": False, "error": "lease timeout"}
                if target is not None and target["node_id"] != self.node_id.hex():
                    return {"granted": False, "spillback": target["address"]}
                if target is None:
                    return {"granted": False, "error": "bundle not found"}
        deadline = time.monotonic() + wait_s
        # "tpu" = a process started with ceil(TPU) chips of its own
        kind = "tpu" if resources.get("TPU") else "cpu"
        owner_conn = conn if (bind_to_conn and conn is not None) else None
        from ray_tpu.core import runtime_env as runtime_env_mod

        env_hash = runtime_env_mod.env_hash(runtime_env)
        return self._lease_wait(  # rtlint: ignore[dispatcher-block] the agent dispatch pool spawns per-request threads (never queues), so a parked lease holds no shared thread; slicing would double scheduler RPCs on the grant hot path
            resources, bundle, deadline, kind, strategy, owner_conn,
            runtime_env, env_hash,
        )

    def _lease_wait(self, resources, bundle, deadline, kind, strategy=None,
                    owner_conn=None, env_spec=None, env_hash=""):
        spawned_for_me = False
        n_chips = math.ceil(resources.get("TPU", 0.0) - _RES_EPS)
        starved = False  # counted toward autoscaler demand
        last_spill_check = time.monotonic()
        self._lock.acquire()
        try:
            while True:
                ok, resolved_bundle = self._try_allocate_locked(resources, bundle)
                if not ok and bundle is None and not starved:
                    # Resource-starved (NOT merely waiting on a worker
                    # spawn, and not bundle-pinned — a new node can't
                    # serve those): the autoscaler's demand signal.
                    starved = True
                    self._pending_leases += 1
                    # sticky per-SHAPE record: zero-wait scheduler retries
                    # make the counter flicker faster than heartbeats
                    # sample, but the shape entry survives (TTL-reported)
                    # so the autoscaler can bin-pack real demand
                    shape_key = tuple(
                        sorted((k, float(v)) for k, v in resources.items())
                    )
                    self._starved_shapes[shape_key] = time.monotonic()
                if ok:
                    if owner_conn is not None and not owner_conn.alive:
                        # the owner disconnected while this request waited
                        # — its reap callback has already run, so a grant
                        # now would register an unreapable (stranded)
                        # lease
                        self._deallocate_locked(resources, resolved_bundle)
                        return {
                            "granted": False, "error": "owner disconnected",
                        }
                    worker = self._pop_idle_worker_locked(
                        kind, env_hash, n_chips
                    )
                    if worker is not None:
                        lease_id = uuid.uuid4().hex
                        worker.state = "leased"
                        worker.lease_id = lease_id
                        self._leases[lease_id] = {
                            "resources": resources,
                            "bundle": resolved_bundle,
                            "worker_id": worker.worker_id,
                            "conn_id": (
                                id(owner_conn)
                                if owner_conn is not None else None
                            ),
                        }
                        # no re-check needed: we hold self._lock from the
                        # liveness check through this insert, and the reap
                        # scan (_owner_conn_closed) needs the same lock —
                        # a disconnect after the check reaps post-insert
                        if core_metrics.ENABLED:
                            core_metrics.lease_grants.inc()
                        return {
                            "granted": True,
                            "worker_address": worker.address,
                            "lease_id": lease_id,
                            "node_id": self.node_id.hex(),
                        }
                    # Resources ok but no idle worker: undo the allocation,
                    # ensure a spawn is in flight for this request, wait.
                    # Capacity cap: short zero-wait lease retries (the
                    # control-store scheduler queue) must not each spawn
                    # their own worker — the pool never needs more workers
                    # of a kind than the node can concurrently lease.
                    self._deallocate_locked(resources, resolved_bundle)
                    if kind == "tpu":
                        # chip holders are never pooled: one is started
                        # for this lease once its chips are free, however
                        # often that has to be re-tried while the
                        # previous holder exits
                        if not spawned_for_me:
                            spawned_for_me = self._spawn_tpu_worker_locked(
                                n_chips, env_spec, env_hash
                            )
                    elif not spawned_for_me:
                        spawned_for_me = True
                        cap = max(1, int(self.resources_total.get("CPU", 1)))
                        n_kind = sum(
                            1 for w in self._workers.values()
                            if w.kind == kind and w.state != "dead"
                        )
                        evicted = None
                        if n_kind + self._pending_spawns >= cap:
                            # at capacity with idle workers of another
                            # runtime env: evict one to make room
                            evicted = self._evict_idle_mismatch_locked(
                                kind, env_hash
                            )
                        # pending_spawns == 0 always allows a spawn: the
                        # demand DID fit the resources (ok was True), so
                        # zero/fractional-CPU requests past the capacity
                        # cap must still make progress — the cap only
                        # throttles CONCURRENT spawns from retry storms.
                        # Zero-wait requests (the store scheduler's
                        # fire-and-forget retries) can never use their own
                        # spawn — it is purely a spawn-AHEAD for a later
                        # retry — so they slow-start (at most max(2,
                        # n_kind) in flight, doubling as workers register)
                        # instead of fork-bombing up to cap at once: after
                        # a mass kill, the straggler retries of
                        # already-dead actors otherwise spawn a full
                        # pool's worth of workers nobody will use, and
                        # the fork storm convoys every other RPC on the
                        # node (PG prepares, lease releases) for seconds
                        limit = cap
                        if deadline <= time.monotonic():
                            limit = min(cap, max(2, n_kind))
                        if evicted is not None or self._pending_spawns == 0 or (
                            n_kind + self._pending_spawns < limit
                        ):
                            # reserve the slot BEFORE dropping the lock:
                            # the fork takes ~100ms and an unreserved
                            # gate would let every concurrently-parked
                            # request pass it in that window
                            self._pending_spawns += 1
                            spawned = False
                            self._lock.release()
                            try:
                                if evicted is not None:
                                    self._terminate_worker(evicted)
                                self._spawn_worker(
                                    kind, env_spec, env_hash,
                                    slot_reserved=True,
                                )
                                spawned = True
                            finally:
                                self._lock.acquire()
                                if not spawned:
                                    self._pending_spawns = max(
                                        0, self._pending_spawns - 1
                                    )
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return {"granted": False, "error": "lease timeout"}
                # A queued lease must notice capacity that appears
                # ELSEWHERE (an autoscaler-launched node): periodically
                # re-consult the cluster view — WITH the original strategy
                # (hard affinity must not be hijacked) — and spill only to
                # a node that actually has the resources AVAILABLE (a
                # feasible-by-total-but-full node would just bounce the
                # lease back and forth until the hop cap kills the task).
                if (
                    not ok
                    and bundle is None
                    and time.monotonic() - last_spill_check > 1.0
                ):
                    last_spill_check = time.monotonic()
                    self._lock.release()
                    try:
                        target = self._pick_available_target(
                            resources, strategy
                        )
                    finally:
                        self._lock.acquire()
                    if (
                        target is not None
                        and target["node_id"] != self.node_id.hex()
                    ):
                        return {
                            "granted": False,
                            "spillback": target["address"],
                        }
                self._cv.wait(min(remaining, 0.5))
        finally:
            if starved:
                self._pending_leases -= 1
            self._lock.release()

    def _pick_available_target(self, resources, strategy):
        """Like _pick_target_node, but only returns nodes whose AVAILABLE
        resources fit the request (used by the mid-wait re-spill)."""
        try:
            view = self._control.call("get_cluster_view", timeout_s=5.0)
        except RpcError:
            return None
        node_id = scheduling.pick_node(
            view, resources, strategy, local_node_id=self.node_id.hex()
        )
        if node_id is None or node_id not in view:
            return None
        avail = view[node_id].get("resources_available", {})
        if not all(avail.get(k, 0.0) >= v for k, v in resources.items() if v > 0):
            return None
        return {"node_id": node_id, "address": view[node_id]["address"]}

    def _owner_conn_closed(self, conn) -> None:
        """An RPC client disconnected: reap every conn-bound lease it
        held (reference: raylet disconnects kill the worker leases of a
        dead owner). kill=True — the worker may be mid-task for the dead
        owner; a poisoned warm worker is worse than a respawn."""
        conn_id = id(conn)
        with self._lock:
            dead = [
                lid for lid, info in self._leases.items()
                if info.get("conn_id") == conn_id
            ]
        for lid in dead:
            try:
                self.rpc_release_worker(None, lid, kill=True)
            except Exception:  # noqa: BLE001 — teardown path
                logger.exception("lease %s reap failed", lid[:8])

    def rpc_release_worker(self, conn, lease_id: str, kill: bool = False):
        with self._lock:
            info = self._leases.pop(lease_id, None)
            if info is None:
                return False
            self._release_resources_locked(info)
            worker = self._workers.get(info["worker_id"])
            if worker is not None:
                # a process that holds chips is never parked idle
                kill = kill or worker.kind == "tpu"
                if kill:
                    self._workers.pop(worker.worker_id, None)
                else:
                    worker.state = "idle"
                    worker.lease_id = None
            self._cv.notify_all()
        if kill and worker is not None:
            self._terminate_worker(worker)
        self._notify_capacity_freed()
        return True

    def rpc_release_workers(self, conn, lease_ids: List[str],
                            kill: bool = False):
        """Bulk lease release (ISSUE 14 kill-drain): one lock pass frees
        every lease's resources, workers terminate outside the lock, and
        the whole batch sends ONE capacity kick instead of one per lease.
        Returns the number of leases actually released (unknown ids are
        skipped — releases are idempotent)."""
        released = 0
        doomed_workers = []
        with self._lock:
            for lease_id in lease_ids:
                info = self._leases.pop(lease_id, None)
                if info is None:
                    continue
                released += 1
                self._release_resources_locked(info)
                worker = self._workers.get(info["worker_id"])
                if worker is not None:
                    if kill or worker.kind == "tpu":
                        self._workers.pop(worker.worker_id, None)
                        doomed_workers.append(worker)
                    else:
                        worker.state = "idle"
                        worker.lease_id = None
            if released:
                self._cv.notify_all()
        for worker in doomed_workers:
            self._terminate_worker(worker)
        if released:
            self._notify_capacity_freed()
        return released

    def _release_resources_locked(self, info: Dict[str, Any]) -> None:
        self._deallocate_locked(info["resources"], info["bundle"])

    def _notify_capacity_freed(self) -> None:
        """Tell the store capacity freed here so pending actors/PGs retry
        NOW instead of waiting out their (up to 2s) scheduler backoff.
        Debounced: a burst of releases sends one kick per 50ms."""
        now = time.monotonic()
        if now - getattr(self, "_last_free_notify", 0.0) < 0.05:
            return
        self._last_free_notify = now
        try:
            self._control.call_oneway(
                "capacity_freed", node_id=self.node_id.hex()
            )
        except RpcError:
            pass  # heartbeat anti-entropy covers the lost kick

    @staticmethod
    def _fits(pool, need) -> bool:
        """Epsilon-tolerant resource fit: repeated fractional
        allocate/credit cycles (e.g. 400 PG carve-outs of 0.01 CPU)
        leave float dust in the availability counters, and an exact >=
        would then refuse a whole-CPU request forever on a node that is
        arithmetically idle."""
        return all(
            pool.get(k, 0.0) >= v - _RES_EPS for k, v in need.items()
        )

    def _credit_main_locked(self, resources) -> None:
        """Credit the node pool, snapping each counter back to the node
        total when it lands within epsilon — the dust from fractional
        churn must not accumulate across workload generations."""
        for k, v in resources.items():
            avail = self.resources_available.get(k, 0.0) + v
            total = self.resources_total.get(k, 0.0)
            if abs(avail - total) < 1e-6:
                avail = total
            self.resources_available[k] = avail

    def _try_allocate_locked(self, resources, bundle):
        """Returns (ok, resolved_bundle). resolved_bundle pins the concrete
        pool index an index=-1 bundle request landed in, so release returns
        capacity to the exact pool it came from."""
        if bundle is not None:
            pg_id, idx = bundle
            rec = self._bundles.get(pg_id)
            if rec is None or rec["state"] != "committed":
                return False, None
            pool_idx = self._bundle_pool_index(rec, idx, resources)
            if pool_idx is None:
                return False, None
            pool = rec["available"][pool_idx]
            for k, v in resources.items():
                pool[k] = pool.get(k, 0.0) - v
            return True, (pg_id, pool_idx)
        if not self._fits(self.resources_available, resources):
            return False, None
        for k, v in resources.items():
            left = self.resources_available.get(k, 0.0) - v
            # the epsilon fit may leave -1e-12 dust; never go negative
            self.resources_available[k] = left if left > 0.0 else 0.0
        return True, None

    def _bundle_pool_index(self, rec, idx, resources) -> Optional[int]:
        if idx is not None and idx >= 0:
            pool = rec["available"].get(idx)
            if pool is not None and self._fits(pool, resources):
                return idx
            return None
        for i, pool in sorted(rec["available"].items()):
            if self._fits(pool, resources):
                return i
        return None

    def _deallocate_locked(self, resources, bundle) -> None:
        if bundle is not None:
            # bundle is always the allocation-resolved (pg_id, pool_idx)
            # pair — _try_allocate_locked pins the concrete pool, so credit
            # goes back exactly where it came from.
            pg_id, pool_idx = bundle
            rec = self._bundles.get(pg_id)
            if rec is None:
                return
            pool = rec["available"].setdefault(pool_idx, {})
            for k, v in resources.items():
                pool[k] = pool.get(k, 0.0) + v
            return
        self._credit_main_locked(resources)

    def _pop_idle_worker_locked(self, kind: str = "cpu", env_hash: str = "",
                                n_chips: int = 0) -> Optional[_Worker]:
        for w in self._workers.values():
            if (
                w.state == "idle" and w.kind == kind
                and w.env_hash == env_hash and len(w.chips) == n_chips
            ):
                return w
        return None

    def _spawn_tpu_worker_locked(self, n_chips: int, env_spec,
                                 env_hash: str) -> bool:
        """Start a worker on ``n_chips`` free chips (lock held; dropped
        around the fork). False when the chips are not free yet: idle tpu
        workers — started for a lease that went elsewhere, on another
        chip count or runtime env — are told to exit, and the caller
        tries again once their ids are back (_reap_worker)."""
        if len(self._tpu_chips_free) < n_chips:
            idle = [
                w for w in self._workers.values()
                if w.kind == "tpu" and w.state == "idle"
            ]
            for w in idle:
                self._workers.pop(w.worker_id, None)
                w.state = "dead"
            if idle:
                self._lock.release()
                try:
                    for w in idle:
                        self._terminate_worker(w)
                finally:
                    self._lock.acquire()
            return False
        chips = tuple(self._tpu_chips_free[:n_chips])
        del self._tpu_chips_free[:n_chips]
        self._pending_spawns += 1
        spawned = False
        self._lock.release()
        try:
            self._spawn_worker(
                "tpu", env_spec, env_hash, slot_reserved=True, chips=chips
            )
            spawned = True
        finally:
            self._lock.acquire()
            if not spawned:
                self._pending_spawns = max(0, self._pending_spawns - 1)
                self._return_chips_locked(chips)
        return spawned

    def _return_chips_locked(self, chips) -> None:
        self._tpu_chips_free.extend(chips)
        self._tpu_chips_free.sort()

    def _evict_idle_mismatch_locked(self, kind: str,
                                    env_hash: str) -> Optional[_Worker]:
        """An idle worker of the right kind but the WRONG runtime env:
        evictable to make room under the kind capacity cap (reference:
        the pool kills idle workers when a differently-env'd lease needs
        the slot)."""
        for w in self._workers.values():
            if (
                w.state == "idle" and w.kind == kind
                and w.env_hash != env_hash
            ):
                self._workers.pop(w.worker_id, None)
                w.state = "dead"
                return w
        return None

    def _feasible_locally(self, resources) -> bool:
        return all(
            self.resources_total.get(k, 0.0) >= v for k, v in resources.items()
        )

    def _pick_bundle_node(self, bundle):
        """Resolve which node hosts a PG bundle via the control store."""
        pg_id, idx = bundle
        try:
            pg = self._control.call("get_placement_group", pg_id=pg_id)
            view = self._control.call("get_cluster_view", timeout_s=5.0)
        except RpcError:
            # Transient control-store failure must not become a permanent
            # "bundle not found" for a healthy PG — have the caller retry.
            return "pending"
        if not pg:
            return None
        if pg.get("state") == "REMOVED":
            return None  # removed PG must error out, not retry forever
        locs = pg.get("bundle_locations") or {}
        if not locs:
            return "pending"
        node_id = None
        if idx is not None and idx >= 0:
            node_id = locs.get(idx, locs.get(str(idx)))
        elif locs:
            node_id = next(iter(locs.values()))
        if node_id is None:
            return None
        if node_id not in view:
            # Bundle host absent from the alive-node view: either a
            # heartbeat blip or a real death (in which case the control
            # store re-places the PG, _mark_node_dead). Either way the
            # right answer is "retry", not a permanent "bundle not found".
            return "pending"
        return {"node_id": node_id, "address": view[node_id]["address"]}

    def _pick_target_node(self, resources, strategy):
        """Cluster view consult for spillback (reference hybrid policy).
        The view is cached for a beat: a task-submission storm funnels
        every lease request through this consult, and re-fetching the
        view per request turns one storm into a second one aimed at the
        control store. Spillback targets computed on a ≤100 ms-stale
        view are already racy by nature (the view is a snapshot); a
        wrong pick costs one extra hop."""
        now = time.monotonic()
        with self._view_cache_lock:
            ts, cached = self._view_cache
            view = cached if now - ts < 0.1 else None
        if view is None:
            try:
                view = self._control.call("get_cluster_view", timeout_s=5.0)
            except RpcError:
                return None
            with self._view_cache_lock:
                self._view_cache = (now, view)
        node_id = scheduling.pick_node(
            view, resources, strategy, local_node_id=self.node_id.hex()
        )
        if node_id is None:
            return None
        return {"node_id": node_id, "address": view[node_id]["address"]}

    # ------------------------------------------------------------------
    # placement-group bundles (reference C3 raylet side: 2PC)
    # ------------------------------------------------------------------

    def rpc_prepare_bundles(self, conn, pg_id: str, bundles: Dict[int, Dict[str, float]]):
        with self._lock:
            bundles = {int(i): dict(b) for i, b in bundles.items()}
            existing = self._bundles.get(pg_id)
            if existing is not None:
                if existing["state"] == "prepared":
                    # Idempotent retry only if it's the same reservation; a
                    # record with a different bundle set must NOT be
                    # resurrected.
                    return existing["bundles"] == bundles
                # Committed record: a PG re-placement after node death may
                # land the lost bundles on a node already hosting surviving
                # bundles. Stage the new indices; commit merges them.
                staged = existing.get("staged") or {}
                if staged:
                    return staged == bundles  # idempotent retry
                if any(i in existing["bundles"] for i in bundles):
                    return False  # overlaps committed indices: invalid add
                need: Dict[str, float] = {}
                for b in bundles.values():
                    for k, v in b.items():
                        need[k] = need.get(k, 0.0) + v
                if not self._fits(self.resources_available, need):
                    return False
                for k, v in need.items():
                    left = self.resources_available.get(k, 0.0) - v
                    self.resources_available[k] = left if left > 0.0 else 0.0
                existing["staged"] = bundles
                return True
            need = {}
            for b in bundles.values():
                for k, v in b.items():
                    need[k] = need.get(k, 0.0) + v
            if not self._fits(self.resources_available, need):
                return False
            for k, v in need.items():
                left = self.resources_available.get(k, 0.0) - v
                self.resources_available[k] = left if left > 0.0 else 0.0
            self._bundles[pg_id] = {
                "state": "prepared",
                "bundles": {i: dict(b) for i, b in bundles.items()},
                "available": {i: dict(b) for i, b in bundles.items()},
            }
            return True

    def rpc_commit_bundles(self, conn, pg_id: str):
        with self._lock:
            rec = self._bundles.get(pg_id)
            if rec is None:
                return False
            for i, b in (rec.pop("staged", None) or {}).items():
                rec["bundles"][i] = dict(b)
                rec["available"][i] = dict(b)
            rec["state"] = "committed"
            self._cv.notify_all()
            return True

    def rpc_return_bundles(self, conn, pg_id: str, idxs: Optional[List[int]] = None):
        """Return bundle reservations to the node pool.

        idxs=None: full teardown (PG removed / total rollback). idxs given:
        partial rollback of a re-placement — only those bundle indices are
        returned (committed or staged), surviving bundles keep running.
        Any lease granted against a returned bundle is void — the worker
        holding it is killed and its caller retries against the re-placed
        PG (the reference likewise kills workers using removed bundles).
        """
        doomed = []
        with self._lock:
            rec = self._bundles.get(pg_id)
            if rec is None:
                return True
            staged = rec.get("staged") or {}
            if idxs is None:
                idx_set = set(rec["bundles"]) | set(staged)
            else:
                idx_set = {int(i) for i in idxs}
            for lease_id, info in list(self._leases.items()):
                b = info.get("bundle")
                if b and b[0] == pg_id and b[1] in idx_set:
                    self._leases.pop(lease_id, None)
                    w = self._workers.pop(info["worker_id"], None)
                    if w is not None:
                        doomed.append(w)
            for i in idx_set:
                spec = rec["bundles"].pop(i, None) or staged.pop(i, None)
                if spec is None:
                    continue
                rec["available"].pop(i, None)
                self._credit_main_locked(spec)
            if not rec["bundles"] and not staged:
                self._bundles.pop(pg_id, None)
            self._cv.notify_all()
        for w in doomed:
            self._terminate_worker(w)
        return True

    # ------------------------------------------------------------------
    # object store host (reference C7)
    # ------------------------------------------------------------------

    def rpc_create_object(self, conn, oid_hex: str, size: int):
        return self.store.create(oid_hex, size)

    def rpc_seal_object(self, conn, oid_hex: str):
        self.store.seal(oid_hex)
        return True

    def rpc_get_object_meta(self, conn, oid_hex: str, timeout_s: Optional[float] = None):
        return self.store.get_meta(oid_hex, timeout_s)

    def rpc_object_contains(self, conn, oid_hex: str):
        return self.store.contains(oid_hex)

    def rpc_delete_objects(self, conn, oid_hexes: List[str]):
        for h in oid_hexes:
            self.store.delete(h)
        return True

    def rpc_store_usage(self, conn):
        return self.store.usage()

    def _raw_seal_object(self, conn, req_id, args, kwargs):
        oid_hex = kwargs.get("oid_hex") or args[0]
        self.store.seal(oid_hex)
        RpcServer.reply(conn, req_id, True, True)

    def _raw_recycle_object(self, conn, req_id, args, kwargs):
        """Owner says: delete this never-shared object, recycling its
        segment pages into the pool (ShmObjectStore.recycle). Fast path
        runs inline in the connection read loop (lock-only, no blocking);
        an entry caught mid-spill/restore falls back to a threaded
        delete, which waits the move out."""
        oid_hex = kwargs.get("oid_hex") or args[0]
        if not self.store.recycle(oid_hex):
            threading.Thread(
                target=lambda: self.store.delete(oid_hex),
                name="agent-recycle-fallback", daemon=True,
            ).start()
        RpcServer.reply(conn, req_id, True, True)

    def rpc_read_object_chunk(self, conn, path: str, offset: int, length: int):
        """Serve a byte range of a local segment to a cross-node puller
        (reference C8: push_manager.h chunked transfer). The chunk rides
        the reply as a raw wire segment (serialization.Frame), not an
        in-band pickle copy."""
        chunk = self.store.read_chunk(path, offset, length)
        return None if chunk is None else serialization.maybe_frame(chunk)

    # ------------------------------------------------------------------
    # introspection (state API backing)
    # ------------------------------------------------------------------

    def rpc_list_objects(self, conn):
        """Object-store inventory for `state.objects()` / `rt memory`."""
        return {
            "node_id": self.node_id.hex(),
            "objects": self.store.inventory(),
        }

    def rpc_tail_worker_logs(self, conn, tail_bytes: int = 4096):
        """Tails of every worker's captured stdout/stderr on this node
        (`state.worker_logs()` / `rt logs`) — how a `print()` inside a
        task reaches the driver machine. Covers dead workers too: the
        files outlive the process."""
        tail_bytes = max(0, min(int(tail_bytes), 1 << 20))
        with self._lock:
            live = {
                os.path.basename(w.log_base): {
                    "worker_id": wid, "pid": w.pid, "state": w.state,
                }
                for wid, w in self._workers.items() if w.log_base
            }
        logs = []
        log_dir = os.path.join(self.temp_dir, "logs")
        try:
            names = sorted(os.listdir(log_dir))
        except OSError:
            names = []
        for fname in names:
            base, dot, ext = fname.rpartition(".")
            if ext not in ("out", "err") or not base.startswith("worker-"):
                continue
            path = os.path.join(log_dir, fname)
            try:
                size = os.path.getsize(path)
                with open(path, "rb") as f:
                    if size > tail_bytes:
                        f.seek(size - tail_bytes)
                    data = f.read(tail_bytes)
            except OSError:
                continue
            entry = {
                "node_id": self.node_id.hex(),
                "file": fname,
                "stream": "stdout" if ext == "out" else "stderr",
                "size": size,
                "tail": data.decode(errors="replace"),
            }
            entry.update(live.get(base, {}))
            logs.append(entry)
        # crash artifacts (faulthandler files + black boxes) surface
        # through the same listing — they too outlive their process
        crash_d = os.path.join(self.temp_dir, "crash")
        try:
            crash_names = sorted(os.listdir(crash_d))
        except OSError:
            crash_names = []
        for fname in crash_names:
            if fname.startswith("crash-"):
                stream = "crash"
            elif fname.startswith("blackbox-") and fname.endswith(".json"):
                stream = "blackbox"
            else:
                continue
            path = os.path.join(crash_d, fname)
            try:
                size = os.path.getsize(path)
                with open(path, "rb") as f:
                    if size > tail_bytes:
                        f.seek(size - tail_bytes)
                    data = f.read(tail_bytes)
            except OSError:
                continue
            logs.append({
                "node_id": self.node_id.hex(),
                "file": fname,
                "stream": stream,
                "size": size,
                "tail": data.decode(errors="replace"),
            })
        return logs

    def rpc_profile(self, conn, duration_s: float = 5.0,
                    hz: float = 99.0):
        """Sample this agent process's threads. The caller-supplied
        duration is capped so a profile RPC can hold a dispatcher
        thread for at most profiler_max_duration_s."""
        duration_s = min(
            float(duration_s), float(config.profiler_max_duration_s)
        )
        return profiler.capture(duration_s=duration_s, hz=hz)

    def rpc_stack_dump(self, conn):
        """All-thread stacks from this agent (hang forensics)."""
        return forensics.all_thread_stacks()

    def rpc_crash_reports(self, conn, pid: Optional[int] = None):
        """Crash artifacts on this node — black boxes + faulthandler
        files, dead workers included (`rt postmortem`)."""
        return {
            "node_id": self.node_id.hex(),
            "reports": forensics.list_crash_reports(
                dirs=[os.path.join(self.temp_dir, "crash")], pid=pid
            ),
        }

    def rpc_get_metrics(self, conn):
        """This process's metric registry (lease/pool/object-store series
        for a standalone agent; on the head this is the same registry the
        driver serves — state.cluster_metrics dedups by token)."""
        from ray_tpu.utils import metrics as metrics_mod

        return {
            "token": metrics_mod.PROCESS_TOKEN,
            "metrics": metrics_mod.snapshot_all(),
        }

    def rpc_get_state(self, conn):
        with self._lock:
            if core_metrics.ENABLED:
                self._update_pool_gauge_locked()
            return {
                "node_id": self.node_id.hex(),
                "address": self.address,
                "resources_total": dict(self.resources_total),
                "resources_available": dict(self.resources_available),
                "labels": dict(self.labels),
                "workers": {
                    wid: {"address": w.address, "pid": w.pid,
                          "state": w.state, "kind": w.kind,
                          "chips": list(w.chips)}
                    for wid, w in self._workers.items()
                },
                "tpu_chips_free": list(self._tpu_chips_free),
                "leases": {lid: dict(i) for lid, i in self._leases.items()},
                "bundles": {
                    pg: {"state": r["state"], "bundles": r["bundles"]}
                    for pg, r in self._bundles.items()
                },
                "store_usage": self.store.usage(),
                "spill_stats": self.store.spill_stats(),
                "heartbeat_stats": {
                    "full": self._hb_full, "light": self._hb_light,
                },
            }


_PROC_REGISTRY: Dict[int, subprocess.Popen] = {}
_PROC_LOGS: Dict[int, str] = {}
