"""Control-plane scale envelope (ISSUE 14): WAL group-commit crash
atomicity and determinism, batched actor lifecycle (register_actors /
kill_actors) semantics and HA-replay determinism, and a tier-1-sized
batched register + parallel kill-drain smoke.

The crash test kills a child process with SIGKILL while it is appending
inside an open group-commit window: recovery must see exactly a
contiguous prefix of the applied ops (the group is one contiguous write
of whole frames, so a torn tail is always a whole-frame prefix), and
every op the child ACKED through ``barrier()`` — the store acks RPCs
only after that barrier — must be in the prefix."""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

import ray_tpu
from ray_tpu.core.control_store import ControlStore
from ray_tpu.core.ha.wal import FileBackend, HAState
from ray_tpu.utils.rpc import RpcClient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _canon(o):
    """Canonical (object-identity-independent) form of the durable
    tables — same helper as test_ha_failover.py."""
    if isinstance(o, dict):
        return [[repr(k), _canon(v)] for k, v in o.items()]
    if isinstance(o, (list, tuple)):
        return [_canon(v) for v in o]
    if isinstance(o, bytes):
        return "b:" + o.hex()
    return o


def _canonical_bytes(tables) -> bytes:
    return json.dumps(_canon(tables)).encode()


# -- WAL group commit ----------------------------------------------------

_CRASH_CHILD = textwrap.dedent("""
    import sys
    sys.path.insert(0, sys.argv[1])
    from ray_tpu.core.ha.wal import FileBackend, HAState

    ha = HAState(FileBackend(sys.argv[2]), compact_entries=10**9,
                 fsync=False, group_commit_ms=25.0)
    ha.recover()
    ha.start(lambda: {"kv": {}})
    applied = {}
    state = lambda: {"kv": dict(applied)}
    i = 0
    while True:
        key = "k%06d" % i
        ha.append("kv_put", (key, "v%d" % i), state)
        applied[key] = "v%d" % i
        if i % 100 == 99:
            # the store's post-dispatch hook: ack only after the barrier
            ha.barrier()
            print("ACK", i, flush=True)
        i += 1
""")


def _replay_kv(path):
    """Recover the child's kv projection: snapshot tables + WAL tail
    replayed through the same trivial mutation."""
    ha = HAState(FileBackend(path))
    tables, records = ha.recover()
    kv = dict((tables or {}).get("kv", {}))
    for op, args in records:
        assert op == "kv_put"
        kv[args[0]] = args[1]
    ha.backend.close()
    return kv


def test_group_commit_crash_atomicity(tmp_path):
    """kill -9 while appends sit in an open group-commit window: the
    durable projection is a byte-identical CONTIGUOUS prefix of the
    applied sequence, covering at least every acked op."""
    path = str(tmp_path / "crash.db")
    proc = subprocess.Popen(
        [sys.executable, "-c", _CRASH_CHILD, REPO, path],
        stdout=subprocess.PIPE, text=True,
    )
    try:
        acked = -1
        for _ in range(3):
            line = proc.stdout.readline()
            assert line.startswith("ACK"), f"child failed: {line!r}"
            acked = int(line.split()[1])
        # more appends are in flight past the last barrier — kill NOW,
        # mid-window
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        proc.kill()
        proc.stdout.close()

    kv = _replay_kv(path)
    n = len(kv)
    assert n > acked  # acked implies durable, even across kill -9
    # contiguous applied prefix, values byte-identical — no holes, no
    # partial mid-group record
    assert kv == {"k%06d" % j: "v%d" % j for j in range(n)}


def test_group_commit_wal_bytes_match_per_op(tmp_path):
    """The same op sequence produces a byte-identical WAL whether frames
    land one write per op or grouped: group commit changes write-call
    granularity only, never content (close() flushes the open window)."""
    ops = [("kv_put", ("ns", "k%d" % i, b"v" * (i % 7))) for i in range(200)]
    wal_paths = {}
    for mode, ms in (("group", 50.0), ("per_op", 0.0)):
        path = str(tmp_path / f"{mode}.db")
        ha = HAState(FileBackend(path), compact_entries=10**9,
                     group_commit_ms=ms)
        ha.recover()
        ha.start(lambda: {})
        for op, args in ops:
            ha.append(op, args, lambda: {})
        ha.close()
        wal_paths[mode] = path + ".wal"
    with open(wal_paths["group"], "rb") as f:
        grouped = f.read()
    with open(wal_paths["per_op"], "rb") as f:
        per_op = f.read()
    assert grouped and grouped == per_op


# -- batched actor lifecycle against the store --------------------------


def _spec(i, job_id, name=None, ns="default"):
    spec = {
        "actor_id": "%032x" % i,
        "job_id": job_id,
        "class_name": "Bulk",
        "resources": {"CPU": 1.0},
        "max_restarts": 0,
    }
    if name:
        spec["name"] = name
        spec["namespace"] = ns
    return spec


def test_batched_lifecycle_replay_determinism(tmp_path):
    """register_actors + kill_actors land per-record WAL ops: crash
    recovery (WAL tail replay, no final snapshot) rebuilds byte-identical
    durable tables, exactly as with the singular RPCs."""
    path = str(tmp_path / "bulk.db")
    cs = ControlStore("sessK" + "0" * 26, persistence_path=path)
    cs.start()
    client = RpcClient(cs.address, name="bulk")
    job_id = client.call("register_job", driver_address="d:1", metadata={})
    specs = [_spec(i, job_id) for i in range(20)]
    res = client.call("register_actors", specs=specs)
    assert [r["ok"] for r in res] == [True] * 20
    res = client.call(
        "kill_actors", actor_ids=[s["actor_id"] for s in specs[:10]]
    )
    assert all(r["ok"] and r["changed"] for r in res)
    # idempotent: re-killing a dead actor acks without a state change
    # (a retried batch must not fail on records already landed)
    res = client.call("kill_actors", actor_ids=[specs[0]["actor_id"]])
    assert res == [
        {"actor_id": specs[0]["actor_id"], "ok": True, "changed": False}
    ]
    client.close()

    live = _canonical_bytes(cs._durable_state_snapshot())
    # simulate a crash: detach the durable log so stop() writes no final
    # snapshot — recovery then has only the WAL tail
    ha, cs._ha = cs._ha, None
    ha.backend.close()
    cs.stop()

    cs2 = ControlStore("sessL" + "0" * 26, persistence_path=path)
    cs2.start()
    try:
        assert _canonical_bytes(cs2._durable_state_snapshot()) == live
        assert cs2._ha.stats()["wal_replayed"] > 0
    finally:
        cs2.stop()


def test_bulk_register_bad_spec_does_not_poison_batch():
    """Per-record results: a name conflict (and a malformed spec) report
    their error without failing — or registering — their siblings."""
    cs = ControlStore("sessM" + "0" * 26)
    cs.start()
    try:
        client = RpcClient(cs.address, name="mix")
        job_id = client.call(
            "register_job", driver_address="d:1", metadata={}
        )
        specs = [
            _spec(100, job_id, name="dup", ns="ns1"),
            _spec(101, job_id, name="dup", ns="ns1"),  # conflict
            _spec(102, job_id),
        ]
        res = client.call("register_actors", specs=specs)
        assert [r["ok"] for r in res] == [True, False, True]
        assert "already taken" in res[1]["error"]
        ids = {a["actor_id"] for a in client.call("list_actors")}
        assert specs[0]["actor_id"] in ids
        assert specs[2]["actor_id"] in ids
        assert specs[1]["actor_id"] not in ids
        # malformed record (no actor_id): its slot reports the error
        res = client.call(
            "register_actors", specs=[{"job_id": job_id}, _spec(103, job_id)]
        )
        assert res[0]["ok"] is False and "actor_id" in res[0]["error"]
        assert res[1]["ok"] is True
        client.close()
    finally:
        cs.stop()


def test_health_check_does_not_count_a_stall_of_its_own():
    """The time the health loop itself could not run (the head process
    or the whole machine was stalled) is not silence from the nodes: a
    node whose last heartbeat is older than the timeout only because of
    such a stall stays alive; real silence still kills it."""
    import time

    from ray_tpu.utils.config import config

    cs = ControlStore("sessN" + "0" * 26)
    cs.start()
    try:
        client = RpcClient(cs.address, name="hb")
        for nid in ("a" * 32, "b" * 32):
            client.call("register_node", node_info={
                "node_id": nid, "address": "127.0.0.1:1",
                "resources_total": {"CPU": 1.0}, "labels": {},
                "object_store_capacity": 0,
            })
        timeout = float(config.health_check_timeout_s)
        now = time.monotonic()
        with cs._lock:
            for n in cs._nodes.values():
                n["last_heartbeat"] = now - timeout - 2.0
        # the loop woke 9.5 s late: that much of the silence is its own
        cs._health_check(now, late=9.5)
        alive = {n["node_id"]: n["alive"] for n in client.call("get_nodes")}
        assert alive == {"a" * 32: True, "b" * 32: True}
        # on time and still silent past the timeout: dead
        with cs._lock:
            cs._nodes["a" * 32]["last_heartbeat"] = now - timeout - 2.0
        cs._health_check(now, late=0.0)
        alive = {n["node_id"]: n.get("alive") for n in client.call("get_nodes")}
        assert alive.get("a" * 32) in (False, None), alive
        assert alive["b" * 32] is True
        client.close()
    finally:
        cs.stop()


# -- tier-1 smoke: batched register + parallel kill-drain ---------------


def test_batched_lifecycle_smoke_200(rt_init):
    """200 actors on 4 CPUs: the client batcher coalesces the
    registrations (most stay PENDING), the alive cohort still answers,
    then a batched kill drains everything through the parallel teardown
    path — and a submit after kill fails deterministically."""
    from ray_tpu.core.worker import global_worker

    @ray_tpu.remote(num_cpus=1)
    class S:
        def ping(self):
            return 1

    # alive cohort first — it owns the capacity; which of a
    # simultaneously-registered batch wins placement is the scheduler's
    # choice, so pinging an arbitrary member of the pile would block
    alive = [S.remote() for _ in range(4)]
    assert ray_tpu.get([a.ping.remote() for a in alive], timeout=120) == [1] * 4
    actors = alive + [S.remote() for _ in range(196)]
    w = global_worker()
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        if len(w.control.call("list_actors")) >= 200:
            break
        time.sleep(0.05)
    else:
        raise AssertionError("batched registrations did not land")
    # the alive cohort must still answer beneath the pending pile
    assert ray_tpu.get(alive[0].ping.remote(), timeout=120) == 1

    for a in actors:
        ray_tpu.kill(a)
    deadline = time.monotonic() + 120
    states = set()
    while time.monotonic() < deadline:
        states = {a["state"] for a in w.control.call("list_actors")}
        if states == {"DEAD"}:
            break
        time.sleep(0.1)
    else:
        raise AssertionError(f"kill drain incomplete: {states}")

    with pytest.raises(
        (ray_tpu.exceptions.ActorDiedError, ray_tpu.exceptions.TaskError)
    ):
        ray_tpu.get(alive[0].ping.remote(), timeout=30)
