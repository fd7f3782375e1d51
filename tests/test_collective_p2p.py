"""Peer-to-peer ring collective tests: correctness vs an exact local
reference, the head-traffic guarantee (control-store KV bytes are
rendezvous-only, independent of payload size), quantized-allreduce
numerics bounds + wire-byte reduction, transport routing for send/recv,
the RT_COLLECTIVE_P2P kill switch, peer-death failure surfacing with
group re-init, and a chaos leg under injected connection drops."""

import time

import numpy as np
import pytest

import ray_tpu

WORLD = 4
# deterministic per-rank inputs so the driver can compute the exact
# reference without moving data
SEED = 1234


def _rank_input(rank, n, dtype, seed=SEED):
    rng = np.random.default_rng(seed + rank)
    return rng.uniform(-1.0, 1.0, n).astype(dtype)


def _exact(n, dtype, world=WORLD, op="sum", seed=SEED):
    xs = [_rank_input(r, n, dtype, seed).astype(np.float64)
          for r in range(world)]
    if op == "sum":
        out = np.sum(xs, axis=0)
    elif op == "min":
        out = np.min(xs, axis=0)
    elif op == "max":
        out = np.max(xs, axis=0)
    else:
        out = np.prod(xs, axis=0)
    return out


@pytest.fixture(scope="module")
def rt():
    ray_tpu.init(num_cpus=8)
    yield ray_tpu
    ray_tpu.shutdown()


def _head_kv_stats():
    from ray_tpu.core import worker as worker_mod

    return worker_mod.global_worker().control.call("kv_stats")


def _head_kv_bytes():
    s = _head_kv_stats()
    return s["bytes_put"] + s["bytes_out"]


@ray_tpu.remote
class Rank:
    def __init__(self, rank, world):
        self.rank, self.world = rank, world

    def setup(self, group):
        from ray_tpu import collective

        collective.init_collective_group(self.world, self.rank, "cpu", group)
        return True

    def destroy(self, group):
        from ray_tpu import collective

        collective.destroy_collective_group(group)
        return True

    def set_flag(self, name, value):
        from ray_tpu.utils.config import config

        config.set(name, value)
        return True

    def reset_stats(self):
        from ray_tpu.collective import p2p

        return p2p.reset_stats()

    def stats(self):
        from ray_tpu.collective import p2p

        return p2p.snapshot_stats()

    def metric_snapshot(self):
        from ray_tpu.observability import core_metrics

        return {
            "bytes": core_metrics.collective_bytes_sent.snapshot(),
            "latency": core_metrics.collective_op_latency_s.snapshot(),
        }

    def allreduce(self, group, n, dtype="float32", op="sum", quant=None,
                  timeout_s=None, seed=SEED):
        from ray_tpu import collective

        x = _rank_input(self.rank, n, dtype, seed)
        return collective.allreduce(x, op=op, group_name=group,
                                    quant=quant, timeout_s=timeout_s)

    def allreduce_catch(self, group, n, timeout_s, **kw):
        """allreduce that reports failures instead of raising (peer-death
        test: survivors must ERROR, not hang)."""
        from ray_tpu import collective
        from ray_tpu.core.exceptions import CollectiveError

        t0 = time.monotonic()
        try:
            self.allreduce(group, n, timeout_s=timeout_s, **kw)
            return ("ok", time.monotonic() - t0)
        except (CollectiveError, TimeoutError) as e:
            return ("err", type(e).__name__, str(e)[:200],
                    time.monotonic() - t0)

    def reducescatter(self, group, shape, dtype="float32", op="sum",
                      seed=SEED):
        from ray_tpu import collective

        n = int(np.prod(shape))
        x = _rank_input(self.rank, n, dtype, seed).reshape(shape)
        return collective.reducescatter(x, op=op, group_name=group)

    def allgather(self, group, n_mine):
        from ray_tpu import collective

        x = np.full(n_mine, float(self.rank), dtype=np.float32)
        return [np.asarray(a) for a in
                collective.allgather(x, group_name=group)]

    def broadcast(self, group, src, n):
        from ray_tpu import collective

        x = (_rank_input(src, n, "float32") if self.rank == src
             else np.zeros(1, dtype=np.float32))
        return collective.broadcast(x, src_rank=src, group_name=group)

    def send(self, group, dst, n, seed=SEED):
        from ray_tpu import collective

        collective.send(_rank_input(self.rank, n, "float32", seed), dst,
                        group_name=group)
        return True

    def recv(self, group, src, timeout_s=60.0):
        from ray_tpu import collective

        return np.asarray(collective.recv(src, group_name=group,
                                          timeout_s=timeout_s))

    def quant_validation_errors(self, group):
        """Exercise quant parameter validation inside the rank process."""
        from ray_tpu.collective import p2p

        g = p2p.group_for(group)
        errs = []
        for kwargs in (
            {"op": "min", "quant": "int8"},
            {"op": "sum", "quant": "int4"},
        ):
            try:
                p2p.ring_allreduce(g, np.ones(4, np.float32),
                                   kwargs["op"], "vtag",
                                   quant=kwargs["quant"])
                errs.append(None)
            except ValueError as e:
                errs.append(str(e)[:60])
        try:
            p2p.ring_allreduce(g, np.ones(4, np.int32), "sum", "vtag2",
                               quant="int8")
            errs.append(None)
        except ValueError as e:
            errs.append(str(e)[:60])
        return errs

    def raw_p2p_send(self, group, dst, n):
        """Drive the ring transport directly (stale-incarnation test)."""
        from ray_tpu.collective import p2p
        from ray_tpu.core.exceptions import CollectiveError

        g = p2p.group_for(group)
        try:
            p2p.p2p_send(g, dst, "stale-probe",
                         np.zeros(n, np.float32), timeout_s=8.0)
            return "ok"
        except CollectiveError as e:
            return ("err", str(e)[:160])

    def arm_death_at_step(self, step_no):
        """Kill this process the moment its NEXT ring op reaches reduce-
        scatter step ``step_no`` — deterministic mid-ring death."""
        import os

        from ray_tpu.collective import p2p

        def hook(phase, step):
            if phase == "rs" and step >= step_no:
                os._exit(1)

        p2p._step_hook = hook
        return True


def _make_group(rt, world, group, cls=Rank):
    members = [cls.remote(i, world) for i in range(world)]
    rt.get([m.setup.remote(group) for m in members], timeout=60)
    return members


# ---------------------------------------------------------------------------
# correctness + wire accounting
# ---------------------------------------------------------------------------


def test_p2p_allreduce_matches_exact_and_wire_bytes(rt):
    members = _make_group(rt, WORLD, "p2p_ar")
    n = 65536  # 256 KiB f32 — well above the p2p floor
    rt.get([m.reset_stats.remote() for m in members], timeout=30)
    head0 = _head_kv_bytes()
    for op in ("sum", "min", "max"):
        outs = rt.get(
            [m.allreduce.remote("p2p_ar", n, op=op) for m in members],
            timeout=120,
        )
        exact = _exact(n, "float32", op=op)
        for out in outs:
            assert out.dtype == np.float32 and out.shape == (n,)
            np.testing.assert_allclose(out, exact, rtol=1e-5, atol=1e-5)
            np.testing.assert_array_equal(out, outs[0])
    # every byte rode the ring: ring allreduce moves exactly
    # 2*(world-1)*(n/world) elements per rank per op, and the head saw
    # NO collective payload traffic at all
    stats = rt.get([m.stats.remote() for m in members], timeout=30)
    expect = 3 * 2 * (WORLD - 1) * (n // WORLD) * 4
    for s in stats:
        assert s["bytes_sent"] == expect, s
        assert s["bytes_recv"] == expect, s
    assert _head_kv_bytes() == head0


def test_head_traffic_independent_of_payload_size(rt):
    members = _make_group(rt, WORLD, "p2p_head")
    deltas = []
    for n in (65536, 262144):  # 256 KiB vs 1 MiB
        before = _head_kv_bytes()
        rt.get([m.allreduce.remote("p2p_head", n) for m in members],
               timeout=120)
        deltas.append(_head_kv_bytes() - before)
    # rendezvous happened at init; the ops themselves are head-free —
    # 4x the payload moves zero extra bytes through the control store
    assert deltas == [0, 0]


def test_reducescatter_allgather_broadcast_p2p(rt):
    members = _make_group(rt, WORLD, "p2p_ops")
    rt.get([m.reset_stats.remote() for m in members], timeout=30)
    head0 = _head_kv_bytes()

    # reducescatter: (8, 8192) f32 = 256 KiB, rank r gets rows 2r..2r+2
    shape = (8, 8192)
    outs = rt.get(
        [m.reducescatter.remote("p2p_ops", shape) for m in members],
        timeout=120,
    )
    exact = _exact(int(np.prod(shape)), "float32").reshape(shape)
    rows = shape[0] // WORLD
    for r, out in enumerate(outs):
        assert out.shape == (rows, shape[1])
        np.testing.assert_allclose(
            out, exact[r * rows:(r + 1) * rows], rtol=1e-5, atol=1e-5
        )

    # allgather with DIFFERENT per-rank sizes (the KV path required
    # nothing here either, but size-divergent routing must not hang)
    gathered = rt.get(
        [m.allgather.remote("p2p_ops", 1000 * (i + 1))
         for i, m in enumerate(members)],
        timeout=120,
    )
    for g in gathered:
        assert [a.size for a in g] == [1000, 2000, 3000, 4000]
        for r, a in enumerate(g):
            np.testing.assert_array_equal(a, np.full(1000 * (r + 1),
                                                     float(r)))

    # broadcast 256 KiB from a non-zero source
    src, n = 1, 65536
    outs = rt.get(
        [m.broadcast.remote("p2p_ops", src, n) for m in members],
        timeout=120,
    )
    ref = _rank_input(src, n, "float32")
    for out in outs:
        np.testing.assert_array_equal(np.asarray(out).reshape(-1), ref)

    stats = rt.get([m.stats.remote() for m in members], timeout=30)
    assert all(s["bytes_sent"] > 0 for s in stats)
    assert _head_kv_bytes() == head0


# ---------------------------------------------------------------------------
# quantized allreduce
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,extra_tol", [
    ("float32", 0.0),
    ("float16", 0.02),   # input representation + final f16 rounding
    ("float64", 0.0),    # accumulation is f32 by design
])
def test_quantized_allreduce_error_bound(rt, dtype, extra_tol):
    members = _make_group(rt, WORLD, f"p2p_q_{dtype}")
    n = 32768 + 7  # non-divisible: exercises ring padding
    outs = rt.get(
        [m.allreduce.remote(f"p2p_q_{dtype}", n, dtype=dtype,
                            quant="int8") for m in members],
        timeout=120,
    )
    exact = _exact(n, dtype)
    # per-element bound: each reduce-scatter hop requantizes a partial
    # sum of k rank contributions (|x| <= 1), error <= k/254 per hop;
    # the allgather quantizes each final chunk once more. For world=4
    # that sums to ~0.05; assert the generous closed form w^2/127.
    bound = (WORLD * WORLD) / 127.0 + extra_tol
    for out in outs:
        assert out.dtype == np.dtype(dtype)
        err = np.abs(out.astype(np.float64) - exact)
        assert err.max() <= bound, (dtype, err.max(), bound)
        # and the quantization is actually useful, not garbage
        assert np.sqrt((err ** 2).mean()) < 0.05
    # allreduce contract: IDENTICAL result on every rank (each chunk's
    # owner adopts the same quantization loss it ships, so data-parallel
    # replicas cannot diverge)
    for out in outs[1:]:
        np.testing.assert_array_equal(out, outs[0])


def test_quantized_allreduce_wire_bytes_reduction(rt):
    members = _make_group(rt, WORLD, "p2p_qwire")
    n = 262144  # 1 MiB f32
    rt.get([m.reset_stats.remote() for m in members], timeout=30)
    rt.get([m.allreduce.remote("p2p_qwire", n) for m in members],
           timeout=120)
    f32_bytes = sum(
        s["bytes_sent"]
        for s in rt.get([m.reset_stats.remote() for m in members],
                        timeout=30)
    )
    rt.get([m.allreduce.remote("p2p_qwire", n, quant="int8")
            for m in members], timeout=120)
    q_bytes = sum(
        s["bytes_sent"]
        for s in rt.get([m.stats.remote() for m in members], timeout=30)
    )
    assert f32_bytes > 0 and q_bytes > 0
    # int8 payload + one f32 scale per 2048-element block ≈ 3.99x fewer
    # wire bytes than f32; the acceptance bar is ≥2x
    assert f32_bytes / q_bytes >= 2.0, (f32_bytes, q_bytes)
    assert f32_bytes / q_bytes > 3.5, (f32_bytes, q_bytes)


def test_quant_parameter_validation(rt):
    members = _make_group(rt, 2, "p2p_qval")
    errs = rt.get(members[0].quant_validation_errors.remote("p2p_qval"),
                  timeout=30)
    assert len(errs) == 3 and all(e is not None for e in errs), errs


def test_quant_roundtrip_unit():
    """Blockwise int8 codec: bounded error, exact zeros, padding tails."""
    from ray_tpu.collective import p2p

    rng = np.random.default_rng(7)
    for n in (1, 100, 2048, 2048 * 3 + 5):
        x = rng.uniform(-3.0, 3.0, n).astype(np.float32)
        block, q, scales = p2p._quant_int8(x)
        assert q.dtype == np.int8 and scales.dtype == np.float32
        back = p2p._dequant_int8(block, q, scales)
        assert back.shape == x.shape
        # half-ulp of the blockwise scale
        per_block_bound = np.repeat(scales, block)[:n] / 2.0 + 1e-7
        assert (np.abs(back - x) <= per_block_bound).all()
    z = np.zeros(100, np.float32)
    block, q, scales = p2p._quant_int8(z)
    np.testing.assert_array_equal(p2p._dequant_int8(block, q, scales), z)


# ---------------------------------------------------------------------------
# send/recv routing
# ---------------------------------------------------------------------------


def test_send_recv_routes_by_size(rt):
    members = _make_group(rt, 2, "p2p_sr")
    a, b = members
    rt.get([m.reset_stats.remote() for m in members], timeout=30)

    # large payload (256 KiB): rides p2p, head sees nothing
    head0 = _head_kv_bytes()
    n_big = 65536
    s = a.send.remote("p2p_sr", 1, n_big, seed=11)
    got = rt.get(b.recv.remote("p2p_sr", 0), timeout=60)
    rt.get(s, timeout=30)
    np.testing.assert_array_equal(got, _rank_input(0, n_big, "float32",
                                                   11))
    assert _head_kv_bytes() == head0
    assert rt.get(b.stats.remote(), timeout=30)["bytes_recv"] == n_big * 4

    # small payload (512 B): rides KV — the receiver's dual wait picks
    # it up off the kv_wait leg
    n_small = 128
    s = a.send.remote("p2p_sr", 1, n_small, seed=12)
    got = rt.get(b.recv.remote("p2p_sr", 0), timeout=60)
    rt.get(s, timeout=30)
    np.testing.assert_array_equal(got, _rank_input(0, n_small, "float32",
                                                   12))
    assert _head_kv_bytes() - head0 >= n_small * 4
    # p2p counters did not move for the small send
    assert rt.get(b.stats.remote(), timeout=30)["bytes_recv"] == n_big * 4

    # interleaved small-then-big to the same receiver stays ordered
    s1 = a.send.remote("p2p_sr", 1, n_small, seed=13)
    rt.get(s1, timeout=30)
    s2 = a.send.remote("p2p_sr", 1, n_big, seed=14)
    got1 = rt.get(b.recv.remote("p2p_sr", 0), timeout=60)
    got2 = rt.get(b.recv.remote("p2p_sr", 0), timeout=60)
    rt.get(s2, timeout=30)
    np.testing.assert_array_equal(
        got1, _rank_input(0, n_small, "float32", 13))
    np.testing.assert_array_equal(
        got2, _rank_input(0, n_big, "float32", 14))


# ---------------------------------------------------------------------------
# kill switch + tiny-payload fallback
# ---------------------------------------------------------------------------


def test_kill_switch_falls_back_to_kv(rt):
    members = [Rank.remote(i, 2) for i in range(2)]
    rt.get([m.set_flag.remote("collective_p2p", False) for m in members],
           timeout=30)
    rt.get([m.setup.remote("p2p_off") for m in members], timeout=60)
    rt.get([m.reset_stats.remote() for m in members], timeout=30)
    head0 = _head_kv_bytes()
    n = 65536
    outs = rt.get([m.allreduce.remote("p2p_off", n) for m in members],
                  timeout=120)
    exact = _exact(n, "float32", world=2)
    for out in outs:
        np.testing.assert_allclose(out, exact, rtol=1e-5, atol=1e-5)
    # everything moved through the head, nothing through the ring
    stats = rt.get([m.stats.remote() for m in members], timeout=30)
    assert all(s["bytes_sent"] == 0 and s["bytes_recv"] == 0
               for s in stats)
    assert _head_kv_bytes() - head0 >= 2 * n * 4
    # restore: worker processes can outlive the actor (pool reuse)
    rt.get([m.set_flag.remote("collective_p2p", True) for m in members],
           timeout=30)


def test_tiny_payload_rides_kv_even_with_p2p(rt):
    members = _make_group(rt, 2, "p2p_tiny")
    rt.get([m.reset_stats.remote() for m in members], timeout=30)
    head0 = _head_kv_bytes()
    outs = rt.get([m.allreduce.remote("p2p_tiny", 16) for m in members],
                  timeout=60)
    exact = _exact(16, "float32", world=2)
    for out in outs:
        np.testing.assert_allclose(out, exact, rtol=1e-6, atol=1e-6)
    stats = rt.get([m.stats.remote() for m in members], timeout=30)
    assert all(s["bytes_sent"] == 0 for s in stats)  # below the floor
    assert _head_kv_bytes() > head0


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------


def test_collective_metrics_recorded(rt):
    members = _make_group(rt, 2, "p2p_metrics")
    outs = rt.get(
        [m.allreduce.remote("p2p_metrics", 65536) for m in members],
        timeout=60,
    )
    # doubles as the 2-rank ring correctness check (1-step phases)
    exact = _exact(65536, "float32", world=2)
    for out in outs:
        np.testing.assert_allclose(out, exact, rtol=1e-5, atol=1e-5)
    snap = rt.get(members[0].metric_snapshot.remote(), timeout=30)
    # series keys are tag-value tuples ordered per tag_keys
    assert snap["bytes"]["series"].get(("allreduce", "p2p"), 0) > 0, snap
    lat = snap["latency"]["series"].get(("allreduce",))
    assert lat is not None and lat["count"] >= 1, snap


# ---------------------------------------------------------------------------
# failure paths
# ---------------------------------------------------------------------------


def test_peer_death_surfaces_error_and_group_reinits(rt):
    members = _make_group(rt, WORLD, "p2p_death")
    victim = members[2]
    survivors = [m for i, m in enumerate(members) if i != 2]
    # fast redial budget so the dead peer surfaces quickly (each retry
    # to a closed port otherwise burns the full 10s connect budget)
    rt.get([m.set_flag.remote("rpc_connect_timeout_s", 2.0)
            for m in survivors], timeout=30)
    # the victim enters the op and dies deterministically MID-ring, at
    # reduce-scatter step 1 (step 0's chunks already exchanged)
    rt.get(victim.arm_death_at_step.remote(1), timeout=30)
    victim.allreduce_catch.remote("p2p_death", 262144, 30.0)
    # no wall-clock bound here: the op carries its own 30 s deadline, and
    # what is asserted is that each survivor's op ENDED, in the op's own
    # error — ring poison where a send to the dead peer failed, the op's
    # deadline where every send had been acknowledged before it died. A
    # survivor that hung would leave this get to raise instead.
    results = rt.get(
        [m.allreduce_catch.remote("p2p_death", 262144, 30.0)
         for m in survivors],
        timeout=300,
    )
    assert all(tuple(r[:2]) == ("err", "CollectiveError") for r in results), results
    rt.get([m.set_flag.remote("rpc_connect_timeout_s", 10.0)
            for m in survivors], timeout=30)

    # re-init after failure: survivors destroy, a replacement rank 2
    # joins, the SAME group name works again
    rt.get([m.destroy.remote("p2p_death") for m in survivors], timeout=30)
    replacement = Rank.remote(2, WORLD)
    regroup = survivors[:2] + [replacement] + survivors[2:]
    rt.get([m.setup.remote("p2p_death") for m in regroup], timeout=180)
    outs = rt.get(
        [m.allreduce.remote("p2p_death", 65536) for m in regroup],
        timeout=120,
    )
    exact = _exact(65536, "float32")
    for out in outs:
        np.testing.assert_allclose(out, exact, rtol=1e-5, atol=1e-5)


def test_send_to_destroyed_incarnation_fails_fast(rt):
    """A delivery the receiver bounces (group destroyed/re-initialized,
    token mismatch) must surface as CollectiveError on the SENDER, not
    be silently swallowed as a clean ack."""
    members = _make_group(rt, 2, "p2p_stale")
    rt.get(members[1].destroy.remote("p2p_stale"), timeout=30)
    res = rt.get(members[0].raw_p2p_send.remote("p2p_stale", 1, 16384),
                 timeout=60)
    assert res[0] == "err" and "dropped" in res[1], res


def test_chaos_allreduce_under_connection_drops(rt):
    """4-rank allreduce with 5% injected request/response drops on the
    ring delivery RPC: idempotent tagged delivery + the reap retry
    ladder must still converge to exact results."""
    members = _make_group(rt, WORLD, "p2p_chaos")
    rt.get(
        [m.set_flag.remote("testing_rpc_failure", "coll_deliver:0.05:0.05")
         for m in members],
        timeout=30,
    )
    try:
        for seed in (21, 22, 23):
            outs = rt.get(
                [m.allreduce.remote("p2p_chaos", 65536, seed=seed)
                 for m in members],
                timeout=180,
            )
            exact = _exact(65536, "float32", seed=seed)
            for out in outs:
                np.testing.assert_allclose(out, exact, rtol=1e-5,
                                           atol=1e-5)
    finally:
        rt.get([m.set_flag.remote("testing_rpc_failure", "")
                for m in members], timeout=30)
