"""Compiled graphs (ray_tpu/dag.py).

Parity model: reference python/ray/dag tests — bind/compile/execute over
static actor DAGs, channel reuse, error propagation, teardown, and the
headline property: the compiled path beats the RPC path per call.
"""

import time

import pytest

import ray_tpu
from ray_tpu.dag import InputNode, MultiOutputNode


@pytest.fixture(scope="module")
def rt():
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


@ray_tpu.remote
class Adder:
    def __init__(self, delta):
        self.delta = delta

    def add(self, x):
        return x + self.delta

    def slow_add(self, x):
        time.sleep(6.0)
        return x + self.delta

    def boom(self, x):
        raise ValueError("boom")


def test_single_actor_chain(rt):
    a = Adder.remote(10)
    with InputNode() as inp:
        dag = a.add.bind(inp)
    cdag = dag.experimental_compile()
    try:
        assert cdag.execute(1).get() == 11
        assert cdag.execute(2).get() == 12
        for i in range(50):  # channel reuse across many rounds
            assert cdag.execute(i).get() == i + 10
    finally:
        cdag.teardown()


def test_two_actor_chain(rt):
    a = Adder.remote(1)
    b = Adder.remote(100)
    with InputNode() as inp:
        dag = b.add.bind(a.add.bind(inp))
    cdag = dag.experimental_compile()
    try:
        assert cdag.execute(5).get() == 106
        assert cdag.execute(6).get() == 107
    finally:
        cdag.teardown()


def test_multi_output(rt):
    a = Adder.remote(1)
    b = Adder.remote(2)
    with InputNode() as inp:
        dag = MultiOutputNode([a.add.bind(inp), b.add.bind(inp)])
    cdag = dag.experimental_compile()
    try:
        assert cdag.execute(10).get() == [11, 12]
    finally:
        cdag.teardown()


def test_error_propagates_and_dag_survives(rt):
    a = Adder.remote(1)
    b = Adder.remote(2)
    with InputNode() as inp:
        dag = b.add.bind(a.boom.bind(inp))
    cdag = dag.experimental_compile()
    try:
        with pytest.raises(ValueError, match="boom"):
            cdag.execute(1).get()
        # the loop keeps serving after an application error
        with pytest.raises(ValueError, match="boom"):
            cdag.execute(2).get()
    finally:
        cdag.teardown()


def test_actor_usable_after_teardown(rt):
    a = Adder.remote(5)
    with InputNode() as inp:
        dag = a.add.bind(inp)
    cdag = dag.experimental_compile()
    assert cdag.execute(1).get() == 6
    cdag.teardown()
    # the exec loop released the actor's executor slot
    assert rt.get(a.add.remote(10), timeout=30) == 15


def test_constant_args(rt):
    @ray_tpu.remote
    class Mixer:
        def mix(self, x, y, z):
            return (x, y, z)

    m = Mixer.remote()
    with InputNode() as inp:
        dag = m.mix.bind(inp, "const", 3)
    cdag = dag.experimental_compile()
    try:
        assert cdag.execute(1).get() == (1, "const", 3)
    finally:
        cdag.teardown()


def test_teardown_with_unconsumed_results(rt):
    """teardown() must not wedge the actor when execute() rounds were
    never consumed (the exec loop is blocked writing the unread output:
    teardown drains it). The actor must serve normal calls afterwards."""
    a = Adder.remote(1)
    with InputNode() as inp:
        dag = a.add.bind(inp)
    cdag = dag.experimental_compile()
    cdag.execute(1)
    cdag.execute(2)  # two unconsumed rounds: exec loop blocked on write
    t0 = time.monotonic()
    cdag.teardown()
    assert time.monotonic() - t0 < 30.0, "teardown stalled"
    # the exec-loop slot was released: plain actor calls work again
    assert rt.get(a.add.remote(10), timeout=60) == 11


def test_execute_inflight_bound(rt):
    """Unconsumed rounds beyond the channel backpressure bound raise a
    clear error instead of blocking inside execute() (reference raises
    RayCgraphCapacityExceeded)."""
    a = Adder.remote(1)
    with InputNode() as inp:
        dag = a.add.bind(inp)
    cdag = dag.experimental_compile()
    try:
        refs = [cdag.execute(1), cdag.execute(2)]
        with pytest.raises(RuntimeError, match="unconsumed"):
            cdag.execute(3)
        assert [r.get() for r in refs] == [2, 3]
        assert cdag.execute(4).get() == 5  # drained: capacity back
    finally:
        cdag.teardown()


def test_execute_inflight_bound_is_configurable(rt):
    """experimental_compile(max_inflight=N) streams N unconsumed rounds
    through the slot rings before raising (satellite: the bound is a
    compile knob now, not a hardcoded 2)."""
    a = Adder.remote(1)
    with InputNode() as inp:
        dag = a.add.bind(inp)
    cdag = dag.experimental_compile(max_inflight=4)
    try:
        refs = [cdag.execute(i) for i in range(4)]  # would raise at 2 before
        with pytest.raises(RuntimeError, match="unconsumed"):
            cdag.execute(99)
        assert [r.get() for r in refs] == [1, 2, 3, 4]
        assert cdag.execute(10).get() == 11  # drained: capacity back
    finally:
        cdag.teardown()


def test_compile_rejects_bad_bounds(rt):
    a = Adder.remote(1)
    with InputNode() as inp:
        dag = a.add.bind(inp)
    with pytest.raises(ValueError, match="max_inflight"):
        dag.experimental_compile(max_inflight=0)
    with pytest.raises(ValueError, match="channel_slots"):
        dag.experimental_compile(channel_slots=0)


def test_teardown_warns_and_unlinks_on_wedged_loop(rt, caplog):
    """A loop stuck in user code past the drain deadline: teardown must
    SAY so (not silently fall through) and still unlink every channel —
    no /dev/shm/rtchan_* debris for sweep_stale_runtime."""
    import logging
    import os

    a = Adder.remote(1)
    with InputNode() as inp:
        dag = a.slow_add.bind(inp)
    cdag = dag.experimental_compile()
    paths = [
        ch.path
        for ch in (cdag._input_channels + cdag._output_channels
                   + cdag._edge_channels)
    ]
    cdag.execute(1)
    time.sleep(0.3)  # the loop is now inside slow_add's sleep
    with caplog.at_level(logging.WARNING, logger="ray_tpu.dag"):
        t0 = time.monotonic()
        cdag.teardown(timeout_s=1.5)
        assert time.monotonic() - t0 < 6.0
    assert "still running" in caplog.text
    for p in paths:
        assert not os.path.exists(p), f"teardown leaked {p}"
        assert not os.path.exists(p + ".d"), f"teardown leaked {p}.d"


def test_multi_actor_edge_channels_unlinked(rt):
    """Actor→actor edge channels (not just driver-facing ones) are
    reclaimed at teardown."""
    import os

    a = Adder.remote(1)
    b = Adder.remote(2)
    with InputNode() as inp:
        dag = b.add.bind(a.add.bind(inp))
    cdag = dag.experimental_compile()
    assert len(cdag._edge_channels) == 1  # the a→b hop
    paths = [ch.path for ch in cdag._edge_channels]
    assert cdag.execute(1).get() == 4
    cdag.teardown()
    for p in paths:
        assert not os.path.exists(p), f"edge channel leaked {p}"


def _submitted_task_ids(name_suffix: str) -> set:
    """Task ids the driver has submitted (its ring of lifecycle events,
    the record ``rt tasks`` is built from) whose name ends in
    ``name_suffix``. A set, so that an old event falling off the bounded
    ring cannot hide a new one from a before/after difference."""
    from ray_tpu.core.worker import global_worker
    from ray_tpu.observability import tracing

    assert tracing.ENABLED, "this test counts the tracer's submit events"
    return {
        e["task_id"] for e in list(global_worker()._task_events)
        if e.get("type") == "lifecycle" and e.get("phase") == tracing.SUBMITTED
        and e["name"].endswith(name_suffix)
    }


def test_compiled_path_beats_rpc_path(rt):
    """What the compiled path promises is structural: after compile,
    ``execute()`` submits no task per call, where ``remote()`` submits
    one. Counted on the owner's submit events, not timed: a wall-clock
    ratio on a shared CPU says how loaded the box is."""
    a = Adder.remote(1)
    # IMPORTANT: run the RPC path BEFORE compiling — the parked exec
    # loop occupies the actor's executor slot (dedicated actor, like the
    # reference), so remote() calls queue until teardown.
    rt.get(a.add.remote(0))
    n = 200
    before = _submitted_task_ids(".add")
    t0 = time.perf_counter()
    for i in range(n):
        assert rt.get(a.add.remote(i)) == i + 1
    rpc_s = (time.perf_counter() - t0) / n
    assert len(_submitted_task_ids(".add") - before) == n

    with InputNode() as inp:
        dag = a.add.bind(inp)
    cdag = dag.experimental_compile()
    try:
        cdag.execute(0).get()
        before = _submitted_task_ids("")
        t0 = time.perf_counter()
        for i in range(n):
            assert cdag.execute(i).get() == i + 1
        compiled_s = (time.perf_counter() - t0) / n
        assert _submitted_task_ids("") - before == set()
    finally:
        cdag.teardown()
    # information only: no assertion reads a clock
    print(f"compiled={compiled_s*1e6:.0f}us rpc={rpc_s*1e6:.0f}us "
          f"ratio={rpc_s / compiled_s:.1f}x")
