"""Actor tests (parity model: python/ray/tests/test_actor.py)."""

import time

import pytest

import ray_tpu


@pytest.fixture(scope="module")
def rt():
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


@ray_tpu.remote
class Counter:
    def __init__(self, start=0):
        self.value = start

    def incr(self, by=1):
        self.value += by
        return self.value

    def get(self):
        return self.value

    def boom(self):
        raise RuntimeError("actor method failed")


def test_actor_basic(rt):
    c = Counter.remote(10)
    assert rt.get(c.incr.remote()) == 11
    assert rt.get(c.incr.remote(5)) == 16
    assert rt.get(c.get.remote()) == 16


def test_actor_ordered_execution(rt):
    c = Counter.remote(0)
    refs = [c.incr.remote() for _ in range(50)]
    # per-caller ordering: results must be 1..50 in submission order
    assert rt.get(refs) == list(range(1, 51))


def test_actor_method_exception(rt):
    c = Counter.remote()
    with pytest.raises(ray_tpu.exceptions.TaskError, match="actor method failed"):
        rt.get(c.boom.remote())
    # actor survives a method exception
    assert rt.get(c.incr.remote()) == 1


def test_two_actors_isolated(rt):
    a = Counter.remote(0)
    b = Counter.remote(100)
    rt.get([a.incr.remote(), b.incr.remote()])
    assert rt.get(a.get.remote()) == 1
    assert rt.get(b.get.remote()) == 101


def test_named_actor(rt):
    c = Counter.options(name="global_counter").remote(7)
    rt.get(c.get.remote())  # ensure alive
    h = rt.get_actor("global_counter")
    assert rt.get(h.get.remote()) == 7
    # duplicate name rejected
    with pytest.raises(Exception, match="already taken"):
        Counter.options(name="global_counter").remote()


def test_actor_handle_passed_to_task(rt):
    c = Counter.remote(0)

    @rt.remote
    def bump(handle, n):
        import ray_tpu as rt2

        return rt2.get(handle.incr.remote(n))

    assert rt.get(bump.remote(c, 5)) == 5
    assert rt.get(c.get.remote()) == 5


def test_kill_actor(rt):
    c = Counter.remote(0)
    rt.get(c.get.remote())
    rt.kill(c)
    with pytest.raises(
        (ray_tpu.exceptions.ActorDiedError, ray_tpu.exceptions.TaskError)
    ):
        rt.get(c.get.remote(), timeout=30)


def test_actor_restart_on_crash(rt):
    @rt.remote
    class Flaky:
        def __init__(self):
            self.count = 0

        def pid(self):
            import os

            return os.getpid()

        def crash(self):
            import os

            os._exit(1)

    f = Flaky.options(max_restarts=1).remote()
    pid1 = rt.get(f.pid.remote())
    try:
        rt.get(f.crash.remote(), timeout=30)
    except Exception:
        pass
    # actor restarts on a fresh worker
    deadline = time.monotonic() + 30
    pid2 = None
    while time.monotonic() < deadline:
        try:
            pid2 = rt.get(f.pid.remote(), timeout=10)
            break
        except Exception:
            time.sleep(0.2)
    assert pid2 is not None and pid2 != pid1


def test_max_concurrency(rt):
    @rt.remote
    class Slow:
        def work(self):
            import time as t

            t.sleep(0.4)
            return 1

    s = Slow.options(max_concurrency=4).remote()
    start = time.monotonic()
    rt.get([s.work.remote() for _ in range(4)])
    assert time.monotonic() - start < 1.3  # overlapped, not 1.6s serial


def test_detached_lifetime_field(rt):
    c = Counter.options(name="det", lifetime="detached").remote()
    rt.get(c.get.remote())
    info = rt.get_actor("det")
    assert info is not None


def test_async_actor_concurrent_io(rt):
    """async def methods share the actor's event loop: many IO-bound
    calls overlap even with max_concurrency=1 threads (parity: reference
    async actors on the asyncio execution queue)."""
    import time

    import ray_tpu

    @ray_tpu.remote
    class AsyncActor:
        def __init__(self):
            self.peak = 0
            self.active = 0

        async def nap(self, s):
            import asyncio

            self.active += 1
            self.peak = max(self.peak, self.active)
            await asyncio.sleep(s)
            self.active -= 1
            return "ok"

        async def get_peak(self):
            return self.peak

        def sync_echo(self, x):
            return x  # sync methods still work on the same actor

    a = AsyncActor.remote()
    t0 = time.monotonic()
    out = ray_tpu.get([a.nap.remote(1.0) for _ in range(8)], timeout=60)
    elapsed = time.monotonic() - t0
    assert out == ["ok"] * 8
    # 8 overlapping 1s naps must take far less than 8s serial
    assert elapsed < 5.0, f"async calls did not overlap ({elapsed:.1f}s)"
    assert ray_tpu.get(a.get_peak.remote(), timeout=30) >= 2
    assert ray_tpu.get(a.sync_echo.remote(7), timeout=30) == 7


def test_async_actor_errors_propagate(rt):
    import ray_tpu

    @ray_tpu.remote
    class Boomer:
        async def boom(self):
            raise ValueError("async kaboom")

    b = Boomer.remote()
    import pytest as _pytest

    with _pytest.raises(Exception, match="async kaboom"):
        ray_tpu.get(b.boom.remote(), timeout=60)


def test_streaming_actor_method(rt):
    """num_returns="streaming" on actor methods: items arrive through an
    ObjectRefGenerator as the generator yields (parity: reference
    streaming generators on actors)."""
    import ray_tpu

    @ray_tpu.remote(num_cpus=0)
    class Gen:
        @ray_tpu.method(num_returns="streaming")
        def count(self, n):
            for i in range(n):
                yield i * 10

        @ray_tpu.method(num_returns="streaming")
        def flaky(self):
            yield 1
            raise ValueError("stream kaboom")

    g = Gen.remote()
    vals = [ray_tpu.get(r, timeout=60) for r in g.count.remote(5)]
    assert vals == [0, 10, 20, 30, 40]
    # plain methods on the same actor still work
    gen2 = g.count.options(num_returns="streaming").remote(2)
    assert [ray_tpu.get(r, timeout=60) for r in gen2] == [0, 10]
    # errors raise after the produced prefix
    import pytest as _pytest

    gen3 = g.flaky.remote()
    assert ray_tpu.get(next(gen3), timeout=60) == 1
    with _pytest.raises(Exception, match="stream kaboom"):
        ray_tpu.get(next(gen3), timeout=60)


def test_a_consumer_that_fell_behind_takes_what_has_arrived_in_one_go(rt):
    """``ObjectRefGenerator.ready_refs``: after ``next`` gave one item,
    every later item that has already arrived, in order, without waiting;
    nothing where none has, never past the end, and an error is left for
    ``next`` to raise."""
    import time

    import pytest as _pytest

    import ray_tpu

    @ray_tpu.remote(num_cpus=0)
    class Gen:
        @ray_tpu.method(num_returns="streaming")
        def burst_then_slow(self, n):
            for i in range(n):
                yield i
            time.sleep(1.5)
            yield n

        @ray_tpu.method(num_returns="streaming")
        def flaky(self):
            yield 1
            raise ValueError("stream kaboom")

    g = Gen.remote()
    gen = g.burst_then_slow.remote(6)
    first = ray_tpu.get(next(gen), timeout=60)
    time.sleep(0.5)  # the consumer falls behind: the burst lands meanwhile
    rest = [ray_tpu.get(r, timeout=60) for r in gen.ready_refs()]
    assert [first, *rest] == [0, 1, 2, 3, 4, 5]
    assert gen.ready_refs() == []  # the seventh is not there yet, and nobody waits
    assert ray_tpu.get(next(gen), timeout=60) == 6
    time.sleep(0.3)
    assert gen.ready_refs() == []  # the stream has ended
    with _pytest.raises(StopIteration):
        next(gen)
    gen = g.flaky.remote()
    assert ray_tpu.get(next(gen), timeout=60) == 1
    assert gen.ready_refs() == []
    with _pytest.raises(Exception, match="stream kaboom"):
        ray_tpu.get(next(gen), timeout=60)


def test_a_streaming_producer_keeps_to_its_owners_pace(rt, monkeypatch):
    """Each streamed item is answered by the owner before the generator
    resumes (``CoreWorker._stream_returns``): an owner that takes 50 ms an
    item holds a producer that yields at once to that pace, and every item
    still arrives, in order."""
    import time

    import ray_tpu
    from ray_tpu.core.worker import global_worker

    @ray_tpu.remote(num_cpus=0)
    class Gen:
        @ray_tpu.method(num_returns="streaming")
        def burst(self, n):
            t0 = time.monotonic()
            for i in range(n):
                yield i
            yield time.monotonic() - t0

    handlers = global_worker().server._handlers
    landed = handlers["stream_item"]

    def slow(*args, **kwargs):
        time.sleep(0.05)
        return landed(*args, **kwargs)

    monkeypatch.setitem(handlers, "stream_item", slow)
    g = Gen.remote()
    got = [ray_tpu.get(r, timeout=60) for r in g.burst.remote(10)]
    assert got[:10] == list(range(10))
    assert got[10] >= 0.45  # ten replies waited for; sent one way it was ~0
