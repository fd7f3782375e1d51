"""Fixture tests for the rtlint passes added with the unified engine
(blocking-async, dispatcher-block, resource-leak, config-hygiene): one
true positive, one suppressed-with-reason, and one clean negative per
pass, exercised through the engine's check_source entry."""

import os
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.rtlint import check_source  # noqa: E402


def _run(body: str, pass_id: str, filename: str = "<source>"):
    findings = check_source(
        textwrap.dedent(body), filename, pass_ids=[pass_id]
    )
    live = [f for f in findings if not f.suppressed]
    suppressed = [f for f in findings if f.suppressed]
    return live, suppressed


# -- blocking-async ------------------------------------------------------


def test_blocking_async_flags_sleep_in_async_def():
    live, _ = _run("""
        async def handle(self, req):
            time.sleep(0.5)
            return req
    """, "blocking-async")
    assert len(live) == 1
    assert live[0].pass_id == "blocking-async"
    assert "time.sleep" in live[0].message


def test_blocking_async_flags_sync_rpc_in_fast_handler():
    # the serve proxy shape: a fast_handler callback runs ON the event
    # loop even though it is a plain def — regression fixture for the
    # bug class this pass exists to keep out (no live instance exists
    # in ray_tpu today; this pins the detector)
    live, _ = _run("""
        class Proxy:
            def start(self, server):
                server.register("push", fast_handler=self._on_push)

            def _on_push(self, conn, msg):
                self.control.call("ack", msg_id=msg["id"])
                self._ready.wait()
    """, "blocking-async")
    assert len(live) == 2
    assert all("fast_handler" in f.message for f in live)


def test_blocking_async_suppressed_with_reason():
    live, suppressed = _run("""
        async def handle(self, req):
            time.sleep(0.001)  # rtlint: ignore[blocking-async] sub-ms settle before the duplicate-delivery check; measured harmless
    """, "blocking-async")
    assert not live
    assert len(suppressed) == 1 and suppressed[0].reason


def test_blocking_async_clean_negative():
    live, _ = _run("""
        async def handle(self, req, parts):
            await asyncio.sleep(0.5)
            await asyncio.wait_for(self._ready.wait(), timeout=1.0)
            p = self.control.call_async("ack", msg_id=req)
            banner = ", ".join(parts)
            if self._lock.acquire(False):
                self._lock.release()
            return banner, await p.wait_async()
    """, "blocking-async")
    assert not live, [f.format() for f in live]


def test_blocking_async_nested_sync_def_exempt():
    # a nested def is shipped to the pool, not run on the loop
    live, _ = _run("""
        async def handle(self, req):
            def work():
                time.sleep(1.0)
            return await loop.run_in_executor(None, work)
    """, "blocking-async")
    assert not live, [f.format() for f in live]


# -- dispatcher-block ----------------------------------------------------

_DISPATCH_FILE = "ray_tpu/core/control_store.py"


def test_dispatcher_block_flags_caller_deadline_loop():
    live, _ = _run("""
        def rpc_kv_wait(self, conn, key, wait_s):
            deadline = time.monotonic() + wait_s
            while time.monotonic() < deadline:
                with self._cv:
                    self._cv.wait(0.05)
    """, "dispatcher-block", _DISPATCH_FILE)
    assert len(live) == 1
    assert "caller-supplied deadline" in live[0].message


def test_dispatcher_block_flags_direct_param_wait():
    live, _ = _run("""
        def rpc_wait_thing(self, conn, wait_s):
            self._ev.wait(wait_s)
    """, "dispatcher-block", _DISPATCH_FILE)
    assert len(live) == 1
    assert "without a server-side slice cap" in live[0].message


def test_dispatcher_block_flags_helper_one_call_deep():
    live, _ = _run("""
        def rpc_lease(self, conn, wait_s):
            return self._park(wait_s)

        def _park(self, budget):
            end = time.monotonic() + budget
            while time.monotonic() < end:
                self._cv.wait(0.05)
    """, "dispatcher-block", _DISPATCH_FILE)
    assert len(live) == 1
    assert "_park()" in live[0].message


def test_dispatcher_block_flags_bulk_for_loop_deadline_wait():
    # bulk-handler shape (ISSUE 14): iterating the batch with a
    # deadline-bounded wait per record holds the dispatcher thread for
    # batch_size x deadline
    live, _ = _run("""
        def rpc_kill_actors(self, conn, actor_ids, wait_s):
            deadline = time.monotonic() + wait_s
            for actor_id in actor_ids:
                while self._alive(actor_id) and time.monotonic() < deadline:
                    time.sleep(0.01)
        def rpc_register_actors(self, conn, specs, wait_s):
            deadline = time.monotonic() + wait_s
            for spec in specs:
                self._done[spec["actor_id"]].wait(deadline - time.monotonic())
    """, "dispatcher-block", _DISPATCH_FILE)
    assert len(live) >= 2
    assert all("caller-supplied deadline" in f.message for f in live[:2])


def test_dispatcher_block_flags_unbounded_future_result():
    # fan-out-then-block: a bulk handler that parks on pool futures with
    # no timeout holds the dispatcher for as long as the slowest agent
    live, _ = _run("""
        def rpc_kill_actors(self, conn, actor_ids):
            futs = [self._pool.submit(self._kill_one, a) for a in actor_ids]
            return [f.result() for f in futs]
    """, "dispatcher-block", _DISPATCH_FILE)
    assert len(live) == 1
    assert ".result()" in live[0].message


def test_dispatcher_block_bounded_future_result_is_clean():
    live, _ = _run("""
        def rpc_kill_actors(self, conn, actor_ids):
            futs = [self._pool.submit(self._kill_one, a) for a in actor_ids]
            return [f.result(timeout=10.0) for f in futs]
    """, "dispatcher-block", _DISPATCH_FILE)
    assert not live, [f.format() for f in live]


def test_dispatcher_block_suppressed_with_reason():
    live, suppressed = _run("""
        def rpc_wait_thing(self, conn, wait_s):
            self._ev.wait(wait_s)  # rtlint: ignore[dispatcher-block] per-request thread pool, a parked wait holds no shared dispatcher
    """, "dispatcher-block", _DISPATCH_FILE)
    assert not live
    assert len(suppressed) == 1 and suppressed[0].reason


def test_dispatcher_block_sliced_wait_is_clean():
    live, _ = _run("""
        def rpc_kv_wait(self, conn, key, wait_s):
            wait_s = min(wait_s, float(config.dispatch_wait_slice_s))
            deadline = time.monotonic() + wait_s
            while time.monotonic() < deadline:
                with self._cv:
                    self._cv.wait(0.05)
    """, "dispatcher-block", _DISPATCH_FILE)
    assert not live, [f.format() for f in live]


def test_dispatcher_block_periodic_maintenance_is_clean():
    live, _ = _run("""
        def rpc_noop(self, conn):
            return True

        def _health_loop(self):
            while not self._stopped.wait(1.0):
                self._sweep()
    """, "dispatcher-block", _DISPATCH_FILE)
    assert not live, [f.format() for f in live]


# -- resource-leak -------------------------------------------------------


def test_resource_leak_flags_unclosed_channel():
    live, _ = _run("""
        def send(handle, value, timeout_s):
            chan = channels.open_channel(handle, "write")
            chan.write_value(value, timeout_s=timeout_s)
    """, "resource-leak", "ray_tpu/x.py")
    assert len(live) == 1
    assert "never reaches close" in live[0].message


def test_resource_leak_flags_discarded_creation():
    live, _ = _run("""
        def notify(h):
            open_channel(h, "write").write(b"stop")
    """, "resource-leak", "ray_tpu/x.py")
    assert len(live) == 1
    assert "used without a handle" in live[0].message


def test_resource_leak_suppressed_with_reason():
    live, suppressed = _run("""
        def spawn(self):
            t = threading.Thread(target=self._run)  # rtlint: ignore[resource-leak] joined by the registry's shutdown sweep, not here
            t.start()
    """, "resource-leak", "ray_tpu/x.py")
    assert not live
    assert len(suppressed) == 1 and suppressed[0].reason


def test_resource_leak_clean_negatives():
    live, _ = _run("""
        def a(handle, shipment):
            chan = channels.open_channel(handle, "write")
            try:
                chan.write_value(shipment)
            finally:
                chan.close()

        def b(path):
            with mmap.mmap(-1, 4096) as m:
                return bytes(m[:16])

        def c(self):
            self._sock = socket.socket()

        def d():
            return socket.create_connection(("h", 1))

        def e(self):
            t = threading.Thread(target=self._run, daemon=True)
            t.start()
    """, "resource-leak", "ray_tpu/x.py")
    assert not live, [f.format() for f in live]


# -- config-hygiene ------------------------------------------------------


def test_config_hygiene_flags_raw_rt_read():
    live, _ = _run("""
        def addr():
            return os.environ.get("RT_ADDRESS", "")
    """, "config-hygiene", "ray_tpu/x.py")
    assert len(live) == 1
    assert "bypasses utils/config" in live[0].message


def test_config_hygiene_flags_subscript_and_getenv():
    live, _ = _run("""
        KEY = "RT_XLA_RANK"

        def rank():
            if "RT_XLA_GROUP" in os.environ:
                return int(os.environ[KEY])
            return int(os.getenv("RT_XLA_RANK", "0"))
    """, "config-hygiene", "ray_tpu/x.py")
    assert len(live) == 3


def test_config_hygiene_suppressed_with_reason():
    live, suppressed = _run("""
        def boot():
            return os.environ.get("RT_CONFIG_SNAPSHOT")  # rtlint: ignore[config-hygiene] boot protocol: read before config exists
    """, "config-hygiene", "ray_tpu/x.py")
    assert not live
    assert len(suppressed) == 1 and suppressed[0].reason


def test_config_hygiene_clean_negative():
    live, _ = _run("""
        def fine():
            home = os.environ.get("HOME", "/")
            chips = os.environ.get("TPU_VISIBLE_CHIPS")
            return home, chips, config.num_tpus
    """, "config-hygiene", "ray_tpu/x.py")
    assert not live, [f.format() for f in live]


def _project_findings(tmp_path, config_src: str, reader_src: str):
    """config-hygiene's project rules over a tree of three files: a
    utils/config.py, one module that may read it, a README naming RT_A
    and RT_B."""
    from tools.rtlint.passes.config_hygiene import PASS

    pkg = tmp_path / "ray_tpu"
    (pkg / "utils").mkdir(parents=True)
    (pkg / "utils" / "config.py").write_text(textwrap.dedent(config_src))
    (pkg / "reader.py").write_text(textwrap.dedent(reader_src))
    (tmp_path / "README.md").write_text("| `RT_A` | | `RT_B` |\n")
    return PASS.project_check(str(tmp_path))


def test_config_hygiene_flags_a_flag_nothing_reads(tmp_path):
    # its own define, a set and a mention in a comment are not reads
    findings = _project_findings(tmp_path, """
        config.define("a", 1)
        config.define("b", 2)
    """, """
        def f(config):
            # b is the other one
            return config.a
    """)
    assert [(f.line, f.suppressed) for f in findings] == [(3, False)]
    assert "'b'" in findings[0].message and "read by no file" in findings[0].message


def test_config_hygiene_read_flags_are_clean_and_unread_is_suppressible(tmp_path):
    findings = _project_findings(tmp_path, """
        config.define("a", 1)
        config.define("b", 2)
        config.define("c", 3)  # rtlint: ignore[config-hygiene] read by the native core, not by Python
    """, """
        def f(config):
            return config.a, config.get("b")
    """)
    # c is neither documented nor read: both findings carry the reason
    assert [f.line for f in findings] == [4, 4]
    assert all(f.suppressed and f.reason for f in findings)
