"""In-band payload static check (tier-1): the zero-copy data plane's
invariant — hot-path RPC sends never carry raw packed payloads in-band —
must hold for the checked-in source, and the checker must keep catching
each bypass pattern."""

import os
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")
sys.path.insert(0, TOOLS)
sys.path.insert(0, REPO)

from check_inband_payloads import HOT_PATHS, check_file, check_source  # noqa: E402
from tools.rtlint import check_source as rtlint_check  # noqa: E402


def test_hot_paths_have_no_inband_payloads():
    for rel in HOT_PATHS:
        with open(os.path.join(REPO, rel)) as f:
            src = f.read()
        findings = [
            f for f in rtlint_check(src, rel, pass_ids=["inband-payloads"])
            if not f.suppressed
        ]
        assert not findings, "\n".join(f.format() for f in findings)


def test_legacy_shim_api_preserved():
    # tools/check_inband_payloads.py stays a runnable entry point: the
    # string-formatted check_source/check_file surface other repos'
    # CI glue may call.
    violations = check_source(
        'def send(self, v):\n'
        '    self.peer.call("a", payload=serialization.pack(v))\n'
    )
    assert len(violations) == 1
    assert isinstance(violations[0], str) and "send()" in violations[0]
    assert callable(check_file)


def _check(body: str):
    findings = rtlint_check(
        textwrap.dedent(body), pass_ids=["inband-payloads"]
    )
    return [f.message for f in findings if not f.suppressed]


def test_flags_direct_pack_into_call():
    violations = _check("""
        def send(self, value):
            self.agent.call("store", payload=serialization.pack(value))
    """)
    assert len(violations) == 1 and "send()" in violations[0]


def test_flags_pack_via_alias():
    violations = _check("""
        def send(self, value):
            frame = serialization.pack(value)
            self.owner.call_oneway("stream_item", payload=frame)
    """)
    assert len(violations) == 1 and "alias 'frame'" in violations[0]


def test_flags_nested_payload_tuple():
    violations = _check("""
        def send(self, value):
            self.owner.call_oneway(
                "stream_item", payload=("frame", serialization.pack(value))
            )
    """)
    assert len(violations) == 1


def test_flags_tobytes_and_bytes_copies():
    violations = _check("""
        def send(self, arr, view):
            self.peer.call("a", data=arr.tobytes())
            self.peer.call("b", data=bytes(view))
    """)
    assert len(violations) == 2


def test_flags_reply_and_push():
    violations = _check("""
        def handle(self, conn, req_id, value):
            RpcServer.reply(conn, req_id, True, serialization.pack(value))
            conn.push("topic", serialization.dumps(value))
    """)
    assert len(violations) == 2


def test_wrapped_payloads_are_clean():
    violations = _check("""
        def send(self, value, frame):
            self.owner.call_oneway(
                "stream_item",
                payload=("frame", serialization.maybe_frame(
                    serialization.pack_parts(meta, views))),
            )
            self.peer.call("get", payload=serialization.Frame(frame))
            self.peer.call("obj", payload=value)
    """)
    assert not violations, violations


def test_honors_opt_out_comment():
    violations = _check("""
        def send(self, value):
            self.peer.call("wal_append", rec=serialization.dumps(value))  # inband: ok
    """)
    assert not violations, violations


def test_alias_chain_is_tracked():
    violations = _check("""
        def send(self, value):
            blob = serialization.dumps(value)
            rec = blob
            self.peer.call("kv_put", value=rec)
    """)
    assert len(violations) == 1 and "alias 'rec'" in violations[0]


# -- rule 3: RPC reply producers (serve proxy→replica hot path) ----------


def test_flags_raw_return_from_rpc_handler():
    violations = _check("""
        def rpc_serve_call(self, conn, payload):
            return ("ok", serialization.pack(payload))
    """)
    assert len(violations) == 1 and "RPC reply" in violations[0]


def test_flags_raw_return_from_handle_request_direct():
    violations = _check("""
        def handle_request_direct(self, payload, method=None):
            result = self.handle_request(payload, method=method)
            return ("raw", result.tobytes())
    """)
    assert len(violations) == 1 and "handle_request_direct()" in violations[0]


def test_flags_aliased_return_from_rpc_handler():
    violations = _check("""
        def rpc_read_chunk(self, conn, oid):
            blob = serialization.pack(self.store[oid])
            return blob
    """)
    assert len(violations) == 1 and "alias 'blob'" in violations[0]


def test_wrapped_return_is_clean():
    violations = _check("""
        def handle_request_direct(self, payload, method=None):
            result = self.handle_request(payload, method=method)
            if isinstance(result, bytes):
                return ("raw", serialization.maybe_frame(result))
            return ("obj", result)
    """)
    assert not violations, violations


def test_non_reply_functions_may_return_packed():
    # only rpc_*/DIRECT_REPLY_FNS returns are replies; an internal helper
    # returning packed bytes (e.g. for the WAL) is not a wire payload
    violations = _check("""
        def _encode_record(self, value):
            return serialization.dumps(value)
    """)
    assert not violations, violations


def test_nested_generator_returns_are_not_replies():
    # a streaming closure inside an rpc_ handler replies via stream_item
    # pushes (already rule-1 checked), not via its return value
    violations = _check("""
        def rpc_stream(self, conn, payload):
            def gen():
                return serialization.pack(payload)
            return ("ok", None)
    """)
    assert not violations, violations


def test_flags_packed_ring_chunk_send():
    # collective transport shape: a ring chunk delivery must pass the
    # ndarray itself, never a packed blob (which would re-pickle the
    # whole chunk in-band)
    violations = _check("""
        def send_async(g, dst, tag, sub):
            blob = serialization.pack(sub)
            return client.call_async(
                "coll_deliver", group=g.name, tag=tag, payload=blob
            )
    """)
    assert len(violations) == 1 and "alias 'blob'" in violations[0]


def test_ndarray_ring_chunk_send_is_clean():
    violations = _check("""
        def send_async(g, dst, tag, sub):
            return client.call_async(
                "coll_deliver", group=g.name, tag=tag, payload=sub
            )
    """)
    assert not violations, violations


# -- channel-write rule: compiled exec-loop modules (dag/pipeline) -------


def _check_channel(body: str, filename="ray_tpu/dag.py"):
    findings = rtlint_check(
        textwrap.dedent(body), filename, pass_ids=["inband-payloads"]
    )
    return [f.message for f in findings if not f.suppressed]


def test_flags_packed_channel_write_in_dag():
    violations = _check_channel("""
        def _actor_exec_loop(instance, plan):
            ch.write(serialization.pack(result), timeout_s=None)
    """)
    assert len(violations) == 1 and ".write()" in violations[0]


def test_flags_aliased_packed_channel_write_in_pipeline():
    violations = _check_channel("""
        def _stage_exec_loop(instance, plan):
            frame = serialization.pack(activation)
            fwd_out.write(frame)
    """, filename="ray_tpu/parallel/pipeline.py")
    assert len(violations) == 1 and "alias 'frame'" in violations[0]


def test_write_value_and_stop_sentinel_are_clean():
    violations = _check_channel("""
        def _stage_exec_loop(instance, plan):
            fwd_out.write_value(instance.forward(k, x), timeout_s=t)
            ch.write_views(serialization.frame_parts(meta, views))
            cmd.write(_STOP, timeout_s=1.0)
    """)
    assert not violations, violations


def test_channel_write_rule_only_applies_to_exec_loop_modules():
    # a file .write() elsewhere (WAL, sockets) is not a channel send
    violations = _check("""
        def append(self, value):
            self._f.write(serialization.dumps(value))
    """)
    assert not violations, violations
