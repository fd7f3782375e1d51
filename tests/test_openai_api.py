"""OpenAI front door tests (serve/openai/): an openai-client-shaped
suite — completions + chat + SSE streaming against a 4-replica
deployment through the HTTP proxy, session/model affinity, usage
accounting, OpenAI error bodies, and the SSE edge cases (zero-token
completions, stream/unary parity, client disconnect freeing the
engine's KV slot). No real ``openai`` dependency: the requests and the
response-shape assertions mirror what openai-python sends and parses.
"""

import http.client
import json
import socket
import time
import urllib.error
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve

MODEL = "tiny"
DEPLOYMENT = "openai-llm"


@pytest.fixture(scope="module")
def rt():
    ray_tpu.init(num_cpus=8)
    serve.start(http_port=0)
    yield ray_tpu
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def front(rt):
    """4-replica OpenAI deployment + the proxy address serving it."""
    from ray_tpu.serve import llm as serve_llm

    handle = serve_llm.deploy(
        {MODEL: serve_llm.LLMConfig(model_id="gpt2-tiny", max_batch_size=4)},
        name=DEPLOYMENT, num_replicas=4, route_prefix="/v1",
    )
    deadline = time.monotonic() + 60
    addrs = []
    while time.monotonic() < deadline and not addrs:
        addrs = serve.proxy_addresses()
        time.sleep(0.2)
    assert addrs, "no HTTP proxy came up"
    yield addrs[0], handle
    serve.delete(DEPLOYMENT)


def _post(addr, path, body, timeout=180):
    req = urllib.request.Request(
        f"http://{addr}{path}", data=json.dumps(body).encode(),
        method="POST", headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _sse_events(raw: bytes):
    """Parse an SSE byte stream into its data payloads, asserting the
    exact framing: every event is one ``data: {...}\\n\\n`` block."""
    text = raw.decode()
    blocks = [b for b in text.split("\n\n") if b.strip()]
    events = []
    for b in blocks:
        assert b.startswith("data: "), f"bad SSE framing: {b!r}"
        events.append(b[len("data: "):])
    return events


def _stream(addr, path, body, timeout=180, read_events=None):
    """POST with stream=true over http.client; returns (status, ctype,
    sse payload strings)."""
    host, port = addr.rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        conn.request("POST", path, body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        return resp.status, resp.getheader("Content-Type"), _sse_events(raw)
    finally:
        conn.close()


# ---------------------------------------------------------------------------
# the openai-client-shaped pass: completions + chat + streaming, 4 replicas
# ---------------------------------------------------------------------------


def test_models_endpoint(front):
    addr, _ = front
    with urllib.request.urlopen(f"http://{addr}/v1/models", timeout=30) as r:
        body = json.loads(r.read())
    assert body["object"] == "list"
    assert [m["id"] for m in body["data"]] == [MODEL]
    assert body["data"][0]["object"] == "model"


def test_completion_shape_and_usage(front):
    addr, _ = front
    st, body = _post(addr, "/v1/completions", {
        "model": MODEL, "prompt": "hello world", "max_tokens": 6,
        "temperature": 0, "user": "alice",
    })
    assert st == 200
    assert body["id"].startswith("cmpl-")
    assert body["object"] == "text_completion"
    assert body["model"] == MODEL
    choice = body["choices"][0]
    assert choice["index"] == 0 and isinstance(choice["text"], str)
    assert choice["finish_reason"] == "length"
    usage = body["usage"]
    assert usage["prompt_tokens"] == len("hello world".encode())
    assert usage["completion_tokens"] == 6
    assert usage["total_tokens"] == usage["prompt_tokens"] + 6
    assert body["system_fingerprint"].startswith("rt-replica-")


def test_chat_completion_shape(front):
    addr, _ = front
    st, body = _post(addr, "/v1/chat/completions", {
        "model": MODEL, "max_tokens": 5, "temperature": 0, "user": "alice",
        "messages": [
            {"role": "system", "content": "you are terse"},
            {"role": "user", "content": "hi"},
        ],
    })
    assert st == 200
    assert body["id"].startswith("chatcmpl-")
    assert body["object"] == "chat.completion"
    msg = body["choices"][0]["message"]
    assert msg["role"] == "assistant" and isinstance(msg["content"], str)
    assert body["choices"][0]["finish_reason"] == "length"
    assert body["usage"]["completion_tokens"] == 5


def test_stream_unary_parity_same_prompt(front):
    """stream=false and stream=true on the same greedy prompt decode the
    same text (pinned to one replica by the session key, so both hit the
    same engine deterministically)."""
    addr, _ = front
    req = {"model": MODEL, "prompt": "abcabc", "max_tokens": 8,
           "temperature": 0, "user": "alice"}
    st, unary = _post(addr, "/v1/completions", req)
    assert st == 200
    unary_text = unary["choices"][0]["text"]

    st, ctype, events = _stream(addr, "/v1/completions",
                                {**req, "stream": True})
    assert st == 200 and ctype == "text/event-stream"
    assert events[-1] == "[DONE]"
    chunks = [json.loads(e) for e in events[:-1]]
    streamed = "".join(c["choices"][0]["text"] for c in chunks)
    assert streamed == unary_text, (streamed, unary_text)
    # exactly one chunk carries the finish_reason, and it is the last
    finals = [c for c in chunks if c["choices"][0]["finish_reason"]]
    assert len(finals) == 1 and finals[0] is chunks[-1]
    assert finals[0]["usage"]["completion_tokens"] == 8


def test_chat_streaming_role_then_deltas(front):
    addr, _ = front
    st, ctype, events = _stream(addr, "/v1/chat/completions", {
        "model": MODEL, "max_tokens": 4, "temperature": 0, "user": "alice",
        "stream": True,
        "messages": [{"role": "user", "content": "hey"}],
    })
    assert st == 200 and ctype == "text/event-stream"
    assert events[-1] == "[DONE]"
    chunks = [json.loads(e) for e in events[:-1]]
    assert all(c["object"] == "chat.completion.chunk" for c in chunks)
    # first chunk announces the assistant role, middles carry content,
    # the final chunk has the finish_reason and usage
    assert chunks[0]["choices"][0]["delta"].get("role") == "assistant"
    assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    assert chunks[-1]["usage"]["completion_tokens"] == 4
    content = "".join(
        c["choices"][0]["delta"].get("content", "") for c in chunks
    )
    assert isinstance(content, str)


def test_zero_token_completion_unary_and_stream(front):
    addr, _ = front
    req = {"model": MODEL, "prompt": "xyz", "max_tokens": 0,
           "temperature": 0, "user": "alice"}
    st, body = _post(addr, "/v1/completions", req)
    assert st == 200
    assert body["choices"][0]["text"] == ""
    assert body["choices"][0]["finish_reason"] == "length"
    assert body["usage"]["completion_tokens"] == 0

    st, _ctype, events = _stream(addr, "/v1/completions",
                                 {**req, "stream": True})
    assert st == 200 and events[-1] == "[DONE]"
    chunks = [json.loads(e) for e in events[:-1]]
    # no content chunks — only the finish_reason chunk
    assert all(c["choices"][0]["text"] == "" for c in chunks)
    assert chunks[-1]["usage"]["completion_tokens"] == 0


def test_session_affinity_pins_one_replica(front):
    """The 4-replica affinity criterion: every request with one session
    key lands on the SAME replica (rendezvous pin → warm KV slots),
    while distinct sessions spread across replicas."""
    addr, _ = front
    fingerprints = set()
    for _ in range(6):
        st, body = _post(addr, "/v1/completions", {
            "model": MODEL, "prompt": "pin me", "max_tokens": 1,
            "temperature": 0, "user": "alice",
        })
        assert st == 200
        fingerprints.add(body["system_fingerprint"])
    assert len(fingerprints) == 1, fingerprints

    spread = set()
    for i in range(8):
        st, body = _post(addr, "/v1/completions", {
            "model": MODEL, "prompt": "spread", "max_tokens": 0,
            "temperature": 0, "user": f"user-{i}",
        })
        assert st == 200
        spread.add(body["system_fingerprint"])
    # 8 independent sessions over 4 replicas: all landing on one replica
    # would mean the session key is ignored (P ≈ 6e-5 by chance)
    assert len(spread) >= 2, spread


def test_openai_error_bodies(front):
    addr, _ = front
    st, body = _post(addr, "/v1/completions", {"model": MODEL})
    assert st == 400
    err = body["error"]
    assert err["type"] == "invalid_request_error"
    assert err["param"] == "prompt" and err["code"] == "missing_field"

    st, body = _post(addr, "/v1/completions",
                     {"model": "no-such-model", "prompt": "x"})
    assert st == 404
    assert body["error"]["code"] == "model_not_found"

    st, body = _post(addr, "/v1/chat/completions",
                     {"model": MODEL, "messages": []})
    assert st == 400 and body["error"]["param"] == "messages"


def test_stream_error_rides_sse(front):
    """A stream=true request that fails validation still answers on the
    SSE channel (the proxy committed to streaming from the body probe)."""
    addr, _ = front
    st, ctype, events = _stream(addr, "/v1/completions", {
        "model": "no-such-model", "prompt": "x", "stream": True,
    })
    assert st == 200 and ctype == "text/event-stream"
    assert events[-1] == "[DONE]"
    err = json.loads(events[0])["error"]
    assert err["code"] == "model_not_found"


def test_client_disconnect_mid_stream_keeps_serving(front):
    """Abruptly closing the socket mid-SSE must not wedge the proxy or
    the replica: the stream generator is closed (cancelling the replica
    task), the engine drains back to zero occupied KV slots, and the
    same session keeps serving."""
    addr, handle = front
    host, port = addr.rsplit(":", 1)
    sock = socket.create_connection((host, int(port)), timeout=60)
    body = json.dumps({
        "model": MODEL, "prompt": "disconnect", "max_tokens": 100,
        "temperature": 0, "user": "alice", "stream": True,
    }).encode()
    sock.sendall(
        b"POST /v1/completions HTTP/1.1\r\nHost: x\r\n"
        b"Content-Type: application/json\r\n"
        b"Content-Length: %d\r\n\r\n%s" % (len(body), body)
    )
    got = b""
    while b"data: " not in got:  # first SSE event arrived
        chunk = sock.recv(4096)
        assert chunk, "stream ended before first event"
        got += chunk
    sock.close()  # mid-stream disconnect

    # the engine drains its slot (alice's replica is the only one holding
    # the model, so the model-affinity handle reaches exactly it)
    stats_handle = handle.options(multiplexed_model_id=MODEL)
    deadline = time.monotonic() + 60
    occupied = None
    while time.monotonic() < deadline:
        stats = stats_handle.remote(
            None, method="engine_stats"
        ).result(timeout_s=60)
        occupied = stats.get("occupied")
        if occupied == 0:
            break
        time.sleep(0.3)
    assert occupied == 0, stats

    # and the front door still serves the same session
    st, body = _post(addr, "/v1/completions", {
        "model": MODEL, "prompt": "still alive", "max_tokens": 2,
        "temperature": 0, "user": "alice",
    })
    assert st == 200 and len(body["choices"][0]["text"]) >= 0


# ---------------------------------------------------------------------------
# engine-level: closing the token stream frees the KV slot mid-decode
# ---------------------------------------------------------------------------


def _throttle_decode(monkeypatch, seconds=0.05):
    """Slow down the two decode programs the engine dispatches, so that
    a cancellation or an unload provably lands mid-generation. Returns
    the count of throttled dispatches: a test asserts it moved, or the
    throttle sits on programs the engine no longer calls."""
    from ray_tpu.models import gpt2_decode

    entered = {"n": 0}

    def slow(real):
        def wrapped(*a, **kw):
            entered["n"] += 1
            time.sleep(seconds)
            return real(*a, **kw)
        return wrapped

    for name in ("decode_multi_paged", "decode_paged_and_sample"):
        monkeypatch.setattr(
            gpt2_decode, name, slow(getattr(gpt2_decode, name))
        )
    return entered


def test_engine_stream_close_frees_kv_slot(monkeypatch):
    """Unit-level pin of the cancellation chain: closing _stream_tokens
    marks the request cancelled and the engine reaps its row at the
    next round instead of decoding to max_new for nobody. The decode
    step is throttled so cancellation provably lands mid-generation."""
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    throttled = _throttle_decode(monkeypatch)
    server = LLMServer(LLMConfig(model_id="gpt2-tiny", max_batch_size=2))
    try:
        gen = server({"prompt_tokens": [1, 2, 3], "max_new_tokens": 120,
                      "temperature": 0.0, "stream": True})
        seen = [next(gen) for _ in range(3)]
        assert [s["index"] for s in seen] == [0, 1, 2]
        assert throttled["n"] >= 1, "the engine did not call the throttle"
        rounds_at_close = server.batch_stats()["batches"]
        gen.close()  # client went away

        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if server.batch_stats()["occupied"] == 0:
                break
            time.sleep(0.05)
        stats = server.batch_stats()
        assert stats["occupied"] == 0, stats
        # the engine must NOT have decoded anywhere near the 120-token
        # budget after the close (15+ throttled rounds); a couple of
        # in-flight rounds are allowed
        assert stats["batches"] - rounds_at_close <= 4, (
            stats, rounds_at_close
        )
    finally:
        server.unload()


def test_engine_unload_fails_inflight_requests(monkeypatch):
    """Evicting an engine (multiplex LRU) must FAIL in-flight streams
    immediately — not strand their consumers until the 300s timeout."""
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    throttled = _throttle_decode(monkeypatch)
    server = LLMServer(LLMConfig(model_id="gpt2-tiny", max_batch_size=2))
    gen = server({"prompt_tokens": [1, 2], "max_new_tokens": 120,
                  "temperature": 0.0, "stream": True})
    next(gen)  # request admitted into a decode row
    next(gen)  # ... and a throttled decode chunk has been dispatched
    assert throttled["n"] >= 1, "the engine did not call the throttle"
    t0 = time.monotonic()
    server.unload()
    with pytest.raises(RuntimeError, match="unloaded"):
        for _ in gen:
            pass
    assert time.monotonic() - t0 < 10


def test_engine_unload_releases_prefix_block_pool():
    """Multiplex eviction must not leak the prefix pool: after unload()
    the pool is closed (0 resident blocks, unregistered) even when the
    evicted engine still had cached blocks parked."""
    from ray_tpu.serve import prefix_cache
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    server = LLMServer(LLMConfig(model_id="gpt2-tiny", max_batch_size=2))
    prompt = list(range(100))
    server({"prompt_tokens": prompt, "max_new_tokens": 2,
            "temperature": 0.0})
    pool = server._prefix_pool
    assert pool.resident() > 0
    assert pool in prefix_cache.live_pools()
    server.unload()
    assert pool.resident() == 0
    assert pool not in prefix_cache.live_pools()


# ---------------------------------------------------------------------------
# tokenizer + protocol units
# ---------------------------------------------------------------------------


def test_byte_tokenizer_roundtrip_and_incremental():
    from ray_tpu.serve.openai.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    for text in ("hello", "héllo wörld", "日本語", ""):
        assert tok.decode(tok.encode(text)) == text
    # incremental decoding never splits a multibyte character
    dec = tok.incremental_decoder()
    out = "".join(dec.feed(t) for t in tok.encode("héllo")) + dec.flush()
    assert out == "héllo"


def test_chat_template_flattens_roles():
    from ray_tpu.serve.openai.protocol import ChatMessage
    from ray_tpu.serve.openai.tokenizer import ByteTokenizer, render_chat

    msgs = [ChatMessage("system", "be brief"), ChatMessage("user", "hi")]
    flat = render_chat(msgs)
    assert flat.index("be brief") < flat.index("hi")
    assert flat.endswith("<|assistant|>")
    assert ByteTokenizer().decode(ByteTokenizer().encode(flat)) == flat


def test_request_validation():
    from ray_tpu.serve.openai.protocol import (
        ChatCompletionRequest,
        CompletionRequest,
        OpenAIError,
    )

    r = CompletionRequest.from_body(
        {"model": "m", "prompt": ["one"], "max_tokens": 3}
    )
    assert r.prompt == "one" and r.max_tokens == 3
    with pytest.raises(OpenAIError):
        CompletionRequest.from_body({"prompt": "x"})  # missing model
    with pytest.raises(OpenAIError):
        CompletionRequest.from_body(
            {"model": "m", "prompt": "x", "temperature": 9}
        )
    r = ChatCompletionRequest.from_body({
        "model": "m", "max_completion_tokens": 7,
        "messages": [{"role": "user", "content": "x"}],
    })
    assert r.max_tokens == 7
