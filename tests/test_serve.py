"""ray_tpu.serve tests (parity model: python/ray/serve/tests/ —
test_deploy, test_proxy, test_autoscaling subset)."""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(scope="module")
def rt():
    ray_tpu.init(num_cpus=6)
    serve.start(http_port=0)
    yield ray_tpu
    serve.shutdown()
    ray_tpu.shutdown()


def _http(addr, path, body=None):
    url = f"http://{addr}{path}"
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method="POST" if data else "GET")
    with urllib.request.urlopen(req, timeout=30) as resp:
        return resp.status, json.loads(resp.read())


def test_function_deployment_handle(rt):
    @serve.deployment(num_replicas=1)
    def square(req):
        return req * req

    handle = serve.run(square.bind())
    assert handle.remote(7).result() == 49
    serve.delete("square")


def test_class_deployment_with_state(rt):
    @serve.deployment(num_replicas=1)
    class Greeter:
        def __init__(self, greeting):
            self.greeting = greeting

        def __call__(self, req):
            return f"{self.greeting}, {req}!"

    handle = serve.run(Greeter.bind("hello"))
    assert handle.remote("world").result() == "hello, world!"
    serve.delete("Greeter")


def test_http_proxy_routes(rt):
    @serve.deployment(num_replicas=1, route_prefix="/echo")
    class Echo:
        def __call__(self, request):
            return {"you_sent": request.json(), "path": request.path}

    serve.run(Echo.bind())
    deadline = time.monotonic() + 30
    addrs = []
    while time.monotonic() < deadline and not addrs:
        addrs = serve.proxy_addresses()
        time.sleep(0.2)
    assert addrs, "no HTTP proxy came up"
    status, body = _http(addrs[0], "/echo", {"a": 1})
    assert status == 200
    assert body["you_sent"] == {"a": 1}
    # unknown route -> 404
    with pytest.raises(urllib.error.HTTPError) as ei:
        _http(addrs[0], "/nope")
    assert ei.value.code == 404
    serve.delete("Echo")


def test_streaming_deployment_over_http(rt):
    """?stream=1 responses arrive as chunked ndjson, one item per yielded
    value (core actor streaming generators under the proxy's chunked
    transfer; parity: reference streaming deployment responses)."""
    import json as json_mod

    @serve.deployment(num_replicas=1, route_prefix="/tick")
    class Ticker:
        def __call__(self, request):
            n = int(request.json().get("n", 3))
            for i in range(n):
                yield {"i": i}

    serve.run(Ticker.bind())
    deadline = time.monotonic() + 30
    addrs = []
    while time.monotonic() < deadline and not addrs:
        addrs = serve.proxy_addresses()
        time.sleep(0.2)
    data = json_mod.dumps({"n": 5}).encode()
    req = urllib.request.Request(
        f"http://{addrs[0]}/tick?stream=1", data=data, method="POST"
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        assert resp.headers.get("Content-Type") == "application/x-ndjson"
        lines = [
            json_mod.loads(raw) for raw in resp.read().decode().splitlines()
            if raw.strip()
        ]
    assert lines == [{"i": i} for i in range(5)], lines
    serve.delete("Ticker")


def test_llm_streaming_tokens_match_batch(rt):
    """stream=True yields tokens one by one and matches the non-streamed
    greedy output (the KV engine pushes per decode step)."""
    from ray_tpu.serve.llm import LLMConfig, build_llm_deployment

    app = build_llm_deployment(LLMConfig(
        model_id="gpt2-tiny", max_batch_size=4,
    ))
    handle = serve.run(app)
    body = {"prompt_tokens": [5, 6, 7], "max_new_tokens": 6}
    full = handle.remote(body).result(timeout_s=180)
    deadline = time.monotonic() + 30
    addrs = []
    while time.monotonic() < deadline and not addrs:
        addrs = serve.proxy_addresses()
        time.sleep(0.2)
    import json as json_mod

    req = urllib.request.Request(
        f"http://{addrs[0]}/llm?stream=1",
        data=json_mod.dumps(body).encode(), method="POST",
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        toks = [
            json_mod.loads(raw)["token"]
            for raw in resp.read().decode().splitlines() if raw.strip()
        ]
    assert toks == full["tokens"], (toks, full)
    serve.delete("llm-gpt2-tiny")


def test_large_response_body_roundtrips(rt):
    """A bulk bytes response crosses the proxy→replica direct RPC as a
    Frame (out-of-band multiseg segment past 32 KiB) and reaches the
    HTTP client intact."""
    payload = bytes(range(256)) * 1024  # 256 KiB, position-dependent

    @serve.deployment(num_replicas=1, route_prefix="/blob")
    class Blob:
        def __call__(self, request):
            return bytes(range(256)) * 1024

    serve.run(Blob.bind())
    deadline = time.monotonic() + 30
    addrs = []
    while time.monotonic() < deadline and not addrs:
        addrs = serve.proxy_addresses()
        time.sleep(0.2)
    with urllib.request.urlopen(
        f"http://{addrs[0]}/blob", timeout=60
    ) as resp:
        body = resp.read()
    assert body == payload
    serve.delete("Blob")


def test_replica_death_recovery(rt):
    @serve.deployment(num_replicas=2)
    def ping(req):
        return "pong"

    handle = serve.run(ping.bind())
    assert handle.remote(None).result() == "pong"

    # kill one replica out from under the controller
    victim = ray_tpu.get_actor("SERVE_REPLICA::ping#0")
    ray_tpu.kill(victim)

    # requests keep succeeding (other replica; router retries)
    for _ in range(5):
        assert handle.remote(None).result(timeout_s=30) == "pong"

    # controller restores 2 healthy replicas
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        st = serve.status()["ping"]
        if st["running"] >= 2:
            break
        time.sleep(0.3)
    assert serve.status()["ping"]["running"] >= 2
    serve.delete("ping")


def test_autoscaling_up_and_down(rt):
    @serve.deployment(
        autoscaling_config={
            "min_replicas": 1,
            "max_replicas": 3,
            "target_ongoing_requests": 1,
        },
        max_concurrency=4,
    )
    def slow(req):
        time.sleep(1.5)
        return "done"

    handle = serve.run(slow.bind())
    assert serve.status()["slow"]["running"] == 1

    # burst of concurrent requests -> scale up
    refs = [handle.remote(None) for _ in range(8)]
    deadline = time.monotonic() + 60
    peak = 1
    while time.monotonic() < deadline:
        peak = max(peak, serve.status()["slow"]["running"])
        if peak >= 2:
            break
        time.sleep(0.3)
    assert peak >= 2, f"never scaled up (peak={peak})"
    assert [r.result(timeout_s=120) for r in refs] == ["done"] * 8

    # idle -> scale back down to min
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if serve.status()["slow"]["running"] == 1:
            break
        time.sleep(0.5)
    assert serve.status()["slow"]["running"] == 1
    serve.delete("slow")


def test_jax_model_deployment(rt):
    """A JAX model served from a replica (the Serve-LLM-lite path)."""

    @serve.deployment(num_replicas=1)
    class Model:
        def __init__(self):
            import numpy as np

            rng = np.random.default_rng(0)
            self.w = rng.normal(size=(4, 2))

        def __call__(self, x):
            import numpy as np

            return (np.asarray(x) @ self.w).tolist()

    handle = serve.run(Model.bind())
    out = handle.remote([[1.0, 0.0, 0.0, 0.0]]).result()
    assert len(out) == 1 and len(out[0]) == 2
    serve.delete("Model")


def test_redeploy_replaces_code(rt):
    @serve.deployment(num_replicas=1)
    def ver(req):
        return "v1"

    handle = serve.run(ver.bind())
    assert handle.remote(None).result() == "v1"

    @serve.deployment(name="ver", num_replicas=1)
    def ver2(req):
        return "v2"

    handle = serve.run(ver2.bind())
    deadline = time.monotonic() + 30
    got = None
    while time.monotonic() < deadline:
        got = handle.remote(None).result(timeout_s=30)
        if got == "v2":
            break
        time.sleep(0.2)
    assert got == "v2"
    serve.delete("ver")


def test_replica_constructor_failure_fails_run_at_once(rt):
    """A replica is ready once its constructor has returned, and a
    constructor that raises fails serve.run with the reason — not a
    ready() that says yes and requests that time out later."""

    @serve.deployment(num_replicas=1)
    class Broken:
        def __init__(self):
            raise RuntimeError("leased a chip, found platform 'cpu'")

        def __call__(self, req):
            return "never"

    t0 = time.monotonic()
    with pytest.raises(Exception, match="found platform 'cpu'"):
        serve.run(Broken.bind(), ready_timeout_s=60)
    assert time.monotonic() - t0 < 30
    serve.delete("Broken")

    @serve.deployment(num_replicas=1)
    class Slow:
        def __init__(self):
            time.sleep(1.5)  # a model load: not ready until it returns
            self.loaded = True

        def __call__(self, req):
            return self.loaded

    t0 = time.monotonic()
    handle = serve.run(Slow.bind())
    assert time.monotonic() - t0 >= 1.5
    assert handle.remote(None).result(timeout_s=30) is True
    serve.delete("Slow")


def test_llm_deployment_batched_generation(rt):
    """Serve-LLM-lite: a GPT-2 deployment decodes token requests, greedy
    decoding is deterministic, and concurrent requests coalesce into
    micro-batches (parity surface of serve.llm's vLLM engine wrapper)."""
    from ray_tpu.serve.llm import LLMConfig, build_llm_deployment

    app = build_llm_deployment(LLMConfig(
        model_id="gpt2-tiny", max_batch_size=8,
    ))
    handle = serve.run(app)
    req = {"prompt_tokens": [1, 2, 3], "max_new_tokens": 5}
    out1 = handle.remote(req).result(timeout_s=120)
    assert len(out1["tokens"]) == 5
    assert all(isinstance(t, int) for t in out1["tokens"])
    # greedy decoding is deterministic
    out2 = handle.remote(req).result(timeout_s=120)
    assert out2["tokens"] == out1["tokens"]
    # sampling with temperature still returns the right count
    out3 = handle.remote(
        {"prompt_tokens": [1, 2, 3], "max_new_tokens": 4, "temperature": 1.0}
    ).result(timeout_s=120)
    assert len(out3["tokens"]) == 4

    # concurrent burst: all succeed, and at least one batch had >1 request
    resps = [
        handle.remote({"prompt_tokens": [i], "max_new_tokens": 3})
        for i in range(8)
    ]
    results = [r.result(timeout_s=180) for r in resps]
    assert all(len(r["tokens"]) == 3 for r in results)
    stats = handle.remote(None, method="batch_stats").result(timeout_s=60)
    assert stats["max_batch"] >= 2, stats
    serve.delete("llm-gpt2-tiny")
