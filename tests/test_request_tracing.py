"""End-to-end request tracing + serving/pipeline SLO metrics (tier-1):
one streaming OpenAI request against a 4-replica deployment must show up
in ``state.timeline()`` as a single cross-pid flow whose
proxy/router/replica/engine spans share one trace id, populate the
TTFT / inter-token-latency histograms, and roll up into a
``state.request_summary()`` row; a compiled-pipeline step must stamp
per-stage fwd/bwd/idle slices whose measured bubble fraction separates
1F1B from GPipe at equal microbatches."""

import http.client
import json
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve, state
from ray_tpu.observability import tracing

MODEL = "tiny"
DEPLOYMENT = "traced-llm"


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

    serve_llm.deploy(
        {MODEL: serve_llm.LLMConfig(model_id="gpt2-tiny", max_batch_size=4)},
        name=DEPLOYMENT, num_replicas=4, route_prefix="/v1",
    )
    deadline = time.monotonic() + 60
    addrs = []
    while time.monotonic() < deadline and not addrs:
        addrs = serve.proxy_addresses()
        time.sleep(0.2)
    assert addrs, "no HTTP proxy came up"
    yield addrs[0]
    serve.delete(DEPLOYMENT)


def _stream_chat(addr, body, headers=None, timeout=180):
    """POST a stream=true chat request; returns (status, sse payloads)."""
    host, port = addr.rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        conn.request(
            "POST", "/v1/chat/completions", body=json.dumps(body),
            headers={"Content-Type": "application/json", **(headers or {})},
        )
        resp = conn.getresponse()
        raw = resp.read().decode()
        events = [
            b[len("data: "):] for b in raw.split("\n\n") if b.strip()
        ]
        return resp.status, events
    finally:
        conn.close()


def _request_slices(trace, trace_id):
    return [
        ev for ev in trace
        if ev.get("cat") == "request" and ev.get("ph") == "X"
        and ev["args"].get("trace_id") == trace_id
    ]


def test_streaming_request_joins_one_trace(front):
    """The curl-shaped acceptance request: one SSE chat completion with a
    client-supplied x-rt-trace-id shows up as ONE joined flow — proxy,
    router, replica, and engine spans all carrying that id — with the
    TTFT/ITL/KV series populated and a request_summary row."""
    addr = front
    tid = "feedfacecafe0001"
    st, events = _stream_chat(addr, {
        "model": MODEL, "max_tokens": 8, "temperature": 0, "user": "alice",
        "stream": True,
        "messages": [{"role": "user", "content": "trace me"}],
    }, headers={tracing.TRACE_HEADER: tid})
    assert st == 200 and events[-1] == "[DONE]"

    # span collection is asynchronous across pids (the proxy stamps in
    # the stream generator's finally): poll until the flow is complete
    deadline = time.monotonic() + 30
    spans = []
    while time.monotonic() < deadline:
        spans = _request_slices(state.timeline(), tid)
        comps = {ev["name"].split(":")[0] for ev in spans}
        if {"proxy", "router", "replica", "engine"} <= comps:
            break
        time.sleep(0.3)
    comps = {ev["name"].split(":")[0] for ev in spans}
    assert {"proxy", "router", "replica", "engine"} <= comps, spans
    # component slices name their deployment (the engine leg reports the
    # model it decoded for)
    assert any(ev["name"] == f"proxy:{DEPLOYMENT}" for ev in spans)
    assert any(ev["name"] == "engine:gpt2-tiny" for ev in spans)
    # proxy and router share one process/clock: proxy opens first and
    # its end-to-end span covers the router's routing span
    proxy = next(ev for ev in spans if ev["name"].startswith("proxy:"))
    router = next(ev for ev in spans if ev["name"].startswith("router:"))
    assert proxy["ts"] <= router["ts"]
    assert proxy["ts"] + proxy["dur"] >= router["ts"] + router["dur"]

    # the flow join: one chain per trace id, start + terminator present,
    # one step per span, the terminator bound to its enclosing slice
    flow = [
        ev for ev in state.timeline()
        if ev.get("cat") == "request_flow" and ev.get("id") == tid
    ]
    assert len(flow) == len(_request_slices(state.timeline(), tid))
    phases = [ev["ph"] for ev in sorted(flow, key=lambda e: e["ts"])]
    assert phases[0] == "s" and phases[-1] == "f"
    assert all(p == "t" for p in phases[1:-1])
    assert next(ev for ev in flow if ev["ph"] == "f")["bp"] == "e"


def test_llm_serving_metrics_populated(front):
    """After traffic, the LLM SLO series are non-empty cluster-wide:
    TTFT and inter-token histograms counted, tokens counter >= the
    request budget, KV-occupancy and queue gauges published."""
    addr = front
    st, _ = _stream_chat(addr, {
        "model": MODEL, "max_tokens": 8, "temperature": 0, "user": "bob",
        "stream": True,
        "messages": [{"role": "user", "content": "measure me"}],
    })
    assert st == 200
    deadline = time.monotonic() + 30
    mx = {}
    while time.monotonic() < deadline:
        mx = state.cluster_metrics()
        ttft = mx.get("rt_serve_ttft_s", {}).get("series", {})
        itl = mx.get("rt_serve_inter_token_s", {}).get("series", {})
        if (
            any(s["count"] for s in ttft.values())
            and any(s["count"] for s in itl.values())
        ):
            break
        time.sleep(0.3)
    ttft = mx["rt_serve_ttft_s"]
    assert any(s["count"] >= 1 for s in ttft["series"].values())
    # the histogram keeps its bucket detail across the merge (identical
    # boundaries in every engine process)
    assert ttft["boundaries"], ttft
    assert any(
        s["count"] >= 1
        for s in mx["rt_serve_inter_token_s"]["series"].values()
    )
    tokens = mx.get("rt_serve_tokens_generated_total", {}).get("series", {})
    assert sum(tokens.values()) >= 8
    assert mx.get("rt_serve_kv_pages_occupied", {}).get("series"), mx.keys()
    assert mx.get("rt_serve_queued_requests", {}).get("series")
    fill = mx.get("rt_serve_batch_fill", {}).get("series", {})
    assert any(s["count"] >= 1 for s in fill.values())


def test_request_summary_rolls_up_percentiles(front):
    """state.request_summary() turns the request spans into a
    per-deployment row: e2e (proxy), queue (router), exec (replica)
    percentile splits, each covering the traffic sent so far."""
    addr = front
    st, _ = _stream_chat(addr, {
        "model": MODEL, "max_tokens": 4, "temperature": 0, "user": "carol",
        "stream": True,
        "messages": [{"role": "user", "content": "summarize me"}],
    })
    assert st == 200
    deadline = time.monotonic() + 30
    entry = None
    while time.monotonic() < deadline:
        summary = state.request_summary()
        entry = summary["deployments"].get(DEPLOYMENT)
        if entry and entry["count"] >= 1 and "exec_s" in entry:
            break
        time.sleep(0.3)
    assert entry and entry["count"] >= 1, entry
    for split in ("e2e_s", "queue_s", "exec_s"):
        assert split in entry, (split, entry)
        for pct in ("p50", "p95", "p99", "mean", "max"):
            assert entry[split][pct] >= 0.0
    # the proxy span wraps replica execution: e2e can't be faster
    assert entry["e2e_s"]["max"] >= entry["exec_s"]["p50"]


def test_engine_phases_nest_under_the_engine_span(front):
    """The engine's leg carries its phases as children: queue, prefill
    and decode slices with parent "engine" under one trace id, inside
    the engine slice, every leg naming the leg that encloses it; the
    flow still has one step per span; and request_summary() rolls the
    phases up under the engine's deployment name."""
    addr = front
    tid = "feedfacecafe0002"
    st, events = _stream_chat(addr, {
        "model": MODEL, "max_tokens": 8, "temperature": 0, "user": "erin",
        "stream": True,
        "messages": [{"role": "user", "content": "phase me"}],
    }, headers={tracing.TRACE_HEADER: tid})
    assert st == 200 and events[-1] == "[DONE]"
    want = {"proxy", "router", "replica", "engine", "engine.queue",
            "engine.prefill", "engine.decode"}
    deadline = time.monotonic() + 30
    by_comp = {}
    while time.monotonic() < deadline and not want <= set(by_comp):
        trace = state.timeline()
        by_comp = {
            ev["name"].split(":")[0]: ev for ev in _request_slices(trace, tid)
        }
        time.sleep(0.3)
    assert want <= set(by_comp), sorted(by_comp)
    parents = {c: ev["args"].get("parent") for c, ev in by_comp.items()}
    assert parents == {
        "proxy": None, "router": "proxy", "replica": "router",
        "engine": "replica", "engine.queue": "engine",
        "engine.prefill": "engine", "engine.decode": "engine",
    }
    eng = by_comp["engine"]
    phases = [by_comp[c] for c in
              ("engine.queue", "engine.prefill", "engine.decode")]
    assert phases[0]["ts"] == eng["ts"]
    for a, b in zip(phases, phases[1:]):
        assert abs(a["ts"] + a["dur"] - b["ts"]) <= 1000
    assert abs(phases[-1]["ts"] + phases[-1]["dur"]
               - eng["ts"] - eng["dur"]) <= 1000
    assert by_comp["engine.prefill"]["args"]["prompt_tokens"] > 0
    assert by_comp["engine.decode"]["args"]["tokens"] == 8
    # the flow follows parentage: one step per span, the engine's
    # phases after every leg that encloses them
    flow = sorted(
        (ev for ev in trace
         if ev.get("cat") == "request_flow" and ev.get("id") == tid),
        key=lambda e: "stf".index(e["ph"]),
    )
    assert len(flow) == len(by_comp)
    assert flow[0]["ph"] == "s" and flow[0]["ts"] == by_comp["proxy"]["ts"]
    assert flow[-1]["ph"] == "f" and flow[-1]["ts"] == phases[-1]["ts"]
    entry = state.request_summary()["deployments"]["gpt2-tiny"]
    for split in ("engine_queue_s", "page_wait_s", "admit_to_first_s"):
        assert entry[split]["p50"] >= 0.0 and entry[split]["max"] < 60.0
    # a roomy pool refuses nobody
    assert entry["page_wait_s"]["max"] == 0.0


def test_trace_minted_when_client_sends_none(front):
    """Without an x-rt-trace-id header the proxy mints one, and the
    downstream legs still join on it."""
    addr = front
    st, _ = _stream_chat(addr, {
        "model": MODEL, "max_tokens": 2, "temperature": 0, "user": "dave",
        "stream": True,
        "messages": [{"role": "user", "content": "mint me"}],
    })
    assert st == 200
    deadline = time.monotonic() + 30
    joined = set()
    while time.monotonic() < deadline and not joined:
        by_tid = {}
        for ev in state.timeline():
            if ev.get("cat") == "request" and ev.get("ph") == "X":
                by_tid.setdefault(ev["args"]["trace_id"], set()).add(
                    ev["name"].split(":")[0]
                )
        joined = {
            tid for tid, comps in by_tid.items()
            if {"proxy", "router", "replica"} <= comps
        }
        time.sleep(0.3)
    assert joined, by_tid
    # minted ids follow new_trace_id()'s shape
    assert any(len(t) == 16 and int(t, 16) >= 0 for t in joined)


# ---------------------------------------------------------------------------
# compiled-pipeline slices + bubble fraction: 1F1B vs GPipe
# ---------------------------------------------------------------------------


def _weighted_stages():
    """Two stages with deliberate, sleep-dominated costs: stage0's
    FORWARD is slow (~30ms) and stage1's BACKWARD is slow (~20ms, via a
    custom_vjp sleep — pullbacks are cached at forward time, so a sleep
    in the primal would never reach the backward op). GPipe can only run
    stage1's expensive backwards after the full forward flush, leaving
    stage0 idle for every one of them; 1F1B overlaps them with stage0's
    remaining forwards, so stage0's measured input-wait (the bubble) is
    structurally smaller."""
    rng = np.random.default_rng(7)
    W1 = rng.normal(size=(8, 16)).astype(np.float32) * 0.3
    W2 = rng.normal(size=(16, 4)).astype(np.float32) * 0.3
    X = rng.normal(size=(32, 8)).astype(np.float32)
    Y = rng.normal(size=(32, 4)).astype(np.float32)

    def stage1(params, x):
        import time as _t

        import jax.numpy as jnp

        _t.sleep(0.03)
        return jnp.tanh(x @ params["w"])

    def stage2(params, h):
        import jax

        @jax.custom_vjp
        def slow_grad_ident(x):
            return x

        def vjp_fwd(x):
            return x, None

        def vjp_bwd(_res, g):
            import time as _t

            _t.sleep(0.02)
            return (g,)

        slow_grad_ident.defvjp(vjp_fwd, vjp_bwd)
        return slow_grad_ident(h @ params["w"])

    def loss_fn(pred, target):
        import jax.numpy as jnp

        return jnp.mean((pred - target) ** 2)

    return W1, W2, X, Y, stage1, stage2, loss_fn


def _stage_events(kind=None, schedule=None, stage=None):
    out = []
    for e in state.task_events():
        if e.get("type") != "pipeline":
            continue
        if kind is not None and e["kind"] != kind:
            continue
        if schedule is not None and e.get("schedule") != schedule:
            continue
        if stage is not None and e["stage"] != stage:
            continue
        out.append(e)
    return out


def test_pipeline_slices_and_bubble_1f1b_beats_gpipe(rt):
    """A compiled step stamps per-stage fwd/bwd slices plus a per-step
    summary carrying bubble_frac, and at equal microbatches the measured
    stage-0 bubble of 1F1B is below GPipe's — the two schedules are
    comparable in one timeline."""
    from ray_tpu.parallel.pipeline import Pipeline

    W1, W2, X, Y, stage1, stage2, loss_fn = _weighted_stages()
    n_mb, n_steps = 4, 3
    bubbles = {}
    for sched in ("gpipe", "1f1b"):
        pipe = Pipeline([stage1, stage2], [{"w": W1}, {"w": W2}], loss_fn)
        cp = pipe.compile(schedule=sched, step_timeout_s=60.0)
        try:
            for _ in range(n_steps):
                cp.train_step(
                    list(np.split(X, n_mb)), list(np.split(Y, n_mb)), lr=0.1
                )
            # collect BEFORE teardown: the slices live in the stage
            # actors' worker event rings
            fwd = _stage_events(kind="fwd", schedule=sched, stage=0)
            bwd = _stage_events(kind="bwd", schedule=sched, stage=0)
            steps = _stage_events(kind="step", schedule=sched, stage=0)
            mx = state.cluster_metrics()
        finally:
            cp.teardown(timeout_s=30.0)
            pipe.shutdown()
        # every microbatch of every step left a slice, stamped with its
        # step/microbatch coordinates
        assert len(fwd) >= n_mb * n_steps, (sched, len(fwd))
        assert len(bwd) >= n_mb * n_steps, (sched, len(bwd))
        assert {e["microbatch"] for e in fwd} == set(range(n_mb))
        assert all(e["dur_us"] > 0 for e in fwd + bwd)
        assert len(steps) >= n_steps
        for e in steps:
            assert 0.0 <= e["bubble_frac"] < 1.0
            assert e["n_microbatches"] == n_mb
        # compare on warm steps only: step 0 carries one-time jax
        # dispatch/compile costs that are schedule-independent noise
        warm = [e["bubble_frac"] for e in steps if e["step"] >= 1]
        bubbles[sched] = sum(warm) / len(warm)
        # the fwd slices are sleep-dominated: stage0's forward floor
        assert max(e["dur_us"] for e in fwd) >= 25_000
        # the cluster-wide metric carries this run's schedule label
        # (snapshotted before teardown: the series live in the stage
        # actors' processes)
        bf = mx.get("rt_pipeline_bubble_fraction", {})
        scheds = {
            dict(zip(bf.get("tag_keys", ()), k)).get("schedule")
            for k in bf.get("series", {})
        }
        assert sched in scheds, (sched, scheds)
        busy = mx.get("rt_pipeline_stage_busy_s", {}).get("series", {})
        assert any(s["count"] >= 1 for s in busy.values())
    # the observability acceptance inequality: same work, same
    # microbatches — 1F1B's interleaving shrinks stage-0's input wait
    assert bubbles["1f1b"] < bubbles["gpipe"], bubbles
