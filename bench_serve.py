"""Standing serve load harness: open-loop Poisson load against the
OpenAI front door, with client-vs-server latency cross-validation.

Closed-loop load (N workers, each waiting for its response before
sending the next) hides queueing collapse: when the server slows down,
a closed loop slows its own arrival rate and the measured latency looks
flat. This harness is **open-loop** — arrival times are drawn from a
Poisson process (exponential inter-arrivals at ``--rate``) up front and
requests launch on schedule regardless of completions, so queueing
delay lands in the numbers instead of in the arrival process. Arrivals
beyond ``--max-inflight`` concurrent SSE clients are counted as shed,
never delayed.

Each client streams ``POST /v1/completions`` (``stream: true``) over a
raw ``http.client`` connection, timestamping every SSE event off the
socket: TTFT = first token event, ITL = gaps between token events, e2e
= request start → ``[DONE]``. Prompt lengths are heavy-tailed
(lognormal, capped) — the byte-level tokenizer maps an ``"a"*n`` prompt
to exactly n tokens, so the tail exercises the power-of-two prefill
buckets the way mixed real traffic would.

After the run the harness cross-validates the observability plane: the
client-measured TTFT p95 must agree with the server-side
histogram-interpolated p95 (``rt_serve_ttft_s`` bucket DELTAS over the
measured window, interpolated by ``utils/metrics.hist_quantile`` — the
same code path ``rt top`` and the alert engine use) within
``max(p95 bucket span, 30% of the larger value, 10 ms)`` — bucket
interpolation cannot resolve finer than the bucket it lands in.

Legs (``--leg``):

- ``steady`` (default): one Poisson window at ``--rate``.
- ``swing``: a 10x load swing in thirds — [rate, 10*rate, rate] — against
  an AUTOSCALING deployment (min 1, max ``--replicas``). A background
  sampler records the replica trajectory (running/target/draining each
  second) and every autoscale decision; the row carries per-phase client
  TTFT so the question "did the autoscaler hold p95 through the swing?"
  is answerable from BENCH_SERVE.json alone.
- ``overload``: arrivals at 10x ``--rate`` against a deployment whose
  proxy admission bound (``--max-queued``) is far below capacity: the
  surplus must shed CLEANLY — instant unary 429/503 + Retry-After,
  counted client-side (``shed_503``/``shed_429``) and server-side
  (``rt_serve_shed_total`` delta), with zero client hangs.
- ``pagedkv``: interleaved same-day A/B of the paged KV engine against
  the pre-paged slot engine (``RT_SERVE_PAGED_KV=0`` semantics, flipped
  per-arm via ``LLMConfig(paged_kv=...)`` so no env churn) at MATCHED
  memory — the paged pool auto-sizes to exactly the slot cache's element
  count. Arms run paged/slot/paged/slot, each a fresh redeploy + its own
  identically-seeded Poisson window, so drift affects both engines
  equally. Each arm records client goodput + tokens/s plus the
  server-side ``rt_serve_batch_fill`` histogram delta (mean fill — the
  page-based-admission shift) and the ``rt_serve_kv_block_copies_total``
  delta (paged prefix hits must not copy).
- ``asyncdecode``: interleaved same-day A/B of the async decode
  pipeline (``RT_SERVE_ASYNC_DECODE``, flipped per-arm via
  ``LLMConfig(async_decode=...)``) on a CLOSED-batch steady leg: a
  fixed pool of ``max_batch_size * replicas`` clients each issues
  back-to-back streams, holding batch fill at the pool size — the
  regime where per-chunk host overhead, not arrival jitter, sets ITL.
  Arms run async/sync/async/sync; each records client ITL p50/p95 +
  aggregate tokens/s plus the server-side
  ``rt_serve_engine_round_host_s`` delta (the engine thread's own time
  per round, which the one-step lookahead runs under the device).

Every run appends one row to BENCH_SERVE.json.

Run: python bench_serve.py --rate 30 --duration 20
     python bench_serve.py --leg swing --rate 2 --duration 60
     python bench_serve.py --leg overload --rate 3 --duration 15
     python bench_serve.py --leg pagedkv --rate 30 --duration 15
     python bench_serve.py --leg asyncdecode --duration 15
"""

import argparse
import http.client
import json
import math
import os
import random
import sys
import threading
import time

MODEL = "bench"
DEPLOYMENT = "bench-llm"


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return None
    idx = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[idx]


def _sample_prompt_len(rng, median, sigma, cap):
    """Lognormal prompt length: median * e^(sigma*N(0,1)), capped. The
    tail (sigma=1 puts ~5% of prompts past 5x the median) is the point —
    uniform prompts would never leave one prefill bucket."""
    n = int(median * math.exp(sigma * rng.gauss(0.0, 1.0)))
    return max(1, min(n, cap))


def _stream_one(host, port, prompt_len, max_tokens, timeout_s):
    """One SSE client: returns a record with ttft/itl/e2e or an error."""
    body = json.dumps({
        "model": MODEL, "prompt": "a" * prompt_len,
        "max_tokens": max_tokens, "temperature": 0, "stream": True,
    })
    rec = {"ok": False, "tokens": 0, "itls": []}
    t0 = time.perf_counter()
    conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
    try:
        conn.request("POST", "/v1/completions", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            resp.read()
            rec["error"] = f"http {resp.status}"
            return rec
        ttft = None
        last = None
        while True:
            line = resp.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue  # SSE blank separator lines
            now = time.perf_counter()
            if line[6:].strip() == b"[DONE]":
                break
            if ttft is None:
                ttft = now - t0
            else:
                rec["itls"].append(now - last)
            last = now
            rec["tokens"] += 1
        rec["ok"] = ttft is not None
        rec["ttft"] = ttft
        rec["e2e"] = time.perf_counter() - t0
    except Exception as e:  # noqa: BLE001 — every failure mode is data
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    return rec


def _hist_bucket_span(bounds, buckets, q):
    """Width of the bucket the q-quantile falls in — the interpolation
    error bound for the server-side percentile."""
    total = sum(buckets)
    if not total or not bounds:
        return 0.0
    rank = q * total
    acc = 0.0
    for i, b in enumerate(buckets[:len(bounds)]):
        acc += b
        if acc >= rank:
            return bounds[i] - (bounds[i - 1] if i else 0.0)
    return bounds[-1] - (bounds[-2] if len(bounds) > 1 else 0.0)


def _sum_ttft_hist(mx):
    """(bounds, buckets, count) of rt_serve_ttft_s summed across series."""
    m = mx.get("rt_serve_ttft_s") or {}
    bounds = list(m.get("boundaries") or ())
    buckets = None
    count = 0.0
    for h in (m.get("series") or {}).values():
        bk = list(h.get("buckets") or ())
        if buckets is None:
            buckets = [0.0] * max(len(bk), len(bounds) + 1)
        for i, v in enumerate(bk):
            buckets[i] += v
        count += h.get("count", 0)
    return bounds, (buckets or []), count


def _batch_fill_totals(mx):
    """(count, sum) of rt_serve_batch_fill summed across series."""
    m = mx.get("rt_serve_batch_fill") or {}
    cnt = sm = 0.0
    for h in (m.get("series") or {}).values():
        cnt += float(h.get("count", 0.0))
        sm += float(h.get("sum", 0.0))
    return cnt, sm


def _counter_total(mx, name):
    m = mx.get(name) or {}
    return float(sum((m.get("series") or {}).values()))


def _append_row(path, row):
    doc = {"schema": 1, "rows": []}
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
        except ValueError:
            pass
    doc.setdefault("rows", []).append(row)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")


def _run_arm_window(host, port, args):
    """One open-loop Poisson window with a per-arm re-seeded RNG, so
    every A/B arm replays the identical arrival schedule + prompt mix."""
    rng = random.Random(args.seed)
    arrivals = []
    t = 0.0
    while True:
        t += rng.expovariate(args.rate)
        if t >= args.duration:
            break
        arrivals.append(t)
    results = []
    lock = threading.Lock()
    inflight = threading.Semaphore(args.max_inflight)
    shed = 0
    threads = []

    def worker(prompt_len):
        try:
            rec = _stream_one(
                host, port, prompt_len, args.max_tokens, args.timeout
            )
        finally:
            inflight.release()
        with lock:
            results.append(rec)

    t0 = time.perf_counter()
    for at in arrivals:
        delay = at - (time.perf_counter() - t0)
        if delay > 0:
            time.sleep(delay)
        if not inflight.acquire(blocking=False):
            shed += 1
            continue
        th = threading.Thread(
            target=worker,
            args=(_sample_prompt_len(
                rng, args.prompt_median, args.prompt_sigma, args.prompt_cap,
            ),),
            daemon=True,
        )
        th.start()
        threads.append(th)
    hung = 0
    for th in threads:
        th.join(timeout=args.timeout + 30)
        hung += th.is_alive()
    wall_s = time.perf_counter() - t0
    return results, len(arrivals), shed, hung, wall_s


def _pagedkv_leg(args, host_meta):
    """Interleaved paged-vs-slot A/B. Redeploying the same deployment
    name swaps the engine (the controller replaces replicas in place and
    the /v1 route survives), and metric deltas are taken strictly inside
    each arm's replica lifetime, so histogram sums never go backwards
    under the merge."""
    import ray_tpu
    from ray_tpu import serve, state
    from ray_tpu.serve import llm as serve_llm

    order = [("paged", True), ("slot", False), ("paged", True),
             ("slot", False)]
    ray_tpu.init(num_cpus=max(8, args.replicas * 2))
    serve.start(http_port=0)
    arms = []
    try:
        for i, (label, paged) in enumerate(order):
            serve_llm.deploy(
                {MODEL: serve_llm.LLMConfig(
                    model_id="gpt2-tiny",
                    max_batch_size=args.max_batch_size,
                    paged_kv=paged,
                )},
                name=DEPLOYMENT, route_prefix="/v1",
                num_replicas=args.replicas,
            )
            deadline = time.monotonic() + 60
            addrs = []
            while time.monotonic() < deadline and not addrs:
                addrs = serve.proxy_addresses()
                time.sleep(0.2)
            assert addrs, "no HTTP proxy came up"
            host, port = addrs[0].rsplit(":", 1)
            port = int(port)
            for n in (8, args.prompt_median, args.prompt_median * 4):
                for _ in range(args.replicas):
                    _stream_one(host, port, n, 4, args.timeout)

            mx0 = state.cluster_metrics()
            c0, s0 = _batch_fill_totals(mx0)
            cp0 = _counter_total(mx0, "rt_serve_kv_block_copies_total")
            results, scheduled, shed, hung, wall_s = _run_arm_window(
                host, port, args
            )
            mx1 = state.cluster_metrics()
            c1, s1 = _batch_fill_totals(mx1)
            cp1 = _counter_total(mx1, "rt_serve_kv_block_copies_total")

            ok = [r for r in results if r.get("ok")]
            ttfts = sorted(r["ttft"] for r in ok)
            itls = sorted(g for r in ok for g in r["itls"])
            tokens = sum(r["tokens"] for r in ok)
            fill = (s1 - s0) / (c1 - c0) if c1 > c0 else None
            p95 = _percentile(ttfts, 0.95)
            itl95 = _percentile(itls, 0.95)
            arms.append({
                "arm": i,
                "engine": label,
                "scheduled": scheduled,
                "requests_ok": len(ok),
                "errors": len(results) - len(ok),
                "shed_client": shed,
                "hung_clients": hung,
                "goodput_rps": round(len(ok) / wall_s, 2),
                "tokens_per_s": round(tokens / wall_s, 1),
                "batch_fill_mean": (
                    round(fill, 3) if fill is not None else None
                ),
                "ttft_p95_ms": round(p95 * 1e3, 1) if p95 else None,
                "itl_p95_ms": round(itl95 * 1e3, 2) if itl95 else None,
                "kv_block_copies": max(0.0, round(cp1 - cp0, 0)),
            })
            print(json.dumps({"arm_done": arms[-1]}), flush=True)

        def mean_of(engine, key):
            vals = [
                a[key] for a in arms
                if a["engine"] == engine and a[key] is not None
            ]
            return sum(vals) / len(vals) if vals else None

        summary = {}
        for key in ("goodput_rps", "tokens_per_s", "batch_fill_mean"):
            p, s = mean_of("paged", key), mean_of("slot", key)
            summary[key] = {
                "paged": round(p, 3) if p is not None else None,
                "slot": round(s, 3) if s is not None else None,
                "ratio": round(p / s, 3) if p and s else None,
            }
        summary["kv_block_copies"] = {
            "paged": mean_of("paged", "kv_block_copies"),
            "slot": None,  # slot engine doesn't publish the counter
        }
        row = {
            "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "host": host_meta,
            "leg": "pagedkv",
            "rate_rps": args.rate,
            "duration_s": args.duration,
            "replicas": args.replicas,
            "max_batch_size": args.max_batch_size,
            "max_tokens": args.max_tokens,
            "prompt": {"median": args.prompt_median,
                       "sigma": args.prompt_sigma, "cap": args.prompt_cap},
            "arms": arms,
            "summary": summary,
        }
        print(json.dumps(row, indent=2))
        _append_row(args.out, row)
        assert all(a["requests_ok"] for a in arms), "an arm served nothing"
        print(json.dumps({"ok": True, "summary": summary}))
        return 0
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def _hist_totals(mx, name):
    """(count, sum) of a histogram summed across series."""
    m = mx.get(name) or {}
    cnt = sm = 0.0
    for h in (m.get("series") or {}).values():
        cnt += float(h.get("count", 0.0))
        sm += float(h.get("sum", 0.0))
    return cnt, sm


def _run_closed_window(host, port, args):
    """Closed-batch steady load: a fixed pool of clients, each issuing
    back-to-back SSE requests for the duration. Per-client re-seeded
    RNGs make every A/B arm replay the identical prompt mix, and the
    closed loop holds batch fill at the pool size — the regime where
    per-chunk host overhead (not arrival jitter) sets ITL."""
    clients = args.max_batch_size * args.replicas
    results = []
    lock = threading.Lock()
    t_end = time.perf_counter() + args.duration

    def worker(wid):
        rng = random.Random(args.seed * 1000 + wid)
        while time.perf_counter() < t_end:
            rec = _stream_one(
                host, port,
                _sample_prompt_len(
                    rng, args.prompt_median, args.prompt_sigma,
                    args.prompt_cap,
                ),
                args.max_tokens, args.timeout,
            )
            with lock:
                results.append(rec)

    t0 = time.perf_counter()
    threads = [
        threading.Thread(target=worker, args=(w,), daemon=True)
        for w in range(clients)
    ]
    for th in threads:
        th.start()
    hung = 0
    for th in threads:
        th.join(timeout=args.duration + args.timeout + 30)
        hung += th.is_alive()
    return results, clients, hung, time.perf_counter() - t0


def _asyncdecode_leg(args, host_meta):
    """Interleaved async-vs-sync decode pipeline A/B on the closed-batch
    steady leg. Both arms run the paged engine with matched batch and
    pool sizes; only RT_SERVE_ASYNC_DECODE flips (carried per-arm on the
    pickled LLMConfig, so no env coordination with replicas). Reports
    client-side ITL p50/p95 + aggregate tokens/s and the server-side
    rt_serve_engine_round_host_s delta — the engine thread's own time
    per round, which the lookahead exists to hide."""
    import ray_tpu
    from ray_tpu import serve, state
    from ray_tpu.serve import llm as serve_llm

    order = [("async", True), ("sync", False), ("async", True),
             ("sync", False)]
    ray_tpu.init(num_cpus=max(8, args.replicas * 2))
    serve.start(http_port=0)
    arms = []
    try:
        for i, (label, async_on) in enumerate(order):
            serve_llm.deploy(
                {MODEL: serve_llm.LLMConfig(
                    model_id="gpt2-tiny",
                    max_batch_size=args.max_batch_size,
                    paged_kv=True, async_decode=async_on,
                )},
                name=DEPLOYMENT, route_prefix="/v1",
                num_replicas=args.replicas,
            )
            deadline = time.monotonic() + 60
            addrs = []
            while time.monotonic() < deadline and not addrs:
                addrs = serve.proxy_addresses()
                time.sleep(0.2)
            assert addrs, "no HTTP proxy came up"
            host, port = addrs[0].rsplit(":", 1)
            port = int(port)
            for n in (8, args.prompt_median, args.prompt_median * 4):
                for _ in range(args.replicas):
                    _stream_one(host, port, n, 4, args.timeout)

            mx0 = state.cluster_metrics()
            g0c, g0s = _hist_totals(mx0, "rt_serve_engine_round_host_s")
            results, clients, hung, wall_s = _run_closed_window(
                host, port, args
            )
            mx1 = state.cluster_metrics()
            g1c, g1s = _hist_totals(mx1, "rt_serve_engine_round_host_s")

            ok = [r for r in results if r.get("ok")]
            itls = sorted(g for r in ok for g in r["itls"])
            tokens = sum(r["tokens"] for r in ok)
            itl50 = _percentile(itls, 0.5)
            itl95 = _percentile(itls, 0.95)
            gap_mean = (g1s - g0s) / (g1c - g0c) if g1c > g0c else None
            arms.append({
                "arm": i,
                "pipeline": label,
                "clients": clients,
                "requests_ok": len(ok),
                "errors": len(results) - len(ok),
                "hung_clients": hung,
                "tokens_per_s": round(tokens / wall_s, 1),
                "itl_p50_ms": round(itl50 * 1e3, 2) if itl50 else None,
                "itl_p95_ms": round(itl95 * 1e3, 2) if itl95 else None,
                "host_ms_mean": (
                    round(gap_mean * 1e3, 3) if gap_mean is not None
                    else None
                ),
                "host_rounds": round(g1c - g0c, 0),
            })
            print(json.dumps({"arm_done": arms[-1]}), flush=True)

        def mean_of(pipeline, key):
            vals = [
                a[key] for a in arms
                if a["pipeline"] == pipeline and a[key] is not None
            ]
            return sum(vals) / len(vals) if vals else None

        summary = {}
        for key in ("tokens_per_s", "itl_p50_ms", "itl_p95_ms",
                    "host_ms_mean"):
            a, s = mean_of("async", key), mean_of("sync", key)
            summary[key] = {
                "async": round(a, 3) if a is not None else None,
                "sync": round(s, 3) if s is not None else None,
                "ratio": round(a / s, 3) if a and s else None,
            }
        row = {
            "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "host": host_meta,
            "leg": "asyncdecode",
            "duration_s": args.duration,
            "replicas": args.replicas,
            "max_batch_size": args.max_batch_size,
            "max_tokens": args.max_tokens,
            "prompt": {"median": args.prompt_median,
                       "sigma": args.prompt_sigma, "cap": args.prompt_cap},
            "arms": arms,
            "summary": summary,
        }
        print(json.dumps(row, indent=2))
        _append_row(args.out, row)
        assert all(a["requests_ok"] for a in arms), "an arm served nothing"
        print(json.dumps({"ok": True, "summary": summary}))
        return 0
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def _autoscale_sampler(stop, out, deployment):
    """1 Hz recorder of the serve control loop: replica trajectory +
    every distinct autoscale decision (deduped by decision timestamp)."""
    from ray_tpu import serve

    seen = set()
    while not stop.wait(1.0):
        try:
            st = serve.autoscale_status().get(deployment)
        except Exception:  # noqa: BLE001 — controller restarting
            continue
        if not st:
            continue
        out["trajectory"].append({
            "t": round(time.perf_counter() - out["t0"], 1),
            "running": st["running"],
            "target": st["target"],
            "draining": len(st["draining"] or {}),
        })
        dec = st.get("last_decision")
        if dec and dec.get("ts") not in seen:
            seen.add(dec.get("ts"))
            out["decisions"].append(dict(dec))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--leg",
                    choices=("steady", "swing", "overload", "pagedkv",
                             "asyncdecode"),
                    default="steady",
                    help="load shape: one rate, a 10x swing against an "
                         "autoscaling deployment, sustained overload "
                         "against a tight admission bound, an "
                         "interleaved paged-vs-slot KV engine A/B, or "
                         "a closed-batch async-vs-sync decode pipeline "
                         "A/B")
    ap.add_argument("--rate", type=float, default=30.0,
                    help="mean arrival rate, requests/s (Poisson); the "
                         "swing/overload legs burst at 10x this")
    ap.add_argument("--duration", type=float, default=20.0,
                    help="load window, seconds")
    ap.add_argument("--replicas", type=int, default=2,
                    help="fixed replica count (steady/overload); the "
                         "autoscaler's max_replicas on the swing leg")
    ap.add_argument("--max-queued", type=int, default=8,
                    help="overload leg: per-deployment proxy admission "
                         "bound (max_queued_requests)")
    ap.add_argument("--target-ongoing", type=int, default=4,
                    help="swing leg: autoscaler target_ongoing_requests")
    ap.add_argument("--max-batch-size", type=int, default=4)
    ap.add_argument("--max-tokens", type=int, default=16,
                    help="tokens generated per request")
    ap.add_argument("--prompt-median", type=int, default=32)
    ap.add_argument("--prompt-sigma", type=float, default=1.0)
    ap.add_argument("--prompt-cap", type=int, default=512)
    ap.add_argument("--max-inflight", type=int, default=256,
                    help="concurrent SSE clients; arrivals past this shed")
    ap.add_argument("--timeout", type=float, default=60.0,
                    help="per-request client timeout, seconds")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_SERVE.json"))
    args = ap.parse_args()

    import ray_tpu
    from ray_tpu import serve, state
    from ray_tpu.observability.history import hist_delta
    from ray_tpu.serve import llm as serve_llm
    from ray_tpu.utils.metrics import hist_quantile

    # sweep debris a SIGKILLed previous run left behind (orphaned
    # daemons, stale shm) — leaked node_mains depress serve numbers —
    # and record the host state the row was measured under, so an
    # outlier in BENCH_SERVE.json is explainable after the fact
    from ray_tpu.core.cluster_utils import sweep_stale_runtime

    swept = sweep_stale_runtime()
    try:
        load1, load5, load15 = os.getloadavg()
    except OSError:
        load1 = load5 = load15 = -1.0
    host_meta = {
        "loadavg": [round(load1, 2), round(load5, 2), round(load15, 2)],
        "cpus": os.cpu_count(),
        "stale_killed": swept.get("killed", 0),
        "stale_removed": swept.get("removed", 0),
    }
    if swept.get("killed") or swept.get("removed"):
        print(json.dumps({"swept_stale_runtime": swept}), flush=True)

    if args.leg == "pagedkv":
        return _pagedkv_leg(args, host_meta)
    if args.leg == "asyncdecode":
        return _asyncdecode_leg(args, host_meta)

    rng = random.Random(args.seed)
    ray_tpu.init(num_cpus=max(8, args.replicas * 2))
    serve.start(http_port=0)
    try:
        deploy_kwargs = {}
        if args.leg == "swing":
            # the swing leg measures the CONTROL LOOP: start at one
            # replica and let the SLO policy ride the 10x burst
            deploy_kwargs = {
                "num_replicas": 1,
                "autoscaling_config": {
                    "min_replicas": 1,
                    "max_replicas": args.replicas,
                    "target_ongoing_requests": args.target_ongoing,
                },
            }
        elif args.leg == "overload":
            deploy_kwargs = {
                "num_replicas": args.replicas,
                "max_queued_requests": args.max_queued,
            }
        else:
            deploy_kwargs = {"num_replicas": args.replicas}
        serve_llm.deploy(
            {MODEL: serve_llm.LLMConfig(
                model_id="gpt2-tiny", max_batch_size=args.max_batch_size,
            )},
            name=DEPLOYMENT, route_prefix="/v1", **deploy_kwargs,
        )
        deadline = time.monotonic() + 60
        addrs = []
        while time.monotonic() < deadline and not addrs:
            addrs = serve.proxy_addresses()
            time.sleep(0.2)
        assert addrs, "no HTTP proxy came up"
        host, port = addrs[0].rsplit(":", 1)
        port = int(port)

        # warm every prefill bucket the lognormal mix will hit, and every
        # replica's decode path, before the measured window
        for n in (8, args.prompt_median, args.prompt_median * 4):
            for _ in range(args.replicas):
                _stream_one(host, port, n, 4, args.timeout)

        # ---- measured window: open-loop Poisson arrivals, piecewise
        # per leg: steady [r], swing [r, 10r, r], overload [10r] ----
        if args.leg == "swing":
            third = args.duration / 3.0
            phases = [(args.rate, third), (10.0 * args.rate, third),
                      (args.rate, third)]
        elif args.leg == "overload":
            phases = [(10.0 * args.rate, args.duration)]
        else:
            phases = [(args.rate, args.duration)]
        arrivals = []
        offset = 0.0
        for rate, dur in phases:
            t = 0.0
            while True:
                t += rng.expovariate(rate)
                if t >= dur:
                    break
                arrivals.append(offset + t)
            offset += dur
        mx0 = state.cluster_metrics()
        b0, k0, c0 = _sum_ttft_hist(mx0)
        shed0 = sum(
            (mx0.get("rt_serve_shed_total") or {}).get("series", {}).values()
        )

        results = []
        results_lock = threading.Lock()
        inflight = threading.Semaphore(args.max_inflight)
        shed = 0
        threads = []

        def worker(at, prompt_len):
            try:
                rec = _stream_one(
                    host, port, prompt_len, args.max_tokens, args.timeout
                )
            finally:
                inflight.release()
            rec["at"] = at  # arrival time: phase attribution in rollup
            with results_lock:
                results.append(rec)

        sampler_stop = threading.Event()
        sampler_out = None
        if args.leg == "swing":
            sampler_out = {
                "t0": time.perf_counter(), "trajectory": [], "decisions": [],
            }
            threading.Thread(
                target=_autoscale_sampler,
                args=(sampler_stop, sampler_out, DEPLOYMENT),
                daemon=True,
            ).start()

        bench_t0 = time.perf_counter()
        for at in arrivals:
            delay = at - (time.perf_counter() - bench_t0)
            if delay > 0:
                time.sleep(delay)
            if not inflight.acquire(blocking=False):
                shed += 1  # open loop: never delay the arrival process
                continue
            th = threading.Thread(
                target=worker,
                args=(at, _sample_prompt_len(
                    rng, args.prompt_median, args.prompt_sigma,
                    args.prompt_cap,
                )),
                daemon=True,
            )
            th.start()
            threads.append(th)
        hung = 0
        for th in threads:
            th.join(timeout=args.timeout + 30)
            hung += th.is_alive()
        wall_s = time.perf_counter() - bench_t0
        sampler_stop.set()

        # ---- client-side rollup ----
        ok = [r for r in results if r.get("ok")]
        shed_429 = sum(
            1 for r in results if r.get("error") == "http 429"
        )
        shed_503 = sum(
            1 for r in results if r.get("error") == "http 503"
        )
        errors = [
            r for r in results
            if not r.get("ok")
            and r.get("error") not in ("http 429", "http 503")
        ]
        ttfts = sorted(r["ttft"] for r in ok)
        e2es = sorted(r["e2e"] for r in ok)
        itls = sorted(g for r in ok for g in r["itls"])
        tokens = sum(r["tokens"] for r in ok)
        client_p95 = _percentile(ttfts, 0.95)

        # ---- server-side: TTFT histogram DELTAS over the window ----
        mx1 = state.cluster_metrics()
        b1, k1, c1 = _sum_ttft_hist(mx1)
        _dc, _ds, dbuckets = hist_delta(
            {"count": c0, "sum": 0.0, "buckets": k0},
            {"count": c1, "sum": 0.0, "buckets": k1},
        )
        server_p95 = hist_quantile(b1, dbuckets, 0.95)
        span = _hist_bucket_span(b1, dbuckets, 0.95)

        assert ok, f"no request succeeded ({len(errors)} errors)"
        assert client_p95 is not None and server_p95 is not None
        tolerance = max(span, 0.30 * max(client_p95, server_p95), 0.010)
        delta = abs(client_p95 - server_p95)
        agree = delta <= tolerance

        alerts_rep = state.alerts()
        firing = [
            a["name"] for a in alerts_rep.get("alerts", ())
            if a.get("state") == "firing"
        ]
        mx_shed = state.cluster_metrics().get("rt_serve_shed_total") or {}
        server_shed = sum(mx_shed.get("series", {}).values()) - shed0

        # per-phase TTFT: the swing question is "did p95 hold through
        # the 10x burst", answered by attributing each ok request to the
        # phase its ARRIVAL fell in
        phase_stats = []
        if len(phases) > 1:
            start = 0.0
            for rate, dur in phases:
                end = start + dur
                sub = sorted(
                    r["ttft"] for r in ok if start <= r.get("at", 0.0) < end
                )
                p50, p95 = _percentile(sub, 0.50), _percentile(sub, 0.95)
                phase_stats.append({
                    "rate_rps": rate,
                    "requests_ok": len(sub),
                    "ttft_p50_ms": round(p50 * 1e3, 1) if p50 else None,
                    "ttft_p95_ms": round(p95 * 1e3, 1) if p95 else None,
                })
                start = end

        row = {
            "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "host": host_meta,
            "leg": args.leg,
            "rate_rps": args.rate,
            "duration_s": args.duration,
            "replicas": args.replicas,
            "max_batch_size": args.max_batch_size,
            "max_tokens": args.max_tokens,
            "prompt": {"median": args.prompt_median,
                       "sigma": args.prompt_sigma, "cap": args.prompt_cap},
            "requests": {
                "scheduled": len(arrivals), "ok": len(ok),
                "errors": len(errors), "shed": shed,
                "shed_429": shed_429, "shed_503": shed_503,
                "server_shed": round(server_shed, 0),
                "hung_clients": hung,
            },
            "goodput_rps": round(len(ok) / wall_s, 2),
            "tokens_per_s": round(tokens / wall_s, 1),
            "client_ms": {
                "ttft_p50": round(_percentile(ttfts, 0.50) * 1e3, 1),
                "ttft_p95": round(client_p95 * 1e3, 1),
                "ttft_p99": round(_percentile(ttfts, 0.99) * 1e3, 1),
                "itl_p50": round((_percentile(itls, 0.50) or 0) * 1e3, 2),
                "itl_p95": round((_percentile(itls, 0.95) or 0) * 1e3, 2),
                "e2e_p50": round(_percentile(e2es, 0.50) * 1e3, 1),
                "e2e_p95": round(_percentile(e2es, 0.95) * 1e3, 1),
            },
            "server_ms": {
                "ttft_p95": round(server_p95 * 1e3, 1),
                "p95_bucket_span": round(span * 1e3, 1),
                "window_count": _dc,
            },
            "agreement": {
                "delta_ms": round(delta * 1e3, 1),
                "tolerance_ms": round(tolerance * 1e3, 1),
                "ok": agree,
            },
            "alerts_firing": firing,
        }
        if phase_stats:
            row["phases"] = phase_stats
        if sampler_out is not None:
            traj = sampler_out["trajectory"]
            row["autoscale"] = {
                "peak_replicas": max(
                    (p["running"] for p in traj), default=0
                ),
                "decisions": sampler_out["decisions"],
                "trajectory": traj,
            }
        print(json.dumps(row, indent=2))

        _append_row(args.out, row)

        if not agree:
            print(
                f"FAIL: client p95 TTFT {client_p95 * 1e3:.1f}ms vs server "
                f"{server_p95 * 1e3:.1f}ms differs by {delta * 1e3:.1f}ms "
                f"> tolerance {tolerance * 1e3:.1f}ms",
                file=sys.stderr,
            )
            return 1
        print(json.dumps({
            "ok": True,
            "goodput_rps": row["goodput_rps"],
            "client_ttft_p95_ms": row["client_ms"]["ttft_p95"],
            "server_ttft_p95_ms": row["server_ms"]["ttft_p95"],
        }))
        return 0
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
