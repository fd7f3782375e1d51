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

Every run appends one row to BENCH_SERVE.json.

Run: python bench_serve.py --rate 30 --duration 20
     python bench_serve.py --leg swing --rate 2 --duration 60
     python bench_serve.py --leg overload --rate 3 --duration 15
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
                    choices=("steady", "swing", "overload"),
                    default="steady",
                    help="load shape: one rate, a 10x swing against an "
                         "autoscaling deployment, or sustained overload "
                         "against a tight admission bound")
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
