"""The serving generator: users in a closed loop against the OpenAI front
door, each running sessions of one or more turns from a traffic plan.

One general generator reads every serving mix: an offline batch is
sessions of one unshared turn with no think time on /v1/completions; a
chat mix is multi-turn sessions over a shared system prompt. It deploys
the configuration through ``serve.llm.deploy``, warms the programs the
mix names, brings the engine to its steady state with the mix's own
traffic, measures for the window, and checks what came back.
"""

from __future__ import annotations

import http.client
import json
import socket
import threading
import time
from typing import Any, Dict, List, Optional

from benchmark import estimators, harness, traffic
from benchmark.harness import say

DEPLOYMENT = "openai-llm"


class Load:
    """Closed-loop users on raw ``http.client`` connections; every SSE
    event is stamped on arrival with ``time.monotonic()``."""

    def __init__(self, addr: str, tr: Dict[str, Any], model: str,
                 plan: Dict[str, Any]):
        self.host, port = addr.rsplit(":", 1)
        self.port = int(port)
        self.tr, self.model, self.plan = tr, model, plan
        self.chat = tr["endpoint"].endswith("/chat/completions")
        self.records: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self._open: Dict[int, http.client.HTTPConnection] = {}
        self.stop = threading.Event()
        self._threads: List[threading.Thread] = []

    # -- one request ------------------------------------------------------

    def request(self, body: Dict[str, Any], kind: str, due: float,
                timeout_s: float, expect_prompt: Optional[int] = None) -> Dict[str, Any]:
        rec: Dict[str, Any] = {
            "kind": kind, "due": due, "sent": None, "events": [], "text": [],
            "status": None, "error": None, "done": None, "cut": False,
            "max_tokens": body["max_tokens"], "usage": None,
            "expect_prompt": expect_prompt,
        }
        conn = http.client.HTTPConnection(self.host, self.port, timeout=timeout_s)
        with self._lock:
            rec["i"] = len(self.records)
            self.records.append(rec)
            self._open[rec["i"]] = conn
        try:
            rec["sent"] = time.monotonic()
            conn.request("POST", self.tr["endpoint"], body=json.dumps(body).encode(),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            rec["status"] = resp.status
            if resp.status != 200:
                rec["error"] = resp.read()[:300].decode(errors="replace")
                return rec
            self._read_stream(resp, rec)
        except Exception as e:  # noqa: BLE001 — every failure is data
            if not rec["cut"]:
                rec["error"] = f"{type(e).__name__}: {e}"
        finally:
            with self._lock:
                self._open.pop(rec["i"], None)
            conn.close()
        return rec

    def _read_stream(self, resp, rec: Dict[str, Any]) -> None:
        counted = 0
        while True:
            line = resp.readline()
            if not line:
                break
            now = time.monotonic()
            if not line.startswith(b"data: "):
                continue
            payload = line[6:].strip()
            if payload == b"[DONE]":
                rec["done"] = now
                return
            doc = json.loads(payload)
            if "error" in doc:
                rec["error"] = json.dumps(doc["error"])[:300]
                return
            choice = doc["choices"][0]
            piece = (choice.get("delta", {}).get("content") if self.chat
                     else choice.get("text")) or ""
            n = estimators.tokens_in_text(piece)
            if doc.get("usage"):
                # tokens a broken multi-byte sequence hid ride the last event
                rec["usage"] = doc["usage"]
                n += max(0, doc["usage"]["completion_tokens"] - counted - n)
            if piece:
                rec["text"].append(piece)
            if n:
                rec["events"].append((now, n))
                counted += n
        if not rec["cut"]:
            rec["error"] = "stream ended without [DONE]"

    # -- users --------------------------------------------------------------

    def start_users(self) -> None:
        t_start = time.monotonic()
        for u in range(int(self.tr["users"])):
            th = threading.Thread(target=self._user, args=(u, t_start),
                                  name=f"bench-user-{u}", daemon=True)
            th.start()
            self._threads.append(th)

    def _user(self, u: int, t_start: float) -> None:
        users = int(self.tr["users"])
        sessions = self.plan["sessions"]
        timeout_s = float(self.tr.get("request_timeout_s", 120))
        due = t_start + float(self.tr.get("ramp_s", 0)) * u / users
        idx, first = u, True
        while not self.stop.is_set():
            session = sessions[idx % len(sessions)]
            idx += users
            script = session["script"]
            # users begin at staggered depths, history already in the
            # prompt, so that the window meets sessions of every depth
            k0 = u % len(script) if first and self.tr.get("stagger_depth") else 0
            first = False
            for k in range(k0, len(script)):
                due += float(script[k]["think_s"])
                if self.stop.wait(max(0.0, due - time.monotonic())):
                    return
                body = traffic.turn_request(self.tr, self.model, self.plan, session, k)
                rec = self.request(body, "load", due, timeout_s,
                                   expect_prompt=int(script[k]["prompt_tokens"]))
                if rec["error"] and self.stop.wait(0.5):
                    return
                due = time.monotonic()

    def in_flight(self) -> int:
        with self._lock:
            return len(self._open)

    def cut(self) -> int:
        """Drop what is still in flight: the front door cancels a request
        whose client has gone."""
        with self._lock:
            conns = list(self._open.items())
            for i, _ in conns:
                self.records[i]["cut"] = True
        for _, conn in conns:
            try:
                if conn.sock is not None:
                    conn.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        return len(conns)

    def join(self, timeout_s: float = 30.0) -> None:
        deadline = time.monotonic() + timeout_s
        for th in self._threads:
            th.join(max(0.0, deadline - time.monotonic()))


def _replica(ray_tpu, serve):
    from ray_tpu.core.actor import ActorHandle

    table = ray_tpu.get(serve.start().get_routing_table.remote(), timeout=30)
    replicas = table["table"][DEPLOYMENT]["replicas"]
    if len(replicas) != 1:
        raise RuntimeError(f"{len(replicas)} replicas in the routing table, want 1")
    return ActorHandle(*replicas[0]["handle_info"])


def _warm_bodies(tr: Dict[str, Any], model: str, seed: int) -> List[Dict[str, Any]]:
    """One request alone for every program the mix will meet: a prompt of
    exactly each prefill width (unshared, so nothing of it is cached),
    and for each decode chunk size K a reply of K + 1 tokens (the first
    comes from the prefill, the rest in one chunk of K). The widths are
    the powers of two from 16 that the engine pads a prefill to, or what
    is left of the context where that is less."""
    import random

    warm = tr["warm"]
    chat = tr["endpoint"].endswith("/chat/completions")
    bodies = []

    def body(n_tokens: int, max_tokens: int, tag: str) -> Dict[str, Any]:
        if chat:
            n_text = n_tokens - len("<|user|>\n<|assistant|>")
            content = traffic.text(random.Random(f"warm/{seed}/{tag}"), n_text)
            return {"model": model, "max_tokens": max_tokens, "temperature": 0,
                    "stream": True,
                    "messages": [{"role": "user", "content": content}]}
        return {"model": model, "max_tokens": max_tokens, "temperature": 0,
                "stream": True,
                "prompt": traffic.text(random.Random(f"warm/{seed}/{tag}"), n_tokens)}

    for w in warm["prefill_widths"]:
        bodies.append(body(int(w), 2, f"w{w}"))
    # a width under one page is met only by the tail behind a prefix hit:
    # the same prompt of one page and w - 2 tokens, twice
    page = int(warm.get("page_tokens", 64))
    for w in warm.get("tail_widths", []):
        bodies += [body(page + int(w) - 2, 2, f"t{w}")] * 2
    # a long tail far into the context is padded to what is left of the
    # context, not to a power of two (274 tokens behind 9 cached pages:
    # 1,024 - 576 = 448): [pages cached, tail tokens], the head first
    for pages, tail in warm.get("tails_behind", []):
        head = int(pages) * page
        bodies += [body(head + page // 2, 2, f"b{pages}"),
                   body(head + int(tail), 2, f"b{pages}")]
    for k in warm["decode_k"]:
        bodies.append(body(int(warm.get("k_prompt_tokens", 48)), int(k) + 1, f"k{k}"))
    return bodies


def run(ctx) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve import llm as serve_llm

    from benchmark import replica_hooks

    tr, cfg, phases = ctx.traffic, ctx.config, ctx.phases
    model = cfg["model_id"]
    plan = traffic.plan(tr, ctx.seed)
    obs: Dict[str, Any] = {"kind": "serve", "model": cfg["model"], "problems": []}
    entries_start = harness.cache_entries()

    ray_tpu.init(**cfg.get("init", {}))
    phases.mark("cluster_up")
    try:
        serve.start(http_port=0)
        with replica_hooks.hooked_front_door():
            serve_llm.deploy(
                {model: serve_llm.LLMConfig(model_id=model, **cfg["engine"])},
                name=DEPLOYMENT, num_replicas=1, **cfg.get("deploy", {}),
            )
        deadline = time.monotonic() + 60
        addrs: List[str] = []
        while not addrs and time.monotonic() < deadline:
            addrs = serve.proxy_addresses()
            time.sleep(0.1)
        if not addrs:
            raise RuntimeError("no HTTP proxy came up")
        obs["deploy_ready_s"] = phases.mark("deploy_ready")
        replica = _replica(ray_tpu, serve)

        def on_replica(method: str, payload: Any = None, timeout: float = 120):
            return ray_tpu.get(
                replica.handle_request.remote(payload, method=method), timeout=timeout
            )

        load = Load(addrs[0], tr, model, plan)
        # 1. every program of this mix, one request at a time; the first
        # loads the engine (weights, pool) and compiles or loads from the
        # cache whatever it meets
        for body in _warm_bodies(tr, model, ctx.seed):
            rec = load.request(body, "warm", time.monotonic(), 900)
            if rec["error"]:
                raise RuntimeError(f"warm request failed: {rec['error']}")
        # the row-update program, one compile per number of changed rows
        warmed = on_replica("bench_warm_rows",
                            {"family": ctx.family, "model": cfg["model"]}, timeout=600)
        say(f"row updates warmed for {warmed}")
        phases.mark("engine_load_and_programs")
        stats = on_replica("engine_stats")
        obs["engine_load_s"] = stats.get("load_s")
        phases.set("engine_load (the engine's own load_s)", stats.get("load_s"))

        # 2. the probe, alone: shorter than one page, so never cached, and
        # prefilled at the same width before and after
        probe = {"model": model, "max_tokens": int(tr["probe"]["max_tokens"]),
                 "temperature": 0, "stream": True}
        if load.chat:
            probe["messages"] = [{"role": "user", "content": plan["probe_prompt"]}]
        else:
            probe["prompt"] = plan["probe_prompt"]
        before = load.request(probe, "probe", time.monotonic(), 300)

        # 3. the mix's own traffic until the engine is in its steady state
        load.start_users()
        time.sleep(float(tr["warm"]["seconds"]))
        phases.mark("warm_traffic")
        names_t0 = harness.cache_names()
        entries_t0 = len(names_t0)
        c0 = harness.counters()

        # ---- the window -------------------------------------------------
        t0, wall0 = time.monotonic(), time.time()
        obs["setup_s"] = wall0 - phases.t0
        say(f"window opens, set-up took {obs['setup_s']:.2f}s")
        traced = None
        samples: List[Dict[str, Any]] = []
        if ctx.trace:
            # per-layer numbers only: counters sampled in the window, and
            # a short trace of the replica's process in the middle of it
            t_trace = min(float(tr.get("trace_offset_s", 5)), ctx.seconds / 3)
            n_trace = min(float(tr.get("trace_seconds", 4)), ctx.seconds / 3)
            while time.monotonic() < t0 + t_trace:
                samples.append(harness.counters())
                time.sleep(1.0)
            ca, ta = harness.counters(), time.monotonic()
            ref = None
            if ctx.platform == "tpu":
                ref = replica.handle_request.remote(
                    {"dir": harness.fresh_trace_dir(), "seconds": n_trace},
                    method="bench_trace",
                )
            time.sleep(n_trace)
            cb, tb = harness.counters(), time.monotonic()
            obs["trace_counters"] = {"before": ca, "after": cb, "seconds": tb - ta}
            if ref is not None:
                traced = ray_tpu.get(ref, timeout=300)
            while time.monotonic() < t0 + ctx.seconds - 1.0:
                samples.append(harness.counters())
                time.sleep(1.0)
        time.sleep(max(0.0, t0 + ctx.seconds - time.monotonic()))
        t1 = time.monotonic()
        # ---- the window is over -------------------------------------------
        c1 = harness.counters()
        names_t1 = harness.cache_names()
        entries_t1 = len(names_t1)
        load.stop.set()
        drain_until = time.monotonic() + float(tr.get("drain_s", 0))
        while load.in_flight() and time.monotonic() < drain_until:
            time.sleep(0.05)
        n_cut = load.cut()
        load.join()
        say(f"window closed; {n_cut} request(s) still in flight were cut")
        idle_by = time.monotonic() + 30
        while time.monotonic() < idle_by:
            if not on_replica("engine_stats").get("occupied"):
                break
            time.sleep(0.2)
        after = load.request(probe, "probe", time.monotonic(), 300)
        # the engine's programs on its own parameters against the plain
        # float32 reference, logits compared, in the replica: after the
        # window, so that the window meets the replica as the traffic left it
        t_check = time.monotonic()
        obs["reference"] = on_replica(
            "bench_check",
            {"family": ctx.family, "model": cfg["model"], "seed": ctx.seed, **cfg["check"]},
            timeout=900,
        )
        say(f"reference check took {time.monotonic() - t_check:.2f}s")
        obs["device"] = harness.with_memory(on_replica("bench_device"))
        obs["engine"] = on_replica("engine_stats")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()

    obs.update({
        "t0": t0, "t1": t1, "records": load.records,
        "counters": {"before": c0, "after": c1, "samples": samples},
        "cache_entries": {"start": entries_start, "t0": entries_t0, "t1": entries_t1,
                          "gained": sorted(names_t1 - names_t0)},
        "trace_dir": traced, "traffic": tr, "check": cfg["check"],
    })
    obs["notes"] = _readings(obs)
    _check(obs, before, after)
    return obs


def _readings(obs: Dict[str, Any]) -> List[str]:
    """The whole-window readings, the sliced ones beside them, and what
    says whether the window was an ordinary one."""
    stream = estimators.stream_of(obs["records"])
    t0, t1 = obs["t0"], obs["t1"]
    due = [r for r in obs["records"] if r["kind"] == "load" and t0 <= r["due"] < t1]

    def ms(x):
        return "none" if x is None else f"{1000 * x:.3f}"

    mem = (obs["device"].get("memory") or [{}])[0]
    return [
        f"allocator { {k: v for k, v in mem.items() if 'bytes' in k} }",
        f"tokens/s: plain (tokens in window / seconds) {estimators.plain_rate(stream, t0, t1):.4f}",
        f"ms per token after the first: pooled whole window "
        f"{ms(estimators.pooled_tpot(stream, t0, t1))}, median of 10 slices "
        f"{ms(estimators.slice_tpot(stream, t0, t1, 10))}",
        "tokens/s by slice: " + " ".join(
            f"{estimators.plain_rate(stream, a, b):.2f}"
            for a, b in estimators.slices(t0, t1, 10)),
        f"{len(due)} turns due in the window, {sum(1 for r in due if r['events'])} "
        f"showed text; {len(stream)} text events",
        "longest pause between token arrivals in the window: {:.3f}s, {:.1f}s after it opened"
        .format(*estimators.longest_pause(stream, t0, t1)),
    ]


def _check(obs: Dict[str, Any], before: Dict[str, Any], after: Dict[str, Any]) -> None:
    """``correct``: every request that ended returned exactly its
    ``max_tokens`` with matching ``usage`` and a closing [DONE], its
    prompt counted as the benchmark counted it, nothing failed, the
    probe, alone before the window and alone after it, read the same, the
    engine's logits are the plain reference's within the configuration's
    tolerance, and nothing compiled inside the window."""
    problems: List[str] = obs["problems"]
    failed = 0
    for rec in obs["records"]:
        bad = None
        if rec["error"] or (rec["status"] not in (200, None)):
            bad = f"HTTP {rec['status']} {rec['error']}"
        elif rec["cut"]:
            continue
        elif rec["done"] is None or rec["usage"] is None:
            bad = "no usage or no [DONE]"
        else:
            got = rec["usage"]["completion_tokens"]
            seen = sum(n for _, n in rec["events"])
            if got != rec["max_tokens"] or seen != got:
                bad = (f"completion_tokens {got}, events carried {seen}, "
                       f"max_tokens {rec['max_tokens']}")
            elif rec["expect_prompt"] is not None and (
                rec["usage"]["prompt_tokens"] != rec["expect_prompt"]
            ):
                bad = (f"prompt_tokens {rec['usage']['prompt_tokens']} != "
                       f"{rec['expect_prompt']} sent")
        if bad:
            failed += 1
            if len(problems) < 8:
                problems.append(f"request {rec['i']} ({rec['kind']}): {bad}")
    a, b = "".join(before["text"]), "".join(after["text"])
    if not a or a != b:
        problems.append("the probe read differently before and after the window")
    ref, tol = obs["reference"], float(obs["check"]["logit_tolerance"])
    worst = max(ref["prefill_max_abs"], ref["decode_max_abs"])
    obs["notes"].append(
        f"against the plain float32 reference (family {ref.get('family')}), "
        f"{ref['rows']} rows of {ref['prompt_lens']} "
        f"prompt tokens and {ref['decode_steps']} decode steps: max |logit difference| "
        f"{ref['prefill_max_abs']:.4g} (prefill) {ref['decode_max_abs']:.4g} (decode), "
        f"reference logits' spread {ref['reference_logit_std']:.3g}, tolerance {tol:g}"
    )
    if not worst <= tol:
        problems.append(f"program and reference logits differ by {worst:.4g} > {tol:g}")
    entries = obs["cache_entries"]
    if entries["t1"] != entries["t0"]:
        problems.append(f"the compile cache went from {entries['t0']} to {entries['t1']} "
                        "entries inside the window: something compiled there "
                        f"({', '.join(n[:48] for n in entries.get('gained', [])[:6])})")
    obs["attempted"] = len(obs["records"])
    obs["failed"] = failed
    obs["compared"] = {
        "requests_failed": [failed, 0],
        "probe_changed": [int(not a or a != b), 0],
        "prefill_logit_gap": [ref["prefill_max_abs"], tol],
        "decode_logit_gap": [ref["decode_max_abs"], tol],
        "cache_entries_gained": [entries["t1"] - entries["t0"], 0],
    }
