"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One command, no arguments. It drives the two main paths once, through the
entry points a user calls, at gpt2-small's published width with seeded
random weights, sized from the number of chips the node agent finds
(N = 1 or 4):

  serve   serve.start + serve.llm.deploy, N one-chip replicas, requests over
          HTTP to the proxy (/v1/completions and /v1/chat/completions,
          unary and SSE, cold cache);
  train   train.JaxTrainer, one worker holding all N chips, tokens from a
          ray_tpu.data pipeline, gpt2.make_train_step at the tuned
          configuration, one warm-up step and five timed ones.

This process never initialises a JAX backend: a chip belongs to one process
at a time, and the processes that compute are the workers the node agent
leases chips to. The phases hold the chips one after the other.

It exits non-zero — and prints no result line — when there is no TPU to run
on, when JAX_PLATFORMS keeps the program off it, and on any failed check,
request or phase. On success the last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as JAX reports it inside the train worker. Throughput lines
are information for the benchmark to come, not metrics.

tests/test_chip_smoke.py rehearses both phases on the CPU at gpt2-tiny by
calling serve_phase/train_phase with platform="cpu".
"""

from __future__ import annotations

import concurrent.futures
import http.client
import importlib.metadata
import json
import math
import os
import sys
import time
from typing import Any, Dict, List, Optional

MODEL = "gpt2-small"
DEPLOYMENT = "openai-llm"
BATCH_PER_CHIP = 32
TIMED_STEPS = 5
# one cold request pays the engine load and every compile on its way;
# the proxy allows a unary call 300 s and a stream 600 s per event
REQUEST_TIMEOUT_S = 600


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CheckFailed(RuntimeError):
    pass


def require(failures: List[str], ok: bool, what: str) -> None:
    """Record a failed check; phases raise once, naming all of them."""
    if not ok:
        failures.append(what)
        say(f"CHECK FAILED: {what}")


# ---------------------------------------------------------------------------
# what the parent may know without touching JAX
# ---------------------------------------------------------------------------


def compile_cache_dir() -> str:
    from ray_tpu.accelerators import tpu as tpu_mod

    return os.environ.get(tpu_mod.COMPILE_CACHE_ENV) or (
        tpu_mod.DEFAULT_COMPILE_CACHE_DIR
    )


def cache_entries() -> int:
    return sum(len(files) for _, _, files in os.walk(compile_cache_dir()))


def _model_dims(model_id: str) -> Dict[str, int]:
    """Runs in a cpu worker: the parent never imports the model code."""
    from ray_tpu.models import gpt2

    cfg = gpt2.CONFIGS[model_id]
    return {
        "n_positions": cfg.n_positions, "vocab_size": cfg.vocab_size,
        "n_head": cfg.n_head, "n_layer": cfg.n_layer,
    }


def model_dims(model_id: str) -> Dict[str, int]:
    import ray_tpu

    return ray_tpu.get(
        ray_tpu.remote(num_cpus=1)(_model_dims).remote(model_id), timeout=120
    )


def agent_state() -> Dict[str, Any]:
    from ray_tpu.core import worker as worker_mod

    return worker_mod.global_worker().agent.call("get_state")


def wait_chips_free(n_chips: int, timeout_s: float = 120.0) -> float:
    """Block until no process holds a chip (the agent takes an id back
    only once its holder has been reaped). Returns the seconds waited."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        if len(agent_state()["tpu_chips_free"]) >= n_chips:
            return time.monotonic() - t0
        time.sleep(0.2)
    raise CheckFailed(
        f"chips still held {timeout_s:.0f}s after the serve phase ended: "
        f"{agent_state()['workers']}"
    )


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------


def _post(addr: str, path: str, body: Dict[str, Any]) -> Dict[str, Any]:
    """One front-door request; returns {status, text, usage, fingerprint,
    seconds}. A streamed body is reassembled from its SSE events."""
    host, port = addr.rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=REQUEST_TIMEOUT_S)
    t0 = time.monotonic()
    try:
        conn.request(
            "POST", path, body=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        raw = resp.read()
    finally:
        conn.close()
    out: Dict[str, Any] = {
        "status": resp.status, "seconds": time.monotonic() - t0,
        "text": None, "usage": None, "fingerprint": None, "error": None,
    }
    chat = path.endswith("/chat/completions")
    if resp.status != 200:
        out["error"] = raw[:300].decode(errors="replace")
        return out
    if not body.get("stream"):
        doc = json.loads(raw)
        choice = doc["choices"][0]
        out["text"] = choice["message"]["content"] if chat else choice["text"]
        out["usage"] = doc.get("usage")
        out["fingerprint"] = doc.get("system_fingerprint")
        return out
    pieces: List[str] = []
    done = False
    for block in raw.decode().split("\n\n"):
        if not block.strip():
            continue
        if not block.startswith("data: "):
            out["error"] = f"bad SSE framing: {block[:80]!r}"
            return out
        payload = block[len("data: "):]
        if payload == "[DONE]":
            done = True
            continue
        doc = json.loads(payload)
        if "error" in doc:
            out["error"] = json.dumps(doc["error"])[:300]
            return out
        choice = doc["choices"][0]
        piece = (
            choice.get("delta", {}).get("content") if chat
            else choice.get("text")
        )
        pieces.append(piece or "")
        out["usage"] = doc.get("usage") or out["usage"]
        out["fingerprint"] = doc.get("system_fingerprint") or out["fingerprint"]
    if not done:
        out["error"] = "stream ended without [DONE]"
    out["text"] = "".join(pieces)
    return out


def _prompt(seed: int, n_bytes: int) -> str:
    """Deterministic ASCII text of exactly n_bytes (= n tokens under the
    byte tokenizer)."""
    words = ("chip", "smoke", "serve", "train", "page", "token", "mesh", "lease")
    out, i = "", seed
    while len(out) < n_bytes:
        out += words[i % len(words)] + " "
        i = i * 7 + 3
    return out[:n_bytes]


def serve_phase(platform: str, n_chips: int, model_id: str = MODEL) -> Dict[str, Any]:
    """N one-chip replicas behind the OpenAI front door, >= 8 requests on
    a cold cache. Raises CheckFailed naming every failed check."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core.actor import ActorHandle
    from ray_tpu.serve import llm as serve_llm
    from ray_tpu.utils.config import config

    failures: List[str] = []
    dims = model_dims(model_id)
    chunk = int(config.serve_prefill_chunk_tokens)
    # longest prompt that leaves 64 tokens to generate after the chat
    # template's role markup
    room = dims["n_positions"] - 64 - 32
    short_len = max(8, min(dims["n_positions"] // 16, room))
    long_len = min(chunk + chunk // 4, room)
    say(f"serve: model {model_id}, {n_chips} replica(s), prompts of "
        f"{short_len} and {long_len} tokens (prefill chunk {chunk})")

    entries_before = cache_entries()
    t0 = time.monotonic()
    serve.start(http_port=0)
    try:
        serve_llm.deploy(
            {model_id: serve_llm.LLMConfig(model_id=model_id)},
            name=DEPLOYMENT, num_replicas=n_chips,
        )
        deadline = time.monotonic() + 60
        addrs: List[str] = []
        while not addrs and time.monotonic() < deadline:
            addrs = serve.proxy_addresses()
            time.sleep(0.2)
        if not addrs:
            raise CheckFailed("no HTTP proxy came up")
        addr = addrs[0]
        ready_s = time.monotonic() - t0
        say(f"serve: deployment ready in {ready_s:.1f}s, proxy at {addr}")

        short, long_ = _prompt(1, short_len), _prompt(2, long_len)
        chat_short = [{"role": "user", "content": _prompt(3, short_len)}]
        chat_long = [{"role": "user", "content": _prompt(4, long_len)}]

        def completion(prompt, max_tokens, stream, user):
            return ("/v1/completions", {
                "model": model_id, "prompt": prompt, "max_tokens": max_tokens,
                "temperature": 0, "stream": stream, "user": user,
            })

        def chat(messages, max_tokens, stream, user):
            return ("/v1/chat/completions", {
                "model": model_id, "messages": messages,
                "max_tokens": max_tokens, "temperature": 0, "stream": stream,
                "user": user,
            })

        # One session key per group of identical requests: the key pins
        # the group to one replica, and the groups spread over replicas.
        groups = {
            "completion/short/32": lambda stream: completion(short, 32, stream, "smoke-a"),
            "completion/long/64": lambda stream: completion(long_, 64, stream, "smoke-b"),
            "chat/short/32": lambda stream: chat(chat_short, 32, stream, "smoke-c"),
            "chat/long/64": lambda stream: chat(chat_long, 64, stream, "smoke-d"),
        }
        results: Dict[str, Dict[str, Any]] = {}
        bodies: Dict[str, Dict[str, Any]] = {}

        def send(name, request):
            bodies[name] = request[1]
            return _post(addr, *request)

        # 1. one request alone on the cold cache
        first = next(iter(groups))
        results[f"cold/{first}"] = send(f"cold/{first}", groups[first](False))
        cold_s = results[f"cold/{first}"]["seconds"]
        say(f"serve: cold/{first}: HTTP {results[f'cold/{first}']['status']} in "
            f"{cold_s:.1f}s (engine load + compiles)")
        # 2. every kind of request, unary and streamed, four at a time
        t_conc = time.monotonic()
        with concurrent.futures.ThreadPoolExecutor(max_workers=4) as pool:
            futs = {
                f"concurrent/{'sse' if stream else 'unary'}/{g}":
                    pool.submit(send, f"concurrent/{'sse' if stream else 'unary'}/{g}",
                                make(stream))
                for g, make in groups.items() for stream in (False, True)
            }
            for name, fut in futs.items():
                results[name] = fut.result()
        conc_s = time.monotonic() - t_conc
        conc_tokens = sum((results[n]["usage"] or {}).get("completion_tokens", 0)
                          for n in futs)
        # 3. one at a time: a request that runs alone meets the same cached
        # prefix and the same chunk sizes every time, so equal programs on
        # equal inputs must give equal tokens. (Across a prefix-cache hit
        # and a miss they need not on the chip: the tail is prefilled at
        # another width, a differently tiled bf16 program.)
        for g, make in groups.items():
            results[f"alone/unary/{g}"] = send(f"alone/unary/{g}", make(False))
            results[f"alone/sse/{g}"] = send(f"alone/sse/{g}", make(True))
        results[f"alone/again/{first}"] = send(f"alone/again/{first}", groups[first](False))

        for name, r in results.items():
            say(f"serve: {name}: HTTP {r['status']} {r['seconds']:.1f}s "
                f"usage={r['usage']} replica={r['fingerprint']}"
                + (f" error={r['error']}" if r["error"] else ""))
            require(failures, r["status"] == 200 and not r["error"],
                    f"{name}: HTTP {r['status']} {r['error']}")
            want = bodies[name]["max_tokens"]
            got = (r["usage"] or {}).get("completion_tokens")
            require(failures, got == want,
                    f"{name}: completion_tokens {got} != max_tokens {want}")
        prompt_tokens = (results[f"cold/{first}"]["usage"] or {}).get("prompt_tokens")
        require(failures, prompt_tokens == short_len,
                f"prompt_tokens {prompt_tokens} != {short_len} bytes sent")
        for g in groups:
            a, b = results[f"alone/unary/{g}"], results[f"alone/sse/{g}"]
            require(failures, a["text"] is not None and a["text"] == b["text"],
                    f"unary != streamed for {g} at temperature 0")
        require(failures, results[f"alone/unary/{first}"]["text"]
                == results[f"alone/again/{first}"]["text"],
                f"the same prompt gave different tokens twice ({first})")
        # information: did the first (cache-miss) answer equal the cached ones?
        miss_equals_hit = {
            g: results[f"concurrent/unary/{g}"]["text"]
            == results[f"alone/unary/{g}"]["text"] for g in groups
        }
        say(f"serve: concurrent answer == answer alone, per group: {miss_equals_hit}")

        # every replica must have answered: engines load on a replica's
        # first request, and a replica that never loaded one has nothing
        # to report. Fresh session keys until all have been seen.
        seen = {r["fingerprint"] for r in results.values() if r["fingerprint"]}
        extra = 0
        while len(seen) < n_chips and extra < 64:
            r = _post(addr, *completion(short, 1, False, f"spread-{extra}"))
            extra += 1
            require(failures, r["status"] == 200, f"spread request: {r['error']}")
            if r["fingerprint"]:
                seen.add(r["fingerprint"])
        require(failures, len(seen) == n_chips,
                f"{len(seen)} of {n_chips} replicas answered a request")

        controller = serve.start()
        table = ray_tpu.get(controller.get_routing_table.remote(), timeout=30)
        replicas = table["table"][DEPLOYMENT]["replicas"]
        require(failures, len(replicas) == n_chips,
                f"{len(replicas)} replicas in the routing table, want {n_chips}")
        reports = []
        for rep in replicas:
            stats = ray_tpu.get(
                ActorHandle(*rep["handle_info"]).handle_request.remote(
                    None, method="engine_stats"
                ),
                timeout=60,
            )
            devices = stats.get("devices") or []
            say(f"serve: replica {rep['replica_id']} ({stats.get('fingerprint')}): "
                f"engine load {stats.get('load_s')}s, {stats.get('batches')} "
                f"batches, params+KV on {devices}")
            require(failures, len(devices) == 1,
                    f"{rep['replica_id']}: params and KV pool on "
                    f"{len(devices)} devices, want exactly 1")
            for d in devices:
                require(failures, d["platform"] == platform,
                        f"{rep['replica_id']}: platform {d['platform']!r}, "
                        f"want {platform!r}")
                if platform == "tpu":
                    require(failures, "v5" in d["device_kind"],
                            f"{rep['replica_id']}: device_kind {d['device_kind']!r}")
            reports.append({"replica": rep["replica_id"], "devices": devices,
                            "load_s": stats.get("load_s")})
        if platform == "tpu":
            held = [d["leased_chips"] for r in reports for d in r["devices"]]
            require(failures, len(set(held)) == n_chips,
                    f"replicas hold chips {held}: want {n_chips} distinct ids")
    finally:
        serve.shutdown()

    sent = len(results) + extra
    failed = sum(1 for r in results.values() if r["status"] != 200 or r["error"])
    again_s = results[f"alone/again/{first}"]["seconds"]
    out = {
        "phase": "serve", "platform": platform, "replicas": reports,
        "requests_sent": sent, "requests_failed": failed,
        "requests_succeeded": sent - failed,
        "deploy_ready_s": round(ready_s, 1),
        "cold_first_request_s": round(cold_s, 1),
        "same_request_warm_s": round(again_s, 2),
        "load_and_compile_s_first_request": round(cold_s - again_s, 1),
        "concurrent_wall_s": round(conc_s, 1),
        "concurrent_tokens_per_s_info": round(conc_tokens / conc_s, 1),
        "concurrent_equals_alone": miss_equals_hit,
        "cache_dir": compile_cache_dir(),
        "cache_entries_before": entries_before,
        "cache_entries_after": cache_entries(),
    }
    say(f"serve: {json.dumps(out)}")
    if failures:
        raise CheckFailed("serve phase: " + "; ".join(failures))
    return out


# ---------------------------------------------------------------------------
# train phase
# ---------------------------------------------------------------------------


def _train_loop(cfg: Dict[str, Any]) -> None:
    """Runs in the train worker, the one process that holds the chips."""
    import dataclasses
    import re

    import jax
    import optax
    from jax.sharding import NamedSharding

    import ray_tpu.train as train
    from ray_tpu.models import gpt2
    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.parallel import MeshConfig, build_mesh, shard_pytree
    from ray_tpu.parallel.sharding import batch_spec, gpt_rules, tree_shardings

    devices = jax.devices()
    platform = devices[0].platform
    if platform != cfg["platform"] or len(devices) != cfg["n_chips"]:
        raise RuntimeError(
            f"train worker wants {cfg['n_chips']} {cfg['platform']} device(s), "
            f"JAX found {len(devices)} x {platform!r}"
        )
    interpret = fa._interpret()
    if interpret != (platform == "cpu"):
        raise RuntimeError(f"flash interpret={interpret} on platform {platform}")

    # the configuration the training cells run (PERF.md §4): flash attention,
    # no remat, unrolled layer scan, fused CE head in chunks of 256
    mcfg = dataclasses.replace(
        gpt2.CONFIGS[cfg["model_id"]], attn_impl="flash", remat=False,
        scan_unroll=12, loss_impl="fused", loss_chunk=256,
    )
    seq = mcfg.n_positions
    batch = cfg["batch_per_chip"] * len(devices)
    mesh = build_mesh(MeshConfig(dp=len(devices)))
    rules = gpt_rules()
    opt = optax.adamw(3e-4, weight_decay=0.01)

    t0 = time.monotonic()
    params = gpt2.init(jax.random.PRNGKey(0), mcfg)
    opt_state = opt.init(params)
    params = shard_pytree(params, mesh, rules)
    opt_state = shard_pytree(opt_state, mesh, rules)
    jax.block_until_ready((params, opt_state))
    load_s = time.monotonic() - t0
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))

    data_sharding = NamedSharding(mesh, batch_spec())
    batches = train.get_dataset_shard("train").iter_batches(
        batch_size=batch, prefetch_batches=2, drop_last=True
    )

    def next_tokens():
        return jax.device_put(next(batches)["tokens"], data_sharding)

    tokens = next_tokens()
    with jax.set_mesh(mesh):
        t0 = time.monotonic()
        step = jax.jit(
            gpt2.make_train_step(mcfg, opt),
            in_shardings=(
                tree_shardings(mesh, rules, params),
                tree_shardings(mesh, rules, opt_state),
                data_sharding,
            ),
            donate_argnums=(0, 1),
        ).lower(params, opt_state, tokens).compile()
        compile_s = time.monotonic() - t0
    hlo = step.as_text()
    # Mosaic kernels appear as tpu_custom_call; their bf16 [B, T, H*Dh]
    # operands show whether each chip got its own shard of the batch
    kernel_lines = [l for l in hlo.splitlines() if "tpu_custom_call" in l]
    kernel_rows = sorted({
        int(m.group(1)) for l in kernel_lines
        for m in re.finditer(r"bf16\[(\d+),%d,%d\]" % (seq, mcfg.n_head * mcfg.head_dim), l)
    })

    params, opt_state, loss = step(params, opt_state, tokens)  # warm-up
    losses = [float(loss)]
    t0 = time.perf_counter()
    step_losses = []
    for _ in range(cfg["timed_steps"]):
        params, opt_state, loss = step(params, opt_state, next_tokens())
        step_losses.append(loss)
    losses += [float(l) for l in step_losses]  # host transfer: steps are done
    dt = time.perf_counter() - t0

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    train.report({
        "platform": platform, "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "device_ids": [d.id for d in devices],
        "leased_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "flash_interpret": interpret,
        "mosaic_custom_calls": len(kernel_lines),
        "mosaic_operand_rows": kernel_rows,
        "rows_per_chip": cfg["batch_per_chip"],
        "hlo_all_gathers": len(re.findall(r"\ball-gather(-start)?\(", hlo)),
        "hlo_all_reduces": len(re.findall(r"\ball-reduce(-start)?\(", hlo)),
        "batch": batch, "seq": seq, "vocab_size": mcfg.vocab_size,
        "losses": losses, "load_s": round(load_s, 2),
        "compile_s": round(compile_s, 2),
        "step_s": round(dt / cfg["timed_steps"], 4),
        "tokens_per_s_info": round(batch * seq * cfg["timed_steps"] / dt, 1),
        "param_bytes": int(param_bytes),
        "peak_bytes_in_use": peaks,
        # the step's own temporaries, which the allocator's peak leaves out
        "program_temp_bytes": getattr(
            step.memory_analysis(), "temp_size_in_bytes", None
        ),
        "jax_cache_dir": jax.config.jax_compilation_cache_dir,
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
    })


def _make_tokens(batch: Dict[str, Any], width: int, vocab: int) -> Dict[str, Any]:
    import numpy as np

    ids = batch["id"]
    rng = np.random.default_rng(int(ids[0]) + 1)
    return {"tokens": rng.integers(0, vocab, (len(ids), width), dtype=np.int32)}


def train_phase(platform: str, n_chips: int, model_id: str = MODEL) -> Dict[str, Any]:
    """JaxTrainer, one worker on all N chips, 1 + 5 steps. Raises
    CheckFailed naming every failed check."""
    import functools

    from ray_tpu import data as rtd
    from ray_tpu import train

    failures: List[str] = []
    dims = model_dims(model_id)
    steps = 1 + TIMED_STEPS
    rows = BATCH_PER_CHIP * n_chips * steps
    ds = rtd.range(rows, parallelism=steps).map_batches(functools.partial(
        _make_tokens, width=dims["n_positions"] + 1, vocab=dims["vocab_size"],
    ))
    entries_before = cache_entries()
    say(f"train: model {model_id}, 1 worker x {n_chips} chip(s), global batch "
        f"{BATCH_PER_CHIP * n_chips} x {dims['n_positions']} tokens")
    t0 = time.monotonic()
    result = train.JaxTrainer(
        _train_loop,
        train_loop_config={
            "platform": platform, "n_chips": n_chips, "model_id": model_id,
            "batch_per_chip": BATCH_PER_CHIP, "timed_steps": TIMED_STEPS,
        },
        scaling_config=train.ScalingConfig(
            num_workers=1, use_tpu=(platform == "tpu"),
            tpu_chips_per_worker=n_chips,
        ),
        datasets={"train": ds},
    ).fit()
    wall_s = time.monotonic() - t0
    if result.error is not None:
        raise CheckFailed(f"train phase: result.error = {result.error}")
    m = result.metrics
    say(f"train: {json.dumps(m)}")
    want = math.log(dims["vocab_size"])
    require(failures, m["platform"] == platform,
            f"worker platform {m['platform']!r}, want {platform!r}")
    require(failures, m["device_count"] == n_chips,
            f"worker drove {m['device_count']} devices, want {n_chips}")
    require(failures, len(m["losses"]) == steps
            and all(math.isfinite(l) and abs(l - want) <= 0.8 for l in m["losses"]),
            f"losses {m['losses']} not finite within 0.8 of ln(V) = {want:.2f}")
    if platform == "tpu":
        require(failures, "v5" in m["device_kind"],
                f"device_kind {m['device_kind']!r}")
        require(failures, not m["flash_interpret"] and m["mosaic_custom_calls"] > 0,
                "flash kernels did not run compiled: interpret="
                f"{m['flash_interpret']}, {m['mosaic_custom_calls']} Mosaic "
                "custom calls in the step's HLO")
        require(failures, m["mosaic_operand_rows"] == [m["rows_per_chip"]],
                f"Mosaic operands have {m['mosaic_operand_rows']} rows, want "
                f"[{m['rows_per_chip']}]: the kernel is not running per shard")
        require(failures, all(p and p > m["param_bytes"]
                              for p in m["peak_bytes_in_use"]),
                f"peak_bytes_in_use {m['peak_bytes_in_use']}: a chip held less "
                f"than one copy of the weights ({m['param_bytes']} B)")
    out = {
        "phase": "train", "platform": platform, "wall_s": round(wall_s, 1),
        "load_s": m["load_s"], "compile_s": m["compile_s"],
        "step_s": m["step_s"], "tokens_per_s_info": m["tokens_per_s_info"],
        "loss_first": m["losses"][0], "loss_last": m["losses"][-1],
        "device": {"platform": m["platform"], "kind": m["device_kind"],
                   "count": m["device_count"]},
        "cache_dir": compile_cache_dir(),
        "cache_entries_before": entries_before,
        "cache_entries_after": cache_entries(),
    }
    say(f"train: {json.dumps(out)}")
    if failures:
        raise CheckFailed("train phase: " + "; ".join(failures))
    return out


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def _versions() -> Dict[str, Optional[str]]:
    out: Dict[str, Optional[str]] = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def main() -> int:
    import ray_tpu
    from ray_tpu import native
    from ray_tpu.accelerators import tpu as tpu_mod

    if not tpu_mod.tpu_allowed_by_env(os.environ):
        sys.exit(
            "chip_smoke: wants a TPU (device kind 'TPU v5 lite'), but "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} keeps this "
            "program off it; no phase runs on the CPU"
        )
    n_chips = tpu_mod.TPUAcceleratorManager.get_current_node_num_accelerators()
    if n_chips < 1:
        sys.exit(
            "chip_smoke: wants a TPU (device kind 'TPU v5 lite'), but this "
            "machine exposes no chip (no /dev/accel*, no /dev/vfio/<group>); "
            "no phase runs on the CPU"
        )
    t_start = time.monotonic()
    say(f"versions {json.dumps(_versions())}")
    say(f"chips found by the node agent's discovery: {n_chips}")
    say(f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} "
        f"compile cache {compile_cache_dir()} ({cache_entries()} entries)")
    say("native cores: " + ", ".join(
        f"{name}={'loaded' if native.load(name) is not None else 'fallback'}"
        for name in ("store_core", "channel_core")
    ))
    ray_tpu.init()
    try:
        serve_out = serve_phase("tpu", n_chips)
        waited = wait_chips_free(n_chips)
        say(f"serve replicas gone, all {n_chips} chip(s) free after {waited:.1f}s")
        train_out = train_phase("tpu", n_chips)
    finally:
        ray_tpu.shutdown()
    backend_touched = "jax" in sys.modules and bool(
        sys.modules["jax"]._src.xla_bridge.backends_are_initialized()
    )
    say(f"parent imported jax: {'jax' in sys.modules}; "
        f"parent initialised a JAX backend: {backend_touched}")
    if backend_touched:
        raise CheckFailed("the parent process initialised a JAX backend")
    serve_kind = serve_out["replicas"][0]["devices"][0]["device_kind"]
    if serve_kind != train_out["device"]["kind"]:
        raise CheckFailed(
            f"serve ran on {serve_kind!r}, train on {train_out['device']['kind']!r}"
        )
    say(f"total {time.monotonic() - t_start:.0f}s")
    print(json.dumps({"ok": True, "device": train_out["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
