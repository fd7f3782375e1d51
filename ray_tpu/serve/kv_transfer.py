"""Disaggregated prefill/decode: KV handoff over the zero-copy plane.

The serving split the reference implements with NIXL-backed tensor
transport (nixl_tensor_transport.py): prefill and decode run as
SEPARATE deployments so compute-bound prefill can scale independently
of latency-bound decode. Here the handoff rides the repo's own data
plane — the decode-side ingress mints an RpcChannel handle (its own
worker is the reader), calls the prefill deployment with it, and the
prefill replica ships the prompt's KV rows back through
``write_value`` (scatter-gather multiseg frames: the KV tensors travel
as raw out-of-band segments, never in-band pickles — the first
production consumer of the PR-3/8 zero-copy path outside benchmarks).

Flow per request (trace id rides every leg, so state.timeline() shows
prefill → transfer → decode as one request):

    ingress (decode replica)                 prefill replica
      mint rpc channel handle  ──payload──►  prefix-aware prefill
      resp.result()  ◄────────────ack──────  write_value(KV shipment)
      recv_kv(reader)                        [PREFILL span]
      [TRANSFER span]
      engine admit imports KV rows, decodes

Failure contract: the prefill call carries a deadline
(RT_SERVE_DISAGG_TIMEOUT_S); a SIGKILLed prefill replica surfaces as
ActorDied/Timeout on the ack or a channel-read timeout — the request
FAILS within the budget, decode never hangs on a half-open channel.
Kill switch: RT_SERVE_DISAGG=0 (ingress prefills locally as before).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional

from ray_tpu.observability import core_metrics, tracing


def channel_capacity(model_cfg) -> int:
    """Upper bound for one KV shipment: full-length K+V rows in f32
    plus slack for the frame header/meta."""
    row = (
        model_cfg.n_layer * model_cfg.n_positions
        * model_cfg.n_head * model_cfg.head_dim * 4
    )
    return 2 * row + (1 << 20)


class PrefillEngine:
    """Prefill-only engine: one working sequence, no decode loop.
    Shares the LLMServer's weights recipe (same PRNGKey(0) init /
    checkpoint), so at temperature=0 the first token and KV rows are
    exactly what the monolithic engine would have produced.

    The prefill tier runs on the SAME PagedKVPool + paged kernels as
    the decode engine — prefix KV and working KV live in one device
    pool and the shipment is a gather of the sequence's pages."""

    def __init__(self, cfg) -> None:
        import jax

        from ray_tpu import models
        from ray_tpu.serve import prefix_cache
        from ray_tpu.utils.config import config

        self.cfg = cfg
        self.model_cfg, self._dec = models.resolve(cfg.model_id)
        if not self._dec.KV_TRANSFER:
            raise RuntimeError(
                f"model {cfg.model_id!r} has no KV transfer: its cache is not "
                f"K and V pages of one shape, and a shipment is nothing else"
            )
        dec = self._dec
        self.params = dec.load_serving_params(self.model_cfg, cfg.checkpoint_path)
        self._rng = jax.random.PRNGKey(1)
        B = int(config.serve_prefix_block_tokens)
        max_pages = -(-self.model_cfg.n_positions // B)
        # resident-prefix capacity (serve_prefix_pool_blocks), plus one
        # full working reservation (+ the scratch page 0), so alloc can
        # always cover a prompt by evicting LRU residents
        self._pool = prefix_cache.PagedKVPool(
            cfg.model_id,
            num_pages=int(config.serve_prefix_pool_blocks) + max_pages + 1,
            page_tokens=B,
        )
        self._lock = threading.Lock()
        self._cache_k = self._cache_v = None  # init_paged_cache's, lazy

    def prefill(self, prompt_tokens: List[int],
                temperature: float) -> Dict[str, Any]:
        """Prefix-cache-aware prefill of the prompt: match resident
        prefix pages (refcount bump, zero copies), prefill only the tail
        into freshly allocated pages, seal the new full blocks, sample
        the first token, and gather the sequence's pages into the host
        shipment dict the decode engine's ``kv_import`` path expects."""
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.serve import prefix_cache
        from ray_tpu.utils.config import config

        dec = self._dec
        mcfg = self.model_cfg
        T_max = mcfg.n_positions
        prompt = list(prompt_tokens)[-(T_max - 1):] or [0]
        pool = self._pool
        B = pool.page_tokens
        max_pages = -(-T_max // B)

        def bucket(n: int, cap: int) -> int:
            p = 16
            while p < n:
                p *= 2
            return min(p, cap)

        with self._lock:
            if self._cache_k is None:
                self._cache_k, self._cache_v = dec.init_paged_cache(
                    mcfg, pool.num_pages, B
                )
                pool.reset()
            use_prefix = bool(config.serve_prefix_cache)
            digests = (
                prefix_cache.hash_blocks(prompt, B) if use_prefix else []
            )
            held_pages: List[int] = []
            new_pages: List[int] = []
            try:
                # keep >=1 prompt token uncached: the tail prefill
                # produces the first-token logits
                _, held_pages = pool.match_pages(
                    digests, max_tokens=len(prompt) - 1
                )
                cached = len(held_pages) * B
                n_pages = -(-len(prompt) // B)
                alloc = pool.alloc(n_pages - len(held_pages))
                if alloc is None:
                    raise RuntimeError(
                        f"prefill page pool exhausted: need "
                        f"{n_pages - len(held_pages)} pages"
                    )
                new_pages = alloc
                pages = held_pages + new_pages
                table = np.zeros((max_pages,), np.int32)
                table[: len(pages)] = pages
                tail = prompt[cached:]
                tok = np.zeros(
                    (1, bucket(len(tail), max_pages * B - cached)), np.int32
                )
                tok[0, : len(tail)] = tail
                logits, self._cache_k, self._cache_v = dec.prefill_paged(
                    mcfg, self.params, jnp.asarray(tok), jnp.int32(cached),
                    jnp.int32(len(tail)), self._cache_k, self._cache_v,
                    jnp.asarray(table),
                )
                first = self._sample_one(logits, temperature)
                # shipment = gather of this sequence's pages (device
                # gather + ONE host copy; no per-block host pool copies),
                # in the wire's [L, T, H, Dh] whatever shape the pool
                # is stored in
                n = len(prompt)
                row_k, row_v = (
                    np.asarray(blocks).reshape(
                        mcfg.n_layer, n_pages * B, mcfg.n_head, mcfg.head_dim
                    )
                    for blocks in dec.read_pages(
                        mcfg, self._cache_k, self._cache_v,
                        jnp.asarray(table[:n_pages]),
                    )
                )
                n_full = n // B
                for j in range(len(held_pages), min(n_full, len(digests))):
                    pool.seal(digests[j], int(pages[j]))
            except Exception:
                # prefill donates the caches: a post-dispatch error
                # leaves them deleted — rebuild (and reset the pool,
                # whose sealed pages pointed into them) lazily next call
                self._cache_k = self._cache_v = None
                raise
            finally:
                pool.release_pages(held_pages + new_pages)
        return {
            "k": np.ascontiguousarray(row_k[:, :n]),
            "v": np.ascontiguousarray(row_v[:, :n]),
            "first_token": first,
            "prompt_len": n,
            "cached_tokens": cached,
        }

    def _sample_one(self, logits, temperature: float) -> int:
        import jax
        import jax.numpy as jnp

        if temperature <= 0:
            return int(jnp.argmax(logits))
        self._rng, sub = jax.random.split(self._rng)
        return int(jax.random.categorical(sub, logits / temperature))

    def batch_stats(self, _payload=None) -> Dict[str, Any]:
        return {"prefix": self._pool.stats(), "pid": os.getpid()}

    def unload(self) -> None:
        """Multiplex eviction: the prefix pool dies with the engine."""
        self._pool.close()
        self._cache_k = self._cache_v = None


class PrefillServer:
    """The prefill deployment callable: receives
    ``{model, prompt_tokens, temperature, chan, trace_id}`` payloads
    from decode-side ingress replicas, runs prefill, and ships the KV
    rows back through the caller's channel handle."""

    def __init__(self, models, max_engines_per_replica: int = 2):
        from ray_tpu.accelerators.tpu import require_leased_platform
        from ray_tpu.serve import multiplex
        from ray_tpu.serve.openai.ingress import _normalize_models

        require_leased_platform()
        self._models = _normalize_models(models)
        self._engines = multiplex.make_multiplexer(
            lambda model: self._load_engine(model),
            max_models=max_engines_per_replica,
        )

    def _load_engine(self, model: str) -> PrefillEngine:
        cfg = self._models.get(model)
        if cfg is None:
            raise ValueError(f"model {model!r} does not exist")
        return PrefillEngine(cfg)

    def __call__(self, payload: Dict[str, Any]) -> Dict[str, Any]:
        if payload.get("op") == "info":
            # test/ops hook: which process serves this replica
            return {"pid": os.getpid(),
                    "models": sorted(self._models)}
        model = payload["model"]
        trace_id = payload.get("trace_id")
        t0u = tracing.now_us() if (tracing.ENABLED and trace_id) else 0
        engine = self._engines.get(model)
        shipment = engine.prefill(
            payload["prompt_tokens"], float(payload.get("temperature", 0.0))
        )
        nbytes = shipment["k"].nbytes + shipment["v"].nbytes
        send_kv(payload["chan"], shipment,
                timeout_s=float(payload.get("timeout_s", 30.0)))
        if core_metrics.ENABLED:
            core_metrics.serve_kv_transfer_bytes.inc(
                nbytes, tags={"deployment": model}
            )
        if tracing.ENABLED and trace_id:
            tracing.emit(tracing.request_span(
                trace_id, tracing.PREFILL, model, t0u,
                tracing.now_us() - t0u, parent=tracing.REPLICA,
                tokens=shipment["prompt_len"],
                cached=shipment["cached_tokens"] > 0,
                kv_bytes=nbytes,
            ))
        return {
            "ok": True,
            "prompt_len": shipment["prompt_len"],
            "cached_tokens": shipment["cached_tokens"],
            "kv_bytes": nbytes,
        }


def send_kv(handle: Dict[str, Any], shipment: Dict[str, Any],
            timeout_s: float = 30.0) -> None:
    """Writer leg: serialize the shipment scatter-gather (the KV
    ndarrays become out-of-band segments; the ≥32 KiB frame rides the
    multiseg wire format, pinned by tools/check_inband_payloads.py)."""
    from ray_tpu.core import channels

    chan = channels.open_channel(handle, "write")
    try:
        chan.write_value(shipment, timeout_s=timeout_s)
    finally:
        chan.close()


def recv_kv(reader, timeout_s: float = 30.0) -> Dict[str, Any]:
    """Reader leg: one shipment off the channel (zero-copy frame)."""
    return reader.read_value(timeout_s=timeout_s)


def prefill_remote(deployment: str, model: str, eng_req: Dict[str, Any],
                   model_cfg) -> Dict[str, Any]:
    """Decode-side orchestration: run ``eng_req``'s prefill on the
    ``deployment`` prefill tier and return the ``kv_import`` dict for
    the local engine's admission. Raises within the
    RT_SERVE_DISAGG_TIMEOUT_S budget when the prefill tier is dead."""
    from ray_tpu import serve
    from ray_tpu.core import channels
    from ray_tpu.core import worker as worker_mod
    from ray_tpu.utils.config import config

    deadline = time.monotonic() + config.serve_disagg_timeout_s
    w = worker_mod.global_worker()
    handle = channels.rpc_channel_handle(
        w.address, channel_capacity(model_cfg), slots=2
    )
    reader = channels.open_channel(handle, "read")
    trace_id = eng_req.get("trace_id")
    try:
        h = serve.get_deployment_handle(deployment)
        resp = h.remote({
            "model": model,
            "prompt_tokens": eng_req["prompt_tokens"],
            "temperature": eng_req.get("temperature", 0.0),
            "chan": handle,
            "trace_id": trace_id,
            "timeout_s": max(1.0, deadline - time.monotonic()),
        })
        ack = resp.result(
            timeout_s=max(1.0, deadline - time.monotonic())
        )
        if not isinstance(ack, dict) or not ack.get("ok"):
            raise RuntimeError(f"prefill deployment returned {ack!r}")
        t0u = tracing.now_us() if (tracing.ENABLED and trace_id) else 0
        shipment = recv_kv(
            reader, timeout_s=max(1.0, deadline - time.monotonic())
        )
        if tracing.ENABLED and trace_id:
            tracing.emit(tracing.request_span(
                trace_id, tracing.TRANSFER, model, t0u,
                tracing.now_us() - t0u, parent=tracing.REPLICA,
                kv_bytes=int(ack.get("kv_bytes", 0)),
            ))
        return {
            "k": shipment["k"],
            "v": shipment["v"],
            "first_token": shipment["first_token"],
            "prompt_len": shipment["prompt_len"],
        }
    finally:
        reader.close()
