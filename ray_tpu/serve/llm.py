"""Serve LLM — autoregressive model deployments on a KV-cache engine.

Parity: the reference serve.llm stack (python/ray/serve/llm — deployment
+ engine wrapper + OpenAI-ish request shape) whose engine tier is vLLM
(/root/reference/python/ray/llm/_internal/serve/engines/vllm/). Here the
engine is native JAX and serves any registered model: it finds a model's
config and its decode programs by ``model_id`` (``models.resolve``;
``models/gpt2_decode.py`` and ``models/mimo_v2.py`` implement the
interface): a prefill/decode split
over one paged, static-shape KV pool with CONTINUOUS BATCHING — new
requests are admitted into free decode rows between decode steps as
long as the pool has pages for them, so a long generation never blocks
short ones and every decode step runs all live rows in one jitted
call. Generating N tokens costs N single-token forwards over cached
K/V, not N full-prefix recomputes.

Token-level API (this image has no tokenizer vocab files): requests are
{"prompt_tokens": [int], "max_new_tokens": N, "temperature": T};
responses are {"tokens": [int]}. Weights are randomly initialized unless
a checkpoint path of arrays in the layout of the model's own ``init`` is
given — the serving machinery, not the text quality, is the parity surface.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

from ray_tpu import serve
from ray_tpu.observability import core_metrics, tracing


class LLMConfig:
    def __init__(
        self,
        model_id: str = "gpt2-tiny",
        num_replicas: int = 1,
        max_batch_size: int = 8,
        max_new_tokens_cap: int = 256,
        checkpoint_path: Optional[str] = None,
        route_prefix: Optional[str] = "/llm",
        max_concurrency: int = 16,
        engine: str = "kv",
        paged_kv: Optional[bool] = None,
        async_decode: Optional[bool] = None,
    ):
        self.model_id = model_id
        self.num_replicas = num_replicas
        self.max_batch_size = max_batch_size
        self.max_new_tokens_cap = max_new_tokens_cap
        self.checkpoint_path = checkpoint_path
        self.route_prefix = route_prefix
        self.max_concurrency = max_concurrency
        # The last three keywords select nothing: there is one engine,
        # the paged asynchronous one. They are still accepted because
        # the benchmark's configuration files pass them (ROADMAP D11(o)
        # drops them there, then here), and a value that asks for a
        # path that is gone is refused by name rather than ignored.
        if engine != "kv":
            raise ValueError(
                f"engine={engine!r}: the only engine is 'kv', the paged "
                f"KV engine (the 'recompute' engine was removed in PR 31)"
            )
        if paged_kv is not None and not paged_kv:
            raise ValueError(
                "paged_kv=False: the slot KV engine was removed (PR 31); "
                "the only engine keeps its KV in the page pool"
            )
        if async_decode is not None and not async_decode:
            raise ValueError(
                "async_decode=False: the synchronous decode loop was "
                "removed (PR 31); the engine always dispatches one chunk "
                "ahead of its harvest"
            )


class _Request:
    __slots__ = ("prompt", "max_new", "temperature", "event", "result",
                 "error", "token_q", "cancelled", "trace_id", "t_enqueue",
                 "t_refused", "t0_us")

    def __init__(self, prompt, max_new, temperature, stream=False):
        self.prompt = prompt
        self.max_new = max_new
        self.temperature = temperature
        self.event = threading.Event()
        self.result: Optional[List[int]] = None
        self.error: Optional[BaseException] = None
        # observability (set at enqueue only when the switches are on):
        # trace id propagated from the proxy, wall/monotonic enqueue
        # stamps for the engine span and the TTFT histogram
        self.trace_id: Optional[str] = None
        self.t_enqueue: Optional[float] = None
        # paged engine: first time admission refused this request for
        # want of KV pages (None if it never was)
        self.t_refused: Optional[float] = None
        self.t0_us = 0
        # set when the consumer abandoned the request (client disconnect
        # mid-stream): the engine frees its KV pages at the next round
        # instead of decoding to max_new for nobody
        self.cancelled = False
        # streaming consumers read tokens here as the engine produces
        # them; None marks the end of the stream
        self.token_q: Optional["queue.Queue"] = None
        if stream:
            import queue

            self.token_q = queue.Queue()


class _Chunk:
    """One dispatched-but-unharvested decode chunk (the async pipeline's
    in-flight lookahead). The engine dispatches chunk N+1 from chunk N's
    device-resident outputs BEFORE materializing chunk N's tokens; this
    record carries everything the later harvest needs: the device token
    array, the (row, seq, finish_pending) set captured at dispatch, rows
    cancelled while the chunk was in flight (their tokens are dropped on
    the host), and pages whose free is deferred until this chunk — the
    last one that can scatter into them — has completed."""

    __slots__ = ("toks_dev", "counted_dev", "n_steps", "rows", "by_row",
                 "dropped", "free_after")

    def __init__(self, toks_dev, n_steps: int, counted_dev=None):
        # on device: [S] when K == 1, else [MAX_DECODE_CHUNK, S] with
        # the first K rows written
        self.toks_dev = toks_dev
        # on device: what the chunk's steps counted beside their tokens
        # (the decode module's STEP_COUNTERS), None where it counts nothing
        self.counted_dev = counted_dev
        self.n_steps = n_steps
        self.rows: List[tuple] = []  # (row, seq, finish_pending)
        self.by_row: Dict[int, Any] = {}
        self.dropped: set = set()  # rows cancelled mid-flight
        self.free_after: List[int] = []  # pages released at harvest


class _FirstTokens(NamedTuple):
    """First tokens sampled on the device and not yet on the host. The
    decode call that carries them takes them from the vector on the
    device; the host fetches them behind it."""

    dev: Any  # what ``dec.sample`` left: one token a row of the prefill call
    rows: List[tuple]  # (position in ``dev``, decode row, sequence) of each prompt that ended


class _PagedSeq:
    """One live sequence in the paged engine: the request it serves,
    its page pins, and its prefill/decode cursors. Admission reserves
    EVERY page the sequence can ever touch (ceil(min(prompt+max_new,
    T_max)/page_tokens)), so the page-table row never changes while the
    sequence is in flight."""

    __slots__ = ("req", "prompt", "pages", "released", "digests", "n_hit",
                 "table", "cached_tokens", "prefill_pos", "length",
                 "produced", "last_token", "t_last", "ttft_us", "active",
                 "budget_left", "t_admit", "t_first")

    def __init__(self, req: _Request, prompt: List[int]):
        self.req = req
        self.prompt = prompt
        # page pins held in the engine's PagedKVPool: matched prefix
        # pages first, then freshly allocated ones. Released EXACTLY
        # once (the ``released`` latch) when the request leaves the
        # engine — finish, cancel, fail, or unload may race, and a
        # double release would corrupt another sequence's refcounts.
        self.pages: List[int] = []
        self.released = False
        self.digests: List[str] = []
        self.n_hit = 0  # leading pages that came from the prefix cache
        self.table = None  # np [MaxPages] page-table row
        self.cached_tokens = 0
        self.prefill_pos = 0  # prompt tokens already in the pool
        self.length = 0  # tokens in KV once active
        self.produced: List[int] = []
        self.last_token = 0
        self.t_last: Optional[float] = None
        self.ttft_us = 0
        # monotonic stamps of the request's phases (with the enqueue and
        # first-refusal stamps on the request): pages reserved, first
        # token sampled. A handful per request, never per token.
        self.t_admit: Optional[float] = None
        self.t_first: Optional[float] = None
        self.active = False  # prefill complete, decoding
        # decode steps this sequence may still be dispatched for;
        # decremented AT DISPATCH (not harvest) so the pipelined loop
        # knows deterministically, before any token materializes, which
        # rows finish in the chunk it just launched
        self.budget_left = 0


class _RoundAccount:
    """The engine thread's account of its own round, kept by the thread
    itself: the seconds of each phase (``core_metrics.ENGINE_PHASES``,
    which are the ``rt/engine/<phase>`` spans), and of those the seconds
    in which it KNEW the device dry.

    The engine hands the device its programs in order and one device runs
    them in order, so the result of the last program handed over (a
    prefill call's logits or the first tokens sampled from them, a decode
    call's tokens) is the ``tail``: once it is done nothing is left to run.
    The thread learns that where a phase that blocks on the tail returns,
    or where ``tail.is_ready()`` is true at a phase boundary, and from that
    instant until it hands the device its next program of any kind
    (``device_call``: a prefill or decode call, the scatter of changed rows
    before one, the sampling of first tokens) the device is known dry. Those
    seconds go to the phase they pass in. A lower bound of the seconds the
    device ran no program of the engine's: the stretch begins after the
    device's last operation and ends before the next is handed over, and
    what passes between that operation and the boundary is missed.

    Reads no clock and asks no array anything in a round that began with
    ``core_metrics.ENABLED`` false."""

    clock = staticmethod(time.monotonic)
    # (phase, the attribute of core_metrics that holds its series): looked
    # up by name each round, because a registry reset rebinds them
    _SERIES = tuple((p, f"serve_engine_{p}_s") for p in core_metrics.ENGINE_PHASES)
    _DRY_SERIES = tuple((p, f"serve_engine_dry_{p}_s") for p in core_metrics.ENGINE_HOST_PHASES)

    def __init__(self, tags: Dict[str, str]):
        self.tags = tags
        self.on = False  # this round is timed: the switch as the round began
        self.seconds = dict.fromkeys(core_metrics.ENGINE_PHASES, 0.0)
        self.dry = dict.fromkeys(core_metrics.ENGINE_HOST_PHASES, 0.0)
        self.current = "other"  # the phase the thread is in
        self.t_round = 0.0
        self.tail = None  # outstanding and not yet known done
        self.dry_since: Optional[float] = None

    def phase(self, name: str, waits_for=None, **args):
        """Context manager: the span ``rt/engine/<name>`` with ``args``
        and, in a timed round, its seconds charged to the phase.
        ``waits_for`` is the result a blocking phase waits for."""
        span = tracing.span("rt/engine/" + name, **args)
        return _Phase(self, name, span, waits_for) if self.on else span

    def boundary(self, passed_in: Optional[str]) -> float:
        """A phase begins or ends: the clock's reading, with the dry
        seconds since the last boundary charged to the phase they passed
        in (to none where it is not the host's own code), or a dry stretch
        begun here if the tail is found done."""
        t = self.clock()
        if self.dry_since is not None:
            if passed_in in self.dry:
                self.dry[passed_in] += t - self.dry_since
            self.dry_since = t
        elif self.tail is not None and self.tail.is_ready():
            self.tail = None
            self.dry_since = t
        return t

    def device_call(self) -> None:
        """The thread is about to hand the device a program: it has
        something to run again."""
        if self.dry_since is not None:
            self.dry[self.current] += self.clock() - self.dry_since
            self.dry_since = None

    def handed(self, tail) -> None:
        """``tail`` is the result of the last program handed over since
        ``device_call``."""
        if self.on:
            self.tail = tail

    def forget(self) -> None:
        self.tail = self.dry_since = None

    def begin(self) -> None:
        self.on = core_metrics.ENABLED
        if not self.on:
            self.forget()
            return
        for name in self.seconds:
            self.seconds[name] = 0.0
        for name in self.dry:
            self.dry[name] = 0.0
        self.current = "other"
        # what passed since the last round ended is no round's
        self.t_round = self.boundary(None)

    def end(self, worked: bool) -> None:
        """Observe the round: every phase and every phase's dry seconds,
        zeros included, so that each series counts the rounds that worked
        and sum / count is seconds a round; nothing for a round that only
        parked (no row was live: its dry seconds are nobody's). Host and
        blocked are sums of the same readings."""
        if not (self.on and worked):
            return
        sec, tags = self.seconds, self.tags
        total = self.boundary("other") - self.t_round
        if not core_metrics.ENABLED:
            return
        blocked = sum(sec[p] for p in core_metrics.ENGINE_BLOCKED_PHASES)
        sec["other"] = total - sum(sec.values())
        core_metrics.serve_engine_round_blocked_s.observe(blocked, tags=tags)
        core_metrics.serve_engine_round_host_s.observe(total - blocked, tags=tags)
        for name, series in self._SERIES:
            getattr(core_metrics, series).observe(sec[name], tags=tags)
        for name, series in self._DRY_SERIES:
            getattr(core_metrics, series).observe(self.dry[name], tags=tags)


class _Phase:
    """One phase of a timed round: its span, and its seconds on the
    account's clock, read inside the span."""

    __slots__ = ("acct", "name", "span", "waits_for", "t0")

    def __init__(self, acct: _RoundAccount, name: str, span, waits_for):
        self.acct, self.name, self.span, self.waits_for = acct, name, span, waits_for

    def __enter__(self):
        self.span.__enter__()
        self.t0 = self.acct.boundary("other")
        self.acct.current = self.name
        return self

    def __exit__(self, exc_type, exc, tb):
        acct = self.acct
        t = acct.boundary(self.name)
        acct.seconds[self.name] += t - self.t0
        acct.current = "other"
        if exc_type is None and self.waits_for is not None and self.waits_for is acct.tail:
            # the tail is on the host: nothing is left on the device
            acct.tail = None
            acct.dry_since = t
        return self.span.__exit__(exc_type, exc, tb)


def _upload(*host_arrays):
    """Device copies of the engine's long-lived host mirrors, each made
    from a private copy on the host. On the CPU backend ``jnp.asarray`` of
    a 64-byte-aligned NumPy array SHARES its memory, and ``jnp.array`` of
    one shares it too until a copy that is queued behind the programs in
    flight has run; the loop writes those mirrors again (``retire()``
    zeroes a row at dispatch) before then, and since the decode call goes
    over behind a prefill call still running, a row that ended in its first
    chunk decoded over an all-zero page table. A TPU copies during the
    call, so only the host's copy here differs by platform."""
    import jax.numpy as jnp

    return tuple(jnp.asarray(a.copy()) for a in host_arrays)


def _plan_prefill_rows(pending, widths, row_counts, several: bool, limit: int):
    """The rows of one prefill call of a decode module that takes rows.

    ``pending`` is [(decode row, prefill position, prompt tokens left)] of
    the admitted sequences that are not yet active, in row order;
    ``widths`` the widths of a call and ``row_counts`` the row counts
    compiled, both ascending; ``several`` says that consecutive chunks of
    one sequence may be rows of one call (every layer paged: a later row
    reads the earlier one's positions from the pages); a row holds at most
    ``limit`` tokens, whatever its width.

    Every width is tried: sequences in order, a row a chunk, until the
    rows of the largest call are taken. A sequence whose tail takes several
    rows takes them in one call or waits for the next: only the first may
    be cut (a tail over a whole call), so that no rest of a few tokens is
    left to read every weight for in a call of its own. The width kept is
    the one that prefills the most tokens; among those the one whose call,
    padded to a compiled row count, pays for the fewest positions; among
    those the widest (fewer rows, each reading its prefix once). Returns
    (rows of the call, width, [(decode row, start, tokens)])."""
    most = row_counts[-1]
    best = None
    for P in widths:
        a_row = min(P, limit)
        rows: List[tuple] = []
        for i, pos, left in pending:
            takes = -(-left // a_row) if several else 1
            if rows and len(rows) + takes > most:
                continue
            for _ in range(min(takes, most)):
                n = min(left, a_row)
                rows.append((i, pos, n))
                pos, left = pos + n, left - n
            if len(rows) == most:
                break
        R = next(r for r in row_counts if r >= len(rows))
        key = (sum(n for _, _, n in rows), -R * P, P)
        if best is None or key > best[0]:
            best = (key, R, P, rows)
    return best[1:]


class LLMServer:
    """The deployment callable: continuous-batched decode over one paged
    KV pool, one chunk dispatched ahead of its harvest.

    The model's config and its decode programs are found by
    ``config.model_id`` (``models.resolve``), and only that model's
    modules are imported. ``params`` are held on the device in the type
    the programs compute in (``model_cfg.dtype``), cast once at load by
    the decode module's ``load_serving_params`` (norms stay float32);
    ``batch_stats()["weights_bytes"]`` says what the tree holds."""

    def __init__(self, config: LLMConfig):
        import jax

        from ray_tpu import models
        from ray_tpu.accelerators.tpu import require_leased_platform
        from ray_tpu.serve import prefix_cache
        from ray_tpu.utils.config import config as rtcfg

        require_leased_platform()
        self._t_load = time.monotonic()
        self.cfg = config
        self.model_cfg, self._dec = models.resolve(config.model_id)
        self.params = self._dec.load_serving_params(
            self.model_cfg, config.checkpoint_path
        )
        self._rng = jax.random.PRNGKey(1)

        self._queue: collections.deque = collections.deque()
        self._lock = threading.Lock()
        self._work = threading.Event()
        self._batch_sizes = collections.deque(maxlen=1000)
        self._total_batches = 0
        self._max_batch_seen = 0
        self._occupied = 0  # decode rows held after the last engine round
        # per-process gauge label (the cluster merge keeps the latest
        # value PER SERIES; distinct tags keep every engine process)
        self._node_tag = f"pid{os.getpid()}"
        self._stop = threading.Event()
        # ONE page pool holds generation and prefix KV. Default size:
        # max_batch_size full-length sequences, S*ceil(T_max/B) pages of
        # B tokens (+1 reserved scratch page that inactive rows scatter
        # into).
        B = int(rtcfg.serve_prefix_block_tokens)
        max_pages = -(-self.model_cfg.n_positions // B)
        pool_pages = int(rtcfg.serve_kv_pool_pages) or (
            config.max_batch_size * max_pages
        )
        self._prefix_pool = prefix_cache.PagedKVPool(
            config.model_id, num_pages=pool_pages + 1, page_tokens=B,
        )
        # start-up hand-off: the engine thread allocates its KV pool,
        # reports where it landed and only then takes requests; a
        # failure on the way (out of device memory, no such platform)
        # raises HERE instead of leaving callers to time out
        self._started = threading.Event()
        self._start_error: Optional[BaseException] = None
        self._devices: List[Dict[str, Any]] = []
        self._load_s = 0.0
        self._kv_pool_shape: List[Any] = []
        self._kv_pool_bytes = 0
        self._kv_bytes_by_kind: Dict[str, int] = {}
        # held by the one stream that is sending an event (_stream_tokens)
        self._stream_turn = threading.Lock()
        threading.Thread(
            target=self._run_engine, name="llm-engine", daemon=True,
        ).start()
        self._started.wait()
        if self._start_error is not None:
            raise RuntimeError(
                f"engine {config.model_id!r} failed to start: "
                f"{type(self._start_error).__name__}: {self._start_error}"
            ) from self._start_error

    def _run_engine(self) -> None:
        try:
            self._engine_loop_paged()
        except BaseException as e:  # noqa: BLE001 — reported to __init__
            self._start_error = e
            self._started.set()
            raise

    def _engine_loaded(self, cache_k, cache_v) -> None:
        """Called by the engine loop once its allocations exist: waits
        for them (device allocation is asynchronous), and records the
        seconds the load took and the devices holding the parameters and
        the KV pool. The loop releases __init__ (``_started``) once the
        programs no request alone would meet are compiled too."""
        import jax

        jax.block_until_ready((cache_k, cache_v))
        self._load_s = round(time.monotonic() - self._t_load, 2)
        pool = jax.tree.leaves((cache_k, cache_v))
        held = {
            d for a in (*jax.tree.leaves(self.params), *pool)
            for d in a.devices()
        }
        self._devices = [
            {
                "platform": d.platform, "device_kind": d.device_kind,
                "id": d.id, "coords": getattr(d, "coords", None),
                # the chip id the node agent started this process with
                "leased_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            }
            for d in sorted(held, key=lambda d: d.id)
        ]
        # the caches as init_paged_cache stored them and as the device
        # holds them, tiling's padding included, by the kind of layer
        # (paged full layers, a window layer's ring a row, paged latent rows,
        # a recurrent layer's state a row)
        layout = self._dec.cache_layout(self.model_cfg, cache_k, cache_v)
        self._kv_pool_shape = layout["shape"]
        # every kind this model's layout names, and zero under the kinds
        # every engine reports
        self._kv_bytes_by_kind = {
            kind: int(layout["bytes"].get(kind, 0)) for kind in core_metrics.KV_KINDS
            if kind in layout["bytes"] or kind in core_metrics.KV_KINDS_EVERY_MODEL
        }
        self._kv_pool_bytes = sum(layout["bytes"].values())
        if core_metrics.ENABLED:
            ntags = {"deployment": self.cfg.model_id, "node": self._node_tag}
            for kind, held in self._kv_bytes_by_kind.items():
                getattr(core_metrics, f"serve_kv_{kind}_bytes").set(held, tags=ntags)

    # -- request path ---------------------------------------------------

    def _parse(self, request: Any) -> "_Request":
        trace_id = None
        if hasattr(request, "json"):  # HTTP proxy path
            if tracing.ENABLED:
                trace_id = request.headers.get(tracing.TRACE_HEADER)
            body = request.json()
            stream = (
                bool(body.get("stream"))
                or request.query.get("stream") in ("1", "true")
            )
            request = body
        else:
            stream = bool(request.get("stream"))
            if tracing.ENABLED:
                trace_id = request.get("trace_id")
        prompt = list(request.get("prompt_tokens") or [0])
        max_new = min(
            int(request.get("max_new_tokens", 16)),
            self.cfg.max_new_tokens_cap,
        )
        temperature = float(request.get("temperature", 0.0))
        req = _Request(prompt, max_new, temperature, stream=stream)
        req.trace_id = trace_id
        return req

    def __call__(self, request: Any):
        req = self._parse(request)
        if core_metrics.ENABLED or tracing.ENABLED:
            req.t_enqueue = time.monotonic()
            if tracing.ENABLED and req.trace_id:
                # one reading for both clocks: the engine span and its
                # phases then tile exactly
                req.t0_us = tracing.mono_us(req.t_enqueue)
        with self._lock:
            self._queue.append(req)
        self._work.set()
        if req.token_q is not None:
            return self._stream_tokens(req)
        if not req.event.wait(timeout=300):
            raise TimeoutError("generation timed out")
        if req.error is not None:
            raise req.error
        return {"tokens": req.result}

    def _stream_tokens(self, req: "_Request"):
        """Token-by-token generator (continuous batching pushes each
        decoded token as its step completes; parity: vLLM's streaming
        generate in the reference's serve.llm engine). An item's ``more``
        says that the next token had already been produced when this one
        was taken. Closing the
        generator before exhaustion — the client disconnected — cancels
        the request so the engine frees its KV pages."""
        import queue as queue_mod

        produced = 0
        done = False
        try:
            while True:
                try:
                    tok = req.token_q.get(timeout=300)
                except queue_mod.Empty:
                    raise TimeoutError("generation stalled") from None
                # One stream at a time from here until the proxy has its
                # event (the lock is held across the yields, while the
                # consumer builds the event and the worker sends it and
                # waits for the owner's reply, core/worker.py
                # _stream_returns): the other rows' threads wait here, not
                # runnable, and their tokens pile up in their queues
                # meanwhile, so the events a second are what the proxy
                # takes and the tokens an event what the engine makes in
                # between. With every row's
                # thread runnable at every step the engine thread waits
                # for the interpreter, steps slow down, every token gets
                # an event of its own and the replica settles at a third
                # of its rate; with coalesced sends that never block it
                # outran the proxy by 30 s (PERF.md, PR 46). A consumer
                # that stops pulling without closing holds the turn, and
                # one thread pulling two streams in turn would wait for
                # itself: the serving path drives each from its own
                # thread and only ever waits for the proxy's reply.
                with self._stream_turn:
                    # what the engine produced while this thread waited (a
                    # chunk of K steps, or its turn) comes with it;
                    # ``more`` tells the consumer that the next token is
                    # there already, so it sends them on as one event
                    ready = [tok]
                    while ready[-1] is not None:
                        try:
                            ready.append(req.token_q.get_nowait())
                        except queue_mod.Empty:
                            break
                    for j, tok in enumerate(ready):
                        if tok is None:
                            done = True
                            if req.error is not None:
                                raise req.error
                            return
                        produced += 1
                        yield {"token": int(tok), "index": produced - 1,
                               "more": j + 1 < len(ready) and ready[j + 1] is not None}
        finally:
            if not done:
                req.cancelled = True
                self._work.set()  # wake the engine to reap the row

    def batch_stats(self, _payload=None) -> Dict[str, Any]:
        with self._lock:
            sizes = list(self._batch_sizes)
            total = self._total_batches
            mx = self._max_batch_seen
        return {
            "batches": total,
            "max_batch": mx,
            "mean_batch": sum(sizes) / len(sizes) if sizes else 0,
            "occupied": self._occupied,
            # devices holding the parameters and the KV pool, and the
            # seconds it took to put them there (weights + pool, before
            # any request compiled anything)
            "devices": self._devices,
            "load_s": self._load_s,
            # bytes the parameters hold on the device: half of what
            # ``param_dtype`` would take where the engine computes in
            # bfloat16, so a regression to float32 shows without a trace
            "weights_bytes": self._dec.params_bytes(self.params),
            # what the decode programs attend over (the decode module's
            # word): the page pool itself under an ownership mask, or a
            # row's own pages and rings. Kept so a reader of two trees'
            # numbers can tell which body ran.
            "decode_attention": self._dec.DECODE_ATTENTION,
            # the shape the cache is stored in (one pool's where every
            # layer is paged, else [kind, *shape] a layer) and the bytes
            # the device holds for K and V, in all and by kind of layer:
            # a pool that went back to a padded or relaid form, or a
            # window layer that grew with its rows, shows here without a
            # trace
            "kv_pool_shape": self._kv_pool_shape,
            "kv_pool_bytes": self._kv_pool_bytes,
            "kv_bytes_by_kind": self._kv_bytes_by_kind,
            "prefix": self._prefix_pool.stats(),
        }

    def unload(self) -> None:
        """Multiplex eviction hook: stop the engine thread so an evicted
        engine doesn't keep a decode loop (and its KV cache) alive.
        Queued requests fail HERE and in-flight ones fail in the engine
        loop's exit path — callers get an immediate error, not a 300s
        timeout wait."""
        self._stop.set()
        self._work.set()
        err = RuntimeError(f"engine {self.cfg.model_id!r} was unloaded")
        while True:
            with self._lock:
                req = self._queue.popleft() if self._queue else None
            if req is None:
                break
            self._fail_request(req, err)
        # the page pool dies with the engine: close() drops every
        # resident page regardless of refcounts (in-flight sequences fail
        # in the loop's exit path; their pins would otherwise strand pages)
        self._prefix_pool.close()

    @staticmethod
    def _fail_request(req: "_Request", err: BaseException) -> None:
        req.error = err
        req.event.set()
        if req.token_q is not None:
            req.token_q.put(None)

    # -- the engine (one refcounted page pool, chunked prefill) ---------

    def _record_step_paged(self, fill: int, pst: Dict[str, int]) -> None:
        with self._lock:
            self._batch_sizes.append(fill)
            self._total_batches += 1
            self._max_batch_seen = max(self._max_batch_seen, fill)
            queued = len(self._queue)
        if core_metrics.ENABLED:
            dep = self.cfg.model_id
            core_metrics.serve_batch_fill.observe(
                fill, tags={"deployment": dep}
            )
            ntags = {"deployment": dep, "node": self._node_tag}
            core_metrics.serve_kv_pages_total.set(
                pst["pages_total"], tags=ntags
            )
            core_metrics.serve_kv_pages_occupied.set(
                pst["pages_occupied"], tags=ntags
            )
            core_metrics.serve_kv_pages_prefix_resident.set(
                pst["prefix_resident"], tags=ntags
            )
            core_metrics.serve_queued_requests.set(queued, tags=ntags)

    def _engine_loop_paged(self) -> None:
        """Continuous batching over ONE paged KV pool: generation and
        prefix pages coexist, a prefix hit is a refcount bump (zero
        copies), admission is page-granular (free pages, not free
        rows), and long prompts prefill in RT_SERVE_PREFILL_CHUNK_TOKENS
        chunks interleaved with decode so in-flight streams keep a
        bounded ITL."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.serve import prefix_cache
        from ray_tpu.utils.config import config

        dec = self._dec
        mcfg = self.model_cfg
        T_max = mcfg.n_positions
        pool = self._prefix_pool
        B = pool.page_tokens
        max_pages = -(-T_max // B)  # page-table width per sequence
        n_phys = pool.num_pages
        # decode rows: page-granular admission packs more short
        # sequences than max_batch_size full-length ones, bounded by the
        # pool itself (every live sequence pins >= 1 page)
        S = int(config.serve_paged_max_seqs) or min(
            pool.num_pages - 1, 4 * self.cfg.max_batch_size
        )
        S = max(1, min(S, pool.num_pages - 1))
        cache_k, cache_v = dec.init_paged_cache(mcfg, n_phys, B, S)
        seqs: List[Optional[_PagedSeq]] = [None] * S
        tables = np.zeros((S, max_pages), np.int32)  # 0 rows -> scratch
        last = np.zeros((S,), np.int32)
        lengths = np.zeros((S,), np.int32)
        temps = np.zeros((S,), np.float32)
        greedy = np.ones((S,), bool)
        # device-resident step state (incl. page tables): fully uploaded
        # only at (re)build; admissions/retirements push JUST their rows
        # via dec.update_rows_paged
        dev_state = None
        dirty: set = set()  # rows whose host state must reach the device
        rng_base = self._rng
        step_no = 0
        # the decode pipeline: at most ONE dispatched-but-unharvested
        # chunk; None when the pipeline is drained
        inflight: Optional[_Chunk] = None
        dep_tags = {"deployment": self.cfg.model_id}
        # the thread's account of its round; every rt/engine/* span but
        # the round's own is opened through it
        acct = _RoundAccount(dep_tags)
        phase = acct.phase

        def _bucket(n: int, cap: int) -> int:
            """Width of a prefill call for ``n`` tokens with ``cap``
            positions of context left: the next power of two, and where
            that would pass the context the power of two below it
            (run_prefill's loop takes the rest in a further call). Every
            width is a power of two: ``min(p, cap)`` made 192, 320 and
            384 out of tails deep in a context, programs no warm-up
            names, compiled under load where they were first met."""
            p = 16
            while p < n:
                p *= 2
            while p > cap:
                p //= 2
            return p

        def take_pages(s: _PagedSeq) -> List[int]:
            # a sequence's pages leave it EXACTLY once, however many of
            # finish/cancel/fail/unload race for it — a second release
            # would decref pages another sequence may already have
            # re-allocated
            if s.released:
                return []
            s.released = True
            pages, s.pages = s.pages, []
            return pages

        def retire(i: int, rec: Optional[_Chunk] = None) -> None:
            """Row i leaves the decode batch. Its pages free NOW unless
            an in-flight chunk still scatters into them (``rec``): then
            the free is DEFERRED until that chunk is harvested — one
            step — so a lookahead never reads (or writes) a freed page
            that admission re-allocated underneath it."""
            s = seqs[i]
            seqs[i] = None
            tables[i] = 0  # this row's junk scatters -> scratch page
            lengths[i] = 0
            last[i] = 0
            dirty.add(i)
            pages = take_pages(s)
            if rec is not None:
                rec.free_after.extend(pages)
            elif pages:
                pool.release_pages(pages)

        def activate(i: int, s: _PagedSeq, kv_len: int) -> None:
            """Prefill complete: the sequence joins the decode batch at
            position ``kv_len`` with all the host knows of it; its first
            token reaches ``last`` from wherever it was sampled. A sequence
            whose budget ends at that token (one token asked or none, or
            the context full) is live in no decode call and changes no row."""
            s.active = True
            s.length = kv_len
            s.budget_left = min(s.req.max_new - 1, T_max - 1 - kv_len)
            if s.budget_left <= 0:
                return
            tables[i] = s.table
            lengths[i] = kv_len
            temps[i] = max(s.req.temperature, 1e-6)
            greedy[i] = s.req.temperature <= 0
            dirty.add(i)

        def first_token_on_host(i: int, s: _PagedSeq, first: int) -> None:
            """The sequence's first token is on the host: kept, stamped
            and sent on."""
            s.produced = [first]
            s.last_token = first
            if seqs[i] is s and s.budget_left > 0:
                # the host mirror, for a full rebuild of the step state
                last[i] = first
            if core_metrics.ENABLED or tracing.ENABLED:
                now = s.t_first = time.monotonic()
                if tracing.ENABLED and s.req.t0_us:
                    s.ttft_us = tracing.mono_us(now) - s.req.t0_us
                if core_metrics.ENABLED:
                    s.t_last = now
                    if s.req.t_enqueue is not None:
                        core_metrics.serve_ttft_s.observe(
                            now - s.req.t_enqueue, tags=dep_tags
                        )
                    if s.t_admit is not None:
                        core_metrics.serve_engine_first_token_s.observe(
                            now - s.t_admit, tags=dep_tags
                        )
                    core_metrics.serve_tokens_generated.inc(tags=dep_tags)
            if s.req.token_q is not None and s.req.max_new >= 1:
                # zero-token completions must not leak the sampled-but-
                # unrequested first token into the stream
                s.req.token_q.put(first)

        def admit(i: int, req: _Request) -> bool:
            """Page-based admission: reserve EVERY page the sequence
            can ever touch up front (tables never change mid-flight,
            decode can never OOM mid-generation). Returns False — and
            takes nothing — when the pool can't cover the reservation:
            the caller requeues the request until pages free up."""
            prompt = req.prompt[-(T_max - 1):]
            use_prefix = bool(config.serve_prefix_cache)
            if use_prefix and not dec.PREFIX_CACHE:
                # refused by name, never a silent wrong hit: a hit would
                # have to restore state that pages do not hold
                use_prefix = False
                if core_metrics.ENABLED:
                    core_metrics.serve_prefix_refused.inc(tags=dep_tags)
            total_tokens = min(len(prompt) + req.max_new, T_max)
            n_pages = -(-total_tokens // B)
            if n_pages > pool.num_pages - 1:
                self._fail_request(req, RuntimeError(
                    f"request needs {n_pages} KV pages; pool has "
                    f"{pool.num_pages - 1}"
                ))
                return True  # consumed (failed); keep admitting
            digests = (
                prefix_cache.hash_blocks(prompt, B) if use_prefix else []
            )
            # keep >=1 prompt token uncached: the tail prefill
            # produces the first-token logits
            _, hit_pages = pool.match_pages(
                digests, max_tokens=len(prompt) - 1
            )
            new_pages = pool.alloc(n_pages - len(hit_pages))
            if new_pages is None:
                pool.release_pages(hit_pages)
                if req.t_enqueue is not None and req.t_refused is None:
                    req.t_refused = time.monotonic()
                return False
            s = _PagedSeq(req, prompt)
            s.pages = hit_pages + new_pages
            s.digests = digests
            s.n_hit = len(hit_pages)
            s.cached_tokens = len(hit_pages) * B
            s.prefill_pos = s.cached_tokens
            # never written after this line: run_prefill hands it to the
            # device as it is (see _upload for what a shared write does)
            row = np.zeros((max_pages,), np.int32)
            row[: len(s.pages)] = s.pages
            s.table = row
            seqs[i] = s
            if core_metrics.ENABLED or tracing.ENABLED:
                s.t_admit = time.monotonic()
            if core_metrics.ENABLED:
                # per admission, not per attempt: a request refused for
                # pages is matched again every round until it fits, and
                # only the attempt that admits it counts here
                core_metrics.serve_prompt_tokens.inc(
                    len(prompt), tags=dep_tags
                )
                if s.cached_tokens:
                    core_metrics.serve_prefix_tokens_reused.inc(
                        s.cached_tokens, tags=dep_tags
                    )
                if req.t_enqueue is not None:
                    core_metrics.serve_engine_queue_wait_s.observe(
                        s.t_admit - req.t_enqueue, tags=dep_tags
                    )
                    core_metrics.serve_engine_page_wait_s.observe(
                        s.t_admit - req.t_refused
                        if req.t_refused is not None else 0.0,
                        tags=dep_tags,
                    )
            return True

        # where a model's prompt positions stop short of its last layers,
        # its word for how many of a call's went through them all
        cross_positions = getattr(dec, "prefill_cross_positions", None)

        def count_prefill(rows: int, tokens: int, positions: int) -> None:
            if core_metrics.ENABLED:
                core_metrics.serve_prefill_calls.inc(tags=dep_tags)
                core_metrics.serve_prefill_rows.inc(rows, tags=dep_tags)
                core_metrics.serve_prefill_tokens.inc(tokens, tags=dep_tags)
                if cross_positions is not None:
                    core_metrics.serve_prefill_cross_positions.inc(
                        cross_positions(rows, tokens), tags=dep_tags)
                core_metrics.serve_prefill_width.observe(positions, tags=dep_tags)

        def seal_prompt(s: _PagedSeq) -> None:
            # full prompt blocks this sequence just wrote become
            # shareable prefix pages: seal registers the page
            # under its chain digest with NO copy
            n_full = len(s.prompt) // B
            for j in range(s.n_hit, min(n_full, len(s.digests))):
                pool.seal(s.digests[j], int(s.pages[j]))

        def prefill_a_sequence_a_call() -> None:
            """Chunked prefill, one sequence's chunk a call: at most
            RT_SERVE_PREFILL_CHUNK_TOKENS prompt tokens per engine round
            (0 = unchunked) for all sequences together, so a long
            prompt prefills across rounds interleaved with decode steps
            and in-flight streams keep a bounded ITL. A first token is
            fetched where its prompt ends: none is left on the device."""
            nonlocal cache_k, cache_v
            chunk = int(config.serve_prefill_chunk_tokens)
            budget = chunk if chunk > 0 else (1 << 30)
            for i in range(S):
                s = seqs[i]
                if s is None or s.active or s.req.cancelled:
                    continue
                if budget <= 0:
                    break
                logits = None
                while s.prefill_pos < len(s.prompt) and budget > 0:
                    start = s.prefill_pos
                    n = min(len(s.prompt) - start, budget)
                    width = _bucket(n, max_pages * B - start)
                    n = min(n, width)
                    # start and width say what a traced call was: a cold
                    # chunk, or a tail behind a prefix
                    with phase("prefill", start=start, width=width, rows=1):
                        tok = np.zeros((1, width), np.int32)
                        tok[0, :n] = s.prompt[start : start + n]
                        acct.device_call()
                        logits, cache_k, cache_v = dec.prefill_paged(
                            mcfg, self.params, jnp.asarray(tok),
                            jnp.int32(start), jnp.int32(n),
                            cache_k, cache_v, jnp.asarray(s.table),
                            np.int32(i),
                        )
                        acct.handed(logits)
                    count_prefill(1, n, width)
                    s.prefill_pos = start + n
                    budget -= n
                if s.prefill_pos >= len(s.prompt) and logits is not None:
                    seal_prompt(s)
                    with phase("first_token_sync", waits_for=logits):
                        first = self._sample_one(logits, s.req.temperature)
                    activate(i, s, len(s.prompt))
                    first_token_on_host(i, s, int(first))

        # what a prefill call of a module that takes rows looks like (the
        # decode module's word): the row counts compiled, and the widths
        # of a call that the context has room for
        row_counts = tuple(dec.PREFILL_ROWS)
        row_widths = tuple(
            w for w in dec.PREFILL_ROW_WIDTHS if w <= max_pages * B
        ) if row_counts[-1] > 1 else ()
        # first tokens of a call of rows, sampled together, and the
        # program that writes them into the step state's last tokens where
        # they lie: ``rows`` [R] names the decode row of each, and a
        # position past the last row is nobody's and dropped
        self._sample_rows = jax.jit(dec.sample)
        self._place_rows = jax.jit(
            lambda last_tokens, firsts, rows: last_tokens.at[rows].set(firsts, mode="drop")
        )

        def call_rows(R: int, P: int, rows: List[tuple]):
            """Dispatch one ``prefill_paged`` of ``R`` rows ``P`` wide for
            ``rows`` [(decode row, start, tokens)]; the rows behind them
            have no length and write to the scratch page. Logits [R, V]."""
            nonlocal cache_k, cache_v
            tok = np.zeros((R, P), np.int32)
            start = np.zeros((R,), np.int32)
            length = np.zeros((R,), np.int32)
            table = np.zeros((R, max_pages), np.int32)
            at = np.zeros((R,), np.int32)
            for r, (i, pos, n) in enumerate(rows):
                tok[r, :n] = seqs[i].prompt[pos : pos + n]
                start[r], length[r], at[r] = pos, n, i
                table[r] = seqs[i].table
            acct.device_call()
            logits, cache_k, cache_v = dec.prefill_paged(
                mcfg, self.params, jnp.asarray(tok), jnp.asarray(start),
                jnp.asarray(length), cache_k, cache_v, jnp.asarray(table),
                jnp.asarray(at),
            )
            acct.handed(logits)
            return logits

        def first_tokens(logits, temperatures: List[float]):
            """One token a row of ``logits`` [R, V], sampled together on
            the device; the rows behind ``temperatures`` are nobody's."""
            temps_r = np.zeros((logits.shape[0],), np.float32)
            temps_r[: len(temperatures)] = temperatures
            self._rng, sub = jax.random.split(self._rng)
            return self._sample_rows(
                logits, jnp.asarray(np.maximum(temps_r, 1e-6)),
                jnp.asarray(temps_r <= 0), sub,
            )

        def compile_calls_of_rows() -> None:
            """Every (R, P) a round can dispatch, the sampling behind it
            and the placing of its first tokens, run once on the scratch
            page with rows of no length:
            compiled (or loaded from the cache) before the engine reports
            ready, because requests that arrive one at a time never meet
            a call of several rows and a program first met under load
            compiles there."""
            nobody = jnp.zeros((S,), jnp.int32)
            for R in row_counts:
                for P in row_widths:
                    firsts = first_tokens(call_rows(R, P, []), [])
                jax.block_until_ready(
                    self._place_rows(nobody, firsts, jnp.full((R,), S, jnp.int32))
                )

        def prefill_rows() -> Optional[_FirstTokens]:
            """Chunked prefill of a decode module that takes rows: ONE
            call a round, for the next chunk (at most
            RT_SERVE_PREFILL_CHUNK_TOKENS tokens, a row) of every admitted
            sequence that is not yet active, up to the rows a call takes;
            where every layer is paged a sequence's tail takes several
            rows of the call. The expert layers and every other weight are
            read once for all of them. First tokens are sampled together
            and STAY on the device: returned where a prompt ended in the
            call, for the round to hand its decode call over before it
            waits for them (``land_first_tokens``)."""
            pending = [
                (i, s.prefill_pos, len(s.prompt) - s.prefill_pos)
                for i, s in enumerate(seqs)
                if s is not None and not s.active and not s.req.cancelled
                and s.prefill_pos < len(s.prompt)
            ]
            if not pending:
                return None
            chunk = int(config.serve_prefill_chunk_tokens)
            R, P, rows = _plan_prefill_rows(
                pending, row_widths, row_counts, dec.PREFIX_CACHE,
                chunk if chunk > 0 else row_widths[-1],
            )
            # start (the first row's) and width say what a traced call
            # was: a cold chunk, or tails behind their prefixes
            with phase("prefill", start=rows[0][1], width=P, rows=len(rows)):
                logits = call_rows(R, P, rows)
            count_prefill(len(rows), sum(n for _, _, n in rows), R * P)
            for i, pos, n in rows:
                seqs[i].prefill_pos = pos + n
            # the rows that hold their prompt's end
            done = [
                (r, i) for r, (i, pos, n) in enumerate(rows)
                if pos + n >= len(seqs[i].prompt)
            ]
            if not done:
                return None
            for _, i in done:
                seal_prompt(seqs[i])
            ends = dict(done)
            acct.device_call()
            firsts_dev = first_tokens(logits, [
                seqs[ends[r]].req.temperature if r in ends else 0.0
                for r in range(len(rows))
            ])
            acct.handed(firsts_dev)
            for _, i in done:
                activate(i, seqs[i], len(seqs[i].prompt))
            return _FirstTokens(firsts_dev, [(r, i, seqs[i]) for r, i in done])

        def land_first_tokens(ahead: _FirstTokens, ended: List[int]) -> None:
            """The one wait for a call's first tokens, behind the decode
            call that carries them where the round had one to hand over:
            each is delivered as soon as it is ON THE HOST, and a sequence
            that ends at its first token (``ended``, rows) is answered."""
            with phase("first_token_sync", waits_for=ahead.dev):
                toks = np.asarray(ahead.dev)
            for r, i, s in ahead.rows:
                first_token_on_host(i, s, int(toks[r]))
            for i in ended:
                finish(i)

        # separate paths by what the decode module declares, not one path
        # with parameters: a module of one row makes the calls it always
        # made, in the same order under the same tokens a round
        run_prefill = prefill_rows if row_widths else prefill_a_sequence_a_call

        def complete(s: _PagedSeq) -> None:
            s.req.result = s.produced[: s.req.max_new]
            if tracing.ENABLED and s.req.trace_id and s.req.t0_us:
                tid, dep = s.req.trace_id, self.cfg.model_id
                t0, end = s.req.t0_us, tracing.now_us()
                tracing.emit(tracing.request_span(
                    tid, tracing.ENGINE, dep, t0, end - t0,
                    parent=tracing.REPLICA, tokens=len(s.req.result),
                    cached=s.cached_tokens > 0, ttft_us=s.ttft_us,
                ))
                if s.t_admit is not None and s.t_first is not None:
                    # the request's phases, tiling the span above
                    adm = tracing.mono_us(s.t_admit)
                    first = tracing.mono_us(s.t_first)
                    refused = s.req.t_refused
                    tracing.emit(tracing.request_span(
                        tid, tracing.ENGINE_QUEUE, dep, t0, adm - t0,
                        parent=tracing.ENGINE,
                        page_wait_us=adm - tracing.mono_us(refused)
                        if refused is not None else 0,
                    ))
                    tracing.emit(tracing.request_span(
                        tid, tracing.ENGINE_PREFILL, dep, adm, first - adm,
                        parent=tracing.ENGINE,
                        cached_tokens=s.cached_tokens,
                        prompt_tokens=len(s.prompt),
                    ))
                    tracing.emit(tracing.request_span(
                        tid, tracing.ENGINE_DECODE, dep, first, end - first,
                        parent=tracing.ENGINE, tokens=len(s.req.result),
                    ))
            s.req.event.set()
            if s.req.token_q is not None:
                s.req.token_q.put(None)  # end of stream

        def finish(i: int) -> None:
            s = seqs[i]
            retire(i)
            complete(s)

        def fail_inflight(e: BaseException) -> None:
            nonlocal inflight
            for i in range(S):
                if seqs[i] is not None:
                    s = seqs[i]
                    retire(i)
                    self._fail_request(s.req, e)
            if inflight is not None:
                rec, inflight = inflight, None
                fail_chunk(rec, e)

        def fail_chunk(rec: _Chunk, e: BaseException) -> None:
            # a dispatched chunk dies unharvested: release its deferred
            # pages (the pool resets with the cache rebuild anyway — this
            # keeps occupancy honest even if the rebuild itself keeps
            # failing) and fail the requests whose finish was scheduled
            # at its dispatch
            if rec.free_after:
                pool.release_pages(rec.free_after)
                rec.free_after = []
            for _i, s, fin in rec.rows:
                if fin:
                    self._fail_request(s.req, e)

        def harvest(rec: _Chunk) -> None:
            """Materialize a dispatched chunk's tokens and run all its
            host bookkeeping: fan-out, SSE queue puts, metric stamps,
            completions, deferred page frees. This executes while the
            NEXT chunk (already dispatched) keeps the device busy —
            np.asarray is the only sync point."""
            with phase("harvest_sync", waits_for=rec.toks_dev):
                if rec.counted_dev is None:
                    toks = np.asarray(rec.toks_dev)
                else:
                    # the chunk's counts came with its tokens: one sync
                    toks, counted = jax.device_get((rec.toks_dev, rec.counted_dev))
            if rec.counted_dev is not None and core_metrics.ENABLED:
                for name, n in zip(dec.STEP_COUNTERS, counted):
                    getattr(core_metrics, f"serve_{name}").inc(int(n), tags=dep_tags)
            with phase("harvest"):
                deliver(rec, toks)

        def deliver(rec: _Chunk, toks) -> None:
            if toks.ndim == 1:
                toks = toks[None]  # [1, S]
            n_new = rec.n_steps
            live = [r for r in rec.rows if r[0] not in rec.dropped]
            if core_metrics.ENABLED:
                now = time.monotonic()
                # counted here, not at dispatch: the chunk's steps have
                # now run on the device
                core_metrics.serve_decode_steps.inc(n_new, tags=dep_tags)
                core_metrics.serve_decode_row_steps.inc(
                    n_new * len(live), tags=dep_tags
                )
                core_metrics.serve_tokens_generated.inc(
                    n_new * len(live), tags=dep_tags
                )
                for _i, s, _fin in live:
                    if s.t_last is not None:
                        core_metrics.serve_inter_token_s.observe(
                            (now - s.t_last) / n_new, tags=dep_tags
                        )
                    s.t_last = now
            for k in range(n_new):
                for i, s, _fin in live:
                    s.length += 1
                    s.last_token = int(toks[k, i])
                    s.produced.append(s.last_token)
                    if (
                        s.req.token_q is not None
                        and not s.req.cancelled
                        and len(s.produced) > 1  # the first was sent where it landed
                        and len(s.produced) <= s.req.max_new
                    ):
                        s.req.token_q.put(s.last_token)
            for i, s, fin in live:
                if fin:
                    complete(s)
                elif seqs[i] is s:
                    # keep the host mirror accurate for full rebuilds
                    last[i] = s.last_token
                    lengths[i] = s.length
            if rec.free_after:
                # deferred frees: this chunk was the last dispatch that
                # could scatter into these pages — they are now safe to
                # re-allocate
                pool.release_pages(rec.free_after)
                rec.free_after = []

        def dispatch(active: List[int], K: int,
                     ahead: Optional[_FirstTokens] = None) -> _Chunk:
            """Hand the device the next decode call of ``K`` steps for the
            rows ``active``, behind the scatter of the rows that changed;
            the first tokens of ``ahead`` go from the sampled vector into
            the call's last tokens on the device, no host in between."""
            nonlocal cache_k, cache_v, dev_state, step_no
            if dev_state is None:
                dev_state = _upload(last, lengths, temps, greedy, tables)
                dirty.clear()
            elif dirty:
                # incremental dev_state: scatter ONLY the changed rows
                # (admits/retires) into the device-resident step state
                # instead of re-uploading all five arrays (a fancy index
                # is a fresh array: nothing below shares a mirror)
                idx = np.asarray(sorted(dirty), np.int32)
                changed = [jnp.asarray(a) for a in (
                    idx, last[idx], lengths[idx], temps[idx], greedy[idx], tables[idx],
                )]
                # uploaded: the scatter is the device's next program
                acct.device_call()
                dev_state = dec.update_rows_paged(*dev_state, *changed)
                dirty.clear()
            d_last, d_len, d_temps, d_greedy, d_tables = dev_state
            # a sequence that ended at its first token is in no call: its
            # position stays past the last row, like a padded one's
            carried = [(r, i) for r, i, s in ahead.rows if s.budget_left > 0] if ahead else []
            if carried:
                at = np.full(ahead.dev.shape, S, np.int32)
                for r, i in carried:
                    at[r] = i
                acct.device_call()
                d_last = self._place_rows(d_last, ahead.dev, jnp.asarray(at))
            self._record_step_paged(len(active), pool.stats())
            acct.device_call()
            # a module with STEP_COUNTERS returns their counts last
            if K > 1:
                toks_dev, d_last2, d_len, cache_k, cache_v, *counted = (
                    dec.decode_multi_paged(
                        mcfg, self.params, d_last, d_len, cache_k,
                        cache_v, d_tables, d_temps, d_greedy, rng_base,
                        K, step_no,
                    )
                )
                step_no += K
                dev_state = (d_last2, d_len, d_temps, d_greedy, d_tables)
            else:
                step_no += 1
                toks_dev, d_len, cache_k, cache_v, *counted = (
                    dec.decode_paged_and_sample(
                        mcfg, self.params, d_last, d_len, cache_k,
                        cache_v, d_tables, d_temps, d_greedy, rng_base,
                        step_no,
                    )
                )
                dev_state = (toks_dev, d_len, d_temps, d_greedy, d_tables)
            acct.handed(toks_dev)
            if carried and core_metrics.ENABLED:
                core_metrics.serve_first_tokens_ahead.inc(len(carried), tags=dep_tags)
            rec = _Chunk(toks_dev, K, counted[0] if counted else None)
            for i in active:
                s = seqs[i]
                s.budget_left -= K
                fin = s.budget_left <= 0
                rec.rows.append((i, s, fin))
                rec.by_row[i] = s
                if fin:
                    # deterministic finish (budgets, not token values,
                    # end generations here): the row leaves the batch
                    # AT DISPATCH so the next chunk never includes it;
                    # its pages free when THIS chunk — the last one
                    # scattering into them — is harvested
                    retire(i, rec)
            return rec

        def admit_waiting() -> bool:
            """Reap abandoned requests (their pages go back to the pool
            instead of decoding to max_new for nobody), then admit
            queued ones into free rows until the queue or the pool runs
            out. True if any was admitted."""
            for i in range(S):
                s = seqs[i]
                if s is not None and s.req.cancelled:
                    rec = (
                        inflight
                        if inflight is not None
                        and inflight.by_row.get(i) is s
                        else None
                    )
                    if rec is not None:
                        # mid-lookahead cancel: the in-flight chunk's
                        # tokens for this row drop at harvest, and its
                        # pages free only once that chunk completes
                        rec.dropped.add(i)
                    retire(i, rec)
                    s.req.event.set()
            admitted = False
            for i in range(S):
                if seqs[i] is not None:
                    continue
                while True:
                    with self._lock:
                        req = self._queue.popleft() if self._queue else None
                    if req is None or not req.cancelled:
                        break
                    req.event.set()  # cancelled while queued: never admit
                if req is None:
                    break
                if not admit(i, req):
                    # page pressure: requeue at the FRONT (FIFO order
                    # holds) and stop admitting until pages free up
                    with self._lock:
                        self._queue.appendleft(req)
                    break
                admitted = True
            return admitted

        def run_round() -> bool:
            """The round itself; False if all it did was park in the
            idle wait."""
            nonlocal cache_k, cache_v, dev_state, inflight
            if cache_k is None:
                # rebuild after a poisoned (donated) round. The pool's
                # sealed pages pointed into the deleted cache, so ALL
                # pool metadata resets with it
                cache_k, cache_v = dec.init_paged_cache(mcfg, n_phys, B, S)
                pool.reset()
                dev_state = None
                dirty.clear()
            # consume the wake flag BEFORE the queue/cancel scans: a
            # set() landing after the scans stays pending for the idle
            # wait below, so an idle engine can never sleep through a
            # request that arrived between scan and wait (the old
            # wait-then-clear order could eat exactly that wakeup — up
            # to 500 ms of TTFT on an idle engine)
            self._work.clear()
            with phase("admit"):
                admitted = admit_waiting()
            ahead = run_prefill()
            prefilling = any(
                s is not None and not s.active for s in seqs
            )
            live = [
                i for i in range(S)
                if seqs[i] is not None and seqs[i].active
            ]
            # single-token answers (and 0-token asks, and a context
            # filled by its prompt) end at their first token and enter no
            # decode call: finished as soon as that token is on the host,
            # which for a module of one row it is
            ended = [i for i in live if seqs[i].budget_left <= 0]
            active = [i for i in live if seqs[i].budget_left > 0]
            if ahead is None:
                for i in ended:
                    finish(i)
            # rows held, live or still prefilling: a sequence whose client
            # has gone is found out at its first token, so a queue of such
            # prompts keeps rows held with none live for as long as they
            # prefill, and a caller that waits for an idle engine must not
            # take that for idle
            self._occupied = sum(s is not None for s in seqs)
            if not active:
                if ahead is not None:
                    # no decode call to hand over: the wait is for the tail
                    land_first_tokens(ahead, ended)
                    self._occupied = sum(s is not None for s in seqs)
                if inflight is not None:
                    # drain the lookahead before idling: its tokens are
                    # real and its pending finishes must complete
                    rec, inflight = inflight, None
                    harvest(rec)
                elif not admitted and not prefilling:
                    with tracing.span("rt/engine/idle"):
                        self._work.wait(timeout=0.5)
                    return False
                return True
            with self._lock:
                waiting = bool(self._queue)
            # Chunk size: single-step while requests wait for admission
            # OR any sequence is mid-prefill (the next prefill chunk
            # must interleave after ONE decode step, or ITL for live
            # streams would stretch by the whole chunk).
            K = 1
            if not waiting and not prefilling:
                K = max(1, min(dec.MAX_DECODE_CHUNK, min(
                    seqs[i].budget_left for i in active
                )))
            with phase("dispatch", k=K, rows=len(active)):
                rec = dispatch(active, K, ahead)
            # one-step lookahead: chunk N+1 is on the device; fetch the
            # first tokens it carries and run chunk N's host bookkeeping
            # underneath it
            prev, inflight = inflight, rec
            if ahead is not None:
                try:
                    land_first_tokens(ahead, ended)
                except Exception as e:
                    if prev is not None:
                        # neither in flight nor harvested: nobody else's
                        fail_chunk(prev, e)
                    raise
            if prev is not None:
                harvest(prev)
            return True

        def one_round() -> None:
            """One round under its span, and the engine thread's account
            of it (``_RoundAccount``): seconds by phase, which add up to
            seconds in its own code and seconds blocked on the device. A
            round that only parked in the idle wait is in neither, so over
            a window host + blocked + idle is the window."""
            acct.begin()
            with tracing.span(
                "rt/engine/round",
                # the chunk in flight as the round starts, whose harvest
                # this round blocks on; ts_us is the ring's clock at the
                # span's start, which fixes the offset between the two
                k=inflight.n_steps if inflight is not None else 0,
                rows=len(inflight.rows) if inflight is not None else 0,
                ts_us=tracing.now_us() if tracing.ENABLED else 0,
            ):
                worked = run_round()
            acct.end(worked)

        self._engine_loaded(cache_k, cache_v)
        if row_widths:
            compile_calls_of_rows()
        self._started.set()
        while not self._stop.is_set():
            try:
                one_round()
            except Exception as e:  # noqa: BLE001 — engine must survive
                import logging

                logging.getLogger(__name__).exception(
                    "paged kv engine round failed; failing in-flight"
                    " requests"
                )
                fail_inflight(e)
                dev_state = None
                dirty.clear()
                acct.forget()
                # prefill/decode/write donate the caches: an exception
                # after dispatch leaves them deleted — mark for rebuild
                # (done inside the next round's try, with a pool.reset
                # alongside, so a failing rebuild can't kill the thread)
                cache_k = cache_v = None
                time.sleep(0.05)  # don't hot-spin on a persistent fault
        fail_inflight(
            RuntimeError(f"engine {self.cfg.model_id!r} was unloaded")
        )
        self._occupied = 0

    def _sample_one(self, logits, temperature: float) -> int:
        import jax
        import jax.numpy as jnp

        if temperature <= 0:
            return int(jnp.argmax(logits))
        self._rng, sub = jax.random.split(self._rng)
        return int(jax.random.categorical(sub, logits / temperature))


def _replica_resources(
    ray_actor_options: Optional[Dict[str, float]] = None,
) -> Dict[str, float]:
    """What one engine-hosting replica is leased. The caller's
    ``ray_actor_options`` if given; otherwise one TPU chip — unless
    JAX_PLATFORMS explicitly keeps this program off the TPU (tier-1 and
    CPU-only users say ``cpu``), the only way to get CPU replicas. A
    cluster with no chip fails here, by name, instead of leaving the
    lease pending until the ready timeout."""
    import ray_tpu
    from ray_tpu.accelerators.tpu import tpu_allowed_by_env

    if ray_actor_options is not None:
        return ray_actor_options
    if not tpu_allowed_by_env(os.environ):
        return {"CPU": 1.0}
    if not ray_tpu.cluster_resources().get("TPU"):
        raise RuntimeError(
            "an LLM replica takes one TPU chip and this cluster has none; "
            "set JAX_PLATFORMS=cpu to serve from the CPU"
        )
    return {"CPU": 1.0, "TPU": 1.0}


def build_llm_deployment(config: Optional[LLMConfig] = None) -> Any:
    """Deployment for an LLM server (parity: serve.llm build_llm_deployment)."""
    config = config or LLMConfig()
    dep = serve.deployment(
        LLMServer,
        name=f"llm-{config.model_id}",
        num_replicas=config.num_replicas,
        route_prefix=config.route_prefix,
        max_concurrency=config.max_concurrency,
        ray_actor_options=_replica_resources(),
    )
    return dep.bind(config)


def deploy(
    models: Any = "gpt2-tiny",
    *,
    name: str = "openai-llm",
    num_replicas: int = 1,
    route_prefix: str = "/v1",
    tokenizer: Optional[str] = None,
    max_engines_per_replica: int = 2,
    max_concurrency: int = 16,
    autoscaling_config: Optional[Dict[str, Any]] = None,
    ray_actor_options: Optional[Dict[str, float]] = None,
    max_queued_requests: Optional[int] = None,
    wait_ready: bool = True,
    ready_timeout_s: float = 300.0,
):
    """Run the OpenAI-compatible front door (parity: the reference's
    ``serve.llm build_openai_app`` + ``serve.run``): a multi-replica
    ingress deployment under ``route_prefix`` serving
    ``/v1/completions``, ``/v1/chat/completions`` (both with SSE
    streaming) and ``/v1/models`` over every node's HTTP proxy.

    ``models`` maps OpenAI model names to engine configs — a model id
    string, an :class:`LLMConfig`, or ``{name: LLMConfig | model_id |
    kwargs-dict}``. Each replica loads engines lazily per model
    (LRU-bounded at ``max_engines_per_replica``) and the router prefers
    replicas already holding the requested model; the OpenAI ``user``
    field pins a session to one replica's warm prefix pages.

    Each replica is leased one TPU chip (``ray_actor_options`` overrides;
    ``JAX_PLATFORMS=cpu`` in the caller's environment is the explicit way
    to CPU replicas), and a replica whose constructor fails — leased a
    chip, found another platform — fails this call at once.

    Returns the DeploymentHandle."""
    from ray_tpu.serve.openai.ingress import build_openai_deployment

    ray_actor_options = _replica_resources(ray_actor_options)
    app = build_openai_deployment(
        models,
        name=name,
        num_replicas=num_replicas,
        route_prefix=route_prefix,
        tokenizer=tokenizer,
        max_engines_per_replica=max_engines_per_replica,
        max_concurrency=max_concurrency,
        autoscaling_config=autoscaling_config,
        ray_actor_options=ray_actor_options,
        max_queued_requests=max_queued_requests,
    )
    return serve.run(
        app, wait_ready=wait_ready, ready_timeout_s=ready_timeout_s
    )
