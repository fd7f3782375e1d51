"""Built-in core metrics for the runtime's own hot paths.

Parity target: the reference's ~100 built-in metrics (ray_metric_defs,
exported via OpenCensus → dashboard agent → Prometheus). Instruments
register in the ordinary per-process registry (utils/metrics.py), so
``state.cluster_metrics`` / the dashboard's ``/metrics`` aggregate them
across every process exactly like user metrics — no second pipeline.

Hot-path contract: callers guard every update with the module-level
``ENABLED`` flag (``if core_metrics.ENABLED: core_metrics.X...``), never
a registry lookup, so ``RT_OBSERVABILITY_ENABLED=0`` reduces the whole
subsystem to one attribute check per site.

Import discipline: this module may import only ``ray_tpu.utils.*`` —
it is imported from the RPC substrate itself.
"""

from __future__ import annotations

from ray_tpu.utils.config import config
from ray_tpu.utils.metrics import (
    Counter,
    Gauge,
    Histogram,
    register_reset_hook,
)

ENABLED = bool(config.observability_enabled)

# Latency instruments get sub-millisecond-resolution buckets: the core
# plane's interesting range is 10us..1s (an RPC roundtrip is ~100us).
_LATENCY_BOUNDS = (
    0.00001, 0.00005, 0.0001, 0.0005, 0.001, 0.005,
    0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0,
)
_BATCH_BOUNDS = (1, 2, 4, 8, 16, 32, 64, 128)
# the kinds of layer a serving cache is made of, a ``serve_kv_<kind>_bytes``
# gauge each: what a model's ``cache_layout`` names some of
KV_KINDS = ("full", "window", "latent", "state")
# the kinds every engine reports, zero where its model has none of them;
# ``state`` came later (PR 57) and is reported by the models that name it,
# so that what another family's ``kv_bytes_by_kind`` reads did not change
KV_KINDS_EVERY_MODEL = KV_KINDS[:3]
# the phases of a round of the paged engine that worked, which are its
# ``rt/engine/<phase>`` spans (serve/llm.py ``_RoundAccount``): a
# ``serve_engine_<phase>_s`` histogram each. ``other`` is what the round's
# span covers outside every inner one. A round that only parked in the idle
# wait (``rt/engine/idle``) is no phase and observes nothing
ENGINE_PHASES = (
    "admit", "prefill", "first_token_sync", "dispatch", "harvest_sync", "harvest", "other",
)
# blocked on the device; together ``serve_engine_round_blocked_s``
ENGINE_BLOCKED_PHASES = tuple(p for p in ENGINE_PHASES if p.endswith("_sync"))
# the thread's own code; together ``serve_engine_round_host_s``, and a
# ``serve_engine_dry_<phase>_s`` histogram each
ENGINE_HOST_PHASES = tuple(p for p in ENGINE_PHASES if p not in ENGINE_BLOCKED_PHASES)


def _engine_phase_series() -> dict:
    series = {}
    for phase in ENGINE_PHASES:
        series[f"serve_engine_{phase}_s"] = Histogram(
            f"rt_serve_engine_{phase}_s",
            f"engine-thread time per working round in its phase {phase!r} "
            "(zeros included: sum/count is seconds a round)",
            boundaries=_LATENCY_BOUNDS,
            tag_keys=("deployment",),
        )
    for phase in ENGINE_HOST_PHASES:
        series[f"serve_engine_dry_{phase}_s"] = Histogram(
            f"rt_serve_engine_dry_{phase}_s",
            f"of the phase {phase!r}, the time per working round from where "
            "the engine thread learned that the device had run all it was "
            "handed to the thread's next program (a lower bound of the "
            "device's idle time; zeros included)",
            boundaries=_LATENCY_BOUNDS,
            tag_keys=("deployment",),
        )
    return series


def _build() -> dict:
    return {
        # -- scheduler (control_store.py) --
        "sched_queue_depth": Gauge(
            "rt_sched_queue_depth",
            "control-store scheduler queue depth (actors + PGs pending)",
        ),
        "sched_dispatch_latency_s": Histogram(
            "rt_sched_dispatch_latency_s",
            "time a scheduler item waits in the queue before processing",
            boundaries=_LATENCY_BOUNDS,
            tag_keys=("kind",),
        ),
        # -- leases (node_agent.py: agent side; worker.py: owner side) --
        "lease_requests": Counter(
            "rt_lease_requests_total",
            "lease_worker RPCs received by this node agent",
        ),
        "lease_grants": Counter(
            "rt_lease_grants_total",
            "worker leases granted by this node agent",
        ),
        "lease_cache_hits": Counter(
            "rt_lease_cache_hits_total",
            "tasks dispatched onto an already-held (cached) worker lease "
            "without a lease RPC",
        ),
        # per-node gauges carry a node label: the cluster merge keeps the
        # LATEST value per series key, so unlabelled per-node gauges
        # would collapse an N-node cluster to whichever agent answered
        # last
        "worker_pool_size": Gauge(
            "rt_worker_pool_size",
            "workers in this node agent's pool by state",
            tag_keys=("state", "node"),
        ),
        # -- object store (object_store.py) --
        "object_store_used_bytes": Gauge(
            "rt_object_store_used_bytes",
            "bytes of sealed+unsealed segments resident in shm",
            tag_keys=("node",),
        ),
        "object_store_spilled_bytes": Gauge(
            "rt_object_store_spilled_bytes",
            "bytes currently spilled to disk",
            tag_keys=("node",),
        ),
        "object_store_spills": Counter(
            "rt_object_store_spill_total",
            "segments spilled shm -> disk under memory pressure",
        ),
        "object_store_restores": Counter(
            "rt_object_store_restore_total",
            "segments restored disk -> shm for same-host readers",
        ),
        # -- RPC substrate (utils/rpc.py) --
        "rpc_client_latency_s": Histogram(
            "rt_rpc_client_latency_s",
            "client-observed RPC round-trip latency by method family",
            boundaries=_LATENCY_BOUNDS,
            tag_keys=("family",),
        ),
        # -- serve (serve/router.py, serve/batching.py) --
        "serve_router_requests": Counter(
            "rt_serve_router_requests_total",
            "requests routed by deployment",
            tag_keys=("deployment",),
        ),
        "serve_router_queue_wait_s": Histogram(
            "rt_serve_router_queue_wait_s",
            "time a request waits in the router for a replica assignment",
            boundaries=_LATENCY_BOUNDS,
        ),
        "serve_batch_size": Histogram(
            "rt_serve_batch_size",
            "@serve.batch executed batch sizes",
            boundaries=_BATCH_BOUNDS,
        ),
        "serve_batch_wait_s": Histogram(
            "rt_serve_batch_wait_s",
            "time a request waits in a @serve.batch queue before its "
            "batch executes",
            boundaries=_LATENCY_BOUNDS,
        ),
        # -- LLM serving (serve/llm.py, serve/openai/ingress.py) --
        "serve_ttft_s": Histogram(
            "rt_serve_ttft_s",
            "time from request admission to first generated token",
            boundaries=_LATENCY_BOUNDS,
            tag_keys=("deployment",),
        ),
        "serve_inter_token_s": Histogram(
            "rt_serve_inter_token_s",
            "gap between consecutive generated tokens of one request",
            boundaries=_LATENCY_BOUNDS,
            tag_keys=("deployment",),
        ),
        # the paged engine's own account of itself (serve/llm.py
        # _engine_loop_paged): a request's waits, the engine thread's
        # time per round, and counts stamped where the work happens.
        # Sum and count are what is read; the buckets are coarse.
        "serve_engine_queue_wait_s": Histogram(
            "rt_serve_engine_queue_wait_s",
            "time a request waits in the engine's queue, enqueue to "
            "pages reserved, observed at admission",
            boundaries=_LATENCY_BOUNDS,
            tag_keys=("deployment",),
        ),
        "serve_engine_page_wait_s": Histogram(
            "rt_serve_engine_page_wait_s",
            "part of the queue wait spent refused for KV pages (first "
            "refusal to admission); 0 for a request never refused, so "
            "sum/count is the mean over all admitted",
            boundaries=_LATENCY_BOUNDS,
            tag_keys=("deployment",),
        ),
        "serve_engine_first_token_s": Histogram(
            "rt_serve_engine_first_token_s",
            "time from pages reserved to the first token sampled "
            "(prefill, and the decode chunks queued ahead of it)",
            boundaries=_LATENCY_BOUNDS,
            tag_keys=("deployment",),
        ),
        "serve_engine_round_host_s": Histogram(
            "rt_serve_engine_round_host_s",
            "engine-thread time per working round in its own code "
            "(ENGINE_HOST_PHASES); device syncs and idle waits excluded",
            boundaries=_LATENCY_BOUNDS,
            tag_keys=("deployment",),
        ),
        "serve_engine_round_blocked_s": Histogram(
            "rt_serve_engine_round_blocked_s",
            "engine-thread time per working round blocked on the device "
            "(ENGINE_BLOCKED_PHASES: first-token sample, harvest of the "
            "in-flight chunk)",
            boundaries=_LATENCY_BOUNDS,
            tag_keys=("deployment",),
        ),
        **_engine_phase_series(),
        "serve_decode_steps": Counter(
            "rt_serve_decode_steps_total",
            "decode token-steps executed (K per harvested chunk)",
            tag_keys=("deployment",),
        ),
        "serve_decode_row_steps": Counter(
            "rt_serve_decode_row_steps_total",
            "decode token-steps times live rows (= tokens generated by "
            "decode, first tokens excluded)",
            tag_keys=("deployment",),
        ),
        "serve_prompt_tokens": Counter(
            "rt_serve_prompt_tokens_total",
            "prompt tokens of admitted requests (once per admission)",
            tag_keys=("deployment",),
        ),
        "serve_prefix_tokens_reused": Counter(
            "rt_serve_prefix_tokens_reused_total",
            "prompt tokens of admitted requests served from resident "
            "prefix pages (once per admission, not per attempt)",
            tag_keys=("deployment",),
        ),
        "serve_prefill_tokens": Counter(
            "rt_serve_prefill_tokens_total",
            "prompt tokens computed by prefill calls (padding excluded)",
            tag_keys=("deployment",),
        ),
        "serve_prefill_cross_positions": Counter(
            "rt_serve_prefill_cross_positions_total",
            "prompt positions of prefill calls that went through every "
            "layer, in a model whose other prompt positions stop short of "
            "its last layers (models/phi4flash.py: the cross-decoder runs "
            "on a row's last position alone); no series from another model",
            tag_keys=("deployment",),
        ),
        "serve_prefill_calls": Counter(
            "rt_serve_prefill_calls_total",
            "prefill calls dispatched",
            tag_keys=("deployment",),
        ),
        "serve_prefill_rows": Counter(
            "rt_serve_prefill_rows_total",
            "rows of prefill calls that held a sequence's chunk (the rows "
            "a call was padded with excluded)",
            tag_keys=("deployment",),
        ),
        "serve_first_tokens_ahead": Counter(
            "rt_serve_first_tokens_ahead_total",
            "first tokens that reached a decode call from the device, "
            "where the sampling program left them, before the host had "
            "fetched them; counted where that decode call is handed over. "
            "Beside rt_serve_ttft_s's count, the share of first tokens "
            "whose wait lay behind a decode call. No series from a decode "
            "module of one row, whose first token is fetched where its "
            "prompt ends",
            tag_keys=("deployment",),
        ),
        "serve_prefill_width": Histogram(
            "rt_serve_prefill_width",
            "positions of each prefill call as dispatched, rows x padded "
            "width (sum = positions paid for, count = calls)",
            boundaries=(16, 32, 64, 128, 256, 512, 1024),
            tag_keys=("deployment",),
        ),
        "serve_tokens_generated": Counter(
            "rt_serve_tokens_generated_total",
            "tokens generated by the LLM engine",
            tag_keys=("deployment",),
        ),
        "serve_queued_requests": Gauge(
            "rt_serve_queued_requests",
            "requests waiting for a decode row and KV pages in this "
            "engine process",
            tag_keys=("deployment", "node"),
        ),
        "serve_batch_fill": Histogram(
            "rt_serve_batch_fill",
            "live decode rows per continuous-batching decode round",
            boundaries=_BATCH_BOUNDS,
            tag_keys=("deployment",),
        ),
        "serve_prefix_cache_hits": Counter(
            "rt_serve_prefix_cache_hits_total",
            "prompt prefix blocks served from the engine block pool "
            "instead of being re-prefilled",
            tag_keys=("deployment",),
        ),
        "serve_prefix_cache_misses": Counter(
            "rt_serve_prefix_cache_misses_total",
            "prompt prefix blocks that had to be prefilled (not resident)",
            tag_keys=("deployment",),
        ),
        # paged KV pool (serve/prefix_cache.PagedKVPool): one page pool
        # holds generation AND prefix KV; occupied counts pages pinned
        # by live requests or resident as sealed prefix blocks; total
        # beside it so the occupancy RATIO is computable by the alert
        # engine without knowing every deployment's pool size
        "serve_kv_pages_total": Gauge(
            "rt_serve_kv_pages_total",
            "KV page-pool capacity (pages) per engine process",
            tag_keys=("deployment", "node"),
        ),
        "serve_kv_pages_occupied": Gauge(
            "rt_serve_kv_pages_occupied",
            "KV pages pinned by live requests or resident as sealed "
            "prefix blocks, per engine process",
            tag_keys=("deployment", "node"),
        ),
        "serve_kv_pages_prefix_resident": Gauge(
            "rt_serve_kv_pages_prefix_resident",
            "sealed prefix pages resident in this engine's page pool",
            tag_keys=("deployment", "node"),
        ),
        # what the cache holds by kind of layer (``KV_KINDS``): a full
        # layer's pages, a window layer's ring a decode row
        # (models/mimo_v2.py), a latent layer's pages
        # (models/deepseek_v3.py), a recurrent layer's state a decode row
        # (models/phi4flash.py, models/qwen3_next.py); a model reads 0
        # under the kinds it has none of
        "serve_kv_full_bytes": Gauge(
            "rt_serve_kv_full_bytes",
            "bytes of K and V the device holds for paged full-attention "
            "layers, per engine process",
            tag_keys=("deployment", "node"),
        ),
        "serve_kv_window_bytes": Gauge(
            "rt_serve_kv_window_bytes",
            "bytes of K and V the device holds for window-attention "
            "layers (a ring a decode row), per engine process",
            tag_keys=("deployment", "node"),
        ),
        "serve_kv_latent_bytes": Gauge(
            "rt_serve_kv_latent_bytes",
            "bytes of latent rows the device holds for paged latent-"
            "attention layers (models/deepseek_v3.py), per engine process",
            tag_keys=("deployment", "node"),
        ),
        "serve_kv_state_bytes": Gauge(
            "rt_serve_kv_state_bytes",
            "bytes the device holds for recurrent layers (a state and the "
            "convolution's last inputs a decode row: a Mamba layer's of "
            "models/phi4flash.py, a gated delta-rule layer's matrix a head "
            "of models/qwen3_next.py), per engine process",
            tag_keys=("deployment", "node"),
        ),
        "serve_prefix_refused": Counter(
            "rt_serve_prefix_refused_total",
            "admissions whose prefix match was refused by name because "
            "the model's cache keeps state that pages do not hold",
            tag_keys=("deployment",),
        ),
        # the expert layer (ops/moe.py), counted on the device beside the
        # sampled tokens by the decode programs and added at harvest
        "serve_moe_assignments": Counter(
            "rt_serve_moe_assignments_total",
            "token-expert pairs of decode steps that landed on experts "
            "held by this engine",
            tag_keys=("deployment",),
        ),
        "serve_moe_expert_steps": Counter(
            "rt_serve_moe_expert_steps_total",
            "held experts x expert layers x decode steps",
            tag_keys=("deployment",),
        ),
        "serve_moe_experts_hit": Counter(
            "rt_serve_moe_experts_hit_total",
            "held experts that got at least one token, summed over expert "
            "layers and decode steps",
            tag_keys=("deployment",),
        ),
        "serve_moe_max_load": Counter(
            "rt_serve_moe_max_load_total",
            "tokens of the fullest held expert, summed over expert layers "
            "and decode steps",
            tag_keys=("deployment",),
        ),
        "serve_mla_context_tokens": Counter(
            "rt_serve_mla_context_tokens_total",
            "positions the live rows of decode steps attended over in a "
            "latent cache, summed over rows and steps (once a step, not a "
            "layer); counted on the device beside the sampled tokens",
            tag_keys=("deployment",),
        ),
        "serve_attn_context_tokens": Counter(
            "rt_serve_attn_context_tokens_total",
            "positions the live rows of decode steps attended over in the "
            "paged full-attention layers of a model that keeps K and V a "
            "head, summed over rows and steps (once a step, not a layer); "
            "counted on the device beside the sampled tokens",
            tag_keys=("deployment",),
        ),
        "serve_attn_loop_tokens": Counter(
            "rt_serve_attn_loop_tokens_total",
            "positions the decode steps' attention kernels read over "
            "page-table columns, each live row walking its own turns: "
            "where K and V are pools the row's pages x positions a page (a "
            "last turn is read as far as the row's last page), in the "
            "latent family its turns x positions a turn; summed over steps "
            "(once a step, not a layer); what the live rows attended over "
            "is the useful part of it",
            tag_keys=("deployment",),
        ),
        "serve_window_context_tokens": Counter(
            "rt_serve_window_context_tokens_total",
            "positions the live rows of decode steps attended over in the "
            "window-attention layers, min(position + 1, window) a row, "
            "summed over rows and steps (once a step, not a layer); counted "
            "on the device beside the sampled tokens",
            tag_keys=("deployment",),
        ),
        "serve_multiplex_loads": Counter(
            "rt_serve_multiplex_loads_total",
            "per-model multiplex loads (cold model pulled into a replica)",
            tag_keys=("model",),
        ),
        "serve_multiplex_evictions": Counter(
            "rt_serve_multiplex_evictions_total",
            "per-model multiplex LRU evictions",
            tag_keys=("model",),
        ),
        # -- compiled pipelines (parallel/pipeline.py) --
        "pipeline_stage_busy_s": Histogram(
            "rt_pipeline_stage_busy_s",
            "per-stage compute time (fwd+bwd) per compiled-pipeline step",
            boundaries=_LATENCY_BOUNDS,
            tag_keys=("stage",),
        ),
        "pipeline_bubble_fraction": Histogram(
            "rt_pipeline_bubble_fraction",
            "per-stage idle/(idle+busy) fraction per compiled-pipeline "
            "step, by schedule",
            boundaries=(0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8,
                        0.9),
            tag_keys=("stage", "schedule"),
        ),
        # -- channels (core/channels.py) --
        "channel_write_blocks": Counter(
            "rt_channel_write_blocks_total",
            "channel writes that blocked or bounced on a full ring / "
            "mailbox, by transport",
            tag_keys=("transport",),
        ),
        # -- host collectives (collective/collective.py, collective/p2p.py) --
        "collective_bytes_sent": Counter(
            "rt_collective_bytes_sent_total",
            "host-collective payload bytes sent by this process, by op "
            "and transport (p2p ring deliveries vs control-store KV)",
            tag_keys=("op", "transport"),
        ),
        "collective_op_latency_s": Histogram(
            "rt_collective_op_latency_s",
            "end-to-end host collective op latency by op",
            boundaries=_LATENCY_BOUNDS,
            tag_keys=("op",),
        ),
        # -- bucketed grad sync (collective/bucketed.py) --
        "collective_overlap_hidden_frac": Histogram(
            "rt_collective_overlap_hidden_frac",
            "fraction of grad_sync bucket comm time hidden behind caller "
            "compute, from joining bucket spans against the window before "
            "join() (1.0 = fully overlapped)",
            boundaries=(0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8,
                        0.9),
        ),
        "collective_bucket_bytes": Counter(
            "rt_collective_bucket_bytes_total",
            "gradient bytes shipped through bucketed grad_sync, by "
            "transport (flat ring / two-level hierarchical / KV fallback)",
            tag_keys=("transport",),
        ),
        "collective_inter_bytes": Counter(
            "rt_collective_inter_host_bytes_total",
            "collective payload bytes whose ring delivery crossed a host "
            "boundary (destination host differs from the sender's)",
            tag_keys=("op",),
        ),
        # -- task event buffer (worker.py) --
        "task_events_dropped": Counter(
            "rt_task_events_dropped_total",
            "task lifecycle/execution events evicted from the bounded "
            "per-worker ring buffer",
        ),
        # -- cluster health (core/control_store.py health loop) --
        "cluster_nodes_dead": Gauge(
            "rt_cluster_nodes_dead",
            "nodes currently marked dead by the head's heartbeat health "
            "loop (feeds the node_heartbeat_missed alert rule)",
        ),
        # -- profiler + forensics (observability/profiler.py, forensics.py) --
        "profile_samples": Counter(
            "rt_profile_samples_total",
            "continuous-sampler stack samples by attributed subsystem",
            tag_keys=("subsystem",),
        ),
        "profiler_continuous_hz": Gauge(
            "rt_profiler_hz",
            "continuous sampler rate in this process (0 = off)",
        ),
        "task_stalls": Counter(
            "rt_task_stalls_total",
            "tasks flagged by the stall watchdog (ran past "
            "task_stall_dump_s without finishing)",
        ),
        # -- serving control loop (serve/autoscale/) --
        "serve_shed": Counter(
            "rt_serve_shed_total",
            "requests shed by proxy admission control (429/503 + "
            "Retry-After), by deployment and reason",
            tag_keys=("deployment", "reason"),
        ),
        "serve_admission_inflight": Gauge(
            "rt_serve_admission_inflight",
            "requests currently admitted (queued + executing) through "
            "this proxy, per deployment",
            tag_keys=("deployment", "node"),
        ),
        "serve_replicas_running": Gauge(
            "rt_serve_replicas_running",
            "serving replicas currently live per deployment",
            tag_keys=("deployment",),
        ),
        "serve_replicas_target": Gauge(
            "rt_serve_replicas_target",
            "autoscaler target replica count per deployment",
            tag_keys=("deployment",),
        ),
        "serve_replicas_draining": Gauge(
            "rt_serve_replicas_draining",
            "replicas in session-aware drain (out of the routing table, "
            "finishing live streams) per deployment",
            tag_keys=("deployment",),
        ),
        "serve_autoscale_decisions": Counter(
            "rt_serve_autoscale_decisions_total",
            "autoscaler scale decisions by deployment and direction",
            tag_keys=("deployment", "direction"),
        ),
    }


def _reinstall() -> None:
    """(Re)create every instrument and rebind the module attributes.
    Registered as a registry reset hook, so the runtime's
    self-instrumentation survives test resets."""
    for key, instrument in _build().items():
        globals()[key] = instrument


def set_enabled(on: bool) -> None:
    global ENABLED
    ENABLED = bool(on)
    config.set("observability_enabled", bool(on))


_reinstall()
register_reset_hook(_reinstall)
