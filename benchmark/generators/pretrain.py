"""The training generator: seeded random token rows through ``ray_tpu.data``
into ``train.JaxTrainer``, one worker process holding every chip of the
cell.

The train loop is the benchmark's own function. It builds the model step
the configuration names (``gpt2.make_train_step`` under the mesh, as
``chip_smoke.py`` does), syncs on the loss every ``steps_per_sync`` steps
as a logging trainer does, and stamps each sync: the readings between
stamps are what the metrics are made of. The window opens at the sync
that ends the warm-up and the run ends at the first sync after
``--seconds``.
"""

from __future__ import annotations

import functools
import math
import statistics
import time
from typing import Any, Dict

from benchmark import estimators, harness
from benchmark.harness import say


def _make_tokens(batch: Dict[str, Any], width: int, vocab: int, seed: int) -> Dict[str, Any]:
    import numpy as np

    ids = batch["id"]
    rng = np.random.default_rng([int(seed), int(ids[0])])
    return {"tokens": rng.integers(0, vocab, (len(ids), width), dtype=np.int32)}


def _train_loop(cfg: Dict[str, Any]) -> None:
    """Runs in the train worker, the one process that holds the chips."""
    import dataclasses

    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding

    import ray_tpu.train as train
    from benchmark import harness as harness_mod, replica_hooks, trace as trace_mod
    from benchmark.reference import check
    from ray_tpu.models import gpt2
    from ray_tpu.parallel import MeshConfig, build_mesh, shard_pytree
    from ray_tpu.parallel.sharding import batch_spec, gpt_rules, tree_shardings

    entered_wall = time.time()
    devices = jax.devices()
    reach_chip_s = time.time() - entered_wall
    if devices[0].platform != cfg["platform"] or len(devices) != cfg["chips"]:
        raise RuntimeError(
            f"train worker wants {cfg['chips']} {cfg['platform']} device(s), "
            f"JAX found {len(devices)} x {devices[0].platform!r}"
        )
    tc = cfg["train"]
    mcfg = dataclasses.replace(
        gpt2.CONFIGS[tc["model_id"]], attn_impl=tc["attn_impl"], remat=tc["remat"],
        scan_unroll=tc["scan_unroll"], loss_impl=tc["loss_impl"],
        loss_chunk=tc["loss_chunk"],
    )
    seq = mcfg.n_positions
    batch = tc["batch_per_chip"] * len(devices)
    mesh = build_mesh(MeshConfig(dp=len(devices)))
    rules = gpt_rules()
    opt = optax.adamw(tc["learning_rate"], b1=check.ADAM_B1, weight_decay=tc["weight_decay"])
    seed = int(cfg["seed"])
    spans: Dict[str, float] = {
        "worker_start_s (fit() to the train loop entered)": entered_wall - cfg["t_fit"],
        "reach_chip_s (jax.devices() in the worker)": reach_chip_s,
    }

    # weights and optimizer state on the device, one jitted call each,
    # from the seed (a seed may need more than 31 bits)
    t0 = time.perf_counter()
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    params = jax.jit(functools.partial(gpt2.init, cfg=mcfg))(key)
    opt_state = jax.jit(opt.init)(params)
    params = shard_pytree(params, mesh, rules)
    opt_state = shard_pytree(opt_state, mesh, rules)
    jax.block_until_ready((params, opt_state))
    spans["init_s"] = time.perf_counter() - t0

    data_sharding = NamedSharding(mesh, batch_spec())
    shard = train.get_dataset_shard("train")

    def epochs():
        while True:
            yield from shard.iter_batches(
                batch_size=batch, prefetch_batches=int(cfg["prefetch_batches"]),
                drop_last=True,
            )

    batches = epochs()
    waited = [0.0]

    def next_tokens():
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/next_batch"):
            host = next(batches)["tokens"]
        with jax.profiler.TraceAnnotation("bench/device_put"):
            out = jax.device_put(host, data_sharding)
        waited[0] += time.perf_counter() - t
        return out

    # one seeded sequence per chip, repeated to fill the chip's share of
    # the batch: the reference check's batch, and the step's shape
    probe = np.random.default_rng([seed, 7]).integers(
        0, mcfg.vocab_size, (len(devices), seq + 1), dtype=np.int32
    )
    tokens = jax.device_put(np.repeat(probe, tc["batch_per_chip"], axis=0), data_sharding)
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        step = jax.jit(
            gpt2.make_train_step(mcfg, opt),
            in_shardings=(
                tree_shardings(mesh, rules, params),
                tree_shardings(mesh, rules, opt_state),
                data_sharding,
            ),
            donate_argnums=(0, 1),
        ).lower(params, opt_state, tokens).compile()
    spans["compile_or_cache_load_s"] = time.perf_counter() - t0
    analysis = step.memory_analysis()
    program_bytes = {
        k: int(getattr(analysis, k + "_size_in_bytes", 0) or 0)
        for k in ("argument", "output", "alias", "temp", "generated_code")
    }

    # one optimizer step from the initial parameters against the plain
    # float32 reference at the highest matmul precision: loss, gradient
    # (all-reduced, where there are chips to reduce over), loss after it
    first_dispatch_wall = time.time()
    t0 = time.perf_counter()
    reference, params, opt_state = check.compare_step(
        step, params, opt_state, tokens, probe, cfg["model"], opt
    )
    spans["reference_check_s"] = time.perf_counter() - t0

    per_sync = int(cfg["steps_per_sync"])
    losses = []
    tokens = next_tokens()
    for _ in range(int(cfg["warm_syncs"])):
        for _ in range(per_sync):
            params, opt_state, loss = step(params, opt_state, tokens)
            tokens = next_tokens()
        losses.append(float(loss))

    # ---- the window: opens at the sync that ended the warm-up ----------
    entries_t0 = harness_mod.cache_entries()
    wall0 = time.time()
    stamps = [time.perf_counter()]
    waited[0] = 0.0
    tracing, traced_syncs, trace_dir, window_span = False, 0, None, None
    while stamps[-1] - stamps[0] < cfg["seconds"]:
        n_sync = len(stamps) - 1
        if cfg["trace"] and not tracing and trace_dir is None and (
            n_sync == int(cfg["trace_offset_syncs"])
        ):
            trace_dir = cfg["trace_dir"]
            jax.profiler.start_trace(trace_dir)
            window_span = jax.profiler.TraceAnnotation("bench/window")
            window_span.__enter__()
            tracing = True
        for _ in range(per_sync):
            with jax.profiler.TraceAnnotation("bench/dispatch"):
                params, opt_state, loss = step(params, opt_state, tokens)
            tokens = next_tokens()
        with jax.profiler.TraceAnnotation("bench/sync"):
            losses.append(float(loss))
        stamps.append(time.perf_counter())
        if tracing:
            traced_syncs += 1
            if traced_syncs >= int(cfg["trace_syncs"]):
                window_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing = False
    if tracing:
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    input_wait_s = waited[0]
    entries_t1 = harness_mod.cache_entries()

    reduced = None
    if trace_dir is not None:
        path = trace_mod.find_xplane(trace_dir)
        if path:
            reduced = trace_mod.reduce(trace_mod.load_xplane(path))
    device = replica_hooks.device_report()
    train.report({
        "device": device, "program_bytes": program_bytes,
        "stamps": stamps, "wall0": wall0, "losses": losses,
        "first_dispatch_wall": first_dispatch_wall, "spans": spans,
        "input_wait_s": input_wait_s, "batch": batch, "seq": seq,
        "reference": reference,
        "trace": reduced, "traced_steps": traced_syncs * per_sync,
        "cache_entries": {"t0": entries_t0, "t1": entries_t1},
    })


def run(ctx) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu import data as rtd
    from ray_tpu import train

    tr, cfg, phases = ctx.traffic, ctx.config, ctx.phases
    chips = int(ctx.cell["chips"])
    model = cfg["model"]
    entries_start = harness.cache_entries()
    ray_tpu.init(**cfg.get("init", {}))
    phases.mark("cluster_up")
    try:
        rows = int(tr["rows_per_chip"]) * chips
        ds = rtd.range(rows, parallelism=max(1, rows // int(tr["rows_per_block"]))).map_batches(
            functools.partial(
                _make_tokens, width=int(model["n_positions"]) + 1, vocab=int(model["vocab_size"]),
                seed=ctx.seed,
            )
        )
        t_fit = time.time()
        result = train.JaxTrainer(
            _train_loop,
            train_loop_config={
                "platform": ctx.platform, "chips": chips, "train": cfg["train"],
                "model": model, "seed": ctx.seed, "seconds": ctx.seconds, "t_fit": t_fit,
                "steps_per_sync": tr["steps_per_sync"], "warm_syncs": tr["warm_syncs"],
                "prefetch_batches": tr["prefetch_batches"],
                "trace": bool(ctx.trace and ctx.platform == "tpu"),
                "trace_dir": harness.fresh_trace_dir() if ctx.trace else None,
                "trace_offset_syncs": tr.get("trace_offset_syncs", 2),
                "trace_syncs": tr.get("trace_syncs", 2),
            },
            scaling_config=train.ScalingConfig(
                num_workers=1, use_tpu=(ctx.platform == "tpu"),
                # on the CPU the trainer gives the worker this many virtual devices
                tpu_chips_per_worker=chips,
            ),
            datasets={"train": ds},
        ).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise RuntimeError(f"the trainer failed: {result.error}")
    m = result.metrics
    for name, s in m["spans"].items():
        phases.set(name, s)
    phases.set("trainer_ready (fit() to the first step dispatched)",
               m["first_dispatch_wall"] - t_fit)
    stamps = m["stamps"]
    obs: Dict[str, Any] = {
        "kind": "train", "model": model, "problems": [], "traffic": tr,
        "setup_s": m["wall0"] - phases.t0,
        "trainer_ready_s": m["first_dispatch_wall"] - t_fit,
        "syncs": stamps, "steps_per_sync": int(tr["steps_per_sync"]),
        "tokens_per_sync": int(tr["steps_per_sync"]) * m["batch"] * m["seq"],
        "input_wait": 100.0 * m["input_wait_s"] / (stamps[-1] - stamps[0]),
        "trace": m["trace"], "traced_steps": m["traced_steps"],
        "device": harness.with_memory(m["device"]),
        "cache_entries": {"start": entries_start, **m["cache_entries"]},
        "attention_shape": {
            "bh": int(cfg["train"]["batch_per_chip"]) * int(model["n_head"]),
            "t": int(model["n_positions"]),
            "d": int(model["n_embd"]) // int(model["n_head"]),
        },
    }
    say(f"window opens, set-up took {obs['setup_s']:.2f}s; "
        f"{len(stamps) - 1} syncs in {stamps[-1] - stamps[0]:.2f}s")
    _check(obs, m, cfg)
    return obs


def _check(obs: Dict[str, Any], m: Dict[str, Any], cfg: Dict[str, Any]) -> None:
    """``correct``: every synced loss finite and within 0.8 of ln V
    (seeded random tokens cannot be learned; ``chip_smoke.py`` holds the
    same band), one optimizer step on the seeded probe from the initial
    parameters equal to the plain float32 reference's within the
    configuration's tolerances (loss, gradient, loss after the step), and
    nothing compiled inside the window."""
    from benchmark.reference import check

    want = math.log(obs["model"]["vocab_size"])
    bad = [l for l in m["losses"] if not (math.isfinite(l) and abs(l - want) <= 0.8)]
    if bad:
        obs["problems"].append(f"losses not finite within 0.8 of ln V = {want:.3f}: {bad[:4]}")
    ref, tol = m["reference"], cfg["reference_tolerance"]
    stamps = obs["syncs"]
    obs["notes"] = [
        f"step program bytes {m['program_bytes']}; allocator "
        f"{ {k: v for k, v in obs['device']['memory'][0].items() if 'bytes' in k} }",
        f"tokens/s: whole window (all tokens between the first and last sync / seconds) "
        f"{obs['tokens_per_sync'] * (len(stamps) - 1) / (stamps[-1] - stamps[0]):.3f}, "
        f"median of {len(stamps) - 1} sync-to-sync readings "
        f"{statistics.median(estimators.sync_readings(stamps, obs['tokens_per_sync'])):.3f}",
        f"one step on the seeded probe, program against the plain float32 reference: loss "
        f"{ref['loss_program']:.6f} / {ref['loss_reference']:.6f} (tolerance {tol['loss']:g}); "
        f"gradient norm {ref['grad_norm_program']:.6g} / {ref['grad_norm_reference']:.6g}, "
        f"|difference| over |reference| {ref['grad_rel_error']:.3e} (tolerance {tol['grad']:g}); "
        f"loss after the step {ref['loss_after_program']:.6f} / "
        f"{ref['loss_after_reference']:.6f} (tolerance {tol['loss_after']:g})",
    ]
    obs["problems"] += check.step_problems(ref, tol)
    entries = obs["cache_entries"]
    if entries["t1"] != entries["t0"]:
        obs["problems"].append(f"the compile cache went from {entries['t0']} to "
                               f"{entries['t1']} entries inside the window: something compiled there")
    obs["attempted"] = len(m["losses"])
    obs["failed"] = len(bad)
    obs["compared"] = {
        "losses_outside_the_band": [len(bad), 0],
        "loss_gap": [abs(ref["loss_program"] - ref["loss_reference"]), tol["loss"]],
        "grad_rel_gap": [ref["grad_rel_error"], tol["grad"]],
        "loss_after_gap": [abs(ref["loss_after_program"] - ref["loss_after_reference"]),
                           tol["loss_after"]],
        "cache_entries_gained": [entries["t1"] - entries["t0"], 0],
    }
