"""The training check's three numbers under the program, under its control
and under a planted fault, a seed a line: the readings the limits of a
training configuration's ``reference_tolerance`` are set between.

    python3 benchmark/tools/step_control.py <config> <seed> [<seed> ...]

In this process, on the first device JAX finds (one chip: the probe is one
sequence repeated over the chip's share of the batch, as the run's is).
For each seed ``check.compare_step`` is given, in turn,

    program   the configuration's own train step (``gpt2.make_train_step``),
              what a run compares;
    control   the plain reference in the program's place, every matrix it
              multiplies by (the kernels and the two embeddings) rounded to
              float8_e4m3fn, the nearest precision below the bfloat16 the
              configuration computes in, the gradient at the rounded
              weights taken for the unrounded ones' (straight through the
              rounding) and the update applied to those: the step a later
              PR would be tempted by;
    unchanged the program's step with its state handed back as it came:
              the loss it reports is right and nothing moved.

The control and the fault have to read over the limit on one of the three
numbers, the program under it on all: ``tests/bench/test_bench_reference.py``
holds the same at the tiny preset.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
from typing import Any, Callable, Dict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

ROUNDED = ("kernel", "wte", "wpe")  # the leaves a matrix product reads


def float8_step(model: Dict[str, int], opt, every: int) -> Callable:
    """The control's step: loss and gradient by the plain reference on the
    batch's distinct sequences (each fills ``every`` rows running), its
    matrices rounded to float8_e4m3fn. The rounding is a program of its
    own whose result is held in float8: inside one program with the loss
    the TPU's compiler drops a narrowing and widening pair of converts
    (``xla_allow_excess_precision``), and the control then IS the reference
    (my chip run, PR 62: every gap 0 to seven digits on four seeds)."""
    import jax
    import jax.numpy as jnp
    import optax

    from benchmark.reference import gpt2_ref

    @jax.jit
    def narrowed(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, a: (a.astype(jnp.float8_e4m3fn)
                             if getattr(path[-1], "key", None) in ROUNDED else a), params)

    @jax.jit
    def stepped(params, low, opt_state, tokens):
        seen = jax.tree.map(lambda l, p: l.astype(p.dtype), low, params)
        value, grads = jax.value_and_grad(
            lambda p: gpt2_ref.loss(p, tokens[::every], model))(seen)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, value

    def step(params, opt_state, tokens):
        return stepped(params, narrowed(params), opt_state, tokens)

    return step


def unchanged(step: Callable) -> Callable:
    """``step`` with its state handed back as it came (``step`` may donate
    its arguments, so the state waits on the host meanwhile)."""
    import jax

    def faulty(params, opt_state, tokens):
        keep = jax.device_get((params, opt_state))
        _, _, loss = step(params, opt_state, tokens)
        params, opt_state = jax.device_put(keep)
        return params, opt_state, loss

    return faulty


def gaps(got: Dict[str, float]) -> Dict[str, float]:
    """The three numbers a run prints under ``compared``."""
    return {"loss_gap": abs(got["loss_program"] - got["loss_reference"]),
            "grad_rel_gap": got["grad_rel_error"],
            "loss_after_gap": abs(got["loss_after_program"] - got["loss_after_reference"])}


def readings(cfg: Dict[str, Any], seed: int) -> Dict[str, Dict[str, float]]:
    """``{"program" | "control" | "unchanged": gaps}`` for one seed, the
    weights and the probe made from it as ``generators/pretrain.py`` makes
    them."""
    import jax
    import numpy as np
    import optax

    from benchmark.reference import check
    from ray_tpu.models import gpt2

    tc = cfg["train"]
    mcfg = dataclasses.replace(
        gpt2.CONFIGS[tc["model_id"]], attn_impl=tc["attn_impl"], remat=tc["remat"],
        scan_unroll=tc["scan_unroll"], loss_impl=tc["loss_impl"], loss_chunk=tc["loss_chunk"],
    )
    opt = optax.adamw(tc["learning_rate"], b1=check.ADAM_B1, weight_decay=tc["weight_decay"])
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    probe = np.random.default_rng([seed, 7]).integers(
        0, mcfg.vocab_size, (1, mcfg.n_positions + 1), dtype=np.int32)
    tokens = jax.numpy.asarray(np.repeat(probe, tc["batch_per_chip"], axis=0))
    program = jax.jit(gpt2.make_train_step(mcfg, opt), donate_argnums=(0, 1))
    steps = {"program": program,
             "control": float8_step(cfg["model"], opt, tc["batch_per_chip"]),
             "unchanged": unchanged(program)}
    init, init_opt = jax.jit(lambda k: gpt2.init(k, mcfg)), jax.jit(opt.init)
    out = {}
    for name, step in steps.items():
        params = init(key)  # anew: the program's step donates its arguments
        got, _, _ = check.compare_step(step, params, init_opt(params), tokens,
                                       probe, cfg["model"], opt)
        out[name] = {**gaps(got), "loss_reference": got["loss_reference"],
                     "loss_after_reference": got["loss_after_reference"]}
    return out


def main(config: str, *seeds: str) -> int:
    import jax

    from benchmark import harness

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == config)
    cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
    device = jax.devices()[0]
    for seed in seeds:
        print(json.dumps({"config": config, "seed": int(seed), "device": device.device_kind,
                          "tolerance": {k: v for k, v in cfg["reference_tolerance"].items()
                                        if k != "why"},
                          **readings(cfg, int(seed))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
