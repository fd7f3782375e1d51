"""The program against the plain reference, logits compared.

Serving: the family's ``compare_serve`` (``benchmark/families/<family>.py``):
a seeded prompt prefilled into the paged cache and the seeded continuation
decoded through it, every step's logits held against the reference's full
forward pass over the same sequence. Training (GPT-2's by name): one
optimizer step on seeded sequences from the initial parameters, program
against reference: the loss before it, the gradient the optimizer was
given, and the loss after it. Logits, losses and gradients, not sampled
tokens: with random weights the largest logit changes on rounding.

``python3 benchmark/run.py --check <config>`` runs it at the
configuration's published sizes in this process, on whatever device JAX
finds, outside any run; the tests run it at the tiny preset on the CPU.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ADAM_B1 = 0.9  # compare_step reads the gradient back from Adam's first moment


def reference_step(params, sequences, model: Dict[str, int], opt):
    """One optimizer step as published, on one device: loss and gradient
    of the mean next-token loss over ``sequences`` by ``jax.grad`` of the
    plain reference, one sequence at a time; ``opt``'s first update from
    that gradient in float32; the loss after it. Returns (loss before,
    gradient, loss after)."""
    import jax
    import optax

    from benchmark.reference import gpt2_ref

    value_and_grad = jax.jit(jax.value_and_grad(lambda p, t: gpt2_ref.loss(p, t, model)))
    mean = jax.jit(lambda trees: jax.tree.map(lambda *x: sum(x) / len(x), *trees))

    @jax.jit
    def updated(p, grads):
        updates, _ = opt.update(grads, opt.init(p), p)
        return optax.apply_updates(p, updates)

    def over_sequences(p):
        out = [value_and_grad(p, seq[None]) for seq in sequences]
        return sum(float(l) for l, _ in out) / len(out), mean([g for _, g in out])

    before, grads = over_sequences(params)
    after, _ = over_sequences(updated(params, grads))
    return before, grads, after


def compare_step(step, params, opt_state, tokens, sequences, model: Dict[str, int], opt):
    """The program's train step, twice on ``tokens`` from the initial
    parameters, against ``reference_step`` on the distinct ``sequences``
    that ``tokens`` repeats equally often (so both take the mean over the
    same losses; where the batch is sharded over chips, chip i holds only
    sequence i, and the gradient the optimizer sees is right only if the
    all-reduce is). ``step`` may donate its arguments, so the initial
    parameters wait on the host meanwhile. The gradient is read from
    Adam's first moment after one step, ``mu = (1 - ADAM_B1) g``, so
    ``opt`` is an optax Adam built with ``b1=ADAM_B1``. Returns (readings,
    params, opt_state)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    start = jax.device_get(params)
    params, opt_state, loss0 = step(params, opt_state, tokens)
    loss0 = float(loss0)
    mu = jax.device_get(opt_state[0].mu)
    params, opt_state, loss1 = step(params, opt_state, tokens)
    device = jax.devices()[0]
    want0, want_grads, want1 = reference_step(
        jax.device_put(start, device), jnp.asarray(np.asarray(sequences)), model, opt
    )

    @jax.jit
    def squares(mu, want):
        got = jax.tree.map(lambda m: m.astype(jnp.float32) / (1.0 - ADAM_B1), mu)
        total = lambda tree: sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree))
        return (total(jax.tree.map(jnp.subtract, got, want)), total(want), total(got))

    sq_err, sq_ref, sq_got = (float(x) for x in squares(jax.device_put(mu, device), want_grads))
    return {
        "loss_program": loss0, "loss_reference": want0,
        "loss_after_program": float(loss1), "loss_after_reference": want1,
        "grad_norm_program": sq_got ** 0.5, "grad_norm_reference": sq_ref ** 0.5,
        "grad_rel_error": (sq_err / sq_ref) ** 0.5,
    }, params, opt_state


def step_problems(got: Dict[str, float], tolerance: Dict[str, float]) -> List[str]:
    """What of ``compare_step``'s readings misses the configuration's
    ``reference_tolerance``: ``loss`` and ``loss_after`` are absolute,
    ``grad`` is the norm of the difference over the reference's norm."""
    misses = [
        ("loss before the step", abs(got["loss_program"] - got["loss_reference"]),
         tolerance["loss"]),
        ("gradient, relative", got["grad_rel_error"], tolerance["grad"]),
        ("loss after the step", abs(got["loss_after_program"] - got["loss_after_reference"]),
         tolerance["loss_after"]),
    ]
    return [f"program and reference differ in {what} by {miss:.3e} > {tol:g}"
            for what, miss, tol in misses if not miss <= float(tol)]


def main(bench: Dict[str, Any], config_name: str, seed: int) -> int:
    import jax

    from benchmark import harness

    entry = next(c for c in bench["configs"] if c["name"] == config_name)
    cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
    device = jax.devices()[0]
    out: Dict[str, Any] = {"config": config_name, "device": {
        "platform": device.platform, "kind": device.device_kind,
    }}
    if "train" in cfg:
        import numpy as np
        import optax

        from ray_tpu.models import gpt2

        tc = cfg["train"]
        mcfg = dataclasses.replace(
            gpt2.CONFIGS[tc["model_id"]], attn_impl=tc["attn_impl"],
            remat=tc["remat"], scan_unroll=tc["scan_unroll"],
            loss_impl=tc["loss_impl"], loss_chunk=tc["loss_chunk"],
        )
        opt = optax.adamw(tc["learning_rate"], b1=ADAM_B1, weight_decay=tc["weight_decay"])
        params = jax.jit(lambda k: gpt2.init(k, mcfg))(jax.random.PRNGKey(seed & 0x7FFFFFFF))
        sequences = np.random.default_rng([seed, 7]).integers(
            0, mcfg.vocab_size, (1, mcfg.n_positions + 1), dtype=np.int32
        )
        got, _, _ = compare_step(
            jax.jit(gpt2.make_train_step(mcfg, opt), donate_argnums=(0, 1)),
            params, jax.jit(opt.init)(params),
            jax.numpy.asarray(np.repeat(sequences, tc["batch_per_chip"], axis=0)),
            sequences, cfg["model"], opt,
        )
        out.update(got)
        out["tolerance"] = cfg["reference_tolerance"]
        out["problems"] = step_problems(got, cfg["reference_tolerance"])
        out["ok"] = not out["problems"]
    else:
        fam = harness.family(harness.find(bench, "families", cfg["family"], ".py"))
        mcfg, params = fam.serve_params(cfg["model_id"])  # the engine's own weights
        chk = cfg["check"]
        out.update(fam.compare_serve(
            mcfg, cfg["model"], params, seed,
            prompt_lens=chk["prompt_lens"], steps=int(chk["decode_steps"]),
        ))
        out["tolerance"] = chk["logit_tolerance"]
        out["ok"] = max(out["prefill_max_abs"], out["decode_max_abs"]) <= out["tolerance"]
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1
