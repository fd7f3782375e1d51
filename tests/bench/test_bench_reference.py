"""The plain float32 reference against the program, at gpt2-tiny on the CPU."""

import dataclasses

import pytest

MODEL = {"n_embd": 64, "n_layer": 2, "n_head": 4, "n_positions": 128, "vocab_size": 256}


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2

    out = {}
    for name, dt in (("float32", jnp.float32), ("bfloat16", jnp.bfloat16)):
        cfg = dataclasses.replace(gpt2.CONFIGS["gpt2-tiny"], dtype=dt)
        out[name] = (cfg, gpt2.init(jax.random.PRNGKey(0), cfg))
    return out


def test_forward_and_loss_agree_with_models_gpt2(tiny):
    import jax
    import jax.numpy as jnp

    from benchmark.reference import gpt2_ref
    from ray_tpu.models import gpt2

    cfg, params = tiny["float32"]
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, 256)
    got = gpt2.forward(params, tokens[:, :-1], cfg)[..., :256]
    want = gpt2_ref.forward(params, tokens[:, :-1], MODEL)
    # float32 on both sides, the same mathematics in another order of
    # operations: differences are rounding, a few ulp of logits near 1
    assert float(jnp.abs(got - want).max()) < 5e-6
    assert float(gpt2.loss_fn(params, tokens, cfg)) == pytest.approx(
        float(gpt2_ref.loss(params, tokens, MODEL)), abs=5e-6
    )


def test_the_reference_is_causal_and_reads_only_the_published_vocabulary(tiny):
    import jax.numpy as jnp

    from benchmark.reference import gpt2_ref

    _, params = tiny["float32"]
    a = jnp.arange(40)[None] % 256
    b = a.at[0, 30].set(7)
    la, lb = gpt2_ref.forward(params, a, MODEL), gpt2_ref.forward(params, b, MODEL)
    assert la.shape == (1, 40, 256)
    assert float(jnp.abs(la[0, :30] - lb[0, :30]).max()) == 0.0
    assert float(jnp.abs(la[0, 30:] - lb[0, 30:]).max()) > 0.0


@pytest.mark.parametrize("dtype,tolerance", [
    # float32 compute: rounding only
    ("float32", 5e-6),
    # the tiny preset as served, bfloat16 compute: 8 bits of mantissa on
    # logits whose spread is 0.17 gives errors of a few 1e-3; a compute
    # type with fewer bits (or a lost cache row) is tens of times that
    ("bfloat16", 2e-2),
])
def test_prefill_then_decode_through_the_paged_cache_matches_the_full_forward(
    tiny, dtype, tolerance
):
    from benchmark.families import gpt2 as family

    cfg, params = tiny[dtype]
    got = family.compare_serve(cfg, MODEL, params, seed=5, prompt_lens=[70, 33], steps=12)
    assert got["prefill_max_abs"] < tolerance
    assert got["decode_max_abs"] < tolerance
    assert got["reference_logit_std"] > 0.05  # the logits are not all alike
    if dtype == "bfloat16":
        assert got["decode_max_abs"] > 1e-5  # and bfloat16 really was computed


def test_a_wrong_cache_row_is_caught(tiny):
    """The comparison has teeth: decoding with the two rows' page tables
    swapped reads another sequence's K/V and misses by far more than the
    tolerance."""
    import numpy as np

    from benchmark.families import gpt2 as family
    from ray_tpu.models import gpt2_decode as dec

    cfg, params = tiny["float32"]
    real = dec._decode_paged_impl

    def swapped(c, p, last, lens, ck, cv, tables):
        return real(c, p, last, lens, ck, cv, tables[::-1])

    dec._decode_paged_impl = swapped
    try:
        got = family.compare_serve(cfg, MODEL, params, seed=5, prompt_lens=[70, 33], steps=4)
    finally:
        dec._decode_paged_impl = real
    assert got["decode_max_abs"] > 100 * 5e-6
    assert np.isfinite(got["decode_max_abs"])


@pytest.fixture(scope="module")
def tiny_step(tiny):
    """gpt2-tiny's train step as the training generator builds it: 8 rows
    of two seeded sequences, four of each."""
    import jax
    import numpy as np
    import optax

    from benchmark.reference import check
    from ray_tpu.models import gpt2

    cfg, _ = tiny["bfloat16"]
    cfg = dataclasses.replace(cfg, loss_impl="fused", loss_chunk=32, scan_unroll=2)
    opt = optax.adamw(3e-4, b1=check.ADAM_B1, weight_decay=0.01)
    sequences = np.random.default_rng([5, 7]).integers(0, 256, (2, 129), dtype=np.int32)
    return {"cfg": cfg, "opt": opt, "sequences": sequences,
            "tokens": jax.numpy.asarray(np.repeat(sequences, 4, axis=0)),
            "step": jax.jit(gpt2.make_train_step(cfg, opt))}


def compare(tiny_step, step):
    import jax

    from benchmark.reference import check
    from ray_tpu.models import gpt2

    params = gpt2.init(jax.random.PRNGKey(3), tiny_step["cfg"])
    got, _, _ = check.compare_step(
        step, params, tiny_step["opt"].init(params), tiny_step["tokens"],
        tiny_step["sequences"], MODEL, tiny_step["opt"],
    )
    return got


# bfloat16 compute against float32 at this size, measured here on the CPU:
# loss 2e-6, gradient 1.5e-2 of its norm, loss after the step 1.8e-4 (it
# falls by 0.067)
TINY_TOLERANCE = {"loss": 5e-3, "grad": 8e-2, "loss_after": 2e-3}


def test_one_train_step_against_the_reference(tiny_step):
    from benchmark.reference import check

    got = compare(tiny_step, tiny_step["step"])
    assert check.step_problems(got, TINY_TOLERANCE) == []
    assert abs(got["loss_reference"] - 5.545) < 0.1  # ln 256
    assert got["grad_norm_reference"] > 0 and got["grad_rel_error"] > 1e-6
    # one AdamW step moves every weight against its gradient: the loss
    # on the same sequences falls, by far more than the tolerance
    assert got["loss_reference"] - got["loss_after_reference"] > 10 * TINY_TOLERANCE["loss_after"]


def test_the_float8_control_and_a_state_left_unchanged_read_over_the_program(tiny_step):
    """The readings a training configuration's ``reference_tolerance`` is set
    between, by the tool that reads them on the chip
    (``benchmark/tools/step_control.py``), at the tiny preset: the control
    (the plain reference with its matrices rounded to float8_e4m3fn in the
    program's place) reads its gradient several times farther from the
    reference than the program does and fails the tolerance a size of this
    kind is held to on the chip; a step that hands its state back unchanged
    reports the right loss, a gradient that is all missing and the loss's
    whole fall."""
    import jax

    from benchmark.reference import check
    from benchmark.tools import step_control

    opt = tiny_step["opt"]
    program = step_control.gaps(compare(tiny_step, tiny_step["step"]))
    control = step_control.gaps(compare(tiny_step, step_control.float8_step(MODEL, opt, 4)))
    unchanged = compare(tiny_step, step_control.unchanged(jax.jit(tiny_step["step"])))
    assert check.step_problems(unchanged, TINY_TOLERANCE)
    unchanged = step_control.gaps(unchanged)
    assert program["grad_rel_gap"] < 0.02 and control["grad_rel_gap"] > 3 * program["grad_rel_gap"]
    assert control["grad_rel_gap"] > 0.04  # gpt2-small-train's limit is 0.06 for 0.0125 read
    # (the loss after the step is not the control's to fail: at gpt2-small's size it
    # read 5.0e-4 on one seed of three where the program reads up to 2.26e-3; PERF.md)
    assert unchanged["grad_rel_gap"] == pytest.approx(1.0)
    assert unchanged["loss_gap"] == pytest.approx(program["loss_gap"])
    assert unchanged["loss_after_gap"] > 10 * TINY_TOLERANCE["loss_after"]


@pytest.mark.parametrize("fault", ["mlp_gradient_dropped", "gradient_not_averaged",
                                   "update_not_applied_to_blocks"])
def test_a_wrong_train_step_is_caught(tiny_step, fault):
    """The comparison has teeth: each of these keeps the loss before the
    step, and a loss near ln V ever after, and misses the reference."""
    import jax

    from benchmark.reference import check
    from ray_tpu.models import gpt2

    cfg, opt = tiny_step["cfg"], tiny_step["opt"]

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(gpt2.loss_fn)(params, tokens, cfg)
        if fault == "mlp_gradient_dropped":
            grads["blocks"]["mlp"] = jax.tree.map(jax.numpy.zeros_like, grads["blocks"]["mlp"])
        if fault == "gradient_not_averaged":  # a sum over four chips where a mean belongs
            grads = jax.tree.map(lambda g: 4.0 * g, grads)
        updates, opt_state = opt.update(grads, opt_state, params)
        new = jax.tree.map(lambda p, u: (p + u).astype(p.dtype), params, updates)
        if fault == "update_not_applied_to_blocks":
            new["blocks"] = params["blocks"]
        return new, opt_state, loss

    got = compare(tiny_step, jax.jit(step))
    problems = check.step_problems(got, TINY_TOLERANCE)
    assert problems and not any("before the step" in p for p in problems)
