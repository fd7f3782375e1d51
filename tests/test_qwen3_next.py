"""Qwen3-Next (``model_type: qwen3_next``) through its cache of two kinds
(models/qwen3_next.py: a delta-rule state a decode row, two layers' pages)
against the plain reference's full forward pass
(benchmark/reference/qwen3_next_ref.py), at the tiny preset on the CPU,
logits compared.

The comparison is the benchmark's own (``families/qwen3_next.compare_serve``:
chunked prefill into a row's states and pages, then decode side by side).
The reference runs the recurrence position by position from a zero state, the
program's prefill the chunked form from what the row's earlier chunks left
and its decode one step a call. In float32 it is tight, and every way of
getting the model wrong that is listed below breaks it; in bfloat16, as
served, it is held to the tiny twin's tolerance.
"""

import dataclasses
import json
import math
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = "qwen3-next-tiny"
TIGHT = 2e-3  # float32 program against float32 reference, logits' spread ~1
SEEN = 0.02   # what a model computed wrongly must exceed


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    from benchmark.families import qwen3_next as family
    from ray_tpu.models import qwen3_next

    cfg = dataclasses.replace(qwen3_next.CONFIGS[MODEL], dtype=jnp.float32)
    return cfg, qwen3_next.load_serving_params(cfg), family.program_sizes(MODEL)


def compare(tiny, **kw):
    from benchmark.families import qwen3_next as family

    cfg, params, model = tiny
    kw = {"prompt_lens": [70, 33, 5], "steps": 24, "page_tokens": 16, "chunk": 32, **kw}
    return family.compare_serve(cfg, model, params, 11, **kw)


def worst(out):
    """The larger phase's largest gap, the tied tokens among them: in
    float32 nothing is ranked the other way."""
    return max(out["prefill_max_abs"], out["decode_max_abs"], out["tied_worst"])


def test_prefill_in_chunks_then_decode_through_the_cache_is_the_full_forward(tiny):
    """Rows of unequal length side by side; row 0 is prefilled in three
    chunks of 32 (positions at start > 0 carry a state, convolution inputs
    and pages) and grows to 94 positions; row 2 is shorter than the
    convolution is wide by one. Every token agrees, the tied ones too."""
    out = compare(tiny)
    assert out["reference_logit_std"] > 0.3
    assert 0 < out["prefill_max_abs"] < TIGHT and 0 < out["decode_max_abs"] < TIGHT
    assert out["tied_worst"] < TIGHT
    assert (out["rows"], out["decode_steps"], out["tokens_compared"]) == (3, 24, 6 + 3 * 24)
    assert out["prefill_judged"] + out["decode_judged"] + out["tokens_tied"] == 78
    assert out["prefill_judged"] >= 1 and out["decode_judged"] >= 20


@pytest.mark.parametrize("chunk", [8, 20, 64])
def test_a_chunk_of_any_width_carries_the_state_over_its_boundary(tiny, chunk):
    """At 8 a prompt of 61 is eight calls, at 20 four whose last is one
    position, at 64 one call of one block."""
    out = compare(tiny, prompt_lens=[61, 17], steps=6, chunk=chunk)
    assert 0 < worst(out) < TIGHT


def test_two_rows_in_one_prefill_call_are_the_rows_alone(tiny):
    """A call of two rows at different starts, one padded more than the
    other, gives each row the logits and the state its own call gives."""
    import jax.numpy as jnp

    from ray_tpu.models import qwen3_next as dec

    cfg, params, _ = tiny
    rng = np.random.default_rng(4)
    seqs = [rng.integers(0, 256, n, dtype=np.int32) for n in (40, 23)]
    tables = np.zeros((2, 16), np.int32)
    tables[0, :4], tables[1, :4] = [1, 2, 3, 4], [5, 6, 7, 8]

    def call(k, v, rows, start, n):
        tok = np.zeros((len(rows), 32), np.int32)
        for i, r in enumerate(rows):
            tok[i, :n[i]] = seqs[r][start[i]:start[i] + n[i]]
        return dec.prefill_paged(cfg, params, jnp.asarray(tok), jnp.asarray(start),
                                 jnp.asarray(n), k, v, jnp.asarray(tables[rows]),
                                 jnp.asarray(rows))

    def first_chunk():  # row 0's, alone; a call donates its caches, so made twice
        return call(*dec.init_paged_cache(cfg, 9, 16, 2), [0], [0], [32])[1:]

    both, k2, v2 = call(*first_chunk(), [0, 1], [32, 0], [8, 23])  # its tail beside row 1's
    tail, k1, v1 = call(*first_chunk(), [0], [32], [8])
    _, k1, v1 = call(k1, v1, [1], [0], [23])
    alone, _, _ = call(*dec.init_paged_cache(cfg, 9, 16, 2), [1], [0], [23])
    np.testing.assert_allclose(np.asarray(both[0]), np.asarray(tail[0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(both[1]), np.asarray(alone[0]), atol=1e-5)
    for a, b in zip(k2.layers + v2.layers, k1.layers + v1.layers):
        np.testing.assert_allclose(np.asarray(a[:2]), np.asarray(b[:2]), atol=1e-5)


@pytest.mark.parametrize("wrong", ["no_attn_gate", "plain_norm_scale", "full_rotary",
                                   "sigmoid_scores", "no_shared_gate", "no_decay",
                                   "no_delta", "no_l2norm"])
def test_a_model_computed_wrongly_fails_the_comparison(tiny, wrong):
    """The attention's output without its gate; the norms' scales taken as
    ``w`` and not ``1 + w``; every dimension of a head rotated; sigmoid
    scores in the router; the shared expert without its gate; a state that
    never decays; the plain linear-attention update without the delta; q and
    k not normalised: each is another model, and the comparison says so."""
    from benchmark.reference import qwen3_next_ref

    assert wrong in qwen3_next_ref.WRONG
    out = compare(tiny, prompt_lens=[40, 9], steps=4, wrong=[wrong])
    assert worst(out) > SEEN


def test_a_state_that_is_not_reset_at_start_zero_fails_the_comparison(tiny, monkeypatch):
    """The comparison's rows start with dirty states (a retired sequence's,
    in the engine). A prefill that takes them as they are, whatever
    ``start``, is caught."""
    from ray_tpu.ops import gated_delta

    sound = gated_delta.chunk_scan
    monkeypatch.setattr(
        gated_delta, "chunk_scan",
        lambda params, c, a, b, state, start, length: sound(
            params, c, a, b, state, start + 1, length))
    cfg, params, model = tiny
    # a config of its own, so that the program is traced with the fault in
    cfg = dataclasses.replace(cfg, rms_norm_eps=1.000001e-6)
    out = compare((cfg, params, model), prompt_lens=[40, 9], steps=4)
    assert worst(out) > SEEN


def test_the_state_in_bfloat16_is_the_same_programs_on_another_cache(tiny):
    """The check's control: it runs, and moves the logits."""
    sound = compare(tiny, prompt_lens=[40, 9], steps=4)
    control = compare(tiny, prompt_lens=[40, 9], steps=4, state_control=True)
    assert worst(control) > 2 * worst(sound)


def test_every_stored_state_is_held_to_the_references_and_the_control_fails_by_them(tiny):
    """Every value head's state of every linear layer of every row, behind
    the prompt and behind the last decode step, against the state the
    reference's position-by-position recurrence holds there. The state's type
    moves the states a thousand times further than float32's rounding does,
    and ``compare_serve`` hands the harness the larger of the logits' gap and
    the states' in the tolerance's unit."""
    from benchmark.families import qwen3_next as family

    cfg, params, model = tiny
    tokens, states = family.token_gaps(cfg, model, params, 11, [70, 33, 5], 24,
                                       page_tokens=16, chunk=32)
    assert len(tokens) == 78
    assert sorted((s["phase"], s["steps"], s["row"], s["layer"]) for s in states) == sorted(
        (phase, steps, r, l) for phase, steps in (("prefill", 0), ("decode", 24))
        for r in range(3) for l in (0, 1, 2))
    assert all(len(s["gaps"]) == cfg.linear_num_value_heads for s in states)
    sound = compare(tiny)
    assert 0 < sound["prefill_state_gap"] < 1e-4 and 0 < sound["decode_state_gap"] < 1e-4
    control = compare(tiny, state_control=True)
    assert control["decode_state_gap"] > 1000 * sound["decode_state_gap"]
    unit = family.LOGIT_LIMIT / family.STATE_LIMIT
    for out in (sound, control):
        for phase in ("prefill", "decode"):
            assert out[f"{phase}_max_abs"] == pytest.approx(
                max(out[f"{phase}_logit_gap"], out[f"{phase}_state_gap"] * unit))
    assert control["decode_max_abs"] == pytest.approx(control["decode_state_gap"] * unit)
    assert control["state_gap_behind_experts"] > 1000 * sound["state_gap_behind_experts"] > 0
    # a state behind more steps than were run is not compared, and not missed
    _, some = family.token_gaps(cfg, model, params, 11, [40, 9], 6, page_tokens=16,
                                chunk=32, state_steps=(0, 2, 6))
    assert sorted({s["steps"] for s in some}) == [0, 2, 6]


def test_the_references_states_are_causal(tiny):
    """The state the reference holds behind position p of a sequence is the
    one it ends a sequence of p + 1 tokens with."""
    from benchmark.reference import qwen3_next_ref

    cfg, params, model = tiny
    tokens = np.random.default_rng(3).integers(0, cfg.vocab_size, 40, dtype=np.int32)
    _, whole = qwen3_next_ref.forward(params, tokens, model, states_at=[11, 39])
    _, cut = qwen3_next_ref.forward(params, tokens[:12], model, states_at=[11])
    assert len(whole) == 3 and whole[0].shape == (2, 4, 8, 8)
    for a, b in zip(whole, cut):
        np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]), rtol=1e-5, atol=1e-7)
        assert not np.allclose(np.asarray(a[0]), np.asarray(a[1]))


def test_as_served_in_bfloat16_within_the_tiny_twins_tolerance():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from benchmark.families import qwen3_next as family

    with open(os.path.join(ROOT, "tests/bench/configs/qwen3next-tiny-serve.json")) as f:
        cfg = json.load(f)
    mcfg, params = family.serve_params(cfg["model_id"])
    out = family.compare_serve(mcfg, cfg["model"], params, 11,
                               prompt_lens=cfg["check"]["prompt_lens"],
                               steps=cfg["check"]["decode_steps"], page_tokens=16, chunk=32)
    assert out["prefill_judged"] >= 1 and out["decode_judged"] >= 10
    assert 0 < max(out["prefill_max_abs"], out["decode_max_abs"]) <= cfg["check"][
        "logit_tolerance"]


def test_params_count_is_the_stored_trees_size_at_the_published_widths():
    """3,667,251,328 parameters, by ``jax.eval_shape``: no weights made."""
    import jax

    from benchmark.families import qwen3_next as family
    from ray_tpu.models import qwen3_next

    stored = {}
    for model_id in ("qwen3-next-80b-a3b", MODEL):
        cfg = qwen3_next.CONFIGS[model_id]
        tree = jax.eval_shape(lambda: qwen3_next.init(jax.random.PRNGKey(0), cfg))
        stored[model_id] = sum(math.prod(a.shape) for a in jax.tree.leaves(tree))
        assert family.params_count(family.program_sizes(model_id)) == stored[model_id]
    assert stored["qwen3-next-80b-a3b"] == 3_667_251_328


def test_the_layers_and_the_cache_are_the_published_ones():
    import jax
    import jax.numpy as jnp

    from benchmark.families import qwen3_next as family
    from ray_tpu.models import qwen3_next

    cfg = qwen3_next.CONFIGS["qwen3-next-80b-a3b"]
    assert [cfg.full(l) for l in range(cfg.n_layer)] == [False, False, False, True] * 2
    assert (cfg.rotary_dim, cfg.conv_channels, cfg.value_width) == (64, 8192, 4096)
    assert (cfg.num_experts, cfg.router_experts, cfg.first_expert) == (128, 512, 0)
    spec = qwen3_next.cache_spec(cfg)
    assert [s["kind"] for s in spec] == ["state", "state", "state", "full"] * 2
    assert spec[0]["k_row"] == (32, 128, 128) and spec[0]["k_dtype"] == jnp.float32
    assert spec[0]["v_row"] == (3 * 8192,) and spec[0]["v_dtype"] == jnp.bfloat16
    assert (spec[3]["kv_heads"], spec[3]["k_size"], spec[3]["v_size"]) == (2, 256, 256)
    tiny = qwen3_next.CONFIGS[MODEL]
    k, v = qwen3_next.init_paged_cache(tiny, 5, 16, 3)
    held = qwen3_next.cache_layout(tiny, k, v)
    assert [s[0] for s in held["shape"]] == ["state", "state", "state", "full"]
    assert list(held["shape"][0][1:]) == [3, 4, 8, 8]
    assert held["bytes"]["state"] > 0 and held["bytes"]["full"] > 0
    assert held["bytes"]["window"] == 0
    assert not qwen3_next.PREFIX_CACHE and qwen3_next.PREFILL_ROWS == (1, 2)
    assert qwen3_next.STEP_COUNTERS[-2:] == ("attn_context_tokens", "attn_loop_tokens")
    # what a decode row keeps in the linear layers, at the published widths (no array made)
    k, v = jax.eval_shape(lambda: qwen3_next.init_paged_cache(cfg, 2, 64, 128))
    states = [a for s, pair in zip(spec, zip(k.layers, v.layers)) if s["kind"] == "state"
              for a in pair]
    assert sum(math.prod(a.shape) * a.dtype.itemsize for a in states) == 128 * 12_877_824
    assert family.state_bytes(family.program_sizes("qwen3-next-80b-a3b")) * 6 == 12_877_824


def test_a_decode_step_counts_its_live_rows_and_leaves_the_others_states(tiny):
    """Three rows, the middle one nobody's: the step counts the two live
    rows' positions and experts, and the third's state and convolution
    inputs are the arrays it held, bit for bit."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import qwen3_next as dec

    cfg, params, _ = tiny
    k, v = dec.init_paged_cache(cfg, 7, 16, 3)
    k = type(k)(tuple(a + 1 if a.ndim == 4 else a for a in k.layers), k.page_tokens)
    v = type(v)(tuple(a + 1 if a.ndim == 2 else a for a in v.layers), v.page_tokens)
    before = [np.asarray(a).copy() for a in k.layers + v.layers]
    tables = jnp.asarray([[1, 2] + [0] * 14, [0] * 16, [3, 4] + [0] * 14], jnp.int32)
    step = jax.jit(dec._decode_paged_impl, static_argnums=(0,))
    logits, k, v, counted = step(cfg, params, jnp.asarray([5, 0, 9]), jnp.asarray([3, 0, 17]),
                                 k, v, tables)
    named = dict(zip(dec.STEP_COUNTERS, np.asarray(counted)))
    assert named["attn_context_tokens"] == 4 + 18 and named["attn_loop_tokens"] == 16 + 32
    assert named["moe_expert_steps"] == 4 * cfg.n_layer
    assert np.isfinite(np.asarray(logits)).all()
    for l, (was, now) in enumerate(zip(before, k.layers + v.layers)):
        if cfg.full(l % cfg.n_layer):
            continue
        now = np.asarray(now)
        assert np.array_equal(was[1].view(np.uint32), now[1].view(np.uint32)), l
        assert not np.array_equal(was[0], now[0]), l
