"""``model_type: deepseek_v3`` through the paged latent cache
(models/deepseek_v3.py) against the plain reference's full forward pass
(benchmark/reference/deepseek_v3_ref.py), at the ``kanana-2-tiny`` preset
on the CPU, logits compared.

The comparison is the benchmark's own (``families/deepseek_v3.compare_serve``:
chunked prefill, expanded, into a row's pages, then absorbed decode side by
side). In float32 it is tight; as served, in bfloat16, it is held to the
tiny twin's tolerance, which every way of getting the model wrong that is
listed below breaks.
"""

import dataclasses
import json
import os

import numpy as np
import pytest
from test_mimo_v2 import edit_layers, rows_of_every_length

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TIGHT = 2e-3  # float32 program against float32 reference, logits' spread ~1


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    from benchmark.families import deepseek_v3 as family
    from ray_tpu.models import deepseek_v3

    cfg = dataclasses.replace(deepseek_v3.CONFIGS["kanana-2-tiny"], dtype=jnp.float32)
    return cfg, deepseek_v3.load_serving_params(cfg), family.program_sizes("kanana-2-tiny")


@pytest.fixture(scope="module")
def twin():
    """The tiny twin as served: bfloat16, the engine's stored weights, and
    its own check (``tests/bench/configs/kanana-2-tiny-serve.json``)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from benchmark.families import deepseek_v3 as family

    with open(os.path.join(ROOT, "tests/bench/configs/kanana-2-tiny-serve.json")) as f:
        cfg = json.load(f)
    return (cfg, *family.serve_params(cfg["model_id"]))


def test_prefill_in_chunks_then_decode_through_the_latent_cache_is_the_full_forward(tiny):
    """Rows of unequal length side by side; row 0 is prefilled in three
    chunks (the expanded path over a prefix, start > 0) and grows over six
    pages of 16 and past one turn of page-table columns; row 2 is shorter
    than a page."""
    from benchmark.families import deepseek_v3 as family

    cfg, params, model = tiny
    out = family.compare_serve(cfg, model, params, 11, prompt_lens=[70, 33, 5], steps=24,
                               page_tokens=16, chunk=32)
    assert out["reference_logit_std"] > 0.3
    assert 0 < out["prefill_max_abs"] < TIGHT and 0 < out["decode_max_abs"] < TIGHT
    # float32 against float32 ranks no two experts the other way round
    assert out["tied_worst"] < TIGHT
    assert (out["rows"], out["decode_steps"], out["tokens_compared"]) == (3, 24, 6 + 3 * 24)


def _a_filled_cache(cfg, params, lens, B=16):
    """Rows of ``lens`` tokens prefilled into their own pages: (cache,
    none, tables, the rows' next tokens)."""
    import jax.numpy as jnp

    from ray_tpu.models import deepseek_v3 as dec

    rng = np.random.default_rng(5)
    need = [-(-(n + 8) // B) for n in lens]
    cache, none = dec.init_paged_cache(cfg, 1 + sum(need), B, len(lens))
    tables = np.zeros((len(lens), cfg.n_positions // B), np.int32)
    nxt = 1
    for r, (n, pages) in enumerate(zip(lens, need)):
        tables[r, :pages] = np.arange(nxt, nxt + pages)
        nxt += pages
        tok = np.zeros((1, 64), np.int32)
        tok[0, :n] = rng.integers(0, cfg.vocab_size, n)
        _, cache, none = dec.prefill_paged(cfg, params, jnp.asarray(tok), jnp.int32(0),
                                           jnp.int32(n), cache, none, jnp.asarray(tables[r]))
    return cache, none, jnp.asarray(tables), jnp.asarray(rng.integers(0, cfg.vocab_size, len(lens)))


def test_absorbed_decode_is_expanded_attention_on_the_same_cache(tiny, monkeypatch):
    """One decode step, twice, on the same latent rows: with W_kb absorbed
    into the query and W_vb applied to the weighted latent (what the
    engine runs), and with K and V a head expanded from the same pages
    (prefill's attention, a row at a time). The same logits, the same
    rows written."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import deepseek_v3 as dec
    from ray_tpu.ops import page_loops

    cfg, params, _ = tiny
    lens = [50, 17, 3]
    cache, none, tables, last = _a_filled_cache(cfg, params, lens)
    args = (cfg, params, last, jnp.asarray(lens, jnp.int32), cache, none, tables)
    absorbed = jax.jit(dec._decode_paged_impl, static_argnums=(0,))(*args)

    def a_row_at_a_time(cfg, attn, q_nope, q_rope, pool, tables, pos):
        """Decode's attention the expanded way: prefill's, each row its own
        sequence of one query."""
        return jnp.stack([
            dec._expanded_attend(cfg, attn, q_nope[r:r + 1], q_rope[r:r + 1], pool,
                                 tables[r], pos[r:r + 1])[0]
            for r in range(q_nope.shape[0])])

    monkeypatch.setattr(dec, "_absorbed_attend", a_row_at_a_time)
    expanded = jax.jit(lambda *a: dec._decode_paged_impl(*a), static_argnums=(0,))(*args)
    assert float(jnp.abs(absorbed[0] - expanded[0]).max()) < 1e-4
    assert float(jnp.std(absorbed[0])) > 0.3
    # the first layer's rows come before any attention; the next layers'
    # behind one that rounds differently
    assert np.array_equal(np.asarray(absorbed[1].layers[0]), np.asarray(expanded[1].layers[0]))
    for a, b in zip(absorbed[1].layers, expanded[1].layers):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    # the row lies as [c_kv | k_rope | zeros]: the stored width is whole lanes
    assert cache.layers[0].shape[-1] == cfg.stored_width == 128
    assert not np.asarray(absorbed[1].layers[0][..., cfg.latent_width:]).any()
    # what the step counted last: the live rows' contexts, the new position
    # among them, and the one turn the kernel read for each of three rows
    turn = 16 * page_loops.DECODE_PAGES
    assert list(map(int, absorbed[3][-2:])) == [sum(n + 1 for n in lens), 3 * turn]


@pytest.mark.parametrize("rows", [3, 16, 32, 40])
def test_rows_of_every_length_attend_absorbed_as_in_the_expanded_form(tiny, rows):
    """The absorbed attention through the kernel that walks each row's own
    pages (``ops/paged_latent_attention.py``) against the expanded form a
    row at a time, on rows of every length in shuffled order, their pages
    scattered over the pool; and the rows permuted give the same rows
    permuted, to the bit: a row's result does not know its neighbours."""
    import jax.numpy as jnp

    from ray_tpu.models import deepseek_v3 as dec

    cfg, params, _ = tiny
    attn = params["layers"][1]["attn"]
    rng = np.random.default_rng(0)
    B, H = 16, cfg.num_attention_heads
    pos, tables, N = rows_of_every_length(rng, rows, B, cfg.n_positions // B)
    draw = lambda *shape: jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
    q_nope, q_rope = draw(rows, H, cfg.qk_nope_head_dim), draw(rows, H, cfg.qk_rope_head_dim)
    pool = draw(N, B, cfg.stored_width).at[..., cfg.latent_width:].set(0.0)
    pos, tables = jnp.asarray(pos, jnp.int32), jnp.asarray(tables)
    got = dec._absorbed_attend(cfg, attn, q_nope, q_rope, pool, tables, pos)
    assert float(jnp.abs(got).max()) > 0.1
    expanded = jnp.stack([
        dec._expanded_attend(cfg, attn, q_nope[r:r + 1], q_rope[r:r + 1], pool,
                             tables[r], pos[r:r + 1])[0] for r in range(rows)])
    assert float(jnp.abs(got - expanded).max()) < 1e-4
    perm = np.random.default_rng(rows).permutation(rows)
    moved = dec._absorbed_attend(cfg, attn, q_nope[perm], q_rope[perm], pool, tables[perm],
                                 pos[perm])
    assert np.array_equal(np.asarray(moved), np.asarray(got[perm]))


def test_the_step_counts_what_its_kernel_read(tiny):
    """``attn_loop_tokens`` is what the attention's kernel reads for the
    live rows: each row's own turns x the positions a turn, written out by
    hand for 32 rows; ``mla_context_tokens`` is what it was: the live rows'
    positions, the new one among them."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import deepseek_v3 as dec
    from ray_tpu.ops import page_loops

    cfg, params, _ = tiny
    B, S = 16, 32
    turn = B * page_loops.DECODE_PAGES
    # eight rows nobody holds, which count nowhere; rows inside their first
    # turn, its last position among them; rows whose new position opens the
    # second; and rows up to 1,400
    short = [1, 5, 9, 20, 33, 40, turn - 2, turn - 1]
    long = [turn + 1, 300, 500, 699, 700, 1000, 1399, 1400]
    lens = np.asarray([0] * 8 + short + [turn] * 8 + long)
    lens = lens[np.random.default_rng(2).permutation(S)]
    tables = np.zeros((S, cfg.n_positions // B), np.int32)
    at = 1
    for r, n in enumerate(lens):
        need = n // B + 1 if n else 0
        tables[r, :need] = np.arange(at, at + need)
        at += need
    cache, none = dec.init_paged_cache(cfg, at, B, S)
    out = jax.jit(dec._decode_paged_impl, static_argnums=(0,))(
        cfg, params, jnp.zeros((S,), jnp.int32), jnp.asarray(lens, jnp.int32), cache, none,
        jnp.asarray(tables))
    by_name = dict(zip(dec.STEP_COUNTERS, map(int, out[3])))
    assert by_name["attn_loop_tokens"] == turn * (8 * 1 + 8 * 2 + sum(n // turn + 1 for n in long))
    assert by_name["mla_context_tokens"] == int(sum(n + 1 for n in lens if n))
    # four loops by length (PR 49) covered every row to its group's longest
    assert by_name["attn_loop_tokens"] < 8 * turn * (1 + 1 + 2 + 1400 // turn + 1)
    # and no row reads a whole turn behind its own length
    assert by_name["attn_loop_tokens"] < by_name["mla_context_tokens"] + 24 * turn


def test_a_chunk_of_k_steps_is_k_single_steps(tiny):
    """``decode_multi_paged`` against ``decode_paged_and_sample`` step by
    step: the same tokens, lengths and counts, a row of no length staying
    nobody's."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import deepseek_v3 as dec

    cfg, params, _ = tiny
    S, B = 3, 16
    tables = np.zeros((S, cfg.n_positions // B), np.int32)
    tables[0, :3], tables[2, :3] = [1, 2, 3], [4, 5, 6]
    state = (jnp.asarray([7, 0, 9]), jnp.asarray([4, 0, 20]))
    common = (jnp.asarray(tables), jnp.ones((S,)), jnp.ones((S,), bool), jax.random.PRNGKey(0))

    toks, last, lens, _, _, counted = dec.decode_multi_paged(
        cfg, params, *state, *dec.init_paged_cache(cfg, 7, B, S), *common, 5, 0)
    cache, none = dec.init_paged_cache(cfg, 7, B, S)
    singles, total = [], 0
    cur, cur_lens = state
    for i in range(5):
        cur, cur_lens, cache, none, c = dec.decode_paged_and_sample(
            cfg, params, cur, cur_lens, cache, none, *common, i)
        singles.append(np.asarray(cur))
        total = total + np.asarray(c)
    assert np.array_equal(np.asarray(toks)[:5, [0, 2]], np.stack(singles)[:, [0, 2]])
    assert list(np.asarray(lens)) == list(np.asarray(cur_lens)) == [9, 0, 25]
    assert list(np.asarray(counted)) == list(total)
    by_name = dict(zip(dec.STEP_COUNTERS, map(int, counted)))
    # 2 expert layers x 5 steps x 16 experts; 2 live rows x top 3; the empty row nowhere
    assert by_name["moe_expert_steps"] == 2 * 5 * 16
    assert by_name["moe_assignments"] == 2 * 5 * 2 * 3
    # rows at 4 and 20 positions, five steps each, the new position among them
    assert by_name["mla_context_tokens"] == sum(5 + i + 21 + i for i in range(5))


# -- planted faults ---------------------------------------------------------


def roll_the_heads_of_q(model):
    """Every head's q columns moved on by the rotary width: the rotation
    then lands on another 64 of the head's dimensions (8 at the tiny
    preset)."""
    import jax.numpy as jnp

    heads, width = model["num_attention_heads"], model["qk_head_dim"]

    def roll(wq):
        per_head = wq.reshape(wq.shape[0], heads, width)
        return jnp.roll(per_head, model["qk_rope_head_dim"], axis=-1).reshape(wq.shape)

    return roll


def no_latent_norm(ref, model):
    """``rmsnorm`` leaves a latent (what is ``kv_lora_rank`` wide) as it is."""
    plain = ref.rmsnorm
    return {"rmsnorm": lambda x, scale, eps: x if scale.shape == (model["kv_lora_rank"],)
            else plain(x, scale, eps)}


def bias_in_the_gate(ref, model):
    """The gates are taken from score + bias, where the bias may only move
    the choice."""
    import jax.numpy as jnp

    plain = ref.routing

    def routing(x, moe, model):
        chosen, _, biased = plain(x, moe, model)
        picked = jnp.take_along_axis(biased, chosen, axis=1)
        return chosen, picked / picked.sum(-1, keepdims=True), biased

    return {"routing": routing}


# what the reference is given instead of the model: (parameters, model,
# functions of the reference to replace). Each must move the logits past
# the tolerance, or the check could not tell the program apart from a
# program that computes this
FAULTS = {
    "no_latent_norm": lambda p, m, ref: (p, m, no_latent_norm(ref, m)),
    "rotary_on_the_wrong_dimensions": lambda p, m, ref: (
        edit_layers(p, ("attn", "wq"), roll_the_heads_of_q(m)), m, {}),
    "softmax_scaled_by_the_nope_width": lambda p, m, ref: (
        edit_layers(p, ("attn", "wq"),
                    lambda w: w * (m["qk_head_dim"] / m["qk_nope_head_dim"]) ** 0.5), m, {}),
    "no_routed_scaling_factor": lambda p, m, ref: (p, {**m, "routed_scaling_factor": 1.0}, {}),
    "gates_not_renormalised": lambda p, m, ref: (p, {**m, "norm_topk_prob": False}, {}),
    "bias_in_the_gate": lambda p, m, ref: (p, m, bias_in_the_gate(ref, m)),
    "one_expert_fewer": lambda p, m, ref: (
        p, {**m, "num_experts_per_tok": m["num_experts_per_tok"] - 1}, {}),
    "shared_expert_dropped": lambda p, m, ref: (
        edit_layers(p, ("shared", "down"), lambda w: 0 * w), m, {}),
    "shared_expert_scaled_like_a_routed_one": lambda p, m, ref: (
        edit_layers(p, ("shared", "down"), lambda w: w * m["routed_scaling_factor"]), m, {}),
}


def give_the_reference(fault, monkeypatch, only_tokens=None):
    """From here on the reference computes the model with ``fault`` (for
    sequences of ``only_tokens`` tokens alone, where that is given)."""
    from benchmark.reference import deepseek_v3_ref as ref

    forward = ref.forward

    def wrong(params, tokens, model, margins=False, positions=None):
        if only_tokens is not None and tokens.shape[0] != only_tokens:
            return forward(params, tokens, model, margins, positions)
        params, model, replaced = FAULTS[fault](params, dict(model), ref)
        with monkeypatch.context() as m:
            for name, fn in replaced.items():
                m.setattr(ref, name, fn)
            # the reference's jitted pieces were traced with its own functions
            ref.attention.clear_cache()
            try:
                return forward(params, tokens, model, margins, positions)
            finally:
                ref.attention.clear_cache()

    monkeypatch.setattr(ref, "forward", wrong)


SHORT = {"prompt_lens": [70, 30, 20], "steps": 12, "page_tokens": 16, "chunk": 32}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_planted_fault_breaks_the_check(tiny, fault, monkeypatch):
    """Float32 program against the float32 reference of the wrong model:
    ten times what the right one reads, or more."""
    from benchmark.families import deepseek_v3 as family

    cfg, params, model = tiny
    give_the_reference(fault, monkeypatch)
    out = family.compare_serve(cfg, model, params, 7, **SHORT)
    assert max(out["prefill_max_abs"], out["decode_max_abs"]) > 10 * TIGHT, out


# the bias is drawn small beside the scores (0.02 to ~0.7: PERF.md, PR 46),
# so taken into the gate it moves a gate by a thirtieth and the logits by
# 0.08, which bfloat16's tolerance hides and float32's does not
UNDER_BFLOAT16 = {"bias_in_the_gate"}


@pytest.mark.parametrize("fault", sorted(set(FAULTS) - UNDER_BFLOAT16))
def test_each_planted_fault_breaks_the_tiny_twins_own_tolerance(twin, fault, monkeypatch):
    """As served, in bfloat16: each fault but the smallest moves the
    largest gap of the judged tokens past the configuration's tolerance."""
    from benchmark.families import deepseek_v3 as family

    cfg, mcfg, params = twin
    give_the_reference(fault, monkeypatch)
    out = family.compare_serve(mcfg, cfg["model"], params, 7, **SHORT)
    assert max(out["prefill_max_abs"], out["decode_max_abs"]) > cfg["check"]["logit_tolerance"], out


def test_the_tiny_twin_as_served_keeps_its_tolerance(twin):
    from benchmark.families import deepseek_v3 as family

    cfg, mcfg, params = twin
    out = family.compare_serve(mcfg, cfg["model"], params, 7, **SHORT)
    worst = max(out["prefill_max_abs"], out["decode_max_abs"])
    assert 1e-3 < worst <= cfg["check"]["logit_tolerance"] / 2, out
    assert out["prefill_judged"] > 0 and out["decode_judged"] > out["tokens_compared"] / 2


def test_a_fault_in_one_row_alone_is_caught(twin, monkeypatch):
    """One row of three whose reference drops the gate's scale: a third of
    the tokens move, the median does not see it, and the largest gap of
    that row's judged tokens breaks the tolerance."""
    from benchmark.families import deepseek_v3 as family

    cfg, mcfg, params = twin
    give_the_reference("no_routed_scaling_factor", monkeypatch, only_tokens=20 + 12)
    tokens = family.token_gaps(mcfg, cfg["model"], params, 7, [70, 30, 20], 12,
                               page_tokens=16, chunk=32)
    tol = cfg["check"]["logit_tolerance"]
    gaps = sorted(t["gap"] for t in tokens)
    assert gaps[len(gaps) // 2] < tol / 2
    judged = [t for t in tokens if t["margin"] >= family.TIE]
    assert max(t["gap"] for t in judged if t["row"] == 2) > tol
    assert max(t["gap"] for t in judged if t["row"] != 2) <= tol


def through_the_check(reference, check):
    """``serve_sessions._check`` on a run in which nothing else is amiss:
    what it says of ``reference`` under the configuration's ``check``."""
    from benchmark.generators import serve_sessions

    obs = {"records": [], "problems": [], "notes": [],
           "reference": {**reference, "family": "deepseek_v3"},
           "check": check, "cache_entries": {"t0": 3, "t1": 3, "gained": []}}
    serve_sessions._check(obs, {"text": ["a"]}, {"text": ["a"]})
    return obs


def test_the_float8_control_is_not_correct_by_the_harness_own_comparison(twin):
    """The reading that holds the tolerance from above, through the
    comparison that decides ``correct``: the same programs on weights
    rounded to float8_e4m3fn, the nearest precision below bfloat16,
    against the reference on the weights as they are. Not correct; as
    served, correct."""
    from benchmark.families import deepseek_v3 as family

    cfg, mcfg, params = twin
    served = through_the_check(
        family.compare_serve(mcfg, cfg["model"], params, 3_000_000_019, **SHORT), cfg["check"])
    assert served["problems"] == []
    control = through_the_check(
        family.compare_serve(mcfg, cfg["model"], params, 3_000_000_019, **SHORT,
                             served=family.lower_precision(params)), cfg["check"])
    assert len(control["problems"]) == 1 and "logits differ" in control["problems"][0]
    gaps = control["compared"]
    assert max(gaps["prefill_logit_gap"][0], gaps["decode_logit_gap"][0]) > 3 * cfg["check"]["logit_tolerance"]


# -- a prefill call of several rows (PR 50) ----------------------------------


# B = 16, rows 64 wide: (pages a sequence, prior, rows)
LATENT_PACKS = {
    "rows_of_different_start": (
        [[1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11], [12, 13, 14, 15, 16]],
        [(0, 0, 48), (2, 0, 7)],
        [(0, 48, 50), (1, 0, 9), (2, 7, 64)]),
    "a_row_of_no_length_in_the_middle": (
        [[1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11], [12, 13, 14, 15, 16]],
        [(0, 0, 40)],
        [(0, 40, 33), (1, 0, 0), (2, 0, 64), (3, 0, 0)]),
    # two pages that sequence 0 sealed stand under sequence 1 as well: its
    # row starts behind them, reads them and writes neither
    "a_prefix_hit_under_one_row": (
        [[1, 2, 3, 4, 5], [1, 2, 6, 7, 8, 9]],
        [(0, 0, 40)],
        [(1, 32, 50), (0, 40, 20)]),
    # one tail over a row's width: its second row reads the first one's
    # positions from the pages both write in this call
    "one_tail_as_two_rows": (
        [[1, 2, 3, 4, 5, 6, 7, 8, 9]],
        [(0, 0, 37)],
        [(0, 37, 64), (0, 101, 16)]),
    "two_tails_as_four_rows": (
        [[1, 2, 3, 4, 5, 6, 7, 8, 9], [10, 11, 12, 13, 14, 15, 16, 17]],
        [(0, 0, 37)],
        [(0, 37, 64), (0, 101, 16), (1, 0, 64), (1, 64, 40)]),
}


@pytest.mark.parametrize("pack", sorted(LATENT_PACKS))
def test_a_prefill_call_of_rows_is_the_same_chunks_one_a_call(tiny, pack):
    """Logits and latent pools of a call of several rows against the same
    chunks prefilled a call each, in the same order."""
    from test_mimo_v2 import assert_the_same, packed_against_single

    from ray_tpu.models import deepseek_v3 as dec

    cfg, params, _ = tiny
    pages, prior, rows = LATENT_PACKS[pack]
    assert_the_same(*packed_against_single(dec, cfg, params, B=16, P=64, pages=pages,
                                           prior=prior, rows=rows))


def test_a_call_of_rows_as_served_agrees_to_bfloat16s_rounding(twin):
    """As served, in bfloat16: tokens do not mix in the expert layer and
    rows do not mix in attention, so a call of rows differs from a call a
    row by the rounding of products of another shape and no more; and no
    expert drops a token at the larger pair count (the sorted buffer is
    sized by the call's tokens: a dropped pair moves a token's logits by an
    expert's whole output)."""
    from test_mimo_v2 import packed_against_single

    from ray_tpu.models import deepseek_v3 as dec

    _, mcfg, params = twin
    pages, prior, rows = LATENT_PACKS["two_tails_as_four_rows"]
    got, want, packed, single = packed_against_single(
        dec, mcfg, params, B=16, P=64, pages=pages, prior=prior, rows=rows)
    assert float(np.std(got)) > 0.3
    assert max(np.abs(got[r] - w).max() for r, w in want.items()) < 0.1
    assert max(np.abs(a - b).max() for a, b in zip(packed, single)) < 0.1
