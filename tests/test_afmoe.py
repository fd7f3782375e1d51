"""Trinity (``model_type: afmoe``) through the paged cache and the rings
(models/afmoe.py) against the plain reference's full forward pass
(benchmark/reference/afmoe_ref.py), at the tiny preset on the CPU, logits
compared.

The comparison is the benchmark's own (``families/afmoe.compare_serve``:
chunked prefill into a row's cache, then decode side by side). In float32
it is tight, and every way of getting the model wrong that is listed below
breaks it; in bfloat16, as served, it is held to the tiny twin's tolerance.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TIGHT = 2e-3  # float32 program against float32 reference, logits' spread ~1


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    from benchmark.families import afmoe as family
    from ray_tpu.models import afmoe

    cfg = dataclasses.replace(afmoe.CONFIGS["trinity-tiny"], dtype=jnp.float32)
    return cfg, afmoe.load_serving_params(cfg), family.program_sizes("trinity-tiny")


def compare(tiny, **kw):
    from benchmark.families import afmoe as family

    cfg, params, model = tiny
    kw = {"prompt_lens": [70, 33, 5], "steps": 24, "page_tokens": 16, "chunk": 32, **kw}
    return family.compare_serve(cfg, model, params, 11, **kw)


def test_prefill_then_decode_through_the_cache_is_the_full_forward(tiny):
    """Rows of unequal length side by side; row 0 is prefilled in three
    chunks of 32 (positions at start > 0, each chunk twice the window of
    16, its ring wrapped by the second) and grows to 94 positions; row 2
    starts inside the window and wraps its ring while it decodes."""
    out = compare(tiny)
    assert out["reference_logit_std"] > 0.3
    assert 0 < out["prefill_max_abs"] < TIGHT and 0 < out["decode_max_abs"] < TIGHT
    # float32 against float32 ranks no two experts the other way round:
    # the tokens whose scores lie close are as near as the others
    assert 0 < out["tokens_tied"] < out["tokens_compared"] and out["tied_worst"] < TIGHT
    assert (out["rows"], out["decode_steps"], out["tokens_compared"]) == (3, 24, 6 + 3 * 24)


@pytest.mark.parametrize("chunk", [8, 20, 64])
def test_a_chunk_narrower_than_the_window_or_as_wide_as_the_prompt(tiny, chunk):
    """The published shapes have a chunk a quarter of the window: at 8 a
    chunk meets a ring that holds two earlier chunks, at 20 chunk and ring
    do not line up, at 64 the first call holds four windows."""
    out = compare(tiny, prompt_lens=[61, 17], steps=6, chunk=chunk)
    assert 0 < out["prefill_max_abs"] < TIGHT and 0 < out["decode_max_abs"] < TIGHT


def roll_kv_heads(params, model):
    """K and V heads moved on by one: query head h then reads head h // G + 1."""
    import jax.numpy as jnp

    def moved(layer):
        attn = dict(layer["attn"])
        for name in ("wk", "wv"):
            w = attn[name]
            attn[name] = jnp.roll(w.reshape(w.shape[0], -1, model["head_dim"]), 1,
                                  axis=1).reshape(w.shape)
        return {**layer, "attn": attn}

    return {**params, "layers": [moved(l) for l in params["layers"]]}


def edit_layers(params, path, fn):
    def one(layer):
        if path[0] not in layer or path[1] not in layer[path[0]]:
            return layer
        return {**layer, path[0]: {**layer[path[0]], path[1]: fn(layer[path[0]][path[1]])}}

    return {**params, "layers": [one(l) for l in params["layers"]]}


def wrong(*names):
    return lambda p, m: (p, {**m, "wrong": names})


# what the reference is given instead of the model: each must move the
# logits past the tolerance, or the check could not tell the program apart
# from a program that computes this
FAULTS = {
    "gate_dropped": wrong("no_gate"),
    "qk_norm_dropped": wrong("no_qk_norm"),
    "rope_in_the_full_layer": wrong("rope_in_full"),
    "no_rope_in_a_sliding_layer": wrong("no_rope_in_sliding"),
    "post_attention_norm_dropped": wrong("no_post_attn_norm"),
    "post_mlp_norm_dropped": wrong("no_post_mlp_norm"),
    "bias_in_the_gate": wrong("bias_in_gate"),
    "embedding_not_scaled": lambda p, m: (p, {**m, "mup_enabled": False}),
    "route_scale_dropped": lambda p, m: (p, {**m, "route_scale": 1.0}),
    "shared_expert_dropped": lambda p, m: (p, {**m, "num_shared_experts": 0}),
    "one_expert_fewer": lambda p, m: (p, {**m, "num_experts_per_tok": m["num_experts_per_tok"] - 1}),
    "gates_not_renormalised": lambda p, m: (p, {**m, "route_norm": False}),
    "selection_bias_dropped": lambda p, m: (edit_layers(p, ("moe", "bias"), lambda b: 0 * b), m),
    "q_norm_scale_dropped": lambda p, m: (edit_layers(p, ("attn", "q_norm"), lambda s: 0 * s + 1), m),
    "window_one_short": lambda p, m: (p, {**m, "sliding_window": m["sliding_window"] - 1}),
    "no_window": lambda p, m: (p, {**m, "sliding_window": 10**6}),
    "every_layer_full": lambda p, m: (p, {**m, "layer_types": ["full_attention"] * len(m["layer_types"])}),
    "kv_head_mapping_off_by_one": lambda p, m: (roll_kv_heads(p, m), m),
}


def give_the_reference(fault, monkeypatch):
    """From here on the reference computes the model with ``fault``."""
    from benchmark.reference import afmoe_ref

    forward = afmoe_ref.forward

    def faulty(params, tokens, model, margins=False, positions=None):
        params, model = FAULTS[fault](params, dict(model))
        return forward(params, tokens, model, margins, positions, model.pop("wrong", ()))

    monkeypatch.setattr(afmoe_ref, "forward", faulty)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_model_that_is_wrong_in_one_way_breaks_the_check(tiny, fault, monkeypatch):
    give_the_reference(fault, monkeypatch)
    out = compare(tiny, prompt_lens=[40, 21], steps=12)
    assert max(out["prefill_max_abs"], out["decode_max_abs"]) > 10 * TIGHT, out


def test_a_chunk_of_k_steps_is_k_single_steps(tiny):
    """``decode_multi_paged`` against ``decode_paged_and_sample`` step by
    step: the same tokens, lengths and counts, a row of no length staying
    nobody's; row 0 wraps its ring inside the chunk (positions 14 .. 18)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import afmoe as dec

    cfg, params, _ = tiny
    S, B = 3, 16
    tables = np.zeros((S, cfg.n_positions // B), np.int32)
    tables[0, :3], tables[2, :3] = [1, 2, 3], [4, 5, 6]
    state = (jnp.asarray([7, 0, 9]), jnp.asarray([14, 0, 20]))
    common = (jnp.asarray(tables), jnp.ones((S,)), jnp.ones((S,), bool), jax.random.PRNGKey(0))

    def caches():
        return dec.init_paged_cache(cfg, 7, B, S)

    toks, last, lens, _, _, counted = dec.decode_multi_paged(
        cfg, params, *state, *caches(), *common, 5, 0)
    ck, cv = caches()
    singles, total = [], 0
    cur, cur_lens = state
    for i in range(5):
        cur, cur_lens, ck, cv, c = dec.decode_paged_and_sample(
            cfg, params, cur, cur_lens, ck, cv, *common, i)
        singles.append(np.asarray(cur))
        total = total + np.asarray(c)
    assert np.array_equal(np.asarray(toks)[:5, [0, 2]], np.stack(singles)[:, [0, 2]])
    assert list(np.asarray(lens)) == list(np.asarray(cur_lens)) == [19, 0, 25]
    assert list(np.asarray(counted)) == list(total)
    by_name = dict(zip(dec.STEP_COUNTERS, map(int, counted)))
    # 3 expert layers x 5 steps x 16 held experts; the empty row counts nowhere
    assert by_name["moe_expert_steps"] == 3 * 5 * 16
    assert 0 < by_name["moe_assignments"] <= 2 * 4 * 3 * 5
    # positions attended over: row 0 at 14 .. 18, row 2 at 20 .. 24, the new
    # one among them; the sliding layers stop at the window of 16
    assert by_name["attn_context_tokens"] == sum(range(15, 20)) + sum(range(21, 26))
    assert by_name["window_context_tokens"] == (15 + 16 * 4) + 16 * 5


def test_the_step_counts_once_a_step_what_rings_and_pages_held(tiny):
    """32 rows of every length, eight of them nobody's: ``window_context_tokens``
    is min(p + 1, 16) a live row, ``attn_context_tokens`` p + 1, and
    ``attn_loop_tokens`` what the full layer's kernel reads for the live
    rows, each row's own pages x the positions a page."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import afmoe as dec
    from ray_tpu.ops import page_loops

    cfg, params, _ = tiny
    B, S = 4, 32
    turn = B * page_loops.DECODE_PAGES
    long = [turn + 1, 100, 120, 150, 180, 200, 249, 250]
    lens = [0] * 8 + [1, 2, 3, 4, 5, 6, turn - 2, turn - 1] + [turn] * 8 + long
    lens = np.asarray(lens)[np.random.default_rng(2).permutation(S)]
    tables = np.zeros((S, cfg.n_positions // B), np.int32)
    at = 1
    for r, n in enumerate(lens):
        need = n // B + 1 if n else 0
        tables[r, :need] = np.arange(at, at + need)
        at += need
    out = jax.jit(dec._decode_paged_impl, static_argnums=(0,))(
        cfg, params, jnp.zeros((S,), jnp.int32), jnp.asarray(lens, jnp.int32),
        *dec.init_paged_cache(cfg, at, B, S), jnp.asarray(tables))
    by_name = dict(zip(dec.STEP_COUNTERS, map(int, out[3])))
    assert by_name["attn_loop_tokens"] == B * sum(n // B + 1 for n in lens if n)
    assert by_name["attn_context_tokens"] == int(sum(n + 1 for n in lens if n))
    assert by_name["window_context_tokens"] == int(sum(min(n + 1, 16) for n in lens if n))
    assert bool(jnp.isfinite(out[0]).all())


def test_rings_hold_the_window_however_long_a_row_grows(tiny):
    """The cache by kind: a sliding layer's bytes are rows x window x its
    K/V widths whatever the pool and the context, the full layer's go with
    the pages."""
    from ray_tpu.models import afmoe as dec

    cfg, _, _ = tiny
    small = dec.cache_layout(cfg, *dec.init_paged_cache(cfg, 9, 16, 4))
    large = dec.cache_layout(cfg, *dec.init_paged_cache(cfg, 65, 64, 4))
    assert small["bytes"]["window"] == large["bytes"]["window"] > 0
    assert large["bytes"]["full"] > 20 * small["bytes"]["full"]
    assert [s[0] for s in small["shape"]] == ["window", "window", "full", "window"]
    for shape in small["shape"]:
        if shape[0] == "window":
            assert shape[1:] == [4, cfg.sliding_window, 2 * 16]  # rows x window, no more
    spec = dec.cache_spec(cfg)
    assert {(s["kv_heads"], s["k_size"], s["v_size"]) for s in spec} == {(2, 16, 16)}
    # at the published sizes: two thirds of the cache are rings
    from benchmark.families import afmoe as family

    model = family.program_sizes("trinity-mini")
    rings = 128 * 2048 * 4 * family.position_bytes(model)
    pool = 8193 * 64 * 1 * family.position_bytes(model)
    assert rings == pytest.approx(2.147e9, rel=1e-3) and pool == pytest.approx(1.074e9, rel=1e-3)
    assert rings / (rings + pool) == pytest.approx(0.667, abs=0.001)


@pytest.fixture(scope="module")
def twin():
    """The tiny twin as served: bfloat16, the engine's stored weights, and
    its own check (``tests/bench/configs/trinity-tiny-serve.json``)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from benchmark.families import afmoe as family

    with open(os.path.join(ROOT, "tests/bench/configs/trinity-tiny-serve.json")) as f:
        cfg = json.load(f)
    return (cfg, *family.serve_params(cfg["model_id"]))


SHORT = {"prompt_lens": [70, 30, 20], "steps": 12, "page_tokens": 16, "chunk": 32}


def test_the_tiny_twin_as_served_keeps_its_tolerance_on_the_largest_gap(twin):
    """Every judged token by the largest gap; the tokens whose selection
    margin is under ``TIE`` are counted and may be off by an expert's whole
    output."""
    from benchmark.families import afmoe as family

    cfg, mcfg, params = twin
    tokens = family.token_gaps(mcfg, cfg["model"], params, 2, cfg["check"]["prompt_lens"],
                               cfg["check"]["decode_steps"], page_tokens=16)
    judged = [t["gap"] for t in tokens if t["margin"] >= family.TIE]
    assert len(judged) > 0.3 * len(tokens)
    assert 1e-3 < max(judged) <= cfg["check"]["logit_tolerance"], max(judged)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_wrong_model_breaks_the_tiny_twins_own_tolerance_in_bfloat16(twin, fault, monkeypatch):
    """As served: each fault moves the largest gap of the judged tokens
    past the configuration's tolerance."""
    from benchmark.families import afmoe as family

    cfg, mcfg, params = twin
    give_the_reference(fault, monkeypatch)
    out = family.compare_serve(mcfg, cfg["model"], params, 7, **SHORT)
    assert max(out["prefill_max_abs"], out["decode_max_abs"]) > cfg["check"]["logit_tolerance"], out


def test_a_fault_in_one_row_alone_breaks_the_check(twin, monkeypatch):
    """One row of three whose sliding layers lose their ring's oldest
    position (the reference is given a window one short for that row
    only). A third of the tokens move; the largest gap of the judged ones
    is held, so it fails."""
    from benchmark.families import afmoe as family
    from benchmark.reference import afmoe_ref

    cfg, mcfg, params = twin
    forward = afmoe_ref.forward

    def wrong_for_the_shortest(params, tokens, model, margins=False, positions=None):
        if tokens.shape[0] == 20 + 12:
            model = {**model, "sliding_window": model["sliding_window"] - 1}
        return forward(params, tokens, model, margins, positions)

    monkeypatch.setattr(afmoe_ref, "forward", wrong_for_the_shortest)
    tokens = family.token_gaps(mcfg, cfg["model"], params, 7, **SHORT)
    tol = cfg["check"]["logit_tolerance"]
    judged = [t for t in tokens if t["margin"] >= family.TIE]
    assert max(t["gap"] for t in judged if t["row"] == 2) > tol
    assert max(t["gap"] for t in judged if t["row"] != 2) <= tol


def through_the_check(reference, check):
    """``serve_sessions._check`` on a run in which nothing else is amiss:
    what it says of ``reference`` under the configuration's ``check``."""
    from benchmark.generators import serve_sessions

    obs = {"records": [], "problems": [], "notes": [], "reference": {**reference, "family": "afmoe"},
           "check": check, "cache_entries": {"t0": 3, "t1": 3, "gained": []}}
    serve_sessions._check(obs, {"text": ["a"]}, {"text": ["a"]})
    return obs


def test_the_lower_precision_control_is_not_correct_by_the_harness_own_comparison(twin):
    """The reading that holds the tolerance from above, through the
    comparison that decides ``correct``: the same programs on weights
    rounded to float8_e4m3fn, the nearest precision below bfloat16, against
    the reference on the weights as they are. Not correct, by the largest
    gap of the judged tokens; as served, correct."""
    from benchmark.families import afmoe as family

    cfg, mcfg, params = twin
    served = through_the_check(
        family.compare_serve(mcfg, cfg["model"], params, 3_000_000_019, **SHORT), cfg["check"])
    assert served["problems"] == []
    control = through_the_check(
        family.compare_serve(mcfg, cfg["model"], params, 3_000_000_019, **SHORT,
                             served=family.lower_precision(params)), cfg["check"])
    assert len(control["problems"]) == 1 and "logits differ" in control["problems"][0]
    gaps = control["compared"]
    assert max(gaps["prefill_logit_gap"][0], gaps["decode_logit_gap"][0]) > 2 * cfg["check"]["logit_tolerance"]


def test_the_expected_experts_hit_is_what_the_program_counts(tiny):
    """``decode_step_bytes`` charges a step for the distinct experts its
    rows are expected to reach, E * (1 - (1 - k / E) ** rows); the program's
    own count over many steps agrees."""
    import jax
    import jax.numpy as jnp

    from benchmark.families import afmoe as family
    from ray_tpu.models import afmoe as dec

    cfg, params, model = tiny
    S, B, steps = 6, 16, 8
    tables = np.zeros((S, cfg.n_positions // B), np.int32)
    for r in range(S):
        tables[r, :2] = [1 + 2 * r, 2 + 2 * r]
    ck, cv = dec.init_paged_cache(cfg, 1 + 2 * S, B, S)
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, S))
    lens = jnp.asarray(rng.integers(1, 8, S))
    _, _, _, _, _, counted = dec.decode_multi_paged(
        cfg, params, toks, lens, ck, cv, jnp.asarray(tables), jnp.ones((S,)),
        jnp.zeros((S,), bool), jax.random.PRNGKey(1), steps, 0)
    layer_steps = int(counted[1]) / cfg.num_experts
    assert layer_steps == 3 * steps
    hit = int(counted[2]) / layer_steps
    want = family.expected_experts_hit(model, S)
    assert want == pytest.approx(16 * (1 - 0.75 ** 6))
    assert abs(hit - want) < 1.5, (hit, want)
    assert family.decode_step_bytes(model, 1, 8) < family.decode_step_bytes(model, 6, 8)
    # past the window only the full layer grows: 1 layer x K and V x 2 heads x 16
    assert (family.decode_step_bytes(model, 6, 5000) - family.decode_step_bytes(model, 6, 4000)
            == 2.0 * 6 * 1000 * 2 * 2 * 16)


# -- a prefill call of several rows ------------------------------------------

# B = 16, rows 64 wide, a window of 16: (pages a sequence, prior, rows)
PACKS = {
    # sequence 0 continues behind three pages and a ring that has wrapped, 1
    # starts cold and is shorter than the window, 2 continues inside its
    # first page and its window
    "rows_of_different_start": (
        [[1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11], [12, 13, 14, 15, 16]],
        [(0, 0, 48), (2, 0, 7)],
        [(0, 48, 50), (1, 0, 9), (2, 7, 64)]),
    "a_row_of_no_length_in_the_middle": (
        [[1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11], [12, 13, 14, 15, 16]],
        [(0, 0, 40)],
        [(0, 40, 33), (1, 0, 0), (2, 0, 64), (3, 0, 0)]),
}


@pytest.mark.parametrize("pack", sorted(PACKS))
def test_a_prefill_call_of_rows_is_the_same_chunks_one_a_call(tiny, pack):
    """Logits, pools and rings of a call of several rows against the same
    chunks prefilled a call each."""
    from test_mimo_v2 import assert_the_same, packed_against_single

    from ray_tpu.models import afmoe as dec

    cfg, params, _ = tiny
    pages, prior, rows = PACKS[pack]
    out = packed_against_single(dec, cfg, params, B=16, P=64, pages=pages, prior=prior,
                                rows=rows)
    assert_the_same(*out)


def test_a_row_of_no_length_writes_no_ring_and_no_page_of_its_own(tiny):
    """A call whose rows all have no length, as the engine compiles its
    programs before it reports ready: the rings and every page but the
    scratch page are as they were."""
    import jax.numpy as jnp

    from ray_tpu.models import afmoe as dec

    cfg, params, _ = tiny
    B, R, P = 16, 4, 64
    fill = lambda c: type(c)(tuple(a + 1 for a in c.layers), c.page_tokens)
    k, v = map(fill, dec.init_paged_cache(cfg, 9, B, 4))
    zeros = jnp.zeros((R,), jnp.int32)
    logits, k2, v2 = dec.prefill_paged(
        cfg, params, jnp.zeros((R, P), jnp.int32), zeros, zeros, k, v,
        jnp.zeros((R, cfg.n_positions // B), jnp.int32), zeros)
    assert logits.shape == (R, cfg.vocab_size) and bool(jnp.isfinite(logits).all())
    for s, a in zip(dec.cache_spec(cfg) * 2, k2.layers + v2.layers):
        kept = a if s["kind"] == "window" else a[1:]
        assert bool((kept == 1).all())


def test_the_reference_reads_nothing_of_the_program_but_its_parameters():
    with open(os.path.join(ROOT, "benchmark/reference/afmoe_ref.py")) as f:
        text = f.read()
    imports = [ln for ln in text.splitlines() if ln.startswith(("import ", "from "))]
    assert imports and not [ln for ln in imports if "ray_tpu" in ln or "benchmark" in ln], imports
