"""MiMo-V2 through the paged cache (models/mimo_v2.py) against the plain
reference's full forward pass (benchmark/reference/mimo_v2_ref.py), at the
tiny preset on the CPU, logits compared.

The comparison is the benchmark's own (``families/mimo_v2.compare_serve``:
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
    from benchmark.families import mimo_v2 as family
    from ray_tpu.models import mimo_v2

    cfg = dataclasses.replace(mimo_v2.CONFIGS["mimo-v2-tiny"], dtype=jnp.float32)
    return cfg, mimo_v2.load_serving_params(cfg), family.program_sizes("mimo-v2-tiny")


def compare(tiny, **kw):
    from benchmark.families import mimo_v2 as family

    cfg, params, model = tiny
    kw = {"prompt_lens": [70, 33, 5], "steps": 24, "page_tokens": 16, "chunk": 32, **kw}
    return family.compare_serve(cfg, model, params, 11, **kw)


def test_prefill_then_decode_through_the_cache_is_the_full_forward(tiny):
    """Rows of unequal length side by side; row 0 is prefilled in three
    chunks (positions at start > 0) and grows to 94 positions, past the
    window of 16 and two pages of 16; row 2 starts inside the window."""
    out = compare(tiny)
    assert out["reference_logit_std"] > 0.3
    assert 0 < out["prefill_max_abs"] < TIGHT and 0 < out["decode_max_abs"] < TIGHT
    # float32 against float32 ranks no two experts the other way round:
    # the tokens whose scores lie close are as near as the others
    assert 0 < out["tokens_tied"] < out["tokens_compared"] / 2 and out["tied_worst"] < TIGHT
    assert (out["rows"], out["decode_steps"], out["tokens_compared"]) == (3, 24, 6 + 3 * 24)


def roll_kv_heads(params, model):
    """Window layers' K and V heads moved on by one: query head h then
    reads head h // G + 1."""
    import jax.numpy as jnp

    def moved(layer, window):
        if not window:
            return layer
        attn = dict(layer["attn"])
        for name, size in (("wk", model["head_dim"]), ("wv", model["v_head_dim"])):
            w = attn[name]
            attn[name] = jnp.roll(w.reshape(w.shape[0], -1, size), 1, axis=1).reshape(w.shape)
        return {**layer, "attn": attn}

    return {**params, "layers": [moved(l, w) for l, w in
                                 zip(params["layers"], model["hybrid_layer_pattern"])]}


def edit_layers(params, path, fn):
    def one(layer):
        if path[0] not in layer or path[1] not in layer[path[0]]:
            return layer
        return {**layer, path[0]: {**layer[path[0]], path[1]: fn(layer[path[0]][path[1]])}}

    return {**params, "layers": [one(l) for l in params["layers"]]}


# what the reference is given instead of the model: each must move the
# logits past the tolerance, or the check could not tell the program apart
# from a program that computes this
FAULTS = {
    "one_expert_fewer": lambda p, m: (p, {**m, "num_experts_per_tok": m["num_experts_per_tok"] - 1}),
    "gates_not_renormalised": lambda p, m: (p, {**m, "norm_topk_prob": False}),
    "selection_bias_dropped": lambda p, m: (edit_layers(p, ("moe", "bias"), lambda b: 0 * b), m),
    "sink_dropped": lambda p, m: (edit_layers(p, ("attn", "sink"), lambda s: s - 1e30), m),
    "window_one_short": lambda p, m: (p, {**m, "sliding_window": m["sliding_window"] - 1}),
    "no_window": lambda p, m: (p, {**m, "sliding_window": 10**6}),
    "value_scale_dropped": lambda p, m: (p, {**m, "attention_value_scale": 1.0}),
    "rotary_bases_swapped": lambda p, m: (p, {**m, "rope_theta": m["swa_rope_theta"],
                                               "swa_rope_theta": m["rope_theta"]}),
    "rotary_on_every_dimension": lambda p, m: (p, {**m, "partial_rotary_factor": 1.0}),
    "kv_head_mapping_off_by_one": lambda p, m: (roll_kv_heads(p, m), m),
    "another_share_of_the_experts": lambda p, m: (p, {**m, "held_first": 4}),
}


def give_the_reference(fault, monkeypatch):
    """From here on the reference computes the model with ``fault``."""
    from benchmark.reference import mimo_v2_ref

    forward = mimo_v2_ref.forward

    def wrong(params, tokens, model, held=None, margins=False):
        params, model = FAULTS[fault](params, dict(model))
        if "held_first" in model:
            held = (model.pop("held_first"), model["n_routed_experts"])
        return forward(params, tokens, model, held, margins)

    monkeypatch.setattr(mimo_v2_ref, "forward", wrong)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_model_that_is_wrong_in_one_way_breaks_the_check(tiny, fault, monkeypatch):
    give_the_reference(fault, monkeypatch)
    out = compare(tiny, prompt_lens=[40, 21], steps=12)
    assert max(out["prefill_max_abs"], out["decode_max_abs"]) > 10 * TIGHT, out


def test_a_chunk_of_k_steps_is_k_single_steps(tiny):
    """``decode_multi_paged`` against ``decode_paged_and_sample`` step by
    step: the same tokens, lengths and expert counts, a row of no length
    staying nobody's."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import mimo_v2 as dec

    cfg, params, _ = tiny
    S, B = 3, 16
    tables = np.zeros((S, cfg.n_positions // B), np.int32)
    tables[0, :3], tables[2, :3] = [1, 2, 3], [4, 5, 6]
    state = (jnp.asarray([7, 0, 9]), jnp.asarray([4, 0, 20]))
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
    assert list(np.asarray(lens)) == list(np.asarray(cur_lens)) == [9, 0, 25]
    assert list(np.asarray(counted)) == list(total)
    # 3 expert layers x 5 steps x 4 held experts; the empty row counts nowhere
    assert int(counted[1]) == 3 * 5 * 4 and 0 < int(counted[0]) <= 2 * 4 * 3 * 5


def test_window_layers_hold_the_window_however_long_a_row_grows(tiny):
    """The cache by kind: a window layer's bytes are rows x window x its
    K/V widths whatever the pool and the context, a full layer's go with
    the pages."""
    from ray_tpu.models import mimo_v2 as dec

    cfg, _, _ = tiny
    small = dec.cache_layout(cfg, *dec.init_paged_cache(cfg, 9, 16, 4))
    large = dec.cache_layout(cfg, *dec.init_paged_cache(cfg, 65, 64, 4))
    assert small["bytes"]["window"] == large["bytes"]["window"] > 0
    assert large["bytes"]["full"] > 20 * small["bytes"]["full"]
    kinds = [s[0] for s in small["shape"]]
    assert kinds == ["full", "window", "window", "full"]
    for shape in small["shape"]:
        if shape[0] == "window":
            assert shape[1:3] == [4, cfg.sliding_window]  # rows x window, no more
    spec = dec.cache_spec(cfg)
    assert [s["kv_heads"] for s in spec] == [1, 2, 2, 1]
    assert {(s["k_size"], s["v_size"]) for s in spec} == {(24, 16)}


@pytest.fixture(scope="module")
def twin():
    """The tiny twin as served: bfloat16, the engine's stored weights, and
    its own check (``tests/bench/configs/mimo-v2-tiny-serve.json``)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from benchmark.families import mimo_v2 as family

    with open(os.path.join(ROOT, "tests/bench/configs/mimo-v2-tiny-serve.json")) as f:
        cfg = json.load(f)
    return (cfg, *family.serve_params(cfg["model_id"]))


def test_the_tiny_twin_as_served_keeps_its_tolerance_on_the_largest_gap(twin):
    """Seed 2 meets a router's tie: the token at position 103 of row 0 is
    off by 0.56, the reference's own scores of the two experts 0.0013
    apart. That token is not judged; every other is, by the largest gap,
    the next one (which attends to it) included."""
    from benchmark.families import mimo_v2 as family

    cfg, mcfg, params = twin
    tokens = family.token_gaps(mcfg, cfg["model"], params, 2,
                               cfg["check"]["prompt_lens"], cfg["check"]["decode_steps"],
                               page_tokens=16)
    off = [t for t in tokens if t["gap"] > cfg["check"]["logit_tolerance"]]
    assert [(t["row"], t["position"]) for t in off] == [(0, 103)]
    assert off[0]["gap"] > 0.5 and off[0]["margin"] < family.TIE / 2
    judged = [t["gap"] for t in tokens if t["margin"] >= family.TIE]
    assert len(judged) > 0.7 * len(tokens)
    assert 1e-3 < max(judged) <= cfg["check"]["logit_tolerance"], max(judged)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_wrong_model_breaks_the_tiny_twins_own_tolerance_in_bfloat16(twin, fault, monkeypatch):
    """As served: each fault moves the largest gap of the judged tokens
    past the configuration's tolerance."""
    from benchmark.families import mimo_v2 as family

    cfg, mcfg, params = twin
    give_the_reference(fault, monkeypatch)
    # a shorter check than the configuration's own, for the suite's time
    out = family.compare_serve(mcfg, cfg["model"], params, 7,
                               prompt_lens=[70, 30, 20], steps=12, page_tokens=16)
    assert max(out["prefill_max_abs"], out["decode_max_abs"]) > cfg["check"]["logit_tolerance"], out


def test_a_fault_in_one_row_alone_breaks_the_check(twin, monkeypatch):
    """What a median over all tokens let through: one row of three whose
    window layers lose their ring's oldest position (the reference is
    given a window one short for that row only). A third of the tokens
    move; the largest gap of the judged ones is held, so it fails."""
    from benchmark.families import mimo_v2 as family
    from benchmark.reference import mimo_v2_ref

    cfg, mcfg, params = twin
    forward = mimo_v2_ref.forward

    def wrong_for_the_shortest(params, tokens, model, held=None, margins=False):
        if tokens.shape[0] == 20 + 12:
            model = {**model, "sliding_window": model["sliding_window"] - 1}
        return forward(params, tokens, model, held, margins)

    monkeypatch.setattr(mimo_v2_ref, "forward", wrong_for_the_shortest)
    tokens = family.token_gaps(mcfg, cfg["model"], params, 7, [70, 30, 20], 12, page_tokens=16)
    tol = cfg["check"]["logit_tolerance"]
    gaps = sorted(t["gap"] for t in tokens)
    assert gaps[len(gaps) // 2] < tol / 2           # the median does not see it
    judged = [t for t in tokens if t["margin"] >= family.TIE]
    assert max(t["gap"] for t in judged if t["row"] == 2) > tol
    assert max(t["gap"] for t in judged if t["row"] != 2) <= tol


def through_the_check(reference, check):
    """``serve_sessions._check`` on a run in which nothing else is amiss:
    what it says of ``reference`` under the configuration's ``check``."""
    from benchmark.generators import serve_sessions

    obs = {"records": [], "problems": [], "notes": [], "reference": {**reference, "family": "mimo_v2"},
           "check": check, "cache_entries": {"t0": 3, "t1": 3, "gained": []}}
    serve_sessions._check(obs, {"text": ["a"]}, {"text": ["a"]})
    return obs


def test_the_lower_precision_control_is_not_correct_by_the_harness_own_comparison(twin):
    """The reading that holds the tolerance from above, through the
    comparison that decides ``correct``: the same programs on weights
    rounded to float8_e4m3fn, the nearest precision below bfloat16,
    against the reference on the weights as they are. Not correct, by the
    largest gap of the judged tokens; as served, correct. (At the
    published widths the same two functions gave the chip's readings:
    ``benchmark/configs/mimo-v2.5-serve.json`` ``check.why``.)"""
    from benchmark.families import mimo_v2 as family

    cfg, mcfg, params = twin
    shape = {"prompt_lens": [70, 30, 20], "steps": 12, "page_tokens": 16}
    served = through_the_check(
        family.compare_serve(mcfg, cfg["model"], params, 3_000_000_019, **shape), cfg["check"])
    assert served["problems"] == []
    control = through_the_check(
        family.compare_serve(mcfg, cfg["model"], params, 3_000_000_019, **shape,
                             served=family.lower_precision(params)), cfg["check"])
    assert len(control["problems"]) == 1 and "logits differ" in control["problems"][0]
    gaps = control["compared"]
    assert max(gaps["prefill_logit_gap"][0], gaps["decode_logit_gap"][0]) > 3 * cfg["check"]["logit_tolerance"]


def test_the_expected_experts_hit_is_what_the_program_counts(tiny):
    """``decode_step_bytes`` charges a step for the distinct held experts
    its rows are expected to reach, held * (1 - (1 - k / routed) ** rows);
    the program's own count over many steps agrees."""
    import jax
    import jax.numpy as jnp

    from benchmark.families import mimo_v2 as family
    from ray_tpu.models import mimo_v2 as dec

    cfg, params, model = tiny
    S, B, steps = 6, 16, 8
    tables = np.zeros((S, cfg.n_positions // B), np.int32)
    for r in range(S):
        tables[r, :2] = [1 + 2 * r, 2 + 2 * r]
    ck, cv = dec.init_paged_cache(cfg, 1 + 2 * S, B, S)
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, S))
    lens = jnp.asarray(rng.integers(1, 8, S))
    toks_all, _, _, _, _, counted = dec.decode_multi_paged(
        cfg, params, toks, lens, ck, cv, jnp.asarray(tables), jnp.ones((S,)),
        jnp.zeros((S,), bool), jax.random.PRNGKey(1), steps, 0)
    layer_steps = int(counted[1]) / cfg.n_routed_experts
    assert layer_steps == 3 * steps
    hit = int(counted[2]) / layer_steps
    want = family.expected_experts_hit(model, S)
    assert want == pytest.approx(4 * (1 - 0.75 ** 6))
    assert abs(hit - want) < 0.6, (hit, want)
    # and no more than the step must read: fewer rows, fewer bytes
    assert family.decode_step_bytes(model, 1, 8) < family.decode_step_bytes(model, 6, 8)
    assert family.decode_step_bytes(model, 6, 8) < family.decode_step_bytes(model, 6, 200)
    assert (family.decode_step_bytes(model, 6, 5000) - family.decode_step_bytes(model, 6, 4000)
            == 2.0 * 6 * 1000 * 2 * 1 * (24 + 16))  # only the full layers grow past the window


def plain_attention(q, k, v, visible, kv_heads, sink=None):
    """A softmax a head, written plainly: q [R, Q, H, Dk] over k [R, T, Hkv *
    Dk] and v [R, T, Hkv * Dv] where ``visible`` [R, Q, T]; query head h
    reads K/V head ``h // (H / Hkv)``; a ``sink`` [H] joins the denominator
    alone. Float32 at the highest precision -> [R, Q, H * Dv]."""
    import jax
    import jax.numpy as jnp

    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    R, Q, H, Dk = q.shape
    Dv = v.shape[-1] // kv_heads
    heads = []
    for h in range(H):
        j = h // (H // kv_heads)
        s = jnp.einsum("rqd,rtd->rqt", q[:, :, h], k[:, :, j * Dk:(j + 1) * Dk],
                       precision=jax.lax.Precision.HIGHEST) * Dk ** -0.5
        s = jnp.where(visible, s, -jnp.inf)
        if sink is not None:
            s = jnp.concatenate([s, jnp.full((R, Q, 1), sink[h], jnp.float32)], -1)
        p = jax.nn.softmax(s, axis=-1)[:, :, : k.shape[1]]
        heads.append(jnp.einsum("rqt,rtv->rqv", p, v[:, :, j * Dv:(j + 1) * Dv],
                                precision=jax.lax.Precision.HIGHEST))
    return jnp.stack(heads, axis=2).reshape(R, Q, H * Dv)


def walk_of(Q, last, span, most):
    """How ``paged_attend`` takes its rows' pages for ``Q`` queries a row
    whose last positions are ``last``: decode's one query in the kernel
    (``ops/paged_kv_attention.py``: the step's visits, no row walking more
    than ``most`` turns of ``span`` positions), a chunk of several under
    prefill's one loop."""
    import jax.numpy as jnp

    from ray_tpu.ops import page_loops, paged_kv_attention

    last = jnp.asarray(last, jnp.int32)
    if Q == 1:
        return paged_kv_attention.visits(last, span, most)
    return page_loops.one_loop(last, span)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("Q", [1, 5])
@pytest.mark.parametrize("sizes", [(24, 16), (192, 128)], ids=["tiny", "published"])
@pytest.mark.parametrize("kv_heads", [1, 2, 4, 8])
def test_attention_over_merged_heads_is_a_softmax_a_head(kv_heads, sizes, Q, dtype):
    """The two products that take K and V with the heads merged, as the
    caches store them, against a plain softmax a head: a full layer over a
    page table (a row of length 0, rows that end mid-page, pages in no
    order, noise in the pages a row does not own) and a window layer (a
    sink a head, a ring not yet full), one query a row (q spread over the
    K/V heads' columns: the pages in decode's kernel, the ring in one
    block) and a chunk of five (the keys split)."""
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.models import mimo_v2 as m

    dt = jnp.dtype(dtype)
    # bfloat16: the probabilities are rounded to 2**-8 before the second
    # product, on values of size ~1 (the cases read up to 3.0e-3, and
    # 1.3e-6 in float32)
    tol = 1e-5 if dtype == "float32" else 1e-2
    Dk, Dv = sizes
    H, R, B, max_pages, N = 8, 5, 8, 4, 12
    rng = np.random.default_rng(kv_heads * 100 + Dk + Q)

    def draw(*shape):
        return jnp.asarray(rng.normal(0, 1, shape), dt)

    q = draw(R, Q, H, Dk)
    # full layer: the last query's position a row; 0 is a row of no length
    # (its table points at the scratch page), 11 and 17 end mid-page, 31
    # fills the table
    last = np.asarray([0, 11, 17, 31, 8])
    q_pos = np.maximum(last[:, None] - np.arange(Q)[::-1][None], 0)
    tables = np.zeros((R, max_pages), np.int32)
    free = list(rng.permutation(np.arange(1, N)))
    for r in range(1, R):
        for c in range(last[r] // B + 1):
            tables[r, c] = free.pop()
    k_pool, v_pool = draw(N, B, kv_heads * Dk), draw(N, B, kv_heads * Dv)
    got = m._paged_attend(q, k_pool, v_pool, jnp.asarray(tables),
                          jnp.asarray(q_pos, jnp.int32), kv_heads,
                          walk_of(Q, last, 2 * B, max_pages // 2))
    T = max_pages * B
    visible = np.arange(T)[None, None, :] <= q_pos[:, :, None]
    want = plain_attention(q, k_pool[tables].reshape(R, T, -1),
                           v_pool[tables].reshape(R, T, -1), visible, kv_heads)
    assert got.shape == (R, Q, H * Dv) and got.dtype == jnp.float32
    assert float(jnp.abs(want).max()) > 0.5
    assert float(jnp.abs(got - want).max()) < tol

    # window layer: a ring of 16 slots a row, as many of them written as the
    # row is long, each query seeing what its own position allows
    W = 16
    ring_k, ring_v = draw(R, W, kv_heads * Dk), draw(R, W, kv_heads * Dv)
    sink = jnp.asarray(rng.normal(0, 1, (H,)), jnp.float32)
    held = np.asarray([1, 7, 16, 12, 3])  # slots written; 16 is a full ring
    seen = np.minimum(held[:, None] - np.arange(Q)[::-1][None], W).clip(1)
    visible = np.arange(W)[None, None, :] < seen[:, :, None]
    got = m._window_attend(q, ring_k, ring_v, jnp.asarray(visible), kv_heads, sink)
    want = plain_attention(q, ring_k, ring_v, visible, kv_heads, sink)
    assert got.shape == (R, Q, H * Dv)
    assert float(jnp.abs(got - want).max()) < tol


def rows_of_every_length(rng, rows, B, max_pages):
    """``rows`` rows' last positions, shuffled: 0 (nobody's), 1, B - 1, B,
    B + 1, several turns of four pages, the table's last position and
    random ones; their page tables, each row with pages of its own in no
    order (a row of no length points at the scratch page); and the pages a
    pool needs for them."""
    T = max_pages * B
    special = [0, 1, B - 1, B, B + 1, 3 * 4 * B + 5, 7 * 4 * B, T - 1]
    pos = np.asarray((special + list(rng.integers(1, T, max(0, rows - len(special)))))[:rows])
    pos = pos[rng.permutation(rows)]
    need = np.where(pos > 0, pos // B + 1, 0)
    free = rng.permutation(np.arange(1, 1 + need.sum()))
    tables = np.zeros((rows, max_pages), np.int32)
    for r, at in enumerate(np.cumsum(need) - need):
        tables[r, :need[r]] = free[at:at + need[r]]
    return pos, tables, 1 + need.sum()


@pytest.mark.parametrize("kv_heads", [1, 4])
@pytest.mark.parametrize("rows", [3, 16, 32, 40])
def test_rows_each_to_their_own_length_attend_as_one_loop_and_as_a_softmax_a_head(rows, kv_heads):
    """A full layer's decode attention in the kernel, each row walking its
    own turns, against the one loop over all rows that prefill keeps (<=
    1e-6 in float32: a turn behind a row's length adds exact zeros) and
    against a plain softmax a head, on rows of every length in shuffled
    order; the rows permuted give the same rows permuted, to the bit."""
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.models import mimo_v2 as m
    from ray_tpu.ops import page_loops

    rng = np.random.default_rng(0)
    H, Dk, Dv, B, max_pages = 8, 24, 16, 8, 64
    pos, tables, N = rows_of_every_length(rng, rows, B, max_pages)
    draw = lambda *shape: jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
    q, k_pool, v_pool = draw(rows, 1, H, Dk), draw(N, B, kv_heads * Dk), draw(N, B, kv_heads * Dv)
    pos, tables = jnp.asarray(pos, jnp.int32), jnp.asarray(tables)
    T, span = max_pages * B, 4 * B
    walk = walk_of(1, pos, span, T // span)
    assert int(walk.first[-1]) == int((pos // span + 1).sum())  # no turn behind a row's own
    got = m._paged_attend(q, k_pool, v_pool, tables, pos[:, None], kv_heads, walk)
    one = m._paged_attend(q, k_pool, v_pool, tables, pos[:, None], kv_heads,
                          page_loops.one_loop(pos, span))
    assert float(jnp.abs(got - one).max()) <= 1e-6
    visible = np.arange(T)[None, None, :] <= np.asarray(pos)[:, None, None]
    want = plain_attention(q, k_pool[tables].reshape(rows, T, -1),
                           v_pool[tables].reshape(rows, T, -1), visible, kv_heads)
    assert float(jnp.abs(want).max()) > 0.5
    assert float(jnp.abs(got - want).max()) < 1e-5
    perm = np.random.default_rng(rows).permutation(rows)
    moved = m._paged_attend(q[perm], k_pool, v_pool, tables[perm], pos[perm][:, None],
                            kv_heads, walk_of(1, pos[perm], span, T // span))
    assert np.array_equal(np.asarray(moved), np.asarray(got[perm]))


def test_the_step_counts_what_its_full_layers_kernel_read(tiny):
    """``attn_loop_tokens`` is what the full layers' kernel reads for the
    live rows, each row's own pages x the positions a page, for 32 rows of
    every length, and ``attn_context_tokens`` the live rows' positions,
    the new one among them; once a step, not a layer."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import mimo_v2 as dec
    from ray_tpu.ops import page_loops

    cfg, params, _ = tiny
    B, S = 4, 32
    turn = B * page_loops.DECODE_PAGES
    # eight rows nobody holds, which count nowhere; rows inside their first
    # turn, its last position among them; rows whose new position opens the
    # second; and rows up to 250
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
    # four loops by length (PR 49) covered every row to its group's longest
    assert by_name["attn_loop_tokens"] < 8 * turn * (1 + 1 + 2 + 250 // turn + 1)
    # and no row reads a whole page behind its own length
    assert by_name["attn_loop_tokens"] < by_name["attn_context_tokens"] + 24 * B



# -- a prefill call of several rows (PR 50) ----------------------------------


def packed_against_single(dec, cfg, params, *, B, P, pages, prior, rows, decode_rows=4,
                          seed=0):
    """The same chunks prefilled as the rows of ONE call and one a call.

    ``pages`` gives every sequence's pages (sequences may share leading
    pages: a prefix another one sealed); ``prior`` and ``rows`` are
    [(sequence, start, tokens)], a sequence being its decode row too:
    ``prior`` is prefilled first, a call a chunk, in both arms; ``rows``
    are the call under test, ``tokens`` 0 for a row of no length. Returns
    (the call's logits [R, V], the single calls' logits by row, and both
    arms' caches as lists of arrays, a paged layer without its scratch
    page)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    max_pages = cfg.n_positions // B
    tables = np.zeros((len(pages), max_pages), np.int32)
    for s, own in enumerate(pages):
        tables[s, :len(own)] = own
    text = {s: rng.integers(0, cfg.vocab_size, max_pages * B, dtype=np.int32)
            for s in range(len(pages))}
    n_pages = 1 + max(max(own) for own in pages)

    def alone(caches, seq, start, n):
        tok = np.zeros((1, P), np.int32)
        tok[0, :n] = text[seq][start:start + n]
        logits, *caches = dec.prefill_paged(
            cfg, params, jnp.asarray(tok), jnp.int32(start), jnp.int32(n), *caches,
            jnp.asarray(tables[seq]), np.int32(seq))
        return np.asarray(logits), caches

    def arm(packed):
        caches = list(dec.init_paged_cache(cfg, n_pages, B, decode_rows))
        for seq, start, n in prior:
            _, caches = alone(caches, seq, start, n)
        if packed:
            R = len(rows)
            tok = np.zeros((R, P), np.int32)
            for r, (seq, start, n) in enumerate(rows):
                if n:
                    tok[r, :n] = text[seq][start:start + n]
            logits, *caches = dec.prefill_paged(
                cfg, params, jnp.asarray(tok),
                jnp.asarray([start for _, start, _ in rows], jnp.int32),
                jnp.asarray([n for _, _, n in rows], jnp.int32), *caches,
                jnp.asarray(np.stack([tables[seq] if n else 0 * tables[0]
                                      for seq, _, n in rows])),
                jnp.asarray([seq for seq, _, _ in rows], jnp.int32))
            logits = np.asarray(logits)
        else:
            logits = {}
            for r, (seq, start, n) in enumerate(rows):
                if n:
                    logits[r], caches = alone(caches, seq, start, n)
        held = []
        for cache in caches:
            for a in cache.layers:
                # a pool without its scratch page; a ring a row whole
                held.append(np.asarray(a[1:] if a.shape[0] == n_pages else a, np.float32))
        return logits, held

    (got, packed), (want, single) = arm(True), arm(False)
    return got, want, packed, single


def assert_the_same(got, want, packed, single, tol=1e-4):
    assert want and all(np.abs(got[r] - w).max() < tol for r, w in want.items())
    assert float(np.std(got[list(want)])) > 0.3
    assert len(packed) == len(single) > 0
    for a, b in zip(packed, single):
        assert a.shape == b.shape and np.abs(a - b).max() < tol
    assert max(np.abs(a).max() for a in packed) > 0.1


# B = 16, rows 64 wide, a window of 16: (pages a sequence, prior, rows)
MIMO_PACKS = {
    # sequence 0 continues behind three pages and its ring, 1 starts cold
    # and is shorter than the window, 2 continues inside its first page
    "rows_of_different_start": (
        [[1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11], [12, 13, 14, 15, 16]],
        [(0, 0, 48), (2, 0, 7)],
        [(0, 48, 50), (1, 0, 9), (2, 7, 64)]),
    "a_row_of_no_length_in_the_middle": (
        [[1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11], [12, 13, 14, 15, 16]],
        [(0, 0, 40)],
        [(0, 40, 33), (1, 0, 0), (2, 0, 64), (3, 0, 0)]),
    # two pages of sequence 0 stand under sequence 1 as well: the call
    # reads them for it and writes neither
    "a_prefix_under_one_row": (
        [[1, 2, 3, 4, 5], [1, 2, 6, 7, 8, 9]],
        [(0, 0, 40)],
        [(1, 32, 50), (0, 40, 20)]),
}


@pytest.mark.parametrize("pack", sorted(MIMO_PACKS))
def test_a_prefill_call_of_rows_is_the_same_chunks_one_a_call(tiny, pack):
    """Logits, pools and rings of a call of several rows against the same
    chunks prefilled a call each. A prefix under a row of this model has no
    ring to restore, so its row attends as if the window began at its
    start in both arms; the full layers read the shared pages."""
    from ray_tpu.models import mimo_v2 as dec

    cfg, params, _ = tiny
    pages, prior, rows = MIMO_PACKS[pack]
    assert len({seq for seq, _, n in rows if n}) == sum(1 for _, _, n in rows if n)
    out = packed_against_single(dec, cfg, params, B=16, P=64, pages=pages, prior=prior,
                                rows=rows)
    assert_the_same(*out)


def test_a_row_of_no_length_writes_no_ring_and_no_page_of_its_own(tiny):
    """A call whose rows all have no length, as the engine compiles its
    programs before it reports ready: the rings and every page but the
    scratch page are as they were."""
    import jax.numpy as jnp

    from ray_tpu.models import mimo_v2 as dec

    cfg, params, _ = tiny
    B, R, P = 16, 4, 64
    fill = lambda c: type(c)(tuple(a + 1 for a in c.layers), c.page_tokens)
    k, v = map(fill, dec.init_paged_cache(cfg, 9, B, 4))
    zeros = jnp.zeros((R,), jnp.int32)
    logits, k2, v2 = dec.prefill_paged(
        cfg, params, jnp.zeros((R, P), jnp.int32), zeros, zeros, k, v,
        jnp.zeros((R, cfg.n_positions // B), jnp.int32), zeros)
    assert logits.shape == (R, cfg.vocab_size) and bool(jnp.isfinite(logits).all())
    for s, a in zip(dec.cache_spec(cfg), k2.layers + v2.layers):
        kept = a if s["kind"] == "window" else a[1:]
        assert bool((kept == 1).all())


def test_a_call_of_rows_as_served_agrees_to_bfloat16s_rounding(twin):
    """As served, in bfloat16: tokens do not mix in the expert layer and
    rows do not mix in attention, so a call of rows differs from a call a
    row by the rounding of products of another shape and no more (a pair
    dropped from the sorted buffer, sized by the call's tokens, would move
    a token by an expert's whole output)."""
    from ray_tpu.models import mimo_v2 as dec

    _, mcfg, params = twin
    pages, prior, rows = MIMO_PACKS["rows_of_different_start"]
    got, want, packed, single = packed_against_single(
        dec, mcfg, params, B=16, P=64, pages=pages, prior=prior, rows=rows)
    assert float(np.std(got)) > 0.3
    assert max(np.abs(got[r] - w).max() for r, w in want.items()) < 0.1
    assert max(np.abs(a - b).max() for a, b in zip(packed, single)) < 0.1
