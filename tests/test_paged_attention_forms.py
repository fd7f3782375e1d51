"""Decode over paged KV attends over the pool layer itself under an
ownership mask (``models/gpt2_decode._decode_paged_impl``). Held here
against a plain per-row gather of every row's virtual context, which is
what the program did before and is kept below as the reference: same
logits for live rows, same pools outside the scratch page, over the
table shapes an engine produces; and a structural guard that the
per-row gather (a tensor of rows x max_pages x page_tokens positions of
K/V) cannot come back into the lowered program unnoticed.

The pools are stored in the shape ``init_paged_cache`` decides
(``gpt2_decode.PagePool``: positions minor, so that the device keeps
them in the layout the layer loops use), and nothing here knows it. The
reference below keeps the plain ``[L, N, B, H, Dh]``; ``_stored`` and
``_plain`` convert between the two through ``write_pages`` and
``read_pages``, as an engine does. Held here
too: prefill at ``start > 0`` over pages another row wrote and K-chunk
decode of rows sharing them, against the full forward; the two page
functions' round trip; and, compiled for a described v5e at gpt2-xl's
serving shapes, that no serving program copies a whole pool, and at
MiMo-V2.5's that decode's attention relays no gathered span and no ring."""

import json
import os
import re
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2, gpt2_decode as dec
from tools import aot_serving_programs as aot

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = gpt2.CONFIGS["gpt2-tiny"]  # n_positions 128: 16 pages of 8
B = 8
MAX_PAGES = CFG.n_positions // B


def _gather_reference(cfg, params, last_tokens, lengths, cache_k, cache_v,
                      page_tables):
    """The per-row gather: every row's K/V gathered through its table
    into ``[S, max_pages * B, H, Dh]``, masked by ``arange(T) <= pos``."""
    dt = cfg.dtype
    S = last_tokens.shape[0]
    T = page_tables.shape[1] * cache_k.shape[2]
    pos = jnp.clip(lengths, 0, T - 1)
    x = (params["wte"].astype(dt)[last_tokens][:, None]
         + params["wpe"].astype(dt)[pos][:, None])
    mask = jnp.arange(T)[None] <= pos[:, None]
    page_of = page_tables[jnp.arange(S), pos // cache_k.shape[2]]
    off = pos % cache_k.shape[2]
    for l in range(cfg.n_layer):
        layer = jax.tree_util.tree_map(lambda a: a[l], params["blocks"])
        h = gpt2._layernorm(x, layer["ln1"]["scale"], layer["ln1"]["bias"])
        q, k, v = dec._qkv(h, layer, cfg)
        cache_k = cache_k.at[l, page_of, off].set(k[:, 0].astype(dt))
        cache_v = cache_v.at[l, page_of, off].set(v[:, 0].astype(dt))
        ck_l = cache_k[l][page_tables].reshape(S, T, cfg.n_head, cfg.head_dim)
        cv_l = cache_v[l][page_tables].reshape(S, T, cfg.n_head, cfg.head_dim)
        scores = jnp.einsum("shn,sthn->sht", q[:, 0], ck_l) / cfg.head_dim ** 0.5
        scores = jnp.where(mask[:, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dt)
        att = jnp.einsum("sht,sthn->shn", probs, cv_l)[:, None]
        x = dec._proj_mlp(x, att, layer, cfg)
    x = gpt2._layernorm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    logits = jnp.einsum("sd,vd->sv", x[:, 0].astype(dt), params["wte"].astype(dt),
                        preferred_element_type=jnp.float32)
    return logits[:, : cfg.vocab_size], cache_k, cache_v


def _stored(cfg, plain_k, plain_v):
    """Plain ``[L, N, B, H, Dh]`` arrays as the pools ``init_paged_cache``
    would hold them."""
    n, b = plain_k.shape[1:3]
    ck, cv = dec.init_paged_cache(cfg, n, b)
    return dec.write_pages(jnp.asarray(plain_k), jnp.asarray(plain_v), ck, cv,
                           jnp.arange(n))


def _plain(cfg, ck, cv, n):
    """Stored pools of ``n`` pages as float32 ``[L, N, B, H, Dh]`` arrays,
    through ``read_pages`` of every page."""
    return tuple(np.asarray(a, np.float32)
                 for a in dec.read_pages(cfg, ck, cv, jnp.arange(n)))


def _form(pools):
    """Shape and type of every array the pools hold."""
    return jax.tree.map(lambda a: (a.shape, a.dtype), pools)


def _tables(rows_pages):
    t = np.zeros((len(rows_pages), MAX_PAGES), np.int32)
    for r, pages in enumerate(rows_pages):
        t[r, : len(pages)] = pages
    return t


# name -> (pages of each row in table order, length of each row, pool pages)
CASES = {
    "unequal_lengths": ([[1, 2, 3], [4], [5, 6]], [20, 3, 13], 7),
    "shared_prefix_pages": ([[1, 2, 3], [1, 2, 4]], [19, 22], 5),
    "pos_first_slot_of_a_page": ([[1, 2], [3, 4, 5]], [8, 16], 6),
    "pos_last_slot_of_a_page": ([[1], [2, 3]], [7, 15], 4),
    "full_context": ([list(range(1, 17)), [17, 18]], [127, 9], 19),
    "inactive_rows_between_live": ([[], [2, 1], [], [3], []], [0, 11, 0, 5, 0], 4),
    # pages 4..8 belong to nobody and hold noise, as a pool does once
    # retired sequences have left their K/V behind
    "noise_in_unowned_pages": ([[1, 2], [3]], [12, 6], 9),
}


@pytest.fixture(scope="module")
def params():
    return gpt2.init(jax.random.PRNGKey(0), CFG)


@pytest.mark.parametrize("case", sorted(CASES))
def test_pool_form_equals_the_per_row_gather(case, params):
    rows_pages, lengths, num_pages = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    tables = _tables(rows_pages)
    S = len(lengths)
    shape = (CFG.n_layer, num_pages, B, CFG.n_head, CFG.head_dim)
    # every page holds K/V of the model's own scale; pages nobody owns
    # hold large finite noise in the noise case
    pool = rng.normal(0.0, 1.0, (2,) + shape).astype(np.float32)
    if case == "noise_in_unowned_pages":
        pool[:, :, 4:] *= 1e4
    ck, cv = (jnp.asarray(p, CFG.dtype) for p in pool)
    last = jnp.asarray(rng.integers(0, CFG.vocab_size, S), jnp.int32)
    head = (CFG, params, last, jnp.asarray(lengths, jnp.int32))
    sk, sv = _stored(CFG, ck, cv)
    form = _form((sk, sv))
    got, gk, gv = jax.jit(dec._decode_paged_impl, static_argnums=0)(
        *head, sk, sv, jnp.asarray(tables)
    )
    # the pools come back as they went in
    assert _form((gk, gv)) == form
    gk, gv = _plain(CFG, gk, gv, num_pages)
    want, wk, wv = jax.jit(_gather_reference, static_argnums=0)(
        *head, ck, cv, jnp.asarray(tables)
    )
    live = [r for r, pages in enumerate(rows_pages) if pages]
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    spread = float(want[live].std())
    assert 0.05 < spread < 0.5  # the tolerance below is set against this
    assert np.abs(got[live] - want[live]).max() < 1e-2
    # outside the scratch page (inactive rows write other junk there) the
    # pools are equal, to bfloat16's rounding of what was written: the
    # step wrote one position a live row and touched nothing else
    for a, b in ((gk, wk), (gv, wv)):
        np.testing.assert_allclose(
            np.asarray(a[:, 1:], np.float32), np.asarray(b[:, 1:], np.float32),
            rtol=0, atol=1e-2,
        )
    untouched = np.ones(shape[1:3], bool)
    untouched[0] = False
    for r in live:
        untouched[tables[r, lengths[r] // B], lengths[r] % B] = False
    for a, b in ((gk, ck), (gv, cv)):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32)[:, untouched],
            np.asarray(b, np.float32)[:, untouched],
        )


def test_lowered_decode_holds_no_per_row_gather():
    """At gpt2-xl's serving shapes (S 24, N 97, B 64, 16 pages a row) the
    lowered program holds no K/V tensor of S x max_pages x B positions:
    ``[384,64,...]`` or ``[24,1024,...]`` in any element type."""
    cfg = gpt2.CONFIGS["gpt2-xl"]
    S, N, Bx, mp = 24, 97, 64, 16
    sds = jax.ShapeDtypeStruct
    p = jax.eval_shape(lambda: gpt2.init(jax.random.PRNGKey(0), cfg))
    ck, cv = jax.eval_shape(lambda: dec.init_paged_cache(cfg, N, Bx))
    text = jax.jit(dec._decode_paged_impl, static_argnums=0).lower(
        cfg, p, sds((S,), jnp.int32), sds((S,), jnp.int32), ck, cv,
        sds((S, mp), jnp.int32),
    ).as_text()
    pool = "x".join(map(str, jax.tree.leaves(ck)[0].shape))
    assert f"tensor<{pool}x" in text  # the pool is there
    # every row's context of one layer is this many elements of K or V,
    # in whatever order a gather would lay them
    row_contexts = S * mp * Bx * cfg.n_head * cfg.head_dim
    gathered = [
        t for t in set(re.findall(r"tensor<((?:\d+x)+)\w+>", text))
        if np.prod([int(d) for d in t.split("x")[:-1]]) == row_contexts
    ]
    assert not gathered, sorted(gathered)


def test_write_pages_then_read_pages_returns_the_blocks_to_the_bit():
    """The two functions that convert between the wire's blocks
    ``[L, n, B, H, Dh]`` and the stored pool are each other's inverse,
    touch the named pages only, and hand back the stored shape."""
    rng = np.random.default_rng(5)
    N, pages = 7, np.asarray([5, 1, 3], np.int32)
    ck, cv = dec.init_paged_cache(CFG, N, B)
    form = _form((ck, cv))
    blocks = rng.normal(0, 1, (2, CFG.n_layer, len(pages), B, CFG.n_head,
                               CFG.head_dim)).astype(np.float32)
    kb, vb = (jnp.asarray(b, CFG.dtype) for b in blocks)
    ck, cv = dec.write_pages(kb, vb, ck, cv, jnp.asarray(pages))
    assert _form((ck, cv)) == form
    rk, rv = dec.read_pages(CFG, ck, cv, jnp.asarray(pages))
    assert rk.shape == kb.shape and rk.dtype == CFG.dtype
    np.testing.assert_array_equal(np.asarray(rk, np.float32),
                                  np.asarray(kb, np.float32))
    np.testing.assert_array_equal(np.asarray(rv, np.float32),
                                  np.asarray(vb, np.float32))
    others = np.setdiff1d(np.arange(N), pages)
    for plain in _plain(CFG, ck, cv, N):
        assert not plain[:, others].any()


@pytest.mark.parametrize("K", [1, 4, 8])
def test_tail_prefill_over_shared_pages_then_k_chunks(K, params):
    """The chat cell's path: row A prefills a whole prompt; row B, whose
    prompt starts with the same two pages, points its table at A's and
    prefills only its tail at ``start = 16``; both then decode in chunks
    of K steps (``decode_multi_paged``) beside an inactive row. B's
    prefill logits are the full forward's at its last position, and
    every token of both rows is the full forward's greedy token."""
    from _llm_reference import greedy_reference

    rng = np.random.default_rng(40 + K)
    n_new = 8
    prefix = [int(t) for t in rng.integers(0, CFG.vocab_size, 2 * B)]
    prompt_a = prefix + [int(t) for t in rng.integers(0, CFG.vocab_size, 5)]
    prompt_b = prefix + [int(t) for t in rng.integers(0, CFG.vocab_size, 9)]
    # pages: 1, 2 shared; A goes on in 3, 4; B in 5, 6; row 1 is inactive
    tables = _tables([[1, 2, 3, 4], [], [1, 2, 5, 6]])
    ck, cv = dec.init_paged_cache(CFG, 7, B)

    def prefill(tokens, start, width, ck, cv, table):
        tok = np.zeros((1, width), np.int32)
        tok[0, : len(tokens)] = tokens
        return dec.prefill_paged(
            CFG, params, jnp.asarray(tok), jnp.int32(start),
            jnp.int32(len(tokens)), ck, cv, jnp.asarray(table),
        )

    la, ck, cv = prefill(prompt_a, 0, 32, ck, cv, tables[0])
    lb, ck, cv = prefill(prompt_b[2 * B:], 2 * B, 16, ck, cv, tables[2])
    tok = np.zeros((1, CFG.n_positions), np.int32)
    tok[0, : len(prompt_b)] = prompt_b
    full = gpt2.forward(params, jnp.asarray(tok), CFG)[0, len(prompt_b) - 1]
    want = np.asarray(full[: CFG.vocab_size], np.float32)
    assert 0.05 < want.std() < 0.5
    assert np.abs(np.asarray(lb, np.float32) - want).max() < 2e-2
    firsts = [int(jnp.argmax(la)), 0, int(jnp.argmax(lb))]
    out = {0: [firsts[0]], 2: [firsts[2]]}
    last = jnp.asarray(firsts, jnp.int32)
    lens = jnp.asarray([len(prompt_a), 0, len(prompt_b)], jnp.int32)
    S = 3
    for c in range(n_new // K):
        toks, last, lens, ck, cv = dec.decode_multi_paged(
            CFG, params, last, lens, ck, cv, jnp.asarray(tables),
            jnp.zeros((S,), jnp.float32), jnp.ones((S,), bool),
            jax.random.PRNGKey(0), K, jnp.int32(c * K),
        )
        for row in out:
            out[row] += [int(t) for t in np.asarray(toks)[:K, row]]
        assert toks.shape == (dec.MAX_DECODE_CHUNK, S)
        assert not np.asarray(toks)[K:].any()  # only K rows were written
        # the inactive row owns nothing and must stay where it was
        last = last.at[1].set(0)
        lens = lens.at[1].set(0)
    assert out[0] == greedy_reference(CFG, params, prompt_a, 1 + n_new)
    assert out[2] == greedy_reference(CFG, params, prompt_b, 1 + n_new)


# -- the compiled programs, for a chip that is described and not attached --


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def time_limit():
    """The three compiles take under a minute together on the CPU; a
    compiler that hangs fails this test instead of the whole run."""
    def over(signum, frame):
        raise TimeoutError("compiling the serving programs took over 300 s")

    old = signal.signal(signal.SIGALRM, over)
    signal.alarm(300)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


@pytest.fixture
def kernels_for_the_chip(monkeypatch):
    """Pallas kernels are lowered for the chip the test compiles for, not
    interpreted as this process's own backend, the CPU, would have them."""
    from ray_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret", lambda: False)


def test_compiled_serving_programs_copy_no_whole_pool(one_chip, time_limit):
    """At gpt2-xl's serving shapes (S 24, 97 pages of 64, the weights as
    an engine holds them) none of the three programs, as the v5e's
    compiler writes them, holds a ``copy`` whose result is a whole page
    pool: the pools enter in the layout the layer loops scatter into and
    read. Stored ``[L, N, B, H, Dh]`` each held four (two pools relaid
    on the way in, two on the way out: 27 ms of a 45 ms step on the
    chip, PERF.md PR 35) and 4.88 GB of temporaries for them. Nor a
    copy of one layer of a pool, which is what the decode loops held
    with the width minor (23 ms a step)."""
    cfg = gpt2.CONFIGS["gpt2-xl"]
    S, N, Bx, mp = 24, 97, 64, 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    stored = jax.eval_shape(lambda: dec.init_paged_cache(cfg, N, Bx))[0]
    pool = jax.tree.map(lambda a: sds(a.shape, a.dtype), stored)
    whole = jax.tree.leaves(stored)[0].shape
    tree = jax.eval_shape(
        lambda: dec.serving_params(cfg, gpt2.init(jax.random.PRNGKey(0), cfg))
    )
    p = jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)
    rows = (sds((S,), jnp.int32), sds((S,), jnp.int32))
    tail = (sds((S, mp), jnp.int32), sds((S,), jnp.float32), sds((S,), jnp.bool_),
            sds((2,), jnp.uint32))
    i32 = sds((), jnp.int32)
    programs = {
        "decode_paged_and_sample": dec.decode_paged_and_sample.lower(
            cfg, p, *rows, pool, pool, *tail, i32),
        "decode_multi_paged": dec.decode_multi_paged.lower(
            cfg, p, *rows, pool, pool, *tail, i32, i32),
        "prefill_paged": dec.prefill_paged.lower(
            cfg, p, sds((1, 128), jnp.int32), i32, i32, pool, pool,
            sds((mp,), jnp.int32)),
    }
    dims = ",".join(map(str, whole))
    layer = ",".join(map(str, whole[1:]))
    for name, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        assert f"[{dims}]" in text, name  # the pool is there under that shape
        copies = re.findall(rf"= \w+\[(?:{dims}|1,{layer}|{layer})\]\S* copy\(", text)
        assert not copies, (name, copies)
        if name != "decode_multi_paged":  # its fc_out relay is 0.98 GB, known
            temps = compiled.memory_analysis().temp_size_in_bytes
            assert temps < 0.5e9, (name, temps)


@pytest.mark.parametrize("kind", ["full", "window"])
def test_compiled_mimo_decode_attention_splits_no_cached_heads(
        one_chip, time_limit, kind, kernels_for_the_chip):
    """At ``mimo-reason-decode``'s shapes (128 rows of one query, 64 heads
    of 192 over 4 K/V heads in a full layer and 8 in a window layer, V of
    128; pools of 2,049 pages of 64, eight pages a turn; rings of 128) a
    layer's decode attention, as the v5e's compiler writes it, takes K and
    V with the heads merged as ``init_paged_cache`` stores them: no
    ``copy``, ``reshape``, ``transpose`` or fusion whose result is a
    gathered span or a ring split into heads, and no ``copy`` of a ring.
    Split, 4 or 8 heads are no multiple of 8 sublanes and 192 none of 128
    lanes, and every K and V byte the step reads was written three more
    times on the way (``reshape bf16[128,256,4,192]`` alone 13.8% of the
    step on the chip; PERF.md, PR 47). A full layer's is one call of
    ``ops/paged_kv_attention.py``'s kernel since PR 58, which takes both
    pools where they lie: no loop over page-table columns and no gathered
    span of any rows is left."""
    from ray_tpu.models import mimo_v2 as m
    from ray_tpu.ops import paged_kv_attention

    cfg = m.CONFIGS["mimo-v2.5"]
    S, N, Bx, mp = 128, 2049, 64, 64
    H, Dv, W = cfg.num_attention_heads, cfg.v_head_dim, cfg.sliding_window
    l = cfg.hybrid_layer_pattern.index(kind == "window")
    Hkv = cfg.kv_heads(l)

    def sds(shape, dtype=cfg.dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    stored = jax.eval_shape(lambda: m.init_paged_cache(cfg, N, Bx, S))
    k, v = (sds(c.layers[l].shape) for c in stored)
    q = sds((S, 1, H, cfg.head_dim))
    if kind == "full":
        assert (k.shape, v.shape) == ((N, Bx, 768), (N, Bx, 512))

        def attend(q, k, v, tables, q_pos):
            walk = paged_kv_attention.page_visits(q_pos[:, 0], mp, Bx)
            return m._paged_attend(q, k, v, tables, q_pos, Hkv, walk)

        span = paged_kv_attention.page_visits(jnp.zeros((S,), jnp.int32), mp, Bx).span
        lowered = jax.jit(attend).lower(
            q, k, v, sds((S, mp), jnp.int32), sds((S, 1), jnp.int32))
    else:
        assert (k.shape, v.shape) == ((S, W, 1536), (S, W, 1024))
        span = W

        lowered = jax.jit(m._window_attend, static_argnums=4).lower(
            q, k, v, sds((S, 1, W), jnp.bool_), Hkv, sds((H,), jnp.float32))
    text = lowered.compile().as_text()
    split = rf"\d+,{span},{Hkv},(?:{cfg.head_dim}|{Dv})"
    relays = re.findall(
        rf"= \w+\[(?:{split})\]\S* (?:copy|reshape|transpose|fusion)\(", text)
    relays += re.findall(rf"= \w+\[{S},{W},\d+\]\S* copy\(", text)
    assert not relays, relays
    if kind == "full":
        # one call of the kernel, no loop, and nothing [.., .., stored
        # width] but the pools themselves: no turn's pages gathered
        assert aot.kernels_of(text) == {"paged_kv_attention": 1}
        assert not aot.loops_of(text)
        wide = set(re.findall(rf"= bf16\[(\d+,\d+),(?:{k.shape[2]}|{v.shape[2]})\]", text))
        assert wide <= {f"{N},{Bx}"}, wide
    else:
        # the rings are there, merged, and go into the products as they lie
        assert re.search(rf"= bf16\[{S},{W},(?:{k.shape[2]}|{v.shape[2]})\]", text)


HLO_LOOPS = {
    "a group's page loop": (
        "  %while.27 = (s32[]{:T(128)}, f32[32,32,1]{2,1,0:T(8,128)S(1)}, f32[32,32,1]{2,1,0:T(8,128)}, "
        "f32[32,32,128]{2,1,0:T(8,128)}, /*index=5*/s32[32,512]{1,0:T(8,128)}) "
        "while(%tuple.474), condition=%c, body=%b",
        {"(s32[],f32[32,32,1],..)": 1}),
    "an expert layer's loop": (
        "  %while.32 = (s32[]{:T(128)}, f32[128,2048]{1,0:T(8,128)}, s32[]{:T(128)}) "
        "while(%tuple.1), condition=%c, body=%b",
        {"(s32[],f32[128,2048],..)": 1}),
    "a loop whose carry opens otherwise, and no loop": (
        "  %while.1 = (bf16[4]{0}, s32[]) while(%t), condition=%c, body=%b\n"
        "  %fusion.3 = (s32[], f32[32,32]{1,0}) fusion(%while.27), kind=kLoop",
        {}),
}


@pytest.mark.parametrize("case", sorted(HLO_LOOPS))
def test_the_tool_names_a_loop_as_the_trace_does(case):
    """``tools/aot_serving_programs.loops_of`` reads a compiled program's
    ``while`` operations by how their carry opens, the name the chip's trace
    gives them and ``window_attn_roofline`` matches a ring's by
    (``benchmark/metrics/window_attn_roofline.json``)."""
    text, want = HLO_LOOPS[case]
    assert aot.loops_of(text + "\n" + text) == {k: 2 * v for k, v in want.items()}


HLO_KERNELS = {
    "the latent attention's, its result kept in fast memory": (
        "  %paged_latent_attention.12 = bf16[128,32,640]{2,1,0:T(8,128)(2,1)S(1)} custom-call("
        "%bitcast.221, %copy-done.89, /*index=5*/%fusion.47), "
        'custom_call_target="tpu_custom_call", operand_layout_constraints={s32[65536]{0}}',
        {"paged_latent_attention": 1}),
    "decode's attention over a K pool and a V pool: a layer's pages, a layer's ring": (
        "  %paged_kv_attention.7 = f32[128,40,128]{2,1,0:T(8,128)} custom-call(%bitcast.9, "
        "%fusion.12, /*index=5*/%param.3, %param.4), "
        'custom_call_target="tpu_custom_call", operand_layout_constraints={s32[32768]{0}}\n'
        "  %ring_kv_attention.3 = f32[128,40,128]{2,1,0:T(8,128)} custom-call(%bitcast.2, "
        '%bitcast.5, %bitcast.6), custom_call_target="tpu_custom_call"',
        {"paged_kv_attention": 1, "ring_kv_attention": 1}),
    "an expert layer's two": (
        "  %grouped_matmul.2 = bf16[768,768]{1,0:T(8,128)(2,1)} custom-call(%max.4, %g.1, %u.1), "
        'custom_call_target="tpu_custom_call"\n'
        "  ROOT %grouped_matmul = f32[768,2048]{1,0:T(8,128)} custom-call(%max.4, %d.1), "
        'custom_call_target="tpu_custom_call"',
        {"grouped_matmul": 2}),
    "another custom call, and a fusion of a kernel's result": (
        '  %custom-call.1 = s32[8192]{0} custom-call(%p), custom_call_target="AssumeGatherIndicesInBound"\n'
        "  %fusion.3 = f32[768,2048]{1,0} fusion(%grouped_matmul.3), kind=kLoop",
        {}),
}


@pytest.mark.parametrize("case", sorted(HLO_KERNELS))
def test_the_tool_names_a_kernel_as_the_trace_does(case):
    """``tools/aot_serving_programs.kernels_of`` reads a compiled program's
    Pallas calls by the kernel's name, which is the operation's name in the
    chip's trace and what ``mla_paged_roofline`` and ``moe_gmm_roofline``
    match (``paged_kv_attention`` and ``ring_kv_attention`` are the names a
    roofline of PR 58's kernel would match: PERF.md §7)."""
    text, want = HLO_KERNELS[case]
    assert aot.kernels_of(text + "\n" + text) == {k: 2 * v for k, v in want.items()}


def test_compiled_latent_programs_copy_no_pool_and_decode_builds_no_head(
        one_chip, time_limit, kernels_for_the_chip):
    """At ``kanana-agent-sessions``' shapes (128 rows, five pools of 16,385
    pages of 64 latent rows, 512 page-table columns, a prefill chunk of
    512; the weights as an engine holds them) none of the three programs,
    as the v5e's compiler writes them, holds a ``copy`` of a layer's latent
    pool (6.7 GB in all: one copy of a layer does not fit beside it, and
    with the row left 576 wide the compiler laid the pools out pages-minor
    and a decode step held 20; PERF.md, PR 48), the pools enter in the
    layout ``init_paged_cache`` wrote, and the decode programs hold no K
    or V a head of a cached span: decode attends in the latent space, in
    one call of ``ops/paged_latent_attention.py``'s kernel a layer, which
    takes the pool where it lies: no operation's result is a turn's pages
    gathered, and no loop over page-table columns is left."""
    from unittest import mock

    from ray_tpu.models import deepseek_v3 as m
    from ray_tpu.ops import flash_attention as fa

    cfg = m.CONFIGS["kanana-2-30b-a3b"]
    S, N, Bx, mp = 128, 16385, 64, 512

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    stored, none = jax.eval_shape(lambda: m.init_paged_cache(cfg, N, Bx, S))
    whole = stored.layers[0].shape
    assert whole == (N, Bx, 640) and not jax.tree.leaves(none)
    pool, none = on_chip(stored), on_chip(none)
    p = on_chip(jax.eval_shape(lambda: m.load_serving_params(cfg)))
    rows = (sds((S,), jnp.int32), sds((S,), jnp.int32))
    tail = (sds((S, mp), jnp.int32), sds((S,), jnp.float32), sds((S,), jnp.bool_),
            sds((2,), jnp.uint32))
    i32 = sds((), jnp.int32)

    def compiled(lower):
        with mock.patch.object(fa, "_interpret", lambda: False):  # for the chip
            c = lower().compile()
        return c.as_text(), c.memory_analysis().temp_size_in_bytes

    programs = {
        # these very shapes: compiled once for this file's tests
        "decode_paged_and_sample": lambda: cell_program(
            m, "kanana-2-30b-a3b", one_chip, "decode"),
        "decode_multi_paged": lambda: compiled(lambda: m.decode_multi_paged.lower(
            cfg, p, *rows, pool, none, *tail, i32, i32)),
        "prefill_paged": lambda: compiled(lambda: m.prefill_paged.lower(
            cfg, p, sds((1, 512), jnp.int32), i32, i32, pool, none, sds((mp,), jnp.int32))),
    }
    dims = ",".join(map(str, whole))
    H, sizes = cfg.num_attention_heads, f"(?:{cfg.qk_nope_head_dim}|{cfg.qk_head_dim})"
    a_head = rf"\d+,(?:\d{{2,}},{H}|{H},\d{{2,}}),{sizes}"
    for name, program in programs.items():
        text, temps = program()
        entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text).group(1)
        assert f"bf16[{dims}]{{2,1,0:" in entry, name  # as written, the row minor
        copies = re.findall(rf"= \w+\[{dims}\]\S* copy\(", text)
        assert not copies, (name, copies)
        assert temps < 0.5e9, name
        if name.startswith("decode"):
            heads = re.findall(rf"= \w+\[{a_head}\]\S* [\w\-]+\(", text)
            assert not heads, (name, heads[:3])
            # one call of the kernel a layer beside the expert layers' two,
            # under the name ``mla_paged_roofline`` knows it by
            experts = cfg.n_layer - cfg.first_k_dense_replace
            assert aot.kernels_of(text) == {
                "paged_latent_attention": cfg.n_layer, "grouped_matmul": 2 * experts}, name
            with open(os.path.join(ROOT, "benchmark/metrics/mla_paged_roofline.json")) as f:
                named = json.load(f)["args"]["ops"]
            assert re.search(named, "paged_latent_attention.11"), named
            # the loops over page-table columns are gone, and what
            # ``mla_roofline`` knew them by matches nothing
            with open(os.path.join(ROOT, "benchmark/metrics/mla_roofline.json")) as f:
                old = json.load(f)["args"]["ops"]
            loops = aot.loops_of(text)
            assert not [k for k in loops if re.search(old, f"while {k} 1in")], (name, loops)
            # nothing [.., .., stored width] but the queries a head and the
            # pools: no turn's pages gathered, as [rows, positions, width]
            # or as [rows x pages, page, width]
            rows_wide = set(re.findall(rf"= bf16\[(\d+,\d+),{whole[2]}\]", text))
            assert rows_wide <= {f"{S},{H}", f"{N},{Bx}"}, (name, rows_wide)


_COMPILED = {}


def cell_program(m, model_id, one_chip, which):
    """``decode`` (``decode_paged_and_sample``) or ``prefill`` (the largest
    prefill call of several rows) of a served MoE model at its cell's shapes
    (128 decode rows, 32 sequences' worth of pages of 64, the weights as an
    engine holds them), as the v5e's compiler writes it: (its text, its
    temporaries' bytes). Compiled once a process, with the Pallas kernels
    lowered for the chip and not interpreted."""
    from unittest import mock

    from ray_tpu.ops import flash_attention as fa

    if (model_id, which) in _COMPILED:
        return _COMPILED[model_id, which]
    cfg = m.CONFIGS[model_id]
    S, Bx = 128, 64
    mp = -(-cfg.n_positions // Bx)
    R, P = m.PREFILL_ROWS[-1], m.PREFILL_ROW_WIDTHS[-1]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    k, v = on_chip(jax.eval_shape(lambda: m.init_paged_cache(cfg, 32 * mp + 1, Bx, S)))
    p = on_chip(jax.eval_shape(lambda: m.load_serving_params(cfg)))
    with mock.patch.object(fa, "_interpret", lambda: False):
        if which == "decode":
            lowered = m.decode_paged_and_sample.lower(
                cfg, p, sds((S,), jnp.int32), sds((S,), jnp.int32), k, v,
                sds((S, mp), jnp.int32), sds((S,), jnp.float32), sds((S,), jnp.bool_),
                sds((2,), jnp.uint32), sds((), jnp.int32))
        else:
            a_row = sds((R,), jnp.int32)
            lowered = m.prefill_paged.lower(
                cfg, p, sds((R, P), jnp.int32), a_row, a_row, k, v,
                sds((R, mp), jnp.int32), a_row)
        compiled = lowered.compile()
    _COMPILED[model_id, which] = compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes
    return _COMPILED[model_id, which]


def largest_prefill_of_rows(m, model_id, one_chip, family):
    """The largest prefill call of several rows of a served model at its
    cell's shapes, as the v5e's compiler writes it: (the config, what the
    tool counts in its operations by label, its loops, its temporaries'
    bytes)."""
    cfg = m.CONFIGS[model_id]
    text, temps = cell_program(m, model_id, one_chip, "prefill")
    ops = [ln for ln in text.splitlines() if re.match(r"\s*(ROOT )?%\S+ = ", ln)]
    stored = jax.eval_shape(
        lambda: m.init_paged_cache(cfg, 32 * -(-cfg.n_positions // 64) + 1, 64, 128))
    _, watch, _ = family(cfg, m, stored[0], None)
    return cfg, {label: count(ops) for label, count in watch}, aot.loops_of(text), temps


def test_compiled_latent_prefill_of_rows_copies_no_pool(one_chip, time_limit):
    """Kanana's prefill call of four rows of 512 (PR 50) beside 13 GB of
    weights and pools: no copy of a layer's latent pool, temporaries under
    half a gigabyte (0.24 GB; a call of one row 0.03), an attention loop a
    row a layer, each stopping behind its own row, and ONE expert loop a
    layer over the 2,048 tokens of all rows (since PR 55 the scatter-add
    behind the grouped-product kernel's two calls, one turn where every
    expert is held): the expert layers read their weights once a call."""
    from ray_tpu.models import deepseek_v3 as m

    cfg, counted, loops, temps = largest_prefill_of_rows(
        m, "kanana-2-30b-a3b", one_chip, aot.deepseek_v3_family)
    assert (m.PREFILL_ROWS[-1], m.PREFILL_ROW_WIDTHS[-1]) == (4, 512)
    assert counted["latent-pool copies"] == 0, counted
    assert temps < 0.5e9, temps
    H, D = cfg.num_attention_heads, cfg.hidden_size
    assert loops == {f"(s32[],f32[{H},512],..)": 4 * cfg.n_layer,
                     f"(s32[],f32[2048,{D}],..)": cfg.n_layer - cfg.first_k_dense_replace}, loops


def test_compiled_mimo_prefill_of_rows_copies_no_pool_and_no_ring(one_chip, time_limit):
    """MiMo's prefill call of two rows of 512 (PR 50): no copy of a full
    layer's pool, none of a window layer's rings (the rows' rings are
    gathered, and written back through a scatter that drops the rows of no
    length), temporaries under half a gigabyte, one loop over the rows'
    pages a full layer and one expert loop a layer over all 1,024 tokens
    (since PR 55 the scatter-add behind the kernel's two calls, in blocks
    that stop after the last pair)."""
    from ray_tpu.models import mimo_v2 as m

    cfg, counted, loops, temps = largest_prefill_of_rows(
        m, "mimo-v2.5", one_chip, aot.mimo_v2_family)
    assert (m.PREFILL_ROWS[-1], m.PREFILL_ROW_WIDTHS[-1]) == (2, 512)
    assert counted["whole-pool copies"] == 0 and counted["ring copies"] == 0, counted
    assert temps < 0.5e9, temps
    full = cfg.hybrid_layer_pattern.count(0)
    assert loops == {f"(s32[],f32[2,{cfg.num_attention_heads},512],..)": full,
                     f"(s32[],f32[1024,{cfg.hidden_size}],..)": sum(cfg.moe_layer_freq)}, loops


def test_compiled_trinity_programs_copy_no_pool_and_no_ring(one_chip, time_limit):
    """Trinity's decode step and its largest prefill call at the cell's
    shapes (PR 53), beside 8.5 GB of weights, 2.15 GB of rings and 1.07 GB
    of pool: no copy of the full layer's pool and none of a ring, neither
    as it is stored ``[128, 2048, 512]`` nor as decode reads it in blocks
    ``[512, 512, 512]`` (a ring is 0.27 GB for K alone: one copy a layer
    would be a tenth of a step); decode holds one call of
    ``ops/paged_kv_attention.py``'s kernel a sliding layer over its ring's
    blocks where they lie (``ring_kv_attention``) and one in the full layer
    over its pages (``paged_kv_attention``), and since PR 58 no loop over
    either and no gathered span; an expert layer holds one loop, the
    scatter-add behind the kernel's two calls (PR 55). Prefill keeps its
    loops."""
    from ray_tpu.models import afmoe as m

    cfg, counted, loops, temps = largest_prefill_of_rows(
        m, "trinity-mini", one_chip, aot.mimo_v2_family)
    assert (m.PREFILL_ROWS[-1], m.PREFILL_ROW_WIDTHS[-1]) == (2, 512)
    assert counted["whole-pool copies"] == 0 and counted["ring copies"] == 0, counted
    assert temps < 0.5e9, temps
    sliding = sum(cfg.sliding(l) for l in range(cfg.n_layer))
    H, D = cfg.num_attention_heads, cfg.hidden_size
    # a loop over the ring's blocks a sliding layer and one over the rows' pages
    # in the full layer, both carrying [rows, heads, chunk]; an expert loop a layer
    assert loops == {f"(s32[],f32[2,{H},512],..)": cfg.n_layer,
                     f"(s32[],f32[1024,{D}],..)": cfg.n_layer - cfg.num_dense_layers}, loops

    text, temps = cell_program(m, "trinity-mini", one_chip, "decode")
    ops = [ln for ln in text.splitlines() if re.match(r"\s*(ROOT )?%\S+ = ", ln)]
    stored = jax.eval_shape(
        lambda: m.init_paged_cache(cfg, 32 * (cfg.n_positions // 64) + 1, 64, 128))
    _, watch, _ = aot.mimo_v2_family(cfg, m, stored[0], None)
    counted = {label: count(ops) for label, count in watch}
    assert counted == {"whole-pool copies": 0, "ring copies": 0, "K/V split into heads": 0}, counted
    assert temps < 0.3e9
    assert aot.loops_of(text) == {
        f"(s32[],f32[128,{D}],..)": cfg.n_layer - cfg.num_dense_layers}
    N = 32 * (cfg.n_positions // 64) + 1
    assert_decode_attends_in_the_kernel(
        text, pages=cfg.n_layer - sliding, rings=sliding,
        stored={f"{N},64", "128,2048", "512,512"},
        width=cfg.num_key_value_heads * cfg.head_dim)


MOE_CELLS = {"kanana-2-30b-a3b": "deepseek_v3", "mimo-v2.5": "mimo_v2", "trinity-mini": "afmoe"}


@pytest.mark.parametrize("which", ["decode", "prefill"])
@pytest.mark.parametrize("model_id", list(MOE_CELLS))
def test_compiled_expert_layers_are_the_kernel_and_copy_no_stack(
        one_chip, time_limit, model_id, which):
    """The decode step and the largest prefill call of the three MoE
    families at their cells' shapes, as the v5e's compiler writes them
    (PR 55): no ``ragged-dot``; two calls of the grouped-product kernel an
    expert layer (gate and up as one, down), which Mosaic accepted with
    the blocks ``ops/grouped_matmul.py`` cuts at these widths inside the
    VMEM the call asks for, itself under the chip's 128 MiB; and no expert
    stack written anew in any layout: a stack relaid for the kernel would
    cost its 0.4 to 0.8 GB a call."""
    import importlib

    from ray_tpu.ops import grouped_matmul as gm

    m = importlib.import_module(f"ray_tpu.models.{MOE_CELLS[model_id]}")
    cfg = m.CONFIGS[model_id]
    text, _ = cell_program(m, model_id, one_chip, which)
    tree = jax.eval_shape(lambda: m.load_serving_params(cfg))
    expert_layers = sum("moe" in layer for layer in tree["layers"])
    assert expert_layers and aot.expert_layer_counts(text, tree) == {
        "ragged-dot": 0, "kernel calls": 2 * expert_layers, "expert-stack copies": 0}
    # the trace names an operation by its instruction: what
    # ``moe_gmm_roofline`` times is every call of this kernel and nothing
    # else (the latent family's decode holds its attention's kernel too)
    calls = re.findall(r"%(\S+) = \S+ custom-call\(.*custom_call_target=\"tpu_custom_call\"", text)
    with open(os.path.join(ROOT, "benchmark/metrics/moe_gmm_roofline.json")) as f:
        named = json.load(f)["args"]["ops"]
    calls = [c for c in calls if re.search(named, c)]
    assert len(calls) == 2 * expert_layers == aot.kernels_of(text)["grouped_matmul"], calls
    others = re.findall(r"%(\S+) = \S+ (?!custom-call)[\w\-]+\(", text)
    assert not [o for o in others if re.search(named, o)]
    _, K, N = tree["layers"][-1]["moe"]["gate"].shape
    for k, n, matrices, out in ((K, N, 2, 2), (N, K, 1, 4)):
        tn = gm._column_tile(k, n, matrices, 2)
        assert n % tn == 0 and tn % 128 == 0
        assert gm._vmem_bytes(128, k, tn, matrices, 2, out) < 64 << 20


@pytest.mark.parametrize(
    "shape", [(32, 1024, 12, 64), (4, 1024, 25, 64)], ids=["two-heads-a-block", "whole-row-of-25"]
)
def test_compiled_flash_kernels_fit_the_chip(one_chip, time_limit, shape, monkeypatch):
    """The four flash calls as the v5e's compiler takes them, at the
    training cells' shape and at gpt2-xl's 25 heads of 64, whose blocks
    are the whole 1600-lane row: Mosaic accepts the tiles each kernel cuts
    and the blocks fit the VMEM a kernel is given, which the interpreter
    cannot tell."""
    from ray_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    text = jax.jit(jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v, True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2),
    )).lower(x, x, x).compile().as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 4


def test_compiled_train_step_copies_no_attention_operand(one_chip, time_limit, monkeypatch):
    """Two layers of the training cells' step at their widths (batch 32,
    1024 positions, 12 heads of 64), as the v5e's compiler writes it: no
    ``copy`` of an array the size of q stands around the flash kernels.
    With q, k, v as ``[B, T, H, Dh]`` results of the projection the
    compiler lays them out positions-minor (Dh 64 fills half a tile) and
    copies each operand and result of every kernel, 8% of the step on the
    chip (PERF.md, PR 45); the projections keep ``H*Dh`` merged so that
    it does not."""
    import dataclasses

    import optax

    from ray_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    cfg = dataclasses.replace(
        gpt2.CONFIGS["gpt2-small"], n_layer=2, attn_impl="flash", remat=False,
        scan_unroll=2, loss_impl="fused", loss_chunk=256,
    )
    opt = optax.adamw(3e-4)
    params = jax.eval_shape(lambda: gpt2.init(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(opt.init, params)
    put = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    tokens = jax.ShapeDtypeStruct((32, cfg.n_positions + 1), jnp.int32, sharding=one_chip)
    text = jax.jit(gpt2.make_train_step(cfg, opt)).lower(
        put(params), put(state), tokens).compile().as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 8
    q = r"32,1024,768|32,1024,12,64|32,12,1024,64|384,1024,64"
    copies = re.findall(rf"= (?:bf16|f32)\[(?:{q})\]\S* copy\(", text)
    assert not copies, copies


def test_compiled_phi4flash_programs_copy_no_pool_no_ring_and_no_state(one_chip, time_limit):
    """Phi-4-mini-flash's decode step and its largest prefill call at the
    cell's shapes (PR 57), beside 7.7 GB of weights, 2.68 GB of rings, 2.68
    GB of pool and 0.41 GB of states: no copy of the full layer's pool,
    none of a ring (stored ``[128, 512, 1280]`` or read in blocks ``[512,
    128, 1280]``) and none of a Mamba layer's state ``[128, 16, 5120]`` (read
    and written every step: a relay would double its bytes). Decode holds
    one call of ``ops/paged_kv_attention.py``'s kernel a window layer, over
    the ring's blocks where they lie (``ring_kv_attention``), and one in
    EACH of the eight layers that attend over layer 17's pages
    (``paged_kv_attention``): since PR 58 no loop over page-table
    columns or ring blocks, and no turn's pages or blocks gathered for any
    group of rows; what ``shared_kv_roofline`` and ``window_attn_roofline``
    knew the loops by matches nothing. Prefill keeps its loops: a scan a
    Mamba layer, a loop a window layer and one in the full layer over the
    chunk, and one a cross layer over one position a row."""
    from benchmark import trace as trace_mod
    from ray_tpu.models import phi4flash as m

    cfg, counted, loops, temps = largest_prefill_of_rows(
        m, "phi-4-mini-flash-reasoning", one_chip, aot.phi4flash_family)
    R, P = m.PREFILL_ROWS[-1], m.PREFILL_ROW_WIDTHS[-1]
    assert counted["whole-pool copies"] == 0 and counted["ring copies"] == 0, counted
    assert counted["state copies"] == 0, counted
    assert temps < 0.5e9, temps
    kinds = [cfg.kind(l) for l in range(cfg.n_layer)]
    H = cfg.num_attention_heads
    assert loops == {
        f"(s32[],f32[{R},{cfg.mamba_d_state},{cfg.d_inner}],..)": kinds.count("mamba"),
        f"(s32[],f32[{R},{H},{P}],..)": kinds.count("window") + 1,
        f"(s32[],f32[{R},{H},1],..)": kinds.count("cross")}, loops

    text, temps = cell_program(m, "phi-4-mini-flash-reasoning", one_chip, "decode")
    ops = [ln for ln in text.splitlines() if re.match(r"\s*(ROOT )?%\S+ = ", ln)]
    stored = jax.eval_shape(
        lambda: m.init_paged_cache(cfg, 32 * (cfg.n_positions // 64) + 1, 64, 128))
    _, watch, _ = aot.phi4flash_family(cfg, m, stored[0], None)
    counted = {label: count(ops) for label, count in watch}
    assert counted == {"whole-pool copies": 0, "ring copies": 0, "K/V split into heads": 0,
                       "state copies": 0}, counted
    assert temps < 0.3e9
    readers = kinds.count("full") + kinds.count("cross")
    assert_decode_attends_in_the_kernel(
        text, pages=readers, rings=kinds.count("window"),
        stored={"8193,64", "128,512", "512,128"}, width=cfg.num_key_value_heads * cfg.head_dim)


def assert_decode_attends_in_the_kernel(text, pages, rings, stored, width):
    """A compiled decode program attends over ``pages`` layers' pages and
    ``rings`` layers' rings in ``ops/paged_kv_attention.py``'s kernel alone:
    so many calls under the names the trace gives them, pages apart from
    rings; no loop whose carry opens ``f32[rows, heads, 1]`` or ``f32[rows,
    heads, 1, size]``, the names ``shared_kv_roofline`` and
    ``window_attn_roofline`` knew the loops by; and no bfloat16 array
    ``[.., .., width]`` (K or V with the heads merged) but those in
    ``stored`` (the pool, the rings, and the rings cut into blocks where
    they lie): no turn's pages or blocks gathered for a group of rows."""
    from benchmark import trace as trace_mod

    kernels = aot.kernels_of(text)
    attention = {k: n for k, n in kernels.items() if k.endswith("kv_attention")}
    assert attention == {k: n for k, n in (("paged_kv_attention", pages),
                                           ("ring_kv_attention", rings)) if n}, kernels
    metrics = os.path.join(ROOT, "benchmark/metrics")
    loops = aot.loops_of(text)
    assert not [k for k in loops if re.search(r"f32\[\d+,\d+,1(,\d+)?\]", k)], loops
    ops = [ln for ln in text.splitlines() if re.match(r"\s*(ROOT )?%\S+ = ", ln)]
    whiles = [trace_mod.short_op_name(ln.strip()) for ln in ops if ") while(" in ln]
    for metric in ("shared_kv_roofline", "window_attn_roofline"):
        with open(os.path.join(metrics, metric + ".json")) as f:
            old = re.compile(json.load(f)["args"]["ops"])
        assert not [w for w in whiles if old.search(w)], metric
    wide = set(re.findall(rf"= bf16\[(\d+,\d+),{width}\]", text))
    assert wide <= stored, wide
