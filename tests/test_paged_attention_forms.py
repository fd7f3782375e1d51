"""Decode over paged KV attends over the pool layer itself under an
ownership mask (``models/gpt2_decode._decode_paged_impl``). Held here
against a plain per-row gather of every row's virtual context, which is
what the program did before and is kept below as the reference: same
logits for live rows, same pools outside the scratch page, over the
table shapes an engine produces; and a structural guard that the
per-row gather (a tensor of rows x max_pages x page_tokens positions of
K/V) cannot come back into the lowered program unnoticed."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2, gpt2_decode as dec

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
    args = (CFG, params, last, jnp.asarray(lengths, jnp.int32), ck, cv,
            jnp.asarray(tables))
    got, gk, gv = jax.jit(dec._decode_paged_impl, static_argnums=0)(*args)
    want, wk, wv = jax.jit(_gather_reference, static_argnums=0)(*args)
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
    assert f"{N}x{Bx}x{cfg.n_head}x{cfg.head_dim}" in text  # the pool is there
    gathered = re.findall(
        rf"tensor<(?:{S * mp}x{Bx}|{S}x{mp * Bx}|{S}x{mp}x{Bx})x{cfg.n_head}x{cfg.head_dim}x\w+>",
        text,
    )
    assert not gathered, sorted(set(gathered))
