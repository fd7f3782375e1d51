"""KV-cached autoregressive decoding for GPT-2 — the serving engine core.

Parity role: the engine tier the reference delegates to vLLM
(/root/reference/python/ray/llm/_internal/serve/engines/vllm/) — here a
native JAX engine: a prefill/decode split over a paged, static-shape KV
pool, so generating token N costs one single-token forward over cached
K/V instead of re-running the whole prefix.

vLLM-style paged attention at the jnp level: physical KV pages of B
positions in HBM (``PagePool``: ``[L, H * Dh, N_pages * B]``, positions
minor; ``init_paged_cache`` decides the stored shape and only this file
knows it), per-sequence page tables ``[S, MaxPages]`` mapping virtual
position p to physical position table[p // B] * B + p % B. A
prefix-cache hit points the table at pages another sequence already
wrote (zero copies); admission reserves
ceil(tokens/B) pages up front so tables never change mid-flight.
Page 0 is reserved scratch: inactive rows carry all-zero tables and
length 0, so their junk writes land there and the jitted step needs
no validity branch.

TPU-first shape discipline: the pool, the row count S and the table
width are fixed — every jitted function has static shapes, and the
decode step runs all S rows batched whether or not each is active
(masked), which is exactly the static-batch regime the MXU wants.
Continuous batching lives OUTSIDE jit (the engine loop admits requests
between steps; serve/llm.py drives it).

Weights: an engine holds them in the type it computes in.
``load_serving_params`` casts once, at load (``serving_params`` is the
rule), so no program converts the stack per call. The ``.astype(dt)`` at
every use below stay all the same: on a leaf already in ``cfg.dtype``
they are nothing, and the benchmark's ``--check`` hands these programs
the float32 tree of ``gpt2.init``, which must give the same numbers.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import gpt2
from ray_tpu.models.gpt2 import GPT2Config, _layernorm


# what the engine asks of a decode module beside its programs
# (``models/__init__.py``): sealed prefix pages may be matched, and a
# decode program counts nothing beside its tokens
PREFIX_CACHE = True
STEP_COUNTERS = ()
# a prefill call takes one sequence's chunk: the pool layer's select and
# write-back are built around one row (ROADMAP S11), and the engine keeps
# this module on its path of one call a sequence and chunk
PREFILL_ROWS = (1,)
DECODE_ATTENTION = "pool"  # the pool as it lies, under an ownership mask


def serving_params(cfg: GPT2Config, params):
    """``params`` as a serving engine stores them: the leaves the programs
    below cast at their use (``wte``, ``wpe``, every kernel and bias under
    ``blocks.attn`` and ``blocks.mlp``) in ``cfg.dtype``, once. ``ln1``,
    ``ln2`` and ``ln_f`` stay as they are: ``_layernorm`` multiplies them
    in float32, so casting them would be a lower precision. Every product
    already reads ``leaf.astype(cfg.dtype)``; this stores what that
    returns, so the programs' outputs are the same bits."""
    def cast(tree):
        return jax.tree.map(lambda a: jnp.asarray(a, cfg.dtype), tree)

    blocks = params["blocks"]
    return {
        **params,
        "wte": cast(params["wte"]),
        "wpe": cast(params["wpe"]),
        "blocks": {**blocks, "attn": cast(blocks["attn"]),
                   "mlp": cast(blocks["mlp"])},
    }


def compile_init(cfg: GPT2Config, key):
    """``gpt2.init`` and the cast as one program, so that the
    ``param_dtype`` tree never exists on the device. Compiled without
    XLA's algebraic simplifier: it folds ``(erf_inv(u) * sqrt(2)) * std``
    into one constant, which moves a float32 value in fourteen by an ulp
    and, once rounded, a few weights in a million; without it the
    program returns eager ``gpt2.init``'s values to the bit."""
    init = jax.jit(lambda k: serving_params(cfg, gpt2.init(k, cfg)))
    return init.lower(key).compile(
        compiler_options={"xla_disable_hlo_passes": "algsimp"}
    )


def load_serving_params(cfg: GPT2Config, checkpoint_path=None):
    """The weights of an engine of ``cfg``, on the device, as
    ``serving_params`` stores them: a pickled tree from
    ``checkpoint_path`` cast leaf by leaf, else ``gpt2.init`` from
    ``PRNGKey(0)`` (``compile_init``)."""
    if checkpoint_path:
        import pickle

        with open(checkpoint_path, "rb") as f:
            held = serving_params(cfg, pickle.load(f))
        # the leaves the cast left alone may still be the pickle's NumPy
        return jax.tree.map(jnp.asarray, held)
    key = jax.random.PRNGKey(0)
    return compile_init(cfg, key)(key)


def params_bytes(params) -> int:
    """Bytes the tree's leaves hold."""
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))


def _qkv(h, layer, cfg: GPT2Config):
    dt = cfg.dtype
    qkv = (
        jnp.einsum("btd,dchn->btchn", h, layer["attn"]["qkv"]["kernel"].astype(dt))
        + layer["attn"]["qkv"]["bias"].astype(dt)
    )
    return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # [B,T,H,Dh]


def _proj_mlp(x, att, layer, cfg: GPT2Config):
    dt = cfg.dtype
    att = (
        jnp.einsum("bthn,hnd->btd", att, layer["attn"]["proj"]["kernel"].astype(dt))
        + layer["attn"]["proj"]["bias"].astype(dt)
    )
    x = x + att
    h = _layernorm(x, layer["ln2"]["scale"], layer["ln2"]["bias"])
    h = (
        jnp.einsum("btd,df->btf", h, layer["mlp"]["fc_in"]["kernel"].astype(dt))
        + layer["mlp"]["fc_in"]["bias"].astype(dt)
    )
    h = jax.nn.gelu(h, approximate=True)
    h = (
        jnp.einsum("btf,fd->btd", h, layer["mlp"]["fc_out"]["kernel"].astype(dt))
        + layer["mlp"]["fc_out"]["bias"].astype(dt)
    )
    return x + h


def sample(logits, temps, greedy_mask, rng):
    """Per-row temperature/greedy sampling. logits [S, V]."""
    greedy = jnp.argmax(logits, axis=-1)
    sampled = jax.random.categorical(
        rng, logits / jnp.maximum(temps, 1e-6)[:, None]
    )
    return jnp.where(greedy_mask, greedy, sampled).astype(jnp.int32)


@partial(jax.jit, donate_argnums=(1, 2, 3, 4))
def update_rows_paged(last_tokens, lengths, temps, greedy_mask,
                      page_tables, rows, row_last, row_len, row_temps,
                      row_greedy, row_tables):
    """Incremental decode-state update: write admission/retirement
    values into ``rows`` of the device-resident step state WITHOUT
    re-uploading the full arrays — the decode pipeline's steady-state
    churn path (one small scatter per array instead of five
    host->device transfers at every admit/retire). A retired row's
    page table goes all-zero so its junk scatters land in the scratch
    page; an admitted row brings its freshly reserved table.

    ``last_tokens`` is deliberately NOT donated: in the single-step
    decode regime it aliases the chunk's token output, which the host
    may not have materialized yet (the in-flight lookahead)."""
    return (
        last_tokens.at[rows].set(row_last),
        lengths.at[rows].set(row_len),
        temps.at[rows].set(row_temps),
        greedy_mask.at[rows].set(row_greedy),
        page_tables.at[rows].set(row_tables),
    )


@partial(jax.tree_util.register_dataclass, data_fields=["data"],
         meta_fields=["page_tokens"])
@dataclasses.dataclass(frozen=True)
class PagePool:
    """One page pool, K or V, as the device stores it: ``data``
    [n_layer, H * Dh, N_pages * B] in the compute dtype, positions minor
    (page n holds positions n * B .. n * B + B - 1), and B beside it,
    which that shape cannot say.

    The shape decides the layout the device keeps an array in between
    calls, and the layer loops read a layer with positions in lanes:
    both attention products are emitted that way. Stored
    ``[L, N, B, H, Dh]`` (the TPU's default for minor dimensions 25 x 64
    is page-minor) every program relaid both pools whole on the way in
    and again on the way out, 27 ms of a 45 ms step at gpt2-xl; stored
    ``[L, N, B, H * Dh]`` the loops transposed each layer instead,
    23 ms a step (PERF.md, PR 35). In this shape a program reads the
    pool as it lies and writes it in place, ``_write_layer``."""

    data: jax.Array
    page_tokens: int


def init_paged_cache(cfg: GPT2Config, num_pages: int, page_tokens: int,
                     rows: int = 1):
    """(k, v) page pools, zeroed: the one place that decides the shape a
    pool is stored in (``PagePool``). Every layer is paged, so the pools
    do not depend on the engine's decode ``rows``."""
    shape = (cfg.n_layer, cfg.n_head * cfg.head_dim, num_pages * page_tokens)
    return (PagePool(jnp.zeros(shape, cfg.dtype), page_tokens),
            PagePool(jnp.zeros(shape, cfg.dtype), page_tokens))


def cache_layout(cfg: GPT2Config, cache_k: PagePool, cache_v: PagePool) -> Dict[str, Any]:
    """The shape one pool is stored in and the bytes both hold on the
    device, tiling's padding included; every layer is a full one."""
    return {"shape": list(cache_k.data.shape),
            "bytes": {"full": cache_k.data.on_device_size_in_bytes()
                      + cache_v.data.on_device_size_in_bytes(), "window": 0}}


def _writers(phys, n_positions: int, dtype):
    """Who writes where, for ``_write_layer``: ``phys`` [P] is the
    physical position each of P new K/V rows goes to. Returns ``sel``
    [P, n_positions], one where row p writes position t, and ``hit``
    [n_positions], true where any row does. Rows that name the same
    position (junk headed for the scratch page) are settled here, one
    writer a position, so the product below copies and never sums."""
    P = phys.shape[0]
    rows = jnp.arange(P, dtype=jnp.int32)
    writer = jnp.full((n_positions,), -1, jnp.int32).at[phys].set(rows)
    return (writer[None, :] == rows[:, None]).astype(dtype), writer >= 0


def _write_layer(data, layer_idx, new, sel, hit):
    """Write ``new`` [P, H, Dh] into layer ``layer_idx`` of ``data`` at
    the positions ``_writers`` settled, in place on the donated carry.
    Returns the pool and the written layer [H * Dh, N * B].

    Not ``data.at[layer_idx, :, phys].set(new)``: the compiler does not
    scatter along a minor dimension in place, it relays the whole pool
    to width-minor for the scatter and back (the four whole-pool copies
    again, and a transposition of the layer in every turn of the loop;
    measured, PERF.md PR 35). The layer is read whole for the attention
    anyway; this rewrites it through a select and puts it back, one
    more pass over 20 MB a layer at gpt2-xl instead of a relay. ``sel``
    holds one 1 a written position, so the product copies a value and
    sums nothing (a row that is not finite would reach the other rows'
    new positions through it, where a scatter kept rows apart)."""
    new = new.reshape(new.shape[0], -1).astype(data.dtype)
    old = jax.lax.dynamic_index_in_dim(data, layer_idx, 0, keepdims=False)
    layer = jnp.where(hit[None], jnp.einsum("pd,pt->dt", new, sel), old)
    return jax.lax.dynamic_update_index_in_dim(data, layer, layer_idx, 0), layer


@partial(jax.jit, donate_argnums=(2, 3))
def write_pages(k_blocks, v_blocks, cache_k, cache_v, pages):
    """Plain blocks into the pools: write ``k_blocks``/``v_blocks``
    [L, n, B, H, Dh] into physical pages ``pages`` [n], converting those
    few pages to the stored shape. With ``read_pages`` this is how code
    outside this module (the tests that build a pool from a reference's
    K and V) stays ignorant of that shape; the engine never calls it."""
    B = cache_k.page_tokens

    def put(pool, blocks):
        L, n = blocks.shape[:2]
        # [L, n, B, H, Dh] -> n pages of [L, H * Dh, B]
        blocks = blocks.reshape(L, n, B, -1).transpose(1, 0, 3, 2)
        data = pool.data
        for j in range(n):
            data = jax.lax.dynamic_update_slice_in_dim(
                data, blocks[j].astype(data.dtype), pages[j] * B, axis=2
            )
        return PagePool(data, B)

    return put(cache_k, k_blocks), put(cache_v, v_blocks)


@partial(jax.jit, static_argnums=(0,))
def read_pages(cfg: GPT2Config, cache_k, cache_v, pages):
    """``write_pages``' inverse: physical pages ``pages`` [n] of the
    pools as plain ``(k, v)`` blocks [L, n, B, H, Dh]. Converts those few
    pages, not the pool, and donates nothing: the pools stay usable."""
    B = cache_k.page_tokens

    def get(pool):
        got = jnp.stack([
            jax.lax.dynamic_slice_in_dim(pool.data, pages[j] * B, B, axis=2)
            for j in range(pages.shape[0])
        ])  # [n, L, H * Dh, B]
        return got.transpose(1, 0, 3, 2).reshape(
            got.shape[1], got.shape[0], B, cfg.n_head, cfg.head_dim
        )

    return get(cache_k), get(cache_v)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(5, 6))
def prefill_paged(cfg: GPT2Config, params, tokens, start, length, cache_k,
                  cache_v, page_table, row=None):
    """Prefill one CHUNK of a prompt into paged KV: ``tokens`` [1, P]
    (right-padded, ``length`` real) are virtual positions
    start..start+P-1 of the sequence whose page table is ``page_table``
    [MaxPages]; pages holding positions 0..start-1 are already written
    (a prefix hit, a KV import, or this sequence's previous chunk —
    chunked prefill is just repeated calls with advancing ``start``).
    Writes the chunk's K/V through the page table, attends the chunk
    over the sequence's own pages (one layer's ``MaxPages`` pages taken
    through the table, never the pool), and returns the last real
    position's logits [vocab] plus the updated pools. ``row``, the decode
    row the sequence was admitted to, is for models that keep state a row
    (``models/mimo_v2.py``); every layer here is paged and it is not read.

    The caller guarantees start + P <= MaxPages * B (bucket the chunk
    width against that cap); positions past the sequence's reserved
    pages hit table entries that are 0 = the scratch page, so padding
    writes are harmless.

    fori_loop (not scan) over layers, here and in the decode step, so
    the cache updates are IN-PLACE on the donated carry — a scan would
    stack fresh per-layer cache outputs, copying the whole pool per
    call (measured 300x slower at gpt2-small)."""
    dt = cfg.dtype
    P = tokens.shape[1]
    B = cache_k.page_tokens
    max_pages = page_table.shape[0]
    T = max_pages * B  # virtual row width
    W = params["wpe"].shape[0]
    pos = start + jnp.arange(P)
    x = (
        params["wte"].astype(dt)[tokens]
        + params["wpe"].astype(dt)[jnp.clip(pos, 0, W - 1)][None]
    )
    # chunk position start+i may attend every written position 0..start+i
    mask = jnp.arange(T)[None] <= pos[:, None]  # [P, T]
    page_of = page_table[jnp.clip(pos // B, 0, max_pages - 1)]  # [P]
    sel, hit = _writers(page_of * B + pos % B, cache_k.data.shape[2], dt)

    def row_of(layer):
        """The sequence's pages of a written layer [H * Dh, N * B], in
        table order: [H, Dh, T]. A slice a page, so that the layer is
        not relaid for a gather."""
        return jnp.concatenate([
            jax.lax.dynamic_slice_in_dim(layer, page_table[j] * B, B, axis=1)
            for j in range(max_pages)
        ], axis=1).reshape(cfg.n_head, cfg.head_dim, T)

    def body(layer_idx, carry):
        x, ck, cv = carry
        layer = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, layer_idx, axis=0, keepdims=False
            ),
            params["blocks"],
        )
        h = _layernorm(x, layer["ln1"]["scale"], layer["ln1"]["bias"])
        q, k, v = _qkv(h, layer, cfg)  # [1, P, H, Dh]
        # write the chunk's K/V through the page table (in place on
        # the donated carry), then take the sequence's pages so the
        # chunk sees prefix pages it never computed
        ck, k_l = _write_layer(ck, layer_idx, k[0], sel, hit)
        cv, v_l = _write_layer(cv, layer_idx, v[0], sel, hit)
        scale = 1.0 / (cfg.head_dim ** 0.5)
        scores = jnp.einsum("bthn,hns->bhts", q, row_of(k_l)) * scale
        scores = jnp.where(mask[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dt)
        att = jnp.einsum("bhts,hns->bthn", probs, row_of(v_l))
        x = _proj_mlp(x, att, layer, cfg)
        return x, ck, cv

    x, data_k, data_v = jax.lax.fori_loop(
        0, cfg.n_layer, body, (x, cache_k.data, cache_v.data)
    )
    cache_k, cache_v = PagePool(data_k, B), PagePool(data_v, B)
    x = _layernorm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    last = jax.lax.dynamic_index_in_dim(
        x[0], jnp.maximum(length - 1, 0), axis=0, keepdims=False
    )
    logits = jnp.einsum(
        "d,vd->v", last.astype(dt), params["wte"].astype(dt),
        preferred_element_type=jnp.float32,
    )
    return logits[: cfg.vocab_size], cache_k, cache_v


def _decode_paged_impl(cfg: GPT2Config, params, last_tokens, lengths,
                       cache_k, cache_v, page_tables):
    """One token for every sequence over paged KV: [S] last tokens at
    virtual positions ``lengths`` write their new K/V through
    ``page_tables`` [S, MaxPages] and attend over the pool layer as it
    lies, every position of every page, under a mask that says which
    positions a row owns. No row's context is gathered: the work grows
    with the pool, not with S * MaxPages, and an engine's pool is the
    smaller of the two (on the chip it won at every S down to 1 against
    a pool of 97 pages; PERF.md, PR 30). Returns logits [S, vocab] and
    the updated pools."""
    dt = cfg.dtype
    S = last_tokens.shape[0]
    B = cache_k.page_tokens
    N = cache_k.data.shape[2] // B
    max_pages = page_tables.shape[1]
    T = max_pages * B
    W = params["wpe"].shape[0]
    pos = jnp.clip(lengths, 0, T - 1)
    x = (
        params["wte"].astype(dt)[last_tokens][:, None]
        + params["wpe"].astype(dt)[jnp.clip(pos, 0, W - 1)][:, None]
    )  # [S, 1, D]
    rows = jnp.arange(S)
    page_of = page_tables[rows, pos // B]  # [S]
    sel, hit = _writers(page_of * B + pos % B, N * B, dt)
    # col[s, n]: the column at which page n stands in row s's table, -1
    # where it does not. Every unused table entry names page 0 (scratch),
    # so page 0 is nobody's; a prefix page shared by several rows is
    # owned by each of them.
    col = jnp.full((S, N), -1, jnp.int32)
    col = col.at[rows[:, None], page_tables].set(
        jnp.arange(max_pages, dtype=jnp.int32)[None]
    )
    col = col.at[:, 0].set(-1)
    # position b of page n is virtual position col * B + b: attend 0..pos
    virt = col[:, :, None] * B + jnp.arange(B)[None, None]  # [S, N, B]
    mask = (col[:, :, None] >= 0) & (virt <= pos[:, None, None])
    mask = mask.reshape(S, N * B)

    def body(layer_idx, carry):
        x, ck, cv = carry
        layer = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, layer_idx, axis=0, keepdims=False
            ),
            params["blocks"],
        )
        h = _layernorm(x, layer["ln1"]["scale"], layer["ln1"]["bias"])
        q, k, v = _qkv(h, layer, cfg)  # [S, 1, H, Dh]
        # in-place write of the new token's K/V through the tables
        # (inactive rows have zero tables: their junk lands in the
        # scratch page, and owning nothing they attend uniformly over
        # the pool's finite contents)
        ck, k_l = _write_layer(ck, layer_idx, k[:, 0], sel, hit)
        cv, v_l = _write_layer(cv, layer_idx, v[:, 0], sel, hit)
        k_l = k_l.reshape(cfg.n_head, cfg.head_dim, N * B)
        v_l = v_l.reshape(cfg.n_head, cfg.head_dim, N * B)
        scale = 1.0 / (cfg.head_dim ** 0.5)
        scores = jnp.einsum("shn,hnt->sht", q[:, 0], k_l) * scale
        scores = jnp.where(mask[:, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dt)
        att = jnp.einsum("sht,hnt->shn", probs, v_l)[:, None]
        x = _proj_mlp(x, att, layer, cfg)
        return x, ck, cv

    x, data_k, data_v = jax.lax.fori_loop(
        0, cfg.n_layer, body, (x, cache_k.data, cache_v.data)
    )
    cache_k, cache_v = PagePool(data_k, B), PagePool(data_v, B)
    x = _layernorm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    logits = jnp.einsum(
        "sd,vd->sv", x[:, 0].astype(dt), params["wte"].astype(dt),
        preferred_element_type=jnp.float32,
    )
    return logits[:, : cfg.vocab_size], cache_k, cache_v


@partial(jax.jit, static_argnums=(0,), donate_argnums=(4, 5))
def decode_paged_and_sample(cfg: GPT2Config, params, last_tokens, lengths,
                            cache_k, cache_v, page_tables, temps,
                            greedy_mask, rng_base, step):
    """Decode + sample (+ RNG fold + cursor bump) fused into ONE
    dispatch — the serving loop pays exactly one dispatch + one token
    sync per step. Returns (next_tokens, next_lengths, k, v): the engine
    feeds them straight back in without re-uploading."""
    logits, cache_k, cache_v = _decode_paged_impl(
        cfg, params, last_tokens, lengths, cache_k, cache_v, page_tables
    )
    rng = jax.random.fold_in(rng_base, step)
    nxt = sample(logits, temps, greedy_mask, rng)
    return nxt, lengths + 1, cache_k, cache_v


# the most steps one K-chunk dispatch runs: the engine's K rule caps K
# here, and ``decode_multi_paged`` returns this many rows of tokens
MAX_DECODE_CHUNK = 8


@partial(jax.jit, static_argnums=(0,), donate_argnums=(4, 5))
def decode_multi_paged(cfg: GPT2Config, params, last_tokens, lengths,
                       cache_k, cache_v, page_tables, temps, greedy_mask,
                       rng_base, n_steps, step0):
    """``n_steps`` tokens per sequence in ONE dispatch (fori_loop on
    device), the page-table write recomputed per step (the tables
    themselves are fixed — admission reserved every page up front).
    The engine picks K from the active rows' remaining budgets and drops
    to K=1 whenever requests are waiting for admission (continuous
    batching latency stays one step).

    ``n_steps`` (at most ``MAX_DECODE_CHUNK``) is an argument of the
    program, not part of its signature: one program runs every K, and
    the tokens come back as ``[MAX_DECODE_CHUNK, S]`` with the first
    ``n_steps`` rows written. A program a K was seven compiles where
    one does, and a K first met under load compiled there, stalling
    every stream for seconds: once the step was fast enough for an
    offline batch to empty the queue between generations, every K from
    2 to 7 turned up in a cell that had warmed 1 and 8 (PERF.md,
    PR 35)."""
    S = last_tokens.shape[0]
    toks0 = jnp.zeros((MAX_DECODE_CHUNK, S), jnp.int32)

    def body(i, carry):
        last, lens, ck, cv, toks = carry
        logits, ck, cv = _decode_paged_impl(
            cfg, params, last, lens, ck, cv, page_tables
        )
        rng = jax.random.fold_in(rng_base, step0 + i)
        nxt = sample(logits, temps, greedy_mask, rng)
        toks = jax.lax.dynamic_update_index_in_dim(toks, nxt, i, axis=0)
        return nxt, lens + 1, ck, cv, toks

    last, lens, cache_k, cache_v, toks = jax.lax.fori_loop(
        0, n_steps, body, (last_tokens, lengths, cache_k, cache_v, toks0)
    )
    return toks, last, lens, cache_k, cache_v
