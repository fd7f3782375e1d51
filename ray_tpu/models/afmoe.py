"""``model_type: afmoe`` for the serving engine, as Trinity-Mini
(https://huggingface.co/arcee-ai/Trinity-Mini, 26B-A3B) sets it: gated,
QK-normed attention, rotary over a window in three layers of four and
position-free over everything in the fourth, and expert layers that hold
every routed expert beside a shared one.

The block has four norms (RMSNorm with a learned scale, no bias anywhere):

    x = embed[t] * sqrt(hidden_size)                             (mup_enabled)
    x += norm_post_attn(Attn(norm_in(x)))
    x += norm_post_mlp(FFN(norm_pre_mlp(x)));   logits = W_head norm_f(x)

- Attention: ``q = W_q h`` as H heads of ``head_dim``, ``k``, ``v`` as Hkv
  heads, and a gate ``g = W_g h`` a channel of the heads' output. q and k
  are RMS-normed over ``head_dim`` with one learned scale for all heads,
  then, in sliding layers only, rotated (rotate-half over all of
  ``head_dim``, base ``rope_theta``); a full layer has no positions at all.
  ``a = softmax(q . k / sqrt(head_dim)) v``, causal, in sliding layers over
  the last ``sliding_window`` positions, the current one among them; the
  output projection takes ``a * sigmoid(g)``.
- FFN: a SwiGLU, dense in the first ``num_dense_layers`` layers, else one
  shared SwiGLU every token passes (a dense product, no held expert, in no
  ``moe_*`` count) beside ``ops.moe.expert_layer``: sigmoid scores, the top
  ``num_experts_per_tok`` of score plus selection bias, gates renormalised
  and scaled by ``route_scale``.

The cache has a spec a layer (``cache_spec``) and lives in
``ops/cached_attention.py`` with the attention over it: a full layer's K
and V paged, a sliding layer's a ring a decode row, position p in slot ``p
% sliding_window``. The ring is 2,048 positions, four prefill chunks: a
prefill chunk meets the ring a block at a time as far as it is filled and
then itself; decode reads a row's ring in the same kernel as its pages
(``ops/paged_kv_attention.py``), a block a turn as far as the ring is
filled, so a row inside its window reads what it has written. A prefix hit
would have to restore the rings, which nothing does yet: ``PREFIX_CACHE``
is False.

The programs are the engine's interface, under the names GPT-2's have
(``models/__init__.py``), and what a step counted rides beside its tokens
(``STEP_COUNTERS``).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.gpt2_decode import (  # noqa: F401 — the engine's interface
    params_bytes, sample, update_rows_paged,
)
from ray_tpu.ops import cached_attention as ca
from ray_tpu.ops import moe, page_loops, paged_kv_attention
from ray_tpu.ops.cached_attention import LayerCache

PREFIX_CACHE = False   # a hit would have to restore the sliding layers' rings
DECODE_ATTENTION = "own_pages_and_rings"
MAX_DECODE_CHUNK = 8
# the rows a prefill call takes and the widths of a row (a ring belongs to a
# decode row, so a sequence takes one row of a call): chosen by a sweep on
# the chip at the served shapes (PERF.md §6, PR 53)
PREFILL_ROWS = (1, 2)
PREFILL_ROW_WIDTHS = (128, 256, 512)
# what a decode program counts beside its tokens: the expert layers' counts
# summed over layers and steps; the positions its live rows attended over in
# the full layers and the positions the kernel read for them (the live rows'
# pages x positions a page); and
# the positions the rows' sliding layers attended over, min(p + 1, window);
# all summed over steps, once a step and not a layer
STEP_COUNTERS = (*(f"moe_{name}" for name in moe.STATS), "attn_context_tokens",
                 "attn_loop_tokens", "window_context_tokens")

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """The published config's keys, under their names. ``layer_types`` says
    how many layers there are and of which kind each is."""

    vocab_size: int = 200192
    max_position_embeddings: int = 131072
    hidden_size: int = 2048
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    rope_theta: float = 10_000.0
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    num_dense_layers: int = 2
    route_scale: float = 2.826
    rms_norm_eps: float = 1e-5
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 8
    dtype: Any = jnp.bfloat16  # compute type, and the stored weights' and cache's

    # what the engine asks of any model's config
    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    def sliding(self, layer: int) -> bool:
        return self.layer_types[layer] == SLIDING


# the leading dense layer and the first whole period behind the dense ones
# (published layers 1 and 4-7), every width, expert and vocabulary row
# (benchmark/configs/trinity-mini-serve.json)
CONFIGS: Dict[str, AfmoeConfig] = {
    "trinity-mini": AfmoeConfig(
        max_position_embeddings=16384, num_dense_layers=1,
        layer_types=(SLIDING, SLIDING, SLIDING, SLIDING, FULL),
    ),
    # the CPU tests' preset: every mechanism, no published width
    "trinity-tiny": AfmoeConfig(
        vocab_size=256, max_position_embeddings=256, hidden_size=64,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        sliding_window=16, intermediate_size=128, moe_intermediate_size=32,
        num_experts=16, num_experts_per_tok=4, num_dense_layers=1,
        layer_types=(SLIDING, SLIDING, FULL, SLIDING),
    ),
}


# -- parameters -----------------------------------------------------------


@partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init(key, cfg: AfmoeConfig):
    """Seeded weights in the type the programs compute in, a leaf a
    program. The embedding is drawn at 1 / sqrt(hidden_size), so that the
    scaled vectors have size one, and every sublayer ends in a norm, so
    each moves the residual stream by about its own size. The selection
    bias is drawn non-zero and the norms' scales (the q and k norms' among
    them) away from one, so that a program that drops one does not agree
    with the reference; the gate's kernel is drawn like any other, so the
    gate is a half on average and anything from 0.1 to 0.9 a channel."""
    dt = cfg.dtype
    D, H, Hkv, Dh = (cfg.hidden_size, cfg.num_attention_heads,
                     cfg.num_key_value_heads, cfg.head_dim)
    F, Fm, E = cfg.intermediate_size, cfg.moe_intermediate_size, cfg.num_experts
    Fs = cfg.num_shared_experts * Fm
    keys = iter(jax.random.split(key, 24 * cfg.n_layer + 4))

    def w(shape, fan_in, gain=1.0, dtype=dt):
        return _normal(next(keys), tuple(shape), gain / fan_in ** 0.5, dtype)

    def scale(n):
        return 1.0 + _normal(next(keys), (n,), 0.1, jnp.float32)

    layers: List[Dict[str, Any]] = []
    for l in range(cfg.n_layer):
        attn = {"wq": w((D, H * Dh), D), "wk": w((D, Hkv * Dh), D),
                "wv": w((D, Hkv * Dh), D), "wg": w((D, H * Dh), D),
                "wo": w((H * Dh, D), H * Dh),
                "q_norm": scale(Dh), "k_norm": scale(Dh)}
        layer = {"norm_in": scale(D), "norm_post_attn": scale(D),
                 "norm_pre_mlp": scale(D), "norm_post_mlp": scale(D), "attn": attn}
        if l < cfg.num_dense_layers:
            layer["mlp"] = {"gate": w((D, F), D), "up": w((D, F), D),
                            "down": w((F, D), F, gain=2.0)}
        else:
            layer["moe"] = {
                "router": w((D, E), D, dtype=jnp.float32),
                # small beside the scores' own spread (0.2): it reorders
                # neighbours and does not decide the choice (PERF.md, PR 46)
                "bias": _normal(next(keys), (E,), 0.02, jnp.float32),
                "gate": w((E, D, Fm), D), "up": w((E, D, Fm), D),
                # the routed sum is route_scale times a mean of the chosen
                # experts' outputs
                "down": w((E, Fm, D), Fm, gain=2.0),
            }
            layer["shared"] = {"gate": w((D, Fs), D), "up": w((D, Fs), D),
                               "down": w((Fs, D), Fs, gain=2.0)}
        layers.append(layer)
    return {"embed": w((cfg.vocab_size, D), D), "layers": layers,
            "norm_f": scale(D), "head": w((cfg.vocab_size, D), D)}


def load_serving_params(cfg: AfmoeConfig, checkpoint_path=None):
    """The weights of an engine of ``cfg``, on the device, in ``cfg.dtype``
    (the norms, the router and its bias in float32): a pickled tree of
    ``init``'s layout cast leaf by leaf, else ``init`` from ``PRNGKey(0)``."""
    if checkpoint_path:
        import pickle

        with open(checkpoint_path, "rb") as f:
            tree = pickle.load(f)
        like = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
        return jax.tree.map(lambda a, s: jnp.asarray(a, s.dtype), tree, like)
    return init(jax.random.PRNGKey(0), cfg)


# -- the cache ------------------------------------------------------------


def cache_spec(cfg: AfmoeConfig) -> List[Dict[str, Any]]:
    """What one layer keeps a position: its kind, K/V heads and sizes."""
    return [{"kind": "window" if cfg.sliding(l) else "full",
             "kv_heads": cfg.num_key_value_heads, "k_size": cfg.head_dim,
             "v_size": cfg.head_dim} for l in range(cfg.n_layer)]


def init_paged_cache(cfg: AfmoeConfig, num_pages: int, page_tokens: int,
                     rows: int = 1):
    """(k, v) caches, zeroed, for ``rows`` decode rows over ``num_pages``
    pages (``ops.cached_attention.init_caches`` decides the stored shapes)."""
    return ca.init_caches(cache_spec(cfg), cfg.sliding_window, num_pages,
                          page_tokens, rows, cfg.dtype)


def cache_layout(cfg: AfmoeConfig, cache_k: LayerCache, cache_v: LayerCache) -> Dict[str, Any]:
    """The stored shape of every layer's K and the bytes both caches hold
    on the device, by kind."""
    return ca.layout(cache_spec(cfg), cache_k, cache_v)


# -- the block ------------------------------------------------------------


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def _rope(x, pos, theta: float):
    """Rotate-half over the whole of ``x`` [T, heads, size] (float32) at
    positions ``pos`` [T]: dimension i turns with i + size / 2."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv             # [T, 1, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _qkvg(cfg: AfmoeConfig, l: int, attn, h, pos):
    """h [T, D] at positions ``pos`` [T] -> q [T, H, Dh] and, the heads
    merged as the caches store them, k, v [T, Hkv * Dh], and the gate's
    logits [T, H * Dh] float32; q and k normed and, in a sliding layer,
    rotated."""
    dt = cfg.dtype
    T = h.shape[0]
    q = (h @ attn["wq"].astype(dt)).reshape(T, cfg.num_attention_heads, cfg.head_dim)
    k = (h @ attn["wk"].astype(dt)).reshape(T, cfg.num_key_value_heads, cfg.head_dim)
    v = h @ attn["wv"].astype(dt)
    g = jnp.dot(h, attn["wg"].astype(dt), preferred_element_type=jnp.float32)
    q = _rmsnorm(q, attn["q_norm"], cfg.rms_norm_eps)
    k = _rmsnorm(k, attn["k_norm"], cfg.rms_norm_eps)
    if cfg.sliding(l):
        q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    return q.astype(dt), k.astype(dt).reshape(T, -1), v, g


def _swiglu(dt, mlp, h):
    g = jnp.dot(h, mlp["gate"].astype(dt), preferred_element_type=jnp.float32)
    u = jnp.dot(h, mlp["up"].astype(dt), preferred_element_type=jnp.float32)
    return jnp.dot((jax.nn.silu(g) * u).astype(dt), mlp["down"].astype(dt),
                   preferred_element_type=jnp.float32)


def _ffn(cfg: AfmoeConfig, layer, h, live):
    """(the FFN on h [T, D], float32; its expert counts)."""
    if "moe" not in layer:
        return _swiglu(cfg.dtype, layer["mlp"], h), jnp.zeros((len(moe.STATS),), jnp.int32)
    routed, stats = moe.expert_layer(
        h, layer["moe"], first=0, top_k=cfg.num_experts_per_tok, live=live,
        scale=cfg.route_scale)
    return routed + _swiglu(cfg.dtype, layer["shared"], h), stats


def _rest_of_block(cfg: AfmoeConfig, layer, x, att, gate, live):
    """x [T, D] float32, the heads' output att [T, H * Dh] and the gate's
    logits -> the block's output and its expert counts."""
    dt, eps = cfg.dtype, cfg.rms_norm_eps
    gated = (att * jax.nn.sigmoid(gate)).astype(dt)
    out = jnp.dot(gated, layer["attn"]["wo"].astype(dt), preferred_element_type=jnp.float32)
    x = x + _rmsnorm(out, layer["norm_post_attn"], eps)
    y, stats = _ffn(cfg, layer, _rmsnorm(x, layer["norm_pre_mlp"], eps).astype(dt), live)
    return x + _rmsnorm(y, layer["norm_post_mlp"], eps), stats


def _embed(cfg: AfmoeConfig, params, tokens):
    return (params["embed"].astype(cfg.dtype)[tokens].astype(jnp.float32)
            * cfg.hidden_size ** 0.5)


def _logits(cfg: AfmoeConfig, params, x):
    h = _rmsnorm(x, params["norm_f"], cfg.rms_norm_eps).astype(cfg.dtype)
    return jnp.dot(h, params["head"].astype(cfg.dtype).T,
                   preferred_element_type=jnp.float32)


# -- the programs ---------------------------------------------------------


@partial(jax.jit, static_argnums=(0,), donate_argnums=(5, 6))
def prefill_paged(cfg: AfmoeConfig, params, tokens, start, length, cache_k,
                  cache_v, page_table, row=0):
    """Prefill the rows of one call: ``tokens`` [R, P] (right-padded,
    ``length`` [R] real) are positions start .. start + P - 1 (``start``
    [R]) of the sequences in decode rows ``row`` [R], no two the same,
    whose page tables are ``page_table`` [R, MaxPages]; what lies before a
    row's ``start`` is already cached (this sequence's earlier chunks).
    Full layers write every row's chunk through its page table and attend
    over each row's own pages; sliding layers attend over each row's ring
    as the earlier chunks left it and the chunk itself
    (``ring_chunk_attend``), then put the chunk's last real positions into
    the ring. The rest of the block, the experts among it, runs once on the
    [R * P, D] tokens of all rows, so a call reads its weights once. A row
    of length 0 is nobody's: it writes to the scratch page and into no
    ring, what its queries see is not used and the experts do not see it.
    Returns the last real position's logits of every row [R, vocab] and
    the caches.

    A call of one row may give ``start``, ``length`` and ``row`` as scalars
    and ``page_table`` as [MaxPages], and gets its logits as [vocab]."""
    one = page_table.ndim == 1
    if one:
        start, length, page_table, row = (
            jnp.asarray(a)[None] for a in (start, length, page_table, row))
    dt, Hkv = cfg.dtype, cfg.num_key_value_heads
    R, P = tokens.shape
    B = cache_k.page_tokens
    max_pages = page_table.shape[1]
    pos = start[:, None] + jnp.arange(P)                              # [R, P]
    live = jnp.arange(P) < length[:, None]
    x = _embed(cfg, params, tokens.reshape(-1))                       # [R * P, D]
    page_of = jnp.take_along_axis(page_table, jnp.clip(pos // B, 0, max_pages - 1), axis=1)
    page_of = jnp.where(live, page_of, 0)
    # one loop over the rows' pages, to the longest row's last real position
    loops = page_loops.one_loop(
        jnp.where(length > 0, start + length - 1, 0),
        B * page_loops.pages_a_turn(max_pages, 8))
    ks, vs = list(cache_k.layers), list(cache_v.layers)
    for l, layer in enumerate(params["layers"]):
        h = _rmsnorm(x, layer["norm_in"], cfg.rms_norm_eps).astype(dt)
        q, k, v, g = _qkvg(cfg, l, layer["attn"], h, pos.reshape(-1))
        q, k, v = (a.reshape(R, P, *a.shape[1:]) for a in (q, k, v))
        if cfg.sliding(l):
            own_k, own_v = ca.ring_rows(ks[l], row), ca.ring_rows(vs[l], row)
            att = ca.ring_chunk_attend(q, own_k, own_v, k, v, start, pos,
                                       cfg.sliding_window, Hkv)
            ks[l] = ca.ring_take(ks[l], own_k, k, start, length, row)
            vs[l] = ca.ring_take(vs[l], own_v, v, start, length, row)
        else:
            ks[l] = ks[l].at[page_of, pos % B].set(k)
            vs[l] = vs[l].at[page_of, pos % B].set(v)
            att = ca.paged_attend(q, ks[l], vs[l], page_table, pos, Hkv, loops)
        x, _ = _rest_of_block(cfg, layer, x, att.reshape(R * P, -1), g, live.reshape(-1))
    ends = x.reshape(R, P, -1)[jnp.arange(R), jnp.maximum(length - 1, 0)]
    logits = _logits(cfg, params, ends)
    return ((logits[0] if one else logits), LayerCache(tuple(ks), B),
            LayerCache(tuple(vs), B))


def _decode_paged_impl(cfg: AfmoeConfig, params, last_tokens, lengths,
                       cache_k, cache_v, page_tables):
    """One token for every row: [S] last tokens at positions ``lengths``
    write their K/V (full layers through ``page_tables`` [S, MaxPages],
    sliding layers into their row's ring) and attend, full layers over the
    row's own pages, sliding layers over the ring, each row to its own
    length (``ops/paged_kv_attention.py``). A row of length 0 is nobody's:
    its full-layer write lands in the scratch page, it writes no ring, and
    the experts do not see it. Returns logits [S, vocab], the caches and
    what the step counted (``STEP_COUNTERS``)."""
    dt, Hkv = cfg.dtype, cfg.num_key_value_heads
    S = last_tokens.shape[0]
    B = cache_k.page_tokens
    W = cfg.sliding_window
    T = page_tables.shape[1] * B
    pos = jnp.clip(lengths, 0, T - 1)
    live = lengths > 0
    rows = jnp.arange(S)
    x = _embed(cfg, params, last_tokens)                              # [S, D]
    page_of = page_tables[rows, pos // B]
    slot = jnp.where(live, pos % W, W)  # W is no slot: the write is dropped
    walk = paged_kv_attention.page_visits(pos, page_tables.shape[1], B)
    in_rings = ca.ring_visits(pos, W)
    ks, vs = list(cache_k.layers), list(cache_v.layers)
    stats = jnp.zeros((len(moe.STATS),), jnp.int32)
    for l, layer in enumerate(params["layers"]):
        h = _rmsnorm(x, layer["norm_in"], cfg.rms_norm_eps).astype(dt)
        q, k, v, g = _qkvg(cfg, l, layer["attn"], h, pos)
        if cfg.sliding(l):
            ks[l] = ks[l].at[rows, slot].set(k, mode="drop")
            vs[l] = vs[l].at[rows, slot].set(v, mode="drop")
            att = ca.ring_decode_attend(q[:, None], ks[l], vs[l], pos, Hkv, in_rings)[:, 0]
        else:
            ks[l] = ks[l].at[page_of, pos % B].set(k)
            vs[l] = vs[l].at[page_of, pos % B].set(v)
            att = ca.paged_attend(q[:, None], ks[l], vs[l], page_tables,
                                  pos[:, None], Hkv, walk)[:, 0]
        x, counted = _rest_of_block(cfg, layer, x, att, g, live)
        stats = stats + counted
    context = jnp.sum(jnp.where(live, pos + 1, 0), dtype=jnp.int32)
    read = paged_kv_attention.positions_read(pos, live, B)
    in_window = jnp.sum(jnp.where(live, jnp.minimum(pos + 1, W), 0), dtype=jnp.int32)
    return (_logits(cfg, params, x), LayerCache(tuple(ks), B),
            LayerCache(tuple(vs), B),
            jnp.concatenate([stats, context[None], read[None], in_window[None]]))


@partial(jax.jit, static_argnums=(0,), donate_argnums=(4, 5))
def decode_paged_and_sample(cfg: AfmoeConfig, params, last_tokens, lengths,
                            cache_k, cache_v, page_tables, temps,
                            greedy_mask, rng_base, step):
    """Decode, sample, fold the RNG and bump the cursor in one dispatch.
    Returns (next tokens, next lengths, k, v, the step's counts)."""
    logits, cache_k, cache_v, counted = _decode_paged_impl(
        cfg, params, last_tokens, lengths, cache_k, cache_v, page_tables
    )
    rng = jax.random.fold_in(rng_base, step)
    nxt = sample(logits, temps, greedy_mask, rng)
    # a row that had no length has none after the step either: it stays
    # nobody's until the engine writes a sequence into it
    return nxt, jnp.where(lengths > 0, lengths + 1, 0), cache_k, cache_v, counted


@partial(jax.jit, static_argnums=(0,), donate_argnums=(4, 5))
def decode_multi_paged(cfg: AfmoeConfig, params, last_tokens, lengths,
                       cache_k, cache_v, page_tables, temps, greedy_mask,
                       rng_base, n_steps, step0):
    """``n_steps`` (at most ``MAX_DECODE_CHUNK``) tokens a row in one
    dispatch; one program runs every ``n_steps``. Returns (tokens
    [MAX_DECODE_CHUNK, S] with the first ``n_steps`` rows written, last
    tokens, lengths, k, v, the steps' counts)."""
    S = last_tokens.shape[0]

    def body(i, carry):
        last, lens, ck, cv, toks, counted = carry
        logits, ck, cv, step_counted = _decode_paged_impl(
            cfg, params, last, lens, ck, cv, page_tables
        )
        rng = jax.random.fold_in(rng_base, step0 + i)
        nxt = sample(logits, temps, greedy_mask, rng)
        toks = lax.dynamic_update_index_in_dim(toks, nxt, i, axis=0)
        return nxt, jnp.where(lens > 0, lens + 1, 0), ck, cv, toks, counted + step_counted

    last, lens, cache_k, cache_v, toks, counted = lax.fori_loop(
        0, n_steps, body,
        (last_tokens, lengths, cache_k, cache_v,
         jnp.zeros((MAX_DECODE_CHUNK, S), jnp.int32),
         jnp.zeros((len(STEP_COUNTERS),), jnp.int32)),
    )
    return toks, last, lens, cache_k, cache_v, counted
