"""MiMo-V2 (``model_type: mimo_v2``) for the serving engine: the language
model of https://huggingface.co/XiaomiMiMo/MiMo-V2.5, as one chip of an
expert-parallel deployment holds it, with its paged decode.

The block, pre-norm and without a bias anywhere:

    x += Attn(rmsnorm(x));  x += FFN(rmsnorm(x));  logits = W_head rmsnorm(x)

- Layers are of three kinds, by ``hybrid_layer_pattern`` (0 full, 1 window
  attention) and ``moe_layer_freq`` (0 dense, 1 experts), so the layers are
  a Python list and the programs unroll over it: GPT-2's loop over one
  stacked block does not carry over.
- Attention: 64 query heads of 192 over 4 (full) or 8 (window) K/V heads,
  K of 192 and V of 128, V scaled by ``attention_value_scale``; rotary
  positions (rotate-half) on the leading ``int(192 * 0.334) = 64``
  dimensions with base ``rope_theta`` (full) or ``swa_rope_theta``
  (window). Window layers see the last ``sliding_window`` positions, the
  current one among them, and a learned logit a head (the sink) joins the
  softmax's denominator and nothing else.
- FFN: a SwiGLU, dense in layer 0, else ``ops.moe.expert_layer``: sigmoid
  scores over all ``router_experts``, top ``num_experts_per_tok`` of score
  plus selection bias, gates renormalised; this chip computes the part of
  the sum its ``n_routed_experts`` held experts give.
- The embedding and the untied head hold ``vocab_size`` rows: the chip's
  slice of the vocabulary, over which logits and sampling run.

The cache has a spec a layer (``cache_spec``): a full layer's K and V are
paged like GPT-2's, ``[pages, B, kv_heads * size]`` with a sequence's
pages named by its page table; a window layer's are a ring a decode row,
``[rows, sliding_window, kv_heads * size]``, position p in slot
``p % sliding_window``, so that its bytes and its reads are the window's
however long the row grows. Decode reads a row's own pages in full layers
(a loop over page-table columns that stops at the longest row) and the
ring in window layers. A prefix hit would have to restore the rings, which
nothing does yet: ``PREFIX_CACHE`` is False and the engine refuses hits by
name.

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
from ray_tpu.ops import cached_attention, moe, page_loops, paged_kv_attention
# attention over pages and rings, shared with the other family that has both
# kinds of layer (models/afmoe.py)
from ray_tpu.ops.cached_attention import (  # noqa: F401
    LayerCache, paged_attend as _paged_attend, ring_positions as _ring_positions,
    window_attend as _window_attend,
)

PREFIX_CACHE = False   # a hit would have to restore the window layers' rings
DECODE_ATTENTION = "own_pages_and_rings"
MAX_DECODE_CHUNK = 8
# the rows a prefill call takes: the row counts the engine compiles (a call
# of fewer rows is padded with rows of length 0) and the widths of a row. A
# window layer's ring belongs to a decode row, so a sequence takes one row
# of a call (``PREFIX_CACHE`` is False). Chosen by a sweep on the chip at
# the served shapes (PERF.md §6, PR 50): the dense products are most of a
# call here, so a row of no length costs more than the weights read once
# save (two prompts of 300: 54.5 ms as two calls, 42.1 ms as two rows, 66.8
# ms as two of four rows); a call of four rows is worth a fifth of itself
# when four prompts wait together (68.3 ms against 84.2), which the served
# traffic does too rarely (1.14 rows a call) to pay three more programs'
# set-up
PREFILL_ROWS = (1, 2)
PREFILL_ROW_WIDTHS = (128, 256, 512)
# what a decode program counts beside its tokens: the expert layers' counts
# summed over layers and steps, the positions its live rows attended over in
# a full layer and the positions the kernel read for them (the live rows'
# pages x positions a page), both summed over steps (once a step, not a
# layer); the engine adds each to its ``rt_serve_<name>_total``
STEP_COUNTERS = (*(f"moe_{name}" for name in moe.STATS), "attn_context_tokens",
                 "attn_loop_tokens")


@dataclasses.dataclass(frozen=True)
class MiMoV2Config:
    """The published config's keys, under their names. ``n_routed_experts``
    and ``vocab_size`` are what this chip holds; ``router_experts`` is the
    published count the router scores, ``first_expert`` the first held."""

    vocab_size: int = 152576
    max_position_embeddings: int = 1048576
    hidden_size: int = 4096
    num_attention_heads: int = 64
    head_dim: int = 192
    v_head_dim: int = 128
    num_key_value_heads: int = 4
    swa_num_key_value_heads: int = 8
    sliding_window: int = 128
    rope_theta: float = 10_000_000.0
    swa_rope_theta: float = 10_000.0
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    router_experts: int = 256
    first_expert: int = 0
    num_experts_per_tok: int = 8
    hybrid_layer_pattern: Tuple[int, ...] = (0, 1, 1, 1, 1, 0)
    moe_layer_freq: Tuple[int, ...] = (0, 1, 1, 1, 1, 1)
    layernorm_epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16  # compute type, and the stored weights'

    # what the engine asks of any model's config
    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def n_layer(self) -> int:
        return len(self.hybrid_layer_pattern)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def kv_heads(self, layer: int) -> int:
        return (self.swa_num_key_value_heads if self.hybrid_layer_pattern[layer]
                else self.num_key_value_heads)


# the chip's share of a 16-chip deployment (benchmark/configs/mimo-v2.5-serve.json):
# 16 of 256 experts, an eighth of the vocabulary, the leading dense layer
# and one whole period
CONFIGS: Dict[str, MiMoV2Config] = {
    "mimo-v2.5": MiMoV2Config(
        vocab_size=19072, max_position_embeddings=4096, n_routed_experts=16,
        hybrid_layer_pattern=(0, 1, 1, 1, 1, 1, 0),
        moe_layer_freq=(0, 1, 1, 1, 1, 1, 1),
    ),
    # the CPU tests' preset: every mechanism, no published width
    "mimo-v2-tiny": MiMoV2Config(
        vocab_size=256, max_position_embeddings=256, hidden_size=64,
        num_attention_heads=4, head_dim=24, v_head_dim=16,
        num_key_value_heads=1, swa_num_key_value_heads=2, sliding_window=16,
        intermediate_size=128, moe_intermediate_size=32, n_routed_experts=4,
        router_experts=16, num_experts_per_tok=4,
        hybrid_layer_pattern=(0, 1, 1, 0), moe_layer_freq=(0, 1, 1, 1),
    ),
}


# -- parameters -----------------------------------------------------------


@partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init(key, cfg: MiMoV2Config):
    """Seeded weights in the type the programs compute in, a leaf a
    program (the float32 draw of the largest leaf, 16 experts' gate
    kernels, is 0.5 GB and gone before the next). Scales are chosen so
    that every sublayer moves the residual stream by about its own size;
    the selection bias and the sinks are drawn non-zero, so that a program
    that drops them does not agree with the reference."""
    dt = cfg.dtype
    D, H, Dk, Dv = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim, cfg.v_head_dim
    F, Fm, El, E = (cfg.intermediate_size, cfg.moe_intermediate_size,
                    cfg.n_routed_experts, cfg.router_experts)
    keys = iter(jax.random.split(key, 16 * cfg.n_layer + 4))

    def w(shape, fan_in, gain=1.0, dtype=dt):
        return _normal(next(keys), tuple(shape), gain / fan_in ** 0.5, dtype)

    layers: List[Dict[str, Any]] = []
    for l in range(cfg.n_layer):
        Hkv = cfg.kv_heads(l)
        attn = {"wq": w((D, H * Dk), D), "wk": w((D, Hkv * Dk), D),
                "wv": w((D, Hkv * Dv), D), "wo": w((H * Dv, D), H * Dv)}
        if cfg.hybrid_layer_pattern[l]:
            attn["sink"] = _normal(next(keys), (H,), 1.0, jnp.float32)
        layer = {"norm1": jnp.ones((D,), jnp.float32),
                 "norm2": jnp.ones((D,), jnp.float32), "attn": attn}
        if cfg.moe_layer_freq[l]:
            layer["moe"] = {
                "router": w((D, E), D, dtype=jnp.float32),
                # small beside the scores' own spread (0.2): a trained
                # bias balances the experts, one drawn at random skews
                # them, and at 0.1 a quarter of the held experts took most
                # of the tokens (PERF.md, PR 46)
                "bias": _normal(next(keys), (E,), 0.02, jnp.float32),
                "gate": w((El, D, Fm), D), "up": w((El, D, Fm), D),
                # an expert's output at the size of the other sublayers':
                # a token takes an eighth of each of eight
                "down": w((El, Fm, D), Fm, gain=4.0),
            }
        else:
            layer["mlp"] = {"gate": w((D, F), D), "up": w((D, F), D),
                            "down": w((F, D), F, gain=2.0)}
        layers.append(layer)
    return {"embed": w((cfg.vocab_size, D), 1.0), "layers": layers,
            "norm_f": jnp.ones((D,), jnp.float32),
            "head": w((cfg.vocab_size, D), D)}


def load_serving_params(cfg: MiMoV2Config, checkpoint_path=None):
    """The weights of an engine of ``cfg``, on the device, in ``cfg.dtype``
    (the norms, the sinks and the router in float32): a pickled tree of
    ``init``'s layout cast leaf by leaf, else ``init`` from ``PRNGKey(0)``."""
    if checkpoint_path:
        import pickle

        with open(checkpoint_path, "rb") as f:
            tree = pickle.load(f)
        like = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
        return jax.tree.map(lambda a, s: jnp.asarray(a, s.dtype), tree, like)
    return init(jax.random.PRNGKey(0), cfg)


# -- the cache ------------------------------------------------------------


def cache_spec(cfg: MiMoV2Config) -> List[Dict[str, Any]]:
    """What one layer keeps a position: its kind, K/V heads and sizes."""
    return [{"kind": "window" if cfg.hybrid_layer_pattern[l] else "full",
             "kv_heads": cfg.kv_heads(l), "k_size": cfg.head_dim,
             "v_size": cfg.v_head_dim} for l in range(cfg.n_layer)]


def init_paged_cache(cfg: MiMoV2Config, num_pages: int, page_tokens: int,
                     rows: int = 1):
    """(k, v) caches, zeroed, for ``rows`` decode rows over ``num_pages``
    pages (``ops.cached_attention.init_caches`` decides the stored shapes)."""
    return cached_attention.init_caches(cache_spec(cfg), cfg.sliding_window, num_pages,
                                        page_tokens, rows, cfg.dtype)


def cache_layout(cfg: MiMoV2Config, cache_k: LayerCache, cache_v: LayerCache) -> Dict[str, Any]:
    """The stored shape of every layer's K and the bytes both caches hold
    on the device, by kind."""
    return cached_attention.layout(cache_spec(cfg), cache_k, cache_v)


# -- the block ------------------------------------------------------------


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def _rope(x, pos, rotary_dim: int, theta: float):
    """Rotate-half on the leading ``rotary_dim`` dimensions of ``x``
    [..., heads, size] at positions ``pos`` [...]; the rest pass."""
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary_dim)
    ang = pos.astype(jnp.float32)[..., None, None] * inv  # [..., 1, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b, rest = x32[..., :half], x32[..., half:rotary_dim], x32[..., rotary_dim:]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, rest], axis=-1
    ).astype(x.dtype)


def _qkv(cfg: MiMoV2Config, l: int, attn, h, pos):
    """h [T, D] at positions ``pos`` [T] -> q [T, H, Dk] and, the heads
    merged as the caches store them, k [T, Hkv * Dk], v [T, Hkv * Dv];
    rotated and scaled."""
    dt = cfg.dtype
    T = h.shape[0]
    window = bool(cfg.hybrid_layer_pattern[l])
    theta = cfg.swa_rope_theta if window else cfg.rope_theta
    q = (h @ attn["wq"].astype(dt)).reshape(T, cfg.num_attention_heads, cfg.head_dim)
    k = (h @ attn["wk"].astype(dt)).reshape(T, cfg.kv_heads(l), cfg.head_dim)
    v = (h @ attn["wv"].astype(dt)).reshape(T, cfg.kv_heads(l), cfg.v_head_dim)
    q = _rope(q, pos, cfg.rotary_dim, theta)
    k = _rope(k, pos, cfg.rotary_dim, theta)
    v = (v.astype(jnp.float32) * cfg.attention_value_scale).astype(dt)
    return q, k.reshape(T, -1), v.reshape(T, -1)


def _ffn(cfg: MiMoV2Config, layer, h, live):
    """(the block's second half on h [T, D], float32; its expert counts)."""
    dt = cfg.dtype
    if "moe" in layer:
        return moe.expert_layer(h, layer["moe"], first=cfg.first_expert,
                                top_k=cfg.num_experts_per_tok, live=live)
    mlp = layer["mlp"]
    g = jnp.dot(h, mlp["gate"].astype(dt), preferred_element_type=jnp.float32)
    u = jnp.dot(h, mlp["up"].astype(dt), preferred_element_type=jnp.float32)
    y = jnp.dot((jax.nn.silu(g) * u).astype(dt), mlp["down"].astype(dt),
                preferred_element_type=jnp.float32)
    return y, jnp.zeros((len(moe.STATS),), jnp.int32)


def _rest_of_block(cfg: MiMoV2Config, layer, x, att, live):
    """x [T, D] float32 and the heads' output att [T, H * Dv] -> the
    block's output and its expert counts."""
    dt = cfg.dtype
    x = x + jnp.dot(att.astype(dt), layer["attn"]["wo"].astype(dt),
                    preferred_element_type=jnp.float32)
    h = _rmsnorm(x, layer["norm2"], cfg.layernorm_epsilon).astype(dt)
    y, stats = _ffn(cfg, layer, h, live)
    return x + y, stats


def _logits(cfg: MiMoV2Config, params, x):
    h = _rmsnorm(x, params["norm_f"], cfg.layernorm_epsilon).astype(cfg.dtype)
    return jnp.dot(h, params["head"].astype(cfg.dtype).T,
                   preferred_element_type=jnp.float32)


# -- the programs ---------------------------------------------------------


@partial(jax.jit, static_argnums=(0,), donate_argnums=(5, 6))
def prefill_paged(cfg: MiMoV2Config, params, tokens, start, length, cache_k,
                  cache_v, page_table, row=0):
    """Prefill the rows of one call: ``tokens`` [R, P] (right-padded,
    ``length`` [R] real) are positions start .. start + P - 1 (``start``
    [R]) of the sequences in decode rows ``row`` [R], no two the same,
    whose page tables are ``page_table`` [R, MaxPages]; what lies before a
    row's ``start`` is already cached (this sequence's earlier chunk). Full
    layers write every row's chunk through its page table and attend over
    each row's own pages; window layers attend over each row's ring as the
    earlier chunk left it and the chunk itself, then put the chunk's last
    ``sliding_window`` real positions into the ring. The rest of the block,
    the experts among it, runs once on the [R * P, D] tokens of all rows,
    so a call reads its weights once. A row of length 0 is nobody's: it
    writes to the scratch page and into no ring, what its queries see is
    not used and the experts do not see it. Returns the last real
    position's logits of every row [R, vocab] and the caches.

    A call of one row may give ``start``, ``length`` and ``row`` as scalars
    and ``page_table`` as [MaxPages], and gets its logits as [vocab].

    Padded positions are written to the scratch page and into no ring."""
    one = page_table.ndim == 1
    if one:
        start, length, page_table, row = (
            jnp.asarray(a)[None] for a in (start, length, page_table, row))
    dt = cfg.dtype
    R, P = tokens.shape
    B = cache_k.page_tokens
    W = cfg.sliding_window
    max_pages = page_table.shape[1]
    pos = start[:, None] + jnp.arange(P)                              # [R, P]
    live = jnp.arange(P) < length[:, None]
    x = params["embed"].astype(dt)[tokens.reshape(-1)].astype(jnp.float32)  # [R * P, D]
    page_of = jnp.take_along_axis(page_table, jnp.clip(pos // B, 0, max_pages - 1), axis=1)
    page_of = jnp.where(live, page_of, 0)
    # the rings before this call, and after it: slot r of a row's ring held
    # position ``before[r]`` and takes the chunk's ``after[r] - start``
    # where that is one of the chunk's own
    before = _ring_positions(start, W)                                # [R, W]
    after = _ring_positions(start + length, W)
    takes = (after >= start[:, None]) & (length[:, None] > 0)
    src = jnp.clip(after - start[:, None], 0, P - 1)[..., None]
    k_pos = jnp.concatenate([before, pos], axis=1)                    # [R, W + P]
    gap = pos[:, :, None] - k_pos[:, None, :]
    in_window = (k_pos >= 0)[:, None, :] & (gap >= 0) & (gap < W)     # [R, P, W + P]
    # one loop over the rows' pages, to the longest row's last real position
    loops = page_loops.one_loop(
        jnp.where(length > 0, start + length - 1, 0),
        B * page_loops.pages_a_turn(max_pages, 8))
    ks, vs = list(cache_k.layers), list(cache_v.layers)
    for l, layer in enumerate(params["layers"]):
        Hkv = cfg.kv_heads(l)
        h = _rmsnorm(x, layer["norm1"], cfg.layernorm_epsilon).astype(dt)
        q, k, v = _qkv(cfg, l, layer["attn"], h, pos.reshape(-1))
        q, k, v = (a.reshape(R, P, *a.shape[1:]) for a in (q, k, v))
        if cfg.hybrid_layer_pattern[l]:
            old_k, old_v = ks[l][row], vs[l][row]                     # [R, W, ..]
            att = _window_attend(q, jnp.concatenate([old_k, k], axis=1),
                                 jnp.concatenate([old_v, v], axis=1), in_window,
                                 Hkv, layer["attn"]["sink"])
            new_k = jnp.where(takes[..., None], jnp.take_along_axis(k, src, axis=1), old_k)
            new_v = jnp.where(takes[..., None], jnp.take_along_axis(v, src, axis=1), old_v)
            # behind the last ring where the row has no length: dropped
            ring = jnp.where(length > 0, row, ks[l].shape[0])
            ks[l] = ks[l].at[ring].set(new_k, mode="drop")
            vs[l] = vs[l].at[ring].set(new_v, mode="drop")
        else:
            ks[l] = ks[l].at[page_of, pos % B].set(k)
            vs[l] = vs[l].at[page_of, pos % B].set(v)
            att = _paged_attend(q, ks[l], vs[l], page_table, pos, Hkv, loops)
        x, _ = _rest_of_block(cfg, layer, x, att.reshape(R * P, -1), live.reshape(-1))
    ends = x.reshape(R, P, -1)[jnp.arange(R), jnp.maximum(length - 1, 0)]
    logits = _logits(cfg, params, ends)
    return ((logits[0] if one else logits), LayerCache(tuple(ks), B),
            LayerCache(tuple(vs), B))


def _decode_paged_impl(cfg: MiMoV2Config, params, last_tokens, lengths,
                       cache_k, cache_v, page_tables):
    """One token for every row: [S] last tokens at positions ``lengths``
    write their K/V (full layers through ``page_tables`` [S, MaxPages],
    window layers into their row's ring) and attend, full layers over the
    row's own pages, window layers over the ring. A row of length 0 is
    nobody's: its full-layer write lands in the scratch page, it writes no
    ring, and the experts do not see it. Returns logits [S, vocab], the
    caches and what the step counted (``STEP_COUNTERS``)."""
    dt = cfg.dtype
    S = last_tokens.shape[0]
    B = cache_k.page_tokens
    W = cfg.sliding_window
    T = page_tables.shape[1] * B
    pos = jnp.clip(lengths, 0, T - 1)
    live = lengths > 0
    rows = jnp.arange(S)
    x = params["embed"].astype(dt)[last_tokens].astype(jnp.float32)  # [S, D]
    page_of = page_tables[rows, pos // B]
    slot = jnp.where(live, pos % W, W)  # W is no slot: the write is dropped
    in_ring = _ring_positions(pos + 1, W) >= 0  # [S, W]
    walk = paged_kv_attention.page_visits(pos, page_tables.shape[1], B)
    ks, vs = list(cache_k.layers), list(cache_v.layers)
    stats = jnp.zeros((len(moe.STATS),), jnp.int32)
    for l, layer in enumerate(params["layers"]):
        Hkv = cfg.kv_heads(l)
        h = _rmsnorm(x, layer["norm1"], cfg.layernorm_epsilon).astype(dt)
        q, k, v = _qkv(cfg, l, layer["attn"], h, pos)
        if cfg.hybrid_layer_pattern[l]:
            ks[l] = ks[l].at[rows, slot].set(k, mode="drop")
            vs[l] = vs[l].at[rows, slot].set(v, mode="drop")
            att = _window_attend(q[:, None], ks[l], vs[l], in_ring[:, None],
                                 Hkv, layer["attn"]["sink"])[:, 0]
        else:
            ks[l] = ks[l].at[page_of, pos % B].set(k)
            vs[l] = vs[l].at[page_of, pos % B].set(v)
            att = _paged_attend(q[:, None], ks[l], vs[l], page_tables,
                                pos[:, None], Hkv, walk)[:, 0]
        x, counted = _rest_of_block(cfg, layer, x, att, live)
        stats = stats + counted
    context = jnp.sum(jnp.where(live, pos + 1, 0), dtype=jnp.int32)
    read = paged_kv_attention.positions_read(pos, live, B)
    return (_logits(cfg, params, x), LayerCache(tuple(ks), B),
            LayerCache(tuple(vs), B),
            jnp.concatenate([stats, context[None], read[None]]))


@partial(jax.jit, static_argnums=(0,), donate_argnums=(4, 5))
def decode_paged_and_sample(cfg: MiMoV2Config, params, last_tokens, lengths,
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
def decode_multi_paged(cfg: MiMoV2Config, params, last_tokens, lengths,
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
