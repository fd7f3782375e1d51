"""``model_type: phi4flash`` for the serving engine, as
Phi-4-mini-flash-reasoning
(https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning, 3.8 B dense;
"SambaY", arXiv:2507.06607) sets it: a self-decoder of Mamba layers and
differential attention over a window, one full-attention layer whose K and V
the whole cross-decoder reads, and gated memory units on one Mamba layer's
scan output.

Pre-norm, LayerNorm with bias everywhere, no positions of any kind, the
head tied to the embedding:

    x = embed[t];  x += Mix_l(LN1_l(x));  x += MLP_l(LN2_l(x));  logits = embed . LN_f(x)
    MLP(h) = W2 (u * silu(g)),  [g | u] = W1 h

``Mix_l`` by ``kind(l)``, N layers (``mb_per_layer`` 2: even layers are
Mamba-kind, odd layers attention-kind):

- ``mamba`` (even l <= N/2): ``[x | z] = W_in h``; ``y`` the selective scan
  of ``x`` (``ops/selective_scan.py``); ``out = W_out (y * silu(z))``. Layer
  N/2 also keeps its ``y`` as the memory ``m`` of the pass.
- ``window`` (odd l < N/2) and ``full`` (l = N/2 + 1): differential attention
  (arXiv:2410.05258, two softmaxes). The H query heads and Hkv K/V heads are
  taken as pairs (head 2j and 2j + 1); query pair j reads K/V pair j // (H /
  Hkv): ``A1 = softmax(q1 k1' / sqrt(Dh))``, ``A2 = softmax(q2 k2' / sqrt(Dh))``,
  ``o = (A1 - lambda A2) [v1 | v2]``, ``lambda = exp(lq1 . lk1) - exp(lq2 .
  lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``; ``o =
  RMSNorm(o) * (1 - lambda_init)`` over a pair's 2 Dh; ``out = W_o o + b_o``.
- ``gmu`` (even l > N/2 + 1): ``out = W_o (m * silu(W_i h))``, m of the same
  position. No cache.
- ``cross`` (odd l > N/2 + 1): the same attention with queries of its own
  (``q = W_q h + b``) over the full layer's K and V as cached. No cache.

Query head 2j meets K head 2 (j // r) and head 2j + 1 meets K head 2 (j // r)
+ 1 (r = H / Hkv), which is not the grouping ``ops.cached_attention.products``
knows (head h reads h // r). The query heads are therefore taken in another
order (``_pairs_first``): then the usual grouping holds for the keys, and the
values, taken as Hkv / 2 heads of 2 Dh as they lie in the cache
(``v_heads``), fall to the right heads too. Two softmaxes over H heads of Dh
keys and 2 Dh values: the walks over pages and rings are the other families'.

The cache has a spec a layer (``cache_spec``): a Mamba layer keeps a
``state`` a decode row (the recurrent state in float32, because it is
multiplied into itself at every position, 0.38 of the 0.41 GB; the
convolution's last inputs in the compute type), a window layer a ring a row,
the full layer pages; the layers behind it keep nothing. A prefix hit would
have to restore rings and states: ``PREFIX_CACHE`` is False.

Layers behind the full one write no cache and pass nothing forward in time,
so a prompt position nobody samples from needs the self-decoder only:
``prefill_paged`` runs layers 0 .. N/2 + 1 over the chunk and the
cross-decoder over each row's last real position alone.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.gpt2_decode import (  # noqa: F401 — the engine's interface
    params_bytes, sample, update_rows_paged,
)
from ray_tpu.ops import cached_attention as ca
from ray_tpu.ops import page_loops, paged_kv_attention, selective_scan
from ray_tpu.ops.cached_attention import LayerCache

PREFIX_CACHE = False   # a hit would have to restore the rings and the states
DECODE_ATTENTION = "own_pages_rings_and_states"
MAX_DECODE_CHUNK = 8
# the rows a prefill call takes and the widths of a row (rings and states
# belong to a decode row, so a sequence takes one row of a call)
PREFILL_ROWS = (1, 2)
PREFILL_ROW_WIDTHS = (128, 256, 512)
# what a decode program counts beside its tokens, summed over steps, once a
# step and not a layer: the positions its live rows attended over in the full
# layer (every cross layer attends over the same), the positions the kernel
# read for them (the live rows' pages x positions a page), and the positions
# the window layers attended over, min(p + 1, window)
STEP_COUNTERS = ("attn_context_tokens", "attn_loop_tokens", "window_context_tokens")

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """The published config's keys, under their names, and the Mamba
    mixer's sizes, which the published config leaves to the modelling code's
    defaults (``assumed`` in the configuration's file)."""

    vocab_size: int = 200064
    max_position_embeddings: int = 262144
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    dtype: Any = jnp.bfloat16  # compute type, and the stored weights' and K/V's

    # what the engine asks of any model's config
    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return math.ceil(self.hidden_size / 16)

    @property
    def full_layer(self) -> int:
        return self.num_hidden_layers // 2 + 1

    def kind(self, l: int) -> str:
        attends = l % self.mb_per_layer == self.mb_per_layer - 1
        if l < self.full_layer:
            return WINDOW if attends else MAMBA
        if l == self.full_layer:
            return FULL
        return CROSS if attends else GMU

    def lambda_init(self, l: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * l)


CONFIGS: Dict[str, Phi4FlashConfig] = {
    # whole, every width; the declared positions cut to what the engine's
    # page tables and pool are sized by (benchmark/configs/phi-4-mini-flash-serve.json)
    "phi-4-mini-flash-reasoning": Phi4FlashConfig(max_position_embeddings=16384),
    # the CPU tests' preset: every kind of layer, no published width
    "phi-4-mini-flash-tiny": Phi4FlashConfig(
        vocab_size=256, max_position_embeddings=256, hidden_size=64,
        intermediate_size=128, num_hidden_layers=8, num_attention_heads=8,
        num_key_value_heads=4, sliding_window=16,
    ),
}


# -- parameters -----------------------------------------------------------


@partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init(key, cfg: Phi4FlashConfig):
    """Seeded weights, the matrices in the type the programs compute in and
    everything a vector long in float32, a leaf a program. Every matrix is
    drawn at 1 / sqrt(fan-in) and no larger: the stream has no norm behind a
    sublayer, 32 layers of products of products (a gate times a value, a
    step times an input times B times C) take a rounding error further the
    larger they are drawn, and at gains of 2 and 3 bfloat16 alone moved the
    logits by 0.9 (PERF.md §6, PR 57). The embedding's 1 / sqrt(hidden_size)
    gives the tied head logits of size one. Norms' scales are drawn away
    from one and biases, the lambda vectors and ``D`` away from zero, so
    that a program that drops one does not agree with the reference. The
    Mamba mixer as its paper draws it: ``A = -(1 .. N)`` a channel, the
    step's bias so that ``softplus`` gives steps log-uniform in [0.001,
    0.1], ``D`` about one; the convolution's four taps at 1 / sqrt(2)."""
    dt = cfg.dtype
    D, H, Hkv, Dh = (cfg.hidden_size, cfg.num_attention_heads,
                     cfg.num_key_value_heads, cfg.head_dim)
    F, Din, N, K = cfg.intermediate_size, cfg.d_inner, cfg.mamba_d_state, cfg.dt_rank
    keys = iter(jax.random.split(key, 24 * cfg.n_layer + 4))

    def w(shape, fan_in, dtype=dt):
        return _normal(next(keys), tuple(shape), fan_in ** -0.5, dtype)

    def vec(n, std, mean=0.0):
        return mean + _normal(next(keys), (n,), std, jnp.float32)

    def norm():
        return {"scale": vec(D, 0.1, 1.0), "bias": vec(D, 0.1)}

    def attention(kv: bool):
        wide = (H + 2 * Hkv) * Dh if kv else H * Dh
        return {"wqkv" if kv else "wq": w((D, wide), D),
                "bqkv" if kv else "bq": vec(wide, 0.1),
                "wo": w((H * Dh, D), H * Dh), "bo": vec(D, 0.1),
                "lambda_q1": vec(Dh, 0.1), "lambda_k1": vec(Dh, 0.1),
                "lambda_q2": vec(Dh, 0.1), "lambda_k2": vec(Dh, 0.1),
                "subln": vec(2 * Dh, 0.1, 1.0)}

    def mamba():
        step = jnp.exp(jax.random.uniform(next(keys), (Din,), jnp.float32)
                       * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        return {"in_proj": w((D, 2 * Din), D), "conv_w": w((cfg.mamba_d_conv, Din), 2.0,
                                                          dtype=jnp.float32),
                "conv_b": vec(Din, 0.1), "x_proj": w((Din, K + 2 * N), Din),
                "dt_proj": w((K, Din), K), "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.log(jnp.broadcast_to(
                    jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, Din))),
                "D": vec(Din, 0.1, 1.0), "out_proj": w((Din, D), Din)}

    layers: List[Dict[str, Any]] = []
    for l in range(cfg.n_layer):
        kind = cfg.kind(l)
        mix = (mamba() if kind == MAMBA else
               {"in_proj": w((D, Din), D), "out_proj": w((Din, D), Din)}
               if kind == GMU else attention(kv=kind != CROSS))
        layers.append({"norm1": norm(), "norm2": norm(), "mix": mix,
                       "mlp": {"w1": w((D, 2 * F), D), "w2": w((F, D), F)}})
    return {"embed": w((cfg.vocab_size, D), D), "layers": layers, "norm_f": norm()}


def load_serving_params(cfg: Phi4FlashConfig, checkpoint_path=None):
    """The weights of an engine of ``cfg``, on the device, in the types of
    ``init``: a pickled tree of its layout cast leaf by leaf, else ``init``
    from ``PRNGKey(0)`` (every leaf drawn and cast in one jitted program)."""
    if checkpoint_path:
        import pickle

        with open(checkpoint_path, "rb") as f:
            tree = pickle.load(f)
        like = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
        return jax.tree.map(lambda a, s: jnp.asarray(a, s.dtype), tree, like)
    return init(jax.random.PRNGKey(0), cfg)


# -- the cache ------------------------------------------------------------


def cache_spec(cfg: Phi4FlashConfig) -> List[Dict[str, Any]]:
    """What one layer keeps: K and V a position (a window layer in a ring
    a decode row, the full layer in pages), a Mamba layer two arrays a
    decode row, the cross-decoder nothing: a cross layer ``reads`` the full
    layer's pages."""
    kv = {"kv_heads": cfg.num_key_value_heads, "k_size": cfg.head_dim,
          "v_size": cfg.head_dim}
    state = {"k_row": (cfg.mamba_d_state, cfg.d_inner), "k_dtype": jnp.float32,
             "v_row": ((cfg.mamba_d_conv - 1) * cfg.d_inner,), "v_dtype": cfg.dtype}
    by_kind = {MAMBA: {"kind": "state", **state}, WINDOW: {"kind": "window", **kv},
               FULL: {"kind": "full", **kv}, GMU: {"kind": "none"},
               CROSS: {"kind": "none", "reads": cfg.full_layer}}
    return [by_kind[cfg.kind(l)] for l in range(cfg.n_layer)]


def init_paged_cache(cfg: Phi4FlashConfig, num_pages: int, page_tokens: int,
                     rows: int = 1):
    """(k, v) caches, zeroed, for ``rows`` decode rows over ``num_pages``
    pages (``ops.cached_attention.init_caches`` decides the stored shapes):
    a Mamba layer's state is its entry of ``k``, its convolution's inputs
    that of ``v``."""
    return ca.init_caches(cache_spec(cfg), cfg.sliding_window, num_pages,
                          page_tokens, rows, cfg.dtype)


def cache_layout(cfg: Phi4FlashConfig, cache_k: LayerCache, cache_v: LayerCache) -> Dict[str, Any]:
    """The stored shape of every layer's entry of ``k`` and the bytes both
    caches hold on the device, by kind."""
    return ca.layout(cache_spec(cfg), cache_k, cache_v)


def prefill_cross_positions(rows: int, tokens: int) -> int:
    """Of the ``tokens`` real positions of a prefill call's ``rows`` rows,
    those that went through the cross-decoder: the last one of each."""
    return rows


# -- the block ------------------------------------------------------------


def _layernorm(x, norm, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return (x32 - mean) * lax.rsqrt(var + eps) * norm["scale"] + norm["bias"]


def _mlp(cfg: Phi4FlashConfig, mlp, h):
    gu = jnp.dot(h, mlp["w1"], preferred_element_type=jnp.float32)
    g, u = jnp.split(gu, 2, axis=-1)
    return jnp.dot((u * jax.nn.silu(g)).astype(cfg.dtype), mlp["w2"],
                   preferred_element_type=jnp.float32)


def _pairs_first(cfg: Phi4FlashConfig, q):
    """q [..., H, Dh], head (p r + a) 2 + b the b-th of query pair p r + a,
    which reads K/V pair p -> head (2 p + b) r + a: the heads that read K
    head 2 p + b side by side, as ``products`` groups them, and the four
    that read value pair p too."""
    r = cfg.num_attention_heads // cfg.num_key_value_heads
    split = q.reshape(*q.shape[:-2], cfg.num_key_value_heads // 2, r, 2, q.shape[-1])
    return jnp.swapaxes(split, -3, -2).reshape(q.shape)


def _queries(cfg: Phi4FlashConfig, mix, h):
    """h [T, D] -> q [T, H, Dh] in ``_pairs_first``'s order, and K and V
    [T, Hkv * Dh] as the cache stores them (None from a cross layer)."""
    H, Dh = cfg.num_attention_heads, cfg.head_dim
    if "wq" in mix:
        q, k, v = h @ mix["wq"] + mix["bq"].astype(cfg.dtype), None, None
    else:
        qkv = h @ mix["wqkv"] + mix["bqkv"].astype(cfg.dtype)
        q, k, v = jnp.split(qkv, [H * Dh, (H + cfg.num_key_value_heads) * Dh], axis=-1)
    return _pairs_first(cfg, q.reshape(-1, H, Dh)), k, v


def _differential(cfg: Phi4FlashConfig, l: int, mix, att):
    """The heads' two softmaxes' outputs ``att`` [T, H * 2 Dh] float32 (in
    ``_pairs_first``'s order) -> the mixer's output [T, D] float32."""
    Hkv, Dh = cfg.num_key_value_heads, cfg.head_dim
    r = cfg.num_attention_heads // Hkv
    lam = (jnp.exp(jnp.sum(mix["lambda_q1"] * mix["lambda_k1"]))
           - jnp.exp(jnp.sum(mix["lambda_q2"] * mix["lambda_k2"])) + cfg.lambda_init(l))
    both = att.reshape(-1, Hkv // 2, 2, r, 2 * Dh)
    o = both[:, :, 0] - lam * both[:, :, 1]                     # [T, Hkv / 2, r, 2 Dh]
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.layer_norm_eps)
    o = o * mix["subln"] * (1.0 - cfg.lambda_init(l))
    return jnp.dot(o.reshape(o.shape[0], -1).astype(cfg.dtype), mix["wo"],
                   preferred_element_type=jnp.float32) + mix["bo"]


def _gmu(cfg: Phi4FlashConfig, mix, h, memory):
    with jax.named_scope("gated_memory_unit"):
        g = jnp.dot(h, mix["in_proj"], preferred_element_type=jnp.float32)
        return jnp.dot((memory * jax.nn.silu(g)).astype(cfg.dtype), mix["out_proj"],
                       preferred_element_type=jnp.float32)


def _mamba_in(mix, h):
    xz = jnp.dot(h, mix["in_proj"], preferred_element_type=jnp.float32)
    return jnp.split(xz, 2, axis=-1)


def _mamba_out(cfg: Phi4FlashConfig, mix, y, z):
    return jnp.dot((y * jax.nn.silu(z)).astype(cfg.dtype), mix["out_proj"],
                   preferred_element_type=jnp.float32)


def _logits(cfg: Phi4FlashConfig, params, x):
    h = _layernorm(x, params["norm_f"], cfg.layer_norm_eps).astype(cfg.dtype)
    return jnp.dot(h, params["embed"].T, preferred_element_type=jnp.float32)


def _cross_decoder(cfg: Phi4FlashConfig, params, x, memory, attend):
    """Layers behind the full one on ``x`` [S, D] float32, one position a
    row, ``memory`` [S, d_inner] the memory layer's scan output at that
    position; ``attend(q [S, 1, H, Dh])`` -> [S, 1, H * 2 Dh] attends over
    the full layer's pages. Returns x."""
    dt, eps = cfg.dtype, cfg.layer_norm_eps
    with jax.named_scope("cross_decoder"):
        for l in range(cfg.full_layer + 1, cfg.n_layer):
            layer = params["layers"][l]
            h = _layernorm(x, layer["norm1"], eps).astype(dt)
            if cfg.kind(l) == GMU:
                x = x + _gmu(cfg, layer["mix"], h, memory)
            else:
                q, _, _ = _queries(cfg, layer["mix"], h)
                x = x + _differential(cfg, l, layer["mix"], attend(q[:, None])[:, 0])
            x = x + _mlp(cfg, layer["mlp"], _layernorm(x, layer["norm2"], eps).astype(dt))
    return x


# -- the programs ---------------------------------------------------------


@partial(jax.jit, static_argnums=(0,), donate_argnums=(5, 6))
def prefill_paged(cfg: Phi4FlashConfig, params, tokens, start, length, cache_k,
                  cache_v, page_table, row=0):
    """Prefill the rows of one call: ``tokens`` [R, P] (right-padded,
    ``length`` [R] real) are positions start .. start + P - 1 (``start``
    [R]) of the sequences in decode rows ``row`` [R], no two the same,
    whose page tables are ``page_table`` [R, MaxPages]; what lies before a
    row's ``start`` is already cached (this sequence's earlier chunks): in
    the full layer's pages, the window layers' rings and the Mamba layers'
    states of its row. A row whose ``start`` is 0 starts from a zero state
    and an empty ring whatever the row held, so a retired row needs no
    cleaning. The self-decoder (layers 0 .. N/2 + 1) runs over the chunk;
    the cross-decoder runs over each row's last real position alone, over
    the full layer's pages as this call left them. A row of length 0 is
    nobody's: it writes to the scratch page, into no ring and no state, and
    what its queries see is not used. Returns the last real position's
    logits of every row [R, vocab] and the caches.

    A call of one row may give ``start``, ``length`` and ``row`` as scalars
    and ``page_table`` as [MaxPages], and gets its logits as [vocab]."""
    one = page_table.ndim == 1
    if one:
        start, length, page_table, row = (
            jnp.asarray(a)[None] for a in (start, length, page_table, row))
    dt, eps, Hkv, W = cfg.dtype, cfg.layer_norm_eps, cfg.num_key_value_heads, cfg.sliding_window
    R, P = tokens.shape
    B = cache_k.page_tokens
    max_pages = page_table.shape[1]
    pos = start[:, None] + jnp.arange(P)                              # [R, P]
    live = jnp.arange(P) < length[:, None]
    x = params["embed"][tokens.reshape(-1)].astype(jnp.float32)       # [R * P, D]
    page_of = jnp.take_along_axis(page_table, jnp.clip(pos // B, 0, max_pages - 1), axis=1)
    page_of = jnp.where(live, page_of, 0)
    end = jnp.maximum(length - 1, 0)                                  # [R] in the chunk
    last = jnp.where(length > 0, start + length - 1, 0)               # [R] in the sequence
    # one loop over the rows' pages, to the longest row's last real position
    span = B * page_loops.pages_a_turn(max_pages, 8)
    loops = page_loops.one_loop(last, span)
    # where a row of no length writes: behind the last row, dropped
    own = jnp.where(length > 0, row, cache_k.layers[0].shape[0])
    ks, vs = list(cache_k.layers), list(cache_v.layers)
    memory = None
    for l in range(cfg.full_layer + 1):
        layer, kind = params["layers"][l], cfg.kind(l)
        mix = layer["mix"]
        h = _layernorm(x, layer["norm1"], eps).astype(dt)
        if kind == MAMBA:
            with jax.named_scope("selective_scan"):
                xm, z = _mamba_in(mix, h)
                y, (s, conv) = selective_scan.chunk_scan(
                    mix, xm.reshape(R, P, -1), (ks[l][row], vs[l][row]), start, length)
                ks[l] = ks[l].at[own].set(s, mode="drop")
                vs[l] = vs[l].at[own].set(conv, mode="drop")
            y = y.reshape(R * P, -1)
            if l == cfg.full_layer - 1:
                memory = y.reshape(R, P, -1)[jnp.arange(R), end]      # [R, d_inner]
            x = x + _mamba_out(cfg, mix, y, z)
        else:
            q, k, v = _queries(cfg, mix, h)
            q, k, v = (a.reshape(R, P, *a.shape[1:]) for a in (q, k, v))
            if kind == WINDOW:
                own_k, own_v = ca.ring_rows(ks[l], row), ca.ring_rows(vs[l], row)
                att = ca.ring_chunk_attend(q, own_k, own_v, k, v, start, pos, W, Hkv,
                                           v_heads=Hkv // 2)
                ks[l] = ca.ring_take(ks[l], own_k, k, start, length, row)
                vs[l] = ca.ring_take(vs[l], own_v, v, start, length, row)
            else:
                ks[l] = ks[l].at[page_of, pos % B].set(k)
                vs[l] = vs[l].at[page_of, pos % B].set(v)
                att = ca.paged_attend(q, ks[l], vs[l], page_table, pos, Hkv, loops,
                                      v_heads=Hkv // 2)
            x = x + _differential(cfg, l, mix, att.reshape(R * P, -1))
        x = x + _mlp(cfg, layer["mlp"], _layernorm(x, layer["norm2"], eps).astype(dt))
    pool_k, pool_v = ks[cfg.full_layer], vs[cfg.full_layer]

    def attend(q):
        return ca.paged_attend(q, pool_k, pool_v, page_table, last[:, None], Hkv, loops,
                               v_heads=Hkv // 2)

    ends = x.reshape(R, P, -1)[jnp.arange(R), end]
    logits = _logits(cfg, params, _cross_decoder(cfg, params, ends, memory, attend))
    return ((logits[0] if one else logits), LayerCache(tuple(ks), B),
            LayerCache(tuple(vs), B))


def _decode_paged_impl(cfg: Phi4FlashConfig, params, last_tokens, lengths,
                       cache_k, cache_v, page_tables):
    """One token for every row: [S] last tokens at positions ``lengths``
    advance their Mamba layers' states, write their K/V (the full layer
    through ``page_tables`` [S, MaxPages], window layers into their row's
    ring) and attend: window layers over the ring, the full layer and every
    cross layer over the row's own pages of the full layer, each row to its
    own length, under one numbering of the step's visits
    (``ops/paged_kv_attention.py``). A row of length 0 is nobody's (a free
    row, or one whose sequence is still being prefilled): its full-layer
    write lands in the scratch page, and its rings, states and convolution
    inputs stay as they are. Returns logits [S, vocab], the caches and what
    the step counted (``STEP_COUNTERS``)."""
    dt, eps, Hkv, W = cfg.dtype, cfg.layer_norm_eps, cfg.num_key_value_heads, cfg.sliding_window
    S = last_tokens.shape[0]
    B = cache_k.page_tokens
    T = page_tables.shape[1] * B
    pos = jnp.clip(lengths, 0, T - 1)
    live = lengths > 0
    rows = jnp.arange(S)
    x = params["embed"][last_tokens].astype(jnp.float32)              # [S, D]
    page_of = page_tables[rows, pos // B]
    slot = jnp.where(live, pos % W, W)  # W is no slot: the write is dropped
    walk = paged_kv_attention.page_visits(pos, page_tables.shape[1], B)
    in_rings = ca.ring_visits(pos, W)
    ks, vs = list(cache_k.layers), list(cache_v.layers)
    memory = None
    for l in range(cfg.full_layer + 1):
        layer, kind = params["layers"][l], cfg.kind(l)
        mix = layer["mix"]
        h = _layernorm(x, layer["norm1"], eps).astype(dt)
        if kind == MAMBA:
            with jax.named_scope("selective_scan"):
                xm, z = _mamba_in(mix, h)
                y, (ks[l], vs[l]) = selective_scan.step(mix, xm, (ks[l], vs[l]), live)
            if l == cfg.full_layer - 1:
                memory = y
            x = x + _mamba_out(cfg, mix, y, z)
        else:
            q, k, v = _queries(cfg, mix, h)
            if kind == WINDOW:
                ks[l] = ks[l].at[rows, slot].set(k, mode="drop")
                vs[l] = vs[l].at[rows, slot].set(v, mode="drop")
                att = ca.ring_decode_attend(q[:, None], ks[l], vs[l], pos, Hkv, in_rings,
                                            v_heads=Hkv // 2)[:, 0]
            else:
                ks[l] = ks[l].at[page_of, pos % B].set(k)
                vs[l] = vs[l].at[page_of, pos % B].set(v)
                att = ca.paged_attend(q[:, None], ks[l], vs[l], page_tables,
                                      pos[:, None], Hkv, walk, v_heads=Hkv // 2)[:, 0]
            x = x + _differential(cfg, l, mix, att)
        x = x + _mlp(cfg, layer["mlp"], _layernorm(x, layer["norm2"], eps).astype(dt))
    pool_k, pool_v = ks[cfg.full_layer], vs[cfg.full_layer]

    def attend(q):
        return ca.paged_attend(q, pool_k, pool_v, page_tables, pos[:, None], Hkv, walk,
                               v_heads=Hkv // 2)

    x = _cross_decoder(cfg, params, x, memory, attend)
    context = jnp.sum(jnp.where(live, pos + 1, 0), dtype=jnp.int32)
    read = paged_kv_attention.positions_read(pos, live, B)
    in_window = jnp.sum(jnp.where(live, jnp.minimum(pos + 1, W), 0), dtype=jnp.int32)
    return (_logits(cfg, params, x), LayerCache(tuple(ks), B), LayerCache(tuple(vs), B),
            jnp.stack([context, read, in_window]))


@partial(jax.jit, static_argnums=(0,), donate_argnums=(4, 5))
def decode_paged_and_sample(cfg: Phi4FlashConfig, params, last_tokens, lengths,
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
def decode_multi_paged(cfg: Phi4FlashConfig, params, last_tokens, lengths,
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
