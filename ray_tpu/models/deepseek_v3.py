"""``model_type: deepseek_v3`` for the serving engine, as Kanana-2-30B-A3B
(https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601) sets it:
multi-head latent attention served from a latent cache, and expert layers
that hold every routed expert beside a shared one.

The block, pre-norm, RMSNorm and no bias anywhere:

    x += Attn(rmsnorm(x));  x += FFN(rmsnorm(x));  logits = W_head rmsnorm(x)

- Attention (MLA, no low-rank q): ``q = W_q h`` as H heads of ``[q_nope |
  q_rope]``; ``[c | k_r] = W_kva h``; ``c_kv = rmsnorm(c)``, ``k_rope =
  rope(k_r)``, one rotary head that every query head shares; a head's
  ``k_nope = W_kb c_kv`` and ``v = W_vb c_kv``; ``score = (q_nope . k_nope
  + rope(q_rope) . k_rope) / sqrt(nope + rope)``; causal softmax. Rotary
  positions pair dimensions (2m, 2m + 1) (``rope_interleave``), with no
  scaling.
- FFN: a SwiGLU, dense in the first ``first_k_dense_replace`` layers, else
  ``routed_scaling_factor * ops.moe.expert_layer(h) + S(h)``: sigmoid
  scores, the top ``num_experts_per_tok`` of score plus selection bias,
  gates renormalised, and ``S`` one SwiGLU of ``n_shared_experts`` times
  the experts' width that every token passes (the shared experts, fused:
  a dense product here, no "held expert" and in no ``moe_*`` count).

The cache holds a position's ``[c_kv | k_rope]`` (after the norm, after the
rotation) and nothing a head: ``kv_lora_rank + qk_rope_head_dim`` numbers a
token a layer, paged by the engine's page tables, pages of one kind. So
``PREFIX_CACHE`` is True: a sealed page holds all a later sequence needs of
it. Decode attends in the latent space: ``W_kb`` is absorbed into the query
(``q_lat = W_kb^T q_nope``), the scores are ``[q_lat | q_rope]`` against a
page's rows as they lie, the probabilities weigh the same rows, and ``W_vb``
takes the result to the heads: no K or V a head exists for a cached
position. Prefill attends in the expanded space: the rows behind the
chunk and the chunk's own are up-projected a block of pages at a time and
attended per head (at a chunk's width the expansion is the cheaper side).
Both write the same latent rows.

The programs are the engine's interface, under the names GPT-2's have
(``models/__init__.py``); a step's ``ops.moe.STATS`` and the context its
live rows attended over ride beside its tokens (``STEP_COUNTERS``).
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
from ray_tpu.ops import moe, page_loops, paged_latent_attention

PREFIX_CACHE = True    # pages of one kind: a sealed page is all of its positions
DECODE_ATTENTION = "own_latent_pages"
MAX_DECODE_CHUNK = 8
# the rows a prefill call takes: the row counts the engine compiles (a call
# of fewer rows is padded with rows of length 0) and the widths of a row.
# ``PREFIX_CACHE`` says besides that a sequence may take several rows of one
# call. Chosen by a sweep on the chip at the served shapes (PERF.md §6, PR
# 50): a tail of 600 tokens is 36.5 ms as two rows of 512 and 37.8 ms as
# three of 256 beside a fourth of no length (47.6 ms as two calls), so a
# call of two rows would not pay for its three programs' set-up
PREFILL_ROWS = (1, 4)
PREFILL_ROW_WIDTHS = (128, 256, 512)
# what a decode program counts beside its tokens: the expert layers' counts
# summed over layers and steps, the positions its live rows attended over
# and the positions the attention's kernel read for them (whole turns, to
# each row's own length), both summed over steps (once a step, not a layer)
STEP_COUNTERS = (*(f"moe_{name}" for name in moe.STATS), "mla_context_tokens",
                 "attn_loop_tokens")


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    """The published config's keys, under their names. ``head_dim`` and
    ``num_key_value_heads`` are carried and take no part: the heads' sizes
    are ``qk_nope_head_dim``, ``qk_rope_head_dim`` and ``v_head_dim``, and
    every head has its own K and V (up-projected from the shared latent)."""

    vocab_size: int = 128256
    max_position_embeddings: int = 32768
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: int = 64
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1_000_000.0
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    first_k_dense_replace: int = 1
    n_routed_experts: int = 128
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.448
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16  # compute type, and the stored weights' and cache's

    # what the engine asks of any model's config
    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """What the cache keeps of one position in one layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def stored_width(self) -> int:
        """The row the cache stores: ``latent_width`` and zeros up to whole
        lanes of 128. Left at 576 the TPU's compiler lays the pool out
        with the PAGES minor, to pad less, and every program then relays
        it (20 copies of a layer's pool in one decode step, compiled for a
        described v5e: PERF.md, PR 48); padded here it stays as written."""
        return -(-self.latent_width // 128) * 128


# five whole layers of 48, every width, expert and vocabulary row
# (benchmark/configs/kanana-2-30b-a3b-serve.json)
CONFIGS: Dict[str, DeepseekV3Config] = {
    "kanana-2-30b-a3b": DeepseekV3Config(num_hidden_layers=5),
    # the CPU tests' preset: every mechanism, no published width
    "kanana-2-tiny": DeepseekV3Config(
        vocab_size=256, max_position_embeddings=2048, hidden_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        head_dim=8, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        n_routed_experts=16, num_experts_per_tok=3,
    ),
}


# -- parameters -----------------------------------------------------------


@partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init(key, cfg: DeepseekV3Config):
    """Seeded weights in the type the programs compute in, a leaf a
    program. Scales are chosen so that every sublayer moves the residual
    stream by about its own size; the selection bias is drawn non-zero and
    the norms' scales away from one, so that a program that drops either
    does not agree with the reference. ``W_kvb`` is kept as its two halves,
    a head's ``wkb`` [H, nope, rank] and ``wvb`` [H, rank, v]: storage
    only."""
    dt = cfg.dtype
    D, H = cfg.hidden_size, cfg.num_attention_heads
    N, R, V, C = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    F, Fm, E = cfg.intermediate_size, cfg.moe_intermediate_size, cfg.n_routed_experts
    Fs = cfg.n_shared_experts * Fm
    keys = iter(jax.random.split(key, 20 * cfg.n_layer + 4))

    def w(shape, fan_in, gain=1.0, dtype=dt):
        return _normal(next(keys), tuple(shape), gain / fan_in ** 0.5, dtype)

    def scale(n):
        return 1.0 + _normal(next(keys), (n,), 0.1, jnp.float32)

    layers: List[Dict[str, Any]] = []
    for l in range(cfg.n_layer):
        attn = {"wq": w((D, H * (N + R)), D), "wkva": w((D, C + R), D),
                "kv_norm": scale(C),
                "wkb": w((H, N, C), C), "wvb": w((H, C, V), C),
                "wo": w((H * V, D), H * V)}
        layer = {"norm1": scale(D), "norm2": scale(D), "attn": attn}
        if l < cfg.first_k_dense_replace:
            layer["mlp"] = {"gate": w((D, F), D), "up": w((D, F), D),
                            "down": w((F, D), F, gain=2.0)}
        else:
            layer["moe"] = {
                "router": w((D, E), D, dtype=jnp.float32),
                # small beside the scores' own spread (0.2): it reorders
                # neighbours and does not decide the choice (PERF.md, PR 46)
                "bias": _normal(next(keys), (E,), 0.02, jnp.float32),
                "gate": w((E, D, Fm), D), "up": w((E, D, Fm), D),
                # the routed sum is routed_scaling_factor times a mean of
                # the chosen experts' outputs
                "down": w((E, Fm, D), Fm, gain=2.0),
            }
            layer["shared"] = {"gate": w((D, Fs), D), "up": w((D, Fs), D),
                               "down": w((Fs, D), Fs, gain=2.0)}
        layers.append(layer)
    return {"embed": w((cfg.vocab_size, D), 1.0), "layers": layers,
            "norm_f": scale(D), "head": w((cfg.vocab_size, D), D)}


def load_serving_params(cfg: DeepseekV3Config, checkpoint_path=None):
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


@partial(jax.tree_util.register_dataclass, data_fields=["layers"],
         meta_fields=["page_tokens"])
@dataclasses.dataclass(frozen=True)
class LatentCache:
    """The latent rows of every layer, ``[pages, B, stored_width]`` a
    layer, and B beside them. A row lies as both of decode's products read
    it: ``[c_kv | k_rope | zeros]`` against ``[q_lat | q_rope | zeros]``,
    and the probabilities weigh the whole row (the result's tail behind
    its rank is not used)."""

    layers: Tuple[jax.Array, ...]
    page_tokens: int


def init_paged_cache(cfg: DeepseekV3Config, num_pages: int, page_tokens: int,
                     rows: int = 1):
    """(latent cache, an empty one): the engine carries a pair, and this
    model keeps one array a layer where others keep K and V. ``rows`` takes
    no part: nothing here is kept a decode row."""
    latent = LatentCache(tuple(
        jnp.zeros((num_pages, page_tokens, cfg.stored_width), cfg.dtype)
        for _ in range(cfg.n_layer)), page_tokens)
    return latent, LatentCache((), page_tokens)


def cache_layout(cfg: DeepseekV3Config, cache: LatentCache, _none: LatentCache) -> Dict[str, Any]:
    """The stored shape of every layer's pool and the bytes the device
    holds for them, tiling's padding included."""
    return {"shape": [["latent", *a.shape] for a in cache.layers],
            "bytes": {"latent": sum(a.on_device_size_in_bytes() for a in cache.layers)}}


# -- the block ------------------------------------------------------------


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def _rope(x, pos, theta: float):
    """Rotary positions on all of ``x`` [T, ..., R] at ``pos`` [T], pairs
    (2m, 2m + 1) turned by ``pos * theta ** (-2m / R)``; float32."""
    R = x.shape[-1]
    inv = theta ** (-jnp.arange(R // 2, dtype=jnp.float32) * 2.0 / R)
    ang = pos.astype(jnp.float32).reshape(-1, *([1] * (x.ndim - 1))) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], R // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def _queries_and_rows(cfg: DeepseekV3Config, attn, h, pos):
    """h [T, D] at positions ``pos`` [T] -> the heads' queries, q_nope [T,
    H, N] and q_rope [T, H, R] (rotated), and the positions' latent rows
    ``[c_kv | k_rope]`` [T, C + R], normed and rotated, as the cache keeps
    them (``stored_width`` wide, zeros behind the rotary part)."""
    dt = cfg.dtype
    T = h.shape[0]
    C, N = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = jnp.dot(h, attn["wq"].astype(dt)).reshape(T, cfg.num_attention_heads, -1)
    q_rope = _rope(q[..., N:], pos, cfg.rope_theta).astype(dt)
    kva = jnp.dot(h, attn["wkva"].astype(dt), preferred_element_type=jnp.float32)
    rows = jnp.concatenate([
        _rmsnorm(kva[:, :C], attn["kv_norm"], cfg.rms_norm_eps),
        _rope(kva[:, C:], pos, cfg.rope_theta),
        jnp.zeros((T, cfg.stored_width - cfg.latent_width), jnp.float32),
    ], axis=-1).astype(dt)
    return q[..., :N], q_rope, rows


def _softmax_turn(carry, scores, visible, weighted):
    """One block of an online softmax over the last dimension of
    ``scores`` (float32); ``weighted(p)`` is the block's part of the sum."""
    m, den, acc = carry
    scores = jnp.where(visible, scores, -1e30)
    m_new = jnp.maximum(m, scores.max(-1))
    scale = jnp.exp(m - m_new)
    p = jnp.where(visible, jnp.exp(scores - m_new[..., None]), 0.0)
    return m_new, den * scale + p.sum(-1), acc * scale[..., None] + weighted(p)


def _start(shape, width):
    return (jnp.full(shape, -1e30, jnp.float32), jnp.zeros(shape, jnp.float32),
            jnp.zeros((*shape, width), jnp.float32))


def _absorbed_attend(cfg: DeepseekV3Config, attn, q_nope, q_rope, pool, tables, pos):
    """Decode's attention, in the latent space: one query a row, ``q_nope``
    [S, H, N] and ``q_rope`` [S, H, R] at positions ``pos`` [S], over each
    row's own pages of ``pool`` (``tables`` [S, MaxPages]). ``W_kb`` goes
    into the query, the pages' rows into both products as they lie in the
    pool (``ops/paged_latent_attention.py``: one kernel a layer, each row
    to its own length, no page gathered), and ``W_vb`` takes the weighted
    latent to the heads. Returns [S, H * V]."""
    dt = cfg.dtype
    S, H, _ = q_nope.shape
    W = pool.shape[2]
    # a product a head (the CPU's compiler has no such product that widens
    # its result, and both results are wanted in ``dt``)
    q_lat = jnp.einsum("shn,hnc->shc", q_nope, attn["wkb"].astype(dt))
    q_cat = jnp.concatenate(
        [q_lat, q_rope, jnp.zeros((S, H, W - cfg.latent_width), dt)], axis=-1)
    weighted = paged_latent_attention.attend(q_cat, pool, tables, pos,
                                             scale=cfg.qk_head_dim ** -0.5)
    return jnp.einsum("shc,hcv->shv", weighted[..., :cfg.kv_lora_rank],
                      attn["wvb"].astype(dt)).reshape(S, -1)


def _expanded_attend(cfg: DeepseekV3Config, attn, q_nope, q_rope, pool, table, pos,
                     last=None):
    """Prefill's attention, in the expanded space: one row's queries,
    ``q_nope`` [P, H, N] and ``q_rope`` [P, H, R] at positions ``pos`` [P],
    over one sequence's pages of ``pool`` (``table`` [MaxPages]), the prefix
    behind the chunk and the chunk itself, which is already written (by
    this row and by the call's other rows). A block of pages a turn is
    up-projected to a head's K and V and attended; the loop stops behind
    ``last``, the row's last real position (the last of ``pos`` where none
    is given), and makes no turn where that is negative (a row of length
    0). Returns [P, H * V]."""
    dt = cfg.dtype
    P, H, _ = q_nope.shape
    B = pool.shape[1]
    C = page_loops.pages_a_turn(table.shape[0], 8)
    span = C * B
    rank = cfg.kv_lora_rank
    scale = cfg.qk_head_dim ** -0.5
    wkb, wvb = attn["wkb"].astype(dt), attn["wvb"].astype(dt)

    def turn(j, carry):
        rows = pool[lax.dynamic_slice_in_dim(table, j * C, C)].reshape(span, -1)
        c_kv, k_rope = rows[:, :rank], rows[:, rank:cfg.latent_width]
        k_nope = jnp.einsum("tc,hnc->thn", c_kv, wkb,
                            preferred_element_type=jnp.float32).astype(dt)
        v = jnp.einsum("tc,hcv->thv", c_kv, wvb,
                       preferred_element_type=jnp.float32).astype(dt)
        scores = scale * (
            jnp.einsum("phn,thn->hpt", q_nope, k_nope, preferred_element_type=jnp.float32)
            + jnp.einsum("phr,tr->hpt", q_rope, k_rope, preferred_element_type=jnp.float32))
        visible = (j * span + jnp.arange(span))[None, :] <= pos[:, None]  # [P, T]
        return _softmax_turn(
            carry, scores, visible[None],
            lambda p: jnp.einsum("hpt,thv->hpv", p.astype(dt), v,
                                 preferred_element_type=jnp.float32))

    last = jnp.max(pos) if last is None else last
    _, den, acc = lax.fori_loop(0, jnp.maximum(last // span + 1, 0), turn,
                                _start((H, P), cfg.v_head_dim))
    # a query that saw nothing (a row of length 0) gives zeros, not 0 / 0
    return (acc / jnp.maximum(den, 1e-30)[..., None]).transpose(1, 0, 2).reshape(P, -1)


def _swiglu(dt, mlp, h):
    g = jnp.dot(h, mlp["gate"].astype(dt), preferred_element_type=jnp.float32)
    u = jnp.dot(h, mlp["up"].astype(dt), preferred_element_type=jnp.float32)
    return jnp.dot((jax.nn.silu(g) * u).astype(dt), mlp["down"].astype(dt),
                   preferred_element_type=jnp.float32)


def _ffn(cfg: DeepseekV3Config, layer, h, live):
    """(the block's second half on h [T, D], float32; its expert counts)."""
    if "moe" not in layer:
        return _swiglu(cfg.dtype, layer["mlp"], h), jnp.zeros((len(moe.STATS),), jnp.int32)
    routed, stats = moe.expert_layer(
        h, layer["moe"], first=0, top_k=cfg.num_experts_per_tok, live=live,
        scale=cfg.routed_scaling_factor)
    return routed + _swiglu(cfg.dtype, layer["shared"], h), stats


def _rest_of_block(cfg: DeepseekV3Config, layer, x, att, live):
    """x [T, D] float32 and the heads' output att [T, H * V] -> the
    block's output and its expert counts."""
    dt = cfg.dtype
    x = x + jnp.dot(att.astype(dt), layer["attn"]["wo"].astype(dt),
                    preferred_element_type=jnp.float32)
    h = _rmsnorm(x, layer["norm2"], cfg.rms_norm_eps).astype(dt)
    y, stats = _ffn(cfg, layer, h, live)
    return x + y, stats


def _logits(cfg: DeepseekV3Config, params, x):
    h = _rmsnorm(x, params["norm_f"], cfg.rms_norm_eps).astype(cfg.dtype)
    return jnp.dot(h, params["head"].astype(cfg.dtype).T,
                   preferred_element_type=jnp.float32)


# -- the programs ---------------------------------------------------------


@partial(jax.jit, static_argnums=(0,), donate_argnums=(5, 6))
def prefill_paged(cfg: DeepseekV3Config, params, tokens, start, length, cache,
                  none, page_table, row=0):
    """Prefill the rows of one call: ``tokens`` [R, P] (right-padded,
    ``length`` [R] real) are positions start .. start + P - 1 (``start``
    [R]) of the sequences whose page tables are ``page_table`` [R,
    MaxPages]; what lies before a row's ``start`` is already cached (an
    earlier chunk, a prefix another sequence sealed) or is written by
    another row of this call: several rows may be consecutive chunks of one
    sequence, since every layer writes all the rows' latent rows through
    their page tables first and then attends, expanded, a row at a time over
    the row's own pages. The rest of the block, the experts among it, runs
    once on the [R * P, D] tokens of all rows, so a call reads its weights
    once. A row of length 0 is nobody's: it writes to the scratch page, its
    attention makes no turn and the experts do not see it. ``row`` takes no
    part. Returns the last real position's logits of every row [R, vocab]
    and the caches.

    A call of one row may give ``start``, ``length`` and ``row`` as scalars
    and ``page_table`` as [MaxPages], and gets its logits as [vocab].

    Padded positions are written to the scratch page."""
    one = page_table.ndim == 1
    if one:
        start, length, page_table = (jnp.asarray(a)[None] for a in (start, length, page_table))
    dt = cfg.dtype
    R, P = tokens.shape
    B = cache.page_tokens
    pos = start[:, None] + jnp.arange(P)                              # [R, P]
    live = jnp.arange(P) < length[:, None]
    last = jnp.where(length > 0, start + length - 1, -1)  # a row's last real position
    page_of = jnp.take_along_axis(
        page_table, jnp.clip(pos // B, 0, page_table.shape[1] - 1), axis=1)
    page_of = jnp.where(live, page_of, 0).reshape(-1)
    pos, live = pos.reshape(-1), live.reshape(-1)
    x = params["embed"].astype(dt)[tokens.reshape(-1)].astype(jnp.float32)  # [R * P, D]
    pools = list(cache.layers)
    for l, layer in enumerate(params["layers"]):
        h = _rmsnorm(x, layer["norm1"], cfg.rms_norm_eps).astype(dt)
        q_nope, q_rope, rows = _queries_and_rows(cfg, layer["attn"], h, pos)
        pools[l] = pools[l].at[page_of, pos % B].set(rows)
        att = jnp.concatenate([
            _expanded_attend(cfg, layer["attn"], q_nope[r * P:(r + 1) * P],
                             q_rope[r * P:(r + 1) * P], pools[l], page_table[r],
                             pos[r * P:(r + 1) * P], last[r])
            for r in range(R)])
        x, _ = _rest_of_block(cfg, layer, x, att, live)
    ends = x.reshape(R, P, -1)[jnp.arange(R), jnp.maximum(length - 1, 0)]
    logits = _logits(cfg, params, ends)
    return (logits[0] if one else logits), LatentCache(tuple(pools), B), none


def _decode_paged_impl(cfg: DeepseekV3Config, params, last_tokens, lengths,
                       cache, none, page_tables):
    """One token for every row: [S] last tokens at positions ``lengths``
    write their latent rows through ``page_tables`` [S, MaxPages] and
    attend, absorbed, over the row's own pages. A row of length 0 is
    nobody's: its write lands in the scratch page, the experts do not see
    it and it counts nowhere. Returns logits [S, vocab], the caches and
    what the step counted (``STEP_COUNTERS``)."""
    dt = cfg.dtype
    S = last_tokens.shape[0]
    B = cache.page_tokens
    T = page_tables.shape[1] * B
    pos = jnp.clip(lengths, 0, T - 1)
    live = lengths > 0
    x = params["embed"].astype(dt)[last_tokens].astype(jnp.float32)  # [S, D]
    page_of = page_tables[jnp.arange(S), pos // B]
    pools = list(cache.layers)
    stats = jnp.zeros((len(moe.STATS),), jnp.int32)
    for l, layer in enumerate(params["layers"]):
        h = _rmsnorm(x, layer["norm1"], cfg.rms_norm_eps).astype(dt)
        q_nope, q_rope, rows = _queries_and_rows(cfg, layer["attn"], h, pos)
        pools[l] = pools[l].at[page_of, pos % B].set(rows)
        att = _absorbed_attend(cfg, layer["attn"], q_nope, q_rope, pools[l],
                               page_tables, pos)
        x, counted = _rest_of_block(cfg, layer, x, att, live)
        stats = stats + counted
    context = jnp.sum(jnp.where(live, pos + 1, 0), dtype=jnp.int32)
    read = paged_latent_attention.positions_read(pos, live, page_tables.shape[1], B)
    return (_logits(cfg, params, x), LatentCache(tuple(pools), B), none,
            jnp.concatenate([stats, context[None], read[None]]))


@partial(jax.jit, static_argnums=(0,), donate_argnums=(4, 5))
def decode_paged_and_sample(cfg: DeepseekV3Config, params, last_tokens, lengths,
                            cache, none, page_tables, temps, greedy_mask,
                            rng_base, step):
    """Decode, sample, fold the RNG and bump the cursor in one dispatch.
    Returns (next tokens, next lengths, caches, the step's counts)."""
    logits, cache, none, counted = _decode_paged_impl(
        cfg, params, last_tokens, lengths, cache, none, page_tables
    )
    rng = jax.random.fold_in(rng_base, step)
    nxt = sample(logits, temps, greedy_mask, rng)
    # a row that had no length has none after the step either: it stays
    # nobody's until the engine writes a sequence into it
    return nxt, jnp.where(lengths > 0, lengths + 1, 0), cache, none, counted


@partial(jax.jit, static_argnums=(0,), donate_argnums=(4, 5))
def decode_multi_paged(cfg: DeepseekV3Config, params, last_tokens, lengths,
                       cache, none, page_tables, temps, greedy_mask,
                       rng_base, n_steps, step0):
    """``n_steps`` (at most ``MAX_DECODE_CHUNK``) tokens a row in one
    dispatch; one program runs every ``n_steps``. Returns (tokens
    [MAX_DECODE_CHUNK, S] with the first ``n_steps`` rows written, last
    tokens, lengths, caches, the steps' counts)."""
    S = last_tokens.shape[0]

    def body(i, carry):
        last, lens, pools, toks, counted = carry
        logits, pools, _, step_counted = _decode_paged_impl(
            cfg, params, last, lens, pools, none, page_tables
        )
        rng = jax.random.fold_in(rng_base, step0 + i)
        nxt = sample(logits, temps, greedy_mask, rng)
        toks = lax.dynamic_update_index_in_dim(toks, nxt, i, axis=0)
        return nxt, jnp.where(lens > 0, lens + 1, 0), pools, toks, counted + step_counted

    last, lens, cache, toks, counted = lax.fori_loop(
        0, n_steps, body,
        (last_tokens, lengths, cache,
         jnp.zeros((MAX_DECODE_CHUNK, S), jnp.int32),
         jnp.zeros((len(STEP_COUNTERS),), jnp.int32)),
    )
    return toks, last, lens, cache, none, counted

