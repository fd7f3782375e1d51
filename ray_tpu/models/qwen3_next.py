"""``model_type: qwen3_next`` for the serving engine, as
Qwen3-Next-80B-A3B-Instruct
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, 80B-A3B) sets it:
three layers of four mix positions by the gated delta rule (Gated DeltaNet,
arXiv:2412.06464: a matrix-valued state a value head a decode row), the
fourth by gated full attention over pages, and every layer ends in 512
softmax-routed experts beside a gated shared one.

Pre-norm, RMSNorm with a zero-centred scale (``x / rms(x) * (1 + w)``), no
bias anywhere, the head untied:

    x = embed[t];  x += Mix_l(norm1(x));  x += MoE(norm2(x));  logits = W_head norm_f(x)

``Mix_l`` is full attention where ``(l + 1) % full_attention_interval == 0``
and the linear layer otherwise:

- Full attention: ``[q_h | gate_h] = W_q h`` a head (2 x ``head_dim``), ``k``,
  ``v`` as Hkv heads; q and k RMS-normed over ``head_dim`` (zero-centred
  scale, one for all heads), then rotated on their leading ``head_dim *
  partial_rotary_factor`` dimensions (rotate-half, base ``rope_theta``); ``a =
  softmax(q . k / sqrt(head_dim)) v``, causal, query head h reading K/V head
  ``h // (H / Hkv)``; the output projection takes ``a * sigmoid(gate)``.
- Linear layer: ``[q | k | v | z] = W_qkvz h``, ``[b | a] = W_ba h``; q, k and
  v go through a causal depthwise convolution and the gated delta rule
  (``ops/gated_delta.py``), whose output a value head is RMS-normed (plain
  scale), gated by ``silu(z)`` and projected.
- MoE: ``ops.moe.expert_layer`` with softmax scores over ``router_experts``,
  the top ``num_experts_per_tok`` renormalised, no bias and no scale, beside
  ``sigmoid(w_s . h) * Shared(h)``, a SwiGLU every token passes (a dense
  product, no held expert, in no ``moe_*`` count).

The cache has a spec a layer (``cache_spec``): a linear layer keeps a
``state`` a decode row (the matrix state in float32, because it is
multiplied into itself at every position: 2.1 MB a row a layer at the
published sizes; the convolution's last inputs in the compute type), a full
layer pages. A prefix hit would have to restore the states: ``PREFIX_CACHE``
is False.

The programs are the engine's interface, under the names GPT-2's have
(``models/__init__.py``), and what a step counted rides beside its tokens
(``STEP_COUNTERS``).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.decode_step import counting_decode_programs
from ray_tpu.models.gpt2_decode import (  # noqa: F401 — the engine's interface
    MAX_DECODE_CHUNK, params_bytes, sample, update_rows_paged,
)
from ray_tpu.ops import cached_attention as ca
from ray_tpu.ops import gated_delta, moe, page_loops, paged_kv_attention
from ray_tpu.ops.cached_attention import LayerCache

PREFIX_CACHE = False   # a hit would have to restore the linear layers' states
DECODE_ATTENTION = "own_pages_and_states"
# the rows a prefill call takes and the widths of a row (a state belongs to a
# decode row, so a sequence takes one row of a call)
PREFILL_ROWS = (1, 2)
PREFILL_ROW_WIDTHS = (128, 256, 512)
# what a decode program counts beside its tokens: the expert layers' counts
# summed over layers and steps; the positions its live rows attended over in
# the full layers and the positions the kernel read for them (the live rows'
# pages x positions a page), once a step and not a layer. The rows whose
# states a step advances are its live rows, which the engine counts already
# (``rt_serve_batch_fill``)
STEP_COUNTERS = (*(f"moe_{name}" for name in moe.STATS), "attn_context_tokens",
                 "attn_loop_tokens")


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The published config's keys, under their names. ``num_experts`` and
    ``vocab_size`` are what this chip holds; ``router_experts`` is the
    published count the router scores, ``first_expert`` the first held.
    ``intermediate_size`` is the width of a dense MLP, which no layer has
    (``mlp_only_layers`` is empty): carried, not used."""

    vocab_size: int = 151936
    max_position_embeddings: int = 262144
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10_000_000.0
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    intermediate_size: int = 5120
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512
    router_experts: int = 512
    first_expert: int = 0
    num_experts_per_tok: int = 10
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16  # compute type, and the stored weights' and K/V's

    # what the engine asks of any model's config
    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def conv_channels(self) -> int:
        """What the convolution mixes: q and k of every key head, v of every
        value head."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def value_width(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    def full(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0


# the chip's share of a deployment in which four chips share each layer
# (benchmark/configs/qwen3-next-80b-a3b-serve.json): 128 of 512 experts, a
# quarter of the vocabulary, two whole periods
CONFIGS: Dict[str, Qwen3NextConfig] = {
    "qwen3-next-80b-a3b": Qwen3NextConfig(
        vocab_size=37984, max_position_embeddings=32768, num_hidden_layers=8,
        num_experts=128,
    ),
    # the CPU tests' preset: every mechanism, no published width
    "qwen3-next-tiny": Qwen3NextConfig(
        vocab_size=256, max_position_embeddings=256, hidden_size=64,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8, intermediate_size=128,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        num_experts=4, router_experts=16, num_experts_per_tok=4,
    ),
}


# -- parameters -----------------------------------------------------------


@partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init(key, cfg: Qwen3NextConfig):
    """Seeded weights, the matrices in the type the programs compute in and
    everything a vector long in float32, a leaf a program. Matrices are
    drawn at 1 / sqrt(fan-in) and the embedding at size one, so every
    sublayer moves the residual stream by about its own size, and no
    larger: the routed experts' ``down`` was first drawn at three times that
    (the chip adds the part of a quarter of a token's ten experts), and one
    expert ranked the other way then moved a token's logits by 0.3 and,
    through the states, the tokens behind it; with 512 experts eight times
    a token nearly every token has such a neighbour (PERF.md section 6, PR
    63). The zero-centred norms'
    scales are drawn around zero and the gated norm's around one, away from
    both, so that a program that drops one, or takes one kind for the other,
    does not agree with the reference. The delta rule's decay is drawn as a
    trained model's lies: ``A_log = log(uniform(0.5, 4))`` and ``dt_bias``
    so that ``softplus(dt_bias)`` is log-uniform in [0.001, 0.1], which puts
    ``exp(g)`` at a gate logit of zero between 0.67 and 0.9995, most of it
    in 0.9-0.999: a state that remembers hundreds of positions, not one that
    forgets at once and leaves the check nothing to hold. The convolution's
    four taps at 1 / sqrt(2)."""
    dt = cfg.dtype
    D, H, Hkv, Dh = (cfg.hidden_size, cfg.num_attention_heads,
                     cfg.num_key_value_heads, cfg.head_dim)
    Hv, Dv, Vw, Cc = (cfg.linear_num_value_heads, cfg.linear_value_head_dim,
                      cfg.value_width, cfg.conv_channels)
    Fm, Fs, El, E = (cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size,
                     cfg.num_experts, cfg.router_experts)
    keys = iter(jax.random.split(key, 24 * cfg.n_layer + 4))

    def w(shape, fan_in, dtype=dt):
        return _normal(next(keys), tuple(shape), fan_in ** -0.5, dtype)

    def vec(n, std, mean=0.0):
        return mean + _normal(next(keys), (n,), std, jnp.float32)

    def linear():
        step = jnp.exp(jax.random.uniform(next(keys), (Hv,), jnp.float32)
                       * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
        return {"in_qkvz": w((D, Cc + Vw), D), "in_ba": w((D, 2 * Hv), D),
                "conv_w": w((cfg.linear_conv_kernel_dim, Cc), 2.0, dtype=jnp.float32),
                "A_log": jnp.log(jax.random.uniform(next(keys), (Hv,), jnp.float32, 0.5, 4.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "norm": vec(Dv, 0.1, 1.0), "out_proj": w((Vw, D), Vw)}

    def attention():
        return {"wq": w((D, H * 2 * Dh), D), "wk": w((D, Hkv * Dh), D),
                "wv": w((D, Hkv * Dh), D), "wo": w((H * Dh, D), H * Dh),
                "q_norm": vec(Dh, 0.1), "k_norm": vec(Dh, 0.1)}

    layers: List[Dict[str, Any]] = []
    for l in range(cfg.n_layer):
        layers.append({
            "norm1": vec(D, 0.1), "norm2": vec(D, 0.1),
            "mix": attention() if cfg.full(l) else linear(),
            "moe": {"router": w((D, E), D, dtype=jnp.float32),
                    "gate": w((El, D, Fm), D), "up": w((El, D, Fm), D),
                    "down": w((El, Fm, D), Fm)},
            "shared": {"gate": w((D, Fs), D), "up": w((D, Fs), D),
                       "down": w((Fs, D), Fs), "gate_w": w((D,), D)},
        })
    return {"embed": w((cfg.vocab_size, D), 1.0), "layers": layers,
            "norm_f": vec(D, 0.1), "head": w((cfg.vocab_size, D), D)}


def load_serving_params(cfg: Qwen3NextConfig, checkpoint_path=None):
    """The weights of an engine of ``cfg``, on the device, in the types of
    ``init``: a pickled tree of its layout cast leaf by leaf, else ``init``
    from ``PRNGKey(0)``."""
    if checkpoint_path:
        import pickle

        with open(checkpoint_path, "rb") as f:
            tree = pickle.load(f)
        like = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
        return jax.tree.map(lambda a, s: jnp.asarray(a, s.dtype), tree, like)
    return init(jax.random.PRNGKey(0), cfg)


# -- the cache ------------------------------------------------------------


def cache_spec(cfg: Qwen3NextConfig) -> List[Dict[str, Any]]:
    """What one layer keeps: a linear layer two arrays a decode row (the
    state a value head, the convolution's last inputs), a full layer K and V
    a position in pages."""
    state = {"kind": "state",
             "k_row": (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                       cfg.linear_value_head_dim), "k_dtype": jnp.float32,
             "v_row": ((cfg.linear_conv_kernel_dim - 1) * cfg.conv_channels,),
             "v_dtype": cfg.dtype}
    full = {"kind": "full", "kv_heads": cfg.num_key_value_heads,
            "k_size": cfg.head_dim, "v_size": cfg.head_dim}
    return [full if cfg.full(l) else state for l in range(cfg.n_layer)]


def init_paged_cache(cfg: Qwen3NextConfig, num_pages: int, page_tokens: int,
                     rows: int = 1):
    """(k, v) caches, zeroed, for ``rows`` decode rows over ``num_pages``
    pages (``ops.cached_attention.init_caches`` decides the stored shapes):
    a linear layer's state is its entry of ``k``, its convolution's inputs
    that of ``v``."""
    return ca.init_caches(cache_spec(cfg), 0, num_pages, page_tokens, rows, cfg.dtype)


def cache_layout(cfg: Qwen3NextConfig, cache_k: LayerCache, cache_v: LayerCache) -> Dict[str, Any]:
    """The stored shape of every layer's entry of ``k`` and the bytes both
    caches hold on the device, by kind."""
    return ca.layout(cache_spec(cfg), cache_k, cache_v)


# -- the block ------------------------------------------------------------


def _norm(x, w, eps):
    """RMSNorm with a zero-centred scale, float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y * (1.0 + w.astype(jnp.float32))


def _rope(x, pos, rotary_dim: int, theta: float):
    """Rotate-half on the leading ``rotary_dim`` dimensions of ``x`` [T,
    heads, size] (float32) at positions ``pos`` [T]: dimension i turns with
    i + rotary_dim / 2; the rest pass."""
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary_dim)
    ang = pos.astype(jnp.float32)[:, None, None] * inv               # [T, 1, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def _qkvg(cfg: Qwen3NextConfig, attn, h, pos):
    """h [T, D] at positions ``pos`` [T] -> q [T, H, Dh] and, the heads
    merged as the caches store them, k, v [T, Hkv * Dh], and the gate's
    logits [T, H * Dh] float32; q and k normed and rotated."""
    dt, H, Dh = cfg.dtype, cfg.num_attention_heads, cfg.head_dim
    T = h.shape[0]
    qg = jnp.dot(h, attn["wq"], preferred_element_type=jnp.float32).reshape(T, H, 2 * Dh)
    q, g = qg[..., :Dh], qg[..., Dh:].reshape(T, H * Dh)
    k = jnp.dot(h, attn["wk"], preferred_element_type=jnp.float32).reshape(T, -1, Dh)
    v = h @ attn["wv"]
    q = _rope(_norm(q, attn["q_norm"], cfg.rms_norm_eps), pos, cfg.rotary_dim, cfg.rope_theta)
    k = _rope(_norm(k, attn["k_norm"], cfg.rms_norm_eps), pos, cfg.rotary_dim, cfg.rope_theta)
    return q.astype(dt), k.astype(dt).reshape(T, -1), v, g


def _attn_out(cfg: Qwen3NextConfig, attn, att, gate):
    gated = (att * jax.nn.sigmoid(gate)).astype(cfg.dtype)
    return jnp.dot(gated, attn["wo"], preferred_element_type=jnp.float32)


def _linear_in(cfg: Qwen3NextConfig, mix, h):
    """h [T, D] -> the convolution's input [T, channels], z [T, Hv * Dv] and
    the two gate logits a value head, b and a [T, Hv], float32."""
    cz = jnp.dot(h, mix["in_qkvz"], preferred_element_type=jnp.float32)
    ba = jnp.dot(h, mix["in_ba"], preferred_element_type=jnp.float32)
    c, z = jnp.split(cz, [cfg.conv_channels], axis=-1)
    b, a = jnp.split(ba, 2, axis=-1)
    return c, z, a, b


def _linear_out(cfg: Qwen3NextConfig, mix, o, z):
    """The rule's output o [T, Hv, Dv] float32 -> the mixer's [T, D]: a
    head's RMSNorm (plain scale), the gate, the projection."""
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    y = (o * mix["norm"]).reshape(o.shape[0], -1) * jax.nn.silu(z)
    return jnp.dot(y.astype(cfg.dtype), mix["out_proj"], preferred_element_type=jnp.float32)


def _swiglu(dt, mlp, h):
    g = jnp.dot(h, mlp["gate"], preferred_element_type=jnp.float32)
    u = jnp.dot(h, mlp["up"], preferred_element_type=jnp.float32)
    return jnp.dot((jax.nn.silu(g) * u).astype(dt), mlp["down"],
                   preferred_element_type=jnp.float32)


def _ffn(cfg: Qwen3NextConfig, layer, x, live):
    """x [T, D] float32 -> (x + MoE(norm2(x)), the expert counts)."""
    h = _norm(x, layer["norm2"], cfg.rms_norm_eps).astype(cfg.dtype)
    routed, stats = moe.expert_layer(
        h, layer["moe"], first=cfg.first_expert, top_k=cfg.num_experts_per_tok,
        live=live, scoring="softmax")
    shared = layer["shared"]
    open_ = jax.nn.sigmoid(jnp.dot(h, shared["gate_w"], preferred_element_type=jnp.float32))
    return x + routed + open_[:, None] * _swiglu(cfg.dtype, shared, h), stats


def _logits(cfg: Qwen3NextConfig, params, x):
    h = _norm(x, params["norm_f"], cfg.rms_norm_eps).astype(cfg.dtype)
    return jnp.dot(h, params["head"].T, preferred_element_type=jnp.float32)


# -- the programs ---------------------------------------------------------


@partial(jax.jit, static_argnums=(0,), donate_argnums=(5, 6))
def prefill_paged(cfg: Qwen3NextConfig, params, tokens, start, length, cache_k,
                  cache_v, page_table, row=0):
    """Prefill the rows of one call: ``tokens`` [R, P] (right-padded,
    ``length`` [R] real) are positions start .. start + P - 1 (``start``
    [R]) of the sequences in decode rows ``row`` [R], no two the same,
    whose page tables are ``page_table`` [R, MaxPages]; what lies before a
    row's ``start`` is already cached (this sequence's earlier chunks): in
    the full layers' pages and the linear layers' states of its row. A row
    whose ``start`` is 0 starts from a zero state whatever the row held, so
    a retired row needs no cleaning. Full layers write every row's chunk
    through its page table and attend over each row's own pages; linear
    layers run the chunked delta rule from each row's stored state
    (``gated_delta.chunk_scan``) and store the state behind the row's last
    real position. The expert layers run once on the [R * P, D] tokens of all
    rows, so a call reads its weights once. A row of length 0 is nobody's: it
    writes to the scratch page and into no state, what its queries see is not
    used and the experts do not see it. Returns the last real position's
    logits of every row [R, vocab] and the caches.

    A call of one row may give ``start``, ``length`` and ``row`` as scalars
    and ``page_table`` as [MaxPages], and gets its logits as [vocab]."""
    one = page_table.ndim == 1
    if one:
        start, length, page_table, row = (
            jnp.asarray(a)[None] for a in (start, length, page_table, row))
    dt, eps, Hkv = cfg.dtype, cfg.rms_norm_eps, cfg.num_key_value_heads
    R, P = tokens.shape
    B = cache_k.page_tokens
    max_pages = page_table.shape[1]
    pos = start[:, None] + jnp.arange(P)                              # [R, P]
    live = jnp.arange(P) < length[:, None]
    x = params["embed"][tokens.reshape(-1)].astype(jnp.float32)       # [R * P, D]
    page_of = jnp.take_along_axis(page_table, jnp.clip(pos // B, 0, max_pages - 1), axis=1)
    page_of = jnp.where(live, page_of, 0)
    # one loop over the rows' pages, to the longest row's last real position
    loops = page_loops.one_loop(
        jnp.where(length > 0, start + length - 1, 0),
        B * page_loops.pages_a_turn(max_pages, 8))
    ks, vs = list(cache_k.layers), list(cache_v.layers)
    # where a row of no length writes its state: behind the last row, dropped
    own = jnp.where(length > 0, row, ks[0].shape[0])
    for l, layer in enumerate(params["layers"]):
        mix = layer["mix"]
        h = _norm(x, layer["norm1"], eps).astype(dt)
        if cfg.full(l):
            q, k, v, g = _qkvg(cfg, mix, h, pos.reshape(-1))
            q, k, v = (a.reshape(R, P, *a.shape[1:]) for a in (q, k, v))
            ks[l] = ks[l].at[page_of, pos % B].set(k)
            vs[l] = vs[l].at[page_of, pos % B].set(v)
            att = ca.paged_attend(q, ks[l], vs[l], page_table, pos, Hkv, loops)
            x = x + _attn_out(cfg, mix, att.reshape(R * P, -1), g)
        else:
            c, z, a, b = _linear_in(cfg, mix, h)
            with jax.named_scope("gated_delta_chunk"):
                o, (s, conv) = gated_delta.chunk_scan(
                    mix, c.reshape(R, P, -1), a.reshape(R, P, -1), b.reshape(R, P, -1),
                    (ks[l][row], vs[l][row]), start, length)
            ks[l] = ks[l].at[own].set(s, mode="drop")
            vs[l] = vs[l].at[own].set(conv, mode="drop")
            x = x + _linear_out(cfg, mix, o.reshape(R * P, *o.shape[2:]), z)
        x, _ = _ffn(cfg, layer, x, live.reshape(-1))
    ends = x.reshape(R, P, -1)[jnp.arange(R), jnp.maximum(length - 1, 0)]
    logits = _logits(cfg, params, ends)
    return ((logits[0] if one else logits), LayerCache(tuple(ks), B),
            LayerCache(tuple(vs), B))


def _decode_paged_impl(cfg: Qwen3NextConfig, params, last_tokens, lengths,
                       cache_k, cache_v, page_tables):
    """One token for every row: [S] last tokens at positions ``lengths``
    advance their linear layers' states (``gated_delta.step``), write their
    K/V through ``page_tables`` [S, MaxPages] in the full layers and attend
    there over the row's own pages, each row to its own length
    (``ops/paged_kv_attention.py``). A row of length 0 is nobody's (a free
    row, or one whose sequence is still being prefilled): its full-layer
    write lands in the scratch page, its states and convolution inputs stay
    as they are, and the experts do not see it. Returns logits [S, vocab],
    the caches and what the step counted (``STEP_COUNTERS``)."""
    dt, eps, Hkv = cfg.dtype, cfg.rms_norm_eps, cfg.num_key_value_heads
    S = last_tokens.shape[0]
    B = cache_k.page_tokens
    T = page_tables.shape[1] * B
    pos = jnp.clip(lengths, 0, T - 1)
    live = lengths > 0
    rows = jnp.arange(S)
    x = params["embed"][last_tokens].astype(jnp.float32)              # [S, D]
    page_of = page_tables[rows, pos // B]
    walk = paged_kv_attention.page_visits(pos, page_tables.shape[1], B)
    ks, vs = list(cache_k.layers), list(cache_v.layers)
    stats = jnp.zeros((len(moe.STATS),), jnp.int32)
    for l, layer in enumerate(params["layers"]):
        mix = layer["mix"]
        h = _norm(x, layer["norm1"], eps).astype(dt)
        if cfg.full(l):
            q, k, v, g = _qkvg(cfg, mix, h, pos)
            ks[l] = ks[l].at[page_of, pos % B].set(k)
            vs[l] = vs[l].at[page_of, pos % B].set(v)
            att = ca.paged_attend(q[:, None], ks[l], vs[l], page_tables,
                                  pos[:, None], Hkv, walk)[:, 0]
            x = x + _attn_out(cfg, mix, att, g)
        else:
            c, z, a, b = _linear_in(cfg, mix, h)
            with jax.named_scope("gated_delta_step"):
                o, (ks[l], vs[l]) = gated_delta.step(mix, c, a, b, (ks[l], vs[l]), live)
            x = x + _linear_out(cfg, mix, o, z)
        x, counted = _ffn(cfg, layer, x, live)
        stats = stats + counted
    context = jnp.sum(jnp.where(live, pos + 1, 0), dtype=jnp.int32)
    read = paged_kv_attention.positions_read(pos, live, B)
    return (_logits(cfg, params, x), LayerCache(tuple(ks), B), LayerCache(tuple(vs), B),
            jnp.concatenate([stats, context[None], read[None]]))


# one token a row and sample, and K of them in one dispatch: the engine's two
# decode programs around the step above
decode_paged_and_sample, decode_multi_paged = counting_decode_programs(
    _decode_paged_impl, len(STEP_COUNTERS))
