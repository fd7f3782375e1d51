"""``model_type: deepseek_v3`` as Kanana-2-30B-A3B publishes it, in plain
``jax.numpy`` and float32: the full forward pass over one sequence. No
cache, no absorption, no grouping, no batching: K and V of every head are
expanded for every position, and every expert is applied to every token
and weighed by a gate that is zero where it was not chosen. One layer after
the other, a layer's stored weights upcast as they are met, an expert's
only while it is applied and the head a slice of the vocabulary at a time
(3.15 B float32 parameters would not fit beside the engine that is being
checked, whose weights and latent pool hold 12.3 of the chip's 16.9 GB).
It reads the program's parameter layout (``ray_tpu/models/deepseek_v3.py``
``init``) and the configuration file's ``model`` block, and nothing else of
the program.

Source: https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json
(``model_type: deepseek_v3``) and the DeepSeek-V2/V3 papers' description of
the layers. The equations:

    x = embed[tokens]
    each layer:  x += Attn(rmsnorm(x));  x += FFN(rmsnorm(x))
    logits = W_head rmsnorm(x)                (head untied, eps 1e-6)

Attention (multi-head latent attention, ``q_lora_rank`` null): q = W_q h
as 32 heads of [q_nope 128 | q_rope 64]; [c | k_r] = W_kva h (512 + 64);
c_kv = rmsnorm(c); k_rope = rope(k_r), one head that all 32 share; a head's
[k_nope | v] = W_kvb c_kv (128 + 128); a_ij = (q_nope_i . k_nope_j +
rope(q_rope_i) . k_rope_j) / sqrt(192); p = causal softmax (``rope_scaling``
null: no factor on it); out = W_o [sum_j p_ij v_j of every head].

Dense FFN (layer < first_k_dense_replace): W_down(silu(W_gate h) * W_up h),
width 6,144. Expert FFN: s = sigmoid(W_r h); chosen = top 6 of s + b (b the
selection bias of noaux_tc; n_group = topk_group = 1: no group limit);
g_e = s_e / sum over chosen of s (norm_topk_prob); y = routed_scaling_factor
* sum over chosen of g_e E_e(h) + S(h), E_e a SwiGLU of width 768 and S the
shared experts.

Departures from the published description, each also under ``assumed`` in
the configuration file:
- rotary positions turn the pairs (2m, 2m + 1) of the 64 rotary dimensions
  in place, which is what ``rope_interleave: true`` says of the stored
  layout; Hugging Face's code permutes such q and k to halves and turns
  those, the same permutation on both sides of every product, so the scores
  are the same;
- the ``n_shared_experts`` = 2 shared experts are one SwiGLU of width
  2 x 768 = 1,536, as Hugging Face's ``deepseek_v3`` builds them: the sum
  of two SwiGLUs of 768 is one of 1,536 with the kernels side by side;
- ``W_kvb`` is read as the program stores it, a head's ``wkb`` [128, 512]
  and ``wvb`` [512, 128]: storage only;
- ``head_dim`` 64 and ``num_key_value_heads`` 32 take no part beyond what
  ``qk_rope_head_dim`` and ``num_attention_heads`` say;
- weights are seeded, with the selection bias b and the norms' scales
  drawn away from 0 and 1; no multi-token-prediction layer is in any key,
  and none is built.

On a TPU a float32 matrix multiplication runs in lower precision unless
told otherwise, so everything runs under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

HEAD_SLICE = 16384  # vocabulary rows of the head upcast at a time


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rmsnorm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta: float):
    """x [T, ..., R] at positions 0 .. T - 1: the pair (x[2m], x[2m + 1])
    turned by the angle position * theta ** (-2m / R)."""
    T, R = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = angle.reshape(T, *([1] * (x.ndim - 2)), R // 2)
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                        odd * jnp.cos(angle) + even * jnp.sin(angle)], axis=-1)
    return turned.reshape(x.shape)


def one_head(q_nope, q_rope, k_nope, k_rope, v):
    """Causal attention of one head: q_nope, k_nope [T, N], q_rope, k_rope
    [T, R], v [T, V] -> [T, V]."""
    T = q_nope.shape[0]
    a = (q_nope @ k_nope.T + q_rope @ k_rope.T) / math.sqrt(q_nope.shape[1] + q_rope.shape[1])
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    a = jnp.where(j <= i, a, -jnp.inf)
    return jax.nn.softmax(a, axis=-1) @ v


@functools.partial(jax.jit, static_argnames=("heads", "nope", "rank", "theta", "eps"))
def attention(x, attn, *, heads: int, nope: int, rank: int, theta: float, eps: float):
    """x [T, D] -> [T, D]. One program a sequence length and the heads one
    after the other (``lax.map``): 32 heads' [T, T] scores at once are 0.6
    GB at the check's longest prompt, beside an engine that fills the chip."""
    T = x.shape[0]
    q = (x @ attn["wq"]).reshape(T, heads, -1)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], theta)
    kva = x @ attn["wkva"]
    c_kv = rmsnorm(kva[:, :rank], attn["kv_norm"], eps)
    k_rope = rope(kva[:, rank:], theta)                      # one head for all

    def head(i):
        k_nope = c_kv @ attn["wkb"][i].T                      # [T, N]
        v = c_kv @ attn["wvb"][i]                             # [T, V]
        return one_head(q_nope[:, i], q_rope[:, i], k_nope, k_rope, v)

    out = jax.lax.map(head, jnp.arange(heads))                # [H, T, V]
    return out.transpose(1, 0, 2).reshape(T, -1) @ attn["wo"]


@jax.jit
def swiglu(x, gate, up, down):
    gate, up, down = (jnp.asarray(w, jnp.float32) for w in (gate, up, down))
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def routing(x, moe, model: Dict[str, Any]):
    """(chosen experts [T, k], their gates [T, k], every expert's score +
    bias [T, E])."""
    return _routing(x, moe["router"], moe["bias"], k=int(model["num_experts_per_tok"]),
                    renormalise=bool(model["norm_topk_prob"]))


@functools.partial(jax.jit, static_argnames=("k", "renormalise"))
def _routing(x, router, bias, *, k: int, renormalise: bool):
    s = jax.nn.sigmoid(x @ _f32(router))
    biased = s + _f32(bias)                                 # bias: choice only
    _, chosen = jax.lax.top_k(biased, k)
    picked = jnp.take_along_axis(s, chosen, axis=1)
    g = picked / picked.sum(-1, keepdims=True) if renormalise else picked
    return chosen, g, biased


def experts(x, moe, model: Dict[str, Any], routed=None):
    """The routed sum, before its scale: every expert on every token, its
    gate zero where it was not chosen."""
    chosen, g, _ = routed or routing(x, moe, model)
    y = jnp.zeros_like(x)
    for e in range(model["n_routed_experts"]):
        y = _add_gated(y, chosen, g, e, swiglu(x, moe["gate"][e], moe["up"][e], moe["down"][e]))
    return y


@jax.jit
def _add_gated(y, chosen, g, e, out):
    g_e = jnp.where(chosen == e, g, 0.0).sum(-1)            # zero where not chosen
    return y + g_e[:, None] * out


def selection_margin(biased, k: int):
    """How far a token's choice of experts is from another choice, [T]:
    the lowest chosen expert's score + bias less the best unchosen one's.
    Scores closer than a computation's rounding are ranked either way,
    rightly both times, and the token's result then differs by an expert's
    whole output: not a gap of precision, and not one a comparison of
    logits should count (``families/deepseek_v3.py``)."""
    ranked, _ = jax.lax.top_k(biased, k + 1)
    return ranked[:, k - 1] - ranked[:, k]


def block(x, layer, model: Dict[str, Any]):
    """The layer's output and, from an expert layer, every token's
    selection margin (None from a dense one)."""
    eps = model["rms_norm_eps"]
    x = x + attention(
        rmsnorm(x, _f32(layer["norm1"]), eps), _f32(layer["attn"]),
        heads=model["num_attention_heads"], nope=model["qk_nope_head_dim"],
        rank=model["kv_lora_rank"], theta=float(model["rope_theta"]), eps=eps)
    h = rmsnorm(x, _f32(layer["norm2"]), eps)
    if "moe" not in layer:
        return x + swiglu(h, *(layer["mlp"][name] for name in ("gate", "up", "down"))), None
    routed = routing(h, layer["moe"], model)
    shared = swiglu(h, *(layer["shared"][name] for name in ("gate", "up", "down")))
    y = model["routed_scaling_factor"] * experts(h, layer["moe"], model, routed) + shared
    return x + y, selection_margin(routed[2], model["num_experts_per_tok"])


def head(x, params, model: Dict[str, Any]):
    x = rmsnorm(x, jnp.asarray(params["norm_f"], jnp.float32), model["rms_norm_eps"])
    rows = params["head"].shape[0]
    return jnp.concatenate([
        x @ jnp.asarray(params["head"][lo:lo + HEAD_SLICE], jnp.float32).T
        for lo in range(0, rows, HEAD_SLICE)], axis=-1)


def forward(params: Dict[str, Any], tokens, model: Dict[str, Any],
            margins: bool = False, positions: Optional[Sequence[int]] = None):
    """tokens [T] -> logits [T, vocab_size], float32, or those of
    ``positions`` alone ([len(positions), vocab_size]: the whole sequence
    runs through every layer, and the head where it is asked). With
    ``margins`` also every token's smallest selection margin over the
    expert layers, [T]."""
    dense = int(model["first_k_dense_replace"])
    if len(params["layers"]) != int(model["num_hidden_layers"]) or any(
        ("moe" in layer) != (l >= dense) for l, layer in enumerate(params["layers"])
    ):
        raise ValueError("the parameters' layers are not the model's layers")
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"][tokens], jnp.float32)
        closest = jnp.full(x.shape[:1], jnp.inf)
        for layer in params["layers"]:
            x, margin = block(x, layer, model)
            if margin is not None:
                closest = jnp.minimum(closest, margin)
        if positions is not None:
            x = x[jnp.asarray(positions)]
        logits = head(x, params, model)
    return (logits, closest) if margins else logits
