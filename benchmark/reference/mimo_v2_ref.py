"""MiMo-V2 (``model_type: mimo_v2``) as published, in plain ``jax.numpy``
and float32: the full forward pass over one sequence. No cache, no
kernels, no batching: one layer after the other, operation by operation,
a layer's stored weights upcast as they are met and an expert's only
while it is applied (3.43 B float32 parameters would not fit beside the
engine that is being checked; a whole expert layer upcast at once, with
what a "highest" product keeps of its operands, brought the chip to 16.46
of its 16.9 GB: PERF.md, PR 46). It reads the program's
parameter layout (``ray_tpu/models/mimo_v2.py`` ``init``) and the
configuration file's ``model`` block, and nothing else of the program.

Source: https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json
and the model card's description of its layers. The equations:

    x = embed[tokens]
    each layer:  x += Attn(rmsnorm(x));  x += FFN(rmsnorm(x))
    logits = W_head rmsnorm(x)                (head untied, eps 1e-5)

Attention, both kinds: q = W_q x as 64 heads of 192, k = W_k x as Hkv heads
of 192, v = attention_value_scale * W_v x as Hkv heads of 128; Hkv = 4
(full) or 8 (window); rotate-half rotary positions on the first
int(192 * 0.334) = 64 dimensions of q and k with base rope_theta (full) or
swa_rope_theta (window); a_ij = q_i . k_j / sqrt(192), query head h reads
K/V head h // (64 / Hkv); visible j: j <= i (full), 0 <= i - j < 128
(window). Full: p = softmax_j(a). Window (add_swa_attention_sink_bias):
a learned scalar s_h a head joins the denominator only,
p_ij = exp(a_ij - m) / (exp(s_h - m) + sum_j exp(a_ij - m)).
out = W_o (sum_j p_ij v_j).

Dense FFN (moe_layer_freq 0): W_down(silu(W_gate x) * W_up x). Expert FFN:
s = sigmoid(W_r x) over all routed experts; chosen = top 8 of s + b (b the
selection bias of noaux_tc; n_group = topk_group = 1: no group limit);
g_e = s_e / sum over chosen of s (norm_topk_prob; routed_scaling_factor
null = 1); y = sum over chosen of g_e E_e(x), E_e a SwiGLU of width 2,048;
no shared expert.

The chip's share: ``held`` = (first, count) names the experts whose weights
are in the tree; the sum runs over the chosen experts among them, the
normalisation over all eight. With every expert held it is the uncut
layer.

Departures from the published description, each also under ``assumed`` in
the configuration file:
- rotate-half pairing (dimension i with i + 32) on the LEADING 64
  dimensions is this reference's reading of ``partial_rotary_factor``; the
  config does not say which dimensions or which pairing;
- ``attention_chunk_size`` takes no part: the window slides;
- ``attention_projection_layout: fused_qkv`` is storage only;
- no MTP layers, no vision or audio tower: text through the language model.

On a TPU a float32 matrix multiplication runs in lower precision unless
told otherwise, so everything runs under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rmsnorm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, rotary_dim: int, theta: float):
    """x [T, heads, size], positions 0 .. T - 1."""
    T = x.shape[0]
    half = rotary_dim // 2
    inv_freq = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]  # [T, 1, rotary_dim]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    rotated_half = jnp.concatenate([-rot[..., half:], rot[..., :half]], axis=-1)
    rot = rot * jnp.cos(angle) + rotated_half * jnp.sin(angle)
    return jnp.concatenate([rot, rest], axis=-1)


def attention(x, attn, model: Dict[str, Any], window: bool):
    T = x.shape[0]
    H, Dk, Dv = model["num_attention_heads"], model["head_dim"], model["v_head_dim"]
    Hkv = model["swa_num_key_value_heads"] if window else model["num_key_value_heads"]
    theta = model["swa_rope_theta"] if window else model["rope_theta"]
    rotary_dim = int(Dk * model["partial_rotary_factor"])
    q = rope((x @ attn["wq"]).reshape(T, H, Dk), rotary_dim, theta)
    k = rope((x @ attn["wk"]).reshape(T, Hkv, Dk), rotary_dim, theta)
    v = model["attention_value_scale"] * (x @ attn["wv"]).reshape(T, Hkv, Dv)
    # query head h reads K/V head h // (H / Hkv)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    a = jnp.einsum("ihd,jhd->hij", q, k) / math.sqrt(Dk)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    visible = j <= i
    if window:
        visible &= i - j < model["sliding_window"]
    a = jnp.where(visible[None], a, -jnp.inf)
    m = a.max(-1, keepdims=True)
    if window:
        sink = attn["sink"][:, None, None]
        m = jnp.maximum(m, sink)
        denominator = jnp.exp(sink - m) + jnp.exp(a - m).sum(-1, keepdims=True)
    else:
        denominator = jnp.exp(a - m).sum(-1, keepdims=True)
    p = jnp.exp(a - m) / denominator
    o = jnp.einsum("hij,jhd->ihd", p, v).reshape(T, H * Dv)
    return o @ attn["wo"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def routing(x, moe, model: Dict[str, Any]):
    """(chosen experts [T, k], their gates [T, k], every expert's score +
    bias [T, all routed])."""
    s = jax.nn.sigmoid(x @ _f32(moe["router"]))             # [T, all routed]
    biased = s + _f32(moe["bias"])                          # bias: choice only
    _, chosen = jax.lax.top_k(biased, model["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=1)
    g = picked / picked.sum(-1, keepdims=True) if model["norm_topk_prob"] else picked
    return chosen, g, biased


def experts(x, moe, model: Dict[str, Any], held: Tuple[int, int], routed=None):
    """The part of the expert layer that the experts ``held`` = (first,
    count) give; ``moe["gate"][e]`` is expert ``first + e``."""
    chosen, g, _ = routed or routing(x, moe, model)
    first, count = held
    y = jnp.zeros_like(x)
    for e in range(count):
        g_e = jnp.where(chosen == first + e, g, 0.0).sum(-1)   # 0 where not chosen
        y = y + g_e[:, None] * swiglu(
            x, _f32(moe["gate"][e]), _f32(moe["up"][e]), _f32(moe["down"][e]))
    return y


def selection_margin(biased, k: int, held: Tuple[int, int]):
    """How far a token's choice of experts is from another choice that
    this share of the layer would notice, [T]: the smaller of (the lowest
    chosen held expert's score + bias) - (the best unchosen one's, held or
    not) and (the lowest chosen one's, held or not) - (the best unchosen
    held expert's). Scores closer than a computation's rounding are
    ranked either way, rightly both times, and the token's result then
    differs by an expert's whole output: not a gap of precision, and not
    one a comparison of logits should count (``families/mimo_v2.py``). A
    tie among experts that are all held elsewhere moves nothing here."""
    ranked, _ = jax.lax.top_k(biased, k + 1)
    last_in, first_out = ranked[:, k - 1], ranked[:, k]
    first, count = held
    e = jnp.arange(biased.shape[1])
    here = (e >= first) & (e < first + count)
    chosen = biased >= last_in[:, None]
    last_in_here = jnp.where(chosen & here, biased, jnp.inf).min(-1)
    first_out_here = jnp.where(~chosen & here, biased, -jnp.inf).max(-1)
    return jnp.minimum(last_in_here - first_out, last_in - first_out_here)


def block(x, layer, model: Dict[str, Any], window: bool, held: Tuple[int, int]):
    """The layer's output and, from an expert layer, every token's
    selection margin (None from a dense one)."""
    eps = model["layernorm_epsilon"]
    x = x + attention(rmsnorm(x, _f32(layer["norm1"]), eps), _f32(layer["attn"]), model, window)
    h = rmsnorm(x, _f32(layer["norm2"]), eps)
    if "moe" in layer:
        routed = routing(h, layer["moe"], model)
        margin = selection_margin(routed[2], model["num_experts_per_tok"], held)
        return x + experts(h, layer["moe"], model, held, routed), margin
    return x + swiglu(h, *(_f32(layer["mlp"][name]) for name in ("gate", "up", "down"))), None


def forward(params: Dict[str, Any], tokens, model: Dict[str, Any],
            held: Optional[Tuple[int, int]] = None, margins: bool = False):
    """tokens [T] -> logits [T, vocab_size], float32. ``held`` defaults to
    the first ``model["n_routed_experts"]`` experts, which is the share the
    configuration file describes. With ``margins`` also every token's
    smallest selection margin over the expert layers, [T]."""
    held = held or (0, int(model["n_routed_experts"]))
    kinds = list(zip(model["hybrid_layer_pattern"], model["moe_layer_freq"]))
    if len(params["layers"]) != len(kinds) or any(
        ("moe" in layer) != bool(experts_here)
        for layer, (_, experts_here) in zip(params["layers"], kinds)
    ):
        raise ValueError("the parameters' layers are not the model's layer pattern")
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"], jnp.float32)[tokens]
        closest = jnp.full(x.shape[:1], jnp.inf)
        for layer, (window, _) in zip(params["layers"], kinds):
            x, margin = block(x, layer, model, bool(window), held)
            if margin is not None:
                closest = jnp.minimum(closest, margin)
        x = rmsnorm(x, jnp.asarray(params["norm_f"], jnp.float32), model["layernorm_epsilon"])
        logits = x @ jnp.asarray(params["head"], jnp.float32).T
    return (logits, closest) if margins else logits
