"""``model_type: afmoe`` (Trinity-Mini) in plain ``jax.numpy`` and float32:
the full forward pass over one sequence. No cache, no kernels, no
batching: one layer after the other, a layer's stored weights upcast as
they are met, an expert's only while it is applied, the K/V heads' groups
of query heads and the head's vocabulary rows a slice at a time (the whole
sequence's scores of 32 heads, or the head upcast whole, would not fit
beside the engine that is being checked). It reads the program's parameter
layout (``ray_tpu/models/afmoe.py`` ``init``) and the configuration file's
``model`` block, and nothing else of the program.

Source: https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json
for the sizes, ``layer_types``, ``score_func``, ``route_norm``,
``route_scale``, ``num_shared_experts``, ``num_dense_layers`` and
``mup_enabled``; the public ``afmoe`` modelling code (Hugging Face
transformers, ``models/afmoe/modeling_afmoe.py``) for the rest. Token t at
position p, layer l of kind ``layer_types[l]``, eps ``rms_norm_eps``, every
RMSNorm with its learned scale:

    x = E[t] * sqrt(hidden_size)                                  (mup_enabled)
    h = norm_in(x);  q = Wq h [H, Dh];  k = Wk h [Hkv, Dh];  v = Wv h [Hkv, Dh]
    g = Wg h [H * Dh]
    q = rmsnorm(q) * gq;  k = rmsnorm(k) * gk      (over Dh, one scale for all heads)
    sliding layers only: q, k = rope(q, k; p, rope_theta, rotate-half over all Dh)
    a_h = softmax_j(q_h . k_{h // (H / Hkv), j} / sqrt(Dh)) v_{h // (H / Hkv), j}
          over j <= p, and in sliding layers p - j < sliding_window
    x = x + norm_post_attn(Wo (concat_h a_h * sigmoid(g)))
    m = norm_pre_mlp(x)
    l < num_dense_layers:  f = Wd (silu(Wg' m) * Wu m)
    else:  s = sigmoid(Wr m) [num_experts];  C = top k of (s + b)
           g_e = route_scale * s_e / sum over C of s              (route_norm)
           f = Shared(m) + sum over C of g_e * E_e(m)             (SwiGLUs)
    x = x + norm_post_mlp(f);   logits = W_head norm_f(x)         (head untied)

Departures from the source, each also under ``assumed`` in the
configuration file: the ``1e-20`` the source adds to the gates' denominator
is left out (a sum of sigmoids is not zero); ``n_group``,
``num_expert_groups``, ``topk_group`` of 1 mean no group limit and take no
part.

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
    """x [T, heads, size] at positions 0 .. T - 1, rotate-half over the
    whole size."""
    T, size = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, size, 2, dtype=jnp.float32) / size)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]      # [T, 1, size]
    turned = jnp.concatenate([-x[..., size // 2:], x[..., :size // 2]], axis=-1)
    return x * jnp.cos(angle) + turned * jnp.sin(angle)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "window", "rotary",
                                             "theta", "eps", "gated", "qk_norm"))
def attention(x, attn, *, heads: int, kv_heads: int, window: Optional[int],
              rotary: bool, theta: float, eps: float, gated: bool = True,
              qk_norm: bool = True):
    """x [T, D] -> [T, D] (before the post-norm). ``window`` None: every
    earlier position. One K/V head's group of query heads after the other
    (``lax.map``)."""
    T = x.shape[0]
    q = (x @ attn["wq"]).reshape(T, heads, -1)
    k = (x @ attn["wk"]).reshape(T, kv_heads, -1)
    v = (x @ attn["wv"]).reshape(T, kv_heads, -1)
    size = q.shape[-1]
    if qk_norm:
        q, k = rmsnorm(q, attn["q_norm"], eps), rmsnorm(k, attn["k_norm"], eps)
    if rotary:
        q, k = rope(q, theta), rope(k, theta)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    visible = j <= i
    if window is not None:
        visible &= i - j < window
    group = heads // kv_heads

    def kv_head(n):
        qn = jax.lax.dynamic_slice_in_dim(q, n * group, group, axis=1)   # [T, G, size]
        a = jnp.einsum("igd,jd->gij", qn, k[:, n]) / math.sqrt(size)
        a = jnp.where(visible[None], a, -jnp.inf)
        return jnp.einsum("gij,jd->igd", jax.nn.softmax(a, axis=-1), v[:, n])

    out = jax.lax.map(kv_head, jnp.arange(kv_heads))                    # [Hkv, T, G, size]
    out = out.transpose(1, 0, 2, 3).reshape(T, heads * size)
    if gated:
        out = out * jax.nn.sigmoid(x @ attn["wg"])
    return out @ attn["wo"]


@jax.jit
def swiglu(x, gate, up, down):
    gate, up, down = (jnp.asarray(w, jnp.float32) for w in (gate, up, down))
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def routing(x, moe, model: Dict[str, Any], bias_in_gate: bool = False):
    """(chosen experts [T, k], their gates [T, k] before ``route_scale``,
    every expert's score + bias [T, E])."""
    return _routing(x, moe["router"], moe["bias"], k=int(model["num_experts_per_tok"]),
                    renormalise=bool(model["route_norm"]), bias_in_gate=bias_in_gate)


@functools.partial(jax.jit, static_argnames=("k", "renormalise", "bias_in_gate"))
def _routing(x, router, bias, *, k: int, renormalise: bool, bias_in_gate: bool):
    s = jax.nn.sigmoid(x @ _f32(router))
    biased = s + _f32(bias)                                 # bias: choice only
    _, chosen = jax.lax.top_k(biased, k)
    picked = jnp.take_along_axis(biased if bias_in_gate else s, chosen, axis=1)
    g = picked / picked.sum(-1, keepdims=True) if renormalise else picked
    return chosen, g, biased


def experts(x, moe, model: Dict[str, Any], routed=None):
    """The routed sum, before its scale: every expert on every token, its
    gate zero where it was not chosen."""
    chosen, g, _ = routed or routing(x, moe, model)
    y = jnp.zeros_like(x)
    for e in range(model["num_experts"]):
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
    logits should count (``families/afmoe.py``)."""
    ranked, _ = jax.lax.top_k(biased, k + 1)
    return ranked[:, k - 1] - ranked[:, k]


# what ``wrong`` may name: the model computed wrongly in one way, for the
# tests that show the comparison sees each (a fault that is a key of the
# model block, such as ``mup_enabled`` or ``route_scale``, is given there)
WRONG = ("no_gate", "no_qk_norm", "rope_in_full", "no_rope_in_sliding",
         "no_post_attn_norm", "no_post_mlp_norm", "bias_in_gate")


def block(x, layer, model: Dict[str, Any], l: int, wrong: Sequence[str] = ()):
    """The layer's output and, from an expert layer, every token's
    selection margin (None from a dense one)."""
    eps = model["rms_norm_eps"]
    sliding = model["layer_types"][l] == "sliding_attention"
    a = attention(
        rmsnorm(x, _f32(layer["norm_in"]), eps), _f32(layer["attn"]),
        heads=model["num_attention_heads"], kv_heads=model["num_key_value_heads"],
        window=int(model["sliding_window"]) if sliding else None,
        rotary=("no_rope_in_sliding" not in wrong) if sliding else ("rope_in_full" in wrong),
        theta=float(model["rope_theta"]), eps=eps, gated="no_gate" not in wrong,
        qk_norm="no_qk_norm" not in wrong)
    x = x + (a if "no_post_attn_norm" in wrong
             else rmsnorm(a, _f32(layer["norm_post_attn"]), eps))
    m = rmsnorm(x, _f32(layer["norm_pre_mlp"]), eps)
    margin = None
    if "moe" not in layer:
        f = swiglu(m, *(layer["mlp"][name] for name in ("gate", "up", "down")))
    else:
        routed = routing(m, layer["moe"], model, "bias_in_gate" in wrong)
        f = model["route_scale"] * experts(m, layer["moe"], model, routed)
        if model["num_shared_experts"]:
            f = f + swiglu(m, *(layer["shared"][name] for name in ("gate", "up", "down")))
        margin = selection_margin(routed[2], model["num_experts_per_tok"])
    x = x + (f if "no_post_mlp_norm" in wrong
             else rmsnorm(f, _f32(layer["norm_post_mlp"]), eps))
    return x, margin


def head(x, params, model: Dict[str, Any]):
    x = rmsnorm(x, jnp.asarray(params["norm_f"], jnp.float32), model["rms_norm_eps"])
    rows = params["head"].shape[0]
    return jnp.concatenate([
        x @ jnp.asarray(params["head"][lo:lo + HEAD_SLICE], jnp.float32).T
        for lo in range(0, rows, HEAD_SLICE)], axis=-1)


def forward(params: Dict[str, Any], tokens, model: Dict[str, Any],
            margins: bool = False, positions: Optional[Sequence[int]] = None,
            wrong: Sequence[str] = ()):
    """tokens [T] -> logits [T, vocab_size], float32, or those of
    ``positions`` alone ([len(positions), vocab_size]: the whole sequence
    runs through every layer, and the head where it is asked). With
    ``margins`` also every token's smallest selection margin over the
    expert layers, [T]. ``wrong`` (of ``WRONG``) computes another model."""
    if set(wrong) - set(WRONG):
        raise ValueError(f"wrong {sorted(set(wrong) - set(WRONG))}: not one of {WRONG}")
    dense = int(model["num_dense_layers"])
    if len(params["layers"]) != len(model["layer_types"]) or any(
        ("moe" in layer) != (l >= dense) for l, layer in enumerate(params["layers"])
    ):
        raise ValueError("the parameters' layers are not the model's layers")
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"][tokens], jnp.float32)
        if model["mup_enabled"]:
            x = x * math.sqrt(model["hidden_size"])
        closest = jnp.full(x.shape[:1], jnp.inf)
        for l, layer in enumerate(params["layers"]):
            x, margin = block(x, layer, model, l, wrong)
            if margin is not None:
                closest = jnp.minimum(closest, margin)
        if positions is not None:
            x = x[jnp.asarray(positions)]
        logits = head(x, params, model)
    return (logits, closest) if margins else logits
