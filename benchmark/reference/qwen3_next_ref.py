"""``model_type: qwen3_next`` (Qwen3-Next-80B-A3B-Instruct) in plain
``jax.numpy`` and float32: the full forward pass over one sequence. No
cache, no kernels, no batching, no chunked form: one layer after the other,
a layer's stored weights upcast as they are met, an expert's only while it
is applied (a scan over the held experts), the delta rule position by
position from a zero state (``lax.scan`` over positions, the five lines of
the recurrence its body),
attention over full score matrices with masks (one K/V head's query heads at
a time), the head's vocabulary rows a slice at a time. It reads the
program's parameter layout (``ray_tpu/models/qwen3_next.py`` ``init``) and
the configuration file's ``model`` block, and nothing else of the program.

Source: https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json
for the sizes, ``full_attention_interval``, ``partial_rotary_factor``,
``norm_topk_prob`` and the ``linear_*`` keys; the public ``qwen3_next``
modelling code (Hugging Face transformers,
``models/qwen3_next/modeling_qwen3_next.py``) and arXiv:2412.06464 (Gated
Delta Networks) for the rest. Token t at position p, D = ``hidden_size``,
eps ``rms_norm_eps``, ``norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``:

    x = E[t]
    x = x + Mix_l(norm1(x));  x = x + MoE(norm2(x));  logits = W_head norm_f(x)

    (l + 1) % full_attention_interval == 0 (gated full attention):
        [q_h | gate_h] = Wq h a head (2 x head_dim);  k = Wk h, v = Wv h [Hkv, Dh]
        q = norm(q), k = norm(k) over Dh (one scale for all heads)
        q, k = rope(.; p, rope_theta) on the first Dh * partial_rotary_factor
               dimensions (rotate-half), the rest pass
        a_h = softmax_j(q_h . k_{h // (H / Hkv), j} / sqrt(Dh)) v_{h // (H / Hkv), j}, j <= p
        Mix = Wo (concat_h a_h * sigmoid(gate))
    else (gated delta rule; Hk key heads, Hv value heads, Dk, Dv):
        [q | k | v | z] = Wqkvz h  (Hk Dk, Hk Dk, Hv Dv, Hv Dv);  [b | a] = Wba h  (Hv, Hv)
        [q | k | v]_p = silu(sum_j w_j [q | k | v]_{p - 3 + j})      (4 taps, no bias)
        q, k = x / sqrt(sum(x^2) + 1e-6) over Dk;  q = q * Dk^-0.5
        value head i reads key head i // (Hv / Hk)
        beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)  (a value head)
        S = exp(g) S;  r = S' k;  d = beta (v - r);  S = S + k d';  o = S' q    (S_{-1} = 0)
        y = w_n * o / sqrt(mean(o^2) + eps) * silu(z) a head;  Mix = Wout y
    MoE(m) = sigmoid(w_s . m) Shared(m) + sum over C of g_e E_e(m)        (SwiGLUs)
        p = softmax(Wr m) over all the experts;  C = top k of p;  g_e = p_e / sum over C of p

Departures from the source, each also under ``assumed`` in the
configuration file: ``Wqkvz`` and ``Wba`` are read as the flat
concatenations above (the source interleaves them a key head's group: an
order of storage); the convolution's kernel is read ``[taps, channels]``;
the multi-token-prediction head the model card names has no key in the
config and is left out. A chip's share: ``held`` = (first, count) names the
experts whose part is computed, the gates normalised over all the chosen;
the head is whatever vocabulary rows the parameters hold.

The selection margin (``selection_margin``) is taken between the router's
LOGITS, the logarithm of the softmax's scores: with 512 experts the scores
are about 0.01 and every difference of two of them would lie under any
threshold made for sigmoid scores.

On a TPU a float32 matrix multiplication runs in lower precision unless
told otherwise, so everything runs under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

HEAD_SLICE = 16384  # vocabulary rows of the head upcast at a time
L2_EPS = 1e-6


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def norm(x, w, eps, zero_centred: bool = True):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (
        1.0 + w if zero_centred else w)


def full_layer(model: Dict[str, Any], l: int) -> bool:
    return (l + 1) % int(model["full_attention_interval"]) == 0


def rope(x, rotary_dim: int, theta: float):
    """x [T, heads, size], positions 0 .. T - 1."""
    T = x.shape[0]
    half = rotary_dim // 2
    inv_freq = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]  # [T, 1, rotary_dim]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], axis=-1)
    return jnp.concatenate([rot * jnp.cos(angle) + turned * jnp.sin(angle), rest], axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "rotary_dim", "theta",
                                             "eps", "gated", "zero_centred"))
def attention(x, attn, *, heads: int, kv_heads: int, rotary_dim: int, theta: float,
              eps: float, gated: bool = True, zero_centred: bool = True):
    """x [T, D] -> [T, D]. One K/V head's group of query heads after the
    other (``lax.map``)."""
    T = x.shape[0]
    qg = (x @ attn["wq"]).reshape(T, heads, -1)
    size = qg.shape[-1] // 2
    q, gate = qg[..., :size], qg[..., size:].reshape(T, heads * size)
    k = (x @ attn["wk"]).reshape(T, kv_heads, size)
    v = (x @ attn["wv"]).reshape(T, kv_heads, size)
    q = rope(norm(q, attn["q_norm"], eps, zero_centred), rotary_dim, theta)
    k = rope(norm(k, attn["k_norm"], eps, zero_centred), rotary_dim, theta)
    visible = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    group = heads // kv_heads

    def kv_head(n):
        qn = jax.lax.dynamic_slice_in_dim(q, n * group, group, axis=1)   # [T, G, size]
        a = jnp.einsum("igd,jd->gij", qn, k[:, n]) / math.sqrt(size)
        a = jnp.where(visible[None], a, -jnp.inf)
        return jnp.einsum("gij,jd->igd", jax.nn.softmax(a, axis=-1), v[:, n])

    out = jax.lax.map(kv_head, jnp.arange(kv_heads))                    # [Hkv, T, G, size]
    out = out.transpose(1, 0, 2, 3).reshape(T, heads * size)
    if gated:
        out = out * jax.nn.sigmoid(gate)
    return out @ attn["wo"]


@functools.partial(jax.jit, static_argnames=("delta", "decay"))
def delta_rule(q, k, v, g, beta, keep, delta: bool = True, decay: bool = True):
    """q, k [T, Hv, Dk], v [T, Hv, Dv], g and beta [T, Hv] -> (o [T, Hv,
    Dv], the state behind each position of ``keep`` [n]: [n, Hv, Dk, Dv]),
    position by position from a zero state."""
    def position(carry, at):
        (S, kept), (t, q_t, k_t, v_t, g_t, beta_t) = carry, at
        if decay:
            S = jnp.exp(g_t)[:, None, None] * S
        r = jnp.einsum("hkv,hk->hv", S, k_t)
        d = beta_t[:, None] * (v_t - r if delta else v_t)
        S = S + k_t[:, :, None] * d[:, None, :]
        kept = jnp.where((keep == t)[:, None, None, None], S[None], kept)
        return (S, kept), jnp.einsum("hkv,hk->hv", S, q_t)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    (_, kept), o = jax.lax.scan(position, (S0, jnp.zeros((keep.shape[0], *S0.shape))),
                                (jnp.arange(q.shape[0]), q, k, v, g, beta))
    return o, kept


def linear_attention(x, mix, model: Dict[str, Any], wrong: Sequence[str] = (),
                     keep: Sequence[int] = ()):
    """x [T, D] -> ([T, D], the states behind the positions ``keep``): the
    gated delta-rule mixer."""
    T = x.shape[0]
    Hk, Hv = int(model["linear_num_key_heads"]), int(model["linear_num_value_heads"])
    Dk, Dv = int(model["linear_key_head_dim"]), int(model["linear_value_head_dim"])
    mixed = x @ mix["in_qkvz"]
    c, z = mixed[:, :2 * Hk * Dk + Hv * Dv], mixed[:, 2 * Hk * Dk + Hv * Dv:]
    ba = x @ mix["in_ba"]
    b, a = ba[:, :Hv], ba[:, Hv:]
    taps = mix["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, c.shape[1]), jnp.float32), c])
    u = jax.nn.silu(sum(mix["conv_w"][j] * padded[j:j + T] for j in range(taps)))
    q = u[:, :Hk * Dk].reshape(T, Hk, Dk)
    k = u[:, Hk * Dk:2 * Hk * Dk].reshape(T, Hk, Dk)
    v = u[:, 2 * Hk * Dk:].reshape(T, Hv, Dv)
    if "no_l2norm" not in wrong:
        q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS)
        k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    q = jnp.repeat(q, Hv // Hk, axis=1) * Dk ** -0.5
    k = jnp.repeat(k, Hv // Hk, axis=1)
    g = -jnp.exp(mix["A_log"]) * jax.nn.softplus(a + mix["dt_bias"])
    o, kept = delta_rule(q, k, v, g, jax.nn.sigmoid(b), jnp.asarray(keep, jnp.int32),
                         delta="no_delta" not in wrong, decay="no_decay" not in wrong)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + model["rms_norm_eps"])
    y = (mix["norm"] * o).reshape(T, Hv * Dv) * jax.nn.silu(z)
    return y @ mix["out_proj"], kept


@jax.jit
def swiglu(x, gate, up, down):
    gate, up, down = (jnp.asarray(w, jnp.float32) for w in (gate, up, down))
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@functools.partial(jax.jit, static_argnames=("k", "renormalise", "scoring"))
def _routing(x, router, *, k: int, renormalise: bool, scoring: str):
    logits = x @ jnp.asarray(router, jnp.float32)
    p = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" else jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(p, k)
    picked = jnp.take_along_axis(p, chosen, axis=1)
    g = picked / picked.sum(-1, keepdims=True) if renormalise else picked
    return chosen, g, logits


def routing(x, moe, model: Dict[str, Any], scoring: str = "softmax"):
    """(chosen experts [T, k], their gates [T, k], every expert's logit [T,
    all routed])."""
    return _routing(x, moe["router"], k=int(model["num_experts_per_tok"]),
                    renormalise=bool(model["norm_topk_prob"]), scoring=scoring)


def experts(x, moe, model: Dict[str, Any], held: Tuple[int, int], routed=None):
    """The part of the routed sum that the experts ``held`` = (first, count)
    give; ``moe["gate"][e]`` is expert ``first + e``. Every held expert on
    every token, its gate zero where it was not chosen, one expert after the
    other and its weights upcast only while it is applied (``lax.scan`` over
    the stacks: one program a layer, where a Python loop was 128 x 5
    dispatches and most of the check's time)."""
    chosen, g, _ = routed or routing(x, moe, model)
    first, count = held
    return _held_sum(x, chosen, g, moe["gate"][:count], moe["up"][:count],
                     moe["down"][:count], first)


@jax.jit
def _held_sum(x, chosen, g, gate, up, down, first):
    def one(carry, stored):
        y, e = carry
        g_e = jnp.where(chosen == e, g, 0.0).sum(-1)            # zero where not chosen
        return (y + g_e[:, None] * swiglu(x, *stored), e + 1), None

    (y, _), _ = jax.lax.scan(one, (jnp.zeros_like(x), jnp.asarray(first, chosen.dtype)),
                             (gate, up, down))
    return y


def shared_expert(x, shared, gated: bool = True):
    y = swiglu(x, shared["gate"], shared["up"], shared["down"])
    if gated:
        y = jax.nn.sigmoid(x @ jnp.asarray(shared["gate_w"], jnp.float32))[:, None] * y
    return y


def selection_margin(logits, k: int, held: Tuple[int, int]):
    """How far a token's choice of experts is from another choice that this
    share of the layer would notice, [T], between the router's logits (the
    logarithm of the scores' ratio): the smaller of (the lowest chosen held
    expert's) - (the best unchosen one's, held or not) and (the lowest
    chosen one's, held or not) - (the best unchosen held expert's). Logits
    closer than a computation's rounding are ranked either way, rightly both
    times, and the token's result then differs by an expert's whole output:
    not a gap of precision, and not one a comparison of logits should count
    (``families/qwen3_next.py``). A tie among experts that are all held
    elsewhere moves nothing here."""
    ranked, _ = jax.lax.top_k(logits, k + 1)
    last_in, first_out = ranked[:, k - 1], ranked[:, k]
    first, count = held
    e = jnp.arange(logits.shape[1])
    here = (e >= first) & (e < first + count)
    chosen = logits >= last_in[:, None]
    last_in_here = jnp.where(chosen & here, logits, jnp.inf).min(-1)
    first_out_here = jnp.where(~chosen & here, logits, -jnp.inf).max(-1)
    return jnp.minimum(last_in_here - first_out, last_in - first_out_here)


# what ``wrong`` may name: the model computed wrongly in one way, for the
# tests that show the comparison sees each
WRONG = ("no_attn_gate", "plain_norm_scale", "full_rotary", "sigmoid_scores",
         "no_shared_gate", "no_decay", "no_delta", "no_l2norm")


def block(x, layer, model: Dict[str, Any], l: int, held: Tuple[int, int],
          wrong: Sequence[str] = (), keep: Sequence[int] = ()):
    """The layer's output, every token's selection margin and, of a linear
    layer, the states behind the positions ``keep`` (else None)."""
    eps = model["rms_norm_eps"]
    zero_centred = "plain_norm_scale" not in wrong
    h = norm(x, _f32(layer["norm1"]), eps, zero_centred)
    kept = None
    if full_layer(model, l):
        size = int(model["head_dim"])
        x = x + attention(
            h, _f32(layer["mix"]), heads=int(model["num_attention_heads"]),
            kv_heads=int(model["num_key_value_heads"]),
            rotary_dim=size if "full_rotary" in wrong
            else int(size * model["partial_rotary_factor"]),
            theta=float(model["rope_theta"]), eps=eps, gated="no_attn_gate" not in wrong,
            zero_centred=zero_centred)
    else:
        mixed, kept = linear_attention(h, _f32(layer["mix"]), model, wrong, keep)
        x = x + mixed
    m = norm(x, _f32(layer["norm2"]), eps, zero_centred)
    routed = routing(m, layer["moe"], model,
                     "sigmoid" if "sigmoid_scores" in wrong else "softmax")
    margin = selection_margin(routed[2], int(model["num_experts_per_tok"]), held)
    f = (experts(m, layer["moe"], model, held, routed)
         + shared_expert(m, layer["shared"], "no_shared_gate" not in wrong))
    return x + f, margin, kept


def head(x, params, model: Dict[str, Any], zero_centred: bool = True):
    x = norm(x, jnp.asarray(params["norm_f"], jnp.float32), model["rms_norm_eps"],
             zero_centred)
    rows = params["head"].shape[0]
    return jnp.concatenate([
        x @ jnp.asarray(params["head"][lo:lo + HEAD_SLICE], jnp.float32).T
        for lo in range(0, rows, HEAD_SLICE)], axis=-1)


def forward(params: Dict[str, Any], tokens, model: Dict[str, Any],
            held: Optional[Tuple[int, int]] = None, margins: bool = False,
            positions: Optional[Sequence[int]] = None, wrong: Sequence[str] = (),
            states_at: Optional[Sequence[int]] = None):
    """tokens [T] -> logits [T, vocab rows held], float32, or those of
    ``positions`` alone (the whole sequence runs through every layer, and
    the head where it is asked). ``held`` defaults to the first
    ``model["num_experts"]`` experts, which is the share the configuration
    file describes. With ``margins`` also every token's smallest selection
    margin over the layers, [T]. With ``states_at`` also, last, every linear
    layer's state behind those positions, a list of [len(states_at), Hv, Dk,
    Dv]: what a decode row holds once it has taken in that position.
    ``wrong`` (of ``WRONG``) computes another model."""
    if set(wrong) - set(WRONG):
        raise ValueError(f"wrong {sorted(set(wrong) - set(WRONG))}: not one of {WRONG}")
    if len(params["layers"]) != int(model["num_hidden_layers"]) or any(
        ("wq" in layer["mix"]) != full_layer(model, l)
        for l, layer in enumerate(params["layers"])
    ):
        raise ValueError("the parameters' layers are not the model's layers")
    held = held or (0, int(model["num_experts"]))
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"][jnp.asarray(tokens)], jnp.float32)
        closest = jnp.full(x.shape[:1], jnp.inf)
        states = []
        for l, layer in enumerate(params["layers"]):
            x, margin, kept = block(x, layer, model, l, held, wrong, states_at or ())
            closest = jnp.minimum(closest, margin)
            if kept is not None:
                states.append(kept)
        if positions is not None:
            x = x[jnp.asarray(positions)]
            closest = closest[jnp.asarray(positions)]
        logits = head(x, params, model, "plain_norm_scale" not in wrong)
    out = [logits] + [closest] * margins + [states] * (states_at is not None)
    return tuple(out) if len(out) > 1 else logits
