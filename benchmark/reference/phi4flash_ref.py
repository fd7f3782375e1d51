"""``model_type: phi4flash`` (Phi-4-mini-flash-reasoning, "SambaY",
arXiv:2507.06607) in plain ``jax.numpy`` and float32: the full forward pass
over one sequence, all layers over all positions. No cache, no kernels, no
batching: one layer after the other, a layer's stored weights upcast as
they are met, the recurrence a Python loop over positions, attention over
full score matrices with masks (one K/V pair's query heads at a time), the
tied head's vocabulary rows a slice at a time (the embedding upcast whole
is 2 GB and would not fit beside the engine that is being checked). It
reads the program's parameter layout (``ray_tpu/models/phi4flash.py``
``init``) and the configuration file's ``model`` block, and nothing else of
the program.

Source: https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json
for the sizes, ``sliding_window``, ``mb_per_layer`` and
``tie_word_embeddings``; the public ``phi4flash`` modelling code
(``modeling_phi4flash.py`` beside it) for the rest. N layers, token t at
position p, every LayerNorm with scale and bias at ``layer_norm_eps``:

    x = E[t]
    x = x + Mix_l(LN1_l(x));  x = x + W2 (u * silu(g)), [g | u] = W1 LN2_l(x)
    logits = E LN_f(x)                                          (head tied)

    l even, l <= N/2 (Mamba-1, arXiv:2312.00752):
        [c | z] = W_in h;  u_p = silu(sum_k w_k c_{p - 3 + k} + b_c)
        [dt | B | C] = W_x u_p;  dt = softplus(W_dt dt + b_dt)
        s_p = exp(dt A) s_{p-1} + (dt u_p) outer B,  A = -exp(A_log),  s_{-1} = 0
        y_p = s_p C + D u_p;  Mix = W_out (y_p * silu(z));  layer N/2's y is m
    l odd, l <= N/2 + 1 (differential attention, arXiv:2410.05258):
        [q | k | v] = W_qkv h + b as H, Hkv, Hkv heads of Dh; heads 2j, 2j + 1
        are pair j; query pair j reads K/V pair j // (H / Hkv)
        A1 = softmax(q1 k1' / sqrt(Dh)), A2 = softmax(q2 k2' / sqrt(Dh)) over
        positions j <= p, and for l < N/2 only those with p - j < sliding_window
        o = (A1 - lambda A2) [v1 | v2]
        lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l)
        lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)
        Mix = W_o concat_j (rmsnorm(o_j) * g * (1 - lambda_init(l))) + b_o
    l even, l > N/2 + 1 (gated memory unit):  Mix = W_o (m_p * silu(W_i h))
    l odd, l > N/2 + 1 (cross-attention):  q = W_q h + b, K and V layer N/2 + 1's

Departures from the source, each also under ``assumed`` in the
configuration file: ``A_log`` is read as ``[d_state, d_inner]`` (the
program stores it so; the source's is the transpose); the source runs the
cross-decoder on the last position alone while prefilling, this runs every
layer on every position, which is what the source's decoding computes
position by position; the source's dropouts are 0 and take no part.

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

HEAD_SLICE = 16384  # vocabulary rows of the embedding upcast at a time


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def layernorm(x, norm, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * norm["scale"] + norm["bias"]


def kind(model: Dict[str, Any], l: int) -> str:
    n, per = int(model["num_hidden_layers"]), int(model["mb_per_layer"])
    attends = l % per == per - 1
    if l <= n // 2:
        return "window" if attends else "mamba"
    if l == n // 2 + 1:
        return "full"
    return "cross" if attends else "gmu"


def lambda_init(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


@jax.jit
def mlp(x, w1, w2):
    g, u = jnp.split(x @ _f32(w1), 2, axis=-1)
    return (u * jax.nn.silu(g)) @ _f32(w2)


@jax.jit
def _mamba_inputs(h, mix):
    """h [T, D] -> (u, dt [T, d_inner], B, C [T, N], z [T, d_inner])."""
    T = h.shape[0]
    c, z = jnp.split(h @ mix["in_proj"], 2, axis=-1)
    taps = mix["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, c.shape[1])), c])
    u = mix["conv_b"] + sum(mix["conv_w"][k] * padded[k:k + T] for k in range(taps))
    u = jax.nn.silu(u)
    rank, n = mix["dt_proj"].shape[0], mix["A_log"].shape[0]
    dbc = u @ mix["x_proj"]
    dt, b, c_out = dbc[:, :rank], dbc[:, rank:rank + n], dbc[:, rank + n:]
    dt = jax.nn.softplus(dt @ mix["dt_proj"] + mix["dt_bias"])
    return u, dt, b, c_out, z


@jax.jit
def _mamba_step(s, a, d_skip, u, dt, b, c, p):
    """Position ``p`` of u, dt [T, d_inner], b, c [T, N]: s [N, d_inner] ->
    (y [d_inner], s)."""
    u, dt, b, c = u[p], dt[p], b[p], c[p]
    s = jnp.exp(dt[None, :] * a) * s + (dt * u)[None, :] * b[:, None]
    return (s * c[:, None]).sum(0) + d_skip * u, s


def mamba(h, mix):
    """h [T, D] -> (the mixer's output [T, D], its scan output y [T,
    d_inner]); the recurrence a Python loop over positions."""
    mix = _f32(mix)
    u, dt, b, c, z = _mamba_inputs(h, mix)
    a = -jnp.exp(mix["A_log"])
    s = jnp.zeros_like(a)
    ys = []
    for p in range(h.shape[0]):
        y, s = _mamba_step(s, a, mix["D"], u, dt, b, c, p)
        ys.append(y)
    y = jnp.stack(ys)
    return (y * jax.nn.silu(z)) @ mix["out_proj"], y


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "window", "eps",
                                             "grouped"))
def attention(h, mix, k, v, lam_init, *, heads: int, kv_heads: int,
              window: Optional[int], eps: float, grouped: bool = False):
    """Differential attention of queries from ``h`` [T, D] over ``k``, ``v``
    [T, Hkv, Dh] -> [T, D], ``lam_init`` the layer's ``lambda_init``.
    ``window`` None: every earlier position.
    ``grouped`` computes another model: query head h reads K head h // (H /
    Hkv), the usual grouping, in place of the pairs'."""
    T = h.shape[0]
    q = (h @ (mix["wq"] if "wq" in mix else mix["wqkv"][:, :mix["wo"].shape[0]])
         + (mix["bq"] if "bq" in mix else mix["bqkv"][:mix["wo"].shape[0]]))
    q = q.reshape(T, heads, -1)
    size = q.shape[-1]
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    visible = j <= i
    if window is not None:
        visible &= i - j < window
    lam = (jnp.exp(mix["lambda_q1"] @ mix["lambda_k1"])
           - jnp.exp(mix["lambda_q2"] @ mix["lambda_k2"]) + lam_init)
    per = heads // kv_heads  # query pairs a K/V pair

    def softmaxed(qh, kh):
        a = jnp.where(visible, qh @ kh.T / math.sqrt(size), -jnp.inf)
        return jax.nn.softmax(a, axis=-1)

    out = []
    for pair in range(heads // 2):
        kv = pair // per
        if grouped:
            k1, k2 = k[:, (2 * pair) // per], k[:, (2 * pair + 1) // per]
        else:
            k1, k2 = k[:, 2 * kv], k[:, 2 * kv + 1]
        both = jnp.concatenate([v[:, 2 * kv], v[:, 2 * kv + 1]], axis=-1)
        o = (softmaxed(q[:, 2 * pair], k1) - lam * softmaxed(q[:, 2 * pair + 1], k2)) @ both
        o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + eps) * mix["subln"]
        out.append(o * (1.0 - lam_init))
    return jnp.concatenate(out, axis=-1) @ mix["wo"] + mix["bo"]


def keys_and_values(h, mix, heads: int, kv_heads: int):
    """h [T, D] -> K, V [T, Hkv, Dh] of a layer that has them."""
    T = h.shape[0]
    kv = h @ mix["wqkv"] + mix["bqkv"]
    size = kv.shape[1] // (heads + 2 * kv_heads)
    k, v = jnp.split(kv[:, heads * size:], 2, axis=-1)
    return k.reshape(T, kv_heads, size), v.reshape(T, kv_heads, size)


def head(x, params, model: Dict[str, Any]):
    x = layernorm(x, _f32(params["norm_f"]), model["layer_norm_eps"])
    rows = params["embed"].shape[0]
    return jnp.concatenate([
        x @ jnp.asarray(params["embed"][lo:lo + HEAD_SLICE], jnp.float32).T
        for lo in range(0, rows, HEAD_SLICE)], axis=-1)


# what ``wrong`` may name: the model computed wrongly in one way, for the
# tests that show the comparison sees each
WRONG = ("lambda_init_of_layer_0", "grouped_heads", "memory_after_gate", "cross_own_kv")


def forward(params: Dict[str, Any], tokens, model: Dict[str, Any],
            positions: Optional[Sequence[int]] = None, wrong: Sequence[str] = ()):
    """tokens [T] -> logits [T, vocab_size], float32, or those of
    ``positions`` alone ([len(positions), vocab_size]: the whole sequence
    runs through every layer, and the head where it is asked). ``wrong`` (of
    ``WRONG``) computes another model: every layer with layer 0's
    ``lambda_init``; the usual grouping of query heads over K heads; the
    memory taken behind its layer's gate (``y * silu(z)``); the cross
    layers attending over K and V made from their own input (through the
    full layer's K and V weights, having none) instead of the full layer's."""
    if set(wrong) - set(WRONG):
        raise ValueError(f"wrong {sorted(set(wrong) - set(WRONG))}: not one of {WRONG}")
    n = int(model["num_hidden_layers"])
    if len(params["layers"]) != n:
        raise ValueError("the parameters' layers are not the model's layers")
    heads, kv_heads = int(model["num_attention_heads"]), int(model["num_key_value_heads"])
    eps = model["layer_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"][jnp.asarray(tokens)], jnp.float32)
        memory = shared = None
        for l, layer in enumerate(params["layers"]):
            what, mix = kind(model, l), _f32(layer["mix"])
            h = layernorm(x, _f32(layer["norm1"]), eps)
            if what == "mamba":
                out, y = mamba(h, mix)
                if l == n // 2:
                    memory = (y * jax.nn.silu(jnp.split(h @ mix["in_proj"], 2, axis=-1)[1])
                              if "memory_after_gate" in wrong else y)
            elif what == "gmu":
                out = (memory * jax.nn.silu(h @ mix["in_proj"])) @ mix["out_proj"]
            else:
                if what == "cross":
                    k, v = (keys_and_values(h, shared, heads, kv_heads)
                            if "cross_own_kv" in wrong else kept)
                else:
                    k, v = keys_and_values(h, mix, heads, kv_heads)
                    if what == "full":
                        shared, kept = mix, (k, v)
                out = attention(
                    h, mix, k, v,
                    lambda_init(0 if "lambda_init_of_layer_0" in wrong else l),
                    heads=heads, kv_heads=kv_heads,
                    window=int(model["sliding_window"]) if what == "window" else None,
                    eps=eps, grouped="grouped_heads" in wrong)
            x = x + out
            x = x + mlp(layernorm(x, _f32(layer["norm2"]), eps),
                        layer["mlp"]["w1"], layer["mlp"]["w2"])
        if positions is not None:
            x = x[jnp.asarray(positions)]
        return head(x, params, model)
