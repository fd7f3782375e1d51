"""GPT-2 as published, in plain ``jax.numpy`` and float32: forward pass
and next-token loss. No kernels, no cache, no padding tricks, one layer
after the other (a ``lax.scan`` over the stacked layers, so that the
compiler meets one layer and not ``n_layer`` copies of it: the gradient
of twelve unrolled float32 layers took it 150 s). It reads the program's parameter layout (layers stacked
on a leading axis; the tied embedding padded to a multiple of 128 rows,
of which only the published vocabulary is used) and nothing else of the
program.

Departures from the published model: none in the mathematics. On a TPU
a float32 matrix multiplication runs in lower precision unless told
otherwise, so everything here runs under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def layernorm(x, scale, bias, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def block(x, layer):
    """One pre-LN block on [B, T, D] float32."""
    B, T, D = x.shape
    h = layernorm(x, layer["ln1"]["scale"], layer["ln1"]["bias"])
    qkv = jnp.einsum("btd,dchn->btchn", h, layer["attn"]["qkv"]["kernel"])
    qkv = qkv + layer["attn"]["qkv"]["bias"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # [B, T, H, Dh]
    scores = jnp.einsum("bqhn,bkhn->bhqk", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhn->bqhn", jax.nn.softmax(scores, axis=-1), v)
    att = jnp.einsum("bthn,hnd->btd", att, layer["attn"]["proj"]["kernel"])
    x = x + att + layer["attn"]["proj"]["bias"]
    h = layernorm(x, layer["ln2"]["scale"], layer["ln2"]["bias"])
    h = gelu_tanh(h @ layer["mlp"]["fc_in"]["kernel"] + layer["mlp"]["fc_in"]["bias"])
    return x + h @ layer["mlp"]["fc_out"]["kernel"] + layer["mlp"]["fc_out"]["bias"]


def forward(params: Dict[str, Any], tokens, model: Dict[str, int]):
    """tokens [B, T] -> logits [B, T, vocab_size], float32."""
    with jax.default_matmul_precision("highest"):
        T = tokens.shape[1]
        wte = jnp.asarray(params["wte"], jnp.float32)
        x = wte[tokens] + jnp.asarray(params["wpe"], jnp.float32)[:T][None]
        stacked = {len(a) for a in jax.tree.leaves(params["blocks"])}
        if stacked != {int(model["n_layer"])}:
            raise ValueError(f"{stacked} stacked layers, the model has {model['n_layer']}")
        x, _ = jax.lax.scan(lambda x, layer: (block(x, _f32(layer)), None), x, params["blocks"])
        x = layernorm(x, *(_f32(params["ln_f"])[k] for k in ("scale", "bias")))
        return (x @ wte.T)[..., : int(model["vocab_size"])]


def loss(params: Dict[str, Any], tokens, model: Dict[str, int]):
    """Mean next-token cross-entropy of tokens [B, T + 1]."""
    logits = forward(params, tokens[:, :-1], model)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -picked.mean()
