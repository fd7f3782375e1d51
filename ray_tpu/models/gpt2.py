"""GPT-2 — the flagship model (BASELINE.md configs 1/3: GPT-2 pretrain).

Pure-JAX pytree implementation, TPU-first:
  - layers STACKED on a leading L dim and iterated with lax.scan → one
    compiled block body instead of L unrolled copies (fast compile, XLA
    pipelines the loop);
  - fused QKV projection, single (D, 3, H, Dh) matmul feeding the MXU;
  - vocab padded to a multiple of 128 (MXU lane width);
  - bf16 compute / fp32 master params; logits + softmax in fp32;
  - jax.checkpoint (remat) around each block to trade FLOPs for HBM;
  - GSPMD sharding via parallel.sharding.gpt_rules: TP on heads/hidden,
    FSDP on the complementary dim, batch over dp axes, sequence over cp.

The weights are compatible in spirit (same architecture: pre-LN, learned
positions, GELU, tied LM head) with the reference's GPT-2 configs used by
its Train benchmarks (reference release/train_tests/benchmark).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from ray_tpu.ops.attention import attention as attention_op


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    d_model: int = 768
    n_layer: int = 12
    n_head: int = 12
    dtype: Any = jnp.bfloat16  # compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True
    # "full" recomputes the whole block; "dots" saves matmul outputs and
    # recomputes only cheap elementwise ops (less recompute, more HBM)
    remat_policy: str = "full"
    attn_impl: str = "reference"  # reference | flash | ring
    cp_axis: Optional[str] = None  # mesh axis name when attn_impl="ring"
    # Cross-entropy in T-chunks of this many tokens: the [B,T,V] fp32
    # logits tensor (6.6GB for gpt2-small at B=32,T=1024) never
    # materializes — each chunk's logits are recomputed in the backward
    # pass. 0 disables chunking.
    loss_chunk: int = 128
    # lax.scan unroll factor over layers. 1 = rolled (fast compile, the
    # right default for deep models); n_layer = fully unrolled (XLA sees
    # every layer: no dynamic-update-slice gradient stacking, better
    # inter-layer scheduling — measurably faster for small L).
    scan_unroll: int = 1
    # "chunked": scan+checkpoint CE (loss_chunk controls chunk size).
    # "fused": custom-vjp CE that emits bf16 dlogits in the forward —
    # backward is matmul-only, no [B,T,V] fp32 tensor ever exists.
    loss_impl: str = "chunked"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def padded_vocab(self) -> int:
        return ((self.vocab_size + 127) // 128) * 128

    @property
    def d_ff(self) -> int:
        return 4 * self.d_model

    def num_params(self) -> int:
        d, l, v = self.d_model, self.n_layer, self.padded_vocab
        per_layer = 4 * d * d + 2 * 4 * d * d + 3 * d + 4 * d + 2 * 2 * d + d
        return v * d + self.n_positions * d + l * per_layer + 2 * d


# Reference configs (model sizes the reference benchmarks use)
CONFIGS = {
    "gpt2-small": GPT2Config(),
    "gpt2-medium": GPT2Config(d_model=1024, n_layer=24, n_head=16),
    "gpt2-large": GPT2Config(d_model=1280, n_layer=36, n_head=20),
    "gpt2-xl": GPT2Config(d_model=1600, n_layer=48, n_head=25),
    "gpt2-tiny": GPT2Config(  # tests / dryruns
        vocab_size=256, n_positions=128, d_model=64, n_layer=2, n_head=4,
        remat=False,
    ),
}


def init(rng: jax.Array, cfg: GPT2Config) -> Dict[str, Any]:
    """Initialize the parameter pytree (stacked-layer layout)."""
    d, l, h, hd, f = cfg.d_model, cfg.n_layer, cfg.n_head, cfg.head_dim, cfg.d_ff
    v, t = cfg.padded_vocab, cfg.n_positions
    k = iter(jax.random.split(rng, 16))
    std = 0.02
    proj_std = std / math.sqrt(2 * l)  # GPT-2 residual-scale init
    pd = cfg.param_dtype

    def norm(key, shape, s):
        return (jax.random.normal(key, shape) * s).astype(pd)

    return {
        "wte": norm(next(k), (v, d), std),
        "wpe": norm(next(k), (t, d), std),
        "blocks": {
            "ln1": {"scale": jnp.ones((l, d), pd), "bias": jnp.zeros((l, d), pd)},
            "ln2": {"scale": jnp.ones((l, d), pd), "bias": jnp.zeros((l, d), pd)},
            "attn": {
                "qkv": {
                    "kernel": norm(next(k), (l, d, 3, h, hd), std),
                    "bias": jnp.zeros((l, 3, h, hd), pd),
                },
                "proj": {
                    "kernel": norm(next(k), (l, h, hd, d), proj_std),
                    "bias": jnp.zeros((l, d), pd),
                },
            },
            "mlp": {
                "fc_in": {
                    "kernel": norm(next(k), (l, d, f), std),
                    "bias": jnp.zeros((l, f), pd),
                },
                "fc_out": {
                    "kernel": norm(next(k), (l, f, d), proj_std),
                    "bias": jnp.zeros((l, d), pd),
                },
            },
        },
        "ln_f": {"scale": jnp.ones((d,), pd), "bias": jnp.zeros((d,), pd)},
    }


def _layernorm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _block(x, layer, cfg: GPT2Config):
    """One pre-LN transformer block (body of the layer scan)."""
    dt = cfg.dtype
    h = _layernorm(x, layer["ln1"]["scale"], layer["ln1"]["bias"])
    # heads stay merged into one minor dimension of H*Dh through the
    # projections (a [.., H, Dh] result with Dh 64 minor makes XLA lay the
    # array out positions-minor, and attention then pays a transposition
    # of q, k, v and the output each way); the split into heads is a
    # reshape of that minor dimension, which the flash kernel undoes
    B, T, _ = h.shape
    w_qkv = layer["attn"]["qkv"]["kernel"].astype(dt)   # [D, 3, H, Dh]
    D, _, H, Dh = w_qkv.shape
    qkv = (
        jnp.einsum("btd,dcf->btcf", h, w_qkv.reshape(D, 3, H * Dh))
        + layer["attn"]["qkv"]["bias"].astype(dt).reshape(3, H * Dh)
    )
    q, k, v = (qkv[:, :, c].reshape(B, T, H, Dh) for c in range(3))
    att = attention_op(
        q, k, v, causal=True, impl=cfg.attn_impl, axis_name=cfg.cp_axis
    )
    # checkpointable under the "dots+attn" remat policy: saving the
    # attention output avoids re-running the flash kernel in the backward
    att = jax.ad_checkpoint.checkpoint_name(att, "attn_out")
    w_proj = layer["attn"]["proj"]["kernel"].astype(dt)  # [H, Dh, D]
    att = (
        jnp.einsum("btf,fd->btd", att.reshape(B, T, H * Dh),
                   w_proj.reshape(H * Dh, D))
        + layer["attn"]["proj"]["bias"].astype(dt)
    )
    x = x + att
    h = _layernorm(x, layer["ln2"]["scale"], layer["ln2"]["bias"])
    h = (
        jnp.einsum("btd,df->btf", h, layer["mlp"]["fc_in"]["kernel"].astype(dt))
        + layer["mlp"]["fc_in"]["bias"].astype(dt)
    )
    h = jax.nn.gelu(h, approximate=True)
    h = (
        jnp.einsum("btf,fd->btd", h, layer["mlp"]["fc_out"]["kernel"].astype(dt))
        + layer["mlp"]["fc_out"]["bias"].astype(dt)
    )
    return x + h


def backbone(params: Dict[str, Any], tokens: jax.Array, cfg: GPT2Config) -> jax.Array:
    """tokens [B, T] int32 -> final hidden states [B, T, D] (compute dtype)."""
    B, T = tokens.shape
    dt = cfg.dtype
    x = params["wte"].astype(dt)[tokens] + params["wpe"].astype(dt)[:T][None]

    def body(carry, layer):
        return _block(carry, layer, cfg), None

    if cfg.remat:
        policy = None
        if cfg.remat_policy == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        elif cfg.remat_policy == "dots_saveable":
            # Save every matmul output and the attention output; recompute
            # only cheap elementwise ops (LN, gelu, bias) in the backward.
            # ~6GB of residuals at gpt2-small B=32,T=1024 — the right
            # trade on a 16GB chip, vs "full" re-running every block fwd.
            policy = jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_saveable,
                jax.checkpoint_policies.save_only_these_names("attn_out"),
            )
        elif cfg.remat_policy == "attn_out":
            # Save ONLY the attention output (50MB/layer): the backward's
            # recompute re-runs the cheap matmuls but never the flash
            # kernel — the most expensive-to-recompute op in the block.
            policy = jax.checkpoint_policies.save_only_these_names("attn_out")
        body = jax.checkpoint(body, prevent_cse=False, policy=policy)
    x, _ = jax.lax.scan(
        body, x, params["blocks"], unroll=min(cfg.scan_unroll, cfg.n_layer)
    )
    return _layernorm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: GPT2Config) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, padded_vocab] (fp32)."""
    x = backbone(params, tokens, cfg)
    dt = cfg.dtype
    # tied LM head: bf16 operands on the MXU, fp32 accumulation → fp32
    # logits for a stable softmax without paying the 8x fp32-matmul tax
    return jnp.einsum(
        "btd,vd->btv", x.astype(dt), params["wte"].astype(dt),
        preferred_element_type=jnp.float32,
    )


def _chunk_nll(x_chunk, targets_chunk, wte, cfg: GPT2Config) -> jax.Array:
    """Cross-entropy over one T-chunk; returns summed NLL (fp32 scalar)."""
    logits = jnp.einsum(
        "bcd,vd->bcv", x_chunk, wte,
        preferred_element_type=jnp.float32,
    )
    if cfg.padded_vocab != cfg.vocab_size:
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask[None, None], -1e30, logits)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets_chunk[..., None], axis=-1)[..., 0]
    return nll.sum()


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_ce(x, wte, targets, n_chunks: int, vocab_size: int):
    loss, _ = _fused_ce_fwd(x, wte, targets, n_chunks, vocab_size)
    return loss


def _fused_ce_fwd(x, wte, targets, n_chunks: int, vocab_size: int):
    """Chunked CE that emits dlogits = (softmax - onehot) in bf16 DURING
    the forward: the [B,T,V] fp32 logits tensor never materializes, the
    backward is two pure matmuls (dx = dl @ wte, dwte = dl^T @ x) with no
    recompute, and the softmax elementwise work runs once over fp32
    chunks instead of three passes over a 6.6GB tensor.

    lax.map (sequential) over chunks rather than vmap: it GUARANTEES one
    fp32 logits chunk live at a time (vmap leaves that to XLA fusion
    luck)."""
    B, T, D = x.shape
    V = wte.shape[0]
    C = T // n_chunks
    xs = jnp.moveaxis(x.reshape(B, n_chunks, C, D), 1, 0)
    ts = jnp.moveaxis(targets.reshape(B, n_chunks, C), 1, 0)

    def chunk(xt):
        xc, tc = xt
        logits = jnp.einsum("bcd,vd->bcv", xc, wte,
                            preferred_element_type=jnp.float32)
        if vocab_size != V:
            pad = jax.lax.broadcasted_iota(jnp.int32, (1, 1, V), 2) >= vocab_size
            logits = jnp.where(pad, -1e30, logits)
        m = jnp.max(logits, axis=-1, keepdims=True)
        e = jnp.exp(logits - m)
        s = jnp.sum(e, axis=-1, keepdims=True)
        lse = (m + jnp.log(s))[..., 0]                       # [B, C]
        tgt = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        nll = jnp.sum(lse - tgt)
        p = e / s
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, (1, 1, V), 2) == tc[..., None]
        )
        dl = (p - onehot.astype(p.dtype)).astype(x.dtype)    # [B, C, V] bf16
        return nll, dl

    nlls, dls = jax.lax.map(chunk, (xs, ts))
    loss = jnp.sum(nlls) / (B * T)
    return loss, (x, wte, dls)


def _fused_ce_bwd(n_chunks: int, vocab_size: int, res, g):
    x, wte, dls = res
    B, T, D = x.shape
    C = T // n_chunks
    scale = g / (B * T)
    xs = jnp.moveaxis(x.reshape(B, n_chunks, C, D), 1, 0)
    # dx = dl @ wte ; dwte = sum_chunks dl^T @ x
    dx = jnp.einsum("nbcv,vd->nbcd", dls, wte)               # bf16 matmul
    dx = jnp.moveaxis(dx, 0, 1).reshape(B, T, D) * scale.astype(x.dtype)
    dwte = jnp.einsum("nbcv,nbcd->vd", dls, xs,
                      preferred_element_type=jnp.float32) * scale
    dtargets = None
    return dx.astype(x.dtype), dwte, dtargets


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def loss_fn(params, tokens, cfg: GPT2Config) -> jax.Array:
    """Next-token cross-entropy; masks padded-vocab logits.

    With cfg.loss_chunk > 0 the head runs per T-chunk under jax.checkpoint:
    peak memory holds one [B, C, V] logits block instead of [B, T, V], and
    the backward pass recomputes each chunk's logits instead of re-reading
    a giant fp32 tensor from HBM (bandwidth ≫ the recompute FLOPs here).
    """
    x = backbone(params, tokens[:, :-1], cfg)
    targets = tokens[:, 1:]
    B, T, D = x.shape
    dt = cfg.dtype
    wte = params["wte"].astype(dt)
    if cfg.loss_impl == "fused":
        n_chunks = max(1, T // max(1, cfg.loss_chunk)) if cfg.loss_chunk else 1
        while T % n_chunks:
            n_chunks -= 1
        return _fused_ce(x, wte, targets, n_chunks, cfg.vocab_size)
    C = cfg.loss_chunk
    if C <= 0 or T <= C:
        total = _chunk_nll(x, targets, wte, cfg)
        return total / (B * T)

    # T rarely divides C (next-token loss makes T = seq-1, e.g. 1023):
    # scan over the full chunks, then one remainder chunk outside the
    # scan, so chunking never silently degrades to the [B,T,V] fallback.
    nC, rem = divmod(T, C)
    xs = jnp.moveaxis(x[:, : nC * C].reshape(B, nC, C, D), 1, 0)    # [nC, B, C, D]
    ts = jnp.moveaxis(targets[:, : nC * C].reshape(B, nC, C), 1, 0)  # [nC, B, C]

    def chunk_body(acc, xt):
        xc, tc = xt
        return acc + _chunk_nll(xc, tc, wte, cfg), None

    total, _ = jax.lax.scan(
        jax.checkpoint(chunk_body, prevent_cse=False), jnp.float32(0.0), (xs, ts)
    )
    if rem:
        total = total + jax.checkpoint(
            lambda xc, tc: _chunk_nll(xc, tc, wte, cfg), prevent_cse=False
        )(x[:, nC * C :], targets[:, nC * C :])
    return total / (B * T)


def make_train_step(cfg: GPT2Config, optimizer):
    """Returns train_step(params, opt_state, tokens) -> (params, opt_state, loss).

    Pure function of pytrees: jit it with shardings from
    parallel.sharding.gpt_rules over any mesh (dp/fsdp/tp/cp) — XLA
    inserts the gradient psum over data axes from the shardings alone.
    """

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: (p + u).astype(p.dtype), params, updates)
        return params, opt_state, loss

    return train_step
