"""Attribute GPT-2 train-step time to components on the real chip.

Times (all jitted, donated where applicable):
  fwd backbone only | fwd+loss | grad (fwd+bwd) | full step (grad+adamw)
  flash attention kernel fwd / fwd+bwd in isolation
Derives: bwd time, optimizer time, attention share, recompute share.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models import gpt2
from ray_tpu.ops import flash_attention

PEAK = 197e12
B, T = 32, 1024


def _sync(out):
    # a device->host scalar read: the work has finished when it returns
    leaf = jax.tree.leaves(out)[0]
    float(jnp.asarray(leaf).ravel()[0])


def timeit(fn, *args, steps=10, donate=False):
    out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / steps


def main():
    cfg = dataclasses.replace(
        gpt2.CONFIGS["gpt2-small"], attn_impl="flash", remat=True
    )
    params = gpt2.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (B, T + 1), 0, cfg.vocab_size, dtype="int32"
    )
    opt = optax.adamw(3e-4, weight_decay=0.01)
    opt_state = opt.init(params)

    n_params = sum(x.size for x in jax.tree.leaves(params))
    flops_counted = 6.0 * n_params * B * T
    # attention matmul flops (fwd): 2 * 2 * B*T^2*D per layer (qk + av)
    attn_fwd = 2 * 2 * B * T * T * cfg.d_model * cfg.n_layer

    # 1. backbone fwd only
    f_backbone = jax.jit(lambda p, t: gpt2.backbone(p, t[:, :-1], cfg))
    t_backbone = timeit(f_backbone, params, tokens)

    # 2. fwd + loss
    f_loss = jax.jit(lambda p, t: gpt2.loss_fn(p, t, cfg))
    t_loss = timeit(f_loss, params, tokens)

    # 3. grad
    f_grad = jax.jit(lambda p, t: jax.grad(gpt2.loss_fn)(p, t, cfg))
    t_grad = timeit(f_grad, params, tokens)

    # 4. full step
    step = jax.jit(gpt2.make_train_step(cfg, opt), donate_argnums=(0, 1))
    p2, o2, loss = step(params, opt_state, tokens)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(10):
        p2, o2, loss = step(p2, o2, tokens)
    float(loss)
    t_step = (time.perf_counter() - t0) / 10

    # 5. flash kernel in isolation
    q = jax.random.normal(jax.random.PRNGKey(2), (B, T, cfg.n_head, cfg.head_dim),
                          dtype=jnp.bfloat16)
    fa = jax.jit(lambda q: flash_attention.flash_attention(q, q, q, causal=True))
    t_fa_fwd = timeit(fa, q)
    fa_g = jax.jit(lambda q: jax.grad(
        lambda q: flash_attention.flash_attention(q, q, q, causal=True).sum()
    )(q))
    t_fa_full = timeit(fa_g, q)

    t_bwd = t_grad - t_loss
    t_opt = t_step - t_grad
    t_head = t_loss - t_backbone
    print(f"params={n_params/1e6:.1f}M  counted_flops/step={flops_counted/1e12:.2f}T "
          f"attn_fwd_flops={attn_fwd/1e12:.2f}T")
    print(f"backbone fwd      {t_backbone*1000:7.1f} ms   "
          f"({flops_counted/3/ (t_backbone)/1e12:.1f} TF/s eff on 1/3 of counted)")
    print(f"loss head (fwd)   {t_head*1000:7.1f} ms")
    print(f"fwd+loss          {t_loss*1000:7.1f} ms")
    print(f"bwd (grad-fwd)    {t_bwd*1000:7.1f} ms")
    print(f"grad total        {t_grad*1000:7.1f} ms")
    print(f"optimizer (adamw) {t_opt*1000:7.1f} ms")
    print(f"FULL STEP         {t_step*1000:7.1f} ms   mfu={flops_counted/t_step/PEAK:.4f}")
    print(f"flash fwd 12x     {t_fa_fwd*12*1000:7.1f} ms (1 layer x12: {t_fa_fwd*1000:.2f})")
    print(f"flash fwd+bwd 12x {t_fa_full*12*1000:7.1f} ms")


main()
