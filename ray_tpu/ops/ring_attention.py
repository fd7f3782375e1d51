"""Ring attention — context parallelism over the mesh "cp" axis.

Sequence-parallel exact attention for sequences too long for one chip:
each device holds a T/n slice of Q, K, V; K/V blocks rotate around the
ring via lax.ppermute (nearest-neighbor ICI hops) while every device
accumulates its queries' attention over all blocks, merging block
results through their log-sum-exp — numerically identical to full
attention.

The reference has NO equivalent (SURVEY.md §5 "long-context": it
delegates sequence scaling to vLLM/DeepSpeed); this is a required
capability-parity addition, built TPU-first.

Block math runs in the Pallas flash kernel (ops/flash_attention.py
flash_fwd_block / flash_bwd_block): no [Tq, Tk] score tensor ever hits
HBM. The whole ring is a jax.custom_vjp: the forward ring saves (q, k,
v, o, global lse); the backward runs a second ring in which each
visiting block's (dk, dv) accumulators travel WITH the block, so after a
full rotation every block arrives home carrying gradient contributions
from every rank's queries (the standard ring-attention backward).

Ring-step visibility under causal masking (global positions):
  src == my  -> the diagonal block: causal flash kernel
  src <  my  -> fully visible: non-causal flash kernel
  src >  my  -> fully masked: skipped (zero output, -inf lse)

Usage: inside shard_map with q, k, v sharded on T over axis_name, or via
ring_attention_sharded() which applies the shard_map given a mesh.
`block_impl="einsum"` keeps the readable einsum block math as a numerics
oracle for tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import flash_attention as fa

_NEG = -1e30


# ---------------------------------------------------------------------------
# flash-block ring (custom VJP)
# ---------------------------------------------------------------------------


def _lse_to_btH1(lse, B, H):
    """[B*H, 8, Tl] sublane-layout lse -> [B, Tl, H, 1] merge weights."""
    Tl = lse.shape[-1]
    return lse[:, 0, :].reshape(B, H, Tl).transpose(0, 2, 1)[..., None]


def _ring_cases(src, my, causal, diag_fn, full_fn, skip_fn):
    if not causal:
        return full_fn()
    return lax.cond(
        src == my,
        diag_fn,
        lambda: lax.cond(src < my, full_fn, skip_fn),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def ring_attention(
    q: jax.Array,  # local shard [B, Tl, H, Dh]
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "cp",
    causal: bool = True,
) -> jax.Array:
    """Exact attention across the ring; call under shard_map with the
    sequence dim sharded over `axis_name`."""
    out, _ = _ring_fwd(q, k, v, axis_name, causal)
    return out


def _ring_fwd(q, k, v, axis_name, causal):
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    B, Tl, H, D = q.shape
    BH = B * H
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, s):
        o_acc, lse_run, kk, vv = carry
        src = (my - s) % n

        def diag():
            return fa.flash_fwd_block(q, kk, vv, causal=True)

        def full():
            return fa.flash_fwd_block(q, kk, vv, causal=False)

        def skip():
            return (
                jnp.zeros((B, Tl, H, D), jnp.float32),
                jnp.full((BH, 8, Tl), _NEG, jnp.float32),
            )

        o_b, lse_b = _ring_cases(src, my, causal, diag, full, skip)
        # merge via lse: o = sum_b o_b * exp(lse_b - lse_global)
        lse_new = jnp.logaddexp(lse_run, lse_b)
        w_run = jnp.exp(lse_run - lse_new)
        w_b = jnp.exp(lse_b - lse_new)
        o_acc = (
            o_acc * _lse_to_btH1(w_run, B, H)
            + o_b * _lse_to_btH1(w_b, B, H)
        )
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        return (o_acc, lse_new, kk, vv), None

    o0 = jnp.zeros((B, Tl, H, D), jnp.float32)
    lse0 = jnp.full((BH, 8, Tl), _NEG, jnp.float32)
    (o_acc, lse, _, _), _ = lax.scan(step, (o0, lse0, k, v), jnp.arange(n))
    out = o_acc.astype(q.dtype)
    return out, (q, k, v, out, lse)


def _ring_bwd(axis_name, causal, res, do):
    q, k, v, out, lse = res
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    B, Tl, H, D = q.shape
    BH = B * H
    perm = [(i, (i + 1) % n) for i in range(n)]
    # delta = rowsum(dO * O) in the kernel's 8-row sublane layout
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # [B, Tl, H]
    delta = delta.transpose(0, 2, 1).reshape(BH, Tl)
    delta = jnp.broadcast_to(delta[:, None, :], (BH, 8, Tl))

    def step(carry, s):
        dq_acc, kk, vv, dk_acc, dv_acc = carry
        src = (my - s) % n

        def diag():
            return fa.flash_bwd_block(q, kk, vv, do, lse, delta, causal=True)

        def full():
            return fa.flash_bwd_block(q, kk, vv, do, lse, delta, causal=False)

        def skip():
            z = jnp.zeros((B, Tl, H, D), jnp.float32)
            return z, z, z

        dq_b, dk_b, dv_b = _ring_cases(src, my, causal, diag, full, skip)
        dq_acc = dq_acc + dq_b
        dk_acc = dk_acc + dk_b
        dv_acc = dv_acc + dv_b
        # the visiting block AND its gradient accumulators rotate together;
        # after n steps each block is home with every rank's contribution
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        dk_acc = lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = lax.ppermute(dv_acc, axis_name, perm)
        return (dq_acc, kk, vv, dk_acc, dv_acc), None

    dq0 = jnp.zeros((B, Tl, H, D), jnp.float32)
    dkv0 = jnp.zeros((B, Tl, H, D), jnp.float32)
    (dq, _, _, dk, dv), _ = lax.scan(
        step, (dq0, k, v, dkv0, dkv0), jnp.arange(n)
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


ring_attention.defvjp(
    lambda q, k, v, axis_name, causal: _ring_fwd(q, k, v, axis_name, causal),
    _ring_bwd,
)


# ---------------------------------------------------------------------------
# einsum block math (numerics oracle; differentiable end-to-end via autodiff)
# ---------------------------------------------------------------------------


def _block_scores(q, kb, q_off, k_off, causal):
    """Masked scores for one (q-shard, k-block) pair, global positions."""
    d = q.shape[-1]
    s = jnp.einsum(
        "bthd,bshd->bhts", q, kb, preferred_element_type=jnp.float32
    ) * (1.0 / d**0.5)
    if causal:
        Tq, Tk = q.shape[1], kb.shape[1]
        row = jax.lax.broadcasted_iota(jnp.int32, (Tq, Tk), 0) + q_off
        col = jax.lax.broadcasted_iota(jnp.int32, (Tq, Tk), 1) + k_off
        s = jnp.where((col <= row)[None, None], s, _NEG)
    return s  # [B, H, Tq, Tk] fp32


def ring_attention_einsum(
    q: jax.Array,  # local shard [B, Tl, H, Dh]
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "cp",
    causal: bool = True,
) -> jax.Array:
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    B, Tl, H, D = q.shape

    def step(carry, s):
        acc, m_run, l_run, kk, vv = carry
        # kk/vv currently hold the block originally owned by rank (my - s)
        src = (my - s) % n
        scores = _block_scores(q, kk, my * Tl, src * Tl, causal)
        m_b = jnp.max(scores, axis=-1, keepdims=True)  # [B,H,Tq,1]
        m_b = jnp.maximum(m_b, _NEG)  # keep fully-masked rows finite
        p = jnp.exp(scores - m_b)
        # re-zero fully-masked entries (exp(-1e30 - -1e30) = 1)
        if causal:
            p = jnp.where(scores <= _NEG / 2, 0.0, p)
        l_b = jnp.sum(p, axis=-1, keepdims=True)
        o_b = jnp.einsum("bhts,bshd->bthd", p.astype(vv.dtype), vv)

        m_new = jnp.maximum(m_run, m_b)
        scale_run = jnp.exp(m_run - m_new)
        scale_b = jnp.exp(m_b - m_new)
        # [B,H,T,1] -> [B,T,H,1] for the output layout
        tr = lambda x: x.transpose(0, 2, 1, 3)
        acc = acc * tr(scale_run) + o_b.astype(jnp.float32) * tr(scale_b)
        l_run = l_run * scale_run + l_b * scale_b
        m_run = m_new
        # rotate kv to the next rank (nearest-neighbor ring on ICI)
        perm = [(i, (i + 1) % n) for i in range(n)]
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        return (acc, m_run, l_run, kk, vv), None

    acc0 = jnp.zeros((B, Tl, H, D), jnp.float32)
    m0 = jnp.full((B, H, Tl, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, Tl, 1), jnp.float32)
    (acc, m_run, l_run, _, _), _ = lax.scan(
        step, (acc0, m0, l0, k, v), jnp.arange(n)
    )
    l_safe = jnp.maximum(l_run, 1e-30).transpose(0, 2, 1, 3)
    return (acc / l_safe).astype(q.dtype)


def ring_attention_sharded(
    q: jax.Array,  # global [B, T, H, Dh]
    k: jax.Array,
    v: jax.Array,
    mesh,
    causal: bool = True,
    cp_axis: str = "cp",
    batch_axes=("dcn", "dp", "fsdp"),
    head_axis: Optional[str] = "tp",
    block_impl: str = "flash",
) -> jax.Array:
    """shard_map wrapper: T over cp, batch over data axes, heads over tp."""
    from jax.sharding import PartitionSpec as P

    batch = tuple(a for a in batch_axes if a in mesh.shape)
    spec = P(batch if batch else None, cp_axis, head_axis, None)
    impl = ring_attention if block_impl == "flash" else ring_attention_einsum
    fn = functools.partial(impl, axis_name=cp_axis, causal=causal)
    # check_vma off: the checker cannot prove the replication of the
    # ppermute ring's carries
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
