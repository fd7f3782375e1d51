"""Attention implementations.

impl="reference": readable jnp einsum attention (numerics oracle for tests).
impl="flash":     Pallas TPU kernel (ray_tpu.ops.flash_attention) — tiled
                  online-softmax so the T x T score matrix never hits HBM.
impl="ring":      blockwise ring attention over the mesh "cp" axis
                  (ray_tpu.ops.ring_attention) for sequence lengths that
                  don't fit one chip. Absent from the reference entirely
                  (SURVEY.md §5 "long-context"): it delegates long-sequence
                  scaling to vLLM/DeepSpeed; here it is native.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def attention(
    q: jax.Array,  # [B, T, H, Dh]
    k: jax.Array,  # [B, S, H, Dh]
    v: jax.Array,  # [B, S, H, Dh]
    causal: bool = True,
    impl: str = "reference",
    axis_name: Optional[str] = None,  # mesh axis for impl="ring"
) -> jax.Array:
    if impl == "reference":
        return _reference_attention(q, k, v, causal)
    if impl == "flash":
        return _flash_per_shard(q, k, v, causal)
    if impl == "ring":
        from ray_tpu.ops.ring_attention import ring_attention

        return ring_attention(q, k, v, axis_name=axis_name or "cp", causal=causal)
    raise ValueError(f"unknown attention impl {impl!r}")


def _flash_per_shard(q, k, v, causal):
    """The flash kernel on each device's own shard.

    A Pallas call is opaque to the SPMD partitioner: left bare under a
    mesh, its operands are all-gathered and every chip computes the
    whole batch. So under a context mesh (the step is jitted inside
    ``jax.set_mesh(mesh)``) the kernel is shard_mapped: batch over the
    data axes, heads over tp, nothing to communicate. With no context
    mesh (one device) it is called as is."""
    from ray_tpu.ops.flash_attention import flash_attention

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return flash_attention(q, k, v, causal)
    # shards cross the boundary as [b, T, h*Dh], the kernel's own view: a
    # [.., h, Dh] array standing between the model's reshape and the
    # kernel's would be laid out positions-minor and copied each way
    B, T, H, Dh = q.shape

    def per_shard(*qkv):
        out = flash_attention(*(x.reshape(*x.shape[:2], -1, Dh) for x in qkv), causal)
        return out.reshape(*out.shape[:2], -1)

    batch = tuple(a for a in ("dcn", "dp", "fsdp") if a in mesh.axis_names)
    spec = P(batch or None, None, "tp" if "tp" in mesh.axis_names else None)
    out = jax.shard_map(
        per_shard, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
    )(*(x.reshape(B, -1, H * Dh) for x in (q, k, v)))
    return out.reshape(B, T, H, Dh)


def _reference_attention(q, k, v, causal):
    *_, T, _, d = q.shape
    S = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    # [B, H, T, S]; bf16 operands, fp32 accumulation on the MXU
    scores = (
        jnp.einsum("bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32)
        * scale
    )
    if causal:
        mask = jnp.tril(jnp.ones((T, S), dtype=bool), k=S - T)
        scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)
