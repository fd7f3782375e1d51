"""Mixture-of-Experts block — expert parallelism over the mesh "ep" axis.

GShard-style top-k routing with static capacity (TPU-first: fixed
shapes, no data-dependent control flow — over-capacity tokens drop, the
standard accelerator MoE trade), expert weights sharded over "ep", and
token exchange via lax.all_to_all on the ICI mesh axis.

The reference has no native MoE (SURVEY.md §2.4 EP row: vLLM passthrough
only) — this is a capability-parity addition like ring attention.

Layout (under shard_map over the "ep" axis, n = axis size):
  x        [Bl, D]            local token shard
  wg       [D, E]             router (replicated)
  w_in     [El, D, F]         this device's experts (E = n * El)
  w_out    [El, F, D]
dispatch:  [Bl, E, C] one-hot -> all_to_all -> experts run [El, n*C, D]
combine:   reverse all_to_all -> weighted sum back into [Bl, D].
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def router_dispatch(
    x: jax.Array,          # [B, D]
    wg: jax.Array,         # [D, E]
    capacity: int,
    top_k: int = 2,
) -> Tuple[jax.Array, jax.Array]:
    """Compute (dispatch [B, E, C] float, combine [B, E, C] float).

    Top-k gating with position-in-expert assignment by cumulative count;
    tokens beyond an expert's capacity C are dropped (their combine
    weights are zero), matching GShard/Switch semantics."""
    B, D = x.shape
    E = wg.shape[1]
    gates = jax.nn.softmax(
        x.astype(jnp.float32) @ wg.astype(jnp.float32), axis=-1
    )  # [B, E]
    topv, topi = lax.top_k(gates, top_k)  # [B, K]
    # renormalize the selected gates
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    dispatch = jnp.zeros((B, E, capacity), jnp.float32)
    combine = jnp.zeros((B, E, capacity), jnp.float32)
    # fill counts per expert across the k choices in priority order
    fill = jnp.zeros((E,), jnp.int32)
    for k in range(top_k):
        e_k = topi[:, k]                      # [B]
        onehot = jax.nn.one_hot(e_k, E, dtype=jnp.int32)  # [B, E]
        pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot) + fill[None]  # [B, E]
        pos = jnp.sum(pos_in_e * onehot, axis=1)          # [B]
        keep = pos < capacity
        pos_oh = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)
        sel = onehot.astype(jnp.float32) * keep[:, None]
        dispatch = dispatch + sel[:, :, None] * pos_oh[:, None, :]
        combine = combine + (
            sel * topv[:, k][:, None]
        )[:, :, None] * pos_oh[:, None, :]
        fill = fill + jnp.sum(onehot * keep[:, None].astype(jnp.int32), axis=0)
    return dispatch, combine


def moe_block_local(x, wg, w_in, w_out, capacity: int, top_k: int = 2):
    """Single-device MoE (numerics oracle): all experts local."""
    dispatch, combine = router_dispatch(x, wg, capacity, top_k)
    expert_in = jnp.einsum("bec,bd->ecd", dispatch, x.astype(jnp.float32))
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", expert_in, w_in))
    out = jnp.einsum("ecf,efd->ecd", h, w_out)
    return jnp.einsum("bec,ecd->bd", combine, out).astype(x.dtype)


def moe_block(
    x: jax.Array,        # local [Bl, D]
    wg: jax.Array,       # [D, E] replicated
    w_in: jax.Array,     # local experts [El, D, F]
    w_out: jax.Array,    # [El, F, D]
    capacity: int,
    axis_name: str = "ep",
    top_k: int = 2,
) -> jax.Array:
    """Expert-parallel MoE under shard_map: dispatch/combine all_to_all
    over `axis_name` (ICI), experts sharded across it."""
    n = lax.psum(1, axis_name)
    Bl, D = x.shape
    El = w_in.shape[0]
    E = n * El
    dispatch, combine = router_dispatch(x, wg, capacity, top_k)  # [Bl,E,C]
    C = capacity
    # tokens for each expert, grouped by owning device
    expert_in = jnp.einsum(
        "bec,bd->ecd", dispatch, x.astype(jnp.float32)
    )  # [E, C, D]
    expert_in = expert_in.reshape(n, El, C, D)
    # all_to_all: device r sends expert_in[p] to device p; receives its
    # own experts' tokens from every peer -> [n, El, C, D]
    recv = lax.all_to_all(expert_in, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)
    recv = recv.reshape(n, El, C, D).transpose(1, 0, 2, 3).reshape(
        El, n * C, D
    )
    h = jax.nn.gelu(jnp.einsum("etd,edf->etf", recv, w_in))
    out = jnp.einsum("etf,efd->etd", h, w_out)  # [El, n*C, D]
    # reverse exchange: send each peer its tokens' outputs back
    out = out.reshape(El, n, C, D).transpose(1, 0, 2, 3)  # [n, El, C, D]
    back = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)
    back = back.reshape(E, C, D)
    return jnp.einsum("bec,ecd->bd", combine, back).astype(x.dtype)


def moe_block_sharded(
    x: jax.Array,        # global [B, D]
    wg: jax.Array,       # [D, E]
    w_in: jax.Array,     # [E, D, F]
    w_out: jax.Array,    # [E, F, D]
    mesh,
    capacity: int,
    ep_axis: str = "ep",
    top_k: int = 2,
) -> jax.Array:
    """shard_map wrapper: batch over ep (tokens sharded), experts over ep."""
    from jax.sharding import PartitionSpec as P

    fn = functools.partial(
        moe_block, capacity=capacity, axis_name=ep_axis, top_k=top_k
    )
    # check_vma off: the checker cannot prove the replication of the
    # all_to_all dispatch/combine pair
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(ep_axis, None),       # tokens sharded over ep
            P(None, None),          # router replicated
            P(ep_axis, None, None),  # experts sharded over ep
            P(ep_axis, None, None),
        ),
        out_specs=P(ep_axis, None),
        check_vma=False,
    )(x, wg, w_in, w_out)
