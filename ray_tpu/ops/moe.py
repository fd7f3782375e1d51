"""Mixture-of-experts layer as one chip of an expert-parallel deployment
runs it: told which experts it holds, it routes every token over all the
experts there are, drops none at any imbalance, and computes the part of
the result that its own experts give.

    y = sum over (chosen experts that are held here) of g_e * E_e(x)

with ``E_e`` a SwiGLU and the gates ``g_e`` normalised over ALL the chosen
experts, held or not: the shares of all chips add up to the whole layer
(``tests/test_moe.py``). What the absent experts would add is left out,
and on one chip the layer runs without its exchange; nothing here stands
in for the absent chips.

Routing is one of two kinds (``route``'s ``scoring``). ``"sigmoid"``
(``noaux_tc``): scores ``s = sigmoid(W_r x)`` in float32, the top ``k`` of
``s + b`` chosen (``b`` a selection bias that takes no part in the gate),
gates ``s_e / sum of the chosen s``, times a ``scale`` where the model has
one (``routed_scaling_factor``). ``"softmax"`` (``norm_topk_prob``):
``s = softmax(W_r x)`` over all the experts in float32, the top ``k`` of
``s``, the same renormalised gates; a model of this kind has no bias.

The product is grouped, not one-hot: the token-expert pairs that landed
here are sorted by expert and go through ``ops/grouped_matmul.py``, a
Pallas kernel that walks the (expert, row tile) pairs in which an expert
has a row: it reads an expert's weights once for all its rows, the next
expert's while this one's few rows are multiplied, and no weight of an
expert that got none. Gate and up are one call (``silu(g) * u`` its
epilogue), down another, on one grid computed once a layer. There is no
capacity: the sorted buffer has room for every pair that can land here
(``tokens * min(k, held)``) and is handed to the kernel whole, whose time
goes with the pairs that landed and not with the room. What stands behind
the kernel does go with the rows it is handed: the gates' product and the
scatter-add run in blocks of ``_block_rows`` rows by a loop that stops
after the last pair. Where a chip holds 16 experts of 256 the buffer is
sixteen times the pairs expected, and a layer of 4096 x 2048 experts took
3.32 ms with the whole buffer scattered and 1.96 ms in blocks at 1,024
tokens, 1.41 and 1.32 ms at 128; where every expert is held the buffer is
one block and the loop one turn (my chip run, PERF.md section 6, PR 55).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import grouped_matmul

# what ``expert_layer`` counts, in the order of its fourth result
STATS = ("assignments", "expert_steps", "experts_hit", "max_load")


SCORINGS = ("sigmoid", "softmax")


def route(x: jax.Array, router: jax.Array, bias: Optional[jax.Array],
          top_k: int, scale: float = 1.0,
          scoring: str = "sigmoid") -> Tuple[jax.Array, jax.Array]:
    """Routing of ``x`` [T, D] over ``router`` [D, E] in float32, the scores
    a sigmoid an expert or a softmax over all of them (``scoring``):
    (chosen experts [T, k], their gates [T, k]). The bias (None: the model
    has none) moves the choice and not the gate; the gates of one token sum
    to ``scale``."""
    if scoring not in SCORINGS:
        raise ValueError(f"scoring {scoring!r}: not one of {SCORINGS}")
    logits = jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    scores = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    _, chosen = lax.top_k(
        scores if bias is None else scores + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    gates = picked / picked.sum(-1, keepdims=True)
    # no operation where there is no scale: such a model's program is as it was
    return chosen, gates if scale == 1.0 else gates * scale


def _block_rows(tokens: int, top_k: int, held: int, routed: int) -> int:
    """Rows one turn of the loop behind the products takes: twice the pairs
    that land here under even routing, as a power of two from 128."""
    rows = 128
    while rows < 2 * tokens * top_k * held / routed:
        rows *= 2
    return rows


def expert_layer(
    x: jax.Array,                      # [T, D]
    weights: Dict[str, jax.Array],     # router [D, E], bias [E] (or none), gate/up [El, D, F], down [El, F, D]
    *,
    first: int,                        # the first expert held here
    top_k: int,
    live: Optional[jax.Array] = None,  # [T] bool: rows that are real tokens
    scale: float = 1.0,                # the gates of one token sum to it
    scoring: str = "sigmoid",          # how the router scores (``route``)
) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the layer for ``x``, float32 [T, D], and
    what it counted (``STATS``, int32 [4]): token-expert pairs that landed
    on held experts, held experts, held experts that got a pair, and the
    fullest held expert's pairs. Rows that are not ``live`` are routed
    nowhere and count nowhere."""
    T, D = x.shape
    held = weights["gate"].shape[0]
    with jax.named_scope("moe_experts"):
        chosen, gates = route(x, weights["router"], weights.get("bias"), top_k, scale,
                              scoring)
        local = chosen - first
        here = (local >= 0) & (local < held)
        if live is not None:
            here &= live[:, None]
        # pairs by expert, those for other chips' experts last
        group = jnp.where(here, local, held).reshape(-1)
        # room for every pair that can land here, in whole blocks of whole
        # row tiles (a block of 128 rows or more is row tiles of 128)
        most = T * min(top_k, held)
        tm = grouped_matmul.row_tile(most)
        rows = _block_rows(T, top_k, held, weights["router"].shape[1])
        if rows >= most:
            rows = -(-most // tm) * tm
        most = -(-most // rows) * rows
        order = jnp.argsort(group, stable=True)[:most]
        order = jnp.pad(order, (0, most - order.shape[0]))
        token = order // top_k
        sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
        n_pairs = sizes.sum()
        xs = x[token]                                           # [most, D]
        ws = jnp.where(jnp.arange(most) < n_pairs,
                       gates.reshape(-1)[order], 0.0)
        out = grouped_matmul.swiglu(xs, weights["gate"], weights["up"],
                                    weights["down"], sizes, tm=tm)

        def block(i, y):
            lo = i * rows
            ob = lax.dynamic_slice_in_dim(out, lo, rows)
            wb = lax.dynamic_slice_in_dim(ws, lo, rows)
            # rows past the last pair belong to no group: whatever the
            # product left there is not added
            ob = jnp.where((wb > 0)[:, None], ob * wb[:, None], 0.0)
            return y.at[lax.dynamic_slice_in_dim(token, lo, rows)].add(ob)

        y = lax.fori_loop(0, -(-n_pairs // rows), block,
                          jnp.zeros((T, D), jnp.float32))
        stats = jnp.stack([
            n_pairs, jnp.int32(held), jnp.sum(sizes > 0, dtype=jnp.int32),
            jnp.max(sizes),
        ]).astype(jnp.int32)
    return y, stats
