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

Routing is the sigmoid kind (``noaux_tc``): scores ``s = sigmoid(W_r x)``
in float32, the top ``k`` of ``s + b`` chosen (``b`` a selection bias that
takes no part in the gate), gates ``s_e / sum of the chosen s``, times a
``scale`` where the model has one (``routed_scaling_factor``).

The product is grouped, not one-hot: the token-expert pairs that landed
here are sorted by expert and multiplied by ``jax.lax.ragged_dot``, which
reads an expert's weights once for all its rows and reads no weight of an
expert that got none. There is no capacity: the sorted buffer has room for
every pair that can land here (``tokens * min(k, held)``), and is worked
through in blocks of ``block`` rows by a loop that stops after the last
pair, because ``ragged_dot``'s time goes with the rows it is handed, not
with the rows its groups cover (2.6 ms for 1,024 rows of which 64 were
grouped, 1.3 ms for 64 rows, 16 experts of 4096 x 2048; PERF.md, PR 46).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# what ``expert_layer`` counts, in the order of its fourth result
STATS = ("assignments", "expert_steps", "experts_hit", "max_load")


def route(x: jax.Array, router: jax.Array, bias: jax.Array,
          top_k: int, scale: float = 1.0) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid routing of ``x`` [T, D] over ``router`` [D, E] in float32:
    (chosen experts [T, k], their gates [T, k]). The bias moves the choice
    and not the gate; the gates of one token sum to ``scale``."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    ))
    _, chosen = lax.top_k(scores + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    gates = picked / picked.sum(-1, keepdims=True)
    # no operation where there is no scale: such a model's program is as it was
    return chosen, gates if scale == 1.0 else gates * scale


def _block_rows(tokens: int, top_k: int, held: int, routed: int) -> int:
    """Rows one turn of the loop multiplies: twice the pairs that land
    here under even routing, as a power of two from 128, and no more than
    can land here at all."""
    most = tokens * min(top_k, held)
    expected = tokens * top_k * held / routed
    rows = 128
    while rows < 2 * expected:
        rows *= 2
    return min(rows, most)


def expert_layer(
    x: jax.Array,                      # [T, D]
    weights: Dict[str, jax.Array],     # router [D, E], bias [E], gate/up [El, D, F], down [El, F, D]
    *,
    first: int,                        # the first expert held here
    top_k: int,
    live: Optional[jax.Array] = None,  # [T] bool: rows that are real tokens
    scale: float = 1.0,                # the gates of one token sum to it
) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the layer for ``x``, float32 [T, D], and
    what it counted (``STATS``, int32 [4]): token-expert pairs that landed
    on held experts, held experts, held experts that got a pair, and the
    fullest held expert's pairs. Rows that are not ``live`` are routed
    nowhere and count nowhere."""
    T, D = x.shape
    held = weights["gate"].shape[0]
    routed = weights["router"].shape[1]
    with jax.named_scope("moe_experts"):
        chosen, gates = route(x, weights["router"], weights["bias"], top_k, scale)
        local = chosen - first
        here = (local >= 0) & (local < held)
        if live is not None:
            here &= live[:, None]
        # pairs by expert, those for other chips' experts last
        group = jnp.where(here, local, held).reshape(-1)
        rows = _block_rows(T, top_k, held, routed)
        # room for every pair that can land here, in whole blocks
        most = -(-T * min(top_k, held) // rows) * rows
        order = jnp.argsort(group, stable=True)[:most]
        order = jnp.pad(order, (0, most - order.shape[0]))
        token = order // top_k
        sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
        ends = jnp.cumsum(sizes)
        starts = ends - sizes
        n_pairs = ends[-1]
        xs = x[token]                                           # [most, D]
        ws = jnp.where(jnp.arange(most) < n_pairs,
                       gates.reshape(-1)[order], 0.0)

        def block(i, y):
            lo = i * rows
            part = jnp.clip(ends, lo, lo + rows) - jnp.clip(starts, lo, lo + rows)
            xb = lax.dynamic_slice_in_dim(xs, lo, rows)
            g = lax.ragged_dot(xb, weights["gate"], part,
                               preferred_element_type=jnp.float32)
            u = lax.ragged_dot(xb, weights["up"], part,
                               preferred_element_type=jnp.float32)
            h = (jax.nn.silu(g) * u).astype(x.dtype)
            out = lax.ragged_dot(h, weights["down"], part,
                                 preferred_element_type=jnp.float32)
            wb = lax.dynamic_slice_in_dim(ws, lo, rows)
            # rows past the last pair belong to no group: whatever the
            # product left there is not added
            out = jnp.where((wb > 0)[:, None], out * wb[:, None], 0.0)
            return y.at[lax.dynamic_slice_in_dim(token, lo, rows)].add(out)

        y = lax.fori_loop(0, -(-n_pairs // rows), block,
                          jnp.zeros((T, D), jnp.float32))
        stats = jnp.stack([
            n_pairs, jnp.int32(held), jnp.sum(sizes > 0, dtype=jnp.int32),
            jnp.max(sizes),
        ]).astype(jnp.int32)
    return y, stats
