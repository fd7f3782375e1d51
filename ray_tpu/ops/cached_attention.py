"""Attention over a serving cache that keeps K and V a head: a full layer's
pages and a window layer's ring a decode row, for the families that have
both kinds of layer (``models/mimo_v2.py``, ``models/afmoe.py``,
``models/phi4flash.py``).

The cache has a spec a layer (the family's ``cache_spec``: kind, K/V heads
and sizes): a full layer's K and V are paged like GPT-2's, ``[pages, B,
kv_heads * size]`` with a sequence's pages named by its page table; a
window layer's are a ring a decode row, ``[rows, window, kv_heads *
size]``, position p in slot ``p % window``, so that its bytes and its reads
are the window's however long the row grows. Two kinds hold no K or V: a
``state`` layer keeps two arrays a decode row of the shapes and types its
spec gives (a recurrent layer's state and its convolution's last inputs:
``ops/selective_scan.py``), and a ``none`` layer keeps nothing (it carries
nothing from token to token, or attends over another layer's pages, which
its spec names under ``reads``).

Everything here takes K and V with the heads merged in the minor
dimension, as the caches store them (``products``). A full layer attends
over each row's own pages (``paged_attend``): a prefill chunk under one XLA
loop over page-table columns (``ops/page_loops.py``), decode's one query a
row in the Pallas kernel of ``ops/paged_kv_attention.py``, which reads the
pages where they lie. A window layer attends in one of three ways, by the
size of the ring beside the queries: in one block over keys as they lie
(``window_attend``: a ring smaller than a prefill chunk), a decode row's
ring a block a turn in the same kernel as the pages, each row as far as its
ring is filled (``ring_decode_attend``), and a prefill chunk over the blocks
of the ring the earlier chunks filled and then over itself
(``ring_chunk_attend``).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import page_loops, paged_kv_attention

RING_TURNS = 4  # the blocks a ring is read in, where it is read in blocks


@partial(jax.tree_util.register_dataclass, data_fields=["layers"],
         meta_fields=["page_tokens"])
@dataclasses.dataclass(frozen=True)
class LayerCache:
    """K or V of every layer, an array a layer by the family's
    ``cache_spec``: a full layer's ``[pages, B, kv_heads * size]``, a window
    layer's ``[rows, window, kv_heads * size]``, a state layer's ``[rows,
    ...]`` as its spec says, an empty array for a layer that keeps nothing;
    and B beside them. The
    heads stay merged in the last dimension at rest: split into ``[..,
    kv_heads, 192]`` the tiling pads 4 heads to 8 and 192 to 256, and every
    program relaid the whole pool on the way in (0.49 s of a traced 4 s;
    PERF.md, PR 46), and they stay merged through decode's attention too
    (``products``): a gathered span of pages is as costly to split."""

    layers: Tuple[jax.Array, ...]
    page_tokens: int


def init_caches(spec: Sequence[Dict[str, Any]], window: int, num_pages: int,
                page_tokens: int, rows: int, dtype):
    """(k, v) caches, zeroed, for ``rows`` decode rows over ``num_pages``
    pages: the one place that decides the stored shapes."""
    def make(which):
        def one(s):
            if s["kind"] == "none":
                return jnp.zeros((0,), dtype)
            if s["kind"] == "state":
                return jnp.zeros((rows, *s[f"{which}_row"]), s[f"{which}_dtype"])
            width = s["kv_heads"] * s[f"{which}_size"]
            return jnp.zeros((rows, window, width) if s["kind"] == "window"
                             else (num_pages, page_tokens, width), dtype)

        return LayerCache(tuple(one(s) for s in spec), page_tokens)

    return make("k"), make("v")


def layout(spec: Sequence[Dict[str, Any]], cache_k: LayerCache,
           cache_v: LayerCache) -> Dict[str, Any]:
    """The stored shape of every layer's K and the bytes both caches hold
    on the device, by kind (``batch_stats()["kv_pool_shape"]`` and the
    ``rt_serve_kv_*_bytes`` gauges)."""
    held = {"full": 0, "window": 0}
    for s, k, v in zip(spec, cache_k.layers, cache_v.layers):
        if s["kind"] != "none":
            held[s["kind"]] = (held.get(s["kind"], 0) + k.on_device_size_in_bytes()
                               + v.on_device_size_in_bytes())
    return {"shape": [[s["kind"], *k.shape] for s, k in zip(spec, cache_k.layers)],
            "bytes": held}


def products(q, kv_heads: int, v_heads: Optional[int] = None):
    """The two products of attention for queries ``q`` [R, Q, H, Dk], as
    functions of K and V with the heads merged in the minor dimension, as
    the caches store them: ``scores(k [R, T, Hkv * Dk])`` -> [R, H, Q, T]
    float32 and ``weighted(p [R, H, Q, T], v [R, T, Hkv * Dv])`` -> [R, H,
    Q, Dv] float32; query head h reads K/V head ``h // (H / Hkv)``. With
    ``v_heads`` the values are taken as that many heads instead (query head
    h weighs value head ``h // (H / v_heads)``, ``Dv`` the wider for it):
    differential attention's pair of K/V heads, whose values lie side by
    side as the cache stores them (``models/phi4flash.py``).

    Split into ``[R, T, Hkv, size]`` a gathered span of pages or a ring is
    relaid whole (4 or 8 heads are no multiple of 8 sublanes, 192 none of
    128 lanes: every K and V byte a decode step reads written three more
    times, a fifth of the step; PERF.md, PR 47), so the split is made on
    the side that is small, and which side that is the queries a row say.
    One query a row is small beside K and V: each head is spread once over
    all Hkv * Dk columns, its own numbers in its K/V head's columns and
    exact zeros in the others, which add exact zeros to a float32 sum; a
    K/V head's values are a slice of columns (whole lanes at Dv 128), its
    query heads' probabilities a slice of rows, a product a K/V head. A
    chunk of queries is not small: Hkv times the operations would show (2
    to 4 ms of a 512-wide prefill call's 24), and its few keys and values
    are split."""
    R, Q, H, Dk = q.shape
    G = H // kv_heads
    v_heads = v_heads or kv_heads
    Gv = H // v_heads
    qg = q.reshape(R, Q, kv_heads, G, Dk)
    scale = Dk ** -0.5
    if Q == 1:
        own = jnp.eye(kv_heads, dtype=q.dtype)[:, None, :, None]
        spread = (qg[..., None, :] * own).reshape(R, Q, H, kv_heads * Dk)
        # left to itself the compiler sinks the spreading into a loop over
        # pages and makes the 12.6 MB anew every turn (0.7 ms a step)
        spread = lax.optimization_barrier(spread)

        def scores(k):
            return scale * jnp.einsum("rqhc,rtc->rhqt", spread, k,
                                      preferred_element_type=jnp.float32)

        def weighted(p, v):
            Dv = v.shape[2] // v_heads
            return jnp.concatenate([
                jnp.einsum("rgqt,rtv->rgqv", p[:, j * Gv:(j + 1) * Gv],
                           v[:, :, j * Dv:(j + 1) * Dv],
                           preferred_element_type=jnp.float32)
                for j in range(v_heads)], axis=1)
    else:
        def scores(k):
            return scale * jnp.einsum(
                "rqjgd,rtjd->rjgqt", qg, k.reshape(R, -1, kv_heads, Dk),
                preferred_element_type=jnp.float32).reshape(R, H, Q, -1)

        def weighted(p, v):
            T = v.shape[1]
            return jnp.einsum(
                "rjgqt,rtjv->rjgqv", p.reshape(R, v_heads, Gv, Q, T),
                v.reshape(R, T, v_heads, -1),
                preferred_element_type=jnp.float32).reshape(R, H, Q, -1)

    return scores, weighted


def softmax_update(carry, scores, values, visible, weighted):
    """One block of an online softmax: ``scores`` [R, H, Q, T] float32 of
    ``products``, ``visible`` broadcastable to them, ``values`` [R, T, Hkv
    * Dv] for ``weighted`` of the same ``products``."""
    m, den, acc = carry
    scores = jnp.where(visible, scores, -1e30)
    m_new = jnp.maximum(m, scores.max(-1))
    scale = jnp.exp(m - m_new)
    p = jnp.where(visible, jnp.exp(scores - m_new[..., None]), 0.0)
    acc = acc * scale[..., None] + weighted(p.astype(values.dtype), values)
    return m_new, den * scale + p.sum(-1), acc


def finish(carry, sink=None):
    """The softmax's quotient, [R, H, Q, Dv] -> [R, Q, H * Dv]. A ``sink``
    [H], one learned logit a head, joins the denominator and nothing else."""
    m, den, acc = carry
    if sink is not None:
        s = sink.astype(jnp.float32)[None, :, None]
        m_new = jnp.maximum(m, s)
        scale = jnp.exp(m - m_new)
        den, acc = den * scale + jnp.exp(s - m_new), acc * scale[..., None]
    out = acc / den[..., None]
    R, H, Q, Dv = out.shape
    return out.transpose(0, 2, 1, 3).reshape(R, Q, H * Dv)


def start(R, H, Q, Dv):
    return (jnp.full((R, H, Q), -1e30, jnp.float32),
            jnp.zeros((R, H, Q), jnp.float32),
            jnp.zeros((R, H, Q, Dv), jnp.float32))


def paged_attend(q, k_pool, v_pool, tables, q_pos, kv_heads: int, loops,
                 v_heads: Optional[int] = None, name: str = "paged_kv_attention"):
    """Causal attention of ``q`` [R, Q, H, Dk] at positions ``q_pos`` [R, Q]
    over each row's own pages of a full layer (``tables`` [R, MaxPages]),
    in one of two forms by what ``loops`` is. Returns [R, Q, H * Dv] float32.

    A prefill chunk takes ``page_loops.one_loop`` of the rows' last
    positions: one loop over page-table columns, a few a turn, that stops
    behind the last position any query sees, so a call reads the longest
    row's context and neither the table's width nor the pool; the gathered
    pages go into the products as they lie.

    Decode's one query a row takes the step's ``paged_kv_attention.Visits``:
    the same products and the same online softmax, turn for turn, in the
    kernel that reads each row's pages where they lie and gathers nothing,
    under ``name`` in the chip's trace."""
    if isinstance(loops, paged_kv_attention.Visits):
        out = paged_kv_attention.attend(
            q[:, 0], k_pool, v_pool, tables, q_pos[:, 0], loops, kv_heads=kv_heads,
            v_heads=v_heads or kv_heads, name=name)
        return out.reshape(out.shape[0], 1, -1)
    R, Q, H, _ = q.shape
    B = k_pool.shape[1]
    span, C = loops.span, loops.span // B
    scores, weighted = products(q, kv_heads, v_heads)

    def turn(j, carry):
        pages = lax.dynamic_slice_in_dim(tables, j * C, C, axis=1)  # [R, C]
        kc = k_pool[pages].reshape(R, span, -1)
        vc = v_pool[pages].reshape(R, span, -1)
        kv_pos = j * span + jnp.arange(span)
        visible = kv_pos[None, None, :] <= q_pos[:, :, None]  # [R, Q, T]
        return softmax_update(carry, scores(kc), vc, visible[:, None], weighted)

    return finish(lax.fori_loop(
        0, loops.turns, turn, start(R, H, Q, v_pool.shape[2] // (v_heads or kv_heads))))


def window_attend(q, keys, values, visible, kv_heads: int, sink):
    """Attention of ``q`` [R, Q, H, Dk] over a window layer's keys and values
    as they lie, [R, T, Hkv * size] (a ring, or a ring and the chunk behind
    it), where ``visible`` [R, Q, T], the layer's ``sink`` [H] in the
    denominator. Returns [R, Q, H * Dv]."""
    R, Q, H, _ = q.shape
    scores, weighted = products(q, kv_heads)
    carry = softmax_update(start(R, H, Q, values.shape[2] // kv_heads),
                           scores(keys), values, visible[:, None], weighted)
    return finish(carry, sink)


def ring_positions(upto, size: int):
    """The position each slot of a ring holds once positions 0 .. ``upto``
    - 1 are written: for slot r the largest p < ``upto`` with p % size ==
    r, negative where there is none. ``upto`` [...] -> [..., size]."""
    last = upto[..., None] - 1
    return last - (last - jnp.arange(size)) % size


def ring_span(window: int) -> int:
    """Slots a turn where a ring is read in blocks: ``RING_TURNS`` blocks
    where the window divides so."""
    return window // RING_TURNS if window % RING_TURNS == 0 else window


def ring_visits(pos, window: int) -> paged_kv_attention.Visits:
    """A decode step's visits of its rows' rings, the same in every window
    layer: a row at ``pos`` [S] holds slots 0 .. min(pos, window - 1) and
    walks the blocks up to that one, so a row inside its window reads what
    it has written and a row past it all of its ring."""
    span = ring_span(window)
    return paged_kv_attention.visits(jnp.minimum(pos, window - 1), span, window // span)


def ring_decode_attend(q, ring_k, ring_v, pos, kv_heads: int,
                       walk: paged_kv_attention.Visits, v_heads: Optional[int] = None):
    """One query a row, ``q`` [S, 1, H, Dk] at ``pos`` [S] (already written
    to its slot), over the row's ring ``[S, window, Hkv * size]`` under
    ``ring_visits``: the ring read as the pages of its row, a block each (a
    reshape of the array as it lies), every slot up to min(pos, window - 1)
    visible, whatever position it holds: before the ring wraps those are
    the positions written, after it all of them, each within the window.
    Returns [S, 1, H * Dv]."""
    S, W, _ = ring_k.shape
    n = W // walk.span
    tables = jnp.arange(S, dtype=jnp.int32)[:, None] * n + jnp.arange(n, dtype=jnp.int32)
    last = jnp.minimum(pos, W - 1)
    return paged_attend(q, ring_k.reshape(S * n, walk.span, -1),
                        ring_v.reshape(S * n, walk.span, -1), tables, last[:, None],
                        kv_heads, walk, v_heads=v_heads, name="ring_kv_attention")


def ring_chunk_attend(q, ring_k, ring_v, k, v, first, pos, window: int,
                      kv_heads: int, v_heads: Optional[int] = None):
    """A prefill chunk in a window layer: ``q`` [R, P, H, Dk] at ``pos``
    [R, P] over the rows' rings ``[R, window, Hkv * size]`` as the earlier
    chunks left them (positions 0 .. ``first`` [R] - 1 written) and the
    chunk's own ``k``, ``v`` [R, P, Hkv * size], a query seeing the
    ``window`` positions up to its own. The ring is met a block a turn, as
    far as the fullest row's is filled (a first chunk meets none of it),
    then the chunk, under one online softmax. Returns [R, P, H * Dv]."""
    R, P, H, _ = q.shape
    span = ring_span(window)
    scores, weighted = products(q, kv_heads, v_heads)
    held = ring_positions(first, window)                          # [R, W]

    def visible(k_pos):  # [R, T] -> [R, 1, P, T]
        gap = pos[:, :, None] - k_pos[:, None, :]
        return ((k_pos >= 0)[:, None, :] & (gap >= 0) & (gap < window))[:, None]

    def turn(j, carry):
        kc = lax.dynamic_slice_in_dim(ring_k, j * span, span, axis=1)
        vc = lax.dynamic_slice_in_dim(ring_v, j * span, span, axis=1)
        at = lax.dynamic_slice_in_dim(held, j * span, span, axis=1)
        return softmax_update(carry, scores(kc), vc, visible(at), weighted)

    filled = jnp.minimum(jnp.max(first), window)
    carry = lax.fori_loop(0, (filled + span - 1) // span, turn,
                          start(R, H, P, v.shape[2] // (v_heads or kv_heads)))
    return finish(softmax_update(carry, scores(k), v, visible(pos), weighted))


def ring_rows(ring, row):
    """The rings of rows ``row`` [R] out of ``ring`` [rows, window, C], a
    slice a row: a gather of whole rings of 2,048 positions is compiled as
    a cut of ALL the rings into blocks first, a copy of every ring (0.7 ms
    each at 128 rows: PERF.md §6, PR 53)."""
    return jnp.stack([lax.dynamic_index_in_dim(ring, row[r], 0, keepdims=False)
                      for r in range(row.shape[0])])


def ring_take(ring, own, chunk, first, length, row):
    """The rings ``[rows, window, C]`` after rows ``row`` [R], whose rings
    were ``own`` [R, window, C], took their chunks ``chunk`` [R, P, C]
    (positions ``first`` .. ``first + length`` - 1, [R] each): a slot takes
    the chunk's last real position that falls into it. A row of no length
    writes no ring."""
    W, P = ring.shape[1], chunk.shape[1]
    after = ring_positions(first + length, W)                     # [R, W]
    takes = (after >= first[:, None]) & (length[:, None] > 0)
    src = jnp.clip(after - first[:, None], 0, P - 1)[..., None]
    new = jnp.where(takes[..., None], jnp.take_along_axis(chunk, src, axis=1), own)
    # behind the last ring where the row has no length: dropped
    return ring.at[jnp.where(length > 0, row, ring.shape[0])].set(new, mode="drop")
