"""Decode attention over a K pool and a V pool, as one Pallas kernel that
reads each row's pages, or its ring's blocks, where they lie: the one-query
form of ``ops/cached_attention.paged_attend`` (``models/mimo_v2.py``,
``models/afmoe.py``, ``models/phi4flash.py``), the sibling of
``ops/paged_latent_attention.py``, whose one pool is keys and values at once.

One query a row (``q`` [S, H, Dk]) attends over the row's own pages of
``k_pool`` [pages, B, Hkv * Dk] and ``v_pool`` [pages, B, Hv * Dv], the heads
merged in the minor dimension as the caches store them (``tables`` [S,
MaxPages] names the pages, ``pos`` [S] is the row's last visible position).
Neither pool is gathered, split or copied: both enter the kernel in HBM as
they are, and a TURN of a few pages (each one contiguous run) is brought
into one slot of a K buffer and of a V buffer in VMEM by as many DMAs each,
while both products and the online softmax's update run on the turn before
it in another slot. A row walks its own turns and stops behind its own
``pos``: of its last turn only the pages up to that position's are read,
the others stay as the slot holds them and are masked. The step's VISITS, its (row, turn) pairs in the order walked, are
numbered through; visit v lands in slot ``v % _SLOTS`` and is started
``_SLOTS - 1`` visits ahead, so a row's last turns start the next rows'
first and the read never waits for a row to begin. The grid is the rows, in
order; the page table, the positions and the visits are scalar-prefetched.
The visits are a function of the positions and the turn alone, the same in
every layer of a step, so the step computes them once (``visits``) and
every layer's call takes them.

The mathematics is ``products`` + ``softmax_update`` + ``finish`` of
``ops/cached_attention.py``, turn for turn (that loop stays for prefill):
the query spread once a row over all Hkv * Dk columns, its own numbers in
its K/V head's columns and exact zeros elsewhere; float32 scores, running
maximum and denominator; the probabilities rounded to the type of ``q``
against the running maximum of their turn before the second product; a
value head a slice of whole lanes; positions behind ``pos`` masked. The
turn is therefore part of the result's rounding and is the caller's to
say (``Visits.span``): ``page_loops.DECODE_PAGES`` pages for a paged layer,
one block of ``ring_span`` slots for a ring (PERF.md section 6, PR 49, 56
and 58). A row nobody holds (``pos`` 0) makes one turn over the first page
its table names.

On a CPU the kernel runs in the Pallas interpreter, under the rule of
``flash_attention._interpret``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import flash_attention, page_loops

# turns in VMEM at once: the one multiplied and the ones on their way
_SLOTS = 3


@functools.partial(jax.tree_util.register_dataclass, data_fields=["first", "row_of"],
                   meta_fields=["span"])
@dataclasses.dataclass(frozen=True)
class Visits:
    """A step's (row, turn) pairs, numbered in the order walked."""

    span: int          # positions a turn
    first: jax.Array   # [S + 1] the visit a row starts at; the last: all of them
    row_of: jax.Array  # [S * most] the row of every visit


def visits(last: jax.Array, span: int, most: int) -> Visits:
    """The visits of rows whose last visible positions are ``last`` [S], a
    turn covering ``span`` positions, no row walking more than ``most``: a
    row walks up to its own last position, in whole turns."""
    each = last.astype(jnp.int32) // span + 1
    S = last.shape[0]
    first = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(each, dtype=jnp.int32)])
    row_of = jnp.repeat(jnp.arange(S, dtype=jnp.int32), each, total_repeat_length=S * most)
    return Visits(span, first, row_of)


def page_visits(pos: jax.Array, max_pages: int, page_tokens: int) -> Visits:
    """A decode step's visits of its rows' pages: one query a row at ``pos``
    [S] over tables ``max_pages`` wide of pages of ``page_tokens`` positions,
    ``page_loops.DECODE_PAGES`` pages a turn."""
    pages = page_loops.pages_a_turn(max_pages, page_loops.DECODE_PAGES)
    return visits(pos, pages * page_tokens, max_pages // pages)


def positions_read(last: jax.Array, live: jax.Array, page_tokens: int) -> jax.Array:
    """The positions ``attend`` reads for the rows that are ``live``, in one
    call over pages of ``page_tokens``: each row's pages up to its last
    visible position's, whole (int32)."""
    return jnp.sum(jnp.where(live, (last // page_tokens + 1) * page_tokens, 0), dtype=jnp.int32)


def _kernel(tables, pos, first, row_of, q_ref, lift_ref, own_ref, k_pool, v_pool, out_ref,
            k_buf, v_buf, sems, *, pages: int, max_pages: int, v_heads: int, scale: float):
    r = pl.program_id(0)
    slots, _, B, _ = k_buf.shape
    span = pages * B
    at = pos[r]
    mine, total = first[r], first[pl.num_programs(0)]

    def visit(v, act):
        """``act`` on the DMAs of visit ``v``, the v-th (row, turn) of the
        step in the order walked: the turn's pages of K and of V into the
        visit's slot, as far as the row's last visible position lies (the
        pages behind it are left as the slot holds them, and masked)."""
        row = row_of[v]
        turn = v - first[row]
        page = row * max_pages + turn * pages
        held = (pos[row] - turn * span) // B + 1
        slot = v % slots
        for i in range(pages):
            def a_page(i=i):
                for pool, buf in ((k_pool, k_buf), (v_pool, v_buf)):
                    act(pltpu.make_async_copy(pool.at[tables[page + i]], buf.at[slot, i],
                                              sems.at[slot]))
            if i == 0:
                a_page()
            else:
                pl.when(i < held)(a_page)

    def start(v):
        @pl.when(v < total)
        def _():
            visit(v, lambda c: c.start())

    @pl.when(r == 0)
    def _():
        if pages > 1:
            # a page no DMA has written yet meets probabilities of exact
            # zero, and what it holds must be a number for that
            v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
        for v in range(slots - 1):
            start(v)

    # the query spread over its K/V head's columns: ``lift`` copies a head's
    # Dk numbers under every K/V head (a product with ones and zeros, exact),
    # ``own`` keeps the head's own
    q = q_ref[...]
    spread = (jnp.dot(q, lift_ref[...], preferred_element_type=jnp.float32)
              * own_ref[...]).astype(q.dtype)                    # [H, Hkv * Dk]
    H = q.shape[0]
    Dv = out_ref.shape[1]
    head_of = lax.broadcasted_iota(jnp.int32, (H, Dv), 0) // (H // v_heads)

    def turn(j, carry):
        m, den, acc = carry
        v = mine + j
        start(v + slots - 1)  # this row's, or the next rows' first
        visit(v, lambda c: c.wait())
        keys = k_buf[v % slots].reshape(span, -1)
        values = v_buf[v % slots].reshape(span, -1)
        scores = scale * lax.dot_general(spread, keys, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)  # [H, span]
        visible = j * span + lax.broadcasted_iota(jnp.int32, scores.shape, 1) <= at
        scores = jnp.where(visible, scores, -1e30)
        m_new = jnp.maximum(m, scores.max(-1, keepdims=True))
        fade = jnp.exp(m - m_new)
        p = jnp.where(visible, jnp.exp(scores - m_new), 0.0)
        # every head's probabilities over each value head's lanes; a head
        # keeps its own value head's: the sums it keeps are its own product's
        pv = p.astype(values.dtype)
        new = jnp.zeros((H, Dv), jnp.float32)
        for h in range(v_heads):
            new = jnp.where(head_of == h, jnp.dot(pv, values[:, h * Dv:(h + 1) * Dv],
                                                  preferred_element_type=jnp.float32), new)
        return m_new, den * fade + p.sum(-1, keepdims=True), acc * fade + new

    _, den, acc = lax.fori_loop(0, first[r + 1] - mine, turn, (
        jnp.full((H, 1), -1e30, jnp.float32), jnp.zeros((H, 1), jnp.float32),
        jnp.zeros((H, Dv), jnp.float32)))
    out_ref[...] = acc / den


@functools.partial(jax.jit, static_argnames=("kv_heads", "v_heads", "name"))
def attend(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array, tables: jax.Array,
           pos: jax.Array, walk: Visits, *, kv_heads: int, v_heads: int,
           name: str) -> jax.Array:
    """A softmax a head of ``q`` [S, H, Dk] over each row's own pages:
    ``k_pool`` [pages, B, kv_heads * Dk] and ``v_pool`` [pages, B, v_heads *
    Dv] of the type of ``q``, ``tables`` [S, MaxPages] int32, ``pos`` [S]
    int32 the last position a row sees (its table names every page up to
    that one's), ``walk`` the step's ``visits`` of those positions. Query
    head h reads K head ``h // (H / kv_heads)`` and weighs value head ``h //
    (H / v_heads)``. Returns [S, H, Dv] float32, the softmax's quotient as
    ``cached_attention.finish`` leaves it. ``name`` is the kernel's in the
    trace. Jitted so that a program of several layers traces and lowers the
    kernel once a name."""
    S, H, Dk = q.shape
    B, Wk = k_pool.shape[1:]
    Wv = v_pool.shape[2]
    Dv = Wv // v_heads
    max_pages = tables.shape[1]
    pages = walk.span // B
    column = jnp.arange(Wk)
    lift = (column[None, :] % Dk == jnp.arange(Dk)[:, None]).astype(q.dtype)
    own = (column[None, :] // Dk == jnp.arange(H)[:, None] // (H // kv_heads)).astype(jnp.float32)
    isz = jnp.dtype(q.dtype).itemsize
    whole = lambda a: pl.BlockSpec(a.shape, lambda r, *_: (0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, pages=pages, max_pages=max_pages, v_heads=v_heads,
                          scale=Dk ** -0.5),
        out_shape=jax.ShapeDtypeStruct((S, H, Dv), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(S,),
            in_specs=[pl.BlockSpec((None, H, Dk), lambda r, *_: (r, 0, 0)),
                      whole(lift), whole(own), in_hbm, in_hbm],
            out_specs=pl.BlockSpec((None, H, Dv), lambda r, *_: (r, 0, 0)),
            scratch_shapes=[pltpu.VMEM((_SLOTS, pages, B, Wk), k_pool.dtype),
                            pltpu.VMEM((_SLOTS, pages, B, Wv), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((_SLOTS,))],
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        # half the tables' room: what a step reads is known only as it runs
        cost_estimate=pl.CostEstimate(
            flops=S * H * (Wk + Wv) * max_pages * B,
            transcendentals=S * H * max_pages * B // 2,
            bytes_accessed=S * max_pages * B * (Wk + Wv) * isz // 2 + S * H * (Dk * isz + Dv * 4)),
        name=name,
        interpret=flash_attention._interpret(),
    )(tables.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32), walk.first, walk.row_of,
      q, lift, own, k_pool, v_pool)
