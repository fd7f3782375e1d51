"""Decode attention over a paged pool whose rows are keys and values at
once, as one Pallas kernel that reads each row's pages where they lie.

One query a row (``q`` [S, H, W], H heads) attends over the row's own pages
of ``pool`` [pages, B, W] (``tables`` [S, MaxPages] names them, ``pos`` [S]
is the row's last visible position):

    scores = scale * q . pool_rows        [H, positions]
    out    = softmax(scores) . pool_rows  [H, W]

The pool is never gathered, relaid or copied: it enters the kernel in HBM
as it is, and a TURN of ``pages_a_turn`` pages (each one contiguous run) is
brought into one slot of a VMEM buffer by as many DMAs, while both products
and the online softmax's update run on the turn before it in another
slot. A row walks its own turns and stops behind its own ``pos``. The
step's VISITS, its (row, turn) pairs in the order walked, are numbered
through; visit v lands in slot ``v % _SLOTS`` and is started ``_SLOTS - 1``
visits ahead, so a row's last turns start the next rows' first and the
read never waits for a row to begin. The grid is the rows, in order; the
page table, the positions and the visits are scalar-prefetched.

The mathematics is the XLA loop's it replaces (``ops/page_loops.py`` keeps
that form for the families with a K and a V pool): operands in the type of
``q``, float32 products, float32 running maximum and denominator, the
probabilities rounded to the type of ``q`` against the running maximum of
their turn before the second product, positions behind ``pos`` masked. The
turn is therefore part of the result's rounding, and ``DECODE_PAGES`` stays
what it was (``page_loops.DECODE_PAGES``; PERF.md section 6, PR 49 and 56).
A row nobody holds (``pos`` 0) makes one turn over what its table names.

On a CPU the kernel runs in the Pallas interpreter, under the rule of
``flash_attention._interpret``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import flash_attention, page_loops

# turns in VMEM at once: the one multiplied and the ones on their way
_SLOTS = 3


def _pages_a_turn(max_pages: int) -> int:
    return page_loops.pages_a_turn(max_pages, page_loops.DECODE_PAGES)


def _turns(pos: jax.Array, span: int) -> jax.Array:
    """The turns each row walks: up to its own ``pos``, in whole turns."""
    return pos // span + 1


def positions_read(pos: jax.Array, live: jax.Array, max_pages: int,
                   page_tokens: int) -> jax.Array:
    """The positions ``attend`` reads for the rows that are ``live``, in one
    call: each row's turns times the positions a turn (int32)."""
    span = page_tokens * _pages_a_turn(max_pages)
    return jnp.sum(jnp.where(live, _turns(pos, span) * span, 0), dtype=jnp.int32)


def _kernel(tables, pos, first, row_of, q_ref, pool, out_ref, buf, sems, *,
            pages: int, max_pages: int, scale: float):
    r = pl.program_id(0)
    slots, _, B, W = buf.shape
    span = pages * B
    at = pos[r]
    mine, total = first[r], first[pl.num_programs(0)]

    def copies(v):
        """The DMAs of visit ``v``, the v-th (row, turn) of the step in the
        order walked: the turn's pages into the visit's slot."""
        row = row_of[v]
        page = row * max_pages + (v - first[row]) * pages
        return [pltpu.make_async_copy(pool.at[tables[page + i]], buf.at[v % slots, i],
                                      sems.at[v % slots]) for i in range(pages)]

    def start(v):
        @pl.when(v < total)
        def _():
            for c in copies(v):
                c.start()

    @pl.when(r == 0)
    def _():
        for v in range(slots - 1):
            start(v)

    q = q_ref[...]

    def turn(j, carry):
        m, den, acc = carry
        v = mine + j
        start(v + slots - 1)  # this row's, or the next rows' first
        for c in copies(v):
            c.wait()
        rows = buf[v % slots].reshape(span, W)
        scores = scale * lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)  # [H, span]
        visible = j * span + lax.broadcasted_iota(jnp.int32, scores.shape, 1) <= at
        scores = jnp.where(visible, scores, -1e30)
        m_new = jnp.maximum(m, scores.max(-1, keepdims=True))
        fade = jnp.exp(m - m_new)
        p = jnp.where(visible, jnp.exp(scores - m_new), 0.0)
        acc = acc * fade + jnp.dot(p.astype(rows.dtype), rows,
                                   preferred_element_type=jnp.float32)
        return m_new, den * fade + p.sum(-1, keepdims=True), acc

    H = q.shape[0]
    _, den, acc = lax.fori_loop(0, first[r + 1] - mine, turn, (
        jnp.full((H, 1), -1e30, jnp.float32), jnp.zeros((H, 1), jnp.float32),
        jnp.zeros((H, W), jnp.float32)))
    out_ref[...] = (acc / den).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale",))
def attend(q: jax.Array, pool: jax.Array, tables: jax.Array, pos: jax.Array, *,
           scale: float) -> jax.Array:
    """``softmax(scale * q . rows) . rows`` a row over its own pages:
    ``q`` [S, H, W], ``pool`` [pages, B, W] of the same type, ``tables`` [S,
    MaxPages] int32, ``pos`` [S] int32 the last position a row sees (its
    table names every page up to that one's). Returns [S, H, W] in the type
    of ``q``. Jitted so that a program of several layers traces and lowers
    the kernel once."""
    S, H, W = q.shape
    B = pool.shape[1]
    max_pages = tables.shape[1]
    pages = _pages_a_turn(max_pages)
    pos = pos.astype(jnp.int32)
    # the step's visits, a (row, turn) each, in the order walked: the visit
    # a row starts at, and the row of every visit
    each = _turns(pos, pages * B)
    first = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(each, dtype=jnp.int32)])
    row_of = jnp.repeat(jnp.arange(S, dtype=jnp.int32), each,
                        total_repeat_length=S * (max_pages // pages))
    isz = jnp.dtype(q.dtype).itemsize
    block = pl.BlockSpec((None, H, W), lambda r, *_: (r, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, pages=pages, max_pages=max_pages, scale=scale),
        out_shape=jax.ShapeDtypeStruct((S, H, W), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(S,),
            in_specs=[block, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=block,
            scratch_shapes=[pltpu.VMEM((_SLOTS, pages, B, W), pool.dtype),
                            pltpu.SemaphoreType.DMA((_SLOTS,))],
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        # half the tables' room: what a step reads is known only as it runs
        cost_estimate=pl.CostEstimate(
            flops=2 * S * H * W * max_pages * B,
            transcendentals=S * H * max_pages * B // 2,
            bytes_accessed=S * max_pages * B * W * isz // 2 + 2 * S * H * W * isz),
        name="paged_latent_attention",
        interpret=flash_attention._interpret(),
    )(tables.reshape(-1).astype(jnp.int32), pos, first, row_of, q, pool)
