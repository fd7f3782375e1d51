"""The loop of paged attention over a row's page-table columns, a few
pages a turn, for the two families whose decode attends over each row's own
pages of a K pool and a V pool (``models/mimo_v2.py`` and ``models/afmoe.py``,
through ``ops/cached_attention.py``). The latent family's decode walks its
one pool in a Pallas kernel since PR 56 (``ops/paged_latent_attention.py``),
which keeps ``DECODE_PAGES`` as its turn; its prefill still takes
``pages_a_turn`` from here.

One loop over all rows runs to the longest row's context: every row
gathers, multiplies and masks the turns behind its own length, which add
exact zeros to its softmax. Where there are rows enough, the rows are taken
in order of their length and cut into ``GROUPS`` groups of equal size, a
loop a group, each bounded by its own longest row: a row meets its own
pages in the same order under the same online softmax and stops near its
own context, and its result is the one loop's to the bit. What is chosen is
chosen from what the call is given, the row count (static) and the lengths;
a program of one row (prefill) or of fewer rows than two groups keeps the
single loop.

``GROUPS`` and ``DECODE_PAGES`` are what the chip measured best at the
served shapes (PERF.md §6, PR 49). The pages a turn are also the block of
the online softmax, whose probabilities are rounded to the compute type
against the running maximum of their block: another ``DECODE_PAGES`` is the
same sum rounded at other places (in bfloat16 a hundredth of a logit, which
a router's tie can turn into another token; PERF.md §6).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

GROUPS = 4         # loops a layer where the rows allow it; a power of two
DECODE_PAGES = 8   # pages a turn of a decode row's loop
MIN_ROWS = 8       # a group holds at least a float32 tile's sublanes


def pages_a_turn(max_pages: int, wanted: int) -> int:
    """``wanted`` pages a turn, halved until it divides the table's width."""
    while max_pages % wanted:
        wanted //= 2
    return wanted


class Loops(NamedTuple):
    """How the rows of one step take their loops, the same in every layer."""

    span: int                     # positions a turn
    turns: jax.Array              # [G] turns of each group's loop
    covered: jax.Array            # positions the loops cover a layer (int32)
    order: Optional[jax.Array]    # [G, R / G] rows by length; None: one loop
    back: Optional[jax.Array]     # [R] where row r stands in ``order``


def _loops(span: int, turns: jax.Array, rows: int, order=None, back=None) -> Loops:
    # a group's rows x its turns x the positions a turn, summed over groups
    covered = (rows // turns.shape[0]) * span * jnp.sum(turns, dtype=jnp.int32)
    return Loops(span, turns, covered, order, back)


def one_loop(last: jax.Array, span: int) -> Loops:
    """Every row to the longest row's context: rows whose last visible
    positions are ``last`` [R], a turn covering ``span`` positions."""
    return _loops(span, (jnp.max(last) // span + 1)[None], last.shape[0])


def by_length(last: jax.Array, span: int) -> Loops:
    """The loops for rows whose last visible positions are ``last`` [R], a
    turn covering ``span`` positions: ``GROUPS`` of them where the rows
    allow it, else ``one_loop``. Rows nobody holds (position 0) sort first
    and cost their group one turn."""
    R = last.shape[0]
    G = GROUPS
    while G > 1 and (R % G or R // G < MIN_ROWS):
        G //= 2
    if G == 1:
        return one_loop(last, span)
    flat = jnp.argsort(last)
    order = flat.reshape(G, R // G)
    back = jnp.zeros((R,), jnp.int32).at[flat].set(jnp.arange(R, dtype=jnp.int32))
    return _loops(span, last[order[:, -1]] // span + 1, R, order, back)


def for_decode(pos: jax.Array, page_tables: jax.Array, page_tokens: int) -> Loops:
    """A decode step's loops: one query a row at ``pos`` [S], over tables
    [S, MaxPages] of pages of ``page_tokens`` positions."""
    return by_length(pos, page_tokens * pages_a_turn(page_tables.shape[1], DECODE_PAGES))


def run(loops: Loops, rows: Any, make_turn: Callable, start: Callable,
        finish: Callable) -> jax.Array:
    """Attend every row over its own pages. ``rows`` is a tree of arrays a
    row ([R, ...]: queries, page-table rows, positions);
    ``make_turn(rows)`` gives the body ``turn(j, carry)`` of a loop over
    those rows, ``start(n)`` the carry of n rows, ``finish(carry)`` their
    result [n, ...]. Returns [R, ...] in the rows' own order."""
    if loops.order is None:
        R = jax.tree.leaves(rows)[0].shape[0]
        return finish(lax.fori_loop(0, loops.turns[0], make_turn(rows), start(R)))
    n = loops.order.shape[1]
    out = []
    for g in range(loops.order.shape[0]):
        own = jax.tree.map(lambda a: a[loops.order[g]], rows)
        out.append(finish(lax.fori_loop(0, loops.turns[g], make_turn(own), start(n))))
    return jnp.concatenate(out)[loops.back]
