"""The turn of paged attention over a row's page-table columns, a few pages
at a time, and the XLA loop a prefill call takes over them.

A prefill call's rows (``ops/cached_attention.paged_attend`` for the
families with a K and a V pool, ``models/deepseek_v3.py`` for the latent
one) walk ONE loop to the longest row's context (``one_loop``): every row
gathers, multiplies and masks the turns behind its own length, which add
exact zeros to its softmax. Decode walks no loop here: each row reads its
own pages to its own length inside a Pallas kernel
(``ops/paged_kv_attention.py`` since PR 58, ``ops/paged_latent_attention.py``
since PR 56), which take ``DECODE_PAGES`` as their turn.

``DECODE_PAGES`` is what the chip measured best at the served shapes
(PERF.md section 6, PR 49). The pages a turn are also the block of the
online softmax, whose probabilities are rounded to the compute type against
the running maximum of their block: another ``DECODE_PAGES`` is the same
sum rounded at other places (in bfloat16 a hundredth of a logit, which a
router's tie can turn into another token; PERF.md section 6).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

DECODE_PAGES = 8   # pages a turn of a decode row's walk


def pages_a_turn(max_pages: int, wanted: int) -> int:
    """``wanted`` pages a turn, halved until it divides the table's width."""
    while max_pages % wanted:
        wanted //= 2
    return wanted


class Loops(NamedTuple):
    """The one loop a prefill call's rows take, the same in every layer."""

    span: int          # positions a turn
    turns: jax.Array   # the loop's turns (int32 scalar)


def one_loop(last: jax.Array, span: int) -> Loops:
    """Every row to the longest row's context: rows whose last visible
    positions are ``last`` [R], a turn covering ``span`` positions."""
    return Loops(span, jnp.max(last) // span + 1)
