"""Grouped matrix products for the expert layer, as one Pallas kernel.

Rows sorted by group (``lhs`` [M, K], the first ``sizes[0]`` rows of group
0, the next ``sizes[1]`` of group 1, ...) times one matrix a group
(``rhs`` [G, K, N], as the experts are stored):

    out[r] = lhs[r] @ rhs[group of r]

The design is that of ``jax.experimental.pallas.ops.tpu.megablox.gmm``:
the rows are cut into tiles of ``tm``, and the grid walks the VISITS, the
(group, row tile) pairs in which a group has a row, in the order of the
rows. The group and the tile of every visit are scalar-prefetched
(``visits``), so the block a visit reads is known a step ahead and its
weights are on their way while the visit before it is multiplied: a group
costs its bytes, and a group without a row costs nothing, because no visit
names it. The grid's length is the number of visits, a run-time number:
time goes with the pairs that landed, not with the room of the buffer. A
tile that two groups share is visited by both, and each stores its own
rows only. Rows that belong to no group (past the last pair) are written
by nobody: the caller must not read them.

``gated`` runs the first two products of a SwiGLU as one call: the rows'
tile is read once, ``silu(lhs @ gate) * (lhs @ up)`` is the epilogue, and
the result leaves in the type of ``lhs``. ``product`` is the plain form,
float32 out. Both accumulate in float32. ``swiglu`` is the three products
of an expert layer on one grid, which is what ``ops/moe.py`` calls.

The tiles are a function of what the call sees: ``row_tile`` of the rows
handed, ``_column_tile`` of K, N and the element size, so that a visit's
weights, twice (the block in flight beside the block in use), stay within
``_WEIGHT_BLOCK_BYTES``. K is never cut: the rows' tile then stays in
VMEM from one group to the next, and there is no accumulator to carry.
The constants come from a sweep on a v5e at the serving cells' shapes
(PERF.md section 6, PR 55).

On a CPU the kernel runs in the Pallas interpreter, under the rule of
``flash_attention._interpret``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import flash_attention

_LANES = 128
# most rows to a tile: the MXU's own height, so that a group of a few rows
# pays for one pass of its weights and a full tile wastes none
_ROW_TILE = 128
# a visit's weights (both matrices of ``gated``) in one block
_WEIGHT_BLOCK_BYTES = 8 << 20


class Visits(NamedTuple):
    """The grid of one layer's products: what ``visits`` returns."""

    tm: int             # rows to a tile
    offsets: jax.Array  # [G + 1] the row each group starts at
    group: jax.Array    # [V] the group of a visit
    tile: jax.Array     # [V] the row tile of a visit
    count: jax.Array    # [] visits that hold a row; the rest are padding


def row_tile(rows: int) -> int:
    """Rows to a tile for a buffer of ``rows``: 128, or all of a smaller
    buffer in whole sublane groups of a 16-bit type."""
    return min(_ROW_TILE, -(-rows // 16) * 16)


def visits(sizes: jax.Array, rows: int, tm: int) -> Visits:
    """The visits of groups of ``sizes`` [G] laid end to end over ``rows``
    rows in tiles of ``tm`` (``rows`` a multiple of it): a group visits
    every tile it has a row in, an empty group none. At most
    ``rows / tm + G - 1`` of them, which is the static length. Where no
    group has a row there is still one visit, the last group's to the first
    tile, which stores nothing: the grid is never empty."""
    G = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    each = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    V = rows // tm + G - 1
    group = jnp.repeat(jnp.arange(G, dtype=jnp.int32), each, total_repeat_length=V)
    before = jnp.cumsum(each) - each
    tile = first[group] + jnp.arange(V, dtype=jnp.int32) - before[group]
    return Visits(
        tm, jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]).astype(jnp.int32),
        group, jnp.clip(tile, 0, rows // tm - 1).astype(jnp.int32),
        jnp.maximum(each.sum(), 1).astype(jnp.int32))


def _column_tile(K: int, N: int, matrices: int, itemsize: int) -> int:
    """Columns of the weights to a block: all N where a visit's weights fit
    ``_WEIGHT_BLOCK_BYTES``, else N halved while the halves are whole lane
    groups."""
    tn = N
    while matrices * K * tn * itemsize > _WEIGHT_BLOCK_BYTES and tn % (2 * _LANES) == 0:
        tn //= 2
    return tn


def _vmem_bytes(tm: int, K: int, tn: int, matrices: int, itemsize: int,
                out_itemsize: int) -> int:
    """What a call asks for: every block twice (the one in flight beside
    the one in use), the products and the epilogue in float32, and room."""
    blocks = tm * K * itemsize + matrices * K * tn * itemsize + tm * tn * out_itemsize
    return 2 * blocks + (matrices + 2) * tm * tn * 4 + (4 << 20)


def _kernel(offsets, group, tile, lhs, *refs, tm: int):
    *weights, out = refs
    v = pl.program_id(1)
    x = lhs[...]
    acc = [jnp.dot(x, w[...], preferred_element_type=jnp.float32) for w in weights]
    val = acc[0] if len(acc) == 1 else jax.nn.silu(acc[0]) * acc[1]
    g = group[v]
    row = tile[v] * tm + jax.lax.broadcasted_iota(jnp.int32, val.shape, 0)
    mine = (row >= offsets[g]) & (row < offsets[g + 1])
    # the tile's other rows are another group's, or nobody's
    out[...] = jnp.where(mine, val, out[...].astype(jnp.float32)).astype(out.dtype)


def _call(lhs, weights, grid: Visits, out_dtype):
    M, K = lhs.shape
    N = weights[0].shape[2]
    tm = grid.tm
    isz = jnp.dtype(lhs.dtype).itemsize
    tn = _column_tile(K, N, len(weights), isz)
    osz = jnp.dtype(out_dtype).itemsize
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // tn, grid.count),
            in_specs=[pl.BlockSpec((tm, K), lambda n, v, o, g, t: (t[v], 0))] + [
                pl.BlockSpec((None, K, tn), lambda n, v, o, g, t: (g[v], 0, n))
                for _ in weights],
            out_specs=pl.BlockSpec((tm, tn), lambda n, v, o, g, t: (t[v], n)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(tm, K, tn, len(weights), isz, osz)),
        cost_estimate=pl.CostEstimate(
            flops=2 * len(weights) * M * K * N, transcendentals=0,
            bytes_accessed=(M * K * isz + M * N * osz
                            + sum(w.size for w in weights) * isz)),
        name="grouped_matmul",
        interpret=flash_attention._interpret(),
    )(grid.offsets, grid.group, grid.tile, lhs, *weights)


def gated(lhs: jax.Array, gate: jax.Array, up: jax.Array, grid: Visits) -> jax.Array:
    """``silu(lhs @ gate[g]) * (lhs @ up[g])`` a row of group g, [M, N] in
    the type of ``lhs``; ``gate`` and ``up`` are [G, K, N]."""
    return _call(lhs, (gate, up), grid, lhs.dtype)


def product(lhs: jax.Array, rhs: jax.Array, grid: Visits) -> jax.Array:
    """``lhs @ rhs[g]`` a row of group g, float32 [M, N]; ``rhs`` [G, K, N]."""
    return _call(lhs, (rhs,), grid, jnp.float32)


@functools.partial(jax.jit, static_argnames=("tm",))
def swiglu(lhs: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array,
           sizes: jax.Array, *, tm: int) -> jax.Array:
    """The three products of a SwiGLU a group, ``(silu(lhs @ gate[g]) *
    (lhs @ up[g])) @ down[g]``, float32 [M, K], on one grid: ``lhs`` [M, K]
    sorted by group in tiles of ``tm`` rows, ``sizes`` [G] rows a group.
    Jitted so that a program of several expert layers of one shape traces
    and lowers the kernels once, not once a layer."""
    grid = visits(sizes, lhs.shape[0], tm)
    return product(gated(lhs, gate, up, grid), down, grid)
