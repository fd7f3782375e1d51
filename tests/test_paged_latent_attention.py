"""``ops/paged_latent_attention.attend`` (one query a row over the row's own
pages of a pool that is keys and values at once) in the Pallas interpreter
on the CPU, against a plain float32 softmax a row at a time over the row's
pages gathered by hand."""

import numpy as np
import pytest
from test_mimo_v2 import rows_of_every_length

B, H, W, MAX_PAGES = 16, 4, 128, 128   # the tiny latent family's: pages of 16, 2,048 positions
SCALE = 24 ** -0.5


@pytest.fixture(scope="module")
def ops():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.ops import page_loops, paged_latent_attention

    return paged_latent_attention, B * page_loops.pages_a_turn(MAX_PAGES, page_loops.DECODE_PAGES)


def plain(q, pool, tables, pos):
    """float32, a row at a time: the row's pages laid end to end, cut
    behind its position, one softmax a head."""
    q, pool = np.asarray(q, np.float32), np.asarray(pool, np.float32)
    out = []
    for r in range(q.shape[0]):
        rows = pool[np.asarray(tables[r])].reshape(-1, q.shape[2])[: int(pos[r]) + 1]
        s = SCALE * q[r] @ rows.T
        p = np.exp(s - s.max(-1, keepdims=True))
        out.append((p / p.sum(-1, keepdims=True)) @ rows)
    return np.stack(out)


def draw(rng, *shape, dtype="float32"):
    import jax.numpy as jnp

    return jnp.asarray(rng.normal(0, 1, shape), dtype)


@pytest.mark.parametrize("rows", [3, 16, 32, 40])
def test_rows_of_every_length_in_any_order_attend_as_a_plain_softmax(ops, rows):
    """Rows of every length, shuffled (nobody's, one position, a page's
    edge, several turns, the table's last position), their pages scattered
    over the pool; the rows permuted give the same rows permuted, to the
    bit. A row nobody holds (position 0) attends over the one position its
    table names, in the scratch page."""
    import jax.numpy as jnp

    pla, _ = ops
    rng = np.random.default_rng(rows)
    pos, tables, N = rows_of_every_length(rng, rows, B, MAX_PAGES)
    q, pool = draw(rng, rows, H, W), draw(rng, N, B, W)
    pos, tables = jnp.asarray(pos, jnp.int32), jnp.asarray(tables)
    got = pla.attend(q, pool, tables, pos, scale=SCALE)
    assert got.shape == q.shape and got.dtype == q.dtype
    want = plain(q, pool, tables, pos)
    assert np.abs(want).max() > 0.5
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    nobodys = np.flatnonzero(np.asarray(pos) == 0)
    assert len(nobodys) == 1
    assert np.allclose(np.asarray(got[nobodys[0]]), np.asarray(pool[0, 0])[None], atol=1e-6)
    perm = rng.permutation(rows)
    moved = pla.attend(q[perm], pool, tables[perm], pos[perm], scale=SCALE)
    assert np.array_equal(np.asarray(moved), np.asarray(got)[perm])


def test_a_row_stops_at_its_own_turn_and_shares_its_first_pages(ops):
    """Rows whose last position ends a turn exactly, opens the next, and
    ends the table; every row's first pages are the same pages (the system
    prompt's, in the cell) and the rest its own, scattered. What lies in a
    row's pages behind its position, and in pages behind its last turn
    (here: huge numbers), reaches no result."""
    import jax.numpy as jnp

    pla, span = ops
    rng = np.random.default_rng(7)
    pos = np.asarray([span - 1, span, 2 * span - 1, 2 * span, 5, MAX_PAGES * B - 1, span + 1])
    rows, shared = len(pos), 6
    need = pos // B + 1
    own = rng.permutation(np.arange(1 + shared, 1 + shared + need.sum()))
    tables = np.zeros((rows, MAX_PAGES), np.int32)
    at = 0
    for r in range(rows):
        tables[r, :need[r]] = own[at:at + need[r]]
        tables[r, :min(shared, need[r])] = 1 + np.arange(min(shared, need[r]))
        at += need[r]
    pool = np.array(draw(rng, 2 + shared + need.sum(), B, W))
    poison = len(pool) - 1
    pool[poison] = 1e4
    for r in range(rows):                       # behind the position: poisoned
        tables[r, need[r]:] = poison
        if (pos[r] + 1) % B and need[r] > shared:
            pool[tables[r, need[r] - 1], (pos[r] + 1) % B:] = 1e4
    q = draw(rng, rows, H, W)
    got = pla.attend(q, jnp.asarray(pool), jnp.asarray(tables), jnp.asarray(pos, jnp.int32),
                     scale=SCALE)
    want = plain(q, pool, tables, pos)
    assert np.abs(np.asarray(got)).max() < 10
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    live = jnp.asarray(pos > 5)
    assert int(pla.positions_read(jnp.asarray(pos, jnp.int32), live, MAX_PAGES, B)) == span * (
        1 + 2 + 2 + 3 + MAX_PAGES * B // span + 2)


def test_bfloat16_operands_round_as_the_loop_it_replaced(ops):
    """As served: bfloat16 queries and pool, float32 sums, the
    probabilities rounded to bfloat16 against their turn's running maximum.
    The same sum written with ``jax.numpy``, a turn at a time over gathered
    pages (the form ``models/deepseek_v3._absorbed_attend`` had until PR
    56), agrees to float32's rounding of the products; the plain float32
    softmax of the same bfloat16 numbers to bfloat16's."""
    import jax.numpy as jnp

    pla, span = ops
    rng = np.random.default_rng(3)
    rows = 8
    pos, tables, N = rows_of_every_length(rng, rows, B, MAX_PAGES)
    q, pool = draw(rng, rows, H, W, dtype="bfloat16"), draw(rng, N, B, W, dtype="bfloat16")
    got = pla.attend(q, pool, jnp.asarray(tables), jnp.asarray(pos, jnp.int32), scale=SCALE)
    assert got.dtype == jnp.bfloat16

    def a_turn_at_a_time(r):
        m, den = jnp.full((H,), -1e30), jnp.zeros((H,))
        acc = jnp.zeros((H, W))
        for j in range(int(pos[r]) // span + 1):
            rows_j = pool[tables[r, j * span // B:(j + 1) * span // B]].reshape(span, W)
            s = SCALE * jnp.einsum("hw,tw->ht", q[r], rows_j, preferred_element_type=jnp.float32)
            visible = (j * span + jnp.arange(span) <= pos[r])[None]
            s = jnp.where(visible, s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.where(visible, jnp.exp(s - m_new[:, None]), 0.0)
            fade = jnp.exp(m - m_new)
            den = den * fade + p.sum(-1)
            acc = acc * fade[:, None] + jnp.einsum(
                "ht,tw->hw", p.astype(jnp.bfloat16), rows_j, preferred_element_type=jnp.float32)
            m = m_new
        return (acc / den[:, None]).astype(jnp.bfloat16)

    loop = jnp.stack([a_turn_at_a_time(r) for r in range(rows)])
    as32 = lambda a: np.asarray(a, np.float32)
    assert np.abs(as32(got) - as32(loop)).max() <= 2 ** -7   # one bfloat16 step at 1
    assert np.mean(as32(got) == as32(loop)) > 0.98
    assert np.abs(as32(got) - plain(q, pool, tables, pos)).max() < 0.03
