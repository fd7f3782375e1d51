"""``ops/paged_kv_attention.attend`` (one query a row over the row's own
pages of a K pool and a V pool, or over the blocks of its ring) in the
Pallas interpreter on the CPU, at the head counts and sizes of the three
families that call it, each from its own ``cache_spec``: against a plain
float32 softmax a head, a row at a time over the row's pages gathered by
hand, and against the XLA loop it replaced in decode
(``cached_attention.paged_attend`` under ``page_loops.one_loop`` with the
same turn, which prefill keeps)."""

import numpy as np
import pytest
from test_mimo_v2 import rows_of_every_length

B, MAX_PAGES, PAGES = 8, 32, 4   # small pages: 256 positions a row, 32 a turn
SPAN = B * PAGES
FAMILIES = ("phi-4-mini-flash-reasoning", "trinity-mini", "mimo-v2.5")
WITH_RINGS = FAMILIES[:2]  # MiMo-V2's rings of 128 are read in one block


def heads(model_id, kind):
    """(H, K heads, value heads, Dk, Dv) of the family's layers of ``kind``
    as decode's attention takes them: Phi-4-flash's values as half as many
    heads twice as wide, the pair as it lies in the cache."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu import models

    cfg, dec = models.resolve(model_id)
    (Hkv, Dk, Dv), = {(s["kv_heads"], s["k_size"], s["v_size"])
                      for s in dec.cache_spec(cfg) if s["kind"] == kind}
    Hv = Hkv // 2 if model_id.startswith("phi") else Hkv
    return cfg.num_attention_heads, Hkv, Hv, Dk, Dv * Hkv // Hv


@pytest.fixture(scope="module")
def ops():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.ops import cached_attention, page_loops, paged_kv_attention

    return paged_kv_attention, cached_attention, page_loops


def plain(q, keys, values, Hkv, Hv):
    """float32, one row: ``q`` [H, Dk] over the visible ``keys`` [T, Hkv *
    Dk] and ``values`` [T, Hv * Dv], one softmax a head -> [H, Dv]."""
    q, keys, values = (np.asarray(a, np.float32) for a in (q, keys, values))
    H, Dk = q.shape
    Dv = values.shape[1] // Hv
    out = []
    for h in range(H):
        j, jv = h // (H // Hkv), h // (H // Hv)
        s = Dk ** -0.5 * keys[:, j * Dk:(j + 1) * Dk] @ q[h]
        p = np.exp(s - s.max())
        out.append((p / p.sum()) @ values[:, jv * Dv:(jv + 1) * Dv])
    return np.stack(out)


def plain_pages(q, k_pool, v_pool, tables, pos, Hkv, Hv):
    k_pool, v_pool = np.asarray(k_pool, np.float32), np.asarray(v_pool, np.float32)
    return np.stack([
        plain(q[r], k_pool[np.asarray(tables[r])].reshape(-1, k_pool.shape[2])[: int(pos[r]) + 1],
              v_pool[np.asarray(tables[r])].reshape(-1, v_pool.shape[2])[: int(pos[r]) + 1],
              Hkv, Hv) for r in range(q.shape[0])])


def draw(rng, *shape, dtype="float32"):
    import jax.numpy as jnp

    return jnp.asarray(rng.normal(0, 1, shape), dtype)


def attend(pka, q, k_pool, v_pool, tables, pos, Hkv, Hv, span=SPAN, name="paged_kv_attention"):
    import jax.numpy as jnp

    pos = jnp.asarray(pos, jnp.int32)
    tables = jnp.asarray(tables)
    walk = pka.visits(pos, span, tables.shape[1] * k_pool.shape[1] // span)
    return pka.attend(q, k_pool, v_pool, tables, pos, walk, kv_heads=Hkv, v_heads=Hv, name=name)


@pytest.mark.parametrize("rows", [3, 16, 40])
@pytest.mark.parametrize("model_id", FAMILIES)
def test_rows_of_every_length_in_any_order_attend_as_a_plain_softmax_a_head(ops, model_id, rows):
    """Rows of every length, shuffled (nobody's, one position, a page's
    edge, several turns, the table's last position), their pages scattered
    over the pools; the rows permuted give the same rows permuted, to the
    bit. A row nobody holds (position 0) makes one turn and attends over the
    one position its table names, in the scratch page."""
    pka = ops[0]
    H, Hkv, Hv, Dk, Dv = heads(model_id, "full")
    rng = np.random.default_rng(rows)
    pos, tables, N = rows_of_every_length(rng, rows, B, MAX_PAGES)
    q, k_pool, v_pool = draw(rng, rows, H, Dk), draw(rng, N, B, Hkv * Dk), draw(rng, N, B, Hv * Dv)
    got = attend(pka, q, k_pool, v_pool, tables, pos, Hkv, Hv)
    assert got.shape == (rows, H, Dv) and got.dtype == np.float32
    want = plain_pages(q, k_pool, v_pool, tables, pos, Hkv, Hv)
    assert np.abs(want).max() > 0.5
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    nobodys = np.flatnonzero(pos == 0)
    assert len(nobodys) == 1
    own = np.asarray(v_pool[0, 0]).reshape(Hv, 1, Dv).repeat(H // Hv, 1).reshape(H, Dv)
    assert np.allclose(np.asarray(got[nobodys[0]]), own, atol=1e-6)
    perm = rng.permutation(rows)
    moved = attend(pka, q[perm], k_pool, v_pool, tables[perm], pos[perm], Hkv, Hv)
    assert np.array_equal(np.asarray(moved), np.asarray(got)[perm])


@pytest.mark.parametrize("model_id", FAMILIES)
def test_a_row_stops_at_its_own_turn_and_shares_its_first_pages(ops, model_id):
    """Rows whose last position ends a turn exactly, opens the next, and
    ends the table; every row's first pages are the same pages (a shared
    prefix's) and the rest its own, scattered. What lies in a row's pages
    behind its position, and in pages behind its last turn (here: huge
    numbers or none at all), reaches no result; ``positions_read`` is the
    pages a row's turns hold up to its own position's."""
    import jax.numpy as jnp

    pka = ops[0]
    H, Hkv, Hv, Dk, Dv = heads(model_id, "full")
    rng = np.random.default_rng(7)
    pos = np.asarray([SPAN - 1, SPAN, 2 * SPAN - 1, 2 * SPAN, 5, MAX_PAGES * B - 1, SPAN + 1])
    rows, shared = len(pos), 6
    need = pos // B + 1
    own = rng.permutation(np.arange(1 + shared, 1 + shared + need.sum()))
    tables = np.zeros((rows, MAX_PAGES), np.int32)
    at = 0
    for r in range(rows):
        tables[r, :need[r]] = own[at:at + need[r]]
        tables[r, :min(shared, need[r])] = 1 + np.arange(min(shared, need[r]))
        at += need[r]
    N = 2 + shared + need.sum()
    k_pool, v_pool = np.array(draw(rng, N, B, Hkv * Dk)), np.array(draw(rng, N, B, Hv * Dv))
    poison = N - 1                              # behind a row's last page: never read
    k_pool[poison] = v_pool[poison] = np.nan
    for r in range(rows):                       # behind the position: poisoned
        tables[r, need[r]:] = poison
        if (pos[r] + 1) % B and need[r] > shared:
            k_pool[tables[r, need[r] - 1], (pos[r] + 1) % B:] = 1e4
            v_pool[tables[r, need[r] - 1], (pos[r] + 1) % B:] = 1e4
    q = draw(rng, rows, H, Dk)
    got = attend(pka, q, jnp.asarray(k_pool), jnp.asarray(v_pool), tables, pos, Hkv, Hv)
    want = plain_pages(q, k_pool, v_pool, tables, pos, Hkv, Hv)
    assert np.abs(np.asarray(got)).max() < 10
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    pos = jnp.asarray(pos, jnp.int32)
    walked = np.diff(np.asarray(pka.visits(pos, SPAN, MAX_PAGES // PAGES).first))
    assert list(walked) == [1, 2, 2, 3, 1, MAX_PAGES // PAGES, 2]
    live = pos > 5
    assert int(pka.positions_read(pos, live, B)) == B * int((need * (np.asarray(pos) > 5)).sum())
    assert pka.page_visits(pos, 64, 16).span == 16 * ops[2].DECODE_PAGES


@pytest.mark.parametrize("model_id", WITH_RINGS)
def test_a_ring_before_it_wraps_exactly_full_and_wrapped(ops, model_id):
    """``cached_attention.ring_decode_attend`` over rings of four blocks of
    16: rows inside their first block, at a block's edge, one short of the
    window, exactly at it (the ring full, not yet wrapped) and far past it
    (wrapped many times), one of no length, against a plain softmax over
    the last ``window`` positions of the sequence. A row walks the blocks
    it has written and no more, under the ring's own name."""
    import jax.numpy as jnp

    pka, ca, _ = ops
    H, Hkv, Hv, Dk, Dv = heads(model_id, "window")
    W = 64
    rng = np.random.default_rng(11)
    pos = np.asarray([0, 1, 15, 16, W - 2, W - 1, W, W + 1, 3 * W + 5, 7 * W, 40, 200])
    rows = len(pos)
    ks = rng.normal(0, 1, (rows, pos.max() + 1, Hkv * Dk)).astype(np.float32)
    vs = rng.normal(0, 1, (rows, pos.max() + 1, Hv * Dv)).astype(np.float32)
    ring_k = np.zeros((rows, W, Hkv * Dk), np.float32)
    ring_v = np.zeros((rows, W, Hv * Dv), np.float32)
    for r in range(rows):
        for p in range(pos[r] + 1):
            ring_k[r, p % W], ring_v[r, p % W] = ks[r, p], vs[r, p]
    q = draw(rng, rows, H, Dk)
    walk = ca.ring_visits(jnp.asarray(pos, jnp.int32), W)
    assert walk.span == 16
    assert list(np.diff(np.asarray(walk.first))) == [1, 1, 1, 2, 4, 4, 4, 4, 4, 4, 3, 4]
    got = ca.ring_decode_attend(q[:, None], jnp.asarray(ring_k), jnp.asarray(ring_v),
                                jnp.asarray(pos, jnp.int32), Hkv, walk, v_heads=Hv)
    want = np.stack([plain(q[r], ks[r, max(0, pos[r] - W + 1): pos[r] + 1],
                           vs[r, max(0, pos[r] - W + 1): pos[r] + 1], Hkv, Hv)
                     for r in range(rows)])
    assert got.shape == (rows, 1, H * Dv) and np.abs(want).max() > 0.5
    assert np.abs(np.asarray(got).reshape(rows, H, Dv) - want).max() < 1e-5


@pytest.mark.parametrize("model_id,kind", [(m, "full") for m in FAMILIES]
                         + [(m, "window") for m in WITH_RINGS])
def test_bfloat16_operands_round_as_the_loop_it_replaced(ops, model_id, kind):
    """As served: bfloat16 queries, K and V, float32 sums, the probabilities
    rounded to bfloat16 against their turn's running maximum, the quotient
    left in float32. The loop decode had until PR 58
    (``cached_attention.paged_attend`` under ``page_loops.one_loop`` with
    the same turn: prefill's form) agrees to float32's rounding of the
    products, and rounded to bfloat16, as the output projection takes them,
    to the bit but for a number in fifty, which lies one bfloat16 step off;
    the plain float32 softmax of the same bfloat16 numbers to bfloat16's. A
    ring's turn is one block, a paged layer's a few pages."""
    import jax.numpy as jnp

    pka, ca, page_loops = ops
    H, Hkv, Hv, Dk, Dv = heads(model_id, kind)
    rng = np.random.default_rng(3)
    rows = 8
    if kind == "full":
        pos, tables, N = rows_of_every_length(rng, rows, B, MAX_PAGES)
        page, span, name = B, SPAN, "paged_kv_attention"
    else:  # rings of four blocks of 16, read as four pages of their row
        pos, N = np.asarray([0, 3, 15, 16, 31, 47, 62, 63]), 4 * rows
        page, span, name = 16, 16, "ring_kv_attention"
        tables = np.arange(N, dtype=np.int32).reshape(rows, 4)
    q = draw(rng, rows, H, Dk, dtype="bfloat16")
    k_pool = draw(rng, N, page, Hkv * Dk, dtype="bfloat16")
    v_pool = draw(rng, N, page, Hv * Dv, dtype="bfloat16")
    got = attend(pka, q, k_pool, v_pool, tables, pos, Hkv, Hv, span=span, name=name)
    assert got.dtype == jnp.float32
    pos = jnp.asarray(pos, jnp.int32)
    loop = ca.paged_attend(q[:, None], k_pool, v_pool, jnp.asarray(tables), pos[:, None], Hkv,
                           page_loops.one_loop(pos, span), v_heads=Hv)
    assert loop.dtype == jnp.float32
    got, loop = np.asarray(got).reshape(rows, -1), np.asarray(loop)[:, 0]
    assert np.abs(got - loop).max() < 1e-5
    as16 = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32)
    assert np.abs(as16(got) - as16(loop)).max() <= 2 ** -7   # one bfloat16 step at 1
    assert np.mean(as16(got) == as16(loop)) > 0.98
    want = plain_pages(q, k_pool, v_pool, tables, pos, Hkv, Hv).reshape(rows, -1)
    assert np.abs(got - want).max() < 0.03
