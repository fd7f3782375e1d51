"""The attention helpers two families share (``ops/cached_attention.py``),
held to a plain softmax over the visible positions at both families' tiny
presets: MiMo-V2's (a sink a head, K of 24 and V of 16, 1 or 2 K/V heads)
and Trinity's (no sink, 2 K/V heads of 16). The same functions, the same
cases: pages, a ring in one block, a ring in decode's kernel and a ring
met by a prefill chunk, each with the ring wrapped and not, one query a row
and several.
"""

import numpy as np
import pytest
from test_mimo_v2 import plain_attention, walk_of  # a softmax a head, written plainly

FAMILIES = ("mimo-v2-tiny", "trinity-tiny")


def shapes(model_id):
    """(query heads, [(kv_heads, k_size, v_size) of the window layers], window,
    whether a window layer has a sink)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu import models

    cfg, dec = models.resolve(model_id)
    window = sorted({(s["kv_heads"], s["k_size"], s["v_size"])
                     for s in dec.cache_spec(cfg) if s["kind"] == "window"})
    assert len(window) == 1
    return cfg.num_attention_heads, window[0], cfg.sliding_window, model_id.startswith("mimo")


def sequences(rng, lengths, width):
    """K or V of whole sequences, [R, longest, width], noise behind a
    row's length."""
    return rng.normal(0, 1, (len(lengths), max(lengths), width)).astype(np.float32)


def ring_of(seq, upto, W):
    """The ring a row holds once positions 0 .. upto - 1 of ``seq`` [T, C]
    are written, other slots noise-free zeros."""
    ring = np.zeros((W, seq.shape[1]), np.float32)
    for p in range(upto):
        ring[p % W] = seq[p]
    return ring


@pytest.mark.parametrize("Q", [1, 5])
@pytest.mark.parametrize("model_id", FAMILIES)
def test_pages_and_a_ring_in_one_block_are_a_softmax_a_head(model_id, Q):
    """A full layer over a page table (a row of length 0, rows that end
    mid-page, pages in no order) and a window layer's keys in one block
    (the family's sink or none; a ring not yet full and a full one)."""
    import jax.numpy as jnp

    from ray_tpu.ops import cached_attention as ca

    H, (Hkv, Dk, Dv), W, has_sink = shapes(model_id)
    rng = np.random.default_rng(Q)
    R, B, max_pages, N = 5, 8, 4, 12
    draw = lambda *shape: jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
    q = draw(R, Q, H, Dk)
    last = np.asarray([0, 11, 17, 31, 8])
    q_pos = np.maximum(last[:, None] - np.arange(Q)[::-1][None], 0)
    tables = np.zeros((R, max_pages), np.int32)
    free = list(rng.permutation(np.arange(1, N)))
    for r in range(1, R):
        for c in range(last[r] // B + 1):
            tables[r, c] = free.pop()
    k_pool, v_pool = draw(N, B, Hkv * Dk), draw(N, B, Hkv * Dv)
    got = ca.paged_attend(q, k_pool, v_pool, jnp.asarray(tables),
                          jnp.asarray(q_pos, jnp.int32), Hkv,
                          walk_of(Q, last, 2 * B, max_pages // 2))
    T = max_pages * B
    visible = np.arange(T)[None, None, :] <= q_pos[:, :, None]
    want = plain_attention(q, k_pool[tables].reshape(R, T, -1),
                           v_pool[tables].reshape(R, T, -1), visible, Hkv)
    assert got.shape == (R, Q, H * Dv) and float(jnp.abs(want).max()) > 0.5
    assert float(jnp.abs(got - want).max()) < 1e-5

    ring_k, ring_v = draw(R, W, Hkv * Dk), draw(R, W, Hkv * Dv)
    sink = jnp.asarray(rng.normal(0, 1, (H,)), jnp.float32) if has_sink else None
    held = np.asarray([1, 7, W, 12, 3])  # slots written; W is a full ring
    seen = np.minimum(held[:, None] - np.arange(Q)[::-1][None], W).clip(1)
    visible = np.arange(W)[None, None, :] < seen[:, :, None]
    got = ca.window_attend(q, ring_k, ring_v, jnp.asarray(visible), Hkv, sink)
    want = plain_attention(q, ring_k, ring_v, visible, Hkv, sink)
    assert float(jnp.abs(got - want).max()) < 1e-5


@pytest.mark.parametrize("rows", [5, 16, 32])
@pytest.mark.parametrize("model_id", FAMILIES)
def test_a_decode_rows_ring_in_the_kernel_is_a_softmax_over_its_window(model_id, rows):
    """One query a row at positions inside the window, at its edge and far
    past it (the ring wrapped many times), rows of no length among them,
    in shuffled order: the ring read a block a turn in decode's kernel, each
    row as far as its ring is filled, against a plain softmax over the last
    ``window`` positions of the sequence."""
    import jax.numpy as jnp

    from ray_tpu.ops import cached_attention as ca

    H, (Hkv, Dk, Dv), W, _ = shapes(model_id)
    rng = np.random.default_rng(rows)
    special = [0, 1, W // 4 - 1, W // 4, W - 2, W - 1, W, W + 1, 3 * W + 5, 7 * W]
    pos = np.asarray((special + list(rng.integers(1, 5 * W, max(0, rows - len(special)))))[:rows])
    pos = pos[rng.permutation(rows)]
    ks, vs = sequences(rng, pos + 1, Hkv * Dk), sequences(rng, pos + 1, Hkv * Dv)
    ring_k = np.stack([ring_of(ks[r], pos[r] + 1, W) for r in range(rows)])
    ring_v = np.stack([ring_of(vs[r], pos[r] + 1, W) for r in range(rows)])
    q = jnp.asarray(rng.normal(0, 1, (rows, 1, H, Dk)), jnp.float32)
    walk = ca.ring_visits(jnp.asarray(pos, jnp.int32), W)
    assert walk.span == ca.ring_span(W) == W // 4
    got = ca.ring_decode_attend(q, jnp.asarray(ring_k), jnp.asarray(ring_v),
                                jnp.asarray(pos, jnp.int32), Hkv, walk)
    T = ks.shape[1]
    at = np.arange(T)[None, None, :]
    visible = (at <= pos[:, None, None]) & (pos[:, None, None] - at < W)
    want = plain_attention(q, ks, vs, visible, Hkv)
    assert got.shape == (rows, 1, H * Dv) and float(jnp.abs(want).max()) > 0.5
    assert float(jnp.abs(got - want).max()) < 1e-5
    # a row past its window reads all of its ring, a row inside it the
    # blocks it has written
    turns = np.diff(np.asarray(walk.first))
    assert turns.max() == 4 and list(turns) == list(np.minimum(pos, W - 1) // (W // 4) + 1)
    # rows inside a quarter of their window read a quarter of their rings
    short = ca.ring_visits(jnp.asarray(pos % (W // 4), jnp.int32), W)
    assert int(short.first[-1]) == rows


@pytest.mark.parametrize("P", [1, 4, 24])
@pytest.mark.parametrize("model_id", FAMILIES)
def test_a_prefill_chunk_meets_the_ring_then_itself_and_leaves_the_ring_right(model_id, P):
    """Rows that start cold, inside the window, at its edge and far past it
    (a ring that has wrapped), one of no length: a chunk of P positions
    (one, a few, more than the window of 16) over the ring as the earlier
    chunks left it and over itself, against a plain softmax over the last
    ``window`` positions of the sequence; then the ring the chunk leaves,
    against the ring written position by position."""
    import jax.numpy as jnp

    from ray_tpu.ops import cached_attention as ca

    H, (Hkv, Dk, Dv), W, _ = shapes(model_id)
    rng = np.random.default_rng(P)
    first = np.asarray([0, 3, W - 1, W, 2 * W + 5, 9, 0])
    length = np.asarray([P, P, max(P - 1, 1), P, P, P, 0])
    R = len(first)
    total = first + P
    ks, vs = sequences(rng, total, Hkv * Dk), sequences(rng, total, Hkv * Dv)
    ring_k = np.stack([ring_of(ks[r], first[r], W) for r in range(R)])
    ring_v = np.stack([ring_of(vs[r], first[r], W) for r in range(R)])
    chunk_k = np.stack([ks[r, first[r]:first[r] + P] for r in range(R)])
    chunk_v = np.stack([vs[r, first[r]:first[r] + P] for r in range(R)])
    q = jnp.asarray(rng.normal(0, 1, (R, P, H, Dk)), jnp.float32)
    pos = first[:, None] + np.arange(P)[None]
    got = ca.ring_chunk_attend(q, jnp.asarray(ring_k), jnp.asarray(ring_v),
                               jnp.asarray(chunk_k), jnp.asarray(chunk_v),
                               jnp.asarray(first, jnp.int32), jnp.asarray(pos, jnp.int32), W, Hkv)
    T = ks.shape[1]
    at = np.arange(T)[None, None, :]
    visible = (at <= pos[:, :, None]) & (pos[:, :, None] - at < W)
    want = plain_attention(q, ks, vs, visible, Hkv)
    assert float(jnp.abs(want).max()) > 0.5
    assert float(jnp.abs(got - want).max()) < 1e-5

    # the rings of eight decode rows, these seven in rows 6 .. 0
    row = jnp.asarray(np.arange(R)[::-1].copy(), jnp.int32)
    rings = np.full((8, W, Hkv * Dk), 7.0, np.float32)
    rings[np.asarray(row)] = ring_k
    own = ca.ring_rows(jnp.asarray(rings), row)
    assert np.array_equal(np.asarray(own), ring_k)  # a slice a row, in the call's order
    after = ca.ring_take(jnp.asarray(rings), own, jnp.asarray(chunk_k),
                         jnp.asarray(first, jnp.int32), jnp.asarray(length, jnp.int32), row)
    for r in range(R):
        kept = np.asarray(after[int(row[r])])
        if length[r] == 0:
            assert (kept == ring_k[r]).all()  # a row of no length writes no ring
        else:
            assert np.array_equal(kept, ring_of(ks[r], first[r] + length[r], W)), r
    assert (np.asarray(after[7]) == 7.0).all()  # nobody's ring is as it was


def test_the_ring_positions_are_the_last_written_to_each_slot():
    import jax.numpy as jnp

    from ray_tpu.ops import cached_attention as ca

    assert list(np.asarray(ca.ring_positions(jnp.asarray(0), 4))) == [-4, -3, -2, -1]
    assert list(np.asarray(ca.ring_positions(jnp.asarray(3), 4))) == [0, 1, 2, -1]
    assert list(np.asarray(ca.ring_positions(jnp.asarray(10), 4))) == [8, 9, 6, 7]
    assert ca.ring_span(2048) == 512 and ca.ring_span(128) == 32 and ca.ring_span(6) == 6
