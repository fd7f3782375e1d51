"""The grouped products of the expert layer (ops/grouped_matmul.py) against
``jax.lax.ragged_dot``, float32 on the CPU, the kernel in the Pallas
interpreter (the rule of ``flash_attention._interpret``)."""

import numpy as np
import pytest

# name: (rows handed, K, N, group sizes, bytes a weight block may hold or None)
CASES = {
    "empty groups between full ones": (64, 32, 16, [0, 20, 0, 0, 30, 14, 0], None),
    "every row in one group": (48, 32, 16, [0, 0, 48, 0], None),
    "a group straddles two row tiles": (300, 32, 16, [100, 0, 150, 30], None),
    "a group covers three row tiles": (400, 16, 8, [10, 380, 10], None),
    "rows past the last pair": (256, 32, 16, [3, 5, 0, 2], None),
    "no pair at all": (32, 32, 16, [0, 0, 0], None),
    "rows not a multiple of the tile": (40, 32, 16, [13, 0, 27], None),
    "more rows than a tile, not a multiple": (200, 32, 16, [70, 70, 50], None),
    "columns cut into blocks": (64, 32, 512, [30, 0, 34], 32 * 128 * 4),
    "kanana's experts, a sixteenth": (96, 128, 48, [6] * 12 + [0, 24, 0, 0], None),
    "trinity's experts, a sixteenth": (128, 128, 64, [0] * 5 + [8] * 9 + [0, 40], None),
    "mimo's experts, a sixteenth by an eighth": (128, 256, 256, [0, 9, 0, 3], 256 * 128 * 4),
}


@pytest.mark.parametrize("case", list(CASES))
def test_grouped_products_are_ragged_dots(case, monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax import lax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.ops import grouped_matmul as gm

    rows, K, N, sizes, block_bytes = CASES[case]
    if block_bytes:
        monkeypatch.setattr(gm, "_WEIGHT_BLOCK_BYTES", block_bytes)
        assert gm._column_tile(K, N, 1, 4) < N
    G, pairs = len(sizes), sum(sizes)
    sizes = jnp.asarray(sizes, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(rows + K), 4)
    # the buffer as ``expert_layer`` hands it: whole row tiles
    tm = gm.row_tile(rows)
    M = -(-rows // tm) * tm
    x = jax.random.normal(ks[0], (M, K), jnp.float32)
    gate, up = (jax.random.normal(k, (G, K, N), jnp.float32) / K ** 0.5 for k in ks[1:3])
    down = jax.random.normal(ks[3], (G, N, K), jnp.float32) / N ** 0.5

    grid = gm.visits(sizes, M, tm)
    # every group visits the tiles it has a row in, in the order of the rows
    starts = np.cumsum(sizes) - np.asarray(sizes)
    want = [(g, t) for g in range(G) if sizes[g]
            for t in range(starts[g] // tm, (starts[g] + int(sizes[g]) - 1) // tm + 1)]
    n = int(grid.count)
    # no pair at all is still one visit, which stores nothing
    want = want or [(G - 1, 0)]
    assert list(zip(np.asarray(grid.group)[:n], np.asarray(grid.tile)[:n])) == want
    assert grid.group.shape[0] == M // tm + G - 1 >= n

    with jax.default_matmul_precision("highest"):
        ragged = lambda a, w: lax.ragged_dot(a, w, sizes, preferred_element_type=jnp.float32)  # noqa: E731
        h = gm.gated(x, gate, up, grid)
        h_want = jax.nn.silu(ragged(x, gate)) * ragged(x, up)
        out = gm.product(h, down, grid)
        out_want = ragged(jnp.where(jnp.arange(M)[:, None] < pairs, h, 0.0), down)
        # what ``expert_layer`` calls: the three products on one grid, jitted
        whole = gm.swiglu(x, gate, up, down, sizes, tm=tm)
    np.testing.assert_array_equal(np.asarray(whole[:pairs]), np.asarray(out[:pairs]))
    assert h.dtype == x.dtype and out.dtype == jnp.float32
    # rows past the last pair are nobody's: the kernel writes none of them
    np.testing.assert_allclose(np.asarray(h[:pairs]), np.asarray(h_want[:pairs]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(out[:pairs]), np.asarray(out_want[:pairs]), atol=1e-5)


@pytest.mark.parametrize("rows,tile", [(1, 16), (16, 16), (96, 96), (100, 112), (128, 128),
                                       (768, 128), (12288, 128)])
def test_row_tile_is_the_mxu_height_or_a_small_buffer_whole(rows, tile):
    from ray_tpu.ops import grouped_matmul as gm

    assert gm.row_tile(rows) == tile


@pytest.mark.parametrize("K,N,matrices,tn", [
    (2048, 768, 2, 768),     # Kanana's gate and up: 6.3 MB, whole
    (768, 2048, 1, 2048),    # its down
    (2048, 1024, 2, 1024),   # Trinity's gate and up: 8 MiB, whole
    (4096, 2048, 2, 512),    # MiMo's: 33.6 MB, a quarter of the columns a block
    (2048, 4096, 1, 2048),   # its down: 16.8 MB, half
    (4096, 192, 2, 192),     # columns that halve to no whole lane group stay whole
])
def test_a_visits_weights_fit_the_block(K, N, matrices, tn):
    from ray_tpu.ops import grouped_matmul as gm

    assert gm._column_tile(K, N, matrices, 2) == tn
    assert N % tn == 0
