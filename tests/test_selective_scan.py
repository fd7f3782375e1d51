"""``ops/selective_scan.py``: one step and a chunk's scan of the Mamba-1
recurrence, each against a Python loop over positions written out from the
equations, and the carrying of state from call to call that the serving
engine leans on: a prompt in three calls, padding past ``length``, a row
that starts anew at ``start == 0``, a decode row nobody holds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import selective_scan

D_INNER, N, TAPS, RANK = 24, 4, 4, 3


@pytest.fixture(scope="module")
def params():
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    draw = lambda *shape: jax.random.normal(next(keys), shape, jnp.float32)  # noqa: E731
    return {"conv_w": 0.5 * draw(TAPS, D_INNER), "conv_b": 0.1 * draw(D_INNER),
            "x_proj": 0.3 * draw(D_INNER, RANK + 2 * N), "dt_proj": 0.5 * draw(RANK, D_INNER),
            "dt_bias": -2.0 + 0.5 * draw(D_INNER),
            "A_log": jnp.log(jnp.broadcast_to(jnp.arange(1.0, N + 1)[:, None], (N, D_INNER))),
            "D": 1.0 + 0.1 * draw(D_INNER)}


def by_hand(params, x):
    """x [T, d_inner] from a zero state -> (y [T, d_inner], s [N, d_inner],
    the last TAPS - 1 inputs), position by position in numpy float64."""
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    x = np.asarray(x, np.float64)
    T = x.shape[0]
    padded = np.concatenate([np.zeros((TAPS - 1, D_INNER)), x])
    a = -np.exp(p["A_log"])
    s = np.zeros((N, D_INNER))
    ys = []
    for t in range(T):
        u = p["conv_b"] + sum(p["conv_w"][k] * padded[t + k] for k in range(TAPS))
        u = u / (1.0 + np.exp(-u))
        dbc = u @ p["x_proj"]
        dt = np.log1p(np.exp(dbc[:RANK] @ p["dt_proj"] + p["dt_bias"]))
        b, c = dbc[RANK:RANK + N], dbc[RANK + N:]
        s = np.exp(dt[None, :] * a) * s + (dt * u)[None, :] * b[:, None]
        ys.append((s * c[:, None]).sum(0) + p["D"] * u)
    return np.stack(ys), s, padded[T:].reshape(-1)


def zero_state(rows):
    return (jnp.zeros((rows, N, D_INNER), jnp.float32),
            jnp.zeros((rows, (TAPS - 1) * D_INNER), jnp.float32))


def inputs(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), jnp.float32)


def test_step_after_step_is_the_loop_written_out(params):
    x = inputs(1, 9, D_INNER)
    want_y, want_s, want_conv = by_hand(params, x)
    state = zero_state(1)
    for t in range(9):
        y, state = selective_scan.step(params, x[t][None], state, jnp.asarray([True]))
        np.testing.assert_allclose(np.asarray(y[0]), want_y[t], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(state[0][0]), want_s, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(state[1][0]), want_conv, rtol=1e-6)


def test_a_chunk_is_the_loop_written_out_and_rows_do_not_mix(params):
    x = inputs(2, 2, 13, D_INNER)
    y, (s, conv) = selective_scan.chunk_scan(
        params, x, zero_state(2), jnp.asarray([0, 0]), jnp.asarray([13, 13]))
    for r in range(2):
        want_y, want_s, want_conv = by_hand(params, x[r])
        np.testing.assert_allclose(np.asarray(y[r]), want_y, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(s[r]), want_s, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(conv[r]), want_conv, rtol=1e-6)


def test_one_call_and_three_calls_that_carry_the_state_agree(params):
    """29 positions at once, and as 12 + 12 + 5 in calls 12 wide, the last
    padded: the chunks at ``start > 0`` begin from what the row holds."""
    x = inputs(3, 1, 29, D_INNER)
    whole_y, (whole_s, whole_conv) = selective_scan.chunk_scan(
        params, x, zero_state(1), jnp.asarray([0]), jnp.asarray([29]))
    state, ys = zero_state(1), []
    for start in (0, 12, 24):
        n = min(12, 29 - start)
        chunk = jnp.zeros((1, 12, D_INNER)).at[:, :n].set(x[:, start:start + n])
        y, state = selective_scan.chunk_scan(
            params, chunk, state, jnp.asarray([start]), jnp.asarray([n]))
        ys.append(y[:, :n])
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, axis=1)), np.asarray(whole_y),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(state[0]), np.asarray(whole_s), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(state[1]), np.asarray(whole_conv))


def test_padding_past_length_leaves_the_state_to_the_bit(params):
    """A row of 5 real positions in a call 16 wide holds what a call 5 wide
    leaves (to a product of another shape's rounding); a row of no length
    gets back exactly what it held."""
    x = inputs(4, 2, 16, D_INNER)
    held = (inputs(5, 2, N, D_INNER), inputs(6, 2, (TAPS - 1) * D_INNER))
    _, (s, conv) = selective_scan.chunk_scan(
        params, x, held, jnp.asarray([7, 7]), jnp.asarray([5, 0]))
    _, (s5, conv5) = selective_scan.chunk_scan(
        params, x[:1, :5], (held[0][:1], held[1][:1]), jnp.asarray([7]), jnp.asarray([5]))
    np.testing.assert_allclose(np.asarray(s[0]), np.asarray(s5[0]), rtol=2e-6, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(conv[0]), np.asarray(conv5[0]))
    np.testing.assert_array_equal(np.asarray(s[1]), np.asarray(held[0][1]))
    np.testing.assert_array_equal(np.asarray(conv[1]), np.asarray(held[1][1]))


def test_a_row_that_starts_at_zero_ignores_what_it_held(params):
    x = inputs(7, 1, 10, D_INNER)
    dirty = (inputs(8, 1, N, D_INNER), inputs(9, 1, (TAPS - 1) * D_INNER))
    start, length = jnp.asarray([0]), jnp.asarray([10])
    y, state = selective_scan.chunk_scan(params, x, dirty, start, length)
    clean_y, clean = selective_scan.chunk_scan(params, x, zero_state(1), start, length)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(clean_y))
    np.testing.assert_array_equal(np.asarray(state[0]), np.asarray(clean[0]))
    np.testing.assert_array_equal(np.asarray(state[1]), np.asarray(clean[1]))
    # and behind zero it does not: the same chunk at start 3 reads the state
    y3, _ = selective_scan.chunk_scan(params, x, dirty, jnp.asarray([3]), length)
    assert float(jnp.abs(y3 - clean_y).max()) > 1e-3


def test_a_decode_row_nobody_holds_keeps_its_state_and_inputs(params):
    held = (inputs(10, 3, N, D_INNER), inputs(11, 3, (TAPS - 1) * D_INNER))
    live = jnp.asarray([True, False, True])
    _, (s, conv) = selective_scan.step(params, inputs(12, 3, D_INNER), held, live)
    np.testing.assert_array_equal(np.asarray(s[1]), np.asarray(held[0][1]))
    np.testing.assert_array_equal(np.asarray(conv[1]), np.asarray(held[1][1]))
    assert float(jnp.abs(s[0] - held[0][0]).max()) > 1e-3
    assert float(jnp.abs(conv[2] - held[1][2]).max()) > 1e-3


def test_the_state_comes_back_in_the_type_it_came_in(params):
    """The engine keeps it in float32; the check's control in bfloat16."""
    s, conv = zero_state(2)
    for kept in (jnp.float32, jnp.bfloat16):
        _, (after, _) = selective_scan.step(
            params, inputs(13, 2, D_INNER), (s.astype(kept), conv), jnp.asarray([True, True]))
        assert after.dtype == kept
        _, (after, _) = selective_scan.chunk_scan(
            params, inputs(14, 2, 6, D_INNER), (s.astype(kept), conv),
            jnp.asarray([0, 0]), jnp.asarray([6, 6]))
        assert after.dtype == kept
