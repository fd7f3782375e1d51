"""``ops/gated_delta.py``: one step and a chunk's scan of the gated delta
rule, each against a Python loop over positions written out from the
equations in float64, and the carrying of state from call to call that the
serving engine leans on: a prompt in several calls, blocks that do and do not
divide the length, padding past ``length``, a row that starts anew at ``start
== 0``, a decode row nobody holds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gated_delta

HK, HV, DK, DV, TAPS = 2, 4, 8, 8, 4
CHANNELS = 2 * HK * DK + HV * DV


@pytest.fixture(scope="module")
def params():
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 4))
    return {"conv_w": 0.5 * jax.random.normal(next(keys), (TAPS, CHANNELS), jnp.float32),
            # decays of 0.9 to 0.999 a position at a gate logit of 0
            "A_log": jnp.log(jax.random.uniform(next(keys), (HV,), jnp.float32, 0.5, 4.0)),
            "dt_bias": -3.0 + jax.random.normal(next(keys), (HV,), jnp.float32)}


def by_hand(params, c, a, b, s=None, tail=None):
    """c [T, channels], a, b [T, Hv] from state ``s`` [Hv, Dk, Dv] and the
    last inputs ``tail`` [TAPS - 1, channels] (zeros where not given) -> (o
    [T, Hv, Dv], the state, the last inputs), position by position in numpy
    float64."""
    p = {name: np.asarray(v, np.float64) for name, v in params.items()}
    c, a, b = (np.asarray(x, np.float64) for x in (c, a, b))
    T = c.shape[0]
    tail = np.zeros((TAPS - 1, CHANNELS)) if tail is None else np.asarray(tail, np.float64)
    padded = np.concatenate([tail, c])
    s = np.zeros((HV, DK, DV)) if s is None else np.asarray(s, np.float64).copy()
    out = []
    for t in range(T):
        u = sum(p["conv_w"][j] * padded[t + j] for j in range(TAPS))
        u = u / (1.0 + np.exp(-u))
        q, k, v = np.split(u, [HK * DK, 2 * HK * DK])
        q, k = q.reshape(HK, DK), k.reshape(HK, DK)
        q = q / np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) * DK ** -0.5
        k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
        v = v.reshape(HV, DV)
        g = -np.exp(p["A_log"]) * np.log1p(np.exp(a[t] + p["dt_bias"]))
        beta = 1.0 / (1.0 + np.exp(-b[t]))
        o = np.zeros((HV, DV))
        for h in range(HV):
            kh, qh = k[h // (HV // HK)], q[h // (HV // HK)]
            s[h] = np.exp(g[h]) * s[h]
            d = beta[h] * (v[h] - s[h].T @ kh)
            s[h] = s[h] + np.outer(kh, d)
            o[h] = s[h].T @ qh
        out.append(o)
    return np.stack(out), s, padded[T:].reshape(-1)


def zero_state(rows):
    return (jnp.zeros((rows, HV, DK, DV), jnp.float32),
            jnp.zeros((rows, (TAPS - 1) * CHANNELS), jnp.float32))


def inputs(seed, *lead):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(*lead, CHANNELS)), jnp.float32),
            jnp.asarray(rng.normal(size=(*lead, HV)), jnp.float32),
            jnp.asarray(rng.normal(size=(*lead, HV)), jnp.float32))


def test_step_after_step_is_the_loop_written_out(params):
    c, a, b = inputs(1, 9)
    state = zero_state(1)
    got = []
    for t in range(9):
        o, state = gated_delta.step(params, c[t][None], a[t][None], b[t][None], state,
                                    jnp.array([True]))
        got.append(np.asarray(o[0]))
    want, s, tail = by_hand(params, c, a, b)
    np.testing.assert_allclose(np.stack(got), want, atol=2e-6)
    np.testing.assert_allclose(np.asarray(state[0][0]), s, atol=2e-6)
    np.testing.assert_allclose(np.asarray(state[1][0]), tail, atol=1e-6)


@pytest.mark.parametrize("block", [4, 5, 16, 64])
def test_a_chunk_is_the_steps_whatever_the_block(params, block):
    """13 positions in blocks of 4 and 5 (neither divides 13: the last block
    is padded), of 16 (one block, padded) and of 64 (cut to the width), from
    a state and last inputs that are not zero."""
    c, a, b = inputs(2, 2, 13)
    rng = np.random.default_rng(3)
    s0 = jnp.asarray(rng.normal(size=(2, HV, DK, DV)), jnp.float32)
    tail0 = jnp.asarray(rng.normal(size=(2, (TAPS - 1) * CHANNELS)), jnp.float32)
    o, (s, tail) = gated_delta.chunk_scan(
        params, c, a, b, (s0, tail0), jnp.array([7, 20]), jnp.array([13, 13]), block=block)
    for r in range(2):
        want, s_want, tail_want = by_hand(params, c[r], a[r], b[r], s0[r],
                                          np.asarray(tail0[r]).reshape(TAPS - 1, CHANNELS))
        np.testing.assert_allclose(np.asarray(o[r]), want, atol=1e-5)
        np.testing.assert_allclose(np.asarray(s[r]), s_want, atol=1e-5)
        np.testing.assert_allclose(np.asarray(tail[r]), tail_want, atol=1e-6)
    # and the steps themselves, from the same state
    state = (s0, tail0)
    for t in range(13):
        o_t, state = gated_delta.step(params, c[:, t], a[:, t], b[:, t], state,
                                      jnp.array([True, True]))
        np.testing.assert_allclose(np.asarray(o_t), np.asarray(o[:, t]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(state[0]), np.asarray(s), atol=1e-5)


def test_a_prompt_in_three_calls_is_the_prompt_in_one(params):
    """Chunks of 8, 8 and 5 of 21 positions, each padded to 8, against one
    call of 21 and against the loop: the state and the last inputs carry."""
    c, a, b = inputs(4, 1, 21)
    whole, (s_whole, tail_whole) = gated_delta.chunk_scan(
        params, c, a, b, zero_state(1), jnp.array([0]), jnp.array([21]), block=4)
    state = zero_state(1)
    parts = []
    for start in (0, 8, 16):
        n = min(8, 21 - start)
        pad = lambda x: jnp.pad(x[:, start:start + n], ((0, 0), (0, 8 - n), (0, 0)))  # noqa: E731
        o, state = gated_delta.chunk_scan(params, pad(c), pad(a), pad(b), state,
                                          jnp.array([start]), jnp.array([n]), block=4)
        parts.append(np.asarray(o[0, :n]))
    want, s, tail = by_hand(params, c[0], a[0], b[0])
    np.testing.assert_allclose(np.concatenate(parts), want, atol=1e-5)
    np.testing.assert_allclose(np.concatenate(parts), np.asarray(whole[0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(state[0][0]), s, atol=1e-5)
    np.testing.assert_allclose(np.asarray(state[0]), np.asarray(s_whole), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(state[1]), np.asarray(tail_whole))
    np.testing.assert_allclose(np.asarray(state[1][0]), tail, atol=1e-6)


def dirty_state(rows, seed=5):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(rows, HV, DK, DV)), jnp.float32),
            jnp.asarray(rng.normal(size=(rows, (TAPS - 1) * CHANNELS)), jnp.float32))


def bits(x):
    return np.asarray(x).view(np.uint32)


def test_padding_and_a_row_of_no_length_leave_the_state_to_the_bit(params):
    """Row 0 has 6 real positions of 16 and row 1 none: row 0's state is
    what 6 positions alone leave (blocks of 4: one whole block of padding
    behind a half-filled one), row 1's state and last inputs are the arrays
    it held, bit for bit, whatever its ``start``."""
    c, a, b = inputs(6, 2, 16)
    held = dirty_state(2)
    _, (s, tail) = gated_delta.chunk_scan(
        params, c, a, b, held, jnp.array([5, 0]), jnp.array([6, 0]), block=4)
    _, (s6, tail6) = gated_delta.chunk_scan(
        params, c[:1, :6], a[:1, :6], b[:1, :6], (held[0][:1], held[1][:1]),
        jnp.array([5]), jnp.array([6]), block=4)
    np.testing.assert_allclose(np.asarray(s[0]), np.asarray(s6[0]), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail[0]), np.asarray(tail6[0]))
    np.testing.assert_array_equal(bits(s[1]), bits(held[0][1]))
    np.testing.assert_array_equal(bits(tail[1]), bits(held[1][1]))


def test_a_decode_row_nobody_holds_keeps_its_state_to_the_bit(params):
    c, a, b = inputs(7, 3)
    held = dirty_state(3)
    o, (s, tail) = gated_delta.step(params, c, a, b, held, jnp.array([True, False, True]))
    np.testing.assert_array_equal(bits(s[1]), bits(held[0][1]))
    np.testing.assert_array_equal(bits(tail[1]), bits(held[1][1]))
    assert not np.array_equal(np.asarray(s[0]), np.asarray(held[0][0]))
    assert np.isfinite(np.asarray(o)).all()


def test_a_row_that_starts_at_zero_starts_from_nothing(params):
    c, a, b = inputs(8, 1, 10)
    clean, (s_clean, _) = gated_delta.chunk_scan(
        params, c, a, b, zero_state(1), jnp.array([0]), jnp.array([10]))
    dirty, (s_dirty, _) = gated_delta.chunk_scan(
        params, c, a, b, dirty_state(1), jnp.array([0]), jnp.array([10]))
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))
    np.testing.assert_array_equal(np.asarray(s_clean), np.asarray(s_dirty))


def test_the_state_comes_back_in_the_type_it_came_in(params):
    """The check's control hands the state over in bfloat16: the same
    programs run and write it back so."""
    c, a, b = inputs(9, 2, 8)
    s, tail = dirty_state(2)
    _, (s_chunk, _) = gated_delta.chunk_scan(
        params, c, a, b, (s.astype(jnp.bfloat16), tail), jnp.array([3, 3]), jnp.array([8, 8]))
    _, (s_step, _) = gated_delta.step(params, c[:, 0], a[:, 0], b[:, 0],
                                      (s.astype(jnp.bfloat16), tail), jnp.array([True, True]))
    assert s_chunk.dtype == s_step.dtype == jnp.bfloat16


def test_keys_that_lie_close_do_not_break_the_inverse(params):
    """Neighbouring positions with nearly the same key and beta near one:
    the entries of A are near one, where a sum of its powers would cancel."""
    rng = np.random.default_rng(10)
    base = rng.normal(size=(CHANNELS,))
    c = jnp.asarray(base + 0.05 * rng.normal(size=(1, 40, CHANNELS)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(1, 40, HV)), jnp.float32)
    b = jnp.full((1, 40, HV), 4.0, jnp.float32)
    o, (s, _) = gated_delta.chunk_scan(params, c, a, b, zero_state(1), jnp.array([0]),
                                       jnp.array([40]), block=64)
    want, s_want, _ = by_hand(params, c[0], a[0], b[0])
    np.testing.assert_allclose(np.asarray(o[0]), want, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s[0]), s_want, atol=1e-4)
