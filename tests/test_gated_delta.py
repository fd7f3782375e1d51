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


@pytest.mark.parametrize("block, T", [(4, 13), (5, 13), (16, 13), (64, 13), (32, 70), (48, 70)])
def test_a_chunk_is_the_steps_whatever_the_block(params, block, T):
    """13 positions in blocks of 4 and 5 (neither divides 13: the last block
    is padded), of 16 (one block, padded) and of 64 (cut to the width), and
    70 in blocks of 32 and 48, which the inverse solves in two and three
    sub-blocks and merges (48: a pair, then the pair with the third), from a
    state and last inputs that are not zero."""
    c, a, b = inputs(2, 2, T)
    rng = np.random.default_rng(3)
    s0 = jnp.asarray(rng.normal(size=(2, HV, DK, DV)), jnp.float32)
    tail0 = jnp.asarray(rng.normal(size=(2, (TAPS - 1) * CHANNELS)), jnp.float32)
    o, (s, tail) = gated_delta.chunk_scan(
        params, c, a, b, (s0, tail0), jnp.array([7, 20]), jnp.array([T, T]), block=block)
    for r in range(2):
        want, s_want, tail_want = by_hand(params, c[r], a[r], b[r], s0[r],
                                          np.asarray(tail0[r]).reshape(TAPS - 1, CHANNELS))
        np.testing.assert_allclose(np.asarray(o[r]), want, atol=1e-5)
        np.testing.assert_allclose(np.asarray(s[r]), s_want, atol=1e-5)
        np.testing.assert_allclose(np.asarray(tail[r]), tail_want, atol=1e-6)
    # and the steps themselves, from the same state
    state = (s0, tail0)
    step = jax.jit(gated_delta.step)
    for t in range(T):
        o_t, state = step(params, c[:, t], a[:, t], b[:, t], state, jnp.array([True, True]))
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


# the engine's call: 2 rows of 512 positions, 32 value heads of 128 x 128 over
# 16 key heads; the heads cut to what a CPU runs in seconds, the positions not
ENGINE_ROWS, ENGINE_WIDTH = 2, 512


def engine_shaped(hk=1, hv=2, dk=16, dv=16, seed=11):
    """(params, c, a, b, state) of a call of the engine's rows and width, the
    decays drawn as the configuration's seeded weights draw them."""
    rng = np.random.default_rng(seed)
    channels = 2 * hk * dk + hv * dv
    step = np.exp(rng.uniform(np.log(0.001), np.log(0.1), size=hv))
    p = {"conv_w": jnp.asarray(rng.normal(size=(TAPS, channels)) / np.sqrt(2), jnp.float32),
         "A_log": jnp.asarray(np.log(rng.uniform(0.5, 4.0, size=hv)), jnp.float32),
         "dt_bias": jnp.asarray(step + np.log(-np.expm1(-step)), jnp.float32)}
    lead = (ENGINE_ROWS, ENGINE_WIDTH)
    c, a, b = (jnp.asarray(rng.normal(size=(*lead, n)), jnp.float32) for n in (channels, hv, hv))
    state = (jnp.asarray(rng.normal(size=(ENGINE_ROWS, hv, dk, dv)), jnp.float32),
             jnp.asarray(rng.normal(size=(ENGINE_ROWS, (TAPS - 1) * channels)), jnp.float32))
    return p, c, a, b, state


def test_a_chunk_at_the_engines_shape_is_the_steps():
    """Eight blocks of 64 a row, each inverted in four sub-blocks and two
    merges, the second row's last block half padding: what the steps give
    from the same state, position by position."""
    p, c, a, b, state = engine_shaped()
    length = jnp.array([ENGINE_WIDTH, ENGINE_WIDTH - 30])
    o, (s, tail) = jax.jit(gated_delta.chunk_scan)(p, c, a, b, state, jnp.array([512, 64]), length)
    step = jax.jit(gated_delta.step)
    for t in range(ENGINE_WIDTH):
        live = t < length
        o_t, state = step(p, c[:, t], a[:, t], b[:, t], state, live)
        np.testing.assert_allclose(np.asarray(o_t)[np.asarray(live)],
                                   np.asarray(o[:, t])[np.asarray(live)], atol=2e-5)
    np.testing.assert_allclose(np.asarray(state[0]), np.asarray(s), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(state[1]), np.asarray(tail))


def blocks_of_keys(alike, blocks=64, C=64, dk=128, seed=12):
    """``A`` [blocks, C, C] of the chunked form in float64: keys that share
    ``alike`` of their squared length, decays of the configuration's range
    (``models/qwen3_next.init``'s draws at gate logits of size one), beta a
    sigmoid of the same."""
    rng = np.random.default_rng(seed)
    k = (np.sqrt(alike) * rng.normal(size=(blocks, 1, dk))
         + np.sqrt(1.0 - alike) * rng.normal(size=(blocks, C, dk)))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    step = np.exp(rng.uniform(np.log(0.001), np.log(0.1), size=(blocks, 1)))
    g = -rng.uniform(0.5, 4.0, size=(blocks, 1)) * np.log1p(
        np.exp(rng.normal(size=(blocks, C)) + step + np.log(-np.expm1(-step))))
    beta = 1.0 / (1.0 + np.exp(-rng.normal(size=(blocks, C))))
    G = np.cumsum(g, axis=-1)
    t, src = np.arange(C)[:, None], np.arange(C)[None, :]
    decay = np.exp(np.where(src <= t, G[:, :, None] - G[:, None, :], -np.inf))
    return np.where(src < t, beta[:, :, None] * decay * (k @ k.transpose(0, 2, 1)), 0.0)


def row_by_row(a):
    """(I + a)^-1 by forward substitution over all C rows in float32: the
    form the blocked inverse replaced, and the error it is held to."""
    t = -np.asarray(a, np.float32)
    for i in range(1, t.shape[-1]):
        t[:, i] += np.einsum("bj,bjc->bc", t[:, i], t)
    return t + np.eye(t.shape[-1], dtype=np.float32)


@pytest.mark.parametrize("alike", [0.0, 0.9, 0.99])
def test_the_blocked_inverse_is_as_near_the_float64_inverse_as_row_by_row(alike):
    """At the engine's block of 64, against ``numpy.linalg.inv`` of the same
    I + A in float64: the relative error of the worst of 64 blocks is no
    larger than twice what substitution over all 63 rows leaves, and tiny.
    Keys that are alike put A's entries near one, where a sum of its powers
    cancels; substitution and the block formula do not."""
    a = blocks_of_keys(alike).astype(np.float32)
    want = np.linalg.inv(np.eye(64) + a.astype(np.float64))

    def error(got):
        return np.max(np.linalg.norm(np.asarray(got, np.float64) - want, axis=(1, 2))
                      / np.linalg.norm(want, axis=(1, 2)))

    blocked, rows = error(jax.jit(gated_delta._inverse)(jnp.asarray(a))), error(row_by_row(a))
    assert blocked <= max(2.0 * rows, 1e-7), (blocked, rows)
    assert blocked < 1e-6, blocked


def loops(jaxpr):
    """(primitive, turns) of every loop in a jaxpr and the jaxprs inside it;
    a ``while`` has no count to read and comes back with None."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(("scan", eqn.params["length"]))
        elif eqn.primitive.name == "while":
            found.append(("while", None))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += loops(sub)
    return found


def test_the_engines_chunk_holds_no_loop_but_the_one_over_its_blocks():
    """The inverse was a loop of 63 turns a block, each over the whole of T,
    and the largest operation of the long-documents cell (PERF.md section 6,
    PR 64). At the engine's shape the traced program holds the scan over the
    eight blocks and no other loop of more than ``SUB`` turns, and no while
    at all, whose turns nothing here could count."""
    p, c, a, b, state = engine_shaped()
    traced = jax.make_jaxpr(gated_delta.chunk_scan)(
        p, c, a, b, state, jnp.array([0, 0]), jnp.array([ENGINE_WIDTH, ENGINE_WIDTH]))
    found = loops(traced.jaxpr)
    over_blocks = ("scan", ENGINE_WIDTH // gated_delta.BLOCK)
    assert found.count(over_blocks) == 1, found
    found.remove(over_blocks)
    assert all(kind == "scan" and turns <= gated_delta.SUB for kind, turns in found), found
