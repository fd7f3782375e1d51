"""The selective state-space recurrence of Mamba-1 (arXiv:2312.00752) for
the serving engine: one step for a decode row, and a scan over a prefill
chunk that starts from what the row's earlier chunks left
(``models/phi4flash.py``).

For an input ``x_t`` [d_inner] (the mixer's ``W_in`` product, before the
convolution), with ``d_state`` N and ``dt_rank`` K:

    u_t = silu(conv_causal_depthwise(x)_t + b_c)        (the last d_conv inputs)
    [dt | B | C] = W_x u_t                              (K + N + N)
    dt = softplus(W_dt dt + b_dt)                       [d_inner]
    s_t = exp(dt * A) * s_{t-1} + (dt * u_t) outer B    A = -exp(A_log)
    y_t = s_t . C + D * u_t                             [d_inner]

What a row carries from token to token is the state ``s`` and the
convolution's last ``d_conv - 1`` inputs. They are stored with d_inner
minor, ``s`` as ``[rows, N, d_inner]`` and the inputs as ``[rows, (d_conv -
1) * d_inner]`` (oldest first): N = 16 or d_conv - 1 = 3 in the minor
dimensions would be padded to whole tiles, eight and five times the bytes.
``A_log`` is stored ``[N, d_inner]`` for the same reason. The recurrence
runs in float32 whatever the state is stored in, and the state is written
back in the type it came in (float32 in the engine: it is multiplied into
itself thousands of times; the check's control carries it in bfloat16).

A position that is not real (a chunk's padding, a decode row nobody holds)
gets ``dt = 0``: ``exp(0 * A) = 1`` and ``0 * u outer B = 0``, so the state
it leaves is the state it met, to the bit.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

UNROLL = 8  # positions of a chunk the scan's body holds


def _projections(params: Dict[str, Any], u, real, n_state: int):
    """u [..., d_inner] (float32) -> dt [..., d_inner] (0 where not
    ``real``), B and C [..., N], float32; the products in the weights' type."""
    dt_rank = params["dt_proj"].shape[0]
    cdt = params["x_proj"].dtype
    dbc = jnp.dot(u.astype(cdt), params["x_proj"], preferred_element_type=jnp.float32)
    dt, b, c = jnp.split(dbc, [dt_rank, dt_rank + n_state], axis=-1)
    dt = jnp.dot(dt.astype(cdt), params["dt_proj"], preferred_element_type=jnp.float32)
    dt = jax.nn.softplus(dt + params["dt_bias"].astype(jnp.float32))
    return jnp.where(real[..., None], dt, 0.0), b, c


def _advance(a, d_skip, s, u, dt, b, c):
    """One position: s [R, N, d_inner], u and dt [R, d_inner], b and c [R,
    N] -> (y [R, d_inner], the state after it), float32."""
    s = jnp.exp(dt[:, None, :] * a) * s + (dt * u)[:, None, :] * b[:, :, None]
    return jnp.sum(s * c[:, :, None], axis=1) + d_skip * u, s


def _a_and_d(params):
    return (-jnp.exp(params["A_log"].astype(jnp.float32)),
            params["D"].astype(jnp.float32))


def step(params: Dict[str, Any], x, state: Tuple[jax.Array, jax.Array], live):
    """One token a row: ``x`` [S, d_inner], ``state`` (s [S, N, d_inner],
    conv [S, (d_conv - 1) * d_inner]) -> (y [S, d_inner] float32, the
    state after it). A row that is not ``live`` [S] keeps both arrays as
    they were."""
    s, conv = state
    n_state, d_inner = s.shape[1], s.shape[2]
    w = params["conv_w"].astype(jnp.float32)                     # [d_conv, d_inner]
    taps = w.shape[0]
    seen = jnp.concatenate([conv, x.astype(conv.dtype)], axis=1)  # oldest first
    u = params["conv_b"].astype(jnp.float32) + sum(
        w[k] * seen[:, k * d_inner:(k + 1) * d_inner].astype(jnp.float32)
        for k in range(taps))
    u = jax.nn.silu(u)
    dt, b, c = _projections(params, u, live, n_state)
    y, s_new = _advance(*_a_and_d(params), s.astype(jnp.float32), u, dt, b, c)
    conv_new = jnp.where(live[:, None], seen[:, d_inner:], conv)
    return y, (s_new.astype(s.dtype), conv_new)


def chunk_scan(params: Dict[str, Any], x, state: Tuple[jax.Array, jax.Array],
               start, length):
    """A prefill call's rows: ``x`` [R, P, d_inner] are positions ``start``
    .. ``start + P - 1`` of each row, ``length`` [R] of them real (the call
    is padded to its width), ``state`` what the rows held. A row whose
    ``start`` is 0 starts from zeros whatever it held; positions at or past
    ``length`` leave the state untouched, so a row of no length gets back
    what it held. Returns (y [R, P, d_inner] float32, the state after the
    last real position).

    The convolution, the projections and the softplus run over the chunk
    in parallel; the recurrence is a ``lax.scan`` over positions, the state
    [R, N, d_inner] its carry, so the states of all positions ([R, P, N,
    d_inner]: 335 MB for two rows of 512) never exist at once."""
    s, conv = state
    R, P, d_inner = x.shape
    n_state = s.shape[1]
    fresh = (start == 0) & (length > 0)
    s0 = jnp.where(fresh[:, None, None], 0.0, s.astype(jnp.float32))
    conv0 = jnp.where(fresh[:, None], jnp.zeros_like(conv), conv)
    w = params["conv_w"].astype(jnp.float32)
    taps = w.shape[0]
    seen = jnp.concatenate(
        [conv0.reshape(R, taps - 1, d_inner), x.astype(conv.dtype)], axis=1)
    u = params["conv_b"].astype(jnp.float32) + sum(
        w[k] * seen[:, k:k + P].astype(jnp.float32) for k in range(taps))
    u = jax.nn.silu(u)
    real = jnp.arange(P) < length[:, None]
    dt, b, c = _projections(params, u, real, n_state)
    a, d_skip = _a_and_d(params)

    def body(s, at):
        u_t, dt_t, b_t, c_t = at
        y_t, s = _advance(a, d_skip, s, u_t, dt_t, b_t, c_t)
        return s, y_t

    s_new, y = lax.scan(body, s0, tuple(jnp.swapaxes(v, 0, 1) for v in (u, dt, b, c)),
                        unroll=UNROLL)
    # the last d_conv - 1 real inputs: positions length - 3 .. length - 1 of
    # the chunk are entries length .. length + 2 of ``seen``
    last = length[:, None] + jnp.arange(taps - 1)
    conv_new = jnp.take_along_axis(seen, last[:, :, None], axis=1).reshape(R, -1)
    return jnp.swapaxes(y, 0, 1), (s_new.astype(s.dtype), conv_new)
