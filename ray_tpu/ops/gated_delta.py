"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) for the serving
engine: one step for a decode row, and a chunked scan over a prefill call
that starts from what the row's earlier chunks left
(``models/qwen3_next.py``). The sibling of ``ops/selective_scan.py``, whose
recurrence keeps a diagonal state a channel; this one keeps a matrix a head.

For a position's mixed input ``c_t`` [2 Hk Dk + Hv Dv] (the mixer's q, k and
v channels side by side, before the convolution) and its two gate logits a
value head, ``a_t`` and ``b_t`` [Hv]:

    [q | k | v] = silu(conv_causal_depthwise(c)_t)      (the last d_conv inputs, no bias)
    q, k = l2norm(q), l2norm(k) over Dk;  q *= Dk^-0.5  (key head j serves value
                                                         heads j Hv/Hk .. (j + 1) Hv/Hk - 1)
    g = -exp(A_log) * softplus(a_t + dt_bias)           [Hv], log of the decay
    beta = sigmoid(b_t)                                 [Hv]
    S <- exp(g) S;  r = S' k;  d = beta (v - r);  S <- S + k d';  o_t = S' q

with ``S`` [Dk, Dv] a value head. What a row carries from token to token is
``S`` ([rows, Hv, Dk, Dv]) and the convolution's last ``d_conv - 1`` inputs
([rows, (d_conv - 1) * channels], oldest first). Everything behind the
convolution runs in float32 whatever the state is stored in, every product
of the recurrence at the highest precision (on a TPU a float32 product is
otherwise rounded to bfloat16 on its way into the multiplier), and the state
is written back in the type it came in: float32 in the engine, because it is
multiplied into itself at every position; the check's control carries it in
bfloat16.

``chunk_scan`` is the chunked form of the paper (section 3.3), blocks of
``BLOCK`` positions. With ``G_t`` the sum of ``g`` from the block's first
position to t and ``S_0`` the state the block met:

    A[t, s] = beta_t exp(G_t - G_s) (k_t . k_s), s < t           (strictly lower)
    T = (I + A)^-1                                               (block substitution)
    U = T (beta v),  W = T (beta exp(G) k)                       (all blocks at once)
    D = U - W S_0                                                (the d of every position)
    O = exp(G) (Q S_0) + (exp(G_t - G_s) (q_t . k_s), s <= t) D
    S_C = exp(G_C) S_0 + (exp(G_C - G) k)' D

Everything down to U and W is computed for all blocks of a call in
parallel; ``lax.scan`` carries the state from block to block. The inverse is
block forward substitution. The diagonal sub-blocks of ``SUB`` positions are
inverted row by row (row i of a sub-block's inverse needs its rows 0 .. i -
1), all sub-blocks of all blocks at once with the batch in the lanes: 15
turns over 2 MB at the engine's shape. Neighbouring diagonal blocks are then
merged upward, 16 to 32 to 64, by the exact formula for a lower triangular
matrix, ``[[X, 0], [-Y A21 X, Y]]`` with X and Y the inverses of the two
halves. It is never a product of powers of A (``(I - A)(I + A^2)(I + A^4)
..``): the keys of neighbouring positions are alike, A's entries are not
small, and its powers cancel catastrophically before they vanish.

A position that is not real (a chunk's padding, a decode row nobody holds)
gets ``g = 0`` and ``beta = 0``: ``exp(0) S + k 0' = S``, and both functions
also hand such a row, and a block without a real position, the state it met
and not the sum, so what it leaves is what it met to the bit.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

BLOCK = 64     # positions of a block of the chunked form
SUB = 16       # of a diagonal sub-block of it, inverted by substitution
EPS = 1e-6     # of the l2 norms of q and k
_HI = lax.Precision.HIGHEST


def _gates(params: Dict[str, Any], a, b, real):
    """a, b [..., Hv] -> (g, beta) float32, both 0 where not ``real``."""
    g = -jnp.exp(params["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    return jnp.where(real[..., None], g, 0.0), jnp.where(real[..., None], beta, 0.0)


def _heads(u, value_heads: int, dk: int, dv: int):
    """The convolution's output u [..., 2 Hk Dk + Hv Dv] (float32) -> q, k
    [..., Hv, Dk], normed, q scaled, each key head repeated for the value
    heads it serves, and v [..., Hv, Dv]."""
    key_heads = (u.shape[-1] - value_heads * dv) // (2 * dk)
    q, k, v = jnp.split(u, [key_heads * dk, 2 * key_heads * dk], axis=-1)

    def normed(x):
        x = x.reshape(*x.shape[:-1], key_heads, dk)
        x = x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + EPS)
        return jnp.repeat(x, value_heads // key_heads, axis=-2)

    return (normed(q) * dk ** -0.5, normed(k),
            v.reshape(*v.shape[:-1], value_heads, dv))


def step(params: Dict[str, Any], c, a, b, state: Tuple[jax.Array, jax.Array], live):
    """One token a row: ``c`` [S, channels], ``a`` and ``b`` [S, Hv],
    ``state`` (s [S, Hv, Dk, Dv], conv [S, (d_conv - 1) * channels]) -> (o
    [S, Hv, Dv] float32, the state after it). A row that is not ``live`` [S]
    keeps both arrays as they were."""
    s, conv = state
    _, hv, dk, dv = s.shape
    channels = c.shape[-1]
    w = params["conv_w"].astype(jnp.float32)                     # [d_conv, channels]
    taps = w.shape[0]
    seen = jnp.concatenate([conv, c.astype(conv.dtype)], axis=1)  # oldest first
    u = jax.nn.silu(sum(
        w[j] * seen[:, j * channels:(j + 1) * channels].astype(jnp.float32)
        for j in range(taps)))
    q, k, v = _heads(u, hv, dk, dv)
    g, beta = _gates(params, a, b, live)
    s32 = s.astype(jnp.float32) * jnp.exp(g)[:, :, None, None]
    r = jnp.einsum("rhkv,rhk->rhv", s32, k, precision=_HI)
    d = beta[:, :, None] * (v - r)
    s32 = s32 + k[:, :, :, None] * d[:, :, None, :]
    o = jnp.einsum("rhkv,rhk->rhv", s32, q, precision=_HI)
    s_new = jnp.where(live[:, None, None, None], s32.astype(s.dtype), s)
    conv_new = jnp.where(live[:, None], seen[:, channels:], conv)
    return o, (s_new, conv_new)


def _substituted(d):
    """The strictly lower part of (I + d)^-1 for ``d`` [w, w, B], strictly
    lower triangular in its first two axes: row i is -d_i - sum over j < i of
    d_ij row_j (d_i is zero from column i on, so the sum may run over every
    j). A turn is an elementwise pass over [w, w, B] with the batch in the
    lanes, where a row of w alone would fill an eighth of them. The w - 1
    turns are a loop and not written out: written out they ran no faster on
    the chip, and the long-documents cell's set-up took 13% longer with
    every program in the compile cache, the six prefill programs being
    slower to trace, lower and load (PERF.md section 6, PR 64)."""

    def row(i, t):
        r = lax.dynamic_index_in_dim(t, i, axis=0, keepdims=False)
        r = r + jnp.sum(r[:, None] * t, axis=0)
        return lax.dynamic_update_index_in_dim(t, r, i, axis=0)

    return lax.fori_loop(1, d.shape[0], row, -d)


def _inverse(a):
    """(I + a)^-1 for ``a`` [..., C, C] strictly lower triangular, by block
    forward substitution: the diagonal sub-blocks of ``SUB`` by rows
    (``_substituted``), then neighbouring diagonal blocks of width w merged
    into one of 2 w, [[X, 0], [-Y a21 X, Y]], until one is left. Every pair
    of every block is merged in the same two products over the whole width:
    a21 is ``a`` with all but the pairs' lower left quarters zeroed, and the
    inverse so far is zero off its diagonal blocks, so the zeros multiply to
    exact zeros. A width that ``SUB`` does not divide is one sub-block."""
    C = a.shape[-1]
    w = SUB if C % SUB == 0 else C
    starts = range(0, C, w)
    d = jnp.stack([a[..., p:p + w, p:p + w] for p in starts])   # [C / w, ..., w, w]
    low = _substituted(jnp.moveaxis(d.reshape(-1, w, w), 0, -1))
    low = jnp.moveaxis(low, -1, 0).reshape(d.shape)
    lead = [(0, 0)] * (a.ndim - 1)
    t = jnp.eye(C, dtype=a.dtype) + jnp.concatenate(
        [jnp.pad(x, lead + [(p, C - w - p)]) for p, x in zip(starts, low)], axis=-2)
    row, col = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    while w < C:
        a21 = jnp.where((row // (2 * w) == col // (2 * w)) & (row // w != col // w), a, 0.0)
        t = t - jnp.einsum("...ij,...jk->...ik", t,
                           jnp.einsum("...ij,...jk->...ik", a21, t, precision=_HI),
                           precision=_HI)
        w *= 2
    return t


def chunk_scan(params: Dict[str, Any], c, a, b, state: Tuple[jax.Array, jax.Array],
               start, length, block: int = BLOCK):
    """A prefill call's rows: ``c`` [R, P, channels], ``a`` and ``b`` [R, P,
    Hv] are positions ``start`` .. ``start + P - 1`` of each row, ``length``
    [R] of them real (the call is padded to its width), ``state`` what the
    rows held. A row whose ``start`` is 0 starts from zeros whatever it
    held; positions at or past ``length`` leave the state untouched, and a
    row of no length gets back what it held. Returns (o [R, P, Hv, Dv]
    float32, the state after the last real position).

    ``block`` positions are solved at once (P is padded to a multiple of
    it); the result does not depend on it but for rounding."""
    s, conv = state
    R, P, channels = c.shape
    _, hv, dk, dv = s.shape
    fresh = (start == 0) & (length > 0)
    s0 = jnp.where(fresh[:, None, None, None], 0.0, s.astype(jnp.float32))
    conv0 = jnp.where(fresh[:, None], jnp.zeros_like(conv), conv)
    w = params["conv_w"].astype(jnp.float32)
    taps = w.shape[0]
    seen = jnp.concatenate(
        [conv0.reshape(R, taps - 1, channels), c.astype(conv.dtype)], axis=1)
    u = jax.nn.silu(sum(w[j] * seen[:, j:j + P].astype(jnp.float32) for j in range(taps)))
    real = jnp.arange(P) < length[:, None]
    q, k, v = _heads(u, hv, dk, dv)                               # [R, P, Hv, .]
    g, beta = _gates(params, a, b, real)                          # [R, P, Hv]

    C = min(block, P)
    pad = -P % C
    n = (P + pad) // C

    def blocks(x):  # [R, P, Hv, ...] -> [n, R, Hv, C, ...]
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(R, n, C, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g, beta = (blocks(x) for x in (q, k, v, g, beta))
    any_real = blocks(real[:, :, None]).any(axis=-1)              # [n, R, 1]
    G = jnp.cumsum(g, axis=-1)                                    # [n, R, Hv, C]
    t, src = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    # exp(G_t - G_s) where s <= t: the exponent is never positive there, and
    # never looked at elsewhere
    decay = jnp.exp(jnp.where(src <= t, G[..., :, None] - G[..., None, :], -jnp.inf))
    kk = jnp.einsum("...tk,...sk->...ts", k, k, precision=_HI)
    inv = _inverse(jnp.where(src < t, beta[..., :, None] * decay * kk, 0.0))
    U = jnp.einsum("...ts,...sv->...tv", inv, beta[..., None] * v, precision=_HI)
    W = jnp.einsum("...ts,...sk->...tk", inv, (beta * jnp.exp(G))[..., None] * k,
                   precision=_HI)
    qk = decay * jnp.einsum("...tk,...sk->...ts", q, k, precision=_HI)
    q_in = jnp.exp(G)[..., None] * q                              # meets S_0
    k_out = jnp.exp(G[..., -1:] - G)[..., None] * k               # reaches S_C
    last = jnp.exp(G[..., -1])[..., None, None]                   # [n, R, Hv, 1, 1]

    def body(s, at):
        U_n, W_n, qk_n, q_n, k_n, last_n, real_n = at
        d = U_n - jnp.einsum("rhtk,rhkv->rhtv", W_n, s, precision=_HI)
        o = (jnp.einsum("rhtk,rhkv->rhtv", q_n, s, precision=_HI)
             + jnp.einsum("rhts,rhsv->rhtv", qk_n, d, precision=_HI))
        s_next = last_n * s + jnp.einsum("rhtk,rhtv->rhkv", k_n, d, precision=_HI)
        return jnp.where(real_n[:, :, None, None], s_next, s), o

    s_end, o = lax.scan(body, s0, (U, W, qk, q_in, k_out, last, any_real))
    o = jnp.moveaxis(o, 0, 1)                                     # [R, n, Hv, C, Dv]
    o = jnp.moveaxis(o, 2, 3).reshape(R, n * C, hv, dv)[:, :P]
    # the last d_conv - 1 real inputs: positions length - 3 .. length - 1 of
    # the chunk are entries length .. length + 2 of ``seen``
    tail = length[:, None] + jnp.arange(taps - 1)
    conv_new = jnp.take_along_axis(seen, tail[:, :, None], axis=1).reshape(R, -1)
    held = length > 0
    s_new = jnp.where(held[:, None, None, None], s_end.astype(s.dtype), s)
    return o, (s_new, jnp.where(held[:, None], conv_new, conv))
