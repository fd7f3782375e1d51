"""Flash attention — K-blocked online-softmax Pallas TPU kernel, custom VJP.

The hot op of the transformer stack (no reference equivalent: the
reference delegates attention math to torch/vLLM; SURVEY.md §2.4). True
flash algorithm (Dao et al.), shaped for the TPU memory hierarchy
(pallas_guide.md):

  - grid (B, H/hb, T/bq, T/bk) with the K dimension innermost ("arbitrary"
    semantics): running max / normalizer / output accumulator live in VMEM
    scratch across K blocks — only [bq, bk] score tiles ever exist, so
    sequence length is bounded by HBM, not VMEM (the round-1 kernel held
    the full [bq, T] score row and one-shot softmaxed it).
  - causal block skipping: (iq, ik) tiles strictly above the diagonal are
    skipped entirely — for causal attention this halves both MXU and VPU
    work, which matters because at moderate T the kernel is VPU-bound
    (exp/mask/select passes), not MXU-bound.
  - fp32 accumulation for scores/normalizers; bf16 into the MXU for the
    p@v and ds@k products.
  - backward: dq kernel accumulates over K blocks, dk/dv kernel over Q
    blocks, each recomputing only its own [bq, bk] score tile from q, k
    and the saved lse (no full-T recompute as in round 1).

Layout: q, k, v, the output and every gradient stay in the model's own
[B, T, H, Dh], which the kernels see as [B, T, H*Dh] (a reshape of the
minor dimensions, no data moves). A block takes `hb` whole heads of the
last dimension (_heads_per_block: two at Dh 64, so a block is one
128-lane tile) and the kernel takes each head out in VMEM by zeroing the
other heads' lanes of the tile (_tiles), so nothing is transposed around
a call. lse/delta ride an 8-row sublane layout ([B*H, 8, T], row b*H + h,
~12MB at gpt2-small scale) to keep stores tile-legal; delta = rowsum(dO*O)
comes from a small kernel of its own over the same blocks, since a sum
over Dh in XLA relays the float32 product positions-minor first.

Context parallelism composes on top: ops/ring_attention.py rotates K/V
shards around the mesh and calls the block kernel per shard.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    """Interpret mode, only where the platform is EXPLICITLY the CPU
    (tests and cpu workers run under JAX_PLATFORMS=cpu). CPU has no
    Mosaic backend, so the same code stays testable on the virtual host
    mesh; but a process that merely ended up on the CPU — no chip found,
    backend fell back — must not run the interpreter under a TPU run's
    name."""
    if jax.default_backend() != "cpu":
        return False
    if jax.config.jax_platforms != "cpu":
        raise RuntimeError(
            "flash attention found no TPU backend "
            f"(jax_platforms={jax.config.jax_platforms!r}); the Pallas "
            "interpreter runs only under an explicit JAX_PLATFORMS=cpu"
        )
    return True


_NEG_INF = -1e30
_LANES = 128
_BLOCK_ELEMS = 256 * 1024


def _visible(iq, ik, bq, bk, causal: bool):
    """Does K block ik contribute anything to Q block iq?"""
    if not causal:
        return True
    return ik * bk <= (iq + 1) * bq - 1


def _mask_tile(s, iq, ik, bq, bk, causal: bool):
    """Apply the causal mask to a [bq, bk] score tile (diagonal tiles only)."""
    if not causal:
        return s
    # Strictly-below-diagonal tiles need no mask; the compare/select pair
    # only runs for tiles overlapping the diagonal.
    row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ik * bk
    fully_visible = (ik + 1) * bk <= iq * bq + 1
    return jnp.where(
        jnp.logical_or(fully_visible, col <= row), s, _NEG_INF
    )


def _tiles(width: int, n_heads: int):
    """How a kernel takes single heads out of a [rows, width] block of
    n_heads heads: [(lanes, [(h, mask), ..]), ..]. `lanes` is the 128-lane
    tile (or run of tiles) that holds the heads listed with it, a slice
    that starts and ends on tile boundaries, so reading or writing it moves
    no lanes; `mask` [1, lanes] is true on head h's own lanes, None where
    the head has the tiles to itself (Dh a multiple of 128, or the odd last
    head of a whole row). A product over the masked tile contracts 128
    lanes of which the other heads' are zero: the MXU takes the pass it
    needs for 64 either way (measured against slicing 64 lanes out, which
    shifts lanes: PERF.md §6, PR 45)."""
    d = width // n_heads
    tiles = []  # [lo, hi, heads]: a head whose tiles overlap the last joins it
    for h in range(n_heads):
        lo = h * d // _LANES * _LANES
        hi = min(width, -(-(h + 1) * d // _LANES) * _LANES)
        if tiles and lo < tiles[-1][1]:
            tiles[-1][1] = hi
            tiles[-1][2].append(h)
        else:
            tiles.append([lo, hi, [h]])
    out = []
    for lo, hi, heads in tiles:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, hi - lo), 1) + lo
        out.append((slice(lo, hi), [
            (h, None if (lo, hi) == (h * d, (h + 1) * d) else
             jnp.logical_and(lane >= h * d, lane < (h + 1) * d))
            for h in heads
        ]))
    return out


def _only(x, mask):
    """x with every lane outside the head's own set to zero."""
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _scores(q, k, scale, iq, ik, block_q, block_k, causal):
    """Masked, scaled [bq, bk] f32 score tile of one head: one of q, k has
    the other heads' lanes zeroed."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    return _mask_tile(s, iq, ik, block_q, block_k, causal)


def _rows_of(x, shape):
    """A per-row [bq] vector on the 8-row sublane layout of lse/delta."""
    return jnp.broadcast_to(x[None, :], shape)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, heads, block_q, block_k, causal,
                single_k: bool):
    iq, ik = pl.program_id(2), pl.program_id(3)
    n_k = pl.num_programs(3)
    width = q_ref.shape[-1]
    scale = 1.0 / ((width // heads) ** 0.5)
    tiles = _tiles(width, heads)

    if single_k:
        # One K block covers the whole sequence: one-shot softmax, no
        # scratch carry — saves the init/rescale VPU passes that dominate
        # at moderate T.
        for lanes, in_tile in tiles:
            q, k, v = q_ref[:, lanes], k_ref[:, lanes], v_ref[:, lanes]
            out = None
            for h, mine in in_tile:
                s = _scores(_only(q, mine), k, scale, iq, ik, block_q,
                            block_k, causal)
                m = jnp.max(s, axis=1, keepdims=True)      # [bq, 1]
                p = jnp.exp(s - m)
                l = jnp.sum(p, axis=1, keepdims=True)      # [bq, 1]
                o = jax.lax.dot_general(
                    p.astype(v.dtype), _only(v, mine), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) / l                                      # zero off h's lanes
                out = o if out is None else out + o
                lse_ref[h] = _rows_of((m + jnp.log(l))[:, 0], lse_ref.shape[1:])
            o_ref[:, lanes] = out.astype(o_ref.dtype)
        return

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_visible(iq, ik, block_q, block_k, causal))
    def _compute():
        for lanes, in_tile in tiles:
            q, k, v = q_ref[:, lanes], k_ref[:, lanes], v_ref[:, lanes]
            for h, mine in in_tile:
                s = _scores(_only(q, mine), k, scale, iq, ik, block_q,
                            block_k, causal)
                m_prev = m_ref[h]                          # [bq, LANES] replicated
                l_prev = l_ref[h]
                m_cur = jnp.max(s, axis=1, keepdims=True)  # [bq, 1]
                m_next = jnp.maximum(m_prev, m_cur)        # [bq, LANES]
                alpha = jnp.exp(m_prev - m_next)           # [bq, LANES]
                p = jnp.exp(s - m_next[:, :1])             # [bq, bk]
                l_ref[h] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
                m_ref[h] = m_next
                pv = jax.lax.dot_general(
                    p.astype(v.dtype), _only(v, mine), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [bq, lanes], zero off h's lanes
                acc = acc_ref[:, lanes]
                scaled = acc * alpha[:, :1]
                if mine is not None:
                    scaled = jnp.where(mine, scaled, acc)
                acc_ref[:, lanes] = scaled + pv

    @pl.when(ik == n_k - 1)
    def _finalize():
        for lanes, in_tile in tiles:
            out = None
            for h, mine in in_tile:
                l = l_ref[h][:, :1]  # [bq, 1]
                o = _only(acc_ref[:, lanes] / l, mine)
                out = o if out is None else out + o
                lse = m_ref[h][:, 0] + jnp.log(l[:, 0])  # [bq]
                lse_ref[h] = _rows_of(lse, lse_ref.shape[1:])
            o_ref[:, lanes] = out.astype(o_ref.dtype)


def _delta_kernel(do_ref, o_ref, delta_ref, *, heads):
    """delta = rowsum(dO * O) over each head's lanes, in f32, written on
    the 8-row sublane layout the backward kernels read beside lse."""
    for lanes, in_tile in _tiles(do_ref.shape[-1], heads):
        prod = (do_ref[:, lanes].astype(jnp.float32)
                * o_ref[:, lanes].astype(jnp.float32))
        for h, mine in in_tile:
            delta = jnp.sum(_only(prod, mine), axis=1)     # [bq]
            delta_ref[h] = _rows_of(delta, delta_ref.shape[1:])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, heads, block_q, block_k, causal):
    iq, ik = pl.program_id(2), pl.program_id(3)
    n_k = pl.num_programs(3)
    width = q_ref.shape[-1]
    scale = 1.0 / ((width // heads) ** 0.5)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(_visible(iq, ik, block_q, block_k, causal))
    def _compute():
        for lanes, in_tile in _tiles(width, heads):
            q, k = q_ref[:, lanes], k_ref[:, lanes]
            v, do = v_ref[:, lanes], do_ref[:, lanes]
            for h, mine in in_tile:
                k_h = _only(k, mine)
                s = _scores(q, k_h, scale, iq, ik, block_q, block_k, causal)
                p = jnp.exp(s - lse_ref[h, 0][:, None])    # [bq, bk]
                dp = jax.lax.dot_general(
                    do, _only(v, mine), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [bq, bk]
                ds = (p * (dp - delta_ref[h, 0][:, None]) * scale).astype(k.dtype)
                dq_acc[:, lanes] += jax.lax.dot_general(
                    ds, k_h, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # zero off h's lanes

    @pl.when(ik == n_k - 1)
    def _finalize():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, heads, block_q, block_k,
                causal):
    ik, iq = pl.program_id(2), pl.program_id(3)
    n_q = pl.num_programs(3)
    width = q_ref.shape[-1]
    scale = 1.0 / ((width // heads) ** 0.5)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_visible(iq, ik, block_q, block_k, causal))
    def _compute():
        for lanes, in_tile in _tiles(width, heads):
            q, k = q_ref[:, lanes], k_ref[:, lanes]
            v, do = v_ref[:, lanes], do_ref[:, lanes]
            for h, mine in in_tile:
                q_h, do_h = _only(q, mine), _only(do, mine)
                s = _scores(q_h, k, scale, iq, ik, block_q, block_k, causal)
                p = jnp.exp(s - lse_ref[h, 0][:, None])    # [bq, bk]
                dv_acc[:, lanes] += jax.lax.dot_general(
                    p.astype(do.dtype), do_h, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [bk, lanes], zero off h's lanes
                dp = jax.lax.dot_general(
                    do_h, v, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [bq, bk]
                ds = (p * (dp - delta_ref[h, 0][:, None]) * scale).astype(q.dtype)
                dk_acc[:, lanes] += jax.lax.dot_general(
                    ds, q_h, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [bk, lanes]

    @pl.when(iq == n_q - 1)
    def _finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _pick_block(t: int, target: int) -> int:
    for b in (target, 1024, 512, 256, 128, 64, 32, 16, 8):
        if b <= target and t % b == 0:
            return min(b, t)
    return t


def _block_sizes(T: int, width: int):
    """(bq, bk) for sequence length T and blocks `width` lanes wide.
    1024x1024 for the train step: the [bq, bk] f32 score tile is 4MB of
    VMEM, large q tiles amortize the [bq, Dh]-contraction's half-width MXU occupancy (Dh=64), and at
    T<=1024 the kernel runs the one-shot softmax path (single K block, no
    online-softmax carries). VMEM stays bounded for long sequences (T=128k
    runs at the same tile size). RT_FLASH_BQ/BK (dynamic flags) override
    per process for sweeps. A block holds at most _BLOCK_ELEMS elements of
    an operand, which only blocks wider than 256 lanes reach (the whole
    rows of _heads_per_block: 25 heads of 64 get 128 rows), so that they
    too fit the 16MB of VMEM a kernel is given."""
    from ray_tpu.utils.config import config

    cap = max(8, _BLOCK_ELEMS // width)
    return (
        _pick_block(T, min(int(config.flash_bq), cap)),
        _pick_block(T, min(int(config.flash_bk), cap)),
    )


def _heads_per_block(H: int, D: int) -> int:
    """Heads to one block of the [B, T, H*D] view, from the shape alone:
    the fewest whole heads that fill whole 128-lane tiles and divide H —
    128 // D heads where D divides 128 (two at D 64), one head where D is
    a multiple of 128. Where no such number divides H (gpt2-xl's 25 heads
    of 64, any odd head count at D 64) the block is the whole row of H
    heads, which is legal at any width because it is the array's own; the
    kernel then walks H heads a grid step, on the smaller blocks that
    _block_sizes gives a row that wide."""
    for hb in range(1, H):
        if H % hb == 0 and (hb * D) % _LANES == 0:
            return hb
    return H


def _rows(x):  # [B, T, H, D] -> [B, T, H*D], minor dimensions only
    B, T, H, D = x.shape
    return x.reshape(B, T, H * D)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    )


class _Blocking(NamedTuple):
    """How H heads of D over Tq x Tk are cut for grid (B, H/heads, ., .)."""

    heads: int      # heads to a block
    groups: int     # H / heads, the grid's second axis
    bq: int
    bk: int
    q: Any          # BlockSpec of a q-side [bq, heads*D] block
    k: Any          # BlockSpec of a k-side [bk, heads*D] block
    rows: Any       # BlockSpec of the q rows' [heads, 8, bq] lse/delta block


def _blocking(H, D, Tq, Tk, q_major: bool = True) -> _Blocking:
    """The last two grid axes are (iq, ik) when q_major, else (ik, iq)."""
    hb = _heads_per_block(H, D)
    n_hb = H // hb
    bq, _ = _block_sizes(Tq, hb * D)
    _, bk = _block_sizes(Tk, hb * D)

    def at(f):
        return f if q_major else (lambda b, g, j, i: f(b, g, i, j))

    return _Blocking(
        hb, n_hb, bq, bk,
        q=pl.BlockSpec((None, bq, hb * D), at(lambda b, g, i, j: (b, i, g))),
        k=pl.BlockSpec((None, bk, hb * D), at(lambda b, g, i, j: (b, j, g))),
        rows=pl.BlockSpec((hb, 8, bq), at(lambda b, g, i, j: (b * n_hb + g, 0, i))),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention(q, k, v, causal: bool = True):
    out, _ = _flash_fwd(q, k, v, causal)
    return out


def _flash_fwd(q, k, v, causal, out_dtype=None):
    B, T, H, D = q.shape
    Tk = k.shape[1]
    if causal and Tk != T:
        raise ValueError("causal flash attention requires Tq == Tk")
    blk = _blocking(H, D, T, Tk)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, heads=blk.heads, block_q=blk.bq, block_k=blk.bk,
            causal=causal, single_k=(Tk // blk.bk == 1),
        ),
        grid=(B, blk.groups, T // blk.bq, Tk // blk.bk),
        in_specs=[blk.q, blk.k, blk.k],
        out_specs=[blk.q, blk.rows],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, H * D), out_dtype or q.dtype),
            jax.ShapeDtypeStruct((B * H, 8, T), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk.bq, blk.heads * D), jnp.float32),
            pltpu.VMEM((blk.heads, blk.bq, _LANES), jnp.float32),
            pltpu.VMEM((blk.heads, blk.bq, _LANES), jnp.float32),
        ],
        compiler_params=_params(),
        interpret=_interpret(),
    )(_rows(q), _rows(k), _rows(v))
    out = out.reshape(B, T, H, D)
    return out, (q, k, v, out, lse)


def _flash_fwd_rule(q, k, v, causal):
    return _flash_fwd(q, k, v, causal)


def _bwd_kernels(q, k, v, do, lse, delta, causal, q_dtype, k_dtype, v_dtype):
    """dq + (dk, dv) pallas calls on [B, T, H, D] operands, results in the
    same layout. Tq and Tk may differ (ring attention feeds visiting K/V
    blocks); lse and delta are the GLOBAL log-sum-exp / rowsum(dO*O) for
    the q rows, which is exactly what the flash decomposition needs per
    block."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    operands = (_rows(q), _rows(k), _rows(v), _rows(do), lse, delta)

    blk = _blocking(H, D, Tq, Tk)
    width = blk.heads * D
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, heads=blk.heads, block_q=blk.bq, block_k=blk.bk,
            causal=causal,
        ),
        grid=(B, blk.groups, Tq // blk.bq, Tk // blk.bk),
        in_specs=[blk.q, blk.k, blk.k, blk.q, blk.rows, blk.rows],
        out_specs=blk.q,
        out_shape=jax.ShapeDtypeStruct((B, Tq, H * D), q_dtype),
        scratch_shapes=[pltpu.VMEM((blk.bq, width), jnp.float32)],
        compiler_params=_params(),
        interpret=_interpret(),
    )(*operands)

    blk = _blocking(H, D, Tq, Tk, q_major=False)
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, heads=blk.heads, block_q=blk.bq, block_k=blk.bk,
            causal=causal,
        ),
        grid=(B, blk.groups, Tk // blk.bk, Tq // blk.bq),
        in_specs=[blk.q, blk.k, blk.k, blk.q, blk.rows, blk.rows],
        out_specs=[blk.k, blk.k],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tk, H * D), k_dtype),
            jax.ShapeDtypeStruct((B, Tk, H * D), v_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk.bk, width), jnp.float32),
            pltpu.VMEM((blk.bk, width), jnp.float32),
        ],
        compiler_params=_params(),
        interpret=_interpret(),
    )(*operands)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


def _flash_bwd_rule(causal, res, dout):
    q, k, v, out, lse = res
    B, T, H, D = q.shape
    blk = _blocking(H, D, T, T)
    delta = pl.pallas_call(
        functools.partial(_delta_kernel, heads=blk.heads),
        grid=(B, blk.groups, T // blk.bq, 1),
        in_specs=[blk.q, blk.q],
        out_specs=blk.rows,
        out_shape=jax.ShapeDtypeStruct((B * H, 8, T), jnp.float32),
        compiler_params=_params(),
        interpret=_interpret(),
    )(_rows(dout), _rows(out))
    return _bwd_kernels(
        q, k, v, dout, lse, delta, causal, q.dtype, k.dtype, v.dtype
    )


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---------------------------------------------------------------------------
# Block-level entry points for ring attention (ops/ring_attention.py):
# one K/V block visits per ring step; outputs merge via the global lse.
# ---------------------------------------------------------------------------


def flash_fwd_block(q, k, v, causal: bool):
    """One (q-shard, kv-block) flash forward.

    q [B,Tq,H,D], k/v [B,Tk,H,D] (Tk may differ when causal=False) ->
    (o [B,Tq,H,D] fp32, normalized within the block, lse [B*H, 8, Tq]).
    fp32 output: the ring merges blocks in fp32, and rounding each
    block's o before the merge would lose the fp32-accumulation guarantee
    the monolithic kernel has across its K tiles."""
    out, (_, _, _, _, lse) = _flash_fwd(q, k, v, causal, out_dtype=jnp.float32)
    return out, lse


def flash_bwd_block(q, k, v, do, lse, delta, causal: bool):
    """Per-block backward against the GLOBAL lse/delta: returns this
    block's (dq-contribution, dk, dv), in fp32 (the ring accumulates
    across blocks; one downcast happens at the very end)."""
    f32 = jnp.float32
    return _bwd_kernels(q, k, v, do, lse, delta, causal, f32, f32, f32)
