"""Flash attention — K-blocked online-softmax Pallas TPU kernel, custom VJP.

The hot op of the transformer stack (no reference equivalent: the
reference delegates attention math to torch/vLLM; SURVEY.md §2.4). True
flash algorithm (Dao et al.), shaped for the TPU memory hierarchy
(pallas_guide.md):

  - grid (B*H, T/bq, T/bk) with the K dimension innermost ("arbitrary"
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

Layout: q,k,v [B, T, H, Dh] (model layout) — folded to [B*H, T, Dh] for
the kernel. lse/delta ride an 8-row sublane layout ([BH, 8, T], ~12MB at
gpt2-small scale) to keep stores tile-legal.

Context parallelism composes on top: ops/ring_attention.py rotates K/V
shards around the mesh and calls the block kernel per shard.
"""

from __future__ import annotations

import functools

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


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, block_q, block_k, causal,
                single_k: bool):
    iq, ik = pl.program_id(1), pl.program_id(2)
    n_k = pl.num_programs(2)

    def _scores():
        q = q_ref[...]
        k = k_ref[...]
        scale = 1.0 / (q.shape[-1] ** 0.5)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk] f32
        return _mask_tile(s, iq, ik, block_q, block_k, causal)

    if single_k:
        # One K block covers the whole sequence: one-shot softmax, no
        # scratch carry — saves the init/rescale VPU passes that dominate
        # at moderate T.
        s = _scores()
        m = jnp.max(s, axis=1, keepdims=True)      # [bq, 1]
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)      # [bq, 1]
        o = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[...] = (o / l).astype(o_ref.dtype)
        lse = (m + jnp.log(l))[:, 0]               # [bq]
        lse_ref[...] = jnp.broadcast_to(lse[None, :], (8, block_q))
        return

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_visible(iq, ik, block_q, block_k, causal))
    def _compute():
        s = _scores()
        m_prev = m_ref[...]                       # [bq, LANES] replicated
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # [bq, 1]
        m_next = jnp.maximum(m_prev, m_cur)        # [bq, LANES]
        alpha = jnp.exp(m_prev - m_next)           # [bq, LANES]
        p = jnp.exp(s - m_next[:, :1])             # [bq, bk]
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_next
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, D]
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv

    @pl.when(ik == n_k - 1)
    def _finalize():
        l = l_ref[...][:, :1]  # [bq, 1]
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse = m_ref[...][:, 0] + jnp.log(l_ref[...][:, 0])  # [bq]
        lse_ref[...] = jnp.broadcast_to(lse[None, :], (8, block_q))


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, block_q, block_k, causal):
    iq, ik = pl.program_id(1), pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(_visible(iq, ik, block_q, block_k, causal))
    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        scale = 1.0 / (q.shape[-1] ** 0.5)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        s = _mask_tile(s, iq, ik, block_q, block_k, causal)
        p = jnp.exp(s - lse_ref[0][:, None])       # [bq, bk]
        dp = jax.lax.dot_general(
            do_ref[...], v_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        ds = (p * (dp - delta_ref[0][:, None]) * scale).astype(k.dtype)
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ik == n_k - 1)
    def _finalize():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, block_q, block_k, causal):
    ik, iq = pl.program_id(1), pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_visible(iq, ik, block_q, block_k, causal))
    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        scale = 1.0 / (q.shape[-1] ** 0.5)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        s = _mask_tile(s, iq, ik, block_q, block_k, causal)
        p = jnp.exp(s - lse_ref[0][:, None])       # [bq, bk]
        do = do_ref[...]
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, D]
        dp = jax.lax.dot_general(
            do, v_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        ds = (p * (dp - delta_ref[0][:, None]) * scale).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bk, D]

    @pl.when(iq == n_q - 1)
    def _finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _pick_block(t: int, target: int) -> int:
    for b in (target, 1024, 512, 256, 128, 64, 32, 16, 8):
        if b <= target and t % b == 0:
            return min(b, t)
    return t


def _block_sizes(T: int):
    """(bq, bk) for sequence length T. 1024x1024 measured fastest on v5e
    for the train step (PROFILE.md): the [bq, bk] f32 score tile is 4MB of
    VMEM, large q tiles amortize the [bq, D]-contraction's half-width MXU
    occupancy (D=64), and at T<=1024 the kernel runs the one-shot
    softmax path (single K block, no online-softmax carries). VMEM stays
    bounded for long sequences (T=128k runs at the same tile size).
    RT_FLASH_BQ/BK (dynamic flags) override per process for sweeps."""
    from ray_tpu.utils.config import config

    return _pick_block(T, int(config.flash_bq)), _pick_block(T, int(config.flash_bk))


def _fold(x):  # [B, T, H, D] -> [B*H, T, D]
    B, T, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _unfold(x, B, H):  # [B*H, T, D] -> [B, T, H, D]
    BH, T, D = x.shape
    return x.reshape(B, H, T, D).transpose(0, 2, 1, 3)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
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
    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    BH = B * H
    bq, _ = _block_sizes(T)
    _, bk = _block_sizes(Tk)
    grid = (BH, T // bq, Tk // bk)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, block_q=bq, block_k=bk, causal=causal,
            single_k=(Tk // bk == 1),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 8, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), out_dtype or q.dtype),
            jax.ShapeDtypeStruct((BH, 8, T), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        compiler_params=_params(),
        interpret=_interpret(),
    )(qf, kf, vf)
    return _unfold(out, B, H), (q, k, v, out, lse)


def _flash_fwd_rule(q, k, v, causal):
    return _flash_fwd(q, k, v, causal)


def _bwd_kernels(qf, kf, vf, dof, lse, delta, causal, q_dtype, k_dtype,
                 v_dtype):
    """dq + (dk, dv) pallas calls on folded [BH, T, D] operands. Tq and Tk
    may differ (ring attention feeds visiting K/V blocks); lse and delta
    are the GLOBAL log-sum-exp / rowsum(dO*O) for the q rows, which is
    exactly what the flash decomposition needs per block."""
    BH, Tq, D = qf.shape
    Tk = kf.shape[1]
    bq, _ = _block_sizes(Tq)
    _, bk = _block_sizes(Tk)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_q=bq, block_k=bk, causal=causal),
        grid=(BH, Tq // bq, Tk // bk),
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 8, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((None, 8, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Tq, D), q_dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_params(),
        interpret=_interpret(),
    )(qf, kf, vf, dof, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=bq, block_k=bk, causal=causal),
        grid=(BH, Tk // bk, Tq // bq),
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((None, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((None, 8, bq), lambda b, j, i: (b, 0, i)),
            pl.BlockSpec((None, 8, bq), lambda b, j, i: (b, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((None, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tk, D), k_dtype),
            jax.ShapeDtypeStruct((BH, Tk, D), v_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=_params(),
        interpret=_interpret(),
    )(qf, kf, vf, dof, lse, delta)
    return dq, dk, dv


def _flash_bwd_rule(causal, res, dout):
    q, k, v, out_f, lse = res
    B, T, H, D = q.shape
    qf, kf, vf, dof = _fold(q), _fold(k), _fold(v), _fold(dout)
    BH = B * H
    # delta = rowsum(dO * O), on the same 8-row sublane layout as lse
    delta = jnp.sum(dof.astype(jnp.float32) * out_f.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (BH, 8, T))
    dq, dk, dv = _bwd_kernels(
        qf, kf, vf, dof, lse, delta, causal, q.dtype, k.dtype, v.dtype
    )
    return _unfold(dq, B, H), _unfold(dk, B, H), _unfold(dv, B, H)


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
    B, Tq, H, D = q.shape
    qf, kf, vf, dof = _fold(q), _fold(k), _fold(v), _fold(do)
    f32 = jnp.float32
    dq, dk, dv = _bwd_kernels(
        qf, kf, vf, dof, lse, delta, causal, f32, f32, f32
    )
    return _unfold(dq, B, H), _unfold(dk, B, H), _unfold(dv, B, H)
