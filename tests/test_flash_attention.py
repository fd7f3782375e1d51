"""The flash kernels on the model's own [B, T, H, Dh] arrays.

Interpret mode on the CPU against ``_reference_attention``: every way a
shape can be blocked (two heads to a 128-lane block, one head of 128, the
whole row where neither fits), several q and k blocks with causal
skipping, and the ring's block entry points at Tq != Tk. One structural
test holds what the trace's roofline metrics and the step's time rest on:
nothing the size of q is transposed around a kernel call, and the three
attention kernels keep the operand counts and three-dimensional results
that ``benchmark/metrics/flash_*_roofline.json`` match by form.
"""

import numpy as np
import pytest


def _rand(shape, seed):
    import jax
    import jax.numpy as jnp

    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _qkv(shape_q, shape_k=None):
    return _rand(shape_q, 0), _rand(shape_k or shape_q, 1), _rand(shape_k or shape_q, 2)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "shape,block",
    [
        ((2, 128, 2, 64), None),     # two heads to one 128-lane block
        ((2, 256, 12, 64), 128),     # six such blocks, 2x2 q/k tiles, causal skipping
        ((1, 128, 3, 64), None),     # odd heads: the whole row of 192 lanes
        ((1, 128, 1, 128), None),    # one head of 128
        ((1, 128, 4, 32), None),     # four heads to a tile
        ((1, 128, 4, 96), None),     # heads that straddle tiles: one run of three
    ],
    ids=["2x128x2x64", "2x256x12x64-b128", "1x128x3x64", "1x128x1x128",
         "1x128x4x32", "1x128x4x96"],
)
def test_flash_matches_reference(cpu_mesh_devices, shape, block, causal, monkeypatch):
    """Forward and all three gradients against the plain einsum attention."""
    import jax

    from ray_tpu.ops.attention import _reference_attention
    from ray_tpu.ops.flash_attention import flash_attention

    if block:
        monkeypatch.setenv("RT_FLASH_BQ", str(block))
        monkeypatch.setenv("RT_FLASH_BK", str(block))
    q, k, v = _qkv(shape)
    np.testing.assert_allclose(
        np.asarray(_reference_attention(q, k, v, causal)),
        np.asarray(flash_attention(q, k, v, causal)),
        rtol=1e-5, atol=1e-5,
    )
    w = _rand(shape, 3)  # a loss whose dout differs in every lane
    want = jax.grad(
        lambda q, k, v: (_reference_attention(q, k, v, causal) * w).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    got = jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, causal) * w).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(want, got):
        assert a.shape == b.shape == shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("heads", [2, 3], ids=["paired-heads", "whole-row"])
def test_flash_blocks_at_unequal_lengths(cpu_mesh_devices, heads):
    """flash_fwd_block / flash_bwd_block as the ring calls them: Tq != Tk,
    not causal, float32 out, lse and delta on [B*H, 8, Tq] rows b*H + h."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import _reference_attention
    from ray_tpu.ops.flash_attention import flash_bwd_block, flash_fwd_block

    B, Tq, Tk, H, D = 2, 128, 256, heads, 64
    q, k, v = _qkv((B, Tq, H, D), (B, Tk, H, D))
    out, lse = flash_fwd_block(q, k, v, causal=False)
    assert out.dtype == jnp.float32 and out.shape == (B, Tq, H, D)
    assert lse.dtype == jnp.float32 and lse.shape == (B * H, 8, Tq)
    want, vjp = jax.vjp(lambda q, k, v: _reference_attention(q, k, v, False), q, k, v)
    np.testing.assert_allclose(np.asarray(want), np.asarray(out), rtol=1e-5, atol=1e-5)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / (D ** 0.5)
    want_lse = jax.nn.logsumexp(scores, axis=-1).reshape(B * H, 1, Tq)
    np.testing.assert_allclose(
        np.asarray(jnp.broadcast_to(want_lse, lse.shape)), np.asarray(lse),
        rtol=1e-5, atol=1e-5,
    )

    do = _rand((B, Tq, H, D), 3)
    delta = (do * out).sum(-1).transpose(0, 2, 1).reshape(B * H, 1, Tq)
    delta = jnp.broadcast_to(delta, (B * H, 8, Tq))
    got = flash_bwd_block(q, k, v, do, lse, delta, causal=False)
    for a, b, like in zip(vjp(do), got, (q, k, v)):
        assert b.dtype == jnp.float32 and b.shape == like.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize(
    "H,D,want",
    [(12, 64, 2), (16, 64, 2), (8, 128, 1), (4, 256, 1), (12, 32, 4),
     (25, 64, 25), (3, 64, 3), (4, 16, 4), (4, 96, 4)],
)
def test_heads_per_block_follows_from_the_shape(H, D, want):
    from ray_tpu.ops.flash_attention import _heads_per_block

    assert _heads_per_block(H, D) == want


def test_whole_row_blocks_are_cut_to_fit_vmem(cpu_mesh_devices):
    """gpt2-xl's 25 heads of 64 take the whole 1600-lane row to a block; the
    block then has fewer rows, and a 128-lane block keeps the 1024 it had."""
    from ray_tpu.ops.flash_attention import _block_sizes

    assert _block_sizes(1024, 128) == (1024, 1024)
    assert _block_sizes(1024, 256) == (1024, 1024)
    assert _block_sizes(1024, 25 * 64) == (128, 128)


def _equations(jaxpr, inside_kernel=False):
    """(equation, is it inside a pallas_call) for every equation, nested ones too."""
    for eqn in jaxpr.eqns:
        yield eqn, inside_kernel
        inner = inside_kernel or eqn.primitive.name == "pallas_call"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)  # a ClosedJaxpr holds one
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, inner)


def test_nothing_the_size_of_q_is_transposed_around_the_kernels(cpu_mesh_devices):
    """What the training cells' time and their two roofline metrics rest on.

    The jaxpr of the gradient of a flash_attention loss at [2, 128, 12, 64]
    holds no ``transpose`` outside the kernels (q, k, v, the output, dout
    and the three gradients go in and come out as [B, T, H*Dh] by reshape
    alone); the forward kernel has 3 inputs and results (bf16[a,b,c],
    f32[a,b,c]), dq 6 inputs and one bf16[a,b,c], dk/dv 6 inputs and two:
    the forms ``benchmark/metrics/flash_*_roofline.json`` match in the
    trace. The fourth call is delta = rowsum(dO * O): 2 inputs, one f32
    result, a form neither metric matches."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import flash_attention

    B, T, H, D = 2, 128, 12, 64
    x = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2),
    ))(x, x, x)
    eqns = list(_equations(jaxpr.jaxpr))
    outside = [e for e, inside in eqns if not inside]
    assert not [e for e in outside if e.primitive.name == "transpose"]

    calls = [e for e in outside if e.primitive.name == "pallas_call"]
    forms = sorted(
        (len(e.invars), tuple((str(v.aval.dtype), v.aval.shape) for v in e.outvars))
        for e in calls
    )
    rows, lse = (B, T, H * D), (B * H, 8, T)
    assert forms == sorted([
        (3, (("bfloat16", rows), ("float32", lse))),            # forward
        (6, (("bfloat16", rows),)),                             # dq
        (6, (("bfloat16", rows), ("bfloat16", rows))),          # dk, dv
        (2, (("float32", lse),)),                               # delta
    ])
    for e in calls:
        assert all(len(v.aval.shape) == 3 for v in e.invars)
