"""The expert layer (ops/moe.py): one chip's share of an expert-parallel
layer, held against the plain reference's ``experts``
(benchmark/reference/mimo_v2_ref.py), float32 on the CPU.

These replace, one for one, the four tests of the GShard capacity path
that went with it (PR 46): routing shapes and capacity -> routing and no
capacity; local routing -> the held experts' part; sharded matches local
-> the shares add up; differentiable -> imbalance drops nothing.
"""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def layer():
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    D, F, E, T, K = 32, 16, 16, 24, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    weights = {
        "router": jax.random.normal(ks[0], (D, E)) / D ** 0.5,
        "bias": 0.1 * jax.random.normal(ks[1], (E,)),
        "gate": jax.random.normal(ks[2], (E, D, F)) / D ** 0.5,
        "up": jax.random.normal(ks[3], (E, D, F)) / D ** 0.5,
        "down": jax.random.normal(ks[4], (E, F, D)) / F ** 0.5,
    }
    x = jax.random.normal(ks[5], (T, D), jnp.float32)
    model = {"num_experts_per_tok": K, "norm_topk_prob": True}
    return weights, x, model


def share(weights, first, count):
    return {**weights, **{k: weights[k][first:first + count] for k in ("gate", "up", "down")}}


def test_routing_is_sigmoid_top_k_with_a_bias_that_moves_the_choice_only(layer):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    weights, x, model = layer
    chosen, gates = moe.route(x, weights["router"], weights["bias"], 4)
    s = np.asarray(jax.nn.sigmoid(x @ weights["router"]))
    want = np.argsort(-(s + np.asarray(weights["bias"])), axis=1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(chosen), 1), np.sort(want, 1))
    np.testing.assert_allclose(np.asarray(gates).sum(1), 1.0, rtol=1e-6)
    picked = np.take_along_axis(s, np.asarray(chosen), 1)
    np.testing.assert_allclose(np.asarray(gates), picked / picked.sum(1, keepdims=True), rtol=1e-5)
    # without the bias other experts are chosen for some token
    plain, _ = moe.route(x, weights["router"], jnp.zeros_like(weights["bias"]), 4)
    assert not np.array_equal(np.sort(np.asarray(plain), 1), np.sort(np.asarray(chosen), 1))


@pytest.mark.parametrize("first,count", [(0, 4), (8, 4), (4, 8), (0, 16)])
def test_the_held_experts_part_is_the_references(layer, first, count):
    from benchmark.reference import mimo_v2_ref
    from ray_tpu.ops import moe

    weights, x, model = layer
    mine = share(weights, first, count)
    y, stats = moe.expert_layer(x, mine, first=first, top_k=4)
    want = mimo_v2_ref.experts(x, mine, model, (first, count))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    chosen, _ = moe.route(x, weights["router"], weights["bias"], 4)
    here = (np.asarray(chosen) >= first) & (np.asarray(chosen) < first + count)
    loads = np.bincount(np.asarray(chosen)[here] - first, minlength=count)
    assert list(np.asarray(stats)) == [here.sum(), count, (loads > 0).sum(), loads.max()]


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer(layer):
    """16 experts as 4 shares of 4: every chip routes over all 16 and
    normalises over all the chosen, so the four partial results sum to
    what the reference gives with every expert held."""
    from benchmark.reference import mimo_v2_ref
    from ray_tpu.ops import moe

    weights, x, model = layer
    parts = [moe.expert_layer(x, share(weights, f, 4), first=f, top_k=4) for f in (0, 4, 8, 12)]
    whole = mimo_v2_ref.experts(x, weights, model, (0, 16))
    np.testing.assert_allclose(sum(np.asarray(y) for y, _ in parts), np.asarray(whole), atol=5e-5)
    # every token-expert pair landed on exactly one chip
    assert sum(int(s[0]) for _, s in parts) == x.shape[0] * 4
    assert float(np.abs(np.asarray(whole)).max()) > 0.1


@pytest.mark.parametrize("tokens", [24, 100, 130])
def test_no_token_is_dropped_when_every_token_chooses_one_expert(layer, tokens):
    """A bias that sends every token to expert 2: it gets them all,
    whatever their number (more than one block of the sorted buffer, and
    a number that is no power of two)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import mimo_v2_ref
    from ray_tpu.ops import moe

    weights, _, model = layer
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, weights["router"].shape[0]))
    crowded = {**weights, "bias": weights["bias"].at[2].set(10.0)}
    mine = share(crowded, 0, 4)
    y, stats = moe.expert_layer(x, mine, first=0, top_k=4)
    assert int(stats[3]) == tokens and int(stats[0]) >= tokens
    want = mimo_v2_ref.experts(x, mine, model, (0, 4))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=5e-5)
    # rows that are not live are routed nowhere and counted nowhere
    live = jnp.arange(tokens) < 5
    y5, stats5 = moe.expert_layer(x, mine, first=0, top_k=4, live=live)
    assert int(stats5[3]) == 5
    np.testing.assert_allclose(np.asarray(y5[:5]), np.asarray(want[:5]), atol=5e-5)
    assert float(np.abs(np.asarray(y5[5:])).max()) == 0.0


def test_sigmoid_routing_gives_the_bits_it_gave(layer):
    """``route`` took a ``scoring`` in PR 63. Asked for the sigmoid, by name
    or by default, it gives what the function before it gave (written out
    here as it stood), to the bit: the sigmoid families' programs are the
    operations they were."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ray_tpu.ops import moe

    def as_it_stood(x, router, bias, top_k, scale=1.0):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        ))
        _, chosen = lax.top_k(scores + bias.astype(jnp.float32), top_k)
        picked = jnp.take_along_axis(scores, chosen, axis=1)
        gates = picked / picked.sum(-1, keepdims=True)
        return chosen, gates if scale == 1.0 else gates * scale

    weights, x, _ = layer
    for scale in (1.0, 2.448):
        want = as_it_stood(x, weights["router"], weights["bias"], 4, scale)
        for got in (moe.route(x, weights["router"], weights["bias"], 4, scale),
                    moe.route(x, weights["router"], weights["bias"], 4, scale, "sigmoid")):
            assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
            assert np.array_equal(np.asarray(got[1]).view(np.uint32),
                                  np.asarray(want[1]).view(np.uint32))
        # and the lowered programs are the same operations
        new = jax.jit(lambda a: moe.route(a, weights["router"], weights["bias"], 4, scale))
        old = jax.jit(lambda a: as_it_stood(a, weights["router"], weights["bias"], 4, scale))
        ops = lambda f: [ln.split(" = ")[1].split("(")[0].split()[-1]  # noqa: E731
                         for ln in f.lower(x).as_text().splitlines() if " = " in ln]
        assert ops(new) == ops(old)
    with pytest.raises(ValueError, match="scoring"):
        moe.route(x, weights["router"], weights["bias"], 4, scoring="softmax2")


def test_softmax_routing_is_top_k_of_the_softmax_renormalised_with_no_bias(layer):
    import jax

    from benchmark.reference import qwen3_next_ref
    from ray_tpu.ops import moe

    weights, x, model = layer
    chosen, gates = moe.route(x, weights["router"], None, 4, scoring="softmax")
    p = np.asarray(jax.nn.softmax(x @ weights["router"], axis=-1))
    want = np.argsort(-p, axis=1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(chosen), 1), np.sort(want, 1))
    picked = np.take_along_axis(p, np.asarray(chosen), 1)
    np.testing.assert_allclose(np.asarray(gates), picked / picked.sum(1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(1), 1.0, rtol=1e-6)
    ref_chosen, ref_gates, _ = qwen3_next_ref.routing(x, weights, model)
    assert np.array_equal(np.asarray(ref_chosen), np.asarray(chosen))
    np.testing.assert_allclose(np.asarray(ref_gates), np.asarray(gates), rtol=1e-5)
    # other experts than the sigmoid's biased choice, for some token
    biased, _ = moe.route(x, weights["router"], weights["bias"], 4)
    assert not np.array_equal(np.sort(np.asarray(biased), 1), np.sort(np.asarray(chosen), 1))


@pytest.mark.parametrize("holders", [1, 2, 4, 8])
@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_routed_shares_and_the_shared_expert_once_add_up_at_the_gates_scale(scoring, holders):
    """128 experts, top 6, beside a shared expert that every token passes,
    split over 1, 2, 4 and 8 holders: each holder routes over all 128 and
    computes its own experts' part, the parts add up, and with the shared
    expert counted ONCE (not once a holder) they are the uncut reference
    layer. ``held == routed`` gives the whole routed sum in one share.
    ``sigmoid``: gates that sum to ``routed_scaling_factor`` 2.448 and a
    selection bias, the layer of ``benchmark/reference/deepseek_v3_ref.py``;
    ``softmax``: scores a softmax over all 128, gates that sum to one, no
    bias, and the shared expert behind a sigmoid gate of its own, the layer
    of ``benchmark/reference/qwen3_next_ref.py`` (at four holders: the
    deployment of ``qwen3-next-80b-a3b-serve``)."""
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    from benchmark.reference import deepseek_v3_ref, qwen3_next_ref
    from ray_tpu.ops import moe

    D, F, E, T, K = 32, 16, 128, 40, 6
    scale = 2.448 if scoring == "sigmoid" else 1.0
    ks = jax.random.split(jax.random.PRNGKey(48), 10)
    weights = {
        "router": jax.random.normal(ks[0], (D, E)) / D ** 0.5,
        "bias": 0.02 * jax.random.normal(ks[1], (E,)),
        "gate": jax.random.normal(ks[2], (E, D, F)) / D ** 0.5,
        "up": jax.random.normal(ks[3], (E, D, F)) / D ** 0.5,
        "down": jax.random.normal(ks[4], (E, F, D)) / F ** 0.5,
    }
    shared = [jax.random.normal(ks[5], (D, 2 * F)) / D ** 0.5,
              jax.random.normal(ks[6], (D, 2 * F)) / D ** 0.5,
              jax.random.normal(ks[7], (2 * F, D)) / (2 * F) ** 0.5]
    x = jax.random.normal(ks[8], (T, D), jnp.float32)
    model = {"num_experts_per_tok": K, "norm_topk_prob": True, "n_routed_experts": E}
    with jax.default_matmul_precision("highest"):
        if scoring == "sigmoid":
            once = deepseek_v3_ref.swiglu(x, *shared)
            want = scale * deepseek_v3_ref.experts(x, weights, model) + once
        else:
            del weights["bias"]
            once = qwen3_next_ref.shared_expert(x, dict(
                zip(("gate", "up", "down"), shared), gate_w=jax.random.normal(ks[9], (D,))))
            want = qwen3_next_ref.experts(x, weights, model, (0, E)) + once
        each = E // holders
        parts = [moe.expert_layer(x, share(weights, f, each), first=f, top_k=K, scale=scale,
                                  scoring=scoring)
                 for f in range(0, E, each)]
        got = sum(y for y, _ in parts) + once
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    # every pair landed on exactly one holder; the gates of a token sum to the scale
    assert sum(int(s[0]) for _, s in parts) == T * K
    _, gates = moe.route(x, weights["router"], weights.get("bias"), K, scale, scoring)
    np.testing.assert_allclose(np.asarray(gates).sum(1), scale, rtol=1e-6)
    # the shared expert counted with every share would be off by (holders - 1) of it
    assert holders == 1 or float(np.abs(np.asarray(once)).max()) > 0.1
    assert float(np.abs(np.asarray(want - once)).max()) > 0.1
