"""Mesh/sharding/model tests on the virtual 8-device CPU mesh."""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def jnp_mod(cpu_mesh_devices):
    import jax.numpy as jnp

    return jnp


def test_mesh_presets(cpu_mesh_devices):
    from ray_tpu.parallel import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(dp=-1, tp=2))
    assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2
    mesh2 = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    assert mesh2.shape["fsdp"] == 2
    with pytest.raises(ValueError):
        build_mesh(MeshConfig(dp=3, tp=2))  # 6 doesn't divide 8


def test_shard_pytree_and_constraint(cpu_mesh_devices, jnp_mod):
    import jax
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel import MeshConfig, build_mesh, shard_pytree
    from ray_tpu.parallel.sharding import PartitionRules

    mesh = build_mesh(MeshConfig(dp=2, tp=4))
    rules = PartitionRules([(r"w", P("tp", None)), (r"b", P())])
    tree = {"w": jnp_mod.ones((8, 4)), "b": jnp_mod.ones((4,))}
    sharded = shard_pytree(tree, mesh, rules)
    assert sharded["w"].sharding.spec == P("tp", None)
    np.testing.assert_array_equal(np.asarray(sharded["w"]), np.ones((8, 4)))


def test_gpt2_forward_and_loss(cpu_mesh_devices, jnp_mod):
    import jax

    from ray_tpu.models import gpt2

    cfg = gpt2.CONFIGS["gpt2-tiny"]
    params = gpt2.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits = gpt2.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.padded_vocab)
    loss = gpt2.loss_fn(params, tokens, cfg)
    # random init: loss ~ log(vocab)
    assert 4.0 < float(loss) < 8.0


def test_gpt2_causality(cpu_mesh_devices, jnp_mod):
    """Changing a future token must not affect past logits."""
    import jax

    from ray_tpu.models import gpt2

    cfg = gpt2.CONFIGS["gpt2-tiny"]
    params = gpt2.init(jax.random.PRNGKey(0), cfg)
    t1 = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, cfg.vocab_size)
    t2 = t1.at[0, 10].set((t1[0, 10] + 1) % cfg.vocab_size)
    l1 = gpt2.forward(params, t1, cfg)
    l2 = gpt2.forward(params, t2, cfg)
    np.testing.assert_allclose(
        np.asarray(l1[0, :10]), np.asarray(l2[0, :10]), rtol=2e-2, atol=2e-2
    )
    assert not np.allclose(np.asarray(l1[0, 10:]), np.asarray(l2[0, 10:]), atol=1e-3)


def test_gpt2_sharded_train_step_matches_single_device(cpu_mesh_devices):
    """The full dp+fsdp+tp sharded train step must match unsharded numerics."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import gpt2
    from ray_tpu.parallel import MeshConfig, build_mesh, shard_pytree
    from ray_tpu.parallel.sharding import gpt_rules, tree_shardings

    cfg = gpt2.GPT2Config(
        vocab_size=256, n_positions=64, d_model=64, n_layer=2, n_head=4,
        remat=False, dtype=jnp.float32,
    )
    params = gpt2.init(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-3)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, 256)
    step = gpt2.make_train_step(cfg, opt)

    # single device
    p1, o1, loss1 = jax.jit(step)(params, opt.init(params), tokens)

    # 8-device mesh dp=2 fsdp=2 tp=2
    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    rules = gpt_rules()
    sp = shard_pytree(params, mesh, rules)
    so = shard_pytree(opt.init(params), mesh, rules)
    data_sharding = NamedSharding(mesh, P(("dcn", "dp", "fsdp")))
    stokens = jax.device_put(tokens, data_sharding)
    sharded_step = jax.jit(
        step,
        in_shardings=(
            tree_shardings(mesh, rules, params),
            tree_shardings(mesh, rules, so),
            data_sharding,
        ),
    )
    p2, o2, loss2 = sharded_step(sp, so, stokens)

    np.testing.assert_allclose(float(loss1), float(loss2), rtol=1e-5)
    flat1 = jax.tree_util.tree_leaves(p1)
    flat2 = jax.tree_util.tree_leaves(p2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        )


def test_mlp(cpu_mesh_devices):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import mlp

    cfg = mlp.MLPConfig(in_dim=16, hidden=(32,), num_classes=4)
    params = mlp.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    y = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 4)
    assert mlp.forward(params, x).shape == (8, 4)
    assert float(mlp.loss_fn(params, (x, y))) > 0


def test_fused_ce_matches_reference():
    """loss_impl="fused" (custom-vjp CE head) must match the
    unchunked reference loss and gradients."""
    import dataclasses

    import jax
    import numpy as np

    from ray_tpu.models import gpt2

    cfg = gpt2.CONFIGS["gpt2-tiny"]
    cfg_fused = dataclasses.replace(cfg, loss_impl="fused", loss_chunk=16)
    cfg_ref = dataclasses.replace(cfg, loss_chunk=0)
    params = gpt2.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(
        jax.random.PRNGKey(1), (2, 65), 0, cfg.vocab_size, dtype="int32"
    )
    lf = float(gpt2.loss_fn(params, toks, cfg_fused))
    lr = float(gpt2.loss_fn(params, toks, cfg_ref))
    assert abs(lf - lr) < 1e-3
    gf = jax.grad(gpt2.loss_fn)(params, toks, cfg_fused)
    gr = jax.grad(gpt2.loss_fn)(params, toks, cfg_ref)
    errs = jax.tree.map(
        lambda a, b: float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9)),
        gf, gr,
    )
    assert max(jax.tree.leaves(errs)) < 0.05


def test_scan_unroll_same_numerics():
    import dataclasses

    import jax
    import numpy as np

    from ray_tpu.models import gpt2

    cfg = gpt2.CONFIGS["gpt2-tiny"]
    cfg_u = dataclasses.replace(cfg, scan_unroll=2)
    params = gpt2.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(
        jax.random.PRNGKey(1), (2, 33), 0, cfg.vocab_size, dtype="int32"
    )
    # unrolling changes XLA fusion order, so bf16 logits differ in the
    # low bits; the loss must agree to bf16-roundoff tolerance
    lr = float(gpt2.loss_fn(params, toks, cfg))
    lu = float(gpt2.loss_fn(params, toks, cfg_u))
    np.testing.assert_allclose(lu, lr, rtol=2e-3)
