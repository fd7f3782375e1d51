"""An engine holds its weights in the type it computes in
(``gpt2_decode.serving_params``, cast once by ``load_serving_params``):
the programs give the same bits on that tree as on the float32 one
``gpt2.init`` returns, the loaders of both engines and of both branches
(init, checkpoint) store exactly ``gpt2.init`` cast leaf by leaf, only the
layer norms keep ``param_dtype``, and where the compute type is
``param_dtype`` nothing changes."""

import dataclasses
import pickle

import numpy as np
import pytest

CAST = ("wte", "wpe", "attn", "mlp")  # leaves under these are cast
KEPT = ("ln1", "ln2", "ln_f")


@pytest.fixture(scope="module")
def jax_cpu():
    import jax

    jax.config.update("jax_platforms", "cpu")
    return jax


@pytest.fixture(scope="module")
def tiny(jax_cpu):
    """(cfg, the float32 tree of ``gpt2.init`` from the engines' key)."""
    from ray_tpu.models import gpt2

    cfg = gpt2.CONFIGS["gpt2-tiny"]
    return cfg, gpt2.init(jax_cpu.random.PRNGKey(0), cfg)


def _paths(jax, tree):
    return {
        tuple(k.key for k in path): leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


def _assert_is_init_cast_leaf_by_leaf(jax, cfg, init, held):
    """``held`` is ``init`` with every leaf under CAST in ``cfg.dtype``
    (the same values, rounded once) and every other leaf untouched."""
    want, got = _paths(jax, init), _paths(jax, held)
    assert want.keys() == got.keys()
    for path, leaf in want.items():
        cast = any(part in CAST for part in path)
        assert cast != any(part in KEPT for part in path), path
        expect = leaf.astype(cfg.dtype) if cast else leaf
        assert got[path].dtype == expect.dtype, path
        np.testing.assert_array_equal(
            np.asarray(got[path], np.float32), np.asarray(expect, np.float32),
            err_msg=str(path),
        )


@pytest.mark.parametrize("prompt_len", [19, 77])
def test_programs_give_the_same_bits_on_the_held_tree(jax_cpu, tiny, prompt_len):
    """``prefill_paged`` and ``_decode_paged_impl`` at bfloat16: logits
    and pools bit for bit, float32 tree against ``serving_params`` of it
    (every product already read ``leaf.astype(bfloat16)``)."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt2_decode as dec

    jax = jax_cpu
    cfg, init = tiny
    assert cfg.dtype == jnp.bfloat16
    held = dec.serving_params(cfg, init)
    B, steps = 16, 6
    max_pages = cfg.n_positions // B
    rng = np.random.RandomState(prompt_len)
    seq = rng.randint(0, cfg.vocab_size, prompt_len + steps).astype(np.int32)
    table = np.zeros((max_pages,), np.int32)
    n = -(-(prompt_len + steps) // B)
    table[:n] = np.arange(1, n + 1)
    width = 32 if prompt_len <= 32 else 128
    tok = np.zeros((1, width), np.int32)
    tok[0, :prompt_len] = seq[:prompt_len]
    step = jax.jit(dec._decode_paged_impl, static_argnums=(0,))

    def run(params):
        ck, cv = dec.init_paged_cache(cfg, 1 + n, B)
        logits, ck, cv = dec.prefill_paged(
            cfg, params, jnp.asarray(tok), jnp.int32(0), jnp.int32(prompt_len),
            ck, cv, jnp.asarray(table),
        )
        out = [np.asarray(logits)]
        for i in range(steps):
            logits, ck, cv = step(
                cfg, params, jnp.asarray(seq[prompt_len + i])[None],
                jnp.asarray([prompt_len + i], jnp.int32), ck, cv,
                jnp.asarray(table)[None],
            )
            out.append(np.asarray(logits))
        k, v = dec.read_pages(cfg, ck, cv, jnp.arange(1 + n))
        return out, np.asarray(k, np.float32), np.asarray(v, np.float32)

    (la, ka, va), (lb, kb, vb) = run(init), run(held)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(ka, kb)
    np.testing.assert_array_equal(va, vb)
    assert np.abs(ka).max() > 0


def test_a_started_engine_holds_gpt2_init_in_its_compute_type(jax_cpu, tiny):
    from ray_tpu.models import gpt2_decode as dec
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    jax = jax_cpu
    cfg, init = tiny
    srv = LLMServer(LLMConfig(model_id="gpt2-tiny", max_batch_size=2))
    try:
        _assert_is_init_cast_leaf_by_leaf(jax, cfg, init, srv.params)
        leaves = jax.tree.leaves(srv.params)
        got = srv.batch_stats()["weights_bytes"]
        assert got == sum(a.size * a.dtype.itemsize for a in leaves)
        assert got == dec.params_bytes(srv.params) < dec.params_bytes(init)
        out = srv({"prompt_tokens": [5, 6, 7], "max_new_tokens": 4,
                   "temperature": 0.0})
        assert len(out["tokens"]) == 4
    finally:
        srv.unload()


def test_the_checkpoint_branch_casts_a_pickled_float32_tree(
        jax_cpu, tiny, tmp_path):
    """A pickled float32 tree (NumPy leaves, other values than
    ``gpt2.init``'s) is held as the init branch holds its own."""
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    jax = jax_cpu
    cfg, init = tiny
    tree = jax.tree.map(lambda a: np.asarray(a) * np.float32(1.37) + 0.011, init)
    assert all(a.dtype == np.float32 for a in jax.tree.leaves(tree))
    path = tmp_path / "weights.pkl"
    path.write_bytes(pickle.dumps(tree))
    config = LLMConfig(model_id="gpt2-tiny", max_batch_size=2,
                       checkpoint_path=str(path))
    srv = LLMServer(config)
    try:
        params = srv.params
    finally:
        srv.unload()
    as_jax = jax.tree.map(jax.numpy.asarray, tree)
    _assert_is_init_cast_leaf_by_leaf(jax, cfg, as_jax, params)


def test_at_float32_the_tree_is_returned_unchanged(jax_cpu, tiny):
    import jax.numpy as jnp

    from ray_tpu.models import gpt2_decode as dec

    jax = jax_cpu
    cfg, init = tiny
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    held = dec.serving_params(cfg32, init)
    same = jax.tree.map(lambda a, b: a is b, init, held)
    assert all(jax.tree.leaves(same)), same
    loaded = dec.load_serving_params(cfg32)
    for a, b in zip(jax.tree.leaves(init), jax.tree.leaves(loaded)):
        assert b.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
