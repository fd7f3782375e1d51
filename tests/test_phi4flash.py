"""Phi-4-mini-flash (``model_type: phi4flash``) through its cache of three
kinds (models/phi4flash.py) against the plain reference's full forward pass
(benchmark/reference/phi4flash_ref.py), at the tiny preset on the CPU,
logits compared.

The comparison is the benchmark's own (``families/phi4flash.compare_serve``:
chunked prefill into a row's states, rings and pages, then decode side by
side). The reference runs every layer over every position, the program's
prefill runs the cross-decoder on a row's last position alone, so agreement
proves the skip changes nothing. In float32 it is tight, and every way of
getting the model wrong that is listed below breaks it; in bfloat16, as
served, it is held to the tiny twin's tolerance.
"""

import dataclasses
import json
import math
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = "phi-4-mini-flash-tiny"
TIGHT = 2e-3  # float32 program against float32 reference, logits' spread ~1
SEEN = 0.02   # what a model computed wrongly must exceed


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    from benchmark.families import phi4flash as family
    from ray_tpu.models import phi4flash

    cfg = dataclasses.replace(phi4flash.CONFIGS[MODEL], dtype=jnp.float32)
    return cfg, phi4flash.load_serving_params(cfg), family.program_sizes(MODEL)


def compare(tiny, **kw):
    from benchmark.families import phi4flash as family

    cfg, params, model = tiny
    kw = {"prompt_lens": [70, 33, 5], "steps": 24, "page_tokens": 16, "chunk": 32, **kw}
    return family.compare_serve(cfg, model, params, 11, **kw)


def test_prefill_in_chunks_then_decode_through_the_cache_is_the_full_forward(tiny):
    """Rows of unequal length side by side; row 0 is prefilled in three
    chunks of 32 (positions at start > 0 carry a state, convolution inputs
    and a ring that the second chunk has wrapped) and grows to 94
    positions, past the window of 16; row 2 starts inside the window and
    wraps its ring while it decodes. Every token is judged."""
    out = compare(tiny)
    assert out["reference_logit_std"] > 0.3
    assert 0 < out["prefill_max_abs"] < TIGHT and 0 < out["decode_max_abs"] < TIGHT
    assert (out["rows"], out["decode_steps"], out["tokens_compared"]) == (3, 24, 6 + 3 * 24)
    assert (out["prefill_judged"], out["decode_judged"]) == (6, 72)


@pytest.mark.parametrize("chunk", [8, 20, 64])
def test_a_chunk_narrower_than_the_window_or_as_wide_as_the_prompt(tiny, chunk):
    """At 8 a chunk meets a ring that holds two earlier chunks, at 20 chunk
    and ring do not line up, at 64 the first call holds four windows; the
    state is carried over each boundary."""
    out = compare(tiny, prompt_lens=[61, 17], steps=6, chunk=chunk)
    assert 0 < out["prefill_max_abs"] < TIGHT and 0 < out["decode_max_abs"] < TIGHT


def test_the_cross_decoder_on_the_last_position_is_all_layers_on_all_positions(tiny):
    """Prefixes of a prompt, each prefilled as one call: the program ran the
    layers behind the full one on the last position only, the reference all
    layers on all positions, and the logits there agree."""
    import jax.numpy as jnp

    from benchmark.reference import phi4flash_ref
    from ray_tpu.models import phi4flash as dec

    cfg, params, model = tiny
    seq = np.random.default_rng(2).integers(0, 256, 40, dtype=np.int32)
    want = np.asarray(phi4flash_ref.forward(params, seq, model))
    table = jnp.arange(1, 17, dtype=jnp.int32)
    for n in (1, 2, 15, 16, 17, 40):
        k, v = dec.init_paged_cache(cfg, 17, 16, 1)
        tok = np.zeros((1, 64), np.int32)
        tok[0, :n] = seq[:n]
        logits, _, _ = dec.prefill_paged(cfg, params, jnp.asarray(tok), jnp.int32(0),
                                         jnp.int32(n), k, v, table, np.int32(0))
        assert np.abs(np.asarray(logits) - want[n - 1]).max() < TIGHT, n


@pytest.mark.parametrize("wrong", ["lambda_init_of_layer_0", "grouped_heads",
                                   "memory_after_gate", "cross_own_kv"])
def test_a_model_computed_wrongly_fails_the_comparison(tiny, wrong):
    """Layer 0's lambda_init in every layer; the usual grouping of query
    heads over K heads in place of the pairs'; the memory taken behind its
    layer's gate; the cross layers attending over K and V of their own
    input: each is another model, and the comparison says so."""
    out = compare(tiny, prompt_lens=[40, 9], steps=4, wrong=[wrong])
    assert max(out["prefill_max_abs"], out["decode_max_abs"]) > SEEN


def test_a_state_that_is_not_reset_at_start_zero_fails_the_comparison(tiny, monkeypatch):
    """The comparison's rows start with dirty states (a retired sequence's,
    in the engine). A prefill that takes them as they are, whatever
    ``start``, is caught."""
    from ray_tpu.ops import selective_scan

    sound = selective_scan.chunk_scan
    monkeypatch.setattr(
        selective_scan, "chunk_scan",
        lambda params, x, state, start, length: sound(params, x, state, start + 1, length))
    cfg, params, model = tiny
    # a config of its own, so that the program is traced with the fault in
    cfg = dataclasses.replace(cfg, layer_norm_eps=1.000001e-5)
    out = compare((cfg, params, model), prompt_lens=[40, 9], steps=4)
    assert out["prefill_max_abs"] > SEEN and out["decode_max_abs"] > SEEN


def test_the_state_in_bfloat16_is_the_same_programs_on_another_cache(tiny):
    """The check's control: it runs, and moves the logits."""
    sound = compare(tiny, prompt_lens=[40, 9], steps=4)
    control = compare(tiny, prompt_lens=[40, 9], steps=4, state_control=True)
    assert control["decode_max_abs"] > 2 * sound["decode_max_abs"]


def test_as_served_in_bfloat16_within_the_tiny_twins_tolerance():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from benchmark.families import phi4flash as family

    with open(os.path.join(ROOT, "tests/bench/configs/phi4flash-tiny-serve.json")) as f:
        cfg = json.load(f)
    mcfg, params = family.serve_params(cfg["model_id"])
    out = family.compare_serve(mcfg, cfg["model"], params, 11,
                               prompt_lens=cfg["check"]["prompt_lens"],
                               steps=cfg["check"]["decode_steps"], page_tokens=16, chunk=32)
    worst = max(out["prefill_max_abs"], out["decode_max_abs"])
    assert 0 < worst <= cfg["check"]["logit_tolerance"]


def test_params_count_is_the_stored_trees_size_at_the_published_widths():
    """3,852,562,944 parameters, by ``jax.eval_shape``: no weights made."""
    import jax

    from benchmark.families import phi4flash as family
    from ray_tpu.models import phi4flash

    stored = {}
    for model_id in ("phi-4-mini-flash-reasoning", MODEL):
        cfg = phi4flash.CONFIGS[model_id]
        tree = jax.eval_shape(lambda: phi4flash.init(jax.random.PRNGKey(0), cfg))
        stored[model_id] = sum(math.prod(a.shape) for a in jax.tree.leaves(tree))
        assert family.params_count(family.program_sizes(model_id)) == stored[model_id]
    assert stored["phi-4-mini-flash-reasoning"] == 3_852_562_944


def test_the_layers_are_the_published_ones():
    from ray_tpu.models import phi4flash

    cfg = phi4flash.CONFIGS["phi-4-mini-flash-reasoning"]
    kinds = [cfg.kind(l) for l in range(cfg.n_layer)]
    assert kinds[:18] == ["mamba", "window"] * 8 + ["mamba", "full"]
    assert kinds[18:] == ["gmu", "cross"] * 7
    assert (cfg.d_inner, cfg.dt_rank, cfg.head_dim, cfg.full_layer) == (5120, 160, 64, 17)
    spec = phi4flash.cache_spec(cfg)
    assert [s["kind"] for s in spec] == (["state", "window"] * 8 + ["state", "full"]
                                         + ["none"] * 14)
    assert [s.get("reads") for s in spec[18:]] == [None, 17] * 7
    assert abs(cfg.lambda_init(17) - (0.8 - 0.6 * math.exp(-5.1))) < 1e-12


@pytest.mark.parametrize("queries", [1, 5])
def test_products_take_the_values_as_pairs_of_heads(queries):
    """``v_heads``: 8 query heads over 4 K heads of 8, the values taken as 2
    heads of 16: head h scores against K head h // 2 and weighs value pair
    h // 4."""
    import jax.numpy as jnp

    from ray_tpu.ops import cached_attention as ca

    rng = np.random.default_rng(queries)
    q, k, v = (jnp.asarray(rng.normal(size=s), jnp.float32)
               for s in ((2, queries, 8, 8), (2, 11, 32), (2, 11, 32)))
    scores, weighted = ca.products(q, 4, v_heads=2)
    s = np.asarray(scores(k))
    k4 = np.asarray(k).reshape(2, 11, 4, 8)
    for h in range(8):
        want = np.einsum("rqd,rtd->rqt", np.asarray(q)[:, :, h], k4[:, :, h // 2]) / 8 ** 0.5
        np.testing.assert_allclose(s[:, h], want, rtol=1e-5, atol=1e-5)
    p = jnp.asarray(rng.random(size=(2, 8, queries, 11)), jnp.float32)
    out = np.asarray(weighted(p, v))
    v2 = np.asarray(v).reshape(2, 11, 2, 16)
    for h in range(8):
        want = np.einsum("rqt,rtv->rqv", np.asarray(p)[:, h], v2[:, :, h // 4])
        np.testing.assert_allclose(out[:, h], want, rtol=1e-5, atol=1e-5)
