"""Trinity (``models/afmoe.py``) through the one serving engine
(serve/llm.py), found by its ``model_id``: continuous batching over a cache
of two kinds whose rings wrap, the step's counts fetched with the tokens,
prefix hits refused by name; a request alone and among others reads the
same; and a GPT-2 engine never imports the family.
"""

import dataclasses
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _engine(**kw):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    return LLMServer(LLMConfig(model_id="trinity-tiny", max_batch_size=2,
                               max_new_tokens_cap=64, **kw))


@pytest.fixture(scope="module")
def engine():
    srv = _engine()
    yield srv
    srv.unload()


def _series(name):
    """A counter or gauge of this process, its series added together."""
    from ray_tpu.utils import metrics

    snap = metrics.snapshot_all().get(name)
    return float(sum(snap["series"].values())) if snap else 0.0


def together(srv, prompts, asks):
    out = [None] * len(prompts)

    def ask(i):
        out[i] = srv({"prompt_tokens": prompts[i], "max_new_tokens": asks[i]})["tokens"]

    threads = [threading.Thread(target=ask, args=(i,)) for i in range(len(prompts))]
    [t.start() for t in threads]
    [t.join(180) for t in threads]
    return out


def assert_greedy_by_the_reference(srv, prompt, tokens, margin=0.25):
    """Every generated token is the reference's best, or within ``margin``
    of it (bfloat16 against float32 on logits whose spread is 1)."""
    import jax.numpy as jnp

    from benchmark.families import afmoe as family
    from benchmark.reference import afmoe_ref

    model = family.program_sizes("trinity-tiny")
    seq = list(prompt) + list(tokens)
    logits = np.asarray(afmoe_ref.forward(srv.params, jnp.asarray(seq), model))
    short = 0
    for i, tok in enumerate(tokens):
        at = logits[len(prompt) + i - 1]
        short += at[tok] < at.max() - margin
    # a router's tie may move one token's logits by an expert's output
    assert short <= 1, (short, len(tokens))


def test_rows_of_unequal_length_decode_side_by_side_as_the_reference_does(engine):
    """Prompts of 70 and 40 are past the window of 16 before they decode;
    9 and 3 wrap their rings while they decode."""
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(0, 256, n))) for n in (70, 9, 40, 3)]
    asks = [24, 30, 17, 33]  # K-chunks of several sizes turn up as rows finish
    out = together(engine, prompts, asks)
    for prompt, n, tokens in zip(prompts, asks, out):
        assert tokens is not None and len(tokens) == n
        assert all(0 <= t < 256 for t in tokens)
        assert_greedy_by_the_reference(engine, prompt, tokens)
    stats = engine.batch_stats()
    assert stats["max_batch"] >= 3  # they did share decode steps
    assert stats["prefix"]["pages_occupied"] == 0  # every page came back


def test_the_replica_reports_the_cache_by_kind_and_rings_stay_bounded(engine):
    stats = engine.batch_stats()
    assert stats["decode_attention"] == "own_pages_and_rings"
    kinds = [s[0] for s in stats["kv_pool_shape"]]
    assert kinds == ["window", "window", "full", "window"]
    by_kind = stats["kv_bytes_by_kind"]
    assert by_kind["window"] > 0 and by_kind["full"] > 0 and by_kind["latent"] == 0
    assert stats["kv_pool_bytes"] == by_kind["window"] + by_kind["full"]
    rows, window = stats["kv_pool_shape"][0][1:3]
    assert window == 16  # positions a decode row holds in a sliding layer: the window
    # 3 layers x K and V x rows x 16 positions x 2 heads x 16 x bfloat16
    assert by_kind["window"] >= 3 * 2 * rows * 16 * 2 * 16 * 2
    before = stats["kv_pool_bytes"]
    engine({"prompt_tokens": list(range(200)), "max_new_tokens": 40})
    assert engine.batch_stats()["kv_pool_bytes"] == before


def test_the_steps_counts_come_back_with_the_tokens(engine):
    """One request alone, 5 prompt tokens and 9 new: 8 decode steps at
    positions 5 .. 12, a window of 16 not yet left; then 40 prompt tokens
    and 5 new: 4 steps at 40 .. 43, each over a full window."""
    from ray_tpu.observability import core_metrics

    if not core_metrics.ENABLED:
        pytest.skip("observability is off")
    names = ("rt_serve_moe_expert_steps_total", "rt_serve_attn_context_tokens_total",
             "rt_serve_window_context_tokens_total")
    before = [_series(n) for n in names]
    engine({"prompt_tokens": [1, 2, 3, 4, 5], "max_new_tokens": 9})
    steps, context, window = (_series(n) - b for n, b in zip(names, before))
    # 8 decode steps x 3 expert layers x 16 held experts
    assert steps == 8 * 3 * 16
    assert context == window == sum(range(6, 14))
    before = [_series(n) for n in names]
    engine({"prompt_tokens": list(range(40)), "max_new_tokens": 5})
    _, context, window = (_series(n) - b for n, b in zip(names, before))
    assert (context, window) == (sum(range(41, 45)), 4 * 16)
    assert _series("rt_serve_attn_loop_tokens_total") >= _series(names[1])
    assert _series("rt_serve_kv_window_bytes") > 0 and _series("rt_serve_kv_full_bytes") > 0


def test_a_prefix_hit_is_refused_by_name_not_served_wrong(engine):
    """The same 70-token prompt twice: GPT-2 would serve the second from
    the first's sealed page; here the pages hold only the full layer's
    part, so nothing is matched, the refusal is counted, and the answer is
    the same."""
    prompt = list(range(70))
    first = engine({"prompt_tokens": prompt, "max_new_tokens": 12})["tokens"]
    refused = _series("rt_serve_prefix_refused_total")
    again = engine({"prompt_tokens": prompt, "max_new_tokens": 12})["tokens"]
    assert again == first
    assert _series("rt_serve_prefix_refused_total") == refused + 1
    assert engine.batch_stats()["prefix"]["prefix_resident"] == 0


def test_a_request_alone_and_among_others_returns_the_same_tokens_in_float32(monkeypatch):
    """Temperature 0 at float32: rows do not mix in attention, tokens do
    not mix in the expert layer, and a ring belongs to its row, so what a
    request reads does not depend on who shares its steps, its prefill call
    or its ring's neighbours. (In bfloat16 a product of another shape
    rounds otherwise and a near tie may flip.)"""
    import jax.numpy as jnp

    from ray_tpu.models import afmoe

    monkeypatch.setitem(afmoe.CONFIGS, "trinity-tiny", dataclasses.replace(
        afmoe.CONFIGS["trinity-tiny"], dtype=jnp.float32))
    srv = _engine()
    try:
        rng = np.random.default_rng(3)
        prompts = [list(map(int, rng.integers(0, 256, n))) for n in (45, 7, 100, 20)]
        asks = [20, 28, 12, 25]
        alone = [srv({"prompt_tokens": p, "max_new_tokens": n})["tokens"]
                 for p, n in zip(prompts, asks)]
        among = together(srv, prompts, asks)
        assert among == alone
        assert srv.batch_stats()["max_batch"] >= 3
    finally:
        srv.unload()


def test_a_gpt2_engine_never_imports_the_family():
    code = (
        "import sys, jax; jax.config.update('jax_platforms', 'cpu')\n"
        "from ray_tpu.serve.llm import LLMConfig, LLMServer\n"
        "srv = LLMServer(LLMConfig(model_id='gpt2-tiny', max_batch_size=2))\n"
        "assert len(srv({'prompt_tokens': [1, 2, 3], 'max_new_tokens': 4})['tokens']) == 4\n"
        "loaded = [m for m in sys.modules if 'afmoe' in m or 'cached_attention' in m\n"
        "          or m == 'ray_tpu.ops.moe']\n"
        "assert not loaded, loaded\n"
        "srv.unload()\n"
        # unload() stops the engine thread and does not wait for it; the
        # interpreter must not finalize with that thread inside a JAX call
        "import threading\n"
        "[t.join(60) for t in threading.enumerate() if t.name == 'llm-engine']\n"
        "print('clean')\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0 and "clean" in out.stdout, out.stderr[-2000:]


def test_no_file_of_the_serving_layer_names_the_family():
    """The engine finds the model by its ``model_id`` and asks the decode
    module: no branch on this family's name under ``ray_tpu/serve``."""
    hits = []
    for base, _, files in os.walk(os.path.join(ROOT, "ray_tpu", "serve")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(base, name)) as f:
                    text = f.read().lower()
                hits += [name for word in ("trinity", "afmoe") if word in text]
    assert not hits, hits
