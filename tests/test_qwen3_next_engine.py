"""Qwen3-Next (``models/qwen3_next.py``) through the one serving engine
(serve/llm.py), found by its ``model_id``: continuous batching over a cache
of two kinds (a delta-rule state a decode row, two layers' pages), a prompt
prefilled in several calls while other rows decode between them, a row reused
after its sequence retired, prefix hits refused by name, the step's counts
exported; and a GPT-2 engine never imports the family.
"""

import dataclasses
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = "qwen3-next-tiny"


def _engine(**kw):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    return LLMServer(LLMConfig(model_id=MODEL, max_batch_size=2,
                               max_new_tokens_cap=64, **kw))


@pytest.fixture(scope="module")
def float32_engine():
    """Temperature 0 at float32: what a request reads then does not depend
    on who shares its steps or its prefill call (in bfloat16 a product of
    another shape rounds otherwise and a near tie may flip)."""
    import jax.numpy as jnp

    from ray_tpu.models import qwen3_next

    plain = qwen3_next.CONFIGS[MODEL]
    qwen3_next.CONFIGS[MODEL] = dataclasses.replace(plain, dtype=jnp.float32)
    srv = _engine()
    yield srv
    srv.unload()
    qwen3_next.CONFIGS[MODEL] = plain


def _series(name):
    """A counter or gauge of this process, its series added together."""
    from ray_tpu.utils import metrics

    snap = metrics.snapshot_all().get(name)
    return float(sum(snap["series"].values())) if snap else 0.0


def together(srv, prompts, asks, stagger_s=0.0):
    out = [None] * len(prompts)

    def ask(i):
        out[i] = srv({"prompt_tokens": prompts[i], "max_new_tokens": asks[i]})["tokens"]

    threads = [threading.Thread(target=ask, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
        if stagger_s:
            threading.Event().wait(stagger_s)
    for t in threads:
        t.join(240)
    return out


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(0, 256, n))) for n in lengths]


def test_generated_tokens_are_the_references_best(float32_engine):
    """Prompts of several chunks' blocks and of one position, decoded side
    by side: every token is the float32 reference's argmax over the same
    sequence."""
    from benchmark.families import qwen3_next as family
    from benchmark.reference import qwen3_next_ref

    model = family.program_sizes(MODEL)
    prompts, asks = _prompts(0, (70, 9, 40, 1)), [24, 30, 17, 33]
    out = together(float32_engine, prompts, asks)
    for prompt, n, tokens in zip(prompts, asks, out):
        assert tokens is not None and len(tokens) == n
        logits = np.asarray(qwen3_next_ref.forward(
            float32_engine.params, np.asarray(prompt + tokens), model))
        for i, tok in enumerate(tokens):
            at = logits[len(prompt) + i - 1]
            assert at[tok] >= at.max() - 1e-3, (len(prompt), i)
    stats = float32_engine.batch_stats()
    assert stats["max_batch"] >= 3  # they did share decode steps
    assert stats["prefix"]["pages_occupied"] == 0  # every page came back


def test_two_requests_in_flight_and_the_same_two_alone_give_the_same_tokens(float32_engine):
    prompts, asks = _prompts(3, (45, 7, 100, 20)), [20, 28, 12, 25]
    alone = [float32_engine({"prompt_tokens": p, "max_new_tokens": n})["tokens"]
             for p, n in zip(prompts, asks)]
    assert together(float32_engine, prompts, asks) == alone


def test_a_prompt_prefilled_in_three_calls_while_other_rows_decode_between_them(monkeypatch):
    """A chunk budget of 16 tokens a round: the 45-token prompt takes three
    prefill calls, and between them the rows that are already live take
    decode steps over all rows, its own among them. Its half-built states and
    convolution inputs must come through untouched (a row of no length is
    nobody's: ``gated_delta.step`` hands it back what it held): it gives the
    tokens it gives alone."""
    import jax.numpy as jnp

    from ray_tpu.models import qwen3_next
    from ray_tpu.utils.config import config

    monkeypatch.setitem(qwen3_next.CONFIGS, MODEL, dataclasses.replace(
        qwen3_next.CONFIGS[MODEL], dtype=jnp.float32))
    monkeypatch.setattr(config, "serve_prefill_chunk_tokens", 16)
    srv = _engine()
    try:
        long, short = _prompts(5, (45, 6))
        alone = srv({"prompt_tokens": long, "max_new_tokens": 10})["tokens"]
        calls = _series("rt_serve_prefill_calls_total")
        steps = _series("rt_serve_decode_steps_total")
        # the short one first: it is decoding (40 tokens) when the long
        # one's chunks arrive
        out = together(srv, [short, long], [40, 10], stagger_s=0.3)
        assert out[1] == alone
        assert _series("rt_serve_prefill_calls_total") - calls >= 4
        assert _series("rt_serve_decode_steps_total") - steps >= 40
    finally:
        srv.unload()


def test_a_row_reused_after_retire_gives_what_a_fresh_engine_gives(float32_engine):
    """Eight requests through the rows of a small engine one after the
    other, so rows are reused with the last sequence's states and
    convolution inputs still in them; the ninth reads what the first request
    of a fresh engine reads."""
    prompt = _prompts(9, (30,))[0]
    for filler in _prompts(11, (50, 3, 22, 70, 5, 18, 33, 64)):
        float32_engine({"prompt_tokens": filler, "max_new_tokens": 6})
    used = float32_engine({"prompt_tokens": prompt, "max_new_tokens": 16})["tokens"]
    fresh = _engine()
    try:
        assert fresh({"prompt_tokens": prompt, "max_new_tokens": 16})["tokens"] == used
    finally:
        fresh.unload()


def test_the_replica_reports_the_cache_by_kind_and_the_state_among_it(float32_engine):
    stats = float32_engine.batch_stats()
    assert stats["decode_attention"] == "own_pages_and_states"
    assert [s[0] for s in stats["kv_pool_shape"]] == ["state", "state", "state", "full"]
    by_kind = stats["kv_bytes_by_kind"]
    assert by_kind["state"] > 0 and by_kind["full"] > 0
    assert by_kind["window"] == by_kind["latent"] == 0
    assert stats["kv_pool_bytes"] == by_kind["state"] + by_kind["full"]
    rows = stats["kv_pool_shape"][0][1]
    # 3 linear layers x rows x (4 x 8 x 8 float32 + 3 x 64 in the compute type)
    assert by_kind["state"] >= 3 * rows * (4 * 8 * 8 * 4 + 3 * 64 * 4)
    before = stats["kv_pool_bytes"]
    float32_engine({"prompt_tokens": list(range(200)), "max_new_tokens": 40})
    assert float32_engine.batch_stats()["kv_pool_bytes"] == before


def test_the_steps_counts_come_back_under_their_series(float32_engine):
    """One request alone, 5 prompt tokens and 9 new: 8 decode steps at
    positions 5 .. 12, one live row each, four held experts a layer."""
    from ray_tpu.observability import core_metrics

    if not core_metrics.ENABLED:
        pytest.skip("observability is off")
    names = ("rt_serve_attn_context_tokens_total", "rt_serve_moe_expert_steps_total",
             "rt_serve_decode_steps_total")
    before = [_series(n) for n in names]
    float32_engine({"prompt_tokens": [1, 2, 3, 4, 5], "max_new_tokens": 9})
    context, expert_steps, steps = (_series(n) - b for n, b in zip(names, before))
    assert context == sum(range(6, 14))
    assert steps == 8
    assert expert_steps == 8 * 4 * 4  # steps x layers x held experts
    assert _series("rt_serve_attn_loop_tokens_total") >= _series(names[0])
    assert _series("rt_serve_moe_assignments_total") > 0
    assert _series("rt_serve_kv_state_bytes") > 0


def test_a_prefix_hit_is_refused_by_name_not_served_wrong(float32_engine):
    """The same 70-token prompt twice: the pages hold only the full layer's
    part, so nothing is matched, the refusal is counted, and the answer is
    the same."""
    prompt = list(range(70))
    first = float32_engine({"prompt_tokens": prompt, "max_new_tokens": 12})["tokens"]
    refused = _series("rt_serve_prefix_refused_total")
    again = float32_engine({"prompt_tokens": prompt, "max_new_tokens": 12})["tokens"]
    assert again == first
    assert _series("rt_serve_prefix_refused_total") == refused + 1
    assert float32_engine.batch_stats()["prefix"]["prefix_resident"] == 0


def test_a_gpt2_engine_never_imports_the_family():
    code = (
        "import sys, jax; jax.config.update('jax_platforms', 'cpu')\n"
        "from ray_tpu.serve.llm import LLMConfig, LLMServer\n"
        "srv = LLMServer(LLMConfig(model_id='gpt2-tiny', max_batch_size=2))\n"
        "assert len(srv({'prompt_tokens': [1, 2, 3], 'max_new_tokens': 4})['tokens']) == 4\n"
        "loaded = [m for m in sys.modules if 'qwen3_next' in m or 'gated_delta' in m\n"
        "          or 'ops.moe' in m]\n"
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
                hits += [name for word in ("qwen", "gated_delta", "delta rule") if word in text]
    assert not hits, hits
