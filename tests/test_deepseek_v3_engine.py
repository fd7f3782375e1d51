"""The latent family through the one serving engine (serve/llm.py), found by
its ``model_id``: prefix hits on latent pages, the decode programs' counts
fetched with the tokens, the cache reported by kind, KV import and the
prefill tier refused by name; and a GPT-2 engine never imports the family.
"""

import os
import subprocess
import sys

import numpy as np
import pytest
from test_mimo_engine import _series

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def engine():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    srv = LLMServer(LLMConfig(model_id="kanana-2-tiny", max_batch_size=2,
                              max_new_tokens_cap=64))
    yield srv
    srv.unload()


def assert_greedy_by_the_reference(srv, prompt, tokens, margin=0.25):
    """Every generated token is the reference's best, or within ``margin``
    of it (bfloat16 against float32 on logits whose spread is 1)."""
    import jax.numpy as jnp

    from benchmark.families import deepseek_v3 as family
    from benchmark.reference import deepseek_v3_ref

    model = family.program_sizes("kanana-2-tiny")
    seq = list(prompt) + list(tokens)
    logits = np.asarray(deepseek_v3_ref.forward(srv.params, jnp.asarray(seq), model))
    short = 0
    for i, tok in enumerate(tokens):
        at = logits[len(prompt) + i - 1]
        short += at[tok] < at.max() - margin
    # a router's tie may move one token's logits by an expert's output
    assert short <= 1, (short, len(tokens))


def test_a_second_turn_takes_a_prefix_hit_on_latent_pages_and_answers_as_it_does_cold(engine):
    """A first turn of 150 tokens seals its two whole pages of latent rows;
    a second turn whose prompt extends the first reuses them (no copy, the
    tail alone is prefilled) and answers, at temperature 0, what an engine
    that never saw the first turn answers."""
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    rng = np.random.default_rng(3)
    first = list(map(int, rng.integers(0, 256, 150)))
    reply = engine({"prompt_tokens": first, "max_new_tokens": 10})["tokens"]
    second = first + reply + list(map(int, rng.integers(0, 256, 30)))
    reused = _series("rt_serve_prefix_tokens_reused_total")
    refused = _series("rt_serve_prefix_refused_total")
    warm = engine({"prompt_tokens": second, "max_new_tokens": 12})["tokens"]
    assert _series("rt_serve_prefix_tokens_reused_total") - reused == 128
    assert _series("rt_serve_prefix_refused_total") == refused
    stats = engine.batch_stats()["prefix"]
    assert stats["prefix_resident"] >= 2 and stats["pages_occupied"] == stats["prefix_resident"]
    cold_engine = LLMServer(LLMConfig(model_id="kanana-2-tiny", max_batch_size=2,
                                      max_new_tokens_cap=64))
    try:
        cold = cold_engine({"prompt_tokens": second, "max_new_tokens": 12})["tokens"]
    finally:
        cold_engine.unload()
    assert warm == cold
    assert_greedy_by_the_reference(engine, second, warm)


def test_the_steps_counts_come_back_with_the_tokens(engine):
    from ray_tpu.observability import core_metrics

    if not core_metrics.ENABLED:
        pytest.skip("observability is off")
    steps = _series("rt_serve_moe_expert_steps_total")
    context = _series("rt_serve_mla_context_tokens_total")
    covered = _series("rt_serve_attn_loop_tokens_total")
    engine({"prompt_tokens": [1, 2, 3, 4, 5], "max_new_tokens": 9})
    # 8 decode steps x 2 expert layers x 16 experts, every one held
    assert _series("rt_serve_moe_expert_steps_total") - steps == 8 * 2 * 16
    # the row attends over 6, 7, .. 13 positions: the prompt, what it has
    # generated, the new position
    assert _series("rt_serve_mla_context_tokens_total") - context == sum(range(6, 14))
    # what the attention's kernel read for them: 8 steps x one turn of pages
    # of 64 for the one live row; the engine's seven rows nobody holds count
    # nowhere
    from ray_tpu.ops import page_loops

    turn = 64 * page_loops.DECODE_PAGES
    assert _series("rt_serve_attn_loop_tokens_total") - covered == 8 * turn
    assert _series("rt_serve_moe_assignments_total") > 0
    assert 0 < _series("rt_serve_moe_experts_hit_total") <= _series("rt_serve_moe_expert_steps_total")


def test_the_replica_reports_the_cache_by_kind(engine):
    stats = engine.batch_stats()
    assert [s[0] for s in stats["kv_pool_shape"]] == ["latent"] * 3
    pages, page_tokens, width = stats["kv_pool_shape"][0][1:]
    assert (page_tokens, width) == (64, 128)  # 32 + 8 of a row, and zeros to whole lanes
    by_kind = stats["kv_bytes_by_kind"]
    assert by_kind == {"full": 0, "window": 0, "latent": stats["kv_pool_bytes"]}
    assert by_kind["latent"] >= 3 * pages * 64 * 128 * 2
    assert _series("rt_serve_kv_latent_bytes") >= by_kind["latent"]
    assert stats["decode_attention"] == "own_latent_pages"


@pytest.mark.parametrize("model_id,kinds", [("gpt2-tiny", {"full"}),
                                            ("mimo-v2-tiny", {"full", "window"})])
def test_the_other_families_report_no_latent_bytes(model_id, kinds):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    srv = LLMServer(LLMConfig(model_id=model_id, max_batch_size=2))
    try:
        by_kind = srv.batch_stats()["kv_bytes_by_kind"]
    finally:
        srv.unload()
    assert set(by_kind) == {"full", "window", "latent"} and by_kind["latent"] == 0
    assert {k for k, held in by_kind.items() if held} == kinds


def test_a_gpt2_engine_never_imports_the_family():
    code = (
        "import sys, jax; jax.config.update('jax_platforms', 'cpu')\n"
        "from ray_tpu.serve.llm import LLMConfig, LLMServer\n"
        "srv = LLMServer(LLMConfig(model_id='gpt2-tiny', max_batch_size=2))\n"
        "assert len(srv({'prompt_tokens': [1, 2, 3], 'max_new_tokens': 4})['tokens']) == 4\n"
        "loaded = [m for m in sys.modules if 'deepseek' in m or m == 'ray_tpu.ops.moe']\n"
        "assert not loaded, loaded\n"
        "srv.unload()\n"
        # unload() stops the engine thread and does not wait for it; the
        # interpreter must not finalize with that thread inside a JAX call
        "import threading\n"
        "[t.join(60) for t in threading.enumerate() if t.name == 'llm-engine']\n"
        "print('clean')\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0 and "clean" in proc.stdout, proc.stderr[-2000:]


def test_the_model_is_found_by_its_id_and_by_nothing_else():
    from ray_tpu import models

    cfg, dec = models.resolve("kanana-2-30b-a3b")
    assert (cfg.n_layer, cfg.n_routed_experts, cfg.latent_width, cfg.stored_width) == (5, 128, 576, 640)
    assert dec.__name__ == "ray_tpu.models.deepseek_v3"
    assert dec.STEP_COUNTERS[-2:] == ("mla_context_tokens", "attn_loop_tokens")
    assert len(dec.STEP_COUNTERS) == 6
    with pytest.raises(KeyError, match="kanana-2-tiny"):
        models.resolve("kanana-2-nope")
    with open(os.path.join(ROOT, "ray_tpu/serve/llm.py")) as f:
        engine_source = f.read()
    assert "deepseek" not in engine_source and "kanana" not in engine_source


# -- a prefill call of several rows (PR 50) ----------------------------------


@pytest.fixture(scope="module")
def packer():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from test_mimo_engine import packing_engine

    srv = packing_engine("kanana-2-tiny")
    yield srv
    srv.unload()


def test_a_tail_over_a_rows_width_is_the_rows_of_one_call(packer, monkeypatch):
    """Every layer is paged (``PREFIX_CACHE``), so a later row reads an
    earlier one from the pages: with 128 tokens a row a cold prompt of 300
    is three rows of ONE call, the fourth nobody's, and a second turn
    behind its sealed pages takes its tail of 200 as two rows beside
    another sequence's one. The answers are the reference's."""
    from test_llm_engine import record_prefill_calls
    from test_mimo_engine import ask_together, calls_of_rows, tokens_a_row

    from ray_tpu.models import deepseek_v3 as dec

    seen = []
    record_prefill_calls(monkeypatch, dec, seen)
    calls, rows = (_series("rt_serve_prefill_calls_total"),
                   _series("rt_serve_prefill_rows_total"))
    with tokens_a_row(128):
        (first,), (reply,) = ask_together(packer, (300,), max_new=6)
        assert calls_of_rows(seen) == [(4, 128, [(0, 0, 128), (0, 128, 128), (0, 256, 44)])]
        assert len(seen) == 1
        assert _series("rt_serve_prefill_calls_total") - calls == 1
        assert _series("rt_serve_prefill_rows_total") - rows == 3
        assert_greedy_by_the_reference(packer, first, reply)
        del seen[:]
        rng = np.random.default_rng(4)
        second = first + reply + list(map(int, rng.integers(0, 256, 150)))
        other = list(map(int, rng.integers(0, 256, 70)))
        from test_llm_engine import enqueue_together

        reqs = enqueue_together(packer, [{"prompt_tokens": p, "max_new_tokens": 6}
                                         for p in (second, other)])
        for r in reqs:
            assert r.event.wait(300) and r.error is None
    # four sealed pages of 64 stand under the second turn: 256 of its 456 tokens
    assert calls_of_rows(seen) == [(4, 128, [(0, 256, 128), (0, 384, 72), (1, 0, 70)])]
    assert_greedy_by_the_reference(packer, second, reqs[0].result)
    assert_greedy_by_the_reference(packer, other, reqs[1].result)


def test_a_load_that_meets_every_call_of_rows_compiles_nothing(packer, monkeypatch):
    from test_mimo_engine import meets_every_call_of_rows

    from ray_tpu.models import deepseek_v3 as dec

    # a tail of 600 is two rows of 512, and two such tails are four; three
    # prompts under 256 are a row each, the fourth row nobody's
    meets_every_call_of_rows(
        packer, dec, monkeypatch,
        [(100,), (200,), (400,), (100, 90), (100, 90, 80), (200, 150), (200, 150, 140),
         (600,), (600, 520)],
        widths=(128, 256, 512))


# -- the decode call before the wait for first tokens (PR 61) -----------------


@pytest.fixture(scope="module")
def streams():
    """The seeded cases on an engine nobody has asked anything."""
    import _engine_streams

    return _engine_streams.fresh_streams("kanana-2-tiny")


@pytest.mark.parametrize("case", ["greedy_cold", "sampled_cold", "greedy_second_turn",
                                  "sampled_second_turn", "together", "one_token"])
def test_seeded_requests_get_the_tokens_the_parent_gave(streams, case):
    """Second turns stand behind sealed latent pages here: the first token
    of a tail goes to its decode call on the device like a cold prompt's."""
    import _engine_streams

    assert streams[case] == _engine_streams.expected("kanana-2-tiny")[case]


def test_a_tail_behind_its_prefix_hands_the_decode_call_over_before_the_wait(packer):
    """A second turn behind four sealed pages beside a cold prompt, at a
    temperature and greedy: one prefill call of rows, the sampling, the
    scatter of the changed rows, the first tokens placed on the device, the
    decode call, and only then the wait, which the dispatch span lies
    before; both first tokens are counted ahead, and each request's first
    token is what the decode call was given for its row."""
    import _engine_streams
    from test_llm_engine import enqueue_together
    from test_mimo_engine import ask_together

    (first,), (reply,) = ask_together(packer, (300,), max_new=6, seed=611)
    rng = np.random.default_rng(612)
    second = first + reply + list(map(int, rng.integers(0, 256, 90)))
    other = list(map(int, rng.integers(0, 256, 50)))
    ahead = _series("rt_serve_first_tokens_ahead_total")
    reused = _series("rt_serve_prefix_tokens_reused_total")
    seen, given = [], []
    real_place = packer._place_rows

    def placed(last_tokens, firsts, rows):
        out = real_place(last_tokens, firsts, rows)
        given.append((np.asarray(rows).tolist(), np.asarray(firsts).tolist(),
                      np.asarray(out).tolist()))
        return out

    packer._place_rows = placed
    try:
        with _engine_streams.watch_the_round(packer, seen):
            reqs = enqueue_together(packer, [
                {"prompt_tokens": second, "max_new_tokens": 7, "temperature": 0.8},
                {"prompt_tokens": other, "max_new_tokens": 7}])
            for r in reqs:
                assert r.event.wait(300) and r.error is None
    finally:
        packer._place_rows = real_place
    assert _series("rt_serve_prefix_tokens_reused_total") - reused == 256
    assert _series("rt_serve_first_tokens_ahead_total") - ahead == 2
    assert_greedy_by_the_reference(packer, other, reqs[1].result)
    ending = [r for r in _engine_streams.rounds_of(seen) if ("call", "sample") in r]
    assert len(ending) == 1
    assert [name for kind, name in ending[0] if kind == "call"] == [
        "prefill", "sample", "scatter", "place", "decode"]
    at = ending[0].index
    assert at(("end", "dispatch")) < at(("span", "first_token_sync"))
    # the rows of the call that held a prompt's end, and what the decode call took
    (rows, firsts, last_tokens), = given
    live = [(i, t) for i, t in zip(rows, firsts) if i < len(last_tokens)]
    assert sorted(i for i, _ in live) == [0, 1] and len(rows) == 4
    assert [last_tokens[i] for i, _ in live] == [t for _, t in live]
    assert sorted(t for _, t in live) == sorted(r.result[0] for r in reqs)
