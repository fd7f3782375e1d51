"""MiMo-V2 through the one serving engine (serve/llm.py), found by its
``model_id``: continuous batching over a cache of two kinds, the expert
layer's counts fetched with the tokens, prefix hits and KV import refused
by name; and a GPT-2 engine never imports the family.
"""

import contextlib
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def engine():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    srv = LLMServer(LLMConfig(model_id="mimo-v2-tiny", max_batch_size=2,
                              max_new_tokens_cap=64))
    yield srv
    srv.unload()


def _series(name):
    """A counter or gauge of this process, its series added together."""
    from ray_tpu.utils import metrics

    snap = metrics.snapshot_all().get(name)
    return float(sum(snap["series"].values())) if snap else 0.0


def assert_greedy_by_the_reference(srv, prompt, tokens, margin=0.25):
    """Every generated token is the reference's best, or within ``margin``
    of it (bfloat16 against float32 on logits whose spread is 1)."""
    import jax.numpy as jnp

    from benchmark.families import mimo_v2 as family
    from benchmark.reference import mimo_v2_ref

    model = family.program_sizes("mimo-v2-tiny")
    seq = list(prompt) + list(tokens)
    logits = np.asarray(mimo_v2_ref.forward(srv.params, jnp.asarray(seq), model))
    short = 0
    for i, tok in enumerate(tokens):
        at = logits[len(prompt) + i - 1]
        short += at[tok] < at.max() - margin
    # a router's tie may move one token's logits by an expert's output
    assert short <= 1, (short, len(tokens))


def test_rows_of_unequal_length_decode_side_by_side_as_the_reference_does(engine):
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(0, 256, n))) for n in (70, 9, 40, 3)]
    asks = [24, 30, 17, 33]  # K-chunks of several sizes turn up as rows finish
    out = [None] * len(prompts)

    def ask(i):
        out[i] = engine({"prompt_tokens": prompts[i], "max_new_tokens": asks[i]})["tokens"]

    threads = [threading.Thread(target=ask, args=(i,)) for i in range(len(prompts))]
    [t.start() for t in threads]
    [t.join(120) for t in threads]
    for prompt, n, tokens in zip(prompts, asks, out):
        assert tokens is not None and len(tokens) == n
        assert all(0 <= t < 256 for t in tokens)
        assert_greedy_by_the_reference(engine, prompt, tokens)
    stats = engine.batch_stats()
    assert stats["max_batch"] >= 3  # they did share decode steps
    assert stats["prefix"]["pages_occupied"] == 0  # every page came back


def test_the_replica_reports_the_cache_by_kind_and_window_rows_stay_bounded(engine):
    stats = engine.batch_stats()
    kinds = [s[0] for s in stats["kv_pool_shape"]]
    assert kinds == ["full", "window", "window", "full"]
    by_kind = stats["kv_bytes_by_kind"]
    assert by_kind["window"] > 0 and by_kind["full"] > 0
    assert stats["kv_pool_bytes"] == by_kind["window"] + by_kind["full"]
    rows, window = stats["kv_pool_shape"][1][1:3]
    assert window == 16  # positions a decode row holds in a window layer: the window
    # 2 layers x K and V x rows x 16 positions x 2 heads x (24 + 16) x bfloat16
    assert by_kind["window"] >= 2 * rows * 16 * 2 * (24 + 16) * 2
    before = stats["kv_pool_bytes"]
    engine({"prompt_tokens": list(range(200)), "max_new_tokens": 40})
    assert engine.batch_stats()["kv_pool_bytes"] == before


def test_expert_counts_come_back_with_the_tokens(engine):
    from ray_tpu.observability import core_metrics

    if not core_metrics.ENABLED:
        pytest.skip("observability is off")
    before = _series("rt_serve_moe_expert_steps_total")
    engine({"prompt_tokens": [1, 2, 3, 4, 5], "max_new_tokens": 9})
    after = _series("rt_serve_moe_expert_steps_total")
    # 8 decode steps x 3 expert layers x 4 held experts
    assert after - before == 8 * 3 * 4
    assert _series("rt_serve_moe_assignments_total") > 0
    assert 0 < _series("rt_serve_moe_experts_hit_total") <= _series("rt_serve_moe_expert_steps_total")
    assert _series("rt_serve_moe_max_load_total") >= 1
    assert _series("rt_serve_kv_window_bytes") > 0 and _series("rt_serve_kv_full_bytes") > 0


def test_the_account_of_a_round_adds_up_where_a_call_takes_rows(engine):
    """A module that takes rows hands the device the sampling of a call's
    first tokens between its prefill call and their sync, outside every
    inner span: the round's ``other``. The phases of the thread's own code
    still add up to its host time, and no dry second is outside them."""
    from ray_tpu.observability import core_metrics

    if not core_metrics.ENABLED:
        pytest.skip("observability is off")

    def seconds(key):
        series = getattr(core_metrics, key).snapshot()["series"]
        return sum(s["sum"] for k, s in series.items() if "mimo-v2-tiny" in str(k))

    keys = [f"serve_engine_{p}_s" for p in core_metrics.ENGINE_HOST_PHASES]
    dry = [f"serve_engine_dry_{p}_s" for p in core_metrics.ENGINE_HOST_PHASES]
    before = {k: seconds(k) for k in [*keys, *dry, "serve_engine_round_host_s"]}
    engine({"prompt_tokens": [5, 4, 3, 2, 1], "max_new_tokens": 6})
    time.sleep(0.05)  # the last round's own stamps
    spent = {k: seconds(k) - before[k] for k in before}
    assert sum(spent[k] for k in keys) == pytest.approx(
        spent["serve_engine_round_host_s"], rel=1e-6)
    assert all(0 <= spent[d] <= spent[k] + 1e-9 for d, k in zip(dry, keys))


def test_a_prefix_hit_is_refused_by_name_not_served_wrong(engine):
    """The same 70-token prompt twice: GPT-2 would serve the second from
    the first's sealed page; here the pages hold only the full layers'
    part, so nothing is matched, the refusal is counted, and the answer is
    the same."""
    prompt = list(range(70))
    first = engine({"prompt_tokens": prompt, "max_new_tokens": 12})["tokens"]
    refused = _series("rt_serve_prefix_refused_total")
    again = engine({"prompt_tokens": prompt, "max_new_tokens": 12})["tokens"]
    assert again == first
    assert _series("rt_serve_prefix_refused_total") == refused + 1
    assert engine.batch_stats()["prefix"]["prefix_resident"] == 0


def test_a_gpt2_engine_never_imports_the_family():
    code = (
        "import sys, jax; jax.config.update('jax_platforms', 'cpu')\n"
        "from ray_tpu.serve.llm import LLMConfig, LLMServer\n"
        "srv = LLMServer(LLMConfig(model_id='gpt2-tiny', max_batch_size=2))\n"
        "assert len(srv({'prompt_tokens': [1, 2, 3], 'max_new_tokens': 4})['tokens']) == 4\n"
        "loaded = [m for m in sys.modules if 'mimo' in m or m == 'ray_tpu.ops.moe']\n"
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


def test_an_unknown_model_is_refused_with_the_known_ones():
    from ray_tpu import models

    with pytest.raises(KeyError, match="gpt2-tiny"):
        models.resolve("gpt2-nope")
    with pytest.raises(KeyError, match="families"):
        models.resolve("llama")
    cfg, dec = models.resolve("mimo-v2.5")
    assert cfg.n_layer == 7 and cfg.n_routed_experts == 16 and cfg.router_experts == 256
    assert dec.PREFIX_CACHE is False and len(dec.STEP_COUNTERS) == 6
    assert dec.STEP_COUNTERS[-2:] == ("attn_context_tokens", "attn_loop_tokens")


def test_a_stream_that_fell_behind_takes_what_was_produced_in_one_go(engine):
    """A consumer that keeps up gets a token an item; one that is away
    while the engine goes on gets every token produced meanwhile behind
    the next, each but the last flagged ``more`` (one event at the front
    door). No wait, no threshold: the tokens are the same."""
    import time

    ask = {"prompt_tokens": [3, 1, 4], "max_new_tokens": 12, "stream": True}
    plain = list(engine(ask))
    assert [it["index"] for it in plain] == list(range(12))
    assert not plain[-1]["more"]
    gen = engine(ask)
    late = [next(gen)]
    time.sleep(2.0)  # the engine finishes the other eleven meanwhile
    late += list(gen)
    assert [it["token"] for it in late] == [it["token"] for it in plain]
    assert [it["more"] for it in late[1:]] == [True] * 10 + [False]


def test_one_stream_sends_at_a_time_and_a_closed_one_lets_go(engine):
    """The turn (``_stream_turn``) is held from the moment a stream takes
    its tokens until its consumer comes back for more: another stream's
    tokens are produced meanwhile and wait in its queue, and come as one
    batch when the turn is free. A consumer that goes away frees it."""
    import time

    ask = {"prompt_tokens": [2, 7, 1], "max_new_tokens": 10, "stream": True}
    held = engine(ask)
    first = next(held)  # paused at its yield: this stream has the turn
    assert first["index"] == 0
    got = []
    other = threading.Thread(target=lambda: got.extend(engine(ask)))
    other.start()
    deadline = time.time() + 60
    time.sleep(0.5)
    while engine._occupied and time.time() < deadline:
        time.sleep(0.1)  # until the engine has finished both requests
    assert engine._occupied == 0 and got == []
    held.close()  # the client went away
    other.join(60)
    assert [it["index"] for it in got] == list(range(10))
    assert [it["more"] for it in got] == [True] * 9 + [False]
    assert not engine._stream_turn.locked()


# -- a prefill call of several rows (PR 50) ----------------------------------


def packing_engine(model_id):
    """An engine of ``model_id`` with room for four prompts side by side."""
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    return LLMServer(LLMConfig(model_id=model_id, max_batch_size=4, max_new_tokens_cap=64))


@contextlib.contextmanager
def tokens_a_row(n):
    """``serve_prefill_chunk_tokens``, which the engine reads every round."""
    from ray_tpu.utils.config import config

    keep = config.serve_prefill_chunk_tokens
    config.set("serve_prefill_chunk_tokens", n)
    try:
        yield
    finally:
        config.set("serve_prefill_chunk_tokens", keep)


def ask_together(srv, lengths, max_new=4, seed=0):
    """Prompts of ``lengths`` tokens admitted in one round: (prompts, the
    answers)."""
    from test_llm_engine import enqueue_together

    rng = np.random.default_rng(seed)
    prompts = [list(map(int, rng.integers(0, 256, n))) for n in lengths]
    reqs = enqueue_together(srv, [{"prompt_tokens": p, "max_new_tokens": max_new}
                                  for p in prompts])
    for r in reqs:
        assert r.event.wait(300) and r.error is None, r.error
    return prompts, [r.result for r in reqs]


def calls_of_rows(seen):
    """What ``record_prefill_calls`` saw: (R, P, [(decode row, start,
    length) of the rows that have a length]) a call, each with a page
    table a row."""
    assert all(len(table) == 2 and table[0] == shape[0] for shape, _, _, table, _ in seen)
    return [(shape[0], shape[1],
             [(r, s, n) for r, s, n in zip(row, start, length) if n])
            for shape, start, length, table, row in seen]


@pytest.fixture(scope="module")
def packer():
    import jax

    jax.config.update("jax_platforms", "cpu")
    srv = packing_engine("mimo-v2-tiny")
    yield srv
    srv.unload()


def test_prompts_admitted_together_share_a_call_a_row_each(packer, monkeypatch):
    """Three prompts admitted in one round: two are the rows of one call
    (the most this module compiles), the third goes alone in the next
    round; no call holds two rows of one sequence (a window layer's ring
    belongs to a decode row: ``PREFIX_CACHE`` is False); and every answer
    is the reference's."""
    from test_llm_engine import record_prefill_calls

    from ray_tpu.models import mimo_v2 as dec

    assert dec.PREFILL_ROWS == (1, 2)
    seen = []
    record_prefill_calls(monkeypatch, dec, seen)
    calls = _series("rt_serve_prefill_calls_total")
    rows = _series("rt_serve_prefill_rows_total")
    positions = _series("rt_serve_prefill_tokens_total")
    prompts, answers = ask_together(packer, (100, 40, 128), max_new=6)
    for prompt, tokens in zip(prompts, answers):
        assert_greedy_by_the_reference(packer, prompt, tokens)
    assert calls_of_rows(seen) == [(2, 128, [(0, 0, 100), (1, 0, 40)]),
                                   (1, 128, [(2, 0, 128)])]
    assert _series("rt_serve_prefill_calls_total") - calls == 2
    assert _series("rt_serve_prefill_rows_total") - rows == 3
    assert _series("rt_serve_prefill_tokens_total") - positions == 268
    del seen[:]
    prompts, answers = ask_together(packer, (200, 70), max_new=5, seed=1)
    for prompt, tokens in zip(prompts, answers):
        assert_greedy_by_the_reference(packer, prompt, tokens)
    assert calls_of_rows(seen) == [(2, 256, [(0, 0, 200), (1, 0, 70)])]
    for R, P, live in calls_of_rows(seen):
        assert len({r for r, _, _ in live}) == len(live)


def test_a_prompt_over_a_rows_width_takes_a_row_a_round(packer, monkeypatch):
    """With 128 tokens a row, a prompt of 200 beside one of 60: one row
    each in the first call, and the long one's rest alone in the next
    round, never two rows of one call."""
    from test_llm_engine import record_prefill_calls

    from ray_tpu.models import mimo_v2 as dec

    seen = []
    record_prefill_calls(monkeypatch, dec, seen)
    with tokens_a_row(128):
        prompts, answers = ask_together(packer, (200, 60), max_new=5, seed=2)
    for prompt, tokens in zip(prompts, answers):
        assert_greedy_by_the_reference(packer, prompt, tokens)
    assert calls_of_rows(seen) == [(2, 128, [(0, 0, 128), (1, 0, 60)]),
                                   (1, 128, [(0, 128, 72)])]


def record_releases(monkeypatch, srv):
    """From here on every page the engine's pool is given back lands in the
    list returned: given back once means no page twice."""
    pool, released = srv._prefix_pool, []
    real_release = pool.release_pages
    monkeypatch.setattr(pool, "release_pages",
                        lambda pages: (released.extend(pages), real_release(pages))[1])
    return released


def programs_compiled(srv, prefill_paged):
    return prefill_paged._cache_size(), srv._sample_rows._cache_size()


def meets_every_call_of_rows(srv, dec, monkeypatch, loads, widths=(128, 256)):
    """After the engine has reported ready, ``loads`` (prompt lengths
    admitted together) meet every (R, P) the engine can dispatch, and
    neither ``prefill_paged`` nor the sampling behind it gains a compiled
    program: requests that come one at a time never meet a call of several
    rows, so the engine ran each on the scratch page before it took any."""
    from test_llm_engine import record_prefill_calls

    jitted = dec.prefill_paged
    before = programs_compiled(srv, jitted)
    seen = []
    record_prefill_calls(monkeypatch, dec, seen)
    for k, lengths in enumerate(loads):
        ask_together(srv, lengths, max_new=2, seed=1000 + k)
    assert programs_compiled(srv, jitted) == before
    met = {(R, P) for R, P, _ in calls_of_rows(seen)}
    assert met == {(R, P) for R in dec.PREFILL_ROWS for P in widths}


def test_a_load_that_meets_every_call_of_rows_compiles_nothing(packer, monkeypatch):
    from ray_tpu.models import mimo_v2 as dec

    meets_every_call_of_rows(
        packer, dec, monkeypatch,
        [(100,), (200,), (100, 90), (200, 150), (200, 150, 140)])


def test_a_sequence_cancelled_in_a_call_of_rows_gives_its_pages_back_once(packer, monkeypatch):
    """A request abandoned while its chunk is a row of a call in flight is
    reaped at the next round: its pages go back to the pool once, the
    others answer, and the pool ends with every page free."""
    from test_llm_engine import enqueue_together

    from ray_tpu.models import mimo_v2 as dec

    pool, released = packer._prefix_pool, record_releases(monkeypatch, packer)
    rng = np.random.default_rng(9)
    asks = [{"prompt_tokens": list(map(int, rng.integers(0, 256, n))), "max_new_tokens": 20}
            for n in (90, 60, 30)]
    real = dec.prefill_paged
    reqs = []

    def abandoned_in_flight(cfg, params, tokens, *rest):
        if tokens.shape[0] > 1:
            reqs[1].cancelled = True  # the client went away
        return real(cfg, params, tokens, *rest)

    monkeypatch.setattr(dec, "prefill_paged", abandoned_in_flight)
    reqs.extend(enqueue_together(packer, asks))
    for r in reqs:
        assert r.event.wait(300)
    assert [len(r.result or []) for r in (reqs[0], reqs[2])] == [20, 20]
    assert reqs[1].result is None
    assert len(released) == len(set(released)) > 0
    stats = pool.stats()
    assert stats["pages_occupied"] == 0 and pool.free_pages() == stats["pages_total"]


# -- the decode call before the wait for first tokens (PR 61) -----------------


@pytest.fixture(scope="module")
def streams():
    """The seeded cases on an engine nobody has asked anything."""
    import _engine_streams

    return _engine_streams.fresh_streams("mimo-v2-tiny")


@pytest.mark.parametrize("case", ["greedy_cold", "sampled_cold", "greedy_second_turn",
                                  "sampled_second_turn", "together", "one_token",
                                  "context_full"])
def test_seeded_requests_get_the_tokens_the_parent_gave(streams, case):
    import _engine_streams

    assert streams[case] == _engine_streams.expected("mimo-v2-tiny")[case]


def test_a_round_that_ends_a_prompt_hands_the_decode_call_over_before_it_waits(packer):
    """The device's calls of such a round are prefill, sample, the scatter
    of the changed rows, the placing of the first tokens and decode, and
    the ``first_token_sync`` span opens behind the ``dispatch`` span's end:
    the wait lies under a running step. One first token is counted ahead
    a sequence, and the chunk in flight is harvested behind the wait."""
    import _engine_streams

    ask_together(packer, (20,), max_new=2, seed=60)  # the step state is on the device
    ahead = _series("rt_serve_first_tokens_ahead_total")
    firsts = _series("rt_serve_tokens_generated_total")
    seen = []
    with _engine_streams.watch_the_round(packer, seen):
        prompts, answers = ask_together(packer, (100, 40), max_new=6, seed=61)
        time.sleep(0.1)
    for prompt, tokens in zip(prompts, answers):
        assert_greedy_by_the_reference(packer, prompt, tokens)
    assert _series("rt_serve_first_tokens_ahead_total") - ahead == 2
    assert _series("rt_serve_tokens_generated_total") - firsts == 12
    rounds = _engine_streams.rounds_of(seen)
    ending = [r for r in rounds if ("call", "sample") in r]
    assert len(ending) == 1
    calls = [name for kind, name in ending[0] if kind == "call"]
    assert calls == ["prefill", "sample", "scatter", "place", "decode"]
    order = [ev for ev in ending[0] if ev[0] != "call"]
    assert order == [("span", "admit"), ("end", "admit"), ("span", "prefill"), ("end", "prefill"),
                     ("span", "dispatch"), ("end", "dispatch"),
                     ("span", "first_token_sync"), ("end", "first_token_sync")]
    # inside the dispatch span: everything from the scatter on
    at = ending[0].index
    assert at(("span", "dispatch")) < at(("call", "scatter")) < at(("call", "decode")) < at(
        ("end", "dispatch"))
    # the next round harvests the chunk that carried them and waits for no first token
    after = rounds[rounds.index(ending[0]) + 1]
    assert ("span", "harvest_sync") in after and ("span", "first_token_sync") not in after


def test_an_answer_of_one_token_enters_no_decode_call_and_frees_its_pages_once(packer, monkeypatch):
    """``max_new`` of 1 (and of 0) ends at the first token: no row changes,
    no decode call is handed over for it, it is answered all the same, and
    its pages go back once."""
    import _engine_streams
    from test_llm_engine import enqueue_together

    pool, released = packer._prefix_pool, record_releases(monkeypatch, packer)
    ahead = _series("rt_serve_first_tokens_ahead_total")
    rng = np.random.default_rng(7)
    prompts = [list(map(int, rng.integers(0, 256, n))) for n in (50, 30)]
    seen = []
    with _engine_streams.watch_the_round(packer, seen):
        reqs = enqueue_together(packer, [
            {"prompt_tokens": prompts[0], "max_new_tokens": 1},
            {"prompt_tokens": prompts[1], "max_new_tokens": 0, "stream": True}])
        for r in reqs:
            assert r.event.wait(300) and r.error is None
        time.sleep(0.1)
    assert len(reqs[0].result) == 1 and reqs[1].result == []
    assert_greedy_by_the_reference(packer, prompts[0], reqs[0].result)
    assert reqs[1].token_q.get(timeout=5) is None  # the token nobody asked for is not sent
    calls = [name for kind, name in seen if kind == "call"]
    assert calls == ["prefill", "sample"]
    assert _series("rt_serve_first_tokens_ahead_total") == ahead
    assert len(released) == len(set(released)) > 0
    assert pool.stats()["pages_occupied"] == 0


def test_a_round_that_raises_behind_its_decode_call_fails_every_request_and_frees_once(
        packer, monkeypatch):
    """The wait for first tokens now stands between the decode call's
    hand-over and the harvest of the chunk before it. Where it raises, the
    sequences in rows, the chunk in flight and the chunk that was neither in
    flight nor harvested are all failed (nobody waits out a timeout), every
    page goes back once, and the engine answers the next request."""
    from test_llm_engine import enqueue_together

    pool, released = packer._prefix_pool, record_releases(monkeypatch, packer)

    class Broken:
        def put(self, tok):
            if tok is not None:
                raise RuntimeError("the stream's queue is gone")

    rng = np.random.default_rng(8)
    short, long = (list(map(int, rng.integers(0, 256, n))) for n in (40, 200))
    with tokens_a_row(128):
        # the short prompt ends in the first round and its last step is the
        # chunk in flight when the long one's first token lands, a round later
        reqs = [packer._parse({"prompt_tokens": short, "max_new_tokens": 2}),
                packer._parse({"prompt_tokens": long, "max_new_tokens": 9, "stream": True})]
        real_q, reqs[1].token_q = reqs[1].token_q, Broken()
        with packer._lock:
            packer._queue.extend(reqs)
        packer._work.set()
        for r in reqs:
            assert r.event.wait(20), "a request was left waiting"
    assert all(isinstance(r.error, RuntimeError) for r in reqs), [r.error for r in reqs]
    assert len(released) == len(set(released)) > 0
    deadline = time.monotonic() + 20
    while pool.stats()["pages_occupied"] and time.monotonic() < deadline:
        time.sleep(0.01)
    assert pool.stats()["pages_occupied"] == 0
    reqs[1].token_q = real_q
    (prompt,), (answer,) = ask_together(packer, (60,), max_new=5, seed=62)
    assert_greedy_by_the_reference(packer, prompt, answer)


def test_a_request_cancelled_with_its_first_token_on_the_device_frees_its_pages_once(
        packer, monkeypatch):
    """Cancelled between the sampling of its first token and the wait for
    it: the decode call carries the row, the token is still delivered, the
    next round reaps the row and drops its tokens of the chunk in flight, and
    the pages go back once that chunk is harvested."""
    from test_llm_engine import enqueue_together

    pool, released = packer._prefix_pool, record_releases(monkeypatch, packer)
    rng = np.random.default_rng(10)
    asks = [{"prompt_tokens": list(map(int, rng.integers(0, 256, n))), "max_new_tokens": 12,
             "stream": True} for n in (70, 45)]
    real = packer._sample_rows
    reqs = []

    def gone_as_it_is_sampled(*args):
        reqs[0].cancelled = True
        return real(*args)

    monkeypatch.setattr(packer, "_sample_rows", gone_as_it_is_sampled)
    reqs.extend(enqueue_together(packer, asks))
    for r in reqs:
        assert r.event.wait(300)
    assert reqs[0].result is None and len(reqs[1].result) == 12
    assert isinstance(reqs[0].token_q.get(timeout=5), int)  # its first token came
    assert len(released) == len(set(released)) > 0
    deadline = time.monotonic() + 20
    while pool.stats()["pages_occupied"] and time.monotonic() < deadline:
        time.sleep(0.01)
    assert pool.stats()["pages_occupied"] == 0 and pool.free_pages() == pool.stats()["pages_total"]
