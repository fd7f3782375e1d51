"""KV-cache decode engine (models/gpt2_decode.py + serve/llm.py's loop).

Parity model: the engine-level tests vLLM supplies for the reference's
serve.llm — prefill/decode equivalence, row isolation, continuous
batching.
"""

import numpy as np
import pytest
from _llm_reference import greedy_reference


@pytest.fixture(scope="module")
def tiny():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.models import gpt2

    cfg = gpt2.CONFIGS["gpt2-tiny"]
    params = gpt2.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _paged(cfg, S, rows, pages_per_row, page_tokens=16):
    """A pool and the page tables of ``S`` decode rows, of which
    ``rows`` are live on disjoint pages; the others keep all-zero tables
    and scatter into page 0, the scratch page."""
    from ray_tpu.models import gpt2_decode as dec

    max_pages = -(-cfg.n_positions // page_tokens)
    ck, cv = dec.init_paged_cache(
        cfg, 1 + len(rows) * pages_per_row, page_tokens
    )
    tables = np.zeros((S, max_pages), np.int32)
    for j, r in enumerate(rows):
        first = 1 + j * pages_per_row
        tables[r, :pages_per_row] = np.arange(first, first + pages_per_row)
    return ck, cv, tables


def _prefill(cfg, params, prompt, ck, cv, table):
    import jax.numpy as jnp

    from ray_tpu.models import gpt2_decode as dec

    tok = np.zeros((1, 16), np.int32)
    tok[0, : len(prompt)] = prompt
    return dec.prefill_paged(
        cfg, params, jnp.asarray(tok), jnp.int32(0), jnp.int32(len(prompt)),
        ck, cv, jnp.asarray(table),
    )


def test_paged_decode_matches_full_forward(tiny):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2_decode as dec

    cfg, params = tiny
    rng = np.random.RandomState(7)
    prompt = list(rng.randint(0, cfg.vocab_size, 12))
    ref = greedy_reference(cfg, params, prompt, 6)

    S = 4
    ck, cv, tables = _paged(cfg, S, rows=[1], pages_per_row=2)
    logits0, ck, cv = _prefill(cfg, params, prompt, ck, cv, tables[1])
    out = [int(jnp.argmax(logits0))]
    last = np.zeros((S,), np.int32)
    lengths = np.zeros((S,), np.int32)
    last[1] = out[0]
    lengths[1] = len(prompt)
    step = jax.jit(dec._decode_paged_impl, static_argnums=(0,))
    for _ in range(5):
        logits, ck, cv = step(
            cfg, params, jnp.array(last), jnp.array(lengths), ck, cv,
            jnp.array(tables),
        )
        nxt = int(jnp.argmax(logits[1]))
        out.append(nxt)
        last[1] = nxt
        lengths[1] += 1
    assert out == ref


def test_paged_rows_are_isolated(tiny):
    """Two different prompts decoding in different rows of one pool, on
    disjoint pages, must each match their own single-sequence
    reference."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2_decode as dec

    cfg, params = tiny
    rng = np.random.RandomState(11)
    prompts = [list(rng.randint(0, cfg.vocab_size, 9)),
               list(rng.randint(0, cfg.vocab_size, 14))]
    refs = [greedy_reference(cfg, params, p, 4) for p in prompts]

    S = 3  # row 1 stays empty between the two
    ck, cv, tables = _paged(cfg, S, rows=[0, 2], pages_per_row=2)
    last = np.zeros((S,), np.int32)
    lengths = np.zeros((S,), np.int32)
    outs = {0: [], 2: []}
    for row, p in zip((0, 2), prompts):
        logits0, ck, cv = _prefill(cfg, params, p, ck, cv, tables[row])
        first = int(jnp.argmax(logits0))
        outs[row].append(first)
        last[row] = first
        lengths[row] = len(p)
    step = jax.jit(dec._decode_paged_impl, static_argnums=(0,))
    for _ in range(3):
        logits, ck, cv = step(
            cfg, params, jnp.array(last), jnp.array(lengths), ck, cv,
            jnp.array(tables),
        )
        for row in (0, 2):
            nxt = int(jnp.argmax(logits[row]))
            outs[row].append(nxt)
            last[row] = nxt
            lengths[row] += 1
    assert outs[0] == refs[0]
    assert outs[2] == refs[1]


def test_kv_engine_continuous_batching():
    """Server-level: staggered requests share decode steps (continuous
    batching) and produce the same tokens as solo runs."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import threading

    from ray_tpu.serve.llm import LLMConfig, LLMServer

    srv = LLMServer(LLMConfig(model_id="gpt2-tiny", max_batch_size=4))
    solo = [srv({"prompt_tokens": [i, i + 1], "max_new_tokens": 12})
            for i in range(3)]

    results = [None] * 3

    def call(i):
        results[i] = srv(
            {"prompt_tokens": [i, i + 1], "max_new_tokens": 12}
        )

    threads = [threading.Thread(target=call, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    for i in range(3):
        assert results[i] is not None
        assert results[i]["tokens"] == solo[i]["tokens"]
    stats = srv.batch_stats()
    assert stats["max_batch"] >= 2, stats
    srv._stop.set()


def test_engine_reports_the_pool_it_holds():
    """``batch_stats()`` names the shape one page pool is stored in and
    the bytes the device holds for both: what ``init_paged_cache``
    returns for the engine's page count, so that a pool that went back
    to a padded or relaid form shows without a trace."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.models import gpt2_decode as dec
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    srv = LLMServer(LLMConfig(model_id="gpt2-tiny", max_batch_size=2))
    try:
        pool = srv._prefix_pool
        ck, cv = dec.init_paged_cache(
            srv.model_cfg, pool.num_pages, pool.page_tokens
        )
        stats = srv.batch_stats()
        held = jax.tree.leaves((ck, cv))
        assert stats["kv_pool_shape"] == list(held[0].shape)
        assert stats["kv_pool_bytes"] == sum(
            a.on_device_size_in_bytes() for a in held
        )
        # on the CPU nothing is tiled: the bytes are those of two pools
        # of L x pages x page_tokens x width elements
        mcfg = srv.model_cfg
        assert stats["kv_pool_bytes"] == (
            2 * mcfg.n_layer * pool.num_pages * pool.page_tokens
            * mcfg.n_head * mcfg.head_dim * held[0].dtype.itemsize
        )
    finally:
        srv._stop.set()


def test_every_prefill_width_is_a_power_of_two(monkeypatch):
    """A tail deep in a context (80 tokens cached of 128, 40 to prefill:
    the next power of two, 64, would pass the 48 positions left) is
    prefilled in calls of 32 and 16, not in one of 48: a width that is
    no power of two is a program no warm-up names, compiled under load
    where it is first met. The answer is the full forward's."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from _llm_reference import engine_reference

    from ray_tpu.models import gpt2_decode as dec
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    from ray_tpu.utils.config import config

    keep = config.serve_prefix_block_tokens
    config.set("serve_prefix_block_tokens", 16)
    try:
        srv = LLMServer(LLMConfig(model_id="gpt2-tiny", max_batch_size=2))
    finally:
        config.set("serve_prefix_block_tokens", keep)
    widths = []
    real = dec.prefill_paged

    def seen(cfg, params, tokens, start, *rest):
        widths.append((int(start), tokens.shape[1]))
        return real(cfg, params, tokens, start, *rest)

    monkeypatch.setattr(dec, "prefill_paged", seen)
    rng = np.random.RandomState(36)
    prompt = [int(t) for t in rng.randint(0, 256, 120)]
    try:
        srv({"prompt_tokens": prompt[:80], "max_new_tokens": 2,
             "temperature": 0.0})
        del widths[:]
        out = srv({"prompt_tokens": prompt, "max_new_tokens": 6,
                   "temperature": 0.0})
        assert widths == [(80, 32), (112, 16)]
        assert out["tokens"] == engine_reference(srv, prompt, 6)
    finally:
        srv._stop.set()


@pytest.mark.parametrize("kwargs, removed", [
    ({"engine": "recompute"}, "recompute"),
    ({"paged_kv": False}, "slot KV engine"),
    ({"async_decode": False}, "synchronous decode loop"),
])
def test_config_refuses_the_removed_engines_by_name(kwargs, removed):
    """The three keywords that once chose an engine select nothing now:
    the values that name the only engine are accepted (the benchmark's
    configuration files pass them), and a value that asks for a path
    that was removed raises, naming it, instead of being ignored."""
    from ray_tpu.serve.llm import LLMConfig

    cfg = LLMConfig(model_id="gpt2-tiny", engine="kv", paged_kv=True,
                    async_decode=True)
    assert not {"engine", "paged_kv", "async_decode"} & set(vars(cfg))
    (key, value), = kwargs.items()
    with pytest.raises(ValueError, match=f"{key}={value!r}.*{removed}"):
        LLMConfig(model_id="gpt2-tiny", **kwargs)


def enqueue_together(srv, asks):
    """``asks`` reach the engine's queue in one go, so that one round
    admits them all; returns their requests (wait on ``event``)."""
    reqs = [srv._parse(ask) for ask in asks]
    with srv._lock:
        srv._queue.extend(reqs)
    srv._work.set()
    return reqs


def record_prefill_calls(monkeypatch, dec, seen):
    """From here on every ``prefill_paged`` call leaves (tokens' shape,
    start, length, the page table's shape, row) in ``seen``, arrays as
    lists."""
    real = dec.prefill_paged

    def recorded(cfg, params, tokens, start, length, k, v, table, row=0):
        seen.append((tuple(tokens.shape), np.asarray(start).tolist(),
                     np.asarray(length).tolist(), tuple(table.shape),
                     np.asarray(row).tolist()))
        return real(cfg, params, tokens, start, length, k, v, table, row)

    monkeypatch.setattr(dec, "prefill_paged", recorded)


def test_a_module_of_one_row_makes_the_calls_it_always_made(monkeypatch):
    """GPT-2's decode module says one row a prefill call
    (``PREFILL_ROWS``), and the engine keeps it on the path of one call a
    sequence and chunk: four prompts admitted in one round are prefilled
    in the order of their rows under 64 tokens a round for all of them
    together, each call [1, P] with scalar start, length and row and a
    table of one row, as before PR 50."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.models import gpt2_decode as dec
    from ray_tpu.observability import core_metrics
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    from ray_tpu.utils.config import config

    assert dec.PREFILL_ROWS == (1,)
    keep = config.serve_prefix_block_tokens, config.serve_prefill_chunk_tokens
    config.set("serve_prefix_block_tokens", 16)
    config.set("serve_prefill_chunk_tokens", 64)
    seen = []
    try:
        srv = LLMServer(LLMConfig(model_id="gpt2-tiny", max_batch_size=4))
        record_prefill_calls(monkeypatch, dec, seen)
        calls = core_metrics.serve_prefill_calls.snapshot()["series"]
        before = sum(calls.values())
        rng = np.random.RandomState(50)
        reqs = enqueue_together(srv, [
            {"prompt_tokens": [int(t) for t in rng.randint(0, 256, n)], "max_new_tokens": 3}
            for n in (40, 10, 30, 5)])
        for r in reqs:
            assert r.event.wait(120) and r.error is None and len(r.result) == 3
    finally:
        config.set("serve_prefix_block_tokens", keep[0])
        config.set("serve_prefill_chunk_tokens", keep[1])
        srv._stop.set()
    one_row = (8,)  # 128 positions in pages of 16
    # round one: 40 of 64 tokens, 10, then 14 of the third prompt's 30;
    # round two: its other 16, and the fourth prompt
    assert seen == [
        ((1, 64), 0, 40, one_row, 0), ((1, 16), 0, 10, one_row, 1),
        ((1, 16), 0, 14, one_row, 2), ((1, 16), 14, 16, one_row, 2),
        ((1, 16), 0, 5, one_row, 3)]
    if core_metrics.ENABLED:
        snap = lambda m: sum(m.snapshot()["series"].values())
        assert snap(core_metrics.serve_prefill_calls) - before == 5


W = (128, 256, 512)
# (what waits: tokens left a sequence, row counts, several rows a sequence)
# -> (rows of the call, width, tokens a row by sequence)
PLANS = {
    "a_lone_chunk_takes_the_narrowest_width_that_holds_it": (
        (40,), (1, 4), True, (1, 128, [(0, 40)])),
    "a_tail_under_a_rows_width_is_one_row_not_rows_of_less": (
        (300,), (1, 2, 4), True, (1, 512, [(0, 300)])),
    "a_tail_over_a_rows_width_is_rows_of_one_call": (
        (600,), (1, 2, 4), True, (2, 512, [(0, 512), (0, 88)])),
    "without_a_call_of_two_rows_the_tail_is_three_of_four_rows_of_half_the_width": (
        (600,), (1, 4), True, (4, 256, [(0, 256), (0, 256), (0, 88)])),
    "two_tails_share_a_call": (
        (600, 600), (1, 4), True, (4, 512, [(0, 512), (0, 88), (1, 512), (1, 88)])),
    "a_tail_that_does_not_fit_behind_the_others_waits_whole": (
        (1100, 600, 90), (1, 4), True, (4, 512, [(0, 512), (0, 512), (0, 76), (2, 90)])),
    "only_the_first_tail_is_ever_cut": (
        (2100, 600), (1, 4), True, (4, 512, [(0, 512)] * 4)),
    "rings_a_row_give_a_sequence_one_row_of_a_call": (
        (600, 100), (1, 2, 4), False, (2, 512, [(0, 512), (1, 100)])),
    "two_rows_of_no_length_cost_more_than_a_call_of_two": (
        (300, 280), (1, 2, 4), False, (2, 512, [(0, 300), (1, 280)])),
    "more_sequences_than_rows_wait_a_round": (
        (90, 90, 90, 90, 90), (1, 2, 4), False, (4, 128, [(0, 90), (1, 90), (2, 90), (3, 90)])),
}


@pytest.mark.parametrize("case", sorted(PLANS))
def test_the_rows_of_a_prefill_call(case):
    """``_plan_prefill_rows``: what one call takes of what waits."""
    from ray_tpu.serve.llm import _plan_prefill_rows

    left, row_counts, several, (R, P, taken) = PLANS[case]
    pending = [(10 + i, 64 * i, n) for i, n in enumerate(left)]
    got_R, got_P, rows = _plan_prefill_rows(pending, W, row_counts, several, 512)
    assert (got_R, got_P) == (R, P)
    assert [(i - 10, n) for i, _, n in rows] == taken
    # a sequence's rows follow one another from where its prefill stands
    at = {i: pos for i, pos, _ in pending}
    for i, start, n in rows:
        assert start == at[i]
        at[i] += n


def test_a_row_holds_at_most_the_tokens_a_round_allows_whatever_its_width():
    from ray_tpu.serve.llm import _plan_prefill_rows

    R, P, rows = _plan_prefill_rows([(0, 0, 300), (1, 0, 50)], W, (1, 2, 4), True, 64)
    assert (R, P) == (4, 128) and [n for _, _, n in rows] == [64, 64, 64, 64]
