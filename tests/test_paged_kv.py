"""One paged KV pool (serve/prefix_cache.PagedKVPool + the paged engine
in serve/llm.py): the allocator contract (scratch page 0, all-or-nothing
alloc, refcount pins, seal-no-copy, global LRU over unpinned sealed
pages), and the serving guarantees the engine promises — the full
forward's greedy tokens at temperature=0, bitwise identity hit-vs-cold
and chunked-vs-unchunked, disagg import vs monolithic; a
prefix hit is a refcount bump with ZERO block copies; admission is
page-granular (oversize fails fast, pressure defers in FIFO order);
pages are released exactly once under cancel/unload races; and chunked
prefill keeps a live stream producing while a long prompt prefills."""

import collections
import threading
import time

import numpy as np
import pytest
from _llm_reference import engine_reference

from ray_tpu.serve.prefix_cache import PagedKVPool


# ---------------------------------------------------------------------------
# pool unit tests (no jax, no engine)
# ---------------------------------------------------------------------------


def test_pool_scratch_page_never_allocated():
    pool = PagedKVPool("m", num_pages=5, page_tokens=4)
    got = pool.alloc(4)
    assert sorted(got) == [1, 2, 3, 4]  # page 0 reserved as scratch
    assert pool.alloc(1) is None  # everything pinned: nothing evictable
    pool.release_pages(got)
    assert pool.free_pages() == 4
    with pytest.raises(ValueError):
        PagedKVPool("m", num_pages=1, page_tokens=4)  # scratch-only
    pool.close()


def test_pool_alloc_is_all_or_nothing():
    pool = PagedKVPool("m", num_pages=4, page_tokens=4)  # 3 usable
    held = pool.alloc(2)
    assert pool.alloc(2) is None  # only 1 free: takes NOTHING
    assert pool.free_pages() == 1
    assert pool.alloc(0) == []
    pool.release_pages(held)
    pool.close()


def test_pool_seal_match_is_zero_copy_refcount():
    pool = PagedKVPool("m", num_pages=4, page_tokens=4)
    (pg,) = pool.alloc(1)
    assert pool.seal("d1", pg) is True
    # a racing request sealing the same digest loses: its page stays
    # private and returns to the free list on release
    (other,) = pool.alloc(1)
    assert pool.seal("d1", other) is False
    pool.release_pages([other])
    assert pool.free_pages() == 2
    pool.release_pages([pg])
    # ref-0 SEALED page stays resident — that residency is the cache
    assert pool.resident() == 1 and pool.free_pages() == 2
    held, pages = pool.match_pages(["d1"], max_tokens=100)
    assert held == ["d1"] and pages == [pg]
    assert pool.ref_count("d1") == 1
    # fewer usable tokens than one page -> nothing matched
    assert pool.match_pages(["d1"], max_tokens=3) == ([], [])
    pool.release_pages(pages)
    pool.close()


def test_pool_lru_evicts_only_unpinned_sealed():
    pool = PagedKVPool("m", num_pages=3, page_tokens=4)  # 2 usable
    a, b = pool.alloc(2)
    pool.seal("a", a)
    pool.seal("b", b)
    pool.release_pages([b])  # b: ref-0 sealed -> LRU-evictable
    (c,) = pool.alloc(1)  # free list dry: must evict b, never pinned a
    assert c == b and pool.stats()["evictions"] == 1
    assert pool.match_pages(["b"], 100) == ([], [])
    assert pool.ref_count("a") == 1
    assert pool.alloc(1) is None  # everything pinned again: defer
    pool.release_pages([a, c])
    pool.close()


def test_pool_reset_and_close_drop_everything():
    pool = PagedKVPool("m", num_pages=4, page_tokens=4)
    pgs = pool.alloc(2)
    pool.seal("x", pgs[0])
    pool.reset()  # poisoned engine round: device cache was rebuilt
    assert pool.free_pages() == 3 and pool.resident() == 0
    assert pool.match_pages(["x"], 100) == ([], [])
    pgs = pool.alloc(3)
    pool.close()
    assert pool.alloc(1) is None  # closed pools never hand out pages
    pool.release_pages(pgs)  # post-close release must be a no-op
    assert pool.free_pages() == 0


# ---------------------------------------------------------------------------
# the loop's uploads (no engine)
# ---------------------------------------------------------------------------


def _aligned(shape, dtype, align=64):
    """A NumPy array whose data pointer is ``align``-byte aligned: the
    case in which the CPU backend shares memory instead of copying.
    NumPy's own allocator gives 16 bytes, so a plain array is shared in
    some processes and copied in others."""
    n = int(np.prod(shape)) * np.dtype(dtype).itemsize
    buf = np.zeros(n + align, np.uint8)
    off = (-buf.ctypes.data) % align
    out = buf[off:off + n].view(dtype).reshape(shape)
    assert out.ctypes.data % align == 0
    return out


def test_upload_never_shares_the_loops_host_mirrors():
    """The engine loop zeroes a row of ``tables``/``lengths``/``last``
    when a request retires at dispatch, before the dispatched program
    has run; a device array that shared the mirror's memory would then
    read a zeroed page table (the ``[x, 0, 0, ...]`` answers). What
    ``_upload`` returns must not change when its inputs are written."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.serve.llm import _upload

    S, max_pages = 24, 16
    host = [
        _aligned((S,), np.int32), _aligned((S,), np.int32),
        _aligned((S,), np.float32), _aligned((S,), bool),
        _aligned((S, max_pages), np.int32),
    ]
    for a in host:
        a[...] = 1
    dev = _upload(*host)
    for a in host:
        a[...] = 0  # what retire() does to a row, to all of them
    for d, a in zip(dev, host):
        got = np.asarray(d)
        assert got.shape == a.shape and got.dtype == a.dtype
        assert got.all(), f"{a.dtype}{a.shape} was shared, not copied"


# ---------------------------------------------------------------------------
# engine-level: bitwise identity, zero-copy hits, admission, releases
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def paged_engine():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    srv = LLMServer(LLMConfig(
        model_id="gpt2-tiny", max_batch_size=4,
    ))
    yield srv
    srv._stop.set()


def _req(prompt, max_new=8, **extra):
    return {"prompt_tokens": prompt, "max_new_tokens": max_new,
            "temperature": 0.0, **extra}


def test_engine_says_which_decode_attention_it_runs(paged_engine):
    """The engine's decode programs attend over the pool itself."""
    assert paged_engine.batch_stats()["decode_attention"] == "pool"


@pytest.mark.parametrize("n", [10, 64, 100, 127])
def test_paged_engine_matches_full_forward(paged_engine, n):
    """At temperature=0 the engine's page-table scatter and pool
    attention must generate EXACTLY the full forward's greedy tokens —
    short, block-spanning and window-filling prompts (the 127-token one
    leaves room for a single token)."""
    rng = np.random.RandomState(31)
    prompt = [int(t) for t in rng.randint(0, 256, 127)][:n]
    want = engine_reference(paged_engine, prompt, 8)
    assert len(want) == min(8, 128 - n)
    assert paged_engine(_req(prompt))["tokens"] == want, (
        f"engine != full forward at prompt len {n}"
    )


def test_prefix_hit_is_bitwise_and_copies_nothing(paged_engine):
    """The acceptance property: a repeat prompt admits from resident
    pages (refcount bump) and generates the cold answer bit for bit."""
    pool = paged_engine._prefix_pool
    rng = np.random.RandomState(32)
    prompt = [int(t) for t in rng.randint(0, 256, 100)]
    h0 = pool.stats()["hits"]
    cold = paged_engine(_req(prompt))["tokens"]
    hot = paged_engine(_req(prompt))["tokens"]
    st = pool.stats()
    assert hot == cold
    assert st["hits"] > h0  # the repeat came from the pool


def test_chunked_vs_unchunked_prefill_bitwise(paged_engine):
    """RT_SERVE_PREFILL_CHUNK_TOKENS only reorders WHEN prompt tokens
    prefill (across engine rounds), never what they produce: cold
    generations with a 16-token chunk budget match unchunked ones
    exactly (prefix cache off so both runs genuinely prefill)."""
    from ray_tpu.utils.config import config

    rng = np.random.RandomState(33)
    prompt = [int(t) for t in rng.randint(0, 256, 100)]
    config.set("serve_prefix_cache", False)
    try:
        config.set("serve_prefill_chunk_tokens", 16)
        chunked = paged_engine(_req(prompt))["tokens"]
        config.set("serve_prefill_chunk_tokens", 0)
        unchunked = paged_engine(_req(prompt))["tokens"]
    finally:
        config.set("serve_prefill_chunk_tokens", 512)
        config.set("serve_prefix_cache", True)
    assert chunked == unchunked


def test_page_gauges_are_published_and_slot_gauges_are_not(paged_engine):
    """rt_serve_kv_pages_* gauges carry the engine's KV occupancy (the
    serve_kv_occupancy alert rule, ``rt top`` and the autoscale policy
    read them); the slot engine's gauge names went with it."""
    from ray_tpu.utils import metrics as umetrics

    paged_engine(_req([3, 1, 4], max_new=2))
    snap = umetrics.snapshot_all()
    for name in ("rt_serve_kv_pages_total", "rt_serve_kv_pages_occupied",
                 "rt_serve_kv_pages_prefix_resident"):
        assert snap.get(name, {}).get("series"), f"{name} not published"
    pool = paged_engine._prefix_pool.stats()
    assert pool["pages_total"] in snap["rt_serve_kv_pages_total"][
        "series"].values()
    for name in ("rt_serve_kv_slots_occupied", "rt_serve_kv_slots_total"):
        assert name not in snap, f"{name} is still exported"


def test_page_admission_defers_under_pressure_and_fails_oversize():
    """A pool shrunk to 2 usable pages: two 2-page requests can never
    coexist, so the second DEFERS (requeued at the front) and completes
    after the first frees its pages — while a request that could never
    fit (3 pages) fails immediately instead of spinning forever."""
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    from ray_tpu.utils.config import config

    config.set("serve_kv_pool_pages", 2)
    try:
        srv = LLMServer(LLMConfig(
            model_id="gpt2-tiny", max_batch_size=4,
        ))
    finally:
        config.set("serve_kv_pool_pages", 0)
    try:
        rng = np.random.RandomState(35)
        prompts = {
            "a": [int(t) for t in rng.randint(0, 256, 70)],
            "b": [int(t) for t in rng.randint(0, 256, 70)],
        }
        results = {}

        def call(key):
            results[key] = srv(_req(prompts[key]))["tokens"]

        threads = [
            threading.Thread(target=call, args=(k,)) for k in prompts
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert set(results) == {"a", "b"}
        assert all(len(v) == 8 for v in results.values())
    finally:
        srv._stop.set()

    # oversize fail-fast: gpt2-tiny requests span at most 2 pages, so
    # shrink the pool to ONE usable page — a 2-page ask can never fit
    # and must error immediately instead of deferring forever
    config.set("serve_kv_pool_pages", 1)
    try:
        tiny = LLMServer(LLMConfig(
            model_id="gpt2-tiny", max_batch_size=4,
        ))
    finally:
        config.set("serve_kv_pool_pages", 0)
    try:
        assert len(tiny(_req([2] * 40, max_new=8))["tokens"]) == 8
        with pytest.raises(RuntimeError, match="KV pages"):
            tiny(_req([1] * 70))  # needs 2 pages, pool has 1
    finally:
        tiny._stop.set()


def test_pages_released_exactly_once_under_cancel_and_unload():
    """Satellite: however finish/cancel/unload race for a sequence, its
    pages return to the pool exactly once. Pin it by counting handouts
    (alloc + match pins) vs returns per page — a double release would
    return a page more times than it was ever handed out — and by the
    free-list/refcount invariants after a cancelled stream drains."""
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    srv = LLMServer(LLMConfig(
        model_id="gpt2-tiny", max_batch_size=4,
    ))
    pool = srv._prefix_pool
    handout = collections.Counter()
    returned = collections.Counter()
    orig_alloc, orig_match = pool.alloc, pool.match_pages
    orig_release = pool.release_pages

    def spy_alloc(n):
        out = orig_alloc(n)
        if out:
            handout.update(out)
        return out

    def spy_match(digests, max_tokens):
        held, pages = orig_match(digests, max_tokens)
        handout.update(pages)
        return held, pages

    def spy_release(pages):
        returned.update(pages)
        orig_release(pages)

    pool.alloc, pool.match_pages = spy_alloc, spy_match
    pool.release_pages = spy_release
    try:
        rng = np.random.RandomState(36)
        prompt = [int(t) for t in rng.randint(0, 256, 70)]
        gen = srv(_req(prompt, max_new=64, stream=True))
        it = iter(gen)
        next(it)
        next(it)  # the sequence is live in the decode batch
        gen.close()  # client disconnect: cancel mid-generation
        # a follow-up request forces a reap round and must complete
        out = srv(_req(prompt[:10], max_new=4))
        assert len(out["tokens"]) == 4
        with pool._lock:
            free = list(pool._free)
            pinned = {p.idx: p.refs for p in pool._pages if p.refs}
        assert len(free) == len(set(free)), free  # no duplicate frees
        assert not pinned, pinned  # cancel left no page pinned
        st = pool.stats()
        assert st["pages_free"] + st["pages_occupied"] == st["pages_total"]
        assert st["pages_occupied"] == st["prefix_resident"]
    finally:
        srv.unload()
    # unload raced the engine loop's exit path over the same sequences;
    # give the loop a beat to run it, then check the exactly-once books
    time.sleep(0.5)
    for page, n_returned in returned.items():
        assert n_returned <= handout[page], (
            f"page {page} released {n_returned}x but handed out only "
            f"{handout[page]}x"
        )


def test_chunked_prefill_keeps_live_stream_producing():
    """The ITL bound: while a 900-token prompt prefills in 64-token
    chunks, an already-streaming sequence keeps producing tokens — the
    chunks interleave with decode steps instead of stalling every live
    stream for the whole prefill. (Unchunked, the long prefill is one
    engine round and the stream would get ~1 token in this window.)"""
    from ray_tpu.models import gpt2
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    from ray_tpu.utils.config import config

    gpt2.CONFIGS.setdefault("gpt2-tiny-long", gpt2.GPT2Config(
        vocab_size=256, n_positions=1024, d_model=64, n_layer=2,
        n_head=4, remat=False,
    ))
    config.set("serve_prefill_chunk_tokens", 64)
    srv = None
    try:
        srv = LLMServer(LLMConfig(
            model_id="gpt2-tiny-long", max_batch_size=4,
        ))
        rng = np.random.RandomState(37)
        short = [int(t) for t in rng.randint(0, 256, 16)]
        longp = [int(t) for t in rng.randint(0, 256, 900)]
        gen = srv(_req(short, max_new=64, stream=True))
        it = iter(gen)
        next(it)  # stream live in the decode batch
        done = threading.Event()
        res = {}

        def call_long():
            res["out"] = srv(_req(longp, max_new=4))
            done.set()

        threading.Thread(target=call_long, daemon=True).start()
        during = 0
        while not done.is_set():
            tok = next(it, None)
            if tok is None:
                break
            during += 1
        gen.close()
        assert done.wait(120) and len(res["out"]["tokens"]) == 4
        # ~14 chunks * >=1 interleaved decode step each: the live
        # stream must have advanced repeatedly DURING the long prefill
        assert during >= 3, f"stream produced {during} tokens"
    finally:
        config.set("serve_prefill_chunk_tokens", 512)
        if srv is not None:
            srv._stop.set()
