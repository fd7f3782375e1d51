"""Prefix KV cache (serve/prefix_cache.py + the engine's prefix-aware
admission): chain-hash determinism (including across processes — the
router's affinity hint and multi-replica pools depend on it) and the
serving guarantee: admitting a request from cached pages produces
bitwise-identical generations at temperature=0, under row churn, and
with the kill switch flipped. The pool's own refcount/LRU contract is
held by tests/test_paged_kv.py."""

import json
import subprocess
import sys
import threading

import numpy as np
import pytest
from _llm_reference import engine_reference

from ray_tpu.serve.prefix_cache import PagedKVPool, hash_blocks


# ---------------------------------------------------------------------------
# chain hashing
# ---------------------------------------------------------------------------


def test_hash_blocks_only_full_blocks():
    assert hash_blocks([], 4) == []
    assert hash_blocks([1, 2, 3], 4) == []
    assert len(hash_blocks(list(range(10)), 4)) == 2
    assert len(hash_blocks(list(range(8)), 4)) == 2


def test_hash_blocks_chain_prefix_property():
    a = hash_blocks([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12], 4)
    b = hash_blocks([1, 2, 3, 4, 5, 6, 7, 8, 99, 99, 99, 99], 4)
    assert a[:2] == b[:2] and a[2] != b[2]
    # the chain: a different FIRST block changes every downstream digest
    c = hash_blocks([9, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12], 4)
    assert all(x != y for x, y in zip(a, c))


def test_hash_blocks_deterministic_across_processes():
    """Digests are pure content hashes — another interpreter produces
    exactly the same chain (no pid/seed/hash-randomization leakage), so
    pools on different replicas agree on block identity."""
    tokens = [int(t) for t in np.random.RandomState(3).randint(0, 256, 200)]
    prog = (
        "import json, sys; from ray_tpu.serve.prefix_cache import "
        "hash_blocks; print(json.dumps(hash_blocks(json.loads("
        "sys.argv[1]), 64)))"
    )
    out = subprocess.run(
        [sys.executable, "-c", prog, json.dumps(tokens)],
        capture_output=True, text=True, check=True,
    )
    assert json.loads(out.stdout) == hash_blocks(tokens, 64)


# ---------------------------------------------------------------------------
# the pool's eviction order: the pages kept in order against the scan
# ---------------------------------------------------------------------------


class ScanningPool(PagedKVPool):
    """The reference: the pool as it evicted before its evictable pages were
    kept in order, one scan of every sealed page for the ref-0 one of the
    lowest tick a page evicted."""

    def _evict_one_locked(self):
        victim = None
        for idx in self._sealed.values():
            pg = self._pages[idx]
            if pg.refs == 0 and (victim is None or pg.tick < victim.tick):
                victim = pg
        if victim is None:
            return False
        del self._sealed[victim.digest]
        victim.digest = None
        self._free.append(victim.idx)
        self.evictions += 1
        return True


def churn(pool, seed, steps=600):
    """A seeded walk of requests over ``pool``: admissions that match a
    chain's resident prefix and allocate the rest (refused whole where the
    pool cannot cover them), seals of what an admitted request wrote,
    releases in any order, now and then a reset. What every call returned,
    the pool's stats after each, and the (digest, page) pairs in the order
    they were evicted."""
    rng = np.random.RandomState(seed)
    chains = [[f"c{c}-{j}" for j in range(12)] for c in range(10)]
    held, log, evicted = [], [], []
    evict_one = pool._evict_one_locked

    def watched():
        before = dict(pool._sealed)
        found = evict_one()
        evicted.extend((d, i) for d, i in before.items() if d not in pool._sealed)
        return found

    pool._evict_one_locked = watched
    for _ in range(steps):
        op = rng.choice(["admit", "seal", "release", "reset"], p=[0.36, 0.3, 0.33, 0.01])
        if op == "admit":
            chain = chains[rng.randint(len(chains))]
            want = int(rng.randint(1, len(chain) + 1))
            _, hits = pool.match_pages(chain[:want], max_tokens=want * pool.page_tokens)
            fresh = pool.alloc(want - len(hits) + int(rng.randint(0, 3)))
            if fresh is None:
                pool.release_pages(hits)
            else:
                held.append({"chain": chain, "pages": hits + fresh, "sealed": len(hits),
                             "want": want})
            log.append(("admit", hits, fresh))
        elif op == "seal" and held:
            req = held[rng.randint(len(held))]
            upto = int(rng.randint(req["sealed"], req["want"] + 1))
            log.append(("seal", [pool.seal(req["chain"][j], req["pages"][j])
                                 for j in range(req["sealed"], upto)]))
            req["sealed"] = upto
        elif op == "release" and held:
            req = held.pop(rng.randint(len(held)))
            pages = list(req["pages"])
            rng.shuffle(pages)
            pool.release_pages(pages)
            log.append(("release", pages))
        elif op == "reset":
            pool.reset()
            held.clear()
            log.append(("reset",))
        log.append(pool.stats())
    return log, evicted


@pytest.mark.parametrize("seed", range(8))
def test_the_pool_evicts_the_scans_victims_in_the_scans_order(seed):
    """Same victims, same order, same answers and stats as the scan gave,
    over seeded walks on a pool small enough that most admissions evict."""
    kept, scan = PagedKVPool(f"kept-{seed}", 64, 4), ScanningPool(f"scan-{seed}", 64, 4)
    try:
        got, want = churn(kept, seed), churn(scan, seed)
        assert got == want
        assert kept.stats()["evictions"] == scan.stats()["evictions"] > 50
        assert kept.stats()["hits"] > 100
        # and page for page the two pools hold the same
        assert [(p.refs, p.digest, p.tick) for p in kept._pages] == [
            (p.refs, p.digest, p.tick) for p in scan._pages]
        assert kept._free == scan._free and kept._sealed == scan._sealed
    finally:
        kept.close()
        scan.close()


class CountedPages(list):
    """A pool's pages, counting every look at one."""

    visits = 0

    def __getitem__(self, i):
        self.visits += 1
        return list.__getitem__(self, i)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]


def full_pool(n_pages, name):
    """Every page sealed and released: the free list dry, all evictable."""
    pool = PagedKVPool(name, n_pages + 1, 4)
    pages = pool.alloc(n_pages)
    for j, page in enumerate(pages):
        assert pool.seal(f"d{j}", page)
    pool.release_pages(pages)
    assert pool.free_pages() == 0 and pool.resident() == n_pages
    return pool, pages


@pytest.mark.parametrize("n", [1, 10, 80])
def test_an_alloc_on_a_full_pool_looks_at_the_pages_it_takes(n):
    """Counted in looks at a page, never by a clock: evicting n pages of
    2,000 sealed ones looks at n, and handing them out at n more; the scan
    looked at 2,000 a page evicted."""
    pool, pages = full_pool(2000, f"full-{n}")
    try:
        pool._pages = counted = CountedPages(pool._pages)
        got = pool.alloc(n)
        assert got is not None and sorted(got) == sorted(pages[:n])  # the oldest n
        assert counted.visits <= 2 * n
        assert pool.stats()["evictions"] == n
    finally:
        pool.close()


def test_entries_gone_stale_are_paid_for_once_and_the_heap_stays_small():
    """A hot prefix matched and released a thousand times leaves a stale
    entry each time. They never outnumber the sealed pages by more than
    the rebuild's slack, and the looks of all allocs together stay within
    a few a page asked, released or matched."""
    pool, pages = full_pool(200, "stale")
    try:
        pool._pages = counted = CountedPages(pool._pages)
        hot = [f"d{j}" for j in range(8)]
        asked = 0
        for turn in range(1000):
            _, hit = pool.match_pages(hot, max_tokens=8 * 4)
            assert len(hit) == 8
            pool.release_pages(hit)
            assert len(pool._evictable) <= 2 * pool.resident() + 64 + 1
            if turn % 10 == 0:
                got = pool.alloc(3)  # never a hot page: they were matched last
                assert got is not None and not set(got) & set(pages[:8])
                asked += 3
                for j, page in enumerate(got):
                    assert pool.seal(f"t{turn}-{j}", page)
                pool.release_pages(got)
        moved = asked + 2 * 8 * 1000  # pages asked, matched and released
        assert counted.visits <= 4 * moved
        assert pool.resident() == 200 and pool.stats()["evictions"] == asked
    finally:
        pool.close()


def test_alloc_takes_nothing_where_eviction_cannot_cover_the_ask():
    pool, pages = full_pool(20, "short")
    try:
        _, pinned = pool.match_pages([f"d{j}" for j in range(15)], max_tokens=15 * 4)
        before = pool.stats()
        assert pool.alloc(6) is None  # five evictable, six asked
        after = pool.stats()
        # what it evicted on the way stays evicted and free, as the scan left it
        assert after["evictions"] - before["evictions"] == 5 and after["pages_free"] == 5
        assert sorted(pool.alloc(5)) == sorted(pages[15:])
        pool.release_pages(pinned)
        pool.reset()
        assert pool._evictable == [] and pool.free_pages() == 20 and pool.resident() == 0
        pool.close()
        assert pool._evictable == [] and pool.alloc(1) is None
    finally:
        pool.close()


# ---------------------------------------------------------------------------
# engine-level: cached admission == cold prefill, bit for bit
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    srv = LLMServer(LLMConfig(model_id="gpt2-tiny", max_batch_size=4))
    yield srv
    srv._stop.set()


@pytest.mark.parametrize("n", [100, 128, 65])
def test_cached_vs_cold_generations_bitwise_identical(engine, n):
    """The acceptance property: a prompt admitted from pooled pages +
    tail prefill generates EXACTLY the tokens full prefill generates at
    temperature=0, which are the full forward's — including a
    block-aligned prompt (capped match) and with the kill switch off."""
    from ray_tpu.utils.config import config

    rng = np.random.RandomState(11 + n)
    prompt = [int(t) for t in rng.randint(0, 256, n)]
    req = {"prompt_tokens": prompt, "max_new_tokens": 8,
           "temperature": 0.0}
    pool = engine._prefix_pool
    h0 = pool.stats()["hits"]
    cold = engine(req)["tokens"]
    assert cold == engine_reference(engine, prompt, 8)
    hot = engine(req)["tokens"]
    assert hot == cold
    assert pool.stats()["hits"] > h0  # second pass came from cache
    config.set("serve_prefix_cache", False)
    try:
        off = engine(req)["tokens"]
    finally:
        config.set("serve_prefix_cache", True)
    assert off == cold


def test_refcounts_drain_under_slot_churn(engine):
    """Concurrent requests sharing a prefix churn through the decode
    rows; when they all finish, every page's refcount is back to 0
    (nothing leaks pins) and the shared pages are still resident."""
    rng = np.random.RandomState(12)
    shared = [int(t) for t in rng.randint(0, 256, 64)]
    solo = {}
    for i in range(4):
        solo[i] = engine({"prompt_tokens": shared + [i, i + 1],
                          "max_new_tokens": 6, "temperature": 0.0})["tokens"]

    results = [None] * 4

    def call(i):
        results[i] = engine({"prompt_tokens": shared + [i, i + 1],
                             "max_new_tokens": 6, "temperature": 0.0})

    threads = [threading.Thread(target=call, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    for i in range(4):
        assert results[i] is not None and results[i]["tokens"] == solo[i]
    pool = engine._prefix_pool
    assert pool.resident() > 0
    with pool._lock:
        assert all(p.refs == 0 for p in pool._pages), {
            p.idx: p.refs for p in pool._pages if p.refs
        }
