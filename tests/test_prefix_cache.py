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

from ray_tpu.serve.prefix_cache import hash_blocks


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
