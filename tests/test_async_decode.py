"""The decode pipeline: the engine dispatches decode chunk N+1 from
chunk N's device-resident outputs before materializing chunk N's
tokens, so host bookkeeping (fan-out, SSE puts, metrics, reaping,
admission) overlaps device compute.

Pins its contracts:
  * temp=0 generations are the full forward's greedy tokens, unary and
    SSE — the lookahead reorders WHEN the host sees tokens, never which
    tokens the device samples;
  * cancellation landing while a lookahead chunk is in flight drops
    that chunk's tokens on the host and returns every page (deferred
    one step, so the in-flight chunk never scatters into freed pages);
  * an engine exception mid-lookahead fails the in-flight requests
    (fail_inflight) without hanging callers or leaking pool pages;
  * an idle engine admits a fresh arrival immediately — the old
    wait-then-clear order could eat the wakeup and add a 0.5 s TTFT
    mode (the lost-wakeup race).
"""

import threading
import time

import numpy as np
import pytest
from _llm_reference import engine_reference


def _mk(batch: int = 4):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    return LLMServer(LLMConfig(model_id="gpt2-tiny", max_batch_size=batch))


@pytest.fixture(scope="module")
def engine():
    """Module-scoped — it holds a tiny CPU model."""
    srv = _mk()
    yield srv
    srv._stop.set()
    srv._work.set()


def _req(prompt, max_new=24, **extra):
    return {"prompt_tokens": prompt, "max_new_tokens": max_new,
            "temperature": 0.0, **extra}


# ---------------------------------------------------------------------------
# parity: the lookahead is invisible at temp=0
# ---------------------------------------------------------------------------


def test_pipelined_unary_matches_full_forward(engine):
    """The lookahead must not change a single sampled token: whatever
    chunk sizes and finish budgets the loop chose, the answer is the
    full forward's — short, block-spanning, and window-filling
    prompts."""
    rng = np.random.RandomState(41)
    for n in (10, 64, 127):
        prompt = [int(t) for t in rng.randint(0, 256, n)]
        want = engine_reference(engine, prompt, 24)
        assert len(want) == min(24, 128 - n)
        assert engine(_req(prompt))["tokens"] == want, (
            f"engine != full forward (prompt len {n})"
        )


def test_pipelined_sse_stream_matches_full_forward(engine):
    """SSE rides the pipeline: the streamed token sequence (fan-out
    happens one chunk AFTER dispatch) matches the unary result and the
    full forward exactly, and the stream terminates."""
    rng = np.random.RandomState(42)
    prompt = [int(t) for t in rng.randint(0, 256, 33)]
    a = [ev["token"] for ev in engine(_req(prompt, stream=True))]
    u = engine(_req(prompt))["tokens"]
    assert a == u == engine_reference(engine, prompt, 24)
    assert len(a) == 24


# ---------------------------------------------------------------------------
# mid-lookahead cancellation: dropped tokens, no page leak
# ---------------------------------------------------------------------------


def test_mid_lookahead_cancel_returns_pages(engine):
    """Closing a stream while a lookahead chunk is in flight marks the
    row dropped: its remaining tokens never reach the queue, its pages
    free via the deferred path once the chunk harvests, and occupancy
    returns to idle — no rt_serve_kv_pages_occupied leak."""
    srv = engine
    pool = srv._prefix_pool
    idle_occ = pool.stats()["pages_occupied"]
    gen = srv(_req([7] * 40, max_new=100, stream=True))
    got = [next(gen)["token"] for _ in range(3)]
    assert len(got) == 3
    gen.close()  # client disconnect mid-stream, lookahead in flight
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if (
            srv.batch_stats()["occupied"] == 0
            and pool.stats()["pages_occupied"] <= idle_occ
        ):
            break
        time.sleep(0.05)
    assert srv.batch_stats()["occupied"] == 0
    assert pool.stats()["pages_occupied"] <= idle_occ, pool.stats()
    # the engine keeps serving after the reap
    assert len(srv(_req([7] * 40, max_new=4))["tokens"]) == 4


# ---------------------------------------------------------------------------
# mid-lookahead exception: fail_inflight, reclaim, recover
# ---------------------------------------------------------------------------


def test_mid_lookahead_exception_fails_and_recovers(monkeypatch):
    """A decode fault while a chunk is in flight must fail the caller
    promptly (fail_inflight covers rows whose finish was scheduled at
    dispatch but never harvested), reclaim every page through the
    deferred-free + pool-reset path, and leave the engine serving."""
    from ray_tpu.models import gpt2_decode

    srv = _mk()
    try:
        srv(_req([3] * 20, max_new=4))  # warm the compile caches
        pool = srv._prefix_pool
        idle_occ = pool.stats()["pages_occupied"]

        real_multi = gpt2_decode.decode_multi_paged
        real_single = gpt2_decode.decode_paged_and_sample
        calls = {"n": 0}

        def poison(real):
            def wrapped(*a, **kw):
                calls["n"] += 1
                if calls["n"] >= 2:  # first chunk dispatches clean:
                    # the fault lands with a lookahead in flight
                    raise RuntimeError("injected decode fault")
                return real(*a, **kw)
            return wrapped

        monkeypatch.setattr(
            gpt2_decode, "decode_multi_paged", poison(real_multi)
        )
        monkeypatch.setattr(
            gpt2_decode, "decode_paged_and_sample", poison(real_single)
        )
        with pytest.raises(RuntimeError, match="injected decode fault"):
            srv(_req([3] * 20, max_new=16))
        monkeypatch.setattr(gpt2_decode, "decode_multi_paged", real_multi)
        monkeypatch.setattr(
            gpt2_decode, "decode_paged_and_sample", real_single
        )
        # the rebuild resets the pool: occupancy back to idle, and the
        # engine answers the next request as if nothing happened
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if (
                srv.batch_stats()["occupied"] == 0
                and pool.stats()["pages_occupied"] <= idle_occ
            ):
                break
            time.sleep(0.05)
        assert pool.stats()["pages_occupied"] <= idle_occ, pool.stats()
        assert len(srv(_req([3] * 20, max_new=4))["tokens"]) == 4
    finally:
        srv._stop.set()
        srv._work.set()


# ---------------------------------------------------------------------------
# lost-wakeup race: idle-arrival TTFT has no 0.5 s mode
# ---------------------------------------------------------------------------


def test_idle_arrival_ttft_no_half_second_mode(engine):
    """The engine consumes the wake flag BEFORE scanning the queue, so
    a request arriving while it sleeps in _work.wait(0.5) always wakes
    it immediately. The old wait-then-clear order could eat the set()
    and park a fresh arrival for the full 500 ms timeout."""
    srv = engine
    prompt = [11] * 12
    srv(_req(prompt, max_new=2))  # warm compile caches
    lat = []
    for _ in range(6):
        time.sleep(0.12)  # let the engine reach the idle wait
        t0 = time.monotonic()
        srv(_req(prompt, max_new=2))
        lat.append(time.monotonic() - t0)
    assert max(lat) < 0.45, (
        f"idle-arrival TTFT shows a ~0.5s mode: {sorted(lat)}"
    )


def test_concurrent_streams_all_complete(engine):
    """Batched async decode under churn: several concurrent streams of
    unequal lengths all run to completion with the right token counts
    (staggered finishes exercise retire-at-dispatch + deferred frees)."""
    srv = engine
    out = {}

    def run(tag, n, m):
        out[tag] = [
            ev["token"]
            for ev in srv(_req([tag] * n, max_new=m, stream=True))
        ]

    ts = [
        threading.Thread(target=run, args=(17 + j, 10 + 7 * j, 6 + 5 * j))
        for j in range(3)
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert sorted(len(v) for v in out.values()) == [6, 11, 16]
