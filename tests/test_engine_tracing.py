"""The paged engine's account of itself (tier-1, CPU, gpt2-tiny, in
process): the counters stamped where the work happens add up, a finished
request's phases tile its engine span, the ``rt/engine/*`` spans land in
the profiler's trace on one clock with the ring's, and with both
switches off nothing is stamped at all."""

import glob
import os
import subprocess
import sys
import threading
import time

import pytest

from ray_tpu.observability import core_metrics, tracing
from ray_tpu.utils.config import config

B = 16  # page tokens in these tests
SHARED = list(range(100, 100 + 2 * B))  # two full pages every "C" prompt shares
MAX_NEW = 80
SPANS = ("round", "admit", "prefill", "first_token_sync", "dispatch", "harvest_sync",
         "harvest", "idle")
HOST, BLOCKED = core_metrics.ENGINE_HOST_PHASES, core_metrics.ENGINE_BLOCKED_PHASES
PHASE_SERIES = tuple(f"serve_engine_{p}_s" for p in core_metrics.ENGINE_PHASES)
DRY_SERIES = tuple(f"serve_engine_dry_{p}_s" for p in HOST)
SERIES = (
    "serve_engine_queue_wait_s", "serve_engine_page_wait_s", "serve_engine_first_token_s",
    "serve_engine_round_host_s", "serve_engine_round_blocked_s", "serve_decode_steps",
    "serve_decode_row_steps", "serve_prompt_tokens", "serve_prefix_tokens_reused",
    "serve_prefill_tokens", "serve_prefill_width", "serve_tokens_generated", "serve_ttft_s",
    "serve_prefix_cache_hits", "serve_batch_fill", *PHASE_SERIES, *DRY_SERIES,
)


def totals():
    """Every series the engine stamps, summed over its tags: a counter's
    value, a histogram's (sum, count)."""
    out = {}
    for key in SERIES:
        snap = getattr(core_metrics, key).snapshot()
        if snap["kind"] == "histogram":
            out[key] = (sum(s["sum"] for s in snap["series"].values()),
                        sum(s["count"] for s in snap["series"].values()))
        else:
            out[key] = sum(snap["series"].values())
    return out


def delta(before, after, key):
    a, b = before[key], after[key]
    return (b[0] - a[0], b[1] - a[1]) if isinstance(a, tuple) else b - a


@pytest.fixture(scope="module")
def srv():
    """A paged engine whose pool holds one request of eight pages and
    six pages more: two requests that share two resident pages fit, a
    third is refused until one of them ends."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    keep = {k: getattr(config, k) for k in ("serve_prefix_block_tokens", "serve_kv_pool_pages")}
    config.set("serve_prefix_block_tokens", B)
    config.set("serve_kv_pool_pages", 14)
    try:
        server = LLMServer(LLMConfig(model_id="gpt2-tiny", max_batch_size=4))
    finally:
        for k, v in keep.items():
            config.set(k, v)
    yield server
    server.unload()


@pytest.fixture
def ring(monkeypatch):
    """What the engine would append to its worker's ring (no worker
    exists in this process)."""
    events = []
    monkeypatch.setattr(tracing, "emit", events.append)
    return events


def ask(server, prompt, stream=False, trace_id=None, max_new=MAX_NEW):
    body = {"prompt_tokens": prompt, "max_new_tokens": max_new, "stream": stream}
    if trace_id:
        body["trace_id"] = trace_id
    out = server(body)
    if stream:
        return [ev["token"] for ev in out]
    return out["tokens"]


def ask_together(server, jobs):
    """Each of ``jobs`` (prompt, stream, trace_id) from a thread of its
    own, started in order; the tokens each got."""
    got = [None] * len(jobs)

    def one(i, job):
        got[i] = ask(server, *job)

    threads = [threading.Thread(target=one, args=(i, j)) for i, j in enumerate(jobs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert all(g is not None for g in got)
    return got


def tail(seed):
    return [(seed * 7 + i) % 90 for i in range(8)]


@pytest.fixture(scope="module")
def mix(srv):
    """The traffic every identity below is read from: one request alone
    (it seals the shared pages), three that share its prefix at once,
    streamed and unary (the third is refused for pages while two hold
    the pool), then one unshared alone. Counter snapshots around each
    phase, and what the ring was given."""
    events = []
    real_emit, tracing.emit = tracing.emit, events.append
    try:
        snaps = [totals()]
        wall = [time.monotonic()]
        tokens = [ask(srv, SHARED + tail(1), trace_id="alone")]
        snaps.append(totals())
        tokens += ask_together(srv, [
            (SHARED + tail(2), True, "c1"), (SHARED + tail(3), False, "c2"),
            (SHARED + tail(4), True, "c3"),
        ])
        snaps.append(totals())
        tokens.append(ask(srv, [200 + i % 50 for i in range(40)], stream=True, trace_id="u1"))
        deadline = time.monotonic() + 10
        while len([e for e in events if e["component"] == "engine"]) < 5:
            assert time.monotonic() < deadline, events
            time.sleep(0.01)
        time.sleep(0.05)  # the last round's own stamps
        snaps.append(totals())
        wall.append(time.monotonic())
    finally:
        tracing.emit = real_emit
    return {"snaps": snaps, "tokens": tokens, "events": events, "wall": wall[1] - wall[0]}


def test_decode_steps_count_what_decode_generated(mix):
    first, last = mix["snaps"][0], mix["snaps"][-1]
    assert all(len(t) == MAX_NEW for t in mix["tokens"])
    generated = delta(first, last, "serve_tokens_generated")
    firsts = delta(first, last, "serve_ttft_s")[1]
    assert generated == 5 * MAX_NEW and firsts == 5
    assert delta(first, last, "serve_decode_row_steps") == generated - firsts
    # alone, one row: every token-step is one row-step
    alone = (mix["snaps"][0], mix["snaps"][1])
    assert delta(*alone, "serve_decode_steps") == MAX_NEW - 1
    assert delta(*alone, "serve_decode_row_steps") == MAX_NEW - 1
    # together, rows share steps: fewer steps than row-steps, and the
    # steps per dispatch (what decode_k_mean reads) are between 1 and 8
    shared = (mix["snaps"][1], mix["snaps"][2])
    steps = delta(*shared, "serve_decode_steps")
    assert MAX_NEW - 1 <= steps < delta(*shared, "serve_decode_row_steps") == 3 * (MAX_NEW - 1)
    dispatches = delta(*shared, "serve_batch_fill")[1]
    assert 1.0 <= steps / dispatches <= 8.0


def test_prompt_and_reused_tokens_count_admissions_not_attempts(mix):
    first, last = mix["snaps"][0], mix["snaps"][-1]
    assert delta(first, last, "serve_prompt_tokens") == 5 * 40
    # three admitted requests found the two shared pages resident
    assert delta(first, last, "serve_prefix_tokens_reused") == 3 * len(SHARED)
    # the old counter counts pages per attempt: the refused request
    # matched its two pages again in every round it was refused
    assert delta(first, last, "serve_prefix_cache_hits") * B > 3 * len(SHARED)


def test_prefill_tokens_and_padded_width(mix):
    first, last = mix["snaps"][0], mix["snaps"][-1]
    useful = delta(first, last, "serve_prefill_tokens")
    padded, calls = delta(first, last, "serve_prefill_width")
    # 40 and 40 whole prompts, three tails of 8 behind the shared pages;
    # padded to 64, 64 and three times 16
    assert useful == 40 + 40 + 3 * 8 and calls == 5
    assert padded == 64 + 64 + 3 * 16 and useful <= padded


def test_waits_are_observed_once_per_admission(mix):
    snaps = mix["snaps"]
    queue_s, admitted = delta(snaps[0], snaps[-1], "serve_engine_queue_wait_s")
    assert admitted == 5 and queue_s > 0
    assert delta(snaps[0], snaps[-1], "serve_engine_page_wait_s")[1] == 5
    assert delta(snaps[0], snaps[-1], "serve_engine_first_token_s")[1] == 5
    # a request alone in a roomy pool is never refused ...
    assert delta(snaps[0], snaps[1], "serve_engine_page_wait_s") == (0.0, 1)
    assert delta(snaps[2], snaps[3], "serve_engine_page_wait_s") == (0.0, 1)
    # ... the third of three is, and waits about as long as one of them lasts
    page_s, n = delta(snaps[1], snaps[2], "serve_engine_page_wait_s")
    assert n == 3 and page_s > 0
    assert page_s <= delta(snaps[1], snaps[2], "serve_engine_queue_wait_s")[0]


def test_round_time_splits_into_host_and_blocked(mix):
    first, last = mix["snaps"][0], mix["snaps"][-1]
    host_s, rounds = delta(first, last, "serve_engine_round_host_s")
    blocked_s, blocked_rounds = delta(first, last, "serve_engine_round_blocked_s")
    assert rounds == blocked_rounds > 0
    assert host_s > 0 and blocked_s > 0
    # idle waits belong to neither: together they fit in the wall time
    assert host_s + blocked_s <= mix["wall"]


def test_a_phase_is_a_span_and_the_rounds_self_time_is_other():
    # every span under the round's but ``idle``, which is a round that
    # only parked and observes nothing
    assert core_metrics.ENGINE_PHASES == (*SPANS[1:-1], "other")
    assert BLOCKED == ("first_token_sync", "harvest_sync")
    assert HOST == ("admit", "prefill", "dispatch", "harvest", "other")


def test_phases_add_up_to_host_and_blocked_and_dry_is_within_host(mix):
    first, last = mix["snaps"][0], mix["snaps"][-1]
    host_s, rounds = delta(first, last, "serve_engine_round_host_s")
    blocked_s = delta(first, last, "serve_engine_round_blocked_s")[0]
    by_phase = {p: delta(first, last, f"serve_engine_{p}_s") for p in HOST + BLOCKED}
    dry = {p: delta(first, last, f"serve_engine_dry_{p}_s") for p in HOST}
    # zeros included: every series counts the rounds that worked
    assert {n for _, n in by_phase.values()} == {n for _, n in dry.values()} == {rounds}
    assert all(s >= 0 for s, _ in by_phase.values()) and all(s >= 0 for s, _ in dry.values())
    assert sum(by_phase[p][0] for p in HOST) == pytest.approx(host_s, rel=1e-6)
    assert sum(by_phase[p][0] for p in BLOCKED) == pytest.approx(blocked_s, rel=1e-6)
    # a phase's dry seconds are seconds of that phase; every first token
    # was fetched, and the device had nothing until the decode call
    assert all(dry[p][0] <= by_phase[p][0] + 1e-9 for p in HOST)
    assert 0 < sum(s for s, _ in dry.values()) <= host_s


class Result:
    """What a program handed to the device returns, as the account sees it."""

    def __init__(self, ready=False):
        self.ready, self.asked = ready, 0

    def is_ready(self):
        self.asked += 1
        return self.ready


class Clock:
    def __init__(self):
        self.t, self.reads = 100.0, 0

    def __call__(self):
        self.reads += 1
        return self.t


def account_case(case, monkeypatch):
    """One round, or two, of a ``_RoundAccount`` under a stub clock that
    the case moves by hand; the account, and the seconds by phase and dry
    seconds by phase its rounds should have left in the series."""
    from ray_tpu.serve.llm import _RoundAccount

    clock = Clock()
    monkeypatch.setattr(_RoundAccount, "clock", staticmethod(clock))
    acct = _RoundAccount({"deployment": f"account-{case}"})

    def spend(s):
        clock.t += s

    def in_phase(name, s, waits_for=None, call=None):
        """``s`` seconds in a phase; ``call`` is handed over after half of them."""
        with acct.phase(name, waits_for=waits_for):
            spend(s / 2)
            if call is not None:
                acct.device_call()
                acct.handed(call)
            spend(s / 2)

    chunk, prefill, firsts, nxt = Result(), Result(), Result(), Result()
    acct.begin()
    spend(1)  # other
    if case == "sync_on_the_tail":
        # a prefill call behind the chunk in flight, its first tokens
        # fetched: dry from there, through other and half the dispatch
        acct.handed(chunk)
        in_phase("prefill", 2, call=prefill)
        spend(1)
        acct.device_call()
        acct.handed(firsts)  # sampled from the call's logits: the tail now
        in_phase("first_token_sync", 8, waits_for=firsts)
        spend(3)
        in_phase("dispatch", 4, call=nxt)
        in_phase("harvest_sync", 1, waits_for=chunk)  # not the tail: nxt is
        in_phase("harvest", 2)
        acct.end(True)
        want = {"dry_other": 3, "dry_dispatch": 2, "other": 5, "dispatch": 4, "prefill": 2,
                "first_token_sync": 8, "harvest_sync": 1, "harvest": 2}
        assert acct.tail is nxt and acct.dry_since is None and nxt.asked > 0
    elif case == "tail_not_ready":
        acct.handed(chunk)
        in_phase("admit", 2)
        in_phase("dispatch", 4, call=nxt)
        in_phase("harvest", 2)
        acct.end(True)
        want = {"other": 1, "admit": 2, "dispatch": 4, "harvest": 2}
        assert chunk.asked > 0 and acct.dry_since is None
    elif case == "tail_found_ready_at_a_boundary":
        acct.handed(chunk)
        in_phase("admit", 2)
        chunk.ready = True  # the device ran out somewhere in here ...
        spend(5)
        in_phase("harvest", 2)  # ... and the thread learns it as this begins
        in_phase("dispatch", 4, call=nxt)
        acct.end(True)
        want = {"other": 6, "admit": 2, "harvest": 2, "dispatch": 4,
                "dry_harvest": 2, "dry_dispatch": 2}
        assert acct.tail is nxt and chunk.asked > 0
    elif case == "sync_on_a_chunk_that_is_not_the_tail":
        acct.handed(nxt)
        in_phase("harvest_sync", 6, waits_for=chunk)
        in_phase("harvest", 2)
        acct.end(True)
        want = {"other": 1, "harvest_sync": 6, "harvest": 2}
        assert acct.tail is nxt and acct.dry_since is None
    elif case == "parked_round":
        # the last chunk harvested, then a round that only parks: it
        # observes nothing, and the dry seconds that passed in it are nobody's
        acct.handed(chunk)
        in_phase("harvest_sync", 1, waits_for=chunk)
        acct.end(True)
        acct.begin()
        in_phase("admit", 1)
        spend(500)  # under rt/engine/idle, which is no phase
        acct.end(False)
        acct.begin()
        spend(2)
        in_phase("prefill", 2, call=prefill)
        acct.end(True)
        want = {"other": 1, "harvest_sync": 1, "rounds": 2,
                "then_other": 2, "then_prefill": 2, "then_dry_other": 2, "then_dry_prefill": 1}
    elif case == "any_program_ends_the_stretch":
        # dry from the harvest's sync; the scatter of the changed rows, a
        # second into the dispatch, is the device's next program, not the
        # decode call two seconds behind it
        acct.handed(chunk)
        in_phase("harvest_sync", 1, waits_for=chunk)
        in_phase("admit", 2)
        with acct.phase("dispatch"):
            spend(1)
            acct.device_call()
            spend(2)
            acct.device_call()
            acct.handed(nxt)
            spend(1)
        acct.end(True)
        want = {"other": 1, "harvest_sync": 1, "admit": 2, "dispatch": 4,
                "dry_admit": 2, "dry_dispatch": 1}
        assert acct.tail is nxt and acct.dry_since is None
    elif case == "first_sync_under_a_decode_call":
        # a round that ends a prompt since PR 61: prefill call, sampling,
        # then the scatter and the decode call go over inside the dispatch,
        # and the wait for the first tokens comes behind it. Its return says
        # nothing of the device: the decode call is the tail, and runs
        acct.handed(chunk)
        in_phase("admit", 2)
        in_phase("prefill", 2, call=prefill)
        spend(1)
        acct.device_call()
        acct.handed(firsts)
        spend(1)
        in_phase("dispatch", 4, call=nxt)
        in_phase("first_token_sync", 8, waits_for=firsts)
        assert acct.tail is nxt and acct.dry_since is None
        in_phase("harvest_sync", 1, waits_for=chunk)
        in_phase("harvest", 2)
        acct.end(True)
        want = {"other": 3, "admit": 2, "prefill": 2, "dispatch": 4, "first_token_sync": 8,
                "harvest_sync": 1, "harvest": 2}
        assert acct.tail is nxt and acct.dry_since is None
    elif case == "first_sync_with_no_decode_call_behind":
        # every sequence of the call ended at its first token and no row is
        # live: nothing goes over, the first tokens are the tail and their
        # sync's return opens a stretch, as before
        in_phase("prefill", 2, call=prefill)
        acct.device_call()
        acct.handed(firsts)
        in_phase("first_token_sync", 8, waits_for=firsts)
        spend(3)
        acct.end(True)
        want = {"other": 4, "prefill": 2, "first_token_sync": 8, "dry_other": 3}
        assert acct.tail is None and acct.dry_since is not None
    elif case == "exception_forgets_the_tail":
        acct.handed(chunk)
        with pytest.raises(RuntimeError):
            with acct.phase("harvest_sync", waits_for=chunk):
                spend(1)
                raise RuntimeError("the device fell over")
        assert acct.tail is chunk and acct.dry_since is None  # a failed sync teaches nothing
        acct.forget()  # what the loop does with dev_state
        asked = chunk.asked
        acct.begin()
        in_phase("admit", 1)
        in_phase("dispatch", 2, call=nxt)
        acct.end(True)
        assert chunk.asked == asked and acct.tail is nxt
        want = {"admit": 1, "dispatch": 2}
    return acct, want


ACCOUNT_CASES = ("sync_on_the_tail", "tail_not_ready", "tail_found_ready_at_a_boundary",
                 "sync_on_a_chunk_that_is_not_the_tail", "parked_round",
                 "any_program_ends_the_stretch", "exception_forgets_the_tail",
                 "first_sync_under_a_decode_call", "first_sync_with_no_decode_call_behind")


@pytest.mark.parametrize("case", ACCOUNT_CASES)
def test_the_account_of_a_round_under_a_stub_clock(case, monkeypatch):
    def read(key):
        for snap_key, s in getattr(core_metrics, key).snapshot()["series"].items():
            if f"account-{case}" in str(snap_key):
                return s["sum"], s["count"]
        return 0.0, 0

    acct, want = account_case(case, monkeypatch)
    rounds = want.pop("rounds", 1)
    then = {k[len("then_"):]: want.pop(k) for k in list(want) if k.startswith("then_")}
    both = {**want, **{k: want.get(k, 0) + v for k, v in then.items()}}
    for phase in core_metrics.ENGINE_PHASES:
        total, n = read(f"serve_engine_{phase}_s")
        # a round that worked observes every phase, a parked round none
        assert (total, n) == (pytest.approx(both.get(phase, 0)), rounds), phase
    for phase in HOST:
        assert read(f"serve_engine_dry_{phase}_s") == (
            pytest.approx(both.get(f"dry_{phase}", 0)), rounds), phase
    host = read("serve_engine_round_host_s")
    blocked = read("serve_engine_round_blocked_s")
    assert host == (pytest.approx(sum(both.get(p, 0) for p in HOST)), rounds)
    assert blocked == (pytest.approx(sum(both.get(p, 0) for p in BLOCKED)), rounds)


def test_phases_tile_the_engine_span(mix):
    by_trace = {}
    for e in mix["events"]:
        assert e["type"] == "request"
        by_trace.setdefault(e["trace_id"], {})[e["component"]] = e
    assert set(by_trace) == {"alone", "c1", "c2", "c3", "u1"}
    refused = 0
    for tid, spans in by_trace.items():
        assert set(spans) == {"engine", "engine.queue", "engine.prefill", "engine.decode"}, tid
        eng = spans["engine"]
        assert eng["parent"] == "replica"
        phases = [spans["engine.queue"], spans["engine.prefill"], spans["engine.decode"]]
        assert all(p["parent"] == "engine" and p["trace_id"] == tid for p in phases)
        edges = [eng["ts_us"]] + [p["ts_us"] + p["dur_us"] for p in phases]
        assert [p["ts_us"] for p in phases] == edges[:3]  # each starts where the last ended
        assert abs(edges[-1] - (eng["ts_us"] + eng["dur_us"])) <= 1000
        assert all(p["dur_us"] >= 0 for p in phases)
        assert spans["engine.prefill"]["prompt_tokens"] == 40
        assert spans["engine.prefill"]["cached_tokens"] == (len(SHARED) if tid[0] == "c" else 0)
        assert spans["engine.decode"]["tokens"] == MAX_NEW
        page_wait = spans["engine.queue"]["page_wait_us"]
        assert 0 <= page_wait <= spans["engine.queue"]["dur_us"]
        refused += page_wait > 0
    assert refused == 1


def test_spans_land_in_the_profilers_trace_on_one_clock(srv, tmp_path):
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        ask(srv, [9, 8, 7, 6], max_new=20)
        time.sleep(0.7)  # the engine parks in its idle wait
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[-1]
    events = [
        ev for plane in ProfileData.from_file(path).planes for line in plane.lines
        for ev in line.events if ev.name.startswith("rt/engine/")
    ]
    assert {ev.name for ev in events} == {f"rt/engine/{n}" for n in SPANS}
    rounds = [ev for ev in events if ev.name == "rt/engine/round"]
    assert len(rounds) >= 3
    # ring clock (us) against trace clock (ns): one offset for every round
    offsets = [dict(ev.stats)["ts_us"] * 1000 - ev.start_ns for ev in rounds]
    assert max(offsets) - min(offsets) < 1e6, offsets
    stats = dict(next(ev for ev in events if ev.name == "rt/engine/dispatch").stats)
    assert 1 <= stats["k"] <= 8 and stats["rows"] == 1


def test_switched_off_nothing_is_stamped(srv, ring, monkeypatch):
    import jax

    built = []

    class Counting:
        def __init__(self, *a, **kw):
            built.append(a)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    # the account's clock, and what it asks a result the device owes it
    from ray_tpu.serve.llm import _RoundAccount

    reads, asked = [], []
    monkeypatch.setattr(_RoundAccount, "clock",
                        staticmethod(lambda: reads.append(1) or time.monotonic()))
    array, is_ready = type(jax.numpy.zeros(())), type(jax.numpy.zeros(())).is_ready
    monkeypatch.setattr(array, "is_ready", lambda self: asked.append(1) or is_ready(self))
    tracing.set_enabled(False)
    core_metrics.set_enabled(False)
    try:
        time.sleep(0.6)  # the round that was parked as the switch went off
        del reads[:], asked[:], built[:]
        before = totals()
        out = ask(srv, SHARED + tail(9), trace_id="off", max_new=12)
        time.sleep(0.05)
        assert len(out) == 12
        assert totals() == before
        assert ring == [] and built == []
        assert reads == [] and asked == []
    finally:
        tracing.set_enabled(True)
        core_metrics.set_enabled(True)
    # and on again, both are stamped again
    ask(srv, SHARED + tail(10), trace_id="on", max_new=12)
    deadline = time.monotonic() + 5
    while not ring and time.monotonic() < deadline:
        time.sleep(0.01)
    assert {e["component"] for e in ring} >= {"engine", "engine.queue"} and built
    assert reads and asked


def test_span_without_jax_imports_no_jax():
    code = (
        "import sys\n"
        "from ray_tpu.observability import tracing\n"
        "assert tracing.ENABLED\n"
        "with tracing.span('rt/engine/round', k=1, ts_us=tracing.now_us()) as s:\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "print('ok')\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          timeout=120, cwd=root)
    assert proc.returncode == 0 and proc.stdout.strip() == "ok", proc.stderr[-2000:]


def test_rt_top_shows_the_dry_share_beside_host_ms():
    """Sum of the dry seconds over the sum of every working phase's, per
    deployment, from the series alone; no column where nothing worked."""
    from ray_tpu.cli import _render_top

    def hist(total, count, dep="m"):
        return {"kind": "histogram", "tag_keys": ("deployment",), "boundaries": (),
                "series": {(dep,): {"sum": total, "count": count, "buckets": []}}}

    mx = {f"rt_serve_engine_{p}_s": hist(1.0, 10) for p in core_metrics.ENGINE_PHASES}
    mx.update({f"rt_serve_engine_dry_{p}_s": hist(0.0, 10) for p in HOST})
    mx["rt_serve_engine_dry_dispatch_s"] = hist(0.5, 10)
    mx["rt_serve_engine_dry_other_s"] = hist(0.2, 10)
    mx["rt_serve_engine_round_host_s"] = hist(5.0, 10)
    def cell(frame, column):
        lines = frame.splitlines()
        head = next(i for i, line in enumerate(lines) if "HOST_MS" in line)
        at = lines[head].index(column)
        return lines[head + 2][at:at + len(column) + 2].split()

    frame = _render_top(mx, {}, None)
    assert cell(frame, "HOST_MS") == ["500.0"]
    assert cell(frame, "DRY%") == ["10.0"]  # 0.7 s of the seven phases' 7
    assert cell(_render_top({"rt_serve_engine_round_host_s": hist(5.0, 10)}, {}, None),
                "DRY%") == []


# -- the wait for first tokens behind the decode call (PR 61) ----------------


@pytest.fixture(scope="module")
def streams():
    """The seeded cases on an engine nobody has asked anything."""
    import _engine_streams

    return _engine_streams.fresh_streams("gpt2-tiny")


@pytest.mark.parametrize("case", ["greedy_cold", "sampled_cold", "greedy_second_turn",
                                  "sampled_second_turn", "together", "one_token",
                                  "context_full"])
def test_seeded_requests_get_the_tokens_the_parent_gave(streams, case):
    """GPT-2's module of one row is on the path it was on: its tokens are
    the parent's because its calls are."""
    import _engine_streams

    assert streams[case] == _engine_streams.expected("gpt2-tiny")[case]


def test_a_module_of_one_row_waits_where_it_waited_and_counts_no_token_ahead(srv):
    """``prefill_a_sequence_a_call``: the first token is fetched where its
    prompt ends, before the dispatch, no sampling program and no placing on
    the device, and ``rt_serve_first_tokens_ahead_total`` does not move."""
    import _engine_streams

    def ahead():
        return sum(core_metrics.serve_first_tokens_ahead.snapshot()["series"].values())

    ask(srv, [3, 1, 4, 1, 5], max_new=4)  # the step state is on the device
    before, firsts = ahead(), totals()["serve_ttft_s"][1]
    seen = []
    with _engine_streams.watch_the_round(srv, seen):
        tokens = ask(srv, [9, 2, 6, 5, 3, 5, 8], max_new=6)
    assert len(tokens) == 6
    assert totals()["serve_ttft_s"][1] - firsts == 1 and ahead() == before
    ending = [r for r in _engine_streams.rounds_of(seen) if ("span", "first_token_sync") in r]
    assert len(ending) == 1
    assert [name for kind, name in ending[0] if kind == "call"] == ["prefill", "scatter", "decode"]
    at = ending[0].index
    assert at(("call", "prefill")) < at(("end", "first_token_sync")) < at(("span", "dispatch"))
    assert not hasattr(srv._dec, "PREFILL_ROW_WIDTHS")


def test_the_step_state_is_uploaded_from_a_copy_the_loop_cannot_write():
    """``_upload``: the loop writes its mirrors again right behind the
    upload (``retire()`` zeroes a row at dispatch) and, since PR 61, while a
    prefill call still runs in front of the decode call. On the CPU
    ``jnp.array`` of an aligned mirror read it after the write."""
    import numpy as np

    from ray_tpu.serve.llm import _upload

    raw = np.zeros((4096 + 16,), np.int32)
    start = (-raw.ctypes.data % 64) // 4
    mirror = raw[start:start + 4096]
    assert mirror.ctypes.data % 64 == 0
    for _ in range(8):
        mirror[:] = 7
        (held,) = _upload(mirror)
        mirror[:] = 0
        assert int(np.asarray(held).sum()) == 7 * 4096
