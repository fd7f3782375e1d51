"""Traffic plans: the same seed gives the same inputs, another seed the
same sizes in another order with other bytes."""

import collections
import json
import os

import pytest

from benchmark import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def mix(name):
    for d in (os.path.join(ROOT, "benchmark"), HERE):
        path = os.path.join(d, "traffic", name + ".json")
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
    raise FileNotFoundError(name)


def bodies(tr, seed, n=12):
    plan = traffic.plan(tr, seed)
    out = []
    for session in plan["sessions"][:n]:
        for k in range(len(session["script"])):
            out.append(traffic.turn_request(tr, "m", plan, session, k))
    return plan, out


@pytest.mark.parametrize("name", ["batch-decode", "chat-sessions", "tiny-decode", "tiny-chat"])
def test_reproducible_from_the_seed_and_different_across_seeds(name):
    tr = mix(name)
    big = 3_000_000_019  # more than 32 signed bits hold
    assert bodies(tr, big) == bodies(tr, big)
    plan_a, a = bodies(tr, big)
    plan_b, b = bodies(tr, 11)
    assert a != b
    assert plan_a["probe_prompt"] != plan_b["probe_prompt"]

    def sizes(plan):
        return collections.Counter(
            tuple((t["turn_tokens"], t["reply_tokens"], t["think_s"]) for t in s["script"])
            for s in plan["sessions"]
        )

    # every seed meets the same set of sessions; in another order unless
    # the mix fixes it (where the order itself would change the work)
    assert sizes(plan_a) == sizes(plan_b)
    same_order = [s["script"] for s in plan_a["sessions"]] == [s["script"] for s in plan_b["sessions"]]
    assert same_order == (tr.get("order") == "fixed")


@pytest.mark.parametrize("name", ["chat-sessions", "tiny-chat"])
def test_chat_prompts_have_the_tokens_the_plan_counted(name):
    tr = mix(name)
    plan, _ = bodies(tr, 5)
    n_sys = tr["system_prompt_tokens"]
    shared = set()
    for session in plan["sessions"][:20]:
        for k, turn in enumerate(session["script"]):
            body = traffic.turn_request(tr, "m", plan, session, k)
            assert traffic.chat_prompt_tokens(body["messages"]) == turn["prompt_tokens"]
            assert turn["prompt_tokens"] + turn["reply_tokens"] <= tr["context_limit"] or k == 0
            assert body["max_tokens"] == turn["reply_tokens"]
            rendered = traffic.render_chat(body["messages"])
            shared.add(rendered[:n_sys])
            if k:
                # the earlier turn's whole prompt is a prefix of this one
                before = traffic.turn_request(tr, "m", plan, session, k - 1)
                assert rendered.startswith(traffic.render_chat(before["messages"]))
    assert len(shared) == 1  # one system prompt, exactly n_sys tokens, for all
    assert len(next(iter(shared)).encode()) == n_sys


def test_the_chat_template_is_the_programs():
    from ray_tpu.serve.openai import tokenizer

    messages = [{"role": "system", "content": "s"}, {"role": "user", "content": "u"},
                {"role": "assistant", "content": "a"}, {"role": "user", "content": "v"}]
    assert traffic.render_chat(messages) == tokenizer.render_chat(messages)


def test_batch_decode_sizes_are_what_the_cell_says():
    tr = mix("batch-decode")
    pool = traffic.session_pool(tr)
    assert len(pool) == 256 and all(len(s) == 1 for s in pool)
    for (turn,) in pool:
        assert 64 <= turn["turn_tokens"] <= 128 and 96 <= turn["reply_tokens"] <= 128
        assert turn["think_s"] == 0
        # the up-front reservation: 3 or 4 pages of 64, so 24 rows fit 96
        assert -(-(turn["prompt_tokens"] + turn["reply_tokens"]) // 64) in (3, 4)
    _, reqs = bodies(tr, 7, n=256)
    assert len({r["prompt"] for r in reqs}) == 256  # unshared
    assert all(len(r["prompt"].encode()) == len(r["prompt"]) for r in reqs)  # ASCII


def test_chat_sessions_sizes_are_what_the_cell_says():
    tr = mix("chat-sessions")
    pool = traffic.session_pool(tr)
    turns = [t for s in pool for t in s]
    assert all(16 <= t["turn_tokens"] <= 128 and 16 <= t["reply_tokens"] <= 64 for t in turns)
    assert 2 <= min(len(s) for s in pool) and max(len(s) for s in pool) <= 12
    assert all(t["prompt_tokens"] + t["reply_tokens"] <= 960 for t in turns)
    median = sorted(t["turn_tokens"] for t in turns)[len(turns) // 2]
    assert 50 <= median <= 80


@pytest.mark.parametrize("name", ["batch-decode", "chat-sessions"])
def test_the_probe_is_shorter_than_a_page_so_never_cached(name):
    tr = mix(name)
    plan = traffic.plan(tr, 1)
    n = len(plan["probe_prompt"])
    if tr["endpoint"].endswith("/chat/completions"):
        n = traffic.chat_prompt_tokens([{"role": "user", "content": plan["probe_prompt"]}])
    assert n < tr["warm"]["page_tokens"]


def test_text_is_exactly_as_long_as_asked():
    import random

    for n in (1, 5, 16, 63, 64, 373, 512):
        assert len(traffic.text(random.Random(n), n).encode()) == n


def _prefill_widths(start, total, context, chunk):
    """The widths the paged engine pads a lone prompt's prefill to, from
    ``start`` cached tokens on: the next power of two from 16, at most
    what is left of the ``context``, ``chunk`` tokens a round (the rule of
    ``serve/llm.py`` ``_engine_loop_paged``, copied)."""
    out, pos = [], start
    while pos < total:
        n = min(total - pos, chunk)
        width = 16
        while width < n:
            width *= 2
        width = min(width, context - pos)
        out.append(width)
        pos += min(n, width)
    return out


def benchmark_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def serving_cells():
    """Every cell of BENCHMARK.json whose mix goes to an endpoint, a later
    PR's too, whatever its generator: (cell, mix, configuration file)."""
    bench = benchmark_file()
    files = {c["name"]: c["file"] for c in bench["configs"]}
    return [pytest.param(w["traffic"], files[w["config"]], id=w["name"])
            for w in bench["workloads"] if "endpoint" in mix(w["traffic"])]


@pytest.mark.parametrize("name,config_file", serving_cells())
def test_set_up_warms_every_prefill_width_a_turn_can_meet(name, config_file):
    """A width first met inside the window compiles there (the driver's
    first chat run in a new checkout did: a tail of 274 tokens behind
    nine cached pages is padded to 1,024 - 576 = 448). The context is the
    cell's own configuration's, by its family, and the round's budget the
    program's."""
    from benchmark import harness
    from benchmark.generators import serve_sessions
    from ray_tpu.utils.config import config as rtcfg

    cfg = harness.load_json(os.path.join(ROOT, config_file))
    family = harness.family(harness.find(benchmark_file(), "families", cfg["family"], ".py"))
    context = family.context(cfg["model"])
    chunk = int(rtcfg.serve_prefill_chunk_tokens)
    tr = mix(name)
    assert tr["context_limit"] <= context
    page = int(tr["warm"].get("page_tokens", 64))
    chat = tr["endpoint"].endswith("/chat/completions")
    warmed, seen = set(), []
    for body in serve_sessions._warm_bodies(tr, "m", 5):
        text = (traffic.render_chat(body["messages"]) if chat else body["prompt"])
        # whole pages of an earlier prompt that this one begins with
        cached = max((min(len(os.path.commonprefix([text, t])), len(text) - 1)
                      // page * page for t in seen), default=0)
        warmed.update(_prefill_widths(cached, len(text), context, chunk))
        seen.append(text)
    system = int(tr.get("system_prompt_tokens", 0))
    for script in traffic.session_pool(tr):
        for k, turn in enumerate(script):
            total = turn["prompt_tokens"]
            starts = {0, system}
            if k:
                starts.add(script[k - 1]["prompt_tokens"] // page * page)
            for start in starts:
                start = min(start, (total - 1) // page * page)
                assert set(_prefill_widths(start, total, context, chunk)) <= warmed, (k, turn, start)
    assert {448} <= warmed or name != "chat-sessions"
