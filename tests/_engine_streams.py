"""Seeded requests through a fresh tiny engine, and the token streams the
tree before PR 61 gave for them (``data/engine_streams.json``): greedy and at
a temperature, behind a prefix hit and cold, alone and admitted together,
answers of one token and a context its prompt fills. PR 61 moved the wait for
first tokens behind the decode call that carries them; the programs, their
keys and their order on the device are the parent's, so the tokens are.

The file was written by the parent's tree on this kind of CPU
(``python tests/_engine_streams.py tests/data/engine_streams.json`` in a
checkout of it). The same programs on the same inputs give the same bits; a
CPU that rounds otherwise would move a token whatever the engine does, and
the file is then written anew from the tree that stands.

Also what the tests of a round's order share: ``watch_the_round`` records the
programs the engine hands the device and the spans it opens, in order.
"""

import contextlib
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
STREAMS = os.path.join(HERE, "data", "engine_streams.json")

# page tokens, then prompt lengths: cold greedy, cold sampled, what a second
# turn adds behind each, three admitted together
SIZES = {
    "gpt2-tiny": {"page": 16, "cold": (40, 36), "more": (20, 24), "together": (30, 24, 12)},
    "kanana-2-tiny": {"page": 64, "cold": (150, 100), "more": (40, 70), "together": (90, 60, 30)},
    "mimo-v2-tiny": {"page": 64, "cold": (150, 100), "more": (40, 70), "together": (90, 60, 30)},
}


def fresh_engine(model_id):
    """An engine nobody has asked anything: its sampling key is where a
    fresh replica's is."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    from ray_tpu.utils.config import config

    keep = config.serve_prefix_block_tokens
    config.set("serve_prefix_block_tokens", SIZES[model_id]["page"])
    try:
        return LLMServer(LLMConfig(model_id=model_id, max_batch_size=4, max_new_tokens_cap=64))
    finally:
        config.set("serve_prefix_block_tokens", keep)


def ask_together(srv, asks):
    """``asks`` admitted in one round, streamed: each one's tokens as its
    queue gave them, which are its result."""
    reqs = [srv._parse({**a, "stream": True}) for a in asks]
    with srv._lock:
        srv._queue.extend(reqs)
    srv._work.set()
    out = []
    for r in reqs:
        assert r.event.wait(300) and r.error is None, r.error
        sent = []
        while True:
            tok = r.token_q.get(timeout=60)
            if tok is None:
                break
            sent.append(int(tok))
        assert sent == [int(t) for t in r.result]
        out.append(sent)
    return out


def run_cases(srv, model_id):
    """Every case in order on ``srv`` (the engine's key moves with every
    sampled first token, so the order is part of the seed): {case: [tokens
    of each request]}."""
    sizes = SIZES[model_id]
    rng = np.random.default_rng(61)
    draw = lambda n: [int(t) for t in rng.integers(0, 256, n)]  # noqa: E731
    ask = lambda p, n, t=0.0: {"prompt_tokens": p, "max_new_tokens": n, "temperature": t}  # noqa: E731
    got = {}
    cold, sampled = draw(sizes["cold"][0]), draw(sizes["cold"][1])
    got["greedy_cold"] = ask_together(srv, [ask(cold, 12)])
    got["sampled_cold"] = ask_together(srv, [ask(sampled, 12, 0.8)])
    # a second turn stands behind the first's sealed pages where the model's
    # pages may be matched, and is prefilled whole where they may not
    got["greedy_second_turn"] = ask_together(
        srv, [ask(cold + got["greedy_cold"][0] + draw(sizes["more"][0]), 10)])
    got["sampled_second_turn"] = ask_together(
        srv, [ask(sampled + draw(sizes["more"][1]), 10, 0.7)])
    a, b, c = (draw(n) for n in sizes["together"])
    got["together"] = ask_together(srv, [ask(a, 8), ask(b, 8, 0.9), ask(c, 8)])
    got["one_token"] = ask_together(
        srv, [ask(a[:20], 1), ask(b[:16], 1, 0.5), ask(c[:10], 6), ask(a[:9], 0)])
    # the prompt fills the context: one token, whatever was asked
    full = srv.model_cfg.n_positions - 1
    if full < 1024:
        got["context_full"] = ask_together(srv, [ask(draw(full), 5), ask(draw(14), 4, 0.6)])
    return got


def fresh_streams(model_id):
    """``run_cases`` on an engine of its own, unloaded behind them: what a
    module-scoped fixture of a family's engine tests returns."""
    srv = fresh_engine(model_id)
    try:
        return run_cases(srv, model_id)
    finally:
        srv.unload()


def expected(model_id):
    with open(STREAMS) as f:
        return json.load(f)[model_id]


@contextlib.contextmanager
def watch_the_round(srv, seen):
    """While open, ``seen`` gains in order ``("call", name)`` for every
    program the engine hands the device (``prefill``, ``sample``, ``scatter``
    for the changed rows, ``place`` for first tokens taken on the device,
    ``decode``) and ``("span", name)`` / ``("end", name)`` for the
    ``rt/engine/*`` spans it opens and closes."""
    from ray_tpu.observability import tracing

    dec = srv._dec
    kept = []

    def swap(obj, name, new):
        kept.append((obj, name, getattr(obj, name)))
        setattr(obj, name, new)

    def named(call, real):
        def handed(*args, **kwargs):
            seen.append(("call", call))
            return real(*args, **kwargs)
        return handed

    @contextlib.contextmanager
    def span(name, **_args):
        short = name[len("rt/engine/"):]
        seen.append(("span", short))
        try:
            yield
        finally:
            seen.append(("end", short))

    for name, call in (("prefill_paged", "prefill"), ("update_rows_paged", "scatter"),
                       ("decode_paged_and_sample", "decode"), ("decode_multi_paged", "decode")):
        swap(dec, name, named(call, getattr(dec, name)))
    for name, call in (("_sample_rows", "sample"), ("_place_rows", "place")):
        if hasattr(srv, name):
            swap(srv, name, named(call, getattr(srv, name)))
    swap(tracing, "span", span)
    try:
        yield seen
    finally:
        for obj, name, real in reversed(kept):
            setattr(obj, name, real)


def rounds_of(seen):
    """``seen`` cut at the round spans: a list of events a round, parked
    rounds (``idle``) left out."""
    rounds, cur = [], None
    for ev in seen:
        if ev == ("span", "round"):
            cur = []
        elif ev == ("end", "round"):
            if cur is not None and ("span", "idle") not in cur:
                rounds.append(cur)
            cur = None
        elif cur is not None:
            cur.append(ev)
    return rounds


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(HERE))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    streams = {}
    for model in SIZES:
        streams[model] = fresh_streams(model)
    os.makedirs(os.path.dirname(os.path.abspath(sys.argv[1])), exist_ok=True)
    with open(sys.argv[1], "w") as f:
        json.dump(streams, f, indent=1, sort_keys=True)
    print({m: {c: [len(t) for t in s] for c, s in v.items()} for m, v in streams.items()})
