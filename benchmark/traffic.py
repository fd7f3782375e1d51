"""Traffic plans for the serving generator, from a data file and a seed.

The sizes (turn lengths, reply lengths, think times, how many turns fit a
session) are drawn from the traffic file's own ``pool_seed``: every
``--seed`` meets the same set of sessions. The seed gives the bytes of
every text, so prompts are unshared across sessions and across seeds and
the same seed gives the same inputs, and, where the mix says ``"order":
"shuffled"``, the sessions' order. A mix in which the order itself changes
the work (which sessions meet in the batch) says ``"fixed"``.
"""

from __future__ import annotations

import math
import random
from typing import Any, Dict, List

# the program's chat template (serve/openai/tokenizer.py render_chat),
# copied: the benchmark counts a turn's prompt tokens itself and holds
# the front door's ``usage.prompt_tokens`` to it
_ROLE_OPEN = "<|{role}|>"
_ASSISTANT_CUE = "<|assistant|>"

_WORDS = (
    "page", "token", "mesh", "lease", "chip", "serve", "train", "batch",
    "cache", "round", "shard", "queue", "prefix", "decode", "stream", "route",
)


def render_chat(messages: List[Dict[str, str]]) -> str:
    parts = [_ROLE_OPEN.format(role=m["role"]) + m["content"] for m in messages]
    parts.append(_ASSISTANT_CUE)
    return "\n".join(parts)


def chat_prompt_tokens(messages: List[Dict[str, str]]) -> int:
    """Tokens of a chat prompt under the byte tokenizer."""
    return len(render_chat(messages).encode("utf-8"))


def text(rng: random.Random, n_bytes: int) -> str:
    """ASCII words, exactly ``n_bytes`` long (one byte, one token)."""
    out: List[str] = []
    size = 0
    while size < n_bytes:
        w = _WORDS[rng.randrange(len(_WORDS))] + str(rng.randrange(10))
        out.append(w)
        size += len(w) + 1
    return " ".join(out)[:n_bytes].ljust(n_bytes, ".")


def draw(rng: random.Random, spec: Any) -> float:
    """One value of a distribution given as data: a number, or
    ``{"dist": "uniform"|"lognormal"|"exponential", ...}`` with optional
    ``lo``/``hi`` clamps."""
    if isinstance(spec, (int, float)):
        return spec
    kind = spec["dist"]
    if kind == "uniform":
        x = rng.uniform(spec["lo"], spec["hi"])
    elif kind == "lognormal":
        x = rng.lognormvariate(math.log(spec["median"]), spec["sigma"])
    elif kind == "exponential":
        x = rng.expovariate(1.0 / spec["mean"])
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "lo" in spec:
        x = max(spec["lo"], x)
    if "hi" in spec:
        x = min(spec["hi"], x)
    return x


def _turn_overhead(chat: bool) -> int:
    # "<|user|>" + text + "\n", and the assistant's "<|assistant|>" +
    # reply + "\n" once it is history
    return len("<|user|>\n") if chat else 0


def session_pool(traffic: Dict[str, Any]) -> List[List[Dict[str, float]]]:
    """The fixed set of session scripts: each a list of turns with
    ``turn_tokens``, ``reply_tokens`` and ``think_s``, cut where the next
    turn's prompt and reply would pass ``context_limit``."""
    rng = random.Random(int(traffic["pool_seed"]))
    chat = traffic["endpoint"].endswith("/chat/completions")
    limit = int(traffic["context_limit"])
    max_turns = traffic.get("max_turns") or 10**6
    base = int(traffic.get("system_prompt_tokens", 0))
    cue = len(_ASSISTANT_CUE) if chat else 0
    pool = []
    for _ in range(int(traffic["session_pool"])):
        turns: List[Dict[str, float]] = []
        history = base
        while len(turns) < max_turns:
            turn = {
                "turn_tokens": int(round(draw(rng, traffic["turn_tokens"]))),
                "reply_tokens": int(round(draw(rng, traffic["reply_tokens"]))),
                "think_s": float(draw(rng, traffic.get("think_s", 0))),
            }
            prompt = history + _turn_overhead(chat) + turn["turn_tokens"] + cue
            if turns and prompt + turn["reply_tokens"] > limit:
                break
            turn["prompt_tokens"] = prompt
            turns.append(turn)
            # the reply joins the history as "<|assistant|>reply\n"
            history = prompt + turn["reply_tokens"] + (1 if chat else 0)
        pool.append(turns)
    return pool


def plan(traffic: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The sessions in this seed's order with their texts' seeds, the
    shared system prompt, and the probe's prompt."""
    rng = random.Random(f"plan/{seed}")
    pool = session_pool(traffic)
    order = list(range(len(pool)))
    if traffic.get("order", "shuffled") == "shuffled":
        rng.shuffle(order)
    n_sys = int(traffic.get("system_prompt_tokens", 0))
    system = None
    if n_sys:
        # rendered as "<|system|>" + content + "\n": exactly n_sys tokens
        system = text(random.Random(f"system/{seed}"),
                      n_sys - len("<|system|>\n"))
    probe = traffic["probe"]
    return {
        "system": system,
        "sessions": [{"script": pool[i], "text_seed": f"{seed}/{i}"} for i in order],
        "probe_prompt": text(random.Random(f"probe/{seed}"),
                             int(probe["prompt_tokens"])),
    }


def turn_request(traffic: Dict[str, Any], model: str, plan_: Dict[str, Any],
                 session: Dict[str, Any], k: int) -> Dict[str, Any]:
    """The body of turn ``k`` of a session. Earlier turns are history:
    the user's own texts and, for each reply, ASCII filler of exactly the
    reply's token count (random weights answer in arbitrary bytes, which
    would re-encode to a different length; a real reply re-tokenises to
    its own length)."""
    script = session["script"]
    texts = [
        text(random.Random(f"{session['text_seed']}/u{j}"),
             int(script[j]["turn_tokens"]))
        for j in range(k + 1)
    ]
    body: Dict[str, Any] = {
        "model": model, "max_tokens": int(script[k]["reply_tokens"]),
        "temperature": 0, "stream": True, "user": session["text_seed"],
    }
    if not traffic["endpoint"].endswith("/chat/completions"):
        body["prompt"] = texts[k]
        return body
    messages: List[Dict[str, str]] = []
    if plan_["system"] is not None:
        messages.append({"role": "system", "content": plan_["system"]})
    for j in range(k + 1):
        messages.append({"role": "user", "content": texts[j]})
        if j < k:
            messages.append({
                "role": "assistant",
                "content": text(random.Random(f"{session['text_seed']}/a{j}"),
                                int(script[j]["reply_tokens"])),
            })
    body["messages"] = messages
    return body
