"""``model_type: afmoe`` (``ray_tpu.models.afmoe``, Trinity-Mini), as the
harness sees it: the names ``benchmark/families/gpt2.py`` lists, for the
serving side, and the functions that count the operations and bytes of the
expert layers and of the window layers' ring attention.

The configuration file's ``model`` block holds the published config's keys
and no other. Every routed expert and the whole vocabulary are held, so
``num_experts`` is both what the router scores and what the chip holds.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from benchmark.families.gpt2 import warm_row_updates  # noqa: F401 — the engine's
# row-update program is one program for every model (``update_rows_paged``)
from benchmark.families.mimo_v2 import (  # noqa: F401 — the same for any family:
    # the float8 control, the engine's rule for a prefill call's width, and a
    # routed expert's parameters and the expert layers' cost from the same keys
    _bucket, expert_params, lower_precision, moe_cost,
)

# what the program implements and has no switch for, under the published
# config's keys; a configuration file that says otherwise is not this
# program's: no expert groups (``n_group``, ``num_expert_groups``,
# ``num_limited_groups``, ``topk_group`` other than 1), no rotary scaling, no
# tied embeddings, no other score than the sigmoid. ``load_balance_coeff`` is
# training's and ``use_grouped_mm`` an implementation's word; both are
# carried. ``global_attn_every_n_layers`` is carried too: ``layer_types`` says
# which layers are full, in the source as here
IMPLEMENTS: Dict[str, Any] = {
    "global_attn_every_n_layers": 4, "hidden_act": "silu", "load_balance_coeff": 0.001,
    "model_type": "afmoe", "mup_enabled": True, "n_group": 1, "num_expert_groups": 1,
    "num_limited_groups": 1, "rope_scaling": None, "route_norm": True,
    "score_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "use_grouped_mm": True,
}
BYTES = 2.0  # bfloat16, weights and cache


def program_sizes(model_id: str) -> Dict[str, Any]:
    from ray_tpu.models import afmoe

    c = afmoe.CONFIGS[model_id]
    return {
        **IMPLEMENTS,
        "head_dim": c.head_dim, "hidden_size": c.hidden_size,
        "intermediate_size": c.intermediate_size, "layer_types": list(c.layer_types),
        "max_position_embeddings": c.max_position_embeddings,
        "moe_intermediate_size": c.moe_intermediate_size,
        "num_attention_heads": c.num_attention_heads,
        "num_dense_layers": c.num_dense_layers, "num_experts": c.num_experts,
        "num_experts_per_tok": c.num_experts_per_tok, "num_hidden_layers": c.n_layer,
        "num_key_value_heads": c.num_key_value_heads,
        "num_shared_experts": c.num_shared_experts, "rms_norm_eps": c.rms_norm_eps,
        "rope_theta": c.rope_theta, "route_scale": c.route_scale,
        "sliding_window": c.sliding_window, "vocab_size": c.vocab_size,
    }


def context(model: Dict[str, Any]) -> int:
    return int(model["max_position_embeddings"])


def serve_params(model_id: str):
    """What ``--check`` compares outside any run: the engine's own stored
    weights (``load_serving_params`` from ``PRNGKey(0)``)."""
    from ray_tpu.models import afmoe

    mcfg = afmoe.CONFIGS[model_id]
    return mcfg, afmoe.load_serving_params(mcfg)


# A token whose selection margin (the reference's own: how far its lowest
# chosen and its best unchosen expert's score + bias lie apart, at the
# closest of the expert layers) is under this is not judged: the program
# computes the scores in float32 from a residual stream that bfloat16
# products have moved, ranks two experts that close the other way, rightly,
# and the token is then off by an expert's whole output. 128 experts, top 8,
# as Kanana's family, whose readings set the same limit (twice the distance
# of two bfloat16 numbers in [0.5, 1)); this family's own readings on the
# chip stand in the configuration's ``check.why``.
TIE = 2.0 ** -7


def token_gaps(mcfg, model: Dict[str, Any], params, seed: int,
               prompt_lens: Sequence[int], steps: int,
               page_tokens: int = 64, chunk: int = 512,
               served=None) -> List[Dict[str, Any]]:
    """Seeded prompts are prefilled through ``prefill_paged``, ``chunk``
    tokens a call as the engine does (so a longer prompt meets chunks at
    ``start > 0``, its ring as the earlier chunks left it and, past the
    window, a ring that has wrapped), each into its own decode row, and the
    seeded continuations are decoded side by side one token a step
    (``_decode_paged_impl``, the body of both decode programs), rows of
    unequal length, one past the window beside rows inside it. The logits
    of every prefill call's last position and of every row at every decode
    step are held against the reference's full forward pass over the same
    sequence. The programs run on ``served`` where it is given (the
    control, ``lower_precision``) and on ``params`` otherwise; the
    reference always on ``params``.

    One entry a compared token: ``phase``, ``row``, ``position``, ``gap``
    (max |program - reference| over its logits), ``margin`` (the
    reference's selection margin at that position) and ``reference_std``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import afmoe_ref
    from ray_tpu.models import afmoe as dec

    rows = len(prompt_lens)
    max_pages = -(-context(model) // page_tokens)
    need = [-(-(p + steps) // page_tokens) for p in prompt_lens]
    cache_k, cache_v = dec.init_paged_cache(mcfg, 1 + sum(need), page_tokens, rows)
    tables = np.zeros((rows, max_pages), np.int32)
    nxt = 1  # page 0 is the scratch page
    for r, n in enumerate(need):
        tables[r, :n] = np.arange(nxt, nxt + n)
        nxt += n
    rng = np.random.default_rng([seed, 23])
    seqs = [rng.integers(0, int(model["vocab_size"]), p + steps, dtype=np.int32)
            for p in prompt_lens]
    # the positions whose logits are compared: every prefill call's last,
    # and every decode step's
    asked = [sorted({min(start + chunk, p) - 1 for start in range(0, p, chunk)}
                    | set(range(p, p + steps))) for p in prompt_lens]
    want, margin = [], []
    for s, at in zip(seqs, asked):
        logits, closest = afmoe_ref.forward(params, jnp.asarray(s), model,
                                            margins=True, positions=at)
        want.append(dict(zip(at, np.asarray(logits))))
        margin.append(np.asarray(closest))
    std = float(np.std(np.stack(list(want[0].values()))))
    params = params if served is None else served

    def entry(phase, r, position, got):
        return {"phase": phase, "row": r, "position": position,
                "gap": float(np.abs(got - want[r][position]).max()),
                "margin": float(margin[r][position]), "reference_std": std}

    out: List[Dict[str, Any]] = []
    for r, p in enumerate(prompt_lens):
        start = 0
        while start < p:
            n = min(p - start, chunk)
            tok = np.zeros((1, _bucket(n)), np.int32)
            tok[0, :n] = seqs[r][start:start + n]
            logits, cache_k, cache_v = dec.prefill_paged(
                mcfg, params, jnp.asarray(tok), jnp.int32(start), jnp.int32(n),
                cache_k, cache_v, jnp.asarray(tables[r]), np.int32(r),
            )
            start += n
            out.append(entry("prefill", r, start - 1, np.asarray(logits)))
    step = jax.jit(dec._decode_paged_impl, static_argnums=(0,), donate_argnums=(4, 5))
    for i in range(steps):
        last = jnp.asarray([seqs[r][p + i] for r, p in enumerate(prompt_lens)])
        lens = jnp.asarray([p + i for p in prompt_lens], jnp.int32)
        logits, cache_k, cache_v, _ = step(
            mcfg, params, last, lens, cache_k, cache_v, jnp.asarray(tables)
        )
        got = np.asarray(logits)
        out += [entry("decode", r, p + i, got[r]) for r, p in enumerate(prompt_lens)]
    return out


def compare_serve(mcfg, model: Dict[str, Any], params, seed: int,
                  prompt_lens: Sequence[int], steps: int,
                  page_tokens: int = 64, chunk: int = 512,
                  served=None) -> Dict[str, Any]:
    """``token_gaps``, and under ``prefill_max_abs`` and ``decode_max_abs``
    the LARGEST gap of each phase's tokens, as GPT-2's family gives it,
    over the tokens whose selection margin is ``TIE`` or more. The others
    are counted (``tokens_tied``) and their largest gap rides along
    (``tied_worst``), unjudged; which tokens those are is the reference's
    to say, from its own scores, before the program is looked at. A fault
    in one row, one chunk or one ring moves that row's other tokens and is
    held to the maximum. A phase none of whose tokens is judged reads 0 and
    says so (``prefill_judged``, ``decode_judged``), as Kanana's family
    does and for its reason. With the reference logits' own spread for
    scale: the six keys ``serve_sessions._check`` reads, and the counts."""
    tokens = token_gaps(mcfg, model, params, seed, prompt_lens, steps, page_tokens, chunk,
                        served)

    def judged(phase):
        return [t["gap"] for t in tokens if t["phase"] == phase and t["margin"] >= TIE]

    tied = [t["gap"] for t in tokens if t["margin"] < TIE]
    return {
        "prefill_max_abs": max(judged("prefill"), default=0.0),
        "decode_max_abs": max(judged("decode"), default=0.0),
        "prefill_judged": len(judged("prefill")), "decode_judged": len(judged("decode")),
        "tokens_compared": len(tokens), "tokens_tied": len(tied),
        "tied_worst": max(tied, default=0.0),
        "reference_logit_std": tokens[0]["reference_std"],
        "rows": len(prompt_lens), "prompt_lens": list(prompt_lens), "decode_steps": steps,
    }


# -- operations and bytes -------------------------------------------------


def _kinds(model: Dict[str, Any]):
    """(sliding?, experts?) of every layer."""
    dense = int(model["num_dense_layers"])
    return [(kind == "sliding_attention", l >= dense)
            for l, kind in enumerate(model["layer_types"])]


def attention_params(model: Dict[str, Any]) -> int:
    """q, k, v, the gate, the output and the two norms over a head of one
    layer."""
    d, h, hkv = (int(model[k]) for k in ("hidden_size", "num_attention_heads",
                                         "num_key_value_heads"))
    dh = int(model["head_dim"])
    return d * h * dh * 3 + d * hkv * dh * 2 + 2 * dh


def position_bytes(model: Dict[str, Any]) -> float:
    """What the cache keeps of one position in one layer: K and V of every
    K/V head."""
    return BYTES * 2 * int(model["num_key_value_heads"]) * int(model["head_dim"])


def params_outside_experts(model: Dict[str, Any]) -> int:
    """Every weight a decode step reads whole: attention, the four norms a
    layer, the dense FFN, the shared experts, the routers and the head. Not
    the embedding (a step gathers its rows' vectors, not the table) and not
    the routed experts."""
    d, e = int(model["hidden_size"]), int(model["num_experts"])
    shared = 3 * d * int(model["num_shared_experts"]) * int(model["moe_intermediate_size"])
    total = int(model["vocab_size"]) * d + d
    for _, experts_here in _kinds(model):
        total += attention_params(model) + 4 * d
        total += (shared + d * e + e) if experts_here else 3 * d * int(model["intermediate_size"])
    return total


def params_count(model: Dict[str, Any]) -> int:
    """All parameters the chip holds (4,241.6 M for the cut of
    ``trinity-mini-serve``)."""
    expert_layers = sum(e for _, e in _kinds(model))
    return (params_outside_experts(model) + int(model["vocab_size"]) * int(model["hidden_size"])
            + expert_layers * int(model["num_experts"]) * expert_params(model))


def held_experts(model: Dict[str, Any]) -> int:
    """Routed experts the chip holds a layer (``moe_load_skew``'s mean is
    over them): all of them, under this family's own key."""
    return int(model["num_experts"])


def expected_experts_hit(model: Dict[str, Any], rows: float) -> float:
    """Distinct experts that ``rows`` tokens reach in one layer under even
    routing: E * (1 - (1 - k / E) ** rows)."""
    e = float(model["num_experts"])
    return e * (1.0 - (1.0 - float(model["num_experts_per_tok"]) / e) ** rows)


def decode_step_bytes(model: Dict[str, Any], rows: float, mean_context: float,
                      experts_hit: Optional[float] = None) -> float:
    """Bytes one decode step has to read and no more: the weights outside
    the routed experts once, the rows' embedding vectors, the weights of
    the distinct experts the rows reached a layer-step where the decode
    programs counted them (``experts_hit``, from ``decode_step_mfu``'s
    reader: the kernel visits no expert without a row) and of the experts
    expected under even routing where they did not, the live K/V of the
    full layers and the window's K/V of the sliding layers."""
    hit = expected_experts_hit(model, rows) if experts_hit is None else experts_hit
    total = BYTES * (params_outside_experts(model) + rows * int(model["hidden_size"]))
    for sliding, experts_here in _kinds(model):
        if experts_here:
            total += BYTES * hit * expert_params(model)
        seen = min(mean_context, float(model["sliding_window"])) if sliding else mean_context
        total += rows * seen * position_bytes(model)
    return total


def window_cost(model: Dict[str, Any], window_tokens: float) -> Dict[str, float]:
    """What decode's attention in the sliding layers has to do for
    ``window_tokens`` (the positions the live rows' windows held, min(p +
    1, window) a row, summed over steps), whatever implements it: every
    such position's K and V read once a sliding layer, and for each query
    head a product with its key and one that weighs its value."""
    layers = sum(s for s, _ in _kinds(model))
    heads, dh = int(model["num_attention_heads"]), int(model["head_dim"])
    return {"bytes": window_tokens * layers * position_bytes(model),
            "flops": window_tokens * layers * heads * 2.0 * (dh + dh)}
