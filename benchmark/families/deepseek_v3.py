"""``model_type: deepseek_v3`` (``ray_tpu.models.deepseek_v3``), as the
harness sees it: the names ``benchmark/families/gpt2.py`` lists, for the
serving side, and the functions that count the operations and bytes of the
expert layers and of the latent attention.

The configuration file's ``model`` block holds the published config's keys
and no other. Every routed expert and the whole vocabulary are held, so
``n_routed_experts`` is both what the router scores and what the chip holds.
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
# program's (DeepSeek-V3 itself has a low-rank q, groups and rotary scaling)
IMPLEMENTS: Dict[str, Any] = {
    "attention_bias": False, "hidden_act": "silu", "model_type": "deepseek_v3",
    "moe_layer_freq": 1, "n_group": 1, "norm_topk_prob": True, "q_lora_rank": None,
    "rope_interleave": True, "rope_scaling": None, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
}
BYTES = 2.0  # bfloat16, weights and cache


def program_sizes(model_id: str) -> Dict[str, Any]:
    from ray_tpu.models import deepseek_v3

    c = deepseek_v3.CONFIGS[model_id]
    return {
        **IMPLEMENTS,
        "first_k_dense_replace": c.first_k_dense_replace, "head_dim": c.head_dim,
        "hidden_size": c.hidden_size, "intermediate_size": c.intermediate_size,
        "kv_lora_rank": c.kv_lora_rank,
        "max_position_embeddings": c.max_position_embeddings,
        "moe_intermediate_size": c.moe_intermediate_size,
        "n_routed_experts": c.n_routed_experts, "n_shared_experts": c.n_shared_experts,
        "num_attention_heads": c.num_attention_heads,
        "num_experts_per_tok": c.num_experts_per_tok,
        "num_hidden_layers": c.num_hidden_layers,
        "num_key_value_heads": c.num_key_value_heads,
        "qk_head_dim": c.qk_head_dim, "qk_nope_head_dim": c.qk_nope_head_dim,
        "qk_rope_head_dim": c.qk_rope_head_dim, "rms_norm_eps": c.rms_norm_eps,
        "rope_theta": c.rope_theta, "routed_scaling_factor": c.routed_scaling_factor,
        "v_head_dim": c.v_head_dim, "vocab_size": c.vocab_size,
    }


def context(model: Dict[str, Any]) -> int:
    return int(model["max_position_embeddings"])


def serve_params(model_id: str):
    """What ``--check`` compares outside any run: the engine's own stored
    weights (``load_serving_params`` from ``PRNGKey(0)``)."""
    from ray_tpu.models import deepseek_v3

    mcfg = deepseek_v3.CONFIGS[model_id]
    return mcfg, deepseek_v3.load_serving_params(mcfg)


# A token whose selection margin (the reference's own: how far its lowest
# chosen and its best unchosen expert's score + bias lie apart, at the
# closest of the expert layers) is under this is not judged. The program
# computes the scores in float32 from a residual stream that bfloat16
# products have moved, by about a hundredth of its size at the fourth
# expert layer, and with 128 experts the sixth and the seventh score lie
# close: the program then rightly ranks them the other way and the token is
# off by an expert's whole output. On the chip, token by token over 24
# seeds (2,496 tokens; my chip run, PR 48), the gaps fall in two heaps with
# nothing between: 2,138 tokens at 0.097 or less, and 358 at 0.64 or more
# whose margins are 0.00513 or less (17 of them over 2**-9, one over
# 2**-8). Two bfloat16 numbers in [0.5, 1), where the chosen experts'
# scores lie, are 2**-8 apart; this is twice that, half as much again as
# the largest margin seen to move.
TIE = 2.0 ** -7


def token_gaps(mcfg, model: Dict[str, Any], params, seed: int,
               prompt_lens: Sequence[int], steps: int,
               page_tokens: int = 64, chunk: int = 512,
               served=None) -> List[Dict[str, Any]]:
    """Seeded prompts are prefilled through ``prefill_paged``, ``chunk``
    tokens a call as the engine does (so a longer prompt meets the expanded
    path over a prefix, ``start > 0``), each into its own rows of the page
    table, and the seeded continuations are decoded side by side one token
    a step (``_decode_paged_impl``, the body of both decode programs, the
    absorbed path), rows of unequal length, across pages and page-table
    columns. The logits of every prefill call's last position and of every
    row at every decode step are held against the reference's full forward
    pass over the same sequence. The programs run on ``served`` where it
    is given (the control, ``lower_precision``) and on ``params``
    otherwise; the reference always on ``params``.

    One entry a compared token: ``phase``, ``row``, ``position``, ``gap``
    (max |program - reference| over its logits), ``margin`` (the
    reference's selection margin at that position) and ``reference_std``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import deepseek_v3_ref
    from ray_tpu.models import deepseek_v3 as dec

    rows = len(prompt_lens)
    max_pages = -(-context(model) // page_tokens)
    need = [-(-(p + steps) // page_tokens) for p in prompt_lens]
    cache, none = dec.init_paged_cache(mcfg, 1 + sum(need), page_tokens, rows)
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
        logits, closest = deepseek_v3_ref.forward(params, jnp.asarray(s), model,
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
            logits, cache, none = dec.prefill_paged(
                mcfg, params, jnp.asarray(tok), jnp.int32(start), jnp.int32(n),
                cache, none, jnp.asarray(tables[r]), np.int32(r),
            )
            start += n
            out.append(entry("prefill", r, start - 1, np.asarray(logits)))
    step = jax.jit(dec._decode_paged_impl, static_argnums=(0,), donate_argnums=(4, 5))
    for i in range(steps):
        last = jnp.asarray([seqs[r][p + i] for r, p in enumerate(prompt_lens)])
        lens = jnp.asarray([p + i for p in prompt_lens], jnp.int32)
        logits, cache, none, _ = step(
            mcfg, params, last, lens, cache, none, jnp.asarray(tables)
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
    over the tokens whose selection margin is ``TIE`` or more.

    The others are counted (``tokens_tied``) and their largest gap rides
    along (``tied_worst``), unjudged: where the reference's own scores of
    a chosen and an unchosen expert lie within bfloat16's rounding of each
    other, the program ranks them one way and the float32 reference the
    other, rightly both, and that token's logits then differ by an
    expert's whole output, as much as if the model were wrong. Which
    tokens those are is the reference's to say, from its own scores,
    before the program is looked at. A fault in one row or one chunk moves
    that row's other tokens and is held to the maximum. A phase none of
    whose tokens is judged reads 0 and says so (``prefill_judged``,
    ``decode_judged``): prefill gives one token a call, eight in the
    configuration's check, and where all eight are tied there is nothing of
    that phase to hold in that run (what prefill wrote into the cache the
    decode steps still read); judged on all of them, as MiMo's family does,
    one run in fifteen failed on a token that had rightly moved (seed 101,
    2.31; my chip run, PR 48). With the reference logits' own spread for
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


def attention_params(model: Dict[str, Any]) -> int:
    """q, the down-projection, the latent norm, the up-projection and the
    output of one layer."""
    d, h = int(model["hidden_size"]), int(model["num_attention_heads"])
    rank, rope = int(model["kv_lora_rank"]), int(model["qk_rope_head_dim"])
    nope, v = int(model["qk_nope_head_dim"]), int(model["v_head_dim"])
    return d * h * (nope + rope) + d * (rank + rope) + rank + rank * h * (nope + v) + h * v * d


def _expert_layers(model: Dict[str, Any]) -> int:
    return int(model["num_hidden_layers"]) - int(model["first_k_dense_replace"])


def params_outside_experts(model: Dict[str, Any]) -> int:
    """Every weight a decode step reads whole: attention, the dense FFN,
    the shared experts, the routers, the norms and the head. Not the
    embedding (a step gathers its rows' vectors, not the table) and not
    the routed experts."""
    d, e = int(model["hidden_size"]), int(model["n_routed_experts"])
    dense = int(model["first_k_dense_replace"])
    shared = 3 * d * int(model["n_shared_experts"]) * int(model["moe_intermediate_size"])
    total = int(model["vocab_size"]) * d + d
    total += int(model["num_hidden_layers"]) * (attention_params(model) + 2 * d)
    total += dense * 3 * d * int(model["intermediate_size"])
    total += _expert_layers(model) * (shared + d * e + e)
    return total


def params_count(model: Dict[str, Any]) -> int:
    """All parameters the chip holds (3,149.6 M for the cut of
    ``kanana-2-30b-a3b-serve``)."""
    return (params_outside_experts(model) + int(model["vocab_size"]) * int(model["hidden_size"])
            + _expert_layers(model) * int(model["n_routed_experts"]) * expert_params(model))


def latent_token_bytes(model: Dict[str, Any]) -> float:
    """What the cache has to keep of one position in one layer."""
    return BYTES * (int(model["kv_lora_rank"]) + int(model["qk_rope_head_dim"]))


def expected_experts_hit(model: Dict[str, Any], rows: float) -> float:
    """Distinct experts that ``rows`` tokens reach in one layer under even
    routing: E * (1 - (1 - k / E) ** rows)."""
    e = float(model["n_routed_experts"])
    return e * (1.0 - (1.0 - float(model["num_experts_per_tok"]) / e) ** rows)


def decode_step_bytes(model: Dict[str, Any], rows: float, mean_context: float,
                      experts_hit: Optional[float] = None) -> float:
    """Bytes one decode step has to read and no more: the weights outside
    the routed experts once, the rows' embedding vectors, the weights of
    the distinct experts the rows reached a layer-step where the decode
    programs counted them (``experts_hit``, from ``decode_step_mfu``'s
    reader: the kernel visits no expert without a row) and of the experts
    expected under even routing where they did not, and the rows' latent
    cache in every layer."""
    hit = expected_experts_hit(model, rows) if experts_hit is None else experts_hit
    total = BYTES * (params_outside_experts(model) + rows * int(model["hidden_size"]))
    total += BYTES * _expert_layers(model) * hit * expert_params(model)
    total += rows * mean_context * int(model["num_hidden_layers"]) * latent_token_bytes(model)
    return total


def mla_cost(model: Dict[str, Any], context_tokens: float) -> Dict[str, float]:
    """What decode's attention has to do for ``context_tokens`` (the
    positions the live rows attended over, summed over steps), whatever
    implements it: every such position's latent row read once a layer, and
    for each of the heads a product with the row (rank + rotary) and one
    that weighs its rank."""
    layers, heads = int(model["num_hidden_layers"]), int(model["num_attention_heads"])
    rank, rope = int(model["kv_lora_rank"]), int(model["qk_rope_head_dim"])
    return {"bytes": context_tokens * layers * latent_token_bytes(model),
            "flops": context_tokens * layers * heads * 2.0 * ((rank + rope) + rank)}
