"""``model_type: qwen3_next`` (``ray_tpu.models.qwen3_next``,
Qwen3-Next-80B-A3B-Instruct), as the harness sees it: the names
``benchmark/families/gpt2.py`` lists, for the serving side, and the
functions that count a decode step's bytes and the expert layers' cost.

The configuration file's ``model`` block holds the published config's keys
and no other. ``num_experts`` and ``vocab_size`` there are what this chip
holds of a layer. How many experts the router still scores (the published
``num_experts``) has no key of the source's left to stand under, so the
program's preset says it (``routed_over``), as MiMo-V2's family does.
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
# program's: an expert layer in every layer (``decoder_sparse_step`` 1, no
# ``mlp_only_layers``), gates renormalised over the chosen, no rotary
# scaling, no window, the head untied
IMPLEMENTS: Dict[str, Any] = {
    "decoder_sparse_step": 1, "hidden_act": "silu", "mlp_only_layers": [],
    "model_type": "qwen3_next", "norm_topk_prob": True, "rope_scaling": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
}
BYTES = 2.0        # bfloat16: weights, K and V, the convolution's inputs
STATE_BYTES = 4.0  # float32: the delta rule's state


def program_sizes(model_id: str) -> Dict[str, Any]:
    from ray_tpu.models import qwen3_next

    c = qwen3_next.CONFIGS[model_id]
    return {
        **IMPLEMENTS,
        "full_attention_interval": c.full_attention_interval, "head_dim": c.head_dim,
        "hidden_size": c.hidden_size, "intermediate_size": c.intermediate_size,
        "linear_conv_kernel_dim": c.linear_conv_kernel_dim,
        "linear_key_head_dim": c.linear_key_head_dim,
        "linear_num_key_heads": c.linear_num_key_heads,
        "linear_num_value_heads": c.linear_num_value_heads,
        "linear_value_head_dim": c.linear_value_head_dim,
        "max_position_embeddings": c.max_position_embeddings,
        "moe_intermediate_size": c.moe_intermediate_size,
        "num_attention_heads": c.num_attention_heads, "num_experts": c.num_experts,
        "num_experts_per_tok": c.num_experts_per_tok,
        "num_hidden_layers": c.num_hidden_layers,
        "num_key_value_heads": c.num_key_value_heads,
        "partial_rotary_factor": c.partial_rotary_factor,
        "rms_norm_eps": c.rms_norm_eps, "rope_theta": c.rope_theta,
        "shared_expert_intermediate_size": c.shared_expert_intermediate_size,
        "vocab_size": c.vocab_size,
    }


def routed_over(model: Dict[str, Any]) -> int:
    """The experts the router scores, for the preset whose sizes ``model``
    holds (``Qwen3NextConfig.router_experts``: 512 where 128 are held)."""
    from ray_tpu.models import qwen3_next

    for model_id, c in qwen3_next.CONFIGS.items():
        if program_sizes(model_id) == model:
            return int(c.router_experts)
    raise KeyError("no preset of ray_tpu.models.qwen3_next has these sizes")


def context(model: Dict[str, Any]) -> int:
    return int(model["max_position_embeddings"])


def serve_params(model_id: str):
    """What ``--check`` compares outside any run: the engine's own stored
    weights (``load_serving_params`` from ``PRNGKey(0)``)."""
    from ray_tpu.models import qwen3_next

    mcfg = qwen3_next.CONFIGS[model_id]
    return mcfg, qwen3_next.load_serving_params(mcfg)


def _on_states(mcfg, cache, change):
    """``cache`` (K's or V's) with ``change`` applied to the linear layers'
    arrays and the full layers' pages left as they are."""
    from ray_tpu.models import qwen3_next as dec

    return type(cache)(tuple(
        change(a) if s["kind"] == "state" else a
        for s, a in zip(dec.cache_spec(mcfg), cache.layers)), cache.page_tokens)


def state_in_bfloat16(mcfg, cache_k):
    """A control: the cache with every linear layer's state held in
    bfloat16, the nearest precision below the float32 the configuration
    states. The programs write a state back in the type they met it in, so
    the same programs run."""
    import jax.numpy as jnp

    return _on_states(mcfg, cache_k, lambda a: a.astype(jnp.bfloat16))


# A token whose selection margin in the reference is under this is not
# judged: the program computes the router's logits in float32 from a residual
# stream that bfloat16 products have moved, ranks two experts that close the
# other way, rightly, and the token is then off by an expert's whole output.
# The margin is between LOGITS (``qwen3_next_ref.selection_margin``): the
# softmax's scores over 512 experts are about 0.01, so the 2**-7 that the
# sigmoid families hold between scores in [0, 1] would tie every token here;
# a sigmoid's slope is at most a quarter, so their 2**-7 is 2**-5 between
# logits, and that is what stands here. Token by token on the chip (2,334
# tokens over three seeds; my chip run, PR 63) there are no two heaps as in
# the sigmoid families: one expert of ten, with the smallest gate, moves a
# token by 0.1-0.4, and the largest gap falls with the margin, 0.36 / 0.42 /
# 0.33 / 0.26 / 0.175 under 2**-8 / to 2**-7 / to 2**-6 / to 2**-5 / over,
# where the float8 control's every token reads 0.52 or more. 84-92% of the
# tokens lie under it; the configuration's ``check.why`` has the rest.
TIE = 2.0 ** -5
# Sequences that leave a phase no token to judge are drawn again from the
# same generator, by the reference's margins alone and before the program is
# looked at (``families/mimo_v2.DRAWS``)
DRAWS = 8
# The configuration's ``logit_tolerance``, and the largest gap a stored state
# of layer 0 may show against the reference's (a value head's |S - S_ref| /
# |S_ref|): ``compare_serve``. On the chip (my chip run, PR 63; three seeds,
# prompts to 4,096) the sound programs read 0.0039-0.0043 behind the prompt
# and behind 16 to 512 decode steps alike; with the states stored in bfloat16
# 0.0043-0.0048 behind the prompt (a prefill call rounds a state once) and
# 0.0078 / 0.0097 / 0.0124 / 0.0150 / 0.0173 / 0.0190 at the least behind 16 /
# 32 / 64 / 128 / 256 / 512 steps (a step rounds it once); float8 weights
# 0.070-0.120. The check decodes 128 steps
LOGIT_LIMIT = 0.35
STATE_LIMIT = 0.008


def _compared(prompt_lens: Sequence[int], steps: int, chunk: int):
    """The (row, position) pairs whose logits are compared, by phase: every
    prefill call's last position, ``chunk`` tokens a call, and every decode
    step's."""
    return {
        "prefill": [(r, min(start + chunk, p) - 1)
                    for r, p in enumerate(prompt_lens) for start in range(0, p, chunk)],
        "decode": [(r, p + i) for r, p in enumerate(prompt_lens) for i in range(steps)],
    }


def reference_logits(model: Dict[str, Any], params, seed: int,
                     prompt_lens: Sequence[int], steps: int, chunk: int = 512,
                     wrong: Sequence[str] = (),
                     state_steps: Optional[Sequence[int]] = None):
    """The seeded sequences (a prompt and its continuation a row) and what
    the reference says of them: (sequences, a dict position -> logits a row,
    a dict position -> selection margin a row, a dict decode steps taken ->
    every linear layer's state [layers, Hv, Dk, Dv] a row for ``state_steps``
    (0 is the prompt alone; default: that and the last step, what
    ``token_gaps`` compares), the draw). Sequences that leave a phase no token
    whose margin is ``TIE`` or more are drawn again, up to ``DRAWS`` times."""
    import numpy as np

    from benchmark.reference import qwen3_next_ref

    state_steps = (0, steps) if state_steps is None else tuple(state_steps)
    rng = np.random.default_rng([seed, 23])
    compared = _compared(prompt_lens, steps, chunk)
    asked = [sorted({at for where in compared.values() for r, at in where if r == row})
             for row in range(len(prompt_lens))]
    for draw in range(1, DRAWS + 1):
        seqs = [rng.integers(0, int(model["vocab_size"]), p + steps, dtype=np.int32)
                for p in prompt_lens]
        want, margin, states = [], [], []
        for s, at, p in zip(seqs, asked, prompt_lens):
            logits, closest, kept = qwen3_next_ref.forward(
                params, s, model, margins=True, positions=at, wrong=wrong,
                states_at=[p - 1 + n for n in state_steps])
            want.append(dict(zip(at, np.asarray(logits))))
            margin.append(dict(zip(at, np.asarray(closest))))
            states.append(dict(zip(state_steps, np.stack(kept, axis=1))))
        if all(any(margin[r][at] >= TIE for r, at in where) for where in compared.values()):
            break
    return seqs, want, margin, states, draw


def token_gaps(mcfg, model: Dict[str, Any], params, seed: int,
               prompt_lens: Sequence[int], steps: int,
               page_tokens: int = 64, chunk: int = 512,
               reference=None, state_control: bool = False,
               wrong: Sequence[str] = (), state_steps: Optional[Sequence[int]] = None):
    """Seeded prompts are prefilled through ``prefill_paged``, ``chunk``
    tokens a call as the engine does (so a longer prompt meets chunks at
    ``start > 0``: its states and convolution inputs as the earlier chunks
    left them, its pages as they wrote them), each into its own decode row,
    and the seeded continuations are decoded side by side one token a step
    (``_decode_paged_impl``, the body of both decode programs), rows of
    unequal length. The rows' states start dirty (ones), so a first chunk
    that does not reset them shows. The logits of every prefill call's last
    position and of every row at every decode step, and every linear layer's
    stored state of every row behind its prompt and behind ``state_steps``
    decode steps (default: behind the prompt and behind the last step), are
    held against the reference's full forward pass over the same sequence,
    the recurrence position by position from a zero state
    (``reference_logits`` on ``params``, as another model under ``wrong``:
    ``qwen3_next_ref.WRONG``), which also draws the sequences. A control
    gives ``reference`` as the sound weights made it and ``params`` as it
    would have the programs run (``lower_precision``; two trees of 7.3 GB do
    not fit the chip at once), or asks for the states in bfloat16
    (``state_control``).

    (tokens, states). One entry a compared token: ``phase``, ``row``,
    ``position``, ``gap`` (max |program - reference| over its logits),
    ``margin`` (the reference's selection margin at that position),
    ``reference_std`` and ``draw``. One entry a compared state: ``phase``
    (``prefill`` behind the prompt, else ``decode``), ``row``, ``steps``,
    ``layer`` and ``gaps``, a value head's |S - S_ref| over |S_ref|
    (Frobenius), [Hv]."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import qwen3_next as dec

    state_steps = (0, steps) if state_steps is None else tuple(state_steps)
    rows = len(prompt_lens)
    max_pages = -(-context(model) // page_tokens)
    need = [-(-(p + steps) // page_tokens) for p in prompt_lens]
    cache_k, cache_v = dec.init_paged_cache(mcfg, 1 + sum(need), page_tokens, rows)
    cache_k, cache_v = (_on_states(mcfg, c, lambda a: a + 1) for c in (cache_k, cache_v))
    if state_control:
        cache_k = state_in_bfloat16(mcfg, cache_k)
    tables = np.zeros((rows, max_pages), np.int32)
    nxt = 1  # page 0 is the scratch page
    for r, n in enumerate(need):
        tables[r, :n] = np.arange(nxt, nxt + n)
        nxt += n
    seqs, want, margin, ref_states, draw = reference or reference_logits(
        model, params, seed, prompt_lens, steps, chunk, wrong, state_steps)
    std = float(np.std(np.stack(list(want[0].values()))))
    linear = [l for l, s in enumerate(dec.cache_spec(mcfg)) if s["kind"] == "state"]

    def entry(phase, r, position, got):
        return {"phase": phase, "row": r, "position": position,
                "gap": float(np.abs(got - want[r][position]).max()),
                "margin": float(margin[r][position]), "reference_std": std, "draw": draw}

    def state_entries(cache, taken, which):
        if taken not in state_steps:
            return []
        out = []
        for r in which:
            ref = ref_states[r][taken]                          # [layers, Hv, Dk, Dv]
            for i, l in enumerate(linear):
                got = np.asarray(cache.layers[l][r], np.float32)
                out.append({
                    "phase": "decode" if taken else "prefill", "row": r, "steps": taken,
                    "layer": l,
                    "gaps": (np.linalg.norm(got - ref[i], axis=(1, 2))
                             / np.linalg.norm(ref[i], axis=(1, 2))).tolist()})
        return out

    tokens: List[Dict[str, Any]] = []
    states: List[Dict[str, Any]] = []
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
            tokens.append(entry("prefill", r, start - 1, np.asarray(logits)))
        states += state_entries(cache_k, 0, [r])
    step = jax.jit(dec._decode_paged_impl, static_argnums=(0,), donate_argnums=(4, 5))
    for i in range(steps):
        last = jnp.asarray([seqs[r][p + i] for r, p in enumerate(prompt_lens)])
        lens = jnp.asarray([p + i for p in prompt_lens], jnp.int32)
        logits, cache_k, cache_v, _ = step(
            mcfg, params, last, lens, cache_k, cache_v, jnp.asarray(tables)
        )
        got = np.asarray(logits)
        tokens += [entry("decode", r, p + i, got[r]) for r, p in enumerate(prompt_lens)]
        states += state_entries(cache_k, i + 1, range(rows))
    return tokens, states


def compare_serve(mcfg, model: Dict[str, Any], params, seed: int,
                  prompt_lens: Sequence[int], steps: int,
                  page_tokens: int = 64, chunk: int = 512,
                  reference=None, state_control: bool = False,
                  wrong: Sequence[str] = ()) -> Dict[str, Any]:
    """``token_gaps``, and under ``prefill_max_abs`` and ``decode_max_abs``
    what ``serve_sessions._check`` holds to the configuration's one
    ``logit_tolerance`` (``LOGIT_LIMIT``), a phase: the larger of

    - the LARGEST logit gap of the phase's tokens (``prefill_logit_gap``,
      ``decode_logit_gap``), as GPT-2's family gives it, over the tokens whose
      selection margin is ``TIE`` or more. The others are counted
      (``tokens_tied``) and their largest gap rides along (``tied_worst``),
      unjudged; which tokens those are is the reference's to say, from its own
      logits, before the program is looked at. ``reference_logits`` draws the
      sequences again until each phase has a token to judge (``draws``); a
      phase that ``DRAWS`` draws leave none reads 0 and says so
      (``prefill_judged``, ``decode_judged``);
    - the LARGEST gap of layer 0's stored states (``prefill_state_gap`` behind
      the prompts, ``decode_state_gap`` behind the last decode step: a value
      head's |S - S_ref| / |S_ref|, every head of every row, none set aside),
      in the tolerance's unit: times ``LOGIT_LIMIT / STATE_LIMIT``, so a state
      gap of ``STATE_LIMIT`` reads as the tolerance. The harness reads two
      numbers and one limit, and the state's type moves the states and hardly
      the logits (the configuration's ``check.why``). Layer 0 is the linear
      layer with no expert layer upstream: an expert rightly ranked the other
      way moves the positions behind it in every later layer's state, which
      sums over all of them and can set none aside, so those read ten times
      layer 0's in sound runs and ride along unjudged
      (``state_gap_behind_experts``).

    With the reference logits' own spread for scale: the six keys
    ``serve_sessions._check`` reads, and the counts."""
    tokens, states = token_gaps(mcfg, model, params, seed, prompt_lens, steps, page_tokens,
                                chunk, reference, state_control, wrong)

    def judged(phase):
        return [t["gap"] for t in tokens if t["phase"] == phase and t["margin"] >= TIE]

    def state_gap(phase):
        return max(g for s in states if s["phase"] == phase and s["layer"] == 0
                   for g in s["gaps"])

    tied = [t["gap"] for t in tokens if t["margin"] < TIE]
    out = {}
    for phase in ("prefill", "decode"):
        logit, state = max(judged(phase), default=0.0), state_gap(phase)
        out.update({f"{phase}_max_abs": max(logit, state * LOGIT_LIMIT / STATE_LIMIT),
                    f"{phase}_logit_gap": logit, f"{phase}_state_gap": state,
                    f"{phase}_judged": len(judged(phase))})
    return {
        **out, "state_limit": STATE_LIMIT,
        "state_gap_behind_experts": max(
            (g for s in states if s["layer"] for g in s["gaps"]), default=0.0),
        "tokens_compared": len(tokens), "tokens_tied": len(tied),
        "tied_worst": max(tied, default=0.0), "draws": tokens[0]["draw"],
        "reference_logit_std": tokens[0]["reference_std"],
        "rows": len(prompt_lens), "prompt_lens": list(prompt_lens), "decode_steps": steps,
    }


# -- operations and bytes -------------------------------------------------


def _full(model: Dict[str, Any]) -> List[bool]:
    """Whether each layer is a full-attention one, by the reference's rule."""
    from benchmark.reference import qwen3_next_ref

    return [qwen3_next_ref.full_layer(model, l) for l in range(int(model["num_hidden_layers"]))]


def _linear_sizes(model: Dict[str, Any]):
    """(the convolution's channels, the value heads' width, value heads)."""
    hk, hv = int(model["linear_num_key_heads"]), int(model["linear_num_value_heads"])
    dk, dv = int(model["linear_key_head_dim"]), int(model["linear_value_head_dim"])
    return 2 * hk * dk + hv * dv, hv * dv, hv


def mixer_params(model: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of one mixer of each kind: 33.72 M (linear) and 27.26 M
    (full) at the published widths."""
    d, h, hkv = (int(model[k]) for k in ("hidden_size", "num_attention_heads",
                                         "num_key_value_heads"))
    dh = int(model["head_dim"])
    channels, width, hv = _linear_sizes(model)
    linear = (d * (channels + width) + d * 2 * hv              # in_qkvz, in_ba
              + int(model["linear_conv_kernel_dim"]) * channels  # conv
              + 2 * hv + int(model["linear_value_head_dim"])   # A_log, dt_bias, norm
              + width * d)                                      # out
    full = d * h * 2 * dh + 2 * d * hkv * dh + h * dh * d + 2 * dh
    return {"linear": linear, "full": full}


def position_bytes(model: Dict[str, Any]) -> float:
    """What the cache keeps of one position in one full layer: K and V of
    every K/V head (2,048 B)."""
    return BYTES * 2 * int(model["num_key_value_heads"]) * int(model["head_dim"])


def state_bytes(model: Dict[str, Any]) -> float:
    """What one linear layer keeps a decode row: the state a value head in
    float32 and the convolution's last inputs (2,146,304 B)."""
    channels, _, hv = _linear_sizes(model)
    return (STATE_BYTES * hv * int(model["linear_key_head_dim"])
            * int(model["linear_value_head_dim"])
            + BYTES * (int(model["linear_conv_kernel_dim"]) - 1) * channels)


def params_outside_experts(model: Dict[str, Any]) -> int:
    """Every weight a decode step reads whole: the mixers, the two norms a
    layer, the routers, the shared experts and their gates, the final norm
    and the head. Not the embedding (a step gathers its rows' vectors, not
    the table) and not the routed experts."""
    d = int(model["hidden_size"])
    mix = mixer_params(model)
    shared = 3 * d * int(model["shared_expert_intermediate_size"]) + d
    a_layer = 2 * d + d * routed_over(model) + shared
    return (int(model["vocab_size"]) * d + d
            + sum(a_layer + mix["full" if full else "linear"] for full in _full(model)))


def params_count(model: Dict[str, Any]) -> int:
    """All parameters the chip holds (3,667.5 M for the cut of
    ``qwen3-next-80b-a3b-serve``)."""
    return (params_outside_experts(model) + int(model["vocab_size"]) * int(model["hidden_size"])
            + len(_full(model)) * int(model["num_experts"]) * expert_params(model))


def held_experts(model: Dict[str, Any]) -> int:
    """Routed experts the chip holds a layer (``moe_load_skew``'s mean is
    over them), under this family's own key."""
    return int(model["num_experts"])


def expected_experts_hit(model: Dict[str, Any], rows: float) -> float:
    """Distinct held experts that ``rows`` tokens reach in one layer under
    even routing: held * (1 - (1 - k / routed) ** rows)."""
    p = float(model["num_experts_per_tok"]) / routed_over(model)
    return float(model["num_experts"]) * (1.0 - (1.0 - p) ** rows)


def decode_step_bytes(model: Dict[str, Any], rows: float, mean_context: float,
                      experts_hit: Optional[float] = None) -> float:
    """Bytes one decode step has to read (and, for the states, write) and no
    more: the weights outside the routed experts once, the rows' embedding
    vectors, the weights of the distinct held experts the rows reached a
    layer-step where the decode programs counted them (``experts_hit``, from
    ``decode_step_mfu``'s reader) and of the experts expected under even
    routing where they did not, every linear layer's state and convolution
    inputs of the live rows read AND written (the state is the recurrence's
    carry: a step that did not write it back would have computed nothing),
    and the live K/V of the full layers."""
    hit = expected_experts_hit(model, rows) if experts_hit is None else experts_hit
    full = _full(model)
    linear = len(full) - sum(full)
    return (BYTES * (params_outside_experts(model) + rows * int(model["hidden_size"]))
            + len(full) * BYTES * hit * expert_params(model)
            + rows * (2 * linear * state_bytes(model)
                      + sum(full) * mean_context * position_bytes(model)))
