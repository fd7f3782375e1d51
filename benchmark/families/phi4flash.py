"""``model_type: phi4flash`` (``ray_tpu.models.phi4flash``,
Phi-4-mini-flash-reasoning), as the harness sees it: the names
``benchmark/families/gpt2.py`` lists, for the serving side, and the
functions that count a decode step's bytes and the operations and bytes of
the attention over the one paged layer that eight layers read.

The configuration file's ``model`` block holds the published config's keys
and no other. The Mamba mixer's sizes are not among them: the modelling
code's defaults (``MAMBA``), the same in the program's config.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence

from benchmark.families.gpt2 import warm_row_updates  # noqa: F401 — the engine's
# row-update program is one program for every model (``update_rows_paged``)
from benchmark.families.mimo_v2 import (  # noqa: F401 — the same for any family:
    # the float8 control and the engine's rule for a prefill call's width
    _bucket, lower_precision,
)

# what the program implements and has no switch for, under the published
# config's keys; a configuration file that says otherwise is not this
# program's: the head tied to the embedding, two layers a period (Mamba
# kind, then attention kind), SiLU, no dropout, biases on the attention's
# products and none on the MLP's or the head's
IMPLEMENTS: Dict[str, Any] = {
    "embd_pdrop": 0, "hidden_act": "silu", "lm_head_bias": False, "mb_per_layer": 2,
    "mlp_bias": False, "model_type": "phi4flash", "resid_pdrop": 0,
    "tie_word_embeddings": True,
}
# the Mamba mixer as the modelling code's defaults size it: d_inner = expand
# x hidden_size, dt_rank = ceil(hidden_size / 16)
MAMBA = {"d_state": 16, "d_conv": 4, "expand": 2}
BYTES = 2.0        # bfloat16: weights, K and V, the convolution's inputs
STATE_BYTES = 4.0  # float32: the recurrent state


def program_sizes(model_id: str) -> Dict[str, Any]:
    from ray_tpu.models import phi4flash

    c = phi4flash.CONFIGS[model_id]
    if {"d_state": c.mamba_d_state, "d_conv": c.mamba_d_conv,
            "expand": c.mamba_expand} != MAMBA:
        raise ValueError(f"{model_id}: the program's Mamba sizes are not {MAMBA}")
    return {
        **IMPLEMENTS,
        "hidden_size": c.hidden_size, "intermediate_size": c.intermediate_size,
        "layer_norm_eps": c.layer_norm_eps,
        "max_position_embeddings": c.max_position_embeddings,
        "num_attention_heads": c.num_attention_heads,
        "num_hidden_layers": c.num_hidden_layers,
        "num_key_value_heads": c.num_key_value_heads,
        "sliding_window": c.sliding_window, "vocab_size": c.vocab_size,
    }


def context(model: Dict[str, Any]) -> int:
    return int(model["max_position_embeddings"])


def serve_params(model_id: str):
    """What ``--check`` compares outside any run: the engine's own stored
    weights (``load_serving_params`` from ``PRNGKey(0)``)."""
    from ray_tpu.models import phi4flash

    mcfg = phi4flash.CONFIGS[model_id]
    return mcfg, phi4flash.load_serving_params(mcfg)


def _on_states(mcfg, cache, change):
    """``cache`` (K's or V's) with ``change`` applied to the Mamba layers'
    arrays and the other layers' left as they are."""
    from ray_tpu.models import phi4flash as dec

    return type(cache)(tuple(
        change(a) if s["kind"] == "state" else a
        for s, a in zip(dec.cache_spec(mcfg), cache.layers)), cache.page_tokens)


def state_in_bfloat16(mcfg, cache_k):
    """A control: the cache with every Mamba layer's recurrent state held
    in bfloat16, the nearest precision below the float32 the configuration
    states. The programs write a state back in the type they met it in, so
    the same programs run. (On the chip it reads inside the sound runs'
    range: the configuration's ``check.why``.)"""
    import jax.numpy as jnp

    return _on_states(mcfg, cache_k, lambda a: a.astype(jnp.bfloat16))


def reference_logits(model: Dict[str, Any], params, seed: int,
                     prompt_lens: Sequence[int], steps: int, chunk: int = 512,
                     wrong: Sequence[str] = ()):
    """The seeded sequences (a prompt and its continuation a row) and the
    reference's logits at the positions that are compared: every prefill
    call's last position, ``chunk`` tokens a call, and every decode step's.
    (sequences, a dict position -> logits a row.)"""
    import numpy as np

    from benchmark.reference import phi4flash_ref

    rng = np.random.default_rng([seed, 23])
    seqs = [rng.integers(0, int(model["vocab_size"]), p + steps, dtype=np.int32)
            for p in prompt_lens]
    asked = [sorted({min(start + chunk, p) - 1 for start in range(0, p, chunk)}
                    | set(range(p, p + steps))) for p in prompt_lens]
    want = []
    for s, at in zip(seqs, asked):
        logits = phi4flash_ref.forward(params, s, model, positions=at, wrong=wrong)
        want.append(dict(zip(at, np.asarray(logits))))
    return seqs, want


def token_gaps(mcfg, model: Dict[str, Any], params, seed: int,
               prompt_lens: Sequence[int], steps: int,
               page_tokens: int = 64, chunk: int = 512,
               reference=None, state_control: bool = False,
               wrong: Sequence[str] = ()) -> List[Dict[str, Any]]:
    """Seeded prompts are prefilled through ``prefill_paged``, ``chunk``
    tokens a call as the engine does (so a longer prompt meets chunks at
    ``start > 0``: its states and convolution inputs as the earlier chunks
    left them and, past the window, a ring that has wrapped), each into its
    own decode row, and the seeded continuations are decoded side by side
    one token a step (``_decode_paged_impl``, the body of both decode
    programs), rows of unequal length, one past the window beside one
    inside it. The rows' states start dirty (ones), so a first chunk that
    does not reset them shows. The logits of every prefill call's last
    position (the only one the cross-decoder ran on) and of every row at
    every decode step are held against the reference's full forward pass,
    all layers over all positions, over the same sequence
    (``reference_logits`` on ``params``, as another model under ``wrong``:
    ``phi4flash_ref.WRONG``). A control gives ``reference`` as the sound
    weights made it and ``params`` as it would have the programs run
    (``lower_precision``; two trees of 7.7 GB do not fit the chip at
    once), or asks for the states in bfloat16 (``state_control``).

    One entry a compared token: ``phase``, ``row``, ``position``, ``gap``
    (max |program - reference| over its logits) and ``reference_std``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import phi4flash as dec

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
    seqs, want = reference or reference_logits(model, params, seed, prompt_lens, steps,
                                               chunk, wrong)
    std = float(np.std(np.stack(list(want[0].values()))))

    def entry(phase, r, position, got):
        return {"phase": phase, "row": r, "position": position,
                "gap": float(np.abs(got - want[r][position]).max()),
                "reference_std": std}

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
                  reference=None, state_control: bool = False,
                  wrong: Sequence[str] = ()) -> Dict[str, Any]:
    """``token_gaps``, and under ``prefill_max_abs`` and ``decode_max_abs``
    the LARGEST gap of each phase's tokens, as GPT-2's family gives it.
    There are no experts, so no token is set aside as tied: every compared
    token is judged (``prefill_judged``, ``decode_judged``). With the
    reference logits' own spread for scale: the six keys
    ``serve_sessions._check`` reads, and the counts."""
    tokens = token_gaps(mcfg, model, params, seed, prompt_lens, steps, page_tokens, chunk,
                        reference, state_control, wrong)

    def gaps(phase):
        return [t["gap"] for t in tokens if t["phase"] == phase]

    return {
        "prefill_max_abs": max(gaps("prefill")), "decode_max_abs": max(gaps("decode")),
        "prefill_judged": len(gaps("prefill")), "decode_judged": len(gaps("decode")),
        "tokens_compared": len(tokens),
        "reference_logit_std": tokens[0]["reference_std"],
        "rows": len(prompt_lens), "prompt_lens": list(prompt_lens), "decode_steps": steps,
    }


# -- operations and bytes -------------------------------------------------


def _kinds(model: Dict[str, Any]) -> List[str]:
    """The kind of every layer's mixer, by the reference's rule."""
    from benchmark.reference import phi4flash_ref

    return [phi4flash_ref.kind(model, l) for l in range(int(model["num_hidden_layers"]))]


def mixer_params(model: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of one mixer of each kind, from the published keys and
    ``MAMBA``: 41.24 M, 19.67 M, 26.21 M and 13.11 M at the published
    widths."""
    d, h, hkv = (int(model[k]) for k in ("hidden_size", "num_attention_heads",
                                         "num_key_value_heads"))
    dh = d // h
    din, n, rank = MAMBA["expand"] * d, MAMBA["d_state"], math.ceil(d / 16)
    lambdas_and_norm = 4 * dh + 2 * dh
    attention = (d * (h + 2 * hkv) * dh + (h + 2 * hkv) * dh   # qkv and its bias
                 + h * dh * d + d + lambdas_and_norm)            # output and its bias
    mamba = (d * 2 * din + MAMBA["d_conv"] * din + din          # in, conv and its bias
             + din * (rank + 2 * n) + rank * din + din          # x, dt and its bias
             + n * din + din + din * d)                         # A, D, out
    cross = d * h * dh + h * dh + h * dh * d + d + lambdas_and_norm
    return {"mamba": mamba, "window": attention, "full": attention,
            "gmu": 2 * d * din, "cross": cross}


def params_count(model: Dict[str, Any]) -> int:
    """All parameters the chip holds (3,852,562,944 for
    ``phi-4-mini-flash-serve``): the mixers, an MLP and two LayerNorms a
    layer, the final norm, and the embedding once: it is the head."""
    d, f = int(model["hidden_size"]), int(model["intermediate_size"])
    mix = mixer_params(model)
    return (sum(mix[k] + 3 * d * f + 4 * d for k in _kinds(model))
            + 2 * d + int(model["vocab_size"]) * d)


def position_bytes(model: Dict[str, Any]) -> float:
    """What the cache keeps of one position in one attention layer: K and V
    of every K/V head (5,120 B)."""
    dh = int(model["hidden_size"]) // int(model["num_attention_heads"])
    return BYTES * 2 * int(model["num_key_value_heads"]) * dh


def state_bytes(model: Dict[str, Any]) -> float:
    """What one Mamba layer keeps a decode row: the state in float32 and
    the convolution's last inputs (358,400 B)."""
    din = MAMBA["expand"] * int(model["hidden_size"])
    return STATE_BYTES * MAMBA["d_state"] * din + BYTES * (MAMBA["d_conv"] - 1) * din


def shared_readers(model: Dict[str, Any]) -> int:
    """Layers that attend over the full layer's pages: itself and every
    cross layer (eight)."""
    return sum(k in ("full", "cross") for k in _kinds(model))


def decode_step_bytes(model: Dict[str, Any], rows: float,
                      mean_context: float) -> float:
    """Bytes one decode step has to read (and, for the states, write) and
    no more: every weight once (the tied embedding is the head, so it is
    read whole), the rows' embedding vectors, every Mamba layer's state and
    convolution inputs read and written, the window layers' held positions
    min(context, window), and the full layer's K and V ONCE FOR EACH OF THE
    LAYERS THAT ATTEND OVER THEM. The full layer and the seven cross layers
    have queries of their own and run one after another, each needing the
    whole of the row's K and V; at the cell's shapes that is 128 rows x
    ~1,250 positions x 5,120 B = 0.8 GB a reading, which does not stay in
    the chip's 128 MiB of VMEM from one layer to the next, so no
    implementation on this chip reads it fewer than eight times. Counted
    once, 12.1 of the step's 17.9 GB would be hidden and a step at its
    bytes would read as two thirds."""
    kinds = _kinds(model)
    window = min(mean_context, float(model["sliding_window"]))
    a_row = (2 * kinds.count("mamba") * state_bytes(model)
             + kinds.count("window") * window * position_bytes(model)
             + shared_readers(model) * mean_context * position_bytes(model))
    return (BYTES * (params_count(model) + rows * int(model["hidden_size"]))
            + rows * a_row)


def shared_kv_cost(model: Dict[str, Any], context_tokens: float) -> Dict[str, float]:
    """What decode's attention over the full layer's pages has to do for
    ``context_tokens`` (the positions the live rows attended over, p + 1 a
    row, summed over steps and counted once a step), whatever implements
    it: every such position's K and V read once by each of the layers that
    attend over them (``shared_readers``; ``decode_step_bytes`` says why
    not fewer), and for each query head a product with its key (Dh) and one
    that weighs its pair's values (2 Dh)."""
    heads = int(model["num_attention_heads"])
    dh = int(model["hidden_size"]) // heads
    readers = shared_readers(model)
    return {"bytes": context_tokens * readers * position_bytes(model),
            "flops": context_tokens * readers * heads * 2.0 * (dh + 2 * dh)}


def window_cost(model: Dict[str, Any], window_tokens: float) -> Dict[str, float]:
    """What decode's attention in the window layers has to do for
    ``window_tokens`` (the positions the live rows' windows held, min(p +
    1, window) a row, summed over steps), whatever implements it: every
    such position's K and V read once a window layer, and for each query
    head a product with its key and one that weighs its pair's values."""
    layers = _kinds(model).count("window")
    heads = int(model["num_attention_heads"])
    dh = int(model["hidden_size"]) // heads
    return {"bytes": window_tokens * layers * position_bytes(model),
            "flops": window_tokens * layers * heads * 2.0 * (dh + 2 * dh)}
