"""The two decode programs around a family's one-token step, written once
(ROADMAP D18): ``models/qwen3_next.py`` takes them from here; the four
families before it still write them out, and a ``simplicity`` PR that can
show their cells unchanged moves them, and the helpers they import from
``gpt2_decode.py``, here too."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.models.gpt2_decode import MAX_DECODE_CHUNK, sample


def counting_decode_programs(step_impl, counters: int):
    """``decode_paged_and_sample`` and ``decode_multi_paged`` for a family
    whose rows may be nobody's (length 0) and whose step counts beside its
    tokens: ``step_impl(cfg, params, last_tokens, lengths, cache_k, cache_v,
    page_tables)`` -> (logits, k, v, the step's ``counters`` counts). Both
    return what GPT-2's do and the counts last, summed over a chunk's steps;
    a row that had no length has none after the step either: it stays
    nobody's until the engine writes a sequence into it."""

    @partial(jax.jit, static_argnums=(0,), donate_argnums=(4, 5))
    def decode_paged_and_sample(cfg, params, last_tokens, lengths, cache_k, cache_v,
                                page_tables, temps, greedy_mask, rng_base, step):
        logits, cache_k, cache_v, counted = step_impl(
            cfg, params, last_tokens, lengths, cache_k, cache_v, page_tables
        )
        nxt = sample(logits, temps, greedy_mask, jax.random.fold_in(rng_base, step))
        return nxt, jnp.where(lengths > 0, lengths + 1, 0), cache_k, cache_v, counted

    @partial(jax.jit, static_argnums=(0,), donate_argnums=(4, 5))
    def decode_multi_paged(cfg, params, last_tokens, lengths, cache_k, cache_v,
                           page_tables, temps, greedy_mask, rng_base, n_steps, step0):
        def body(i, carry):
            last, lens, ck, cv, toks, counted = carry
            logits, ck, cv, step_counted = step_impl(
                cfg, params, last, lens, ck, cv, page_tables
            )
            nxt = sample(logits, temps, greedy_mask, jax.random.fold_in(rng_base, step0 + i))
            toks = jax.lax.dynamic_update_index_in_dim(toks, nxt, i, axis=0)
            return nxt, jnp.where(lens > 0, lens + 1, 0), ck, cv, toks, counted + step_counted

        last, lens, cache_k, cache_v, toks, counted = jax.lax.fori_loop(
            0, n_steps, body,
            (last_tokens, lengths, cache_k, cache_v,
             jnp.zeros((MAX_DECODE_CHUNK, last_tokens.shape[0]), jnp.int32),
             jnp.zeros((counters,), jnp.int32)),
        )
        return toks, last, lens, cache_k, cache_v, counted

    return decode_paged_and_sample, decode_multi_paged
