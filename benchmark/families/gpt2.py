"""GPT-2 (``ray_tpu.models.gpt2``, ``gpt2_decode``), as the harness sees it.

A family file is everything of the benchmark that knows one model family:
the harness finds it by the configuration's ``"family"``
(``find(bench, "families", name, ".py")`` under every directory of
``paths``) and asks it for the seven names below and for nothing else, so a
second family is a second file. The serving side only: the training
generator, ``check.compare_step`` and ``train_mfu`` are GPT-2's by name
(PERF.md, section 7).

    program_sizes(model_id)     the program's own sizes, in the keys of the
                                configuration file's ``"model"``
    context(model)              positions one sequence may hold
    serve_params(model_id)      (engine configuration, the engine's own weights)
    compare_serve(...)          prefill and decode through the paged cache
                                against the plain reference, logits compared
    warm_row_updates(...)       the engine's row-update program, every count
    decode_step_bytes(model, rows, mean_context[, experts_hit])
                                bytes one decode step has to read (optional:
                                without it ``decode_step_mfu`` reads nothing).
                                A family of routed experts takes the fourth
                                argument, the experts the rows reached a
                                layer-step as the program counted them, and
                                falls back to the experts expected under
                                even routing where it is not given
    held_experts(model)         routed experts the chip holds a layer (optional:
                                without it ``moe_load_skew`` takes the
                                configuration's ``n_routed_experts``)

What a cell brings beside its family file, and how its test may hold it.
A PR that adds a cell APPENDS: its configuration to ``configs``, its cell to
``workloads``, its metrics to ``per_layer``, and the cell's name to the
``workloads`` of every accepted metric it reports; it moves and edits no
accepted entry (the driver reads an entry put in the middle as a change to
the one it displaced, and refuses it). So the cell's test file under
``tests/bench/`` holds its entries BY NAME AND BY MEMBERSHIP, NEVER BY
PLACE: the cell is in ``workloads`` once and its configuration in
``configs`` once (``test_bench_engine_metrics.listed_once``), its metrics'
names are a subset of the lists it stands on, and no ``[-1]``, ``[-N:]`` or
number indexes ``workloads``, ``configs``, ``per_layer`` or ``end_to_end``
of the real file. The test file gives that check as a function of a
``bench`` dictionary under the name ``the_cell_stands_on_its_lists`` and
calls it with the real file;
``tests/bench/test_bench_contract.py`` finds it by that name and calls it,
with every rule of the contract, on a copy of ``BENCHMARK.json`` that has
one more configuration, cell and per-layer entry appended. That test
(``test_a_copy_with_a_cell_a_configuration_and_a_metric_appended_keeps_every_rule``
and the one behind it) is the definition of "the harness takes a cell
without an edit", and every PR that adds a cell runs it before it hands in:
three accepted PRs (58, 59, 61) could list nothing while one cell's test
pinned the ends of the lists.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence


def program_sizes(model_id: str) -> Dict[str, int]:
    from ray_tpu.models import gpt2

    ours = gpt2.CONFIGS[model_id]
    return {"n_embd": ours.d_model, "n_layer": ours.n_layer, "n_head": ours.n_head,
            "n_positions": ours.n_positions, "vocab_size": ours.vocab_size}


def context(model: Dict[str, int]) -> int:
    return int(model["n_positions"])


def serve_params(model_id: str):
    """What ``--check`` compares outside any run: the engine's own
    weights, ``gpt2.init`` from ``PRNGKey(0)``."""
    import jax

    from ray_tpu.models import gpt2

    mcfg = gpt2.CONFIGS[model_id]
    return mcfg, gpt2.init(jax.random.PRNGKey(0), mcfg)


def compare_serve(mcfg, model: Dict[str, int], params, seed: int,
                  prompt_lens: Sequence[int], steps: int,
                  page_tokens: int = 64) -> Dict[str, Any]:
    """A seeded prompt is prefilled through ``prefill_paged`` into the
    paged cache and the seeded continuation is decoded through it one token
    a step (``_decode_paged_impl``, the body of both decode programs), the
    rows of different lengths side by side; every step's logits are held
    against the reference's full forward pass over the same sequence.
    Returns max |program - reference| over the logits of the prefill's last
    position and of every decode step, with the reference logits' own
    spread for scale: the six keys ``serve_sessions._check`` reads."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import gpt2_ref
    from ray_tpu.models import gpt2_decode as dec

    rows = len(prompt_lens)
    max_pages = -(-mcfg.n_positions // page_tokens)
    need = [-(-(p + steps) // page_tokens) for p in prompt_lens]
    cache_k, cache_v = dec.init_paged_cache(mcfg, 1 + sum(need), page_tokens)
    tables = np.zeros((rows, max_pages), np.int32)
    nxt = 1  # page 0 is the scratch page
    for r, n in enumerate(need):
        tables[r, :n] = np.arange(nxt, nxt + n)
        nxt += n
    rng = np.random.default_rng([seed, 23])
    seqs = [rng.integers(0, mcfg.vocab_size, p + steps, dtype=np.int32)
            for p in prompt_lens]
    reference = jax.jit(lambda p, t: gpt2_ref.forward(p, t, model))
    want = [np.asarray(reference(params, jnp.asarray(s)[None])[0]) for s in seqs]

    def bucket(n: int) -> int:
        p = 16
        while p < n:
            p *= 2
        return p

    prefill_err: List[float] = []
    for r, p in enumerate(prompt_lens):
        tok = np.zeros((1, bucket(p)), np.int32)
        tok[0, :p] = seqs[r][:p]
        logits, cache_k, cache_v = dec.prefill_paged(
            mcfg, params, jnp.asarray(tok), jnp.int32(0), jnp.int32(p),
            cache_k, cache_v, jnp.asarray(tables[r]),
        )
        prefill_err.append(float(np.abs(np.asarray(logits) - want[r][p - 1]).max()))
    step = jax.jit(dec._decode_paged_impl, static_argnums=(0,))
    decode_err: List[float] = []
    for i in range(steps):
        last = jnp.asarray([seqs[r][p + i] for r, p in enumerate(prompt_lens)])
        lens = jnp.asarray([p + i for p in prompt_lens], jnp.int32)
        logits, cache_k, cache_v = step(
            mcfg, params, last, lens, cache_k, cache_v, jnp.asarray(tables)
        )
        got = np.asarray(logits)
        decode_err.append(max(
            float(np.abs(got[r] - want[r][p + i]).max())
            for r, p in enumerate(prompt_lens)
        ))
    return {
        "prefill_max_abs": max(prefill_err), "decode_max_abs": max(decode_err),
        "reference_logit_std": float(np.std(want[0])),
        "rows": rows, "prompt_lens": list(prompt_lens), "decode_steps": steps,
    }


def warm_row_updates(rows: int, max_pages: int) -> None:
    """Compile (or load from the cache) the engine's row-update program
    for every number of changed rows, 1 to ``rows``: it is jitted on the
    number of rows admitted or retired since the last dispatch, so a count
    first met inside a window would compile there. Same shapes and types
    as ``_engine_loop_paged`` passes; should they drift from the engine's,
    the programs compile inside the window after all, the compile cache
    grows there, and the run is not ``correct``."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import gpt2_decode as dec

    host = (np.zeros((rows,), np.int32), np.zeros((rows,), np.int32),
            np.zeros((rows,), np.float32), np.ones((rows,), bool),
            np.zeros((rows, max_pages), np.int32))
    for n in range(1, rows + 1):
        idx = np.arange(n, dtype=np.int32)
        out = dec.update_rows_paged(
            *(jnp.asarray(a) for a in host), jnp.asarray(idx),
            *(jnp.asarray(a[idx]) for a in host),
        )
    out[0].block_until_ready()


def params_count(model: Dict[str, int]) -> int:
    """All parameters at the published sizes (1,557.6 M for gpt2-xl,
    124.4 M for gpt2)."""
    d, l = model["n_embd"], model["n_layer"]
    per_layer = 12 * d * d + 13 * d  # kernels, biases, two layer norms
    return (
        model["vocab_size"] * d + model["n_positions"] * d
        + l * per_layer + 2 * d
    )


def decode_step_bytes(model: Dict[str, int], rows: float,
                      mean_context: float) -> float:
    """Bytes one decode step has to read: every weight once in bf16, and
    the live K and V of the active rows (bf16, every layer)."""
    weights = 2.0 * params_count(model)
    kv = rows * mean_context * 2 * model["n_layer"] * model["n_embd"] * 2.0
    return weights + kv
