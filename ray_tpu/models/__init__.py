"""Models, and the registry the serving engine finds one by.

A served model is a config and a decode module, found by ``model_id``
(``resolve``). The decode module is the engine's interface, the same
names whichever model implements them (``models/gpt2_decode.py``,
``models/mimo_v2.py``, ``models/deepseek_v3.py``, ``models/afmoe.py``,
``models/phi4flash.py`` and ``models/qwen3_next.py`` do):

    load_serving_params(cfg, checkpoint_path)   the stored weights
    params_bytes(params)
    init_paged_cache(cfg, num_pages, page_tokens, rows) -> (k, v)
    cache_layout(cfg, k, v)                     stored shapes, bytes by kind
    prefill_paged(cfg, params, tokens, start, length, k, v, page_table, row)
    decode_paged_and_sample(...), decode_multi_paged(...), MAX_DECODE_CHUNK
    update_rows_paged(...)
    sample(logits, temps, greedy_mask, rng)     the first tokens of a
                                                prefill call of rows
    PREFIX_CACHE                                whether a sealed page may
                                                be matched by a later prompt
    PREFILL_ROWS                                the rows a prefill call takes:
                                                (1,), or the row counts the
                                                engine compiles, and then
    PREFILL_ROW_WIDTHS                          the widths of a call ([R, P]
                                                tokens, start, length, row
                                                [R], page tables [R,
                                                MaxPages]; logits [R, vocab]
                                                back); where PREFIX_CACHE, a
                                                sequence may take several
                                                rows of one call
    DECODE_ATTENTION                            what decode attends over
    STEP_COUNTERS                               names of what a decode
                                                program counts beside its
                                                tokens (its last result)

A module whose prompt positions stop short of its last layers also says
how many of a prefill call's went through them all
(``prefill_cross_positions(rows, tokens)``, ``models/phi4flash.py``: the
engine counts them where the name is there).

A family's modules are imported when a model of it is first resolved and
not before: a replica that serves GPT-2 never imports another family.
"""

from __future__ import annotations

import importlib
from typing import Any, Tuple

# family -> (the module with CONFIGS, the decode module); a model_id
# belongs to the family whose name it starts with
FAMILIES = {
    "gpt2": ("ray_tpu.models.gpt2", "ray_tpu.models.gpt2_decode"),
    "mimo-v2": ("ray_tpu.models.mimo_v2", "ray_tpu.models.mimo_v2"),
    "kanana-2": ("ray_tpu.models.deepseek_v3", "ray_tpu.models.deepseek_v3"),
    "trinity": ("ray_tpu.models.afmoe", "ray_tpu.models.afmoe"),
    "phi-4-mini-flash": ("ray_tpu.models.phi4flash", "ray_tpu.models.phi4flash"),
    "qwen3-next": ("ray_tpu.models.qwen3_next", "ray_tpu.models.qwen3_next"),
}


def resolve(model_id: str) -> Tuple[Any, Any]:
    """(config, decode module) of ``model_id``; a KeyError that names the
    known ids of its family, or the families, otherwise."""
    for family, (configs, decode) in FAMILIES.items():
        if model_id.startswith(family):
            known = importlib.import_module(configs).CONFIGS
            if model_id not in known:
                raise KeyError(
                    f"no model {model_id!r}; the {family} family has {sorted(known)}"
                )
            return known[model_id], importlib.import_module(decode)
    raise KeyError(
        f"no model {model_id!r}: its name starts with none of the families "
        f"{sorted(FAMILIES)}"
    )
