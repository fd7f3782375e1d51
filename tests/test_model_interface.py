"""What the serving engine asks of a model family, held for every family.

``ray_tpu/models/__init__.py``'s docstring lists the names a decode module
has; ``serve/llm.py`` reads them at whatever round first needs one, so a
family that lacks a name fails there, mid-traffic. Here each registered
family is asked for all of them at once, at its tiny preset, on the CPU,
with no engine thread and no program compiled. A fifth family adds its
tiny ``model_id`` to ``TINY`` and reads that docstring.
"""

import re

import pytest

from ray_tpu import models

TINY = ("gpt2-tiny", "mimo-v2-tiny", "kanana-2-tiny", "trinity-tiny",
        "phi-4-mini-flash-tiny", "qwen3-next-tiny")


def interface_names():
    """The names of the docstring's indented list: a line that opens the
    list's column with an identifier, up to its description."""
    names = []
    for line in models.__doc__.splitlines():
        if not re.match(r" {4}[A-Za-z_]", line):
            continue
        head = re.split(r"\s{2,}", line.strip())[0].split(" -> ")[0]
        names += [n.strip() for n in re.sub(r"\([^)]*\)", "", head).split(",")]
    return names


@pytest.fixture(scope="module", params=TINY)
def family(request):
    import jax

    jax.config.update("jax_platforms", "cpu")
    return models.resolve(request.param)


def test_the_docstring_lists_fifteen_names_and_every_family_is_asked():
    names = interface_names()
    assert len(names) == len(set(names)) == 15, names
    assert {"prefill_paged", "sample", "PREFIX_CACHE", "STEP_COUNTERS"} <= set(names)
    assert {m.split("-tiny")[0] for m in TINY} == set(models.FAMILIES)


def test_the_decode_module_has_every_name_of_the_interface(family):
    _, dec = family
    # a module whose prefill call takes one row names no widths of a row
    optional = {"PREFILL_ROW_WIDTHS"} if tuple(dec.PREFILL_ROWS) == (1,) else set()
    missing = [n for n in interface_names() if not hasattr(dec, n) and n not in optional]
    assert not missing, f"{dec.__name__} lacks {missing}"
    # the name the deleted prefill tier asked for (PR 51), spelled in two
    # parts so that a search for it over the tree finds nothing
    assert not hasattr(dec, "KV_" + "TRANSFER")


def test_prefill_rows_ascend_from_one_and_widths_go_with_several_rows(family):
    _, dec = family
    rows = tuple(dec.PREFILL_ROWS)
    assert rows[0] == 1 and list(rows) == sorted(set(rows)), rows
    assert hasattr(dec, "PREFILL_ROW_WIDTHS") == (len(rows) > 1)
    if len(rows) > 1:
        widths = tuple(dec.PREFILL_ROW_WIDTHS)
        assert widths and list(widths) == sorted(set(widths)), widths


def test_cache_layout_names_its_bytes_by_the_kinds_the_gauges_have(family):
    from ray_tpu.observability import core_metrics

    cfg, dec = family
    k, v = dec.init_paged_cache(cfg, 3, 16, 2)
    layout = dec.cache_layout(cfg, k, v)
    assert layout["shape"]
    held = layout["bytes"]
    assert set(held) <= set(core_metrics.KV_KINDS), held
    assert all(isinstance(n, int) and n >= 0 for n in held.values()), held
    assert sum(held.values()) > 0


def test_every_step_counter_has_its_series(family):
    from ray_tpu.observability import core_metrics

    _, dec = family
    missing = [n for n in dec.STEP_COUNTERS
               if not hasattr(core_metrics, f"serve_{n}")]
    assert not missing, f"{dec.__name__} counts {missing}: no core_metrics.serve_<name>"
