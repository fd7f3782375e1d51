"""What the TPU's compiler makes of the three serving programs, without a chip.

Compiles ``decode_paged_and_sample``, ``decode_multi_paged`` and
``prefill_paged`` of a served model (``models.resolve``: its config and
decode module) for a described v5e at the engine's own sizes, and prints
for each: operations with ``remat`` in their name (and how often the text
says the word), the relays its family watches for, ``memory_analysis()``'s
arguments and temporaries, the layout the first pool enters in, the
loops it holds by how their carry opens (prefill's one loop over its rows'
page-table columns a layer, ``ops/page_loops.py``; the expert layers'; the
K-step loop) and the Pallas kernels it calls, by name: decode's attention
is one call a layer and holds no loop, ``paged_kv_attention`` over a
layer's pages and ``ring_kv_attention`` over a layer's rings in the
families with a K and a V pool (``ops/paged_kv_attention.py``, PR 58),
``paged_latent_attention`` in the latent one
(``ops/paged_latent_attention.py``, PR 56).

What a family watches for (a relay is an operation that writes an array
anew in another layout):

- GPT-2 (``gpt2_decode``), on the float32 tree ``gpt2.init`` returns and
  on the tree an engine holds (``serving_params``): copies of a whole page
  pool and of one layer of it (the pool's shape is ``init_paged_cache``'s),
  whole kernel stacks written anew (a ``convert`` of a float32 parameter to
  the compute type, or a ``copy`` to another layout); and the loader's own
  program (``load_serving_params``'s init and cast), whose temporaries are
  what a load holds beyond the weights.
- MiMo-V2 (``mimo_v2``), a cache an array a layer: copies of a full
  layer's pool, copies of a window layer's ring, and K or V split into
  heads (a ``copy``, ``reshape``, ``transpose`` or fusion whose result is
  ``[.., .., kv_heads, size]``: a gathered span of pages or a ring relaid
  so that the heads split, which the decode programs hold none of since
  PR 47 and prefill, whose keys are few, still does).

- Trinity (``afmoe``), the same two kinds of cache and the same counts: a
  ring of 2,048 positions a row is 0.27 GB a layer for K alone, so a ring
  copy in any program costs what a pool copy does.

- Phi-4-mini-flash (``phi4flash``), a cache of three kinds: the one paged
  layer's pool and the eight rings counted as above, and copies of a Mamba
  layer's state whole (``state copies``): the state is read and written
  every step and a relay would double its bytes.

- Qwen3-Next (``qwen3_next``), states and pages: the two paged layers'
  pools and copies of a delta-rule layer's state whole (``[rows, 32, 128,
  128]`` float32, 268 MB a layer at 128 rows), counted as Phi's are.

- ``deepseek_v3``, a latent pool a layer: copies of a layer's pool (6 GB
  in all at the benchmark's sizes: one copy does not fit beside it), and K
  or V a head of a cached span (any result ``[.., positions, heads, size]``
  or ``[.., heads, positions, size]`` with a page of positions or more:
  decode attends in the latent space and may build none; prefill expands a
  block of pages a turn as ``[positions, heads, size]``, one sequence's and
  so without the leading dimension, which this count does not look for).

The text names the operations the chip's trace will show (PERF.md, PR 30,
PR 32 and PR 47); it says nothing about time. Run here, on the CPU:

    JAX_PLATFORMS=cpu python tools/aot_serving_programs.py [--model gpt2-xl]
        [--max-batch-size 6] [--page-tokens 64] [--prefill 128]
    JAX_PLATFORMS=cpu python tools/aot_serving_programs.py --model mimo-v2.5 \\
        --max-batch-size 32 --prefill 512
    JAX_PLATFORMS=cpu python tools/aot_serving_programs.py --model kanana-2-30b-a3b \\
        --max-batch-size 32 --prefill 512 [--prefill-rows 4]
    JAX_PLATFORMS=cpu python tools/aot_serving_programs.py --model trinity-mini \\
        --max-batch-size 32 --prefill 512 [--prefill-rows 2]
    JAX_PLATFORMS=cpu python tools/aot_serving_programs.py \\
        --model phi-4-mini-flash-reasoning --max-batch-size 32 --prefill 512 [--prefill-rows 2]
    JAX_PLATFORMS=cpu python tools/aot_serving_programs.py \\
        --model qwen3-next-80b-a3b --max-batch-size 32 --prefill 512 [--prefill-rows 2]

``--prefill-rows R`` adds the prefill call of R rows (PR 50: the chunks a
round has to prefill in one call, the expert layers once), with the same
counts: its temporaries beside the one-row call's, no pool or ring copy,
and an attention loop a row a layer in the latent family.
"""

from __future__ import annotations

import argparse
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from ray_tpu import models
from ray_tpu.ops import flash_attention


def results_of(ops, shape, kinds="copy") -> int:
    """Operations of ``kinds`` in ``ops`` whose result has ``shape`` (a
    dimension may be a pattern), in any element type and layout."""
    dims = ",".join(map(str, shape))
    return sum(bool(re.search(rf"= \w+\[{dims}\]\S* (?:{kinds})\(", ln)) for ln in ops)


def entry_layouts(text: str, shape) -> list:
    """The layouts the compiled program takes arguments of ``shape`` in,
    from its ``entry_computation_layout``."""
    dims = ",".join(map(str, shape))
    head = re.search(r"entry_computation_layout=\{\((.*?)\)->", text)
    found = re.findall(rf"\w+\[{dims}\](\{{[^}}]*\}})", head.group(1)) if head else []
    return sorted(set(found))


def loops_of(text: str) -> dict:
    """The ``while`` operations of a compiled program by how their carry
    opens, as the trace names them (``(s32[],f32[32,32],..)``: the counter
    and the first array carried), with how many of each the text holds. A
    prefill call's loop over its rows' page-table columns carries its
    running maximum first, ``f32[rows, heads, chunk]``; decode's programs
    hold none over pages or rings since PR 58."""
    found = {}
    for ln in text.splitlines():
        carry = re.search(r"= \(s32\[\][^,]*, (\w+\[[\d,]*\]).*\) while\(", ln)
        if carry:
            name = f"(s32[],{carry.group(1)},..)"
            found[name] = found.get(name, 0) + 1
    return found


def kernels_of(text: str) -> dict:
    """The calls of Pallas kernels in a compiled program, by the kernel's
    name (the ``name`` of its ``pallas_call``, which the chip's trace shows
    as the operation's: ``grouped_matmul.3``, ``paged_latent_attention.11``,
    ``paged_kv_attention.7``, ``ring_kv_attention.3``),
    with how many of each the text holds."""
    found = {}
    for name in re.findall(
            r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*? custom-call\([^\n]*"
            r'custom_call_target="tpu_custom_call"', text):
        found[name] = found.get(name, 0) + 1
    return found


def expert_layer_counts(text: str, tree) -> dict:
    """What a compiled program makes of the expert layers of ``tree`` (the
    stacks ``[experts, .., ..]`` under a layer's ``moe``): its
    ``ragged-dot`` operations, the calls of the grouped products' kernel
    (``ops/grouped_matmul.py``, two an expert layer), and the stacks it
    writes anew, in any layout: a stack relaid for a kernel costs its bytes
    a call."""
    ops = [ln for ln in text.splitlines() if re.match(r"\s*(ROOT )?%\S+ = ", ln)]
    stacks = {leaf.shape for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
              if "'moe'" in jax.tree_util.keystr(path) and len(leaf.shape) == 3}
    if not stacks:
        return {}
    return {
        "ragged-dot": sum(" ragged-dot(" in ln for ln in ops),
        "kernel calls": kernels_of(text).get("grouped_matmul", 0),
        "expert-stack copies": sum(
            results_of(ops, s, "copy|fusion|transpose|convert|reshape") for s in stacks),
    }


def report(name: str, compiled, watch, pool, tree=None) -> None:
    """A program's counts (``watch`` is (label, count of ``ops``) pairs),
    what it makes of the expert layers of ``tree``, and the loops it holds."""
    text = compiled.as_text()
    ops = [ln for ln in text.splitlines() if re.match(r"\s*(ROOT )?%\S+ = ", ln)]
    remat = sum("remat" in ln.split(" = ")[0] for ln in ops)
    mem = compiled.memory_analysis()
    counted = {label: count(ops) for label, count in watch}
    if tree is not None:
        counted.update(expert_layer_counts(text, tree))
    counts = "  ".join(f"{label} {n:2d}" for label, n in counted.items())
    print(
        f"{name:38s} remat ops {remat:2d} ({text.count('remat'):2d} mentions)  {counts}  "
        f"arguments {mem.argument_size_in_bytes / 1e9:5.2f} GB  "
        f"temporaries {mem.temp_size_in_bytes / 1e9:5.2f} GB  "
        f"pools enter as {' '.join(entry_layouts(text, pool)) or '-'}"
    )
    for what, found in (("loops", loops_of(text)), ("kernels", kernels_of(text))):
        print(f"{'':38s} {what}  " + ("  ".join(
            f"{n} x {name}" for name, n in sorted(found.items())) or "none"))


def gpt2_family(cfg, dec, stored_k, key):
    """GPT-2's trees, what to count in a program's operations, and the
    loader's program."""
    from ray_tpu.models import gpt2

    pool = tuple(jax.tree.leaves(stored_k)[0].shape)
    d, f, L = cfg.d_model, cfg.d_ff, cfg.n_layer
    h, hd = cfg.n_head, cfg.head_dim
    stacks = [f"{L},{d},{f}", f"{L},{f},{d}", f"{L},{d},3,{h},{hd}", f"{L},{h},{hd},{d}"]
    watch = [
        ("whole-pool copies", lambda ops: results_of(ops, pool)),
        ("pool-layer copies", lambda ops: results_of(ops, (1,) + pool[1:])
         + results_of(ops, pool[1:])),
        ("kernel stacks rewritten", lambda ops: sum(
            bool(re.search(rf"^\s*%(convert|copy)[.\d]* = bf16\[{s}\]", ln))
            for ln in ops for s in stacks)),
    ]
    as_init = jax.eval_shape(lambda: gpt2.init(jax.random.PRNGKey(0), cfg))
    as_held = jax.eval_shape(lambda p: dec.serving_params(cfg, p), as_init)
    loader = ("load_serving_params (init and cast)", lambda: dec.compile_init(cfg, key))
    return [("float32 tree", as_init), ("serving_params", as_held)], watch, loader


def mimo_v2_family(cfg, dec, stored_k, key):
    """The tree of a family with full and window layers (MiMo-V2, Trinity)
    as an engine holds it and what to count: a cache is an array a layer
    (``cache_spec``), a pool or a ring; a ring also as the blocks decode may
    read it in (``ops.cached_attention.ring_span``)."""
    from ray_tpu.ops import cached_attention

    kept = [(s, k) for s, k in zip(dec.cache_spec(cfg), stored_k.layers)
            if s["kind"] in ("full", "window")]  # the layers that keep K and V
    spec = [s for s, _ in kept]
    # a page of positions or more: q is [rows, kv_heads, group, size] too
    positions = rf"\d{{{len(str(stored_k.page_tokens))},}}"
    by_kind = {"full": set(), "window": set()}
    for s, k in kept:
        for size in (s["k_size"], s["v_size"]):
            by_kind[s["kind"]].add(k.shape[:2] + (s["kv_heads"] * size,))
            if s["kind"] == "window":
                span = cached_attention.ring_span(k.shape[1])
                by_kind["window"].add((k.shape[0] * k.shape[1] // span, span,
                                       s["kv_heads"] * size))
    split = {(r"\d+", positions, s["kv_heads"], size)
             for s in spec for size in (s["k_size"], s["v_size"])}
    # every row's ring cut into its blocks at once: several results
    # ``[rows, span, width]``, which is how a gather of whole rings of 2,048
    # positions was compiled (PERF.md, PR 53)
    cut = {(k.shape[0], cached_attention.ring_span(k.shape[1]), s["kv_heads"] * size)
           for s, k in kept if s["kind"] == "window"
           for size in (s["k_size"], s["v_size"])}

    def rings_cut(ops):
        return sum(bool(re.search(rf"= \(\w+\[{r},{b},{c}\]\S*, \w+\[{r},{b},{c}\]", ln))
                   for ln in ops for r, b, c in cut)
    watch = [
        ("whole-pool copies", lambda ops: sum(results_of(ops, p) for p in by_kind["full"])),
        ("ring copies", lambda ops: sum(results_of(ops, r) for r in by_kind["window"])
         + rings_cut(ops)),
        ("K/V split into heads", lambda ops: sum(
            results_of(ops, s, "copy|reshape|transpose|fusion") for s in split)),
    ]
    as_held = jax.eval_shape(lambda: dec.load_serving_params(cfg))
    return [("load_serving_params", as_held)], watch, None


def phi4flash_family(cfg, dec, stored_k, key):
    """Phi-4-mini-flash: the pool and the rings as ``mimo_v2_family`` counts
    them, and copies of a Mamba layer's state whole (``[rows, d_state,
    d_inner]`` float32, 42 MB a layer at 128 rows: a state relaid every
    step would double its bytes; the update itself is a fusion and is not
    counted, nor are the convolution's inputs, a hundredth of the state,
    which the compiler copies into faster memory ahead of their use)."""
    trees, watch, loader = mimo_v2_family(cfg, dec, stored_k, key)
    states = {(r"\d+", *s["k_row"]) for s in dec.cache_spec(cfg) if s["kind"] == "state"}
    watch.append(("state copies", lambda ops: sum(results_of(ops, dims) for dims in states)))
    return trees, watch, loader


def deepseek_v3_family(cfg, dec, stored, key):
    """The latent family's tree as an engine holds it and what to count."""
    pool = tuple(stored.layers[0].shape)
    positions = rf"\d{{{len(str(stored.page_tokens))},}}"
    heads, sizes = cfg.num_attention_heads, {cfg.qk_nope_head_dim, cfg.qk_head_dim, cfg.v_head_dim}
    a_head = {dims for size in sizes
              for dims in ((r"\d+", positions, heads, size), (r"\d+", heads, positions, size))}
    watch = [
        ("latent-pool copies", lambda ops: results_of(ops, pool)),
        ("K/V a head of a cached span", lambda ops: sum(
            results_of(ops, dims, r"[\w\-]+") for dims in a_head)),
    ]
    as_held = jax.eval_shape(lambda: dec.load_serving_params(cfg))
    return [("load_serving_params", as_held)], watch, None


FAMILIES = {"ray_tpu.models.gpt2_decode": gpt2_family,
            "ray_tpu.models.mimo_v2": mimo_v2_family,
            "ray_tpu.models.afmoe": mimo_v2_family,
            "ray_tpu.models.phi4flash": phi4flash_family,
            "ray_tpu.models.qwen3_next": phi4flash_family,
            "ray_tpu.models.deepseek_v3": deepseek_v3_family}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="gpt2-xl")
    ap.add_argument("--max-batch-size", type=int, default=6)
    ap.add_argument("--page-tokens", type=int, default=64)
    ap.add_argument("--prefill", type=int, default=128, help="prefill width")
    ap.add_argument("--prefill-rows", type=int, default=1,
                    help="rows of the prefill call (a decode module whose "
                         "PREFILL_ROWS holds it): [R, P] tokens, a table a row")
    args = ap.parse_args()

    # this process is on the CPU and compiles for the chip: the Pallas
    # kernels are lowered for it, not interpreted
    flash_attention._interpret = lambda: False
    cfg, dec = models.resolve(args.model)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    # the engine's own sizes (serve/llm.py: pool by batch, rows 4 x batch)
    B = args.page_tokens
    max_pages = -(-cfg.n_positions // B)
    n_pages = args.max_batch_size * max_pages + 1
    S = min(n_pages - 1, 4 * args.max_batch_size)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def on_chip(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    # the stored shapes are init_paged_cache's to decide, not this tool's
    stored_k, stored_v = jax.eval_shape(lambda: dec.init_paged_cache(cfg, n_pages, B, S))
    cache_k, cache_v = on_chip(stored_k), on_chip(stored_v)
    first_pool = tuple(jax.tree.leaves(stored_k)[0].shape)
    rows = (sds((S,), jnp.int32), sds((S,), jnp.int32))
    tables = sds((S, max_pages), jnp.int32)
    sampling = (sds((S,), jnp.float32), sds((S,), jnp.bool_))
    key = sds((2,), jnp.uint32)
    i32 = sds((), jnp.int32)

    trees, watch, loader = FAMILIES[dec.__name__](cfg, dec, stored_k, key)
    print(f"{args.model}: rows {S}, pool {n_pages} x {B}, K stored as "
          f"{sorted({tuple(a.shape) for a in jax.tree.leaves(stored_k)})}, "
          f"on {topo.devices[0].device_kind}")
    for label, tree in trees:
        params = on_chip(tree)
        print(f"-- {label}: {dec.params_bytes(tree) / 1e9:.2f} GB")
        programs = {
            "decode_paged_and_sample": dec.decode_paged_and_sample.lower(
                cfg, params, *rows, cache_k, cache_v, tables, *sampling, key, i32
            ),
            "decode_multi_paged (any K)": dec.decode_multi_paged.lower(
                cfg, params, *rows, cache_k, cache_v, tables, *sampling, key, i32, i32
            ),
            f"prefill_paged (P={args.prefill})": dec.prefill_paged.lower(
                cfg, params, sds((1, args.prefill), jnp.int32), i32, i32, cache_k,
                cache_v, sds((max_pages,), jnp.int32),
            ),
        }
        R = args.prefill_rows
        if R > 1:
            if R not in dec.PREFILL_ROWS:
                raise SystemExit(f"{args.model} takes {dec.PREFILL_ROWS} rows a prefill call")
            a_row = sds((R,), jnp.int32)
            programs[f"prefill_paged (R={R}, P={args.prefill})"] = dec.prefill_paged.lower(
                cfg, params, sds((R, args.prefill), jnp.int32), a_row, a_row, cache_k,
                cache_v, sds((R, max_pages), jnp.int32), a_row,
            )
        for name, lowered in programs.items():
            report(name, lowered.compile(), watch, first_pool, tree)
    if loader:
        print("-- the loader")
        report(loader[0], loader[1](), watch, first_pool)


if __name__ == "__main__":
    main()
