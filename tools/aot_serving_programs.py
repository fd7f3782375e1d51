"""What the TPU's compiler makes of the three serving programs, without a chip.

Compiles ``decode_paged_and_sample``, ``decode_multi_paged`` and
``prefill_paged`` for a described v5e at a configuration's shapes, once with
the float32 tree ``gpt2.init`` returns and once with the tree an engine
holds (``gpt2_decode.serving_params``), and prints for each: operations
with ``remat`` in their name (and how often the text says the word),
copies of a whole page pool and of one layer of it (a relay to another
layout: the pool's shape is ``init_paged_cache``'s, and its layout at the
program's entry is printed beside them), whole kernel stacks written anew
(a ``convert`` of a float32 parameter to the compute type, or a ``copy``
to another layout), and ``memory_analysis()``'s arguments and
temporaries. Also the loader's own program
(``load_serving_params``'s init and cast), whose temporaries are what a
load holds beyond the weights.

The text names the operations the chip's trace will show (PERF.md, PR 30
and PR 32); it says nothing about time. Run here, on the CPU:

    JAX_PLATFORMS=cpu python tools/aot_serving_programs.py [--model gpt2-xl]
        [--max-batch-size 6] [--page-tokens 64] [--prefill 128]
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

from ray_tpu.models import gpt2
from ray_tpu.models import gpt2_decode as dec


def copies_of(ops, shape) -> int:
    """``copy`` operations in ``ops`` whose result has ``shape``, in any
    element type and layout."""
    dims = ",".join(map(str, shape))
    return sum(bool(re.search(rf"= \w+\[{dims}\]\S* copy\(", ln)) for ln in ops)


def entry_layouts(text: str, shape) -> list:
    """The layouts the compiled program takes arguments of ``shape`` in,
    from its ``entry_computation_layout``."""
    dims = ",".join(map(str, shape))
    head = re.search(r"entry_computation_layout=\{\((.*?)\)->", text)
    found = re.findall(rf"\w+\[{dims}\](\{{[^}}]*\}})", head.group(1)) if head else []
    return sorted(set(found))


def report(name: str, compiled, pool, stacks) -> None:
    text = compiled.as_text()
    ops = [ln for ln in text.splitlines() if re.match(r"\s*(ROOT )?%\S+ = ", ln)]
    remat = sum("remat" in ln.split(" = ")[0] for ln in ops)
    pool_copies = copies_of(ops, pool)
    layer_copies = copies_of(ops, (1,) + tuple(pool[1:])) + copies_of(ops, pool[1:])
    rewritten = sum(
        bool(re.search(rf"^\s*%(convert|copy)[.\d]* = bf16\[{s}\]", ln))
        for ln in ops for s in stacks
    )
    mem = compiled.memory_analysis()
    print(
        f"{name:38s} remat ops {remat:2d} ({text.count('remat'):2d} mentions)  "
        f"whole-pool copies {pool_copies:2d}  pool-layer copies {layer_copies:2d}  "
        f"kernel stacks rewritten {rewritten:2d}  "
        f"arguments {mem.argument_size_in_bytes / 1e9:5.2f} GB  "
        f"temporaries {mem.temp_size_in_bytes / 1e9:5.2f} GB  "
        f"pools enter as {' '.join(entry_layouts(text, pool)) or '-'}"
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="gpt2-xl")
    ap.add_argument("--max-batch-size", type=int, default=6)
    ap.add_argument("--page-tokens", type=int, default=64)
    ap.add_argument("--prefill", type=int, default=128, help="prefill width")
    args = ap.parse_args()

    cfg = gpt2.CONFIGS[args.model]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    # the engine's own sizes (serve/llm.py: pool by batch, rows 4 x batch)
    B = args.page_tokens
    max_pages = -(-cfg.n_positions // B)
    n_pages = args.max_batch_size * max_pages + 1
    S = min(n_pages - 1, 4 * args.max_batch_size)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    # the stored shape is init_paged_cache's to decide, not this tool's
    stored = jax.eval_shape(lambda: dec.init_paged_cache(cfg, n_pages, B))[0]
    pool = jax.tree.map(lambda a: sds(a.shape, a.dtype), stored)
    pool_shape = tuple(jax.tree.leaves(stored)[0].shape)
    d, f, L = cfg.d_model, cfg.d_ff, cfg.n_layer
    h, hd = cfg.n_head, cfg.head_dim
    stacks = [f"{L},{d},{f}", f"{L},{f},{d}", f"{L},{d},3,{h},{hd}", f"{L},{h},{hd},{d}"]
    rows = (sds((S,), jnp.int32), sds((S,), jnp.int32))
    tables = sds((S, max_pages), jnp.int32)
    sampling = (sds((S,), jnp.float32), sds((S,), jnp.bool_))
    key = sds((2,), jnp.uint32)
    i32 = sds((), jnp.int32)

    as_init = jax.eval_shape(lambda: gpt2.init(jax.random.PRNGKey(0), cfg))
    as_held = jax.eval_shape(lambda p: dec.serving_params(cfg, p), as_init)
    print(f"{args.model}: rows {S}, pool {n_pages} x {B} stored as "
          f"{list(pool_shape)}, on {topo.devices[0].device_kind}")
    for label, tree in (("float32 tree", as_init), ("serving_params", as_held)):
        params = jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)
        print(f"-- {label}: {dec.params_bytes(tree) / 1e9:.2f} GB")
        programs = {
            "decode_paged_and_sample": dec.decode_paged_and_sample.lower(
                cfg, params, *rows, pool, pool, tables, *sampling, key, i32
            ),
            "decode_multi_paged (any K)": dec.decode_multi_paged.lower(
                cfg, params, *rows, pool, pool, tables, *sampling, key, i32, i32
            ),
            f"prefill_paged (P={args.prefill})": dec.prefill_paged.lower(
                cfg, params, sds((1, args.prefill), jnp.int32), i32, i32, pool, pool,
                sds((max_pages,), jnp.int32),
            ),
        }
        for name, lowered in programs.items():
            report(name, lowered.compile(), pool_shape, stacks)
    print("-- the loader")
    report("load_serving_params (init and cast)", dec.compile_init(cfg, key),
           pool_shape, stacks)


if __name__ == "__main__":
    main()
