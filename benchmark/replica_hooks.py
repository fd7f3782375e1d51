"""What the benchmark needs from inside the process that holds the chip.

Only that process can trace the chip or read its memory, and the program
has no entry for either. So the front door is deployed through
``serve.llm.deploy`` as ever, with this subclass standing in for
``OpenAIServer``: it adds four methods and changes none. What depends on the
model's family comes from the family file the payload names
(``harness.family``).
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Any, Dict

from benchmark import harness
from ray_tpu.serve.openai import ingress


def device_report() -> Dict[str, Any]:
    """The devices of this process as JAX reports them, with the
    allocator's memory statistics of each."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory": [dict(d.memory_stats() or {}) for d in devices],
    }


def trace_for(trace_dir: str, seconds: float) -> str:
    """Trace this process for ``seconds`` under a ``bench/window`` span."""
    import jax

    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("bench/window"):
            time.sleep(seconds)
    finally:
        jax.profiler.stop_trace()
    return trace_dir


def engine_shapes(engine, context: int) -> Dict[str, int]:
    """Decode rows and page-table width of a loaded paged engine, as
    ``_engine_loop_paged`` works them out from its pool and from the
    positions a sequence may hold (the family's ``context(model)``)."""
    from ray_tpu.utils.config import config as rtcfg

    pool = engine._prefix_pool
    rows = int(rtcfg.serve_paged_max_seqs) or min(
        pool.num_pages - 1, 4 * engine.cfg.max_batch_size
    )
    return {
        "rows": max(1, min(rows, pool.num_pages - 1)),
        "max_pages": -(-context // pool.page_tokens),
        "page_tokens": pool.page_tokens,
    }


class BenchOpenAIServer(ingress.OpenAIServer):
    def bench_device(self, _payload: Any = None) -> Dict[str, Any]:
        return device_report()

    def _bench_engine(self):
        for mid in self._engines.model_ids():
            engine = self._engines.peek(mid)
            if engine is not None:
                return engine
        raise RuntimeError("no engine is loaded on this replica yet")

    def bench_warm_rows(self, payload: Dict[str, Any]) -> Dict[str, int]:
        """``payload``: the path of the configuration's family file and
        its ``model``."""
        fam = harness.family(payload["family"])
        shapes = engine_shapes(self._bench_engine(), fam.context(payload["model"]))
        fam.warm_row_updates(shapes["rows"], shapes["max_pages"])
        return shapes

    def bench_check(self, payload: Dict[str, Any]) -> Dict[str, Any]:
        """The engine's prefill and decode programs, on the engine's own
        parameters and a paged cache of its own making, against the plain
        reference: the family's ``compare_serve``. The answer names the
        family file it went through."""
        engine = self._bench_engine()
        out = harness.family(payload["family"]).compare_serve(
            engine.model_cfg, payload["model"], engine.params, int(payload["seed"]),
            prompt_lens=payload["prompt_lens"], steps=int(payload["decode_steps"]),
            page_tokens=engine._prefix_pool.page_tokens,
        )
        return {**out, "family": os.path.basename(payload["family"])[: -len(".py")]}

    def bench_trace(self, payload: Dict[str, Any]) -> str:
        return trace_for(payload["dir"], float(payload["seconds"]))


@contextlib.contextmanager
def hooked_front_door():
    """``serve.llm.deploy`` binds whatever ``ingress.OpenAIServer`` names
    when it is called."""
    plain = ingress.OpenAIServer
    ingress.OpenAIServer = BenchOpenAIServer
    try:
        yield
    finally:
        ingress.OpenAIServer = plain
