"""DataParallelTrainer / JaxTrainer — the user-facing Train API.

Parity: DataParallelTrainer.fit (reference python/ray/train/v2/api/
data_parallel_trainer.py:157) and JaxTrainer (train/v2/jax/jax_trainer.py:20).
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Dict, Optional

import ray_tpu
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.config import Result, RunConfig, ScalingConfig
from ray_tpu.train.controller import TrainController
from ray_tpu.utils import serialization
from ray_tpu.utils.config import config as rt_config


class DataParallelTrainer:
    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: Optional[Dict[str, Any]] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        datasets: Optional[Dict[str, Any]] = None,
        dataset_split_mode: str = "materialize",
    ):
        self._train_fn = train_loop_per_worker
        self._train_loop_config = train_loop_config
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.datasets = datasets or {}
        # "materialize": execute the pipeline ONCE on the driver, hand each
        # rank a FromBundles shard (no duplicated read/preprocess compute;
        # costs full materialization in the object store).
        # "reexecute": each rank streams its own execution filtered to
        # 1/world_size of the block stream (no materialization; read/map
        # compute runs world_size times).
        if dataset_split_mode not in ("materialize", "reexecute"):
            raise ValueError(f"unknown dataset_split_mode {dataset_split_mode!r}")
        self.dataset_split_mode = dataset_split_mode

    def _run_dir(self) -> str:
        base = self.run_config.storage_path or os.path.join(
            rt_config.temp_dir, "runs"
        )
        name = self.run_config.name or f"run_{int(time.time())}"
        path = os.path.join(base, name)
        os.makedirs(path, exist_ok=True)
        return path

    def _dataset_blobs(self):
        """Per-rank dataset dicts, sharded driver-side (each rank receives
        exactly its shard — no shard logic on the worker). dumps_function
        (cloudpickle + by-value module registration) so UDFs defined in
        user modules deserialize on workers."""
        if not self.datasets:
            return None
        n = self.scaling_config.num_workers
        per_rank = [dict() for _ in range(n)]
        for name, ds in self.datasets.items():
            if n <= 1:
                parts = [ds]
            elif self.dataset_split_mode == "materialize":
                parts = ds.split(n)
            else:
                parts = [ds.shard(n, i) for i in range(n)]
            for i in range(n):
                per_rank[i][name] = parts[i]
        return [serialization.dumps_function(d) for d in per_rank]

    def fit(self) -> Result:
        if self.scaling_config.elastic and self.datasets:
            raise ValueError(
                "elastic scaling with datasets= is not supported yet: "
                "dataset shards are split at the initial world size"
            )
        scaling = self.scaling_config
        if scaling.use_tpu and scaling.tpu_chips_per_worker is None:
            scaling = dataclasses.replace(
                scaling, tpu_chips_per_worker=_chips_per_tpu_host()
            )
        run_dir = self._run_dir()
        cc = self.run_config.checkpoint_config
        # Pin the controller to the driver's node (reference v2 runs the
        # controller IN the driver process): it must not die with an
        # arbitrary worker node — its job is to outlive worker failures.
        from ray_tpu.core.api import NodeAffinitySchedulingStrategy

        controller = TrainController.options(
            num_cpus=0,
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=ray_tpu.get_runtime_context().get_node_id(),
                soft=True,
            ),
        ).remote(
            scaling,
            run_dir,
            self.run_config.failure_config.max_failures,
            cc.num_to_keep,
            cc.checkpoint_score_attribute,
            cc.checkpoint_score_order,
        )
        try:
            out = ray_tpu.get(
                controller.run.remote(
                    serialization.dumps_function(self._train_fn),
                    self._train_loop_config,
                    scaling.use_tpu,
                    scaling.tpu_chips_per_worker,
                    self._dataset_blobs(),
                ),
            )
        finally:
            try:
                ray_tpu.kill(controller)
            except Exception:  # noqa: BLE001
                pass
        error = RuntimeError(out["error"]) if out.get("error") else None
        metrics = out.get("metrics")
        if metrics:
            metrics = {k: v for k, v in metrics.items() if not k.startswith("_")}
        ckpt = (
            Checkpoint(out["checkpoint_path"]) if out.get("checkpoint_path") else None
        )
        return Result(metrics=metrics, checkpoint=ckpt, error=error, path=run_dir)


def _chips_per_tpu_host() -> int:
    """Chips a use_tpu worker takes when the caller named no count: all
    of its host's. No TPU node at all is an error here, not a quiet run
    on cpu workers."""
    chips = max(
        (int(n["resources_total"].get("TPU", 0)) for n in ray_tpu.nodes()
         if n.get("alive", True)),
        default=0,
    )
    if not chips:
        raise RuntimeError(
            "use_tpu=True but no node of this cluster has a TPU chip; "
            "say use_tpu=False to train on the CPU"
        )
    return chips


class JaxTrainer(DataParallelTrainer):
    """SPMD JAX training: one worker per host, a mesh over all chips.

    Parity: reference JaxTrainer (TPU-only, _validate_scaling_config
    train/v2/jax/jax_trainer.py:162)."""
