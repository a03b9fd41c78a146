"""WorkerGroup: the gang of training actors.

Equivalent of the reference's WorkerGroup + BackendExecutor
(reference: python/ray/train/_internal/worker_group.py:101 actor gang;
backend_executor.py:105 start / :344 start_training; the torch rendezvous
it performs at train/torch/config.py:63 is replaced by jax.distributed
initialization driven from rank 0's coordinator address).

The gang is reserved through a placement group so SPMD workers land
together (slice-aligned for TPU gangs) and fail/restart as a unit —
the reference's gang semantics (SURVEY.md §7 "hard parts").
"""
from __future__ import annotations

import socket
import threading
import traceback
from typing import Any, Callable

import ray_tpu
from ray_tpu.train.config import ScalingConfig
from ray_tpu.train.session import TrainContext, init_session
from ray_tpu.util.placement_group import placement_group, remove_placement_group
from ray_tpu.util.scheduling_strategies import PlacementGroupSchedulingStrategy


@ray_tpu.remote
class TrainWorker:
    """One rank of the SPMD gang. The user train fn runs on a background
    thread so report-polling actor calls stay responsive."""

    def __init__(self, context_kwargs: dict):
        self.context = TrainContext(**context_kwargs)
        self.session = init_session(self.context)
        self._thread = None

    def get_address(self) -> str:
        return socket.gethostbyname(socket.gethostname())

    def setup_distributed(self, coordinator: str, world_size: int, rank: int,
                          enabled: bool, backend: str = "jax") -> bool:
        """Distributed bootstrap for the gang. backend="jax": opt-in
        jax.distributed (via ScalingConfig.jax_distributed — on a single
        host every worker is its own JAX process and must NOT contend for
        the local chip(s)). backend="torch": a gloo process group over TCP
        (the reference's torch rendezvous, train/torch/config.py:63),
        always initialized — DDP needs it even for world_size 1."""
        import os

        os.environ["RT_COORDINATOR"] = coordinator
        os.environ["RT_WORLD_SIZE"] = str(world_size)
        os.environ["RT_RANK"] = str(rank)
        if backend == "torch":
            import torch.distributed as dist

            if not dist.is_initialized():
                dist.init_process_group(
                    "gloo", init_method=f"tcp://{coordinator}",
                    rank=rank, world_size=world_size,
                )
            return True
        # this process is about to compile the train step: one shared
        # persistent cache (the import is cheap — no backend is touched)
        from ray_tpu._private.compile_cache import enable_compile_cache

        enable_compile_cache()
        if not enabled or world_size <= 1:
            return True
        import jax

        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=world_size,
            process_id=rank,
        )
        return True

    def start_training(self, fn_blob: bytes, train_loop_config: dict | None) -> bool:
        import cloudpickle
        import inspect

        fn = cloudpickle.loads(fn_blob)
        # fn() or fn(config) are both accepted (reference semantics:
        # train_loop_per_worker may take an optional config dict)
        takes_config = bool(inspect.signature(fn).parameters)

        def runner():
            try:
                if takes_config:
                    fn(train_loop_config or {})
                else:
                    fn()
                self.session.finish()
            except Exception:
                self.session.finish(error=traceback.format_exc())

        self._thread = threading.Thread(target=runner, daemon=True)
        self._thread.start()
        return True

    def poll(self, since: int) -> dict:
        reports, done, error = self.session.drain(since)
        return {"reports": reports, "done": done, "error": error}

    def shutdown(self) -> bool:
        return True


class WorkerGroup:
    def __init__(self, scaling: ScalingConfig, run_name: str,
                 storage_path: str, backend: str = "jax"):
        self.scaling = scaling
        self.run_name = run_name
        self.storage_path = storage_path
        self.backend = backend
        self.pg = None
        self.workers: list = []

    def start(
        self,
        experiment_config: dict | None = None,
        datasets: dict | None = None,
    ) -> None:
        n = self.scaling.num_workers
        bundles = [self.scaling.worker_resources() for _ in range(n)]
        self.pg = placement_group(bundles, strategy=self.scaling.placement_strategy)
        if not self.pg.ready(timeout=60):
            remove_placement_group(self.pg)
            raise ray_tpu.exceptions.PlacementGroupUnavailableError(
                f"cannot reserve {bundles} with strategy "
                f"{self.scaling.placement_strategy}"
            )
        # shard each dataset across the gang (reference: streaming_split,
        # python/ray/data/dataset.py:1149; delivered per-worker like
        # data_parallel_trainer.py:59's dataset ingestion)
        shard_table: dict[str, list] = {}
        if datasets:
            # keep the source refs alive for the whole run: the group owns
            # them so ref-counted freeing can't reclaim shard blocks mid-run
            self._dataset_shards = shard_table
            for name, ds in datasets.items():
                shard_table[name] = _shard_dataset(ds, n)
        self.workers = []
        for rank in range(n):
            ctx = dict(
                world_size=n,
                world_rank=rank,
                local_rank=rank,  # single-host: local == world
                trial_name=self.run_name,
                storage_path=self.storage_path,
                trial_dir=f"{self.storage_path}/worker_{rank}",
                experiment_config=experiment_config or {},
                dataset_shards={
                    name: shards[rank] for name, shards in shard_table.items()
                },
            )
            w = TrainWorker.options(
                num_cpus=0,  # the CPU share comes from the bundle
                # the chips are DRAWN from the bundle, so the raylet binds
                # them to this worker (a worker that was assigned no chip
                # is held to the CPU platform — _private/worker.py)
                num_tpus=(self.scaling.chips_per_worker
                          if self.scaling.use_tpu else 0),
                scheduling_strategy=PlacementGroupSchedulingStrategy(
                    placement_group=self.pg, placement_group_bundle_index=rank
                ),
            ).remote(ctx)
            self.workers.append(w)
        # rendezvous
        addr = ray_tpu.get(self.workers[0].get_address.remote(), timeout=120)
        coordinator = f"{addr}:{_free_port()}"
        ray_tpu.get(
            [
                w.setup_distributed.remote(
                    coordinator, n, rank, self.scaling.jax_distributed,
                    self.backend,
                )
                for rank, w in enumerate(self.workers)
            ],
            timeout=300,
        )

    def run(self, fn: Callable, config: dict | None = None) -> None:
        import cloudpickle

        blob = cloudpickle.dumps(fn)
        ray_tpu.get(
            [w.start_training.remote(blob, config) for w in self.workers],
            timeout=300,
        )

    def poll(self, since: list[int]) -> list[dict]:
        return ray_tpu.get(
            [w.poll.remote(s) for w, s in zip(self.workers, since)], timeout=300
        )

    def shutdown(self) -> None:
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        if self.pg is not None:
            try:
                remove_placement_group(self.pg)
            except Exception:
                pass
        self.workers = []


def _shard_dataset(ds, n: int) -> list:
    """Dataset -> n per-worker DataIterators; a DataIterator is replicated
    (the caller pre-sharded); anything else is rejected."""
    from ray_tpu.data.dataset import Dataset
    from ray_tpu.data.iterator import DataIterator

    if isinstance(ds, Dataset):
        return ds.streaming_split(n, equal=True)
    if isinstance(ds, DataIterator):
        return [ds] * n
    raise TypeError(
        f"trainer datasets must be ray_tpu.data Datasets or DataIterators, "
        f"got {type(ds).__name__}"
    )


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port
