"""Executor v2: per-operator pipelining through all-to-all boundaries and
resource-aware actor-pool admission (reference:
python/ray/data/_internal/execution/streaming_executor.py:49,
streaming_executor_state.py — pipelined operator DAG with resource-aware
admission)."""
import time

import pytest


def test_shuffle_maps_overlap_upstream(ray_start):
    """With an explicit num_blocks, shuffle-map tasks must START while the
    upstream fused map stage is still producing — asserted from task-event
    timestamps, not wishful thinking."""
    import ray_tpu
    from ray_tpu import data
    from ray_tpu.util import state

    def slow(r):
        time.sleep(0.25)
        return r

    rows = (data.range(12, parallelism=12)
            .map(slow)
            .random_shuffle(seed=7, num_blocks=4)
            .map(lambda r: {"id": r["id"]})
            .take_all())
    assert sorted(r["id"] for r in rows) == list(range(12))

    tasks = state.list_tasks()
    upstream = [t for t in tasks if t["name"] == "_exec_block"
                and t["finished_at"]]
    shuffle_maps = [t for t in tasks if t["name"] == "_exec_shuffle_map"
                    and t["submitted_at"]]
    assert upstream and shuffle_maps
    # SUBMISSION time is the structural claim: the pipelined exchange
    # dispatches maps while upstream still streams, where the barrier
    # version cannot submit until every upstream task has finished.
    # (started_at would flake on a loaded 1-core box where nothing can
    # actually run concurrently.)
    first_shuffle_submit = min(t["submitted_at"] for t in shuffle_maps)
    last_upstream_finish = max(t["finished_at"] for t in upstream)
    assert first_shuffle_submit < last_upstream_finish, (
        "shuffle maps were only submitted after the whole upstream stage "
        "finished — the exchange still barriers instead of pipelining"
    )


def test_unseeded_default_shuffle_still_correct(ray_start):
    from ray_tpu import data

    rows = data.range(20, parallelism=4).random_shuffle().take_all()
    assert sorted(r["id"] for r in rows) == list(range(20))


def test_pool_sized_to_whole_cluster_completes(ray_start):
    """A pool whose minimum occupies every cluster CPU used to deadlock
    against its own upstream tasks; admission now materializes upstream
    first and the job completes (the round-3 'docstring fix' is gone)."""
    from ray_tpu import data
    from ray_tpu.data import ActorPoolStrategy

    class AddOne:
        def __call__(self, batch):
            return {"id": batch["id"] + 1}

    # ray_start gives the cluster 4 CPUs; min_size=4 x 1 CPU = all of them
    ds = data.range(24, parallelism=6).map_batches(
        AddOne, compute=ActorPoolStrategy(min_size=4, max_size=4),
    )
    rows = ds.take_all()
    assert sorted(r["id"] for r in rows) == list(range(1, 25))


def test_pool_below_cluster_size_pipelines(ray_start):
    """A pool that leaves the reserved upstream slot free streams blocks
    through live (no upstream materialization barrier): pool-worker calls
    begin before the upstream read stage finishes."""
    from ray_tpu import data
    from ray_tpu.data import ActorPoolStrategy
    from ray_tpu.util import state

    class Slow:
        def __call__(self, batch):
            time.sleep(0.2)
            return batch

    def slow_read(r):
        # an upstream stage of several waves (10 blocks on the 2 CPUs the
        # pool leaves): with instant reads every block finishes inside one
        # worker start-up, and whether the FIRST block (the one the pool
        # waits for) lands before the last is the load's to decide
        time.sleep(0.2)
        return r

    rows = (data.range(10, parallelism=10)
            .map(slow_read)
            .map_batches(Slow,
                         compute=ActorPoolStrategy(min_size=2, max_size=2))
            .take_all())
    assert len(rows) == 10

    tasks = state.list_tasks()
    upstream = [t for t in tasks if t["name"] == "_exec_block"
                and t["finished_at"]]
    pool_runs = [t for t in tasks if "_PoolWorker.run" in t["name"]
                 and t["submitted_at"]]
    assert upstream and pool_runs
    # submission-time comparison for the same reason as the shuffle test:
    # a loaded 1-core box serializes execution arbitrarily
    assert min(t["submitted_at"] for t in pool_runs) < max(
        t["finished_at"] for t in upstream)
