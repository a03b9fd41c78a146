"""Round-4 RLlib breadth: Ape-X distributed replay (the architecture test
— replay-buffer ACTORS, prioritized sampling across nodes, async learner),
CQL offline RL, and Evolution Strategies. Reference:
rllib/algorithms/apex_dqn/, cql/, es/."""
import os
import tempfile

import numpy as np
import pytest


def test_apex_learns_corridor_with_replay_actors(jax_cpu, ray_start):
    """Ape-X on the single-node cluster: replay shards are real actors,
    learning goes through them end-to-end."""
    from ray_tpu.rllib.algorithms import ApexDQNConfig

    cfg = (
        ApexDQNConfig()
        .environment("Corridor")
        .env_runners(num_env_runners=0, num_envs_per_runner=4,
                     rollout_length=32)
        .training(
            lr=1e-3, minibatch_size=64, learning_starts=200,
            epsilon_decay_steps=1500, updates_per_iteration=64,
            target_update_freq=100, num_replay_shards=2,
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    try:
        result = {}
        for _ in range(30):
            result = algo.train()
            if result["episode_return_mean"] >= 0.7:
                break
        assert result["replay_shards"] == 2
        assert result["replay_size"] > 0
        assert result["episode_return_mean"] >= 0.7, result
    finally:
        algo.stop()


def test_apex_replay_actors_on_two_node_cluster(ray_cluster):
    """Replay shards scheduled on a 2-node in-process
    cluster, experiences flowing through the inter-node object plane."""
    import time

    import ray_tpu
    from ray_tpu.rllib.algorithms.apex import ReplayShard

    cluster = ray_cluster
    worker_node = cluster.add_node(num_cpus=2)
    # wait for the head raylet to see the second node (delta heartbeats)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        if len(ray_tpu.nodes()) >= 2:
            break
        time.sleep(0.2)
    assert len(ray_tpu.nodes()) >= 2

    Shard = ray_tpu.remote(num_cpus=1)(ReplayShard)
    shards = [Shard.options(scheduling_strategy="SPREAD").remote(
        1000, 4, i, 0.6, 0.4, 32) for i in range(2)]
    rng = np.random.default_rng(0)
    for shard in shards:
        for _ in range(3):
            n = 64
            ray_tpu.get(shard.add_batch.remote(
                rng.standard_normal((n, 4)).astype(np.float32),
                rng.integers(0, 2, n).astype(np.int32),
                rng.standard_normal(n).astype(np.float32),
                rng.standard_normal((n, 4)).astype(np.float32),
                np.zeros(n, bool),
                np.full(n, 0.99, np.float32),
            ), timeout=120)
    sizes = ray_tpu.get([s.size.remote() for s in shards], timeout=120)
    assert sizes == [192, 192]
    mb = ray_tpu.get(shards[0].sample.remote(32), timeout=120)
    assert mb is not None and mb["obs"].shape == (32, 4)
    assert "weights" in mb and "indices" in mb
    # priority update round-trips
    ray_tpu.get(shards[0].update_priorities.remote(
        mb["indices"], np.abs(rng.standard_normal(32))), timeout=120)
    # shards really live on the cluster's scheduler: at least one actor
    # landed via SPREAD on each node OR all on head (small cluster) — the
    # load-bearing claim is that creation+calls worked across the cluster
    cluster.remove_node(worker_node)


def test_cql_trains_from_marwil_format_offline_data(jax_cpu):
    from ray_tpu.rllib.offline import CQLConfig

    # reuse the MARWIL-format expert corridor file generator (tests/ is on
    # sys.path under pytest's rootdir import mode)
    from test_rllib_breadth import _expert_corridor_data

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "exp.jsonl")
        _expert_corridor_data(path, n_episodes=60, noise=0.1)
        algo = (
            CQLConfig()
            .offline_data(input_=path, cql_alpha=1.0)
            .training(lr=1e-3, num_epochs=4, minibatch_size=64)
            .debugging(seed=0)
            .build()
        )
        metrics = {}
        for _ in range(15):
            metrics = algo.train()
        # conservative gap is driven toward the dataset actions
        assert metrics["cql_gap"] < 1.0, metrics
        # the learned Q picks the expert action (right) across the corridor
        for pos in (0.0, 1.0, 2.0, 3.0):
            assert algo.compute_action(np.array([pos])) == 1


def test_cql_rejects_continuous_offline_data(jax_cpu, tmp_path):
    from ray_tpu.rllib.offline import CQLConfig, JsonWriter

    path = str(tmp_path / "cont.jsonl")
    with JsonWriter(path) as w:
        w.write_transition(0, [0.0, 0.0], np.asarray([0.5]), 1.0, True)
    with pytest.raises(ValueError, match="discrete"):
        CQLConfig().offline_data(input_=path).build()


def test_es_improves_corridor(jax_cpu, ray_start):
    from ray_tpu.rllib.algorithms import ESConfig

    cfg = (
        ESConfig()
        .environment("Corridor")
        .training(num_workers=2, episodes_per_batch=16, sigma=0.1,
                  es_lr=0.1, episode_limit=50)
        .debugging(seed=0)
    )
    algo = cfg.build()
    try:
        first = algo.train()
        best = first["episode_return_mean"]
        for _ in range(14):
            m = algo.train()
            best = max(best, m["episode_return_mean"])
            if best >= 0.6:
                break
        # optimal corridor return = 0.85; ES should at least find "go
        # right" from random init within a few generations
        assert best >= 0.6, best
    finally:
        algo.stop()


def test_ars_improves_corridor(jax_cpu, ray_start):
    """ARS (top-k direction selection + sigma_R step normalization +
    observation filter) learns the corridor like ES but with the
    augmented update (reference: rllib_contrib/ars)."""
    from ray_tpu.rllib.algorithms import ARSConfig

    cfg = (
        ARSConfig()
        .environment("Corridor")
        .training(num_workers=2, num_directions=16, num_top_directions=8,
                  sigma=0.1, ars_lr=0.1, episode_limit=50)
        .debugging(seed=0)
    )
    algo = cfg.build()
    try:
        best = algo.train()["episode_return_mean"]
        for _ in range(14):
            m = algo.train()
            best = max(best, m["episode_return_mean"])
            if best >= 0.6:
                break
        assert best >= 0.6, best
        # the merged observation filter saw every rollout step
        assert m["filter_count"] > 0
    finally:
        algo.stop()
