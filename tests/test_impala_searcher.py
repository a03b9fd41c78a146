"""IMPALA (async sampling + V-trace) and the external-searcher adapter
Reference models: rllib/algorithms/impala/ and
tune/search/optuna/optuna_search.py.
"""
from __future__ import annotations

import numpy as np
import pytest


def test_vtrace_scan_matches_numpy_oracle(jax_cpu):
    """The in-graph (lax.scan) V-trace must equal the loop-form oracle,
    including truncation bootstraps and termination masking."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.rllib.algorithms.impala import vtrace_reference_np

    rng = np.random.default_rng(0)
    T, E = 7, 3
    behavior_logp = rng.normal(size=(T, E)).astype(np.float32) * 0.3 - 1.0
    target_logp = behavior_logp + rng.normal(size=(T, E)).astype(np.float32) * 0.2
    rewards = rng.normal(size=(T, E)).astype(np.float32)
    values = rng.normal(size=(T, E)).astype(np.float32)
    last_values = rng.normal(size=E).astype(np.float32)
    dones = rng.uniform(size=(T, E)) < 0.25
    terminateds = dones & (rng.uniform(size=(T, E)) < 0.5)
    boot = np.where(dones, rng.normal(size=(T, E)).astype(np.float32), 0.0)
    gamma = 0.97

    vs_ref, pg_ref = vtrace_reference_np(
        behavior_logp, target_logp, rewards, values, last_values,
        dones, terminateds, boot.astype(np.float32), gamma,
    )

    # scan form (mirrors impala_loss internals)
    not_term = 1.0 - terminateds.astype(np.float32)
    not_done = 1.0 - dones.astype(np.float32)
    rhos = jnp.minimum(jnp.exp(target_logp - behavior_logp), 1.0)
    cs = jnp.minimum(jnp.exp(target_logp - behavior_logp), 1.0)
    v_next = jnp.concatenate([jnp.asarray(values[1:]), last_values[None]], 0)
    v_next = jnp.where(dones, boot, v_next)
    delta = rhos * (rewards + gamma * not_term * v_next - values)

    def scan_fn(acc, xs):
        d, c, nd = xs
        acc = d + gamma * c * nd * acc
        return acc, acc

    _, acc_seq = jax.lax.scan(
        scan_fn, jnp.zeros(E, jnp.float32),
        (delta, cs, jnp.asarray(not_done)), reverse=True,
    )
    vs = values + acc_seq
    vs_next = jnp.concatenate([vs[1:], last_values[None]], 0)
    vs_next = jnp.where(dones, boot, vs_next)
    pg = rhos * (rewards + gamma * not_term * vs_next - values)

    np.testing.assert_allclose(np.asarray(vs), vs_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(pg), pg_ref, rtol=1e-5, atol=1e-5)


def test_impala_learns_cartpole_local(jax_cpu):
    """Single-process IMPALA (local runner) learns CartPole."""
    from ray_tpu.rllib import CartPole, ImpalaConfig

    cfg = (
        ImpalaConfig()
        .environment(CartPole)
        .env_runners(num_env_runners=0, num_envs_per_runner=8,
                     rollout_length=64)
        .training(lr=3e-3, entropy_coeff=0.005)
        .debugging(seed=0)
    )
    algo = cfg.build()
    best = -np.inf
    for _ in range(40):
        m = algo.train()
        if np.isfinite(m["episode_return_mean"]):
            best = max(best, m["episode_return_mean"])
        if best >= 120:
            break
    assert best >= 120, f"IMPALA failed to learn CartPole (best {best})"


@pytest.mark.parametrize("ray_start", [{"num_cpus": 4}], indirect=True)
def test_impala_async_sampling_with_actors(ray_start, jax_cpu):
    """CartPole improves with ASYNC actor sampling —
    runners keep one sample in flight, the learner consumes ready batches
    without a synchronous barrier."""
    from ray_tpu.rllib import CartPole, ImpalaConfig

    cfg = (
        ImpalaConfig()
        .environment(CartPole)
        .env_runners(num_env_runners=2, num_envs_per_runner=8,
                     rollout_length=64)
        .training(lr=3e-3, entropy_coeff=0.005)
        .debugging(seed=0)
    )
    algo = cfg.build()
    try:
        first = None
        best = -np.inf
        for _ in range(30):
            m = algo.train()
            assert m["num_batches_consumed"] >= 1
            r = m["episode_return_mean"]
            if np.isfinite(r):
                if first is None:
                    first = r
                best = max(best, r)
            if best >= 100:
                break
        # async pipeline stayed primed
        assert algo._inflight, "no samples in flight after training"
        assert first is not None and best > max(40, first + 20), (
            f"no learning progress: first={first}, best={best}"
        )
    finally:
        algo.stop()


class _FakeBayesOpt:
    """Stand-in for an external suggest/observe library (the optuna role):
    random-search that, once it has observations, samples near the best."""

    def __init__(self, seed=0):
        self.rng = np.random.default_rng(seed)
        self.history: list[tuple[dict, float | None]] = []

    def ask(self) -> dict:
        scored = [(c, v) for c, v in self.history if v is not None]
        if scored and self.rng.uniform() < 0.5:
            best = max(scored, key=lambda cv: cv[1])[0]
            return {"x": float(np.clip(best["x"] + self.rng.normal(0, 0.3), -4, 4))}
        return {"x": float(self.rng.uniform(-4, 4))}

    def tell(self, config: dict, value: float | None) -> None:
        self.history.append((config, value))


def test_suggest_adapter_runs_sweep(ray_start):
    """10-trial ASHA-style sweep driven by an EXTERNAL optimizer through
    SuggestAdapter; the optimizer observes every completion."""
    from ray_tpu import tune

    opt = _FakeBayesOpt(seed=3)

    def objective(config):
        x = config["x"]
        for i in range(3):
            tune.report({"score": -(x - 1.0) ** 2 - 0.01 * i})

    tuner = tune.Tuner(
        objective,
        tune_config=tune.TuneConfig(
            metric="score",
            mode="max",
            search_alg=tune.SuggestAdapter(opt, max_trials=10),
            max_concurrent_trials=2,
        ),
        run_config=tune.TuneRunConfig(name="adapter-sweep"),
    )
    results = tuner.fit()
    assert len(results) == 10
    assert len(opt.history) == 10, "optimizer missed completions"
    assert all(v is not None for _, v in opt.history)
    best = results.get_best_result()
    assert abs(best.config["x"] - 1.0) < 2.0


def test_suggest_adapter_mode_min_negates(ray_start):
    from ray_tpu import tune

    opt = _FakeBayesOpt(seed=5)

    def objective(config):
        tune.report({"loss": (config["x"] - 2.0) ** 2})

    tuner = tune.Tuner(
        objective,
        tune_config=tune.TuneConfig(
            metric="loss", mode="min",
            search_alg=tune.SuggestAdapter(opt, max_trials=6),
        ),
        run_config=tune.TuneRunConfig(name="adapter-min"),
    )
    tuner.fit()
    # adapter contract: values handed to the optimizer are higher-is-better
    xs = np.array([c["x"] for c, v in opt.history])
    vs = np.array([v for _, v in opt.history])
    assert np.all(vs <= 0)  # negated losses
    assert np.argmax(vs) == np.argmin((xs - 2.0) ** 2)
