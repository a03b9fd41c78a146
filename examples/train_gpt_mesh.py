"""Train a GPT on a device mesh with JaxTrainer.

Mirrors the reference's data-parallel trainer quickstart
(doc/source/train/getting-started) on the TPU-native stack: ScalingConfig
picks the gang, the train loop builds a mesh, shards params by the logical
axis table, and reports through the session.

Run small (CPU mesh):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/train_gpt_mesh.py
"""
import os

import ray_tpu
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

TINY = os.environ.get("EXAMPLE_TINY", "1") == "1"


def train_loop(config):
    import os

    import jax

    # Workers are fresh processes and must match the DRIVER's platform
    # decision, not the ambient env: a driver that runs on the CPU mesh
    # passes force_cpu so workers never probe the accelerator (the chip
    # belongs to one process at a time — the env var alone doesn't
    # capture an in-process jax.config.update("jax_platforms", "cpu") in
    # the driver).
    if config.get("force_cpu") or (
            os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"):
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.gpt import (
        GPTConfig, gpt_init, gpt_loss, gpt_param_axes,
    )
    from ray_tpu.parallel import (
        MeshSpec, ShardingRules, build_mesh, shard_params,
    )
    from ray_tpu.train import session

    cfg = GPTConfig.tiny() if config["tiny"] else GPTConfig.gpt2_small()
    mesh = build_mesh(MeshSpec(dp=-1))  # all local devices on the data axis
    rules = ShardingRules()
    params = shard_params(
        gpt_init(jax.random.PRNGKey(0), cfg), gpt_param_axes(cfg), mesh, rules
    )
    tx = optax.adamw(3e-4)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(gpt_loss)(
            params, batch, cfg, rules=rules, mesh=mesh
        )
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (8, 65), 0, cfg.vocab_size)
    for i in range(config["steps"]):
        params, opt_state, loss = step(params, opt_state, {"tokens": tokens})
        if i % 5 == 0 or i == config["steps"] - 1:
            session.report({"step": i, "loss": float(loss)})


def main():
    import sys

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    # propagate the driver's platform to the gang: if jax is already up on
    # CPU here (tests force it; JAX_PLATFORMS=cpu runs force it), workers
    # must not initialize an accelerator backend
    force_cpu = False
    if "jax" in sys.modules:
        import jax

        # only an EXPLICIT cpu-only platform config counts: the unset
        # default (None) means "use the accelerator", and forcing workers
        # to CPU then would silently de-accelerate real training
        plat = jax.config.jax_platforms or ""
        force_cpu = bool(plat) and set(plat.split(",")) == {"cpu"}
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={"tiny": TINY, "steps": 20 if TINY else 200,
                           "force_cpu": force_cpu},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="gpt-example"),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    print("final:", result.metrics)
    return result


if __name__ == "__main__":
    main()
