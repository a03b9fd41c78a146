"""Pipeline parallelism + MoE expert parallelism on the virtual CPU mesh
(new capabilities absent from the reference — SURVEY.md §2.4 PP/EP rows;
test approach mirrors reference fake-accelerator multi-node strategy §4.3)."""
from __future__ import annotations

import numpy as np
import pytest


@pytest.fixture(scope="module")
def mesh8(jax_cpu):
    from ray_tpu.parallel import MeshSpec, build_mesh

    return build_mesh(MeshSpec(pp=4, dp=2))


def test_pipeline_matches_sequential(jax_cpu, mesh8):
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.parallel.pipeline import (
        pipeline_apply,
        simple_stage_mlp,
        stack_stage_params,
        stage_param_sharding,
    )

    mesh = build_mesh(MeshSpec(pp=8))
    S, M, B, D = 8, 4, 16, 32
    init, stage_fn = simple_stage_mlp(D, 64)
    per_stage = init(jax.random.PRNGKey(0), S)
    stacked = jax.device_put(stack_stage_params(per_stage), stage_param_sharding(mesh))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D))

    piped = jax.jit(pipeline_apply(stage_fn, S, M, mesh))
    y = piped(stacked, x)

    y_ref = x
    for p in per_stage:
        y_ref = stage_fn(p, y_ref)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-4, atol=2e-5)


def test_pipeline_differentiable(jax_cpu):
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.parallel.pipeline import (
        pipeline_apply,
        simple_stage_mlp,
        stack_stage_params,
        stage_param_sharding,
    )

    mesh = build_mesh(MeshSpec(pp=4, dp=2))
    S, M, B, D = 4, 2, 8, 16
    init, stage_fn = simple_stage_mlp(D, 32)
    stacked = jax.device_put(
        stack_stage_params(init(jax.random.PRNGKey(0), S)),
        stage_param_sharding(mesh),
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D))
    apply = pipeline_apply(stage_fn, S, M, mesh)

    def loss(p):
        return jnp.mean(jnp.square(apply(p, x)))

    g = jax.jit(jax.grad(loss))(stacked)
    norms = jax.tree_util.tree_map(lambda a: float(jnp.linalg.norm(a)), g)
    flat = jax.tree_util.tree_leaves(norms)
    assert all(np.isfinite(v) for v in flat)
    assert sum(flat) > 0  # every stage gets gradient signal


def test_moe_matches_dense_reference(jax_cpu):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.moe import (
        MoEConfig,
        moe_forward,
        moe_init,
        moe_reference_dense,
    )

    cfg = MoEConfig(
        d_model=32, d_hidden=64, num_experts=4, top_k=2,
        capacity_factor=8.0,  # ample capacity → no drops → must match dense
        dtype=jnp.float32,
    )
    params = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)
    y, aux = jax.jit(lambda p, x: moe_forward(p, x, cfg))(params, x)
    y_ref = moe_reference_dense(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-4, atol=1e-5)
    assert float(aux) > 0


def test_moe_capacity_drops_tokens(jax_cpu):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.moe import MoEConfig, moe_forward, moe_init

    cfg = MoEConfig(
        d_model=16, d_hidden=32, num_experts=2, top_k=1,
        capacity_factor=0.1, dtype=jnp.float32,
    )
    params = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 16))
    y, _ = jax.jit(lambda p, x: moe_forward(p, x, cfg))(params, x)
    # capacity 0.1*40/2=2 per expert → most tokens dropped → many zero rows
    zero_rows = np.sum(np.all(np.asarray(y) == 0, axis=-1))
    assert zero_rows >= 20


def test_moe_expert_parallel_sharded(jax_cpu):
    """Experts sharded on ep axis: jit with ep-sharded weights must produce
    the same values as unsharded."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.mesh import AxisNames, MeshSpec, build_mesh
    from ray_tpu.ops.moe import MoEConfig, moe_forward, moe_init

    mesh = build_mesh(MeshSpec(ep=8))
    cfg = MoEConfig(
        d_model=32, d_hidden=64, num_experts=8, top_k=2,
        capacity_factor=8.0, dtype=jnp.float32,
    )
    params = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)
    y_unsharded, _ = jax.jit(lambda p, x: moe_forward(p, x, cfg))(params, x)

    sharded = dict(params)
    espec = NamedSharding(mesh, P(AxisNames.EXPERT))
    sharded["w_in"] = jax.device_put(params["w_in"], espec)
    sharded["w_out"] = jax.device_put(params["w_out"], espec)
    sharded["router"] = jax.device_put(params["router"], NamedSharding(mesh, P()))
    with mesh:
        y_sharded, _ = jax.jit(lambda p, x: moe_forward(p, x, cfg))(sharded, x)
    np.testing.assert_allclose(
        np.asarray(y_sharded), np.asarray(y_unsharded), rtol=1e-4, atol=1e-5
    )


def test_multichip_dryrun_compiles_without_spmd_remat():
    """The full dryrun (dp/fsdp/tp, ring-attention sp, pp, ep) must compile
    with ZERO '[SPMD] Involuntary full rematerialization' warnings — those
    mean replicate-then-repartition traffic on every step.
    Subprocess: the dryrun needs its own 8-device CPU backend."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    r = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "Involuntary full rematerialization" not in r.stderr, (
        "SPMD partitioner fell back to full remat:\n"
        + "\n".join(
            l for l in r.stderr.splitlines() if "Involuntary" in l
        )[:2000]
    )
