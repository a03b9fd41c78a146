"""Pipeline parallelism: GPipe-style microbatching over the `pp` mesh axis.

The reference has NO native pipeline parallelism — only Alpa-on-Ray release
tests (reference: release/alpa_tests/app_config.yaml, SURVEY.md §2.4 PP row).
This is new first-class capability, built the TPU way (SURVEY.md §7 item 8):
stages live on the `pp` mesh axis, activations rotate stage-to-stage with
`jax.lax.ppermute` over the ICI ring, and the microbatch schedule is a
`lax.scan` — one compiled program, no host round-trips between ticks.

Layout: stage-stacked params with leading dim S sharded on `pp` (each device
holds exactly its stage's slice). A batch of M microbatches streams through
S stages in M + S - 1 ticks; backward flows through the same scan via
autodiff (reverse ppermute is generated by XLA).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel.mesh import AxisNames


def stack_stage_params(per_stage_params: list) -> Any:
    """Stack S per-stage pytrees into one pytree with leading stage dim
    (shard it on `pp` with `stage_param_sharding`)."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *per_stage_params
    )


def stage_param_sharding(mesh: Mesh):
    """NamedSharding putting the stacked stage dim on the pp axis."""
    return NamedSharding(mesh, P(AxisNames.PIPE))


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    n_stages: int,
    n_microbatches: int,
    mesh: Mesh,
):
    """Build an SPMD pipelined apply: (stacked_params, x) -> y.

    stage_fn(stage_params, activation) is ONE stage's compute (same shape
    in/out). x: [batch, ...]; batch is split into `n_microbatches` equal
    microbatches. Runs under shard_map on the pp axis; other mesh axes pass
    through unreduced (compose dp outside or in stage_fn).
    """
    axis = AxisNames.PIPE

    def spmd(params_stage, x):  # params for THIS device's stage; x replicated
        # shard_map hands each device a [1, ...] slice of the stacked params
        params_stage = jax.tree_util.tree_map(
            lambda a: jnp.squeeze(a, axis=0), params_stage
        )
        stage = jax.lax.axis_index(axis)
        mb = jnp.reshape(x, (n_microbatches, x.shape[0] // n_microbatches) + x.shape[1:])
        total_ticks = n_microbatches + n_stages - 1

        state = jnp.zeros_like(mb[0])  # current activation on this device
        outputs = jnp.zeros_like(mb)

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (when in range); others use the
            # activation received from the previous stage
            feed = jnp.where(
                t < n_microbatches, mb[jnp.minimum(t, n_microbatches - 1)], mb[0]
            )
            inp = jnp.where(stage == 0, feed, state)
            out = stage_fn(params_stage, inp)
            # last stage commits finished microbatch t - (S-1)
            done_idx = t - (n_stages - 1)
            commit = jnp.logical_and(stage == n_stages - 1, done_idx >= 0)
            outputs = jax.lax.cond(
                commit,
                lambda o: o.at[jnp.maximum(done_idx, 0)].set(out),
                lambda o: o,
                outputs,
            )
            # rotate activations forward around the pp ring
            state = jax.lax.ppermute(
                out,
                axis,
                perm=[(i, (i + 1) % n_stages) for i in range(n_stages)],
            )
            return (state, outputs), None

        (state, outputs), _ = jax.lax.scan(
            tick, (state, outputs), jnp.arange(total_ticks)
        )
        # every device ends holding zeros except the last stage's `outputs`;
        # broadcast the finished result from the last stage to all pp ranks
        # (psum of one-hot masked outputs — cheap: one collective at the end)
        mask = (stage == n_stages - 1).astype(outputs.dtype)
        outputs = jax.lax.psum(outputs * mask, axis)
        return jnp.reshape(outputs, x.shape[0:1] + outputs.shape[2:])

    return jax.shard_map(
        spmd, mesh=mesh, in_specs=(P(AxisNames.PIPE), P()), out_specs=P(),
        check_vma=False,
    )


def simple_stage_mlp(d_model: int, d_hidden: int):
    """Helper pair (init_fn, stage_fn) for tests/examples: one residual MLP
    block per stage."""

    def init(key, n_stages: int) -> list:
        ks = jax.random.split(key, n_stages)
        out = []
        for k in ks:
            k1, k2 = jax.random.split(k)
            out.append(
                {
                    "w1": jax.random.normal(k1, (d_model, d_hidden)) * 0.02,
                    "w2": jax.random.normal(k2, (d_hidden, d_model)) * 0.02,
                }
            )
        return out

    def stage_fn(p, x):
        h = jnp.tanh(x @ p["w1"])
        return x + h @ p["w2"]

    return init, stage_fn
