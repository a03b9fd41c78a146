"""Ring attention: sequence/context parallelism over an ICI ring axis.

New capability — the reference has none (SURVEY.md §5.7: no ring attention,
sequence or context parallelism anywhere; grep returns nothing). Design:
KV shards rotate around the `sp` mesh axis via `ppermute` while each device
holds its Q shard; per-step partial attention is combined with the online
softmax (running max/denominator), so the full S×S score matrix never
materializes on any one device — per-device memory is O(S_local²).

Used inside `shard_map` over the sequence axis (see
ray_tpu/parallel/sp.py for the train-layer entry point). The per-block
compute is XLA-level here; the Pallas flash kernel can replace the block
einsums once it returns (m, l) residuals — same combination algebra.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool = True,
    scale: float | None = None,
) -> jax.Array:
    """Attention where K/V are sharded over `axis_name` and rotate.

    Must be called inside shard_map with q,k,v local shards [B,H,S_loc,D].
    Returns the local output shard [B,H,S_loc,D].
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape

    qf = q.astype(jnp.float32)

    def step(j, carry):
        o_acc, m_acc, l_acc, k_rot, v_rot = carry
        # the kv block now held arrived from device (my_idx - j) mod n
        src = (my_idx - j) % n

        s = (
            jnp.einsum(
                "bhqd,bhkd->bhqk",
                qf,
                k_rot.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        if causal:
            q_pos = my_idx * s_loc + jax.lax.broadcasted_iota(
                jnp.int32, (s_loc, s_loc), 0
            )
            k_pos = src * s_loc + jax.lax.broadcasted_iota(
                jnp.int32, (s_loc, s_loc), 1
            )
            s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)

        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_acc, m_cur)
        # guard fully-masked rows (m_new == NEG_INF): exp(NEG_INF - NEG_INF)
        # would be 1; clamp the shift so those rows contribute 0
        shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - shift)
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.where(m_acc <= NEG_INF / 2, 0.0, jnp.exp(m_acc - shift))
        l_new = alpha * l_acc + jnp.sum(p, axis=-1, keepdims=True)
        o_new = o_acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_rot.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_next = jax.lax.ppermute(k_rot, axis_name, perm)
        v_next = jax.lax.ppermute(v_rot, axis_name, perm)
        return o_new, m_new, l_new, k_next, v_next

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    m0 = jnp.full((b, h, s_loc, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc, 1), jnp.float32)
    o, m, l, _, _ = jax.lax.fori_loop(0, n, step, (o0, m0, l0, k, v))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (o / l_safe).astype(q.dtype)


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    batch_axes: tuple[str, ...] = ("dp", "fsdp"),
) -> jax.Array:
    """Global-view entry: q,k,v [B,H,S,D] with S sharded on `axis_name`.

    Wraps `ring_attention` in shard_map with batch sharded over the data
    axes and sequence over the ring axis.
    """
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axes, None, axis_name, None)
    fn = jax.shard_map(
        partial(ring_attention, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
