"""Chunk summaries of EVA attention (Zheng et al., ICLR 2023,
arXiv:2302.04542), as the EvaByte family uses them (models/evabyte.py).

A chunk is ``C`` consecutive positions. Once its window has closed, later
queries no longer see its ``C`` keys and values but ONE pair that stands
for them. With two learned vectors a head, ``phi`` and ``mu`` ``[H, hd]``,
and ``s = hd ** -0.5``::

    a_m = softmax over the chunk's m of (s * k_m . phi_h)
    V_c = sum_m a_m v_m                 the summary's value
    K_c = mean_m k_m + mu_h             the summary's key

A summary has a token's shape, ``[H, hd]`` K and V, so the paged pool holds
it in a slot like any token's (serve/llm/kv_cache.py: the summary table)
and the attention kernel reads it as one more key.

Two formulations with one contract, as the attention ops have: plain
``jax.numpy`` (``backend="xla"``: the CPU's, and the reference semantics)
and a Pallas kernel named ``eva_summarize`` (``"pallas"``: the chip's;
under that name a device trace finds its time, which the leaves' names
cannot give inside a scanned stack). Float32 inside, the caller's dtype
out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_tpu.ops.attention import pallas_interpret
from ray_tpu.ops.kv_cache import physical_slots, write_kv
from ray_tpu.ops.paged_attention import resolve_backend

KERNEL_NAME = "eva_summarize"
# chunks a grid step: a step's K and V blocks are 128 KB a chunk at the
# published widths (16 x 32 x 128 bf16), their float32 copies twice that
_CHUNKS_A_STEP = 4


def _summaries_xla(k, v, phi, mu):
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    scale = k.shape[-1] ** -0.5
    scores = jnp.einsum("nchd,hd->nch", k32, phi.astype(jnp.float32)) * scale
    a = jax.nn.softmax(scores, axis=1)
    v_c = jnp.einsum("nch,nchd->nhd", a, v32)
    k_c = jnp.mean(k32, axis=1) + mu.astype(jnp.float32)
    return k_c.astype(k.dtype), v_c.astype(v.dtype)


def _summaries_kernel(k_ref, v_ref, phi_ref, mu_ref, ko_ref, vo_ref):
    k = k_ref[...].astype(jnp.float32)       # [G, C, H, hd]
    v = v_ref[...].astype(jnp.float32)
    scale = k.shape[-1] ** -0.5
    scores = jnp.sum(k * phi_ref[...][None, None], axis=-1,
                     keepdims=True) * scale  # [G, C, H, 1]
    e = jnp.exp(scores - jnp.max(scores, axis=1, keepdims=True))
    a = e / jnp.sum(e, axis=1, keepdims=True)
    vo_ref[...] = jnp.sum(a * v, axis=1).astype(vo_ref.dtype)
    ko_ref[...] = (jnp.mean(k, axis=1) + mu_ref[...][None]).astype(
        ko_ref.dtype)


def _summaries_pallas(k, v, phi, mu, interpret):
    N, C, H, hd = k.shape
    G = next(g for g in range(min(_CHUNKS_A_STEP, N), 0, -1) if N % g == 0)
    chunks = pl.BlockSpec((G, C, H, hd), lambda i: (i, 0, 0, 0))
    heads = pl.BlockSpec((H, hd), lambda i: (0, 0))
    out = pl.BlockSpec((G, H, hd), lambda i: (i, 0, 0))
    return pl.pallas_call(
        _summaries_kernel,
        grid=(N // G,),
        in_specs=[chunks, chunks, heads, heads],
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((N, H, hd), k.dtype),
                   jax.ShapeDtypeStruct((N, H, hd), v.dtype)],
        name=KERNEL_NAME,
        interpret=interpret,
    )(k, v, phi.astype(jnp.float32), mu.astype(jnp.float32))


def chunk_summaries(k: jax.Array, v: jax.Array, phi: jax.Array,
                    mu: jax.Array, *, backend: str = "auto",
                    interpret: bool | None = None):
    """``k``, ``v`` ``[..., C, H, hd]``: whole chunks, a chunk's ``C`` rows
    in position order; ``phi``, ``mu`` ``[H, hd]``. Returns ``(K_c, V_c)``,
    each ``[..., H, hd]`` in the inputs' dtypes. Which chunks are worth
    keeping is the caller's: a chunk that padding fills gives numbers no
    one reads (models/evabyte.py writes it to the garbage block)."""
    lead = k.shape[:-3]
    flat = (-1, *k.shape[-3:])
    if resolve_backend(backend) == "pallas":
        if interpret is None:
            interpret = pallas_interpret()
        fn = functools.partial(_summaries_pallas, interpret=interpret)
    else:
        fn = _summaries_xla
    k_c, v_c = fn(k.reshape(flat), v.reshape(flat), phi, mu)
    return (k_c.reshape(*lead, *k_c.shape[1:]),
            v_c.reshape(*lead, *v_c.shape[1:]))


def write_prefill_summaries(cache_k, cache_v, k, v, phi, mu, *, layer, start,
                            lengths, summaries, chunk: int,
                            backend: str = "auto"):
    """A prompt chunk's summaries into the pools, where they stand. ``k``,
    ``v`` ``[B, S, H, hd]`` are the chunk's FRESH rows, row b's first at
    true position ``start[b]``, a chunk's first position (the engine sees to
    it), ``lengths[b]`` of them real. Every whole chunk is summarised and
    lands in slot ``position // chunk`` of ``summaries`` ``[B, NB]``, the
    rows' summary tables; a chunk that padding fills goes to the garbage
    block. Returns the pools."""
    B, S, H, hd = k.shape
    n = S // chunk
    # the kernel's name covers its call; the scope also what stands around
    # it (the chunks' reshape, the summaries' scatter into the pools)
    with jax.named_scope(KERNEL_NAME):
        k_c, v_c = chunk_summaries(
            k[:, :n * chunk].reshape(B, n, chunk, H, hd),
            v[:, :n * chunk].reshape(B, n, chunk, H, hd), phi, mu,
            backend=backend)
        cols = jnp.arange(n, dtype=lengths.dtype)[None, :]
        return write_kv(
            cache_k, cache_v, k_c, v_c, start[:, None] // chunk + cols,
            summaries, valid=(cols + 1) * chunk <= lengths[:, None],
            layer=layer)


def write_decode_summaries(cache_k, cache_v, phi, mu, *, layer, t, at, ring,
                           summaries, chunk: int, backend: str = "auto"):
    """A decode step's summaries. Row b's token at true position ``t[b]``
    was just written at index ``at[b]`` of ``ring`` ``[B, NB]`` (the step's
    composed table): its chunk's ``chunk`` slots are read back from the
    pools, its own among them, and summarised; the summary lands in slot ``t
    // chunk`` of ``summaries`` only where the row filled the chunk's last
    slot (every other row's goes to the garbage block). Returns the
    pools."""
    B = t.shape[0]
    H, hd = phi.shape
    with jax.named_scope(KERNEL_NAME):  # the ring's gather is the larger part
        first = at - t % chunk  # the chunk's first slot, in table coordinates
        blk, slot = physical_slots(
            first[:, None] + jnp.arange(chunk, dtype=t.dtype), ring,
            cache_k.shape[2])
        k_c, v_c = chunk_summaries(
            cache_k[layer, blk, slot].reshape(B, chunk, H, hd),
            cache_v[layer, blk, slot].reshape(B, chunk, H, hd), phi, mu,
            backend=backend)
        return write_kv(cache_k, cache_v, k_c, v_c, t // chunk, summaries,
                        valid=t % chunk == chunk - 1, layer=layer)
