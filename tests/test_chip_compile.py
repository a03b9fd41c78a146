"""The main path's Pallas kernels compile for the chip — checked without
one, by compiling for a DESCRIBED ``v5e:2x2`` (on-chip-measurement guide
section 2, rehearsal 3). Interpret mode cannot see what the chip's compiler
refuses: a block whose last two dimensions are not (8k, 128k) or
full-extent, too much VMEM, a kernel GSPMD cannot partition.

All in this one file, and the topology is described inside a fixture, never
at import: only one process may load the TPU's library, so under several
xdist workers only the worker that is handed this file does. Nothing runs —
a compile that passes is not a chip run (chip_smoke.py is).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import re

import pytest


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this image
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without a chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _struct(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# GPT-2 125M's widths in both pool dtypes, and the GQA width ROADMAP R1-R2
# will bring
WIDTHS = {
    "gpt2-f32": (12, 12, 64, "float32"),
    "gpt2-bf16": (12, 12, 64, "bfloat16"),
    "gqa-bf16": (32, 8, 128, "bfloat16"),
}


@pytest.mark.parametrize("quant", [None, "int8"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_paged_attention_compiles(one_chip, kind, width, quant):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import (
        paged_attention_pallas, paged_prefill_attention_pallas,
    )
    from ray_tpu.ops.quantization import QuantizedKV

    hq, hkv, hd, dtype = WIDTHS[width]
    dtype = jnp.dtype(dtype)
    B, bs, num_blocks, NB, S = 8, 16, 2048, 64, 512
    S_ = functools.partial(_struct, sharding=one_chip)
    if quant is None:
        pool = S_((num_blocks, bs, hkv, hd), dtype)
    else:
        pool = QuantizedKV(
            S_((num_blocks, bs, hkv, hd), jnp.int8),
            S_((num_blocks, bs, hkv), jnp.float32),
        )
    tables = S_((B, NB), jnp.int32)
    if kind == "decode":
        fn = functools.partial(paged_attention_pallas, interpret=False)
        args = (S_((B, hq, hd), dtype), pool, pool, tables,
                S_((B,), jnp.int32))
    else:
        fn = functools.partial(
            paged_prefill_attention_pallas, interpret=False
        )
        args = (S_((B, S, hq, hd), dtype), pool, pool, tables,
                S_((B, S), jnp.int32))
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


def test_flash_forward_backward_compiles(one_chip):
    """The training kernels at the benchmark's shape: bs 24 x 12 heads x
    seq 1,024 x head_dim 64, bf16."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import flash_attention

    x = _struct((24, 12, 1024, 64), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x
    ).compile()
    # the forward kernel and the backward
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"'
    ) >= 2


def test_flash_kernel_names_reach_the_lowered_text(one_chip):
    """Each flash kernel's ``name=`` is in what the chip's compiler is
    handed, so a trace shows ``flash_fwd`` and not ``jvp___``. Four KV
    blocks or fewer fuse dq into the dk/dv sweep; more take the second
    pass."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import flash_attention

    def lowered(seq, block):
        x = _struct((2, 4, seq, 64), jnp.bfloat16, one_chip)

        def loss(q, k, v):
            out = flash_attention(q, k, v, block_q=block, block_kv=block,
                                  interpret=False)
            return jnp.sum(out.astype(jnp.float32))

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            x, x, x).as_text()

    fused = lowered(1024, 1024)
    assert "flash_fwd" in fused and "flash_bwd_dkv" in fused
    assert "flash_bwd_dq" not in fused
    two_pass = lowered(2048, 256)
    assert all(n in two_pass
               for n in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"))


def test_sharded_decode_step_compiles_partitioned(topo, monkeypatch):
    """One tp=4 decode step of GPT-2 125M on the four-device mesh, as
    ShardedExecutor runs it (weights by the training rules, pool split
    along KV heads, mesh set around the step): the kernel is there, GSPMD
    did not all-gather the pool to run it whole, and each device holds a
    quarter of the pool."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models.gpt import (
        GPTConfig, gpt_decode_step, gpt_init, gpt_param_axes,
    )
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import ShardingRules, param_shardings

    # the model step passes no ``interpret``: steer it here, in the test —
    # conftest.py's hook would have the kernel interpreted
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    cfg = dataclasses.replace(
        GPTConfig.gpt2_small(), attention_backend="pallas"
    )
    mesh = build_mesh(MeshSpec(tp=4), list(topo.devices))
    params = jax.tree.map(
        lambda s, sh: _struct(s.shape, s.dtype, sh),
        jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg)),
        param_shardings(gpt_param_axes(cfg), mesh, ShardingRules()),
    )
    B, bs, num_blocks, NB = 4, 16, 34 * 64 + 1, 64
    pool = _struct(
        (cfg.n_layer, num_blocks, bs, cfg.n_head, cfg.head_dim), cfg.dtype,
        NamedSharding(mesh, P(None, None, None, "tp")),
    )
    rep = functools.partial(_struct, sharding=NamedSharding(mesh, P()))
    with jax.set_mesh(mesh):
        compiled = jax.jit(
            functools.partial(gpt_decode_step, cfg=cfg)
        ).lower(
            params, pool, pool, rep((B,), jnp.int32), rep((B,), jnp.int32),
            rep((B, NB), jnp.int32),
        ).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert " all-gather(" not in text
    pool_bytes = 2 * pool.size * pool.dtype.itemsize
    per_device = compiled.memory_analysis().argument_size_in_bytes
    # a quarter of the pool plus this device's share of the f32 weights —
    # well under half of the pool alone
    assert per_device < pool_bytes / 2


@pytest.mark.parametrize("case", ["stored", "stored-tp4", "int8", "fp8"])
def test_step_programs_take_no_cross_program_prefetch(topo, monkeypatch, case):
    """GPT-2 125M's 64-row decode step as the serving cell runs it, under
    the step programs' own compiler settings for the TPU (decode.py
    ``_compiler_options``). On the tree the executor stores (matmul
    weights bf16, ISSUE 25) the compiler would by default prefetch an
    entry parameter across programs: on one chip the tied 77 MB ``wte``,
    which then sits in the fast memory for the whole step and pushes each
    layer's pool slice out of it; under tp=4 its shard. The chip's
    compiler knows the option, and with it no program has such a
    prefetch, as none had on float32 masters. A quantized program has
    none to begin with and is the same text either way. No matmul weight
    is cast in the step."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models.gpt import (
        GPTConfig, gpt_decode_step, gpt_init, gpt_param_axes, gpt_quant_axes,
    )
    from ray_tpu.ops.quantization import quantize_params
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import ShardingRules, param_shardings
    from ray_tpu.serve.llm import decode

    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    options = decode._compiler_options("tpu")
    assert options and decode._compiler_options("cpu") is None

    quant = case if case in ("int8", "fp8") else None
    cfg = dataclasses.replace(
        GPTConfig.gpt2_small(), attention_backend="pallas",
        quantization=quant,
    )
    masters = jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg))
    axes = gpt_quant_axes(cfg)
    mesh = build_mesh(
        MeshSpec(tp=4 if case.endswith("tp4") else 1),
        list(topo.devices)[: 4 if case.endswith("tp4") else 1],
    )
    rep = NamedSharding(mesh, P())
    if quant is None:
        params = jax.tree.map(
            lambda s, axis, sh: _struct(
                s.shape, cfg.dtype if axis >= 0 else s.dtype, sh),
            masters, axes,
            param_shardings(gpt_param_axes(cfg), mesh, ShardingRules()),
        )
    else:
        params = jax.tree.map(
            lambda s: _struct(s.shape, s.dtype, rep),
            jax.eval_shape(lambda p: quantize_params(p, axes, quant), masters),
        )
    B, bs, num_blocks = 64, 16, 4097
    pool = _struct(
        (cfg.n_layer, num_blocks, bs, cfg.n_head, cfg.head_dim), cfg.dtype,
        NamedSharding(mesh, P(None, None, None, "tp")),
    )
    i32 = functools.partial(_struct, dtype=jnp.int32, sharding=rep)
    with jax.set_mesh(mesh):
        lowered = jax.jit(functools.partial(gpt_decode_step, cfg=cfg)).lower(
            params, pool, pool, i32((B,)), i32((B,)),
            i32((B, cfg.max_seq_len // bs)),
        )
        default = lowered.compile().as_text()
        text = lowered.compile(compiler_options=options).as_text()
    assert "cross_program_prefetch_index" not in text
    assert 'custom_call_target="tpu_custom_call"' in text
    if quant is not None:
        assert text == default
        return
    assert "cross_program_prefetch_index" in default
    if case == "stored":  # a partitioned program's parameters lose their names
        # biases and norm scales stay float32 and are cast where used (a
        # few KB); no matmul weight is
        assert "%params__" in text and not re.search(
            r"convert\(%params__(blocks____)?(wte|wpe|\w+_w)__", text)
