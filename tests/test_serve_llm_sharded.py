"""Multi-chip sharded LLM serving (ISSUE 6): the ModelExecutor seam.

On the 8-virtual-device CPU mesh (conftest sets
``--xla_force_host_platform_device_count=8``): executor selection and the
KV-pool head-axis sharding invariant, byte-identical token parity between
the sharded and single-device executors (greedy AND temperature/top-p)
for both model families, the frozen compile-kind contract under a
sharded engine, byte-identical mid-stream failover resume ACROSS mesh
shapes, the O(batch) int32 sync budget under sharding, and the
config/mesh validation surface.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest


def _f32(cfg):
    import jax.numpy as jnp

    return dataclasses.replace(cfg, dtype=jnp.float32, attention="xla")


def _model_config(family="llama"):
    if family == "gpt":
        from ray_tpu.models.gpt import GPTConfig

        return _f32(GPTConfig.tiny())
    from ray_tpu.models.llama import LlamaConfig

    return _f32(LlamaConfig.tiny())


def _engine(family, mc, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 64)
    return LLMEngine(
        EngineConfig(model=family, model_config=mc, **kw), auto_step=False
    )


def _drain(eng, streams, steps=400):
    for _ in range(steps):
        if all(s.done for s in streams):
            break
        eng.step()
    while eng.step():  # reconcile any in-flight step (lag-1 drain)
        pass


def _kv_tp_axis(arr):
    """The mesh axis the pool array is partitioned over at its head dim
    (index 3 of [layer, block, slot, kv_head, head_dim], and of a
    lane-dense [layer, block, slot, kv_head * head_dim]: contiguous heads
    a device); None if replicated there."""
    spec = arr.sharding.spec
    return spec[3] if len(spec) > 3 else None


# ------------------------------------------- executor selection + layout

def test_sharded_executor_shards_kv_pool_head_axis(jax_cpu):
    """tp/fsdp config selects ShardedExecutor; the paged KV pool arrays
    carry (and KEEP, through real steps) head-axis tp sharding while the
    block tables stay host-side numpy."""
    from ray_tpu.serve.llm.executor import ShardedExecutor

    eng = _engine("llama", _model_config("llama"), tp=2, fsdp=2)
    assert isinstance(eng.executor, ShardedExecutor)
    assert eng.executor.num_devices == 4
    assert _kv_tp_axis(eng.cache.k) == "tp"
    assert _kv_tp_axis(eng.cache.v) == "tp"
    assert {d for arr in (eng.cache.k, eng.cache.v)
            for d in arr.sharding.device_set} == set(
        eng.executor.mesh.devices.flat
    )

    streams = [eng.submit([i + 1] * 5, max_new_tokens=6) for i in range(3)]
    for _ in range(3):
        eng.step()
    # host-side scheduling state is untouched by sharding: block tables
    # are plain Python lists of ints, padded to numpy on dispatch
    live = dict(eng.cache._tables)
    assert live, "no live sequences while streams are running"
    for table in live.values():
        assert isinstance(table, list)
        assert all(isinstance(b, int) for b in table)
    _drain(eng, streams)
    assert all(len(list(s)) == 6 for s in streams)
    # the invariant SURVIVES jitted prefill/decode updates: GSPMD did not
    # silently replicate (or gather) the pool
    assert _kv_tp_axis(eng.cache.k) == "tp"
    assert _kv_tp_axis(eng.cache.v) == "tp"
    st = eng.stats()
    assert st["executor"] == {"executor": "sharded", "devices": 4,
                              "mesh": {"tp": 2, "fsdp": 2},
                              "platform": "cpu", "device_kind": "cpu",
                              "quantization": None,
                              "weight_dtype": "float32",
                              "weight_bytes": 4 * eng.executor.num_params,
                              "attention_backend": "xla",
                              "kv_layers": 2,
                              # lane-dense: a token's 2 heads of 16 a row
                              "kv_pool_shape": [2, 64, 8, 32],
                              # K and V by head: 2 x 2 heads x 16 x 4 B
                              "kv_pool": {"kind": "heads", "row_bytes": 256,
                                          "stored_row_bytes": 256,
                                          "block_bytes": 4096,
                                          "page_copies": 2},
                              "state": None,
                              "prefix_reuse": True,
                              "speculative": None}
    assert eng.debug_dump()["executor"]["mesh"] == {"tp": 2, "fsdp": 2}


def test_single_device_default_unchanged(jax_cpu):
    """Default config keeps the single-device executor — no mesh in
    stats, one device, and the engine still serves."""
    from ray_tpu.serve.llm.executor import SingleDeviceExecutor

    eng = _engine("llama", _model_config("llama"))
    assert isinstance(eng.executor, SingleDeviceExecutor)
    assert eng.stats()["executor"] == {"executor": "single", "devices": 1,
                                       "mesh": None,
                                       "platform": "cpu",
                                       "device_kind": "cpu",
                                       "quantization": None,
                                       "weight_dtype": "float32",
                                       "weight_bytes":
                                           4 * eng.executor.num_params,
                                       "attention_backend": "xla",
                                       "kv_layers": 2,
                                       "kv_pool_shape": [2, 64, 8, 32],
                                       "kv_pool": {"kind": "heads",
                                                   "row_bytes": 256,
                                                   "stored_row_bytes": 256,
                                                   "block_bytes": 4096,
                                                   "page_copies": 2},
                                       "state": None,
                                       "prefix_reuse": True,
                                       "speculative": None}
    assert len(eng.generate([5, 6, 7], max_new_tokens=4)) == 4


# ------------------------------------------------- byte-identical parity

@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_sharded_greedy_parity_byte_identical(jax_cpu, family):
    """Greedy decode on a tp=2/fsdp=2 mesh must emit exactly the
    single-device token stream — concurrent batched streams, both
    families. (llama tiny has n_kv_head=2, so tp=2 is its max.)"""
    mc = _model_config(family)
    prompts = [[1, 2, 3], [7] * 11, [100, 200, 300, 400, 5]]

    single = _engine(family, mc)
    ref_streams = [single.submit(p, max_new_tokens=8) for p in prompts]
    _drain(single, ref_streams)
    ref = [list(s) for s in ref_streams]

    sharded = _engine(family, mc, tp=2, fsdp=2)
    got_streams = [sharded.submit(p, max_new_tokens=8) for p in prompts]
    _drain(sharded, got_streams)
    assert [list(s) for s in got_streams] == ref


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_sharded_sampled_parity_byte_identical(jax_cpu, family):
    """Keyed (seed, position) sampling with temperature + top-p is also
    byte-identical across executors: the fused pick runs on the
    post-all-reduce full-vocab logits, so the mesh cannot perturb it."""
    mc = _model_config(family)
    prompt = [9, 8, 7, 200, 13]
    kw = dict(max_new_tokens=10, temperature=0.8, top_p=0.9, seed=5)

    ref = _engine(family, mc).generate(prompt, **kw)
    got = _engine(family, mc, tp=2, fsdp=2).generate(prompt, **kw)
    assert got == ref
    assert len(ref) == 10


# ------------------------------------------------- compile-count contract

def test_sharded_compile_kinds_frozen(jax_cpu):
    """The sharded engine reuses the process-shared jit wrappers: a mixed
    greedy/top-k/top-p/temperature wave compiles only
    (prefill, prefill_chunk, decode) x bucket shapes, and a second wave
    with new sampling configs at the same shapes compiles nothing."""
    eng = _engine("llama", _model_config("llama"), tp=2, fsdp=2)
    mixes = [
        dict(),                                     # greedy
        dict(temperature=0.7, top_k=4, seed=1),     # top-k
        dict(temperature=0.9, top_p=0.8, seed=2),   # nucleus
        dict(temperature=1.1, seed=3),              # plain temperature
    ]
    streams = [
        eng.submit([10 + i, 20 + i, 30 + i], max_new_tokens=6, **m)
        for i, m in enumerate(mixes)
    ]
    _drain(eng, streams)
    sigs = eng.fns.signatures
    kinds = {s[0] for s in sigs}
    assert kinds <= {"prefill", "prefill_chunk", "decode"}, kinds
    before = len(sigs)

    streams = [
        eng.submit([40 + i, 50 + i, 60 + i], max_new_tokens=6,
                   temperature=0.3 + 0.1 * i, top_k=2 + i, seed=100 + i)
        for i in range(4)
    ]
    _drain(eng, streams)
    assert len(eng.fns.signatures) == before


# ------------------------------------- failover resume across mesh shapes

def test_resume_byte_identical_across_mesh_shapes(jax_cpu):
    """A stream begun on a tp=2/fsdp=2 replica resumes byte-identically
    on a DIFFERENTLY-shaped replica — tp=2/fsdp=1 and plain single-chip —
    via prior_tokens + start_index, exactly the failover protocol."""
    mc = _model_config("llama")
    prompt = [9, 8, 7, 200, 13]
    kw = dict(max_new_tokens=12, temperature=0.8, top_p=0.9, seed=5)

    full = _engine("llama", mc, tp=2, fsdp=2).generate(prompt, **kw)
    assert len(full) == 12

    shapes = [dict(tp=2, fsdp=1), dict()]  # smaller mesh, then one chip
    for shape in shapes:
        for k in (3, 7):
            resumed = _engine("llama", mc, **shape).generate(
                prompt + full[:k],
                max_new_tokens=12 - k,
                temperature=0.8, top_p=0.9, seed=5,
                start_index=k,
            )
            assert resumed == full[k:], (
                f"divergence resuming at {k} onto {shape or 'single'}"
            )


# --------------------------------------------------- O(batch) sync budget

def test_sharded_host_sync_stays_o_batch_int32(jax_cpu):
    """ISSUE 6 acceptance: sharding must not widen the device->host
    pipe. Every sync record on the sharded engine is still 4*bucket_b
    bytes — the ids are replicated post-all-reduce, so the transfer does
    not scale with device count (and never approaches a logits pull)."""
    mc = _model_config("llama")
    eng = _engine("llama", mc, tp=2, fsdp=2)
    streams = [eng.submit([i + 1] * 5, max_new_tokens=8) for i in range(3)]
    _drain(eng, streams)

    recs = [r for r in eng.debug_dump()["steps"] if "sync_bytes" in r]
    assert recs, "no sync records in the flight ring"
    # a packed prefill step's ids are a row a piece, padded to its ladder
    buckets = set(eng._batch_buckets) | set(eng._piece_rows)
    for r in recs:
        assert r["sync_bytes"] % 4 == 0, r
        assert r["sync_bytes"] // 4 in buckets, r
        assert r["sync_bytes"] < 4 * mc.vocab_size, r


# ----------------------------------------------- config/mesh validation

def test_mesh_and_config_validation(jax_cpu):
    """The error surface fails fast and names the fix: zero axis sizes,
    non-tp/fsdp serving meshes, indivisible KV heads, and bad
    ModelParallelConfig values are all caught at construction."""
    from ray_tpu.parallel import MeshSpec, param_shardings  # noqa: F401
    from ray_tpu.serve.config import ModelParallelConfig

    with pytest.raises(ValueError, match="positive ints"):
        MeshSpec(tp=0).resolve(8)
    with pytest.raises(ValueError, match="at most one"):
        MeshSpec(tp=-1, fsdp=-1).resolve(8)
    with pytest.raises(ValueError, match="tp and fsdp must be >= 1"):
        ModelParallelConfig(tp=0)
    assert ModelParallelConfig(tp=2, fsdp=2).n_devices == 4

    mc = _model_config("llama")  # n_kv_head=2
    with pytest.raises(ValueError, match="n_kv_head=2 is not"):
        _engine("llama", mc, tp=4)
    with pytest.raises(ValueError, match="tp/fsdp only"):
        _engine("llama", mc, mesh={"dp": 2, "tp": 2})
    with pytest.raises(TypeError, match="mesh must be"):
        _engine("llama", mc, mesh=object())


def test_mesh_plumbing_through_config_objects(jax_cpu):
    """Every advertised mesh spelling lands on the same executor:
    ModelParallelConfig, MeshSpec, a dict of axis sizes, and bare
    tp/fsdp ints on the EngineConfig."""
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.serve.config import ModelParallelConfig

    mc = _model_config("llama")
    spellings = [
        dict(mesh=ModelParallelConfig(tp=2, fsdp=2)),
        dict(mesh=MeshSpec(tp=2, fsdp=2)),
        dict(mesh={"tp": 2, "fsdp": 2}),
        dict(tp=2, fsdp=2),
    ]
    for kw in spellings:
        eng = _engine("llama", mc, **kw)
        assert eng.stats()["executor"]["mesh"] == {"tp": 2, "fsdp": 2}, kw
