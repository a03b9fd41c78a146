"""Quantized serving (ISSUE 20): int8/fp8 weights + quantized paged KV
with in-kernel dequant.

The acceptance contract is deliberately two-sided:

- ACROSS configs (quantized engine vs its f32 twin) the bar is
  agreement-rate and perplexity — quantization changes the arithmetic,
  so byte-identity is the wrong ask (docs/SERVING_LLM.md § Quantized
  serving).
- WITHIN a quantized config every byte-identity invariant the repo has
  accumulated must hold exactly: sharded vs single-device, COW /
  demote-promote through the host tier, preempt-resume, disaggregated
  handoff, and mid-stream replica-kill failover — quantize/dequant is
  bit-deterministic and rides the keyed (seed, position) sampler
  unchanged.

Capacity is asserted too: the quantized pool must fit >= 2x the KV
blocks per chip at an equal device-memory budget, and the host tier
(charging entries at actual packed wire size) must hold >= 2x the
entries at an equal ``host_cache_bytes``.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
import pytest

from conftest import shutdown_if_setup_fails

from ray_tpu._private import chaos
from ray_tpu._private.chaos import Fault, FaultPlan

# seeded prompts for workload-shaping tests (compile kinds, hygiene):
# varied lengths so both the monolithic and chunked prefill paths run
PROMPTS = [
    [1, 5, 9, 2, 7, 3],
    [4, 4, 8, 1],
    [2, 9, 9, 9, 5, 6, 7, 1, 3],
    [11, 3, 5, 2, 8, 13, 1, 1, 4, 6, 9, 2],
    [7, 7, 2],
    [3, 1, 4, 1, 5, 9, 2, 6, 5, 3],
]
AGREEMENT_NEW_TOKENS = 16
AGREEMENT_FLOOR = 0.98

KILL_PROMPT = [5, 6, 7]
KILL_SAMPLING = dict(max_new_tokens=8, temperature=0.8, seed=42)
KILL_AT_INDEX = 2


def _f32(cfg):
    import jax.numpy as jnp

    return dataclasses.replace(cfg, dtype=jnp.float32, attention="xla")


def _model_config(family="llama"):
    if family == "gpt":
        from ray_tpu.models.gpt import GPTConfig

        return _f32(GPTConfig.tiny())
    from ray_tpu.models.llama import LlamaConfig

    return _f32(LlamaConfig.tiny())


def _engine(family="llama", mc=None, params=None, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 64)
    return LLMEngine(
        EngineConfig(
            model=family,
            model_config=mc if mc is not None else _model_config(family),
            seed=0,
            **kw,
        ),
        params=params,
        auto_step=False,
    )


def _generate_all(eng, prompts=PROMPTS, n=AGREEMENT_NEW_TOKENS):
    return [eng.generate(p, max_new_tokens=n) for p in prompts]


# --- trained weights for the agreement gate -------------------------
#
# Random-init tiny models have near-uniform logits: the top-2 margin at
# most positions is smaller than ANY quantization's arithmetic noise,
# so free-running greedy agreement there measures coin flips, not
# quantization quality. The gate instead runs on weights briefly
# trained (seeded, deterministic SGD) on an unambiguous cyclic corpus
# (next = cur + 1 mod V): the model predicts with real margins, which
# is the regime the >= 0.98 contract is about.

_TRAINED: dict[str, dict] = {}


def _cyclic_corpus(rng, vocab: int, batch: int, seq: int):
    starts = rng.integers(0, vocab, size=batch)
    return (starts[:, None] + np.arange(seq + 1)[None, :]) % vocab


def _trained_params(family: str):
    import jax
    import jax.numpy as jnp

    if family in _TRAINED:
        return _TRAINED[family]
    mc = _model_config(family)
    if family == "gpt":
        from ray_tpu.models.gpt import gpt_init as init
        from ray_tpu.models.gpt import gpt_loss as loss
        steps = 500  # absolute position embeddings learn the task slower
    else:
        from ray_tpu.models.llama import llama_init as init
        from ray_tpu.models.llama import llama_loss as loss
        steps = 300  # 120 leaves fp8 argmax margins too thin on some prompts
    params = init(jax.random.PRNGKey(0), mc)
    rng = np.random.default_rng(3)

    @jax.jit
    def sgd(p, toks):
        _, g = jax.value_and_grad(loss)(p, {"tokens": toks}, mc)
        return jax.tree.map(lambda a, b: a - 1.0 * b, p, g)

    for _ in range(steps):
        toks = jnp.asarray(
            _cyclic_corpus(rng, mc.vocab_size, 8, 24), jnp.int32)
        params = sgd(params, toks)
    _TRAINED[family] = params
    return params


def _agreement_prompts(family: str, n=6, length=8):
    vocab = _model_config(family).vocab_size
    rng = np.random.default_rng(5)
    return [
        [int(t) for t in _cyclic_corpus(rng, vocab, 1, length - 1)[0]]
        for _ in range(n)
    ]


def _agreement(a: list[list[int]], b: list[list[int]]) -> float:
    assert len(a) == len(b)
    hits = total = 0
    for x, y in zip(a, b):
        assert len(x) == len(y)
        hits += sum(int(t == u) for t, u in zip(x, y))
        total += len(x)
    return hits / total


def _pool_is_clean(eng) -> bool:
    return (
        len(eng.cache._free) + len(eng.cache._lru)
        == eng.cache.cfg.usable_blocks
        and eng.cache._reserved == 0
    )


# ------------------------------------------------------- quantize ops

def test_resolve_quantization_validates():
    from ray_tpu.ops.quantization import resolve_quantization

    assert resolve_quantization(None) is None
    assert resolve_quantization("") is None
    assert resolve_quantization("int8") == "int8"
    assert resolve_quantization("fp8") == "fp8"
    with pytest.raises(ValueError, match="int4"):
        resolve_quantization("int4")  # loud, never a silent f32 fallback


@pytest.mark.parametrize("kind,bound", [("int8", 0.03), ("fp8", 0.15)])
def test_kv_roundtrip_error_bounds(jax_cpu, kind, bound):
    """Per-(slot, head) scale quantization round-trips within the kind's
    expected relative error (int8: 127 levels; fp8 e4m3: ~2 mantissa
    bits)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.quantization import quantize_kv

    x = jax.random.normal(jax.random.PRNGKey(0), (5, 8, 2, 16),
                          jnp.float32) * 3.0
    data, scale = quantize_kv(x, kind)
    assert data.shape == x.shape and scale.shape == x.shape[:-1]
    back = data.astype(jnp.float32) * scale[..., None]
    denom = float(jnp.max(jnp.abs(x)))
    err = float(jnp.max(jnp.abs(back - x))) / denom
    assert err <= bound, f"{kind} roundtrip rel err {err} > {bound}"
    # all-zero rows must quantize to exact zeros, not NaN (guarded scale)
    z_data, z_scale = quantize_kv(jnp.zeros((1, 4, 1, 8)), kind)
    assert float(jnp.max(jnp.abs(
        z_data.astype(jnp.float32) * z_scale[..., None]))) == 0.0


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_weight_quantization_roundtrip(jax_cpu, family):
    """quantize_params produces QuantizedTensor leaves exactly where the
    family's quant-axes tree marks a reduction axis, with broadcastable
    keepdims scales, and dequantizes within int8 error."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.quantization import QuantizedTensor, quantize_params
    from ray_tpu.serve.llm.decode import family_quant_axes

    mc = _model_config(family)
    from ray_tpu.serve.llm.decode import DecodeFns

    params = DecodeFns(family, mc).init(jax.random.PRNGKey(0), mc)
    axes = family_quant_axes(family, mc)
    qp = quantize_params(params, axes, "int8")

    flat_p = jax.tree_util.tree_leaves_with_path(params)
    flat_q = dict(jax.tree_util.tree_leaves_with_path(
        qp, is_leaf=lambda t: isinstance(t, QuantizedTensor)))
    flat_a = dict(jax.tree_util.tree_leaves_with_path(axes))
    n_quant = 0
    for path, leaf in flat_p:
        q = flat_q[path]
        axis = int(flat_a[path])
        if axis < 0:
            assert not isinstance(q, QuantizedTensor)
            assert q is leaf  # untouched f32 leaf, not a copy
            continue
        n_quant += 1
        assert isinstance(q, QuantizedTensor)
        assert q.data.dtype == jnp.int8 and q.data.shape == leaf.shape
        # keepdims scale broadcasts against the data everywhere
        assert q.scale.shape[axis] == 1
        back = q.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(back - leaf)))
        err /= max(float(jnp.max(jnp.abs(leaf))), 1e-9)
        assert err <= 0.03, f"{path} roundtrip rel err {err}"
    assert n_quant > 0, "quant-axes tree marked nothing quantizable"


@pytest.mark.timeout(300)
@pytest.mark.parametrize("mesh_kw", [{}, {"tp": 2}], ids=["single", "tp2"])
@pytest.mark.parametrize("kind", ["int8", "fp8"])
def test_quantized_tree_is_built_from_float32_masters(jax_cpu, kind, mesh_kw):
    """With ``quantization`` set the executor's tree is what
    ``quantize_params`` gives on the float32 masters, byte for byte: the
    store-in-compute-dtype step (ISSUE 25) never runs, although the
    config computes in bfloat16 and it would have something to cast."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.ops.quantization import QuantizedTensor, quantize_params
    from ray_tpu.serve.llm.decode import DecodeFns, family_quant_axes

    mc = dataclasses.replace(LlamaConfig.tiny(), attention="xla")
    assert mc.dtype == jnp.bfloat16
    masters = DecodeFns("llama", mc).init(jax.random.PRNGKey(0), mc)
    want = quantize_params(masters, family_quant_axes("llama", mc), kind)
    eng = _engine(mc=mc, params=masters, quantization=kind, **mesh_kw)
    try:
        d = eng.executor.describe()
        assert d["weight_dtype"] == kind
        got = jax.tree.leaves(
            eng.params, is_leaf=lambda t: isinstance(t, QuantizedTensor))
        exp = jax.tree.leaves(
            want, is_leaf=lambda t: isinstance(t, QuantizedTensor))
        assert len(got) == len(exp)
        n_bytes = 0
        for g, e in zip(got, exp):
            assert isinstance(g, QuantizedTensor) == isinstance(
                e, QuantizedTensor)
            for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(e)):
                assert a.dtype == b.dtype and a.dtype != jnp.bfloat16
                assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
                n_bytes += b.size * b.dtype.itemsize
        assert d["weight_bytes"] == n_bytes
    finally:
        eng.shutdown()


@pytest.mark.timeout(300)
def test_moe_expert_leaves_stay_float32(jax_cpu):
    """``moe_forward`` reads router and expert weights raw (no ``astype``
    seam), so the store-in-compute-dtype step leaves them float32, and
    the streams are those of the program that casts at every use (the
    same engine with the float32 masters put back)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm.decode import DecodeFns

    mc = dataclasses.replace(LlamaConfig.tiny_moe(), attention="xla")
    masters = DecodeFns("llama", mc).init(jax.random.PRNGKey(0), mc)
    eng = _engine(mc=mc, params=masters)
    parent = _engine(mc=mc, params=masters)
    try:
        blocks = eng.params["blocks"]
        for name in ("moe_router", "moe_w_in", "moe_w_out", "ln1_scale"):
            assert blocks[name].dtype == jnp.float32, name
            assert blocks[name] is masters["blocks"][name]
        for w in (blocks["wq"], blocks["wo"], eng.params["wte"],
                  eng.params["lm_head"]):
            assert w.dtype == jnp.bfloat16
        parent.executor.params = masters
        assert _generate_all(eng) == _generate_all(parent)
    finally:
        eng.shutdown()
        parent.shutdown()


# -------------------------------------------- agreement & perplexity

@pytest.mark.timeout(300)
@pytest.mark.parametrize("family", ["gpt", "llama"])
@pytest.mark.parametrize("kind", ["int8", "fp8"])
def test_greedy_agreement_vs_f32(jax_cpu, family, kind):
    """The cross-config acceptance gate: free-running greedy streams
    from a quantized engine agree with the f32 engine on >= 98% of
    tokens over seeded prompts (trained weights — see _trained_params)
    — and the quantized engine is deterministic with itself
    (within-config byte identity)."""
    params = _trained_params(family)
    prompts = _agreement_prompts(family)
    ref_eng = _engine(family, params=params)
    ref = _generate_all(ref_eng, prompts)
    ref_eng.shutdown()

    q_eng = _engine(family, params=params, quantization=kind)
    got = _generate_all(q_eng, prompts)
    assert q_eng.stats()["executor"]["quantization"] == kind
    q_eng.shutdown()

    rate = _agreement(ref, got)
    assert rate >= AGREEMENT_FLOOR, (
        f"{family}/{kind} greedy agreement {rate:.3f} < {AGREEMENT_FLOOR}"
    )

    q_eng2 = _engine(family, params=params, quantization=kind)
    assert _generate_all(q_eng2, prompts) == got, (
        "quantized engine nondeterministic")
    q_eng2.shutdown()


@pytest.mark.timeout(300)
@pytest.mark.parametrize("mesh_kw", [dict(tp=2), dict(fsdp=2)],
                         ids=["tp2", "fsdp2"])
def test_sharded_quantized_byte_identical_to_single(jax_cpu, mesh_kw):
    """Within the quantized config, mesh shape must not change a single
    byte (post-shard quantization is deterministic: amax over an axis is
    layout-invariant) — and the sharded engine still clears the
    agreement floor vs f32."""
    params = _trained_params("llama")
    prompts = _agreement_prompts("llama")
    single = _engine("llama", params=params, quantization="int8")
    ref_q = _generate_all(single, prompts)
    single.shutdown()

    sharded = _engine("llama", params=params, quantization="int8",
                      **mesh_kw)
    got = _generate_all(sharded, prompts)
    desc = sharded.stats()["executor"]
    assert desc["executor"] == "sharded" and desc["quantization"] == "int8"
    sharded.shutdown()
    assert got == ref_q, f"{mesh_kw}: quantized stream changed across mesh"

    f32_eng = _engine("llama", params=params)
    ref = _generate_all(f32_eng, prompts)
    f32_eng.shutdown()
    assert _agreement(ref, got) >= AGREEMENT_FLOOR


@pytest.mark.timeout(300)
@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_perplexity_gate(jax_cpu, family):
    """Teacher-forced loss on the dequantized weights stays within 5%
    perplexity of f32 on seeded token batches — the scalar quality gate
    behind the agreement rate."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.quantization import QuantizedTensor, quantize_params
    from ray_tpu.serve.llm.decode import DecodeFns, family_quant_axes

    if family == "gpt":
        from ray_tpu.models.gpt import gpt_loss as loss_fn
    else:
        from ray_tpu.models.llama import llama_loss as loss_fn

    mc = _model_config(family)
    params = DecodeFns(family, mc).init(jax.random.PRNGKey(0), mc)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, mc.vocab_size, (4, 33)), jnp.int32)}
    base = float(loss_fn(params, batch, mc))
    for kind in ("int8", "fp8"):
        qp = quantize_params(params, family_quant_axes(family, mc), kind)
        deq = jax.tree.map(
            lambda t: (t.astype(jnp.float32)
                       if isinstance(t, QuantizedTensor) else t),
            qp, is_leaf=lambda t: isinstance(t, QuantizedTensor))
        q = float(loss_fn(deq, batch, mc))
        ppl_ratio = float(np.exp(q - base))
        assert ppl_ratio <= 1.05, (
            f"{family}/{kind} perplexity ratio {ppl_ratio:.4f} > 1.05"
        )


# ------------------------------------------------- compile-kind set

@pytest.mark.timeout(300)
def test_compile_kind_set_unchanged_vs_f32(jax_cpu):
    """Quantization is a static engine config: it swaps the traced
    programs (distinct jit-cache entries via the frozen model config) but
    must not add or change any (kind, shape) signature — same bucketed
    traffic, same signature set, on both engines."""
    def drive(eng):
        for p in PROMPTS[:3]:
            eng.generate(p, max_new_tokens=6)
        return eng.executor.signatures

    f32_eng = _engine("gpt")
    f32_sigs = drive(f32_eng)
    f32_eng.shutdown()
    q_eng = _engine("gpt", quantization="int8")
    q_sigs = drive(q_eng)
    q_eng.shutdown()
    assert q_sigs == f32_sigs, (
        f"quantization changed the compile-signature set: "
        f"{q_sigs ^ f32_sigs}"
    )
    assert {s[0] for s in q_sigs} <= {"prefill", "prefill_chunk", "decode"}


# ------------------------------------------------------- capacity

@pytest.mark.timeout(300)
@pytest.mark.parametrize("kind", ["int8", "fp8"])
def test_quantized_pool_fits_2x_blocks(jax_cpu, kind):
    """The tentpole capacity claim: at an equal device-memory budget the
    quantized pool holds >= 2x the KV blocks (1-byte elements + one f32
    scale per (slot, head) vs 4 bytes per element)."""
    import jax

    f32_eng = _engine("llama")
    q_eng = _engine("llama", quantization=kind)

    def pool_bytes(eng):
        leaves = jax.tree.leaves(eng.cache.k) + jax.tree.leaves(eng.cache.v)
        return sum(leaf.nbytes for leaf in leaves)

    nb = f32_eng.cache.cfg.num_blocks
    assert q_eng.cache.cfg.num_blocks == nb
    per_block_f32 = pool_bytes(f32_eng) / nb
    per_block_q = pool_bytes(q_eng) / nb
    ratio = per_block_f32 / per_block_q
    f32_eng.shutdown()
    q_eng.shutdown()
    assert ratio >= 2.0, (
        f"{kind} pool holds only {ratio:.2f}x blocks per byte (need >= 2x)"
    )
    # the wire format shrinks identically (host tier + handoff payloads)
    from ray_tpu.serve.llm.kv_transfer import KVLayout

    base = dict(n_layer=3, block_size=8, n_kv_head=2, head_dim=16)
    wire_ratio = (
        KVLayout(**base, dtype="float32").record_payload_bytes
        / KVLayout(**base, dtype=("int8" if kind == "int8"
                                  else "float8_e4m3fn"),
                   quantization=kind).record_payload_bytes
    )
    assert wire_ratio >= 2.0


@pytest.mark.timeout(300)
def test_host_tier_packed_byte_accounting(jax_cpu):
    """Satellite 2: the host tier charges entries at actual packed wire
    size, so a quantized layout admits >= 2x the blocks at the same
    ``host_cache_bytes`` cap — and ``nbytes`` tracks the packed sum
    exactly."""
    import numpy as onp

    from ray_tpu.ops.quantization import QuantizedKV, quantize_kv
    from ray_tpu.serve.llm.kv_cache import HostKVTier
    from ray_tpu.serve.llm.kv_transfer import KVLayout

    base = dict(n_layer=2, block_size=8, n_kv_head=2, head_dim=16)
    rng = onp.random.default_rng(0)

    def fill(tier, quantized):
        stored = 0
        for i in range(4096):
            x = rng.standard_normal(
                (base["n_layer"], base["block_size"], base["n_kv_head"],
                 base["head_dim"])).astype(onp.float32)
            if quantized:
                import jax.numpy as jnp

                d, s = quantize_kv(jnp.asarray(x), "int8")
                blk = QuantizedKV(onp.asarray(d), onp.asarray(s))
            else:
                blk = x
            ok, evicted = tier.put(bytes([i % 256, i // 256]) * 8, blk, blk)
            if not ok or evicted:
                break
            stored += 1
        return stored

    cap = 256 * 1024
    f32_tier = HostKVTier(cap, KVLayout(**base, dtype="float32"))
    q_tier = HostKVTier(
        cap, KVLayout(**base, dtype="int8", quantization="int8"))
    n_f32 = fill(f32_tier, False)
    n_q = fill(q_tier, True)
    assert n_q >= 2 * n_f32, (
        f"quantized host tier holds {n_q} blocks vs f32 {n_f32} "
        f"at equal byte cap — packed-size accounting broken"
    )
    assert q_tier.nbytes <= cap and q_tier.blocks == n_q


# ------------------------------------------------------- wire format

def test_wire_v2_roundtrip_and_loud_mismatch(jax_cpu):
    """RTKV v2: quantized payloads round-trip (data + scale planes), a
    layout/config mismatch at unpack refuses LOUDLY naming the differing
    field, and v1 f32 payloads stay readable."""
    import jax.numpy as jnp
    import numpy as onp

    from ray_tpu.ops.quantization import QuantizedKV, quantize_kv
    from ray_tpu.serve.llm import kv_transfer
    from ray_tpu.serve.llm.kv_transfer import KVLayout, KVTransferError

    base = dict(n_layer=2, block_size=4, n_kv_head=2, head_dim=8)
    q_layout = KVLayout(**base, dtype="int8", quantization="int8")
    f_layout = KVLayout(**base, dtype="float32")
    shape = (base["n_layer"], base["block_size"], base["n_kv_head"],
             base["head_dim"])
    rng = onp.random.default_rng(1)
    x = rng.standard_normal(shape).astype(onp.float32)

    d, s = quantize_kv(jnp.asarray(x), "int8")
    blk = QuantizedKV(onp.asarray(d), onp.asarray(s))
    wire = kv_transfer.pack_blocks(q_layout, [(b"d" * 16, blk, blk)],
                                   prefix_tokens=4)
    got_layout, prefix_tokens, records = kv_transfer.unpack_blocks(
        wire, expect=q_layout)
    assert got_layout == q_layout and prefix_tokens == 4
    (digest, k_got, v_got), = records
    assert digest == b"d" * 16
    assert isinstance(k_got, QuantizedKV)
    onp.testing.assert_array_equal(onp.asarray(k_got.data),
                                   onp.asarray(blk.data))
    onp.testing.assert_array_equal(onp.asarray(k_got.scale),
                                   onp.asarray(blk.scale))

    # config mismatch refuses loudly, naming the field
    with pytest.raises(KVTransferError, match="quantization"):
        kv_transfer.unpack_blocks(wire, expect=f_layout)

    # v1 f32 payloads still read back fine (and refuse a quantized expect)
    wire_v1 = kv_transfer.pack_blocks(f_layout, [(b"e" * 16, x, x)],
                                      prefix_tokens=0)
    got_layout, _, records = kv_transfer.unpack_blocks(
        wire_v1, expect=f_layout)
    assert got_layout == f_layout
    onp.testing.assert_array_equal(records[0][1], x)
    with pytest.raises(KVTransferError, match="quantization"):
        kv_transfer.unpack_blocks(wire_v1, expect=q_layout)

    # a quantized layout refuses a plain f32 block at pack time
    with pytest.raises(KVTransferError, match="plain ndarray"):
        kv_transfer.pack_blocks(q_layout, [(b"f" * 16, x, x)],
                                prefix_tokens=0)


# ------------------------------------- block hygiene within-config

def _drain(eng, streams, steps=1500):
    for _ in range(steps):
        if all(s.done for s in streams):
            break
        if not eng.step():
            time.sleep(0.02)
    while eng.step():
        pass


@pytest.mark.timeout(300)
def test_block_hygiene_cow_demote_promote_preempt(jax_cpu):
    """Exactly-once block accounting with scale planes riding along:
    shared-prefix COW forks, host-tier demote/promote churn, and a
    priority preemption pause/resume all leave the quantized pool clean,
    and every stream is byte-identical to an unpressured quantized
    engine."""
    common = dict(
        quantization="int8", block_size=4, num_blocks=24,
        host_cache_bytes=1 << 20,
    )
    sampling = dict(temperature=0.8, seed=7)
    batch_prompt = [5, 6, 7, 8, 9, 11]

    ref_eng = _engine("gpt", **common)
    ref_batch = ref_eng.generate(batch_prompt, max_new_tokens=16, **sampling)
    # shared-prefix pair (forces COW on the partial tail block)
    ref_shared = [
        ref_eng.generate(PROMPTS[0], max_new_tokens=8, temperature=0.8,
                         seed=s)
        for s in (1, 2)
    ]
    ref_eng.shutdown()

    eng = _engine(
        "gpt", preemption=dict(kv_pressure=0.5, queue_wait_s=0.05,
                               resume_pressure=0.4),
        **common,
    )
    batch = eng.submit(batch_prompt, max_new_tokens=16, priority="batch",
                       **sampling)
    eng.step()  # prefill
    eng.step()  # one decode before the flood
    shared = [
        eng.submit(PROMPTS[0], max_new_tokens=8, priority="interactive",
                   temperature=0.8, seed=s)
        for s in (1, 2)
    ]
    flood = [
        eng.submit([13 + i, 4, 5], max_new_tokens=8,
                   priority="interactive", temperature=0.8, seed=100 + i)
        for i in range(6)
    ]
    time.sleep(0.07)
    _drain(eng, [batch] + shared + flood)

    assert eng.stats()["preemptions_total"] >= 1, \
        "the flood should have preempted the batch stream"
    assert eng.stats()["preempted"] == 0
    assert list(batch) == ref_batch
    assert [list(s) for s in shared] == ref_shared
    for s in flood:
        assert len(list(s)) == 8
    assert _pool_is_clean(eng), "exactly-once accounting broke under quant"

    # demote/promote replay: churn the pool, then replay the originals —
    # promoted quantized blocks must reproduce the streams byte-for-byte
    for i in range(6):
        eng.generate([31 + i] * 10, max_new_tokens=8)
    assert eng.generate(batch_prompt, max_new_tokens=16,
                        **sampling) == ref_batch
    assert _pool_is_clean(eng)
    stats = eng.stats()
    assert stats["host_cache_blocks"] > 0, "host tier never engaged"
    eng.shutdown()


@pytest.mark.timeout(300)
def test_handoff_byte_identical_within_quantized_config(jax_cpu):
    """Disaggregated prefill/decode handoff inside the quantized config:
    exported quantized blocks adopted by a second engine produce the
    byte-identical stream (and the layouts match including the
    quantization fields)."""
    prompt = [7, 1, 8, 2, 8, 1, 8, 2, 8, 4, 5, 9, 0, 4, 5, 2]
    pe = _engine("llama", quantization="int8")
    de = _engine("llama", quantization="int8")
    try:
        ref = pe.generate(prompt, max_new_tokens=10)
        records = pe.export_prefix(prompt)
        assert records, "prefill engine exported no full blocks"
        layout = pe.kv_layout()
        assert layout == de.kv_layout()
        assert layout.quantization == "int8"
        adopted = de.adopt_prefix(prompt, records)
        assert adopted == len(records)
        assert de.generate(prompt, max_new_tokens=10) == ref
    finally:
        pe.shutdown()
        de.shutdown()


# ----------------------------------------------- chaos: replica kill

@pytest.fixture(scope="module")
def quant_cluster():
    """Two int8-quantized LLM replicas behind serve, with a chaos plan
    killing the tagged request's replica mid-stream — the quantized twin
    of test_serve_llm_ft's failover storyline."""
    import os

    plan = FaultPlan(seed=7, faults=(
        Fault(point="llm.token", action="kill",
              when={"tag": "killme", "index": KILL_AT_INDEX,
                    "resumed": False}),
    ))
    prev = os.environ.get(chaos.ENV_VAR)
    os.environ[chaos.ENV_VAR] = plan.to_json()
    chaos.clear()

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import EngineConfig, build_llm_app

    ray_tpu.init(num_cpus=8)
    with shutdown_if_setup_fails():
        serve.start(http_options={"port": 0}, grpc_options={"port": 0})
        handle = serve.run(
            build_llm_app(
                EngineConfig(model="llama", model_config=_model_config(),
                             seed=0, quantization="int8"),
                num_replicas=2,
            ),
            name="llm-quant", route_prefix="/llmquant", timeout_s=180,
        )
    yield serve, handle
    serve.shutdown()
    ray_tpu.shutdown()
    chaos.clear()
    if prev is None:
        os.environ.pop(chaos.ENV_VAR, None)
    else:
        os.environ[chaos.ENV_VAR] = prev


@pytest.mark.chaos
@pytest.mark.timeout(300)
def test_replica_kill_mid_stream_quantized_byte_identical(quant_cluster):
    """Kill the serving replica after N streamed tokens of a quantized
    stream: the failover resume completes byte-identical to an
    uninterrupted quantized engine (same config, same seed) — the
    within-config losslessness contract under chaos."""
    from ray_tpu.serve.llm import stream_tokens

    serve, handle = quant_cluster
    ref_eng = _engine("llama", quantization="int8")
    reference = ref_eng.generate(KILL_PROMPT, **KILL_SAMPLING)
    ref_eng.shutdown()

    gen = stream_tokens(handle, {
        "prompt": KILL_PROMPT,
        "request_id": "quant-kill-1",
        "chaos_tag": "killme",
        **KILL_SAMPLING,
    })
    chunks = list(gen)
    assert gen.failovers >= 1, "the chaos kill should have forced failover"
    assert [c["index"] for c in chunks] == list(
        range(KILL_SAMPLING["max_new_tokens"]))
    assert [c["token"] for c in chunks] == reference
