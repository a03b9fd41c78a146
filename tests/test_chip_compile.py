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
import math
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


# GPT-2 125M's widths in both pool dtypes, the GQA width of the llama cells,
# GQA at heads of 64 (the lfm2_moe cell's attention layers), and an ODD
# group of 7 over 4 heads of 128 (the smallthinker cell's: a page [4, 128]
# is no whole tile, so its pool is lane-dense, 16 x 512)
WIDTHS = {
    "gpt2-f32": (12, 12, 64, "float32"),
    "gpt2-bf16": (12, 12, 64, "bfloat16"),
    "gqa-bf16": (32, 8, 128, "bfloat16"),
    "gqa64-bf16": (32, 8, 64, "bfloat16"),
    "gqa7-bf16": (28, 4, 128, "bfloat16"),
}


# (B, table width NB, pool blocks, chunk S): a small engine's shapes, then
# the benchmark cells' own — a 64-row decode against the 160-entry table of
# the 2,560-token bucket and a pool of 4,097 blocks (``chat-closed``), the
# 64-entry table of ``chat-closed-1k``, and the 4 x 2,048 prefill.
SHAPES = {
    "decode": {"small": (8, 64, 2048, 1), "cell": (64, 160, 4097, 1),
               "cell-1k": (64, 64, 4097, 1)},
    "prefill": {"small": (8, 64, 2048, 512), "cell": (4, 128, 4097, 2048)},
}


@pytest.mark.parametrize("quant", [None, "int8"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize(
    "kind,shape",
    [(kind, shape) for kind in sorted(SHAPES) for shape in SHAPES[kind]],
)
def test_paged_attention_compiles(one_chip, kind, shape, width, quant):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import (
        paged_attention_pallas, paged_prefill_attention_pallas, pool_shape,
    )
    from ray_tpu.ops.quantization import QuantizedKV

    hq, hkv, hd, dtype = WIDTHS[width]
    dtype = jnp.dtype(dtype)
    B, NB, num_blocks, S = SHAPES[kind][shape]
    bs = 16
    S_ = functools.partial(_struct, sharding=one_chip)
    # one layer of the pool as the cache manager stores it: lane-dense
    # (16 x 1024 at the GQA width of 8 x 128, 16 x 768, 16 x 512)
    stored = pool_shape(1, num_blocks, bs, hkv, hd)[1:]
    assert stored == (num_blocks, bs, hkv * hd)
    if quant is None:
        pool = S_(stored, dtype)
    else:
        pool = QuantizedKV(
            S_(stored, jnp.int8),
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


@pytest.mark.parametrize("window", [None, 4096])
@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_paged_attention_compiles_at_an_odd_group(one_chip, kind, window):
    """The smallthinker cell's own calls: 28 query heads on 4 K/V heads of
    128 (a group of SEVEN: a decode tile is [4, 7, 128], 7 sublanes of 8; a
    prefill tile 128 x 7 = 896 rows) over the lane-dense pool ``[2, 65537,
    16, 512]`` read at a layer, plain and at a window of 4,096 (256 pages a
    row), a 48-row decode step and a 2,048-row chunk against the
    1,024-entry table of the 16,384-token bucket."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import (
        paged_prefill_attention_pallas, pool_shape,
    )

    S_ = functools.partial(_struct, sharding=one_chip)
    pool = S_(pool_shape(2, 65537, 16, 4, 128), jnp.bfloat16)
    assert pool.shape == (2, 65537, 16, 512)
    B, S = (48, 1) if kind == "decode" else (1, 2048)
    fn = functools.partial(paged_prefill_attention_pallas, window=window,
                           layer=1, interpret=False)
    compiled = jax.jit(fn).lower(
        S_((B, S, 28, 128), jnp.bfloat16), pool, pool,
        S_((B, 1024), jnp.int32), S_((B, S), jnp.int32)).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    name = "paged_attention" if window is None else "paged_attention_window"
    assert re.search(rf"%{name}[.\d]* = ", text)
    # the pool is read where it stands: no copy of it, no relayout
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6


@pytest.mark.parametrize("heads", [2, 3])
def test_paged_attention_compiles_for_a_tp_shard(one_chip, heads):
    """What one device of a ``tp`` mesh runs: its local KV heads only (8
    over tp=4, 12 over tp=4), fewer than a sublane tile, so its share of
    the pool is lane-dense: a row of 2 x 128 = 256 lanes is copied where it
    stands, one of 3 x 64 = 192 (no whole lanes) is padded for the kernel,
    a layer's slab a call."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import paged_attention_pallas

    hd = 128 if heads == 2 else 64
    S_ = functools.partial(_struct, sharding=one_chip)
    pool = S_((2048, 16, heads * hd), jnp.bfloat16)
    args = (S_((8, 4 * heads, hd), jnp.bfloat16), pool, pool,
            S_((8, 64), jnp.int32), S_((8,), jnp.int32))
    compiled = jax.jit(
        functools.partial(paged_attention_pallas, interpret=False)
    ).lower(*args).compile()
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
    ShardedExecutor runs it (weights by the training rules, the lane-dense
    pool's rows split into contiguous heads a device, mesh set around the
    step): the kernel is there, GSPMD did not all-gather the pool to run it
    whole, and each device holds a quarter of the pool."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models.gpt import (
        GPTConfig, gpt_decode_step, gpt_init, gpt_param_axes,
    )
    from ray_tpu.ops.paged_attention import pool_shape
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
        pool_shape(cfg.n_layer, num_blocks, bs, cfg.n_head, cfg.head_dim),
        cfg.dtype, NamedSharding(mesh, P(None, None, None, "tp")),
    )
    assert pool.shape[3:] == (cfg.n_head * cfg.head_dim,)
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


def _pool_sized_results(text, num_blocks, slab_elems):
    """``(instruction, opcode, dims, opcodes inside its fusion)`` of every
    instruction outside the fused computations whose result holds an array
    of a layer's slab of the pool or more: ``num_blocks`` divides its
    element count (4,097 = 17 x 241 divides no weight's), and it is no
    parameter, tuple plumbing, ``while`` or ``bitcast``, which make no
    buffer."""
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            bodies[name].append(line)
    fused = set(re.findall(r"kind=k\w+, calls=%?([\w.\-]+)", text))
    found = []
    for name, body in bodies.items():
        if name in fused:
            continue
        for line in body:
            m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*)", line)
            if not m:
                continue
            result = m.group(2)
            depth = end = 0
            if result.startswith("("):  # a tuple's type holds spaces
                for end, c in enumerate(result):
                    depth += (c == "(") - (c == ")")
                    if depth == 0:
                        break
            end = result.index(" ", end)
            op = re.match(r"\s*([\w\-]+)\(", result[end:])
            op = op.group(1) if op else "?"
            if op in ("parameter", "get-tuple-element", "tuple", "while",
                      "bitcast", "conditional", "call"):
                continue
            for dims in re.findall(r"\w+\[([\d,]+)\]", result[:end]):
                n = math.prod(map(int, dims.split(",")))
                if n >= slab_elems and n % num_blocks == 0:
                    calls = re.search(r"calls=%?([\w.\-]+)", result)
                    inside = "\n".join(bodies.get(calls.group(1), [])) \
                        if calls else ""
                    found.append((m.group(1), op, dims, set(
                        re.findall(r" ([\w\-]+)\(", inside))))
    return found


# (config of benchmark/configs, tp, kind): the benchmark cells' 64-row
# decode and 4-row prefill programs against the cells' pool of 4,097 blocks
POOL_PROGRAMS = {
    "mistral-decode": ("mistral-7b-v0.3-6l", 1, "decode"),
    "mistral-prefill": ("mistral-7b-v0.3-6l", 1, "prefill"),
    "mistral-decode-tp4": ("mistral-7b-v0.3-6l", 4, "decode"),
    "gpt2-decode": ("gpt2-small", 1, "decode"),
    "gpt2-prefill": ("gpt2-small", 1, "prefill"),
    "gpt2-decode-tp4": ("gpt2-small", 4, "decode"),
    "lfm2-decode": ("lfm2-24b-a2b-8l", 1, "decode"),
    "lfm2-prefill": ("lfm2-24b-a2b-8l", 1, "prefill"),
    # ISSUE 42: what the dense cells' prefill steps run since: the chunk
    # program over rows of one q tile, the longest rung of the packed
    # ladder under the widest context's table
    "mistral-prefill-packed": ("mistral-7b-v0.3-6l", 1, "packed"),
    "gpt2-prefill-packed": ("gpt2-small", 1, "packed"),
}


@pytest.mark.parametrize("case", sorted(POOL_PROGRAMS))
def test_step_programs_update_the_pool_in_place(topo, monkeypatch, case):
    """ISSUE 29's and ISSUE 31's counter is a property of the compiled
    program. As ``DecodeFns`` compiles a step (pools donated, the step
    programs' own options), for the described v5e and the pool in the
    shape the cache manager STORES it in (``pool_shape``: lane-dense for
    every family since ISSUE 43: Mistral's 8 heads of 128, GPT-2's 12 of
    64, lfm2's 8 of 64, a tp = 4 shard's 2 or 3 heads of its row):
    ``input_output_alias`` names ``cache_k``
    and ``cache_v``, so no second pool exists; the pool parameters rest in
    the order written; NOTHING but the two scatter fusions, in place,
    produces as much as one layer's slab (no slice, no update, no relayout
    copy), and the kernel is called once a layer on the whole pool. The
    one exception is the row that is not whole lanes (GPT-2's 3 heads of
    64 a device under tp = 4), which no layout rests unpadded and Mosaic
    refuses to copy: see the last lines."""
    import sys

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import common
    from ray_tpu.ops.paged_attention import pool_shape
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import ShardingRules, param_shardings
    from ray_tpu.serve.llm import decode

    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    config, tp, kind = POOL_PROGRAMS[case]
    held = common.load_json(
        os.path.join(root, f"benchmark/configs/{config}.json"))
    cfg = dataclasses.replace(
        common.model_config(held), attention_backend="pallas")
    fam = decode.get_family(held["family"])
    mesh = build_mesh(MeshSpec(tp=tp), list(topo.devices)[:tp])
    rep = NamedSharding(mesh, P())
    i32 = functools.partial(_struct, dtype=jnp.int32, sharding=rep)
    more = {}
    if fam.init_state is None:
        # the tree the executor stores: matmul weights in the compute dtype
        params = jax.tree.map(
            lambda s, axis, sh: _struct(
                s.shape, cfg.dtype if axis >= 0 else s.dtype, sh),
            jax.eval_shape(lambda: fam.init(jax.random.PRNGKey(0), cfg)),
            fam.quant_axes(cfg),
            param_shardings(fam.param_axes(cfg), mesh, ShardingRules()),
        )
    else:  # lfm2_moe: bf16 matrix leaves, and its state beside the pool
        init = common.load_named("reference", held["family"]).init_fn()
        params = jax.tree.map(
            lambda s: _struct(s.shape, s.dtype, rep),
            jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg)))
        more["state"] = jax.tree.map(
            lambda s: _struct(s.shape, s.dtype, rep),
            jax.eval_shape(lambda: fam.init_state(cfg, 65)))
    n_kv = getattr(cfg, "n_kv_head", cfg.n_head)
    n_layer = getattr(cfg, "n_kv_layer", cfg.n_layer)
    num_blocks, bs = 4097, 16
    stored = pool_shape(n_layer, num_blocks, bs, n_kv, cfg.head_dim)
    assert stored == (n_layer, num_blocks, bs, n_kv * cfg.head_dim)
    pool = _struct(stored, cfg.dtype,
                   NamedSharding(mesh, P(None, None, None, "tp")))
    S = min(2048, cfg.max_seq_len)
    fns = decode.DecodeFns(held["family"], cfg, platform="tpu")
    with jax.set_mesh(mesh):
        if kind == "decode":
            if more:
                more["slots"] = i32((64,))
            lowered = fns._decode.lower(
                params, pool, pool, i32((64,)), i32((64,)),
                i32((64, min(2560, cfg.max_seq_len) // bs)), sample=None,
                **more)
        elif kind == "packed":
            from ray_tpu.ops.paged_attention import Q_TILE

            top = min(2560, cfg.max_seq_len)
            rows = top // Q_TILE
            lowered = fns._prefill.lower(
                params, pool, pool, i32((rows, Q_TILE)), i32((rows,)),
                i32((rows, top // bs)), start=i32((rows,)), sample=None)
        else:
            if more:
                more["slots"] = i32((4,))
            lowered = fns._prefill.lower(
                params, pool, pool, i32((4, S)), i32((4,)),
                i32((4, S // bs)), sample=None, **more)
        compiled = lowered.compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    # both pools are the program's own output buffers
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    aliased = set(map(int, re.findall(r"\}: \((\d+),", alias)))
    entry = text[text.index("ENTRY"):]
    pools = {int(n): layout for layout, n in re.findall(
        r"%cache_[kv][.\d]* = \w+\[[\d,]+\]\{([\d,]+)[^\n]*? parameter\((\d+)\)",
        entry)}
    if tp == 1:  # a partitioned program's parameters lose their names
        assert len(pools) == 2 and set(pools) == aliased, (pools, alias)
        # and they rest in the order written
        written = ",".join(map(str, reversed(range(len(stored)))))
        assert set(pools.values()) == {written}, pools
    assert len(aliased) == 2, alias
    mem = compiled.memory_analysis()
    pool_bytes = 2 * pool.size * pool.dtype.itemsize // tp
    assert mem.alias_size_in_bytes >= pool_bytes  # tiles pad a layout
    slab = num_blocks * bs * (n_kv // tp) * cfg.head_dim
    big = _pool_sized_results(text, num_blocks, slab)
    scatters = [b for b in big if "scatter" in b[3] or b[1] == "scatter"]
    rest = [b for b in big if b not in scatters]
    # one kernel call a layer, a scatter of K and one of V beside it (a
    # scanned stack holds its one layer in the loop)
    calls = len(re.findall(r"%paged_attention[.\d]* = ", text))
    assert calls == (1 if held["family"] in ("gpt", "llama") else n_layer)
    assert len(scatters) == 2 * calls, big
    if (n_kv // tp * cfg.head_dim) % 128 == 0:
        assert not rest, rest
        return
    # the row that is not whole lanes (192 of them): it cannot rest
    # unpadded, so the device's share is relaid ONCE around the layer loop
    # (a copy of K and of V in the entry computation, what the parent's
    # per-slab path moved a layer at a time), and in the loop a layer's
    # slab is sliced out and padded for the kernel
    loop = text[:text.index("ENTRY")]
    assert all(re.search(rf"%{re.escape(b[0])} = ", loop) is None
               for b in rest if b[1] == "copy"), rest
    assert sum(b[1] == "copy" for b in rest) <= 4, rest
    assert mem.temp_size_in_bytes < 1.5 * pool_bytes, mem


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
    pool = _struct(  # as stored: lane-dense, [12, 4097, 16, 768]
        (cfg.n_layer, num_blocks, bs, cfg.n_head * cfg.head_dim), cfg.dtype,
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


def _lfm2_cell_shapes(one_chip):
    """The lfm2_moe cell's config and its arguments' shapes, as the
    benchmark builds them (bf16 matrix leaves from the reference's init)."""
    import sys

    import jax
    import jax.numpy as jnp

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import common
    from ray_tpu.models.lfm2_moe import lfm2_moe_init_state

    held = common.load_json(os.path.join(
        root, "benchmark/configs/lfm2-24b-a2b-8l.json"))
    cfg = dataclasses.replace(common.model_config(held),
                              attention_backend="pallas")
    init = common.load_named("reference", "lfm2_moe").init_fn()
    on_chip = lambda s: _struct(s.shape, s.dtype, one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init(jax.random.PRNGKey(0), cfg)))
    state = jax.tree.map(on_chip, jax.eval_shape(
        lambda: lfm2_moe_init_state(cfg, 65)))
    # as stored: 8 heads of 64 are one lane-dense row of 512
    pool = _struct((cfg.n_kv_layer, 4097, 16, cfg.n_kv_head * cfg.head_dim),
                   cfg.dtype, one_chip)
    i32 = functools.partial(_struct, dtype=jnp.int32, sharding=one_chip)
    return cfg, params, pool, state, i32


def _gmm_calls(entry):
    """The grouped expert product's calls in a compiled program's entry
    computation: ``moe_gmm_few_rows`` (ops/moe.py; ISSUE 49), one call an
    expert layer with both of its matrices among the operands."""
    return re.findall(r"%moe_gmm_few_rows[.\d]* = [^\n]*", entry)


def test_lfm2_moe_decode_step_compiles_at_published_widths(
        one_chip, monkeypatch):
    """The lfm2_moe cell's 64-row decode step, as the executor compiles it:
    it fits the chip beside nothing else (9.4 GB), the grouped expert
    product is the ``moe_gmm_few_rows`` kernel (ISSUE 49; XLA's
    ``ragged-dot`` before), one call an expert layer, fed the experts'
    matrices AS STORED (no operation produces an expert-sized array: no
    cast, no relayout of 1.2 GB a layer), the paged kernel is there at GQA heads of
    64, and the expert layer's and the convolution's weights reach their
    operations under the names the benchmark's readers look for."""
    import jax

    from ray_tpu.models.lfm2_moe import lfm2_moe_decode_step
    from ray_tpu.serve.llm import decode

    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    cfg, params, pool, state, i32 = _lfm2_cell_shapes(one_chip)
    B = 64
    compiled = jax.jit(
        functools.partial(lfm2_moe_decode_step, cfg=cfg)
    ).lower(
        params, pool, pool, i32((B,)), i32((B,)), i32((B, 160)),
        state=state, slots=i32((B,)),
    ).compile(compiler_options=decode._compiler_options("tpu"))
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 8.0e9 < mem.argument_size_in_bytes < 8.6e9
    assert total < 11e9, total
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    # one grouped product (both matrices) in each of six expert layers, on
    # the stored leaves
    calls = _gmm_calls(entry)
    assert len(calls) == 6
    assert all(re.search(rf"%params__layers___\d___moe_gmm_w_{w}__", c)
               for c in calls for w in ("in", "out"))
    assert "ragged-dot" not in entry
    produced = [ln for ln in entry.splitlines()
                if re.search(r"= \w+\[64,(2048|1536),(3072|2048)\]", ln)
                and " parameter(" not in ln]
    assert not produced, produced[:2]
    assert entry.count("%paged_attention") >= 2
    for needle in ("moe_route_w", "moe_route_bias", "short_conv_w",
                   "short_conv_in", "short_conv_out"):
        assert re.search(rf"\(.*%params__layers___\d___{needle}__", entry), \
            needle
    assert "cross_program_prefetch_index" not in text


def test_laguna_decode_step_compiles_at_published_widths(
        one_chip, monkeypatch):
    """The laguna cell's 64-row decode step, as the executor compiles it,
    tables by group ``[4, 64, 1152]`` at the widest context bucket: both
    pools are updated in place (nothing pool-sized among its temporaries:
    8 layers' worth of slabs would be 4.3 GB), the two full layers call
    ``paged_attention`` and the six sliding ones ``paged_attention_window``
    at GQA groups of 6 and 8, the grouped product is fed the 32 held
    experts' matrices as stored, and the new leaves reach their operations
    under the names the benchmark's readers look for."""
    import sys

    import jax
    import jax.numpy as jnp

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import common
    from ray_tpu.models.laguna import laguna_init_state
    from ray_tpu.serve.llm import decode

    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    held = common.load_json(os.path.join(
        root, "benchmark/configs/laguna-xs.2-ep8-8l.json"))
    cfg = dataclasses.replace(common.model_config(held),
                              attention_backend="pallas")
    init = common.load_named("reference", "laguna").init_fn()
    on_chip = lambda s: _struct(s.shape, s.dtype, one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init(jax.random.PRNGKey(0), cfg)))
    state = jax.tree.map(on_chip, jax.eval_shape(
        lambda: laguna_init_state(cfg, 65)))
    pool = _struct((cfg.n_kv_layer, 32769, 16, cfg.n_kv_head * cfg.head_dim),
                   cfg.dtype, one_chip)
    i32 = functools.partial(_struct, dtype=jnp.int32, sharding=one_chip)
    B, groups = 64, len(cfg.kv_table_groups)
    assert (groups, cfg.n_kv_layer) == (4, 2)
    compiled = decode.DecodeFns("laguna", cfg, platform="tpu")._decode.lower(
        params, pool, pool, i32((B,)), i32((B,)), i32((groups, B, 1152)),
        sample=None, state=state, slots=i32((B,)),
    ).compile()
    mem = compiled.memory_analysis()
    assert 7.0e9 < mem.argument_size_in_bytes < 7.5e9  # 2.96 + 4.29 GB
    assert mem.alias_size_in_bytes >= 2 * pool.size * 2  # both pools
    assert mem.temp_size_in_bytes < 0.2e9, mem.temp_size_in_bytes
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    full = len(re.findall(r"%paged_attention[.\d]* = ", entry))
    sliding = len(re.findall(r"%paged_attention_window[.\d]* = ", entry))
    assert (full, sliding) == (2, 6), (full, sliding)
    calls = _gmm_calls(entry)
    assert len(calls) == 7  # one grouped product in each of 7 layers
    # both matrices as stored: the kernel copies its own tiles
    assert all(re.search(rf"%params__layers___\d___moe_gmm_w_{w}__", c)
               for c in calls for w in ("in", "out"))
    assert "ragged-dot" not in entry
    produced = [ln for ln in entry.splitlines()
                if re.search(r"= \w+\[32,(2048|512),(1024|2048)\]", ln)
                and " parameter(" not in ln
                and "S(1)" not in ln]  # staged into fast memory, no cast
    assert not produced, produced[:2]
    for needle in ("moe_route_w", "moe_shared_w_in", "moe_shared_w_out",
                   "attn_gate_w"):
        assert re.search(rf"\(.*%params__layers___\d___{needle}__", entry), \
            needle
    assert "cross_program_prefetch_index" not in text


@pytest.mark.parametrize("kind", ["decode", "prefill_chunk", "prefill"])
def test_evabyte_step_programs_compile_at_published_widths(
        one_chip, monkeypatch, kind):
    """The EvaByte cell's step programs as the executor compiles them, at
    the cell's own shapes: the 24-row decode step and the 4 x 2,048 prompt
    chunk over composed tables ``[2, B, 192]``, the fresh prefill over
    ``[2, B, 128]``. Both pools (9.13 GB together) are updated in place
    through the K/V write, the summaries' read-back and write and the
    kernel's read: nothing pool-sized is among the temporaries. The one
    scanned layer calls ``paged_attention`` at a GQA group of 1 over a
    lane-dense pool (rows of 4,096), and the chunk summaries are the ``eva_summarize`` kernel
    under its own name, which is how a trace finds their time inside a
    scanned stack."""
    import sys

    import jax
    import jax.numpy as jnp

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import common
    from ray_tpu.ops.paged_attention import pool_shape
    from ray_tpu.serve.llm import decode

    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    held = common.load_json(os.path.join(
        root, "benchmark/configs/evabyte-6.5b-8l.json"))
    traffic = common.load_json(os.path.join(
        root, "benchmark/traffic/bytes-chat-closed.json"))["engine"]
    cfg = dataclasses.replace(common.model_config(held),
                              attention_backend="pallas")
    init = common.load_named("reference", "evabyte").init_fn()
    on_chip = lambda s: _struct(s.shape, s.dtype, one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init(jax.random.PRNGKey(0), cfg)))
    pool = _struct(pool_shape(cfg.n_layer, traffic["num_blocks"], 16,
                              cfg.n_kv_head, cfg.head_dim), cfg.dtype,
                   one_chip)
    assert pool.shape == (8, 4353, 16, 4096)  # a token's 32 heads a row
    i32 = functools.partial(_struct, dtype=jnp.int32, sharding=one_chip)
    fns = decode.DecodeFns("evabyte", cfg, platform="tpu")
    if kind == "decode":
        lowered = fns._decode.lower(
            params, pool, pool, i32((24,)), i32((24,)), i32((2, 24, 192)),
            sample=None)
    else:
        nb, more = (192, {"start": i32((4,))}) if kind == "prefill_chunk" \
            else (128, {})
        lowered = fns._prefill.lower(
            params, pool, pool, i32((4, 2048)), i32((4,)), i32((2, 4, nb)),
            sample=None, **more)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    pool_bytes = math.prod(pool.shape) * 2
    assert abs(2 * pool_bytes - 9.13e9) < 0.01e9
    # 3.26 GB of weights and the two pools
    assert 12.3e9 < mem.argument_size_in_bytes < 12.5e9
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < (0.3e9 if kind == "decode" else 2.0e9), \
        mem.temp_size_in_bytes
    text = compiled.as_text()
    assert len(re.findall(r"%paged_attention[.\d]* = ", text)) == 1
    assert len(re.findall(r"%eva_summarize[.\d]* = ", text)) == 1
    assert "cross_program_prefetch_index" not in text


def _one_plane(cfg, layers, num_blocks, sharding):
    """``[pool, None]``: a latent family's pool as the cache manager
    builds it since ISSUE 53, ONE plane whose rows hold the parts of
    ``kv_planes`` side by side, and no second array."""
    return [_struct((layers, num_blocks, 16,
                     sum(at for _, _, at in cfg.kv_planes)), cfg.dtype,
                    sharding), None]


def _holds_one_pool(text, shape):
    """The compiled program takes ONE pool (``%cache_k``: no ``%cache_v``),
    resting in the order written and donated (it is in the program's
    ``input_output_alias``: updated where it stands), and no array of any
    other shape anywhere in the program spans the pool's blocks."""
    entry = text[text.index("ENTRY"):]
    pools = re.findall(
        r"%cache_[kv][.\d]* = (\w+\[[\d,]+\])\{([\d,]+)[^\n]*? "
        r"parameter\((\d+)\)", entry)
    pool = "bf16[" + ",".join(map(str, shape)) + "]"
    assert [(p, layout) for p, layout, _ in pools] == [(pool, "3,2,1,0")], \
        pools
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    assert int(pools[0][2]) in set(
        map(int, re.findall(r"\}: \((\d+),", alias))), alias
    spans = set(re.findall(rf"\w+\[(?:\d+,)*{shape[1]}(?:,\d+)*\]", text))
    # (a pool of ONE layer is also seen without its layer axis: a bitcast)
    assert spans <= {pool, pool.replace("[1,", "[")} and pool in spans, spans


@pytest.mark.parametrize("kind", ["decode", "prefill_chunk", "prefill",
                                  "prefill_packed"])
def test_pangu_step_programs_compile_at_published_widths(
        one_chip, monkeypatch, kind):
    """The openPangu cell's step programs as the executor compiles them,
    at the cell's own shapes: the 128-row decode step and the 1 x 2,048
    chunk over a table ``[B, 768]`` (the 12,288-token bucket), the fresh
    prefill over ``[1, 128]``; ``prefill_packed`` (ISSUE 47: what the
    engine launches now): the chunk program over the packed ladder's top
    rung, 16 rows of one 128-token q tile under ``[16, 768]``, whose
    temporaries are no larger than the one-row chunk's. The pool is ONE
    PLANE, ``[5, 40961, 16, 640]`` (4.19 GB; ISSUE 53: a page is one copy),
    the program carries no second pool-sized array, the one is in its
    ``input_output_alias`` and nothing pool-sized is among its
    temporaries; each of the 5 layers of a DECODE step calls
    ``paged_attention_latent`` once, each of a PREFILL step (ISSUE 51: the
    expanded form) ``flash_fwd`` once over its own keys and once inside
    the loop over its resident prefix; and nothing in the program has the
    context's length as a dimension: no K or V of a resident context by
    head (12,288 x 128 x 320 x 2 B = 1 GB a layer: ONE block of 2,048 keys
    is expanded at a time), no gathered context, no score matrix. The expert leaves reach their
    operations under the names the benchmark's readers look for."""
    import sys

    import jax
    import jax.numpy as jnp

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import common
    from ray_tpu.serve.llm import decode

    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    held = common.load_json(os.path.join(
        root, "benchmark/configs/openpangu-ultra-moe-ep32-5l.json"))
    engine = common.load_json(os.path.join(
        root, "benchmark/traffic/reason-closed.json"))["engine"]
    cfg = dataclasses.replace(common.model_config(held),
                              attention_backend="pallas")
    fam = decode.get_family("pangu_ultra_moe")
    init = common.load_named("reference", "pangu_ultra_moe").init_fn()
    on_chip = lambda s: _struct(s.shape, s.dtype, one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init(jax.random.PRNGKey(0), cfg)))
    B = {"decode": engine["max_batch_size"], "prefill_packed": 16}.get(
        kind, 1)
    state = jax.tree.map(on_chip, jax.eval_shape(
        lambda: fam.init_state(cfg, engine["max_batch_size"] + 1)))
    planes = _one_plane(cfg, cfg.n_layer, engine["num_blocks"], one_chip)
    assert planes[0].shape == (5, 40961, 16, 640) and planes[1] is None
    i32 = functools.partial(_struct, dtype=jnp.int32, sharding=one_chip)
    ctx = engine["length_buckets"][-1]
    assert ctx == 12288 and engine["prefill_chunk_tokens"] == 2048
    fns = decode.DecodeFns("pangu_ultra_moe", cfg, platform="tpu")
    more = {"state": state, "slots": i32((B,))}
    if kind == "decode":
        assert B == 128
        lowered = fns._decode.lower(
            params, *planes, i32((B,)), i32((B,)), i32((B, ctx // 16)),
            sample=None, **more)
    else:
        nb = 128 if kind == "prefill" else ctx // 16
        if kind != "prefill":
            more["start"] = i32((B,))
        lowered = fns._prefill.lower(
            params, *planes, i32((B, 2048 // B)), i32((B,)), i32((B, nb)),
            sample=None, **more)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    pool_bytes = math.prod(planes[0].shape) * 2
    assert abs(pool_bytes - 4.194e9) < 0.001e9
    # 6.82 GB of weights and the one plane
    assert 10.9e9 < mem.argument_size_in_bytes < 11.1e9
    assert mem.alias_size_in_bytes >= pool_bytes
    _holds_one_pool(compiled.as_text(), planes[0].shape)
    # ISSUE 51: a packed step's temporaries in the expanded form (K and V
    # of the step's own 2,048 tokens and of ONE prefix block by head, a
    # float32 output and log-sum-exp carried from call to call) are no
    # larger than the absorbed form's 1,192,685,568 B (the 335 MB of
    # absorbed queries, their padded copy and the 268 MB result)
    assert mem.temp_size_in_bytes < {
        "decode": 0.1e9, "prefill_packed": 1.1927e9}.get(kind, 1.6e9), \
        mem.temp_size_in_bytes
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    # a decode step attends in the absorbed form, one latent call a layer;
    # a prefill step in the expanded one: a layer's own keys through ONE
    # ``flash_fwd`` call, and one more inside the loop over its prefix
    latent = len(re.findall(r"%paged_attention_latent[.\d]* = ", text))
    flash = len(re.findall(r"%flash_fwd[.\d]* = ", text))
    # (a FRESH step has nothing resident: its loop of no trips is gone)
    assert (latent, flash) == {"decode": (5, 0), "prefill": (0, 5)}.get(
        kind, (0, 10))
    assert len(re.findall(r"%flash_fwd[.\d]* = ", entry)) == min(flash, 5)
    assert not re.findall(r"%paged_attention[.\d]* = ", entry)
    # the four expert layers' grouped product, both matrices as stored
    calls = _gmm_calls(entry)
    assert len(calls) == 4 and "ragged-dot" not in entry
    assert all(re.search(rf"%params__layers___\d___moe_gmm_w_{w}__", c)
               for c in calls for w in ("in", "out"))
    # no array anywhere has the context's 12,288 positions as a dimension
    assert not re.search(r"[\[,]12288[\],]", text)
    if kind == "decode":
        for needle in ("moe_route_w", "moe_gmm_w_in", "moe_shared_w_in",
                       "mla_w_uk", "mla_w_uv"):
            assert re.search(
                rf"\(.*%params__layers___\d___{needle}__", entry), needle
    assert "cross_program_prefetch_index" not in text


# the latent kernel's calls in the three cells that hold it: 128 heads
# (openPangu: 128-row decode over [B, 768], a 1 x 2,048 chunk), 64
# heads (LongCat-Flash: 96-row decode and a 1 x 1,024 chunk over [B, 384])
# and 32 (Ling-3.0-flash: one layer, 64 rows a call over [B, 2560]);
# ISSUE 47: the chunk as the packed ladder's top rung, rows of one
# 128-token q tile (16 x 128 and 8 x 128: the same tiles, a row each)
LATENT_CALLS = {
    "ling-decode": (32, 64, 2560, 73729, 1, 1),
    "pangu-decode": (128, 128, 768, 40961, 1, 5),
    "pangu-chunk": (128, 1, 768, 40961, 2048, 5),
    "pangu-packed": (128, 16, 768, 40961, 128, 5),
    "longcat-decode": (64, 96, 384, 16385, 1, 8),
    "longcat-chunk": (64, 1, 384, 16385, 1024, 8),
    "longcat-packed": (64, 8, 384, 16385, 128, 8),
}


@pytest.mark.parametrize("call", sorted(LATENT_CALLS))
def test_latent_attention_compiles(one_chip, call):
    """``paged_attention_latent`` over a pool in one plane ``[layers,
    blocks, 16, 640]`` (ISSUE 53: a page is ONE copy) at the three head
    counts the cells hold: a tile's rows are queries x heads (decode: 128,
    64 or 32 rows over blocks of 1,024 tokens; a chunk: 8 queries a tile,
    1,024 or 512 rows over blocks of 256), under the VMEM ``_latent_block``
    counts."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import (
        _LATENT_Q_BLOCK, _latent_block, paged_latent_attention_pallas,
    )

    H, B, NB, blocks, S, layers = LATENT_CALLS[call]
    S_ = functools.partial(_struct, sharding=one_chip)
    bf16 = jnp.bfloat16
    fn = functools.partial(paged_latent_attention_pallas, latent_dim=512,
                           scale=192 ** -0.5, layer=layers - 1,
                           interpret=False)
    compiled = jax.jit(fn).lower(
        S_((B, S, H, 576), bf16), S_((layers, blocks, 16, 640), bf16),
        S_((B, NB), jnp.int32), S_((B, S), jnp.int32)).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    rows = min(S, _LATENT_Q_BLOCK) * H
    pages, vmem = _latent_block(16, 640, 512, rows, NB, bf16, bf16)
    # a decode tile (the heads of one query) takes blocks of 1,024 tokens,
    # a chunk's tile of 8 queries x the heads keeps 256
    assert pages == (64 if S == 1 else 16), pages
    assert vmem < (8 << 20 if S == 1 else 16 << 20), vmem


@pytest.mark.parametrize("kind", ["decode", "prefill_chunk", "prefill",
                                  "prefill_packed", "reference"])
def test_longcat_step_programs_compile_at_published_widths(
        one_chip, monkeypatch, kind):
    """The LongCat-Flash cell's step programs as the executor compiles
    them, at the cell's own shapes: the 96-row decode step and the 1 x
    1,024 chunk over a table ``[B, 384]`` (the 6,144-token bucket), the
    fresh prefill over ``[1, 64]``; ``prefill_packed`` (ISSUE 47: what the
    engine launches now): the chunk program over the packed ladder's top
    rung, 8 rows of one 128-token q tile under ``[8, 384]``, whose
    temporaries are no larger than the one-row chunk's. The pool is ONE
    PLANE over EIGHT latent sub-layers for four layers, ``[8, 16385, 16,
    640]`` (2.68 GB; ISSUE 53), the only pool-sized array the program
    carries: it is in the program's ``input_output_alias`` and nothing
    pool-sized is among its temporaries; each of the 4 layers attends TWICE (a decode step
    through ``paged_attention_latent``, a prefill step, ISSUE 51, through
    ``flash_fwd``: its own keys, and the loop over its prefix) and has its
    two grouped products ONCE; nothing has the context's
    length as a dimension. The leaves reach their operations under the
    names the benchmark's readers look for. ``reference``: the reference
    check's float32 pass over 16 x 2,112 padded tokens fits beside the
    weights and the pool."""
    import sys

    import jax
    import jax.numpy as jnp

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import common
    from ray_tpu.serve.llm import decode

    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    held = common.load_json(os.path.join(
        root, "benchmark/configs/longcat-flash-omni-ep32-4l.json"))
    engine = common.load_json(os.path.join(
        root, "benchmark/traffic/think-closed.json"))["engine"]
    cfg = dataclasses.replace(common.model_config(held),
                              attention_backend="pallas")
    fam = decode.get_family("longcat_flash")
    ref = common.load_named("reference", "longcat_flash")
    on_chip = lambda s: _struct(s.shape, s.dtype, one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: ref.init_fn()(jax.random.PRNGKey(0), cfg)))
    weight_bytes = sum(math.prod(a.shape) * a.dtype.itemsize
                       for a in jax.tree.leaves(params))
    assert abs(weight_bytes - 10.345e9) < 0.01e9, weight_bytes
    i32 = functools.partial(_struct, dtype=jnp.int32, sharding=one_chip)
    planes = _one_plane(cfg, cfg.n_kv_layer, engine["num_blocks"], one_chip)
    assert planes[0].shape == (8, 16385, 16, 640) and planes[1] is None
    pool_bytes = math.prod(planes[0].shape) * 2
    assert abs(pool_bytes - 2.684e9) < 0.001e9
    if kind == "reference":
        chk = held["reference_check"]
        compiled = jax.jit(
            lambda pr, t, pos: ref.logits_at(pr, t, pos, cfg)).lower(
            params, i32((chk["requests"], chk["pad_to"])),
            i32((chk["requests"], chk["new_tokens"]))).compile()
        mem = compiled.memory_analysis()
        # beside weights 10.35 GB and the pool 2.68 GB of the chip's 16.9
        assert mem.temp_size_in_bytes < 3.0e9, mem.temp_size_in_bytes
        return
    B = {"decode": engine["max_batch_size"], "prefill_packed": 8}.get(
        kind, 1)
    state = jax.tree.map(on_chip, jax.eval_shape(
        lambda: fam.init_state(cfg, engine["max_batch_size"] + 1)))
    ctx = engine["length_buckets"][-1]
    chunk = engine["prefill_chunk_tokens"]
    assert (ctx, chunk, engine["max_prefill_batch"]) == (6144, 1024, 1)
    fns = decode.DecodeFns("longcat_flash", cfg, platform="tpu")
    more = {"state": state, "slots": i32((B,))}
    if kind == "decode":
        assert B == 96
        lowered = fns._decode.lower(
            params, *planes, i32((B,)), i32((B,)), i32((B, ctx // 16)),
            sample=None, **more)
    else:
        nb = chunk // 16 if kind == "prefill" else ctx // 16
        if kind != "prefill":
            more["start"] = i32((B,))
        lowered = fns._prefill.lower(
            params, *planes, i32((B, chunk // B)), i32((B,)), i32((B, nb)),
            sample=None, **more)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    # 10.35 GB of weights and the one plane
    assert 13.0e9 < mem.argument_size_in_bytes < 13.1e9
    assert mem.alias_size_in_bytes >= pool_bytes
    _holds_one_pool(compiled.as_text(), planes[0].shape)
    # ISSUE 51: a packed step's temporaries in the expanded form are the
    # absorbed form's 0.81 GB (805,874,688 B: the peak stands in the
    # layer's feed-forward half, which the compiler schedules within a
    # hundredth of that whatever attends)
    assert mem.temp_size_in_bytes < {
        "decode": 0.15e9, "prefill_packed": 0.8149e9}.get(kind, 1.6e9), \
        mem.temp_size_in_bytes
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    latent = len(re.findall(r"%paged_attention_latent[.\d]* = ", text))
    flash = len(re.findall(r"%flash_fwd[.\d]* = ", text))
    assert (latent, flash) == {"decode": (8, 0), "prefill": (0, 8)}.get(
        kind, (0, 16))
    assert len(re.findall(r"%flash_fwd[.\d]* = ", entry)) == min(flash, 8)
    assert not re.findall(r"%paged_attention[.\d]* = ", entry)
    assert len(_gmm_calls(entry)) == 4  # one a double layer, every kind
    assert "ragged-dot" not in entry
    # no array anywhere has the context's 6,144 positions as a dimension:
    # d_model is 6,144 too, so the test is on [.., 64 heads, 6144] scores
    # and on K or V by head
    assert not re.search(r"\[\d+,64,6144[\],]", text)
    if kind == "decode":
        for needle in ("moe_route_w", "moe_route_bias", "moe_gmm_w_in"):
            assert re.search(
                rf"\(.*%params__layers___\d___{needle}__", entry), needle
        for needle in ("mla_w_uk", "mla_w_uv", "dense_ffn_w_in",
                       "dense_ffn_w_out"):
            for half in (0, 1):
                assert re.search(
                    rf"%params__layers___\d___sub___{half}___{needle}__",
                    entry), (needle, half)
    assert "cross_program_prefetch_index" not in text


@pytest.mark.parametrize("kind", ["decode", "prefill_chunk", "prefill"])
def test_smallthinker_step_programs_compile_at_published_widths(
        one_chip, monkeypatch, kind):
    """The smallthinker cell's step programs as the executor compiles them,
    at the cell's own shapes: the 48-row decode step and the 4 x 2,048
    chunk over tables by group ``[4, B, 1024]`` (the 16,384-token bucket),
    the fresh prefill over ``[4, 4, 128]``. The pool is lane-dense ``[2,
    65537, 16, 512]`` (4.29 GB, K and V together): both arrays are in the
    program's ``input_output_alias`` and nothing pool-sized is among its
    temporaries; the two full layers call ``paged_attention`` and the six
    sliding ones ``paged_attention_window`` at a group of SEVEN; every
    layer has its two grouped products over all 64 experts' matrices as
    stored; and the leaves reach their operations under the names the
    benchmark's readers look for."""
    import sys

    import jax
    import jax.numpy as jnp

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import common
    from ray_tpu.ops.paged_attention import pool_shape
    from ray_tpu.serve.llm import decode

    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    held = common.load_json(os.path.join(
        root, "benchmark/configs/smallthinker-21b-a3b-8l.json"))
    engine = common.load_json(os.path.join(
        root, "benchmark/traffic/doc-chat-closed.json"))["engine"]
    cfg = dataclasses.replace(common.model_config(held),
                              attention_backend="pallas")
    fam = decode.get_family("smallthinker")
    init = common.load_named("reference", "smallthinker").init_fn()
    on_chip = lambda s: _struct(s.shape, s.dtype, one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init(jax.random.PRNGKey(0), cfg)))
    B = engine["max_batch_size"] if kind == "decode" else 4
    state = jax.tree.map(on_chip, jax.eval_shape(
        lambda: fam.init_state(cfg, engine["max_batch_size"] + 1)))
    pool = _struct(pool_shape(cfg.n_kv_layer, engine["num_blocks"], 16,
                              cfg.n_kv_head, cfg.head_dim),
                   cfg.dtype, one_chip)
    assert pool.shape == (2, 65537, 16, 512)
    i32 = functools.partial(_struct, dtype=jnp.int32, sharding=one_chip)
    groups = len(cfg.kv_table_groups)
    ctx = engine["length_buckets"][-1]
    assert (groups, ctx, B) in ((4, 16384, 48), (4, 16384, 4))
    fns = decode.DecodeFns("smallthinker", cfg, platform="tpu")
    more = {"state": state, "slots": i32((B,))}
    if kind == "decode":
        lowered = fns._decode.lower(
            params, pool, pool, i32((B,)), i32((B,)),
            i32((groups, B, ctx // 16)), sample=None, **more)
    else:
        nb = ctx // 16 if kind == "prefill_chunk" else 128
        if kind == "prefill_chunk":
            more["start"] = i32((B,))
        lowered = fns._prefill.lower(
            params, pool, pool, i32((B, 2048)), i32((B,)),
            i32((groups, B, nb)), sample=None, **more)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    pool_bytes = 2 * math.prod(pool.shape) * 2
    assert abs(pool_bytes - 4.295e9) < 0.001e9
    # 7.93 GB of weights and the pool
    assert 12.1e9 < mem.argument_size_in_bytes < 12.4e9
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < (0.2e9 if kind == "decode" else 3.0e9), \
        mem.temp_size_in_bytes
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    full = len(re.findall(r"%paged_attention[.\d]* = ", entry))
    sliding = len(re.findall(r"%paged_attention_window[.\d]* = ", entry))
    assert (full, sliding) == (2, 6), (full, sliding)
    calls = _gmm_calls(entry)
    assert len(calls) == 8  # one grouped product in each of 8 layers
    assert "ragged-dot" not in entry
    if kind == "decode":
        for needle in ("moe_route_w", "moe_gmm_w_in"):
            assert re.search(
                rf"\(.*%params__layers___\d___{needle}__", entry), needle
    assert "cross_program_prefetch_index" not in text


def _body(text):
    """A compiled program's computations, less what names the CALLER: the
    module's name line, the tables of source files and stack frames, and
    each instruction's ``metadata`` (the call site's line numbers)."""
    text = text[text.index("\n", text.index("HloModule")):]
    if "\nFileNames" in text:
        text = text[:text.index("\nFileNames")] + text[
            text.index("\n\n", text.index("\nStackFrames")):]
    return re.sub(r", metadata=\{[^}]*\}", "", text)


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_other_families_programs_are_what_their_functions_compile_to(
        one_chip, family):
    """ISSUE 26's guard: a third family with a state argument came to
    ``decode.py`` and the executor, and the step programs of the two that
    have none keep their compiled text. What ``DecodeFns``' own wrapper
    compiles for the chip, called as the executor calls it, is to the
    letter what the family's function compiles to alone."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm import decode

    # the XLA attention backend: a Pallas call carries its kernel's own
    # source locations inside its serialized body, which name the caller
    fam = decode.get_family(family)
    cfg = dataclasses.replace(fam.default_config(), attention_backend="xla")
    init, step = fam.init, fam.decode_step
    on_chip = lambda s: _struct(s.shape, s.dtype, one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init(jax.random.PRNGKey(0), cfg)))
    n_kv = getattr(cfg, "n_kv_head", cfg.n_head)
    pool = _struct((cfg.n_layer, 33, 16, n_kv, cfg.head_dim), cfg.dtype,
                   one_chip)
    i32 = functools.partial(_struct, dtype=jnp.int32, sharding=one_chip)
    args = (params, pool, pool, i32((4,)), i32((4,)), i32((4, 8)))
    options = decode._compiler_options("tpu")
    fns = decode.DecodeFns(family, cfg, platform="tpu")
    through = fns._decode.lower(*args, sample=None).compile().as_text()
    alone = jax.jit(
        functools.partial(step, cfg=cfg), donate_argnums=(1, 2)  # the pools
    ).lower(*args).compile(compiler_options=options).as_text()

    assert _body(through) == _body(alone)
    assert len(_body(alone)) > 10000 and "fusion" in _body(alone)
    # and no entry parameter is a state array or a slot list
    assert re.search(r"%params__[\w.]+ = \S+ parameter\(", through)
    assert not re.search(r"%(state|slots)[\w.]* = \S+ parameter\(", through)


# What the cells' step programs and the kernel compile to: sha256[:16] of the
# text under jax 0.9.0's compiler for a described v5e: of a compiled program
# less what names the caller and less the Mosaic kernel's serialized body (it
# carries source lines), and of the kernel's own jaxpr less its source
# location. ISSUE 43 moved every program that holds the K/V kernel, and the
# texts below are ITS tree's (taken on PR 43): Mistral's, laguna's and
# EvaByte's through the stored layout (lane-dense where it was by heads: the
# kernel's pages, the scatter's rows) and through the block a few-row tile
# aims at; GPT-2's and lfm2's decode through the block alone. What ISSUE 43
# did NOT touch keeps the text its own PR recorded, and
# says so here: the trainer's flash kernels
# (``test_flash_kernel_names_reach_the_lowered_text``). ISSUE 46 gave the
# latent call a kernel body of its own and took the ``latent`` branches out
# of ``_paged_attention_kernel``: ``pangu-decode`` is re-recorded and
# ``longcat-decode`` added on ITS tree; every by-head text, the kernel's
# jaxprs among them, is the one recorded before it. ISSUE 47 (a latent
# family's prefill step is packed) changed no program's text and ADDS the
# two it launches in place of the one-row chunk: ``pangu-packed`` and
# ``longcat-packed``, the ladders' top rungs. ISSUE 53 (the latent pool one
# plane) re-recorded the four latent programs on ITS tree and no other.
# ISSUE 50 (the step programs name their parts: ``jax.named_scope``, which
# is metadata and stripped here) left the ten programs of unrolled stacks to
# the letter, and moved the four of SCANNED stacks in their instructions'
# NAMES alone: with scopes inside a scan's body the TPU compiler numbers six
# ``%reshape.N`` otherwise on its way (``.276`` -> ``.282``) and calls one
# merged reshape of Mistral's prefill ``%reshape.N`` where it was
# ``%reshape_reshape``. Those four are re-recorded on ITS tree, and
# ``PARENTS_SHAPE`` holds the PARENT's (PR 49's) text of each with every name
# replaced by its ordinal: the operations, types, layouts, operands' wiring
# and ``backend_config`` of the programs are the parent's.
PARENTS_TEXT = {
    "mistral-decode": "6a2507986461a5dd",
    "mistral-prefill": "8d70a529aa0cdbc8",
    "kernel-decode": "0a60dc1dcd26269e",
    "kernel-prefill": "3eaf0087f777f8e6",
    "kernel-window": "fb1235e986671dce",
    "gpt2-decode": "d1bceaf17f0f6ad3",
    "evabyte-decode": "f2fb1071c8d44c5f",
    # re-recorded by ISSUE 49 (the grouped expert product is the kernel
    # ``moe_gmm_few_rows``, one call an expert layer, in every step program
    # of the five expert families; the seven others above are as they were)
    # and, with the five expert programs below, by ISSUE 57 (the kernel's
    # operands changed: its list holds a window where it held a tile, its
    # output stays in HBM and is copied by the kernel; the seven above and
    # every operation outside the ``moe_gmm`` scope are as they were)
    "laguna-decode": "ef51a924a6c96adc",
    "lfm2-decode": "dadfcdb3ee1843b2",
    # re-recorded by ISSUE 53 (a latent family's pool is ONE plane, a page
    # one copy: the four programs below carry one pool-sized array and the
    # latent kernel one HBM operand; the ten others here are to the letter
    # the texts PR 52's tree compiled to, which is what this test asserts)
    # plane [5, 40961, 16, 640], table [128, 768]
    "pangu-decode": "1370dc4e4d8afe2f",
    # plane [8, 16385, 16, 640], table [96, 384]
    "longcat-decode": "4e37176c9684407e",
    # the chunk program over [16, 128] under [16, 768] and over [8, 128]
    # under [8, 384], the cells' planes (ISSUE 47; ISSUE 51: a prefill step
    # attends in the expanded form, ``flash_fwd`` over its own keys, a loop
    # over its resident prefix, whose blocks are read from the one plane)
    "pangu-packed": "fa6b4eaa6ee03207",
    "longcat-packed": "5ba8792445ebf8c1",
    # pool [2, 65537, 16, 512], tables [4, 48, 1024]
    "smallthinker-decode": "d50a8e6b47762f6a",
}
# of PR 49's tree, names as ordinals (``_program_shape_sha``)
PARENTS_SHAPE = {
    "mistral-decode": "a06fa65e521d71c1",
    "mistral-prefill": "eec10910038721ed",
    "gpt2-decode": "a7e61752764f03ca",
    "evabyte-decode": "ac7ceb0dac99db4f",
}
PARENTS_JAX = "0.9.0"


def _sha(text):
    import hashlib

    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _program_text_sha(text):
    return _sha(re.sub(r'"body":"[^"]*"', '"body":""', _body(text)))


def _program_shape_sha(text):
    """``_program_text_sha`` with every ``%name`` (instructions and
    computations) replaced by the ordinal of its first appearance: what a
    program IS, whatever the compiler's passes numbered on the way."""
    seen = {}
    return _sha(re.sub(
        r"%[A-Za-z_][\w\-]*(?:\.[\w\-]+)*",
        lambda m: seen.setdefault(m.group(0), f"%{len(seen)}"),
        re.sub(r'"body":"[^"]*"', '"body":""', _body(text))))


def _cell_program(which, kind, S_):
    """``(jitted step, args, kwargs)``: a cell's decode program (Mistral's
    prefill too) as ``DecodeFns`` builds it for the chip, over the cell's
    own shapes described on one chip."""
    import sys

    import jax
    import jax.numpy as jnp

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import common
    from ray_tpu.ops.paged_attention import pool_shape
    from ray_tpu.serve.llm import decode

    i32 = functools.partial(S_, dtype=jnp.int32)
    config = {"mistral": "mistral-7b-v0.3-6l", "gpt2": "gpt2-small",
              "lfm2": "lfm2-24b-a2b-8l",
              "laguna": "laguna-xs.2-ep8-8l",
              "evabyte": "evabyte-6.5b-8l",
              "pangu": "openpangu-ultra-moe-ep32-5l",
              "longcat": "longcat-flash-omni-ep32-4l",
              "smallthinker": "smallthinker-21b-a3b-8l"}[which]
    held = common.load_json(
        os.path.join(root, f"benchmark/configs/{config}.json"))
    cfg = dataclasses.replace(
        common.model_config(held), attention_backend="pallas")
    fam = decode.get_family(held["family"])
    on_chip = lambda s: S_(s.shape, s.dtype)
    more = {}
    if which in ("mistral", "gpt2"):
        params = jax.tree.map(
            lambda s, axis: S_(s.shape, cfg.dtype if axis >= 0 else s.dtype),
            jax.eval_shape(lambda: fam.init(jax.random.PRNGKey(0), cfg)),
            fam.quant_axes(cfg))
        num_blocks, tables = 4097, (64, 160 if which == "mistral" else 64)
    else:
        init = common.load_named("reference", held["family"]).init_fn()
        params = jax.tree.map(on_chip, jax.eval_shape(
            lambda: init(jax.random.PRNGKey(0), cfg)))
        num_blocks, tables = {"laguna": (32769, (4, 64, 1152)),
                              "lfm2": (4097, (64, 160)),
                              "evabyte": (4353, (2, 24, 192)),
                              "pangu": (40961, (128, 768)),
                              "longcat": (16385, (96, 384)),
                              "smallthinker": (65537, (4, 48, 1024))}[which]
        if which != "evabyte":  # it keeps no state beside the pool
            rows = tables[-2]
            more = {"state": jax.tree.map(on_chip, jax.eval_shape(
                lambda: fam.init_state(cfg, rows + 1))),
                "slots": i32((rows,))}
    if which in ("pangu", "longcat"):
        # a pool in ONE plane over the cache's layers (longcat: two latent
        # sub-layers a layer), and no second pool
        pools = [S_((getattr(cfg, "n_kv_layer", cfg.n_layer), num_blocks, 16,
                     sum(at for _, _, at in cfg.kv_planes)), cfg.dtype), None]
    else:
        pool = S_(pool_shape(
            getattr(cfg, "n_kv_layer", cfg.n_layer), num_blocks, 16,
            getattr(cfg, "n_kv_head", None) or cfg.n_head, cfg.head_dim),
            cfg.dtype)
        assert len(pool.shape) == 4  # one stored layout
        pools = [pool, pool]
    fns = decode.DecodeFns(held["family"], cfg, platform="tpu")
    if kind == "decode":
        rows = tables[-2]
        return fns._decode, (
            params, *pools, i32((rows,)), i32((rows,)), i32(tables)), {
                "sample": None, **more}
    if kind == "packed":
        # the chunk program over the packed ladder's top rung: rows of one
        # 128-token q tile under the widest context's table
        rows = {"pangu": 16, "longcat": 8}[which]
        return fns._prefill, (
            params, *pools, i32((rows, 128)), i32((rows,)),
            i32((rows, tables[-1]))), {
                "sample": None, "start": i32((rows,)),
                "state": more["state"], "slots": i32((rows,))}
    return fns._prefill, (
        params, *pools, i32((4, 2048)), i32((4,)), i32((4, 128))), {
            "sample": None}


@pytest.mark.parametrize("case", sorted(PARENTS_TEXT))
def test_step_programs_compile_to_the_recorded_text(one_chip, monkeypatch,
                                                    case):
    """A PR that means to leave a cell's programs alone can see that it
    did: the cells' decode programs (and Mistral's prefill) compile to the
    text recorded above, and the kernel's jaxpr at Mistral's and laguna's
    shapes (decode, prefill, windowed decode) is the recorded one. A PR
    that moves the kernel (ISSUE 43 did) records the new texts and says
    which it left. A golden text holds for one compiler: another jax
    skips."""
    import jax
    import jax.numpy as jnp

    if jax.__version__ != PARENTS_JAX:
        pytest.skip(f"the texts were taken under jax {PARENTS_JAX}")
    from ray_tpu.ops.paged_attention import (
        paged_attention_pallas, paged_prefill_attention_pallas, pool_shape,
    )

    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    S_ = functools.partial(_struct, sharding=one_chip)
    i32 = functools.partial(S_, dtype=jnp.int32)
    which, kind = case.split("-")
    if which == "kernel":
        pool = S_(pool_shape(6, 4097, 16, 8, 128), jnp.bfloat16)
        assert pool.shape == (6, 4097, 16, 1024)
        q = lambda *shape: S_(shape, jnp.bfloat16)
        fn, args = {
            "decode": (paged_attention_pallas,
                       (q(64, 32, 128), pool, pool, i32((64, 160)),
                        i32((64,)))),
            "prefill": (paged_prefill_attention_pallas,
                        (q(4, 2048, 32, 128), pool, pool, i32((4, 128)),
                         i32((4, 2048)))),
            "window": (functools.partial(paged_prefill_attention_pallas,
                                         window=512),
                       (q(64, 1, 48, 128), pool, pool, i32((64, 1152)),
                        i32((64, 1)))),
        }[kind]
        jaxpr = str(jax.make_jaxpr(functools.partial(
            fn, interpret=False, layer=1))(*args))
        assert "pallas_call" in jaxpr
        assert _sha(re.sub(r" at [^\s:]+:\d+", "", jaxpr)) \
            == PARENTS_TEXT[case]
        return
    jitted, args, kwargs = _cell_program(which, kind, S_)
    lowered = jitted.lower(*args, **kwargs)
    text = lowered.compile().as_text()
    got = _program_text_sha(text)
    assert got == PARENTS_TEXT[case], got
    if case in PARENTS_SHAPE:
        assert _program_shape_sha(text) == PARENTS_SHAPE[case]


def _kernel_products(jaxpr, named="paged_attention"):
    """The count of ``dot_general`` in the body of every ``pallas_call`` of
    a traced program whose name holds ``named`` (the paged kernels; the
    expert layer's ``moe_gmm_few_rows`` is none of them), one entry a call
    (a scanned stack's one layer once)."""
    calls = []

    def walk(j, inside):
        n = 0
        for eqn in j.eqns:
            n += inside and eqn.primitive.name == "dot_general"
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (list, tuple))
                            else [value]):
                    sub = getattr(sub, "jaxpr", sub)
                    if not hasattr(sub, "eqns"):
                        continue
                    if eqn.primitive.name == "pallas_call":
                        if named in eqn.params["name"]:
                            calls.append(walk(sub, True))
                    else:
                        n += walk(sub, inside)
        return n

    walk(jaxpr.jaxpr, False)
    return calls


# ONE path: a step program's paged kernel calls hold a score and a value
# product for each K/V head of the lane-dense tile, whatever the head's size
# (read off the traced program; a scanned stack's one layer once). ISSUE 43
# tried ONE product an operand for all heads: over the same tile it was 2-11%
# slower at heads of 128 and 14-18% faster at heads of 64, kernel alone, and
# end to end it did not clear the rule set for a second path (PERF.md PR 43).
DECODE_PRODUCTS = {"mistral": 8, "evabyte": 32, "laguna": 8, "gpt2": 12,
                   "lfm2": 8}


@pytest.mark.parametrize("which", sorted(DECODE_PRODUCTS))
def test_decode_programs_hold_a_product_a_head(one_chip, monkeypatch, which):
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    jitted, args, kwargs = _cell_program(
        which, "decode", functools.partial(_struct, sharding=one_chip))
    calls = _kernel_products(jitted.trace(*args, **kwargs).jaxpr)
    paged = [n for n in calls if n]
    assert paged, calls
    assert set(paged) == {2 * DECODE_PRODUCTS[which]}, calls


@pytest.mark.parametrize(
    "cell", ["gpt2-serve-chat-saturated", "lfm2moe-chat-saturated"])
def test_warmup_trace_time_walks_a_cells_warm_up(topo, monkeypatch, cell):
    """``warmup_trace_time.py`` (PR 28: what found the 13 s a process lost
    in its kernel traces) drives the benchmark's own warm-up of a serving
    cell with every program traced and lowered for the described chip and
    none compiled or run: at the rehearsal sizes it reaches every shape
    the warm-up reaches, a family with state beside the pool included,
    and leaves the process's jitted steps as they were."""
    import sys

    from ray_tpu.serve.llm import decode

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv("BENCHMARK_REHEARSAL", "1")
    monkeypatch.chdir(root)
    monkeypatch.syspath_prepend(root)
    import warmup_trace_time

    jit_named, jit_cache = decode._jit_named, dict(decode._jit_cache)
    m = warmup_trace_time.measure(cell, device=topo.devices[0])
    assert decode._jit_named is jit_named and decode._jit_cache == jit_cache
    assert m["shapes"] == m["programs"] >= 8, m
    assert 0 < m["kernel_trace_s"] < m["trace_s"], m
    assert m["trace_s"] + m["lower_s"] < m["warm_up_s"], m
    assert not m["on_chip"] and "v5" in m["device"], m


@pytest.mark.parametrize("kind", ["decode", "prefill_chunk", "prefill"])
def test_minicpm_sala_step_programs_compile_at_published_widths(
        one_chip, monkeypatch, kind):
    """The MiniCPM-SALA cell's step programs as the executor compiles them,
    at the cell's own shapes: the decode step at the cell's rows and the 2,048-token
    chunk against the 832-entry table of the 53,248-token bucket, the fresh
    prefill over 32 entries. The pool is lane-dense ``[2, 24577, 64, 256]``
    (3.22 GB, K and V together): both arrays are in the program's
    ``input_output_alias``; ``state`` (the lightning slots' 0.42 GB, the
    compressed keys' 0.20 GB) is donated too (decode.py ``Family.
    donated_state_counters``): the lightning kernel updates a row's state
    where it stands. The decode step calls ``paged_attention_sparse`` in
    both selecting layers, ``lightning_step`` in the six lightning layers
    and no dense paged kernel; a prefill program holds, for each selecting
    layer, the dense paged kernel (the branch of a chunk below
    ``dense_len``) beside ``paged_attention_select``. Temporaries are what
    longdoc-closed.json ``engine_why`` says: under a third of a GB for
    decode, under half a GB for a chunk at the widest table."""
    import sys

    import jax
    import jax.numpy as jnp

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import common
    from ray_tpu.ops.paged_attention import pool_shape
    from ray_tpu.serve.llm import decode

    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    held = common.load_json(os.path.join(
        root, "benchmark/configs/minicpm-sala-8l.json"))
    engine = common.load_json(os.path.join(
        root, "benchmark/traffic/longdoc-closed.json"))["engine"]
    cfg = dataclasses.replace(common.model_config(held),
                              attention_backend="pallas")
    fam = decode.get_family("minicpm_sala")
    init = common.load_named("reference", "minicpm_sala").init_fn()
    on_chip = lambda s: _struct(s.shape, s.dtype, one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init(jax.random.PRNGKey(0), cfg)))
    state = jax.tree.map(on_chip, jax.eval_shape(
        lambda: fam.init_state(cfg, engine["max_batch_size"] + 1,
                               engine["num_blocks"])))
    assert state["lightning"].shape == (
        6, engine["max_batch_size"] + 1, 32, 128, 128)
    assert state["ckeys"].shape == (2, 24577, 4, 256)
    pool = _struct(pool_shape(cfg.n_kv_layer, engine["num_blocks"],
                              engine["block_size"], cfg.n_kv_head,
                              cfg.head_dim), cfg.dtype, one_chip)
    assert pool.shape == (2, 24577, 64, 256)
    i32 = functools.partial(_struct, dtype=jnp.int32, sharding=one_chip)
    ctx = engine["length_buckets"][-1]
    B = engine["max_batch_size"] if kind == "decode" else 1
    assert (ctx, engine["prefill_chunk_tokens"]) == (53248, 2048)
    assert B in (1, engine["batch_buckets"][-1])
    fns = decode.DecodeFns("minicpm_sala", cfg, platform="tpu")
    more = {"state": state, "slots": i32((B,))}
    if kind == "decode":
        lowered = fns._decode.lower(
            params, pool, pool, i32((B,)), i32((B,)), i32((B, ctx // 64)),
            sample=None, **more)
    else:
        nb = ctx // 64 if kind == "prefill_chunk" else 2048 // 64
        if kind == "prefill_chunk":
            more["start"] = i32((B,))
        lowered = fns._prefill.lower(
            params, pool, pool, i32((B, 2048)), i32((B,)), i32((B, nb)),
            sample=None, **more)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    pool_bytes = 2 * math.prod(pool.shape) * 2
    assert abs(pool_bytes - 3.221e9) < 0.001e9
    # 5.64 GB of weights, the pool and the state (2.10 MB a slot a
    # lightning layer + the compressed keys' 0.20 GB)
    state_bytes = sum(math.prod(a.shape) * a.dtype.itemsize
                      for a in jax.tree.leaves(state))
    assert abs(mem.argument_size_in_bytes
               - (5.642e9 + pool_bytes + state_bytes)) < 0.02e9
    assert mem.alias_size_in_bytes >= pool_bytes + state_bytes - 1e6
    assert mem.temp_size_in_bytes < (0.33e9 if kind == "decode" else 0.5e9), \
        mem.temp_size_in_bytes
    text = compiled.as_text()
    count = lambda name: len(re.findall(rf"%{name}[.\d]* = ", text))
    calls = (count("paged_attention_sparse"), count("lightning_step"),
             count("paged_attention_select"), count("paged_attention"))
    assert calls == ((2, 6, 0, 0) if kind == "decode" else (0, 0, 2, 2)), \
        calls
    assert "cross_program_prefetch_index" not in text
    entry = text[text.index("ENTRY"):]
    assert re.search(r"%state__ckeys__", entry)
    assert re.search(r"%params__layers___1___lightning_wq__", entry)


@pytest.mark.parametrize("kind", ["decode", "prefill_chunk", "prefill"])
def test_ling_hybrid_step_programs_compile_at_published_widths(
        one_chip, monkeypatch, kind):
    """The Ling-3.0-flash cell's step programs as the executor compiles
    them, at the cell's own shapes: the 128-row decode step and the
    2,048-token chunk against the 2,560-entry table of the 40,960-token
    bucket, the fresh prefill over 128 entries. The pool is ONE latent
    layer in one plane (``[1, 73729, 16, 640]``: 1.51 GB; ISSUE 53), in
    the program's ``input_output_alias``; ``state`` (129
    slots of six KDA layers' matrices and convolution rows: 1.68 GB) is
    donated too: the kernel ``kda_step`` updates a row's state where it
    stands. The decode step calls ``kda_step`` in the six KDA layers and
    the latent kernel twice (64 rows a call: 128 tables of 2,560 entries
    pass the chip's scalar memory); a prefill program holds no ``kda_step`` (the
    chunked form is XLA's, under the scope ``kda_chunk``) and attends in
    the expanded form through ``flash_fwd``. All of it inside the chip's
    16 GB with the weights' 5.73 GB."""
    import sys

    import jax
    import jax.numpy as jnp

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import common
    from ray_tpu.serve.llm import decode

    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    held = common.load_json(os.path.join(
        root, "benchmark/configs/ling-3.0-flash-ep8-7l.json"))
    engine = common.load_json(os.path.join(
        root, "benchmark/traffic/reason-long-closed.json"))["engine"]
    cfg = dataclasses.replace(common.model_config(held),
                              attention_backend="pallas")
    fam = decode.get_family("ling_hybrid")
    init = common.load_named("reference", "ling_hybrid").init_fn()
    on_chip = lambda s: _struct(s.shape, s.dtype, one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init(jax.random.PRNGKey(0), cfg)))
    slots = engine["max_batch_size"] + 1
    state = jax.tree.map(on_chip, jax.eval_shape(
        lambda: fam.init_state(cfg, slots)))
    assert state["kda"].shape == (6, 129, 32, 128, 128)
    assert state["conv"].shape == (6, 129, 3, 12288)
    assert engine["block_size"] == 16
    planes = _one_plane(cfg, cfg.n_kv_layer, engine["num_blocks"], one_chip)
    assert planes[0].shape == (1, 73729, 16, 640) and planes[1] is None
    i32 = functools.partial(_struct, dtype=jnp.int32, sharding=one_chip)
    ctx = engine["length_buckets"][-1]
    B = engine["max_batch_size"] if kind == "decode" else 1
    assert (ctx, engine["prefill_chunk_tokens"], B) in (
        (40960, 2048, 128), (40960, 2048, 1))
    fns = decode.DecodeFns("ling_hybrid", cfg, platform="tpu")
    more = {"state": state, "slots": i32((B,))}
    if kind == "decode":
        lowered = fns._decode.lower(
            params, *planes, i32((B,)), i32((B,)), i32((B, ctx // 16)),
            sample=None, **more)
    else:
        nb = ctx // 16 if kind == "prefill_chunk" else 2048 // 16
        if kind == "prefill_chunk":
            more["start"] = i32((B,))
        lowered = fns._prefill.lower(
            params, *planes, i32((B, 2048)), i32((B,)), i32((B, nb)),
            sample=None, **more)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    pool_bytes = math.prod(planes[0].shape) * 2
    assert abs(pool_bytes - 1.510e9) < 0.001e9
    _holds_one_pool(compiled.as_text(), planes[0].shape)
    state_bytes = sum(math.prod(a.shape) * a.dtype.itemsize
                      for a in jax.tree.leaves(state))
    assert abs(state_bytes - 1.680e9) < 0.001e9
    assert abs(mem.argument_size_in_bytes
               - (5.733e9 + pool_bytes + state_bytes)) < 0.02e9
    assert mem.alias_size_in_bytes >= pool_bytes + state_bytes - 1e6
    print(kind, "temp", mem.temp_size_in_bytes, "code",
          mem.generated_code_size_in_bytes)
    assert mem.temp_size_in_bytes < (0.3e9 if kind == "decode" else 1.6e9), \
        mem.temp_size_in_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.generated_code_size_in_bytes) < 16e9
    text = compiled.as_text()
    count = lambda name: len(re.findall(rf"%{name}[.\d]* = ", text))
    calls = (count("kda_step"), count("paged_attention_latent"),
             count("flash_fwd") > 0)
    assert calls == ((6, 2, False) if kind == "decode"
                     else (0, 0, True)), calls
    assert "cross_program_prefetch_index" not in text
    entry = text[text.index("ENTRY"):]
    assert re.search(r"%state__kda__", entry)
    assert re.search(r"%params__layers___1___kda_w_qkv__", entry)


# the sdar cell's block pass (ids and bits [128, 5] under tables [128, 640]):
# re-recorded by ISSUE 55, whose change it is (a row carries a finished block
# and the fresh one behind it: the step is traced [128, 8] and the head runs
# on the 4 positions a row that choose). Under ``PARENTS_JAX`` as the others.
SDAR_DECODE_TEXT = "dd0d1ac56c77b2c9"


@pytest.mark.parametrize("kind", ["decode", "prefill_chunk"])
def test_sdar_moe_step_programs_compile_at_published_widths(
        one_chip, monkeypatch, kind):
    """The sdar cell's step programs as the executor compiles them, WITH
    their epilogue, at the cell's own shapes: the 128-row block pass (ids
    and bits ``[128, 5]`` over tables ``[128, 640]``: the 10,240-token
    bucket; traced ``[128, 8]``: a finished block and the fresh one behind
    it, ISSUE 55) and the packed chunk's widest rung (16 pieces of 128).
    The pool is lane-dense ``[6, num_blocks, 16, 512]``: both arrays are
    in the program's ``input_output_alias`` and nothing pool-sized is
    among its temporaries; every layer calls ``paged_attention`` ONCE (a
    block pass is the decode kernel's situation at 64 query rows a K/V
    head: no kernel of its own) and has its grouped product over all 128
    experts' matrices as stored, ONE ``moe_gmm_few_rows`` call; the block
    pass runs the head on the 512 positions that CHOOSE, 4 a row (the
    float32 logits are its largest temporary: nothing ``[128, 8,
    151936]`` exists), and its temporaries are the 0.57 GB they were
    before a row carried two blocks. ``SDAR_DECODE_TEXT`` records the
    block pass's text (re-recorded by ISSUE 55, which changed it, and by
    ISSUE 57, which changed the grouped product's operands)."""
    import sys

    import jax
    import jax.numpy as jnp

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import common
    from ray_tpu.ops.paged_attention import pool_shape
    from ray_tpu.serve.llm import decode

    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    held = common.load_json(os.path.join(
        root, "benchmark/configs/sdar-30b-a3b-chat-6l.json"))
    engine = common.load_json(os.path.join(
        root, "benchmark/traffic/blockdiff-chat-closed.json"))["engine"]
    cfg = dataclasses.replace(common.model_config(held),
                              attention_backend="pallas")
    fam = decode.get_family("sdar_moe")
    init = common.load_named("reference", "sdar_moe").init_fn()
    on_chip = lambda s: _struct(s.shape, s.dtype, one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init(jax.random.PRNGKey(0), cfg)))
    state = jax.tree.map(on_chip, jax.eval_shape(
        lambda: fam.init_state(cfg, 0)))
    pool = _struct(pool_shape(cfg.n_layer, engine["num_blocks"], 16,
                              cfg.n_kv_head, cfg.head_dim),
                   cfg.dtype, one_chip)
    assert pool.shape == (6, engine["num_blocks"], 16, 512)
    i32 = functools.partial(_struct, dtype=jnp.int32, sharding=one_chip)
    f32 = functools.partial(_struct, dtype=jnp.float32, sharding=one_chip)
    B = engine["max_batch_size"] if kind == "decode" else 16
    nb = engine["length_buckets"][-1] // 16
    assert (B, nb, cfg.block_length) in ((128, 640, 4), (16, 640, 4))
    sample = {"seeds": _struct((B,), jnp.uint32, one_chip),
              "temperature": f32((B,)), "top_k": i32((B,)),
              "top_p": f32((B,)),
              "mask": _struct((B, -(-cfg.vocab_size // 32)), jnp.uint32,
                              one_chip)}
    fns = decode.DecodeFns("sdar_moe", cfg, platform="tpu")
    more = {"state": state, "slots": i32((B,))}
    if kind == "decode":
        sample.update(fill=i32((B,)), remasking=i32((B,)))
        lowered = fns._decode.lower(
            params, pool, pool, i32((B, cfg.block_length + 1)), i32((B,)),
            i32((B, nb)), sample=sample, **more)
    else:
        lowered = fns._prefill.lower(
            params, pool, pool, i32((B, 128)), i32((B,)), i32((B, nb)),
            start=i32((B,)), sample=sample, **more)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    pool_bytes = 2 * math.prod(pool.shape) * 2
    # 8.72 GB of weights and the pool (a chunk chooses no token: its
    # program holds no head, and the compiler's count of the rest varies)
    if kind == "decode":
        assert abs(mem.argument_size_in_bytes
                   - (8.722e9 + pool_bytes)) < 0.03e9
    assert mem.alias_size_in_bytes >= pool_bytes
    print(kind, "args", mem.argument_size_in_bytes, "temp",
          mem.temp_size_in_bytes, "code", mem.generated_code_size_in_bytes)
    assert mem.temp_size_in_bytes < 1.0e9, mem.temp_size_in_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.generated_code_size_in_bytes) < 15.5e9
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    assert len(re.findall(r"%paged_attention[.\d]* = ", entry)) == 6
    # one grouped product a layer; a prompt's chunk chooses no token, so
    # what its LAST layer's experts would add meets nothing and is not
    # computed (nor is the head: 1.83 GB of weights the chunk never reads)
    assert len(_gmm_calls(entry)) == (6 if kind == "decode" else 5)
    assert "ragged-dot" not in entry
    if kind == "decode":
        # ONE head product, over rows x W positions: no value of the
        # vocabulary's width spans a row's 2 W traced positions
        assert "f32[128,4,151936]" in text or "f32[512,151936]" in text
        for wide in ("[128,8,151936]", "[1024,151936]"):
            assert wide not in text, wide
        assert len(re.findall(
            r"= f32\[(?:128,4|512),151936\]\S* (?:convolution|dot)\(",
            text)) == 1
        # the traced step is 1,024 positions: 8,192 sorted pairs a layer
        assert "[8192,2048]" in text
        assert 0.5e9 < mem.temp_size_in_bytes < 0.65e9
        if jax.__version__ == PARENTS_JAX:
            assert _program_text_sha(text) == SDAR_DECODE_TEXT, \
                _program_text_sha(text)
        for needle in ("moe_route_w", "moe_gmm_w_in"):
            assert re.search(
                rf"\(.*%params__layers___\d___{needle}__", entry), needle
    else:
        assert "151936]" not in entry.split("ROOT")[-1]
    assert "cross_program_prefetch_index" not in text


@pytest.mark.parametrize("rows", [96, 16, 1])
def test_ssd_step_compiles_at_the_cells_shape(one_chip, rows):
    """The Falcon-H1 cell's state kernel alone: 96 (16, 1) rows x 32 heads
    over a float32 state ``[128, 256]`` a head in the slots' array ``[5,
    97, 32, 128, 256]`` (2.03 GB), a block a (row, 8 heads of one group),
    the array aliased in and out: no copy of it among the temporaries."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssd

    S_ = functools.partial(_struct, sharding=one_chip)
    f32, bf16 = jnp.float32, jnp.bfloat16
    states = S_((5, 97, 32, 128, 256), f32)
    fn = functools.partial(ssd.ssd_step_pallas, layer=3, interpret=False)
    compiled = jax.jit(
        lambda x, dt, A, Bm, Cm, D, states, slots: fn(
            x, dt, A, Bm, Cm, D, states, slots=slots),
        donate_argnums=(6,)).lower(
        S_((rows, 32, 128), bf16), S_((rows, 32), f32), S_((32,), f32),
        S_((rows, 2, 256), bf16), S_((rows, 2, 256), bf16), S_((32,), f32),
        states, S_((rows,), jnp.int32)).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert re.search(r"%ssd_step[.\d]* = ", text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= math.prod(states.shape) * 4
    assert mem.temp_size_in_bytes < 64e6, mem.temp_size_in_bytes


@pytest.mark.parametrize("kind", ["decode", "prefill_chunk", "prefill"])
def test_falcon_h1_step_programs_compile_at_published_widths(
        one_chip, monkeypatch, kind):
    """The Falcon-H1 cell's step programs as the executor compiles them, at
    the cell's own shapes: the 96-row decode step and the 1,024-token chunk
    against the 384-entry table of the 6,144-token bucket, the fresh prefill
    over 64 entries. The pool spans ALL five layers (``[5, 16385, 16,
    512]``: 1.34 GB a plane) and so does ``state`` (97 slots: 2.05 GB), both
    in the program's ``input_output_alias``: every layer scatters its K/V
    rows, calls the paged kernel AND the kernel ``ssd_step``, which updates
    a row's state where it stands. A prefill program holds no ``ssd_step``
    (the chunked form is XLA's, under the scope ``ssd_chunk``). All of it
    inside the chip's 16 GB with the weights' 9.65 GB: the decode step's
    float32 logits ``[96, 261120]`` are 100 MB of its temporaries."""
    import sys

    import jax
    import jax.numpy as jnp

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import common
    from ray_tpu.ops.paged_attention import pool_shape
    from ray_tpu.serve.llm import decode

    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    held = common.load_json(os.path.join(
        root, "benchmark/configs/falcon-h1-34b-instruct-5l.json"))
    engine = common.load_json(os.path.join(
        root, "benchmark/traffic/worked-answers-closed.json"))["engine"]
    cfg = dataclasses.replace(common.model_config(held),
                              attention_backend="pallas")
    fam = decode.get_family("falcon_h1")
    init = common.load_named("reference", "falcon_h1").init_fn()
    on_chip = lambda s: _struct(s.shape, s.dtype, one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init(jax.random.PRNGKey(0), cfg)))
    assert params["wte"].dtype == jnp.bfloat16
    slots = engine["max_batch_size"] + 1
    state = jax.tree.map(on_chip, jax.eval_shape(
        lambda: fam.init_state(cfg, slots)))
    assert state["ssd"].shape == (5, 97, 32, 128, 256)
    assert state["conv"].shape == (5, 97, 3, 5120)
    assert engine["block_size"] == 16
    pool = _struct(pool_shape(cfg.n_layer, engine["num_blocks"], 16,
                              cfg.n_kv_head, cfg.head_dim), cfg.dtype,
                   one_chip)
    assert pool.shape == (5, 16385, 16, 512)
    i32 = functools.partial(_struct, dtype=jnp.int32, sharding=one_chip)
    ctx = engine["length_buckets"][-1]
    chunk = engine["prefill_chunk_tokens"]
    B = engine["max_batch_size"] if kind == "decode" else 1
    assert (ctx, chunk, B) in ((6144, 1024, 96), (6144, 1024, 1))
    fns = decode.DecodeFns("falcon_h1", cfg, platform="tpu")
    more = {"state": state, "slots": i32((B,))}
    if kind == "decode":
        lowered = fns._decode.lower(
            params, pool, pool, i32((B,)), i32((B,)), i32((B, ctx // 16)),
            sample=None, **more)
    else:
        nb = ctx // 16 if kind == "prefill_chunk" else chunk // 16
        if kind == "prefill_chunk":
            more["start"] = i32((B,))
        lowered = fns._prefill.lower(
            params, pool, pool, i32((B, chunk)), i32((B,)), i32((B, nb)),
            sample=None, **more)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    pool_bytes = 2 * math.prod(pool.shape) * 2
    assert abs(pool_bytes - 2.685e9) < 0.001e9
    state_bytes = sum(math.prod(a.shape) * a.dtype.itemsize
                      for a in jax.tree.leaves(state))
    assert abs(state_bytes - 2.049e9) < 0.001e9
    weights = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree.leaves(params))
    assert abs(weights - 9.649e9) < 0.002e9
    assert abs(mem.argument_size_in_bytes
               - (weights + pool_bytes + state_bytes)) < 0.02e9
    assert mem.alias_size_in_bytes >= pool_bytes + state_bytes - 1e6
    print(kind, "temp", mem.temp_size_in_bytes, "code",
          mem.generated_code_size_in_bytes)
    assert mem.temp_size_in_bytes < (0.5e9 if kind == "decode" else 1.2e9), \
        mem.temp_size_in_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.generated_code_size_in_bytes) < 16e9
    text = compiled.as_text()
    count = lambda name: len(re.findall(rf"%{name}[.\d]* = ", text))
    calls = (count("ssd_step"), count("paged_attention"))
    assert calls == ((5, 5) if kind == "decode" else (0, calls[1])), calls
    assert calls[1] >= (5 if kind != "prefill" else 0)
    assert "cross_program_prefetch_index" not in text
    entry = text[text.index("ENTRY"):]
    assert re.search(r"%state__ssd__", entry)
    assert re.search(r"%params__layers___1___ssm_w_in__", entry)
