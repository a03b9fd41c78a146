"""Serving weights are stored once in the compute dtype (ISSUE 25).

At executor build every matmul weight (the leaves the family's quant-axes
tree gives an axis) is stored as ``model_cfg.dtype``; norm scales, biases
and MoE experts stay float32. The step programs keep their
``.astype(cfg.dtype)`` seams, which are no-ops on such a tree, so the
arithmetic is the parent's bit for bit and no step casts a weight again.
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np
import pytest

PROMPTS = [
    [1, 5, 9, 2, 7, 3],
    [4, 4, 8, 1],
    [2, 9, 9, 9, 5, 6, 7, 1, 3],
    [11, 3, 5, 2, 8, 13, 1, 1, 4, 6, 9, 2],
]
NEW_TOKENS = 12


def _model_config(family, dtype="bfloat16"):
    import jax.numpy as jnp

    if family == "gpt":
        from ray_tpu.models.gpt import GPTConfig as Config
    else:
        from ray_tpu.models.llama import LlamaConfig as Config
    return dataclasses.replace(
        Config.tiny(), dtype=jnp.dtype(dtype), attention="xla")


def _engine(family, mc, params=None, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 64)
    return LLMEngine(
        EngineConfig(model=family, model_config=mc, seed=0, **kw),
        params=params, auto_step=False,
    )


def _masters(family, mc):
    import jax

    from ray_tpu.serve.llm.decode import DecodeFns

    return DecodeFns(family, mc).init(jax.random.PRNGKey(0), mc)


def _with_axes(family, mc, params):
    """[(path, leaf, quant axis)] over a weights tree."""
    import jax

    from ray_tpu.serve.llm.decode import family_quant_axes

    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    axes = treedef.flatten_up_to(family_quant_axes(family, mc))
    return [(jax.tree_util.keystr(p), w, a)
            for (p, w), a in zip(leaves, axes)]


def _hand_cast(family, mc, masters):
    import jax

    from ray_tpu.serve.llm.decode import family_quant_axes

    return jax.tree.map(
        lambda w, a: w.astype(mc.dtype) if a >= 0 else w,
        masters, family_quant_axes(family, mc))


def _generate_all(eng, prompts=PROMPTS, n=NEW_TOKENS):
    streams = [eng.submit(p, max_new_tokens=n, temperature=0.0)
               for p in prompts]
    for _ in range(400):
        if all(s.done for s in streams):
            break
        eng.step()
    while eng.step():
        pass
    return [list(s) for s in streams]


# ------------------------------------------------------------- the tree

@pytest.mark.timeout(300)
@pytest.mark.parametrize("mesh_kw", [{}, {"tp": 2}], ids=["single", "tp2"])
@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_build_stores_matmul_weights_in_compute_dtype(
        jax_cpu, family, mesh_kw):
    import jax
    import jax.numpy as jnp

    mc = _model_config(family)
    masters = _masters(family, mc)
    eng = _engine(family, mc, params=masters, **mesh_kw)
    try:
        stored = _with_axes(family, mc, eng.params)
        assert any(a >= 0 for _, _, a in stored)
        assert any(a < 0 for _, _, a in stored)
        for path, w, axis in stored:
            want = jnp.bfloat16 if axis >= 0 else jnp.float32
            assert w.dtype == want, (path, w.dtype)
        # the caller's masters are the caller's: still float32
        assert all(w.dtype == jnp.float32
                   for w in jax.tree.leaves(masters))
        if mesh_kw:
            from ray_tpu.parallel.sharding import param_shardings
            from ray_tpu.serve.llm.decode import family_param_axes

            ex = eng.executor
            want = param_shardings(
                family_param_axes(family, mc), ex.mesh, ex.rules)
            split = 0
            for w, sh in zip(jax.tree.leaves(eng.params),
                             jax.tree.leaves(want)):
                assert w.sharding.is_equivalent_to(sh, w.ndim)
                split += w.addressable_shards[0].data.shape != w.shape
            assert split  # some weight is in fact partitioned
        # describe(): half the float32 bytes for the cast leaves
        f32 = {p: w.size * 4 for p, w, _ in _with_axes(family, mc, masters)}
        d = eng.executor.describe()
        assert d["weight_dtype"] == "bfloat16"
        assert d["weight_bytes"] == sum(
            f32[p] // 2 if a >= 0 else f32[p] for p, _, a in stored)
        assert eng.stats()["executor"]["weight_bytes"] == d["weight_bytes"]
        # idempotent: the stored tree, handed to another replica, passes
        # through (same dtypes, same bytes; on one device the same arrays)
        twin = _engine(family, mc, params=eng.params, **mesh_kw)
        try:
            for a, b in zip(jax.tree.leaves(eng.params),
                            jax.tree.leaves(twin.params)):
                assert a.dtype == b.dtype
                assert mesh_kw or a is b
                np.testing.assert_array_equal(
                    np.asarray(a, np.float32), np.asarray(b, np.float32))
            assert twin.executor.describe()["weight_bytes"] == \
                d["weight_bytes"]
        finally:
            twin.shutdown()
    finally:
        eng.shutdown()


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_float32_config_keeps_the_tree_it_was_given(jax_cpu, family):
    import jax

    mc = _model_config(family, "float32")
    masters = _masters(family, mc)
    eng = _engine(family, mc, params=masters)
    try:
        for a, b in zip(jax.tree.leaves(masters),
                        jax.tree.leaves(eng.params)):
            assert a is b
        d = eng.executor.describe()
        assert d["weight_dtype"] == "float32"
        assert d["weight_bytes"] == 4 * eng.executor.num_params
    finally:
        eng.shutdown()


# ---------------------------------------------------------- the streams

def _parent_streams(family, mc, masters, prompts=PROMPTS, n=NEW_TOKENS):
    """Greedy streams of the parent's program: the family's jitted
    prefill and decode step called directly on float32 masters, which
    they cast at every use."""
    import jax.numpy as jnp

    from ray_tpu.serve.llm.decode import DecodeFns
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig, PagedKVCache

    fns = DecodeFns(family, mc)
    bs, pad = 8, 32
    cache = PagedKVCache(KVCacheConfig(
        n_layer=mc.n_layer, n_kv_head=getattr(mc, "n_kv_head", mc.n_head),
        head_dim=mc.head_dim, num_blocks=64, block_size=bs, dtype=mc.dtype,
    ))
    outs = []
    for i, prompt in enumerate(prompts):
        seq = list(prompt)
        cache.allocate(i)
        cache.ensure_capacity(i, len(seq), reserved=False)
        tokens = np.zeros((1, 16), np.int32)
        tokens[0, : len(seq)] = seq
        logits, cache.k, cache.v, _ = fns.prefill(
            masters, cache.k, cache.v, jnp.asarray(tokens),
            jnp.asarray([len(seq)], np.int32),
            jnp.asarray(cache.block_table(i, 16 // bs)[None, :]))
        out = []
        while True:
            out.append(int(np.argmax(np.asarray(logits)[0])))
            if len(out) == n:
                break
            seq.append(out[-1])
            cache.ensure_capacity(i, len(seq), reserved=False)
            logits, cache.k, cache.v, _ = fns.decode(
                masters, cache.k, cache.v,
                jnp.asarray([out[-1]], np.int32),
                jnp.asarray([len(seq) - 1], np.int32),
                jnp.asarray(cache.block_table(i, pad // bs)[None, :]))
        outs.append(out)
    return outs


@pytest.mark.timeout(300)
@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_streams_equal_the_cast_every_step_program(jax_cpu, family):
    mc = _model_config(family)
    masters = _masters(family, mc)
    given_masters = _engine(family, mc, params=masters)
    given_cast = _engine(family, mc,
                         params=_hand_cast(family, mc, masters))
    try:
        a = _generate_all(given_masters)
        b = _generate_all(given_cast)
    finally:
        given_masters.shutdown()
        given_cast.shutdown()
    assert all(len(s) == NEW_TOKENS for s in a)
    assert a == b
    assert a == _parent_streams(family, mc, masters)


@pytest.mark.timeout(300)
@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_sharded_streams_equal_the_cast_every_step_program(jax_cpu, family):
    """tp=2: the engine on its stored tree against the same engine made
    to run the parent's program (its weights swapped for the sharded
    float32 masters, cast at every use)."""
    from ray_tpu.parallel.sharding import shard_params
    from ray_tpu.serve.llm.decode import family_param_axes

    mc = _model_config(family)
    masters = _masters(family, mc)
    eng = _engine(family, mc, params=masters, tp=2)
    parent = _engine(family, mc, params=masters, tp=2)
    try:
        ex = parent.executor
        stored = ex.describe()
        ex.params = shard_params(
            masters, family_param_axes(family, mc), ex.mesh, ex.rules)
        # the report is read from the tree that is there, not kept
        assert stored["weight_dtype"] == "bfloat16"
        assert ex.describe()["weight_dtype"] == "float32"
        assert ex.describe()["weight_bytes"] > stored["weight_bytes"]
        assert _generate_all(eng) == _generate_all(parent)
    finally:
        eng.shutdown()
        parent.shutdown()


# ---------------------------------------------------------- the program

def _weight_casts(lowered_text, family, mc, masters):
    """Shapes of the float32 -> bfloat16 converts in a lowered program
    whose operand has a matmul weight's shape (the whole leaf or, inside
    the layer scan, one layer's slice of it)."""
    shapes = set()
    for path, w, axis in _with_axes(family, mc, masters):
        if axis >= 0:
            shapes.add(tuple(w.shape))
            if "blocks" in path:
                shapes.add(tuple(w.shape[1:]))
                shapes.add((1, *w.shape[1:]))
    found = re.findall(
        r"stablehlo\.convert [^\n]*: \(tensor<([0-9x]+)xf32>\) -> "
        r"tensor<[0-9x]+xbf16>", lowered_text)
    return [s for s in found
            if tuple(int(n) for n in s.split("x")) in shapes]


@pytest.mark.parametrize("program", ["prefill", "decode_step"])
@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_step_program_casts_no_weight(jax_cpu, family, program):
    """The guard on the PROGRAM, not the tree: lowered for the tree the
    executor serves, a step holds no float32 -> bfloat16 convert of a
    weight. A model family that forgets the build step fails here, as
    the same program lowered for float32 masters does (the control).
    Read from the lowered text: the CPU compiler's own passes move every
    bfloat16 operand through float32 and back, weights included, so its
    optimised text says nothing about what the program asks for."""
    mc = _model_config(family)
    masters = _masters(family, mc)
    eng = _engine(family, mc, params=masters)
    try:
        fns, cache = eng.fns, eng.cache
        B, nb = 3, 2  # no activation of a weight's shape at three rows
        i32 = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731

        def lowered(params):
            if program == "prefill":
                return fns._prefill.lower(
                    params, cache.k, cache.v, i32(B, nb * 8),
                    np.ones((B,), np.int32), i32(B, nb),
                    sample=None).as_text()
            return fns._decode.lower(
                params, cache.k, cache.v, i32(B), i32(B), i32(B, nb),
                sample=None).as_text()

        control = _weight_casts(lowered(masters), family, mc, masters)
        n_matmul = sum(a >= 0 for _, _, a in _with_axes(family, mc, masters))
        assert len(control) >= n_matmul - 1, control
        assert _weight_casts(lowered(eng.params), family, mc, masters) == []
    finally:
        eng.shutdown()
