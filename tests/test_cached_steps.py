"""The seam between models/cached.py's one cached step and the families
(CPU, float32, XLA attention backend, tiny presets, seeded weights).

Every family x kind of step gives, through the paged cache, the logits of
the family's own full-sequence forward at the same positions; and for the
families that have all three kinds, a decode step IS the one-column verify
window IS the one-token prompt chunk at the row's position: the identity
the skeleton states in code.

Tolerance: float32 on both sides, the same mathematics in another order of
sums (a paged softmax against a dense one): 1e-4 on logits of size ~1-5
(seen 3e-6). The K/V rows a step writes come from the same projections of
the same rows whatever its kind: bit for bit in the first layer, and past
it as close as the attention formulations that feed the next layer (decode
and chunk attention sum in another order: seen 4e-8).
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

BS, NB = 8, 4            # block size, blocks a row
LENGTHS = (13, 9)        # the two sequences; the bucket pads them to 16
KINDS = ("fresh", "chunk", "decode", "verify")


@pytest.fixture(scope="module")
def families(jax_cpu):
    """family -> (Family, float32 config, params, full [B][L, V] logits,
    the sequences)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import gpt_forward
    from ray_tpu.models.lfm2_moe import lfm2_moe_forward
    from ray_tpu.models.llama import llama_forward
    from ray_tpu.serve.llm.decode import get_family

    rng = np.random.default_rng(28)
    seqs = [rng.integers(1, 500, n).astype(np.int32) for n in LENGTHS]
    forwards = {"gpt": gpt_forward, "llama": llama_forward,
                "lfm2_moe": lfm2_moe_forward}
    out = {}
    for name, forward in forwards.items():
        fam = get_family(name)
        cfg = dataclasses.replace(
            fam.default_config(), dtype=jnp.float32, attention_backend="xla",
            **({} if name == "lfm2_moe" else {"attention": "xla"}))
        params = fam.init(jax.random.PRNGKey(1), cfg)
        full = [np.asarray(forward(params, jnp.asarray(s[None]), cfg))[0]
                for s in seqs]
        out[name] = fam, cfg, params, full, seqs
    return out


class _Served:
    """Two rows in a bucket of two, each with its own blocks and state slot,
    driven through the family's step functions as the executor calls them."""

    def __init__(self, fam, cfg, params):
        import jax.numpy as jnp

        self.fam, self.cfg, self.params = fam, cfg, params
        from ray_tpu.ops.paged_attention import pool_shape

        # as the cache manager stores it: by heads, or lane-dense
        self.n_kv = getattr(cfg, "n_kv_head", cfg.n_head)
        self.k = self.v = jnp.zeros(pool_shape(
            getattr(cfg, "n_kv_layer", cfg.n_layer), 1 + 2 * NB, BS,
            self.n_kv, cfg.head_dim), cfg.dtype)
        self.tables = jnp.asarray(
            [[1 + r * NB + i for i in range(NB)] for r in range(2)],
            jnp.int32)
        self.state, self.slots = None, None
        if fam.init_state is not None:
            self.state = fam.init_state(cfg, 3)
            self.slots = jnp.asarray([1, 2], jnp.int32)

    def _run(self, step, *arrays, **kw):
        import jax.numpy as jnp

        out, self.k, self.v, self.state = step(
            self.params, self.k, self.v, *map(jnp.asarray, arrays),
            self.tables, self.cfg, state=self.state, slots=self.slots, **kw)
        return np.asarray(out)

    def prefill(self, parts, start=None):
        """``parts``: each row's tokens, right-padded to the longest."""
        import jax.numpy as jnp

        tokens = np.zeros((2, max(map(len, parts))), np.int32)
        for r, part in enumerate(parts):
            tokens[r, :len(part)] = part
        lengths = np.asarray([len(p) for p in parts], np.int32)
        kw = {} if start is None else {
            "start": jnp.asarray(start, jnp.int32)}
        return self._run(self.fam.prefill, tokens, lengths, **kw)

    def decode(self, tokens, positions):
        return self._run(self.fam.decode_step, np.asarray(tokens, np.int32),
                         np.asarray(positions, np.int32))

    def verify(self, windows, starts, draft_len):
        return self._run(self.fam.verify_step, np.asarray(windows, np.int32),
                         np.asarray(starts, np.int32),
                         np.asarray(draft_len, np.int32))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("family", ["gpt", "llama", "lfm2_moe"])
def test_cached_logits_match_full_forward(families, family, kind):
    fam, cfg, params, full, seqs = families[family]
    if kind == "verify" and fam.verify_step is None:
        # its state cannot be rolled back: the engine never speculates
        assert family == "lfm2_moe"
        return
    served = _Served(fam, cfg, params)
    last = [full[r][len(s) - 1] for r, s in enumerate(seqs)]
    if kind == "fresh":
        got = served.prefill(seqs)
    elif kind == "chunk":
        cut = [8, 5]
        served.prefill([s[:c] for s, c in zip(seqs, cut)])
        got = served.prefill([s[c:] for s, c in zip(seqs, cut)], start=cut)
    elif kind == "decode":
        served.prefill([s[:-1] for s in seqs])
        got = served.decode([s[-1] for s in seqs],
                            [len(s) - 1 for s in seqs])
    else:
        # windows of 4 over each row's last tokens: row 0 all drafts, row 1
        # one draft and two padding columns
        W, draft_len = 4, [3, 1]
        starts = [len(s) - 1 - d for s, d in zip(seqs, draft_len)]
        served.prefill([s[:n] for s, n in zip(seqs, starts)])
        windows = np.zeros((2, W), np.int32)
        for r, (s, n, d) in enumerate(zip(seqs, starts, draft_len)):
            windows[r, :d + 1] = s[n:n + d + 1]
        got = served.verify(windows, starts, draft_len)
        for r, (n, d) in enumerate(zip(starts, draft_len)):
            np.testing.assert_allclose(
                got[r, :d + 1], full[r][n:n + d + 1], atol=1e-4, rtol=1e-4)
        return
    np.testing.assert_allclose(got, np.stack(last), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("as_kind", ["verify", "chunk"])
@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_decode_is_the_one_token_case(families, family, as_kind):
    """decode_step == verify_step with W = 1, draft_len = 0 ==
    prefill(start=positions) with S = 1: the same logits, and the same
    pool afterwards."""
    fam, cfg, params, _, seqs = families[family]
    tokens = [s[-1] for s in seqs]
    positions = [len(s) - 1 for s in seqs]
    ends = []
    for kind in ("decode", as_kind):
        served = _Served(fam, cfg, params)
        served.prefill([s[:-1] for s in seqs])
        if kind == "decode":
            logits = served.decode(tokens, positions)
        elif kind == "verify":
            logits = served.verify(
                [[t] for t in tokens], positions, [0, 0])[:, 0]
        else:
            logits = served.prefill([[t] for t in tokens], start=positions)
        ends.append((logits, np.asarray(served.k), np.asarray(served.v)))
    (want, k, v), (got, k2, v2) = ends
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    for a, b in ((k2, k), (v2, v)):  # block 0 is the garbage sink
        np.testing.assert_array_equal(a[0, 1:], b[0, 1:])
        np.testing.assert_allclose(a[:, 1:], b[:, 1:], atol=1e-6)
    assert np.abs(k[:, 1:]).sum() > 0


# ---------------------------------------------------------------------------
# The pool is updated where it stands (ISSUE 29): the whole pools and a layer
# index go down to ``write_kv`` and the attention call. What a step leaves in
# them is, bit for bit, what PR 28's walk left: every layer on its own slab,
# the pool rebuilt from the slabs.


def _walk_by_slabs(fam, x, layers, cache_k, cache_v, step, state, cfg):
    """PR 28's ``_walk``: the pools as xs -> ys of the layer scan (a list
    of layers: the slab sliced out at the attending ordinal and set back),
    each layer written and attended as ONE layer's pool."""
    import jax

    from ray_tpu.models import cached

    if not isinstance(layers, list):

        def body(carry, xs):
            x, state = carry
            lp, *kv = xs

            def attend(q, k, v):
                attn, kv[0], kv[1] = cached.attend_layer(
                    step, *kv, None, q, k, v, cfg)
                return attn

            x, state = fam.layer(x, lp, attend, step, state, cfg)
            return (x, state), tuple(kv)

        (x, state), (cache_k, cache_v) = jax.lax.scan(
            body, (x, state), (layers, cache_k, cache_v))
        return x, cache_k, cache_v, state
    pools, attended = [cache_k, cache_v], 0

    def attend(q, k, v):
        nonlocal attended
        attn, *slabs = cached.attend_layer(
            step, *jax.tree.map(lambda a: a[attended], tuple(pools)), None,
            q, k, v, cfg)
        pools[:] = jax.tree.map(
            lambda a, slab: a.at[attended].set(slab), tuple(pools),
            tuple(slabs))
        attended += 1
        return attn

    for lp in layers:
        x, state = fam.layer(x, lp, attend, step, state, cfg)
    return x, *pools, state


# every page is stored lane-dense (ops/paged_attention.py ``pool_shape``):
# the tiny presets' (2 heads of 16: a row of 32), and pages of 8 heads of
# 128 (a row of 1,024: whole lanes, the cells' widths)
TILES = {"gpt": {"n_head": 8, "d_model": 1024},
         "llama": {"n_head": 8, "n_kv_head": 8, "d_model": 1024},
         "lfm2_moe": {"n_head": 8, "n_kv_head": 8, "head_dim": 128}}


def _random_pool(rng, shape, n_kv, quant):
    import jax.numpy as jnp

    from ray_tpu.ops.quantization import QuantizedKV

    if quant is None:
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return QuantizedKV(
        jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
        jnp.asarray(rng.uniform(0.01, 0.1, shape[:3] + (n_kv,)),
                    jnp.float32))


@pytest.mark.parametrize("quant", [None, "int8"])
@pytest.mark.parametrize("pages", ["tiny", "tiles"])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("family", ["gpt", "llama", "lfm2_moe"])
def test_step_leaves_the_pool_the_slab_walk_left(
        families, monkeypatch, family, kind, pages, quant):
    """After each kind of step, on a pool that held something everywhere:
    logits, pools (data and scales) and state equal the slab walk's bit
    for bit; the rows written are the step's real tokens' and nothing
    else; every block outside the rows' tables but block 0, the one sink,
    is as it was."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import cached

    fam, cfg, params, _, seqs = families[family]
    if kind == "verify" and fam.verify_step is None:
        return
    cfg = dataclasses.replace(cfg, quantization=quant)
    if pages == "tiles":
        cfg = dataclasses.replace(cfg, **TILES[family])
        params = fam.init(jax.random.PRNGKey(2), cfg)

    def run(walk):
        rng = np.random.default_rng(29)
        served = _Served(fam, cfg, params)
        served.k = _random_pool(rng, served.k.shape, served.n_kv, quant)
        served.v = _random_pool(rng, served.k.shape, served.n_kv, quant)
        assert served.k.ndim == 4
        with monkeypatch.context() as m:
            if walk is not None:
                m.setattr(cached, "_walk", walk)
            cut = [8, 5]
            if kind == "fresh":
                before = served.k, served.v
                out = served.prefill(seqs)
                written = [range(len(s)) for s in seqs]
            elif kind == "chunk":
                served.prefill([s[:c] for s, c in zip(seqs, cut)])
                before = served.k, served.v
                out = served.prefill(
                    [s[c:] for s, c in zip(seqs, cut)], start=cut)
                written = [range(c, len(s)) for s, c in zip(seqs, cut)]
            elif kind == "decode":
                served.prefill([s[:-1] for s in seqs])
                before = served.k, served.v
                out = served.decode([s[-1] for s in seqs],
                                    [len(s) - 1 for s in seqs])
                written = [[len(s) - 1] for s in seqs]
            else:
                W, draft_len = 4, [3, 1]
                starts = [len(s) - 1 - d for s, d in zip(seqs, draft_len)]
                served.prefill([s[:n] for s, n in zip(seqs, starts)])
                before = served.k, served.v
                windows = np.zeros((2, W), np.int32)
                for r, (s, n, d) in enumerate(zip(seqs, starts, draft_len)):
                    windows[r, :d + 1] = s[n:n + d + 1]
                out = served.verify(windows, starts, draft_len)
                written = [range(n, n + d + 1)
                           for n, d in zip(starts, draft_len)]
        return out, before, (served.k, served.v), served.state, written

    got, want = run(None), run(_walk_by_slabs)
    np.testing.assert_array_equal(got[0], want[0])
    for a, b in zip(jax.tree.leaves((got[1:4])), jax.tree.leaves(want[1:4])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # what changed in the pool: the rows written, block 0, and no more
    _, before, after, _, written = got
    tables = np.asarray(_Served(fam, cfg, params).tables)
    for was, now in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        was, now = np.asarray(was), np.asarray(now)
        changed = np.zeros(now.shape[:3], bool)  # [layer, block, slot]
        changed[:, 0] = True
        for r, positions in enumerate(written):
            for p in positions:
                changed[:, tables[r, p // BS], p % BS] = True
        np.testing.assert_array_equal(now[~changed], was[~changed])
        rows = changed.copy()
        rows[:, 0] = False
        assert (now[rows] != was[rows]).any(axis=tuple(
            range(1, now[rows].ndim))).all()


def _platform_donates():
    import jax
    import jax.numpy as jnp

    x = jnp.zeros((8,))
    jax.jit(lambda a: a + 1, donate_argnums=0)(x)
    return x.is_deleted()


@pytest.mark.parametrize("family", ["gpt", "llama", "lfm2_moe"])
def test_executor_donates_the_pools_and_nothing_else(jax_cpu, family):
    """Every kind of step, ``copy_blocks`` and ``land_blocks`` consume the
    pool arrays the executor held (where the platform donates: the CPU
    does) and rebind ``cache.k`` / ``cache.v``; ``export_blocks`` reads
    the pools as they stand; the weights and ``state`` are not donated: a
    ``counter_state()`` taken before the steps still reads after them."""
    import jax

    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    engine = LLMEngine(EngineConfig(model=family, num_blocks=33),
                       auto_step=False)
    ex, cache = engine.executor, engine.cache
    donates = _platform_donates()
    held = ex.counter_state()
    assert (held is None) == (family != "lfm2_moe")
    params = jax.tree.leaves(ex.params)
    B, NB, S, W = 2, 4, 8, 3
    i32 = lambda *shape: np.ones(shape, np.int32)
    tables = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
    slots = i32(B) if cache.state is not None else None
    steps = [
        lambda: ex.prefill(i32(B, S), 5 * i32(B), tables, slots=slots),
        lambda: ex.prefill_chunk(i32(B, S), 3 * i32(B), 5 * i32(B), tables,
                                 slots=slots),
        lambda: ex.decode_step(i32(B), 8 * i32(B), tables, slots=slots),
    ]
    if ex.fns._verify is not None:
        steps.append(lambda: ex.verify_step(
            i32(B, W), 9 * i32(B), i32(B), tables))
    steps += [
        lambda: ex.copy_blocks([(1, 9), (5, 10), (2, 11)]),
        lambda: ex.land_blocks([12], *ex.export_blocks([1])),
    ]
    for step in steps:
        before = jax.tree.leaves((cache.k, cache.v))
        step()
        after = jax.tree.leaves((cache.k, cache.v))
        jax.block_until_ready(after)
        assert all(a.is_deleted() == donates for a in before)
        assert not any(a.is_deleted() for a in after + params)
    k, v = ex.export_blocks([1, 9, 12])
    for got in jax.tree.leaves((k, v)):  # the COW clone and the landing
        np.testing.assert_array_equal(got[:, 0], got[:, 1])
        np.testing.assert_array_equal(got[:, 0], got[:, 2])
        assert np.abs(got[:, 0].astype(np.float32)).sum() > 0
    if held is not None:
        assert not any(a.is_deleted() for a in jax.tree.leaves(held))
        was, now = ex.read_counters(held), ex.read_counters(
            ex.counter_state())
        assert was != now and set(was) == set(now)
    engine.shutdown()
