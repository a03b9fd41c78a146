"""The falcon_h1 family on the CPU at the tiny preset (width 64, two layers,
each a Mamba-2 branch of 4 heads of 16 over a state of 32 in 2 groups BESIDE
4 query heads on 2 K/V heads of 16), seeded weights: ops/ssd.py's three forms
against each other and the masked ``O(n^2)`` product; the program against the
plain reference (benchmark/reference/falcon_h1.py) on LOGITS, prefill (whole
and chunked) then decode through the paged cache and the state slots; every
multiplier, the convolution's bias, the gate's place and the group norm shown
to matter; the blocked reference against the unblocked; the slots' lifetime;
what the engine refuses.

Tolerances, with their reason. Program and reference in float32 compute the
same mathematics and differ in the order of sums (the program carries the SSM
state piece by piece where the reference walks token by token; the program
attends through the paged pool): 3e-4 on logits of size ~1-4 (seen 3e-5). A
dropped multiplier, a missing bias, a gate behind the norm or a norm over all
channels moves them by 1e-2 and more (each asserted), thirty times the limit;
an SSM state rounded to bfloat16 at three seams and five decode steps moves
them by 2e-3, six times the limit.
"""
from __future__ import annotations

import dataclasses
import os
import sys

from unittest import mock

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BS = 8
ATOL = 3e-4


@pytest.fixture(scope="module")
def ref():
    from benchmark import common

    return common.load_named("reference", "falcon_h1")


@pytest.fixture(scope="module")
def tiny(jax_cpu):
    """(float32 config, its seeded params)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.falcon_h1 import FalconH1Config, falcon_h1_init

    cfg = dataclasses.replace(FalconH1Config.tiny(), dtype=jnp.float32)
    return cfg, falcon_h1_init(jax.random.PRNGKey(1), cfg)


def _engine(cfg, params, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    settings = dict(model="falcon_h1", model_config=cfg, num_blocks=129,
                    block_size=BS, max_batch_size=4)
    settings.update(kw)
    return LLMEngine(EngineConfig(**settings), params=params,
                     auto_step=False)


def _prompts(lens, seed=0, vocab=512):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).tolist() for n in lens]


def _drive(engine, streams, limit=4000):
    for _ in range(limit):
        if all(s.done for s in streams):
            return
        engine.step()
    raise AssertionError("streams did not finish")


def _serve_logits(cfg, params, prompts, new, chunk=None, state=None, nb=20,
                  backend="xla", round_state=None, slots=None):
    """Prefill (whole, or by chunks of ``chunk``) then ``new`` greedy decode
    steps of ALL the prompts as the rows of one batch (right-padded, of
    unequal length), through the pool and the state slots, on logits
    (``sample=None``): the logits that chose each generated token [rows,
    new, V], the sequences, the last state."""
    import jax.numpy as jnp

    from ray_tpu.models.falcon_h1 import (
        falcon_h1_decode_step, falcon_h1_init_state, falcon_h1_prefill)
    from ray_tpu.ops.paged_attention import pool_shape

    cfg = dataclasses.replace(cfg, attention_backend=backend)
    R = len(prompts)
    shape = pool_shape(cfg.n_layer, 1 + R * nb, BS, cfg.n_kv_head,
                       cfg.head_dim)
    k, v = jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)
    if state is None:
        state = falcon_h1_init_state(cfg, R + 2)
    if slots is None:
        slots = list(range(1, R + 1))
    table = jnp.asarray([list(range(1 + r * nb, 1 + (r + 1) * nb))
                         for r in range(R)], jnp.int32)
    slots = jnp.asarray(slots, jnp.int32)
    lens = [len(p) for p in prompts]

    def rounded(state):
        if round_state is None:
            return state
        return {**state, "ssd": state["ssd"].astype(round_state).astype(
            jnp.float32)}

    if chunk is None:
        toks = np.zeros((R, max(lens)), np.int32)
        for r, p in enumerate(prompts):
            toks[r, :len(p)] = p
        logits, k, v, state = falcon_h1_prefill(
            params, k, v, jnp.asarray(toks), jnp.asarray(lens, jnp.int32),
            table, cfg, state=state, slots=slots)
        logits = np.asarray(logits)
    else:
        # a row whose prompt has ended rides along as padding: length 1 at
        # the garbage slot and table
        logits = np.zeros((R, cfg.vocab_size), np.float32)
        for s in range(0, max(lens), chunk):
            toks = np.zeros((R, chunk), np.int32)
            real = [max(0, min(chunk, n - s)) for n in lens]
            for r, p in enumerate(prompts):
                toks[r, :real[r]] = p[s:s + chunk]
            live = np.asarray(real) > 0
            out, k, v, state = falcon_h1_prefill(
                params, k, v, jnp.asarray(toks),
                jnp.asarray(np.maximum(real, 1), jnp.int32),
                jnp.where(live[:, None], table, 0), cfg,
                start=jnp.asarray(np.where(live, s, 0), jnp.int32),
                state=rounded(state), slots=jnp.where(live, slots, 0))
            logits = np.where(live[:, None] & (np.asarray(lens) <= s + chunk)
                              [:, None], np.asarray(out), logits)
    seqs, outs = [list(p) for p in prompts], []
    for _ in range(new):
        outs.append(np.asarray(logits))
        for r in range(R):
            seqs[r].append(int(np.argmax(outs[-1][r])))
        logits, k, v, state = falcon_h1_decode_step(
            params, k, v, jnp.asarray([s[-1] for s in seqs], jnp.int32),
            jnp.asarray([len(s) - 1 for s in seqs], jnp.int32), table, cfg,
            state=rounded(state), slots=slots)
    return np.stack(outs, axis=1), seqs, state


def _want(ref, params, cfg, seq, n):
    """The reference's logits that chose the tokens after the first ``n``."""
    import jax.numpy as jnp

    return np.asarray(ref.logits(params, jnp.asarray([seq[:-1]]), cfg))[
        0, n - 1:]


# ------------------------------------------------ the operator's three forms


def _ssd_inputs(B, S, H, P, N, G, seed=0):
    import jax
    import jax.numpy as jnp

    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    x = jax.random.normal(next(k), (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(next(k), (B, S, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(next(k), (H,), minval=0.0, maxval=2.7))
    Bm = jax.random.normal(next(k), (B, S, G, N))
    Cm = jax.random.normal(next(k), (B, S, G, N))
    D = jax.random.normal(next(k), (H,))
    state = jax.random.normal(next(k), (B, H, P, N))
    return x, dt, A, Bm, Cm, D, state


def _masked_product(x, dt, A, Bm, Cm, D, state):
    """THE O(n^2) DEFINITION: ``y_i = exp(c_i) S_0 C_i + sum_{j <= i}
    exp(c_i - c_j) dt_j (C_i . B_j) x_j + D x_i`` over the whole row."""
    import jax.numpy as jnp

    H, G = x.shape[2], Bm.shape[2]
    heads = lambda a: jnp.repeat(a, H // G, axis=2)  # noqa: E731
    Bh, Ch = heads(Bm), heads(Cm)
    c = jnp.cumsum(dt * A, axis=1)                            # [B, S, H]
    S = x.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(
        causal, c[:, :, None] - c[:, None, :], 0.0)), 0.0)
    table = jnp.einsum("bihn,bjhn->bijh", Ch, Bh)
    y = jnp.einsum("bijh,bjhp->bihp", decay * table * dt[:, None], x)
    y = y + jnp.exp(c)[..., None] * jnp.einsum("bihn,bhpn->bihp", Ch, state)
    return y + D[:, None] * x


@pytest.mark.parametrize("S,piece", [(40, 16), (16, 16), (7, 16), (50, 128)])
def test_chunk_is_the_recurrence_is_the_masked_product(jax_cpu, S, piece):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssd

    x, dt, A, Bm, Cm, D, state = _ssd_inputs(2, S, 4, 8, 16, 2)
    with jax.default_matmul_precision("highest"):
        want, last = ssd.ssd_recurrence(x, dt, A, Bm, Cm, D, state)
        np.testing.assert_allclose(
            _masked_product(x, dt, A, Bm, Cm, D, state), want, atol=2e-4)
        got, end = ssd.ssd_chunk(x, dt, A, Bm, Cm, D, state,
                                 jnp.ones((2, S), bool), piece=piece)
    np.testing.assert_allclose(got, want, atol=2e-4)
    np.testing.assert_allclose(end, last, atol=2e-4)


def test_chunk_stops_at_each_rows_last_real_token_and_carries_on(jax_cpu):
    """Right-padded rows of unequal length: the state is the one after each
    row's LAST REAL token (padding neither decays nor adds), and a second
    chunk that starts from it is the recurrence over the joined rows."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssd

    x, dt, A, Bm, Cm, D, state = _ssd_inputs(2, 48, 4, 8, 16, 2, seed=3)
    lens = jnp.asarray([48, 21])
    valid = jnp.arange(48)[None, :] < lens[:, None]
    with jax.default_matmul_precision("highest"):
        want, _ = ssd.ssd_recurrence(x, dt * valid[..., None], A, Bm, Cm, D,
                                     state)
        _, short = ssd.ssd_recurrence(x[1:, :21], dt[1:, :21], A, Bm[1:, :21],
                                      Cm[1:, :21], D, state[1:])
        first, mid = ssd.ssd_chunk(
            x[:, :32], dt[:, :32], A, Bm[:, :32], Cm[:, :32], D, state,
            valid[:, :32], piece=16)
        second, end = ssd.ssd_chunk(
            x[:, 32:], dt[:, 32:], A, Bm[:, 32:], Cm[:, 32:], D, mid,
            valid[:, 32:], piece=16)
    got = jnp.concatenate([first, second], axis=1)
    np.testing.assert_allclose(got[0], want[0], atol=2e-4)
    np.testing.assert_allclose(got[1, :21], want[1, :21], atol=2e-4)
    np.testing.assert_allclose(end[1], short[0], atol=2e-4)


def test_step_token_by_token_is_the_recurrence_and_the_kernel_is_xla(jax_cpu):
    """``ssd_step`` walked over a row is the recurrence; the kernel
    (interpreted), over the slots' array where it stands, is XLA's gather,
    update, scatter: rows in any slots, the other slots untouched."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssd

    B, S, H, P, N, G = 3, 6, 4, 16, 32, 2
    x, dt, A, Bm, Cm, D, state = _ssd_inputs(B, S, H, P, N, G, seed=5)
    with jax.default_matmul_precision("highest"):
        want, last = ssd.ssd_recurrence(x, dt, A, Bm, Cm, D, state)
    states = jax.random.normal(jax.random.PRNGKey(9), (2, 6, H, P, N))
    slots = jnp.asarray([4, 1, 5], jnp.int32)
    pooled = states.at[1, slots].set(state)
    S_x = state
    for t in range(S):
        y_x, S_x = ssd.ssd_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D,
                                S_x)
        y_k, pooled = ssd.ssd_step_pallas(
            x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D, pooled, 1, slots,
            interpret=True)
        np.testing.assert_allclose(y_x, want[:, t], atol=2e-4)
        np.testing.assert_allclose(y_k, y_x, atol=1e-5)
    np.testing.assert_allclose(S_x, last, atol=2e-4)
    np.testing.assert_allclose(pooled[1, slots], S_x, atol=1e-5)
    untouched = jnp.asarray([0, 2, 3])
    assert float(jnp.abs(pooled[1, untouched] - states[1, untouched]).max()) \
        == 0.0
    assert float(jnp.abs(pooled[0] - states[0]).max()) == 0.0


def test_step_bytes_and_chunk_flops_at_the_published_widths():
    from ray_tpu.ops import ssd

    # a row a layer: 32 heads x (2 x 128 x 256 x 4 B of state + x, dt, y) +
    # 2 groups x (B, C) x 256 x 4 B
    assert ssd.step_bytes(1, 32, 128, 256, 2) == 32 * (
        262144 + 256 + 4 + 256) + 4096
    assert ssd.step_bytes(96, 32, 128, 256, 2) == 96 * 8409216
    # a token a layer: ~5.3 MFLOP (ISSUE 58: "~5 MFLOP a token a layer")
    assert ssd.chunk_flops(1, 32, 128, 256, 2) == 2 * (
        2 * 128 * 256 + 32 * (128 * 128 + 2 * 128 * 256))
    assert 5.2e6 < ssd.chunk_flops(1, 32, 128, 256, 2) < 5.4e6


# ------------------------------------------------- program == reference


def test_config_counts_its_widths(jax_cpu):
    import jax

    from ray_tpu.models.falcon_h1 import FalconH1Config, falcon_h1_init_state

    big = FalconH1Config(n_layer=5)
    hash(big)
    assert (big.d_ssm, big.conv_width, big.d_in_proj) == (4096, 5120, 9248)
    slot = jax.eval_shape(lambda: falcon_h1_init_state(big, 1))
    assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(slot)) \
        == 5 * (4194304 + 30720)
    state = falcon_h1_init_state(FalconH1Config.tiny(), 3)
    assert state["ssd"].shape == (2, 3, 4, 16, 32)
    assert state["conv"].shape == (2, 3, 3, 64 + 2 * 2 * 32)
    with pytest.raises(ValueError, match="ssm_multipliers holds 5"):
        FalconH1Config(ssm_multipliers=(1.0, 2.0))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("lens,chunk", [
    ([21, 9], None),      # fresh, whole: rows of unequal length
    ([100, 37], None),    # fresh, whole: pieces of 16, one row ends mid-piece
    ([100, 37], 40),      # 3 chunks (40 + 40 + 20): state, convolution rows
                          # and the paged context carried over two seams,
                          # one row finished after its first chunk
    ([61], 24),           # 3 chunks ending mid-piece (24 = 16 + 8; 13 left)
    ([2], 8),             # shorter than the convolution's history
])
def test_prefill_then_decode_matches_reference_on_logits(
        tiny, ref, lens, chunk, backend):
    cfg, params = tiny
    prompts = _prompts(lens, seed=5 + lens[0])
    got, seqs, _ = _serve_logits(cfg, params, prompts, 5, chunk=chunk,
                                 backend=backend)
    for r, (p, seq) in enumerate(zip(prompts, seqs)):
        np.testing.assert_allclose(
            got[r], _want(ref, params, cfg, seq, len(p)), atol=ATOL)


def test_a_state_in_bfloat16_is_noticed(tiny, ref):
    """The limit 3e-4 is tight enough that an SSM state rounded to bfloat16
    between steps fails it."""
    import jax.numpy as jnp

    cfg, params = tiny
    prompts = _prompts([100], seed=105)
    got, seqs, _ = _serve_logits(cfg, params, prompts, 5, chunk=40,
                                 round_state=jnp.bfloat16)
    want = _want(ref, params, cfg, seqs[0], 100)
    assert np.abs(got[0] - want).max() > 4 * ATOL


# the scalars of the configuration, one case each: (field, index or None)
_SCALARS = [
    ("embedding_multiplier", None), ("lm_head_multiplier", None),
    ("attention_in_multiplier", None), ("attention_out_multiplier", None),
    ("key_multiplier", None), ("ssm_in_multiplier", None),
    ("ssm_out_multiplier", None),
    *[("ssm_multipliers", i) for i in range(5)],
    *[("mlp_multipliers", i) for i in range(2)],
]


def _without(cfg, field, index):
    """``cfg`` with one multiplier dropped (set to 1)."""
    if index is None:
        return dataclasses.replace(cfg, **{field: 1.0})
    value = list(getattr(cfg, field))
    value[index] = 1.0
    return dataclasses.replace(cfg, **{field: tuple(value)})


@pytest.mark.parametrize("field,index", _SCALARS)
def test_each_multiplier_matters(tiny, ref, field, index):
    """Seeded weights carry the multipliers (``falcon_h1_init``): a program
    that drops any ONE of them (here: is built without it, over the same
    weights) fails the comparison the sound one passes, by thirty times the
    limit and more."""
    cfg, params = tiny
    prompts = _prompts([37], seed=11)
    got, seqs, _ = _serve_logits(
        _without(cfg, field, index), params, prompts, 3)
    want = _want(ref, params, cfg, seqs[0], 37)
    assert np.abs(got[0] - want).max() > 30 * ATOL


def test_a_misplaced_multiplier_is_noticed(tiny, ref):
    """Two multipliers SWAPPED (the key's on the query's side is invisible
    to the scores, so: the gate's on ``up``) fail too."""
    cfg, params = tiny
    gate, down = cfg.mlp_multipliers
    prompts = _prompts([37], seed=11)
    got, seqs, _ = _serve_logits(
        dataclasses.replace(cfg, mlp_multipliers=(down, gate)), params,
        prompts, 3)
    assert np.abs(got[0] - _want(ref, params, cfg, seqs[0], 37)).max() \
        > 30 * ATOL


@pytest.fixture(scope="module")
def control():
    """tests/benchmark/control_falcon_h1_readings.py: the references with
    ONE thing changed that the chip's controls judge under."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "control_falcon_h1_readings", os.path.join(
            ROOT, "tests/benchmark/control_falcon_h1_readings.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _no_skip(ref):
    import jax.numpy as jnp

    sound = ref.ssm
    return mock.patch.object(ref, "ssm", lambda u, lp, cfg: sound(
        u, {**lp, "ssm_d": jnp.zeros_like(lp["ssm_d"])}, cfg))


@pytest.mark.parametrize("wrong", [
    "no_conv_bias", "gate_behind_norm", "norm_over_all", "no_skip"])
def test_the_reference_notices_each_reading(tiny, ref, control, wrong):
    """The convolution's bias, the gate's place, the norm's groups and the
    ``D`` skip each move the logits by thirty times the limit: a reference
    (or a program) with the other reading fails."""
    cfg, params = tiny
    prompts = _prompts([37], seed=11)
    got, seqs, _ = _serve_logits(cfg, params, prompts, 3)
    np.testing.assert_allclose(
        got[0], _want(ref, params, cfg, seqs[0], 37), atol=ATOL)
    with (_no_skip(ref) if wrong == "no_skip"
          else control.changed(ref, wrong)):
        off = np.abs(got[0] - _want(ref, params, cfg, seqs[0], 37)).max()
    assert off > 30 * ATOL


def test_blocked_logits_at_is_the_unblocked_pass(tiny, ref, monkeypatch):
    """``logits_at`` in blocks (the SwiGLU by columns, attention by K/V head
    and query block, the head by blocks of the vocabulary at the asked
    positions) is the unblocked forward pass."""
    import jax.numpy as jnp

    cfg, params = tiny
    monkeypatch.setattr(ref, "Q_BLOCK", 16)
    monkeypatch.setattr(ref, "FFN_BLOCKS", 4)
    monkeypatch.setattr(ref, "HEAD_BLOCKS", 8)
    tokens = jnp.asarray(_prompts([50, 50], seed=8))
    positions = jnp.asarray([[0, 17, 49], [3, 31, 48]])
    whole = np.asarray(ref.logits(params, tokens, cfg))
    got = np.asarray(ref.logits_at(params, tokens, positions, cfg))
    for b in range(2):
        np.testing.assert_allclose(got[b], whole[b, positions[b]], atol=2e-5)
    # ... and the checkpoint's dtype changes no arithmetic: float32 inside
    init = ref.init_fn()
    import jax

    rounded = init(jax.random.PRNGKey(1), cfg)
    assert rounded["wte"].dtype == jnp.bfloat16
    assert rounded["layers"][0]["ssm_a_log"].dtype == jnp.float32
    assert ref.logits_at(rounded, tokens, positions, cfg).dtype == jnp.float32


def test_a_leaf_drawn_in_blocks_has_its_std(jax_cpu, monkeypatch):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import falcon_h1

    monkeypatch.setattr(falcon_h1, "_DRAW_ROWS", 64)
    std = jnp.asarray([1.0, 3.0] * 4)
    a = falcon_h1._normal(jax.random.PRNGKey(0), (4 * 48, 8), std,
                          jnp.float32)                  # 4 blocks of 48
    assert a.shape == (192, 8)
    np.testing.assert_allclose(a.std(axis=0), std, rtol=0.2)
    assert float(jnp.abs(a[:48] - a[48:96]).max()) > 0.1  # blocks differ


# --------------------------------------------------------- through the engine


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_engine_streams_match_the_reference_and_solo(tiny, ref, backend):
    """Rows that join and leave under continuous batching (six requests,
    four under the kernels' interpreter, through three slots: a slot is
    reused, and a reused slot starts from zeros in BOTH of its leaves):
    every stream is the reference's greedy continuation, together as alone,
    whole as in 3 chunks."""
    import jax.numpy as jnp

    cfg, params = tiny
    prompts = _prompts([5, 70, 100, 61, 33, 90], seed=0)
    news = [8, 4, 8, 8, 3, 6]
    if backend == "pallas":  # the interpreter is slow: four of the six
        prompts, news = prompts[:4], [4, 3, 4, 4]
    engine = _engine(cfg, params, attention_backend=backend,
                     max_batch_size=3)
    streams = [engine.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, news)]
    _drive(engine, streams)
    outs = [list(s) for s in streams]
    for p, o in zip(prompts, outs):
        seq = p + o
        logits = np.asarray(ref.logits(params, jnp.asarray([seq[:-1]]),
                                       cfg))[0]
        assert logits[len(p) - 1:].argmax(-1).tolist() == o
    stats = engine.stats()
    assert stats["state_slots_high_water"] == 3
    chunky = _engine(cfg, params, attention_backend=backend,
                     prefill_chunk_tokens=48, max_batch_size=3)
    again = [chunky.submit(p, max_new_tokens=n)
             for p, n in zip(prompts, news)]
    _drive(chunky, again)
    assert [list(s) for s in again] == outs
    assert chunky.generate(prompts[2], max_new_tokens=news[2]) == outs[2]
    engine.shutdown()
    chunky.shutdown()


def test_a_reused_slot_starts_from_zeros(tiny):
    """A slot whose last sequence left a state behind (matrix and
    convolution rows both poisoned) serves the next as a clean one does."""
    import jax.numpy as jnp

    from ray_tpu.models.falcon_h1 import falcon_h1_init_state

    cfg, params = tiny
    prompts = _prompts([30], seed=2)
    clean, _, _ = _serve_logits(cfg, params, prompts, 4, chunk=16)
    dirty = falcon_h1_init_state(cfg, 3)
    dirty = {"ssd": dirty["ssd"] + 3.0, "conv": dirty["conv"] - 2.0}
    again, _, state = _serve_logits(cfg, params, prompts, 4, chunk=16,
                                    state=dirty)
    np.testing.assert_allclose(again, clean, atol=1e-6)
    fresh, _, _ = _serve_logits(cfg, params, prompts, 4, state=dirty)
    np.testing.assert_allclose(fresh, clean, atol=ATOL)
    # the other slot was left as it stood
    assert float(jnp.abs(state["ssd"][:, 2] - 3.0).max()) == 0.0
    assert float(jnp.abs(state["conv"][:, 2] + 2.0).max()) == 0.0


def test_stats_and_step_attrs(tiny):
    from ray_tpu.models.falcon_h1 import FalconH1Config, step_attrs

    cfg, params = tiny
    engine = _engine(cfg, params)
    prompts = _prompts([70, 20], seed=3)
    streams = [engine.submit(p, max_new_tokens=5) for p in prompts]
    _drive(engine, streams)
    stats = engine.stats()
    desc = engine.executor.describe()
    assert desc["state"]["arrays"]["ssd"] == [2, 5, 4, 16, 32]
    assert desc["state"]["arrays"]["conv"] == [2, 5, 3, 192]
    assert desc["kv_layers"] == 2          # EVERY layer pages too
    assert stats["state_bytes"] == desc["state"]["bytes"] \
        == 2 * 5 * (4 * 16 * 32 * 4 + 3 * 192 * 4)
    assert stats["state_slots_high_water"] == 2
    decodes = [r for r in engine._flight.snapshot()
               if r["kind"] == "decode" and r["batch"]]
    assert sum(r["rows"] for r in decodes) == 8
    assert all(r["state_mb"] == round(
        r["rows"] * 2 * 4 * 16 * 32 * 4 * 2 / 1e6, 3) for r in decodes)
    engine.shutdown()
    big = FalconH1Config(n_layer=5)
    assert step_attrs(big, "decode", [(63, 1), (9000, 1)]) == {
        "rows": 2, "state_mb": round(2 * 5 * 4194304 * 2 / 1e6, 3)}
    assert step_attrs(big, "prefill", [(2048, 2048), (0, 20)]) == {
        "tokens": 2068, "ssd_pieces": 16 + 1}


# ----------------------------------------------------------- refusals


def test_a_sequence_is_not_split_over_rows(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    why = engine.cache.cfg.why_not_split
    assert "state rows" in why and "matrix state" in why
    assert not engine.cache.cfg.one_table
    assert engine.cache.cfg.state_slots == 5
    engine.shutdown()


@pytest.mark.parametrize("option,match", [
    ({"speculative_k": 2}, "per-sequence state.*speculative_k.*rolled back"),
    ({"host_cache_bytes": 1 << 20},
     "per-sequence state.*host_cache_bytes.*state at its"),
    ({"preemption": "swap"}, "per-sequence state.*preemption.*state slot"),
    ({"quantization": "int8"},
     "per-sequence state.*quantization.*ssm.*quantized path"),
    ({"tp": 2}, "per-sequence state.*tp/fsdp/mesh.*state arrays"),
])
def test_unsupported_options_are_refused_by_name(tiny, option, match):
    cfg, params = tiny
    with pytest.raises(ValueError, match=match):
        _engine(cfg, params, **option)


def test_prefix_reuse_is_off_handoff_refused_and_no_verify_step(tiny):
    from ray_tpu.models import falcon_h1
    from ray_tpu.serve.llm.decode import get_family

    cfg, params = tiny
    engine = _engine(cfg, params)
    prompt = _prompts([40], seed=4)[0]
    a = engine.generate(prompt, max_new_tokens=3)
    b = engine.generate(prompt, max_new_tokens=3)
    assert a == b
    assert engine.cache.peek_prefix(prompt) == 0
    st = engine.stats()
    assert st["prefix_reuse"] is False and st["prefix_hit_tokens"] == 0
    assert "per-sequence state" in st["prefix_reuse_why_not"]
    with pytest.raises(ValueError):
        engine.export_prefix(prompt)
    engine.shutdown()
    assert get_family("falcon_h1").verify_step is None
    assert "rolled back" in falcon_h1.FAMILY.no_verify
    assert not hasattr(falcon_h1, "falcon_h1_verify_step")


def test_scopes_name_the_ssd_parts(tiny):
    """The five ``ssd_*`` names are in the vocabulary and reach the
    programs: a decode program names proj, conv, step and out BESIDE the
    attention kernel's; a prefill program the chunked form."""
    from ray_tpu.serve.llm import obs

    names = ("ssd_proj", "ssd_conv", "ssd_step", "ssd_chunk", "ssd_out")
    for name in names:
        assert name in obs.SCOPES
    cfg, params = tiny
    engine = _engine(cfg, params)
    engine.generate(_prompts([20], seed=1)[0], max_new_tokens=3)
    scopes = engine.program_scopes()
    engine.shutdown()
    seen = {kind: set() for kind in ("prefill", "decode")}
    for program in scopes.values():
        kind = "decode" if "decode" in program["name"] else "prefill"
        assert program["name"].startswith("jit_falcon_h1_")
        seen[kind] |= {entry[0] for entry in program["scopes"].values()}
    assert {"ssd_proj", "ssd_conv", "ssd_step", "ssd_out", "attn_kernel",
            "attn_cache", "ffn", "head"} <= seen["decode"]
    assert {"ssd_proj", "ssd_conv", "ssd_chunk", "ssd_out",
            "attn_kernel"} <= seen["prefill"]
