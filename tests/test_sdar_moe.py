"""The sdar_moe family on the CPU at the tiny preset, seeded weights,
float32: the program against the plain reference
(benchmark/reference/sdar_moe.py), the cached step (chunked prefill under
the block mask, then block passes through the paged cache, rows at
different phases) against the reference's ``generate`` in tokens AND in the
logits of every choosing pass, the engine (every ``len(prompt) % 4``, every
output residue, 1 / 2 / 4 steps, the three orders, a request that overrides
them, batched against solo, the mask's id as an ordinary token, prefix
reuse at page boundaries, EOS inside a block, a cancel mid-block, the
dispatch lag kept), the FOLD (a finished block committed by the next
block's first pass: its K/V bit for bit a separate commit's, the schedule's
pass counts, what a fold wastes and gives back), what the reference
notices, what the engine refuses, and the counters.

Program and reference in float32 compute the same mathematics and differ in
the order of sums: 1e-4 on logits of size ~4 (seen 5e-6).

ONE model configuration serves the whole file (its mask id, 215, is an id
these weights choose by themselves; its confidence threshold, 0.02, one a
tiny vocabulary's confidences pass now and then), and ONE engine shape:
steps and order are a request's DATA, so the file compiles three step
programs.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

W = 4           # the block
MASK_ID = 215   # an id the seeded weights choose: ``test_the_masks_id_...``
PAD = 64        # the reference's one compiled length


@pytest.fixture(scope="module")
def ref():
    from benchmark import common

    return common.load_named("reference", "sdar_moe")


@pytest.fixture(scope="module")
def tiny(jax_cpu):
    """(float32 tiny config: 2 steps, sequential; its seeded params)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.sdar_moe import SdarMoeConfig, sdar_moe_init

    cfg = dataclasses.replace(
        SdarMoeConfig.tiny(), dtype=jnp.float32, denoising_steps=2,
        remasking="sequential", mask_token_id=MASK_ID,
        confidence_threshold=0.02)
    return cfg, sdar_moe_init(jax.random.PRNGKey(1), cfg)


def _engine(cfg, params, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    settings = dict(model="sdar_moe", model_config=cfg, block_size=8,
                    num_blocks=129, max_batch_size=4, max_prefill_batch=2,
                    prefill_chunk_tokens=16, length_buckets=(64,),
                    batch_buckets=(4,))
    settings.update(kw)
    auto = settings.pop("auto_step", False)
    return LLMEngine(EngineConfig(**settings), params=params,
                     auto_step=auto)


@pytest.fixture(scope="module")
def engine(tiny):
    eng = _engine(*tiny)
    yield eng
    eng.shutdown()


def _prompts(lens, seed=0, vocab=512):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).tolist() for n in lens]


def _drive(engine, streams, limit=4000):
    for _ in range(limit):
        if all(s.done for s in streams):
            return
        engine.step()
    raise AssertionError("streams did not finish")


def _serve(engine, prompts, news, **sampling):
    streams = [engine.submit(p, max_new_tokens=n, **sampling)
               for p, n in zip(prompts, news)]
    _drive(engine, streams)
    return [list(s) for s in streams]


# ------------------------------------------------------ the configuration


def test_the_whole_model_is_the_rows_30_billion(jax_cpu):
    """The published widths give the catalog's count: 30.5 B parameters,
    of which 623.1 M a layer and 622.3 M in embedding and head."""
    import jax

    from ray_tpu.models.sdar_moe import SdarMoeConfig, sdar_moe_init

    cfg = SdarMoeConfig()
    shapes = jax.eval_shape(
        lambda: sdar_moe_init(jax.random.PRNGKey(0),
                              dataclasses.replace(cfg, n_layer=1)))
    layer = sum(x.size for x in jax.tree.leaves(shapes["layers"][0]))
    ends = shapes["wte"].size + shapes["lm_head"].size
    assert abs(layer - 623.1e6) < 0.1e6 and abs(ends - 622.3e6) < 0.1e6
    assert abs(48 * layer + ends - 30.53e9) < 0.02e9
    assert abs(6 * layer + ends - 4361e6) < 2e6


@pytest.mark.parametrize("block,steps,counts", [
    (4, 2, (2, 2)), (4, 1, (4,)), (4, 4, (1, 1, 1, 1)), (4, None, (1,) * 4),
    (4, 3, (2, 1, 1)), (8, 3, (3, 3, 2)), (4, 6, (1, 1, 1, 1, 0, 0)),
])
def test_fill_counts_is_the_routines_schedule(jax_cpu, ref, block, steps,
                                              counts):
    """``block // steps`` a pass, a remainder to the first passes: one
    function in the program and one in the reference, the same numbers; a
    first block whose head is the prompt's tail takes fewer passes."""
    from ray_tpu.ops.sampling import fill_counts, pass_fills

    assert fill_counts(block, steps) == counts == ref.fill_counts(
        block, steps)
    assert sum(counts) == block
    for masked in range(1, block + 1):
        fills = pass_fills(masked, counts)
        assert sum(fills) == masked and all(fills)
        assert fills == pass_fills(masked, fills + [9])[:len(fills)]


def test_a_config_refuses_what_the_schedule_cannot_mean(jax_cpu):
    from ray_tpu.models.sdar_moe import SdarMoeConfig

    with pytest.raises(ValueError, match="remasking"):
        SdarMoeConfig(remasking="random")
    with pytest.raises(ValueError, match="block_length"):
        SdarMoeConfig(block_length=31)
    with pytest.raises(ValueError, match="mask_token_id"):
        SdarMoeConfig(mask_token_id=151936)
    with pytest.raises(ValueError, match="denoising_steps"):
        SdarMoeConfig(denoising_steps=0)
    with pytest.raises(ValueError, match="confidence_threshold"):
        SdarMoeConfig(confidence_threshold=1.5)


# ------------------------------------------------- choose-and-unmask alone


def test_unmask_tokens_fills_by_the_rows_own_order(jax_cpu):
    """One call, six rows, each its own schedule: sequential takes the
    first ``fill`` masked positions; the static order the most confident;
    the dynamic order those and every one past the threshold (no more than
    ``fill`` where none passes it); a row with nothing masked gets its NEXT
    block, all masks; an id equal to the mask's at an unmasked position is
    an ordinary token and stays."""
    import jax.numpy as jnp

    from ray_tpu.ops.sampling import REMASKING, unmask_tokens

    V = 8
    peak = np.array([[0.2, 0.9, 0.5, 0.7]] * 5 + [[0.2, 0.3, 0.5, 0.4]],
                    np.float32)  # confidence
    logits = np.zeros((6, W, V), np.float32)
    for j in range(W):
        # position j prefers token j + 1 by a margin that grows with peak
        logits[:, j, j + 1] = np.log(peak[:, j] * (V - 1) / (1 - peak[:, j]))
    ids = np.array([[7, 7, 7, 7], [7, 7, 7, 7], [7, 7, 7, 7], [5, 7, 6, 3],
                    [7, 7, 7, 7], [7, 7, 7, 7]], np.int32)
    masked = np.array([0b1111, 0b1111, 0b1111, 0b0000, 0b1110, 0b1111],
                      np.int32)
    sample = {
        "seeds": np.zeros(6, np.uint32), "temperature": np.zeros(6, np.float32),
        "top_k": np.zeros(6, np.int32), "top_p": np.ones(6, np.float32),
        "fill": np.array([2, 2, 1, 0, 1, 1], np.int32),
        "remasking": np.array([REMASKING.index(m) for m in (
            "sequential", "low_confidence_static", "low_confidence_dynamic",
            "sequential", "sequential", "low_confidence_dynamic")], np.int32),
    }
    out = np.asarray(unmask_tokens(
        jnp.asarray(logits), jnp.asarray(ids), jnp.asarray(masked),
        jnp.zeros((6, W), jnp.int32), sample, mask_token_id=7,
        threshold=0.6))
    assert out[0].tolist() == [1, 2, 7, 7, 0b1100]   # the first two
    assert out[1].tolist() == [7, 2, 7, 4, 0b0101]   # the most confident
    assert out[2].tolist() == [7, 2, 7, 4, 0b0101]   # 0.9; and 0.7 > 0.6
    assert out[3].tolist() == [7, 7, 7, 7, 0b1111]   # committed: next block
    # position 0 holds the mask's ID and is NOT masked: it stays
    assert out[4].tolist() == [7, 2, 7, 7, 0b1100]
    assert out[5].tolist() == [7, 7, 3, 7, 0b1011]   # none passes 0.6: one


# -------------------------------------------- the program and the reference


def test_full_forward_matches_the_reference(tiny, ref):
    """The program's full forward against the reference in float32 (1e-5
    a logit of size ~4) and in bfloat16 (a looser bound, said below)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.sdar_moe import sdar_moe_forward

    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 22), 1,
                                cfg.vocab_size)
    want = ref.logits(params, tokens, cfg)
    with jax.default_matmul_precision("highest"):
        got = sdar_moe_forward(params, tokens, cfg)
    assert float(jnp.abs(want).max()) > 2.0
    assert float(jnp.abs(got - want).max()) < 1e-5
    # bfloat16: seven bits of mantissa through three layers move a logit
    # by ~0.02; where rounding flips one of a token's 3 experts of 8, by
    # up to ~1.5 (seen: median 0.02, largest 1.5)
    half = jnp.abs(sdar_moe_forward(
        params, tokens, dataclasses.replace(cfg, dtype=jnp.bfloat16)) - want)
    assert float(jnp.median(half)) < 0.05 and float(half.max()) < 3.0


def test_logits_at_is_generates_own_record(tiny, ref):
    """``logits_at`` (what the chip's reference check reads, from the
    FINISHED sequence alone, two streams) gives the logits ``generate``
    recorded when it chose each token, for every ``len(prompt) % 4``."""
    import jax.numpy as jnp

    cfg, params = tiny
    for prompt in _prompts([5, 6, 7, 8, 2], seed=2):
        L, new = len(prompt), 10
        g = ref.generate(params, prompt, new, cfg, pad_to=PAD)
        seq = np.zeros((1, PAD), np.int32)
        seq[0, :L + new] = prompt + g["tokens"]
        at = (L + np.arange(new) - 1)[None]
        got = np.asarray(ref.logits_at(
            params, jnp.asarray(seq), jnp.asarray(at), cfg))[0]
        assert np.abs(got - g["logits"]).max() < 1e-5
        assert got.argmax(-1).tolist() == g["tokens"]
    with pytest.raises(ValueError, match="sequential"):
        ref.logits_at(params, jnp.asarray(seq), jnp.asarray(at),
                      dataclasses.replace(
                          cfg, remasking="low_confidence_static"))


def _wrong_logits(ref, params, cfg, change, monkeypatch, prompt, g):
    """The logits a WRONG model gives where ``generate`` chose token 5 (the
    second generated block's first pass), each a reading the
    configuration's ``assumed`` sets aside."""
    import jax.numpy as jnp

    L, new = len(prompt), len(g["tokens"])
    seq = np.zeros((1, PAD), np.int32)
    seq[0, :L + new] = prompt + g["tokens"]
    seq = jnp.asarray(seq)
    at = jnp.asarray((L + np.arange(new) - 1)[None])
    if change == "causal_for_block_mask":
        monkeypatch.setattr(ref, "sees", lambda pos, t, cfg: t <= pos)
    elif change == "qk_norm_dropped":
        monkeypatch.setattr(ref, "qk_norm", lambda x, scale, cfg: x)
    elif change == "weights_not_renormalised":
        cfg = dataclasses.replace(cfg, norm_topk_prob=False)
    elif change == "shifted_logits":
        # the autoregressive reading: the logits at p - 1 choose token p
        return np.asarray(ref.logits_at(params, seq, at - 1, cfg))[0]
    elif change == "provisional_kv_kept":
        # the K/V a later block reads of an earlier one is what its LAST
        # DENOISING pass wrote (its last-filled positions still masks),
        # not the commit pass's: the clean stream with those flagged
        flags = np.zeros((1, PAD), bool)
        for k, s in enumerate(g["passes"]):
            flags[0, L + k] = s == max(g["passes"])
        flags[0, (L + new) // W * W:] = False  # the block being denoised
        masked = flags.copy()
        masked[0, L + 8:] = True  # token 8's block before its first pass
        return np.asarray(ref.logits(
            params, seq, cfg, jnp.asarray(masked)))[0, L:L + new]
    return np.asarray(ref.logits_at(params, seq, at, cfg))[0]


@pytest.mark.parametrize("change", [
    "causal_for_block_mask", "shifted_logits", "provisional_kv_kept",
    "qk_norm_dropped", "weights_not_renormalised"])
def test_the_reference_notices_each_mechanism(tiny, ref, change,
                                              monkeypatch):
    """A causal mask for the block mask, the autoregressive shift, a
    block's K/V kept from a denoising pass instead of the commit's, the
    norm over a head dropped, expert weights not renormalised over the
    chosen: each moves the choosing logits far past the 1e-4 the program
    is held to, in float32 where rounding cannot hide it."""
    cfg, params = tiny
    prompt = _prompts([8], seed=4)[0]
    g = ref.generate(params, prompt, 12, cfg, pad_to=PAD)
    wrong = _wrong_logits(ref, params, cfg, change, monkeypatch, prompt, g)
    # token 8: the third block's first pass, blocks of answers before it
    assert np.abs(wrong[8] - g["logits"][8]).max() > 0.02, change


# ------------------------------ the cached step, rows at different phases


def test_cached_passes_match_generates_choosing_logits(tiny, ref):
    """The family's two step functions by hand, float32: a prompt's whole
    blocks in chunks of 8 (the second against the first, resident), then
    block passes through the paged pool over TWO rows whose first blocks
    differ (2 and 3 masked positions: they commit on different passes, so
    one call holds a denoising row beside a committing one). At every
    pass that fills a position the logits AT it are those ``generate``
    recorded for it (1e-4), the chosen tokens its tokens."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import sdar_moe
    from ray_tpu.models.sdar_moe import sdar_moe_init_state
    from ray_tpu.ops.sampling import fill_counts, pass_fills
    from ray_tpu.ops.paged_attention import pool_shape

    cfg, params = tiny
    sdar_moe_prefill = jax.jit(
        functools.partial(sdar_moe.sdar_moe_prefill, cfg=cfg))
    sdar_moe_decode_step = jax.jit(
        functools.partial(sdar_moe.sdar_moe_decode_step, cfg=cfg))
    prompts = _prompts([14, 17], seed=7)
    new = 9
    want = [ref.generate(params, p, new, cfg, pad_to=PAD) for p in prompts]
    bs, nb = 8, 6
    k = jnp.zeros(pool_shape(cfg.n_layer, 13, bs, cfg.n_kv_head,
                             cfg.head_dim), jnp.float32)
    v = jnp.zeros_like(k)
    state = sdar_moe_init_state(cfg, 0)
    tables = jnp.asarray([[1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12]])
    slots = jnp.ones((2,), jnp.int32)
    # prefill: the prompts' whole blocks (12 and 16 tokens), chunks of 8
    for first in (0, 8):
        toks = np.zeros((2, 8), np.int32)
        lens = np.zeros((2,), np.int32)
        for i, p in enumerate(prompts):
            part = p[first:min(first + 8, len(p) // W * W)]
            toks[i, :len(part)], lens[i] = part, len(part)
        out, k, v, state = sdar_moe_prefill(
            params, k, v, jnp.asarray(toks), jnp.asarray(lens), tables,
            start=jnp.full((2,), first, jnp.int32), state=state,
            slots=slots)
        assert out.shape == (2, 8, cfg.vocab_size)  # sample=None: logits
    counts = fill_counts(W, cfg.denoising_steps)
    rows = []
    for p in prompts:
        lead = len(p) % W
        rows.append({"start": len(p) - lead, "lead": lead,
                     "x": p[len(p) - lead:] + [MASK_ID] * (W - lead),
                     "masked": [o >= lead for o in range(W)],
                     "fills": pass_fills(W - lead, counts), "p": 0,
                     "tokens": [], "logits": []})
    saw_mixed = False
    for _ in range(9):  # 3 + 3 + 3 passes: the slower row's three blocks
        ids = np.asarray([r["x"] + [sum(
            1 << o for o in range(W) if r["masked"][o])] for r in rows],
            np.int32)
        logits, k, v, state = sdar_moe_decode_step(
            params, k, v, jnp.asarray(ids),
            jnp.asarray([r["start"] for r in rows], jnp.int32), tables,
            state=state, slots=slots)
        logits = np.asarray(logits)
        commits = [r["p"] == len(r["fills"]) for r in rows]
        saw_mixed |= commits[0] != commits[1]
        for r, z, commit in zip(rows, logits, commits):
            if commit:
                r["tokens"] += r["x"][r["lead"]:]
                r.update(start=r["start"] + W, lead=0, x=[MASK_ID] * W,
                         masked=[True] * W, fills=list(counts), p=0)
                continue
            todo = [o for o in range(W) if r["masked"][o]][:r["fills"][r["p"]]]
            for o in todo:
                r["x"][o], r["masked"][o] = int(z[o].argmax()), False
                r["logits"].append((r["start"] + o, z[o]))
            r["p"] += 1
    assert saw_mixed
    for r, p, g in zip(rows, prompts, want):
        assert r["tokens"][:new] == g["tokens"]
        chose = dict(r["logits"])
        for j in range(new):
            assert np.abs(chose[len(p) + j] - g["logits"][j]).max() < 1e-4


def _greedy(fill, mode=0):
    """``sample`` for rows that fill ``fill`` positions, greedy."""
    n = len(fill)
    return {"seeds": np.zeros(n, np.uint32),
            "temperature": np.zeros(n, np.float32),
            "top_k": np.zeros(n, np.int32), "top_p": np.ones(n, np.float32),
            "fill": np.asarray(fill, np.int32),
            "remasking": np.full(n, mode, np.int32)}


def test_a_fold_commits_what_a_pass_of_its_own_commits(tiny):
    """The decode program both ways on ONE pool, float32: row 0's block is
    finished and another is due. Apart: a commit pass (``fill`` 0), then
    the next block's first pass over all masks. Folded: one pass, the
    finished ids with no bit set and ``fill`` 2. The finished block's K/V
    rows in the pool are the same BIT FOR BIT, and so are the fresh
    block's provisional rows, its chosen ids and its bits. Row 1 is
    mid-block beside it (a denoising row: its other W columns padding) and
    gets the same either way; the experts count VALID positions alone."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import sdar_moe
    from ray_tpu.models.sdar_moe import sdar_moe_counters, sdar_moe_init_state
    from ray_tpu.ops.paged_attention import pool_shape

    cfg, params = tiny
    prefill = jax.jit(functools.partial(sdar_moe.sdar_moe_prefill, cfg=cfg))
    decode = jax.jit(functools.partial(sdar_moe.sdar_moe_decode_step, cfg=cfg))
    prompts = _prompts([12, 16], seed=11)
    bs = 8
    k = jnp.zeros(pool_shape(cfg.n_layer, 9, bs, cfg.n_kv_head,
                             cfg.head_dim), jnp.float32)
    v = jnp.zeros_like(k)
    state = sdar_moe_init_state(cfg, 0)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]])
    slots = jnp.ones((2,), jnp.int32)
    toks = np.zeros((2, 16), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    _, k, v, state = prefill(
        params, k, v, jnp.asarray(toks), jnp.asarray([12, 16], jnp.int32),
        tables, start=jnp.zeros((2,), jnp.int32), sample=_greedy([0, 0]),
        state=state, slots=slots)
    starts = jnp.asarray([12, 16], jnp.int32)
    x = jnp.asarray([[MASK_ID] * W + [0b1111]] * 2, jnp.int32)
    # row 0: two denoising passes finish its block; row 1 is held back a
    # pass (``fill`` 0 over a masked block changes nothing)
    for fill in ([2, 0], [2, 2]):
        x, k, v, state = decode(params, k, v, x, starts, tables,
                                sample=_greedy(fill), state=state, slots=slots)
    assert x[0, W] == 0 and x[1, W] == 0b1100
    pairs0 = int(sdar_moe_counters(state)["moe_pairs_decode"])
    # apart: the commit (row 1 denoises on), then the fresh block's pass
    xa, ka, va, sa = decode(params, k, v, x, starts, tables,
                            sample=_greedy([0, 2]), state=state, slots=slots)
    assert xa[0].tolist() == [MASK_ID] * W + [0b1111] and xa[1, W] == 0
    committed = (np.asarray(ka), np.asarray(va))
    # (row 1 sits this one out: a table of the garbage block, slot 0)
    xa2, ka, va, _ = decode(
        params, ka, va, jnp.asarray([[MASK_ID] * W + [0b1111]] * 2),
        jnp.asarray([16, 0], jnp.int32), tables.at[1].set(0),
        sample=_greedy([2, 0]), state=sa, slots=jnp.asarray([1, 0]))
    # folded: one pass
    xb, kb, vb, sb = decode(params, k, v, x, starts, tables,
                            sample=_greedy([2, 2]), state=state, slots=slots)
    assert xb[0].tolist() == xa2[0].tolist() and xb[0, W] == 0b1100
    assert xb[1].tolist() == xa[1].tolist()
    for apart, folded, first in ((committed[0], kb, ka), (committed[1], vb, va)):
        folded = np.asarray(folded)
        # the finished block [12, 16): page 2's second half; the denoising
        # row's block [16, 20): page 7's first half. A commit's, exactly
        assert np.array_equal(folded[:, 2, 4:], apart[:, 2, 4:])
        assert np.array_equal(folded[:, 7, :4], apart[:, 7, :4])
        assert np.abs(folded[:, 2, 4:]).max() > 0
        # the fresh block [16, 20): page 3's first half, as its own first
        # pass wrote it; nothing else of a real page moved
        assert np.array_equal(folded[:, 3, :4], np.asarray(first)[:, 3, :4])
        assert np.array_equal(folded[:, 1:], np.asarray(first)[:, 1:])
    # 8 + 4 valid positions were routed, the padding columns nowhere
    assert int(sdar_moe_counters(sb)["moe_pairs_decode"]) - pairs0 == (
        12 * cfg.top_k * cfg.n_layer)


# ----------------------------------------------------- the engine, served


CASES = {
    # every len(prompt) % 4 (a prompt shorter than a block among them) and
    # every residue of max_new_tokens, under the configuration's schedule
    "sequential-2": ({}, [5, 6, 7, 8, 2, 21], [10, 7, 9, 12, 5, 6]),
    # a request overrides the steps: all at once; one position a pass
    "sequential-1": ({"denoising_steps": 1}, [9, 14, 3], [11, 8, 6]),
    "sequential-4": ({"denoising_steps": 4}, [9, 14, 3], [11, 8, 6]),
    "sequential-3": ({"denoising_steps": 3}, [10, 7], [9, 10]),
    # the confidence orders: the static one keeps the dispatch lag, the
    # dynamic one is the device's to time (the configuration's threshold)
    "static-2": ({"remasking": "low_confidence_static"},
                 [5, 6, 7, 8], [10, 7, 9, 12]),
    "static-4": ({"remasking": "low_confidence_static",
                  "denoising_steps": 4}, [6, 11], [8, 9]),
    "dynamic-4": ({"remasking": "low_confidence_dynamic",
                   "denoising_steps": 4}, [5, 6, 7, 8], [10, 7, 9, 12]),
    "dynamic-2": ({"remasking": "low_confidence_dynamic",
                   "denoising_steps": 2}, [6, 11], [8, 9]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_serves_what_generate_generates(tiny, ref, engine, case):
    """Through ``submit`` / streams: chunked prefill under the block mask,
    then blocks through the paged cache, four rows a batch at different
    phases, against the reference's ``generate`` a request: the same
    tokens. Steps and order are the REQUEST's: one engine, the same three
    programs, whatever the case."""
    cfg, params = tiny
    sampling, lens, news = CASES[case]
    prompts = _prompts(lens, seed=sorted(CASES).index(case))
    compiled = engine.num_compiled_shapes
    got = _serve(engine, prompts, news, **sampling)
    for p, n, out in zip(prompts, news, got):
        want = ref.generate(
            params, p, n, cfg, steps=sampling.get("denoising_steps"),
            remasking=sampling.get("remasking"), pad_to=PAD)
        assert out == want["tokens"], (case, len(p), n)
    if compiled:
        assert engine.num_compiled_shapes == compiled
    assert engine.stats()["kv_used_blocks"] == 0


def test_rows_of_one_batch_have_their_own_schedules(tiny, ref, engine):
    """Mixed rows are what one program with the phase as data is for: four
    requests, four schedules, one batch; each stream is its solo run's and
    the reference's. The static schedules ride the dispatch lag."""
    cfg, params = tiny
    prompts = _prompts([9, 6, 11, 4], seed=21)
    settings = [{}, {"denoising_steps": 1}, {"denoising_steps": 4},
                {"remasking": "low_confidence_static"}]
    before = engine.stats()
    streams = [engine.submit(p, max_new_tokens=10, **s)
               for p, s in zip(prompts, settings)]
    _drive(engine, streams)
    after = engine.stats()
    steps = after["decode_steps"] - before["decode_steps"]
    assert steps and (after["decode_steps_steady"]
                      - before["decode_steps_steady"]) >= steps - 1
    for p, s, stream in zip(prompts, settings, streams):
        want = ref.generate(params, p, 10, cfg,
                            steps=s.get("denoising_steps"),
                            remasking=s.get("remasking"), pad_to=PAD)
        assert list(stream) == want["tokens"]
        assert _serve(engine, [p], [10], **s)[0] == want["tokens"]


def test_the_masks_id_is_an_ordinary_token(tiny, ref, engine):
    """Whether a position is masked is a bit, never ``id == mask``: a
    prompt that HOLDS the mask's id (uniform ids over a real vocabulary
    do), and an answer in which the model CHOOSES it (these weights choose
    215 for this prompt), are served as the reference generates them."""
    cfg, params = tiny
    prompt = _prompts([9], seed=5)[0]
    want = ref.generate(params, prompt, 12, cfg, pad_to=PAD)
    assert MASK_ID in want["tokens"]          # chosen, and kept
    assert _serve(engine, [prompt], [12])[0] == want["tokens"]
    holds = prompt[:3] + [MASK_ID] + prompt[3:6] + [MASK_ID, MASK_ID]
    want = ref.generate(params, holds, 9, cfg, pad_to=PAD)
    assert _serve(engine, [holds], [9])[0] == want["tokens"]


def test_a_prefix_is_reused_at_page_boundaries(tiny, ref, engine):
    """A page of 8 tokens is two whole blocks, so its K/V depends on no
    token past the page: a second prompt that shares 16 tokens maps them
    onto the first's pages (no compute), a prompt that IS two whole pages
    prefills nothing at all, and both generate what the reference does."""
    cfg, params = tiny
    shared = _prompts([16], seed=31)[0]
    tails = _prompts([5, 7], seed=32)
    first = _serve(engine, [shared + tails[0]], [8])[0]
    before = engine.stats()
    outs = _serve(engine, [shared + tails[1], shared], [8, 6])
    after = engine.stats()
    assert after["prefix_hit_tokens"] - before["prefix_hit_tokens"] == 32
    # 20 whole-block tokens of the second prompt, less the 16 mapped
    assert (after["prefill_tokens_total"]
            - before["prefill_tokens_total"]) == 4
    for p, n, out in zip([shared + tails[0], shared + tails[1], shared],
                         [8, 8, 6], [first, *outs]):
        assert out == ref.generate(params, p, n, cfg, pad_to=PAD)["tokens"]


def test_host_tier_and_handoff_move_whole_pages(tiny, ref):
    """What moves hashed pages works for this family as it is, because a
    page is whole blocks: a prefix demoted to the host tier by churn is
    promoted back on its re-hit, and a prefix exported by one engine and
    adopted by another is a full hit there; every stream is the
    reference's."""
    cfg, params = tiny
    prefix = _prompts([32], seed=50)[0]
    asks = [prefix + [1, 2, 3], prefix + [9, 9, 9]]
    want = [ref.generate(params, p, 6, cfg, pad_to=PAD)["tokens"]
            for p in asks]
    eng = _engine(cfg, params, host_cache_bytes=1 << 22)
    assert _serve(eng, asks[:1], [6])[0] == want[0]
    for filler in _prompts([60] * 20, seed=51):  # runs the pool dry
        _serve(eng, [filler], [2])
    assert _serve(eng, asks[1:], [6])[0] == want[1]
    st = eng.stats()
    assert st["kv_demoted_blocks"] >= 4 and st["kv_promoted_blocks"] >= 4
    records = eng.export_prefix(asks[1])
    assert len(records) == 4  # the prompt's whole pages
    eng.shutdown()
    other = _engine(cfg, params)
    assert other.adopt_prefix(asks[1], records) == 4
    assert _serve(other, asks[1:], [6])[0] == want[1]
    st = other.stats()
    assert st["prefix_hit_tokens"] == 32 and st["prefill_tokens_total"] == 0
    other.shutdown()


def test_eos_inside_a_block_cuts_the_stream_behind_it(tiny, ref):
    """EOS inside a committed block ends the stream behind it; what the
    block held past it was generated and is not delivered, and the passes
    launched behind the commit give their blocks back."""
    cfg, params = tiny
    prompt = _prompts([9], seed=5)[0]
    want = ref.generate(params, prompt, 12, cfg, pad_to=PAD)["tokens"]
    eos = want[4]  # the second block's second token
    at = want.index(eos)
    eng = _engine(cfg, params, eos_id=eos)
    assert _serve(eng, [prompt], [12])[0] == want[:at + 1]
    while eng.step():  # the pass launched behind the commit is reconciled
        pass
    st = eng.stats()
    assert st["block_tokens_cut"] == (
        st["blocks_committed"] * W - len(prompt) % W - (at + 1))
    assert st["kv_used_blocks"] == 0 and st["decode_inflight"] == 0
    eng.shutdown()


def test_a_cancelled_row_drops_its_provisional_block(tiny, engine):
    prompt = _prompts([13], seed=9)[0]
    stream = engine.submit(prompt, max_new_tokens=40)
    for _ in range(6):
        engine.step()
    assert engine.stats()["kv_used_blocks"] > 0
    assert engine.cancel(stream.request_id)
    for _ in range(4):
        engine.step()
    st = engine.stats()
    assert st["kv_used_blocks"] == 0 and st["decode_inflight"] == 0
    from ray_tpu.exceptions import RequestCancelledError

    with pytest.raises(RequestCancelledError):
        list(stream)


def test_the_stepping_thread_serves_what_hand_steps_do(tiny, ref):
    """Pipelined == hand-stepped: the serving mode's own thread (the lag
    kept: two programs in flight, every decode step launched behind one)
    gives the streams of ``step()`` by hand."""
    cfg, params = tiny
    prompts = _prompts([5, 18, 7, 12], seed=41)
    news = [22, 9, 14, 30]
    eng = _engine(cfg, params, auto_step=True)
    streams = [eng.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, news)]
    got = [list(s) for s in streams]
    deadline = time.monotonic() + 30
    while eng.stats()["decode_inflight"] and time.monotonic() < deadline:
        time.sleep(0.01)
    st = eng.stats()
    eng.shutdown()
    assert st["steps_inflight_high_water"] == 2
    assert st["decode_steps_steady"] >= st["decode_steps"] - 2
    for p, n, out in zip(prompts, news, got):
        assert out == ref.generate(params, p, n, cfg, pad_to=PAD)["tokens"]


# ------------------------------------------------- counters, spans, clocks


def _spy_on_dispatch(monkeypatch):
    """The attributes of every ``executor.dispatch`` span from here on."""
    import ray_tpu.serve.llm.executor as executor
    from ray_tpu.serve.llm import obs

    spans = []
    real = obs.phase

    def spy(table, name, **attrs):
        if name == "executor.dispatch":
            spans.append(dict(attrs))
        return real(table, name, **attrs)

    monkeypatch.setattr(obs, "phase", spy)
    monkeypatch.setattr(executor.obs, "phase", spy)
    return spans


def test_counters_spans_and_clocks_count_blocks(tiny, monkeypatch):
    """``stats()`` counts row-passes, those that chose no token (a
    request's last commit), those that folded, blocks and tokens (cut
    ones apart); a block step's ``executor.dispatch`` span says ``kind``
    ``decode``, its rows, the contexts to the END of what the rows carry
    (a folding row's: its NEXT block's) in whole pages, the block's
    length, the rows that commit alone, the rows that fold and the tokens
    both deliver; a committed block reaches its stream under ONE
    timestamp (TTFT is the first block's; the gaps inside a block are 0),
    and the expert counters count a pass's VALID positions as decode."""
    from benchmark.layer_metrics.block_attn_hbm_pct import block_attn_bytes

    cfg, params = tiny
    eng = _engine(cfg, params)
    spans = _spy_on_dispatch(monkeypatch)
    prompts = _prompts([6, 9], seed=3)
    streams = [eng.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, [7, 9])]
    _drive(eng, streams)
    st = eng.stats()
    # row 0: lead 2: blocks of 2 + 4 + (1 of 4); row 1: lead 1: 3 + 4 + (2)
    assert st["blocks_committed"] == 6
    assert st["block_tokens_committed"] == 16 == st["host"]["emit_rows"]
    assert st["block_tokens_cut"] == 3 + 2
    # a request's last block alone is committed by a pass of its own
    assert st["block_passes_commit"] == 2
    assert st["block_passes_folded"] == 2 + 2
    # 2 passes a block and the last commit; a first block of 2 masked
    # takes 1 pass, of 3: 2
    assert st["block_passes"] == (1 + 2 + 2 + 1) + (2 + 2 + 2 + 1)
    # a folding row routes two blocks' positions
    assert st["moe_pairs_decode"] == (
        st["block_passes"] + st["block_passes_folded"]) * W * cfg.top_k \
        * cfg.n_layer
    assert st["executor"]["generation"] == {
        "kind": "block_diffusion", "block_length": 4, "denoising_steps": 2,
        "remasking": "sequential", "confidence_threshold": 0.02,
        "step_positions": 8}
    assert st["moe_gmm_form"]["decode@4x8"] == "ragged"
    decodes = [s for s in spans if s.get("kind") == "decode"]
    assert decodes and all(s["block_len"] == W for s in decodes)
    assert sum(s["rows"] for s in decodes) == st["block_passes"]
    assert sum(s["rows_commit"] for s in decodes) == 2
    assert sum(s["rows_folded"] for s in decodes) == 4
    assert sum(s["tokens_committed"] for s in decodes) == 16
    # the first pass: blocks [4, 8) and [8, 12): one and two pages of 8
    assert decodes[0]["kv_tokens"] == 8 + 16
    assert decodes[0]["rows_folded"] == 0
    # the second: row 0 folds, [4, 8) and [8, 12) behind it: to 12, two
    # pages; row 1 fills the last position of [8, 12)
    assert decodes[1]["rows_folded"] == 1
    assert decodes[1]["kv_tokens"] == 16 + 16
    assert block_attn_bytes(24, cfg.n_kv_head, cfg.head_dim, 4,
                            cfg.n_layer) == 24 * 2 * 2 * 16 * 4 * 3
    records = [r for r in eng.debug_dump()["steps"]
               if r.get("kind") == "decode" and r.get("batch")]
    assert all(r["block_len"] == W and "rows_commit" in r
               and "rows_folded" in r for r in records)
    assert sum(r["rows_folded"] for r in records) == 4
    for s in streams:
        tl = eng.request_timeline(s.request_id)
        stamps = [e["ts"] for e in tl["events"]
                  if e["event"] in ("first_token", "token")]
        assert len(stamps) == len(list(s)) and len(set(stamps)) == 3
    eng.shutdown()


# ----------------------------------- the fold: the schedule and its wastes


ORDERS = ("sequential", "low_confidence_static", "low_confidence_dynamic")


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_a_request_takes_its_steps_a_block_and_one_commit(
        tiny, ref, engine, monkeypatch, steps, order):
    """n blocks under T steps take ``T n + 1`` row-passes (less what a
    first block's prompt tail saves: it has fewer masks to fill): every
    block but the last is committed by the NEXT block's first pass
    (``block_passes_folded`` n - 1 a request), the last by a pass of its
    own that chooses nothing (``block_passes_commit`` 1 a request). Under
    ``low_confidence_dynamic`` the device ends a block early where
    confidences pass the threshold, so T n + 1 bounds the passes from
    above; the folds and the commits are counted the same. Three requests
    of different tails ride one batch, so a step holds a folding row
    beside a denoising one beside a last commit; every stream is the
    reference's."""
    from ray_tpu.ops.sampling import fill_counts, pass_fills

    cfg, params = tiny
    lens, news = [8, 6, 11], [13, 9, 12]
    prompts = _prompts(lens, seed=60 + steps)
    spans = _spy_on_dispatch(monkeypatch)
    before = engine.stats()
    got = _serve(engine, prompts, news, denoising_steps=steps,
                 remasking=order)
    after = engine.stats()
    moved = {name: after[name] - before[name] for name in (
        "block_passes", "block_passes_commit", "block_passes_folded",
        "blocks_committed", "block_tokens_committed", "moe_pairs_decode")}
    counts = fill_counts(W, steps)
    blocks = [-(-(n + L % W) // W) for L, n in zip(lens, news)]
    most = sum(steps * b + 1 - (steps - len(pass_fills(W - L % W, counts)))
               for b, L in zip(blocks, lens))
    assert moved["block_passes_commit"] == len(lens)
    assert moved["block_passes_folded"] == sum(blocks) - len(lens)
    assert moved["blocks_committed"] == sum(blocks)
    assert moved["block_tokens_committed"] == sum(news)
    if order == "low_confidence_dynamic":
        assert sum(blocks) + len(lens) <= moved["block_passes"] <= most
    else:
        assert moved["block_passes"] == most
    assert moved["moe_pairs_decode"] == (
        moved["block_passes"] + moved["block_passes_folded"]) * W \
        * cfg.top_k * cfg.n_layer
    decodes = [s for s in spans if s.get("kind") == "decode"]
    assert sum(s["rows_folded"] for s in decodes) == sum(blocks) - len(lens)
    # rows of one batch at different phases: a folding row beside one
    # that does not; under 2 steps these lengths put a folding row, a
    # denoising row and a last commit into ONE step
    assert any(0 < s["rows_folded"] < s["rows"] for s in decodes)
    if steps == 2:
        assert any(s["rows_folded"] and s["rows_commit"]
                   and s["rows"] > s["rows_folded"] + s["rows_commit"]
                   for s in decodes)
    for p, n, out in zip(prompts, news, got):
        assert out == ref.generate(params, p, n, cfg, steps=steps,
                                   remasking=order, pad_to=PAD)["tokens"]
    assert after["kv_used_blocks"] == 0


def _watch_writes(eng):
    """Every ``(request, end)`` the engine asks pages for and every
    ``(request, start, end)`` it prepares to write, from here on."""
    asked, wrote = [], []
    ensure, prepare = eng.cache.ensure_capacity, eng.cache.prepare_write

    def ensure_capacity(req, end):
        asked.append((req, end))
        return ensure(req, end)

    def prepare_write(req, start, end):
        wrote.append((req, start, end))
        return prepare(req, start, end)

    eng.cache.ensure_capacity = ensure_capacity
    eng.cache.prepare_write = prepare_write
    return asked, wrote


@pytest.mark.parametrize("lens,news", [
    ([9], [6]), ([8], [5]), ([7], [1]), ([5, 12], [11, 4])])
def test_no_fold_writes_past_the_last_block(tiny, ref, lens, news):
    """``max_new_tokens`` ending inside a block: the block is the LAST, a
    pass of its own commits it and nothing rides behind it, so no write
    reaches past the block that holds the request's last token, which is
    inside the worst-case reservation (a page is whole blocks); what the
    last block generated past the limit is counted as cut."""
    cfg, params = tiny
    eng = _engine(cfg, params)
    asked, wrote = _watch_writes(eng)
    prompts = _prompts(lens, seed=70)
    streams = [eng.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, news)]
    last = {s.request_id: -(-(L + n) // W) * W
            for s, L, n in zip(streams, lens, news)}
    reserved = {s.request_id: -(-(L + n) // 8) * 8
                for s, L, n in zip(streams, lens, news)}
    _drive(eng, streams)
    while eng.step():
        pass
    for p, n, s in zip(prompts, news, streams):
        assert list(s) == ref.generate(params, p, n, cfg, pad_to=PAD)["tokens"]
    assert {req for req, _ in asked} == set(last)
    for req, end in asked:
        assert end <= last[req] <= reserved[req]
    whole = {s.request_id: L // W * W for s, L in zip(streams, lens)}
    wrote = [w for w in wrote if w[1] >= whole[w[0]]]  # the passes' own
    for req, start, end in wrote:
        assert end <= last[req] and end - start in (W, 2 * W)
    assert any(end - start == 2 * W for _, start, end in wrote) == any(
        -(-(n + L % W) // W) > 1 for L, n in zip(lens, news))
    st = eng.stats()
    assert st["block_tokens_cut"] == sum(
        last[s.request_id] - L - n for s, L, n in zip(streams, lens, news))
    assert st["kv_used_blocks"] == 0 and st["decode_inflight"] == 0
    eng.shutdown()


def test_eos_found_at_a_folds_reconcile_wastes_the_fresh_block(tiny, ref):
    """EOS inside a finished block is found where the block is committed:
    at the reconcile of the FOLD that carried it. The fold's second half
    (the fresh block's first pass) and the pass launched behind it are
    wasted, the row's provisional rows go back, and what the finished
    block held past the EOS is counted as cut."""
    cfg, params = tiny
    prompt = _prompts([8], seed=5)[0]
    want = ref.generate(params, prompt, 16, cfg, pad_to=PAD)["tokens"]
    eos = next(t for t in want[4:8] if t not in want[:4])
    at = want.index(eos)
    eng = _engine(cfg, params, eos_id=eos)
    asked, _ = _watch_writes(eng)
    assert _serve(eng, [prompt], [16])[0] == want[:at + 1]
    while eng.step():
        pass
    st = eng.stats()
    # block 0 was folded into block 1's first pass, block 1 (the EOS's)
    # into block 2's: that fold and the pass behind it gave nothing
    assert st["blocks_committed"] == 2 and st["block_passes_folded"] == 2
    assert st["block_passes_commit"] == 0
    assert st["block_passes"] == 2 + 2 + 2
    assert st["block_tokens_cut"] == 2 * W - (at + 1)
    assert max(end for _, end in asked) == 8 + 3 * W
    assert st["kv_used_blocks"] == 0 and st["decode_inflight"] == 0
    eng.shutdown()


def test_a_row_cancelled_during_a_fold_drops_both_blocks(tiny):
    """A cancel with a fold in flight: up to 2 W provisional positions
    (the finished block, not yet delivered, and the fresh one) go back
    with the row's pages; no token of the finished block reaches the
    stream after the cancel."""
    from ray_tpu.exceptions import RequestCancelledError

    cfg, params = tiny
    eng = _engine(cfg, params)
    prompt = _prompts([12], seed=9)[0]
    stream = eng.submit(prompt, max_new_tokens=40)
    for _ in range(40):
        eng.step()
        st = eng.stats()
        if st["block_passes_folded"] and st["decode_inflight"]:
            break
    assert st["block_passes_folded"] == 1 and st["blocks_committed"] == 0
    assert st["kv_used_blocks"] > 0
    assert eng.cancel(stream.request_id)
    while eng.step():
        pass
    st = eng.stats()
    assert st["kv_used_blocks"] == 0 and st["decode_inflight"] == 0
    assert st["block_tokens_committed"] == 0
    with pytest.raises(RequestCancelledError):
        list(stream)
    eng.shutdown()


# ------------------------------------------------------------- the refusals


@pytest.mark.parametrize("option,match", [
    ({"speculative_k": 2}, "speculative_k.*nothing to draft"),
    ({"preemption": {}}, "preemption.*provisional block"),
    ({"quantization": "int8"}, "quantization.*expert weights"),
    ({"tp": 2}, "tp/fsdp/mesh.*expert axis"),
    ({"block_size": 6}, "block_size must be a multiple of 4"),
    ({"prefill_chunk_tokens": 18, "block_size": 4},
     "prefill_chunk_tokens must be a multiple of 4"),
])
def test_what_a_block_family_cannot_carry_is_refused(tiny, option, match):
    cfg, params = tiny
    with pytest.raises(ValueError, match=match):
        _engine(cfg, params, **option)


def test_requests_are_refused_by_name(tiny, engine):
    """A grammar (left to right, a token at a time) has no meaning over
    blocks; a family that yields a token a step has no denoising steps."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine, SamplingParams

    with pytest.raises(ValueError, match="grammar"):
        engine.submit([1, 2, 3], max_new_tokens=4, structured="json")
    with pytest.raises(ValueError, match="remasking"):
        SamplingParams(remasking="random")
    with pytest.raises(ValueError, match="denoising_steps"):
        SamplingParams(denoising_steps=0)
    llama = LLMEngine(EngineConfig(model="llama"), auto_step=False)
    for setting in ({"denoising_steps": 2}, {"remasking": "sequential"}):
        with pytest.raises(ValueError, match="one token a sequence a step"):
            llama.submit([1, 2, 3], max_new_tokens=2, **setting)
    llama.shutdown()


def test_the_family_is_served_and_named(jax_cpu):
    from ray_tpu.serve.llm import decode

    fam = decode.get_family("sdar_moe")
    assert fam.block_steps and fam.verify_step is None
    assert fam.state_rows is False
    assert fam.prefill.__name__ == "sdar_moe_prefill"
    assert fam.decode_step.__name__ == "sdar_moe_decode_step"
    assert [name for name in decode.FAMILIES
            if decode.get_family(name).block_steps] == ["sdar_moe"]
