"""ISSUE 51: a latent family's PREFILL attends in the expanded form
(ops/latent_prefill.py): the step's own keys and values by head through
``flash_fwd`` with a segment id a token, the resident prefix block by
block from the planes, merged by log-sum-exp. CPU; the kernel in the Pallas
interpreter, the "xla" backend through the same structure.

Held to: ``mha_reference`` (the kernel's new operands), the ABSORBED call
over the same planes (what every step ran before, and a decode step still
does), the float32 reference's full forward, and the same request served
whole."""
from __future__ import annotations

import dataclasses
import hashlib
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

FAMILIES = ("longcat_flash", "pangu_ultra_moe")
VOCAB_HELD = 64


# ------------------------------------------------------------ the kernel


def _by_segment(q, k, v, q_seg, k_seg, causal):
    """``mha_reference`` a segment: what ``flash_fwd`` under segment ids
    must give its real queries; zeros for a query that attends nothing."""
    import jax.numpy as jnp

    from ray_tpu.ops.attention import mha_reference

    out = np.zeros((*q.shape[:-1], v.shape[-1]), np.float32)
    for s in sorted(set(q_seg[q_seg >= 0].tolist())):
        qi, ki = np.flatnonzero(q_seg == s), np.flatnonzero(k_seg == s)
        if not len(ki):
            continue
        if causal:  # a segment's tokens are consecutive on both sides
            assert (qi == ki).all()
        out[:, :, qi] = np.asarray(mha_reference(
            jnp.asarray(q[:, :, qi]), jnp.asarray(k[:, :, ki]),
            jnp.asarray(v[:, :, ki]), causal=causal), np.float32)
    return out


# name: (Tq, Tk, causal, q segments, k segments); None: no ids at all
_A, _B = [0] * 100, [1] * 60
KERNEL_CASES = {
    "narrow-value": (128, 128, True, None, None),
    "rectangular": (64, 192, False, None, None),
    "one-segment": (128, 128, True, [0] * 128, [0] * 128),
    "two-sequences-and-padding": (
        256, 256, True, _A + [-2] * 28 + _B + [-2] * 68,
        _A + [-1] * 28 + _B + [-1] * 68),
    "a-prefix-block-by-its-length": (
        128, 192, False, [0] * 64 + [1] * 50 + [-2] * 14,
        [1] * 150 + [-1] * 42),
    "nothing-shared": (64, 128, False, [0] * 64, [1] * 100 + [-1] * 28),
    # ONE block of 256: two updates, its upper right quarter left out
    "the-diagonal-in-halves": (
        256, 256, True, [0] * 100 + [1] * 120 + [-2] * 36,
        [0] * 100 + [1] * 120 + [-1] * 36),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_flash_fwd_takes_a_narrow_value_a_rectangle_and_segments(
        jax_cpu, case, dtype):
    """``flash_fwd`` with a value narrower than the key (12 / 8), ``T_q !=
    T_k`` and segment ids against ``mha_reference`` a segment, blocks of
    64 so that whole blocks are skipped (and ONE of 256: the diagonal's
    split); a query that
    attends nothing gives 0 and a log-sum-exp of ``NEG_INF``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import LOG2E, NEG_INF, _flash_forward

    Tq, Tk, causal, q_seg, k_seg = KERNEL_CASES[case]
    H, D, Dv = 3, 12, 8
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    q = jax.random.normal(ks[0], (1, H, Tq, D), dtype)
    k = jax.random.normal(ks[1], (1, H, Tk, D), dtype)
    v = jax.random.normal(ks[2], (1, H, Tk, Dv), dtype)
    ids = {} if q_seg is None else {
        "q_seg": jnp.asarray(q_seg, jnp.int32),
        "k_seg": jnp.asarray(k_seg, jnp.int32)}
    if case == "the-diagonal-in-halves":
        ids["halve_diagonal"] = True
    block = 256 if case == "the-diagonal-in-halves" else 64
    out, lse = _flash_forward(
        q, k, v, causal=causal, scale=D ** -0.5, block_q=block,
        block_kv=block, interpret=True, save_lse=True, **ids)
    assert out.shape == (1, H, Tq, Dv)
    if ids:  # the serving call: float32 out, the log-sum-exp by lanes
        assert out.dtype == jnp.float32 and lse.shape == (1, H, Tq, 128)
        assert (np.asarray(lse) == np.asarray(lse)[..., :1]).all()
        lse = lse[..., 0]
    assert lse.shape == (1, H, Tq)
    q_seg = np.zeros(Tq, int) if q_seg is None else np.asarray(q_seg)
    k_seg = np.zeros(Tk, int) if k_seg is None else np.asarray(k_seg)
    want = _by_segment(np.asarray(q, np.float32), np.asarray(k, np.float32),
                       np.asarray(v, np.float32), q_seg, k_seg, causal)
    tol = 1e-5 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), want, atol=tol)
    # the log-sum-exp (base 2) of a real query's allowed scores
    s = np.einsum("hqd,hkd->hqk", np.asarray(q, np.float32)[0],
                  np.asarray(k, np.float32)[0]) * D ** -0.5 * LOG2E
    allowed = q_seg[:, None] == k_seg[None, :]
    if causal:
        allowed &= np.arange(Tk)[None, :] <= np.arange(Tq)[:, None]
    s = np.where(allowed[None], s, -np.inf)
    some = allowed.any(axis=1)
    with np.errstate(divide="ignore"):
        want_lse = np.log2(np.exp2(s - s.max(-1, keepdims=True).clip(-1e30))
                           .sum(-1)) + s.max(-1).clip(-1e30)
    np.testing.assert_allclose(
        np.asarray(lse)[0][:, some], want_lse[:, some],
        atol=1e-4 if dtype == "float32" else 5e-2)
    assert (np.asarray(lse)[0][:, ~some] == NEG_INF).all()
    assert not np.asarray(out, np.float32)[0][:, ~some].any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_fwd_goes_on_from_an_earlier_calls_state(jax_cpu, dtype):
    """A context attended in two calls, the second handed the first's
    output and log-sum-exp (``carry``), is the context attended at once:
    a query with keys in both parts, in one only, and in neither."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import _flash_forward

    H, D, Dv, Tq = 2, 12, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, H, Tq, D), dtype)
    k = jax.random.normal(ks[1], (1, H, 256, D), dtype)
    v = jax.random.normal(ks[2], (1, H, 256, Dv), dtype)
    q_seg = jnp.asarray([0] * 40 + [1] * 40 + [2] * 40 + [-2] * 8, jnp.int32)
    # segment 0 has keys in both halves, 1 in the first, 2 in the second
    k_seg = jnp.asarray([0] * 64 + [1] * 64 + [0] * 30 + [2] * 90 + [-1] * 8,
                        jnp.int32)
    call = lambda k, v, k_seg, carry=None: _flash_forward(
        q, k, v, causal=False, scale=D ** -0.5, block_q=64, block_kv=64,
        interpret=True, save_lse=True, q_seg=q_seg, k_seg=k_seg, carry=carry)
    whole, lse = call(k, v, k_seg)
    first = call(k[:, :, :128], v[:, :, :128], k_seg[:128])
    both, lse2 = call(k[:, :, 128:], v[:, :, 128:], k_seg[128:], first)
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(both), np.asarray(whole), atol=tol)
    np.testing.assert_allclose(np.asarray(lse2), np.asarray(lse), atol=tol)
    assert not np.asarray(both)[0, :, 120:].any()


# cell 2's kernel: the text ``flash_attention`` traced to at 24 x 12 x
# 1,024 x 64 bfloat16 (forward; forward and backward) before ISSUE 51 gave
# ``flash_fwd`` its optional operands, under jax 0.9.0
CELL2_JAXPR = {
    "forward": "14c5b0b93b389b6fa8f78b907c15fab157646c0bf1adda69d283b8b521fbb084",
    "grad": "e0ddf374bbd273d473fb5930c153b10c17a7251582af589f9d20d1f3bc48fef7",
}


@pytest.mark.parametrize("which", sorted(CELL2_JAXPR))
def test_the_trainers_flash_attention_is_traced_to_the_text_it_had(
        jax_cpu, monkeypatch, which):
    """``gpt2-train-1k`` runs ``flash_attention`` without ids: its jaxpr
    (the kernel's body, its grid and blocks, the operations around it) is
    letter for letter the parent's."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import flash_attention

    if jax.__version__ != "0.9.0":
        pytest.skip("the text was taken under jax 0.9.0")
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    x = jax.ShapeDtypeStruct((24, 12, 1024, 64), jnp.bfloat16)
    fn = lambda q, k, v: flash_attention(q, k, v, causal=True)
    if which == "grad":
        fn = jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=True).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))
    text = str(jax.make_jaxpr(fn)(x, x, x))
    assert "flash_fwd" in text
    assert hashlib.sha256(text.encode()).hexdigest() == CELL2_JAXPR[which]


# ------------------------------------- the op against the absorbed call

H, C, R, N, V, BS, NB = 4, 16, 4, 8, 8, 8, 16   # a table of 128 positions

# name: (S, rows as (sequence, first position, real tokens)); a sequence
# of -1 is a padding row (length 1 under an all-zero table)
STEPS = {
    "first-chunk-a-row-a-request": (64, [(0, 0, 64)]),
    "prefix-ends-inside-a-key-block": (32, [(0, 50, 32)]),
    "pieces-of-two-sequences-and-padding": (
        16, [(0, 40, 16), (0, 56, 11), (1, 0, 16), (1, 16, 3), (-1, 0, 1)]),
    "rows-are-requests": (32, [(0, 24, 32), (1, 0, 9), (2, 77, 20)]),
    "only-padding-rows": (16, [(-1, 0, 1), (-1, 0, 1)]),
}


def _planes_and_step(step, dtype, seed=0):
    """A pool (ONE plane: rows ``[c | k_rope]``, each part at whole lanes)
    full of finite garbage, each sequence's context (resident prefix and
    this step's rows) written under its own pages."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import latent_row_width, plane_width

    S, rows = STEPS[step]
    B = len(rows)
    rng = np.random.default_rng(seed)
    blocks = 1 + 4 * NB
    pool = rng.normal(
        size=(2, blocks, BS, latent_row_width(C, R))).astype(np.float32)
    pool_c, pool_r = pool[..., :plane_width(C)], pool[..., plane_width(C):]
    pool_c[..., C:] = 0
    pool_r[..., R:] = 0
    c = rng.normal(size=(B, S, C)).astype(np.float32)
    k_r = rng.normal(size=(B, S, R)).astype(np.float32)
    tables = np.zeros((B, NB), np.int32)
    start = np.zeros(B, np.int32)
    valid = np.zeros((B, S), bool)
    for b, (seq, first, n) in enumerate(rows):
        start[b], valid[b, :n] = first, True
        if seq < 0:
            continue
        tables[b] = 1 + seq * NB + rng.permutation(NB) if not b or \
            rows[b - 1][0] != seq else tables[b - 1]
        for t in range(n):  # the step's own rows, where ``write_kv`` puts them
            page, slot = tables[b, (first + t) // BS], (first + t) % BS
            pool_c[1, page, slot, :C] = c[b, t]
            pool_r[1, page, slot, :R] = k_r[b, t]
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    as_ = lambda a: jnp.asarray(a, dtype)
    return dict(
        q_nope=jax.random.normal(ks[0], (B, S, H, N), dtype),
        q_rope=jax.random.normal(ks[1], (B, S, H, R), dtype),
        c=as_(c), k_r=as_(k_r), pool=as_(pool),
        tables=jnp.asarray(tables), start=jnp.asarray(start),
        valid=jnp.asarray(valid),
        w_uk=jax.random.normal(ks[2], (C, H, N), dtype) * 0.3,
        w_uv=jax.random.normal(ks[3], (C, H, V), dtype) * 0.3)


def _absorbed(a, scale):
    """The step through the ABSORBED call (the XLA formulation, float32):
    ``W_uk`` into the query, one row a token as key and value, ``W_uv``
    out of the result: ``[B, S, H * V]``."""
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import latent_attention

    f32 = lambda x: x.astype(jnp.float32)
    B, S = a["valid"].shape
    q = jnp.concatenate([
        jnp.einsum("bshn,chn->bshc", f32(a["q_nope"]), f32(a["w_uk"])),
        f32(a["q_rope"])], axis=-1)
    pos = a["start"][:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    o = latent_attention(
        q, f32(a["pool"]), a["tables"],
        jnp.where(a["valid"], pos, 0), latent_dim=C, scale=scale,
        backend="xla", layer=1)
    return jnp.einsum("bshc,chv->bshv", o, f32(a["w_uv"])).reshape(B, S, -1)


@pytest.mark.parametrize("form", ["xla-float32", "pallas-float32",
                                  "pallas-bfloat16"])
@pytest.mark.parametrize("step", sorted(STEPS))
def test_expanded_prefill_is_the_absorbed_call_on_the_same_planes(
        jax_cpu, monkeypatch, step, form):
    """Every real token of the step gets the absorbed call's output: a
    first chunk, a prefix that ends inside a key block (blocks of 32 keys:
    two trips, the second cut by the length), pieces of two sequences
    beside a padding row (each sequence's blocks attended by its own
    queries), rows that are requests (``_piece`` None), padding alone."""
    import jax.numpy as jnp

    from ray_tpu.ops import latent_prefill as lp

    backend, dtype = form.split("-")
    # tiles of 16 tokens, one block up to 48 of them (its diagonal halved
    # from 32 up), blocks of 32 past it
    monkeypatch.setattr(lp, "PREFIX_BLOCK", 32)
    for name, n in (("_TILE", 16), ("_ONE_BLOCK", 48), ("_HALVE", 32),
                    ("_BLOCK", 32)):
        monkeypatch.setattr(lp, name, n)
    a = _planes_and_step(step, dtype, seed=len(step))
    scale = (N + R) ** -0.5
    got = lp.expanded_prefill_attention(
        jnp.concatenate([a["q_nope"], a["q_rope"]], axis=-1), a["c"],
        a["k_r"], a["pool"], a["tables"], a["valid"],
        a["start"], a["w_uk"], a["w_uv"], scale=scale, backend=backend,
        layer=1)
    B, S = a["valid"].shape
    assert got.shape == (B, S, H * V) and got.dtype == a["c"].dtype
    assert np.isfinite(np.asarray(got, np.float32)).all()
    want = np.asarray(_absorbed(a, scale))
    real = np.asarray(a["valid"]) & (np.asarray(a["tables"]).any(1))[:, None]
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[real], want[real],
        atol=2e-5 if dtype == "float32" else 6e-2)
    if step == "only-padding-rows":
        assert not real.any()


def test_prefix_blocks_counts_the_loops_trips():
    from ray_tpu.ops.latent_prefill import PREFIX_BLOCK, prefix_blocks

    assert PREFIX_BLOCK == 2048
    assert [prefix_blocks(n) for n in (0, 1, 2048, 2049, 10240)] == [
        0, 1, 1, 2, 5]
    assert prefix_blocks(50, 32) == 2


# ------------------------------------------- the families' step programs


@pytest.fixture(scope="module")
def models(jax_cpu):
    """{family: (float32 tiny config at a context of 512 holding two of
    eight experts and 64 rows of the vocabulary, seeded params, reference)}.
    ``rope_theta`` is this file's own, so that its step programs are no
    other file's (serve/llm/decode.py ``_jit_cache``)."""
    import jax
    import jax.numpy as jnp

    from benchmark import common
    from ray_tpu.serve.llm.decode import get_family

    out = {}
    for family in FAMILIES:
        fam = get_family(family)
        cfg = dataclasses.replace(
            type(fam.default_config()).tiny(VOCAB_HELD), dtype=jnp.float32,
            max_seq_len=512, experts_held=(2, 2), attention_backend="xla",
            rope_theta=51000.0)
        out[family] = (cfg, fam.init(jax.random.PRNGKey(1), cfg),
                       common.load_named("reference", family))
    return out


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("family", FAMILIES)
def test_a_familys_chunk_program_in_both_forms(models, monkeypatch, family,
                                               backend):
    """The chunk program over pieces of two sequences, one with a resident
    prefix of one and a half key blocks: its logits in the EXPANDED form
    are those of the same program made to take the ABSORBED form (``step``
    handed to ``cached_heads`` as a decode step's) and the reference's
    full forward; longcat's two sub-layers and its two rescalings ride in
    ``q`` and in the stored ``c``."""
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import parts
    from ray_tpu.ops import latent_prefill as lp
    from ray_tpu.serve.llm.decode import get_family

    monkeypatch.setattr(lp, "PREFIX_BLOCK", 32)
    cfg, params, ref = models[family]
    cfg = dataclasses.replace(cfg, attention_backend=backend)
    fam = get_family(family)
    m = importlib.import_module(f"ray_tpu.models.{family}")
    bs, NB = 8, 16
    a = np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (80,), 1, cfg.vocab_size))
    b = np.asarray(jax.random.randint(
        jax.random.PRNGKey(4), (21,), 1, cfg.vocab_size))
    want = [np.asarray(ref.logits(params, jnp.asarray(t[None]), cfg))[0][-1]
            for t in (a, b)]
    layers = getattr(cfg, "n_kv_layer", cfg.n_layer)

    def run(absorbed):
        heads = parts.cached_heads
        if absorbed:
            monkeypatch.setattr(
                m, "cached_heads", lambda *args: heads(
                    *args[:6], args[6]._replace(kind="decode"), args[7]))
        k, v = jnp.zeros((layers, 1 + 2 * NB, bs, sum(
            stored for _, _, stored in cfg.kv_planes))), None
        state = fam.init_state(cfg, 2)
        tables = 1 + np.arange(2 * NB, dtype=np.int32).reshape(2, NB)
        with jax.default_matmul_precision("highest"):
            # sequence a's first 48 tokens: a first chunk, nothing resident
            first = np.zeros((1, 48), np.int32)
            first[0] = a[:48]
            _, k, v, state = fam.prefill(
                params, k, v, jnp.asarray(first), jnp.asarray([48]),
                jnp.asarray(tables[:1]), cfg, state=state,
                slots=jnp.ones((1,), jnp.int32))
            # then a's last 32 in two pieces, b whole in two, a padding row
            tokens = np.zeros((5, 16), np.int32)
            tokens[0], tokens[1] = a[48:64], a[64:80]
            tokens[2], tokens[3, :5] = b[:16], b[16:]
            out, k, v, state = fam.prefill(
                params, k, v, jnp.asarray(tokens),
                jnp.asarray([16, 16, 16, 5, 1]),
                jnp.asarray(np.stack([tables[0], tables[0], tables[1],
                                      tables[1], np.zeros(NB, np.int32)])),
                cfg, start=jnp.asarray([48, 64, 0, 16, 0]), state=state,
                slots=jnp.asarray([1, 1, 1, 1, 0], jnp.int32))
        monkeypatch.setattr(m, "cached_heads", heads)
        return np.asarray(out)

    expanded, absorbed = run(False), run(True)
    np.testing.assert_allclose(expanded[[1, 3]], absorbed[[1, 3]], atol=1e-4)
    np.testing.assert_allclose(expanded[1], want[0], atol=1e-4)
    np.testing.assert_allclose(expanded[3], want[1], atol=1e-4)


def _engine(models, family, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    cfg, params, _ = models[family]
    settings = dict(model=family, model_config=cfg, block_size=8,
                    num_blocks=129, max_batch_size=4,
                    prefill_chunk_tokens=64, max_prefill_batch=2,
                    length_buckets=(64, 256))
    settings.update(kw)
    return LLMEngine(EngineConfig(**settings), params=params,
                     auto_step=False)


def _drive(engine, streams, limit=4000):
    for _ in range(limit):
        if all(s.done for s in streams):
            break
        if not engine.step():
            time.sleep(0.01)
    while engine.step():
        pass
    assert all(s.done for s in streams)


def _spans(engine, monkeypatch):
    """The attributes of every ``executor.dispatch`` span from here on."""
    from ray_tpu.serve.llm import obs

    seen, real = [], obs.phase

    def spy(table, name, **attrs):
        if name == "executor.dispatch":
            seen.append(attrs)
        return real(table, name, **attrs)

    monkeypatch.setattr(obs, "phase", spy)
    return seen


@pytest.mark.parametrize("family", FAMILIES)
def test_served_by_chunks_is_served_whole_is_the_forward(
        models, monkeypatch, family):
    """A prompt of 150 tokens served in chunks of 64 (prefixes of 64 and
    128 positions: two and four key blocks of 32) streams what the same
    prompt streams served whole (one chunk of 256, nothing resident), and
    every id is the full forward's greedy choice; the dispatch spans say
    the form: ``expanded_pairs`` = ``qk_pairs`` on every prefill span, 0
    on a decode span, ``prefix_blocks`` the trips; ``stats()`` adds them
    up."""
    import jax.numpy as jnp

    from ray_tpu.ops import latent_prefill as lp

    monkeypatch.setattr(lp, "PREFIX_BLOCK", 32)
    cfg, params, ref = models[family]
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, VOCAB_HELD, size=150).tolist()
    runs = {}
    for chunk in (64, 256):
        engine = _engine(models, family, prefill_chunk_tokens=chunk,
                         max_prefill_batch=1)
        spans = _spans(engine, monkeypatch)
        stream = engine.submit(prompt, max_new_tokens=6, temperature=0.0)
        _drive(engine, [stream])
        prefills = [s for s in spans if s.get("kind") == "prefill_chunk"]
        decodes = [s for s in spans if s.get("kind") == "decode"]
        assert prefills and decodes
        assert all(s["expanded_pairs"] == s["qk_pairs"] > 0
                   for s in prefills)
        assert all(s["expanded_pairs"] == 0 and "prefix_blocks" not in s
                   for s in decodes)
        firsts = list(range(0, 150, chunk))
        assert [s["prefix_blocks"] for s in prefills] == [
            -(-first // 32) for first in firsts]
        st = engine.stats()
        assert st["qk_pairs"] == st["expanded_pairs"] == 150 * 151 // 2
        assert st["prefix_blocks"] == sum(-(-f // 32) for f in firsts)
        runs[chunk] = list(stream)
        engine.shutdown()
    assert runs[64] == runs[256]
    out = runs[64]
    logits = np.asarray(ref.logits(params, jnp.asarray([prompt + out]),
                                   cfg))[0]
    rows = logits[len(prompt) - 1: len(prompt) + len(out) - 1]
    deficit = rows.max(-1) - rows[np.arange(len(out)), out]
    assert float(deficit.max()) < 1e-4, deficit


def test_another_familys_spans_and_stats_say_no_expanded_pairs(jax_cpu,
                                                               monkeypatch):
    """A family by heads has no expanded form: its prefill spans carry
    ``qk_pairs`` alone, and ``stats()`` counts 0 beside it."""
    import jax

    from ray_tpu.models.llama import LlamaConfig, llama_init
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    cfg = LlamaConfig.tiny()
    engine = LLMEngine(EngineConfig(
        model="llama", model_config=cfg, block_size=8, num_blocks=65,
        max_batch_size=2, length_buckets=(32, 64)),
        params=llama_init(jax.random.PRNGKey(0), cfg), auto_step=False)
    spans = _spans(engine, monkeypatch)
    stream = engine.submit(list(range(1, 21)), max_new_tokens=3,
                           temperature=0.0)
    _drive(engine, [stream])
    prefills = [s for s in spans if str(s.get("kind")).startswith("prefill")]
    assert prefills and all(
        "qk_pairs" in s and "expanded_pairs" not in s for s in prefills)
    st = engine.stats()
    assert st["qk_pairs"] == 20 * 21 // 2
    assert st["expanded_pairs"] == st["prefix_blocks"] == 0
    engine.shutdown()
