"""ISSUE 47: a latent family's prefill step is filled by tokens. A pool in
planes is pages under ONE table, written before they are read, and a family
whose ``state`` holds only counters keeps no row a sequence: the cache
manager says ``one_table`` for ``pangu_ultra_moe`` and ``longcat_flash``, and
the scheduler cuts their chunks into rows of one 128-token q tile as it does
``llama``'s and ``gpt``'s (tests/test_serve_llm_packed_prefill.py). CPU,
float32, the tiny presets at a context of 1,024, chunks of 256 tokens.

What a packed step computes is held to three things: the float32 reference's
full forward (every streamed id's logit within 1e-4 of the largest), the
same request served alone, and the SAME engine with packing switched off
(``_piece`` None: a row a request, the program the cells ran before), whose
expert counters it must reproduce to the pair: padding routes nowhere."""
from __future__ import annotations

import dataclasses
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

FAMILIES = ("longcat_flash", "pangu_ultra_moe")
VOCAB_HELD = 64  # of 512
PIECE, CHUNK, TOP, NEW = 128, 256, 1024, 5
LENGTHS = [1, 127, 128, 129, 300, 5 * CHUNK // 2]
COUNTERS = ("moe_pairs_prefill", "moe_pairs_held_prefill",
            "moe_zero_picks_prefill")


@pytest.fixture(scope="module")
def models(jax_cpu):
    """{family: (float32 tiny config at a context of 1,024 holding two of
    eight experts and 64 rows of the vocabulary, seeded params, reference)}."""
    import jax
    import jax.numpy as jnp

    from benchmark import common
    from ray_tpu.serve.llm.decode import get_family

    out = {}
    for family in FAMILIES:
        fam = get_family(family)
        cfg = dataclasses.replace(
            type(fam.default_config()).tiny(VOCAB_HELD), dtype=jnp.float32,
            max_seq_len=TOP, experts_held=(2, 2), attention_backend="xla")
        out[family] = (cfg, fam.init(jax.random.PRNGKey(1), cfg),
                       common.load_named("reference", family))
    return out


def _engine(models, family, packed=True, **kw):
    """``packed`` False: the same engine a row a request, as before ISSUE 47
    (the branch every other layout takes)."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    cfg, params, _ = models[family]
    settings = dict(model=family, model_config=cfg, block_size=16,
                    num_blocks=257, max_batch_size=4,
                    prefill_chunk_tokens=CHUNK, max_prefill_batch=1,
                    length_buckets=(CHUNK, TOP))
    settings.update(kw)
    engine = LLMEngine(EngineConfig(**settings), params=params,
                       auto_step=False)
    assert engine.cache.cfg.one_table and engine._piece == PIECE
    assert engine.cache.cfg.state_slots == 0  # counters: no row a sequence
    if not packed:
        engine._piece = None
    return engine


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB_HELD, size=n).tolist() for n in lens]


def _drive(engine, streams, limit=4000):
    for _ in range(limit):
        if all(s.done for s in streams):
            break
        if not engine.step():
            time.sleep(0.01)  # parked streams wait for the resume clock
    while engine.step():
        pass
    assert all(s.done for s in streams)


def _watch(engine):
    """Every prefill launch's staged arrays, as the program was handed
    them."""
    seen = []
    chunk = engine.executor.prefill_chunk

    def prefill_chunk(tokens, lengths, starts, tables, sample=None,
                      span=None, slots=None, **kw):
        seen.append({"tokens": tokens.copy(), "lengths": lengths.copy(),
                     "starts": starts.copy(), "tables": tables.copy(),
                     "slots": None if slots is None else slots.copy()})
        return chunk(tokens, lengths, starts, tables, sample=sample,
                     span=span, slots=slots, **kw)

    engine.executor.prefill_chunk = prefill_chunk
    return seen


_forward = {}


def _is_the_references(models, family, prompt, out):
    """Every id of ``out`` is the full forward's greedy choice. The
    reference runs jitted over one padded length a family (causal, and
    its experts drop nothing: what lies behind a position cannot reach
    it), so that it is compiled once."""
    import jax
    import jax.numpy as jnp

    cfg, params, ref = models[family]
    if family not in _forward:
        _forward[family] = jax.jit(lambda p, t: ref.logits(p, t, cfg))
    chain = prompt + out
    padded = np.zeros((1, 3 * CHUNK), np.int32)
    padded[0, :len(chain)] = chain
    logits = np.asarray(_forward[family](params, jnp.asarray(padded)))[0]
    rows = logits[len(prompt) - 1: len(chain) - 1]
    deficit = rows.max(-1) - rows[np.arange(len(out)), out]
    assert float(deficit.max()) < 1e-4, deficit


def _flight(engine):
    return [r for r in engine.debug_dump()["steps"]
            if r["kind"].startswith("prefill")]


def _counted(engine, seen):
    """Every launch was packed and counted by rows x 128; -> ``stats()``."""
    st = engine.stats()
    flight = _flight(engine)
    assert st["prefill_steps_packed"] == st["prefill_steps"] == len(flight)
    assert len(seen) == len(flight)
    for r, step in zip(flight, seen):
        assert r["kind"] == "prefill_chunk" and r["bucket_len"] == PIECE
        assert r["bucket_b"] in engine._piece_rows
        assert 1 <= r["pieces"] <= r["bucket_b"] == len(step["slots"])
        assert r["pieces"] == -(-r["tokens"] // PIECE) or r["batch"] > 1
        # slots BY ROW: every piece is a real row, the rest is padding
        assert step["slots"].tolist() == (
            [1] * r["pieces"] + [0] * (r["bucket_b"] - r["pieces"]))
        assert (step["lengths"][r["pieces"]:] == 1).all()
        assert not step["tables"][r["pieces"]:].any()
    assert st["prefill_slots"] == sum(
        r["bucket_b"] * PIECE for r in flight)
    return st


_solo = {}


def _solo_runs(models, family, prompts, packed=True):
    """(greedy stream, expert counters) of each prompt served alone."""
    out = []
    for p in prompts:
        key = (family, packed, tuple(p))
        if key not in _solo:
            engine = _engine(models, family, packed)
            s = engine.submit(p, max_new_tokens=NEW, temperature=0.0)
            _drive(engine, [s])
            st = engine.stats()
            assert st["prefill_steps_packed"] == (
                st["prefill_steps"] if packed else 0)
            _solo[key] = list(s), {k: st[k] for k in COUNTERS if k in st}
            engine.shutdown()
        out.append(_solo[key])
    return out


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("n", LENGTHS)
def test_a_latent_prompt_alone_is_its_pieces(models, family, n):
    """One request's chunk a step (``max_prefill_batch`` 1, as the cells
    run): ``ceil(n / 256)`` steps, each the chunk's pieces padded to the
    ladder 1|2; the ids are the full forward's and the unpacked engine's,
    the routed pairs those of the real tokens."""
    cfg = models[family][0]
    engine = _engine(models, family)
    assert engine._piece_rows == (1, 2) and engine._piece_nb == TOP // 16
    seen = _watch(engine)
    (prompt,) = _prompts([n], seed=n)
    stream = engine.submit(prompt, max_new_tokens=NEW, temperature=0.0)
    _drive(engine, [stream])
    out = list(stream)
    _is_the_references(models, family, prompt, out)
    st = _counted(engine, seen)
    chunks = [min(CHUNK, n - at) for at in range(0, n, CHUNK)]
    assert [r["tokens"] for r in _flight(engine)] == chunks
    assert [r["pieces"] for r in _flight(engine)] == [
        -(-c // PIECE) for c in chunks]
    assert [s["starts"][0] for s in seen] == list(range(0, n, CHUNK))
    assert st["prefill_tokens_total"] == n
    ((alone, counters),) = _solo_runs(models, family, [prompt], packed=False)
    assert out == alone
    assert {k: st[k] for k in counters} == counters
    layers = getattr(cfg, "n_moe_layer", cfg.n_layer)
    assert st["moe_pairs_prefill"] == n * cfg.top_k * layers
    # the ladder's rungs and nothing else: no ``prefill`` kind any more
    assert {s[:2] for s in engine.fns.signatures if s[0] != "decode"} == {
        ("prefill_chunk", (rows, PIECE)) for rows in engine._piece_rows}
    engine.shutdown()


@pytest.mark.parametrize("family", FAMILIES)
def test_latent_prompts_together_match_solo_and_unpacked(models, family):
    """Four requests' chunks share a step where the ladder has room
    (``max_prefill_batch`` 4: rungs 1-4): the streams are those of each
    request alone and of the unpacked engine over the same prompts, and
    the expert counters the unpacked engine's to the pair."""
    prompts = _prompts(LENGTHS, seed=21)
    runs = {}
    for packed in (True, False):
        engine = _engine(models, family, packed, max_prefill_batch=4)
        assert engine._piece_rows == (1, 2, 3, 4)
        seen = _watch(engine)
        streams = [engine.submit(p, max_new_tokens=NEW, temperature=0.0)
                   for p in prompts]
        _drive(engine, streams)
        st = _counted(engine, seen) if packed else engine.stats()
        if packed:
            assert max(r["batch"] for r in _flight(engine)) > 1
        else:
            assert st["prefill_steps_packed"] == 0
        runs[packed] = ([list(s) for s in streams],
                        {k: st[k] for k in COUNTERS if k in st},
                        st["prefill_slots"])
        engine.shutdown()
    assert runs[True][0] == runs[False][0] == [
        ids for ids, _ in _solo_runs(models, family, prompts)]
    assert runs[True][1] == runs[False][1] and runs[True][1][
        "moe_pairs_prefill"] > 0
    assert ("moe_zero_picks_prefill" in runs[True][1]) == (
        family == "longcat_flash")
    assert runs[True][2] < runs[False][2]  # fewer slots for the same tokens
    for prompt, out in zip(prompts, runs[True][0]):
        _is_the_references(models, family, prompt, out)


@pytest.mark.parametrize("family", FAMILIES)
def test_a_packed_step_hands_the_program_slots_by_row(models, family):
    """Two requests in one step, the first of two pieces: rows 0-2 are real
    and row 3 is padding. A slot a REQUEST (``slots[i]`` for request i)
    would call row 2, the second request's piece, padding and route its
    tokens nowhere."""
    engine = _engine(models, family, max_prefill_batch=4)
    seen = _watch(engine)
    prompts = _prompts((200, 60), seed=5)
    streams = [engine.submit(p, max_new_tokens=NEW, temperature=0.0)
               for p in prompts]
    _drive(engine, streams)
    (step,) = seen
    assert step["slots"].tolist() == [1, 1, 1]
    assert step["lengths"].tolist() == [128, 72, 60]
    assert step["starts"].tolist() == [0, 128, 0]
    st = _counted(engine, seen)
    cfg = models[family][0]
    assert st["moe_pairs_prefill"] == 260 * cfg.top_k * getattr(
        cfg, "n_moe_layer", cfg.n_layer)
    assert [list(s) for s in streams] == [
        ids for ids, _ in _solo_runs(models, family, prompts)]
    engine.shutdown()


@pytest.mark.parametrize("family", FAMILIES)
def test_a_latent_prefix_hit_starts_its_pieces_behind_the_hit(models, family):
    """A mid-prompt resume: ten whole blocks resident, 240 tokens to
    compute from position 160, in two pieces of one step."""
    engine = _engine(models, family)
    (base,) = _prompts([300], seed=13)
    s0 = engine.submit(base, max_new_tokens=NEW, temperature=0.0)
    _drive(engine, [s0])
    seen = _watch(engine)
    (tail,) = _prompts([240], seed=14)
    prompt = base[:160] + tail
    stream = engine.submit(prompt, max_new_tokens=NEW, temperature=0.0)
    _drive(engine, [stream])
    assert len(seen) == 1 and engine.stats()["prefix_hit_tokens"] == 160
    assert seen[0]["starts"].tolist() == [160, 288]
    assert seen[0]["lengths"].tolist() == [128, 112]
    assert seen[0]["slots"].tolist() == [1, 1]
    out = list(stream)
    _is_the_references(models, family, prompt, out)
    assert [out] == [ids for ids, _ in _solo_runs(models, family, [prompt])]
    engine.shutdown()


@pytest.mark.parametrize("family", FAMILIES)
def test_a_preempted_latent_streams_resume_is_packed_too(models, family):
    """A paused stream re-prefills prompt + generated through the packed
    path its first prefill took, and streams what an unpaused one does."""
    engine = _engine(models, family, preemption={})
    (prompt,) = _prompts([300], seed=17)
    stream = engine.submit(prompt, max_new_tokens=NEW, temperature=0.0,
                           priority="batch")
    for _ in range(5):
        engine.step()
    seen = _watch(engine)
    with engine._lock:
        (row,) = engine._running
        from ray_tpu.serve.llm import obs

        assert engine._preempt_one_locked(row, obs.clock())
    held = len(row.generated)
    assert 0 < held < NEW and engine.stats()["preempted"] == 1
    _drive(engine, [stream])
    out = list(stream)
    st = engine.stats()
    assert st["preemptions_total"] == 1
    assert st["prefill_steps_packed"] == st["prefill_steps"]
    # the chain's uncached rest, chunk by chunk, each in pieces by row
    assert sum(int(s["lengths"][s["slots"] > 0].sum()) for s in seen) == (
        300 + held - row.cached_tokens)
    assert all(r["pieces"] for r in _flight(engine))
    assert [out] == [ids for ids, _ in _solo_runs(models, family, [prompt])]
    _is_the_references(models, family, prompt, out)
    engine.shutdown()


@pytest.mark.parametrize("family", FAMILIES)
def test_packed_rows_go_through_the_latent_kernel(models, family):
    """The Pallas kernel (interpreted) reads a packed step's rows as the
    XLA path does: a prompt of three pieces, the last short, then decode."""
    (prompt,) = _prompts([300], seed=23)
    cfg = models[family][0]
    engine = _engine(
        models, family,
        model_config=dataclasses.replace(cfg, attention_backend="pallas"),
        attention_backend="pallas")
    seen = _watch(engine)
    stream = engine.submit(prompt, max_new_tokens=NEW, temperature=0.0)
    _drive(engine, [stream])
    assert [s["lengths"].tolist() for s in seen] == [[128, 128], [44]]
    _counted(engine, seen)
    _is_the_references(models, family, prompt, list(stream))
    engine.shutdown()


@pytest.mark.parametrize("family", FAMILIES)
def test_an_unrolled_stack_takes_the_wider_ladder(models, family):
    """These families' layers are a LIST (unrolled: a rung's program is as
    long as the layers are many), which the engine sees in the weights'
    tree: a chunk of 1,024 tokens is cut over the rungs 1 2 3 4 6 8, where
    a scanned stack (``llama``, ``gpt``) keeps every count to 8
    (tests/test_serve_llm_packed_prefill.py). Five pieces ride six rows."""
    params = models[family][1]
    assert isinstance(params["layers"], list)
    engine = _engine(models, family, prefill_chunk_tokens=TOP,
                     length_buckets=(TOP,))
    assert engine._piece_rows == (1, 2, 3, 4, 6, 8)
    seen = _watch(engine)
    (prompt,) = _prompts([600], seed=29)
    stream = engine.submit(prompt, max_new_tokens=NEW, temperature=0.0)
    _drive(engine, [stream])
    (step,) = seen
    assert step["lengths"].tolist() == [128] * 4 + [88, 1]
    assert step["slots"].tolist() == [1] * 5 + [0]
    st = _counted(engine, seen)
    assert st["prefill_slots"] == 6 * PIECE
    _is_the_references(models, family, prompt, list(stream))
    engine.shutdown()
