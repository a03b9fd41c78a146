"""ISSUE 42: a prefill step is filled by tokens. Where all a sequence
carries is K/V pages under one table (``llama``, ``gpt``) the prompts of a
step are cut into pieces of the kernel's q tile, a row each, at true
positions under their sequence's table; every other layout keeps a row a
request. CPU, float32, tiny models at a context of 1,024.

What a packed step computes is held to the program it replaced: the
logits at a request's last piece's row are those of the whole prompt
prefilled alone by the program without ``start`` (one row, positions from
0, attention over the chunk's own K/V) to 1e-4, and the greedy streams are
those of one-at-a-time serving."""
import dataclasses

import numpy as np
import pytest

PIECE = 128
LENGTHS = [1, 127, 128, 129, 300, 700]
NEW = 6
TOP = 1024


@pytest.fixture(scope="module")
def models(jax_cpu):
    """{family: (float32 config at a context of 1,024, seeded params)}."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm.decode import get_family

    out = {}
    for family in ("llama", "gpt"):
        fam = get_family(family)
        cfg = dataclasses.replace(
            fam.default_config(), dtype=jnp.float32, max_seq_len=TOP,
            attention_backend="xla")
        out[family] = cfg, fam.init(jax.random.PRNGKey(2), cfg)
    return out


def _engine(models, family, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    cfg, params = models[family]
    settings = dict(model=family, model_config=cfg, block_size=16,
                    num_blocks=257, max_batch_size=4)
    settings.update(kw)
    return LLMEngine(EngineConfig(**settings), params=params,
                     auto_step=False)


def _prompts(lens, seed=0, vocab=512):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).tolist() for n in lens]


def _drive(engine, streams, limit=4000):
    for _ in range(limit):
        if all(s.done for s in streams):
            break
        engine.step()
    while engine.step():
        pass
    assert all(s.done for s in streams)


def _watch(engine):
    """Every prefill launch of ``engine``: its staged arrays, the LOGITS
    the same program gives over them (``sample=None``, on a copy of the
    pool as it stood), and the in-flight record the engine made of it."""
    import jax
    import jax.numpy as jnp

    seen = []
    ex = engine.executor
    chunk, launched = ex.prefill_chunk, engine._launched_locked

    def prefill_chunk(tokens, lengths, starts, tables, sample=None,
                      span=None, slots=None, **kw):
        k, v = jax.tree.map(jnp.copy, (ex.cache.k, ex.cache.v))
        logits = ex.fns._prefill(
            ex.params, k, v, tokens, lengths, tables, start=starts,
            sample=None, state=None, slots=None)[0]
        seen.append({"tokens": tokens.copy(), "lengths": lengths.copy(),
                     "starts": starts.copy(), "tables": tables.copy(),
                     "logits": np.asarray(logits), "span": dict(span)})
        return chunk(tokens, lengths, starts, tables, sample=sample,
                     span=span, slots=slots, **kw)

    def note(rec):
        if rec.rows is not None:
            seen[-1]["rec"] = rec
        return launched(rec)

    ex.prefill_chunk = prefill_chunk
    engine._launched_locked = note
    return seen


_alone = {}


def _logits_alone(models, family, chain):
    """The last token's logits of ``chain`` prefilled alone, one row from
    position 0 by the program without ``start``."""
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import pool_shape
    from ray_tpu.serve.llm.decode import DecodeFns

    cfg, params = models[family]
    key = (family, tuple(chain))
    if key not in _alone:
        fns = DecodeFns(family, cfg)
        n_kv = getattr(cfg, "n_kv_head", None) or cfg.n_head
        pool = jnp.zeros(pool_shape(cfg.n_layer, TOP // 16 + 1, 16, n_kv,
                                    cfg.head_dim), cfg.dtype)
        tokens = np.zeros((1, TOP), np.int32)
        tokens[0, :len(chain)] = chain
        out = fns.prefill(
            params, pool, jnp.copy(pool), tokens,
            np.array([len(chain)], np.int32),
            np.arange(1, TOP // 16 + 1, dtype=np.int32)[None])[0]
        _alone[key] = np.asarray(out)[0]
    return _alone[key]


def _check_steps(models, family, seen, engine):
    """Every launch was a packed step of the engine's ladder, its rows the
    pieces of its requests in order; the logits at a request's last piece
    are the whole chain's, prefilled alone. -> the final chains checked."""
    checked = 0
    for step in seen:
        rec = step["rec"]
        R, S = step["tokens"].shape
        assert rec.kind == "prefill_chunk" and S == PIECE
        assert R in engine._piece_rows and step["tables"].shape == (
            R, TOP // 16)
        row = 0
        for j, (n, chain, done, final) in enumerate(rec.rows):
            start = done - n
            pieces = -(-n // PIECE)
            for p in range(pieces):
                m = min(PIECE, n - p * PIECE)
                at = start + p * PIECE
                assert step["lengths"][row] == m
                assert step["starts"][row] == at
                assert step["tokens"][row, :m].tolist() == chain[at:at + m]
                assert not step["tokens"][row, m:].any()
                row += 1
            assert rec.ids_at[j] == row - 1
            if final:
                np.testing.assert_allclose(
                    step["logits"][row - 1],
                    _logits_alone(models, family, chain[:done]),
                    atol=1e-4, rtol=0)
                checked += 1
        # the rows behind the pieces are padding: length 1 on the sink
        assert (step["lengths"][row:] == 1).all()
        assert not step["tables"][row:].any()
        assert rec.fields["bucket_b"] == R and rec.fields["bucket_len"] == S
        assert rec.fields["tokens"] == sum(n for n, *_ in rec.rows)
        # the attention the step covers follows the requests, not the rows
        assert step["span"]["qk_pairs"] == sum(
            n * (done - n) + n * (n + 1) // 2 for n, _, done, _ in rec.rows)
    return checked


_solo = {}


def _solo_streams(models, family, prompts):
    """Greedy streams of the prompts served one at a time."""
    todo = [p for p in prompts if (family, tuple(p)) not in _solo]
    if todo:
        engine = _engine(models, family)
        for p in todo:
            s = engine.submit(p, max_new_tokens=NEW, temperature=0.0)
            _drive(engine, [s])
            _solo[family, tuple(p)] = list(s)
        engine.shutdown()
    return [_solo[family, tuple(p)] for p in prompts]


def _counted(engine, seen) -> dict:
    """``stats()`` counts what was launched: slots by rows x row length."""
    st = engine.stats()
    assert st["prefill_steps_packed"] == st["prefill_steps"] == len(seen)
    assert st["prefill_slots"] == sum(s["tokens"].size for s in seen)
    assert st["prefill_tokens_total"] == sum(
        s["rec"].fields["tokens"] for s in seen)
    flight = [r for r in engine.debug_dump()["steps"]
              if r["kind"].startswith("prefill")]
    assert st["prefill_slots"] == sum(
        r["bucket_b"] * r["bucket_len"] for r in flight)
    return st


@pytest.mark.parametrize("family", ["llama", "gpt"])
@pytest.mark.parametrize("n", LENGTHS)
def test_a_prompt_alone_is_its_pieces(models, family, n):
    engine = _engine(models, family)
    assert engine._piece == PIECE and engine._piece_rows == (
        1, 2, 3, 4, 5, 6, 7, 8)
    seen = _watch(engine)
    (prompt,) = _prompts([n], seed=n)
    stream = engine.submit(prompt, max_new_tokens=NEW, temperature=0.0)
    _drive(engine, [stream])
    assert len(seen) == 1
    assert seen[0]["tokens"].shape[0] == -(-n // PIECE)  # 1-6: no padding
    assert _check_steps(models, family, seen, engine) == 1
    assert [list(stream)] == _solo_streams(models, family, [prompt])
    st = _counted(engine, seen)
    assert st["prefill_tokens_total"] == n
    assert st["prefill_slots"] == -(-n // PIECE) * PIECE
    # every rung of the ladder was made before the first step, and nothing
    # since: the prefill programs are the ladder whatever the lengths
    assert {s[1] for s in engine.fns.signatures if s[0] != "decode"} == {
        (rows, PIECE) for rows in engine._piece_rows}
    engine.shutdown()


@pytest.mark.parametrize("family", ["llama", "gpt"])
@pytest.mark.parametrize("lens", [(1, 127, 128, 129), (300, 129, 1, 128)])
def test_four_together_share_a_step(models, family, lens):
    engine = _engine(models, family)
    seen = _watch(engine)
    prompts = _prompts(lens, seed=7)
    streams = [engine.submit(p, max_new_tokens=NEW, temperature=0.0)
               for p in prompts]
    _drive(engine, streams)
    pieces = sum(-(-n // PIECE) for n in lens)
    assert len(seen) == 1 and len(seen[0]["rec"].batch) == 4
    assert seen[0]["tokens"].shape[0] == pieces  # 5 and 7 are rungs
    assert _check_steps(models, family, seen, engine) == 4
    assert [list(s) for s in streams] == _solo_streams(
        models, family, prompts)
    st = _counted(engine, seen)
    assert st["prefill_slots"] == pieces * PIECE
    engine.shutdown()


@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_the_budget_splits_a_prompt_over_two_steps(models, family):
    """700 + 300 tokens are 6 + 3 pieces and a step holds 8: the second
    prompt's first 256 tokens ride the first step, its last 44 the next,
    at ``prefill_done``; its first token is the second step's."""
    engine = _engine(models, family)
    seen = _watch(engine)
    prompts = _prompts((700, 300), seed=11)
    streams = [engine.submit(p, max_new_tokens=NEW, temperature=0.0)
               for p in prompts]
    _drive(engine, streams)
    assert [s["tokens"].shape[0] for s in seen] == [8, 1]
    first, second = (s["rec"].rows for s in seen)
    assert [(n, done, final) for n, _, done, final in first] == [
        (700, 700, True), (256, 256, False)]
    assert [(n, done, final) for n, _, done, final in second] == [
        (44, 300, True)]
    assert seen[1]["starts"][0] == 256
    assert _check_steps(models, family, seen, engine) == 2
    assert [list(s) for s in streams] == _solo_streams(
        models, family, prompts)
    st = _counted(engine, seen)
    assert (st["prefill_tokens_total"], st["prefill_slots"]) == (
        1000, 9 * PIECE)
    engine.shutdown()


@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_a_prefix_hit_starts_its_pieces_behind_the_hit(models, family):
    engine = _engine(models, family)
    (base,) = _prompts([300], seed=13)
    s0 = engine.submit(base, max_new_tokens=NEW, temperature=0.0)
    _drive(engine, [s0])
    seen = _watch(engine)
    (tail,) = _prompts([240], seed=14)
    prompt = base[:160] + tail  # ten whole blocks resident, 240 to compute
    stream = engine.submit(prompt, max_new_tokens=NEW, temperature=0.0)
    _drive(engine, [stream])
    assert len(seen) == 1 and engine.stats()["prefix_hit_tokens"] == 160
    assert seen[0]["starts"][:2].tolist() == [160, 288]
    assert seen[0]["lengths"][:2].tolist() == [128, 112]
    assert _check_steps(models, family, seen, engine) == 1
    assert [list(stream)] == _solo_streams(models, family, [prompt])
    engine.shutdown()


@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_a_preempted_streams_resume_is_packed_too(models, family):
    """A paused stream re-prefills prompt + generated through the same
    packed path its first prefill took: the next token is the unpaused
    run's (the logits at its last piece those of the chain alone)."""
    engine = _engine(models, family, preemption={})
    (prompt,) = _prompts([300], seed=17)
    stream = engine.submit(prompt, max_new_tokens=NEW, temperature=0.0,
                           priority="batch")
    for _ in range(4):
        engine.step()
    seen = _watch(engine)
    with engine._lock:
        (row,) = engine._running
        from ray_tpu.serve.llm import obs

        assert engine._preempt_one_locked(row, obs.clock())
    held = len(row.generated)
    assert 0 < held < NEW and engine.stats()["preempted"] == 1
    _drive(engine, [stream])
    out = list(stream)  # a stream is read once
    assert len(seen) == 1 and engine.stats()["preemptions_total"] == 1
    n, chain, done, final = seen[0]["rec"].rows[0]
    assert chain == prompt + out[:held] and final
    assert done == len(chain) and n == done - row.cached_tokens
    assert _check_steps(models, family, seen, engine) == 1
    assert [out] == _solo_streams(models, family, [prompt])
    engine.shutdown()


@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_a_chunk_cap_bounds_the_step_not_the_row(models, family):
    """``prefill_chunk_tokens`` 256 with ``max_prefill_batch`` 4: a step
    holds 4 pieces, a request at most two of them; the 300-token prompt
    takes two steps, the short one rides the first."""
    engine = _engine(models, family, prefill_chunk_tokens=256)
    assert engine._piece == PIECE and engine._piece_rows == (1, 2, 3, 4)
    seen = _watch(engine)
    prompts = _prompts((300, 100), seed=19)
    streams = [engine.submit(p, max_new_tokens=NEW, temperature=0.0)
               for p in prompts]
    _drive(engine, streams)
    assert [[n for n, *_ in s["rec"].rows] for s in seen] == [[256, 100], [44]]
    assert _check_steps(models, family, seen, engine) == 2
    assert [list(s) for s in streams] == _solo_streams(
        models, family, prompts)
    _counted(engine, seen)
    engine.shutdown()


# what keeps a row a request, and why the cache manager says so
_TINY = dict(block_size=4, num_blocks=129, max_batch_size=4,
             prefill_chunk_tokens=16, length_buckets=(16, 32, 64, 128))
ROWS = {
    "lfm2_moe": (dict(num_blocks=65, max_batch_size=4), "state rows"),
    "laguna": (_TINY, "tables by group"),
    "evabyte": (dict(_TINY, num_blocks=257, length_buckets=(16, 160)),
                "a ring and a slot table"),
    "smallthinker": (_TINY, "tables by group"),
}
# ... and what is packed besides ``llama`` and ``gpt`` (ISSUE 47): a pool in
# planes under one table, its ``state`` counters alone
PLANES = ("longcat_flash", "pangu_ultra_moe")


@pytest.mark.parametrize("family", sorted(ROWS))
def test_other_layouts_keep_a_row_a_request(jax_cpu, family):
    """State rows beside the pool, tables by group, a ring and a slot
    table: ``one_table`` is False for the reason the cache manager gives,
    no ladder exists, and a step's rows are its requests padded to the
    longest row's bucket, as they were; the slots are counted all the
    same."""
    from ray_tpu.serve._shapes import pad_to_bucket
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    settings, why = ROWS[family]
    engine = LLMEngine(EngineConfig(model=family, **settings),
                       auto_step=False)
    cache = engine.cache.cfg
    assert not cache.one_table and engine._piece is None
    assert cache.why_not_split.startswith(why)
    assert bool(cache.state_slots) == (family in ("lfm2_moe", "laguna"))
    vocab = min(engine.model_cfg.vocab_size, 300)
    prompts = _prompts((5, 23, 12), seed=3, vocab=vocab)
    streams = [engine.submit(p, max_new_tokens=4, temperature=0.0)
               for p in prompts]
    _drive(engine, streams)
    st = engine.stats()
    assert st["prefill_steps_packed"] == 0 < st["prefill_steps"]
    flight = [r for r in engine.debug_dump()["steps"]
              if r["kind"].startswith("prefill")]
    assert len(flight) == st["prefill_steps"]
    cap = engine.cfg.prefill_chunk_tokens or 10 ** 9
    for r in flight:
        assert "pieces" not in r
        assert r["bucket_b"] == pad_to_bucket(
            r["batch"], engine._batch_buckets)
        assert r["bucket_len"] in engine._length_buckets
        assert r["bucket_len"] <= pad_to_bucket(cap, engine._length_buckets)
    assert flight[0]["batch"] == 3  # the three went as three rows
    assert st["prefill_slots"] == sum(
        r["bucket_b"] * r["bucket_len"] for r in flight)
    assert st["prefill_tokens_total"] == 5 + 23 + 12
    for sig in engine.fns.signatures:
        if sig[0] != "decode":
            assert sig[1][0] in engine._batch_buckets
            assert sig[1][1] in engine._length_buckets
    engine.shutdown()


@pytest.mark.parametrize("family", PLANES)
def test_a_pool_in_planes_under_counters_is_packed(jax_cpu, family):
    """The latent families at the other layouts' tiny settings: a chunk of
    16 tokens is shorter than a q tile, so a piece is the chunk, the
    ladder 1-4 rows, and the three prompts go as 1 + 1 + 1 pieces, then
    the 23-token prompt's second chunk (tests/test_serve_llm_packed_latent
    .py holds them to the reference at pieces of 128)."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    engine = LLMEngine(EngineConfig(model=family, **_TINY), auto_step=False)
    cache = engine.cache.cfg
    assert cache.planes and cache.one_table and cache.why_not_split is None
    assert cache.state_slots == 0 and engine.cache.state is not None
    assert engine._piece == 16 and engine._piece_rows == (1, 2, 3, 4)
    prompts = _prompts((5, 23, 12), seed=3, vocab=300)
    streams = [engine.submit(p, max_new_tokens=4, temperature=0.0)
               for p in prompts]
    _drive(engine, streams)
    st = engine.stats()
    assert st["prefill_steps_packed"] == st["prefill_steps"] == 2
    flight = [r for r in engine.debug_dump()["steps"]
              if r["kind"].startswith("prefill")]
    assert [(r["kind"], r["pieces"], r["bucket_b"], r["bucket_len"])
            for r in flight] == [("prefill_chunk", 3, 3, 16),
                                 ("prefill_chunk", 1, 1, 16)]
    assert st["prefill_slots"] == 4 * 16
    assert st["prefill_tokens_total"] == 5 + 23 + 12
    assert st["executor"]["state"]["slots"] == 0
    engine.shutdown()


_PLAIN = dict(n_layer=2, n_kv_head=2, head_dim=8)
_LATENT = dict(n_layer=2, n_kv_head=1, head_dim=24,
               planes=(("latent", 16, 128), ("rope", 8, 128)))
# layout -> (``KVCacheConfig`` arguments, why a sequence may not be split
# over the rows of one prefill step; None: it may)
LAYOUTS = {
    "heads": (_PLAIN, None),
    "quantized heads": (dict(_PLAIN, quantization="int8"), None),
    "planes, counters-only state": (_LATENT, None),
    "state rows": (dict(_PLAIN, state_slots=5),
                   "state rows beside the pool: a piece's short convolution "
                   "and its matrix state need the piece before it"),
    "sliding groups": (dict(_PLAIN, groups=((None, (0,)), (8, (1,)))),
                       "tables by group: a window's blocks go back behind "
                       "a position"),
    "ring + slots": (dict(_PLAIN, block_size=4, groups=(
        (("ring", 32), (0, 1)), (("slots", 8), (0, 1)))),
        "a ring and a slot table, composed into a step's table by "
        "position"),
}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_one_table_is_what_the_cache_manager_says(layout):
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig

    settings, why = LAYOUTS[layout]
    cache = KVCacheConfig(**settings)
    assert cache.why_not_split == why
    assert cache.one_table == (why is None)
    # a host tier changes nothing of it; state rows refuse a pool in planes
    # as they refuse one by heads
    if not cache.planes:
        assert KVCacheConfig(
            **settings, host_cache_bytes=1 << 20).one_table == (why is None)
    if not cache.state_slots:
        assert not KVCacheConfig(**settings, state_slots=3).one_table


@pytest.mark.parametrize("hi,wide,want", [
    (1, False, (1,)), (4, False, (1, 2, 3, 4)),
    (8, False, (1, 2, 3, 4, 5, 6, 7, 8)),
    (20, False, (1, 2, 3, 4, 5, 6, 7, 8, 10, 13, 17, 20)),
    (64, False,
     (1, 2, 3, 4, 5, 6, 7, 8, 10, 13, 17, 22, 29, 38, 50, 64)),
    # where a program is dear (an unrolled stack's: ISSUE 47)
    (1, True, (1,)), (4, True, (1, 2, 3, 4)), (8, True, (1, 2, 3, 4, 6, 8)),
    (16, True, (1, 2, 3, 4, 6, 9, 13, 16)),
])
def test_stepped_buckets(hi, wide, want):
    from ray_tpu.serve._shapes import pad_to_bucket, stepped_buckets

    ladder = stepped_buckets(hi, wide=wide)
    assert ladder == want
    # a count pads by at most a third (a half on the wider ladder)
    for n in range(1, hi + 1):
        assert n <= pad_to_bucket(n, ladder) <= max(
            n + 1, n + n // (2 if wide else 3) + 1)
    with pytest.raises(ValueError):
        stepped_buckets(0)
