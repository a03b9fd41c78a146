"""On-device fused sampling + dispatch-ahead decode pipeline (ISSUE 5):
greedy parity with the host argmax reference, byte-identical failover
resume under keyed (seed, position) sampling, the bounded compile-kind
contract with sampling fused into the step, lag-1 EOS termination with
exactly-once block release, and the O(batch)-int32 host-sync budget.

The widened pipeline (ISSUE 33): a decode step is launched behind whatever
step is in flight with its ids gathered on the device, a prefill's sync
waits behind the next launch, never more than two step programs in flight;
what still collapses the lag; a first token's clock.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
import pytest

from conftest import assert_pool_clean, run_widened_schedule, watch_pipeline


def _f32(cfg):
    import jax.numpy as jnp

    return dataclasses.replace(cfg, dtype=jnp.float32, attention="xla")


def _model_config(family="llama"):
    if family == "gpt":
        from ray_tpu.models.gpt import GPTConfig

        return _f32(GPTConfig.tiny())
    from ray_tpu.models.llama import LlamaConfig

    return _f32(LlamaConfig.tiny())


def _engine(mc, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 64)
    return LLMEngine(
        EngineConfig(model="llama", model_config=mc, **kw), auto_step=False
    )


def _drain(eng, streams, steps=400):
    for _ in range(steps):
        if all(s.done for s in streams):
            break
        eng.step()
    while eng.step():  # reconcile any in-flight step (lag-1 drain)
        pass


# -------------------------------------------- greedy / on-device parity

@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_fused_greedy_token_matches_host_argmax(jax_cpu, family):
    """The fused epilogue (sample=) must pick exactly the token the old
    host path picked: argmax over the last-valid-position logits, for
    both the prefill and decode programs."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm.decode import DecodeFns
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig, PagedKVCache

    mc = _model_config(family)
    fns = DecodeFns(family, mc)
    params = fns.init(jax.random.PRNGKey(0), mc)
    bs = 8

    def fresh_cache():
        c = PagedKVCache(KVCacheConfig(
            n_layer=mc.n_layer,
            n_kv_head=getattr(mc, "n_kv_head", mc.n_head),
            head_dim=mc.head_dim, num_blocks=32, block_size=bs,
            dtype=mc.dtype,
        ))
        c.allocate("s")
        return c

    prompt = [3, 141, 59, 26, 250, 7, 91]
    tokens = np.zeros((1, 8), np.int32)
    tokens[0, : len(prompt)] = prompt
    greedy = {
        "seeds": jnp.zeros((1,), jnp.uint32),
        "temperature": jnp.zeros((1,), jnp.float32),
        "top_k": jnp.zeros((1,), jnp.int32),
        "top_p": jnp.ones((1,), jnp.float32),
    }

    # prefill: logits path (sample=None) vs fused token path
    cache = fresh_cache()
    cache.ensure_capacity("s", len(prompt), reserved=False)
    args = (
        jnp.asarray(tokens), jnp.asarray([len(prompt)], np.int32),
        jnp.asarray(cache.block_table("s", 1)[None, :]),
    )
    logits, cache.k, cache.v, _ = fns.prefill(params, cache.k, cache.v, *args)
    cache2 = fresh_cache()
    cache2.ensure_capacity("s", len(prompt), reserved=False)
    tok, cache2.k, cache2.v, _ = fns.prefill(
        params, cache2.k, cache2.v, *args, sample=greedy
    )
    ref = int(np.argmax(np.asarray(logits)[0]))
    assert int(np.asarray(tok)[0]) == ref

    # decode: same comparison one step further
    seq_len = len(prompt) + 1
    for c in (cache, cache2):
        c.ensure_capacity("s", seq_len, reserved=False)
    dec_args = lambda c: (  # noqa: E731 — tiny per-cache tuple builder
        jnp.asarray([ref], np.int32),
        jnp.asarray([seq_len - 1], np.int32),
        jnp.asarray(c.block_table("s", 2)[None, :]),
    )
    logits, cache.k, cache.v, _ = fns.decode(
        params, cache.k, cache.v, *dec_args(cache)
    )
    tok, cache2.k, cache2.v, _ = fns.decode(
        params, cache2.k, cache2.v, *dec_args(cache2), sample=greedy
    )
    assert int(np.asarray(tok)[0]) == int(np.argmax(np.asarray(logits)[0]))


def test_pipelined_engine_matches_solo_runs(jax_cpu):
    """Dispatch-ahead must be invisible to outputs: concurrent staggered
    requests produce exactly the solo-run tokens, and the flight ring
    shows the pipeline actually engaged (lag-1 sync records)."""
    mc = _model_config()
    prompts = [[1, 2, 3], [7] * 11, [100, 200, 300, 400, 5]]
    solo = [_engine(mc).generate(p, max_new_tokens=10) for p in prompts]

    eng = _engine(mc)
    streams = [eng.submit(p, max_new_tokens=10) for p in prompts]
    _drain(eng, streams)
    assert [list(s) for s in streams] == solo

    recs = eng.debug_dump()["steps"]
    lags = [r.get("sync_lag") for r in recs if "sync_lag" in r]
    assert 1 in lags, f"pipeline never reached steady state: {lags}"
    assert eng.stats()["decode_inflight"] == 0  # fully drained


# ------------------------------------------------- failover byte-identity

def test_resume_byte_identical_under_keyed_sampling(jax_cpu):
    """Keyed (seed, absolute-position) sampling makes failover resume
    byte-identical BY CONSTRUCTION — including temperature + top-p — with
    no RNG stream to fast-forward: the resumed engine samples token N
    from fold_in(seed, N) exactly as the dead replica would have."""
    mc = _model_config()
    prompt = [9, 8, 7, 200, 13]
    kw = dict(max_new_tokens=12, temperature=0.8, top_p=0.9, seed=5)

    full = _engine(mc).generate(prompt, **kw)
    assert len(full) == 12

    for k in (1, 4, 11):
        resumed = _engine(mc).generate(
            prompt + full[:k],
            max_new_tokens=12 - k,
            temperature=0.8, top_p=0.9, seed=5,
            start_index=k,
        )
        assert resumed == full[k:], f"divergence resuming at {k}"


# ------------------------------------------------- compile-count contract

def test_decode_compile_kinds_do_not_grow_with_sampling(jax_cpu):
    """Fused sampling swaps the program epilogue, not its signature: a
    traffic mix of greedy / top-k / top-p / seeded requests compiles the
    SAME (kind, shape) set as pure greedy — still only
    (prefill, prefill_chunk, decode) x bucket shapes."""
    mc = _model_config()
    eng = _engine(mc)
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

    # a second wave with NEW sampling configs at the same shapes must not
    # compile anything: sampling params are data, not signature
    streams = [
        eng.submit([40 + i, 50 + i, 60 + i], max_new_tokens=6,
                   temperature=0.3 + 0.1 * i, top_k=2 + i, seed=100 + i)
        for i in range(4)
    ]
    _drain(eng, streams)
    assert len(eng.fns.signatures) == before


# --------------------------------------- lag-1 EOS + exactly-once release

def test_eos_under_lag_terminates_exactly_once(jax_cpu):
    """A request hitting EOS while its next token is already in flight
    must (a) never emit the speculative token and (b) release its blocks
    exactly once — the pool accounting survives repeated EOS traffic."""
    mc = _model_config()
    # discover what greedy decode emits first for this prompt...
    probe = _engine(mc).generate([4, 4, 8], max_new_tokens=3)
    eos = probe[1]
    expected = probe[: probe.index(eos) + 1]  # up to and including EOS

    # ...then make that token EOS and run with plenty of budget and a
    # second request keeping the batch busy (so the pipeline stays on)
    eng = _engine(mc, eos_id=eos)
    s1 = eng.submit([4, 4, 8], max_new_tokens=50)
    s2 = eng.submit([7] * 9, max_new_tokens=20)
    _drain(eng, streams := [s1, s2])
    out1 = list(s1)
    assert out1 == expected, "tokens past EOS leaked into the stream"
    assert all(s.done for s in streams)

    # exactly-once release: every block is back (free or prefix-cached),
    # nothing stuck in quarantine, nothing double-freed
    snap = eng.cache.debug_snapshot()
    assert snap["used_blocks"] == 0, snap
    assert snap["quarantined_blocks"] == 0, snap
    assert snap["reserved_blocks"] == 0, snap
    assert snap["live_sequences"] == 0, snap
    assert snap["freed_total"] == snap["allocated_total"], snap

    # and the pool still serves follow-up traffic at full capacity
    # (generate returns at EOS with the speculative step still in
    # flight; one more step collapses the lag and frees the blocks)
    again = eng.generate([4, 4, 8], max_new_tokens=50)
    while eng.step():
        pass
    assert again == expected
    assert eng.cache.debug_snapshot()["used_blocks"] == 0


# ------------------------------------------------- the widened pipeline

@pytest.mark.parametrize("family,sampling", [
    ("gpt", {}), ("llama", {}),
    ("llama", dict(temperature=0.8, top_p=0.9, seed=5)),
])
def test_widened_pipeline_matches_solo_runs(jax_cpu, family, sampling):
    """Staggered budgets and joins in mid-stream: the bytes of solo runs,
    greedy and sampled (a row's draws are keyed by seed and position,
    whatever batch it rode in), with the pipeline kept across every finish
    and join (conftest ``run_widened_schedule`` has the assertions)."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    mc = _model_config(family)

    def make(**kw):
        return LLMEngine(EngineConfig(model=family, model_config=mc,
                                      block_size=8, num_blocks=128, **kw),
                         auto_step=False)

    run_widened_schedule(make, mc.vocab_size, **sampling)


def test_quarantined_block_waits_for_the_newer_step(jax_cpu):
    """Two step programs queued: a block freed then goes back at the sync
    of the NEWER one, not at whichever sync comes next, and exactly once."""
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig, PagedKVCache

    cache = PagedKVCache(KVCacheConfig(
        n_layer=1, n_kv_head=1, head_dim=8, num_blocks=9, block_size=4))
    for seq, fence in (("a", 1), ("b", 2), ("c", 2)):
        cache.allocate(seq)
        cache.ensure_capacity(seq, 8, reserved=False)
    free0 = len(cache._free)
    assert cache.free("a", quarantine=True, fence=1) == 2
    assert cache.free("b", quarantine=True, fence=2) == 2  # two in flight
    assert len(cache._free) == free0
    assert cache.flush_quarantine(upto=1) == 2  # the older step's sync
    assert [f for f, _ in cache._quarantine] == [2, 2]
    assert cache.flush_quarantine(upto=1) == 0
    assert cache.flush_quarantine(upto=2) == 2  # the newer one's
    assert cache.free("c", quarantine=True, fence=2) == 2
    assert cache.flush_quarantine() == 2  # nothing in flight: all of it
    assert len(cache._free) == free0 + 6 == len(set(cache._free))
    assert cache.debug_snapshot()["quarantined_blocks"] == 0


def test_feed_ids_gathers_and_selects(jax_cpu):
    """Row i is ``source[feed[0, i]]`` where that index is >= 0, else the
    id the host held, ``feed[1, i]``; the program carries no step
    program's name (a trace's reader pairs step programs by name)."""
    import jax.numpy as jnp

    from ray_tpu.serve.llm import executor

    source = jnp.asarray([10, 11, 12, 13], jnp.int32)
    feed = np.asarray([[2, -1, 0, -1, 3, -1, -1, -1],
                       [99, 7, 98, 8, 97, 0, 0, 0]], np.int32)
    out = np.asarray(executor.feed_ids(source, jnp.asarray(feed)))
    assert out.tolist() == [12, 7, 10, 8, 13, 0, 0, 0]
    name = executor._feed_ids.__name__
    assert name == "feed_ids"
    assert not any(n in f"jit_{name}" for n in
                   ("_prefill", "_decode_step", "_verify_step"))


def test_id_gather_is_compiled_with_the_step_shapes(jax_cpu):
    """Every (ids of a width, decode row bucket) pair is compiled where
    its step shapes are first run, so a warm-up that reaches the step
    shapes reaches every gather: a second engine over the same shapes,
    joins and finishes in another order, compiles none."""
    from ray_tpu.serve.llm import executor

    mc = _model_config()

    def run(order):
        eng = _engine(mc, max_batch_size=4)
        streams = []
        for n_rows, new in order:
            streams += [eng.submit([i + 1, 2, 3], max_new_tokens=new + i)
                        for i in range(n_rows)]
            for _ in range(2):
                eng.step()
        _drain(eng, streams)
        st = eng.stats()
        eng.shutdown()
        return st

    run([(1, 3), (2, 3), (4, 3)])  # prefill and decode rows 1, 2, 4
    size = executor._feed_ids._cache_size()
    st = run([(2, 5), (1, 2), (1, 9), (2, 4)])
    assert st["decode_steps_remapped"] > 0
    assert executor._feed_ids._cache_size() == size


def test_a_decode_program_has_one_form_of_ids(jax_cpu):
    """A decode program is called with its ids ON THE DEVICE, always: the
    array of the step in flight, the gather from it (also where the host
    holds every id: a decode behind a chunk that is not its row's last)
    or, with nothing in flight, the host's array put there first. jit's
    fast path is keyed by its arguments' kinds, and a call that leaves it
    rebuilds the executable's wrapper (0.9 s on the chip, every stream
    stalled: PR 39): so one entry a decode shape, whatever the traffic."""
    import jax

    mc = dataclasses.replace(_model_config(), max_seq_len=96)  # own jits
    eng = _engine(mc, max_batch_size=4, prefill_chunk_tokens=8,
                  batch_buckets=(1, 4), length_buckets=(96,))
    kinds, decode = [], eng.executor.fns.decode

    def seen(params, k, v, tokens, *a, **kw):
        kinds.append(type(tokens))
        return decode(params, k, v, tokens, *a, **kw)

    eng.executor.fns.decode = seen
    first = eng.submit([1, 2, 3], max_new_tokens=12)
    for _ in range(3):
        eng.step()
    # a prompt of three chunks joins: decode steps run between its chunks
    late = eng.submit(list(range(1, 21)), max_new_tokens=4)
    _drain(eng, [first, late])
    st = eng.stats()
    assert len(kinds) == st["decode_steps"] == st["decode_steps_steady"] > 0
    # every steady step over another batch gathered its ids on the device
    assert st["host"]["spans"]["executor.feed"][0] == \
        st["decode_steps_remapped"] > 0
    shapes = {sig for sig in eng.executor.fns.signatures
              if sig[0] == "decode"}
    assert eng.executor.fns._decode._cache_size() == len(shapes)
    # nothing in flight, the host holds the ids: a constrained row
    # collapses the lag before every launch
    bound = eng.submit([1, 2, 3], max_new_tokens=6,
                       structured={"type": "regex", "pattern": "[a-z]{4}"})
    _drain(eng, [bound])
    st = eng.stats()
    assert len(kinds) == st["decode_steps"] > st["decode_steps_steady"]
    assert not any(issubclass(k, np.ndarray) for k in kinds)
    assert all(issubclass(k, jax.Array) for k in kinds)
    eng.shutdown()


def test_eos_races_the_lag_across_joins_and_finishes(jax_cpu):
    """An EOS arrives one step late, as ever (one wasted row), also where
    it is a row's FIRST token, sampled by a prefill whose sync waits
    behind the next launch: nothing past it reaches the stream, neighbours
    are the bytes of their solo runs, every block goes back once."""
    mc = _model_config()
    probe = _engine(mc).generate([4, 4, 8], max_new_tokens=3)
    for eos in (probe[0], probe[1]):
        others = [([7] * 9, 7), ([5, 6, 7, 8], 12), ([9, 9], 4)]
        solo = []
        for p, n in others:
            out = _engine(mc, eos_id=eos).generate(p, max_new_tokens=n)
            solo.append(out)
        eng = _engine(mc, eos_id=eos, max_batch_size=4)
        seen = watch_pipeline(eng)
        streams = [eng.submit(*others[0][:1], max_new_tokens=others[0][1])]
        eng.step()
        eng.step()
        s_eos = eng.submit([4, 4, 8], max_new_tokens=50)
        streams += [eng.submit(p, max_new_tokens=n) for p, n in others[1:]]
        _drain(eng, streams + [s_eos])
        assert list(s_eos) == probe[: probe.index(eos) + 1]
        assert [list(s) for s in streams] == solo
        decodes = [r for r in eng.debug_dump()["steps"]
                   if r["kind"] == "decode" and r["batch"]]
        assert all(r["steady"] for r in decodes)
        assert seen["most"] == 2 and seen["out"] == 0
        assert_pool_clean(eng)
        eng.shutdown()


@pytest.mark.parametrize("how", ["cancel", "deadline"])
@pytest.mark.parametrize("when", ["prefill_in_flight", "decode_in_flight"])
def test_eviction_with_a_step_in_flight(jax_cpu, how, when):
    """A row cancelled, or past its deadline, while a step program that
    holds it is in flight: its stream fails, its blocks go back exactly
    once at that step's reconcile, the other rows are the bytes of their
    solo runs and the pipeline goes on."""
    from ray_tpu.serve.llm import DeadlineExceededError, RequestCancelledError

    mc = _model_config()
    keep = [([1, 2, 3], 9), ([7] * 11, 6)]
    solo = [_engine(mc).generate(p, max_new_tokens=n) for p, n in keep]
    eng = _engine(mc, max_batch_size=4)
    eng.generate([5, 5, 5], max_new_tokens=3)  # compiled: deadlines are real
    while eng.step():
        pass
    seen = watch_pipeline(eng)
    streams = [eng.submit(p, max_new_tokens=n) for p, n in keep]
    kw = dict(deadline_s=0.05) if how == "deadline" else {}
    victim = eng.submit([9, 8, 7, 6], max_new_tokens=40, **kw)
    eng.step()  # one prefill over the three rows, in flight
    if when == "decode_in_flight":
        eng.step()
        eng.step()
    assert eng.stats()["decode_inflight"] == 1
    if how == "cancel":
        assert eng.cancel(victim.request_id) is True
    else:
        time.sleep(0.08)
    _drain(eng, streams)
    with pytest.raises(RequestCancelledError if how == "cancel"
                       else DeadlineExceededError):
        list(victim)
    assert [list(s) for s in streams] == solo
    assert seen["most"] == 2 and seen["out"] == 0
    assert_pool_clean(eng)
    eng.shutdown()


def test_what_still_collapses_the_lag(jax_cpu):
    """A grammar-constrained row (its allow-mask needs the last id on the
    host) and a verify step (drafts are made from committed tokens) sync
    everything in flight BEFORE they launch: their records are not
    ``steady``, their syncs have lag 0, and nothing is left in flight
    behind a verify step."""
    mc = _model_config()
    eng = _engine(mc, max_batch_size=4, eos_id=2)
    free = eng.submit([4, 5, 6], max_new_tokens=20)
    bound = eng.submit([1, 2, 3], max_new_tokens=12,
                       structured={"type": "regex", "pattern": "[a-z]{8}"})
    _drain(eng, [free, bound])
    assert len(list(bound)) == 8  # the pattern's eight letters
    decodes = [r for r in eng.debug_dump()["steps"]
               if r["kind"] == "decode" and r["batch"]]
    with_bound = [r for r in decodes if r["batch"] == 2]
    assert len(with_bound) >= 6
    assert not any(r["steady"] for r in with_bound)
    # the sync a step makes before its launch is the step's before it
    assert {r["sync_lag"] for r in with_bound[1:]} == {0}
    alone = [r for r in decodes if r["batch"] == 1]
    assert alone and all(r["steady"] for r in alone[1:])
    assert eng.stats()["decode_steps_steady"] < eng.stats()["decode_steps"]
    assert_pool_clean(eng)
    eng.shutdown()

    motif = [435, 326, 262, 138, 158, 21, 39, 9]
    eng = _engine(mc, speculative_k=3)
    s = eng.submit(motif * 3, max_new_tokens=16)
    for _ in range(200):
        if s.done:
            break
        eng.step()
        if eng.last_step_kind == "decode" and eng.debug_dump()["steps"][-1][
                "kind"] == "verify":
            assert eng.stats()["decode_inflight"] == 0
    while eng.step():
        pass
    verifies = [r for r in eng.debug_dump()["steps"] if r["kind"] == "verify"]
    assert verifies and not any(r["steady"] for r in verifies)
    assert {r["sync_lag"] for r in verifies} == {0}
    eng.shutdown()


def test_first_token_does_not_wait_for_the_step_launched_behind(jax_cpu):
    """With a clock. The device is played by a queue: a program ends its
    duration after the later of its launch and the end of the one before,
    and a sync returns when its program has ended. A prefill of 60 ms is
    followed by a decode step of 80 ms launched behind it: the first token
    reaches its stream when the PREFILL ends (a few ms of host work later,
    as with an immediate sync), not when the decode step does, and the
    running row's next token does not wait for the prefill's sync."""
    mc = _model_config()
    eng = _engine(mc, max_batch_size=4)
    eng.generate([5, 5, 5], max_new_tokens=4)  # every shape below compiled
    s0 = eng.submit([1, 2, 3], max_new_tokens=30)
    for _ in range(4):
        eng.step()
    while eng.step() and eng.stats()["decode_inflight"] != 1:
        pass
    ex = eng.executor
    device = {"free_at": 0.0, "ends": {}, "log": []}

    def playing(fn, kind, dur):
        def run(*a, **kw):
            out = fn(*a, **kw)
            now = time.perf_counter()
            end = max(now, device["free_at"]) + dur
            device["free_at"] = end
            device["ends"][id(out)] = (kind, end, out)
            device["log"].append((kind, now, end))
            return out
        return run

    # a dense family's cold prompt is packed: the chunk program
    ex.prefill_chunk = playing(ex.prefill_chunk, "prefill", 0.060)
    ex.decode_step = playing(ex.decode_step, "decode", 0.080)
    sync = ex.sync_tokens

    def synced(tokens):
        kind, end, _ = device["ends"].pop(id(tokens), ("", 0.0, None))
        left = end - time.perf_counter()
        if left > 0:
            time.sleep(left)
        return sync(tokens)

    ex.sync_tokens = synced
    s1 = eng.submit([9, 8, 7, 6], max_new_tokens=5)
    it = iter(s1)
    eng.step()  # the prefill is launched; its sync waits
    assert eng.last_step_kind == "prefill"
    assert eng.stats()["decode_inflight"] == 1
    eng.step()  # the decode step is launched behind it, THEN the sync
    assert eng.last_step_kind == "decode"
    next(it)
    t_first = time.perf_counter()
    (_, _, prefill_end), (_, decode_launch, decode_end) = [
        e for e in device["log"] if e[0] in ("prefill", "decode")][-2:]
    assert decode_launch < prefill_end, "the step was launched behind it"
    assert t_first - prefill_end < 0.030, (t_first - prefill_end)
    assert t_first < decode_end - 0.030, "it waited for the decode step"
    rec = [r for r in eng.debug_dump()["steps"]
           if r["kind"] == "prefill_chunk"][-1]
    assert rec["sync_lag"] == 1
    # dur_ms: from the step's start to its ids on the host
    assert 55.0 <= rec["dur_ms"] <= 60.0 + 35.0
    ex.sync_tokens = sync
    _drain(eng, [s0, s1])
    eng.shutdown()


# --------------------------------------------------- O(batch) sync budget

def test_host_sync_moves_o_batch_int32_not_logits(jax_cpu):
    """ISSUE 5 acceptance: the per-step transfer is bucketed-batch int32
    token ids. Every sync record in the flight ring must be 4*bucket_b
    bytes — a logits pull would be vocab_size times larger."""
    mc = _model_config()
    eng = _engine(mc)
    streams = [eng.submit([i + 1] * 5, max_new_tokens=8) for i in range(3)]
    _drain(eng, streams)

    recs = [r for r in eng.debug_dump()["steps"] if "sync_bytes" in r]
    assert recs, "no sync records in the flight ring"
    # a packed prefill step's ids are a row a piece, padded to its ladder
    buckets = set(eng._batch_buckets) | set(eng._piece_rows)
    for r in recs:
        # 4 bytes per row, rows padded to a batch bucket — and nowhere
        # near a logits transfer (4 * bucket * vocab)
        assert r["sync_bytes"] % 4 == 0, r
        assert r["sync_bytes"] // 4 in buckets, r
        assert r["sync_bytes"] < 4 * mc.vocab_size, r
    st = eng.stats()
    assert st["host_sync_bytes_total"] == sum(r["sync_bytes"] for r in recs)
    assert st["host_sync_seconds_total"] > 0.0


def _leaf(x):
    return x


def _hot_faults(n):
    """Minor page faults taken by ``n`` calls of a tiny function made from
    here: none, unless the caller's frame ends a data-stack chunk."""
    import resource

    before = resource.getrusage(resource.RUSAGE_THREAD).ru_minflt
    for _ in range(n):
        _leaf(1)
    return resource.getrusage(resource.RUSAGE_THREAD).ru_minflt - before


def _at_depth(depth, n):
    return _hot_faults(n) if depth == 0 else _at_depth(depth - 1, n)


@pytest.mark.parametrize("depths", [range(0, 150), range(150, 300)])
def test_first_call_of_a_signature_gets_stack_room(depths):
    """PR 28: CPython keeps a thread's frames in 16 KB chunks and unmaps
    a chunk as the frame that opened it returns, so a tracer's small calls
    made right at a chunk's end each cost a map, a page fault and an unmap
    (the GPT-2 cell's kernel traces: 3.1 s or 16.6 s by the depth the step
    was called at). Under ``_with_stack_room`` no depth has such calls;
    ``DecodeFns._call`` gives that room to a signature's first call, the
    one that traces, and to no other."""
    from ray_tpu.serve.llm import decode

    n = 2000
    roomy = max(
        decode._with_stack_room(_at_depth, (d, n), {}) for d in depths)
    assert roomy < n // 20, (
        f"{roomy} page faults in {n} tiny calls under the roomy frame")

    fns = decode.DecodeFns("gpt", decode.get_family("gpt").default_config())
    frames = []

    def step(*args, **kwargs):
        import sys

        frames.append(sys._getframe(1).f_code.co_name)
        return args, kwargs

    sig = ("decode", (4,), (4, 8))
    seen = []
    fns.on_new_signature = seen.append
    for _ in range(3):
        assert fns._call(step, sig, 1, 2, sample=3) == ((1, 2), {"sample": 3})
    assert frames == ["_with_stack_room", "_call", "_call"]
    assert seen == [sig] and fns.signatures == {sig}


# What the engine asks of each served family (``decode.Family``), as the
# hand-written factory of each gave it before ISSUE 56 and as ``get_family``
# now READS it from the family's module and its ``CachedFamily`` record:
# (config class, verify step, state functions, state_rows, block_steps,
# which of the optional callables are set, donated_state_counters).
FAMILY_FACTS = {
    "gpt": ("GPTConfig", True, False, True, False, (), None),
    "llama": ("LlamaConfig", True, False, True, False, (), None),
    "lfm2_moe": ("Lfm2MoeConfig", False, True, True, False,
                 ("gmm_form",), None),
    "laguna": ("LagunaConfig", False, True, True, False,
               ("gmm_form",), None),
    "evabyte": ("EvaByteConfig", False, False, True, False, (), None),
    "pangu_ultra_moe": ("PanguUltraMoEConfig", False, True, False, False,
                        ("step_attrs", "gmm_form"), None),
    "smallthinker": ("SmallThinkerConfig", False, True, False, False,
                     ("gmm_form",), None),
    "longcat_flash": ("LongCatFlashConfig", False, True, False, False,
                      ("step_attrs", "gmm_form"), None),
    "minicpm_sala": ("MiniCPMSALAConfig", False, True, True, False,
                     ("block_state_bytes", "step_attrs"),
                     ("steps", "blocks")),
    "ling_hybrid": ("LingHybridConfig", False, True, True, False,
                    ("step_attrs", "gmm_form"),
                    ("pairs", "routed", "reads", "groups")),
    "sdar_moe": ("SdarMoeConfig", False, True, False, True,
                 ("gmm_form",), None),
    # state ROWS alone: an ``init_state`` and no ``counters``
    "falcon_h1": ("FalconH1Config", False, "rows alone", True, False,
                  ("step_attrs",), ()),
}


@pytest.mark.parametrize("name", sorted(FAMILY_FACTS))
def test_a_family_is_read_from_its_module(jax_cpu, name):
    """ISSUE 56: ``FAMILIES`` is a table name -> module, and the fifteen
    fields of the engine's ``Family`` are read from the module: its
    functions by their names, the rest from the record it declares."""
    import importlib

    from ray_tpu.models import cached
    from ray_tpu.ops.moe import step_gmm_form
    from ray_tpu.serve.llm import decode

    assert set(decode.FAMILIES) == set(FAMILY_FACTS)
    config, verifies, has_state, rows, blocks, optional, donated = \
        FAMILY_FACTS[name]
    fam = decode.get_family(name)
    assert fam is decode.get_family(name)
    m = importlib.import_module(decode.FAMILIES[name])
    assert isinstance(m.FAMILY, cached.CachedFamily)
    assert m.FAMILY.name == name
    for field in ("init", "prefill", "decode_step", "param_axes",
                  "quant_axes"):
        assert getattr(fam, field) is getattr(m, f"{name}_{field}"), field
    assert fam.prefill.__name__ == f"{name}_prefill"
    assert fam.decode_step.__name__ == f"{name}_decode_step"
    assert (fam.verify_step is not None) == verifies
    assert (m.FAMILY.no_verify is None) == verifies
    # the record alone says whether the module holds a verify step
    assert hasattr(m, f"{name}_verify_step") == verifies
    if verifies:
        assert fam.verify_step is getattr(m, f"{name}_verify_step")
    cfg = fam.default_config()
    assert type(cfg).__name__ == config and cfg == getattr(m, config).tiny()
    assert (fam.init_state is not None) == bool(has_state)
    assert (fam.counters is not None) == (has_state is True)
    if has_state:
        assert fam.init_state is getattr(m, f"{name}_init_state")
        assert fam.counters is getattr(m, f"{name}_counters", None)
    assert fam.state_rows is rows and fam.block_steps is blocks
    for field in ("block_state_bytes", "step_attrs", "gmm_form"):
        assert (getattr(fam, field) is not None) == (field in optional), field
    if "gmm_form" in optional:
        assert fam.gmm_form is step_gmm_form
    assert fam.donated_state_counters == donated
    assert len(dataclasses.fields(fam)) == 15


def test_a_family_that_lacks_a_required_name_fails_where_it_is_read(
        monkeypatch):
    """A misnamed ``<name>_quant_axes`` is an AttributeError that names it,
    raised by ``get_family``, not a None that fails in the executor."""
    import sys
    import types

    from ray_tpu.models import gpt
    from ray_tpu.serve.llm import decode

    fake = types.ModuleType("fake_family")
    fake.FAMILY = gpt.FAMILY
    for field in ("init", "prefill", "decode_step", "verify_step",
                  "param_axes"):
        setattr(fake, f"fake_{field}", getattr(gpt, f"gpt_{field}"))
    monkeypatch.setitem(sys.modules, "fake_family", fake)
    monkeypatch.setitem(decode.FAMILIES, "fake", "fake_family")
    with pytest.raises(AttributeError, match="fake_quant_axes"):
        decode.get_family("fake")


def test_a_familys_module_is_imported_when_it_is_first_asked_for():
    """``get_family`` imports LAZILY: importing the registry imports no
    family's file, and asking for one family imports that one (and what
    its file names of others', no more)."""
    import subprocess
    import sys

    code = """
import sys
from ray_tpu.serve.llm import decode
held = [n for n in decode.FAMILIES if decode.FAMILIES[n] in sys.modules]
assert not held, held
decode.get_family("evabyte")
held = [n for n in decode.FAMILIES if decode.FAMILIES[n] in sys.modules]
assert "evabyte" in held and "sdar_moe" not in held, held
print("lazy", len(decode.FAMILIES))
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "lazy 12"

