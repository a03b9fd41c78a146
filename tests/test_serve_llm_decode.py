"""On-device fused sampling + dispatch-ahead decode pipeline (ISSUE 5):
greedy parity with the host argmax reference, byte-identical failover
resume under keyed (seed, position) sampling, the bounded compile-kind
contract with sampling fused into the step, lag-1 EOS termination with
exactly-once block release, and the O(batch)-int32 host-sync budget.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest


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
    buckets = set(eng._batch_buckets)
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
