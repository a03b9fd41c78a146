"""Lossless speculative decoding (ISSUE 9): draft-and-verify on the
paged-KV engine.

The contract under test is LOSSLESSNESS: with ``speculative_k > 0`` the
committed token stream is byte-identical to the non-speculative engine —
for greedy AND temperature/top-p sampling, for both model families, on
the single-device AND the tp/fsdp-sharded executor, and regardless of
what the drafter proposes (a garbage drafter costs throughput, never
correctness). On top of that: the n-gram drafter actually accepts on
repeating-structure prompts (committed tokens/step > 1.3), the compile
kind set grows by exactly one kind (``verify``) and stays frozen under
mixed traffic, EOS landing mid-accepted-window releases blocks exactly
once, and a replica killed mid-stream with speculation on resumes
byte-identical on a survivor (cross-mode: the reference runs with
speculation OFF).

Parity tests run f32 + XLA attention, like the rest of the serving suite.
"""
from __future__ import annotations

import dataclasses

import pytest

from conftest import shutdown_if_setup_fails

from ray_tpu._private import chaos
from ray_tpu._private.chaos import Fault, FaultPlan


# repeating-structure prompt: the regime prompt-lookup drafting targets.
# This particular motif is one the tiny f32 llama greedily CONTINUES, so
# the n-gram drafter locks on and the accept-rate assertions are
# deterministic (verified: accept 1.0 up to k=4 on this config).
MOTIF = [435, 326, 262, 138, 158, 21, 39, 9]


def _f32(cfg):
    import jax.numpy as jnp

    return dataclasses.replace(cfg, dtype=jnp.float32, attention="xla")


def _model_config(family="llama"):
    if family == "gpt":
        from ray_tpu.models.gpt import GPTConfig

        return _f32(GPTConfig.tiny())
    from ray_tpu.models.llama import LlamaConfig

    return _f32(LlamaConfig.tiny())


def _engine(family, mc, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 64)
    return LLMEngine(
        EngineConfig(model=family, model_config=mc, **kw), auto_step=False
    )


def _drain(eng, streams, steps=600):
    for _ in range(steps):
        if all(s.done for s in streams):
            break
        eng.step()
    while eng.step():  # reconcile any in-flight step (lag-1 drain)
        pass


SAMPLINGS = [
    dict(),                                     # greedy
    dict(temperature=0.8, top_p=0.9, seed=7),   # nucleus
]


# --------------------------------------------------------------- drafter

def test_ngram_drafter_proposes_motif_continuation():
    """Prompt-lookup drafting: when the recent suffix repeats earlier in
    the context, the drafter proposes what followed the MOST RECENT
    earlier occurrence of the LONGEST matching n-gram."""
    from ray_tpu.serve.llm import NGramDrafter

    d = NGramDrafter()
    # context ...[1,2,3,4] 9 [1,2,3,4] — suffix [1,2,3,4] matched at the
    # first occurrence proposes the 9 and then the motif again
    ctx = [1, 2, 3, 4, 9, 1, 2, 3]
    assert d.propose(ctx, [4], 3) == [9, 1, 2]
    # longest n wins: suffix [3,4] -> after most recent [3,4] comes 9,
    # even though a 1-gram [4] also matches at the same spot
    assert d.propose([3, 4, 9, 3], [4], 1) == [9]
    # most recent occurrence wins over an earlier one
    assert d.propose([5, 1, 5, 2], [5], 1) == [2]
    # no earlier occurrence of any suffix n-gram -> no proposal
    assert d.propose([1, 2, 3], [4], 3) == []
    # k truncates at the end of the context
    assert d.propose([7, 8, 7], [], 5) == [8, 7]
    # degenerate contexts never raise
    assert d.propose([], [], 3) == []
    assert d.propose([1], [], 3) == []


def test_ngram_drafter_validates_and_builds():
    from ray_tpu.serve.llm import Drafter, NGramDrafter, build_drafter

    with pytest.raises(ValueError):
        NGramDrafter(max_n=2, min_n=3)
    with pytest.raises(ValueError):
        NGramDrafter(min_n=0)
    assert isinstance(build_drafter("ngram"), NGramDrafter)
    assert build_drafter(None) is None
    with pytest.raises(ValueError):
        build_drafter("markov")
    with pytest.raises(TypeError):
        build_drafter(object())

    class Custom:
        def propose(self, prompt, generated, k):
            return []

    custom = Custom()
    assert build_drafter(custom) is custom
    assert isinstance(custom, Drafter)  # runtime-checkable protocol


# -------------------------------------------- losslessness (single-chip)

@pytest.mark.parametrize("family", ["gpt", "llama"])
@pytest.mark.parametrize("sampling", SAMPLINGS,
                         ids=["greedy", "temp_top_p"])
def test_spec_stream_is_byte_identical(jax_cpu, family, sampling):
    """Acceptance: speculation on vs off produces the SAME tokens, for
    greedy and temperature/top-p, both families. The repeating-motif
    prompt makes the greedy case actually exercise multi-token commits
    (repetition cycles of the tiny models); the sampled case mostly
    rejects — losslessness must hold either way."""
    mc = _model_config(family)
    base = _engine(family, mc).generate(
        MOTIF * 3, max_new_tokens=24, **sampling
    )
    spec = _engine(family, mc, speculative_k=3).generate(
        MOTIF * 3, max_new_tokens=24, **sampling
    )
    assert spec == base
    assert len(base) == 24


def test_spec_accepts_on_repeating_prompts(jax_cpu):
    """The n-gram drafter must EARN its keep on repeating structure:
    accept rate > 0 and mean committed tokens per verify step > 1.3
    (the ISSUE 9 bar), with the speculative config surfaced through
    describe()/stats()/debug_dump()."""
    mc = _model_config()
    eng = _engine("llama", mc, speculative_k=3)
    s = eng.submit(MOTIF * 3, max_new_tokens=32)
    _drain(eng, [s])
    assert len(list(s)) == 32
    st = eng.stats()
    assert st["spec_steps"] > 0
    assert st["spec_accept_rate"] > 0.0
    assert st["spec_committed_per_step"] > 1.3, st
    assert st["spec_committed_tokens"] >= st["spec_accepted_tokens"]
    spec_desc = st["executor"]["speculative"]
    assert spec_desc == {"speculative_k": 3, "drafter": "ngram"}
    assert (
        eng.debug_dump()["stats"]["executor"]["speculative"] == spec_desc
    )
    # non-speculative engines advertise the field as None
    assert _engine("llama", mc).stats()["executor"]["speculative"] is None


def test_spec_budget_never_overshoots(jax_cpu):
    """max_new_tokens is exact under speculation: the k_eff clamp keeps
    a fully-accepted window from committing past the budget."""
    mc = _model_config()
    for budget in (1, 2, 5):
        toks = _engine("llama", mc, speculative_k=3).generate(
            MOTIF * 3, max_new_tokens=budget
        )
        assert len(toks) == budget


# ------------------------------------------------ losslessness (sharded)

def test_spec_stream_is_byte_identical_sharded(jax_cpu):
    """The verify step through the GSPMD ShardedExecutor (tp=2/fsdp=2 on
    the 8-virtual-device CPU mesh) commits the same stream as the
    single-device non-speculative engine — both sampled and greedy."""
    mc = _model_config()
    for sampling in SAMPLINGS:
        base = _engine("llama", mc).generate(
            MOTIF * 3, max_new_tokens=16, **sampling
        )
        eng = _engine("llama", mc, tp=2, fsdp=2, speculative_k=3)
        assert eng.stats()["executor"]["executor"] == "sharded"
        spec = eng.generate(MOTIF * 3, max_new_tokens=16, **sampling)
        while eng.step():
            pass
        assert spec == base


# ---------------------------------------------- compile-kind contract

def test_verify_adds_exactly_one_compile_kind(jax_cpu):
    """At most one new jitted program kind: mixed speculative traffic
    (greedy / top-k / top-p / plain temperature) compiles only
    (prefill, prefill_chunk, decode, verify) x bucket shapes, and a
    second wave with fresh sampling configs compiles nothing — the
    draft length is data, the window width is frozen per engine."""
    mc = _model_config()
    eng = _engine("llama", mc, speculative_k=3, max_batch_size=4)
    mixes = [
        dict(),
        dict(temperature=0.7, top_k=4, seed=1),
        dict(temperature=0.9, top_p=0.8, seed=2),
        dict(temperature=1.1, seed=3),
    ]
    streams = [
        # row 0 (greedy, cycling motif) reliably drafts once its output
        # enters the repetition cycle (within the 32-token budget); ANY
        # drafting row routes the WHOLE mixed batch through verify
        eng.submit(
            MOTIF * 3 if i == 0 else MOTIF * 2 + MOTIF[: i + 1],
            max_new_tokens=32, **m,
        )
        for i, m in enumerate(mixes)
    ]
    _drain(eng, streams)
    sigs = eng.fns.signatures
    kinds = {s[0] for s in sigs}
    assert "verify" in kinds, "speculative traffic never hit the verify path"
    assert kinds <= {"prefill", "prefill_chunk", "decode", "verify"}, kinds
    verify_sigs = {s for s in sigs if s[0] == "verify"}
    # the verify window is FROZEN per engine: every verify program has
    # token shape (B_bucket, speculative_k + 1)
    assert all(s[1][1] == 4 for s in verify_sigs), verify_sigs

    streams = [
        eng.submit(MOTIF * 3, max_new_tokens=32)  # drafts again, same shapes
    ] + [
        eng.submit(MOTIF * 2 + MOTIF[: i + 1], max_new_tokens=32,
                   temperature=0.3 + 0.1 * i, top_k=2 + i, seed=100 + i)
        for i in range(1, 4)
    ]
    _drain(eng, streams)
    after = eng.fns.signatures
    # fresh sampling configs are data, not signature: no new kinds, and
    # the verify programs' TOKEN shapes (the frozen window) are exactly
    # what the first wave compiled. Their block-table widths, like plain
    # decode's and prefill's, may still walk the pre-existing bucket
    # ladder as contexts grow or start shorter — that ladder predates
    # speculation.
    assert {s[0] for s in after} <= {
        "prefill", "prefill_chunk", "decode", "verify"
    }
    assert {s[1] for s in after if s[0] == "verify"} == {
        s[1] for s in verify_sigs}


# --------------------------------------- EOS mid-window, exactly-once

class _OracleDrafter:
    """Proposes the continuation it was seeded with — every draft token
    matches the target, so verify steps commit full k+1 windows. Turns
    'EOS lands mid-accepted-window' from a probabilistic event into a
    deterministic one."""

    def __init__(self, prompt, continuation):
        self._prompt = list(prompt)
        self._continuation = list(continuation)

    def propose(self, prompt, generated, k):
        if list(prompt) != self._prompt:
            return []
        done = len(generated)
        return self._continuation[done:done + k]


def test_eos_mid_accepted_window_releases_blocks_once(jax_cpu):
    """A fully-accepted verify window that contains EOS must stop the
    stream AT the EOS token — nothing past it leaks — and release the
    request's blocks exactly once (no double-free, no leak), with the
    lag-1 pipeline active on surviving traffic."""
    mc = _model_config()
    prompt = MOTIF * 2
    probe = _engine("llama", mc).generate(prompt, max_new_tokens=10)
    # pick an EOS whose FIRST occurrence sits inside the first verify
    # window (positions 1..3 for k=3) so the cut happens mid-window
    eos = next(
        (t for t in probe[2:4] if probe.index(t) >= 2), probe[2]
    )
    expected = probe[: probe.index(eos) + 1]
    assert 3 <= len(expected) <= 4

    eng = _engine(
        "llama", mc, eos_id=eos, speculative_k=3,
        drafter=_OracleDrafter(prompt, probe),
    )
    s1 = eng.submit(prompt, max_new_tokens=50)
    s2 = eng.submit([7] * 9, max_new_tokens=20)  # keeps the batch busy
    _drain(eng, [s1, s2])
    assert list(s1) == expected, "tokens past EOS leaked into the stream"
    assert s2.done
    st = eng.stats()
    assert st["spec_steps"] >= 1 and st["spec_accepted_tokens"] >= 1, st

    snap = eng.cache.debug_snapshot()
    assert snap["used_blocks"] == 0, snap
    assert snap["quarantined_blocks"] == 0, snap
    assert snap["reserved_blocks"] == 0, snap
    assert snap["live_sequences"] == 0, snap
    assert snap["freed_total"] == snap["allocated_total"], snap

    # the pool still serves follow-up traffic at full capacity
    again = eng.generate(prompt, max_new_tokens=50)
    while eng.step():
        pass
    assert again == expected
    assert eng.cache.debug_snapshot()["used_blocks"] == 0


class _GarbageDrafter:
    """Adversarial drafter: out-of-vocab ids, negatives, and wrong-but-
    valid tokens. The engine must filter/reject its way to the exact
    non-speculative stream."""

    def __init__(self, vocab_size):
        self._vocab = vocab_size
        self._calls = 0

    def propose(self, prompt, generated, k):
        self._calls += 1
        garbage = [self._vocab + 5, -1, 0, 1, self._vocab * 2]
        return garbage[self._calls % len(garbage):][:k]


def test_garbage_drafter_is_lossless(jax_cpu):
    """A drafter can only waste compute, never corrupt the stream: with
    adversarial proposals the output still matches the non-speculative
    run byte-for-byte and the pool comes back clean."""
    mc = _model_config()
    for sampling in SAMPLINGS:
        base = _engine("llama", mc).generate(
            MOTIF * 2, max_new_tokens=12, **sampling
        )
        eng = _engine(
            "llama", mc, speculative_k=3,
            drafter=_GarbageDrafter(mc.vocab_size),
        )
        assert eng.generate(MOTIF * 2, max_new_tokens=12, **sampling) == base
        while eng.step():
            pass
        snap = eng.cache.debug_snapshot()
        assert snap["used_blocks"] == 0 and snap["reserved_blocks"] == 0


# ------------------------------------------------------ chaos failover

KILL_PROMPT = MOTIF * 2
KILL_SAMPLING = dict(max_new_tokens=12, seed=0)
KILL_AT_INDEX = 3  # inside the first multi-token committed burst


@pytest.fixture(scope="module")
def spec_ft_cluster():
    """Two speculative replicas (k=3, n-gram drafter) with a chaos plan
    killing the tagged request's replica mid-stream — exported through
    the environment so replica workers inherit it."""
    import os

    plan = FaultPlan(seed=7, faults=(
        Fault(point="llm.token", action="kill",
              when={"tag": "killspec", "index": KILL_AT_INDEX,
                    "resumed": False}),
    ))
    prev = os.environ.get(chaos.ENV_VAR)
    os.environ[chaos.ENV_VAR] = plan.to_json()
    chaos.clear()  # force re-read of the env plan in THIS process too

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import EngineConfig, build_llm_app

    ray_tpu.init(num_cpus=8)
    with shutdown_if_setup_fails():
        serve.start(http_options={"port": 0}, grpc_options={"port": 0})
        handle = serve.run(
            build_llm_app(
                EngineConfig(
                    model="llama", model_config=_model_config(), seed=0,
                    speculative_k=3,
                ),
                num_replicas=2,
            ),
            name="llm-spec-ft", route_prefix="/llmspec", timeout_s=180,
        )
    yield handle
    serve.shutdown()
    ray_tpu.shutdown()
    chaos.clear()
    if prev is None:
        os.environ.pop(chaos.ENV_VAR, None)
    else:
        os.environ[chaos.ENV_VAR] = prev


@pytest.mark.chaos
@pytest.mark.timeout(300)
def test_replica_death_mid_spec_stream_resumes_byte_identical(
    spec_ft_cluster,
):
    """Acceptance: kill the serving replica after N streamed tokens with
    speculation ON; the resumed stream completes byte-identical to an
    uninterrupted NON-speculative run — failover and mixed fleets are
    safe because speculation never changes committed tokens."""
    from ray_tpu.serve.llm import stream_tokens

    handle = spec_ft_cluster
    # cross-mode reference: local engine, speculation OFF
    reference = _engine("llama", _model_config()).generate(
        KILL_PROMPT, **KILL_SAMPLING
    )

    gen = stream_tokens(handle, {
        "prompt": KILL_PROMPT,
        "request_id": "kill-spec-1",
        "chaos_tag": "killspec",
        **KILL_SAMPLING,
    })
    chunks = list(gen)
    assert gen.failovers >= 1, "the chaos kill should have forced a failover"
    assert [c["index"] for c in chunks] == list(
        range(KILL_SAMPLING["max_new_tokens"]))
    assert [c["token"] for c in chunks] == reference
    # the surviving replica resumed the stream — with speculation still on
    stats = [s for s in handle.broadcast("stats") if s]
    assert sum(s.get("requests_resumed", 0) for s in stats) >= 1
    assert all(
        s["executor"]["speculative"]["speculative_k"] == 3 for s in stats
    )
