"""The lfm2_moe family on the CPU at the tiny preset, seeded weights: the
program against the plain reference (benchmark/reference/lfm2_moe.py), the
serving path (paged cache + state slots) against the full forward, the
router and the dropless grouped product case by case, the slots' lifetime,
what the engine refuses for the family, and its counters.

Tolerances, with their reason. Program and reference in float32 compute the
same mathematics and differ in the order of sums: 1e-5 on logits of size ~5
(seen 2.4e-6). With the program in bfloat16, as it is served, activations
are rounded to 8 bits of mantissa at every matmul, and where a token's k-th
and (k+1)-th router scores nearly tie the rounding flips one of its experts:
at hidden 64 with 2 of 8 experts a flip moves a logit by up to ~1.3 (seen),
while MOST positions move by the rounding alone (median 0.05, a ninth of
them past 0.3, seen). So the bf16 case bounds the median (0.15) and the
share of positions past 0.3 (a quarter), which a wrong term (no QK-norm, a
stale conv row, a dropped expert) fails at every position.
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


@pytest.fixture(scope="module")
def ref():
    from benchmark import common

    return common.load_named("reference", "lfm2_moe")


@pytest.fixture(scope="module")
def tiny(jax_cpu):
    """(float32 config, its seeded params)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.lfm2_moe import Lfm2MoeConfig, lfm2_moe_init

    cfg = dataclasses.replace(Lfm2MoeConfig.tiny(), dtype=jnp.float32)
    return cfg, lfm2_moe_init(jax.random.PRNGKey(1), cfg)


def _engine(cfg, params, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    settings = dict(model="lfm2_moe", model_config=cfg, num_blocks=65,
                    max_batch_size=4)
    settings.update(kw)
    return LLMEngine(EngineConfig(**settings), params=params,
                     auto_step=False)


def _prompts(lens, seed=0, vocab=512):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).tolist() for n in lens]


def _drive(engine, streams, limit=2000):
    for _ in range(limit):
        if all(s.done for s in streams):
            return
        engine.step()
    raise AssertionError("streams did not finish")


# ------------------------------------------------- program == reference


def test_config_is_hashable_and_counts_its_layers(jax_cpu):
    from ray_tpu.models.lfm2_moe import Lfm2MoeConfig

    cfg = Lfm2MoeConfig(layer_types=["conv", "conv", "full_attention", "conv",
                                     "conv", "conv", "full_attention",
                                     "conv"])
    assert isinstance(cfg.layer_types, tuple) and hash(cfg) == hash(
        dataclasses.replace(cfg))
    assert (cfg.n_layer, cfg.n_kv_layer, cfg.n_conv_layer,
            cfg.n_moe_layer) == (8, 2, 6, 6)
    assert cfg.head_dim == 64 and cfg.norm_eps == 1e-5
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeConfig(layer_types=("conv", "window"))


def test_forward_matches_reference_float32(tiny, ref):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.lfm2_moe import lfm2_moe_forward

    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(2), (3, 40), 1,
                                cfg.vocab_size)
    want = ref.logits(params, tokens, cfg)
    got = lfm2_moe_forward(params, tokens, cfg)
    assert float(jnp.max(jnp.abs(want))) > 1.0  # the layers do move it
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_forward_matches_reference_bfloat16(jax_cpu, ref):
    """As served: bf16 weights from the reference module's own init (the
    matrix leaves rounded once), activations bf16. See the module
    docstring for the two bounds."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.lfm2_moe import Lfm2MoeConfig, lfm2_moe_forward

    cfg = Lfm2MoeConfig.tiny()
    params = ref.init_fn()(jax.random.PRNGKey(1), cfg)
    assert params["layers"][1]["moe_gmm_w_in"].dtype == jnp.bfloat16
    assert params["layers"][1]["moe_route_bias"].dtype == jnp.float32
    tokens = jax.random.randint(jax.random.PRNGKey(2), (3, 40), 1,
                                cfg.vocab_size)
    diff = np.asarray(jnp.max(jnp.abs(
        lfm2_moe_forward(params, tokens, cfg)
        - ref.logits(params, tokens, cfg)), axis=-1))
    assert np.median(diff) < 0.15, np.median(diff)
    assert (diff > 0.3).mean() < 0.25, (diff > 0.3).mean()


def test_reference_sees_each_mechanism(tiny, ref):
    """The comparison is only as good as what it would catch: leaving out
    the QK-norm, the selection bias or the conv's first tap moves the
    reference's logits by far more than the float32 tolerance."""
    import jax
    import jax.numpy as jnp

    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 1,
                                cfg.vocab_size)
    want = ref.logits(params, tokens, cfg)

    def moved(change):
        p = jax.tree.map(lambda a: a, params)
        change(p["layers"])
        return float(jnp.max(jnp.abs(ref.logits(p, tokens, cfg) - want)))

    def no_qk_norm(layers):
        layers[1]["q_norm"] = layers[1]["q_norm"] * 3.0

    def no_bias(layers):
        layers[2]["moe_route_bias"] = jnp.zeros_like(
            layers[2]["moe_route_bias"])

    def no_first_tap(layers):
        layers[0]["short_conv_w"] = layers[0]["short_conv_w"].at[0].set(0.0)

    for change in (no_qk_norm, no_bias, no_first_tap):
        assert moved(change) > 1e-2, change.__name__


# ----------------------------------------- router and grouped product


def test_init_bias_moves_the_selection_and_keeps_the_load_even(jax_cpu):
    """The seed-made ``moe_route_bias`` at the published router's size (64
    experts, 4 a token): biased and unbiased selection differ for a good
    share of the tokens, and a 64-row decode batch still meets nearly
    every expert, as under a checkpoint's load-balancing bias."""
    import jax
    import numpy as np
    from ray_tpu.models.lfm2_moe import Lfm2MoeConfig, lfm2_moe_init
    from ray_tpu.ops.moe import moe_route

    cfg = Lfm2MoeConfig(
        vocab_size=64, d_model=256, n_head=2, n_kv_head=2, head_dim=16,
        layer_types=("conv",), num_dense_layers=0, d_mlp=32, d_expert=8)
    lp = lfm2_moe_init(jax.random.PRNGKey(3), cfg)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(4), (4096, cfg.d_model))
    _, biased = moe_route(x, lp["moe_route_w"], lp["moe_route_bias"], 4)
    _, plain = moe_route(x, lp["moe_route_w"], None, 4)
    biased, plain = np.sort(biased, -1), np.sort(plain, -1)
    assert 0.1 < (biased != plain).any(-1).mean() < 0.9
    read = [len(np.unique(biased[i:i + 64])) for i in range(0, 4096, 64)]
    assert np.mean(read) > 58, np.mean(read)
    load = np.bincount(biased.ravel(), minlength=64)
    assert load.max() / load.mean() < 2.5


def test_router_bias_changes_the_selection_not_the_weight(jax_cpu):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.moe import ROUTE_NORM_EPS, moe_route

    x = jax.random.normal(jax.random.PRNGKey(0), (16, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 8)) * 32 ** -0.5
    bias = jnp.zeros((8,)).at[5].set(10.0)  # expert 5 always chosen
    wts0, idx0 = moe_route(x, w, None, 2)
    wts1, idx1 = moe_route(x, w, bias, 2)
    assert bool(jnp.all(jnp.any(idx1 == 5, axis=-1)))
    assert not bool(jnp.all(jnp.any(idx0 == 5, axis=-1)))
    # weights come from the UNBIASED scores of whatever was chosen ...
    s = jax.nn.sigmoid(x @ w)
    picked = jnp.take_along_axis(s, idx1, axis=-1)
    np.testing.assert_allclose(
        wts1, picked / (picked.sum(-1, keepdims=True) + ROUTE_NORM_EPS),
        rtol=1e-6)
    # ... and sum to one (less the published code's epsilon)
    np.testing.assert_allclose(wts0.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(wts1.sum(-1), 1.0, atol=1e-5)
    raw, _ = moe_route(x, w, bias, 2, norm_topk=False, scale=2.0)
    np.testing.assert_allclose(raw, 2.0 * picked, rtol=1e-6)


def _loop_over_experts(x, wts, idx, w_in, w_out):
    import jax
    import jax.numpy as jnp

    out = jnp.zeros_like(x)
    for e in range(w_in.shape[0]):
        gate, up = jnp.split(x @ w_in[e], 2, axis=-1)
        y = (jax.nn.silu(gate) * up) @ w_out[e]
        out = out + jnp.sum(jnp.where(idx == e, wts, 0.0), -1)[:, None] * y
    return out


@pytest.mark.parametrize("routing", ["uniform", "all-on-one", "padded"])
def test_dropless_product_matches_loop_over_experts(jax_cpu, routing):
    """Any routing is computed whole: uniform, every token on one expert
    (a capacity layer would drop most of them), and a bucketed batch whose
    padding rows go nowhere and are not counted."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.moe import moe_dropless

    T, D, F, E, k = 24, 16, 8, 6, 2
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(keys[0], (T, D))
    w_in = jax.random.normal(keys[1], (E, D, 2 * F)) * D ** -0.5
    w_out = jax.random.normal(keys[2], (E, F, D)) * F ** -0.5
    wts = jax.nn.softmax(jax.random.normal(keys[3], (T, k)))
    if routing == "all-on-one":
        idx = jnp.stack([jnp.full((T,), 4), jnp.full((T,), 1)], axis=1)
    else:
        idx = jnp.stack([jnp.arange(T) % E, (jnp.arange(T) + 3) % E], axis=1)
    idx = idx.astype(jnp.int32)
    valid = None
    if routing == "padded":
        valid = jnp.arange(T) < 17
    y, sizes = moe_dropless(x, wts, idx, w_in, w_out, dtype=jnp.float32,
                            valid=valid)
    want = _loop_over_experts(x, wts, idx, w_in, w_out)
    if valid is not None:
        want = jnp.where(valid[:, None], want, 0.0)
        assert int(sizes.sum()) == 17 * k
        np.testing.assert_array_equal(np.asarray(y[17:]), 0.0)
    else:
        assert int(sizes.sum()) == T * k
    if routing == "all-on-one":
        assert sizes.tolist() == [0, T, 0, 0, T, 0]
    np.testing.assert_allclose(y, want, atol=2e-5)


def test_short_conv_chunks_and_single_steps_agree(jax_cpu):
    """The filter over a whole sequence == over two chunks with the state
    carried == position by position; zeros before position 0."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.short_conv import short_conv_decode, short_conv_prefill

    B, S, D, K = 2, 11, 8, 3
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    v = jax.random.normal(keys[0], (B, S, D))
    gate = jax.random.normal(keys[1], (B, S, D))
    w = jax.random.normal(keys[2], (K, D))
    full = jnp.full((B,), S, jnp.int32)
    whole, end = short_conv_prefill(v, gate, w, None, full)
    want = gate * sum(
        jnp.pad(v, ((0, 0), (K - 1, 0), (0, 0)))[:, j:j + S] * w[j]
        for j in range(K))
    np.testing.assert_allclose(whole, want, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(end), np.asarray(v[:, -2:]))
    a, mid = short_conv_prefill(v[:, :6], gate[:, :6], w, None,
                                jnp.full((B,), 6, jnp.int32))
    b, end2 = short_conv_prefill(v[:, 6:], gate[:, 6:], w, mid,
                                 jnp.full((B,), 5, jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate([a, b], 1)), np.asarray(whole))
    np.testing.assert_array_equal(np.asarray(end2), np.asarray(end))
    # a row shorter than its chunk leaves the state of its own last column
    _, short = short_conv_prefill(v, gate, w, None,
                                  jnp.array([S, 4], jnp.int32))
    np.testing.assert_array_equal(np.asarray(short[1]), np.asarray(v[1, 2:4]))
    state = jnp.zeros((B, K - 1, D))
    for t in range(S):
        y, state = short_conv_decode(v[:, t], gate[:, t], w, state)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(whole[:, t]))


# ---------------------------------------------- the serving path, logits


def _serve_logits(cfg, params, prompt, new, chunk=None, slot=1, state=None):
    """Prefill then ``new`` greedy decode steps through the paged cache and
    the state slot, on logits (``sample=None``): the logits that chose
    each generated token, [new, V]."""
    import jax.numpy as jnp

    from ray_tpu.models.lfm2_moe import (
        lfm2_moe_decode_step, lfm2_moe_init_state, lfm2_moe_prefill,
    )

    bs, nb = 8, 8
    pool = jnp.zeros((cfg.n_kv_layer, 17, bs, cfg.n_kv_head, cfg.head_dim),
                     cfg.dtype)
    k, v = pool, pool
    if state is None:
        state = lfm2_moe_init_state(cfg, 3)
    table = jnp.asarray([list(range(1 + 8 * (slot - 1), 9 + 8 * (slot - 1)))],
                        jnp.int32)
    slots = jnp.asarray([slot], jnp.int32)
    n = len(prompt)
    out = []
    if chunk is None:
        logits, k, v, state = lfm2_moe_prefill(
            params, k, v, jnp.asarray([prompt], jnp.int32),
            jnp.asarray([n], jnp.int32), table, cfg, state=state,
            slots=slots)
    else:
        for s in range(0, n, chunk):
            part = prompt[s:s + chunk]
            toks = np.zeros((1, chunk), np.int32)
            toks[0, :len(part)] = part
            logits, k, v, state = lfm2_moe_prefill(
                params, k, v, jnp.asarray(toks),
                jnp.asarray([len(part)], jnp.int32), table, cfg,
                start=jnp.asarray([s], jnp.int32), state=state, slots=slots)
    seq = list(prompt)
    for _ in range(new):
        out.append(np.asarray(logits[0]))
        seq.append(int(np.argmax(out[-1])))
        logits, k, v, state = lfm2_moe_decode_step(
            params, k, v, jnp.asarray([seq[-1]], jnp.int32),
            jnp.asarray([len(seq) - 1], jnp.int32), table, cfg, state=state,
            slots=slots)
    return np.stack(out), seq, state


def test_prefill_then_decode_matches_reference_on_logits(tiny, ref):
    import jax.numpy as jnp

    cfg, params = tiny
    prompt = _prompts([21], seed=5)[0]
    got, seq, _ = _serve_logits(cfg, params, prompt, 6)
    want = np.asarray(ref.logits(params, jnp.asarray([seq[:-1]]), cfg))[0]
    np.testing.assert_allclose(got, want[len(prompt) - 1:], atol=1e-5)


def test_chunked_prefill_matches_monolithic(tiny):
    cfg, params = tiny
    prompt = _prompts([21], seed=6)[0]
    whole, seq_a, _ = _serve_logits(cfg, params, prompt, 4)
    chunked, seq_b, _ = _serve_logits(cfg, params, prompt, 4, chunk=8)
    assert seq_a == seq_b
    np.testing.assert_allclose(chunked, whole, atol=1e-5)


def test_a_reused_slot_starts_from_zeros(tiny):
    """Whatever a slot held, a sequence's first chunk ignores it: both the
    monolithic prefill and a chunked one that starts at position 0."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.lfm2_moe import lfm2_moe_init_state

    cfg, params = tiny
    prompt = _prompts([13], seed=7)[0]
    clean, _, _ = _serve_logits(cfg, params, prompt, 3)
    dirty = lfm2_moe_init_state(cfg, 3)
    dirty["conv"] = jax.random.normal(
        jax.random.PRNGKey(9), dirty["conv"].shape, dirty["conv"].dtype) * 5
    for chunk in (None, 8):
        got, _, _ = _serve_logits(cfg, params, prompt, 3, chunk=chunk,
                                  state=dict(dirty))
        np.testing.assert_allclose(got, clean, atol=1e-5)
    assert not bool(jnp.all(dirty["conv"] == 0))


def test_batched_equals_solo_bit_for_bit(tiny):
    """A token's output depends on its own row only: three requests
    served together give each the logits it gets with the bucket to
    itself, bit for bit in float32 (no capacity, no batch-mates in the
    expert layer). The bucket's shape is held: on the CPU a dense product
    of ANOTHER row count sums in another order (1e-6), which is the
    backend's and not the model's."""
    import jax.numpy as jnp

    from ray_tpu.models.lfm2_moe import (
        lfm2_moe_decode_step, lfm2_moe_init_state, lfm2_moe_prefill,
    )

    cfg, params = tiny
    prompts = _prompts([9, 14, 5], seed=8)
    bs = 8
    pool = jnp.zeros((cfg.n_kv_layer, 17, bs, cfg.n_kv_head, cfg.head_dim),
                     cfg.dtype)

    def run(rows):
        """The requests ``rows`` served in one bucket of four, each at its
        own index; the other rows are padding (slot 0, block 0)."""
        B, S = 4, 16
        toks = np.zeros((B, S), np.int32)
        lens = np.ones((B,), np.int32)
        tables = np.zeros((B, 2), np.int32)
        slots = np.zeros((B,), np.int32)
        for r in rows:
            toks[r, :len(prompts[r])] = prompts[r]
            lens[r] = len(prompts[r])
            tables[r] = [1 + 2 * r, 2 + 2 * r]
            slots[r] = r + 1
        state = lfm2_moe_init_state(cfg, 4)
        logits, k, v, state = lfm2_moe_prefill(
            params, pool, pool, jnp.asarray(toks), jnp.asarray(lens),
            jnp.asarray(tables), cfg, state=state, slots=jnp.asarray(slots))
        nxt = np.where(slots > 0, np.asarray(jnp.argmax(logits, -1)), 0)
        pos = np.where(slots > 0, lens, 0).astype(np.int32)
        logits2, *_ = lfm2_moe_decode_step(
            params, k, v, jnp.asarray(nxt.astype(np.int32)),
            jnp.asarray(pos), jnp.asarray(tables), cfg, state=state,
            slots=jnp.asarray(slots))
        return np.asarray(logits), np.asarray(logits2)

    together = run([0, 1, 2])
    for r in range(3):
        alone = run([r])
        np.testing.assert_array_equal(together[0][r], alone[0][r])
        np.testing.assert_array_equal(together[1][r], alone[1][r])


# ------------------------------------------------------- the engine


def test_engine_streams_match_full_forward_and_solo(tiny):
    import jax.numpy as jnp

    from ray_tpu.models.lfm2_moe import lfm2_moe_forward

    cfg, params = tiny
    prompts = _prompts([5, 19, 33], seed=0)
    engine = _engine(cfg, params)
    solo = [engine.generate(p, max_new_tokens=6) for p in prompts]
    for p, out in zip(prompts, solo):
        seq = list(p)
        for _ in range(6):
            logits = lfm2_moe_forward(params, jnp.asarray([seq]), cfg)
            seq.append(int(jnp.argmax(logits[0, -1])))
        assert seq[len(p):] == out
    streams = [engine.submit(p, max_new_tokens=6) for p in prompts]
    _drive(engine, streams)
    assert [list(s) for s in streams] == solo
    # chunked prefill through the engine: the conv state rides the slot
    chunky = _engine(cfg, params, prefill_chunk_tokens=8)
    assert [chunky.generate(p, max_new_tokens=6) for p in prompts] == solo
    # failover re-prefill: prompt + tokens so far rebuilds the state
    resumed = engine.generate(prompts[1] + solo[1][:3], max_new_tokens=3)
    assert resumed == solo[1][3:]
    engine.shutdown()
    chunky.shutdown()


def test_counters_add_up(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompts = _prompts([5, 19, 33], seed=1)
    streams = [engine.submit(p, max_new_tokens=5) for p in prompts]
    _drive(engine, streams)
    st = engine.stats()
    per_token = cfg.top_k * cfg.n_moe_layer
    assert st["moe_pairs_prefill"] == sum(map(len, prompts)) * per_token
    # the first token of each stream comes from its prefill
    decoded = sum(len(list(s)) - 1 for s in streams)
    assert decoded == 12
    # a row that finished while a step was in flight decodes once more
    assert st["moe_pairs_decode"] >= decoded * per_token
    assert st["moe_pairs_decode"] % per_token == 0
    assert sum(st["moe_pairs_by_expert"]) == (
        st["moe_pairs_prefill"] + st["moe_pairs_decode"])
    assert len(st["moe_pairs_by_expert"]) == cfg.num_experts
    # at most every expert of every expert layer, each decode step
    assert 0 < st["moe_expert_reads_decode"] <= (
        st["decode_steps"] * cfg.n_moe_layer * cfg.num_experts)
    assert st["moe_expert_reads_decode"] <= st["moe_pairs_decode"]
    assert st["state_slots"] == 0 and st["state_slots_high_water"] == 3
    engine.shutdown()


def test_stats_reads_the_device_outside_the_lock(tiny):
    """Reading the counters waits for the step in flight: ``stats()`` does
    it with the engine's lock released, so a poll never stalls a step."""
    import threading

    cfg, params = tiny
    engine = _engine(cfg, params)
    read, free = engine.executor.read_counters, []

    def probe(state):
        def take():
            got = engine._lock.acquire(timeout=5)
            free.append(got)
            if got:
                engine._lock.release()

        other = threading.Thread(target=take)
        other.start()
        other.join()
        return read(state)

    engine.executor.read_counters = probe
    assert "moe_pairs_decode" in engine.stats()
    assert free == [True]
    engine.shutdown()


def test_counter_words_carry(jax_cpu):
    import jax.numpy as jnp

    from ray_tpu.models.parts import count_add, count_value

    acc = jnp.asarray([[2 ** 32 - 3, 0], [7, 1]], jnp.uint32)
    out = count_add(acc, jnp.asarray([5, 1]))
    assert count_value(out).tolist() == [2 ** 32 + 2, 2 ** 32 + 8]


@pytest.mark.parametrize("how", ["finish", "cancel", "deadline", "shutdown"])
def test_slots_are_freed_exactly_once(tiny, how):
    from ray_tpu.exceptions import (
        DeadlineExceededError, RequestCancelledError,
    )

    cfg, params = tiny
    engine = _engine(cfg, params)
    cache = engine.cache
    usable = cache.cfg.state_slots - 1
    assert cache.free_slots == usable == 4
    kw = {}
    if how == "deadline":
        # compiled first, so that three steps fit the deadline on a busy host
        _drive(engine, [engine.submit(p, max_new_tokens=2)
                        for p in _prompts([7, 9, 11], seed=2)])
        kw = {"deadline_s": 1.0}
    streams = [engine.submit(p, max_new_tokens=40, **kw)
               for p in _prompts([7, 9, 11], seed=2)]
    for _ in range(3):
        engine.step()
    assert cache.used_slots == 3 and cache.free_slots == 1
    held = {cache.slot(s.request_id) for s in streams}
    assert len(held) == 3 and 0 not in held
    if how == "finish":
        _drive(engine, streams)
    elif how == "cancel":
        for s in streams:
            assert engine.cancel(s.request_id)
            assert not engine.cancel(s.request_id)
        for _ in range(3):
            engine.step()  # the in-flight step's rows are reconciled
        for s in streams:
            with pytest.raises(RequestCancelledError):
                list(s)
    elif how == "deadline":
        time.sleep(1.1)
        for _ in range(3):
            engine.step()
        for s in streams:
            with pytest.raises(DeadlineExceededError):
                list(s)
    else:
        engine.shutdown()
    assert cache.used_slots == 0 and cache.free_slots == usable
    assert sorted(cache._free_slots) == [1, 2, 3, 4]
    assert cache.used_blocks == 0 and cache.reserved_blocks == 0
    if how != "shutdown":
        # the freed slots serve the next requests, from zeros
        again = [engine.submit(p, max_new_tokens=3)
                 for p in _prompts([6, 8, 10, 12], seed=3)]
        _drive(engine, again)
        assert engine.stats()["state_slots_high_water"] == 4
        engine.shutdown()
    assert engine.stats()["state_slots"] == 0


def test_slots_return_when_the_engine_dies(tiny, monkeypatch):
    from ray_tpu.exceptions import EngineDiedError

    cfg, params = tiny
    engine = _engine(cfg, params)
    streams = [engine.submit(p, max_new_tokens=20)
               for p in _prompts([7, 9], seed=4)]
    engine.step()
    assert engine.cache.used_slots == 2

    def boom(*a, **k):
        raise RuntimeError("device lost")

    monkeypatch.setattr(engine.executor, "decode_step", boom)
    try:
        engine.step()
    except RuntimeError as e:
        engine._fail_engine(e)
    for s in streams:
        with pytest.raises(EngineDiedError):
            list(s)
    engine.shutdown()
    assert engine.cache.used_slots == 0 and engine.cache.free_slots == 4
    assert "moe_pairs_decode" not in engine.stats()  # a dead device is not asked


def test_admission_waits_for_a_free_slot(tiny):
    """More requests than slots: the rest wait, none fails, and the pool's
    blocks are not what holds them back."""
    cfg, params = tiny
    engine = _engine(cfg, params, max_batch_size=2)
    streams = [engine.submit(p, max_new_tokens=4)
               for p in _prompts([5, 6, 7, 8, 9], seed=5)]
    engine.step()
    assert engine.cache.used_slots == 2 and engine.stats()["waiting"] == 3
    _drive(engine, streams)
    assert all(len(list(s)) == 4 for s in streams)
    assert engine.stats()["state_slots_high_water"] == 2
    engine.shutdown()


@pytest.mark.parametrize("option,match", [
    ({"speculative_k": 2}, "speculative_k.*rolled back"),
    ({"host_cache_bytes": 1 << 20}, "host_cache_bytes.*host tier"),
    ({"preemption": {}}, "preemption.*state slot"),
    ({"quantization": "int8"}, "quantization.*quantized path"),
    ({"tp": 2}, "tp/fsdp/mesh.*expert axis"),
    ({"fsdp": 2}, "tp/fsdp/mesh"),
    ({"mesh": {"tp": 2}}, "tp/fsdp/mesh"),
])
def test_unsupported_options_are_refused_by_name(tiny, option, match):
    cfg, params = tiny
    with pytest.raises(ValueError, match=match):
        _engine(cfg, params, **option)


def test_prefix_reuse_is_off_and_handoff_refused(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)  # prefix_caching=True, the default
    assert engine.cfg.prefix_caching
    prompt = _prompts([40], seed=6)[0]
    first = engine.generate(prompt, max_new_tokens=3)
    assert engine.cache.peek_prefix(prompt) == 0
    assert engine.generate(prompt, max_new_tokens=3) == first
    st = engine.stats()
    assert st["prefix_reuse"] is False and st["prefix_hit_tokens"] == 0
    assert st["prefix_cached_blocks"] == 0
    described = st["executor"]
    assert described["prefix_reuse"] is False
    assert described["kv_layers"] == cfg.n_kv_layer == 1
    assert described["state"]["slots"] == 4
    assert described["state"]["arrays"]["conv"] == [3, 5, 2, cfg.d_model]
    assert engine.cache.k.shape[0] == 1  # the pool spans attention layers
    with pytest.raises(ValueError, match="handoff"):
        engine.export_prefix(prompt)
    with pytest.raises(ValueError, match="handoff"):
        engine.adopt_prefix(prompt, [])
    engine.shutdown()


def test_unknown_model_name_raises(jax_cpu):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    from ray_tpu.serve.llm.decode import FAMILIES, get_family

    with pytest.raises(ValueError, match="unknown model family 'mamba'"):
        LLMEngine(EngineConfig(model="mamba"), auto_step=False)
    assert sorted(FAMILIES) == ["evabyte", "falcon_h1", "gpt", "laguna",
                                "lfm2_moe",
                                "ling_hybrid", "llama", "longcat_flash",
                                "minicpm_sala", "pangu_ultra_moe",
                                "sdar_moe", "smallthinker"]
    for name in ("gpt", "llama"):
        assert get_family(name).init_state is None
        assert get_family(name).verify_step is not None
    assert get_family("lfm2_moe").verify_step is None
    assert get_family("laguna").verify_step is None
    assert get_family("evabyte").verify_step is None
    assert get_family("pangu_ultra_moe").verify_step is None
    assert get_family("minicpm_sala").verify_step is None
    assert get_family("ling_hybrid").verify_step is None
    # the families whose steps donate ``state`` (a matrix a head a slot)
    assert [n for n in FAMILIES if get_family(n).donated_state_counters] \
        == ["minicpm_sala", "ling_hybrid"]


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_other_families_take_no_state_argument(jax_cpu, family):
    """llama and gpt step programs keep their text: what the executor's
    call path lowers for them is, to the letter, what the family's own
    function lowers to, and nothing named ``state`` or ``slots`` is among
    its parameters."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    from ray_tpu.serve.llm.decode import get_family

    engine = LLMEngine(EngineConfig(model=family, num_blocks=33),
                       auto_step=False)
    assert engine.cache.state is None and engine.cache.cfg.state_slots == 0
    assert engine.executor.describe()["state"] is None
    assert engine.executor.describe()["prefix_reuse"] is True
    assert engine.stats()["state_slots_high_water"] == 0
    cfg = engine.model_cfg
    B, nb = 2, 4
    args = (engine.params, engine.cache.k, engine.cache.v,
            jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
            jnp.zeros((B, nb), jnp.int32))
    own = jax.jit(functools.partial(
        get_family(family).decode_step, cfg=cfg),
        donate_argnums=(1, 2)).lower(*args).as_text()  # as decode.py does
    through = engine.fns._decode.lower(*args, sample=None).as_text()
    strip = lambda t: t.split("\n", 1)[1]  # the module's name line
    assert strip(through) == strip(own)
    assert "state" not in through and "slots" not in through
    # and the call the executor makes carries None for both
    calls = []
    real = engine.fns._decode
    engine.fns._decode = lambda *a, **k: (calls.append(k), real(*a, **k))[1]
    engine.generate([1, 2, 3], max_new_tokens=3)
    assert calls and all(
        set(k) == {"sample", "state", "slots"} and k["state"] is None
        and k["slots"] is None for k in calls)
    engine.shutdown()


def test_widened_pipeline_matches_solo_runs(tiny):
    """ISSUE 33's schedule (conftest ``run_widened_schedule``): a joining
    row's state slot is written by a prefill still in flight when the
    decode step that reads it is launched; the device's order carries it,
    and the streams are the bytes of solo runs."""
    from conftest import run_widened_schedule

    cfg, params = tiny
    st = run_widened_schedule(lambda **kw: _engine(cfg, params, **kw),
                              cfg.vocab_size)
    assert st["state_slots"] == 0 and st["state_slots_high_water"] >= 3
