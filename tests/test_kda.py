"""ops/kda.py on the CPU in float32: the one-token step, the in-place
kernel and the chunked form against the token-by-token recurrence (THE
definition), the short convolution's plain form across a chunk boundary,
and group-limited routing against a written-out loop.

Tolerances, with their reason. All three forms compute the same
mathematics in float32 and differ in the order of sums: the chunked form
solves a unit lower-triangular system a chunk and refers every decay to
its piece's first row where the recurrence multiplies token by token. On
outputs and states of size ~1 the forms agree to 3e-6 (seen); the limit is
2e-5. A state kept in bfloat16 between tokens moves an output by ~1e-2
(``test_a_state_in_bfloat16_is_noticed``): three orders above the limit.
"""
from __future__ import annotations

import numpy as np
import pytest


def _inputs(B, S, H, K, seed=0, bound=-5.0):
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    q = f(B, S, H, K) * K ** -0.5
    k = f(B, S, H, K)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = f(B, S, H, K)
    log_a = bound * jax.nn.sigmoid(2.0 * f(B, S, H, K) - 1.0)
    beta = jax.nn.sigmoid(f(B, S, H))
    return q, k, v, log_a, beta, f(B, H, K, K)


def _masked(valid, log_a, beta):
    import jax.numpy as jnp

    return (jnp.where(valid[..., None, None], log_a, 0.0),
            jnp.where(valid[..., None], beta, 0.0))


@pytest.mark.parametrize("chunk,piece", [(8, 4), (16, 4), (64, 16), (32, 16)])
@pytest.mark.parametrize("lengths", [(37, 37), (37, 20), (5, 1)])
def test_chunked_form_is_the_recurrence(jax_cpu, lengths, chunk, piece):
    """Chunks that end mid-piece (37 = 2 x 16 + 5; 20 = 16 + 4; chunks of 8
    in pieces of 4), padding inside a row (the second row is shorter): the
    outputs at the real tokens and the state after each row's last."""
    import jax.numpy as jnp

    from ray_tpu.ops import kda

    S = max(lengths)
    q, k, v, log_a, beta, S0 = _inputs(2, S, 2, 16, seed=sum(lengths))
    valid = jnp.arange(S)[None, :] < jnp.asarray(lengths)[:, None]
    want_o, want_S = kda.kda_recurrence(
        q, k, v, *_masked(valid, log_a, beta), S0)
    got_o, got_S = kda.kda_chunk(q, k, v, log_a, beta, S0, valid,
                                 chunk=chunk, piece=piece)
    np.testing.assert_allclose(
        np.where(np.asarray(valid)[..., None, None], got_o, 0),
        np.where(np.asarray(valid)[..., None, None], want_o, 0), atol=2e-5)
    np.testing.assert_allclose(got_S, want_S, atol=2e-5)


def test_a_state_is_carried_over_three_chunks(jax_cpu):
    """Three calls of 24, 24 and 13 tokens, each from the state the last
    one left, are one call over the 61 and the recurrence over them."""
    import jax.numpy as jnp

    from ray_tpu.ops import kda

    q, k, v, log_a, beta, S0 = _inputs(1, 61, 3, 16, seed=7)
    valid = jnp.ones((1, 61), bool)
    want_o, want_S = kda.kda_recurrence(q, k, v, log_a, beta, S0)
    state, outs = S0, []
    for lo, hi in ((0, 24), (24, 48), (48, 61)):
        cut = lambda a: a[:, lo:hi]  # noqa: E731
        o, state = kda.kda_chunk(
            cut(q), cut(k), cut(v), cut(log_a), cut(beta), state,
            valid[:, lo:hi], chunk=16, piece=4)
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want_o, atol=2e-5)
    np.testing.assert_allclose(state, want_S, atol=2e-5)


def test_the_lower_bound_in_every_channel_overflows_nothing(jax_cpu):
    """``log a`` at -5 in EVERY channel for 64 tokens, at the served chunk
    and piece: within a chunk the running sum reaches -320 (``e^-320`` is 0
    in float32, ``e^320`` inf): no inf, no nan, and still the recurrence."""
    import jax.numpy as jnp

    from ray_tpu.ops import kda

    q, k, v, _, beta, S0 = _inputs(1, 64, 2, 16, seed=3)
    log_a = jnp.full((1, 64, 2, 16), -5.0)
    valid = jnp.ones((1, 64), bool)
    o, S = kda.kda_chunk(q, k, v, log_a, beta, S0, valid)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(S)).all()
    want_o, want_S = kda.kda_recurrence(q, k, v, log_a, beta, S0)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(S, want_S, atol=2e-5)
    assert kda.PIECE * 5 < 88 and kda.chunk_shape(2048) == (64, 32)
    assert kda.chunk_shape(20) == (32, 1) and kda.chunk_shape(5) == (16, 1)


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_the_step_is_the_recurrence(jax_cpu, form):
    """Token by token through ``kda_step`` (XLA's form) and through the
    kernel over the slots' array: the recurrence's outputs and last state;
    the kernel leaves every other slot and layer as it was."""
    import jax.numpy as jnp

    from ray_tpu.ops import kda

    q, k, v, log_a, beta, S0 = _inputs(2, 9, 4, 16, seed=11)
    want_o, want_S = kda.kda_recurrence(q, k, v, log_a, beta, S0)
    slots = jnp.asarray([2, 3])
    other = jnp.asarray(np.random.default_rng(1).normal(size=(2, 5, 4, 16, 16)),
                        jnp.float32)
    states = other.at[1, slots].set(S0)
    state, outs = S0, []
    for t in range(9):
        args = (q[:, t], k[:, t], v[:, t], log_a[:, t], beta[:, t])
        if form == "xla":
            o, state = kda.kda_step(*args, state)
        else:
            o, states = kda.kda_step_pallas(*args, states, 1, slots)
            state = states[1, slots]
        outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 1), want_o, atol=2e-5)
    np.testing.assert_allclose(state, want_S, atol=2e-5)
    if form == "pallas":
        np.testing.assert_array_equal(states[0], other[0])
        np.testing.assert_array_equal(states[1, jnp.asarray([0, 1, 4])],
                                      other[1, jnp.asarray([0, 1, 4])])


def test_a_state_in_bfloat16_is_noticed(jax_cpu):
    """The limit 2e-5 is tight enough: the same recurrence with the state
    rounded to bfloat16 after every token is 1e-3 and more away."""
    import jax.numpy as jnp

    from ray_tpu.ops import kda

    q, k, v, log_a, beta, S0 = _inputs(1, 48, 2, 16, seed=5)
    want_o, _ = kda.kda_recurrence(q, k, v, log_a, beta, S0)
    state, outs = S0, []
    for t in range(48):
        o, state = kda.kda_step(q[:, t], k[:, t], v[:, t], log_a[:, t],
                                beta[:, t], state)
        state = state.astype(jnp.bfloat16).astype(jnp.float32)
        outs.append(o)
    assert float(jnp.max(jnp.abs(jnp.stack(outs, 1) - want_o))) > 1e-3


def test_counts_by_hand():
    from ray_tpu.ops import kda

    # a row of 32 heads of 128 x 128: the state each way 2 x 2.10 MB, five
    # float32 rows of 128 and a bfloat16 output row a head
    assert kda.step_bytes(1, 32, 128, 128) == 32 * (
        2 * 65536 + 4 * 512 + 512 + 256)
    # a token a head at chunks of 64: 2 x 64 x 128 (two tables) + 64 x 64
    # (the solve) + 64 x 256 (its inverse against [K | V]) + 3 x 128 x 128
    # (the state's three) + 64 x 128 (the table against u), x 2
    assert kda.chunk_flops(1, 1, 128, 128) == 2 * (
        16384 + 4096 + 16384 + 49152 + 8192)


# ------------------------------------------------------ the convolution


def test_plain_convolution_across_a_chunk_boundary(jax_cpu):
    """Width 4 with SiLU, no gate: a row of 19 tokens whole, the same row
    as chunks of 8 + 8 + 3 with the history handed on (right-padded: the
    padding does not enter the history), and token by token; and the
    written-out definition."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.short_conv import short_conv_decode, short_conv_prefill

    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(1, 19, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    ext = np.concatenate([np.zeros((3, 6), np.float32), np.asarray(x[0])])
    want = np.asarray(jax.nn.silu(jnp.asarray(sum(
        ext[j:j + 19] * np.asarray(w)[j] for j in range(4)))))
    act = jax.nn.silu
    whole, last = short_conv_prefill(x, None, w, None, jnp.asarray([19]),
                                     act=act, scope="kda_conv")
    np.testing.assert_allclose(whole[0], want, atol=1e-6)
    np.testing.assert_array_equal(last[0], x[0, 16:19])
    state, outs = None, []
    for lo, n in ((0, 8), (8, 8), (16, 3)):
        part = jnp.zeros((1, 8, 6)).at[:, :n].set(x[:, lo:lo + n])
        y, state = short_conv_prefill(part, None, w, state, jnp.asarray([n]),
                                      act=act)
        outs.append(y[:, :n])
    np.testing.assert_allclose(jnp.concatenate(outs, 1)[0], want, atol=1e-6)
    np.testing.assert_array_equal(state[0], x[0, 16:19])
    state, outs = jnp.zeros((1, 3, 6)), []
    for t in range(19):
        y, state = short_conv_decode(x[:, t], None, w, state, act=act)
        outs.append(y)
    np.testing.assert_allclose(jnp.stack(outs, 1)[0], want, atol=1e-6)


# ---------------------------------------------------------- the router


def _route_by_hand(scores, bias, top_k, n_group, topk_group, scale):
    """One token's (weights by expert) written out as a loop."""
    E = len(scores)
    size = E // n_group
    biased = scores + bias
    group_score = [sum(sorted(biased[g * size:(g + 1) * size])[-2:])
                   for g in range(n_group)]
    kept = sorted(range(n_group), key=lambda g: -group_score[g])[:topk_group]
    among = [e for g in kept for e in range(g * size, (g + 1) * size)]
    chosen = sorted(among, key=lambda e: -biased[e])[:top_k]
    total = sum(scores[e] for e in chosen) + 1e-6
    out = np.zeros(E)
    for e in chosen:
        out[e] = scale * scores[e] / total
    return out, sorted(kept)


def test_group_limited_routing_against_a_written_out_loop(jax_cpu):
    """16 experts in 4 groups, 2 groups kept, 3 a token; the bias CHANGES
    the choice (some token's set differs with it) and not the weight (a
    weight is the unbiased score over the chosen scores' sum)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.moe import moe_route_grouped

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(24, 8)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    bias = jnp.asarray(0.3 * rng.normal(size=(16,)), jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(x @ router), np.float64)
    differs = 0
    for b in (bias, jnp.zeros_like(bias)):
        weights, experts, stays = moe_route_grouped(
            x, router, b, 3, (4, 2), norm_topk=True, scale=2.5)
        assert stays.shape == (24, 4) and (np.asarray(stays).sum(1) == 2).all()
        for t in range(24):
            want, kept = _route_by_hand(scores[t], np.asarray(b, np.float64),
                                        3, 4, 2, 2.5)
            got = np.zeros(16)
            got[np.asarray(experts[t])] = np.asarray(weights[t])
            np.testing.assert_allclose(got, want, atol=1e-6)
            assert np.flatnonzero(np.asarray(stays[t])).tolist() == kept
        if b is bias:
            with_bias = np.asarray(experts)
        else:
            differs = int((np.sort(with_bias, 1)
                           != np.sort(np.asarray(experts), 1)).any(1).sum())
    assert differs > 0
    with pytest.raises(ValueError, match="groups"):
        moe_route_grouped(x, router, bias, 3, (5, 2))
