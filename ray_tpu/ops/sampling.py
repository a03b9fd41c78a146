"""Fused on-device token sampling for the serve/llm decode pipeline.

``sample_tokens`` turns a batch of next-token logits into sampled token
ids INSIDE the jitted model step (models/gpt.py, models/llama.py call it
when the engine passes a ``sample`` pytree), so the per-token
device->host transfer shrinks from O(batch x vocab) float32 logits to
O(batch) int32 ids and the host never touches a probability.

Determinism contract (the engine's failover story depends on it): the
per-token randomness is *stateless per (seed, position)* —

    key = fold_in(PRNGKey(request_seed), absolute_position_of_new_token)

so the token at position p is a pure function of (logits, seed, p). A
mid-stream resume that re-prefills ``prompt + delivered`` reproduces the
remaining tokens byte-identically by construction; no RNG state needs
fast-forwarding (this replaces the old host-side "burn one numpy uniform
per token" contract).

Kernel shape (TPU-friendly, no data-dependent shapes): the non-greedy
path sorts each row once with ``jax.lax.top_k(scaled, V)`` — a full
descending sort — then applies top-k as a rank mask, top-p as an
exclusive-cumsum mask over the sorted probabilities, and draws via
inverse CDF on the renormalized sorted distribution. Greedy rows
(temperature <= 0 or top_k == 1) are argmax; when the WHOLE batch is
greedy a ``lax.cond`` skips the sort entirely (the common serving
config), keeping the fused step as cheap as the old logits-returning one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def apply_allow_mask(logits: jax.Array, mask: jax.Array | None) -> jax.Array:
    """Apply a packed uint32 allow-bitmask to ``logits`` [..., V].

    ``mask`` is ``[..., ceil(V/32)]`` uint32, little-endian packed (bit
    j of word w allows token ``w*32 + j``) — the grammar-constrained
    decoding mask staged by serve/llm/structured.py. Disallowed tokens
    go to ``-inf`` BEFORE the greedy argmax and the top-k sort, so the
    constrained token is still the same pure f(logits, seed, position)
    the failover-resume contract keys on; an all-ones mask is a bitwise
    identity, which is what keeps unconstrained rows byte-identical to
    a maskless build. Rows whose mask allows nothing are left unmasked
    (never NaN): the host-side FSM has already gone dead for such a row
    and terminates the stream, so its sampled token is never emitted.
    """
    if mask is None:
        return logits
    bits = (
        mask[..., None] >> jnp.arange(32, dtype=jnp.uint32)
    ) & jnp.uint32(1)
    allow = bits.reshape(mask.shape[:-1] + (mask.shape[-1] * 32,))
    allow = allow[..., : logits.shape[-1]] != 0
    any_allowed = jnp.any(allow, axis=-1, keepdims=True)
    allow = allow | ~any_allowed
    return jnp.where(allow, logits, -jnp.inf)


def _sampled_row(
    logits: jax.Array,
    seed: jax.Array,
    position: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
) -> jax.Array:
    """One row of the full temperature/top-k/top-p path. All inputs are
    scalars except ``logits`` [V]; returns a scalar int32 token id."""
    V = logits.shape[-1]
    key = jax.random.fold_in(jax.random.PRNGKey(seed), position)
    u = jax.random.uniform(key, dtype=jnp.float32)
    scaled = logits.astype(jnp.float32) / jnp.maximum(
        temperature, jnp.float32(1e-6)
    )
    # full descending sort: rank r holds the (r+1)-th largest logit
    srt, idx = jax.lax.top_k(scaled, V)
    ranks = jnp.arange(V, dtype=jnp.int32)
    k_eff = jnp.where(top_k > 0, top_k, V)
    srt = jnp.where(ranks < k_eff, srt, -jnp.inf)
    probs = jax.nn.softmax(srt)
    # top-p over the sorted distribution: keep ranks whose EXCLUSIVE
    # cumulative mass is below p (rank 0 always survives, so a tiny p
    # degrades to greedy rather than an empty support)
    p_eff = jnp.where((top_p > 0.0) & (top_p < 1.0), top_p, jnp.float32(1.0))
    keep = (jnp.cumsum(probs) - probs) < p_eff
    srt = jnp.where(keep, srt, -jnp.inf)
    probs = jax.nn.softmax(srt)
    pick = jnp.minimum(
        jnp.searchsorted(jnp.cumsum(probs), u, side="right"), V - 1
    )
    return idx[pick].astype(jnp.int32)


def sample_tokens(
    logits: jax.Array,
    positions: jax.Array,
    sample: dict,
) -> jax.Array:
    """Sample one token per row of ``logits`` [B, V] f32.

    ``positions`` [B] int32 is the ABSOLUTE sequence position of the token
    being sampled (prompt tokens occupy 0..len(prompt)-1, so the first
    generated token sits at len(prompt)). ``sample`` is a pytree of [B]
    arrays: ``seeds`` (uint32), ``temperature`` (f32, <= 0 -> greedy),
    ``top_k`` (int32, 0 -> full distribution), ``top_p`` (f32, >= 1 or
    <= 0 -> disabled), plus an optional ``mask`` ([B, ceil(V/32)]
    uint32 packed allow-bitmask; all-ones = unconstrained — see
    ``apply_allow_mask``). Returns [B] int32 token ids.
    """
    logits = apply_allow_mask(logits, sample.get("mask"))
    seeds = sample["seeds"]
    temperature = sample["temperature"]
    top_k = sample["top_k"]
    top_p = sample["top_p"]
    greedy_rows = (temperature <= 0.0) | (top_k == 1)
    # jnp.argmax matches np.argmax tie-breaking (first occurrence), which
    # is what the greedy-parity test pins down
    greedy_toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def all_greedy(_):
        return greedy_toks

    def mixed(_):
        sampled = jax.vmap(_sampled_row)(
            logits, seeds, positions, temperature, top_k, top_p
        )
        return jnp.where(greedy_rows, greedy_toks, sampled)

    return jax.lax.cond(jnp.all(greedy_rows), all_greedy, mixed, None)


def verify_tokens(
    logits: jax.Array,
    starts: jax.Array,
    draft_tokens: jax.Array,
    draft_len: jax.Array,
    sample: dict,
) -> jax.Array:
    """Speculative-decoding rejection epilogue over a [B, W] verify window.

    ``logits`` [B, W, V] f32 are the target model's outputs at window
    columns 0..W-1, where column 0 held the last COMMITTED token (absolute
    position ``starts`` [B]) and columns 1..W-1 held drafted candidates
    ``draft_tokens`` [B, W] (column 0 is the committed token itself;
    columns past ``draft_len`` [B] are padding). The logits at column s
    predict the token at absolute position starts + s + 1, so the target
    token for that position is the SAME pure function
    f(logits, seed, position) as non-speculative decode — ``sample_tokens``
    with keyed fold_in(seed, position) randomness.

    Acceptance is exact-match, not a probability-ratio test: draft column
    s is accepted iff it equals the target token the keyed sampler draws
    at that position given the (accepted, hence true) prefix. By induction
    the committed stream is byte-identical to non-speculative decoding —
    losslessness holds for greedy AND temperature/top-k/top-p, because the
    keyed sampler is deterministic per (logits, seed, position).

    Returns packed [B, W + 1] int32: column 0 = committed count c in
    1..draft_len+1 (accepted prefix plus one corrected/bonus token),
    columns 1..W = the target tokens for positions starts+1..starts+W —
    the committed tokens are packed[b, 1 : 1 + c]. One array => one
    device->host sync per verify step.
    """
    B, W, _ = logits.shape
    # target token for every window position, flattened through the [B, V]
    # sampler with per-row sample leaves tiled across the window
    positions = (
        starts[:, None] + 1 + jnp.arange(W, dtype=jnp.int32)[None, :]
    )  # [B, W]
    # per-row [B] leaves tile across the window; per-column leaves
    # ([B, W, ...] — the structured-decoding mask stages one allow-set
    # per window position) flatten row-major to match logits/positions
    tiled = {
        k: (
            v.reshape((B * W,) + v.shape[2:])
            if v.ndim >= 2
            else jnp.repeat(v, W, axis=0)
        )
        for k, v in sample.items()
    }
    tgt = sample_tokens(
        logits.reshape(B * W, -1), positions.reshape(B * W), tiled
    ).reshape(B, W)
    # leading run of draft columns matching the target drawn one column
    # earlier (logits at column s-1 predict position starts+s, which is
    # where draft column s sits)
    match = draft_tokens[:, 1:] == tgt[:, :-1]  # [B, W-1]
    within = (
        jnp.arange(1, W, dtype=jnp.int32)[None, :] <= draft_len[:, None]
    )
    accepted = jnp.sum(
        jnp.cumprod((match & within).astype(jnp.int32), axis=1), axis=1
    )  # [B] in 0..draft_len
    committed = accepted + 1  # + the corrected/bonus target token
    return jnp.concatenate(
        [committed[:, None].astype(jnp.int32), tgt], axis=1
    )


# ----------------------------------------------------------------------------
# Choose-and-unmask: the epilogue of a family that generates by diffusion
# over blocks (models/sdar_moe.py). A pass yields logits at ALL of a block's
# positions; which of the still-masked ones take their token is the row's
# schedule.
# ----------------------------------------------------------------------------

# how a pass chooses the masked positions it fills; a row's is data (its
# index here), so rows of one batch may differ
REMASKING = ("sequential", "low_confidence_static", "low_confidence_dynamic")


def fill_counts(block_length: int, steps: int | None) -> tuple[int, ...]:
    """How many positions each of a block's ``steps`` passes fills:
    ``block_length // steps``, a remainder going to the first passes (the
    routine's transfer schedule; None: as many steps as the block is
    long)."""
    steps = block_length if steps is None else steps
    base, rem = divmod(block_length, steps)
    return tuple(base + (s < rem) for s in range(steps))


def pass_fills(masked: int, counts: tuple[int, ...]) -> list[int]:
    """The fills of the passes a block with ``masked`` masked positions
    takes under ``counts``, until none is left (a first block whose head is
    the prompt's tail takes fewer); the commit pass follows them."""
    fills = []
    for n in counts:
        if masked <= 0:
            break
        fills.append(min(n, masked))
        masked -= fills[-1]
    return fills


def unmask_tokens(
    logits: jax.Array,
    ids: jax.Array,
    masked: jax.Array,
    positions: jax.Array,
    sample: dict,
    mask_token_id: int,
    threshold: float,
) -> jax.Array:
    """One denoising pass's choice, for every row of a batch at once.

    ``logits`` [B, W, V] f32 at the W positions of each row's block
    (absolute ``positions`` [B, W]); ``ids`` [B, W] what the block holds;
    ``masked`` [B] int32, bit j set where position j is still masked (a
    bit a position, the caller's knowledge: an id equal to the mask's is
    an ordinary token). ``sample``: the leaves of ``sample_tokens`` a ROW,
    and the row's schedule for this pass: ``fill`` [B] int32, how many
    masked positions to fill; ``remasking`` [B] int32, an index of
    ``REMASKING``. ``mask_token_id`` and ``threshold`` are the model
    configuration's, constants of the program.

    A position's token is ``sample_tokens`` of the logits AT it (greedy:
    their argmax; keyed by its absolute position otherwise); its
    confidence the largest softmax probability there. Chosen are, among
    the masked: ``sequential`` the first ``fill``, left to right;
    ``low_confidence_static`` the ``fill`` of highest confidence (ties to
    the left); ``low_confidence_dynamic`` those, and every one whose
    confidence passes ``threshold``. Returns ``[B, W + 1]`` int32: the
    block's ids with the chosen filled, then the bits still masked.

    The block is the one that CHOOSES in the pass (models/cached.py
    ``_block_step``): a row that folds a finished block into its next
    block's first pass hands in the fresh block (all ``mask_token_id``,
    every bit set) with the logits at ITS positions, and is filled like
    any other. A row that comes with NO masked position ran a commit pass
    of its own (a request's last block): it chooses nothing, and what it
    gets back is a block of all ``mask_token_id`` with every bit set,
    which nothing reads."""
    B, W, V = logits.shape
    offs = jnp.arange(W, dtype=jnp.int32)
    is_masked = ((masked[:, None] >> offs[None, :]) & 1) != 0
    rows = {k: sample[k] for k in ("seeds", "temperature", "top_k", "top_p")}
    greedy_rows = (rows["temperature"] <= 0.0) | (rows["top_k"] == 1)

    def drawn(_):
        # one position of every row at a time: the sort a draw takes is
        # then a decode step's [B, V], not W times that
        return jax.lax.map(
            lambda j: sample_tokens(
                jax.lax.dynamic_index_in_dim(logits, j, 1, keepdims=False),
                jax.lax.dynamic_index_in_dim(positions, j, 1, keepdims=False),
                rows),
            offs).T

    toks = jax.lax.cond(
        jnp.all(greedy_rows),
        lambda _: jnp.argmax(logits, axis=-1).astype(jnp.int32), drawn, None)
    confidence = jnp.exp(
        jnp.max(logits, axis=-1) - jax.nn.logsumexp(logits, axis=-1))
    mode = sample["remasking"][:, None]
    order = jnp.where(mode == 0, -offs[None, :].astype(jnp.float32),
                      confidence)
    order = jnp.where(is_masked, order, -jnp.inf)
    # a position's rank among its row's: how many go before it
    before = (order[:, None, :] > order[:, :, None]) | (
        (order[:, None, :] == order[:, :, None])
        & (offs[None, None, :] < offs[None, :, None]))
    rank = jnp.sum(before, axis=-1)
    chosen = is_masked & (rank < sample["fill"][:, None])
    chosen |= is_masked & (mode == 2) & (confidence > threshold)
    still = jnp.sum(
        jnp.where(is_masked & ~chosen, 1 << offs[None, :], 0), axis=-1)
    commit = (masked == 0)[:, None]
    out = jnp.where(commit, mask_token_id, jnp.where(chosen, toks, ids))
    bits = jnp.where(commit[:, 0], (1 << W) - 1, still)
    return jnp.concatenate(
        [out.astype(jnp.int32), bits[:, None].astype(jnp.int32)], axis=1)
