"""The minicpm_sala family on the CPU at the tiny preset (widths 64,
``dense_len`` 64, blocks of 8, kernel 4 / stride 2, ``topk`` 4, window 16),
seeded weights: the program against the plain reference
(benchmark/reference/minicpm_sala.py), the serving path (paged cache, state
slots, the compressed keys' plane by block id) against the full forward,
whole, chunked, and across ``dense_len``; the block sets against the
reference's; the slots' lifetime; what the engine refuses for the family;
its counters.

Tolerances, with their reason. Program and reference in float32 compute the
same mathematics and differ in the order of sums (the program's lightning
layers carry a state chunk by chunk where the reference takes the O(n^2)
masked-decay product; the program keeps SUMS of segments where the
reference takes means of 32 keys): 2e-4 on logits of size ~3 (seen 3e-5),
PROVIDED both chose the same blocks, which in float32 they do unless two
blocks' pooled scores tie to 1e-7 (counted below: none at these seeds). In
bfloat16 a near-tie falls the other way now and then: the share of (query,
head) block sets that differ is counted and bounded, not asserted zero.
"""
from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BS = 8  # the tiny preset's selection block = the cache's page


@pytest.fixture(scope="module")
def ref():
    from benchmark import common

    return common.load_named("reference", "minicpm_sala")


@pytest.fixture(scope="module")
def tiny(jax_cpu):
    """(float32 config, its seeded params)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.minicpm_sala import (
        MiniCPMSALAConfig, minicpm_sala_init,
    )

    cfg = dataclasses.replace(MiniCPMSALAConfig.tiny(), dtype=jnp.float32)
    return cfg, minicpm_sala_init(jax.random.PRNGKey(1), cfg)


def _engine(cfg, params, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    settings = dict(model="minicpm_sala", model_config=cfg, num_blocks=129,
                    block_size=BS, max_batch_size=4)
    settings.update(kw)
    return LLMEngine(EngineConfig(**settings), params=params,
                     auto_step=False)


def _prompts(lens, seed=0, vocab=512):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).tolist() for n in lens]


def _drive(engine, streams, limit=4000):
    for _ in range(limit):
        if all(s.done for s in streams):
            return
        engine.step()
    raise AssertionError("streams did not finish")


def _serve_logits(cfg, params, prompt, new, chunk=None, slot=1, state=None,
                  nb=20):
    """Prefill (whole, or by chunks of ``chunk``) then ``new`` greedy decode
    steps through the paged cache, the state slot and the compressed keys'
    plane, on logits (``sample=None``): the logits that chose each
    generated token [new, V], the sequence, the last state."""
    import jax.numpy as jnp

    from ray_tpu.models.minicpm_sala import (
        minicpm_sala_decode_step, minicpm_sala_init_state,
        minicpm_sala_prefill,
    )

    blocks = 1 + 2 * nb
    pool = jnp.zeros((cfg.n_kv_layer, blocks, BS, cfg.n_kv_head,
                      cfg.head_dim), cfg.dtype)
    k, v = pool, pool
    if state is None:
        state = minicpm_sala_init_state(cfg, 3, blocks)
    first = 1 + nb * (slot - 1)
    table = jnp.asarray([list(range(first, first + nb))], jnp.int32)
    slots = jnp.asarray([slot], jnp.int32)
    n = len(prompt)
    out = []
    if chunk is None:
        logits, k, v, state = minicpm_sala_prefill(
            params, k, v, jnp.asarray([prompt], jnp.int32),
            jnp.asarray([n], jnp.int32), table, cfg, state=state,
            slots=slots)
    else:
        for s in range(0, n, chunk):
            part = prompt[s:s + chunk]
            toks = np.zeros((1, chunk), np.int32)
            toks[0, :len(part)] = part
            logits, k, v, state = minicpm_sala_prefill(
                params, k, v, jnp.asarray(toks),
                jnp.asarray([len(part)], jnp.int32), table, cfg,
                start=jnp.asarray([s], jnp.int32), state=state, slots=slots)
    seq = list(prompt)
    for _ in range(new):
        out.append(np.asarray(logits[0]))
        seq.append(int(np.argmax(out[-1])))
        logits, k, v, state = minicpm_sala_decode_step(
            params, k, v, jnp.asarray([seq[-1]], jnp.int32),
            jnp.asarray([len(seq) - 1], jnp.int32), table, cfg, state=state,
            slots=slots)
    return np.stack(out), seq, state


# ------------------------------------------------- program == reference


def test_config_is_hashable_and_counts_its_layers(jax_cpu):
    from ray_tpu.models.minicpm_sala import MiniCPMSALAConfig

    cfg = MiniCPMSALAConfig(
        mixer_types=["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"],
        layer_index=list(range(9, 17)))
    hash(cfg)
    assert (cfg.n_layer, cfg.n_kv_layer, cfg.n_lightning_layer) == (8, 2, 6)
    assert cfg.sparse.segments == 4 and cfg.sparse.window_blocks == 32
    assert cfg.sparse.list_width == 128
    assert abs(cfg.residual_scale - 1.4 / 32 ** 0.5) < 1e-12
    with pytest.raises(ValueError, match="mixer_types"):
        MiniCPMSALAConfig(mixer_types=("conv",))
    with pytest.raises(ValueError, match="kernel_size"):
        MiniCPMSALAConfig(kernel_size=48)


@pytest.mark.parametrize("n,chunk", [
    (21, None),      # below dense_len, whole
    (100, None),     # past dense_len (64), whole: the masked prefill form
    (100, 16),       # chunks; one starts ON 64
    (100, 24),       # a chunk (48..71) CROSSES dense_len
    (61, 8),         # decode crosses dense_len: 61 + 6 tokens
])
def test_prefill_then_decode_matches_reference_on_logits(tiny, ref, n, chunk):
    import jax.numpy as jnp

    cfg, params = tiny
    prompt = _prompts([n], seed=5 + n)[0]
    got, seq, _ = _serve_logits(cfg, params, prompt, 6, chunk=chunk)
    want = np.asarray(ref.logits(params, jnp.asarray([seq[:-1]]), cfg))[0]
    np.testing.assert_allclose(got, want[n - 1:], atol=2e-4)


def _no_layer_factor(ref, monkeypatch):
    real = ref.decay
    monkeypatch.setattr(ref, "decay", lambda layer, cfg: real(0, cfg))


def _norm_a_head(ref, monkeypatch):
    def a_head(o, scale, cfg):
        H, hd = cfg.lightning_n_head, cfg.lightning_head_dim
        shaped = o.reshape(o.shape[0], H, hd)
        return (ref._rms_norm(shaped, 1.0, cfg.norm_eps)
                * scale.reshape(H, hd)).reshape(o.shape)

    monkeypatch.setattr(ref, "output_norm", a_head)


def _keys_one_stride_late(ref, monkeypatch):
    real = ref.compressed_keys

    def late(k, cfg):
        c = real(k, cfg)
        return c.at[:-1].set(c[1:])  # c_j taken over tokens 2 later

    monkeypatch.setattr(ref, "compressed_keys", late)


@pytest.mark.parametrize("wrong", [
    "dense_where_sparse_is_due", "no_forced_window", "no_forced_first_block",
    "topk_one_short", "keys_one_stride_late", "no_layer_factor_in_the_decay",
    "output_norm_a_head", "no_qk_norm_weight", "rotary_off",
])
def test_the_reference_notices_each_mechanism(tiny, ref, monkeypatch, wrong):
    """What the chip's limit cannot always part from bfloat16's own noise,
    float32 can: each wrong reading moves the reference's logits over a
    100-token sequence by far more than the 2e-4 the program is held to
    (the least seen: 0.17)."""
    import jax.numpy as jnp

    cfg, params = tiny
    tokens = jnp.asarray([_prompts([100], seed=21)[0]])
    right = np.asarray(ref.logits(params, tokens, cfg))
    other, tree = cfg, params
    if wrong == "dense_where_sparse_is_due":
        other = dataclasses.replace(cfg, dense_len=128)
    elif wrong == "no_forced_window":
        other = dataclasses.replace(cfg, window_size=8)   # the own block only
    elif wrong == "no_forced_first_block":
        other = dataclasses.replace(cfg, init_blocks=0)
    elif wrong == "topk_one_short":
        other = dataclasses.replace(cfg, topk=3)
    elif wrong == "keys_one_stride_late":
        _keys_one_stride_late(ref, monkeypatch)
    elif wrong == "no_layer_factor_in_the_decay":
        _no_layer_factor(ref, monkeypatch)
    elif wrong == "output_norm_a_head":
        _norm_a_head(ref, monkeypatch)
    elif wrong == "no_qk_norm_weight":
        tree = {**params, "layers": [
            {**lp, "q_norm": jnp.ones_like(lp["q_norm"])}
            for lp in params["layers"]]}
    elif wrong == "rotary_off":
        monkeypatch.setattr(ref, "rotary", lambda x, cfg: x)
    moved = np.abs(np.asarray(ref.logits(tree, tokens, other)) - right).max()
    print(f"{wrong}: {moved:.4f}")
    assert moved > 10 * 2e-4, moved


def test_a_reused_slot_starts_from_zeros(tiny):
    """Whatever a slot and the blocks' compressed keys held, a sequence's
    first chunk ignores it: the whole prefill and a chunked one."""
    import jax

    from ray_tpu.models.minicpm_sala import minicpm_sala_init_state

    cfg, params = tiny
    prompt = _prompts([75], seed=7)[0]
    clean, _, _ = _serve_logits(cfg, params, prompt, 3)
    dirty = minicpm_sala_init_state(cfg, 3, 41)
    for name in ("lightning", "ckeys"):
        dirty[name] = jax.random.normal(
            jax.random.PRNGKey(9), dirty[name].shape, dirty[name].dtype) * 5
    for chunk in (None, 16):
        got, _, _ = _serve_logits(cfg, params, prompt, 3, chunk=chunk,
                                  state=dict(dirty))
        np.testing.assert_allclose(got, clean, atol=2e-4)


# ------------------------------------------------------ lightning layers


@pytest.mark.parametrize("lengths", [(37, 37), (37, 20), (5, 1)])
def test_lightning_forms_agree(jax_cpu, ref, lengths):
    """The chunked scan = the one-token update = the reference's
    masked-decay product, and a right-padded row's padding reaches neither
    its outputs nor its state. float32: 1e-4 on outputs of size ~5 (the
    three differ in the order of sums alone; seen 1e-5)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.lightning import (
        lightning_chunk, lightning_slopes, lightning_step,
    )

    B, S, H, hd = 2, 37, 4, 16
    q, k, v = (jax.random.normal(kk, (B, S, H, hd), jnp.float32)
               for kk in jax.random.split(jax.random.PRNGKey(0), 3))
    slopes = lightning_slopes(H, 10, 32)
    np.testing.assert_allclose(
        slopes, 2.0 ** (-8 * np.arange(1, 5) / 4) * (1 - 10 / 31 + 1e-5),
        rtol=1e-6)
    lens = jnp.asarray(lengths)
    start = jax.random.normal(jax.random.PRNGKey(4), (B, H, hd, hd))
    out, after = lightning_chunk(q, k, v, start, lens, slopes, 0.25, piece=8)
    state, steps, at_len = start, [], [None] * B
    for t in range(S):
        o, state = lightning_step(q[:, t], k[:, t], v[:, t], state, slopes,
                                  0.25)
        steps.append(o)
        for b in range(B):
            if t == lengths[b] - 1:
                at_len[b] = state[b]
    steps = jnp.stack(steps, 1)
    for b in range(B):
        n = lengths[b]
        np.testing.assert_allclose(out[b, :n], steps[b, :n], atol=1e-4)
        np.testing.assert_allclose(after[b], at_len[b], atol=1e-4)
    # the O(n^2) definition, from a zero state
    out0, _ = lightning_chunk(q, k, v, jnp.zeros_like(start), lens, slopes,
                              0.25, piece=8)
    gap = np.arange(S)[:, None] - np.arange(S)[None, :]
    lam = np.where(gap >= 0, np.exp(-np.asarray(slopes)[:, None, None]
                                    * np.maximum(gap, 0)), 0.0)
    want = np.einsum("bqhd,bkhd,hqk,bkhe->bqhe", q, k, lam, v) * 0.25
    for b in range(B):
        n = lengths[b]
        np.testing.assert_allclose(out0[b, :n], want[b, :n], atol=1e-4)


def test_lightning_step_kernel_updates_the_slots_where_they_stand(jax_cpu):
    """``lightning_step`` as a kernel (the interpreter) against XLA's
    gather, update and scatter: the rows' outputs, their slots' new states,
    every other slot and layer untouched, padding rows in slot 0."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.lightning import (
        lightning_slopes, lightning_step, lightning_step_pallas,
    )

    B, H, hd, L, slots_n = 4, 32, 128, 3, 6
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v = (jax.random.normal(kk, (B, H, hd), jnp.float32)
               for kk in keys[:3])
    states = jax.random.normal(keys[3], (L, slots_n, H, hd, hd), jnp.float32)
    slots = jnp.asarray([3, 0, 5, 0], jnp.int32)
    slopes = lightning_slopes(H, 12, 32)
    want_o, want_s = lightning_step(q, k, v, states[1, slots], slopes, 0.1)
    got_o, got_s = lightning_step_pallas(q, k, v, states, 1, slots, slopes,
                                         0.1, interpret=True)
    real = np.asarray(slots) > 0
    np.testing.assert_allclose(got_o[real], want_o[real], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(got_s[1, slots[real]], want_s[real],
                               rtol=1e-6, atol=1e-6)
    untouched = np.ones((L, slots_n), bool)
    untouched[1, [0, 3, 5]] = False
    np.testing.assert_array_equal(np.asarray(got_s)[untouched],
                                  np.asarray(states)[untouched])


def test_lightning_state_in_bfloat16_is_noticed(tiny, ref):
    """What the chip's tolerance must refuse, at the size rounding cannot
    hide it: a lightning state rounded to bfloat16 between chunks (six
    roundings of a 90-token prompt) moves the logits by 1.6e-3 (seen), five
    times the float32 tolerance and more."""
    import jax.numpy as jnp

    from ray_tpu.ops import lightning

    cfg, params = tiny
    prompt = _prompts([90], seed=3)[0]
    good, _, _ = _serve_logits(cfg, params, prompt, 4, chunk=16)
    real = lightning.lightning_chunk

    def rounded(*a, **k):
        out, state = real(*a, **k)
        return out, state.astype(jnp.bfloat16).astype(jnp.float32)

    import ray_tpu.models.minicpm_sala as m
    import jax

    m.lightning_chunk = rounded
    jax.clear_caches()
    try:
        bad, _, _ = _serve_logits(cfg, params, prompt, 4, chunk=16)
    finally:
        m.lightning_chunk = real
        jax.clear_caches()
    assert np.abs(bad - good).max() > 5 * 2e-4


# ----------------------------------------------------- the selection


def _program_blocks(cfg, q, k, pos):
    """The program's chosen blocks for queries q [Q, Hq, hd] at pos [Q]
    over keys k [S, Hkv, hd], as a [Hkv, Q, NB] mask: the segment sums a
    chunked prefill would have written, then the selection."""
    import jax.numpy as jnp

    from ray_tpu.ops.sparse_select import (
        block_scores, choose_blocks, gather_segments, write_segments,
    )

    sp = cfg.sparse
    S = k.shape[0]
    nb = -(-S // sp.block_size)
    tables = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]
    segs = jnp.zeros((1, nb + 1, sp.segments, k.shape[1] * k.shape[2]),
                     jnp.float32)
    segs = write_segments(
        segs, 0, k.reshape(1, S, -1), jnp.arange(S, dtype=jnp.int32)[None],
        tables, sp, jnp.ones((1, S), bool))
    R = block_scores(q[None], gather_segments(segs, 0, tables), pos[None],
                     sp, k.shape[1], 1.0 / np.sqrt(q.shape[-1]))
    blocks, n = choose_blocks(R, pos[None], sp)
    chosen = (np.asarray(blocks)[0][..., None] == np.arange(nb)).any(-2)
    return chosen.transpose(1, 0, 2), np.asarray(n)[0]


@pytest.mark.parametrize("dtype,allowed", [("float32", 0.0),
                                           ("bfloat16", 0.10)])
def test_block_sets_match_the_reference(jax_cpu, ref, dtype, allowed):
    """The program's block sets = the reference's in float32 (every query
    and K/V head of 200 positions, both sides of dense_len); in bfloat16,
    where the queries and keys are rounded before the pooled scores are
    taken, a near-tie falls the other way for a COUNTED share of (query,
    head) sets, each by one block (seen 3%; bound 10%)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.minicpm_sala import MiniCPMSALAConfig

    cfg = MiniCPMSALAConfig.tiny()
    S, Hq, Hkv, hd = 200, cfg.n_head, cfg.n_kv_head, cfg.head_dim
    kq, kk = jax.random.split(jax.random.PRNGKey(2))
    q = jax.random.normal(kq, (S, Hq, hd), jnp.float32) * 1.4
    k = jax.random.normal(kk, (S, Hkv, hd), jnp.float32) * 1.4
    pos = jnp.arange(S, dtype=jnp.int32)
    nb = S // BS
    want = np.asarray(ref.chosen_blocks(
        q, ref.compressed_keys(k, cfg), pos, nb, cfg))
    dt = getattr(jnp, dtype)
    got, n = _program_blocks(cfg, q.astype(dt), k.astype(dt), pos)
    sparse = np.asarray(pos) >= cfg.dense_len
    assert (n[sparse] == cfg.topk).all()
    assert (n[~sparse] == np.asarray(pos)[~sparse, None] // BS + 1).all()
    differ = (got != want).any(-1)                       # [Hkv, Q]
    assert not differ[:, ~sparse].any()
    share = differ[:, sparse].mean()
    print(f"block sets that differ in {dtype}: {share:.3f}")
    assert share <= allowed, share
    # a swapped set differs by one block out and one in
    assert (got != want).sum(-1).max() <= (0 if allowed == 0 else 4)
    # forced: the first block and the window's two, always
    own = np.asarray(pos) // BS
    for t in np.flatnonzero(sparse):
        for h in range(Hkv):
            assert got[h, t, 0] and got[h, t, own[t]] and got[h, t, own[t] - 1]
            assert not got[h, t, own[t] + 1:].any()


def test_the_mask_without_a_sort_is_the_sorted_list(jax_cpu):
    """``chosen_mask`` (the ONE top-k: the k-th largest found a bit at a
    time, ties from the lower index up) = numpy's stable sort of the
    scores, and ``choose_blocks`` (a decode row's list) = that mask in
    ascending order, on scores with ties, forced (+inf) and unseen (-inf)
    blocks, fewer candidates than ``topk``, and queries on both sides of
    ``dense_len``."""
    import jax.numpy as jnp

    from ray_tpu.ops.sparse_select import (
        SparseConfig, choose_blocks, chosen_mask,
    )

    cfg = SparseConfig(4, 2, 8, 6, 1, 16, 64)
    rng = np.random.default_rng(4)
    B, S, Hkv, NB = 2, 40, 2, 24
    # few distinct values: ties everywhere
    R = rng.integers(0, 5, size=(B, S, Hkv, NB)).astype(np.float32) / 4
    pos = rng.integers(0, NB * 8, size=(B, S)).astype(np.int32)
    own = pos // 8
    b = np.arange(NB)
    R = np.where(b <= own[..., None, None], R, -np.inf)
    R = np.where((b == 0) | (b > own[..., None, None] - 2) & (
        b <= own[..., None, None]), np.inf, R).astype(np.float32)
    R[0, :5] = np.where(b < 3, R[0, :5], -np.inf)   # three candidates
    # the definition: the topk largest, ties to the lower index, of the
    # blocks that can be seen; every block up to the own one below dense_len
    order = np.argsort(-R, axis=-1, kind="stable")[..., :cfg.topk]
    want = np.zeros(R.shape, bool)
    np.put_along_axis(want, order, True, axis=-1)
    want &= R > -np.inf
    dense = (pos < cfg.dense_len)[..., None, None]
    want = np.where(dense, b <= own[..., None, None], want)
    got = np.asarray(chosen_mask(jnp.asarray(R), jnp.asarray(pos), cfg))
    np.testing.assert_array_equal(got, want)
    blocks, n = choose_blocks(jnp.asarray(R), jnp.asarray(pos), cfg)
    blocks, n = np.asarray(blocks), np.asarray(n)
    assert blocks.shape[-1] == cfg.list_width
    np.testing.assert_array_equal((blocks[..., None] == b).any(-2), want)
    assert (want.sum(-1) == n).all()
    listed = np.where(np.arange(blocks.shape[-1]) < n[..., None], blocks, -1)
    assert (np.diff(listed, axis=-1)[listed[..., 1:] >= 0] > 0).all()
    assert (blocks[np.arange(blocks.shape[-1]) >= n[..., None]] == NB).all()
    # the own block is the list's last entry (but where the three
    # candidates above cut it off)
    last = np.take_along_axis(blocks, n[..., None] - 1, -1)[..., 0]
    np.testing.assert_array_equal(
        last[1], np.broadcast_to(own[..., None], last.shape)[1])


def test_compressed_keys_complete_across_chunks_blocks_and_decode(tiny, ref):
    """The plane of segment sums after a chunked prefill (chunks of 24:
    boundaries inside blocks and inside compressed keys) and six decode
    steps holds, for every compressed key whose 4 tokens exist, the
    reference's mean of those keys; a key that straddles a chunk's or a
    block's boundary, or the prompt's end, among them."""
    import jax.numpy as jnp

    from ray_tpu.ops.layers import rms_norm

    cfg, params = tiny
    prompt = _prompts([77], seed=11)[0]
    _, seq, state = _serve_logits(cfg, params, prompt, 6, chunk=24)
    n = len(seq) - 1              # tokens whose K was written
    # layer 0's keys from the reference's side: the model's first layer
    # reads the embedding alone
    lp = params["layers"][0]
    x = params["wte"][jnp.asarray(seq[:n])] * cfg.scale_emb
    h = rms_norm(x, lp["mixer_norm"], cfg.norm_eps)
    k = rms_norm((h @ lp["wk"]).reshape(n, cfg.n_kv_head, cfg.head_dim),
                 lp["k_norm"], cfg.norm_eps)
    want = np.asarray(ref.compressed_keys(k, cfg))       # [NJ, Hkv, hd]
    nb = -(-n // BS)
    segs = np.asarray(state["ckeys"])[0, 1:nb + 1].reshape(
        -1, cfg.n_kv_head, cfg.head_dim)                 # slot 1's blocks
    got = (segs[:-1] + segs[1:]) / cfg.kernel_size
    assert want.shape[0] == (n - cfg.kernel_size) // cfg.kernel_stride + 1
    np.testing.assert_allclose(got[:want.shape[0]], want, atol=1e-5)


@pytest.mark.parametrize("rows", ["sparse", "mixed"])
def test_selected_page_kernel_matches_a_gather(jax_cpu, rows):
    """``paged_attention_sparse`` in the interpreter against the gather
    that defines it: lists of differing lengths a (row, K/V head), pages
    in no order of their ids, a partial last block, a padding row."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.sparse_select import (
        sparse_decode_attention_pallas, sparse_decode_attention_xla,
    )

    B, Hq, Hkv, hd, bs, W, NBLK = 3, 8, 2, 128, 16, 12, 40
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (B, Hq, hd), jnp.float32)
    kp = jax.random.normal(keys[1], (2, NBLK, bs, Hkv * hd), jnp.float32)
    vp = jax.random.normal(keys[2], (2, NBLK, bs, Hkv * hd), jnp.float32)
    pages = jax.random.randint(keys[3], (B, Hkv, W), 1, NBLK)
    if rows == "sparse":
        n = np.array([[12, 12], [12, 9], [7, 12]])
    else:
        n = np.array([[1, 1], [12, 5], [3, 3]])   # row 0: one block
    vpos = jnp.asarray((n - 1) * bs + np.array([[3], [15], [0]]), jnp.int32)
    want = sparse_decode_attention_xla(q, kp, vp, pages, vpos, 1)
    got = sparse_decode_attention_pallas(q, kp, vp, pages, vpos, 1,
                                         interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # what lies past a list's end is never read: point it at poison
    past = np.arange(W)[None, None, :] >= n[:, :, None]
    poison = jnp.where(jnp.asarray(past), 0, pages)
    got2 = sparse_decode_attention_pallas(
        q, kp.at[:, 0].set(jnp.nan), vp.at[:, 0].set(jnp.nan), poison, vpos,
        1, interpret=True)
    np.testing.assert_allclose(got2, want, atol=2e-5)


@pytest.mark.parametrize("S,start", [(24, 64), (40, 48)])
def test_select_prefill_kernel_matches_the_masked_form(jax_cpu, S, start):
    """``paged_attention_select`` in the interpreter against XLA's masked
    form, on the same pools and segment sums: a chunk wholly past
    ``dense_len`` and one that crosses it, two rows of differing lengths,
    pages in no order of their ids."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.minicpm_sala import MiniCPMSALAConfig
    from ray_tpu.ops.sparse_select import (
        gather_segments, sparse_prefill_attention, write_segments,
    )

    cfg = MiniCPMSALAConfig.tiny()
    sp = cfg.sparse
    B, Hq, Hkv, hd, nb = 2, cfg.n_head, cfg.n_kv_head, cfg.head_dim, 16
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    n = start + S
    k_all = jax.random.normal(keys[0], (B, n, Hkv * hd), jnp.float32) * 1.4
    v_all = jax.random.normal(keys[1], (B, n, Hkv * hd), jnp.float32)
    q = jax.random.normal(keys[2], (B, S, Hq, hd), jnp.float32) * 1.4
    perm = np.random.default_rng(0).permutation(np.arange(1, 1 + B * nb))
    tables = jnp.asarray(perm.reshape(B, nb), jnp.int32)
    pool = jnp.zeros((1, 1 + B * nb, BS, Hkv * hd), jnp.float32)
    every = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (B, n))
    blk = jnp.take_along_axis(tables, every // BS, axis=1)
    kp = pool.at[0, blk, every % BS].set(k_all)
    vp = pool.at[0, blk, every % BS].set(v_all)
    segs = write_segments(
        jnp.zeros((1, 1 + B * nb, sp.segments, Hkv * hd), jnp.float32), 0,
        k_all, every, tables, sp, jnp.ones((B, n), bool))
    rows = gather_segments(segs, 0, tables)
    pos = start + jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = jnp.asarray(np.arange(S)[None, :] < np.array([[S], [S - 5]]))
    out = {backend: sparse_prefill_attention(
        q, kp, vp, tables, pos, valid, rows, 0, sp, backend=backend)
        for backend in ("xla", "pallas")}
    for b, real in enumerate((S, S - 5)):
        np.testing.assert_allclose(out["pallas"][b, :real],
                                   out["xla"][b, :real], atol=2e-5)


# ------------------------------------------------------- the engine


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_engine_streams_match_the_reference_and_solo(tiny, ref, backend):
    """Rows that join and leave under continuous batching, prompts on both
    sides of dense_len and one that crosses it while decoding: every
    stream is the reference's greedy continuation, together as alone,
    whole as chunked, and a slot that another sequence left is started
    from zeros."""
    import jax.numpy as jnp

    cfg, params = tiny
    prompts = _prompts([5, 70, 100, 61, 33, 90], seed=0)
    news = [8, 4, 8, 8, 3, 6]
    engine = _engine(cfg, params, attention_backend=backend,
                     max_batch_size=3)
    streams = [engine.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, news)]
    _drive(engine, streams)
    outs = [list(s) for s in streams]
    for p, o in zip(prompts, outs):
        seq = p + o
        logits = np.asarray(ref.logits(params, jnp.asarray([seq[:-1]]),
                                       cfg))[0]
        assert logits[len(p) - 1:].argmax(-1).tolist() == o
    stats = engine.stats()
    assert stats["sparse_row_steps"] > 0 and stats["dense_row_steps"] > 0
    assert stats["state_slots_high_water"] == 3
    chunky = _engine(cfg, params, attention_backend=backend,
                     prefill_chunk_tokens=16, max_batch_size=3)
    again = [chunky.submit(p, max_new_tokens=n)
             for p, n in zip(prompts, news)]
    _drive(chunky, again)
    assert [list(s) for s in again] == outs
    assert chunky.generate(prompts[2], max_new_tokens=8) == outs[2]
    engine.shutdown()
    chunky.shutdown()


def test_counters_and_the_cache_managers_account(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompts = _prompts([70, 20], seed=3)
    streams = [engine.submit(p, max_new_tokens=5) for p in prompts]
    for _ in range(3):
        engine.step()
    snap = engine.cache.debug_snapshot()
    # 2 layers x 4 segments x 2 heads x 16 x 4 B a block id in use
    assert snap["compressed_key_bytes"] == snap["used_blocks"] * 1024
    assert snap["used_blocks"] > 0
    _drive(engine, streams)
    stats = engine.stats()
    # the 70-token prompt decodes 4 steps past dense_len, the 20-token one
    # 4 below it; a sparse row-step attends topk = 4 of its blocks a K/V
    # head a sparse layer: 4 steps x 2 heads x 2 layers x 4
    assert stats["sparse_row_steps"] == 4 and stats["dense_row_steps"] == 4
    assert stats["sparse_blocks_attended"] == 4 * 2 * 2 * 4
    # ... of the 9 or 10 blocks up to its own (positions 70..73: block 8,
    # then 9 from position 72)
    assert stats["sparse_blocks_visible"] == 2 * 2 * (9 + 9 + 10 + 10)
    # the steps' own account of the same row-steps, from the positions
    # they query (``Family.step_attrs``, on the flight records as on the
    # dispatch spans): the host's count is the program's
    decodes = [r for r in engine._flight.snapshot()
               if r["kind"] == "decode" and r["batch"]]
    assert sum(r["rows_sparse"] for r in decodes) == 4
    assert sum(r["rows"] - r["rows_sparse"] for r in decodes) == 4
    assert sum(r["sel_blocks"] for r in decodes) * 2 * 2 == 4 * 2 * 2 * 4 + (
        2 * 2 * (3 + 3 + 3 + 3))   # the short row: blocks 0..2 (20..23)
    desc = engine.executor.describe()
    assert desc["state"]["arrays"]["lightning"] == [2, 5, 4, 16, 16]
    assert desc["state"]["arrays"]["ckeys"] == [2, 129, 4, 32]
    engine.shutdown()


def test_step_attrs_by_hand(jax_cpu):
    """What a step's dispatch span says of the selection (tiny preset:
    dense_len 64, blocks of 8, topk 4)."""
    from ray_tpu.models.minicpm_sala import MiniCPMSALAConfig, step_attrs

    cfg = MiniCPMSALAConfig.tiny()
    # rows at positions 63 (dense: blocks 0..7), 64 and 200 (sparse: 4)
    assert step_attrs(cfg, "decode", [(63, 1), (64, 1), (200, 1)]) == {
        "rows": 3, "rows_sparse": 2, "sel_blocks": 8 + 4 + 4}
    # chunks [0, 32), [48, 80) (16 past 64) and [96, 101) (all 5)
    assert step_attrs(cfg, "prefill", [(0, 32), (48, 32), (96, 5)]) == {
        "tokens": 69, "tokens_sparse": 16 + 5}
    assert cfg.kv_selected_pages == (8, 2)


@pytest.mark.parametrize("option,match", [
    ({"speculative_k": 2}, "speculative_k.*rolled back"),
    ({"host_cache_bytes": 1 << 20}, "host_cache_bytes.*state at its"),
    ({"preemption": "swap"}, "preemption.*state slot"),
    ({"quantization": "int8"}, "quantization.*quantized path"),
    ({"tp": 2}, "tp/fsdp/mesh.*state arrays"),
    ({"block_size": 16}, "block_size must be 8"),
    ({"prefill_chunk_tokens": 20}, "whole blocks of 8"),
])
def test_unsupported_options_are_refused_by_name(tiny, option, match):
    cfg, params = tiny
    with pytest.raises(ValueError, match=match):
        _engine(cfg, params, **option)


def test_prefix_reuse_is_off_and_handoff_refused(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompt = _prompts([40], seed=4)[0]
    a = engine.generate(prompt, max_new_tokens=3)
    b = engine.generate(prompt, max_new_tokens=3)
    assert a == b
    assert engine.cache.peek_prefix(prompt) == 0
    st = engine.stats()
    assert st["prefix_reuse"] is False and st["prefix_hit_tokens"] == 0
    assert st["executor"]["prefix_reuse"] is False
    assert st["executor"]["kv_layers"] == cfg.n_kv_layer == 2
    assert st["kv_compressed_key_bytes"] == 0      # nothing is running
    with pytest.raises(ValueError, match="handoff"):
        engine.export_prefix(prompt)
    with pytest.raises(ValueError, match="handoff"):
        engine.adopt_prefix(prompt, [])
    engine.shutdown()
