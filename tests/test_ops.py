"""Kernel correctness: flash attention (interpret mode) + ring attention
vs the XLA reference."""
import pytest


@pytest.fixture(autouse=True)
def _cpu(jax_cpu):
    return jax_cpu


def test_flash_attention_matches_reference(jax_cpu):
    import jax, jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention, mha_reference

    key = jax.random.PRNGKey(0)
    B, H, S, D = 2, 4, 256, 64
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (B, H, S, D)) for i in range(3)
    )
    ref = mha_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_kv=128)
    assert float(jnp.max(jnp.abs(ref - out))) < 2e-5


@pytest.mark.parametrize("seq,block", [(128, 64), (256, 32)])
def test_flash_attention_grads(jax_cpu, seq, block):
    """(128, 64) -> 2 kv blocks: fused single-sweep backward;
    (256, 32) -> 8 kv blocks: two-pass backward. Both must match XLA."""
    import jax, jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention, mha_reference

    key = jax.random.PRNGKey(1)
    B, H, S, D = 1, 2, seq, 32
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (B, H, S, D)) for i in range(3)
    )
    g1 = jax.grad(lambda *a: jnp.sum(flash_attention(*a, block_q=block, block_kv=block) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(mha_reference(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 5e-4


def test_flash_attention_gqa(jax_cpu):
    import jax, jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention, mha_reference

    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (2, 8, 128, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 2, 128, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 2, 128, 32))
    ref = mha_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64)
    assert float(jnp.max(jnp.abs(ref - out))) < 2e-5


def test_ring_attention_matches_reference(jax_cpu):
    import jax, jax.numpy as jnp
    from ray_tpu.ops.attention import mha_reference
    from ray_tpu.ops.ring_attention import ring_attention_sharded
    from ray_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(dp=2, sp=4))
    key = jax.random.PRNGKey(3)
    B, H, S, D = 4, 2, 256, 32
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (B, H, S, D)) for i in range(3)
    )
    for causal in (True, False):
        ref = mha_reference(q, k, v, causal=causal)
        out = ring_attention_sharded(q, k, v, mesh, causal=causal)
        assert float(jnp.max(jnp.abs(ref - out))) < 2e-5, f"causal={causal}"


def test_ring_attention_grad(jax_cpu):
    import jax, jax.numpy as jnp
    from ray_tpu.ops.attention import mha_reference
    from ray_tpu.ops.ring_attention import ring_attention_sharded
    from ray_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(sp=8))
    key = jax.random.PRNGKey(4)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (1, 2, 128, 16)) for i in range(3)
    )
    g1 = jax.grad(lambda q: jnp.sum(ring_attention_sharded(q, k, v, mesh) ** 2))(q)
    g2 = jax.grad(lambda q: jnp.sum(mha_reference(q, k, v) ** 2))(q)
    assert float(jnp.max(jnp.abs(g1 - g2))) < 5e-4


def test_rope_and_norms(jax_cpu):
    import jax, jax.numpy as jnp
    from ray_tpu.ops.layers import layer_norm, rms_norm, rope, rope_cache

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 4, 32))
    cos, sin = rope_cache(16, 32)
    y = rope(x, cos, sin)
    assert y.shape == x.shape
    # rope preserves norms per head-dim pair
    assert float(jnp.max(jnp.abs(
        jnp.linalg.norm(y, axis=-1) - jnp.linalg.norm(x, axis=-1)
    ))) < 1e-4

    h = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    out = rms_norm(h, jnp.ones(64))
    assert float(jnp.max(jnp.abs(
        jnp.sqrt(jnp.mean(out**2, -1)) - 1.0
    ))) < 1e-3
    out2 = layer_norm(h, jnp.ones(64), jnp.zeros(64))
    assert abs(float(jnp.mean(out2))) < 1e-5


def test_fused_lm_head_loss_matches_reference(jax_cpu):
    """Fused chunked lm-head+CE: loss and both grads match the materialized
    logits formulation, including masking, padding (N % chunk != 0), and a
    scaled upstream cotangent."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.loss import fused_lm_head_loss

    N, D, V = 50, 16, 97
    x = jax.random.normal(jax.random.PRNGKey(0), (N, D))
    w = jax.random.normal(jax.random.PRNGKey(1), (V, D)) * 0.1
    t = jax.random.randint(jax.random.PRNGKey(2), (N,), 0, V)
    m = (jax.random.uniform(jax.random.PRNGKey(3), (N,)) > 0.2).astype(jnp.float32)

    def ref(x, w, t, m):
        logits = (x @ w.T).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - picked) * m) / jnp.maximum(jnp.sum(m), 1)

    def fused(x, w, t, m):
        return fused_lm_head_loss(x, w, t, m, 16)

    assert abs(float(fused(x, w, t, m)) - float(ref(x, w, t, m))) < 1e-5
    g1 = jax.jit(jax.grad(lambda *a: 3.0 * fused(*a), argnums=(0, 1)))(x, w, t, m)
    g2 = jax.grad(lambda *a: 3.0 * ref(*a), argnums=(0, 1))(x, w, t, m)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-3
    # mask=None means every token counts
    l1 = fused_lm_head_loss(x, w, t, None, 16)
    assert abs(float(l1) - float(ref(x, w, t, jnp.ones(N)))) < 1e-5


def test_gpt_loss_fused_vs_unfused(jax_cpu):
    """cfg.fused_loss must not change the training objective: same loss and
    same wte gradient (embedding + tied lm-head contributions) either way."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss

    cfg = dataclasses.replace(
        GPTConfig.tiny(), dtype=jnp.float32, attention="xla"
    )
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 33), 0, cfg.vocab_size, jnp.int32
    )
    batch = {"tokens": tokens}

    cfg_fused = dataclasses.replace(cfg, fused_loss=True)
    cfg_plain = dataclasses.replace(cfg, fused_loss=False)
    l1, g1 = jax.value_and_grad(gpt_loss)(params, batch, cfg_fused)
    l2, g2 = jax.value_and_grad(gpt_loss)(params, batch, cfg_plain)
    assert abs(float(l1) - float(l2)) < 1e-5
    err = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), g1, g2
    )
    assert max(jax.tree.leaves(err)) < 1e-4, err


def _paged_setup(key, lengths, n_kv_head, head_dim, block_size, n_blocks_per_seq,
                 shuffle, lane_dense=False):
    """Build a paged KV pool holding ragged sequences.

    Returns (k_contig, v_contig, k_layer, v_layer, block_tables): contiguous
    [B, T_cap, Hkv, hd] K/V alongside the same tokens scattered into a
    paged pool via write_kv. Block 0 is the garbage sink: the pool is
    pre-filled with noise (so any accidental read of an unowned block is
    loud), tables of sequences shorter than the capacity are padded with 0,
    and `shuffle` scrambles the physical id assignment so tests cover
    non-contiguous layouts. `lane_dense` stores a token's heads as ONE row,
    [num_blocks, block_size, Hkv * hd] (what the cache manager does where
    [Hkv, hd] is not whole tiles): the same values, written by the same
    `write_kv`."""
    import random as _random

    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.kv_cache import write_kv

    B = len(lengths)
    T_cap = n_blocks_per_seq * block_size
    assert max(lengths) <= T_cap
    num_blocks = 1 + B * n_blocks_per_seq
    ids = list(range(1, num_blocks))
    if shuffle:
        _random.Random(1234).shuffle(ids)
    table_rows, next_id = [], 0
    for L in lengths:
        needed = -(-L // block_size)  # ceil
        row = ids[next_id:next_id + needed] + [0] * (n_blocks_per_seq - needed)
        next_id += needed
        table_rows.append(row)
    block_tables = jnp.asarray(table_rows, jnp.int32)

    k_contig = jax.random.normal(
        jax.random.fold_in(key, 1), (B, T_cap, n_kv_head, head_dim)
    )
    v_contig = jax.random.normal(
        jax.random.fold_in(key, 2), (B, T_cap, n_kv_head, head_dim)
    )
    pool_shape = (num_blocks, block_size, n_kv_head, head_dim)
    k_layer = jax.random.normal(jax.random.fold_in(key, 3), pool_shape)
    v_layer = jax.random.normal(jax.random.fold_in(key, 4), pool_shape)
    if lane_dense:
        k_layer, v_layer = (
            x.reshape(num_blocks, block_size, -1) for x in (k_layer, v_layer))
    pos = jnp.broadcast_to(jnp.arange(T_cap, dtype=jnp.int32), (B, T_cap))
    valid = pos < jnp.asarray(lengths, jnp.int32)[:, None]
    k_layer, v_layer = write_kv(
        k_layer, v_layer, k_contig, v_contig, pos, block_tables, valid=valid
    )
    return k_contig, v_contig, k_layer, v_layer, block_tables


@pytest.mark.parametrize("stored", ["heads", "lane-dense"])
@pytest.mark.parametrize("gqa", [1, 2, 4])
@pytest.mark.parametrize("shuffle", [False, True])
def test_paged_attention_matches_reference(jax_cpu, gqa, shuffle, stored):
    """Decode-time paged attention == mha_reference's causal row at each
    sequence's last position, over ragged lengths, block-0-padded tables,
    and (shuffle=True) scrambled physical block ids."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import mha_reference
    from ray_tpu.ops.kv_cache import paged_attention

    key = jax.random.PRNGKey(10 + gqa)
    lengths = [1, 7, 16, 29]
    Hkv, hd, bs, NB = 2, 32, 8, 4
    Hq = Hkv * gqa
    kc, vc, k_layer, v_layer, tables = _paged_setup(
        key, lengths, Hkv, hd, bs, NB, shuffle, stored == "lane-dense"
    )
    assert k_layer.ndim == (3 if stored == "lane-dense" else 4)
    B, T_cap = kc.shape[:2]
    q_full = jax.random.normal(jax.random.fold_in(key, 5), (B, T_cap, Hq, hd))
    ref_full = mha_reference(  # [B, Hq, T_cap, hd]
        q_full.transpose(0, 2, 1, 3),
        kc.transpose(0, 2, 1, 3),
        vc.transpose(0, 2, 1, 3),
        causal=True,
    )
    positions = jnp.asarray(lengths, jnp.int32) - 1
    q = jnp.take_along_axis(
        q_full, positions[:, None, None, None], axis=1
    )[:, 0]  # [B, Hq, hd]
    out = paged_attention(q, k_layer, v_layer, tables, positions)
    ref = jnp.take_along_axis(
        ref_full, positions[:, None, None, None], axis=2
    )[:, :, 0]
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5, (gqa, shuffle)


@pytest.mark.parametrize("stored", ["heads", "lane-dense", "lane-dense-stream"])
@pytest.mark.parametrize("gqa", [1, 2, 4])
def test_paged_prefill_attention_matches_reference(
        jax_cpu, gqa, stored, monkeypatch):
    """Chunked-prefill paged attention == causal mha_reference on every
    valid (non-padding) query row, shuffled tables + ragged lengths; over a
    pool stored by heads or lane-dense, the latter under the streaming scan
    too."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import mha_reference
    from ray_tpu.ops.kv_cache import paged_prefill_attention

    key = jax.random.PRNGKey(20 + gqa)
    lengths = [3, 12, 32, 17]
    Hkv, hd, bs, NB = 2, 16, 8, 4
    Hq = Hkv * gqa
    kc, vc, k_layer, v_layer, tables = _paged_setup(
        key, lengths, Hkv, hd, bs, NB, shuffle=True,
        lane_dense=stored != "heads"
    )
    if stored == "lane-dense-stream":
        import ray_tpu.ops.kv_cache as kvc

        monkeypatch.setattr(kvc, "PREFILL_STREAM_MIN_T", 1)
    B, T_cap = kc.shape[:2]
    q_full = jax.random.normal(jax.random.fold_in(key, 5), (B, T_cap, Hq, hd))
    lens = jnp.asarray(lengths, jnp.int32)
    t = jnp.arange(T_cap, dtype=jnp.int32)
    # padding queries get clamped positions; their rows are discarded below
    positions = jnp.minimum(t[None, :], lens[:, None] - 1)
    out = paged_prefill_attention(
        q_full, k_layer, v_layer, tables, positions
    )  # [B, T_cap, Hq, hd]
    ref = mha_reference(
        q_full.transpose(0, 2, 1, 3),
        kc.transpose(0, 2, 1, 3),
        vc.transpose(0, 2, 1, 3),
        causal=True,
    ).transpose(0, 2, 1, 3)  # back to [B, T_cap, Hq, hd]
    valid = (t[None, :] < lens[:, None])[:, :, None, None]
    err = jnp.max(jnp.abs(jnp.where(valid, out - ref, 0.0)))
    assert float(err) < 2e-5, gqa


def test_flash_attention_odd_bh_and_seq(jax_cpu):
    """Regression: group size must divide batch*heads (bh=12 with the cap
    at 8 once silently skipped heads 8-11), and default 1024 blocks must
    clamp to a divisor of seq (1536 = 3*512)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention, mha_reference

    for B, H, S, D in ((1, 12, 128, 32), (1, 2, 384, 32), (1, 2, 1536, 32)):
        q, k, v = (
            jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(0), i),
                              (B, H, S, D))
            for i in range(3)
        )
        ref = mha_reference(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True)
        assert float(jnp.max(jnp.abs(ref - out))) < 2e-5, (B, H, S, D)
