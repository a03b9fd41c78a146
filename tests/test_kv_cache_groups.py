"""The cache manager's tables by group of layers (serve/llm/kv_cache.py): one
pool, one free list, a table a group; a windowed group gives back the blocks
behind its window. The allocator under random admit / step / finish
sequences driven as the engine drives it: no block in two tables, none
leaked, every block a sliding layer's next query can see still held,
holdings never above what was reserved. And the one-group case: the block
accounting of the families that name no group is what it was.
"""
from __future__ import annotations

import numpy as np
import pytest

BS, WINDOW, CHUNK, ROWS = 4, 10, 16, 3
GROUPS = ((None, (0, 4)), (WINDOW, (1, 5)), (WINDOW, (2, 6)), (WINDOW, (3, 7)))


def _cache(groups=GROUPS, num_blocks=400):
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig, PagedKVCache

    return PagedKVCache(KVCacheConfig(
        n_layer=2, n_kv_head=1, head_dim=4, num_blocks=num_blocks,
        block_size=BS, prefix_reuse=not groups, groups=groups))


def _held(cache, seq):
    """Blocks the sequence holds, by group (entries given back are 0)."""
    return [[b for b in t if b] for t in cache._group_tables(seq)]


def _check(cache, live, room):
    """The invariants, between steps."""
    seen = {}
    for seq in live:
        for g, blocks in enumerate(_held(cache, seq)):
            for b in blocks:
                assert b not in seen, f"block {b} in two tables"
                assert 0 < b < cache.cfg.num_blocks
                seen[b] = (seq, g)
    free = set(cache._free) | set(cache._quarantine)
    assert not free & set(seen), "a held block is on the free list"
    assert len(free) + len(seen) == cache.cfg.usable_blocks, "a block leaked"
    assert cache.used_blocks == len(seen)
    # reservations still promised = what was reserved less what is held
    promised = room + sum(r["reserved"] - r["drawn"] for r in live.values())
    assert cache.reserved_blocks == promised
    for seq, r in live.items():
        assert r["drawn"] == sum(len(b) for b in _held(cache, seq))
        assert r["drawn"] <= r["reserved"], (seq, r)
        # every position a query at ``next`` or later can see is held
        for g, (window, _) in enumerate(cache.cfg.groups):
            table = cache._group_tables(seq)[g]
            lo = 0 if window is None else max(0, r["next"] - window + 1)
            for pos in range(lo, r["next"]):
                assert table[pos // BS] != 0, (seq, g, pos, r["next"])
    assert [g["blocks"] for g in cache.group_report()] == [
        sum(len(_held(cache, seq)[g]) for seq in live)
        for g in range(len(cache.cfg.groups))]


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_random_admit_step_finish_keeps_the_books(seed):
    """Driven as the engine drives it: reserve ``request_blocks`` at
    admission and the prefill room once; a prefill step frees behind its
    start, takes its chunk and frees behind its end; a decode step frees
    behind its position and takes the frontier's block."""
    rng = np.random.default_rng(seed)
    cache = _cache()
    cfg = cache.cfg
    room = cfg.prefill_room(ROWS, CHUNK)
    assert room == ROWS * 3 * (CHUNK // BS)
    cache.reserve(room)
    live: dict[int, dict] = {}
    next_id = 0
    peak_over = 0
    for _ in range(600):
        roll = rng.random()
        if roll < 0.15 and len(live) < 8:
            prompt, new = int(rng.integers(1, 90)), int(rng.integers(1, 40))
            need = cfg.request_blocks(prompt + new)
            if cache.can_reserve(need):
                cache.reserve(need)
                cache.allocate(next_id)
                live[next_id] = {"reserved": need, "drawn": 0, "next": 0,
                                 "prompt": prompt, "total": prompt + new}
                next_id += 1
        elif roll < 0.25 and live:
            seq = int(rng.choice(list(live)))
            r = live.pop(seq)
            assert cache.free(seq) == r["drawn"]
            cache.release_reservation(r["reserved"] - r["drawn"])
        elif live:
            # one step: up to ROWS prefilling rows, or every decoding row
            prefilling = [s for s, r in live.items()
                          if r["next"] < r["prompt"]][:ROWS]
            if prefilling and rng.random() < 0.6:
                for seq in prefilling:
                    r = live[seq]
                    n = min(CHUNK, r["prompt"] - r["next"])
                    r["drawn"] -= cache.free_behind(seq, r["next"])
                    r["drawn"] += cache.ensure_capacity(seq, r["next"] + n)
                    # inside the step a row may stand over its reservation,
                    # by no more than its share of the room
                    peak_over = max(peak_over, r["drawn"] - r["reserved"])
                    assert r["drawn"] - r["reserved"] <= room // ROWS
                for seq in prefilling:
                    r = live[seq]
                    r["next"] += min(CHUNK, r["prompt"] - r["next"])
                    r["drawn"] -= cache.free_behind(seq, r["next"])
            else:
                for seq, r in live.items():
                    if r["prompt"] <= r["next"] < r["total"]:
                        r["drawn"] -= cache.free_behind(seq, r["next"])
                        r["drawn"] += cache.ensure_capacity(
                            seq, r["next"] + 1)
                        r["next"] += 1
        _check(cache, live, room)
    assert peak_over > 0, "no prefill step ever drew on the room"
    st = cache.stats
    assert 0 < st.window_blocks_freed < st.window_blocks_taken
    for seq, r in list(live.items()):
        cache.free(seq)
        cache.release_reservation(r["reserved"] - r["drawn"])
        del live[seq]
    _check(cache, live, room)
    assert cache.used_blocks == 0 and cache.reserved_blocks == room
    assert len(cache._free) == cfg.usable_blocks
    assert st.allocated_total == st.freed_total


@pytest.mark.parametrize("next_pos,floor", [
    (0, 0), (9, 0), (12, 0), (13, 1), (16, 1), (17, 2), (40, 7), (41, 8),
])
def test_free_behind_goes_exactly_to_the_windows_floor(next_pos, floor):
    """A query at q sees t > q - 10: block i (positions 4i .. 4i + 3) goes
    when 4i + 3 <= next_pos - 10, and not one block sooner or later."""
    cache = _cache()
    cache.reserve(cache.cfg.request_blocks(64))
    cache.allocate("s")
    assert cache.ensure_capacity("s", 44) == 4 * 11
    freed = cache.free_behind("s", next_pos)
    assert freed == 3 * floor
    table = cache.block_table("s", 12)
    assert table.shape == (4, 12) and table.dtype == np.int32
    assert (table[0, :11] > 0).all() and (table[:, 11] == 0).all()
    for g in (1, 2, 3):
        assert (table[g, :floor] == 0).all()
        assert (table[g, floor:11] > 0).all()
    # what the kernel starts at is the first page still held
    assert floor == max(0, next_pos - (WINDOW - 1)) // BS
    assert cache.free_behind("s", next_pos) == 0  # nothing twice
    assert cache.stats.window_blocks_freed == freed
    assert cache.free("s") == 44 - freed


def test_freed_blocks_serve_the_other_kind():
    """Memory moves between the kinds by demand: what a sliding group gave
    back is what the full group's next block is made of."""
    cache = _cache(num_blocks=4 * 6 + 1)
    cache.reserve(cache.cfg.request_blocks(24))
    cache.allocate("a")
    cache.ensure_capacity("a", 24)
    assert cache.available_blocks == 0
    back = set(int(b) for b in cache.block_table("a", 6)[1:, :2].ravel())
    assert cache.free_behind("a", 17) == 6
    cache.reserve(1)
    cache.allocate("b")
    assert cache.ensure_capacity("b", 1) == 4
    assert set(cache._tables["b"]) <= back
    cache.release_all()
    assert len(cache._free) == cache.cfg.usable_blocks


def test_reservation_rule_of_the_issue():
    """``ceil((prompt + new) / 16)`` for the full group and 34 a sliding
    group; the prefill room of max_prefill_batch x 3 x 128."""
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig

    cfg = KVCacheConfig(
        n_layer=2, n_kv_head=8, head_dim=128, num_blocks=32769,
        block_size=16, groups=((None, (0, 4)), (512, (1, 5)),
                               (512, (2, 6)), (512, (3, 7))))
    assert cfg.window_blocks(512) == 34
    assert cfg.request_blocks(3424 + 487) == 245 + 3 * 34
    assert cfg.request_blocks(100) == 4 * 7  # shorter than a window
    assert cfg.prefill_room(4, 2048) == 1536
    # the cell's traffic: 64 rows and the room fit the pool
    assert 64 * cfg.request_blocks(3424 + 487) + 1536 < cfg.usable_blocks


def test_one_group_accounting_is_what_it_was():
    """A family that names no group: one table, ``[pad_to]``, and every
    number the engine reads is the old one."""
    cache = _cache(groups=())
    cfg = cache.cfg
    assert cfg.request_blocks(37) == cfg.blocks_for(37) == 10
    assert cfg.prefill_room(4, 2048) == 0
    cache.reserve(10)
    cache.allocate("s")
    assert cache.ensure_capacity("s", 21) == 6
    assert cache.reserved_blocks == 4
    assert cache.free_behind("s", 1000) == 0
    table = cache.block_table("s", 8)
    assert table.shape == (8,) and (table[:6] > 0).all() and not table[6:].any()
    assert cache.num_allocated("s") == 6 and cache.used_blocks == 6
    assert cache.group_report() == []
    snap = cache.debug_snapshot()
    assert snap["groups"] == [] and snap["window_blocks_taken"] == 0
    assert cache.stats.window_blocks_freed == 0
    assert cache.free("s") == 6
    cache.release_reservation(4)
    assert cache.reserved_blocks == 0 and cache.used_blocks == 0
    assert len(cache._free) == cfg.usable_blocks


def test_engine_never_holds_more_than_it_reserved(jax_cpu):
    """The engine's own bookkeeping over mixed traffic, checked between
    steps: every request's drawn blocks are what its tables hold and at
    most what it reserved; at the end nothing is held and only the prefill
    room is still promised."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.laguna import LagunaConfig, laguna_init
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    cfg = dataclasses.replace(LagunaConfig.tiny(), dtype=jnp.float32)
    engine = LLMEngine(EngineConfig(
        model="laguna", model_config=cfg, block_size=4, num_blocks=161,
        max_batch_size=4, prefill_chunk_tokens=16,
        length_buckets=(16, 32, 64, 128)),
        params=laguna_init(jax.random.PRNGKey(0), cfg), auto_step=False)
    rng = np.random.default_rng(7)
    streams, pending = [], [int(n) for n in rng.integers(3, 70, size=10)]
    for _ in range(3000):
        if pending and rng.random() < 0.2:
            n = pending.pop()
            streams.append(engine.submit(
                rng.integers(1, 512, size=n).tolist(),
                max_new_tokens=int(rng.integers(2, 30))))
        if not engine.step() and not pending:
            break
        cache = engine.cache
        live = engine._running + engine._prefilling
        for r in live:
            held = sum(1 for t in cache._group_tables(r.id) for b in t if b)
            assert r.drawn_blocks == held <= r.reserved_blocks
        assert cache.reserved_blocks == engine._kv_room + sum(
            r.reserved_blocks - r.drawn_blocks for r in live)
    assert all(s.done for s in streams) and len(streams) == 10
    assert engine.cache.used_blocks == 0
    assert engine.cache.reserved_blocks == engine._kv_room > 0
    assert engine.stats()["kv_window_blocks_freed"] > 0
    engine.shutdown()
