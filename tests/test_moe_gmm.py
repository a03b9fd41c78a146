"""The grouped expert product's two forms (ops/moe.py): ``ragged``, XLA's
``ragged_dot`` twice, and ``few_rows``, the repo's Pallas kernel for a
decode step's few rows an expert. Float32 through the Pallas interpreter at
the five expert cells' (router outputs, ``top_k``, held experts) with
reduced widths: both forms equal a dense reference (every row through every
held expert, then selected) under every routing that moves a group's edge,
and the rule that chooses the form gives the recorded one at each cell's
decode and prefill shapes (docs/MICROBENCHMARKS.md, PR 49)."""
from __future__ import annotations

import numpy as np
import pytest

# name: (router outputs, top_k, held (first, count) or None, zero_from)
CELLS = {
    "lfm2": (64, 4, None, None),
    "laguna": (256, 8, (0, 32), None),
    "pangu": (256, 8, (0, 8), None),
    "smallthinker": (64, 6, None, None),
    "longcat": (768, 12, (0, 16), 512),
}
ROUTINGS = ("even", "one_group", "one_expert", "behind", "valid_rows",
            "zero_from", "odd_pairs")
D, F = 256, 256
# a row tile of 16 and weight tiles of 128 rows: two tiles each way, and a
# group of more than 16 rows reaches several row tiles
ROWS_TILE, WEIGHT_TILE_BYTES = 16, 128 * D * 4


def _dense_reference(x, weights, experts, w_in, w_out, valid, held,
                     zero_from):
    import jax
    import jax.numpy as jnp

    first, count = held
    hi = jax.lax.Precision.HIGHEST
    h = jnp.einsum("td,edf->etf", x, w_in, precision=hi)
    gate, up = jnp.split(h, 2, axis=-1)
    y = jnp.einsum("etf,efd->etd", jax.nn.silu(gate) * up, w_out,
                   precision=hi)
    w = jnp.where(valid[:, None], weights, 0.0)
    coef = jnp.sum(jnp.where(
        experts[:, :, None] == first + jnp.arange(count), w[:, :, None], 0.0),
        axis=1)
    out = jnp.einsum("etd,te->td", y, coef, precision=hi)
    if zero_from is not None:
        out = out + jnp.sum(
            jnp.where(experts >= zero_from, w, 0.0), -1, keepdims=True) * x
    sizes = jnp.sum(
        (experts[:, :, None] == first + jnp.arange(count))
        & valid[:, None, None], axis=(0, 1))
    return out, sizes


def _case(cell: str, routing: str):
    """(T, experts [T, k], valid, held, zero_from) of one case."""
    E_all, k, held, zero_from = CELLS[cell]
    rng = np.random.default_rng(sum(map(ord, cell + routing)))
    T = 7 if routing == "odd_pairs" else 12
    experts = np.stack([rng.permutation(E_all)[:k] for _ in range(T)])
    valid = np.ones((T,), bool)
    first, count = held or (0, zero_from or E_all)
    if routing == "one_group":
        # one pick a row on ONE held expert, every other pick off them or,
        # where all are held, on the same expert too
        away = first + count if first + count < (zero_from or E_all) else None
        experts[:] = first + count - 1 if away is None else away
        experts[:, 0] = first + count - 1
    elif routing == "one_expert":
        experts[:] = first + 1  # every pair: T x k rows in one group
    elif routing == "behind":
        # two experts held of the router's: ~97% of the pairs behind them
        first, count = 2, 2
        experts[0, 0], experts[3, 1] = 2, 3
    elif routing == "valid_rows":
        valid[T - 5:] = False
    elif routing == "zero_from" and zero_from is None:
        # the last third of the router's outputs name zero-compute experts
        zero_from = 2 * E_all // 3
        count = min(count, zero_from - first)
    return T, experts.astype(np.int32), valid, (first, count), zero_from


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("cell", CELLS)
def test_both_forms_equal_the_dense_reference(jax_cpu, monkeypatch, cell,
                                              routing):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    T, experts, valid, held, zero_from = _case(cell, routing)
    k = experts.shape[1]
    first, count = held
    keys = jax.random.split(jax.random.PRNGKey(len(cell) + len(routing)), 4)
    x = jax.random.normal(keys[0], (T, D))
    w_in = jax.random.normal(keys[1], (count, D, 2 * F)) * D ** -0.5
    w_out = jax.random.normal(keys[2], (count, F, D)) * F ** -0.5
    weights = jax.nn.softmax(jax.random.normal(keys[3], (T, k)))
    want, want_sizes = _dense_reference(
        x, weights, jnp.asarray(experts), w_in, w_out, jnp.asarray(valid),
        held, zero_from)
    monkeypatch.setattr(moe, "_FEW_ROWS_TILE", ROWS_TILE)
    monkeypatch.setattr(moe, "_FEW_WEIGHT_TILE_BYTES", WEIGHT_TILE_BYTES)
    got = {}
    for form in moe.GMM_FORMS:
        monkeypatch.setattr(moe, "gmm_form", lambda *a, form=form: form)
        y, sizes = moe.moe_dropless(
            x, weights, jnp.asarray(experts), w_in, w_out, dtype=jnp.float32,
            valid=jnp.asarray(valid), held=held, zero_from=zero_from)
        np.testing.assert_array_equal(np.asarray(sizes),
                                      np.asarray(want_sizes))
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   atol=3e-5, err_msg=form)
        got[form] = np.asarray(y)
    np.testing.assert_allclose(got["few_rows"], got["ragged"], atol=2e-5)
    if routing == "valid_rows":
        np.testing.assert_array_equal(got["few_rows"][T - 5:], 0.0)
    if routing == "one_expert":  # one group over several row tiles
        assert T * k > 2 * ROWS_TILE
        assert int(want_sizes[1]) == T * k


def _drawn(T, E_all, k, held=None, padding=0.0, seed=0):
    """Group sizes as a cell's router draws them: k distinct experts a row,
    evenly; ``held`` keeps a slice's, ``padding`` is the share of rows that
    hold no token. -> (sizes [E], the sorted buffer's rows T * k)."""
    rng = np.random.default_rng(seed)
    picks = np.stack([rng.permutation(E_all)[:k] for _ in range(T)])
    picks = picks[rng.permutation(T) >= int(T * padding)].reshape(-1)
    first, count = held or (0, E_all)
    picks = picks[(picks >= first) & (picks < first + count)] - first
    return np.bincount(picks, minlength=count).astype(np.int32), T * k


# name: (sizes [E], the sorted buffer's rows m, the row tile tm)
LISTS = {
    "cell13_decode": (*_drawn(1024, 128, 8, padding=0.25), 128),
    "cell13_prefill": (*_drawn(2048, 128, 8, seed=1), 128),
    "cell5_decode": (*_drawn(64, 64, 4, seed=2), 128),
    "cell5_prefill": (*_drawn(768, 64, 4, seed=3), 128),
    "ep_slice_decode": (*_drawn(64, 256, 8, held=(0, 32), seed=4), 128),
    "ep_slice_prefill": (*_drawn(2048, 256, 8, held=(0, 8), seed=5), 128),
    "one_group_of_every_row": (np.array([0, 0, 300, 0], np.int32), 300, 128),
    "exactly_a_tile": (np.array([5, 128, 0, 3], np.int32), 136, 128),
    "a_tile_and_a_row": (np.array([5, 129, 0, 3], np.int32), 144, 128),
    "empty_groups_between": (
        np.array([0, 7, 0, 0, 16, 0, 17, 0, 0, 1, 0], np.int32), 48, 16),
    "every_group_one_row": (np.ones(24, np.int32), 24, 16),
    "more_groups_than_rows": (
        np.array([1, 0, 2, 0, 0, 1, 0, 0, 0, 0, 0, 0], np.int32), 4, 16),
    "no_group_at_all": (np.zeros(8, np.int32), 64, 16),
}


@pytest.mark.parametrize("case", LISTS)
def test_the_list_cuts_at_group_edges(jax_cpu, case):
    """``_work_items`` alone: an item is a group's next ``tm`` rows counted
    from the GROUP's first row, so there are ``sum(ceil(sizes / tm))`` of
    them (``few_rows_items``: an expert streams once wherever its group has
    at most ``tm`` rows, whatever row it starts at), every row of every
    group lies in exactly one item, the items stand in the rows' order,
    each inside its window, and none past the lists' static length."""
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    sizes, m, tm = LISTS[case]
    align = moe._FEW_ROWS_ALIGN
    E = len(sizes)
    rows = tm + align
    m_pad = max(-(-m // align) * align, rows)
    bound = moe.few_rows_items_bound(E, m, tm)
    grp, win, lo, hi, total = (np.asarray(a) for a in moe._work_items(
        jnp.asarray(sizes), tm, bound, align, (m_pad - rows) // align))
    want = int(np.sum(-(-sizes.astype(np.int64) // tm)))
    assert int(total) == want == int(moe.few_rows_items(sizes, tm))
    assert int(moe.few_rows_items(jnp.asarray(sizes), tm)) == want
    assert want <= bound == len(grp) == len(win) == len(lo) == len(hi)
    if tm == moe._FEW_ROWS_TILE:
        assert int(moe.few_rows_items(sizes)) == want
    ends = np.cumsum(sizes)
    owner = np.full(m_pad, -1)  # the group of every sorted row
    for g in range(E):
        owner[ends[g] - sizes[g]:ends[g]] = g
    seen = np.zeros(m_pad, np.int32)
    for w in range(want):
        assert 0 < hi[w] - lo[w] <= tm
        assert np.all(owner[lo[w]:hi[w]] == grp[w])
        seen[lo[w]:hi[w]] += 1
        # counted from the group's first row
        assert (lo[w] - (ends[grp[w]] - sizes[grp[w]])) % tm == 0
        # inside its window, which starts on a tile and ends in the buffer
        assert win[w] * align <= lo[w] and hi[w] <= win[w] * align + rows
        assert win[w] * align + rows <= m_pad
        if w:  # in the rows' order, nothing between two items
            assert lo[w] == hi[w - 1]
    np.testing.assert_array_equal(seen, owner >= 0)
    # past the count: group 0 and no rows
    assert not np.any(grp[want:]) and np.all(hi[want:] <= lo[want:])
    # the list of before made an item an ALIGNED tile of sorted rows a
    # group reaches: a group more, and one for every tile's edge crossed
    before = int(np.sum(np.where(
        sizes > 0, (ends - 1) // tm - (ends - sizes) // tm + 1, 0)))
    assert want <= before
    if case == "cell13_decode":  # ~48 rows a group: once each, was 128 + 47
        assert want == int(np.sum(sizes > 0)) == 128 and before >= 170


# name: (sizes [8], the sorted buffer's rows m); a row tile of 16 and tiles
# of 8 output rows: groups that start and end inside a row tile, off a
# multiple of 16 and of 8, of exactly a tile and a row more, that end with
# the buffer (its last window), a buffer of less than a window, no group
ALONE = {
    "spans_three": ([3, 0, 0, 37, 1, 0, 9, 0], 96),
    "off_every_edge": ([5, 16, 17, 0, 2, 1, 30, 0], 96),
    "a_tile_and_a_row_more": ([3, 16, 17, 0, 16, 0, 0, 7], 64),
    "ends_with_the_buffer": ([1, 0, 20, 0, 0, 0, 3, 16], 40),
    "less_than_a_window": ([2, 0, 1, 0, 0, 0, 0, 0], 5),
    "one_group_of_every_row": ([0, 0, 0, 50, 0, 0, 0, 0], 50),
    "one_row_each": ([1, 1, 1, 1, 1, 1, 1, 1], 24),
    "no_group": ([0, 0, 0, 0, 0, 0, 0, 0], 32),
}


@pytest.mark.parametrize("case", ALONE)
def test_the_kernel_alone_leaves_no_group_out(jax_cpu, case):
    """``moe_gmm_few_rows`` against ``ragged_dot`` on sorted rows: every
    group's rows are ``ragged_dot``'s whatever row the group starts at (an
    item's window overhangs its group on both sides: the neighbours' rows
    are theirs all the same); behind the last group, the rest of its tile
    of 8 is zero as ``ragged_dot`` leaves it, and no item visits the rows
    past it (the interpreter's untouched output is NaN)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    sizes, m = ALONE[case]
    sizes = jnp.asarray(sizes, jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    xs = jax.random.normal(keys[0], (m, D))
    w_in = jax.random.normal(keys[1], (8, D, 2 * F)) * D ** -0.5
    w_out = jax.random.normal(keys[2], (8, F, D)) * F ** -0.5
    got = moe._moe_gmm_few_rows_call(
        xs, w_in, w_out, sizes, act="relu", tm=ROWS_TILE,
        tile_bytes=WEIGHT_TILE_BYTES, interpret=True)
    h = jax.lax.ragged_dot(xs, w_in, sizes)
    gate, up = jnp.split(h, 2, axis=-1)
    want = jax.lax.ragged_dot(jax.nn.relu(gate) * up, w_out, sizes)
    n = int(sizes.sum())
    assert got.shape == (m, D) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got[:n]), np.asarray(want[:n]),
                               atol=2e-5)
    tile = min(-(-n // moe._FEW_ROWS_ALIGN) * moe._FEW_ROWS_ALIGN, m)
    np.testing.assert_array_equal(np.asarray(got[n:tile]), 0.0)
    assert np.all(np.isnan(np.asarray(got[tile:])))


def test_an_engine_decodes_through_the_kernel_and_says_so(jax_cpu):
    """An lfm2_moe engine at widths of whole lane tiles: its step programs
    take ``few_rows`` (the rule, from the programs' shapes), the streams
    are the full forward's greedy tokens, and ``stats()`` and the decode
    flight records name the form; the tiny preset's widths (64, 32) are no
    lane tiles and stay on ``ragged``."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.lfm2_moe import (
        Lfm2MoeConfig, lfm2_moe_forward, lfm2_moe_init)
    from ray_tpu.ops import moe
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    cfg = dataclasses.replace(
        Lfm2MoeConfig.tiny(), dtype=jnp.float32, d_model=128, head_dim=64,
        d_expert=128, num_experts=4)
    params = lfm2_moe_init(jax.random.PRNGKey(2), cfg)
    engine = LLMEngine(
        EngineConfig(model="lfm2_moe", model_config=cfg, num_blocks=65,
                     max_batch_size=4),
        params=params, auto_step=False)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 512, size=n).tolist() for n in (40, 19, 33)]
    streams = [engine.submit(p, max_new_tokens=5) for p in prompts]
    for _ in range(2000):
        if all(s.done for s in streams):
            break
        engine.step()
    for p, s in zip(prompts, streams):
        seq = list(p)
        for _ in range(5):
            logits = lfm2_moe_forward(params, jnp.asarray([seq]), cfg)
            seq.append(int(jnp.argmax(logits[0, -1])))
        assert list(s) == seq[len(p):]
    forms = engine.stats()["moe_gmm_form"]
    assert {k.split("@")[0] for k in forms} == {"prefill", "decode"}
    for program, form in forms.items():
        kind, shape = program.split("@")
        rows = int(np.prod([int(n) for n in shape.split("x")]))
        assert form == moe.gmm_form(rows * cfg.top_k, 4, 128, 128), program
    assert set(forms.values()) == {"few_rows"}
    decodes = [r for r in engine.debug_dump()["steps"]
               if r["kind"] == "decode" and r["batch"]]  # a launch, no drain
    assert decodes and all(r["gmm_form"] == "few_rows" for r in decodes)
    engine.shutdown()
    assert moe.step_gmm_form(Lfm2MoeConfig.tiny(), 4) == "ragged"


# (cell, held experts, d_model, d_expert, top_k): the decode step's rows,
# then the prefill programs' rows (a chunk, or the ladder's ends)
CELL_STEPS = {
    "lfm2": (64, 2048, 1536, 4, 64, (512, 2560, 4 * 2560)),
    "laguna": (32, 2048, 512, 8, 64, (2048, 4 * 2048)),
    "pangu": (8, 7680, 2048, 8, 128, (128, 2048)),
    "smallthinker": (64, 2560, 768, 6, 48, (2048, 4 * 2048)),
    "longcat": (16, 6144, 2048, 12, 96, (128, 1024)),
}


@pytest.mark.parametrize("cell", CELL_STEPS)
def test_the_rule_gives_the_recorded_form_at_the_cells_shapes(cell):
    """docs/MICROBENCHMARKS.md, PR 49: ``few_rows`` timed faster alone at
    every decode shape and inside 2% at every prefill shape of the five
    cells, so each of their programs takes it; a step wider than any that
    was timed, and widths that are no lane tiles, stay on ``ragged``."""
    from ray_tpu.ops import moe

    held, d_model, d_expert, k, decode_rows, prefill_rows = CELL_STEPS[cell]
    for rows in (1, 16, decode_rows, *prefill_rows):
        assert moe.gmm_form(rows * k, held, d_model, d_expert) == "few_rows"
    wide = moe._FEW_PAIRS_AN_EXPERT * held + 1
    assert moe.gmm_form(wide, held, d_model, d_expert) == "ragged"
    assert moe.gmm_form(decode_rows * k, held, d_model, 96) == "ragged"
    assert moe.gmm_form(decode_rows * k, held, 192, d_expert) == "ragged"
