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


def test_the_kernel_alone_leaves_no_group_out(jax_cpu):
    """``moe_gmm_few_rows`` against ``ragged_dot`` on sorted rows: groups
    that start and end inside a row tile, one that spans three, empty ones
    between them, and rows behind the last group that no item visits."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    sizes = jnp.asarray([3, 0, 0, 37, 1, 0, 9, 0], jnp.int32)
    m = 96
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
