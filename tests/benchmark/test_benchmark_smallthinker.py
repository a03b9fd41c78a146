"""The smallthinker reference, the configuration file, the traffic file and
the new cell's readers: ``logits_at`` picks ``logits``' rows; the
configuration holds the catalog row's widths unchanged and builds the
program's config from them; the traffic is the issue's table and its traced slice outlasts a stall
at its opening; the cell runs
end to end on the CPU at its rehearsal size; the two new per-layer readers
compute what they say from plain data and return nothing (they do not raise)
where the program has no such span attribute or counter, as the parent has
not."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, span_reduce  # noqa: E402

CELL = "smallthinker-doc-chat-saturated"
CONFIG = "smallthinker-21b-a3b-8l"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def ref():
    return common.load_named("reference", "smallthinker")


def _tiny():
    from ray_tpu.models.smallthinker import SmallThinkerConfig

    return SmallThinkerConfig.tiny()


def test_logits_at_picks_the_rows_of_logits(ref):
    from ray_tpu.models.smallthinker import SmallThinkerConfig

    cfg = _tiny()
    assert ref.config_class() is SmallThinkerConfig
    assert ref.ENGINE_MODEL == "smallthinker"
    params = ref.init_fn()(jax.random.PRNGKey(1), cfg)
    assert params["layers"][1]["moe_gmm_w_in"].dtype == jnp.bfloat16
    assert params["layers"][1]["moe_route_w"].dtype == jnp.bfloat16
    assert params["ln_f_scale"].dtype == jnp.float32
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 1,
                                cfg.vocab_size)
    want = ref.logits(params, tokens, cfg)
    assert want.shape == (2, 24, cfg.vocab_size) and want.dtype == jnp.float32
    pos = jnp.array([[0, 7, 23], [3, 20, 22]])
    rows = ref.logits_at(params, tokens, pos, cfg)
    assert float(jnp.max(jnp.abs(
        rows - jnp.take_along_axis(want, pos[..., None], axis=1)))) < 1e-5


def test_reference_blocks_of_queries_do_not_change_the_result(ref,
                                                              monkeypatch):
    """Attention one block of queries at a time (what lets 16 prompts of
    9,000 tokens fit the chip) is attention: a block of 8 over 21 tokens
    gives what one block of 256 gives."""
    cfg = _tiny()
    params = ref.init_fn()(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 21), 1,
                                cfg.vocab_size)
    want = ref.logits(params, tokens, cfg)
    monkeypatch.setattr(ref, "Q_BLOCK", 8)
    got = ref.logits(params, tokens, cfg)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


def test_reference_router_weights_only_the_chosen(ref):
    """The [S, E] weights: ``top_k`` a token, a softmax over the chosen
    logits (they sum to 1 and are the softmax over all, renormalised), and
    the routers' input is the normed one (assumed)."""
    cfg = _tiny()
    lp = {"moe_route_w": jax.random.normal(jax.random.PRNGKey(0), (64, 8))}
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 64)) * 3.0
    h = jax.random.normal(jax.random.PRNGKey(2), (5, 64))
    weights = ref.route(x, h, lp, cfg)
    assert weights.shape == (5, 8)
    assert bool(jnp.all((weights > 0).sum(-1) == cfg.top_k))
    assert bool(jnp.allclose(weights.sum(-1), 1.0, atol=1e-6))
    logits = h @ lp["moe_route_w"]  # of h, not of x
    assert bool(jnp.all(jnp.argmax(weights, -1) == jnp.argmax(logits, -1)))
    soft = jax.nn.softmax(logits, axis=-1)
    chosen = weights > 0
    want = jnp.where(chosen, soft, 0.0)
    want = want / want.sum(-1, keepdims=True)
    assert bool(jnp.allclose(weights, want, atol=1e-6))
    assert ref.router_input(x, h) is h


def test_reference_mask_and_positions_by_kind(ref):
    """``sees``: a sliding layer's query at p sees p - W + 1 .. p, a full
    layer's every key up to p; ``positional``: a full layer's heads pass
    unchanged, a sliding layer's are rotated (position 0 excepted)."""
    cfg = _tiny()
    pos, t = jnp.arange(20)[:, None], jnp.arange(20)[None, :]
    full = ref.sees(pos, t, "full_attention", cfg)
    slid = ref.sees(pos, t, "sliding_attention", cfg)
    assert bool(jnp.all(full == (t <= pos)))
    assert [int(n) for n in slid.sum(-1)] == [
        min(p + 1, cfg.sliding_window) for p in range(20)]
    assert bool(slid[19, 12]) and not bool(slid[19, 11])
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 2, cfg.head_dim))
    assert ref.positional(x, "full_attention", cfg) is x
    turned = ref.positional(x, "sliding_attention", cfg)
    assert bool(jnp.allclose(turned[0], x[0]))
    assert float(jnp.abs(turned[1:] - x[1:]).max()) > 0.1
    # a rotation keeps a head's length
    assert bool(jnp.allclose(jnp.linalg.norm(turned, axis=-1),
                             jnp.linalg.norm(x, axis=-1), atol=1e-5))


def test_a_control_precision_cuts_both_operands(ref, monkeypatch):
    """``ROUND_TO``: what the reading 'the reference computed one precision
    lower' is made with. Off by default; on, the logits move."""
    cfg = _tiny()
    params = ref.init_fn()(jax.random.PRNGKey(5), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, 16), 1,
                                cfg.vocab_size)
    assert ref.ROUND_TO is None
    want = ref.logits(params, tokens, cfg)
    monkeypatch.setattr(ref, "ROUND_TO", jnp.float8_e4m3fn)
    got = ref.logits(params, tokens, cfg)
    assert 0.01 < float(jnp.max(jnp.abs(got - want))) < 10.0


def test_configuration_holds_the_rows_widths():
    """Every number of the catalog row's ``config`` is in the file under
    the same key, unchanged but for the ONE key ``reduced`` names, and the
    program's config is built from them."""
    spec = common.resolve_cell(common.load_manifest(), CELL)
    held = spec["config"]
    assert spec["cell"]["chips"] == 1
    assert spec["cell"]["traffic"] == "doc-chat-closed"
    entry = next(c for c in common.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == list(held["reduced"]) == ["num_hidden_layers"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert entry["source"] == held["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert held[key] == value, key
        assert row["config"]["num_hidden_layers"] == \
            held["reduced"]["num_hidden_layers"]["published"] == 52
    assert held["num_hidden_layers"] == 8
    cfg = common.model_config(held)
    kinds = {0: "full_attention", 1: "sliding_attention"}
    assert held["rope_layout"] == held["sliding_window_layout"]
    assert cfg.layer_types == tuple(
        kinds[n] for n in held["rope_layout"][:8]) == (
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention") * 2
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim) == (
        held["hidden_size"], held["num_attention_heads"],
        held["num_key_value_heads"], held["head_dim"]) == (2560, 28, 4, 128)
    # every expert is held and the router takes the published six
    assert (cfg.num_experts, cfg.top_k, cfg.d_expert) == (
        held["moe_num_primary_experts"],
        held["moe_num_active_primary_experts"],
        held["moe_ffn_hidden_size"]) == (64, 6, 768)
    assert cfg.norm_topk_prob is held["norm_topk_prob"] is True
    assert held["moe_primary_router_apply_softmax"] is True
    assert (cfg.vocab_size, cfg.max_seq_len, cfg.sliding_window) == (
        held["vocab_size"], held["max_position_embeddings"],
        held["sliding_window_size"]) == (151936, 16384, 4096)
    assert cfg.rope_theta == held["rope_theta"] == 1500000
    assert held["rope_scaling"] is None
    assert cfg.norm_eps == held["rms_norm_eps"] == 1e-6
    assert cfg.dtype == jnp.bfloat16
    assert set(held["assumed"]) >= {"router_input", "window", "experts",
                                    "weights", "norms", "nope"}
    for item in ("router_input", "window"):  # each with its other reading
        assert "other reading" in held["assumed"][item]
    assert "pipeline stages" in held["deployment"]
    # the byte count the file states: 3,966.9 M parameters, 7.93 GB
    ref = common.load_named("reference", "smallthinker")
    shapes = jax.eval_shape(
        lambda: ref.init_fn()(jax.random.PRNGKey(0), cfg))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert abs(n - 3966.9e6) < 1e5, n
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert abs(nbytes - 7.934e9) < 0.005e9, nbytes
    names = {m["name"] for m in spec["per_layer"]}
    assert {"window_attn_share_pct.sat", "attn_kv_hbm_pct.sat",
            "kv_window_freed_pct", "moe_gmm_hbm_pct.sat", "moe_share_pct.sat",
            "moe_load_max_over_mean", "decode_step_ms.sat",
            "hbm_peak_gb.serve", "kv_high_water_pct", "decode_batch_mean",
            "window_attn_hbm_pct.sat", "window_rows_past_pct.sat"} <= names
    assert "paged_attn_hbm_pct.sat" not in names  # its reader counts n_layer
    assert "moe_pairs_held_pct" not in names      # every expert is held
    assert [m["name"] for m in spec["end_to_end"]] == [
        "setup_s", "serve_tokens_per_s"]
    new = [m for m in common.load_manifest()["per_layer"]
           if m["name"] in ("window_attn_hbm_pct.sat",
                            "window_rows_past_pct.sat")]
    assert [m["workloads"] for m in new] == [[CELL], [CELL]]
    assert [m["source"] for m in new] == ["device_trace", "program_counter"]


def test_traffic_is_the_issues_table():
    spec = common.resolve_cell(common.load_manifest(), CELL)
    traffic = spec["traffic"]
    assert (traffic["runner"], traffic["generator"]) == (
        "serve_engine", "lognormal_chat")
    assert traffic["arrivals"] == {"mode": "closed", "clients": 96}
    assert traffic["prompt_len"] == {
        "median": 4096, "sigma": 0.8, "min": 256, "max": 14336}
    assert traffic["output_len"] == {
        "median": 512, "sigma": 0.7, "min": 64, "max": 2048}
    assert traffic["sampling"] == {"temperature": 0.0}
    engine = traffic["engine"]
    assert (engine["block_size"], engine["max_batch_size"],
            engine["prefill_chunk_tokens"]) == (16, 48, 2048)
    assert engine["length_buckets"] == [2048, 16384]
    gen = common.load_named("generators", "lognormal_chat")
    strata = traffic["strata"]
    schedule = gen.build(traffic, 1, 151936)
    prompts, outputs = schedule.prompts, schedule.outputs
    assert 256 <= min(prompts) and max(prompts) == 14336
    assert 64 <= min(outputs) and max(outputs) <= 2048
    assert abs(sum(prompts) / strata - 5231) < 12
    assert abs(sum(outputs) / strata - 638) < 3
    assert sum(p > 4096 for p in prompts) == strata // 2  # half past it
    assert abs(sum(-(-p // 2048) for p in prompts) / strata - 3.0) < 0.05
    # what a request reserves against what it would hold in one table
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig

    cfg = common.model_config(spec["config"])
    assert max(prompts) + max(outputs) <= cfg.max_seq_len == 16384
    kv = KVCacheConfig(n_layer=cfg.n_kv_layer, n_kv_head=4, head_dim=128,
                       num_blocks=engine["num_blocks"], block_size=16,
                       groups=cfg.kv_table_groups)
    pairs = [(p, o) for p in prompts for o in outputs]
    need = sum(kv.request_blocks(p + o) for p, o in pairs) / len(pairs)
    one_table = sum(4 * kv.blocks_for(p + o) for p, o in pairs) / len(pairs)
    assert abs(need - 1031) < 3 and abs(one_table - 1464) < 12
    room = kv.prefill_room(4, engine["prefill_chunk_tokens"])
    assert room == 1536
    rows = engine["max_batch_size"]
    # 48 rows and the prefill room stand ~78% of the pool: admission is
    # not the pool's to decide; 64 rows would want more than it has
    assert 0.70 < (rows * need + room) / kv.usable_blocks < 0.85
    assert 64 * need + room > kv.usable_blocks
    assert rows * one_table + room > kv.usable_blocks  # one table: 43 rows
    # a sliding group's reservation is a window and its slack, 258 blocks
    assert kv.request_blocks(16384) == 1024 + 3 * 258
    # every context fits the widest bucket and every chunk the lowest
    buckets = engine["length_buckets"]
    assert buckets[0] == engine["prefill_chunk_tokens"]
    assert max(prompts) + max(outputs) <= buckets[-1]
    assert rows in engine["batch_buckets"]
    assert set(traffic["warmup"]["decode_batches"]) <= set(
        engine["batch_buckets"])
    # a prefill step of 2 rows has a bucket of its own (PR 30's refusal)
    assert set(traffic["warmup"]["prefill_batches"]) == {
        b for b in engine["batch_buckets"] if b <= 4} == {1, 2, 4}
    for group in ("window_why", "warmup_why", "strata_why"):
        assert len(traffic[group]) > 100, group
    assert {"num_blocks", "max_batch_size", "batch_buckets",
            "length_buckets"} <= set(traffic["engine_why"])


def _slice_behind_a_stall(trace_s: float, stall_s: float):
    """What the tracer keeps of this cell when the stepping thread stalls
    as the slice opens (the driver's first traced run, seed 281734930:
    3.48 s, the device idle 2.81 s of a 3.0 s slice): the stalled prefill's
    dispatch span began before the tracer and is not in the trace, its run
    is; behind it decode steps of 22.5 ms, dispatched while the step before
    runs, and a prefill chunk of 84 ms every seventh step. Nanoseconds."""
    ms = 1e6
    dispatches, runs = [], []
    t = stall_s * 1e9  # the device starts the stalled prefill here
    runs.append(("jit_smallthinker_prefill(5)", t, t + 84 * ms))
    step = 0
    while runs[-1][2] < trace_s * 1e9:
        step += 1
        kind, dur = ("prefill_chunk", 84 * ms) if step % 7 == 0 else (
            "decode", 22.5 * ms)
        begun = runs[-1][1] + 0.2 * ms  # launched as its predecessor starts
        dispatches.append({"name": "executor.dispatch", "start": begun,
                           "end": begun + 1.4 * ms, "attrs": {"kind": kind}})
        name = "jit_smallthinker_prefill(5)" if step % 7 == 0 else \
            "jit_smallthinker_decode_step(9)"
        runs.append((name, runs[-1][2], runs[-1][2] + dur))
    return dispatches, runs


def test_the_traced_slice_outlasts_a_stall_at_its_opening():
    """``window.trace_s``: behind a stall that eats a 3 s slice the names
    left are decode steps alone, ``align`` pairs nothing and every reader
    over it leaves its metric out (the refusal); the cell's slice holds
    prefill steps behind the longest stall on record (4.4 s, PR 25), so
    one shift fits."""
    window = common.resolve_cell(common.load_manifest(), CELL)[
        "traffic"]["window"]
    assert window["trace_after_s"] + window["trace_s"] + 10 < 30
    dispatches, runs = _slice_behind_a_stall(3.0, 2.85)
    assert len(runs) < 8 and span_reduce.align(dispatches, runs) is None
    for stall in (2.85, 4.4):
        dispatches, runs = _slice_behind_a_stall(window["trace_s"], stall)
        found = span_reduce.align(dispatches, runs)
        assert found is not None and found["shift"] == -1, stall
        assert len(found["pairs"]) > 100
        assert sum("_prefill" in r[0] for _, r in found["pairs"]) >= 15


def test_reference_check_fits_what_the_engine_is_built_for():
    spec = common.resolve_cell(common.load_manifest(), CELL)
    chk, traffic = spec["config"]["reference_check"], spec["traffic"]
    lens = chk["prompt_tokens"][: chk["requests"]]
    assert len(lens) == chk["requests"] == len(set(lens)) == 16
    cfg = common.model_config(spec["config"])
    # prompts pass a chunk and the window, so that prefill in chunks,
    # queries past the window, blocks freed behind it and decode through
    # both kinds of table are all inside the comparison; every position
    assert min(lens) < 400 and max(lens) >= 9000
    assert sum(n > cfg.sliding_window for n in lens) >= 6
    assert sum(n > traffic["engine"]["prefill_chunk_tokens"]
               for n in lens) >= 8
    assert chk["every"] == 1 and chk["new_tokens"] == 64
    assert max(lens) + chk["new_tokens"] <= chk["pad_to"] <= cfg.max_seq_len
    assert chk["requests"] <= traffic["engine"]["max_batch_size"]
    assert 0 < chk["tolerance_logit"] and "fp8" in chk["tolerance_why"]
    for wrong in ("post-attention", "rotary", "silu", "4,095"):
        assert wrong in chk["tolerance_why"], wrong


@pytest.mark.timeout(600)
def test_rehearsal_runs_the_cell_end_to_end_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCHMARK_REHEARSAL="1")
    env.pop("BENCH_RUN", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 41), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=580)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert "'kv_groups': [{'window': None" in out.stdout
    assert "compiled or read from the cache INSIDE" not in out.stdout
    # the counters read on the CPU; the trace's readers find no TPU plane
    # and leave their metrics out without raising
    assert 0 < line["metrics"]["kv_window_freed_pct"]["value"] < 100
    assert 0 < line["metrics"]["window_rows_past_pct.sat"]["value"] <= 100
    assert line["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert line["metrics"]["decode_batch_mean"]["value"] > 0
    for name in ("window_attn_share_pct.sat", "attn_kv_hbm_pct.sat",
                 "window_attn_hbm_pct.sat", "moe_pairs_held_pct"):
        assert name not in line["metrics"]


def _reader(name):
    return common.load_layer_metric(name)


def test_rows_past_the_window_reader_takes_the_windows_difference():
    past = _reader("window_rows_past_pct.sat").read
    ctx = {"stats_before": {"decode_rows": 100, "decode_rows_past_window": 80},
           "stats_after": {"decode_rows": 500, "decode_rows_past_window": 300}}
    assert past(ctx) == pytest.approx(55.0)
    # the parent of this PR keeps no such counters: nothing, not zero
    assert past({"stats_before": {"decode_steps": 1},
                 "stats_after": {"decode_steps": 9}}) is None
    assert past({}) is None
    # a family without sliding layers counts no rows
    none = {"stats_before": {"decode_rows": 0, "decode_rows_past_window": 0},
            "stats_after": {"decode_rows": 0, "decode_rows_past_window": 0}}
    assert past(none) is None
    short = {"stats_before": {"decode_rows": 0, "decode_rows_past_window": 0},
             "stats_after": {"decode_rows": 40, "decode_rows_past_window": 0}}
    assert past(short) == 0.0  # no row has passed the window


def test_window_kernel_bytes_and_roofline_reader(monkeypatch):
    """Two decode runs paired with their dispatch spans: the bytes of the
    spans' ``kv_tokens_window`` in the sliding layers over the WINDOWED
    kernel's time inside the runs; the full layers' calls are not in it."""
    mod = _reader("window_attn_hbm_pct.sat")
    # 48 rows x 3,400 tokens in 6 sliding layers, 2 KB a token a layer
    assert mod.window_attn_bytes(48 * 3400, 6, 4, 128, 2) == \
        48 * 3400 * 6 * 2048
    assert mod.window_attn_bytes(10, 3, 1, 1, 1) == 60
    window = "%paged_attention_window.10 = bf16[48,4,7,128] custom-call(%x)"
    full = "%paged_attention.3 = bf16[48,4,7,128] custom-call(%x)"
    ops = [(window, 100.0 + 50 * i, 110.0 + 50 * i) for i in range(16)] \
        + [(full, 120.0, 140.0), (full, 520.0, 540.0)]
    runs = [("jit_smallthinker_decode_step", 100.0, 500.0),
            ("jit_smallthinker_decode_step", 500.0, 900.0)]
    steps = [{"attrs": {"kind": "decode", "kv_tokens": 900,
                        "kv_tokens_window": 450, "rows_past_window": 1},
              "run": run, "inside": True} for run in runs] + [
        {"attrs": {"kind": "prefill_chunk"},
         "run": ("jit_smallthinker_prefill", 900.0, 1300.0), "inside": True}]
    keys = {"layer_types": ["full_attention", "sliding_attention",
                            "sliding_attention"],
            "n_kv_head": 2, "head_dim": 4, "dtype": "bfloat16"}
    ctx = {"config": {"keys": keys}}
    monkeypatch.setattr(span_reduce, "load", lambda c: (
        {"planes": [{"ops": ops}]}, {"steps": steps}))
    monkeypatch.setattr(common, "peaks_for",
                        lambda kind: {"hbm_gb_per_s": 100.0})
    # 2 steps x 450 tokens x 2 sliding layers x (2 x 2 x 4 x 2 B) over the
    # 16 windowed calls' 10 ns each
    assert mod.read(ctx) == pytest.approx(
        100.0 * (2 * 450 * 2 * 32 / 160.0) / 100.0)
    # both kernels together read more bytes over more time: another number
    both = _reader("attn_kv_hbm_pct.sat").read(ctx)
    assert both == pytest.approx(
        100.0 * (2 * (900 * 1 + 450 * 2) * 32 / 200.0) / 100.0)
    # the parent's spans of another family carry no kv_tokens_window, a
    # trace without the windowed kernel no time: nothing, and no exception
    bare = [dict(s, attrs={"kind": s["attrs"]["kind"], "kv_tokens": 900})
            for s in steps]
    monkeypatch.setattr(span_reduce, "load", lambda c: (
        {"planes": [{"ops": ops}]}, {"steps": bare}))
    assert mod.read(ctx) is None
    monkeypatch.setattr(span_reduce, "load", lambda c: (
        {"planes": [{"ops": [(full, 120.0, 140.0)]}]}, {"steps": steps}))
    assert mod.read(ctx) is None
    monkeypatch.setattr(span_reduce, "load", lambda c: (None, None))
    assert mod.read(ctx) is None
    assert mod.read({"config": {"keys": keys}, "trace_run": None}) is None


def test_accepted_expert_readers_read_this_familys_names():
    """``moe_share_pct`` and ``moe_gmm_hbm_pct`` look for ``moe_route`` /
    ``moe_gmm`` / ``ragged-dot`` in operation names: the family's leaves
    are ``moe_route_w`` and ``moe_gmm_w_in / _out``, so they read this
    cell unedited."""
    from ray_tpu.models.smallthinker import _LEAF_AXES

    assert {"moe_route_w", "moe_gmm_w_in", "moe_gmm_w_out"} <= set(_LEAF_AXES)
    gmm = ("%ragged-dot-none.3 = f32[288,1536] custom-call(%fusion.1, "
           "%params__layers___2___moe_gmm_w_in__.1)")
    route = ("%fusion.9 = f32[48,64] fusion(%x, "
             "%params__layers___2___moe_route_w__.1)")
    reduced = {"busy_s": 2.0, "ops": {
        gmm: {"count": 8, "self_s": 0.6}, route: {"count": 8, "self_s": 0.1},
        "%fusion.1 = fusion()": {"count": 9, "self_s": 0.4}}}
    assert _reader("moe_share_pct.sat").read({"trace": reduced}) == \
        pytest.approx(35.0)
    assert _reader("moe_gmm_hbm_pct.sat").moe_gmm_bytes(63 * 8, 2560, 768, 2) \
        == pytest.approx(63 * 8 * 3 * 2560 * 768 * 2)
