"""The laguna reference, the configuration file, the traffic file and the new
cell's readers: ``logits_at`` picks ``logits``' rows; the configuration holds
the catalog row's widths unchanged and builds the program's config from them;
the traffic is the issue's table; the cell runs end to end on the CPU at its
rehearsal size; the five new per-layer readers compute what they say from
plain data and return nothing (they do not raise) where the program has no
such operation, span attribute or counter, as the parent has not."""
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

from benchmark import common  # noqa: E402

CELL = "laguna-code-mixed-saturated"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def ref():
    return common.load_named("reference", "laguna")


def test_logits_at_picks_the_rows_of_logits(ref):
    from ray_tpu.models.laguna import LagunaConfig

    cfg = LagunaConfig.tiny()
    assert ref.config_class() is LagunaConfig
    assert ref.ENGINE_MODEL == "laguna"
    params = ref.init_fn()(jax.random.PRNGKey(1), cfg)
    assert params["layers"][1]["moe_gmm_w_in"].dtype == jnp.bfloat16
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
    5,000 tokens fit the chip) is attention: a block of 8 over 24 tokens
    gives what one block of 256 gives."""
    from ray_tpu.models.laguna import LagunaConfig

    cfg = LagunaConfig.tiny()
    params = ref.init_fn()(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 21), 1,
                                cfg.vocab_size)
    want = ref.logits(params, tokens, cfg)
    monkeypatch.setattr(ref, "Q_BLOCK", 8)
    got = ref.logits(params, tokens, cfg)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


def test_reference_router_weights_only_the_chosen(ref):
    from ray_tpu.models.laguna import LagunaConfig

    cfg = LagunaConfig.tiny()
    lp = {"moe_route_w": jax.random.normal(jax.random.PRNGKey(0), (64, 8))}
    h = jax.random.normal(jax.random.PRNGKey(1), (5, 64))
    weights = ref.route(h, lp, cfg)
    assert weights.shape == (5, 8)
    assert bool(jnp.all((weights > 0).sum(-1) == cfg.top_k))
    # the chosen scores divided by their sum, times the scaling factor
    assert bool(jnp.allclose(weights.sum(-1), cfg.routed_scaling_factor,
                             atol=1e-4))
    s = jax.nn.sigmoid(h @ lp["moe_route_w"])
    assert bool(jnp.all(jnp.argmax(weights, -1) == jnp.argmax(s, -1)))


def test_a_control_precision_cuts_both_operands(ref, monkeypatch):
    """``ROUND_TO``: what the reading 'the reference computed one precision
    lower' is made with. Off by default; on, the logits move."""
    from ray_tpu.models.laguna import LagunaConfig

    cfg = LagunaConfig.tiny()
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
    the same key, unchanged but for the two keys ``reduced`` names, and
    the program's config is built from them."""
    spec = common.resolve_cell(common.load_manifest(), CELL)
    held = spec["config"]
    assert spec["cell"]["chips"] == 1
    assert spec["cell"]["traffic"] == "code-mixed-closed"
    entry = next(c for c in common.load_manifest()["configs"]
                 if c["name"] == "laguna-xs.2-ep8-8l")
    assert sorted(entry["reduced"]) == sorted(held["reduced"]) == [
        "num_experts", "num_hidden_layers"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Laguna-XS.2")
        assert entry["source"] == held["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert held[key] == value, key
        assert row["config"]["num_experts"] == \
            held["reduced"]["num_experts"]["published"] == 256
        assert row["config"]["num_hidden_layers"] == \
            held["reduced"]["num_hidden_layers"]["published"] == 40
    assert held["num_hidden_layers"] == 8 and held["num_experts"] == 32
    cfg = common.model_config(held)
    assert cfg.layer_types == tuple(held["layer_types"][:8]) == (
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention") * 2
    assert [cfg.n_head_of(k) for k in cfg.layer_types] == \
        held["num_attention_heads_per_layer"][:8]
    assert held["mlp_layer_types"][:8] == ["dense"] + ["sparse"] * 7
    assert (cfg.d_model, cfg.n_kv_head, cfg.head_dim, cfg.d_mlp) == (
        held["hidden_size"], held["num_key_value_heads"], held["head_dim"],
        held["intermediate_size"]) == (2048, 8, 128, 8192)
    # the router's width is the PUBLISHED count; this chip holds 32 of them
    assert (cfg.num_experts, cfg.top_k, cfg.d_expert, cfg.d_shared) == (
        256, held["num_experts_per_tok"], held["moe_intermediate_size"],
        held["shared_expert_intermediate_size"]) == (256, 8, 512, 512)
    assert cfg.experts_held == (0, 32) and cfg.n_held == held["num_experts"]
    assert cfg.routed_scaling_factor == held["moe_routed_scaling_factor"]
    assert (cfg.vocab_size, cfg.max_seq_len, cfg.sliding_window) == (
        held["vocab_size"], held["max_position_embeddings"],
        held["sliding_window"]) == (100352, 262144, 512)
    rope = held["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    assert (cfg.rope_theta_full, cfg.yarn_factor, cfg.yarn_original_max,
            cfg.yarn_beta_fast, cfg.yarn_beta_slow, cfg.yarn_attention_factor,
            cfg.partial_rotary_full) == (
        full["rope_theta"], full["factor"],
        full["original_max_position_embeddings"], full["beta_fast"],
        full["beta_slow"], full["attention_factor"],
        full["partial_rotary_factor"])
    assert cfg.rope_theta_sliding == sliding["rope_theta"] == 10000
    assert cfg.norm_eps == held["rms_norm_eps"] == 1e-6
    assert cfg.dtype == jnp.bfloat16
    assert set(held["assumed"]) >= {"gating", "router", "norms", "weights"}
    assert "8 v5e chips" in held["deployment"]
    # the byte count the file states: 1,477.9 M parameters, 2.96 GB
    ref = common.load_named("reference", "laguna")
    shapes = jax.eval_shape(
        lambda: ref.init_fn()(jax.random.PRNGKey(0), cfg))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert abs(n - 1477.9e6) < 1e5, n
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert abs(nbytes - 2.956e9) < 0.005e9, nbytes
    names = {m["name"] for m in spec["per_layer"]}
    assert {"window_attn_share_pct.sat", "attn_kv_hbm_pct.sat",
            "kv_window_freed_pct", "moe_pairs_held_pct",
            "shared_expert_share_pct.sat", "moe_gmm_hbm_pct.sat",
            "decode_step_ms.sat", "hbm_peak_gb.serve",
            "kv_high_water_pct"} <= names
    assert "paged_attn_hbm_pct.sat" not in names  # its reader counts n_layer
    assert [m["name"] for m in spec["end_to_end"]] == [
        "setup_s", "serve_tokens_per_s"]


def test_traffic_is_the_issues_table():
    spec = common.resolve_cell(common.load_manifest(), CELL)
    traffic = spec["traffic"]
    assert (traffic["runner"], traffic["generator"]) == (
        "serve_engine", "lognormal_chat")
    assert traffic["arrivals"] == {"mode": "closed", "clients": 128}
    assert traffic["prompt_len"] == {
        "median": 2048, "sigma": 1.1, "min": 128, "max": 16384}
    assert traffic["output_len"] == {
        "median": 384, "sigma": 0.7, "min": 32, "max": 2048}
    assert traffic["strata"] == 64
    assert traffic["sampling"] == {"temperature": 0.0}
    engine = traffic["engine"]
    assert (engine["block_size"], engine["num_blocks"],
            engine["max_batch_size"], engine["prefill_chunk_tokens"],
            engine["max_waiting"]) == (16, 32769, 64, 2048, 256)
    gen = common.load_named("generators", "lognormal_chat")
    schedule = gen.build(traffic, 1, 100352)
    prompts, outputs = schedule.prompts, schedule.outputs
    assert 128 <= min(prompts) < 160 and max(prompts) == 16384
    assert 32 <= min(outputs) and max(outputs) <= 2048
    assert abs(sum(prompts) / 64 - 3424) < 2
    assert abs(sum(outputs) / 64 - 487) < 2
    # what a request reserves against what it would hold in one table
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig

    cfg = common.model_config(spec["config"])
    kv = KVCacheConfig(n_layer=cfg.n_kv_layer, n_kv_head=8, head_dim=128,
                       num_blocks=engine["num_blocks"], block_size=16,
                       groups=cfg.kv_table_groups)
    need = [kv.request_blocks(p + o) for p, o in zip(prompts, outputs)]
    one_table = [4 * kv.blocks_for(p + o) for p, o in zip(prompts, outputs)]
    assert abs(sum(need) / 64 - 347) < 4
    assert abs(sum(one_table) / 64 - 979) < 4
    room = kv.prefill_room(4, engine["prefill_chunk_tokens"])
    assert room == 1536 and sum(need) + room < kv.usable_blocks
    assert sum(one_table) > 1.9 * kv.usable_blocks
    # every context fits the widest bucket and every chunk the lowest
    buckets = engine["length_buckets"]
    assert buckets[0] == engine["prefill_chunk_tokens"]
    assert max(prompts) + max(outputs) <= buckets[-1]
    assert engine["max_batch_size"] in engine["batch_buckets"]
    assert set(traffic["warmup"]["decode_batches"]) == set(
        engine["batch_buckets"])
    # a prefill step of 2 rows (a long prompt's next chunk with a newcomer)
    # has a bucket of its own: padded to 4 it costs twice as much, and how
    # many a window holds is the seed's doing (the driver's refusal, PR 30)
    assert set(traffic["warmup"]["prefill_batches"]) == {
        b for b in engine["batch_buckets"] if b <= 4} == {1, 2, 4}


def test_reference_check_fits_what_the_engine_is_built_for():
    spec = common.resolve_cell(common.load_manifest(), CELL)
    chk, traffic = spec["config"]["reference_check"], spec["traffic"]
    lens = chk["prompt_tokens"][: chk["requests"]]
    assert len(lens) == chk["requests"] == len(set(lens)) == 16
    cfg = common.model_config(spec["config"])
    # prompts pass the window and a chunk, so that prefill in chunks, blocks
    # freed behind the window and decode through both kinds of table are
    # all inside the comparison; every position is judged
    assert min(lens) < cfg.sliding_window < max(lens)
    assert sum(n > traffic["engine"]["prefill_chunk_tokens"]
               for n in lens) >= 4
    assert chk["every"] == 1 and chk["new_tokens"] == 64
    assert max(lens) + chk["new_tokens"] <= chk["pad_to"]
    assert chk["requests"] in traffic["warmup"]["decode_batches"]
    assert 0 < chk["tolerance_logit"] and "fp8" in chk["tolerance_why"]


@pytest.mark.timeout(600)
def test_rehearsal_runs_the_cell_end_to_end_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCHMARK_REHEARSAL="1")
    env.pop("BENCH_RUN", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 30), "--seconds", "2", "--trace", "1"],
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
    assert 0 < line["metrics"]["moe_pairs_held_pct"]["value"] < 100
    assert line["metrics"]["decode_batch_mean"]["value"] > 0
    for name in ("window_attn_share_pct.sat", "attn_kv_hbm_pct.sat",
                 "shared_expert_share_pct.sat"):
        assert name not in line["metrics"]


def _reader(name):
    return common.load_layer_metric(name)


def test_share_readers_sum_the_named_operations():
    window = ("%paged_attention_window.3 = bf16[64,8,8,128] custom-call("
              '%fusion.1), custom_call_target="tpu_custom_call"')
    full = "%paged_attention.2 = bf16[64,8,6,128] custom-call(%fusion.2)"
    shared = ("%fusion.7 = bf16[64,1024] fusion(%x, "
              "%params__layers___2___moe_shared_w_in__.1)")
    reduced = {"busy_s": 2.0, "ops": {
        window: {"count": 6, "self_s": 0.3}, full: {"count": 2, "self_s": 0.5},
        shared: {"count": 7, "self_s": 0.1},
        "%fusion.1 = fusion()": {"count": 9, "self_s": 0.4}}}
    assert _reader("window_attn_share_pct.sat").read({"trace": reduced}) == \
        pytest.approx(15.0)
    assert _reader("shared_expert_share_pct.sat").read(
        {"trace": reduced}) == pytest.approx(5.0)
    # both kernels' names hold "paged_attention": the accepted share of
    # the paged kernel covers the two
    assert _reader("paged_attn_share_pct.sat").read({"trace": reduced}) == \
        pytest.approx(40.0)
    # a program without such operations (the parent): nothing, not zero
    bare = {"busy_s": 2.0, "ops": {full: {"count": 2, "self_s": 0.5}}}
    for name in ("window_attn_share_pct.sat", "shared_expert_share_pct.sat"):
        assert _reader(name).read({"trace": bare}) is None
        assert _reader(name).read({}) is None


def test_shared_expert_reader_follows_a_leaf_into_fast_memory():
    """The compiler brings a leaf into fast memory in slices and the
    product reads the joined copy: the reader follows the names (leaf ->
    slice-start -> slice-done -> ConcatBitcast -> the fusion that reads
    it), counts the moves and the reader, and nothing downstream."""
    start = ("%slice-start.108 = ((bf16[2048,1024]), bf16[512,1024], s32[])"
             " slice-start(%params__layers___1___moe_shared_w_in__.1), "
             "slice={[512:1024], [0:1024]}")
    done = "%slice-done.108 = bf16[512,1024] slice-done(%slice-start.108)"
    joined = ("%custom-call.150 = bf16[2048,1024] custom-call(%slice-done.108"
              ', %slice-done.109), custom_call_target="ConcatBitcast"')
    product = "%fusion.77 = bf16[64,1024] fusion(%fusion.70, %custom-call.150)"
    after = "%fusion.78 = bf16[64,2048] fusion(%fusion.77, %params__wo__.1)"
    other = "%slice-start.1 = (bf16[8,8]) slice-start(%params__layers___1___wq__.1)"
    mod = _reader("shared_expert_share_pct.sat")
    names = [after, product, joined, done, start, other]  # any order
    assert mod.readers_of(names) == {start, done, joined, product}
    reduced = {"busy_s": 4.0, "ops": {
        start: {"count": 7, "self_s": 0.01}, done: {"count": 7, "self_s": 0.0},
        joined: {"count": 7, "self_s": 0.0},
        product: {"count": 7, "self_s": 0.19},
        after: {"count": 7, "self_s": 1.0}, other: {"count": 7, "self_s": 1.0}}}
    assert mod.read({"trace": reduced}) == pytest.approx(5.0)
    # the joined copy missing from the trace: what can be seen is counted
    del reduced["ops"][joined]
    assert mod.read({"trace": reduced}) == pytest.approx(0.25)


def test_counter_readers_take_the_windows_difference():
    freed = _reader("kv_window_freed_pct").read
    ctx = {"stats_before": {"kv_window_blocks_taken": 100,
                            "kv_window_blocks_freed": 50},
           "stats_after": {"kv_window_blocks_taken": 300,
                           "kv_window_blocks_freed": 220}}
    assert freed(ctx) == pytest.approx(85.0)
    assert freed({"stats_before": {}, "stats_after": {}}) is None
    assert freed({}) is None
    none_taken = {"stats_before": {"kv_window_blocks_taken": 0,
                                   "kv_window_blocks_freed": 0},
                  "stats_after": {"kv_window_blocks_taken": 0,
                                  "kv_window_blocks_freed": 0}}
    assert freed(none_taken) is None  # one table: no group took a block
    kept = {"stats_before": {"kv_window_blocks_taken": 0,
                             "kv_window_blocks_freed": 0},
            "stats_after": {"kv_window_blocks_taken": 40,
                            "kv_window_blocks_freed": 0}}
    assert freed(kept) == 0.0  # nothing given back
    held = _reader("moe_pairs_held_pct").read
    ctx = {"stats_before": {"moe_pairs_held_decode": 10,
                            "moe_pairs_decode": 100},
           "stats_after": {"moe_pairs_held_decode": 60,
                           "moe_pairs_decode": 500}}
    assert held(ctx) == pytest.approx(12.5)
    # the parent of this PR counts routed pairs, not held ones
    assert held({"stats_before": {"moe_pairs_decode": 1},
                 "stats_after": {"moe_pairs_decode": 9}}) is None
    assert held({}) is None


def test_attention_bytes_and_roofline_reader():
    mod = _reader("attn_kv_hbm_pct.sat")
    # 64 rows x 3,712 tokens in 2 full layers, 64 x 512 in 6 sliding ones,
    # 4 KB a token a layer
    assert mod.attn_kv_bytes(64 * 3712, 64 * 512, 2, 6, 8, 128, 2) == \
        (64 * 3712 * 2 + 64 * 512 * 6) * 4096
    assert mod.attn_kv_bytes(10, 4, 1, 0, 1, 1, 1) == 20
    # no trace, or no spans: nothing, and no exception
    assert mod.read({}) is None
    assert mod.read({"trace_run": None}) is None
