"""The openPangu-Ultra-MoE reference, the configuration file, the traffic file
and the new cell's readers: ``logits_at`` picks ``logits``' rows; the
configuration holds the catalog row's numbers unchanged but for the five keys
``reduced`` names and builds the program's config from them; the bytes the
file states are re-reckoned from the keys; the traffic is the issue's table;
the cell runs end to end on the CPU at its rehearsal size; the three new
per-layer readers compute what they say from hand numbers, from a stand-in
trace and from the small trace recorded on the chip
(``record_tiny_latent_trace.py``), and return nothing (they do not raise)
where a trace holds no latent call, as the parent's holds none."""
import gzip
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, span_reduce, trace_reduce  # noqa: E402

CELL = "pangu-reason-saturated"
CONFIG = "openpangu-ultra-moe-ep32-5l"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tiny_latent_tpu.xplane.pb.gz")
REDUCED = ["first_k_dense_replace", "n_routed_experts",
           "num_hidden_layers", "num_nextn_predict_layers", "vocab_size"]


@pytest.fixture(scope="module")
def ref():
    return common.load_named("reference", "pangu_ultra_moe")


@pytest.fixture(scope="module")
def tiny():
    from ray_tpu.models.pangu_ultra_moe import (
        PanguUltraMoEConfig, pangu_ultra_moe_init,
    )

    import dataclasses

    cfg = dataclasses.replace(PanguUltraMoEConfig.tiny(64), dtype=jnp.float32,
                              experts_held=(0, 2))
    return cfg, pangu_ultra_moe_init(jax.random.PRNGKey(3), cfg)


def _reader(name):
    return common.load_layer_metric(name)


# ----------------------------------------------------------- the reference


def test_logits_at_picks_the_rows_of_logits(ref, tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 20), 1, 64)
    positions = jnp.asarray([[3, 19], [0, 7]])
    whole = ref.logits(params, tokens, cfg)
    picked = ref.logits_at(params, tokens, positions, cfg)
    assert picked.shape == (2, 2, 64)
    for b in range(2):
        for j in range(2):
            assert float(jnp.abs(
                picked[b, j] - whole[b, positions[b, j]]).max()) < 1e-5


def test_reference_blocks_of_queries_do_not_change_the_result(
        ref, tiny, monkeypatch):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 37), 1, 64)
    want = ref.logits(params, tokens, cfg)
    monkeypatch.setattr(ref, "Q_BLOCK", 8)  # five blocks, the last padded
    got = ref.logits(params, tokens, cfg)
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_a_control_precision_cuts_both_operands(ref, monkeypatch):
    x = jnp.asarray([[1.03, -2.06]], jnp.float32)
    w = jnp.asarray([[0.33], [1.07]], jnp.float32)
    exact = float(ref._mm(x, w)[0, 0])
    monkeypatch.setattr(ref, "ROUND_TO", jnp.float8_e4m3fn)
    cut = float(ref._mm(x, w)[0, 0])
    f8 = lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    assert cut == float((f8(x) @ f8(w))[0, 0]) and cut != exact


def test_the_reference_imports_nothing_of_the_program():
    """Plain ``jax.numpy``: the file names ``ray_tpu`` only where it hands
    the harness the program's config class and initialiser."""
    import ast

    path = os.path.join(ROOT, "benchmark/reference/pangu_ultra_moe.py")
    tree = ast.parse(open(path).read())
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    assert sorted(getattr(n, "module", None) or n.names[0].name
                  for n in top) == ["__future__", "jax", "jax.numpy"]
    inner = {n.module for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom) and n not in top}
    assert inner == {"ray_tpu.models.pangu_ultra_moe"}
    names = {a.name for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)
             and n not in top for a in n.names}
    assert names == {"PanguUltraMoEConfig", "pangu_ultra_moe_init"}


# ------------------------------------------------- the configuration file


def test_configuration_holds_the_rows_numbers():
    """Every number of the catalog row's ``config`` is in the file under
    the same key, unchanged but for the five keys ``reduced`` names, and
    the program's config is built from them."""
    spec = common.resolve_cell(common.load_manifest(), CELL)
    held = spec["config"]
    assert spec["cell"]["chips"] == 1
    assert spec["cell"]["traffic"] == "reason-closed"
    entry = next(c for c in common.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(held["reduced"]) == REDUCED
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "openPangu-Ultra-MoE-718B")
        assert entry["source"] == held["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert held[key] == value, key
            else:
                assert held["reduced"][key]["published"] == value, key
    assert {k: held[k] for k in REDUCED} == {
        "first_k_dense_replace": 1, "n_routed_experts": 8,
        "num_hidden_layers": 5, "num_nextn_predict_layers": 0,
        "vocab_size": 19200}
    assert {k: held["reduced"][k]["published"] for k in REDUCED} == {
        "first_k_dense_replace": 3, "n_routed_experts": 256,
        "num_hidden_layers": 61, "num_nextn_predict_layers": 1,
        "vocab_size": 153600}
    cfg = common.model_config(held)
    assert (cfg.d_model, cfg.n_head, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (
        held["hidden_size"], held["num_attention_heads"],
        held["q_lora_rank"], held["kv_lora_rank"], held["qk_nope_head_dim"],
        held["qk_rope_head_dim"], held["v_head_dim"]) == (
        7680, 128, 1536, 512, 128, 64, 128)
    # the router's width is the PUBLISHED count; this chip holds 8 of them
    assert (cfg.num_experts, cfg.top_k, cfg.d_expert, cfg.d_shared,
            cfg.d_mlp) == (256, held["num_experts_per_tok"],
                           held["moe_intermediate_size"],
                           held["n_shared_experts"]
                           * held["moe_intermediate_size"],
                           held["intermediate_size"]) == (
        256, 8, 2048, 2048, 18432)
    assert cfg.experts_held == (0, 8)
    assert cfg.n_held == held["n_routed_experts"]
    assert (cfg.n_layer, cfg.num_dense_layers, cfg.n_moe_layer) == (5, 1, 4)
    assert (cfg.vocab_size, cfg.max_seq_len) == (19200, 131072)
    assert cfg.routed_scaling_factor == held["routed_scaling_factor"] == 2.5
    assert cfg.rope_theta == held["rope_theta"] == 25600000
    assert cfg.norm_eps == held["rms_norm_eps"] == 1e-5
    assert cfg.norm_topk_prob is True and held["sandwich_norm"] is True
    assert cfg.dtype == jnp.bfloat16
    assert cfg.kv_planes == (("latent", 512, 512), ("rope", 64, 128))
    # every key of the program's config is read off a published key, or
    # its derivation is said
    assert set(held["keys"]) == set(held["keys_from"]) | set(
        held["keys_derived"])
    assert not set(held["keys_from"]) & set(held["keys_derived"])
    assert set(held["assumed"]) >= {
        "router", "sandwich_norm", "rotary", "softmax_scale",
        "route_norm_eps", "weights"}
    assert "8 pipeline stages x 32 chips" in held["deployment"]
    for key in ("deployment", "bytes", "reference_check", "rehearsal"):
        assert held[key]
    names = {m["name"] for m in spec["per_layer"]}
    assert {"latent_attn_hbm_pct.sat", "latent_prefill_mxu_pct.sat",
            "latent_absorb_share_pct.sat", "paged_attn_share_pct.sat",
            "moe_share_pct.sat", "moe_gmm_hbm_pct.sat",
            "moe_load_max_over_mean", "moe_pairs_held_pct",
            "shared_expert_share_pct.sat", "decode_step_ms.sat",
            "hbm_peak_gb.serve", "kv_high_water_pct",
            "decode_batch_mean"} <= names
    assert "paged_attn_hbm_pct.sat" not in names  # the dense families' keys
    assert [m["name"] for m in spec["end_to_end"]] == [
        "setup_s", "serve_tokens_per_s"]


def test_the_files_bytes_are_the_keys(ref):
    """The bytes the file states, re-reckoned: 196.6 M parameters of latent
    attention a layer, 621.3 M the dense layer, 623.2 M an expert layer,
    294.9 M embedding and head, 3,409.0 M = 6.82 GB; the whole model
    719.09 B by the same count; a block id 92,160 B by the widths."""
    held = common.load_json(os.path.join(
        ROOT, f"benchmark/configs/{CONFIG}.json"))
    cfg = common.model_config(held)
    shapes = jax.eval_shape(
        lambda: ref.init_fn()(jax.random.PRNGKey(0), cfg))
    count = lambda tree: sum(
        a.size for a in jax.tree.leaves(tree) if len(a.shape) >= 2)
    dense, expert = shapes["layers"][0], shapes["layers"][1]
    mla = sum(a.size for k, a in dense.items() if k.startswith("mla_w_"))
    assert abs(mla - 196.6e6) < 0.05e6, mla
    assert abs(count(dense) - 621.3e6) < 0.1e6
    assert abs(count(expert) - 623.2e6) < 0.1e6
    assert shapes["wte"].size + shapes["lm_head"].size == 2 * 19200 * 7680
    n = count(shapes)
    assert abs(n - 3409.0e6) < 0.1e6, n
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert abs(nbytes - 6.82e9) < 0.01e9, nbytes
    assert "3,409.0 M parameters = 6.82 GB" in held["bytes"]["total"]
    # the whole model by the same count: three dense layers, 58 of 256
    held_e = expert["moe_gmm_w_in"].size + expert["moe_gmm_w_out"].size
    whole = 3 * count(dense) + 58 * (count(expert) + 31 * held_e) \
        + 2 * 153600 * 7680
    assert abs(whole - 719.09e9) < 0.01e9, whole
    # the pool: a row by its widths, a block id over the 5 layers
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig

    engine = common.load_json(os.path.join(
        ROOT, "benchmark/traffic/reason-closed.json"))["engine"]
    kv = KVCacheConfig(n_layer=cfg.n_layer, n_kv_head=1, head_dim=576,
                       num_blocks=engine["num_blocks"], block_size=16,
                       dtype=cfg.dtype, planes=cfg.kv_planes)
    assert kv.row_bytes == 1152 and 16 * 5 * kv.row_bytes == 92160
    assert abs(40961 * 92160 - 3.77e9) < 0.01e9
    assert abs(kv.num_blocks * kv.block_bytes - 4.19e9) < 0.01e9
    assert "1,152 B" in held["bytes"]["kv"] and "1,280 B" in held["bytes"]["kv"]


def test_traffic_is_the_issues_table():
    spec = common.resolve_cell(common.load_manifest(), CELL)
    traffic = spec["traffic"]
    assert (traffic["runner"], traffic["generator"]) == (
        "serve_engine", "lognormal_chat")
    assert traffic["arrivals"] == {"mode": "closed", "clients": 128}
    assert traffic["prompt_len"] == {
        "median": 1536, "sigma": 1.0, "min": 128, "max": 8192}
    assert traffic["output_len"] == {
        "median": 768, "sigma": 0.7, "min": 128, "max": 4096}
    assert traffic["strata"] == 64
    assert traffic["sampling"] == {"temperature": 0.0}
    engine = traffic["engine"]
    assert (engine["block_size"], engine["num_blocks"],
            engine["max_batch_size"], engine["prefill_chunk_tokens"]) == (
        16, 40961, 128, 2048)
    gen = common.load_named("generators", "lognormal_chat")
    schedule = gen.build(traffic, 1, 19200)
    prompts, outputs = schedule.prompts, schedule.outputs
    assert (min(prompts), max(prompts)) == (137, 8192)
    assert (min(outputs), max(outputs)) == (141, 4096)
    assert abs(sum(prompts) / 64 - 2284) < 1
    assert abs(sum(outputs) / 64 - 973) < 1
    assert sum(p > 2048 for p in prompts) == 25  # 39% past one chunk
    # ids come from the slice of the vocabulary the head holds
    ids = schedule.request(0)["prompt"]
    assert 1 <= int(ids.min()) and int(ids.max()) < 19200
    # every context fits the widest bucket and every chunk the lowest
    buckets = engine["length_buckets"]
    assert buckets[0] == engine["prefill_chunk_tokens"]
    assert max(prompts) + max(outputs) <= buckets[-1]
    assert engine["max_batch_size"] in engine["batch_buckets"]
    assert set(traffic["warmup"]["decode_batches"]) == set(
        engine["batch_buckets"])
    # one prompt chunk a prefill step: a 2,048-row chunk's activations are
    # 1.4 GB beside 11 GB of weights and pool, four would be 4.7 GB
    assert engine["max_prefill_batch"] == 1
    assert traffic["warmup"]["prefill_batches"] == [1]
    for key in ("window_why", "warmup_why", "engine_why"):
        assert traffic[key] and "TBD" not in json.dumps(traffic[key]), key


def test_reference_check_fits_what_the_engine_is_built_for():
    spec = common.resolve_cell(common.load_manifest(), CELL)
    chk, traffic = spec["config"]["reference_check"], spec["traffic"]
    lens = chk["prompt_tokens"][: chk["requests"]]
    assert len(lens) == chk["requests"] == len(set(lens)) == 16
    assert (min(lens), max(lens)) == (300, 5000)
    # prompts pass a chunk, so that prefill in chunks against a resident
    # latent context is inside the comparison; every position is judged
    assert sum(n > traffic["engine"]["prefill_chunk_tokens"]
               for n in lens) >= 4
    assert chk["every"] == 1 and chk["new_tokens"] == 64
    assert max(lens) + chk["new_tokens"] <= chk["pad_to"]
    assert chk["requests"] in traffic["warmup"]["decode_batches"]
    assert 0 < chk["tolerance_logit"] and "fp8" in chk["tolerance_why"]
    assert "TBD" not in chk["tolerance_why"]


@pytest.mark.timeout(600)
def test_rehearsal_runs_the_cell_end_to_end_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCHMARK_REHEARSAL="1")
    env.pop("BENCH_RUN", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 39), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=580)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert "'kind': 'latent'" in out.stdout  # the executor's report
    assert "compiled or read from the cache INSIDE" not in out.stdout
    # the counters read on the CPU; the trace's readers find no TPU plane
    # and leave their metrics out without raising
    assert 0 < line["metrics"]["moe_pairs_held_pct"]["value"] < 100
    assert line["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert line["metrics"]["decode_batch_mean"]["value"] > 0
    for name in ("latent_attn_hbm_pct.sat", "latent_prefill_mxu_pct.sat",
                 "latent_absorb_share_pct.sat"):
        assert name not in line["metrics"]


# -------------------------------------------------------------- the readers


def test_latent_bytes_and_flops_on_hand_numbers():
    hbm = _reader("latent_attn_hbm_pct.sat")
    mxu = _reader("latent_prefill_mxu_pct.sat")
    # 128 rows x 3,000 tokens of context, 1,152 B a token a layer, 5 layers
    assert hbm.latent_attn_bytes(128 * 3000, 512, 64, 2, 5) == \
        128 * 3000 * 1152 * 5
    assert hbm.latent_attn_bytes(10, 3, 1, 2, 1) == 80
    # the absorbed products over the same rows: 2 x 128 x (576 + 512) a
    # token a layer: 241.8 flop a byte, the v5e's ridge is 240.5
    flops = hbm.latent_attn_flops(128 * 3000, 128, 512, 64, 5)
    assert flops == 128 * 3000 * 278528 * 5
    ratio = flops / hbm.latent_attn_bytes(128 * 3000, 512, 64, 2, 5)
    assert abs(ratio - 241.8) < 0.1 and abs(ratio / (197e12 / 819e9) - 1) \
        < 0.006
    # a chunk of 2,048 queries from position 0: 2,048 x 2,049 / 2 pairs,
    # 2 x 128 x (192 + 128) operations a pair a layer
    pairs = 2048 * 2049 // 2
    assert mxu.latent_prefill_flops(pairs, 128, 128, 64, 128, 5) == \
        pairs * 81920 * 5
    assert mxu.latent_prefill_flops(7, 1, 1, 1, 1, 1) == 42
    # what the absorbed kernel does for each of them: 3.4 x, so at most 29
    absorbed = 2 * 128 * (2 * 512 + 64)
    assert abs(absorbed / 81920 - 3.4) < 0.01
    assert abs(100 * 81920 / absorbed - 29.4) < 0.1
    keys = common.load_json(os.path.join(
        ROOT, f"benchmark/configs/{CONFIG}.json"))["keys"]
    assert hbm.widths_of(keys) == {
        "kv_lora_rank": 512, "qk_rope_head_dim": 64, "itemsize": 2,
        "n_layer": 5}
    assert mxu.widths_of(keys) == {
        "n_head": 128, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "n_layer": 5}


TINY_KEYS = {"n_head": 2, "kv_lora_rank": 6, "qk_rope_head_dim": 2,
             "qk_nope_head_dim": 3, "v_head_dim": 3, "n_layer": 3,
             "dtype": "bfloat16"}


def test_roofline_readers_on_a_stand_in_trace(monkeypatch):
    """Two decode runs and a prefill run paired with their dispatch spans:
    bytes of the decode spans' ``kv_tokens`` and operations of the prefill
    span's ``qk_pairs`` over the latent kernel's time inside the runs."""
    hbm = _reader("latent_attn_hbm_pct.sat")
    mxu = _reader("latent_prefill_mxu_pct.sat")
    call = "%paged_attention_latent.10 = bf16[4,1,2,8] custom-call(%x)"
    other = "%paged_attention.3 = bf16[4,1,2,8] custom-call(%x)"
    ops = [(call, 100.0 + 50 * i, 110.0 + 50 * i) for i in range(24)] \
        + [(other, 120.0, 130.0)]
    decode = [("jit_pangu_ultra_moe_decode_step", 100.0, 500.0),
              ("jit_pangu_ultra_moe_decode_step", 500.0, 900.0)]
    prefill = ("jit_pangu_ultra_moe_prefill", 900.0, 1300.0)
    steps = [{"attrs": {"kind": "decode", "kv_tokens": 450}, "run": run,
              "inside": True} for run in decode] + [
        {"attrs": {"kind": "prefill_chunk", "qk_pairs": 1000},
         "run": prefill, "inside": True}]
    ctx = {"config": {"keys": TINY_KEYS}}
    monkeypatch.setattr(span_reduce, "load", lambda c: (
        {"planes": [{"ops": ops}]}, {"steps": steps}))
    monkeypatch.setattr(common, "peaks_for", lambda kind: {
        "hbm_gb_per_s": 100.0, "bf16_tflops": 1.0})
    # 2 steps x 450 rows x (6 + 2) x 2 B x 3 layers over 16 x 10 ns
    assert hbm.read(ctx) == pytest.approx(
        100.0 * (2 * 450 * 16 * 3 / 160.0) / 100.0)
    # 1,000 pairs x 2 x 2 heads x (3 + 2 + 3) x 3 layers over 8 x 10 ns
    assert mxu.read(ctx) == pytest.approx(
        100.0 * (1000 * 32 * 3 / 80.0 / 1e3) / 1.0)
    # the parent's spans carry no qk_pairs, another family's keys no ranks,
    # a trace without the kernel no time: nothing, and no exception
    bare = [dict(s, attrs={"kind": s["attrs"]["kind"]}) for s in steps]
    monkeypatch.setattr(span_reduce, "load", lambda c: (
        {"planes": [{"ops": ops}]}, {"steps": bare}))
    assert hbm.read(ctx) is None and mxu.read(ctx) is None
    monkeypatch.setattr(span_reduce, "load", lambda c: (
        {"planes": [{"ops": [(other, 120.0, 130.0)]}]}, {"steps": steps}))
    assert hbm.read(ctx) is None and mxu.read(ctx) is None
    monkeypatch.setattr(span_reduce, "load", lambda c: (
        {"planes": [{"ops": ops}]}, {"steps": steps}))
    llama = {"config": {"keys": {"n_head": 8, "n_layer": 2,
                                 "dtype": "bfloat16"}}}
    assert hbm.read(llama) is None and mxu.read(llama) is None
    monkeypatch.setattr(span_reduce, "load", lambda c: (None, None))
    assert hbm.read(ctx) is None and mxu.read(ctx) is None


def test_absorb_share_follows_the_two_leaves():
    """Operands are printed with their types, and a moved leaf is followed
    by name AND type: another program's ``%slice-done.8`` (a block table)
    is another value."""
    mod = _reader("latent_absorb_share_pct.sat")
    absorb = ("%fusion.7 = bf16[128,128,512]{2,1,0} fusion(bf16[128,16384]"
              "{1,0} %x, bf16[512,16384]{1,0} "
              "%params__layers___2___mla_w_uk__.1), kind=kOutput")
    start = ("%slice-start.8 = ((bf16[512,16384]{1,0}), bf16[256,16384]{1,0}"
             ", s32[]) slice-start(bf16[512,16384]{1,0} "
             "%params__layers___1___mla_w_uv__.1), slice={[0:256], [0:16384]}")
    done = ("%slice-done.8 = bf16[256,16384]{1,0} slice-done(((bf16[512,16384]"
            "{1,0}), bf16[256,16384]{1,0}, s32[]) %slice-start.8)")
    unabsorb = ("%fusion.9 = bf16[128,16384]{1,0} fusion(bf16[128,65536]{1,0}"
                " %fusion.8, bf16[256,16384]{1,0} %slice-done.8)")
    out = ("%fusion.10 = bf16[128,7680]{1,0} fusion(bf16[128,16384]{1,0} "
           "%fusion.9, bf16[16384,7680]{1,0} "
           "%params__layers___1___mla_w_o__.1)")
    # another program of the trace numbers its instructions anew
    tables = ("%slice-done.8 = s32[128,768]{1,0} slice-done((s32[128,768]"
              "{1,0}, s32[128,768]{1,0}, s32[]) %slice-start.8)")
    kernel = ("%paged_attention_latent.6 = bf16[128,1,128,512]{3,2,1,0} "
              "custom-call(s32[128,768]{1,0} %slice-done.8, "
              "bf16[128,1,128,640]{3,2,1,0} %fusion.90)")
    names = [out, kernel, unabsorb, tables, done, start, absorb]
    assert mod.readers_of(names) == {absorb, start, done, unabsorb}
    assert mod._parts(start)[:2] == (
        "%slice-start.8",
        "((bf16[512,16384]{1,0}), bf16[256,16384]{1,0}, s32[])")
    assert mod._parts("a bare name") is None
    reduced = {"busy_s": 2.0, "ops": {
        absorb: {"count": 5, "self_s": 0.06},
        start: {"count": 5, "self_s": 0.01},
        done: {"count": 5, "self_s": 0.0},
        unabsorb: {"count": 5, "self_s": 0.03},
        tables: {"count": 5, "self_s": 0.2},
        kernel: {"count": 5, "self_s": 0.5},
        out: {"count": 5, "self_s": 0.7}}}
    assert mod.read({"trace": reduced}) == pytest.approx(5.0)
    # a program without the leaves (the parent, another family): nothing
    bare = {"busy_s": 2.0, "ops": {out: {"count": 5, "self_s": 0.7}}}
    assert mod.read({"trace": bare}) is None
    assert mod.read({}) is None


# ------------------------------------------- the trace recorded on the chip


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The recorded trace unpacked where ``trace_reduce.find_xplane`` looks
    for a run's trace: a reader's ``ctx`` over it."""
    if not os.path.exists(RECORDED):
        pytest.skip("no trace was recorded on the chip")
    sys.path.insert(0, os.path.dirname(RECORDED))
    from record_tiny_latent_trace import KEYS

    root = tmp_path_factory.mktemp("tiny_latent")
    folder = root / "plugins" / "profile" / "recorded"
    folder.mkdir(parents=True)
    with gzip.open(RECORDED) as src, \
            open(folder / "tiny.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    ctx = {"trace_run": {"dir": str(root)},
           "config": {"keys": dict(KEYS, dtype="bfloat16")}}
    span_reduce.load(ctx)
    ctx["trace"] = trace_reduce.reduce_file(
        trace_reduce.find_xplane(str(root)))
    return ctx


def test_recorded_trace_names_the_programs_and_the_kernel(recorded):
    raw, reduced = recorded["span_trace"]
    assert reduced is not None
    assert {"jit_pangu_ultra_moe_prefill",
            "jit_pangu_ultra_moe_decode_step"} <= set(
        recorded["trace"]["modules"])
    kinds = [s["attrs"]["kind"] for s in reduced["steps"]]
    assert {"prefill", "prefill_chunk", "decode"} <= set(kinds)
    for s in reduced["steps"]:
        if s["attrs"]["kind"] == "decode":
            assert "kv_tokens" in s["attrs"]
        else:
            assert int(s["attrs"]["qk_pairs"]) > 0
    calls = span_reduce.kernel_calls(raw["planes"][0]["ops"],
                                     "paged_attention_latent")
    # one call a layer a step program's run
    assert len(calls) == 3 * len(reduced["steps"])
    assert _reader("paged_attn_share_pct.sat").read(recorded) > 0


def test_recorded_trace_every_latent_reader_gives_a_share(recorded,
                                                          monkeypatch):
    monkeypatch.setattr(common, "peaks_for", lambda kind: {
        "hbm_gb_per_s": 819.0, "bf16_tflops": 197.0})
    got = {name: _reader(name).read(recorded) for name in (
        "latent_attn_hbm_pct.sat", "latent_prefill_mxu_pct.sat",
        "latent_absorb_share_pct.sat")}
    assert all(v is not None and 0 < v < 100 for v in got.values()), got
    # a tiny model's kernel is launch-bound: far under either roofline
    assert got["latent_attn_hbm_pct.sat"] < 5
    assert got["latent_prefill_mxu_pct.sat"] < 5


def test_a_trace_without_a_latent_call_reads_as_nothing(monkeypatch):
    """The small llama trace PR 24 recorded: spans and a paged kernel, no
    latent call: the three readers return None, as on the parent."""
    monkeypatch.setattr(common, "peaks_for", lambda kind: {
        "hbm_gb_per_s": 819.0, "bf16_tflops": 197.0})
    llama = os.path.join(os.path.dirname(RECORDED),
                         "tiny_serve_tpu.xplane.pb.gz")
    import tempfile

    with tempfile.TemporaryDirectory() as root:
        folder = os.path.join(root, "plugins", "profile", "recorded")
        os.makedirs(folder)
        with gzip.open(llama) as src, \
                open(os.path.join(folder, "tiny.xplane.pb"), "wb") as dst:
            shutil.copyfileobj(src, dst)
        ctx = {"trace_run": {"dir": root}, "config": {"keys": dict(
            TINY_KEYS, n_head=8)}}
        span_reduce.load(ctx)
        ctx["trace"] = trace_reduce.reduce_file(
            trace_reduce.find_xplane(root))
        for name in ("latent_attn_hbm_pct.sat", "latent_prefill_mxu_pct.sat",
                     "latent_absorb_share_pct.sat"):
            assert _reader(name).read(ctx) is None, name
