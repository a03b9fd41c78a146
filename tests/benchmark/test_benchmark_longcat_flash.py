"""The LongCat-Flash reference, the configuration file, the traffic file and
the new cell's readers: ``logits_at`` picks ``logits``' rows; the
configuration holds the catalog row's numbers unchanged but for the three
keys ``reduced`` names and builds the program's config from them; the bytes
the file states are re-reckoned from the keys; the traffic is the issue's
table; the cell resolves and runs end to end on the CPU at its rehearsal
size; the four new per-layer readers compute what they say from hand
numbers, from recorded counter sets, from a stand-in trace and from the
small latent trace recorded on the chip, and return nothing (they do not
raise) where the program counts or names no such thing, as the parent's
does not; and the lint of EVERY configuration file's ``reduced`` entries
(each names a ``published`` and a ``here`` that differ, and a ``why``)."""
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

CELL = "longcat-think-saturated"
CONFIG = "longcat-flash-omni-ep32-4l"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tiny_latent_tpu.xplane.pb.gz")
REDUCED = ["n_routed_experts", "num_layers", "vocab_size"]
NEW_READERS = ("latent_attn2_hbm_pct.sat", "moe_zero_pick_pct",
               "moe_step_pairs_max_over_mean", "dense_ffn_share_pct.sat")


@pytest.fixture(scope="module")
def ref():
    return common.load_named("reference", "longcat_flash")


@pytest.fixture(scope="module")
def tiny():
    import dataclasses

    from ray_tpu.models.longcat_flash import (
        LongCatFlashConfig, longcat_flash_init,
    )

    cfg = dataclasses.replace(LongCatFlashConfig.tiny(64), dtype=jnp.float32,
                              experts_held=(0, 2))
    return cfg, longcat_flash_init(jax.random.PRNGKey(3), cfg)


def _reader(name):
    return common.load_layer_metric(name)


# ----------------------------------------------------------- the reference


def test_logits_at_picks_the_rows_of_logits(ref, tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 21), 1,
                                cfg.vocab_size)
    positions = jnp.asarray([[0, 7, 20], [3, 3, 19]], jnp.int32)
    full = ref.logits(params, tokens, cfg)
    some = ref.logits_at(params, tokens, positions, cfg)
    assert some.shape == (2, 3, 64)
    for b in range(2):
        for j in range(3):
            assert jnp.allclose(some[b, j], full[b, positions[b, j]],
                                atol=1e-5)


def test_reference_blocks_of_queries_do_not_change_the_result(
        ref, tiny, monkeypatch):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 23), 1,
                                cfg.vocab_size)
    whole = ref.logits(params, tokens, cfg)
    monkeypatch.setattr(ref, "Q_BLOCK", 8)  # three blocks, the last ragged
    blocked = ref.logits(params, tokens, cfg)
    assert jnp.allclose(whole, blocked, atol=1e-5)


def test_a_control_precision_cuts_both_operands(ref, monkeypatch):
    x = jnp.asarray([[1.0009765625, 3.0]])  # 1 + 2^-10: bf16 drops it
    w = jnp.asarray([[1.0], [1.0009765625]])
    assert float(ref._mm(x, w)[0, 0]) == pytest.approx(4.00390625)
    monkeypatch.setattr(ref, "ROUND_TO", jnp.bfloat16)
    assert float(ref._mm(x, w)[0, 0]) == 4.0


@pytest.mark.parametrize("control", [
    "SHORTCUT_BEHIND_FIRST_HALF", "VALUES_NOT_RESCALED",
    "ZERO_EXPERTS_DROPPED"])
def test_each_wrong_model_control_is_another_model(ref, tiny, monkeypatch,
                                                   control):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, 19), 1,
                                cfg.vocab_size)
    assert getattr(ref, control) is False
    right = ref.logits(params, tokens, cfg)
    monkeypatch.setattr(ref, control, True)
    wrong = ref.logits(params, tokens, cfg)
    assert float(jnp.abs(right - wrong).max()) > 1e-2


def test_the_reference_imports_nothing_of_the_program():
    """Plain ``jax.numpy``: the file names ``ray_tpu`` only where it hands
    the harness the program's config class and initialiser."""
    import ast

    path = os.path.join(ROOT, "benchmark/reference/longcat_flash.py")
    tree = ast.parse(open(path).read())
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    assert sorted(getattr(n, "module", None) or n.names[0].name
                  for n in top) == ["__future__", "jax", "jax.numpy"]
    inner = {n.module for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom) and n not in top}
    assert inner == {"ray_tpu.models.longcat_flash"}
    names = {a.name for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)
             and n not in top for a in n.names}
    assert names == {"LongCatFlashConfig", "longcat_flash_init"}
    doc = ast.get_docstring(tree)
    for line in ("a1 = x  + MLA_1(N(x))", "s  = MoE(h1)",
                 "out = a2 + SwiGLU_2(h2) + s"):
        assert line in doc  # the equations, as the model file states them
    from ray_tpu.models import longcat_flash

    for line in ("a1 = x  + MLA_1(N(x))", "out = a2 + SwiGLU_2(h2) + s",
                 "are not served"):
        assert line in longcat_flash.__doc__


# ------------------------------------------------- the configuration file


def test_configuration_holds_the_rows_numbers():
    """Every number of the catalog row's ``config`` is in the file under
    the same key, unchanged but for the three keys ``reduced`` names, and
    the program's config is built from them."""
    spec = common.resolve_cell(common.load_manifest(), CELL)
    held = spec["config"]
    assert spec["cell"]["chips"] == 1
    assert spec["cell"]["traffic"] == "think-closed"
    entry = next(c for c in common.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(held["reduced"]) == REDUCED
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LongCat-Flash-Omni")
        assert entry["source"] == held["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert held[key] == value, key
            else:
                assert held["reduced"][key]["published"] == value, key
    assert {k: held[k] for k in REDUCED} == {
        "n_routed_experts": 16, "num_layers": 4, "vocab_size": 16384}
    assert {k: held["reduced"][k]["published"] for k in REDUCED} == {
        "n_routed_experts": 512, "num_layers": 28, "vocab_size": 131072}
    cfg = common.model_config(held)
    assert (cfg.d_model, cfg.n_head, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (
        held["hidden_size"], held["num_attention_heads"],
        held["q_lora_rank"], held["kv_lora_rank"], held["qk_nope_head_dim"],
        held["qk_rope_head_dim"], held["v_head_dim"]) == (
        6144, 64, 1536, 512, 128, 64, 128)
    # the router's width is the PUBLISHED count of real experts + the
    # zero-compute ones; this chip holds 16 of the real ones
    assert (cfg.num_experts, cfg.num_zero_experts, cfg.top_k, cfg.d_expert,
            cfg.d_mlp) == (512, held["zero_expert_num"], held["moe_topk"],
                           held["expert_ffn_hidden_size"],
                           held["ffn_hidden_size"]) == (
        512, 256, 12, 2048, 12288)
    assert cfg.experts_held == (0, 16)
    assert cfg.n_held == held["n_routed_experts"]
    assert (cfg.n_layer, cfg.n_kv_layer) == (4, 8)
    assert held["latent_sublayers"] == cfg.n_kv_layer
    assert (cfg.vocab_size, cfg.max_seq_len) == (16384, 131072)
    assert cfg.routed_scaling_factor == held["routed_scaling_factor"] == 6
    assert cfg.rope_theta == held["rope_theta"] == 10000000
    assert cfg.norm_eps == held["rms_norm_eps"] == 1e-5
    assert cfg.norm_topk_prob is False
    assert cfg.q_scale == 2.0 and abs(cfg.c_scale - 12 ** 0.5) < 1e-12
    assert held["zero_expert_type"] == "identity"
    assert cfg.dtype == jnp.bfloat16
    assert cfg.kv_planes == (("latent", 512, 512), ("rope", 64, 128))
    assert set(held["keys"]) == set(held["keys_from"]) | set(
        held["keys_derived"])
    assert not set(held["keys_from"]) & set(held["keys_derived"])
    assert set(held["assumed"]) >= {
        "hidden_act", "mla_scales", "rotary", "router", "selection_bias",
        "zero_experts", "shortcut", "weights"}
    for what in ("audio and vision encoders", "codec decoder",
                 "not served"):
        assert what in held["not_served"]
    assert "7 pipeline stages of 4 layers x 32 chips" in held["deployment"]
    assert "224 chips" in held["deployment"]
    for key in ("deployment", "bytes", "reference_check", "rehearsal"):
        assert held[key]
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW_READERS) | {
        "latent_prefill_mxu_pct.sat", "latent_absorb_share_pct.sat",
        "paged_attn_share_pct.sat", "moe_share_pct.sat",
        "moe_gmm_hbm_pct.sat", "moe_load_max_over_mean",
        "moe_pairs_held_pct", "decode_step_ms.sat", "hbm_peak_gb.serve",
        "kv_high_water_pct", "prefill_fill_pct.sat",
        "prefill_device_share_pct.sat", "decode_batch_mean"} <= names
    # the accepted latent roofline multiplies by ``keys.n_layer`` and
    # would read half; no shared expert here; the dense families' keys
    assert not names & {"latent_attn_hbm_pct.sat",
                        "shared_expert_share_pct.sat",
                        "paged_attn_hbm_pct.sat"}
    assert [m["name"] for m in spec["end_to_end"]] == [
        "setup_s", "serve_tokens_per_s"]
    manifest = common.load_manifest()
    for m in manifest["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == CONFIG
    assert [m["name"] for m in manifest["per_layer"][-4:]] == list(
        NEW_READERS)


def test_the_files_bytes_are_the_keys(ref):
    """The bytes the file states, re-reckoned: 90.57 M parameters a latent
    sub-layer, 638.8 M a layer outside its experts, 604.0 M the 16 held,
    201.3 M embedding and head, 5,172.6 M = 10.35 GB; the whole model
    560.7 B by the same count; a block id 163,840 B as stored."""
    held = common.load_json(os.path.join(
        ROOT, f"benchmark/configs/{CONFIG}.json"))
    cfg = common.model_config(held)
    shapes = jax.eval_shape(
        lambda: ref.init_fn()(jax.random.PRNGKey(0), cfg))
    count = lambda tree: sum(
        a.size for a in jax.tree.leaves(tree) if len(a.shape) >= 2)
    lp = shapes["layers"][2]
    mla = sum(a.size for k, a in lp["sub"][1].items()
              if k.startswith("mla_w_"))
    assert abs(mla - 90.57e6) < 0.01e6, mla
    experts = lp["moe_gmm_w_in"].size + lp["moe_gmm_w_out"].size
    assert abs(experts - 604.0e6) < 0.05e6
    assert abs(count(lp) - experts - 638.8e6) < 0.1e6
    assert abs(count(lp) - 1242.8e6) < 0.1e6
    assert shapes["wte"].size + shapes["lm_head"].size == 2 * 16384 * 6144
    n = count(shapes)
    assert abs(n - 5172.6e6) < 0.1e6, n
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert abs(nbytes - 10.35e9) < 0.01e9, nbytes
    assert "5,172.6 M parameters = 10.35 GB" in held["bytes"]["total"]
    whole = 28 * (count(lp) + 31 * experts) + 2 * 131072 * 6144
    assert abs(whole - 560.7e9) < 0.1e9, whole
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig

    engine = common.load_json(os.path.join(
        ROOT, "benchmark/traffic/think-closed.json"))["engine"]
    kv = KVCacheConfig(n_layer=cfg.n_kv_layer, n_kv_head=1, head_dim=576,
                       num_blocks=engine["num_blocks"], block_size=16,
                       dtype=cfg.dtype, planes=cfg.kv_planes)
    assert kv.row_bytes == 1152 and kv.block_bytes == 163840
    assert abs(kv.num_blocks * kv.block_bytes - 2.68e9) < 0.01e9
    assert "1,152 B" in held["bytes"]["kv"]
    assert "10,240 B" in held["bytes"]["kv"]


def test_traffic_is_the_issues_table():
    spec = common.resolve_cell(common.load_manifest(), CELL)
    traffic = spec["traffic"]
    assert (traffic["runner"], traffic["generator"]) == (
        "serve_engine", "lognormal_chat")
    assert traffic["arrivals"] == {"mode": "closed", "clients": 96}
    assert traffic["prompt_len"] == {
        "median": 384, "sigma": 0.8, "min": 64, "max": 2048}
    assert traffic["output_len"] == {
        "median": 1024, "sigma": 0.6, "min": 256, "max": 4096}
    assert traffic["sampling"] == {"temperature": 0.0}
    assert traffic["window"]["trace_s"] == 8.0  # as cell 9's (PERF.md 7 x)
    engine = traffic["engine"]
    assert (engine["block_size"], engine["num_blocks"],
            engine["max_batch_size"], engine["prefill_chunk_tokens"],
            engine["max_prefill_batch"]) == (16, 16385, 96, 1024, 1)
    gen = common.load_named("generators", "lognormal_chat")
    schedule = gen.build(traffic, 1, 16384)
    prompts, outputs = schedule.prompts, schedule.outputs
    n = traffic["strata"]
    assert n == len(prompts) == traffic["arrivals"]["clients"]
    assert (min(prompts), max(prompts)) == (64, 2048)
    assert (min(outputs), max(outputs)) == (256, 4096)
    assert abs(sum(prompts) / n - 514) < 1
    assert abs(sum(outputs) / n - 1215) < 1  # outputs are 2.4 x prompts
    assert sum(p > 1024 for p in prompts) == 11  # 11% past one chunk
    ids = schedule.request(0)["prompt"]
    assert 1 <= int(ids.min()) and int(ids.max()) < 16384
    buckets = engine["length_buckets"]
    assert buckets[0] == engine["prefill_chunk_tokens"]
    assert max(prompts) + max(outputs) <= buckets[-1]
    assert engine["max_batch_size"] in engine["batch_buckets"]
    assert set(traffic["warmup"]["decode_batches"]) == set(
        engine["batch_buckets"])
    assert traffic["warmup"]["prefill_batches"] == [1]
    for key in ("window_why", "warmup_why", "engine_why", "strata_why"):
        assert traffic[key] and "TBD" not in json.dumps(traffic[key]), key


def test_reference_check_fits_what_the_engine_is_built_for():
    spec = common.resolve_cell(common.load_manifest(), CELL)
    chk, traffic = spec["config"]["reference_check"], spec["traffic"]
    lens = chk["prompt_tokens"][: chk["requests"]]
    assert len(lens) == chk["requests"] == len(set(lens)) == 16
    assert (min(lens), max(lens)) == (64, 2040)
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
         str(2**31 + 45), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=580)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert "'kind': 'latent'" in out.stdout  # the executor's report
    assert "'kv_layers': 4" in out.stdout    # 2 x the rehearsal's 2 layers
    assert "compiled or read from the cache INSIDE" not in out.stdout
    # the counters read on the CPU; the trace's readers find no TPU plane
    # and leave their metrics out without raising
    metrics = line["metrics"]
    assert 10 < metrics["moe_zero_pick_pct"]["value"] < 60  # 4 of 12
    assert metrics["moe_step_pairs_max_over_mean"]["value"] >= 1.0
    assert 0 < metrics["moe_pairs_held_pct"]["value"] < 100
    assert metrics["moe_load_max_over_mean"]["value"] >= 1.0
    assert metrics["decode_batch_mean"]["value"] > 0
    for name in ("latent_attn2_hbm_pct.sat", "dense_ffn_share_pct.sat",
                 "latent_absorb_share_pct.sat"):
        assert name not in metrics


# -------------------------------------------------------------- the readers


def test_zero_pick_share_on_recorded_counters():
    """Two readings of ``engine.stats()`` as a tiny engine gave them (96
    decode picks of which 31 zero, behind a warm-up of 30 of which 9)."""
    mod = _reader("moe_zero_pick_pct")
    assert mod.zero_pick_pct(256, 768) == pytest.approx(100 / 3)
    ctx = {"stats_before": {"moe_zero_picks_decode": 9,
                            "moe_pairs_decode": 30},
           "stats_after": {"moe_zero_picks_decode": 40,
                           "moe_pairs_decode": 126}}
    assert mod.read(ctx) == pytest.approx(100.0 * 31 / 96)
    # another family's counters (the parent's programs count no zero pick)
    assert mod.read({"stats_before": {"moe_pairs_decode": 30},
                     "stats_after": {"moe_pairs_decode": 126}}) is None
    assert mod.read({"stats_before": ctx["stats_before"],
                     "stats_after": ctx["stats_before"]}) is None
    assert mod.read({}) is None


def test_step_pairs_swing_on_a_recorded_histogram():
    """``moe_step_pairs_decode[n]``: decode steps that computed n held real
    pairs. Before the window 2 steps of 3 pairs; in it 4 steps of 1, 3 of
    2 and 1 of 5: the largest 5 over the mean 15 / 8."""
    mod = _reader("moe_step_pairs_max_over_mean")
    before = [0, 0, 0, 2, 0, 0, 0, 0]
    after = [0, 4, 3, 2, 0, 1, 0, 0]
    ctx = {"stats_before": {"moe_step_pairs_decode": before},
           "stats_after": {"moe_step_pairs_decode": after}}
    assert mod.read(ctx) == pytest.approx(5 * 8 / 15)
    assert mod.max_over_mean([0, 0, 7]) == 1.0  # a load that never varies
    assert mod.max_over_mean([5, 0, 0]) is None  # steps, but no pair
    assert mod.max_over_mean([0, 0, 0]) is None
    assert mod.read({"stats_before": {}, "stats_after": {}}) is None
    assert mod.read({}) is None
    # the program's histogram: one bucket a count, the last takes the rest
    from ray_tpu.models.longcat_flash import (
        STEP_PAIRS_BUCKETS, LongCatFlashConfig, longcat_flash_counters,
        longcat_flash_init_state,
    )

    state = longcat_flash_init_state(LongCatFlashConfig.tiny(), 3)
    counters = longcat_flash_counters(state)
    assert len(counters["moe_step_pairs_decode"]) == STEP_PAIRS_BUCKETS
    assert counters["moe_zero_picks_decode"] == 0


def test_dense_ffn_share_follows_the_four_leaves():
    """What reads ``dense_ffn_w_in`` / ``_out`` of either half, directly or
    through the slices that bring a leaf into fast memory; an expert's
    product and the router's are not among them."""
    mod = _reader("dense_ffn_share_pct.sat")
    up = ("%fusion.7 = bf16[96,24576]{1,0} fusion(bf16[96,6144]{1,0} %x, "
          "bf16[6144,24576]{1,0} "
          "%params__layers___2___sub___0___dense_ffn_w_in__.1), kind=kOutput")
    start = ("%slice-start.8 = ((bf16[12288,6144]{1,0}), bf16[4096,6144]"
             "{1,0}, s32[]) slice-start(bf16[12288,6144]{1,0} "
             "%params__layers___2___sub___1___dense_ffn_w_out__.1), "
             "slice={[0:4096], [0:6144]}")
    done = ("%slice-done.8 = bf16[4096,6144]{1,0} slice-done(((bf16[12288,"
            "6144]{1,0}), bf16[4096,6144]{1,0}, s32[]) %slice-start.8)")
    down = ("%fusion.9 = bf16[96,6144]{1,0} fusion(bf16[96,12288]{1,0} "
            "%fusion.8, bf16[4096,6144]{1,0} %slice-done.8)")
    gmm = ("%ragged-dot-none.3 = f32[1152,4096] custom-call(%fusion.1, "
           "%params__layers___2___moe_gmm_w_in__.1)")
    route = ("%fusion.11 = f32[96,768] fusion(%x, "
             "%params__layers___2___moe_route_w__.1)")
    reduced = {"busy_s": 2.0, "ops": {
        up: {"count": 5, "self_s": 0.3}, start: {"count": 5, "self_s": 0.02},
        done: {"count": 5, "self_s": 0.0}, down: {"count": 5, "self_s": 0.18},
        gmm: {"count": 5, "self_s": 0.6}, route: {"count": 5, "self_s": 0.1}}}
    assert mod.read({"trace": reduced}) == pytest.approx(25.0)
    assert _reader("moe_share_pct.sat").read({"trace": reduced}) == \
        pytest.approx(35.0)
    # a program without the leaves (the parent, another family): nothing
    bare = {"busy_s": 2.0, "ops": {gmm: {"count": 5, "self_s": 0.6}}}
    assert mod.read({"trace": bare}) is None
    assert mod.read({}) is None


def test_latent2_bytes_and_flops_on_hand_numbers():
    mod = _reader("latent_attn2_hbm_pct.sat")
    # 96 rows x 1,100 tokens of context, 1,152 B a token a SUB-layer, 8
    assert mod.latent_attn2_bytes(96 * 1100, 512, 64, 2, 8) == \
        96 * 1100 * 1152 * 8
    # 64 heads: 2 x 64 x (576 + 512) flop a row: 120.9 a byte, half the
    # v5e's ridge of 240.5
    flops = mod.latent_attn2_flops(96 * 1100, 64, 512, 64, 8)
    assert flops == 96 * 1100 * 139264 * 8
    ratio = flops / mod.latent_attn2_bytes(96 * 1100, 512, 64, 2, 8)
    assert abs(ratio - 120.9) < 0.1
    assert abs(ratio / (197e12 / 819e9) - 0.5) < 0.01


def test_latent2_reader_on_a_stand_in_trace(monkeypatch):
    """Two decode runs paired with their dispatch spans: the accepted
    reader's arithmetic with the configuration's count of latent
    SUB-layers in the layers' place; the accepted reader itself reads half
    (``keys.n_layer``), which is why the cell does not list it."""
    mod = _reader("latent_attn2_hbm_pct.sat")
    base = _reader("latent_attn_hbm_pct.sat")
    call = "%paged_attention_latent.10 = bf16[4,1,2,8] custom-call(%x)"
    ops = [(call, 100.0 + 50 * i, 110.0 + 50 * i) for i in range(16)]
    decode = [("jit_longcat_flash_decode_step", 100.0, 500.0),
              ("jit_longcat_flash_decode_step", 500.0, 900.0)]
    steps = [{"attrs": {"kind": "decode", "kv_tokens": 450}, "run": run,
              "inside": True} for run in decode]
    keys = {"n_head": 2, "kv_lora_rank": 6, "qk_rope_head_dim": 2,
            "n_layer": 3, "dtype": "bfloat16"}
    monkeypatch.setattr(span_reduce, "load", lambda c: (
        {"planes": [{"ops": ops}]}, {"steps": steps}))
    monkeypatch.setattr(common, "peaks_for", lambda kind: {
        "hbm_gb_per_s": 100.0, "bf16_tflops": 1.0})
    ctx = {"config": {"keys": keys, "latent_sublayers": 6}}
    # 2 steps x 450 rows x (6 + 2) x 2 B x 6 sub-layers over 16 x 10 ns
    assert mod.read(ctx) == pytest.approx(
        100.0 * (2 * 450 * 16 * 6 / 160.0) / 100.0)
    assert base.read(ctx) == pytest.approx(mod.read(ctx) / 2)
    assert ctx["config"]["keys"]["n_layer"] == 3  # the file's is untouched
    # a configuration that names no such count (every other family's, the
    # parent's): nothing, and no exception
    assert mod.read({"config": {"keys": keys}}) is None
    monkeypatch.setattr(span_reduce, "load", lambda c: (None, None))
    assert mod.read(ctx) is None


def test_latent2_reader_on_the_trace_recorded_on_the_chip(
        tmp_path, monkeypatch):
    """The small latent trace PR 39 recorded (3 layers, one latent call
    each): told that the pool held 6 sub-layers, the reader gives twice
    the accepted reader's share, from the same kernel time."""
    if not os.path.exists(RECORDED):
        pytest.skip("no trace was recorded on the chip")
    sys.path.insert(0, os.path.dirname(RECORDED))
    from record_tiny_latent_trace import KEYS

    folder = tmp_path / "plugins" / "profile" / "recorded"
    folder.mkdir(parents=True)
    with gzip.open(RECORDED) as src, \
            open(folder / "tiny.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    monkeypatch.setattr(common, "peaks_for", lambda kind: {
        "hbm_gb_per_s": 819.0, "bf16_tflops": 197.0})
    ctx = {"trace_run": {"dir": str(tmp_path)},
           "config": {"keys": dict(KEYS, dtype="bfloat16"),
                      "latent_sublayers": 2 * KEYS["n_layer"]}}
    got = _reader("latent_attn2_hbm_pct.sat").read(ctx)
    assert "span_trace" in ctx  # read once a run, kept for the next reader
    want = _reader("latent_attn_hbm_pct.sat").read(ctx)
    assert 0 < want < 5 and got == pytest.approx(2 * want)
    ctx["trace"] = trace_reduce.reduce_file(
        trace_reduce.find_xplane(str(tmp_path)))
    # a trace of another family's program holds no dense_ffn leaf
    assert _reader("dense_ffn_share_pct.sat").read(ctx) is None


def test_accepted_readers_read_this_familys_names():
    """The accepted expert and latent readers look for ``moe_route`` /
    ``moe_gmm`` / ``ragged-dot`` and ``mla_w_uk`` / ``mla_w_uv`` in
    operation names: the family's leaves carry those names (the MLA's
    under ``sub``), so they read this cell unedited."""
    from ray_tpu.models.longcat_flash import _LEAF_AXES

    assert {"moe_route_w", "moe_route_bias", "moe_gmm_w_in", "moe_gmm_w_out",
            "mla_w_uk", "mla_w_uv", "dense_ffn_w_in",
            "dense_ffn_w_out"} <= set(_LEAF_AXES)
    absorb = ("%fusion.7 = bf16[96,64,512]{2,1,0} fusion(bf16[96,8192]{1,0} "
              "%x, bf16[512,8192]{1,0} "
              "%params__layers___2___sub___1___mla_w_uk__.1), kind=kOutput")
    other = ("%fusion.10 = bf16[96,6144]{1,0} fusion(bf16[96,8192]{1,0} "
             "%fusion.9, bf16[8192,6144]{1,0} "
             "%params__layers___1___sub___0___mla_w_o__.1)")
    reduced = {"busy_s": 2.0, "ops": {
        absorb: {"count": 8, "self_s": 0.05},
        other: {"count": 8, "self_s": 0.7}}}
    assert _reader("latent_absorb_share_pct.sat").read(
        {"trace": reduced}) == pytest.approx(2.5)
    assert _reader("moe_gmm_hbm_pct.sat").moe_gmm_bytes(
        12.6 * 4, 6144, 2048, 2) == pytest.approx(
            12.6 * 4 * 3 * 6144 * 2048 * 2)


# ------------------------------------ the lint of every configuration file


@pytest.mark.parametrize("entry", common.load_manifest()["configs"],
                         ids=lambda c: c["name"])
def test_reduced_entries_name_a_published_and_a_here(entry):
    """Every key a configuration lists under ``reduced`` says what was
    PUBLISHED, what is HERE and why, the two differ, the file's top-level
    key holds what is here, and no such key is a width."""
    held = common.load_json(os.path.join(ROOT, entry["file"]))
    assert sorted(entry["reduced"]) == sorted(held["reduced"])
    assert len(entry["reduced"]) <= 16
    for key, cut in held["reduced"].items():
        assert {"published", "here", "why"} <= set(cut), (entry["name"], key)
        assert cut["published"] != cut["here"], key
        assert held[key] == cut["here"], key
        assert isinstance(cut["why"], str) and len(cut["why"]) > 20, key
        assert not key.endswith(("_dim", "_rank")), key
        for width in ("hidden_size", "intermediate", "head_dim", "topk",
                      "per_tok"):
            assert width not in key, key
