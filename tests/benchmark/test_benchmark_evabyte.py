"""The EvaByte reference, the configuration file, the traffic file and the new
cell's readers: ``logits_at`` picks ``logits``' rows of output head 0; blocks
of queries and of rows do not change the reference's result; a control
precision cuts both operands; the configuration holds the catalog row's
config unchanged but for the depth and builds the program's config from it;
the traffic is the issue's table; the reference check reaches into closed
windows and closes one inside the compared tokens; the cell runs end to end
on the CPU at its rehearsal size; the three new per-layer readers compute
what they say from plain data and return nothing (they do not raise) where
the program has no such operation, span attribute or record, as the parent
has not."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common  # noqa: E402

CELL = "evabyte-bytes-chat-saturated"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def ref():
    return common.load_named("reference", "evabyte")


@pytest.fixture(scope="module")
def tiny(ref):
    held = common.load_json(os.path.join(
        ROOT, "benchmark/configs/evabyte-6.5b-8l.json"))
    cfg = common.model_config(
        {"family": "evabyte", "keys": held["rehearsal"]["keys"]},
        {"dtype": "float32"})
    return cfg, ref.init_fn()(jax.random.PRNGKey(3), cfg)


def _reader(name):
    return common.load_layer_metric(name)


# ------------------------------------------------------------ the reference


def test_logits_at_picks_the_rows_of_head_zero(ref, tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 75), 0, 320)
    full = np.asarray(ref.logits(params, tokens, cfg))
    assert full.shape == (2, 75, cfg.num_pred_heads, 320)
    at = jnp.asarray([[0, 31, 32, 74], [5, 63, 64, 70]])
    got = np.asarray(ref.logits_at(params, tokens, at, cfg))
    want = np.take_along_axis(full[:, :, 0], np.asarray(at)[..., None], 1)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # init_fn rounds every matrix leaf, phi and mu among them, once
    assert params["blocks"]["wq"].dtype == jnp.bfloat16
    assert params["blocks"]["eva_phi"].dtype == jnp.bfloat16
    assert params["blocks"]["ln1_g"].dtype == jnp.float32


@pytest.mark.parametrize("knob,value", [
    ("Q_BLOCK", 7), ("ROW_BLOCK", 9)])
def test_reference_blocks_do_not_change_the_result(ref, tiny, monkeypatch,
                                                   knob, value):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 70), 0, 320)
    want = np.asarray(ref.logits(params, tokens, cfg))
    monkeypatch.setattr(ref, knob, value)
    np.testing.assert_allclose(
        np.asarray(ref.logits(params, tokens, cfg)), want, atol=2e-5)


def test_a_control_precision_cuts_both_operands(ref, monkeypatch):
    x = jnp.asarray([[1.0 + 2 ** -5, 3.0]])
    w = jnp.asarray([[1.0 + 2 ** -6], [0.5]])
    assert float(ref._mm(x, w)[0, 0]) == pytest.approx(
        (1 + 2 ** -5) * (1 + 2 ** -6) + 1.5)
    monkeypatch.setattr(ref, "ROUND_TO", jnp.float8_e4m3fn)
    assert float(ref._mm(x, w)[0, 0]) == pytest.approx(1.0 + 1.5)


def test_reference_masks_are_built_from_positions(ref):
    """A query sees its own window up to itself and the chunks whose LAST
    position lies in an earlier window: at W 8, C 4 the summaries reach a
    query only from its second window on, and the remote weight is what
    the softmax gives the summary's key."""
    from types import SimpleNamespace

    cfg = SimpleNamespace(n_head=1, head_dim=4, rope_theta=1e5)
    D = 4
    eye = jnp.eye(D)
    lp = {"wq": 0 * eye, "wk": eye, "wv": eye, "wo": eye,
          "eva_phi": jnp.zeros((1, D)), "eva_mu": jnp.zeros((1, D))}
    u = jnp.arange(12 * D, dtype=jnp.float32).reshape(12, D)
    out = np.asarray(ref._attention(u, lp, cfg, 8, 4, 0, True))
    # q = 0: every seen key weighs the same, so a row is the mean of what
    # it sees; v = u
    u = np.asarray(u)
    np.testing.assert_allclose(out[5], u[:6].mean(0), rtol=1e-6)
    np.testing.assert_allclose(out[7], u[:8].mean(0), rtol=1e-6)
    # position 9: chunks 0 and 1 (flat weights: each the mean of its 4
    # rows) and the exact rows 8, 9
    seen = [u[0:4].mean(0), u[4:8].mean(0), u[8], u[9]]
    np.testing.assert_allclose(out[9], np.mean(seen, 0), rtol=1e-6)


# ------------------------------------------- the configuration and the cell


def test_configuration_holds_the_rows_config():
    spec = common.resolve_cell(common.load_manifest(), CELL)
    held = spec["config"]
    assert spec["cell"]["chips"] == 1
    assert spec["cell"]["traffic"] == "bytes-chat-closed"
    entry = next(c for c in common.load_manifest()["configs"]
                 if c["name"] == "evabyte-6.5b-8l")
    assert entry["reduced"] == list(held["reduced"]) == ["num_hidden_layers"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "EvaByte")
        assert entry["source"] == held["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key != "num_hidden_layers":
                assert key in held and held[key] == value, key
        assert row["config"]["num_hidden_layers"] == \
            held["reduced"]["num_hidden_layers"]["published"] == 32
    assert held["num_hidden_layers"] == 8
    cfg = common.model_config(held)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim,
            cfg.d_mlp) == (
        held["hidden_size"], held["num_attention_heads"],
        held["num_key_value_heads"], 128, held["intermediate_size"]) == (
            4096, 32, 32, 128, 11008)
    assert (cfg.vocab_size, cfg.num_pred_heads, cfg.window_size,
            cfg.chunk_size, cfg.max_seq_len, cfg.n_layer) == (
        held["vocab_size"], held["num_pred_heads"], held["window_size"],
        held["chunk_size"], held["max_position_embeddings"], 8) == (
            320, 8, 2048, 16, 32768, 8)
    assert cfg.rope_theta == held["rope_theta"] == 100000
    assert cfg.norm_eps == held["rms_norm_eps"] == 1e-5
    assert cfg.dtype == jnp.bfloat16
    assert cfg.kv_table_groups == (
        (("ring", 2048), tuple(range(8))), (("slots", 16), tuple(range(8))))
    assert set(held["assumed"]) >= {"summary", "rotary", "head", "weights"}
    assert "four v5e" in held["deployment"]
    # the byte count the file states: 1,631 M parameters, 3.26 GB
    ref = common.load_named("reference", "evabyte")
    shapes = jax.eval_shape(
        lambda: ref.init_fn()(jax.random.PRNGKey(0), cfg))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert abs(n - 1631e6) < 1e6, n
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert abs(nbytes - 3.262e9) < 0.005e9, nbytes
    assert shapes["lm_head"].shape == (4096, 2560)
    names = {m["name"] for m in spec["per_layer"]}
    assert {"eva_attn_hbm_pct.sat", "eva_attended_pct",
            "eva_summarize_share_pct.sat", "decode_step_ms.sat",
            "paged_attn_share_pct.sat", "hbm_peak_gb.serve",
            "kv_high_water_pct", "decode_batch_mean",
            "idle_pct.executor.sat"} <= names
    # its reader counts one table of true positions: over 100% here
    assert "paged_attn_hbm_pct.sat" not in names
    assert [m["name"] for m in spec["end_to_end"]] == [
        "setup_s", "serve_tokens_per_s"]


def test_traffic_is_the_issues_table():
    spec = common.resolve_cell(common.load_manifest(), CELL)
    traffic = spec["traffic"]
    assert (traffic["runner"], traffic["generator"]) == (
        "serve_engine", "lognormal_chat")
    assert traffic["arrivals"] == {"mode": "closed", "clients": 48}
    assert traffic["prompt_len"] == {
        "median": 4096, "sigma": 0.7, "min": 512, "max": 16384}
    assert traffic["output_len"] == {
        "median": 768, "sigma": 0.5, "min": 128, "max": 2048}
    assert traffic["strata"] == 16
    assert traffic["sampling"] == {"temperature": 0.0}
    engine = traffic["engine"]
    assert (engine["block_size"], engine["num_blocks"],
            engine["max_batch_size"], engine["prefill_chunk_tokens"]) == (
                16, 4353, 24, 2048)
    gen = common.load_named("generators", "lognormal_chat")
    schedule = gen.build(traffic, 1, 320)
    prompts, outputs = schedule.prompts, schedule.outputs
    assert (min(prompts), max(prompts)) == (1112, 15088)
    assert (min(outputs), max(outputs)) == (303, 1949)
    assert abs(sum(prompts) / 16 - 5112) < 2
    assert abs(sum(outputs) / 16 - 861) < 2
    assert sum(p > 2048 for p in prompts) == 13  # 81% pass one window
    # what a request reserves against what it would hold in one table
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig

    cfg = common.model_config(spec["config"])
    kv = KVCacheConfig(n_layer=cfg.n_layer, n_kv_head=32, head_dim=128,
                       num_blocks=engine["num_blocks"], block_size=16,
                       groups=cfg.kv_table_groups)
    need = [kv.request_blocks(p + o) for p, o in zip(prompts, outputs)]
    one_table = [kv.blocks_for(p + o) for p, o in zip(prompts, outputs)]
    assert abs(sum(need) / 16 - 151) < 3 and max(need) <= 195
    assert abs(sum(one_table) / 16 - 374) < 3 and max(one_table) <= 1065
    assert 24 * sum(need) / 16 < 0.85 * kv.usable_blocks
    assert kv.usable_blocks // (sum(one_table) / 16) == 11
    assert kv.prefill_room(4, 2048) == 0  # nothing is given back mid-step
    # every context fits the widest bucket and every chunk IS the lowest
    buckets = engine["length_buckets"]
    assert buckets[0] == engine["prefill_chunk_tokens"] == cfg.window_size
    assert max(prompts) + max(outputs) <= buckets[-1] <= cfg.max_seq_len
    assert kv.composed_blocks(buckets[-1]) == 192
    assert engine["max_batch_size"] in engine["batch_buckets"]
    assert set(traffic["warmup"]["decode_batches"]) == set(
        engine["batch_buckets"])
    assert set(traffic["warmup"]["prefill_batches"]) == {
        b for b in engine["batch_buckets"] if b <= 4} == {1, 2, 4}
    for key in ("window_why", "warmup_why"):
        assert len(traffic[key]) > 100
    assert set(traffic["engine_why"]) >= set(engine) - {"block_size"}


def test_reference_check_closes_a_window_inside_the_compared_tokens():
    spec = common.resolve_cell(common.load_manifest(), CELL)
    chk, traffic = spec["config"]["reference_check"], spec["traffic"]
    lens = chk["prompt_tokens"][: chk["requests"]]
    assert len(lens) == chk["requests"] == len(set(lens)) == 16
    cfg = common.model_config(spec["config"])
    W = cfg.window_size
    assert 1500 == min(lens) and max(lens) == 9000
    assert chk["every"] == 1 and chk["new_tokens"] == 64
    # summaries of one to four closed windows are inside the comparison
    assert {n // W for n in lens} >= {0, 1, 2, 3, 4}
    # at least four prompts end 32 short of a window's end: the window
    # closes, its last chunk is summarised and the composed table changes
    # INSIDE the 64 compared tokens
    closing = [n for n in lens if (n + chk["new_tokens"]) // W > n // W]
    assert len(closing) >= 4 and all(n % W == W - 32 for n in closing)
    assert sum(n > traffic["engine"]["prefill_chunk_tokens"]
               for n in lens) >= 12
    assert max(lens) + chk["new_tokens"] <= chk["pad_to"]
    assert chk["pad_to"] <= traffic["engine"]["length_buckets"][-1]
    # 16 rows decode at the bucket of 24
    assert chk["requests"] <= max(traffic["warmup"]["decode_batches"])
    assert 0 < chk["tolerance_logit"] and "fp8" in chk["tolerance_why"]


@pytest.mark.timeout(600)
def test_rehearsal_runs_the_cell_end_to_end_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCHMARK_REHEARSAL="1")
    env.pop("BENCH_RUN", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 32), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=580)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert "'kind': 'ring'" in out.stdout and "'kind': 'slots'" in out.stdout
    assert "compiled or read from the cache INSIDE" not in out.stdout
    assert "the warm-up had not" not in out.stdout
    # the flight records read on the CPU; the trace's readers find no TPU
    # plane and leave their metrics out without raising
    assert 0 < line["metrics"]["eva_attended_pct"]["value"] <= 100
    assert line["metrics"]["decode_batch_mean"]["value"] > 0
    for name in ("eva_attn_hbm_pct.sat", "eva_summarize_share_pct.sat"):
        assert name not in line["metrics"]


# ----------------------------------------------------------- the new readers


def test_attention_bytes_of_a_composed_table():
    mod = _reader("eva_attn_hbm_pct.sat")
    # 24 rows x (1,000 rows of their window + 274 summaries), 16 KB a slot
    # a layer, 8 layers
    assert mod.eva_attn_bytes(24 * 1000, 24 * 274, 32, 128, 2, 8) == \
        24 * 1274 * 16384 * 8
    assert mod.eva_attn_bytes(3, 2, 1, 1, 1, 1) == 10
    keys = common.load_json(os.path.join(
        ROOT, "benchmark/configs/evabyte-6.5b-8l.json"))["keys"]
    assert mod.widths_of(keys) == {
        "n_head": 32, "head_dim": 128, "itemsize": 2, "n_layer": 8}
    # no trace, or no spans: nothing, and no exception
    assert mod.read({}) is None
    assert mod.read({"trace_run": None}) is None


def test_roofline_reader_on_a_stand_in_trace(monkeypatch):
    """Two decode runs paired with their dispatch spans: bytes of the
    spans' window rows and summaries over the kernel's time inside the
    runs; a span without ``kv_chunks`` (the parent's) gives nothing."""
    from benchmark import span_reduce

    mod = _reader("eva_attn_hbm_pct.sat")
    call = "%paged_attention.10 = bf16[24,32,1,128] custom-call(%x)"
    ops = [(call, 100.0 + 50 * i, 110.0 + 50 * i) for i in range(16)]
    runs = [("jit_evabyte_decode_step", 100.0, 500.0),
            ("jit_evabyte_decode_step", 500.0, 900.0)]

    def steps(attrs):
        return [{"attrs": dict(kind="decode", **attrs), "run": run,
                 "inside": True} for run in runs]

    ctx = {"config": {"keys": {"n_head": 2, "d_model": 8, "dtype": "bfloat16",
                               "n_layer": 3}}}
    monkeypatch.setattr(span_reduce, "load", lambda c: (
        {"planes": [{"ops": ops}]},
        {"steps": steps({"kv_tokens": 900, "kv_tokens_window": 40,
                         "kv_chunks": 10})}))
    monkeypatch.setattr(common, "peaks_for",
                        lambda kind: {"hbm_gb_per_s": 100.0})
    # 2 steps x 50 slots x (2 x 2 x 4 x 2 B) x 3 layers over 16 x 10 ns
    want = 100.0 * (2 * 50 * 32 * 3 / 160.0) / 100.0
    assert mod.read(ctx) == pytest.approx(want)
    monkeypatch.setattr(span_reduce, "load", lambda c: (
        {"planes": [{"ops": ops}]}, {"steps": steps({"kv_tokens": 900})}))
    assert mod.read(ctx) is None


def test_attended_share_reads_the_windows_decode_records():
    read = _reader("eva_attended_pct").read
    flight = [
        {"kind": "decode", "kv_tokens": 1000, "kv_tokens_window": 150,
         "kv_chunks": 50},
        {"kind": "decode", "kv_tokens": 3000, "kv_tokens_window": 500,
         "kv_chunks": 300},
        {"kind": "prefill_chunk", "tokens": 2048},
        {"kind": "decode", "batch": 0},  # a drain step: no row
    ]
    assert read({"flight": flight}) == pytest.approx(25.0)
    # the parent's records carry no kv_chunks: nothing, not zero
    assert read({"flight": [{"kind": "decode", "kv_tokens": 9}]}) is None
    assert read({"flight": []}) is None and read({}) is None


def test_summarize_share_sums_the_kernels_calls():
    mod = _reader("eva_summarize_share_pct.sat")
    kernel = ("%eva_summarize.3 = (bf16[24,32,128], bf16[24,32,128]) "
              'custom-call(%fusion.9), custom_call_target="tpu_custom_call"')
    leaf = "%fusion.4 = f32[32,128] fusion(%params__blocks__eva_phi__.1)"
    reduced = {"busy_s": 2.0, "ops": {
        kernel: {"count": 8, "self_s": 0.03},
        leaf: {"count": 1, "self_s": 0.01},
        "%paged_attention.1 = custom-call()": {"count": 8, "self_s": 1.0}}}
    assert mod.read({"trace": reduced, "config": {"keys": {}}}) == \
        pytest.approx(2.0)
    bare = {"busy_s": 2.0, "ops": {
        "%paged_attention.1 = custom-call()": {"count": 8, "self_s": 1.0}}}
    assert mod.read({"trace": bare}) is None and mod.read({}) is None
    # 24 chunks of 16 + 1 slots x 8 KB, K and V, 8 layers
    assert mod.eva_summarize_bytes(24, 16, 32, 128, 2, 8) == \
        24 * 2 * 17 * 8192 * 8
