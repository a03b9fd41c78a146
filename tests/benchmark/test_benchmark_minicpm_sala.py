"""The MiniCPM-SALA reference, the configuration file, the traffic file and
the new cell's readers: the manifest resolves the cell; the configuration
holds the catalog row's numbers unchanged but for the keys ``reduced``
names, every ``reduced`` / ``assumed`` entry says what was published, what
is here and the other reading; the parameter count (2,820 M) comes out of
the file's own widths; the traffic is the issue's table; each new reader's
byte and flop function on hand-worked values, the readers on a stand-in
trace, and nothing (no exception) where the program names or counts no
such thing, as the parent's does not; the cell runs end to end on the CPU
at its rehearsal size, whose ``dense_len`` its prompts pass
(``sparse_rows_pct`` > 0)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, span_reduce  # noqa: E402

CELL = "sala-longdoc-saturated"
CONFIG = "minicpm-sala-8l"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = ("sparse_attn_hbm_pct.sat", "sparse_select_share_pct.sat",
               "sparse_prefill_share_pct.sat", "lightning_state_hbm_pct.sat",
               "lightning_prefill_mxu_pct.sat", "sparse_kept_pct",
               "sparse_rows_pct")
KEYS = {"lightning_n_head": 32, "lightning_head_dim": 128, "n_kv_head": 2,
        "head_dim": 128, "sparse_block_size": 64, "kernel_stride": 16,
        "dtype": "bfloat16",
        "mixer_types": ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"]}


def _reader(name):
    return common.load_layer_metric(name)


def _held():
    return common.load_json(os.path.join(
        ROOT, "benchmark/configs", CONFIG + ".json"))


# ------------------------------------------------------------ the manifest


def test_manifest_resolves_the_cell():
    spec = common.resolve_cell(common.load_manifest(), CELL)
    assert spec["cell"]["chips"] == 1
    assert (spec["cell"]["config"], spec["cell"]["traffic"]) == (
        CONFIG, "longdoc-closed")
    assert spec["config"]["family"] == "minicpm_sala"
    assert [m["name"] for m in spec["end_to_end"]] == [
        "setup_s", "serve_tokens_per_s"]
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW_READERS) <= names
    assert {"decode_batch_mean", "kv_high_water_pct", "decode_step_ms.sat",
            "hbm_peak_gb.serve", "prefill_device_share_pct.sat",
            "prefill_fill_pct.sat", "idle_pct.other.sat"} <= names
    # llama's key names and one table: PERF.md 7 e
    assert "paged_attn_hbm_pct.sat" not in names
    manifest = common.load_manifest()
    for m in manifest["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
            assert m["layer"] == \
                "kernels ops/sparse_select.py and ops/lightning.py"
    assert len(manifest["workloads"]) >= 11
    for name in NEW_READERS:
        assert hasattr(_reader(name), "read")


def test_the_reference_imports_nothing_of_the_program():
    """Plain ``jax.numpy`` (and numpy for constant index tables): the file
    names ``ray_tpu`` only where it hands the harness the program's config
    class and initialiser; and the program imports nothing of it."""
    import ast

    path = os.path.join(ROOT, "benchmark/reference/minicpm_sala.py")
    tree = ast.parse(open(path).read())
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    assert sorted(getattr(n, "module", None) or n.names[0].name
                  for n in top) == ["__future__", "jax", "jax.numpy", "math",
                                    "numpy"]
    inner = {n.module for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom) and n not in top}
    assert inner == {"ray_tpu.models.minicpm_sala"}
    names = {a.name for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)
             and n not in top for a in n.names}
    assert names == {"MiniCPMSALAConfig", "minicpm_sala_init"}
    assert 'default_matmul_precision("highest")' in open(path).read()
    for rel in ("ray_tpu/models/minicpm_sala.py", "ray_tpu/ops/lightning.py",
                "ray_tpu/ops/sparse_select.py"):
        assert "benchmark" not in {
            (getattr(n, "module", None) or "").split(".")[0]
            for n in ast.walk(ast.parse(open(os.path.join(ROOT, rel)).read()))
            if isinstance(n, ast.ImportFrom)}


# ------------------------------------------------- the configuration file


def test_configuration_holds_the_rows_numbers():
    """Every key of the catalog row's ``config`` is in the file, unchanged
    but for the two ``reduced`` names; the program's config is built from
    ``keys``; no width is among the cuts."""
    row = next(json.loads(line) for line in open(CATALOG)
               if json.loads(line)["name"] == "MiniCPM-SALA")
    held = _held()
    assert held["source"] == row["source_url"]
    assert sorted(held["reduced"]) == ["mixer_types", "num_hidden_layers"]
    for key, value in row["config"].items():
        if key in held["reduced"]:
            assert held["reduced"][key]["published"] == value
            assert held[key] == held["reduced"][key]["here"] != value
        else:
            assert held[key] == value, key
    assert held["num_hidden_layers"] == len(held["mixer_types"]) == 8
    assert held["mixer_types"] == row["config"]["mixer_types"][9:17]
    cfg = common.model_config(held)
    assert (cfg.n_layer, cfg.n_kv_layer, cfg.n_lightning_layer) == (8, 2, 6)
    assert cfg.layer_index == tuple(range(9, 17))
    assert cfg.n_layer_published == row["config"]["num_hidden_layers"] == 32
    assert (cfg.d_model, cfg.d_mlp, cfg.vocab_size) == (4096, 16384, 73448)
    assert cfg.sparse == (32, 16, 64, 64, 1, 2048, 8192)


def test_reduced_and_assumed_entries_say_what_and_what_else():
    held = _held()
    for key, entry in held["reduced"].items():
        assert entry["published"] != entry["here"] and entry["why"], key
    assumed = held["assumed"]
    assert {"sparse_config", "lightning_decay", "output_norm", "qk_norm",
            "dense_len_rule", "log_sum_exp", "weights"} <= set(assumed)
    sparse = assumed["sparse_config"]
    assert {k: sparse[k] for k in (
        "kernel_size", "kernel_stride", "block_size", "topk", "init_blocks",
        "window_size", "dense_len")} == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
        "init_blocks": 1, "window_size": 2048, "dense_len": 8192}
    assert sparse["from"] and sparse["other_reading"]
    for key in ("lightning_decay", "output_norm", "qk_norm",
                "dense_len_rule", "weights"):
        assert "other reading" in assumed[key].lower(), key
    for key in ("dense_len_rule", "log_sum_exp"):
        assert "DEPARTURE" in assumed[key], key
    assert "four pipeline stages" in held["deployment"]


def test_the_files_widths_give_the_parameter_count():
    """2,820 M parameters = 5.64 GB of bf16, from the file's own widths
    (norm scales, 0.1 M, left out as the file's ``bytes`` leave them)."""
    c = _held()
    D, M, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    Hq, Hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    LH, lhd = c["lightning_nh"], c["lightning_head_dim"]
    lightning = 5 * D * LH * lhd + 3 * D * M
    sparse = 3 * D * Hq * hd + 2 * D * Hkv * hd + 3 * D * M
    total = sum(lightning if m == "lightning-attn" else sparse
                for m in c["mixer_types"]) + 2 * V * D
    assert round(lightning / 1e6, 1) == 285.2
    assert round(sparse / 1e6, 1) == 253.8
    assert round(total / 1e6) == 2820
    assert "2,820 M parameters = 5.64 GB" in c["bytes"]["total"]
    import jax

    ref = common.load_named("reference", "minicpm_sala")
    cfg = common.model_config(c)
    shapes = jax.eval_shape(
        lambda: ref.init_fn()(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree.leaves(shapes)
    assert sum(x.size for x in leaves if x.ndim >= 2) == total
    assert all(str(x.dtype) == "bfloat16" for x in leaves if x.ndim >= 2)


def test_traffic_is_the_issues_table():
    spec = common.resolve_cell(common.load_manifest(), CELL)
    traffic = spec["traffic"]
    assert (traffic["runner"], traffic["generator"]) == (
        "serve_engine", "lognormal_chat_ordered")
    assert traffic["arrivals"]["mode"] == "closed"
    assert traffic["prompt_len"] == {
        "median": 16384, "sigma": 0.6, "min": 4096, "max": 49152}
    assert traffic["output_len"] == {
        "median": 1024, "sigma": 0.6, "min": 256, "max": 4096}
    assert traffic["sampling"] == {"temperature": 0.0}
    assert traffic["window"]["ramp_s"] <= 75
    assert (traffic["window"]["trace_after_s"],
            traffic["window"]["trace_s"]) == (2.0, 8.0)
    engine = traffic["engine"]
    assert (engine["block_size"], engine["prefill_chunk_tokens"],
            engine["max_prefill_batch"]) == (64, 2048, 1)
    assert engine["max_batch_size"] == traffic["arrivals"]["clients"]
    assert engine["max_batch_size"] in (32, 24, 16)   # the issue's ladder
    gen = common.load_named("generators", "lognormal_chat_ordered")
    schedule = gen.build(traffic, 1, 73448)
    prompts, outputs = schedule.prompts, schedule.outputs
    n = traffic["strata"]
    assert n == len(prompts)
    # the clients' first requests are whole blocks of quantiles
    assert traffic["arrivals"]["clients"] % n == 0
    assert 4096 <= min(prompts) and max(prompts) <= 49152
    assert 256 <= min(outputs) and max(outputs) <= 4096
    assert abs(sum(prompts) / n - 19127) < 600    # the issue's mean, to 3%
    assert abs(sum(outputs) / n - 1216) < 60
    assert sum(p > 8192 for p in prompts) / n >= 0.875
    past = sum(max(p - 8192, 0) for p in prompts) / sum(prompts)
    assert 0.55 < past < 0.60       # 58% of prompt tokens past dense_len
    ids = schedule.request(0)["prompt"]
    assert 1 <= int(ids.min()) and int(ids.max()) < 73448
    buckets = engine["length_buckets"]
    assert buckets[0] == engine["prefill_chunk_tokens"]
    assert max(prompts) + max(outputs) <= buckets[-1]
    assert buckets[-1] % engine["block_size"] == 0
    # the pool holds the rows at the clips' mean and more
    tokens = (engine["num_blocks"] - 1) * engine["block_size"]
    assert tokens > 2 * engine["max_batch_size"] * (
        sum(prompts) + sum(outputs)) / n
    assert engine["max_batch_size"] in engine["batch_buckets"]
    assert set(traffic["warmup"]["decode_batches"]) == set(
        engine["batch_buckets"])
    for key in ("window_why", "warmup_why", "engine_why", "strata_why",
                "order_why"):
        assert traffic[key] and "TO BE SET" not in json.dumps(
            traffic[key]), key


def _ordered(seed, **over):
    spec = common.resolve_cell(common.load_manifest(), CELL)
    gen = common.load_named("generators", "lognormal_chat_ordered")
    return gen.build(common.merged(spec["traffic"], over), seed, 73448)


@pytest.mark.parametrize("seed", [1, 7, 2 ** 31 + 99, 3000000007])
def test_the_order_is_no_seeds(seed):
    """Every seed offers the same requests in the same turn (what spread
    this cell's windows was WHICH quantiles a seed put inside them) and
    picks the token ids alone."""
    base, other = _ordered(0), _ordered(seed)
    assert [base.lengths(i) for i in range(96)] == [
        other.lengths(i) for i in range(96)]
    assert not np.array_equal(base.request(5)["prompt"],
                              other.request(5)["prompt"])
    again = _ordered(seed).request(5)
    assert np.array_equal(again["prompt"], other.request(5)["prompt"])
    assert again["max_new_tokens"] == base.lengths(5)[1]


@pytest.mark.parametrize("block", [0, 1, 5])
def test_a_block_holds_every_quantile_once_in_a_balanced_order(block):
    """The distribution is lognormal_chat's, whole; consecutive admissions
    hold long and short prompts in near-even shares."""
    plain = common.load_named("generators", "lognormal_chat")
    s = _ordered(3)
    n = s.strata
    sizes = [s.lengths(block * n + j) for j in range(n)]
    traffic = common.resolve_cell(common.load_manifest(), CELL)["traffic"]
    assert sorted(p for p, _ in sizes) == sorted(
        plain.lognormal_quantiles(traffic["prompt_len"], n))
    assert sorted(o for _, o in sizes) == sorted(
        plain.lognormal_quantiles(traffic["output_len"], n))
    mean = sum(s.prompts) / n
    # any 8 consecutive admissions, across the block's edge too: within a
    # sixth of the mean (a seeded permutation's runs of 8 reach a half)
    run = [s.lengths(block * n + j)[0] for j in range(n + 8)]
    for j in range(n):
        assert abs(sum(run[j:j + 8]) / 8 - mean) < mean / 6, j
    prompts = np.array([p for p, _ in sizes], float)
    outputs = np.array([o for _, o in sizes], float)
    assert abs(np.corrcoef(prompts, outputs)[0, 1]) < 0.05


@pytest.mark.parametrize("over, says", [
    ({"arrivals": {"mode": "open", "rate_per_s": 1.0}}, "closed loop"),
    ({"strata": 24}, "power of two"),
    ({"order": {"output_stride": 10}}, "odd"),
])
def test_the_ordered_generator_refuses_what_it_cannot_order(over, says):
    with pytest.raises(ValueError, match=says):
        _ordered(1, **over)


def test_reference_check_fits_what_the_engine_is_built_for():
    spec = common.resolve_cell(common.load_manifest(), CELL)
    chk, traffic = spec["config"]["reference_check"], spec["traffic"]
    lens = chk["prompt_tokens"][: chk["requests"]]
    assert len(lens) == chk["requests"] == len(set(lens)) == 16
    assert min(lens) <= 300 and max(lens) >= 16000
    assert sum(n > 8192 for n in lens) >= 6
    # two cross dense_len while decoding
    assert sum(n < 8192 <= n + chk["new_tokens"] for n in lens) >= 2
    assert chk["every"] == 1 and chk["new_tokens"] == 64
    assert max(lens) + chk["new_tokens"] <= chk["pad_to"]
    assert chk["pad_to"] <= traffic["engine"]["length_buckets"][-1]
    assert 0 < chk["tolerance_logit"]
    assert "TO BE SET" not in chk["tolerance_why"]


# -------------------------------------------------------------- the readers


def test_byte_and_flop_functions_on_hand_numbers():
    sparse = _reader("sparse_attn_hbm_pct.sat")
    # a sparse row-step a layer: 64 blocks x 64 tokens x 2 heads x 128 x
    # (K, V) x 2 B = 4.19 MB
    assert sparse.sparse_attn_bytes(64, 64, 2, 128, 2, 1) == 4194304
    assert sparse.widths_of(KEYS) == {
        "block_size": 64, "n_kv_head": 2, "head_dim": 128, "itemsize": 2,
        "n_layer": 2}
    state = _reader("lightning_state_hbm_pct.sat")
    # a row a layer: 32 x 128 x 128 x 4 B, read and written: 4.19 MB
    assert state.lightning_state_bytes(1, 32, 128, 1) == 2 * 2097152
    assert state.lightning_state_bytes(32, 32, 128, 6) == 805306368
    assert state.state_type(KEYS).search("f32[32,32,128,128]{3,2,1,0}")
    assert state.state_type(KEYS).search("f32[6,33,32,128,128]")
    assert not state.state_type(KEYS).search("f32[32,128,128]")
    chunk = _reader("lightning_prefill_mxu_pct.sat")
    # a token a head: 4 x 128 x (128 + 128) = 131,072 flop
    assert chunk.lightning_chunk_flops(1, 1, 128, 128, 1) == 131072
    assert chunk.lightning_chunk_flops(2048, 32, 128, 128, 6) == \
        2048 * 32 * 131072 * 6
    select = _reader("sparse_select_share_pct.sat")
    # a row at 32,768 tokens: 2,047 compressed keys x 2 x 128 x 4 B
    assert select.compressed_bytes(32768, 16, 2, 128, 4) == 2047 * 1024
    assert select.compressed_bytes(10, 16, 2, 128, 4) == 0
    assert _reader("sparse_kept_pct").kept_pct(64, 256) == 25.0
    assert _reader("sparse_rows_pct").rows_pct(3, 1) == 75.0


def test_counter_readers_on_recorded_counters():
    before = {"sparse_row_steps": 10, "dense_row_steps": 10,
              "sparse_blocks_attended": 100, "sparse_blocks_visible": 200}
    after = {"sparse_row_steps": 110, "dense_row_steps": 35,
             "sparse_blocks_attended": 25700, "sparse_blocks_visible": 128200}
    ctx = {"stats_before": before, "stats_after": after}
    assert _reader("sparse_rows_pct").read(ctx) == pytest.approx(80.0)
    assert _reader("sparse_kept_pct").read(ctx) == pytest.approx(20.0)
    # the parent's stats have no such keys: nothing, and no exception
    for name in ("sparse_rows_pct", "sparse_kept_pct"):
        assert _reader(name).read({"stats_before": {}, "stats_after": {}}) \
            is None
        assert _reader(name).read({}) is None


def _stand_in(monkeypatch, ops, steps):
    monkeypatch.setattr(span_reduce, "load", lambda c: (
        {"planes": [{"ops": ops}]}, {"steps": steps}))
    monkeypatch.setattr(common, "peaks_for", lambda kind: {
        "hbm_gb_per_s": 100.0, "bf16_tflops": 1.0})


def test_decode_readers_on_a_stand_in_trace(monkeypatch):
    """Two decode runs paired with their dispatch spans."""
    kernel = "%paged_attention_sparse.3 = bf16[32,2,16,128] custom-call(%q)"
    update = ("%multiply_add_fusion = f32[32,32,128,128]{3,2,1,0} "
              "fusion(f32[32,32,128,128] %gather.3, bf16[32,32,128] %k)")
    other = "%fusion.9 = bf16[32,4096] fusion(bf16[32,4096] %x)"
    ops = []
    for base in (100.0, 500.0):
        ops += [(kernel, base + 10, base + 30), (kernel, base + 40, base + 60),
                (update, base + 100, base + 110), (other, base + 200,
                                                   base + 300)]
    runs = [("jit_minicpm_sala_decode_step", 100.0, 500.0),
            ("jit_minicpm_sala_decode_step", 500.0, 900.0)]
    steps = [{"attrs": {"kind": "decode", "rows": 32, "rows_sparse": 30,
                        "sel_blocks": 2000}, "run": run, "inside": True}
             for run in runs]
    _stand_in(monkeypatch, ops, steps)
    ctx = {"config": {"keys": KEYS}}
    # 2 steps x 2,000 blocks x 64 x 2 heads x 128 x 2 x 2 B x 2 layers
    # over 4 x 20 ns
    want = 2 * 2000 * 64 * 2 * 128 * 2 * 2 * 2 / 80.0
    assert _reader("sparse_attn_hbm_pct.sat").read(ctx) == pytest.approx(
        100.0 * want / 100.0)
    # 2 steps x 32 rows x 4.19 MB x 6 layers over 2 x 10 ns
    want = 2 * 32 * 4194304 * 6 / 20.0
    assert _reader("lightning_state_hbm_pct.sat").read(ctx) == pytest.approx(
        100.0 * want / 100.0)
    # another family's configuration, the parent's spans: nothing
    for name in ("sparse_attn_hbm_pct.sat", "lightning_state_hbm_pct.sat"):
        assert _reader(name).read({"config": {"keys": {"n_head": 2}}}) is None
    for step in steps:
        step["attrs"] = {"kind": "decode", "kv_tokens": 5}
    for name in ("sparse_attn_hbm_pct.sat", "lightning_state_hbm_pct.sat"):
        assert _reader(name).read(ctx) is None
    monkeypatch.setattr(span_reduce, "load", lambda c: (None, None))
    for name in NEW_READERS[:5]:
        assert _reader(name).read(
            {"config": {"keys": KEYS}, "traffic": {"engine": {
                "length_buckets": [2048], "block_size": 64,
                "num_blocks": 65}}}) is None


def test_prefill_readers_on_a_stand_in_trace(monkeypatch):
    """One prefill run: six scans of the lightning layers (``while`` loops
    that carry the state) and two attention conditionals, a nested one
    inside the second."""
    scan = ("%while.5 = (s32[], f32[1,32,128,128]{3,2,1,0}, bf16[16,1,128,"
            "32,128]) while(%tuple.9), condition=%c, body=%b")
    inner = "%fusion.3 = f32[1,32,128,128]{3,2,1,0} fusion(%x)"
    cond = "%conditional.2 = bf16[1,2048,32,128] conditional(%p, %a, %b)"
    ops = [(scan, 1000.0 + 100 * i, 1050.0 + 100 * i) for i in range(6)]
    ops += [(inner, 1010.0, 1020.0)]                 # inside the first scan
    ops += [(cond, 2000.0, 2100.0), (cond, 2200.0, 2400.0),
            (cond, 2250.0, 2300.0)]                  # nested: counted once
    run = ("jit_minicpm_sala_prefill", 1000.0, 3000.0)
    steps = [{"attrs": {"kind": "prefill_chunk", "tokens": 2048,
                        "tokens_sparse": 1024, "qk_pairs": 9},
              "run": run, "inside": True}]
    _stand_in(monkeypatch, ops, steps)
    ctx = {"config": {"keys": KEYS}}
    flops = 2048 * 32 * 131072 * 6
    assert _reader("lightning_prefill_mxu_pct.sat").read(ctx) == \
        pytest.approx(100.0 * flops / 300.0 / 1e3 / 1.0)
    assert _reader("sparse_prefill_share_pct.sat").read(ctx) == \
        pytest.approx(100.0 * 300.0 / 2000.0)
    # a second run wholly below dense_len took the DENSE branch inside its
    # conditionals: its time is prefill time, its conditionals are not
    # the selecting attention's
    below = ("jit_minicpm_sala_prefill", 4000.0, 5000.0)
    ops += [(cond, 4100.0, 4400.0)]
    steps.append({"attrs": {"kind": "prefill_chunk", "tokens": 2048,
                            "tokens_sparse": 0, "qk_pairs": 9},
                  "run": below, "inside": True})
    _stand_in(monkeypatch, ops, steps)
    assert _reader("sparse_prefill_share_pct.sat").read(ctx) == \
        pytest.approx(100.0 * 300.0 / 3000.0)
    assert _reader("sparse_prefill_share_pct.sat").outermost(
        [(5, 9), (1, 4), (2, 3)]) == [(1, 4), (5, 9)]


def test_select_share_reader_follows_the_leaf_and_the_types():
    reader = _reader("sparse_select_share_pct.sat")
    ops = {
        "%gather.1 = f32[32,832,4,256]{3,2,1,0} gather(f32[2,24577,4,256] "
        "%state__ckeys__, s32[32,832] %t)": {"self_s": 2.0},
        "%fusion.7 = f32[26624,4,256] fusion(f32[2,24577,4,256] %fusion.11, "
        "s32[26624] %r)": {"self_s": 0.5},
        "%fusion.9 = bf16[32,3328,256] fusion(f32[32,3328,256] %b)":
            {"self_s": 0.5},
        "%fusion.8 = bf16[32,4096] fusion(bf16[32,4096] %x)": {"self_s": 5.0},
        "%conditional.2 = bf16[1,2048,32,128] conditional(f32[1,3328,256] "
        "%s)": {"self_s": 9.0},
    }
    ctx = {"trace": {"ops": ops, "busy_s": 20.0},
           "config": {"keys": KEYS},
           "traffic": {"engine": {"length_buckets": [2048, 53248],
                                  "block_size": 64, "num_blocks": 24577}}}
    assert reader.read(ctx) == pytest.approx(100.0 * 3.0 / 20.0)
    ctx["trace"] = {"ops": {"%fusion.8 = bf16[4] fusion(%x)":
                            {"self_s": 5.0}}, "busy_s": 20.0}
    assert reader.read(ctx) is None
    assert reader.read({"config": {"keys": KEYS}}) is None


# ------------------------------------------------------------ the rehearsal


@pytest.mark.timeout(900)
def test_rehearsal_runs_the_cell_end_to_end_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCHMARK_REHEARSAL="1")
    env.pop("BENCH_RUN", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 48), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=880)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert "'ckeys': [2, 257, 4, 32]" in out.stdout  # the executor's report
    assert "compiled or read from the cache INSIDE" not in out.stdout
    # the counters read on the CPU: the rehearsal's dense_len is 64 and its
    # prompts pass it; the trace's readers find no TPU plane and leave
    # their metrics out without raising
    metrics = line["metrics"]
    assert metrics["sparse_rows_pct"]["value"] > 0
    assert 0 < metrics["sparse_kept_pct"]["value"] < 100
    assert metrics["decode_batch_mean"]["value"] > 0
    for name in NEW_READERS[:5]:
        assert name not in metrics
