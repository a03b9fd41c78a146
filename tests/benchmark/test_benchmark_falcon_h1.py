"""The Falcon-H1 reference, the configuration file, the traffic file and the
new cell's readers: the manifest resolves the cell; the configuration holds
the catalog row's numbers unchanged but for the ONE key ``reduced`` names
(the depth); every ``assumed`` entry that is code says its other reading; the
byte counts (4,824.5 M parameters = 9.65 GB, 21.13 MB of state a slot, 2.68
GB of pool, 14.4 GB resident) come out of the file's own widths; the traffic
is the issue's table and fits the buckets and the pool; each new reader's
byte and flop function on hand-worked values, the readers on recorded spans
(a stand-in trace) and nothing (no exception) where the program names or
counts no such thing, as the parent's does not; the cell's ``scope_pct.*``
and ``unnamed`` add up to 100; the controls move the logits; the cell runs
end to end on the CPU at its rehearsal size."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, scope_reduce, span_reduce  # noqa: E402

CELL = "falconh1-worked-answers-saturated"
CONFIG = "falcon-h1-34b-instruct-5l"
TRAFFIC = "worked-answers-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = ("scope_pct.ssd.sat", "ssd_state_hbm_pct.sat",
               "ssd_prefill_mxu_pct.sat")
MULTIPLIERS = (
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers")
KEYS = {"ssm_n_head": 32, "ssm_head_dim": 128, "ssm_d_state": 256,
        "ssm_n_group": 2, "ssm_chunk": 128, "n_layer": 5,
        "dtype": "bfloat16"}


def _reader(name):
    return common.load_layer_metric(name)


def _held():
    return common.load_json(os.path.join(
        ROOT, "benchmark/configs", CONFIG + ".json"))


def _traffic():
    return common.load_json(os.path.join(
        ROOT, "benchmark/traffic", TRAFFIC + ".json"))


# ------------------------------------------------------------ the manifest


def test_manifest_resolves_the_cell():
    manifest = common.load_manifest()
    spec = common.resolve_cell(manifest, CELL)
    assert spec["cell"]["chips"] == 1
    assert (spec["cell"]["config"], spec["cell"]["traffic"]) == (
        CONFIG, TRAFFIC)
    assert len(spec["cell"]["why"]) <= 200
    assert spec["config"]["family"] == "falcon_h1"
    assert [m["name"] for m in spec["end_to_end"]] == [
        "setup_s", "serve_tokens_per_s"]
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW_READERS) <= names
    # every general serving metric that reads the cell truly ...
    general = {m["name"] for m in manifest["per_layer"]
               if "workloads" in m and {"mistral7b-chat-saturated",
                                        "longcat-think-saturated",
                                        "ling-reason-long-saturated"}
               <= set(m["workloads"])
               and not m["name"].startswith(("moe_", "scope_pct.experts",
                                             "scope_pct.moe_move",
                                             "prefill_scope_pct.experts",
                                             "prefill_scope_pct.moe_move"))}
    assert len(general) >= 39 and general <= names
    # ... and none that would read it falsely (PERF.md 7 (e), (am)) or that
    # is another mechanism's
    assert not {n for n in names if n.startswith((
        "paged_attn_hbm_pct", "scope_pct.mixer", "moe_", "latent_", "kda_",
        "window_", "eva_", "sparse_", "block_", "lightning_"))}
    for m in manifest["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
            assert m["source"] == "device_trace"
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sum(w["config"] == CONFIG for w in manifest["workloads"]) == 1
    for name in NEW_READERS:
        assert hasattr(_reader(name), "read")


def test_the_reference_imports_nothing_of_the_program():
    """Plain ``jax.numpy``: the file names ``ray_tpu`` only where it hands
    the harness the program's config class and initialiser; and the program
    imports nothing of it."""
    import ast

    path = os.path.join(ROOT, "benchmark/reference/falcon_h1.py")
    text = open(path).read()
    tree = ast.parse(text)
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    assert sorted(getattr(n, "module", None) or n.names[0].name
                  for n in top) == ["__future__", "jax", "jax.numpy"]
    inner = [n for n in ast.walk(tree)
             if isinstance(n, (ast.Import, ast.ImportFrom)) and n not in top]
    assert {getattr(n, "module", None) or n.names[0].name
            for n in inner} == {"ray_tpu.models.falcon_h1", "numpy"}
    assert {a.name for n in inner for a in n.names} == {
        "FalconH1Config", "falcon_h1_init", "numpy"}
    assert 'default_matmul_precision("highest")' in text
    assert "lax.scan(token" in text  # the recurrence, token by token
    assert "pallas" not in text
    for rel in ("ray_tpu/models/falcon_h1.py", "ray_tpu/ops/ssd.py"):
        assert "benchmark" not in {
            (getattr(n, "module", None) or "").split(".")[0]
            for n in ast.walk(ast.parse(open(os.path.join(ROOT, rel)).read()))
            if isinstance(n, ast.ImportFrom)}


def test_a_control_precision_cuts_both_operands(monkeypatch):
    """``ROUND_TO``: what the reading 'the reference computed in fp8' of
    ``reference_check.tolerance_why`` sets; ``HEAD_ROUND_TO`` cuts the head's
    product alone."""
    import jax.numpy as jnp

    ref = common.load_named("reference", "falcon_h1")
    x = jnp.asarray([[1.03, -2.06]], jnp.float32)
    w = jnp.asarray([[0.33], [1.07]], jnp.float32)
    assert ref.ROUND_TO is None and ref.STATE_ROUND_TO is None \
        and ref.HEAD_ROUND_TO is None
    exact = float(ref._mm(x, w)[0, 0])
    f8 = lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    assert float(ref._mm(x, w, jnp.float8_e4m3fn)[0, 0]) \
        == float((f8(x) @ f8(w))[0, 0]) != exact
    monkeypatch.setattr(ref, "ROUND_TO", jnp.float8_e4m3fn)
    assert float(ref._mm(x, w)[0, 0]) == float((f8(x) @ f8(w))[0, 0])


def test_the_controls_move_the_logits():
    """Every reading of tests/benchmark/control_falcon_h1_readings.py, at
    the tiny preset in float32: each changes the reference's logits far
    past what a second exact run does (0), but ``exact``."""
    import dataclasses
    import importlib.util

    import jax
    import jax.numpy as jnp
    import numpy as np

    spec = importlib.util.spec_from_file_location(
        "control_falcon_h1_readings", os.path.join(
            ROOT, "tests/benchmark/control_falcon_h1_readings.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    ref = common.load_named("reference", "falcon_h1")
    cfg = dataclasses.replace(ref.config_class().tiny(), dtype=jnp.float32)
    params = ref.init_fn()(jax.random.PRNGKey(3), cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.vocab_size, size=(1, 60)))
    positions = jnp.asarray([[20, 59]])
    exact = np.asarray(ref.logits_at(params, tokens, positions, cfg))
    moved = {}
    for reading in control.READINGS:
        with control.changed(ref, reading):
            got = np.asarray(ref.logits_at(params, tokens, positions, cfg))
        moved[reading] = float(np.abs(got - exact).max())
    assert moved.pop("exact") == 0.0
    # a bfloat16 head moves a logit by its own rounding, a bfloat16 state by
    # little at 60 tokens: the smallest two; every other by tenths and more
    assert 1e-4 < moved.pop("head_bf16") < 0.05
    assert 1e-5 < moved.pop("state_bf16") < 0.05
    assert min(moved.values()) > 0.05, moved
    assert ref.ROUND_TO is None and ref.gated_norm.__name__ == "gated_norm"


# ------------------------------------------------- the configuration file


def test_configuration_holds_the_rows_numbers():
    """Every key of the catalog row's ``config`` is in the file, unchanged
    but for the depth; the program's config is built from ``keys``, each of
    which says where it comes from; no width is among the cuts."""
    row = next(json.loads(line) for line in open(CATALOG)
               if json.loads(line)["name"] == "Falcon-H1-34B-Instruct")
    held = _held()
    assert held["source"] == row["source_url"]
    assert list(held["reduced"]) == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in held["reduced"]:
            assert held["reduced"][key]["published"] == value == 72
            assert held[key] == held["reduced"][key]["here"] == 5
        else:
            assert held[key] == value, key
    for key, source in held["keys_from"].items():
        if source in held["reduced"]:
            assert held["keys"][key] == held["reduced"][source]["here"]
        else:
            assert held["keys"][key] == row["config"][source], key
    assert set(held["keys"]) == set(held["keys_from"]) | {"dtype"}
    cfg = common.model_config(held)
    assert (cfg.n_layer, cfg.d_model, cfg.d_mlp, cfg.vocab_size) == (
        5, 5120, 21504, 261120)
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim) == (20, 4, 128)
    assert (cfg.ssm_n_head, cfg.ssm_head_dim, cfg.ssm_d_state,
            cfg.ssm_n_group, cfg.conv_kernel, cfg.ssm_chunk) == (
        32, 128, 256, 2, 4, 128)
    assert (cfg.d_ssm, cfg.conv_width, cfg.d_in_proj) == (
        row["config"]["mamba_d_ssm"], 5120, 9248)
    assert cfg.rope_theta == 1e11 and cfg.norm_eps == 1e-5
    # the fourteen muP numbers, each the row's
    for name in MULTIPLIERS:
        value = getattr(cfg, name)
        want = row["config"][name]
        assert (list(value) if isinstance(value, tuple) else value) == want
    assert sum(len(v) if isinstance(v, list) else 1 for v in (
        row["config"][n] for n in MULTIPLIERS)) == 14


def test_reduced_and_assumed_entries_say_what_and_what_else():
    held = _held()
    entry = held["reduced"]["num_hidden_layers"]
    assert entry["published"] == 72 and entry["here"] == 5
    assert "14.4 GB" in entry["why"] and "six layers" in entry["why"]
    assumed = held["assumed"]
    assert {"gated_norm", "multipliers_where", "mamba_use_mlp",
            "time_step_limit", "state_dtype", "dtype", "rope",
            "weights"} <= set(assumed)
    for key in ("gated_norm", "multipliers_where", "state_dtype"):
        assert "other reading" in assumed[key].lower(), key
    for key in ("gated_norm", "multipliers_where"):
        assert "one function" in assumed[key].lower(), key
    assert "W_head 5120^-0.5 / lm_head_multiplier = 1.789" in \
        assumed["weights"]
    assert "33.6 B parameters = 67.3 GB" in held["deployment"]
    assert "22% of a decode step's bytes here and 4%" in held["deployment"]
    assert "TO BE SET" not in held["reference_check"]["tolerance_why"]


def test_the_files_widths_give_the_byte_counts():
    """4,824.5 M parameters = 9.65 GB of bf16, 21.13 MB of state a slot,
    2.68 GB of pool and 14.4 GB resident, from the file's own widths."""
    c = _held()
    D, F, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    Hq, Hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    H, P, N, G = (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
                  c["mamba_n_groups"])
    taps, L = c["mamba_d_conv"], c["num_hidden_layers"]
    assert H * P == c["mamba_d_ssm"] == 4096
    attention = D * Hq * hd + 2 * D * Hkv * hd + Hq * hd * D
    w_in = D * (2 * H * P + 2 * G * N + H)
    small = taps * (H * P + 2 * G * N) + (H * P + 2 * G * N) + 3 * H + H * P
    ssm = w_in + H * P * D + small
    ffn = 3 * D * F
    assert round(attention / 1e6, 2) == 31.46
    assert round(w_in / 1e6, 2) == 47.35 and round(small / 1e6, 2) == 0.03
    assert round(ssm / 1e6, 2) == 68.35 and round(ffn / 1e6, 2) == 330.30
    layer = attention + ssm + ffn + 2 * D
    assert round(layer / 1e6, 1) == 430.1 and round(2 * layer / 1e6) == 860
    ends = 2 * V * D
    assert round(ends / 1e6, 1) == 2673.9
    total = L * layer + ends + D
    assert round(total / 1e6, 1) == 4824.5
    assert round(2 * total / 1e9, 2) == 9.65
    assert "4,824.5 M parameters = 9.65 GB" in c["bytes"]["total"]
    # the whole model by the same equations: 67.3 GB (ISSUE 58 says 69.3)
    assert round(2 * (72 * layer + ends) / 1e9, 1) == 67.3
    import jax

    ref = common.load_named("reference", "falcon_h1")
    cfg = common.model_config(c)
    shapes = jax.eval_shape(
        lambda: ref.init_fn()(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree.leaves(shapes)
    assert sum(x.size for x in leaves) == total
    assert all(str(x.dtype) == "bfloat16" for x in leaves if x.ndim >= 2
               and x.shape[0] > taps)
    engine = _traffic()["engine"]
    slot = L * (H * P * N * 4 + (taps - 1) * (H * P + 2 * G * N) * 2)
    from ray_tpu.models.falcon_h1 import falcon_h1_init_state

    one = jax.eval_shape(lambda: falcon_h1_init_state(cfg, 1))
    assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(one)) \
        == slot == 5 * (4194304 + 30720)
    state = (engine["max_batch_size"] + 1) * slot
    assert round(state / 1e9, 2) == 2.05
    token = L * 2 * Hkv * hd * 2
    assert token == 10240
    pool = engine["num_blocks"] * engine["block_size"] * token
    assert round(pool / 1e9, 2) == 2.68
    assert round((2 * total + state + pool) / 1e9, 1) == 14.4
    assert "21.13 MB a slot" in c["bytes"]["state"]
    # a decode step's bytes at 96 rows and ~1.1 k of context: the issue's
    step = (96 * L * H * P * N * 4 * 2, 96 * 1100 * token,
            L * 2 * (attention + ssm), 2 * L * ffn, V * D * 2)
    assert [round(b / 1e9, 2) for b in step] == [4.03, 1.08, 1.0, 3.3, 2.67]
    assert round(sum(step) / 1e9, 1) == 12.1
    assert round(sum(step) / 819e9 * 1e3, 1) == 14.8


def test_traffic_is_the_issues_table_and_fits_buckets_and_pool():
    t = _traffic()
    assert (t["runner"], t["generator"]) == (
        "serve_engine", "lognormal_chat_ordered")
    assert t["arrivals"] == {"mode": "closed", "clients": 96}
    assert t["prompt_len"] == {"median": 384, "sigma": 0.8, "min": 64,
                               "max": 2048}
    assert t["output_len"] == {"median": 1024, "sigma": 0.6, "min": 256,
                               "max": 4096}
    # the lengths of think-closed: cell 10 and this cell differ by the model
    think = common.load_json(os.path.join(
        ROOT, "benchmark/traffic/think-closed.json"))
    for key in ("prompt_len", "output_len", "arrivals", "sampling"):
        assert t[key] == think[key], key
    assert t["sampling"] == {"temperature": 0.0}
    e = t["engine"]
    assert (e["max_batch_size"], e["block_size"], e["max_prefill_batch"]) \
        == (96, 16, 1)
    assert e["prefill_chunk_tokens"] in (1024, 2048)
    assert e["prefill_chunk_tokens"] % 128 == 0
    assert e["length_buckets"] == [e["prefill_chunk_tokens"], 2048 + 4096]
    assert e["batch_buckets"] == [1, 16, 96]
    assert 16384 <= e["num_blocks"] <= 16386
    assert t["window"]["trace_s"] == 8.0
    n = t["strata"]
    assert n & (n - 1) == 0  # the ordered generator's: a power of two
    for key in ("strata_why", "window_why", "warmup_why"):
        assert "TBD" not in t[key] and len(t[key]) > 100, key
    assert set(t["engine_why"]) >= {
        "num_blocks", "max_batch_size", "prefill_chunk_tokens",
        "length_buckets", "batch_buckets"}
    build = common.load_named("generators", t["generator"]).build
    vocab = _held()["vocab_size"]
    sched, other = build(t, 3000000007, vocab), build(t, 11, vocab)
    # one order for every seed; the seed picks the token ids alone
    assert [sched.lengths(i) for i in range(2 * n)] == [
        other.lengths(i) for i in range(2 * n)]
    d = sched.describe()
    assert d["prompt_len"]["min"] >= 64 and d["prompt_len"]["max"] <= 2048
    assert d["output_len"]["min"] >= 256 and d["output_len"]["max"] <= 4096
    assert 1100 < d["output_len"]["mean"] < 1300
    req = sched.request(5)
    assert 1 <= int(req["prompt"].min())
    assert int(req["prompt"].max()) < vocab
    assert int(sched.request(6)["prompt"].max()) > 2 ** 16  # the WHOLE of it
    # the longest request fits the upper bucket, and 96 rows' reservations
    # the pool with room: no request waits for blocks
    assert 2048 + 4096 <= e["length_buckets"][-1]
    blocks = [-(-(p + o) // 16) for p, o in (
        sched.lengths(i) for i in range(n))]
    mean_reserved = sum(blocks) / n
    assert 96 * mean_reserved < 0.75 * (e["num_blocks"] - 1)
    assert 96 * max(blocks) < 2.3 * (e["num_blocks"] - 1)


def test_reference_check_fits_what_the_engine_is_built_for():
    spec = common.resolve_cell(common.load_manifest(), CELL)
    chk, traffic = spec["config"]["reference_check"], spec["traffic"]
    lens = chk["prompt_tokens"][: chk["requests"]]
    assert len(lens) == chk["requests"] == len(set(lens)) == 8
    assert min(lens) <= 300 and max(lens) >= 5000
    top = traffic["engine"]["length_buckets"][-1]
    # two cross the longest bucket's half; every one crosses a piece's edge
    assert sum(n > top // 2 for n in lens) >= 2
    assert all(n > 128 and n % 128 for n in lens)
    assert sum(n > traffic["engine"]["prefill_chunk_tokens"]
               for n in lens) >= 4
    assert chk["every"] == 1 and chk["new_tokens"] == 32
    assert max(lens) + chk["new_tokens"] <= chk["pad_to"] <= top
    # the checked logits stay under 0.3 GB
    assert chk["requests"] * chk["new_tokens"] * 261120 * 4 < 0.3e9
    assert chk["requests"] <= traffic["engine"]["batch_buckets"][1]
    assert 0 < chk["tolerance_logit"]


# -------------------------------------------------------------- the readers


def test_byte_and_flop_functions_on_hand_numbers():
    from ray_tpu.ops import ssd

    state = _reader("ssd_state_hbm_pct.sat")
    # a row a layer: 32 heads x (2 x 131,072 B of state + 256 B of x + 4 B
    # of dt + 256 B of output) + 2 groups x 2 x 1,024 B of B and C
    row = 32 * (262144 + 256 + 4 + 256) + 4096
    assert state.ssd_step_bytes(1, 32, 128, 256, 2, 1) == row == 8409216
    assert state.ssd_step_bytes(96, 32, 128, 256, 2, 5) == 96 * 5 * row
    assert state.ssd_step_bytes(96, 32, 128, 256, 2, 5) \
        == 5 * ssd.step_bytes(96, 32, 128, 256, 2)
    widths = state.widths_of(KEYS)
    assert widths == {"n_head": 32, "head_dim": 128, "d_state": 256,
                      "n_group": 2, "n_layer": 5}
    prefill = _reader("ssd_prefill_mxu_pct.sat")
    token = 2 * 128 * 256 + 32 * (128 * 128 + 2 * 128 * 256)
    assert prefill.ssd_chunk_flops(1, 32, 128, 256, 2, 128, 1) == 2 * token
    assert prefill.ssd_chunk_flops(1024, 32, 128, 256, 2, 128, 5) \
        == 5 * ssd.chunk_flops(1024, 32, 128, 256, 2)
    assert prefill.widths_of(KEYS)["piece"] == 128
    # the file's own keys give the same widths
    assert state.widths_of(_held()["keys"]) == widths


def _stand_in(monkeypatch, ops, steps):
    monkeypatch.setattr(span_reduce, "load", lambda c: (
        {"planes": [{"ops": ops}]}, {"steps": steps}))
    monkeypatch.setattr(common, "peaks_for", lambda kind: {
        "hbm_gb_per_s": 100.0, "bf16_tflops": 1.0})


def test_decode_reader_on_recorded_spans(monkeypatch):
    """Two decode runs paired with their dispatch spans: five ``ssd_step``
    calls (a layer each) and five paged calls a run."""
    kernel = "%ssd_step.3 = (bf16[96,32,128], f32[5,97,32,128,256]) " \
        "custom-call(%x)"
    paged = "%paged_attention.4 = bf16[96,20,128] custom-call(%q)"
    other = "%fusion.9 = bf16[96,5120] fusion(bf16[96,5120] %x)"
    ops = []
    for base in (100.0, 1100.0):
        ops += [(kernel, base + 20 * i, base + 20 * i + 8) for i in range(5)]
        ops += [(paged, base + 20 * i + 10, base + 20 * i + 14)
                for i in range(5)]
        ops += [(other, base + 300, base + 900)]
    runs = [("jit_falcon_h1_decode_step", 100.0, 1100.0),
            ("jit_falcon_h1_decode_step", 1100.0, 2100.0)]
    steps = [{"attrs": {"kind": "decode", "rows": 96, "kv_tokens": 100000,
                        "state_mb": 4026.5}, "run": run, "inside": True}
             for run in runs]
    _stand_in(monkeypatch, ops, steps)
    ctx = {"config": {"keys": KEYS}}
    # 2 steps x 96 rows x 5 layers x 8,409,216 B over 10 x 8 ns
    want = 2 * 96 * 5 * 8409216 / 80.0
    assert _reader("ssd_state_hbm_pct.sat").read(ctx) == pytest.approx(
        100.0 * want / 100.0)
    # another family's configuration, the parent's spans: nothing
    for name in NEW_READERS[1:]:
        assert _reader(name).read({"config": {"keys": {"n_head": 2}}}) is None
    # the xla backend's steps hold no call of that name: nothing
    _stand_in(monkeypatch, [op for op in ops if "ssd_step" not in op[0]],
              steps)
    assert _reader("ssd_state_hbm_pct.sat").read(ctx) is None
    _stand_in(monkeypatch, ops, steps)
    for step in steps:
        step["attrs"] = {"kind": "decode"}
    assert _reader("ssd_state_hbm_pct.sat").read(ctx) is None
    monkeypatch.setattr(span_reduce, "load", lambda c: (None, None))
    for name in NEW_READERS[1:]:
        assert _reader(name).read(ctx) is None


def _table(by):
    busy = sum(s for row in by.values() for s in row.values())
    return {"busy_s": busy, "by": by, "runs": {}, "mixed_s": 0.0,
            "unmatched": {}, "programs": {}}


def test_prefill_reader_reads_the_scope_table(monkeypatch):
    """While the chunked form is XLA's its time is the scope ``ssd_chunk``
    of the prefill programs; once a kernel of that name is in the trace,
    its calls inside the prefill runs."""
    run = ("jit_falcon_h1_prefill", 1000.0, 3000.0)
    steps = [{"attrs": {"kind": "prefill_chunk", "tokens": 1000,
                        "ssd_pieces": 8}, "run": run, "inside": True},
             {"attrs": {"kind": "decode", "rows": 96},
              "run": ("jit_falcon_h1_decode_step", 3000.0, 4000.0),
              "inside": True}]
    _stand_in(monkeypatch, [("%fusion.1 = f32[2] fusion(%x)", 1000.0, 1500.0)],
              steps)
    table = _table({"prefill": {"ssd_chunk": 2e-7, "attn_proj": 5e-7},
                    "decode": {"ssd_step": 3e-7, "ssd_chunk": 9.0}})
    ctx = {"config": {"keys": KEYS}, "scope_table": table}
    flops = 1000 * 2 * 2686976 * 5
    assert _reader("ssd_prefill_mxu_pct.sat").read(ctx) == pytest.approx(
        100.0 * flops / 2e-7 / 1e12 / 1.0)
    named = "%ssd_chunk.2 = bf16[1,1024,32,128] custom-call(%q)"
    _stand_in(monkeypatch, [(named, 1100.0 + 100 * i, 1150.0 + 100 * i)
                            for i in range(5)], steps)
    assert _reader("ssd_prefill_mxu_pct.sat").read(ctx) == pytest.approx(
        100.0 * flops / 250e-9 / 1e12 / 1.0)
    # no table (the parent's program names no such scope): nothing
    _stand_in(monkeypatch, [], steps)
    assert _reader("ssd_prefill_mxu_pct.sat").read(
        {"config": {"keys": KEYS}, "scope_table": None}) is None
    assert _reader("ssd_prefill_mxu_pct.sat").read(
        {"config": {"keys": KEYS},
         "scope_table": _table({"prefill": {"attn_proj": 1.0}})}) is None


def test_the_cells_scope_shares_add_up_to_100():
    """``GROUPS["mixer"]`` does not hold the ``ssd_*`` names, so the cell
    reports ``scope_pct.ssd`` in its place: with it the cell's
    ``scope_pct.*`` and ``unnamed`` cover the table."""
    from ray_tpu.serve.llm import obs

    ssd = _reader("scope_pct.ssd.sat")
    assert set(ssd.SCOPES) <= set(obs.SCOPES)
    assert not set(ssd.SCOPES) & {
        s for g in scope_reduce.GROUPS.values() for s in g}
    by = {"decode": {"ssd_step": 30.0, "ssd_conv": 2.0, "ssd_proj": 6.0,
                     "ssd_out": 0.4, "attn_kernel": 9.0, "attn_cache": 2.0,
                     "attn_proj": 5.0, "ffn": 22.0, "head": 15.0,
                     "sample": 1.5, "embed": 0.1, "unnamed": 0.5},
          "prefill": {"ssd_chunk": 1.0, "ssd_proj": 0.8, "attn_proj": 0.5,
                      "ffn": 3.0, "attn_kernel": 0.5, "ssd_conv": 0.1,
                      "ssd_out": 0.1, "head": 0.5}}
    ctx = {"scope_table": _table(by)}
    spec = common.resolve_cell(common.load_manifest(), CELL)
    shares = [m["name"] for m in spec["per_layer"]
              if m["name"].startswith("scope_pct.")]
    assert sorted(shares) == [
        "scope_pct.attn.sat", "scope_pct.attn_proj.sat", "scope_pct.ffn.sat",
        "scope_pct.head.sat", "scope_pct.ssd.sat"]
    total = sum(_reader(name).read(ctx) for name in shares)
    unnamed = 100.0 - _reader("scope_named_pct.sat").read(ctx)
    assert total + unnamed == pytest.approx(100.0)
    busy = sum(s for row in by.values() for s in row.values())
    assert ssd.read(ctx) == pytest.approx(
        100.0 * (30.0 + 2.0 + 6.0 + 0.4 + 1.0 + 0.8 + 0.1 + 0.1) / busy)
    # under the floor, or a table without the names (the parent): nothing
    assert ssd.read({"scope_table": _table(
        {"decode": {"ssd_step": 1.0, "unnamed": 1.0}})}) is None
    assert ssd.read({"scope_table": _table(
        {"decode": {"attn_proj": 1.0}})}) is None
    assert ssd.read({"scope_table": None}) is None


# ------------------------------------------------------------ the rehearsal


@pytest.mark.timeout(900)
def test_rehearsal_runs_the_cell_end_to_end_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCHMARK_REHEARSAL="1")
    env.pop("BENCH_RUN", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 58), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=880)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # the executor's report: a state slot AND pages in every layer
    assert "'ssd': [2, 5, 4, 16, 32]" in out.stdout
    assert "'kv_layers': 2" in out.stdout
    assert "compiled or read from the cache INSIDE" not in out.stdout
    metrics = line["metrics"]
    assert metrics["decode_batch_mean"]["value"] > 0
    assert metrics["setup_programs"]["value"] == 9
    # the trace's readers find no TPU plane and leave their metrics out
    # without raising
    for name in NEW_READERS:
        assert name not in metrics
