"""The Ling-3.0-flash reference, the configuration file, the traffic file and
the new cell's readers: the manifest resolves the cell; the configuration
holds the catalog row's numbers unchanged but for the five keys ``reduced``
names, every ``reduced`` / ``assumed`` entry says what was published, what
is here and the other reading; the byte counts (2,866 M parameters, 13.03
MB of state a slot, 1.51 GB of pool) come out of the file's own widths; the
traffic is the issue's table; each new reader's byte and flop function on
hand-worked values, the readers on a stand-in trace and on recorded
counters, and nothing (no exception) where the program names or counts no
such thing, as the parent's does not; the cell's ``scope_pct.*`` and
``unnamed`` add up to 100; the cell runs end to end on the CPU at its
rehearsal size."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, scope_reduce, span_reduce  # noqa: E402

CELL = "ling-reason-long-saturated"
CONFIG = "ling-3.0-flash-ep8-7l"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["first_k_dense_replace", "num_experts", "num_hidden_layers",
           "num_nextn_predict_layers", "vocab_size"]
ASSUMED = ("kda_gate", "kda_projections", "gate_head_wise", "kda_rope",
           "qk_norm", "output_norm")
NEW_READERS = ("scope_pct.kda.sat", "kda_state_hbm_pct.sat",
               "kda_prefill_mxu_pct.sat", "latent_attn_kvl_hbm_pct.sat",
               "moe_groups_held_pct", "kda_state_gb")
LAYERS = ["kda", "kda", "kda", "kda", "latent", "kda", "kda"]
KEYS = {"kda_n_head": 32, "kda_head_dim": 128, "layer_types": LAYERS,
        "kv_lora_rank": 512, "qk_rope_head_dim": 64, "dtype": "bfloat16"}


def _reader(name):
    return common.load_layer_metric(name)


def _held():
    return common.load_json(os.path.join(
        ROOT, "benchmark/configs", CONFIG + ".json"))


class _Cfg:
    n_kv_layer = 1


# ------------------------------------------------------------ the manifest


def test_manifest_resolves_the_cell():
    spec = common.resolve_cell(common.load_manifest(), CELL)
    assert spec["cell"]["chips"] == 1
    assert (spec["cell"]["config"], spec["cell"]["traffic"]) == (
        CONFIG, "reason-long-closed")
    assert spec["config"]["family"] == "ling_hybrid"
    assert [m["name"] for m in spec["end_to_end"]] == [
        "setup_s", "serve_tokens_per_s"]
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW_READERS) <= names
    assert {"decode_batch_mean", "kv_high_water_pct", "decode_step_ms.sat",
            "hbm_peak_gb.serve", "prefill_device_share_pct.sat",
            "moe_pairs_held_pct", "moe_gmm_hbm_pct.sat",
            "scope_pct.experts.sat", "scope_pct.attn.sat",
            "latent_absorb_share_pct.sat", "setup_programs"} <= names
    # readers that multiply by keys["n_layer"] (a seventh of the layers
    # cache a latent row here) and the fixed "mixer" group: PERF.md 7 (z)
    assert not {"latent_attn_hbm_pct.sat", "latent_prefill_mxu_pct.sat",
                "scope_pct.mixer.sat"} & names
    manifest = common.load_manifest()
    for m in manifest["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == REDUCED
    assert len(manifest["workloads"]) >= 12
    for name in NEW_READERS:
        assert hasattr(_reader(name), "read")


def test_the_reference_imports_nothing_of_the_program():
    """Plain ``jax.numpy``: the file names ``ray_tpu`` only where it hands
    the harness the program's config class and initialiser; and the program
    imports nothing of it."""
    import ast

    path = os.path.join(ROOT, "benchmark/reference/ling_hybrid.py")
    text = open(path).read()
    tree = ast.parse(text)
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    assert sorted(getattr(n, "module", None) or n.names[0].name
                  for n in top) == ["__future__", "jax", "jax.numpy"]
    inner = [n for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom) and n not in top]
    assert {n.module for n in inner} == {"ray_tpu.models.ling_hybrid"}
    assert {a.name for n in inner for a in n.names} == {
        "LingHybridConfig", "ling_hybrid_init"}
    assert 'default_matmul_precision("highest")' in text
    assert "lax.scan(token" in text  # the recurrence, token by token
    for rel in ("ray_tpu/models/ling_hybrid.py", "ray_tpu/ops/kda.py"):
        assert "benchmark" not in {
            (getattr(n, "module", None) or "").split(".")[0]
            for n in ast.walk(ast.parse(open(os.path.join(ROOT, rel)).read()))
            if isinstance(n, ast.ImportFrom)}


def test_a_control_precision_cuts_both_operands(monkeypatch):
    """``ROUND_TO``: what the reading 'the reference computed in fp8' of
    ``reference_check.tolerance_why`` sets (benchmark/reference/
    pangu_ultra_moe.py's control, its test)."""
    import jax.numpy as jnp

    ref = common.load_named("reference", "ling_hybrid")
    x = jnp.asarray([[1.03, -2.06]], jnp.float32)
    w = jnp.asarray([[0.33], [1.07]], jnp.float32)
    assert ref.ROUND_TO is None and ref.STATE_ROUND_TO is None
    exact = float(ref._mm(x, w)[0, 0])
    monkeypatch.setattr(ref, "ROUND_TO", jnp.float8_e4m3fn)
    cut = float(ref._mm(x, w)[0, 0])
    f8 = lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    assert cut == float((f8(x) @ f8(w))[0, 0]) and cut != exact


def test_the_controls_move_the_logits(monkeypatch):
    """The two controls the tolerance's readings were taken with, re-run
    from the tree at the tiny preset: a KDA state rounded to bfloat16 after
    every token (``STATE_ROUND_TO``) and fp8 operands (``ROUND_TO``) each
    move the reference's logits far past what a second exact run does (at
    this size a flipped expert choice moves a logit by 1 and more under
    either; the chip's readings, at published widths, are in
    ``reference_check.tolerance_why``)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = common.load_named("reference", "ling_hybrid")
    cfg = dataclasses.replace(ref.config_class().tiny(), dtype=jnp.float32)
    params = ref.init_fn()(jax.random.PRNGKey(2), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(3), (1, 48), 1, cfg.vocab_size)
    exact = np.asarray(ref.logits(params, tokens, cfg))
    again = np.asarray(ref.logits(params, tokens, cfg))
    monkeypatch.setattr(ref, "STATE_ROUND_TO", jnp.bfloat16)
    state = np.abs(np.asarray(ref.logits(params, tokens, cfg)) - exact).max()
    monkeypatch.setattr(ref, "STATE_ROUND_TO", None)
    monkeypatch.setattr(ref, "ROUND_TO", jnp.float8_e4m3fn)
    fp8 = np.abs(np.asarray(ref.logits(params, tokens, cfg)) - exact).max()
    assert np.abs(again - exact).max() < 1e-5
    assert state > 1e-3 and fp8 > 1e-3


# ------------------------------------------------- the configuration file


def test_configuration_holds_the_rows_numbers():
    """Every key of the catalog row's ``config`` is in the file, unchanged
    but for the five ``reduced`` names; the program's config is built from
    ``keys``, each of which says where it comes from; no width is among the
    cuts."""
    row = next(json.loads(line) for line in open(CATALOG)
               if json.loads(line)["name"] == "Ling-3.0-flash")
    held = _held()
    assert held["source"] == row["source_url"]
    assert sorted(held["reduced"]) == REDUCED
    for key, value in row["config"].items():
        if key in held["reduced"]:
            assert held["reduced"][key]["published"] == value
            assert held[key] == held["reduced"][key]["here"] != value
        else:
            assert held[key] == value, key
    for key, source in held["keys_from"].items():
        if source in held["reduced"]:
            assert held["keys"][key] == held["reduced"][source]["here"]
        else:
            assert held["keys"][key] == row["config"][source], key
    assert set(held["keys"]) == set(held["keys_from"]) | set(
        held["keys_derived"])
    cfg = common.model_config(held)
    assert (cfg.n_layer, cfg.n_kv_layer, cfg.n_kda_layer) == (7, 1, 6)
    assert list(cfg.layer_types) == LAYERS
    # published layers 0, 2..7: latent iff (l + 1) % 6 == 0
    assert LAYERS == ["latent" if (i + 1) % 6 == 0 else "kda"
                      for i in (0, 2, 3, 4, 5, 6, 7)]
    assert (cfg.num_experts, cfg.n_held, cfg.experts_held) == (
        512, 64, (0, 64))
    assert (cfg.n_group, cfg.topk_group, cfg.top_k) == (8, 4, 8)
    assert cfg.groups_held == (0,)
    assert (cfg.d_model, cfg.d_mlp, cfg.d_expert, cfg.vocab_size) == (
        2560, 6144, 768, 19648)
    # the held layers' clamps are all 0, as published below layer 34
    for l in (0, 2, 3, 4, 5, 6, 7):
        assert held["expert_swiglu_limit_list"][l] == 0
        assert held["share_expert_swiglu_limit_list"][l] == 0
    assert all(pair == (0.0, 0.0) for pair in cfg.swiglu_limits)


def test_reduced_and_assumed_entries_say_what_and_what_else():
    held = _held()
    for key, entry in held["reduced"].items():
        assert entry["published"] != entry["here"] and entry["why"], key
    assert "10.46 GB" in held["reduced"]["num_experts"]["why"]
    assumed = held["assumed"]
    assert set(ASSUMED) <= set(assumed)
    for key in ASSUMED:
        assert "other reading" in assumed[key].lower(), key
        assert "one function" in assumed[key].lower(), key
    assert "std 0.05" in assumed["weights"]
    assert "4 pipeline stages x 8 chips" in held["deployment"]
    assert "124.4 B" in held["deployment"]


def test_the_files_widths_give_the_byte_counts():
    """2,866 M parameters = 5.73 GB of bf16, 13.03 MB of state a slot and
    1.51 GB of pool, from the file's own widths (norm scales and the gates'
    small vectors, 0.05 M, left out as the file's ``bytes`` leave them)."""
    c = _held()
    D, M, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    H, hd = c["num_attention_heads"], c["head_dim"]
    F, E = c["moe_intermediate_size"], c["num_experts"]
    C, N, R, Vh = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                   c["qk_rope_head_dim"], c["v_head_dim"])
    taps = c["short_conv_kernel_size"]
    kda = 3 * D * H * hd + 3 * D * H * hd + D * H + taps * 3 * H * hd
    latent = D * H * (N + R) + D * (C + R) + C * H * (N + Vh) + D * H \
        + H * Vh * D
    expert = 3 * D * F
    sparse = D * 512 + expert + E * expert
    dense = 3 * D * M
    assert round(kda / 1e6, 2) == 63.05 and round(latent / 1e6, 2) == 31.97
    assert round((kda + dense) / 1e6, 1) == 110.2
    assert round((kda + sparse) / 1e6, 1) == 447.7
    assert round((latent + sparse) / 1e6, 1) == 416.7
    total = (kda + dense) + 5 * (kda + sparse) + (latent + sparse) + 2 * V * D
    assert round(total / 1e6) == 2866 and round(2 * total / 1e9, 2) == 5.73
    assert "2,866 M parameters = 5.73 GB" in c["bytes"]["total"]
    # the whole model by the same equations: 124.4 B
    whole = 2 * (kda + dense) + 33 * (kda + D * 512 + 513 * expert) \
        + 7 * (latent + D * 512 + 513 * expert) + 2 * 157184 * D
    assert round(whole / 1e9, 1) == 124.4
    import jax

    ref = common.load_named("reference", "ling_hybrid")
    cfg = common.model_config(c)
    shapes = jax.eval_shape(
        lambda: ref.init_fn()(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree.leaves(shapes)
    assert sum(x.size for x in leaves if x.ndim >= 2) == total
    assert all(str(x.dtype) == "bfloat16" for x in leaves if x.ndim >= 2)
    from ray_tpu.models.ling_hybrid import slot_state_bytes

    engine = common.load_json(os.path.join(
        ROOT, "benchmark/traffic/reason-long-closed.json"))["engine"]
    slot = 6 * (H * hd * hd * 4 + (taps - 1) * 3 * H * hd * 2)
    assert slot_state_bytes(cfg) == slot == 13025280
    assert round((engine["max_batch_size"] + 1) * slot / 1e9, 2) == 1.68
    stored = sum(p[2] for p in cfg.kv_planes) * 2          # 1,280 B a token
    assert stored == 1280 and sum(p[1] for p in cfg.kv_planes) * 2 == 1152
    pool = engine["num_blocks"] * engine["block_size"] * stored
    assert round(pool / 1e9, 2) == 1.51
    assert "13.03 MB a slot" in c["bytes"]["state"]


def test_traffic_is_the_issues_table():
    t = common.load_json(os.path.join(
        ROOT, "benchmark/traffic/reason-long-closed.json"))
    # the issue's traffic letter for letter: its generator, a seeded order
    assert (t["runner"], t["generator"]) == ("serve_engine", "lognormal_chat")
    assert "order" not in t
    assert t["arrivals"] == {"mode": "closed", "clients": 128}
    assert t["prompt_len"] == {"median": 2048, "sigma": 1.2, "min": 256,
                               "max": 32768}
    assert t["output_len"] == {"median": 2048, "sigma": 0.6, "min": 512,
                               "max": 8192}
    assert t["sampling"] == {"temperature": 0.0}
    e = t["engine"]
    assert (e["max_batch_size"], e["block_size"], e["prefill_chunk_tokens"],
            e["max_prefill_batch"]) == (128, 16, 2048, 1)
    assert t["window"]["trace_s"] == 8.0
    assert e["length_buckets"][-1] == 32768 + 8192
    assert e["length_buckets"][0] == e["prefill_chunk_tokens"]
    assert e["batch_buckets"][-1] == e["max_batch_size"]
    # 4% of prompts past 16 k
    from statistics import NormalDist
    import math

    past = 1 - NormalDist().cdf(math.log(16384 / 2048) / 1.2)
    assert 0.035 < past < 0.045
    for key in ("strata_why", "spread_why", "window_why", "warmup_why"):
        assert "TO BE SET" not in t[key], key
    build = common.load_named("generators", t["generator"]).build
    sched, other = build(t, 3000000007, 19648), build(t, 11, 19648)
    # every seed the same sizes a block, each once, in an order of its own
    n = t["strata"]
    assert 128 % n == 0  # the 128 first requests are whole blocks
    for b in range(3):
        block = [sched.lengths(i) for i in range(b * n, (b + 1) * n)]
        assert sorted(p for p, _ in block) == sorted(sched.prompts)
        assert sorted(o for _, o in block) == sorted(sched.outputs)
    assert [sched.lengths(i) for i in range(n)] != [
        other.lengths(i) for i in range(n)]
    # the tail of long documents is drawn: a prompt past 16 k every block
    assert 1 <= sum(p > 16384 for p in sched.prompts) <= n // 8
    d = sched.describe()
    assert d["prompt_len"]["min"] >= 256 and d["prompt_len"]["max"] <= 32768
    assert d["output_len"]["min"] >= 512 and d["output_len"]["max"] <= 8192
    req = sched.request(5)
    assert 1 <= int(req["prompt"].min()) and int(req["prompt"].max()) < 19648


def test_reference_check_fits_what_the_engine_is_built_for():
    spec = common.resolve_cell(common.load_manifest(), CELL)
    chk, traffic = spec["config"]["reference_check"], spec["traffic"]
    lens = chk["prompt_tokens"][: chk["requests"]]
    assert len(lens) == chk["requests"] == len(set(lens)) == 16
    assert min(lens) <= 300 and max(lens) >= 6000
    chunk = traffic["engine"]["prefill_chunk_tokens"]
    # three chunks against a resident state and latent context are inside
    assert sum(n > 2 * chunk for n in lens) >= 4
    assert sum(n <= chunk for n in lens) >= 4
    assert chk["every"] == 1 and chk["new_tokens"] == 64
    assert max(lens) + chk["new_tokens"] <= chk["pad_to"]
    assert chk["pad_to"] <= traffic["engine"]["length_buckets"][-1]
    assert 0 < chk["tolerance_logit"]
    assert "TO BE SET" not in chk["tolerance_why"]


# -------------------------------------------------------------- the readers


def test_byte_and_flop_functions_on_hand_numbers():
    state = _reader("kda_state_hbm_pct.sat")
    # a row a layer: 32 heads x (2 x 65,536 B of state + 4 x 512 B of
    # columns + 512 B of value + 256 B of output)
    assert state.kda_step_bytes(1, 32, 128, 1) == 32 * (131072 + 2048 + 768)
    assert state.kda_step_bytes(128, 32, 128, 6) == 128 * 6 * 32 * 133888
    assert state.widths_of(KEYS) == {"n_head": 32, "head_dim": 128,
                                     "n_layer": 6}
    chunk = _reader("kda_prefill_mxu_pct.sat")
    # a token a head: 2 x (16,384 + 4,096 + 16,384 + 49,152 + 8,192)
    assert chunk.kda_chunk_flops(1, 1, 128, 64, 1) == 188416
    assert chunk.kda_chunk_flops(2048, 32, 128, 64, 6) == \
        2048 * 32 * 188416 * 6
    from ray_tpu.ops import kda

    # the readers' own counts are the program's (the yardstick is written
    # out in the reader so that it does not move with the program)
    assert kda.step_bytes(7, 32, 128, 128) * 6 == \
        state.kda_step_bytes(7, 32, 128, 6)
    assert kda.chunk_flops(2048, 32, 128, 128) * 6 == \
        chunk.kda_chunk_flops(2048, 32, 128, kda.CHUNK, 6)
    latent = _reader("latent_attn_kvl_hbm_pct.sat")
    # 1,152 B a token in the ONE layer that caches a row
    assert latent.latent_attn_bytes(1000, 512, 64, 2, 1) == 1152000
    assert latent.widths_of(KEYS, _Cfg)["n_kv_layer"] == 1


def test_counter_readers_on_recorded_counters():
    before = {"moe_groups_held": 100, "moe_tokens_routed": 200,
              "state_bytes": 1680262200}
    after = {"moe_groups_held": 5100, "moe_tokens_routed": 10200,
             "state_bytes": 1680262200}
    ctx = {"stats_before": before, "stats_after": after}
    assert _reader("moe_groups_held_pct").read(ctx) == pytest.approx(50.0)
    assert _reader("kda_state_gb").read(ctx) == pytest.approx(1.6802622)
    # the parent's stats have no such keys: nothing, and no exception
    for name in ("moe_groups_held_pct", "kda_state_gb"):
        assert _reader(name).read({"stats_before": {}, "stats_after": {}}) \
            is None
        assert _reader(name).read({}) is None
    assert _reader("kda_state_gb").read(
        {"stats_after": {"state_bytes": 0}}) is None


def _stand_in(monkeypatch, ops, steps):
    monkeypatch.setattr(span_reduce, "load", lambda c: (
        {"planes": [{"ops": ops}]}, {"steps": steps}))
    monkeypatch.setattr(common, "peaks_for", lambda kind: {
        "hbm_gb_per_s": 100.0, "bf16_tflops": 1.0})


def test_decode_readers_on_a_stand_in_trace(monkeypatch):
    """Two decode runs paired with their dispatch spans: six ``kda_step``
    calls and two latent calls (64 rows each) a run."""
    kernel = "%kda_step.3 = (bf16[128,32,128], f32[6,129,32,128,128]) " \
        "custom-call(%x)"
    latent = "%paged_attention_latent.4 = bf16[64,32,512] custom-call(%q)"
    other = "%fusion.9 = bf16[128,2560] fusion(bf16[128,2560] %x)"
    ops = []
    for base in (100.0, 1100.0):
        ops += [(kernel, base + 10 * i, base + 10 * i + 5) for i in range(6)]
        ops += [(latent, base + 100, base + 140),
                (latent, base + 150, base + 190),
                (other, base + 300, base + 900)]
    runs = [("jit_ling_hybrid_decode_step", 100.0, 1100.0),
            ("jit_ling_hybrid_decode_step", 1100.0, 2100.0)]
    steps = [{"attrs": {"kind": "decode", "rows": 128, "kv_tokens": 600000,
                        "state_mb": 3221.2}, "run": run, "inside": True}
             for run in runs]
    _stand_in(monkeypatch, ops, steps)
    ctx = {"config": {"keys": KEYS}, "model_config": _Cfg}
    # 2 steps x 128 rows x 6 layers x 32 x 133,888 B over 12 x 5 ns
    want = 2 * 128 * 6 * 32 * 133888 / 60.0
    assert _reader("kda_state_hbm_pct.sat").read(ctx) == pytest.approx(
        100.0 * want / 100.0)
    # 2 steps x 600,000 rows of context x 1,152 B x ONE layer over 4 x 40 ns
    want = 2 * 600000 * 1152 / 160.0
    assert _reader("latent_attn_kvl_hbm_pct.sat").read(ctx) == pytest.approx(
        100.0 * want / 100.0)
    # another family's configuration, the parent's spans: nothing
    for name in ("kda_state_hbm_pct.sat", "latent_attn_kvl_hbm_pct.sat",
                 "kda_prefill_mxu_pct.sat"):
        assert _reader(name).read({"config": {"keys": {"n_head": 2}}}) is None
    # a configuration with latent keys and a config class that names no
    # pool layer count (cells 8 and 10 on the parent): nothing
    assert _reader("latent_attn_kvl_hbm_pct.sat").read(
        {"config": {"keys": KEYS}, "model_config": object()}) is None
    for step in steps:
        step["attrs"] = {"kind": "decode"}
    for name in ("kda_state_hbm_pct.sat", "latent_attn_kvl_hbm_pct.sat"):
        assert _reader(name).read(ctx) is None
    monkeypatch.setattr(span_reduce, "load", lambda c: (None, None))
    for name in NEW_READERS[1:4]:
        assert _reader(name).read(ctx) is None


def _table(by):
    busy = sum(s for row in by.values() for s in row.values())
    return {"busy_s": busy, "by": by, "runs": {}, "mixed_s": 0.0,
            "unmatched": {}, "programs": {}}


def test_prefill_reader_reads_the_scope_table(monkeypatch):
    """While the chunked form is XLA's its time is the scope ``kda_chunk``
    of the prefill programs; once a kernel of that name is in the trace,
    its calls inside the prefill runs."""
    run = ("jit_ling_hybrid_prefill", 1000.0, 3000.0)
    steps = [{"attrs": {"kind": "prefill_chunk", "tokens": 2048,
                        "kda_pieces": 128}, "run": run, "inside": True},
             {"attrs": {"kind": "decode", "rows": 128},
              "run": ("jit_ling_hybrid_decode_step", 3000.0, 4000.0),
              "inside": True}]
    _stand_in(monkeypatch, [("%fusion.1 = f32[2] fusion(%x)", 1000.0, 1500.0)],
              steps)
    table = _table({"prefill": {"kda_chunk": 2e-7, "attn_proj": 5e-7},
                    "decode": {"kda_step": 3e-7, "kda_chunk": 9.0}})
    ctx = {"config": {"keys": KEYS}, "scope_table": table}
    flops = 2048 * 32 * 188416 * 6
    assert _reader("kda_prefill_mxu_pct.sat").read(ctx) == pytest.approx(
        100.0 * flops / 2e-7 / 1e12 / 1.0)
    named = "%kda_chunk.2 = bf16[1,2048,32,128] custom-call(%q)"
    _stand_in(monkeypatch, [(named, 1100.0 + 100 * i, 1150.0 + 100 * i)
                            for i in range(6)], steps)
    assert _reader("kda_prefill_mxu_pct.sat").read(ctx) == pytest.approx(
        100.0 * flops / 300e-9 / 1e12 / 1.0)
    # no table (the parent's program names no such scope): nothing
    _stand_in(monkeypatch, [], steps)
    assert _reader("kda_prefill_mxu_pct.sat").read(
        {"config": {"keys": KEYS}, "scope_table": None}) is None
    assert _reader("kda_prefill_mxu_pct.sat").read(
        {"config": {"keys": KEYS},
         "scope_table": _table({"prefill": {"attn_proj": 1.0}})}) is None


def test_the_cells_scope_shares_add_up_to_100(monkeypatch):
    """``GROUPS["mixer"]`` does not hold the ``kda_*`` names, so the cell
    reports ``scope_pct.kda`` in its place: with it the cell's
    ``scope_pct.*`` and ``unnamed`` cover the table."""
    from ray_tpu.serve.llm import obs

    kda = _reader("scope_pct.kda.sat")
    assert set(kda.SCOPES) <= set(obs.SCOPES)
    assert not set(kda.SCOPES) & {
        s for g in scope_reduce.GROUPS.values() for s in g}
    by = {"decode": {"kda_step": 25.0, "kda_conv": 3.0, "kda_gate": 0.3,
                     "kda_out": 0.1, "moe_gmm": 17.0, "attn_kernel": 12.0,
                     "attn_cache": 3.0, "attn_proj": 7.0, "moe_route": 4.0,
                     "moe_move": 2.0, "moe_shared": 1.0, "ffn": 0.7,
                     "head": 0.7, "sample": 0.1, "embed": 0.1,
                     "counters": 0.1, "unnamed": 0.5},
          "prefill": {"kda_chunk": 7.0, "attn_proj": 5.0, "moe_move": 4.0,
                      "moe_gmm": 2.5, "kda_conv": 2.0, "moe_route": 1.3,
                      "ffn": 0.5, "attn_kernel": 0.5, "kda_out": 0.4,
                      "moe_shared": 0.3}}
    ctx = {"scope_table": _table(by)}
    spec = common.resolve_cell(common.load_manifest(), CELL)
    shares = [m["name"] for m in spec["per_layer"]
              if m["name"].startswith("scope_pct.")]
    assert "scope_pct.kda.sat" in shares and len(shares) == 7
    total = sum(_reader(name).read(ctx) for name in shares)
    unnamed = 100.0 - _reader("scope_named_pct.sat").read(ctx)
    assert total + unnamed == pytest.approx(100.0)
    busy = sum(s for row in by.values() for s in row.values())
    assert kda.read(ctx) == pytest.approx(
        100.0 * (25.0 + 3.0 + 0.3 + 0.1 + 7.0 + 2.0 + 0.4) / busy)
    # under the floor, or a table without the names (the parent): nothing
    assert kda.read({"scope_table": _table(
        {"decode": {"kda_step": 1.0, "unnamed": 1.0}})}) is None
    assert kda.read({"scope_table": _table(
        {"decode": {"attn_proj": 1.0}})}) is None
    assert kda.read({"scope_table": None}) is None


# ------------------------------------------------------------ the rehearsal


@pytest.mark.timeout(900)
def test_rehearsal_runs_the_cell_end_to_end_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCHMARK_REHEARSAL="1")
    env.pop("BENCH_RUN", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 52), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=880)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert "'kda': [3, 5, 4, 16, 16]" in out.stdout  # the executor's report
    assert "'kind': 'latent'" in out.stdout
    assert "compiled or read from the cache INSIDE" not in out.stdout
    # the counters read on the CPU; the trace's readers find no TPU plane
    # and leave their metrics out without raising
    metrics = line["metrics"]
    assert 0 < metrics["moe_groups_held_pct"]["value"] < 100
    assert 0 < metrics["moe_pairs_held_pct"]["value"] < 100
    assert metrics["kda_state_gb"]["value"] > 0
    assert metrics["decode_batch_mean"]["value"] > 0
    for name in NEW_READERS[:4]:
        assert name not in metrics
