"""The SDAR-30B-A3B-Chat reference, the configuration file, the traffic file
and the new cell's readers: the manifest resolves the cell; the
configuration holds the catalog row's numbers unchanged but for the ONE key
``reduced`` names, every ``reduced`` / ``assumed`` entry says what was
published, what is here and the other reading; the byte counts (4,361 M
parameters, 12 KB of K/V a token) come out of the file's own widths; the
traffic is the issue's table; the three new readers on recorded counters
and on a stand-in trace, and nothing (no exception) where the program counts
or says no such thing, as the parent's does not; the cell runs end to end on
the CPU at its rehearsal size."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, span_reduce  # noqa: E402

CELL = "sdar-blockdiff-chat-saturated"
CONFIG = "sdar-30b-a3b-chat-6l"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ASSUMED = ("block_length", "denoising_steps", "remasking", "mask_token_id",
           "masked_is_positional", "qk_norm", "logit_position",
           "prompt_mask")
NEW_READERS = ("block_tokens_per_pass", "block_commit_pass_pct",
               "block_attn_hbm_pct.sat")
KEYS = {"n_kv_head": 4, "head_dim": 128, "n_layer": 6, "dtype": "bfloat16",
        "block_length": 4}


def _reader(name):
    return common.load_layer_metric(name)


def _held():
    return common.load_json(os.path.join(
        ROOT, "benchmark/configs", CONFIG + ".json"))


def _traffic():
    return common.load_json(os.path.join(
        ROOT, "benchmark/traffic/blockdiff-chat-closed.json"))


# ------------------------------------------------------------ the manifest


def test_manifest_resolves_the_cell():
    manifest = common.load_manifest()
    spec = common.resolve_cell(manifest, CELL)
    assert spec["cell"]["chips"] == 1
    assert (spec["cell"]["config"], spec["cell"]["traffic"]) == (
        CONFIG, "blockdiff-chat-closed")
    assert spec["config"]["family"] == "sdar_moe"
    assert [m["name"] for m in spec["end_to_end"]] == [
        "setup_s", "serve_tokens_per_s"]
    names = [m["name"] for m in spec["per_layer"]]
    assert set(NEW_READERS) <= set(names)
    for m in manifest["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]


def test_the_reference_imports_nothing_of_the_program():
    """The reference's forward, generation and ``logits_at`` are its own
    ``jax.numpy``; of the program it takes the config class and the
    initialiser alone (the weights both read)."""
    text = open(os.path.join(ROOT, "benchmark/reference/sdar_moe.py")).read()
    imports = [line.strip() for line in text.splitlines()
               if "import" in line and "ray_tpu" in line]
    assert imports == [
        "from ray_tpu.models.sdar_moe import SdarMoeConfig",
        "from ray_tpu.models.sdar_moe import sdar_moe_init"]
    ref = common.load_named("reference", "sdar_moe")
    assert ref.ENGINE_MODEL == "sdar_moe"
    assert ref.config_class().__name__ == "SdarMoeConfig"


# ------------------------------------------------- the configuration file


def test_configuration_holds_the_rows_numbers():
    """Every key of the catalog row's ``config`` is in the file, unchanged
    but for ``num_hidden_layers``; the program's config is built from
    ``keys``, each of which says where it comes from; no width is cut."""
    row = next(json.loads(line) for line in open(CATALOG)
               if json.loads(line)["name"] == "SDAR-30B-A3B-Chat")
    held = _held()
    assert held["source"] == row["source_url"]
    assert list(held["reduced"]) == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in held["reduced"]:
            assert held["reduced"][key]["published"] == value == 48
            assert held[key] == held["reduced"][key]["here"] == 6
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
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim) == (
        2048, 32, 4, 128)
    assert (cfg.num_experts, cfg.top_k, cfg.d_expert) == (128, 8, 768)
    assert (cfg.vocab_size, cfg.max_seq_len, cfg.n_layer) == (
        151936, 32768, 6)
    assert (cfg.block_length, cfg.denoising_steps, cfg.remasking) == (
        4, 2, "sequential")
    assert 0 <= cfg.mask_token_id < cfg.vocab_size


def test_reduced_and_assumed_entries_say_what_and_what_else():
    held = _held()
    for key, entry in held["reduced"].items():
        assert entry["published"] != entry["here"] and entry["why"], key
    assert "8.72 GB" in held["reduced"]["num_hidden_layers"]["why"]
    assumed = held["assumed"]
    assert set(ASSUMED) <= set(assumed)
    for key in ASSUMED:
        assert "other reading" in assumed[key].lower() or \
            "any id gives the same work" in assumed[key], key
    for key in ("block_length", "qk_norm"):
        assert "one function" in assumed[key].lower(), key
    assert "eight pipeline stages" in held["deployment"]
    assert "61.0 GB" in held["deployment"]


def test_the_files_widths_give_the_byte_counts():
    """4,361 M parameters = 8.72 GB of bf16 and 12 KB of K/V a token come
    out of the file's own widths."""
    import jax

    held = _held()
    cfg = common.model_config(held)
    init = common.load_named("reference", "sdar_moe").init_fn()
    shapes = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
    params = sum(x.size for x in jax.tree.leaves(shapes))
    assert abs(params - 4361e6) < 2e6
    assert "4,361 M parameters = 8.72 GB" in held["bytes"]["total"]
    per_token = 2 * cfg.n_kv_head * cfg.head_dim * 2 * cfg.n_layer
    assert per_token == 12 * 1024
    assert "12 KB a token" in held["bytes"]["kv"]
    engine = _traffic()["engine"]
    pool_gb = engine["num_blocks"] * engine["block_size"] * per_token / 1e9
    assert 3.5 < pool_gb < 5.0


# ---------------------------------------------------------------- traffic


def test_traffic_is_the_issues_table():
    t = _traffic()
    assert (t["runner"], t["generator"]) == (
        "serve_engine", "lognormal_chat_ordered")
    assert t["order"] == {"output_stride": 11, "output_offset": 5}
    assert t["arrivals"] == {"mode": "closed", "clients": 128}
    assert t["prompt_len"] == {"median": 768, "sigma": 0.8, "min": 64,
                               "max": 6144}
    assert t["output_len"] == {"median": 1024, "sigma": 0.6, "min": 128,
                               "max": 4096}
    assert t["strata"] == 32 and t["sampling"] == {"temperature": 0.0}
    e = t["engine"]
    assert (e["max_batch_size"], e["block_size"], e["prefill_chunk_tokens"],
            e["max_prefill_batch"]) == (128, 16, 2048, 1)
    assert e["length_buckets"] == [2048, 10240]
    assert e["batch_buckets"] == [1, 16, 128]
    assert (t["window"]["trace_after_s"], t["window"]["trace_s"]) == (2, 8)
    # the drawn sizes hold every residue mod 4, on both sides
    gen = common.load_named("generators", t["generator"])
    sched = gen.build(t, 1, 151936)
    sizes = [sched.lengths(i) for i in range(32)]
    assert {p % 4 for p, _ in sizes} == {0, 1, 2, 3} == {
        o % 4 for _, o in sizes}
    # the longest context the traffic reaches lies in the last bucket
    assert max(p + o for p, o in sizes) <= e["length_buckets"][-1]


def test_reference_check_fits_what_the_engine_is_built_for():
    held, t = _held(), _traffic()
    chk = held["reference_check"]
    assert chk["requests"] == 16 == len(chk["prompt_tokens"])
    assert (chk["new_tokens"], chk["every"]) == (64, 1)
    assert {n % 4 for n in chk["prompt_tokens"]} == {0, 1, 2, 3}
    assert min(chk["prompt_tokens"]) <= 100
    assert max(chk["prompt_tokens"]) >= 6000
    # the noisy stream spans new + 2 blocks behind the prompt's last block
    assert max(chk["prompt_tokens"]) + chk["new_tokens"] + 8 <= chk["pad_to"]
    assert chk["pad_to"] <= t["engine"]["length_buckets"][-1]
    assert chk["requests"] in t["engine"]["batch_buckets"]
    assert held["keys"]["remasking"] == "sequential"  # ``logits_at``'s order


# ------------------------------------------------------------- the readers


def test_counter_readers_on_recorded_counters():
    """A window of 128 rows x 300 passes, a third of them commits, 4
    tokens a commit less 100 cut."""
    before = {"block_passes": 1000, "block_passes_commit": 300,
              "block_tokens_committed": 1200}
    after = {"block_passes": 39400, "block_passes_commit": 13100,
             "block_tokens_committed": 52300}
    ctx = {"stats_before": before, "stats_after": after}
    assert _reader("block_tokens_per_pass").read(ctx) == pytest.approx(
        51100 / 38400)
    assert _reader("block_commit_pass_pct").read(ctx) == pytest.approx(
        100.0 * 12800 / 38400)
    # an autoregressive family counts no pass; the parent's stats have no
    # such keys: nothing, and no exception
    zeros = dict.fromkeys(before, 0)
    for name in NEW_READERS[:2]:
        assert _reader(name).read(
            {"stats_before": zeros, "stats_after": zeros}) is None
        assert _reader(name).read(
            {"stats_before": {}, "stats_after": {}}) is None
        assert _reader(name).read({}) is None


def test_block_attention_reader_on_a_stand_in_trace(monkeypatch):
    """Two block passes paired with their dispatch spans: six
    ``paged_attention`` calls a run."""
    reader = _reader("block_attn_hbm_pct.sat")
    # K and V of 1,000 tokens: 4 heads x 128 x 2 B, six layers
    assert reader.block_attn_bytes(1000, 4, 128, 2, 6) == 12288000
    kernel = "%paged_attention.7 = bf16[128,4,32,128] custom-call(%q)"
    other = "%fusion.9 = bf16[512,2048] fusion(bf16[512,2048] %x)"
    ops = []
    for base in (100.0, 1100.0):
        ops += [(kernel, base + 50 * i, base + 50 * i + 40) for i in range(6)]
        ops += [(other, base + 400, base + 900)]
    runs = [("jit_sdar_moe_decode_step", 100.0, 1100.0),
            ("jit_sdar_moe_decode_step", 1100.0, 2100.0)]
    steps = [{"attrs": {"kind": "decode", "rows": 128, "kv_tokens": 250000,
                        "block_len": 4, "rows_commit": 43},
              "run": run, "inside": True} for run in runs]
    monkeypatch.setattr(span_reduce, "load", lambda c: (
        {"planes": [{"ops": ops}]}, {"steps": steps}))
    monkeypatch.setattr(common, "peaks_for", lambda kind: {
        "hbm_gb_per_s": 1000.0})
    ctx = {"config": {"keys": KEYS}}
    # 2 steps x 250,000 tokens x 12,288 B over 12 x 40 ns
    want = 2 * 250000 * 12288 / 480.0
    assert reader.read(ctx) == pytest.approx(100.0 * want / 1000.0)
    # another family's configuration: nothing
    assert reader.read({"config": {"keys": {"n_head": 2}}}) is None
    # the spans of a program that says no ``block_len`` (an autoregressive
    # family's decode step): nothing
    for step in steps:
        step["attrs"] = {"kind": "decode", "kv_tokens": 250000}
    assert reader.read(ctx) is None
    monkeypatch.setattr(span_reduce, "load", lambda c: (None, None))
    assert reader.read(ctx) is None


# ------------------------------------------------------------ the rehearsal


@pytest.mark.timeout(900)
def test_rehearsal_runs_the_cell_end_to_end_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCHMARK_REHEARSAL="1")
    env.pop("BENCH_RUN", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 54), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=880)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert "'kind': 'block_diffusion'" in out.stdout  # the executor's report
    assert "compiled or read from the cache INSIDE" not in out.stdout
    # the counters read on the CPU; the trace's reader finds no TPU plane
    # and leaves its metric out without raising
    metrics = line["metrics"]
    assert 0.8 < metrics["block_tokens_per_pass"]["value"] < 4 / 3
    assert 33.0 < metrics["block_commit_pass_pct"]["value"] < 45.0
    assert metrics["decode_batch_mean"]["value"] > 0
    assert metrics["decode_steady_pct.sat"]["value"] > 90
    assert metrics["moe_load_max_over_mean"]["value"] >= 1.0
    assert "block_attn_hbm_pct.sat" not in metrics
