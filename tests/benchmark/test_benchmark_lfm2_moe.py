"""The lfm2_moe reference, the configuration file and the new cell's readers:
``logits_at`` picks ``logits``' rows; the configuration builds the program's
config at published widths with the first eight published layers; the cell
runs end to end on the CPU at its rehearsal size; the four new per-layer
readers compute what they say from plain data and return nothing (they do not
raise) where the program has no such operation or counter."""
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

CELL = "lfm2moe-chat-saturated"


@pytest.fixture(scope="module")
def ref():
    return common.load_named("reference", "lfm2_moe")


def test_logits_at_picks_the_rows_of_logits(ref):
    from ray_tpu.models.lfm2_moe import Lfm2MoeConfig

    cfg = Lfm2MoeConfig.tiny()
    assert ref.config_class() is Lfm2MoeConfig
    assert ref.ENGINE_MODEL == "lfm2_moe"
    params = ref.init_fn()(jax.random.PRNGKey(1), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 1,
                                cfg.vocab_size)
    want = ref.logits(params, tokens, cfg)
    assert want.shape == (2, 24, cfg.vocab_size) and want.dtype == jnp.float32
    pos = jnp.array([[0, 7, 23], [3, 20, 22]])
    rows = ref.logits_at(params, tokens, pos, cfg)
    assert float(jnp.max(jnp.abs(
        rows - jnp.take_along_axis(want, pos[..., None], axis=1)))) < 1e-5


def test_reference_router_weights_only_the_chosen(ref):
    """``route``: top-k by the biased score, weights from the unbiased one,
    zeros elsewhere, each token's weights summing to one."""
    from ray_tpu.models.lfm2_moe import Lfm2MoeConfig

    cfg = Lfm2MoeConfig.tiny()
    lp = {"moe_route_w": jax.random.normal(jax.random.PRNGKey(0), (64, 8)),
          "moe_route_bias": jnp.zeros((8,)).at[3].set(10.0)}
    h = jax.random.normal(jax.random.PRNGKey(1), (5, 64))
    weights = ref.route(h, lp, cfg)
    assert weights.shape == (5, 8)
    assert bool(jnp.all((weights > 0).sum(-1) == cfg.top_k))
    assert bool(jnp.all(weights[:, 3] > 0))  # the bias chose it ...
    s = jax.nn.sigmoid(h @ lp["moe_route_w"])
    other = jnp.where(jnp.arange(8) == 3, -1.0, s).max(-1)
    assert bool(jnp.allclose(  # ... and did not weigh it
        weights[:, 3], s[:, 3] / (s[:, 3] + other + 1e-6), atol=1e-5))
    assert bool(jnp.allclose(weights.sum(-1), 1.0, atol=1e-5))


def test_configuration_is_the_published_first_eight_layers():
    spec = common.resolve_cell(common.load_manifest(), CELL)
    held = spec["config"]
    assert spec["cell"]["chips"] == 1 and spec["cell"]["traffic"] == \
        "chat-closed"
    cfg = common.model_config(held)
    assert cfg.layer_types == tuple(held["layer_types"][:8]) == (
        "conv", "conv", "full_attention", "conv") * 2
    assert len(held["layer_types"]) == 40 and held["num_hidden_layers"] == 8
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim) == (
        2048, 32, 8, 64)
    assert (cfg.d_mlp, cfg.num_experts, cfg.top_k, cfg.d_expert) == (
        11776, 64, 4, 1536)
    assert (cfg.vocab_size, cfg.max_seq_len, cfg.conv_L_cache) == (
        65536, 128000, 3)
    assert cfg.norm_eps == held["norm_eps"] == 1e-5
    assert cfg.rope_theta == held["rope_parameters"]["rope_theta"] == 1e6
    assert (cfg.n_kv_layer, cfg.n_conv_layer, cfg.n_moe_layer,
            cfg.num_dense_layers) == (2, 6, 6, 2)
    assert cfg.dtype == jnp.bfloat16
    assert list(held["reduced"]) == ["num_hidden_layers"]
    # the byte count the file states: 4.025 B parameters
    ref = common.load_named("reference", "lfm2_moe")
    shapes = jax.eval_shape(
        lambda: ref.init_fn()(jax.random.PRNGKey(0), cfg))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert abs(n - 4.025e9) < 0.005e9, n
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert abs(nbytes - 8.05e9) < 0.01e9, nbytes
    # the cell reports the metrics ISSUE 26 lists, the new four among them
    names = {m["name"] for m in spec["per_layer"]}
    assert {"moe_share_pct.sat", "short_conv_share_pct.sat",
            "moe_gmm_hbm_pct.sat", "moe_load_max_over_mean",
            "decode_step_ms.sat", "hbm_peak_gb.serve"} <= names
    assert "paged_attn_hbm_pct.sat" not in names  # its reader counts n_layer
    assert [m["name"] for m in spec["end_to_end"]] == [
        "setup_s", "serve_tokens_per_s"]


def test_reference_check_fits_what_the_engine_is_built_for():
    """The published-size check is only ever run on the chip: what would
    stop it there is checked here (a request longer than ``pad_to`` raises
    in the runner; a batch or a context the warm-up never compiled would
    compile inside the set-up)."""
    spec = common.resolve_cell(common.load_manifest(), CELL)
    chk, traffic = spec["config"]["reference_check"], spec["traffic"]
    lens = chk["prompt_tokens"][: chk["requests"]]
    assert len(lens) == chk["requests"] == len(set(lens))
    assert max(lens) + chk["new_tokens"] <= chk["pad_to"] <= \
        traffic["engine"]["length_buckets"][0]
    assert chk["requests"] in traffic["warmup"]["decode_batches"]
    assert chk["requests"] % max(traffic["warmup"]["prefill_batches"]) == 0
    # the limit's two readings are written beside it
    assert 0 < chk["tolerance_logit"] and "fp8" in chk["tolerance_why"]


@pytest.mark.timeout(420)
def test_rehearsal_runs_the_cell_end_to_end_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCHMARK_REHEARSAL="1")
    env.pop("BENCH_RUN", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 26), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert "'prefix_reuse': False" in out.stdout
    assert "compiled or read from the cache INSIDE" not in out.stdout
    # the counter reads on the CPU; the trace's readers find no TPU plane
    # and leave their metrics out without raising
    assert 1.0 <= line["metrics"]["moe_load_max_over_mean"]["value"] < 8.0
    assert line["metrics"]["decode_batch_mean"]["value"] > 0
    for name in ("moe_share_pct.sat", "short_conv_share_pct.sat",
                 "moe_gmm_hbm_pct.sat"):
        assert name not in line["metrics"]


def _reader(name):
    return common.load_layer_metric(name)


def test_share_readers_sum_the_named_operations():
    gmm = ("%ragged-dot-none.3 = f32[256,3072] custom-call(%fusion.1, "
           "%params__layers___2___moe_gmm_w_in__.1), custom_call_target="
           '"tpu_custom_call"')
    route = "%fusion.7 = f32[64,64] fusion(%x, %params__layers___2___moe_route_w__.1)"
    conv = "%fusion.9 = bf16[64,2048] fusion(%params__layers___0___short_conv_w__.1)"
    reduced = {"busy_s": 2.0, "ops": {
        gmm: {"count": 6, "self_s": 0.8}, route: {"count": 6, "self_s": 0.2},
        conv: {"count": 6, "self_s": 0.1},
        "%paged_attention.2 = custom-call()": {"count": 2, "self_s": 0.5},
        "%fusion.1 = fusion()": {"count": 9, "self_s": 0.4}}}
    assert _reader("moe_share_pct.sat").read({"trace": reduced}) == \
        pytest.approx(50.0)
    assert _reader("short_conv_share_pct.sat").read({"trace": reduced}) == \
        pytest.approx(5.0)
    # a program without such operations (the parent): nothing, not zero
    bare = {"busy_s": 2.0, "ops": {"%fusion.1 = fusion()": {
        "count": 9, "self_s": 0.4}}}
    for name in ("moe_share_pct.sat", "short_conv_share_pct.sat"):
        assert _reader(name).read({"trace": bare}) is None
        assert _reader(name).read({}) is None
    # the router alone, the grouped product under a name the reader does
    # not know: the share's main term is missing, so nothing is reported
    renamed = {"busy_s": 2.0, "ops": {
        route: {"count": 6, "self_s": 0.2},
        "%grouped_matmul.3 = custom-call()": {"count": 6, "self_s": 0.8}}}
    assert _reader("moe_share_pct.sat").read({"trace": renamed}) is None


def test_load_reader_takes_the_windows_difference():
    read = _reader("moe_load_max_over_mean").read
    ctx = {"stats_before": {"moe_pairs_by_expert": [10, 10, 10, 10]},
           "stats_after": {"moe_pairs_by_expert": [20, 40, 20, 20]}}
    assert read(ctx) == pytest.approx(30 * 4 / 60)
    assert read({"stats_before": {}, "stats_after": {}}) is None
    assert read({}) is None
    same = {"stats_before": {"moe_pairs_by_expert": [1, 2]},
            "stats_after": {"moe_pairs_by_expert": [1, 2]}}
    assert read(same) is None


def test_gmm_bytes_and_roofline_reader():
    mod = _reader("moe_gmm_hbm_pct.sat")
    # 63 experts x 6 layers a step, each 3 x 2048 x 1536 x 2 B
    assert mod.moe_gmm_bytes(378, 2048, 1536, 2) == 378 * 18874368
    assert mod.moe_gmm_bytes(1, 4, 2, 2) == 48
    # no counter (the parent), or no trace: nothing, and no exception
    assert mod.read({"stats_before": {}, "stats_after": {}}) is None
    assert mod.read({"stats_before": {"moe_expert_reads_decode": 0,
                                      "decode_steps": 0},
                     "stats_after": {"moe_expert_reads_decode": 10,
                                     "decode_steps": 5}}) is None
