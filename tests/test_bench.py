"""Benchmark helpers that the CPU can check: shapes and env parsing. The
measurement itself (bench.py) needs a TPU and fails without one."""
import pytest


def test_gpt_bench_grows_positional_table_for_long_seq(jax_cpu):
    """BENCH_GPT_SEQ beyond the config's max_seq_len must extend the
    positional table instead of a broadcast error (round-5 long-context
    entries bench seq 8192/16384 against the 1024 default)."""
    from ray_tpu.benchmarks.gpt_mfu import run_gpt_bench

    # the CPU has no published peak: the caller hands one in
    result = run_gpt_bench(config="tiny", batch_size=2, seq_len=256,
                           steps=2, warmup=1, peak_tflops=1.0)
    assert result["seq_len"] == 256  # tiny max_seq_len is 128
    assert result["value"] > 0


def test_paged_attn_shape_env_override(monkeypatch):
    """The paged-attention microbench shape is env-overridable: a valid
    RAY_TPU_PAGED_ATTN_SHAPE parses (',' or 'x' separated), unset means
    None (fall back to the baked-in shape), malformed fails loudly."""
    from ray_tpu.benchmarks import llm_serving

    monkeypatch.delenv("RAY_TPU_PAGED_ATTN_SHAPE", raising=False)
    assert llm_serving._paged_attn_env_shape() is None
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN_SHAPE", "4,8,2,32")
    assert llm_serving._paged_attn_env_shape() == (4, 8, 2, 32)
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN_SHAPE", "4x8x2x32")
    assert llm_serving._paged_attn_env_shape() == (4, 8, 2, 32)
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN_SHAPE", "4,8")
    with pytest.raises(ValueError):
        llm_serving._paged_attn_env_shape()


def test_paged_prefill_shape_env_override(monkeypatch):
    """The prefill microbench's shape override is the decode one's
    5-int twin: RAY_TPU_PAGED_PREFILL_SHAPE="B,S,Hq,Hkv,hd" (',' or 'x'
    separated), unset means None, malformed fails loudly."""
    from ray_tpu.benchmarks import llm_serving

    monkeypatch.delenv("RAY_TPU_PAGED_PREFILL_SHAPE", raising=False)
    assert llm_serving._paged_prefill_env_shape() is None
    monkeypatch.setenv("RAY_TPU_PAGED_PREFILL_SHAPE", "2,32,4,2,32")
    assert llm_serving._paged_prefill_env_shape() == (2, 32, 4, 2, 32)
    monkeypatch.setenv("RAY_TPU_PAGED_PREFILL_SHAPE", "2x32x4x2x32")
    assert llm_serving._paged_prefill_env_shape() == (2, 32, 4, 2, 32)
    monkeypatch.setenv("RAY_TPU_PAGED_PREFILL_SHAPE", "2,32,4")
    with pytest.raises(ValueError):
        llm_serving._paged_prefill_env_shape()
