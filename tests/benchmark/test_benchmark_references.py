"""Both plain references agree with the program on the CPU at tiny sizes."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.reference import gpt as ref_gpt  # noqa: E402
from benchmark.reference import llama as ref_llama  # noqa: E402

# Tolerances, with their reason. With the program in float32 both sides
# compute the same mathematics in the same precision and differ only in
# the order of sums: 1e-5 on logits of size ~0.2 (seen 1.5e-7). With the
# program in bfloat16 (as it is served) activations are rounded to 8 bits
# of mantissa at every matmul: logits of std 0.16 move by up to ~4e-3 at
# two layers (seen 3.4e-3); 2e-2 is five times that, and a wrong term
# (a missing rotary embedding, norm or residual) moves logits by their
# own size, 0.1-0.5.
F32_TOL = 1e-5
BF16_TOL = 2e-2


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)])
def test_llama_reference_matches_llama_forward(dtype, tol):
    from ray_tpu.models.llama import LlamaConfig, llama_forward, llama_init

    cfg = dataclasses.replace(LlamaConfig.tiny(), attention="xla",
                              dtype=dtype, rope_theta=1e6)
    assert ref_llama.config_class() is LlamaConfig
    params = llama_init(jax.random.PRNGKey(1), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 0,
                                cfg.vocab_size)
    want = ref_llama.logits(params, tokens, cfg)
    got = llama_forward(params, tokens, cfg)
    assert float(jnp.max(jnp.abs(got - want))) < tol
    # logits_at picks the same rows
    pos = jnp.array([[0, 7, 39], [3, 20, 38]])
    rows = ref_llama.logits_at(params, tokens, pos, cfg)
    assert float(jnp.max(jnp.abs(
        rows - jnp.take_along_axis(want, pos[..., None], axis=1)))) < 1e-5


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 5e-3)])
def test_gpt_reference_loss_matches_gpt_loss(dtype, tol):
    # loss ~6.2 at tiny size; bf16 moves it by ~1e-6..1e-3
    from ray_tpu.models.gpt import GPTConfig, gpt_forward, gpt_init, gpt_loss

    cfg = dataclasses.replace(GPTConfig.tiny(), attention="xla", dtype=dtype)
    assert ref_gpt.config_class() is GPTConfig
    params = gpt_init(jax.random.PRNGKey(1), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 41), 0,
                                cfg.vocab_size)
    for fused in (True, False):
        c = dataclasses.replace(cfg, fused_loss=fused)
        got = float(gpt_loss(params, {"tokens": tokens}, c))
        assert abs(got - float(ref_gpt.loss(params, tokens, cfg))) < tol
    if dtype == jnp.float32:
        diff = gpt_forward(params, tokens[:, :-1], cfg) - ref_gpt.logits(
            params, tokens[:, :-1], cfg)
        assert float(jnp.max(jnp.abs(diff))) < F32_TOL
