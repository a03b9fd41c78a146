"""Kernels: the decode kernel's share of the HBM roofline. Bytes the
kernel had to read in the slice's decode steps (the ``kv_tokens`` of each
``executor.dispatch`` span x 2 x KV heads x head size x the pool's item
size x layers, widths from the configuration's keys) over the time the
``paged_attention`` operations took inside those steps' runs, over the
chip's published bandwidth (``peaks.json``)."""
import jax
import jax.numpy as jnp

from benchmark import common, span_reduce


def read(ctx):
    raw, reduced = span_reduce.load(ctx)
    if not reduced:
        return None
    keys = ctx["config"]["keys"]
    widths = {
        "n_kv_head": keys.get("n_kv_head", keys["n_head"]),
        "head_dim": keys["d_model"] // keys["n_head"],
        "itemsize": jnp.dtype(keys["dtype"]).itemsize,
        "n_layer": keys["n_layer"],
    }
    # the serving runner's ``ctx`` carries no device report: ask JAX
    peak = common.peaks_for(jax.devices()[0].device_kind)["hbm_gb_per_s"]
    out = span_reduce.paged_attn_hbm_pct(
        reduced, raw["planes"][0]["ops"], widths, peak)
    if out is None:
        return None
    common.say(f"paged attention against HBM: {out} with {widths}")
    return out["pct"]
