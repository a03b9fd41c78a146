"""Kernels: the flash kernels' share of the MXU's bf16 peak. Operations of
causal attention in the slice's whole training steps (forward 2 x B x H x
S^2 x hd, backward 2.5 times that, in every layer, from the job's shapes)
over the time the steps' Pallas calls took, over the chip's published peak
(``peaks.json``)."""
from benchmark import common, span_reduce


def read(ctx):
    raw, _ = span_reduce.load(ctx)
    if not raw:
        return None
    keys, job = ctx["config"]["keys"], ctx["traffic"]
    shapes = {"batch": job["batch_size"], "heads": keys["n_head"],
              "seq": job["seq_len"],
              "head_dim": keys["d_model"] // keys["n_head"],
              "n_layer": keys["n_layer"]}
    peak = common.peaks_for(ctx["device"]["kind"])["bf16_tflops"]
    plane = raw["planes"][0]
    out = span_reduce.flash_attn_mxu_pct(
        plane["modules"], plane["ops"], raw["window"],
        span_reduce.flash_attn_flops(**shapes), peak)
    if out is None:
        return None
    common.say(f"flash attention against the MXU: {out} with {shapes}")
    return out["pct"]
