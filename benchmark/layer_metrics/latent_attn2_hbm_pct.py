"""Kernels: the latent attention kernel's share of the HBM roofline in
decode where a LAYER holds more than one latent sub-layer
(models/longcat_flash.py: two). ``latent_attn_hbm_pct`` counts the bytes a
decode step must read as ``kv_tokens`` x (``kv_lora_rank`` +
``qk_rope_head_dim``) x the item size x ``keys.n_layer`` and would read
half here; this is that reader, unedited, with the layer count replaced by
the configuration's count of latent SUB-LAYERS (the file's
``latent_sublayers``: the pool's layers, 2 x ``n_layer``): 1,152 B a token
a sub-layer at the published widths, over the time the
``paged_attention_latent`` operations took inside the slice's decode runs,
over the chip's published bandwidth (``peaks.json``). At 64 heads the
kernel does 2 x 64 x (576 + 512) flop for each 1,152 B: 121 flop/B, half
the v5e's ridge of 240, so of the two rooflines the bytes' is the one that
can bind; the accepted reader SAYS the same time against the matrix peak.
Nothing where the configuration names no such count, or the trace holds no
latent call."""
from benchmark import common


def _base():
    return common.load_named("layer_metrics", "latent_attn_hbm_pct")


def latent_attn2_bytes(kv_tokens: int, kv_lora_rank: int,
                       qk_rope_head_dim: int, itemsize: int,
                       sublayers: int) -> int:
    """Bytes one decode step's latent attention must read: ONE row a token
    of context in every latent sub-layer, whatever the number of heads."""
    return _base().latent_attn_bytes(
        kv_tokens, kv_lora_rank, qk_rope_head_dim, itemsize, sublayers)


def latent_attn2_flops(kv_tokens: int, n_head: int, kv_lora_rank: int,
                       qk_rope_head_dim: int, sublayers: int) -> int:
    """... and the absorbed form's products over them."""
    return _base().latent_attn_flops(
        kv_tokens, n_head, kv_lora_rank, qk_rope_head_dim, sublayers)


def read(ctx):
    config = ctx["config"]
    sublayers = config.get("latent_sublayers")
    if not sublayers:
        return None
    as_layers = dict(config, keys=dict(config["keys"], n_layer=sublayers))
    inner = dict(ctx, config=as_layers)
    value = _base().read(inner)
    # the trace is read once a run: keep what the inner reader loaded
    if "span_trace" in inner:
        ctx.setdefault("span_trace", inner["span_trace"])
    return value
