"""Kernels: device time in the dense SwiGLUs that stand BESIDE a routed
branch in one layer (models/longcat_flash.py: two a layer, 12,288 wide), as
a share of busy time, to be read beside ``moe_share_pct``: the operations
that read the ``dense_ffn_w_in`` / ``dense_ffn_w_out`` leaves, directly or
through the moves that bring a leaf into fast memory; a leaf is followed by
name as ``shared_expert_share_pct`` does (its ``readers_of``, with this
needle). Whole fusions are counted, whatever else they fuse (the norm
before, the residual add after): errs high. In decode the two SwiGLUs'
weights (453 MB a layer at the published widths) are bytes every step
reads whatever the batch. Nothing where no such operation took time."""
from benchmark import common

NEEDLE = "dense_ffn"


def read(ctx):
    reduced = ctx.get("trace")
    if not reduced:
        return None
    follow = common.load_named("layer_metrics", "shared_expert_share_pct")
    mine = follow.readers_of(reduced["ops"], NEEDLE)
    if not any(reduced["ops"][name]["self_s"] > 0 for name in mine):
        return None
    return 100.0 * sum(reduced["ops"][name]["self_s"] for name in mine) \
        / reduced["busy_s"]
