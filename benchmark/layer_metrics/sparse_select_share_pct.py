"""Kernels: device time in the selection of a selecting layer's decode
steps (``sparse_select``, ops/sparse_select.py: the gather of a row's
compressed keys through its table, the scores against them, the softmax,
the group's sum, the max pool, the forced blocks and the top-k), as a
share of the device's busy time in the slice. What a row-step must READ
for it: ``floor((n - 16) / 16)`` compressed keys x K/V heads x ``head_dim``
x the item size a selecting layer (``compressed_bytes``; float32 segment
sums as stored: 2 MB at 32k tokens), beside the 4.19 MB its chosen blocks
cost: a row's whole context is read once at a sixteenth.

Which operations: XLA's formulation has no name of its own in a trace (an
operation is named by its HLO text), so the selection's operations are
found by what they hold: the ``ckeys`` leaf of ``state`` or the plane's
own printed type (its gathers and its scatters) and the printed type of a
row's gathered segments (``f32[<rows x entries>,<segments>,<row>]`` and
``f32[<rows>,<entries x segments>,<row>]``). The products, the softmax and
the top-k behind them carry no such type and are NOT counted: what this
reads is the selection's GATHER and scatter of compressed keys, its larger
part by bytes, not the whole selection. INFORMATIONAL: a change of fusion
moves it, and a kernel for the selection would blank it (PERF.md 7 ac).
The prefill steps' selection runs inside their attention's
``conditional`` and is counted by ``sparse_prefill_share_pct``. Whole
fusions are counted, whatever else they fuse. Nothing where no such
operation took time."""
import re

LEAF = "ckeys"


def compressed_bytes(context: int, kernel_stride: int, n_kv_head: int,
                     head_dim: int, itemsize: int) -> int:
    """Bytes of compressed keys one row-step's selection must read a
    layer: one row of every K/V head a ``kernel_stride`` tokens, but for
    the context's last (incomplete) one."""
    return max((context - kernel_stride) // kernel_stride, 0) \
        * n_kv_head * head_dim * itemsize


def needles(ctx) -> list:
    """What marks the selection's operations: the leaf's name, the plane's
    own printed type (``[layers, num_blocks, segments, row]``: its gathers
    and scatters, whatever the value is called by then), and the gathered
    segments' printed types at the engine's table widths (``[rows x
    entries, segments, row]`` as gathered, ``[rows, entries x segments,
    row]`` as scored)."""
    keys = ctx["config"]["keys"]
    engine = ctx["traffic"]["engine"]
    per = keys["sparse_block_size"] // keys["kernel_stride"]
    row = keys["n_kv_head"] * keys["head_dim"]
    layers = list(keys["mixer_types"]).count("minicpm4")
    out = [re.compile(LEAF),
           re.compile(rf"f32\[{layers},{engine['num_blocks']},{per},{row}\]"),
           re.compile(rf"f32\[\d+,{per},{row}\]")]
    for bucket in engine["length_buckets"]:
        nb = -(-bucket // engine["block_size"])
        out.append(re.compile(rf"f32\[\d+,{nb * per},{row}\]"))
    return out


def read(ctx):
    reduced = ctx.get("trace")
    if not reduced or "sparse_block_size" not in ctx["config"]["keys"]:
        return None
    marks = needles(ctx)
    t = sum(v["self_s"] for name, v in reduced["ops"].items()
            if any(m.search(name) for m in marks)
            and " conditional(" not in name and " while(" not in name)
    if not t or not reduced["busy_s"]:
        return None
    return 100.0 * t / reduced["busy_s"]
