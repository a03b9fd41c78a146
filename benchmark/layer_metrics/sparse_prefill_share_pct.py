"""Kernels: a selecting layer's attention in PREFILL (its selection and
the attention over what was selected), as a share of the prefill runs'
device time in the slice. The step's attention of such a layer is one
``conditional`` (ops/sparse_select.py ``sparse_prefill_attention``: a chunk
with a query at or past ``dense_len`` takes the masked form, a tile of
queries at a time, each tile's selection inside it; a chunk wholly below
takes the dense paged kernel), and a trace shows a conditional as one
event with its branch's operations inside: the whole of each such event
inside the run of a prefill step that holds a query at or past
``dense_len`` (``tokens_sparse`` > 0 on its dispatch span: the branch is
taken on exactly that; a step wholly below ran the dense paged kernel
inside its conditionals, which is not this metric's) is counted, over
ALL the prefill runs' time. Beside it, as information, the share of the
slice's prefill tokens at or past ``dense_len`` (``tokens_sparse`` over
``tokens``). INFORMATIONAL: the operations are found by a printed word of
XLA's text, not by a name the program gave them (PERF.md 7 ac). Nothing
where no such step ran."""
from benchmark import common, span_reduce

KINDS = ("prefill", "prefill_chunk")


def outermost(calls: list[tuple]) -> list[tuple]:
    """Of ``(start, end)`` events that may nest, those inside no other."""
    out, end = [], -1.0
    for s, e in sorted(calls):
        if s >= end:
            out.append((s, e))
            end = e
    return out


def read(ctx):
    raw, reduced = span_reduce.load(ctx)
    if not reduced or "sparse_block_size" not in ctx["config"]["keys"]:
        return None
    calls = outermost([(s, e) for name, s, e in raw["planes"][0]["ops"]
                       if " conditional(" in name])
    inside_ns, run_ns, steps, tokens, sparse = 0.0, 0.0, 0, 0, 0
    for step in reduced["steps"]:
        a = step["attrs"]
        if a.get("kind") not in KINDS or not step["inside"] \
                or span_reduce.PROGRAM_OF["prefill"] not in step["run"][0]:
            continue
        if int(a.get("tokens_sparse", 0)) > 0:
            inside_ns += span_reduce.time_inside(
                calls, step["run"][1], step["run"][2])
        run_ns += step["run"][2] - step["run"][1]
        tokens += int(a.get("tokens", 0))
        sparse += int(a.get("tokens_sparse", 0))
        steps += 1
    if not steps or not inside_ns:
        return None
    common.say(f"selecting layers' prefill attention: {steps} prefill runs, "
               f"{inside_ns / steps / 1e6:.2f} ms of "
               f"{run_ns / steps / 1e6:.2f} ms a run; "
               f"{100.0 * sparse / max(tokens, 1):.1f}% of their "
               f"{tokens} tokens at or past dense_len")
    return 100.0 * inside_ns / run_ns
