"""Kernels: device time in the absorb and un-absorb products of latent
attention (``q~_h = q_nope,h W_uk,h^T`` before the kernel, ``o_h = o~_h
W_uv,h`` after it), as a share of busy time: the operations whose text
holds ``mla_w_uk`` or ``mla_w_uv``, the leaves' names, directly or through
the moves that relay a leaf or bring it into fast memory (``copy``,
``copy-start`` / ``-done``, ``slice-start`` / ``-done``, ``bitcast``,
``ConcatBitcast``): a leaf is followed by name, as
``shared_expert_share_pct`` does. A trace holds several programs and each
numbers its instructions anew, so a moved value is followed by its name
AND its printed type (``bf16[512,16384]{..} %copy-done.15``: an operand is
printed so): ``%copy-done.15`` of another program, a block table, is
another value. Whole fusions are counted, whatever else they fuse (the
transposes around the product): errs high. Small and latency-bound in
decode (128 rows x 128 heads), the price of reading a latent row once.
Nothing where no such operation took time."""
NEEDLES = ("mla_w_uk", "mla_w_uv")
# operations whose result IS their operand, moved or relaid, by the
# result's name
MOVES = ("%copy", "%slice-start", "%slice-done", "%bitcast")
JOINS = 'custom_call_target="ConcatBitcast"'


def _parts(text: str):
    """``(name, type, rest)`` of ``%name = type opcode(operands), ..``;
    None for a bare name."""
    head, sep, body = text.partition(" = ")
    if not sep:
        return None
    depth = 0
    for i, ch in enumerate(body):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            return head.strip(), body[:i], body[i + 1:]
    return None


def readers_of(names, needles=NEEDLES) -> set:
    """The operations among ``names`` (HLO texts) that read a leaf whose
    name holds one of ``needles``, directly or through moves."""
    parsed = [(text, _parts(text)) for text in names]
    carriers, found = set(), set()
    for _ in range(6):  # leaf -> copy -> start -> done -> joined
        grew = False
        for text, parts in parsed:
            if text in found:
                continue
            rest = text if parts is None else parts[2]
            if not (any(n in rest for n in needles)
                    or any(c in rest for c in carriers)):
                continue
            found.add(text)
            if parts is not None and (
                    parts[0].startswith(MOVES) or JOINS in text):
                carriers.add(f"{parts[1]} {parts[0]}")
                grew = True
        if not grew:
            break
    return found


def read(ctx):
    reduced = ctx.get("trace")
    if not reduced:
        return None
    mine = readers_of(reduced["ops"])
    if not any(reduced["ops"][name]["self_s"] > 0 for name in mine):
        return None
    return 100.0 * sum(reduced["ops"][name]["self_s"] for name in mine) \
        / reduced["busy_s"]
