"""Kernels: device time in the shared expert's operations, as a share of
busy time: what reads the ``moe_shared_w_in`` / ``moe_shared_w_out``
leaves. An operation of the trace is named by its HLO text, operands'
names included, so an operation that reads a leaf directly holds the
leaf's name. The compiler may first bring a leaf into fast memory in
slices (``slice-start`` / ``slice-done``, joined by a ``ConcatBitcast``
custom call): the product then reads the joined copy, under ITS name. So
the names are followed: a value made from the leaf by such a move carries
the leaf, and what reads a carrier is counted, the moves themselves too.
Nothing where no such operation took time."""
import re

from benchmark import trace_reduce

NEEDLE = "moe_shared"
# operations that only move or rename bytes (their result IS the operand),
# by the result's name: the trace prints an asynchronous pair's opcode as
# ``async-start`` / ``async-done`` whatever it moves
MOVES = ("%slice-start", "%slice-done", "%copy-start", "%copy-done",
         "%bitcast")
JOINS = 'custom_call_target="ConcatBitcast"'
_VALUE = re.compile(r"%[\w.\-]+")


def readers_of(names, needle: str = NEEDLE) -> set:
    """The operations among ``names`` (HLO texts) that read a leaf whose
    name holds ``needle``, directly or through moves."""
    parsed = []
    for text in names:
        head, sep, body = text.partition(" = ")
        result = _VALUE.search(head)
        if not sep or result is None:
            parsed.append((text, None, {text}))  # a bare name: itself
            continue
        parsed.append((text, result.group(0), set(_VALUE.findall(body))))
    carriers, found = set(), set()
    for _ in range(6):  # leaf -> start -> done -> joined: a short chain
        grew = False
        for text, result, operands in parsed:
            if text in found or not (
                    needle in text or operands & carriers):
                continue
            found.add(text)
            if result is not None and (
                    result.startswith(MOVES) or JOINS in text):
                carriers.add(result)
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
