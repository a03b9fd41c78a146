"""``lognormal_chat``'s requests in an order that no seed changes.

For a closed loop whose requests are few and large. ``lognormal_chat``
gives every seed the same SET of sizes and lets the seed permute each block
of ``strata``; that is enough where a window holds many blocks. Where a
window holds less than one (cell 11: a block of 32 prompts of 4.5k-49k
tokens is ~37 s of the chip, the window 30 s), WHICH of a block's quantiles
fall inside the window's edges is the seed's, and six seeds' windows differ
by the prompts they happened to admit (13% between quartiles, PERF.md
section 6, PR 48), not by anything the system did.

Here request ``i`` has the same sizes for every seed; the seed picks the
token ids alone (and, in the runner, the weights and the reference check's
prompts). Within a block of ``strata`` (a power of two) request ``j`` takes

- the prompt quantile ``bit_reversed(j)``: any run of consecutive
  admissions holds long and short prompts in near-even shares, so a
  window's prefill work does not hang on where its edges fall;
- the output quantile ``(output_stride * j + output_offset) mod strata``
  (``output_stride`` odd): every quantile once a block, uncorrelated with
  the prompts' order (for 32, 11 and 5: r = 0.00).

Every block has the same order. The sizes themselves, their clips and the
closed loop are ``lognormal_chat``'s, number for number.

Parameters read from the traffic file: ``lognormal_chat``'s, with
``arrivals.mode`` ``closed``, and::

    "order": {"output_stride": 11, "output_offset": 5}
"""
from __future__ import annotations

from benchmark.generators import lognormal_chat


def bit_reversed(n: int) -> list[int]:
    """``j -> j``'s bits read backwards, for a power of two ``n``."""
    bits = n.bit_length() - 1
    if n < 1 or 1 << bits != n:
        raise ValueError(f"strata must be a power of two, not {n}")
    return [int(format(j, f"0{bits}b")[::-1], 2) if bits else 0
            for j in range(n)]


class Schedule(lognormal_chat.Schedule):
    def __init__(self, traffic: dict, seed: int, vocab_size: int):
        super().__init__(traffic, seed, vocab_size)
        if self.open_loop:
            raise ValueError("lognormal_chat_ordered is for a closed loop")
        stride = int(traffic["order"]["output_stride"])
        offset = int(traffic["order"]["output_offset"])
        if stride % 2 == 0:
            raise ValueError("order.output_stride must be odd")
        n = self.strata
        self._order = (bit_reversed(n),
                       [(stride * j + offset) % n for j in range(n)], None)

    def _block(self, b: int) -> tuple:
        """Every block's order, whatever the seed."""
        return self._order


def build(traffic: dict, seed: int, vocab_size: int) -> Schedule:
    return Schedule(traffic, seed, vocab_size)
