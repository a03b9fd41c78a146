"""Device: how well the offset between the host's and the device's clock is
known, in us: ``span_reduce``'s ceiling (the least ``run start - dispatch
start``) less the floor from EVERY sync paired with its run by ``seq`` (the
most ``run end - sync end``: ``host_reduce.offset_floor_by_seq``). The
instrument's own error bar: an idle gap shorter than this cannot be laid
under a span with certainty. Nothing where the spans carry no ``seq``."""
from benchmark import host_reduce


def read(ctx):
    found = host_reduce.load(ctx)
    if not found or found["offset_floor_by_seq_us"] is None:
        return None
    return found["clock_offset_us"] - found["offset_floor_by_seq_us"]
