"""Executor: the id gather of a decode step whose rows joined or left
(``executor.feed``, inside ``executor.stage``: one small transfer and the
launch of ``jit_feed_ids``), in ms a call, from ``engine.stats()["host"]
["spans"]`` at the window's two ends."""
from benchmark import host_reduce


def read(ctx):
    host = host_reduce.host_delta(ctx)
    if not host or not host["spans"].get("executor.feed", [0])[0]:
        return None
    count, seconds, _ = host["spans"]["executor.feed"]
    return 1e3 * seconds / count
