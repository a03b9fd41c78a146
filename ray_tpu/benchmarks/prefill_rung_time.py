"""A serving cell's prefill CHUNK program timed alone on the chip at every
row count of the packed ladder (rows of one 128-token q tile under the widest
context's table) against the one-row program over the same tokens
(docs/MICROBENCHMARKS.md, PERF.md PR 47):

    chiprun -- python3 ray_tpu/benchmarks/prefill_rung_time.py <workload> [<tree>]

``<workload>`` is a serving cell of BENCHMARK.json (its configuration's
weights from a seed, its pool, its engine's buckets); ``<tree>`` the checkout
whose ``ray_tpu`` and ``benchmark`` are imported (``.`` by default). The step
programs are called as the executor calls them (``DecodeFns.prefill`` with
``start``, the pools donated and rebound), each over ONE prompt's tokens from
position ``at``: as ``[1, n]`` under a table of the context's bucket, and as
``[rows, 128]`` pieces under the widest table, for every rung ``rows`` of the
engine's ladder (``stepped_buckets``' default where it has none) and ``n =
rows x 128``. One JSON line a (form, rows, at): milliseconds
a call by the host's clock around ``REPS`` calls in a row ended in
``block_until_ready``, after one call that compiled. Off a TPU the script
refuses; ``REHEARSE=1`` (with ``BENCHMARK_REHEARSAL=1`` for the tiny
configuration) shows that the script runs and prints NO time."""
import json
import os
import sys
import time

workload = sys.argv[1]
tree = os.path.abspath(sys.argv[2] if len(sys.argv) > 2 else ".")
sys.path.insert(0, tree)
os.chdir(tree)
import jax
import numpy as np

from benchmark import common
from benchmark.runners import serve_engine
from ray_tpu.serve._shapes import pad_to_bucket, stepped_buckets

rehearse = bool(os.environ.get("REHEARSE"))
device = jax.devices()[0]
if device.platform != "tpu" and not rehearse:
    sys.exit(f"prefill_rung_time: {device.platform} is no TPU: a time from "
             "it would mean nothing (REHEARSE=1 checks the script alone)")
REPS = 2 if rehearse else 10

spec = common.resolve_cell(common.load_manifest(), workload)
if not rehearse:
    spec["traffic"]["engine"] = dict(
        spec["traffic"]["engine"], attention_backend="pallas")
cfg = common.model_config(spec["config"])
params = jax.block_until_ready(serve_engine.make_params(spec, cfg, 12345))
engine = serve_engine.make_engine(spec, cfg, params, auto_step=False)
ex, settings = engine.executor, spec["traffic"]["engine"]
bs, chunk = settings["block_size"], settings["prefill_chunk_tokens"]
buckets = list(settings["length_buckets"])
piece = min(128, chunk)
# the engine's own ladder where it packs this family (else the default's)
ladder = getattr(engine, "_piece_rows", None) or stepped_buckets(
    -(-chunk // piece))
rng = np.random.default_rng(0)


def call(tokens, lengths, starts, tables):
    B = tokens.shape[0]
    with engine._lock:
        sample = engine._sample_args_locked([], B)
    slots = None if ex.cache.state is None else np.ones((B,), np.int32)
    out, ex.cache.k, ex.cache.v, ex.cache.state = ex.fns.prefill(
        ex.params, ex.cache.k, ex.cache.v, tokens, lengths, tables,
        start=starts, sample=sample, state=ex.cache.state, slots=slots)
    return out


def timed(form, rows, at):
    """``rows x piece`` tokens of one prompt from position ``at``."""
    n = rows * piece
    ids = rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
    held = np.arange(1, -(-(at + n) // bs) + 1, dtype=np.int32)
    if form == "one-row":
        S = pad_to_bucket(n, buckets)
        nb = pad_to_bucket(at + n, buckets) // bs
        tokens = np.zeros((1, S), np.int32)
        tokens[0, :n] = ids
        lengths = np.array([n], np.int32)
        starts = np.array([at], np.int32)
    else:
        nb = buckets[-1] // bs
        tokens = ids.reshape(rows, piece)
        lengths = np.full((rows,), piece, np.int32)
        starts = (at + piece * np.arange(rows)).astype(np.int32)
    tables = np.zeros((tokens.shape[0], nb), np.int32)
    tables[:, :len(held)] = held
    jax.block_until_ready(call(tokens, lengths, starts, tables))
    t = time.perf_counter()
    for _ in range(REPS):
        out = call(tokens, lengths, starts, tables)
    jax.block_until_ready(out)
    ms = (time.perf_counter() - t) / REPS * 1e3
    line = {"workload": workload, "device": device.device_kind,
            "form": form, "rows": tokens.shape[0], "row_len": tokens.shape[1],
            "table_words": nb, "tokens": n, "at": at,
            "ms": None if rehearse else round(ms, 3)}
    print(json.dumps(line), flush=True)


# what a prompt's first chunk costs, whole and in part, and a second chunk
# (attention over a resident chunk besides its own)
for rows in ladder:
    timed("pieces", rows, 0)
for rows in sorted({1, ladder[len(ladder) // 2], ladder[-1]}):
    timed("one-row", rows, 0)
for form in ("pieces", "one-row"):
    timed(form, ladder[-1], chunk)
engine.shutdown()
os._exit(0)  # the runtime's threads have nothing to flush
