"""The parts of a latent layer's prefill attention timed alone on the chip,
at cell 8's (128 heads, chunks of 2,048) and cell 10's (64 heads, 1,024)
shapes (docs/MICROBENCHMARKS.md, PERF.md PR 51):

    chiprun -- python3 ray_tpu/benchmarks/latent_prefill_parts.py

- ``flash``: ``flash_fwd`` alone over ``[H, T, 192]`` keys and ``[H, T,
  128]`` values with segment ids, causal (a chunk's own keys) and not (a
  resident block), at square blocks of 256 / 512 / 1,024 (``BLOCKS=``:
  other shapes and blocks);
- ``expand``: the up-projection of one block of rows ``[T, 512 | 64]`` to
  keys and values by head;
- ``join``: what stands around the ABSORBED kernel's call in a chunk
  program: ``[q~ | q_rope]`` joined, scaled, each part padded to its stored
  width and laid out as the kernel's rows ``[T * H, 640]``;
- ``expanded`` / ``absorbed``: the whole call of each form over the pool,
  a first chunk (nothing resident) and a second (one chunk resident), in
  the packed form the engine launches (rows of 128 tokens under one table;
  ``ROWS=``: other rungs of the ladder than the top one).

One JSON line a (cell, part, variant): microseconds by the host's clock
around 10 calls in a row (median of 5), and the EXPANDED form's operations
(``2 H (192 + 128)`` a pair) over that time against 197 TFLOP/s. ``ONLY=``
keeps parts, ``CELLS=`` cells, ``PREFIX=`` sets the op's block of prefix
keys. Off a TPU the script refuses; ``REHEARSE=1`` runs tiny shapes
through the interpreter and prints no time."""
import functools, json, os, statistics, sys, time
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp

from ray_tpu.ops import latent_prefill as lp
from ray_tpu.ops.attention import _flash_forward
from ray_tpu.ops.paged_attention import latent_attention, latent_row

rehearse = bool(os.environ.get("REHEARSE"))
device = jax.devices()[0]
if device.platform != "tpu" and not rehearse:
    sys.exit(f"latent_prefill_parts: {device.platform} is no TPU "
             "(REHEARSE=1 checks the script alone)")
if os.environ.get("PREFIX"):  # another block of prefix keys than the op's
    lp.PREFIX_BLOCK = int(os.environ["PREFIX"])
only = os.environ.get("ONLY", "flash,expand,join,expanded,absorbed").split(",")
# cell: (heads, chunk tokens, table entries)
CELLS = {"cell8": (128, 2048, 768), "cell10": (64, 1024, 384)}
if os.environ.get("CELLS"):
    CELLS = {k: CELLS[k] for k in os.environ["CELLS"].split(",")}
if rehearse:
    CELLS = {"tiny": (4, 256, 48)}
C, R, N, V, bs, P = 512, 64, 128, 128, 16, 128
bf16 = jnp.bfloat16
lines = []


def timed(fn, *args):
    out = jax.block_until_ready(fn(*args))
    if rehearse:
        return None, out
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(10):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / 10)
    return statistics.median(times), out


def say(cell, part, variant, t, pairs=None, **more):
    H = CELLS[cell][0]
    line = {"cell": cell, "part": part, "variant": variant,
            "device_kind": device.device_kind, **more}
    if t is not None:
        line["call_us"] = t * 1e6
        if pairs:
            line["mxu_pct_expanded_ops"] = (
                2 * pairs * H * (N + R + V) / t / 197e12 * 100)
    print(json.dumps(line), flush=True)
    lines.append(line)


for cell, (H, T, NB) in CELLS.items():
    key = jax.random.PRNGKey(0)
    rnd = lambda i, *shape: jax.random.normal(
        jax.random.fold_in(key, i), shape, bf16)
    if "flash" in only:
        # BLOCKS="<queries>x<keys>:<block_q>x<block_kv>,..." times other
        # shapes and blocks than the square ones
        sweep = [(T, T, b, b) for b in (256, 512, 1024)]
        if os.environ.get("BLOCKS"):
            sweep = [tuple(int(n) for part in item.split(":")
                           for n in part.split("x"))
                     for item in os.environ["BLOCKS"].split(",")]
        for Tq, Tk, bq, bkv in sweep:
            q, k, v = rnd(1, 1, H, Tq, N + R), rnd(2, 1, H, Tk, N + R), rnd(3, 1, H, Tk, V)
            q_seg, k_seg = jnp.zeros((Tq,), jnp.int32), jnp.zeros((Tk,), jnp.int32)
            for causal in (True, False) if Tq == Tk else (False,):
                variant = f"{'causal' if causal else 'full'}-{Tq}x{Tk}-b{bq}x{bkv}"
                fn = jax.jit(functools.partial(
                    _flash_forward, causal=causal, scale=192 ** -0.5,
                    block_q=bq, block_kv=bkv, interpret=rehearse,
                    save_lse=True))
                try:
                    t, _ = timed(lambda q, k, v: fn(q, k, v, q_seg=q_seg, k_seg=k_seg), q, k, v)
                    say(cell, "flash", variant, t,
                        pairs=Tq * (Tq + 1) // 2 if causal else Tq * Tk)
                except Exception as e:  # noqa: BLE001 — a variant the compiler refuses
                    say(cell, "flash", variant, None, error=str(e)[-300:])
    w_uk, w_uv = rnd(4, C, H, N), rnd(5, C, H, V)
    if "expand" in only:
        t, _ = timed(jax.jit(lp._expand), rnd(6, T, C), rnd(7, T, R), w_uk, w_uv)
        say(cell, "expand", f"{T}-rows", t)
    rows = T // P
    if "join" in only:
        def join(q_abs, q_rope):
            q = jnp.concatenate([q_abs, q_rope], axis=-1)
            q = q * jnp.asarray(0.1, q.dtype)
            return jnp.concatenate([
                q[..., :C], jnp.pad(q[..., C:], ((0, 0),) * 3 + ((0, 128 - R),)),
            ], axis=-1).reshape(rows, P * H, C + 128)
        t, _ = timed(jax.jit(join), rnd(8, rows, P, H, C), rnd(9, rows, P, H, R))
        say(cell, "join", f"{T}x{H}x640", t)
    if "expanded" in only or "absorbed" in only:
        num_blocks = 2 * T // bs + 1
        pool = latent_row(rnd(10, 2, num_blocks, bs, C), rnd(11, 2, num_blocks, bs, R))
        table = jnp.zeros((NB,), jnp.int32).at[:2 * T // bs].set(
            jnp.arange(1, 2 * T // bs + 1, dtype=jnp.int32))
        layer = jnp.int32(1)
        # ROWS="16,13,9": other rungs of the packed ladder than the top one
        for rows in [int(n) for n in os.environ.get("ROWS", str(rows)).split(",")]:
            tables = jnp.broadcast_to(table, (rows, NB))
            valid = jnp.ones((rows, P), bool)
            for name, first in (("first-chunk", 0), ("second-chunk", T)):
                start = first + P * jnp.arange(rows, dtype=jnp.int32)
                pos = start[:, None] + jnp.arange(P, dtype=jnp.int32)[None]
                n = rows * P
                pairs = n * first + n * (n + 1) // 2
                if "expanded" in only:
                    fn = jax.jit(lambda q, c, r, start: lp.expanded_prefill_attention(
                        q, c, r, pool, tables, valid, start, w_uk, w_uv,
                        scale=192 ** -0.5, backend="pallas", layer=layer))
                    t, _ = timed(fn, rnd(12, rows, P, H, N + R), rnd(13, rows, P, C),
                                 rnd(14, rows, P, R), start)
                    say(cell, "expanded", f"{name}-{rows}x{P}", t, pairs=pairs)
                if "absorbed" in only:
                    fn = jax.jit(lambda q, pos: latent_attention(
                        q, pool, tables, pos, latent_dim=C,
                        scale=192 ** -0.5, backend="pallas", layer=layer))
                    t, _ = timed(fn, rnd(15, rows, P, H, C + R), pos)
                    say(cell, "absorbed", f"{name}-{rows}x{P}", t, pairs=pairs)
os.makedirs("chiprun_out", exist_ok=True)
with open("chiprun_out/latent_prefill_parts.json", "w") as f:
    json.dump(lines, f, indent=1)
