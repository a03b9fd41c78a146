"""The paged-attention kernel's WHOLE call (table walk, page copies, compute
blocks) timed alone on the chip, at the decode shapes of the benchmark's
cells and two prefill shapes (docs/MICROBENCHMARKS.md, PERF.md PR 43):

    chiprun -- python3 ray_tpu/benchmarks/paged_kernel_time.py <tree> <variant,...>

``<tree>`` is the checkout whose ``ray_tpu`` is imported (``.`` or a copy of
another commit under ``.scratch/``); a variant is ``base`` (the kernel as it
is) or ``t128`` / ``t256`` / ``t512`` / ``t1024`` (a few-row tile's block
pinned to so many tokens, whatever its row: the sweep ``_block_tokens``'s fit
and the latent call's ``_latent_tokens`` came from).
One JSON line a (shape, variant): the device's kind, the call's
microseconds by the host's clock around 20 calls in a row, and the share of
819 GB/s its attended K/V bytes make. ``ONLY=a,b`` keeps those shapes. Off
a TPU the script refuses; ``REHEARSE=1`` runs tiny shapes through the Pallas
interpreter to show that the script runs, and prints NO time."""
import json, os, statistics, sys, time
tree, variants = sys.argv[1], sys.argv[2].split(",")
sys.path.insert(0, os.path.abspath(tree))
import numpy as np
import jax, jax.numpy as jnp
from ray_tpu.ops import paged_attention as pa

rehearse = bool(os.environ.get("REHEARSE"))
device = jax.devices()[0]
if device.platform != "tpu" and not rehearse:
    sys.exit(f"paged_kernel_time: {device.platform} is no TPU: a time from "
             "it would mean nothing (REHEARSE=1 checks the script alone)")

# name: (B, Hq, Hkv, hd, mean context, max context (table), window)
SHAPES = {
    "cell1-mistral": (64, 32, 8, 128, 700, 2560, None),
    "cell6-laguna-full": (64, 48, 8, 128, 3400, 16384, None),
    "cell6-laguna-sliding": (64, 64, 8, 128, 3400, 16384, 512),
    "cell7-evabyte": (24, 32, 32, 128, 1500, 2816, None),
    "cell9-smallthinker-full": (48, 28, 4, 128, 5000, 16384, None),
    "cell9-smallthinker-sliding": (48, 28, 4, 128, 5000, 16384, 4096),
    "cell4-gpt2": (64, 12, 12, 64, 600, 1024, None),
    "cell5-lfm2": (64, 32, 8, 64, 700, 2560, None),
    # latent attention over a pool in planes (one 576-wide row a token for
    # all Hq heads; Hkv and hd unused): decode at the two cells' head counts
    "cell8-pangu-latent": (128, 128, 1, 576, 3000, 12288, "latent"),
    "cell10-longcat-latent": (96, 64, 1, 576, 1100, 6144, "latent"),
    # cell 12's one latent layer: 32 heads, contexts to 40,960 (two calls of
    # 64 rows: ``_latent_rows_a_call``)
    "cell12-ling-latent": (128, 32, 1, 576, 5000, 40960, "latent"),
    # a prefill chunk over a latent pool: 8 queries x the heads a tile
    "cell8-pangu-latent-chunk": (1, 128, 1, 576, 2047, 12288, "latent-chunk"),
    "cell10-longcat-latent-chunk": (1, 64, 1, 576, 1023, 6144, "latent-chunk"),
    # the same chunk in the EXPANDED form (ops/latent_prefill.py: keys and
    # values by head through ``flash_fwd``, what a prefill step runs since
    # ISSUE 51), its up-projections included, beside the absorbed call
    "cell8-pangu-expanded-chunk": (1, 128, 1, 576, 2047, 12288, "expanded-chunk"),
    "cell10-longcat-expanded-chunk": (1, 64, 1, 576, 1023, 6144, "expanded-chunk"),
    "cell1-prefill": (4, 32, 8, 128, 2047, 2048, "prefill"),
    "cell4-prefill": (4, 12, 12, 64, 1023, 1024, "prefill"),
}
if os.environ.get("ONLY"):
    SHAPES = {k: v for k, v in SHAPES.items() if k in os.environ["ONLY"].split(",")}
if rehearse:  # tiny, for the interpreter
    SHAPES = {k: (2, v[1], v[2], v[3], 100, 320,
                  v[6] if isinstance(v[6], str) else v[6] and 64)
              for k, v in list(SHAPES.items())[:3]}
bs = 16
rng = np.random.default_rng(0)
out_lines = []
for name, (B, Hq, Hkv, hd, mean, top, window) in SHAPES.items():
    NB = top // bs
    expanded = window == "expanded-chunk"
    chunk = window == "latent-chunk" or expanded
    prefill = window == "prefill"
    latent = window in ("latent", "latent-chunk", "expanded-chunk")
    if prefill or latent:
        window = None
    ctx = np.clip(rng.lognormal(np.log(mean), 0.5, B).astype(int), 16, top - 1)
    if prefill or chunk:
        ctx[:] = mean
    need = [-(-int(c + 1) // bs) for c in ctx]
    num_blocks = sum(need) + 1
    ids = rng.permutation(np.arange(1, num_blocks))
    tables = np.zeros((B, NB), np.int32)
    at = 0
    for b, n in enumerate(need):
        tables[b, :n] = ids[at:at + n]; at += n
    shape = pa.pool_shape(2, num_blocks, bs, Hkv, hd)
    key = jax.random.PRNGKey(1)
    k_pool = jax.random.normal(key, shape, jnp.bfloat16)
    v_pool = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.bfloat16)
    q = jax.random.normal(jax.random.fold_in(key, 2), (B, Hq, hd), jnp.bfloat16)
    tables_d, pos = jnp.asarray(tables), jnp.asarray(ctx, jnp.int32)
    if prefill:
        q = jax.random.normal(jax.random.fold_in(key, 2), (B, mean + 1, Hq, hd), jnp.bfloat16)
        pos = jnp.broadcast_to(jnp.arange(mean + 1, dtype=jnp.int32), (B, mean + 1))
    attended = np.minimum(ctx + 1, window) if window else ctx + 1
    kv_bytes = int(sum(-(-int(a) // bs) * bs for a in attended)) * Hkv * hd * 2 * 2
    if latent:  # ONE row a token, key and value: 512 + 64 numbers, in planes
        kv_bytes //= 2
        k_pool = jax.random.normal(key, (2, num_blocks, bs, 512), jnp.bfloat16)
        v_pool = jnp.pad(jax.random.normal(
            jax.random.fold_in(key, 1), (2, num_blocks, bs, 64), jnp.bfloat16),
            ((0, 0),) * 3 + ((0, 64),))
        if hasattr(pa, "latent_row"):  # ONE plane since ISSUE 53: the same
            # numbers, a row [c | k_rope | zeros]; two planes in a parent's tree
            k_pool, v_pool = jnp.concatenate([k_pool, v_pool], -1), None
        q = jax.random.normal(jax.random.fold_in(key, 2), (B, 1, Hq, 576), jnp.bfloat16)
        pos = pos[:, None]
        if chunk:  # the whole prompt as one chunk: causal, a frontier a tile
            q = jax.random.normal(jax.random.fold_in(key, 2), (B, mean + 1, Hq, 576), jnp.bfloat16)
            pos = jnp.broadcast_to(jnp.arange(mean + 1, dtype=jnp.int32), (B, mean + 1))
            kv_bytes //= 2  # a tile attends up to its frontier: half on average
        if expanded:  # q as projected, the chunk's own rows, W_uk and W_uv
            rnd = lambda i, *shape: jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.bfloat16)
            q = q[..., :192]
            own = (rnd(3, B, mean + 1, 512), rnd(4, B, mean + 1, 64),
                   rnd(5, 512, Hq, 128), rnd(6, 512, Hq, 128))
    ref = None
    for variant in variants:
        block_tokens = getattr(pa, "_block_tokens", None)
        latent_tokens = getattr(pa, "_latent_tokens", None)
        for n in (128, 256, 512, 1024):
            if variant == f"t{n}":
                pa._block_tokens = lambda R, row_bytes=None, n=n: (
                    n if R < pa._MANY_ROWS else 2 * pa._BLOCK_TOKENS)
                if latent_tokens is not None:  # a latent tile of few rows
                    pa._latent_tokens = lambda rows, n=n: (
                        n if rows <= pa._MANY_ROWS else latent_tokens(rows))
        jax.clear_caches()  # the latent call is behind a jit of its own
        try:
            attend = pa.prefill_attention if prefill else pa.decode_attention
            # the pools: K and V, a parent's two planes, or the one plane
            pools = (k_pool,) if v_pool is None else (k_pool, v_pool)
            fn = jax.jit(lambda q, t, p, *kv: attend(
                q, *kv, t, p, backend="pallas", window=window, layer=jnp.int32(1))
                if not latent else pa.latent_attention(
                    q, *kv, t, p, latent_dim=512, scale=192 ** -0.5,
                    backend="pallas", layer=jnp.int32(1)))
            if expanded:
                from ray_tpu.ops.latent_prefill import expanded_prefill_attention
                fn = jax.jit(lambda q, t, p, *kv: expanded_prefill_attention(
                    q, *own[:2], *kv, t, p >= 0, None, *own[2:],
                    scale=192 ** -0.5, backend="pallas", layer=jnp.int32(1)))
            o = jax.block_until_ready(fn(q, tables_d, pos, *pools))
            times = []
            for _ in range(7):
                t0 = time.perf_counter()
                for _ in range(20):
                    o = fn(q, tables_d, pos, *pools)
                jax.block_until_ready(o)
                times.append((time.perf_counter() - t0) / 20)
            t = statistics.median(times)
            o32 = np.asarray(o.astype(jnp.float32))
            if ref is None:
                ref = o32
            line = {"tree": tree, "shape": name, "variant": variant,
                    "device_kind": device.device_kind, "kv_mb": kv_bytes / 1e6,
                    "max_diff_vs_first": float(np.max(np.abs(o32 - ref)))}
            if not rehearse:
                line.update(call_us=t * 1e6, gb_per_s=kv_bytes / t / 1e9,
                            hbm_pct=kv_bytes / t / 819e9 * 100)
        except Exception as e:
            line = {"tree": tree, "shape": name, "variant": variant, "error": str(e)[-300:]}
        finally:
            if block_tokens is not None:
                pa._block_tokens = block_tokens
            if latent_tokens is not None:
                pa._latent_tokens = latent_tokens
        print(json.dumps(line), flush=True)
        out_lines.append(line)
os.makedirs("chiprun_out", exist_ok=True)
with open(f"chiprun_out/kernel_time-{tree.strip('./').replace('/', '_') or 'change'}.json", "w") as f:
    json.dump(out_lines, f, indent=1)
