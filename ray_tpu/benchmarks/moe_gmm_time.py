"""The expert layer's grouped product timed alone on the chip, at the decode
shapes of the benchmark's seven expert cells (docs/MICROBENCHMARKS.md,
PERF.md PR 49, PR 57):

    chiprun -- python3 ray_tpu/benchmarks/moe_gmm_time.py <tree> <part,...>

``<tree>`` is the checkout whose ``ray_tpu`` is imported (``.`` or a copy of
another commit under ``.scratch/``). A shape is a cell's decode step: T rows,
the router's outputs, ``top_k``, ``experts_held``, ``d_model``, ``d_expert``
(and the share of its rows that are padding: cell 13's folded block pass);
the picks are drawn as the cells' routers draw them over random weights (k
distinct experts a row, evenly), so the group sizes are the cells'. A part is

- ``layer``: ``moe_dropless`` whole, in the form the tree's rule chooses;
- ``ragged`` / ``few``: the same with the form forced (a tree that has
  ``ops.moe.gmm_form``); ``few:<rows>:<MiB>`` pins the kernel's row tile
  and its weight tile;
- ``kernel``: the ``moe_gmm_few_rows`` call alone on sorted rows;
- ``in`` / ``out``: each ``jax.lax.ragged_dot`` call alone at m = T x k;
  ``in@128`` / ``out@256``: the same groups with m cut to so many rows
  (suspect 1: the rows that belong to no group);
- ``around``: ``moe_dropless`` with both products replaced by a row sum
  broadcast to the product's shape (suspect 4: the gather, the float32
  temporaries of m rows, the scatter back).

The shapes ``cell8-d6144`` / ``cell8-d8192`` hold cell 8's bytes an expert
under another ``d_model`` (suspect 2) and ``cell6-8x4`` laguna's bytes as 8
groups of four experts' width (suspect 3). One JSON line a (shape, part):
the device's kind, microseconds a call (the host's clock around ONE program
that makes 16 calls in a row, each fed a word of the one before, so that
the host's ~240 us a dispatch is not in it), GB/s and the share of 819 GB/s
over the bytes of the experts that met a row (the part's own matrices),
and beside them ``items``, the work items the kernel's list holds for these
groups (each streams its expert once: ``ops.moe.few_rows_items``; a tree
from before PR 57 made one a tile of 128 sorted rows a group reaches). The
``kernel`` part also says how far its rows stand from ``ragged_dot``'s.
``ONLY=a,b`` keeps those shapes; ``cell8-pangu@2048`` is the shape at
another T (a prefill step's rows, none of them padding). Off a TPU the script refuses; ``REHEARSE=1`` runs tiny shapes (a kernel through
the Pallas interpreter) to show that the script runs, and prints NO time."""
import contextlib, json, os, statistics, sys, time
from unittest import mock
tree, parts = sys.argv[1], sys.argv[2].split(",")
sys.path.insert(0, os.path.abspath(tree))
rehearse = bool(os.environ.get("REHEARSE"))
if rehearse:
    os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"
import numpy as np
import jax, jax.numpy as jnp
from ray_tpu.ops import moe

device = jax.devices()[0]
if device.platform != "tpu" and not rehearse:
    sys.exit(f"moe_gmm_time: {device.platform} is no TPU: a time from it "
             "would mean nothing (REHEARSE=1 checks the script alone)")

# name: (T rows, router outputs, top_k, held (first, count) or None,
#        d_model, d_expert, zero_from or None, the share of rows that are
#        padding)
SHAPES = {
    "cell5-lfm2": (64, 64, 4, None, 2048, 1536, None, 0),
    "cell6-laguna": (64, 256, 8, (0, 32), 2048, 512, None, 0),
    "cell8-pangu": (128, 256, 8, (0, 8), 7680, 2048, None, 0),
    "cell9-smallthinker": (48, 64, 6, None, 2560, 768, None, 0),
    "cell10-longcat": (96, 768, 12, (0, 16), 6144, 2048, 512, 0),
    "cell12-ling": (128, 512, 8, (0, 64), 2560, 768, None, 0),
    # a folded block pass: 128 rows x 8 positions, of which a quarter hold
    # no token (PERF.md section 6, PR 55)
    "cell13-sdar": (1024, 128, 8, None, 2048, 768, None, 0.25),
    # suspect 2: cell 8's 15.7 M numbers a matrix under another d_model
    "cell8-d6144": (128, 256, 8, (0, 8), 6144, 2560, None, 0),
    "cell8-d8192": (128, 256, 8, (0, 8), 8192, 1920, None, 0),
    # suspect 3: cell 6's bytes as 8 groups of four experts end to end
    "cell6-8x4": (64, 64, 8, (0, 8), 2048, 2048, None, 0),
}
if os.environ.get("ONLY"):  # a name, or name@T: the shape at another T
    SHAPES = {k: ((int(k.partition("@")[2]), *SHAPES[k.partition("@")[0]][1:7], 0)
                  if "@" in k else SHAPES[k])
              for k in os.environ["ONLY"].split(",")}
if rehearse:  # tiny, for the interpreter
    SHAPES = {k: (8, v[1] // 8, min(v[2], 4), v[3] and (0, max(2, v[3][1] // 8)),
                  256, 128, v[6] and v[6] // 8, v[7])
              for k, v in list(SHAPES.items())[:7]}


def items_of(sizes):
    """The work items the tree's kernel makes of these groups."""
    if hasattr(moe, "few_rows_items"):
        return int(moe.few_rows_items(sizes))
    tm = moe._FEW_ROWS_TILE  # before PR 57: a tile of sorted rows a group reaches
    ends = np.cumsum(sizes)
    return int(np.where(sizes > 0, (ends - 1) // tm - (ends - sizes) // tm + 1, 0).sum())


def stub_ragged_dot(lhs, rhs, group_sizes, **kw):
    """The product's shape and dtype from a row sum: what stands around the
    calls keeps its reads and writes, the weights are not read."""
    s = jnp.sum(lhs.astype(jnp.float32), axis=-1, keepdims=True)
    return jnp.broadcast_to(s + group_sizes[0], (lhs.shape[0], rhs.shape[-1]))


REPS = 1 if rehearse else 16


def time_call(fn, small, *rest):
    """(seconds a call, one call's output) of ``fn(small, *rest)``: REPS
    calls inside one program, ``small`` (the group sizes; the layer's x, on
    which ALL of it depends: carried routing weights let the compiler lift
    the products out of the loop) carried through with a bump that depends
    on the call's output and is always 0."""
    def loop(small, *rest):
        def body(_, s):
            r = fn(s, *rest)
            word = r.reshape(-1)[0].astype(jnp.float32)
            return s + jnp.where(word > 3e38, 1, 0).astype(s.dtype)
        return jax.lax.fori_loop(0, REPS, body, small)
    loop = jax.jit(loop)
    jax.block_until_ready(loop(small, *rest))
    times = []
    for _ in range(1 if rehearse else 5):
        t0 = time.perf_counter()
        jax.block_until_ready(loop(small, *rest))
        times.append((time.perf_counter() - t0) / REPS)
    return statistics.median(times), jax.jit(fn)(small, *rest)


rng = np.random.default_rng(0)
out_lines = []
for name, (T, E_all, k, held, D, F, zero_from, padding) in SHAPES.items():
    first, E = held or (0, E_all)
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (T, D), jnp.bfloat16)
    w_in = jax.random.normal(jax.random.fold_in(key, 1), (E, D, 2 * F), jnp.bfloat16) * D ** -0.5
    w_out = jax.random.normal(jax.random.fold_in(key, 2), (E, F, D), jnp.bfloat16) * F ** -0.5
    experts = jnp.asarray(np.stack(
        [rng.permutation(E_all)[:k] for _ in range(T)]).astype(np.int32))
    weights = jnp.full((T, k), 1.0 / k, jnp.float32)
    valid_np = rng.permutation(T) >= int(T * padding)
    valid = jnp.asarray(valid_np)
    flat = np.asarray(experts)[valid_np].reshape(-1) - first
    sizes_np = np.bincount(flat[(flat >= 0) & (flat < E)], minlength=E).astype(np.int32)
    met, rows = int((sizes_np > 0).sum()), int(sizes_np.sum())
    m = T * k
    xs = jax.random.normal(jax.random.fold_in(key, 3), (m, D), jnp.bfloat16)
    gated = jax.random.normal(jax.random.fold_in(key, 4), (m, F), jnp.bfloat16)

    def layer(x, weights, experts, w_in, w_out):
        return moe.moe_dropless(
            x, weights, experts, w_in, w_out, dtype=jnp.bfloat16,
            valid=valid, held=held, zero_from=zero_from)[0]

    def product(sizes, a, w):
        return jax.lax.ragged_dot(a, w, sizes,
                                  preferred_element_type=jnp.float32)

    ref = None
    for part in parts:
        head, _, cut = part.partition("@")
        pins, mats = {}, 3
        try:
            if head == "kernel":  # the Pallas call alone, on sorted rows
                t, o = time_call(
                    lambda sizes, xs, w_in, w_out: moe.moe_gmm_few_rows(
                        xs, w_in, w_out, sizes),
                    jnp.asarray(sizes_np), xs, w_in, w_out)
            elif head in ("in", "out"):
                mm = int(cut) if cut else m
                sizes = np.minimum(np.cumsum(sizes_np), mm)
                sizes = jnp.asarray(np.diff(sizes, prepend=0).astype(np.int32))
                lhs, rhs = (xs, w_in) if head == "in" else (gated, w_out)
                mats = 2 if head == "in" else 1
                t, o = time_call(product, sizes, lhs[:mm], rhs)
            else:
                if head == "around":
                    pins, mats = {"gmm_form": lambda *a: "ragged"}, 0
                elif head == "ragged":
                    pins = {"gmm_form": lambda *a: "ragged"}
                elif head.startswith("few"):
                    pins = {"gmm_form": lambda *a: "few_rows"}
                    if ":" in head:
                        _, tm, mib = head.split(":")
                        pins.update(
                            _FEW_ROWS_TILE=int(tm),
                            _FEW_WEIGHT_TILE_BYTES=int(float(mib) * 2 ** 20))
                elif head != "layer":
                    raise ValueError(f"no part {part!r}")
                if not hasattr(moe, "gmm_form"):  # a tree with one form
                    pins = {}
                jax.clear_caches()  # a forced form is traced anew
                with contextlib.ExitStack() as stack:
                    if pins:
                        stack.enter_context(mock.patch.multiple(moe, **pins))
                    if head == "around":
                        stack.enter_context(mock.patch.object(
                            jax.lax, "ragged_dot", stub_ragged_dot))
                    t, o = time_call(layer, x, weights, experts, w_in, w_out)
            nbytes = met * mats * D * F * 2
            line = {"tree": tree, "shape": name, "part": part,
                    "device_kind": device.device_kind, "rows": m,
                    "rows_in_groups": rows, "experts_met": met, "of": E,
                    "items": items_of(sizes_np), "mb": nbytes / 1e6}
            if head == "kernel":  # the kernel's rows against ``ragged_dot``'s
                gate, up = jnp.split(product(jnp.asarray(sizes_np), xs, w_in), 2, axis=-1)
                want = product(jnp.asarray(sizes_np),
                               (jax.nn.silu(gate) * up).astype(xs.dtype), w_out)
                line["max_diff_vs_ragged"] = float(
                    jnp.max(jnp.abs(o[:rows] - want[:rows])))
            if head not in ("in", "out", "around", "kernel"):
                o32 = np.asarray(o.astype(jnp.float32))
                ref = o32 if ref is None else ref
                line["max_diff_vs_first"] = float(np.max(np.abs(o32 - ref)))
            if not rehearse:
                line["call_us"] = t * 1e6
                if nbytes:
                    line.update(gb_per_s=nbytes / t / 1e9,
                                hbm_pct=nbytes / t / 819e9 * 100)
        except Exception as e:
            line = {"tree": tree, "shape": name, "part": part, "error": str(e)[-400:]}
        print(json.dumps(line), flush=True)
        out_lines.append(line)
os.makedirs("chiprun_out", exist_ok=True)
with open(f"chiprun_out/moe_gmm_time-{tree.strip('./').replace('/', '_') or 'change'}.json", "a") as f:
    for line in out_lines:
        f.write(json.dumps(line) + "\n")
