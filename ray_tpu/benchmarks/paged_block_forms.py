"""One compute block of the paged-attention kernel, timed alone on the chip
in the forms it can hand its K/V tiles to the MXU (docs/MICROBENCHMARKS.md,
PERF.md PR 43):

- ``per_head``: a score and a value product FOR EACH K/V head, a head's
  ``[T, hd]`` tile a strided read of a by-heads block (``by_heads``) or a
  static lane slice of a lane-dense one (``lane``): the kernel's body up to
  PR 42;
- ``fused``: ONE score product of the block-diagonal query ``[Hkv * R, Hkv *
  hd]`` against the lane-dense tile ``[T, Hkv * hd]`` and ONE value product
  ``[Hkv * R, T] x [T, Hkv * hd]``, of which head h keeps its diagonal block;
- ``mirrored``: the score product with the K tile as the MOVING operand
  (``[T, Hkv * hd] x [Hkv * hd, Hkv * R]``), its scores transposed back; the
  value product cannot be mirrored (it contracts the tokens, which are V's
  rows), so it is the fused one.

``--latent`` (PR 46) times the LATENT kernel's block instead: one "head" of
R = 128 or 64 rows (the query heads of one decode token) over the one
``[T, 512]`` latent tile, key and value, and its ``[T, 128]`` rotary rest,
at T = 128, 256 and 512 tokens a block, in the forms ``LATENT_FORMS`` names.

K and V rest in VMEM for the whole call (no page copy, no table): what is
timed is the chain ``products -> max -> exp2 -> sum -> products ->
accumulator`` a block, as the kernel runs it. ``--no-softmax`` leaves the
chain's middle out (the products alone).

    chiprun -- python3 -m ray_tpu.benchmarks.paged_block_forms

prints one JSON line a (shape, form) and writes them all to
``chiprun_out/paged_block_forms.json``. A time comes only from a chip run:
on the CPU the script refuses, unless ``--rehearse`` (tiny, interpreted,
no time is reported)."""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import time

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30
T = 128            # tokens a block
CLOCK_HZ = 1.5e9   # a v5e core's clock, for "cycles a tile"

# (Hkv, G, hd): the decode shapes of the benchmark's cells
SHAPES = {
    "cell1-mistral": (8, 4, 128),
    "cell6-laguna-sliding": (8, 8, 128),
    "cell7-evabyte": (32, 1, 128),
    "cell9-smallthinker": (4, 7, 128),
    "cell4-gpt2": (12, 1, 64),
}


def _kernel(q_ref, pos_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, form, n_blocks, heads, hd, softmax):
    from jax.experimental import pallas as pl

    rows = pos_ref.shape[1]
    R = rows // heads
    W = heads * hd
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    pos = pos_ref[0]                                        # [rows, 1]

    if form in ("fused", "mirrored"):
        # the block-diagonal query, built once a grid step: head h's rows
        # keep the lanes of head h
        q = q_ref[0]                                        # [rows, L]
        L = q.shape[1]
        row_head = lax.div(
            lax.broadcasted_iota(jnp.int32, (rows, L), 0), R)
        lane = lax.broadcasted_iota(jnp.int32, (rows, L), 1)
        q_bd = jnp.concatenate([
            jnp.where(lax.div(c * L + lane, hd) == row_head, q,
                      jnp.zeros_like(q))
            for c in range(W // L)
        ], axis=1)                                          # [rows, W]

    def block(i, carry):
        t = i * T + lax.broadcasted_iota(jnp.int32, (rows, T), 1)
        mask = t <= pos
        tok = pl.ds(pl.multiple_of(i * T, T), T)
        if form in ("by_heads", "lane"):
            if form == "by_heads":
                k_h = [k_ref[tok, h, :] for h in range(heads)]
                v_h = [v_ref[tok, h, :] for h in range(heads)]
            else:
                k_h = [k_ref[tok, h * hd:(h + 1) * hd] for h in range(heads)]
                v_h = [v_ref[tok, h * hd:(h + 1) * hd] for h in range(heads)]
            s = jnp.stack([
                lax.dot_general(q_ref[0, h], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
                for h, k in enumerate(k_h)
            ])                                              # [heads, R, T]
            msk = mask[:R][None]
        else:
            k = k_ref[tok, :]
            if form == "fused":
                s = lax.dot_general(q_bd, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            else:
                s = lax.dot_general(k, q_bd, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32).T
            msk = mask
        if softmax:
            s = jnp.where(msk, s, NEG_INF)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp2((s - m_new).astype(jnp.bfloat16))
            alpha = jnp.exp2(m_prev - m_new)
            l_scr[...] = alpha * l_scr[...] + jnp.sum(
                p, axis=-1, keepdims=True, dtype=jnp.float32)
            m_scr[...] = m_new
        else:
            p = s.astype(jnp.bfloat16)
            alpha = jnp.ones_like(m_scr[...])
        if form in ("by_heads", "lane"):
            pv = jnp.stack([
                lax.dot_general(p[h], v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
                for h, v in enumerate(v_h)
            ])
        else:
            pv = lax.dot_general(p, v_ref[tok, :], (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        return carry

    lax.fori_loop(0, n_blocks, block, 0)
    out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30) if softmax \
        else acc_scr[...]
    if form in ("fused", "mirrored"):
        # head h keeps its diagonal block: lane-aligned column slices,
        # selected by the row's head
        Lo = max(hd, 128)
        row_head = lax.div(
            lax.broadcasted_iota(jnp.int32, (rows, Lo), 0), R)
        lane = lax.broadcasted_iota(jnp.int32, (rows, Lo), 1)
        kept = functools.reduce(jnp.add, [
            jnp.where(lax.div(c * Lo + lane, hd) == row_head,
                      out[:, c * Lo:(c + 1) * Lo], 0.0)
            for c in range(W // Lo)
        ])
        o_ref[0] = kept.astype(o_ref.dtype)
    else:
        o_ref[0] = out.astype(o_ref.dtype)


# --------------------------------------------------------------------------
# The latent kernel's block (PR 46): rows are the query heads of ONE token,
# the context ``c [T, 512]`` is key and value, ``k_r [T, 128]`` the key's
# rotary rest (64 stored at whole lanes).
# --------------------------------------------------------------------------

# R, the rows of a decode tile: the heads of openPangu and of LongCat-Flash
LATENT_SHAPES = {"cell8-pangu-latent": 128, "cell10-longcat-latent": 64}
LATENT_FORMS = (
    # the kernel's block up to PR 45: the context tiles the stationary operand
    "as_built",
    # the same without the causal select (a block wholly under the frontier)
    "no_mask",
    # the mask a [1, T] row (a decode tile's rows share one position)
    "row_mask",
    # the scores transposed, s^T [T, R] = c . q~^T + k_r . q_rope^T: the
    # query stationary, maximum and sum along sublanes, p transposed back
    # once a block for the value product
    "query_stationary",
    # the same, the value product from p^T by a transposed-lhs dot_general
    "query_stationary_tlhs",
    # block i + 1's score products issued before block i's softmax and value
    # product, its scores kept in a VMEM scratch
    "two_in_flight",
    # as_built with q~ and q_rope ONE [R, 640] operand against one [T, 640]
    # tile (what one 640-wide plane would give: not buildable on this pool)
    "one_plane",
)
LATENT_C, LATENT_R = 512, 128


def _latent_kernel(q_ref, pos_ref, posr_ref, c_ref, r_ref, o_ref, m_scr,
                   l_scr, acc_scr, *maybe_s, form, n_blocks, T, softmax):
    from jax.experimental import pallas as pl

    R = q_ref.shape[1]
    C = LATENT_C
    transposed = form.startswith("query_stationary")
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    nt = (((1,), (1,)), ((), ()))

    def tiles(i):
        tok = pl.ds(pl.multiple_of(i * T, T), T)
        return c_ref[tok, :], r_ref[tok, :]

    def scores(i):
        c, kr = tiles(i)
        if form == "one_plane":
            ck = jnp.concatenate([c, kr], axis=1)
            return lax.dot_general(q_ref[0], ck, nt,
                                   preferred_element_type=jnp.float32)
        qc, qr = q_ref[0, :, :C], q_ref[0, :, C:]
        if transposed:  # [T, R]: the query is the latched operand
            return lax.dot_general(
                c, qc, nt, preferred_element_type=jnp.float32
            ) + lax.dot_general(kr, qr, nt,
                                preferred_element_type=jnp.float32)
        return lax.dot_general(
            qc, c, nt, preferred_element_type=jnp.float32
        ) + lax.dot_general(qr, kr, nt, preferred_element_type=jnp.float32)

    def column(row):
        # a [1, R] row of state as the [R, 128] lane-broadcast column the
        # [R, 512] accumulator takes: one 128 x 128 transpose
        full = jnp.broadcast_to(
            jnp.pad(row, ((0, 0), (0, 128 - R))) if R < 128 else row,
            (128, 128))
        return full.T[:R]

    def rest(i, s):
        c, _ = tiles(i)
        axis = 0 if transposed else 1
        if form not in ("no_mask",) and softmax:
            if transposed:
                t = i * T + lax.broadcasted_iota(jnp.int32, (T, R), 0)
                s = jnp.where(t <= posr_ref[0], s, NEG_INF)
            elif form == "row_mask":
                t = i * T + lax.broadcasted_iota(jnp.int32, (1, T), 1)
                s = jnp.where(t <= posr_ref[0, :, :1], s, NEG_INF)
            else:
                t = i * T + lax.broadcasted_iota(jnp.int32, (R, T), 1)
                s = jnp.where(t <= pos_ref[0], s, NEG_INF)
        if softmax:
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=axis, keepdims=True))
            p = jnp.exp2((s - m_new).astype(jnp.bfloat16))
            alpha = jnp.exp2(m_prev - m_new)
            l_scr[...] = alpha * l_scr[...] + jnp.sum(
                p, axis=axis, keepdims=True, dtype=jnp.float32)
            m_scr[...] = m_new
        else:
            p = s.astype(jnp.bfloat16)
            alpha = None
        if form == "query_stationary_tlhs":
            pv = lax.dot_general(p, c, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        else:
            if transposed:
                p = p.T
            pv = lax.dot_general(p, c, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if alpha is None:
            acc_scr[...] = acc_scr[...] + pv
        elif transposed:
            a = column(alpha)
            acc_scr[...] = acc_scr[...] * jnp.concatenate(
                [a] * (C // 128), axis=1) + pv
        else:
            acc_scr[...] = acc_scr[...] * alpha + pv

    if form == "two_in_flight":
        (s_scr,) = maybe_s
        s_scr[...] = scores(0)

        def block(i, carry):
            # the NEXT block's scores first (the last turn recomputes its
            # own: the timing keeps one shape of loop body)
            s_next = scores(jnp.minimum(i + 1, n_blocks - 1))
            rest(i, s_scr[...])
            s_scr[...] = s_next
            return carry
    else:
        def block(i, carry):
            rest(i, scores(i))
            return carry

    lax.fori_loop(0, n_blocks, block, 0)
    acc = acc_scr[...]
    if softmax:
        l = jnp.maximum(l_scr[...], 1e-30)
        if transposed:
            acc = acc / jnp.concatenate([column(l)] * (C // 128), axis=1)
        else:
            acc = acc / l
    o_ref[0] = acc.astype(o_ref.dtype)


def build_latent(form, R, *, T, batch, n_tokens, softmax=True,
                 interpret=False):
    """``(fn, args)``: the jitted call of one latent form at ``T`` tokens a
    block over ``n_tokens`` of context resting in VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, Rp = LATENT_C, LATENT_R
    n_blocks = n_tokens // T
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (batch, R, C + Rp), jnp.bfloat16) * 0.2
    c = jax.random.normal(kk, (n_tokens, C), jnp.bfloat16)
    kr = jax.random.normal(kv, (n_tokens, Rp), jnp.bfloat16)
    pos = jnp.full((batch, R, 1), n_tokens - 3, jnp.int32)
    posr = jnp.full((batch, 1, R), n_tokens - 3, jnp.int32)
    transposed = form.startswith("query_stationary")
    state = (1, R) if transposed else (R, 1)
    scratch = [pltpu.VMEM(state, jnp.float32), pltpu.VMEM(state, jnp.float32),
               pltpu.VMEM((R, C), jnp.float32)]
    if form == "two_in_flight":
        scratch.append(pltpu.VMEM((R, T), jnp.float32))
    call = pl.pallas_call(
        functools.partial(_latent_kernel, form=form, n_blocks=n_blocks, T=T,
                          softmax=softmax),
        grid=(batch,),
        in_specs=[
            pl.BlockSpec((1, R, C + Rp), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, R, 1), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, R), lambda b: (b, 0, 0)),
            pl.BlockSpec(c.shape, lambda b: (0, 0)),
            pl.BlockSpec(kr.shape, lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, R, C), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, R, C), jnp.bfloat16),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name=f"latent_block_{form}",
    )
    return jax.jit(call), (q, pos, posr, c, kr)


def _time(fn, ops):
    times = []
    for _ in range(7):
        t0 = time.perf_counter()
        for _ in range(10):
            out = fn(*ops)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / 10)
    return times


def main_latent(args, on_chip):
    batch, n_tokens = (2, 256) if args.rehearse else (args.batch, 2048)
    lines = []
    for name in (args.shapes or ",".join(LATENT_SHAPES)).split(","):
        R = LATENT_SHAPES[name]
        ref = None
        for T in ((128,) if args.rehearse else (128, 256, 512)):
            for form in LATENT_FORMS:
                fn, ops = build_latent(
                    form, R, T=T, batch=batch, n_tokens=n_tokens,
                    softmax=not args.no_softmax, interpret=not on_chip)
                line = {"shape": name, "rows": R, "form": form, "tokens": T,
                        "softmax": not args.no_softmax,
                        "device": jax.devices()[0].device_kind}
                try:
                    out = jax.block_until_ready(fn(*ops)).astype(jnp.float32)
                except Exception as e:  # noqa: BLE001 — the compiler's refusal
                    line["refused"] = str(e).strip().splitlines()[-1][:300]
                    print(json.dumps(line), flush=True)
                    lines.append(line)
                    continue
                if ref is None:
                    ref = out
                line["max_abs_diff_vs_first"] = float(
                    jnp.max(jnp.abs(out - ref)))
                if on_chip:
                    # three repeats of the median of seven: the spread a
                    # form has to beat
                    reps = [statistics.median(_time(fn, ops))
                            for _ in range(3)]
                    block_s = statistics.median(reps) / (
                        batch * (n_tokens // T))
                    line.update(
                        cycles_a_block=block_s * CLOCK_HZ,
                        cycles_a_token=block_s * CLOCK_HZ / T,
                        repeats_cycles_a_token=[
                            r / (batch * n_tokens) * CLOCK_HZ for r in reps],
                        gb_per_s=T * (LATENT_C + LATENT_R) * 2 / block_s / 1e9,
                        call_ms=statistics.median(reps) * 1e3,
                    )
                print(json.dumps(line), flush=True)
                lines.append(line)
    os.makedirs("chiprun_out", exist_ok=True)
    tag = "-nosoftmax" if args.no_softmax else ""
    with open(f"chiprun_out/latent_block_forms{tag}.json", "w") as f:
        json.dump(lines, f, indent=1)


def build(form, heads, G, hd, *, batch, n_blocks, softmax=True,
          interpret=False):
    """``(fn, args)``: the jitted call of one form and its operands."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, W, rows = G, heads * hd, heads * G
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (batch, heads, R, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (n_blocks * T, W), jnp.bfloat16)
    v = jax.random.normal(kv, (n_blocks * T, W), jnp.bfloat16)
    pos = jnp.full((batch, rows, 1), n_blocks * T - 3, jnp.int32)
    const = lambda b: (0, 0)
    if form in ("by_heads", "lane"):
        q_in = q
        q_spec = pl.BlockSpec((1, heads, R, hd), lambda b: (b, 0, 0, 0))
        state = lambda n: pltpu.VMEM((heads, R, n), jnp.float32)
        out_shape = (batch, heads, R, hd)
        o_spec = q_spec
        widths = (1, 1, hd)
        if form == "by_heads":
            k, v = (a.reshape(n_blocks * T, heads, hd) for a in (k, v))
            const = lambda b: (0, 0, 0)
    else:
        reps = max(1, 128 // hd)
        q_in = jnp.tile(q.reshape(batch, rows, hd), (1, 1, reps))
        q_spec = pl.BlockSpec((1, rows, hd * reps), lambda b: (b, 0, 0))
        state = lambda n: pltpu.VMEM((rows, n), jnp.float32)
        widths = (1, 1, W)
        out_shape = (batch, rows, max(hd, 128))
        o_spec = pl.BlockSpec((1, rows, max(hd, 128)), lambda b: (b, 0, 0))
    call = pl.pallas_call(
        functools.partial(_kernel, form=form, n_blocks=n_blocks,
                          heads=heads, hd=hd, softmax=softmax),
        grid=(batch,),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, rows, 1), lambda b: (b, 0, 0)),
            pl.BlockSpec(k.shape, const),
            pl.BlockSpec(v.shape, const),
        ],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.bfloat16),
        scratch_shapes=[state(n) for n in widths],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name=f"block_{form}",
    )
    return jax.jit(call), (q_in, pos, k, v)


def _head_major(out, heads, G, hd):
    """Any form's output as ``[batch, heads, G, hd]`` float32: a fused
    form's row of a head of 64 holds it in ITS half of 128 lanes, zeros in
    the other."""
    out = out.astype(jnp.float32)
    if out.ndim == 3:
        out = out.reshape(out.shape[0], heads, G, -1, hd).sum(axis=-2)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--no-softmax", action="store_true")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--blocks", type=int, default=8)
    ap.add_argument("--shapes", default=None)
    ap.add_argument("--latent", action="store_true")
    args = ap.parse_args()
    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.rehearse:
        raise SystemExit("a time comes only from a chip run (--rehearse "
                         "checks the forms against each other, untimed)")
    if args.latent:
        return main_latent(args, on_chip)
    args.shapes = args.shapes or ",".join(SHAPES)
    batch, n_blocks = (2, 2) if args.rehearse else (args.batch, args.blocks)
    lines = []
    for name in args.shapes.split(","):
        heads, G, hd = SHAPES[name]
        ref = None
        for form in ("by_heads", "lane", "fused", "mirrored"):
            blocks = n_blocks
            if heads * hd * T * 2 * 2 * 2 * blocks > 48 * 1024 * 1024:
                blocks = max(1, n_blocks // 4)   # K and V, double-buffered
            fn, ops = build(form, heads, G, hd, batch=batch,
                            n_blocks=blocks,
                            softmax=not args.no_softmax,
                            interpret=not on_chip)
            line = {"shape": name, "heads": heads, "group": G, "hd": hd,
                    "form": form, "softmax": not args.no_softmax,
                    "device": jax.devices()[0].device_kind}
            try:
                out = _head_major(
                    jax.block_until_ready(fn(*ops)), heads, G, hd)
            except Exception as e:  # noqa: BLE001 — the compiler's refusal
                line["refused"] = str(e).splitlines()[-1][:300]
                print(json.dumps(line), flush=True)
                lines.append(line)
                continue
            if ref is None:
                ref = out
            line["max_abs_diff_vs_first"] = float(jnp.max(jnp.abs(out - ref)))
            if on_chip:
                times = _time(fn, ops)
                block_s = statistics.median(times) / (batch * blocks)
                tiles = 2 * heads * hd / 128
                line.update(
                    block_us=block_s * 1e6,
                    cycles_a_block=block_s * CLOCK_HZ,
                    cycles_a_tile=block_s * CLOCK_HZ / tiles,
                    gb_per_s=2 * T * heads * hd * 2 / block_s / 1e9,
                    call_ms=statistics.median(times) * 1e3,
                )
            print(json.dumps(line), flush=True)
            lines.append(line)
    os.makedirs("chiprun_out", exist_ok=True)
    tag = "-nosoftmax" if args.no_softmax else ""
    with open(f"chiprun_out/paged_block_forms{tag}.json", "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
