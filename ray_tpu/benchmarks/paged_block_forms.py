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
    ap.add_argument("--shapes", default=",".join(SHAPES))
    args = ap.parse_args()
    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.rehearse:
        raise SystemExit("a time comes only from a chip run (--rehearse "
                         "checks the forms against each other, untimed)")
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
                times = []
                for _ in range(7):
                    t0 = time.perf_counter()
                    for _ in range(10):
                        out = fn(*ops)
                    jax.block_until_ready(out)
                    times.append((time.perf_counter() - t0) / 10)
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
