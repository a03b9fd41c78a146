"""Mamba-2's sequence operator (SSD): a matrix of state a head, a decay that
is DATA a token a head, input and output vectors shared by a group of heads.

The state-space mixer of a ``falcon_h1`` layer (models/falcon_h1.py; Dao and
Gu, "Transformers are SSMs", arXiv:2405.21060, the SSD form). Per head ``h``
of ``P`` channels a float32 state ``S`` ``[P, N]`` that is zero where a
sequence starts; with ``dt_t > 0`` a head (after its softplus), ``A_h < 0``,
the token's ``x_t`` ``[P]`` and the vectors ``B_t``, ``C_t`` ``[N]`` of the
head's GROUP (``G`` groups, head ``h`` reads group ``h // (H / G)``)::

    a_t = exp(dt_t A_h)
    S_t = a_t S_{t-1} + dt_t (x_t outer B_t)       y_t = S_t C_t + D_h x_t

The state is of fixed size whatever the context (32 heads of 128 x 256: 4.19
MB a sequence a layer, twice a KDA layer's), kept beside the paged pool in a
slot a sequence. New against ops/lightning.py (a constant decay a head) and
ops/kda.py (a decay a channel, the delta rule): the decay is one number a
head a token and comes from the data, B and C are read ONCE a group of heads,
and ``D`` skips the state.

Three forms that agree (tests/test_falcon_h1.py holds them to each other and
to the masked ``O(n^2)`` product):

- ``ssd_recurrence``: the definition, token by token under a ``lax.scan``;
- ``ssd_step``: one token a row (decode), XLA's form (gather, update,
  scatter around it), and the kernel ``ssd_step`` (``ssd_step_pallas``) that
  updates the rows' states WHERE THEY STAND in the slots' array (aliased in
  and out, a block a (row, group of heads) found through the rows' slots), as
  ``lightning_step_pallas`` and ``kda_step_pallas`` do;
- ``ssd_chunk``: a chunk of a right-padded row, cut into pieces of ``piece``
  tokens (``mamba_chunk_size`` 128) that a ``lax.scan`` walks with the state
  as carry. With ``c_i = sum_{j <= i} dt_j A`` the cumulative LOG decay over
  the piece's REAL tokens (padding has ``dt = 0``: it neither decays the
  state nor adds to it)::

      y_i    = exp(c_i) (S_prev C_i)
               + sum_{j <= i} exp(c_i - c_j) dt_j (C_i . B_j) x_j  +  D x_i
      S_next = exp(c_last) S_prev + sum_j exp(c_last - c_j) dt_j (x_j outer B_j)

  Every exponent is ``c_i - c_j`` with ``i >= j``, at most 0, taken BEFORE
  the ``exp``: no decay is ever inverted, so nothing overflows at any ``dt``.
  ``C_i . B_j`` is one table a GROUP, shared by its heads.

``ssd_chunk`` is plain ``jax.numpy`` under the named scope of its name (XLA's
formulation, as ``lightning_chunk`` and ``kda_chunk`` are served). Products
take the operands' dtype with float32 accumulation; the state stays float32
and every product WITH the state is taken in float32 at the highest precision
(the state's low bits are what a long context is made of).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

STEP_KERNEL_NAME = "ssd_step"
# heads a grid step of the decode kernel: a [8, 128, 256] float32 tile is
# 1 MB each way (ops/kda.py's and ops/lightning.py's tile)
STEP_HEADS = 8
# tokens a piece of the chunked form (``mamba_chunk_size``)
PIECE = 128

_HI = lax.Precision.HIGHEST


def _by_head(a, n_head: int):
    """``a`` [.., G, N] of the groups as the heads read it: [.., H, N]."""
    return jnp.repeat(a, n_head // a.shape[-2], axis=-2)


def _update(S, x, dt, A, Bm, Cm, D):
    """THE UPDATE, float32: ``S`` [.., H, P, N], ``x`` [.., H, P], ``dt``
    [.., H], ``Bm``, ``Cm`` [.., G, N]. (y [.., H, P], S')."""
    H = x.shape[-2]
    a = jnp.exp(dt * A)
    S = a[..., None, None] * S + (dt[..., None] * x)[..., None] * _by_head(
        Bm, H)[..., None, :]
    y = jnp.einsum("...hpn,...hn->...hp", S, _by_head(Cm, H), precision=_HI)
    return y + D[:, None] * x, S


def ssd_recurrence(x, dt, A, Bm, Cm, D, state):
    """THE DEFINITION: ``x`` [B, S, H, P], ``dt`` [B, S, H] (after the
    softplus; 0 on padding), ``A``, ``D`` [H], ``Bm``, ``Cm`` [B, S, G, N],
    ``state`` [B, H, P, N]. Returns (y [B, S, H, P] float32, the state after
    the last token). Everything in float32."""
    f32 = jnp.float32
    A, D = A.astype(f32), D.astype(f32)

    def one(S, xs):
        y, S = _update(S, *xs[:2], A, *xs[2:], D)
        return S, y

    lead = lambda a: jnp.moveaxis(a.astype(f32), 1, 0)  # noqa: E731
    state, y = lax.scan(one, state.astype(f32),
                        tuple(lead(a) for a in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1), state


def ssd_step(x, dt, A, Bm, Cm, D, state):
    """One token a row: ``x`` [B, H, P], ``dt`` [B, H] float32, ``A``, ``D``
    [H], ``Bm``, ``Cm`` [B, G, N], ``state`` [B, H, P, N] float32. Returns
    (y [B, H, P] in x's dtype, the next state)."""
    with jax.named_scope("ssd_step"):
        f32 = jnp.float32
        y, state = _update(state, x.astype(f32), dt.astype(f32),
                           A.astype(f32), Bm.astype(f32), Cm.astype(f32),
                           D.astype(f32))
        return y.astype(x.dtype), state


def _ssd_step_kernel(slots_ref, layer_ref, dtx_ref, skip_ref, dec_ref, bc_ref,
                     s_ref, o_ref, s_out_ref, *, heads: int, per_group: int):
    """One (row, block of heads of ONE group): the state decayed, the outer
    product added, the read through C, where the state stands. The heads'
    ``dt x`` arrive as ROWS of one lane-dense tile ``[heads, P]`` and are
    turned into columns by ONE transpose in fast memory (an operand stored
    as columns ``[.., P, 1]`` would rest in HBM at 128 x its bytes:
    ops/kda.py); B and C are rows ``[1, N]`` of the block's group and the
    decay a row ``[1, N]`` of one number, so the update is broadcasts along
    sublanes. The read ``S C`` sums over LANES: the lane chunks of 128 are
    added, the ``[P, 128]`` rest transposed, and the sum over sublanes
    leaves the head's output as a row ``[1, P]``."""
    del slots_ref, layer_ref  # the index maps read them
    from jax.experimental import pallas as pl

    g = (pl.program_id(1) * heads) // per_group
    Brow = bc_ref[0, pl.ds(2 * g, 1), :]                    # [1, N]
    Crow = bc_ref[0, pl.ds(2 * g + 1, 1), :]
    dtx = dtx_ref[0]                                        # [heads, P]
    if dtx.shape[0] % 128:
        dtx = jnp.concatenate(
            [dtx, jnp.zeros((-dtx.shape[0] % 128, dtx.shape[1]), dtx.dtype)])
    cols = dtx.T                                            # [P, 128 n]
    N = Brow.shape[-1]
    for h in range(heads):
        S = dec_ref[0, h:h + 1] * s_ref[0, 0, h] + cols[:, h:h + 1] * Brow
        s_out_ref[0, 0, h] = S
        T = S * Crow                                        # [P, N]
        if N > 128 and N % 128 == 0:
            T = sum(T[:, i:i + 128] for i in range(0, N, 128))
        y = jnp.sum(T.T, axis=0, keepdims=True)             # [1, P]
        o_ref[0, h:h + 1] = (y + skip_ref[0, h:h + 1]).astype(o_ref.dtype)


def ssd_step_pallas(x, dt, A, Bm, Cm, D, states, layer, slots,
                    interpret=None):
    """The decode step over the slots' array itself: ``x`` [B, H, P], ``dt``
    [B, H] float32, ``A``, ``D`` [H], ``Bm``, ``Cm`` [B, G, N], ``states``
    [n_layer, n_slots, H, P, N] float32, the rows' ``slots`` [B] (padding
    rows share slot 0, the garbage sink). Returns (y [B, H, P] in x's dtype,
    ``states`` with the rows' states at ``layer`` updated: the same buffer
    where the caller donated it). B and C are fetched once a ROW (both
    groups' in one small tile), so once a group of heads."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops.attention import pallas_interpret

    if interpret is None:
        interpret = pallas_interpret()
    B, H, P = x.shape
    G, N = Bm.shape[-2:]
    per_group = H // G
    hb = STEP_HEADS if per_group % STEP_HEADS == 0 else per_group
    f32 = jnp.float32
    xf, dt = x.astype(f32), dt.astype(f32)
    dtx = dt[..., None] * xf                                # [B, H, P]
    skip = D.astype(f32)[:, None] * xf
    # the decay, one number a head, as the row the state's tile is scaled by
    dec = jnp.broadcast_to(
        jnp.exp(dt * A.astype(f32))[..., None], (B, H, N))
    # a row's [B_0, C_0, B_1, C_1, ..]: [B, 2 G, N]
    bc = jnp.stack([Bm.astype(f32), Cm.astype(f32)], axis=2).reshape(
        B, 2 * G, N)

    def state_map(b, j, slots_ref, layer_ref):
        return (layer_ref[0], slots_ref[b], j, 0, 0)

    row_map = lambda b, j, *refs: (b, j, 0)  # noqa: E731
    state_spec = pl.BlockSpec((1, 1, hb, P, N), state_map)
    y, states = pl.pallas_call(
        functools.partial(_ssd_step_kernel, heads=hb, per_group=per_group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hb),
            in_specs=[pl.BlockSpec((1, hb, P), row_map),
                      pl.BlockSpec((1, hb, P), row_map),
                      pl.BlockSpec((1, hb, N), row_map),
                      pl.BlockSpec((1, 2 * G, N),
                                   lambda b, j, *refs: (b, 0, 0)),
                      state_spec],
            out_specs=[pl.BlockSpec((1, hb, P), row_map), state_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, P), x.dtype),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # operands: slots, layer, dtx, skip, dec, bc, states -> states is 6
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=STEP_KERNEL_NAME,
        interpret=interpret,
    )(slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      dtx, skip, dec, bc, states)
    return y, states


def step_bytes(rows: int, n_head: int, head_dim: int, d_state: int,
               n_group: int) -> int:
    """Bytes the kernel ``ssd_step`` must move for ``rows`` rows of one
    layer: each row's state once each way, its ``x`` columns in (2 B), its
    ``dt`` (4 B a head) and its output (2 B), and B and C once a group."""
    return rows * (n_head * (2 * head_dim * d_state * 4 + head_dim * 2 + 4
                             + head_dim * 2)
                   + n_group * 2 * d_state * 4)


def chunk_flops(tokens: int, n_head: int, head_dim: int, d_state: int,
                n_group: int, piece: int = PIECE) -> int:
    """Multiply-adds x 2 of ``ssd_chunk``'s products over ``tokens`` tokens
    of one layer at full pieces: the table ``C . B`` a GROUP (``piece`` x
    N), the table against ``x`` a head (``piece`` x P), and the carried
    state's two a head (the read ``S C`` and the update: P x N each)."""
    per_token = (n_group * piece * d_state
                 + n_head * (piece * head_dim + 2 * head_dim * d_state))
    return 2 * tokens * per_token


def ssd_chunk(x, dt, A, Bm, Cm, D, state, valid, piece: int = PIECE):
    """A chunk of right-padded rows: ``x`` [B, S, H, P], ``dt`` [B, S, H]
    float32 (after the softplus), ``A``, ``D`` [H], ``Bm``, ``Cm`` [B, S, G,
    N], ``state`` [B, H, P, N] float32 (the rows' states before the chunk:
    zeros where a sequence starts), ``valid`` [B, S] the real tokens.
    Returns (y [B, S, H, P] in x's dtype, the state after each row's last
    real token)."""
    B, S, H, P = x.shape
    G, N = Bm.shape[-2:]
    f32 = jnp.float32
    dtype = x.dtype
    c = min(piece, S)
    n = -(-S // c)
    pad = n * c - S
    with jax.named_scope("ssd_chunk"):
        dt = jnp.where(valid[..., None], dt.astype(f32), 0.0)
        if pad:
            widen = lambda a: jnp.pad(  # noqa: E731
                a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            x, dt, Bm, Cm = map(widen, (x, dt, Bm, Cm))
        A, D = A.astype(f32), D.astype(f32)
        # pieces lead: [n, B, c, ...]
        cut = lambda a: jnp.moveaxis(  # noqa: E731
            a.reshape(B, n, c, *a.shape[2:]), 1, 0)
        causal = jnp.tril(jnp.ones((c, c), bool))

        def one(S_prev, xs):
            xp, dtp, Bp, Cp = xs       # [B,c,H,P] [B,c,H] [B,c,G,N] x 2
            cum = jnp.cumsum(dtp * A, axis=1)                  # [B, c, H]
            gap = cum[:, :, None] - cum[:, None, :]            # [B, i, j, H]
            decay = jnp.where(causal[None, :, :, None], jnp.exp(
                jnp.where(causal[None, :, :, None], gap, 0.0)), 0.0)
            table = jnp.einsum("bign,bjgn->bgij", Cp, Bp,
                               preferred_element_type=f32)     # a GROUP
            weights = (decay * dtp[:, None]).transpose(0, 3, 1, 2)
            M = weights.reshape(B, G, H // G, c, c) * table[:, :, None]
            intra = jnp.einsum(
                "bhij,bjhp->bihp", M.reshape(B, H, c, c).astype(dtype), xp,
                preferred_element_type=f32)
            read = jnp.einsum(
                "bign,bgkpn->bigkp", Cp.astype(f32),
                S_prev.reshape(B, G, H // G, P, N),
                precision=_HI).reshape(B, c, H, P)
            inter = jnp.exp(cum)[..., None] * read
            last = cum[:, -1]                                  # [B, H]
            tail = jnp.exp(last[:, None] - cum) * dtp          # [B, c, H]
            xd = (xp.astype(f32) * tail[..., None]).astype(dtype)
            add = jnp.einsum(
                "bjgkp,bjgn->bgkpn", xd.reshape(B, c, G, H // G, P), Bp,
                preferred_element_type=f32).reshape(B, H, P, N)
            S_next = jnp.exp(last)[..., None, None] * S_prev + add
            y = intra + inter + D[:, None] * xp.astype(f32)
            return S_next, y.astype(dtype)

        state, y = lax.scan(one, state.astype(f32),
                            tuple(cut(a) for a in (x, dt, Bm, Cm)))
        y = jnp.moveaxis(y, 0, 1).reshape(B, n * c, H, P)
        return y[:, :S], state
