"""Kimi Delta Attention (KDA): a matrix of state a head, a decay a CHANNEL
of the key, and the delta rule.

The sequence operator of a ``kda`` layer (models/ling_hybrid.py; Kimi
Linear, arXiv:2510.26692 section 3). Per head a float32 state ``S`` ``[K,
V]`` that is zero where a sequence starts; with ``a_t`` in ``(0, 1]^K``
(``log a_t`` is what the layer hands over), ``beta_t`` in ``[0, 1]``, a
unit key ``k_t`` and a scaled query ``q_t``::

    S~  = Diag(a_t) S_{t-1}
    S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T          o_t = S_t^T q_t

(the same as ``S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t
v_t^T``: the state forgets by channel, then REPLACES what it holds under
``k_t`` by a step towards ``v_t``). The state is of fixed size whatever the
context (32 heads of 128 x 128: 2.10 MB a sequence a layer), kept beside
the paged pool in a slot a sequence. ops/lightning.py computes neither
form: its decay is one constant a head and its update has no ``(I - beta k
k^T)`` factor.

Three forms that agree (tests/test_kda.py holds them to each other):

- ``kda_recurrence``: the definition, token by token under a ``lax.scan``;
- ``kda_step``: one token a row (decode), XLA's form, and the kernel
  ``kda_step`` (``kda_step_pallas``) that updates the rows' states WHERE
  THEY STAND in the slots' array (aliased in and out, a block a (row, group
  of heads) found through the rows' slots), as ``lightning_step_pallas``:
  XLA's form gathers the rows' states, updates them and scatters them
  back, three passes over bytes the kernel moves once each way;
- ``kda_chunk``: a chunk of a right-padded row, cut into chunks of ``C``
  tokens. With ``G_i = sum_{j <= i} log a_j`` from the chunk's start (a
  ``[K]`` vector a token) and the carried ``S_0``::

      A_ij = beta_i (k_i * e^{G_i - G_j}) . k_j      (j < i; 0 otherwise)
      U    = (I + A)^-1 (beta * (V - (K * e^G) S_0))            rows u_i
      o_i  = S_0^T (q_i * e^{G_i}) + sum_{j<=i} ((q_i * e^{G_i-G_j}) . k_j) u_j
      S_C  = Diag(e^{G_C}) S_0 + sum_j (k_j * e^{G_C - G_j}) u_j^T

  ``u_i`` is the pseudo-value the delta rule writes at token ``i``. What
  does not depend on ``S_0`` (``A``, the query-key table, ``T = (I +
  A)^-1``, ``T (beta V)`` and ``T (beta K e^G)``) is computed for every
  chunk of the step at once; a ``lax.scan`` over the chunks then carries
  the state through four products a chunk.

  Every exponent is ``G_i - G_j`` with ``i >= j``, at most 0, but a decay
  is DATA here: a pair's power cannot come from a table, and ``e^{G_i}
  e^{-G_j}`` overflows (``-G_j`` reaches 5 x 64). So a product is formed
  in PIECES of ``piece`` tokens against the piece's own first row ``r``:
  ``(x_i e^{G_i - G_r}) . (k_j e^{G_r - G_j})``; the left exponent is at
  most 0, the right one at most 0 for a ``j`` of an earlier piece and at
  most ``(piece - 1) x 5 = 75 < 88`` inside the piece (the largest
  exponent float32 holds; what ``kda_lower_bound -5`` is for), and every
  difference is taken BEFORE the ``exp``. ``T`` is the inverse of a unit
  lower-triangular matrix: forward substitution on the pieces' diagonal
  blocks, then block by block (``_inv_unit_lower``).

  A right-padded row's padding has ``beta = 0`` and ``log a = 0``: it
  neither decays the state nor adds to it.

``kda_chunk`` is plain ``jax.numpy`` under the named scope of its name
(XLA's formulation, as ``lightning_chunk`` is served; a Pallas body is
ROADMAP's). Products take the operands' dtype with float32 accumulation;
the state stays float32 and every product WITH the state is taken in
float32 at the highest precision (the state's low bits are what a long
context is made of), as is the triangular inverse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

STEP_KERNEL_NAME = "kda_step"
# heads a grid step of the decode kernel: a [16, 128, 128] float32 tile is
# 1 MB each way
STEP_HEADS = 16
# tokens a chunk of the chunked form (the unit of the carried state) and a
# piece of a chunk (the unit inside which a decay is referred to its first
# row): PIECE x 5 < 88
CHUNK = 64
PIECE = 16
# the largest exponent a masked-out pair is allowed before its ``exp``
_EXP_CAP = 80.0

_HI = lax.Precision.HIGHEST


def _delta(S, q, k, v, log_a, beta):
    """THE UPDATE, float32: ``S`` [.., H, K, V] decayed by channel, the
    delta rule's rank-one step, the query's read. (o [.., H, V], S')."""
    S = jnp.exp(log_a)[..., None] * S
    pred = jnp.einsum("...hk,...hkv->...hv", k, S, precision=_HI)
    S = S + (beta[..., None] * k)[..., None] * (v - pred)[..., None, :]
    return jnp.einsum("...hk,...hkv->...hv", q, S, precision=_HI), S


def kda_recurrence(q, k, v, log_a, beta, state):
    """THE DEFINITION: ``q``, ``k`` [B, S, H, K], ``v`` [B, S, H, V],
    ``log_a`` [B, S, H, K] float32, ``beta`` [B, S, H] float32, ``state``
    [B, H, K, V] float32. Returns (o [B, S, H, V] float32, the state after
    the last token). Everything in float32."""
    f32 = jnp.float32

    def one(S, xs):
        o, S = _delta(S, *xs)
        return S, o

    lead = lambda a: jnp.moveaxis(a.astype(f32), 1, 0)  # noqa: E731
    state, o = lax.scan(one, state.astype(f32),
                        tuple(lead(a) for a in (q, k, v, log_a, beta)))
    return jnp.moveaxis(o, 0, 1), state


def kda_step(q, k, v, log_a, beta, state):
    """One token a row: ``q``, ``k`` [B, H, K], ``v`` [B, H, V], ``log_a``
    [B, H, K] float32, ``beta`` [B, H] float32, ``state`` [B, H, K, V]
    float32. Returns (o [B, H, V] in q's dtype, the next state)."""
    with jax.named_scope("kda_step"):
        o, state = _delta(state, *(
            a.astype(jnp.float32) for a in (q, k, v, log_a, beta)))
        return o.astype(q.dtype), state


def _kda_step_kernel(slots_ref, layer_ref, x_ref, v_ref, s_ref, o_ref,
                     s_out_ref, *, heads: int):
    """One (row, group of heads): the state decayed by channel, the delta
    rule's rank-one step, the query's read, where the state stands. The
    heads' four K-vectors (the decay, ``beta k``, k, q) arrive as ROWS of
    one lane-dense tile ``[4 heads, K]`` and are turned into columns by ONE
    transpose in fast memory (an operand stored as columns ``[.., K, 1]``
    would rest in HBM at 128 x its bytes, a lane a tile row); v and the
    output are rows ``[heads, V]``. The two reads of the state are a
    broadcast along lanes and a sum over sublanes, the write an outer
    product."""
    del slots_ref, layer_ref  # the index maps read them
    x = x_ref[0]                                            # [4 heads, K]
    if x.shape[0] % 128:
        x = jnp.concatenate(
            [x, jnp.zeros((-x.shape[0] % 128, x.shape[1]), x.dtype)])
    cols = x.T                                              # [K, 128 n]
    for h in range(heads):
        a, bk, k, q = (cols[:, 4 * h + j:4 * h + j + 1] for j in range(4))
        S = a * s_ref[0, 0, h]                              # [K, V]
        pred = jnp.sum(k * S, axis=0, keepdims=True)        # [1, V]
        S = S + bk * (v_ref[0, h:h + 1] - pred)
        s_out_ref[0, 0, h] = S
        o_ref[0, h:h + 1] = jnp.sum(
            q * S, axis=0, keepdims=True).astype(o_ref.dtype)


def kda_step_pallas(q, k, v, log_a, beta, states, layer, slots,
                    interpret=None):
    """The decode step over the slots' array itself: ``q``, ``k`` [B, H,
    K], ``v`` [B, H, V], ``log_a`` [B, H, K], ``beta`` [B, H], ``states``
    [n_layer, n_slots, H, K, V] float32, the rows' ``slots`` [B] (padding
    rows share slot 0, the garbage sink). Returns (o [B, H, V] in q's
    dtype, ``states`` with the rows' states at ``layer`` updated: the same
    buffer where the caller donated it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops.attention import pallas_interpret

    if interpret is None:
        interpret = pallas_interpret()
    B, H, K = q.shape
    V = v.shape[-1]
    hb = STEP_HEADS if H % STEP_HEADS == 0 else H
    f32 = jnp.float32
    kf = k.astype(f32)
    # a head's four K-vectors, one after the other: [B, 4 H, K]
    x = jnp.stack([jnp.exp(log_a.astype(f32)),
                   beta.astype(f32)[..., None] * kf, kf, q.astype(f32)],
                  axis=2).reshape(B, 4 * H, K)

    def state_map(b, j, slots_ref, layer_ref):
        return (layer_ref[0], slots_ref[b], j, 0, 0)

    row_map = lambda b, j, *refs: (b, j, 0)  # noqa: E731
    state_spec = pl.BlockSpec((1, 1, hb, K, V), state_map)
    o, states = pl.pallas_call(
        functools.partial(_kda_step_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hb),
            in_specs=[pl.BlockSpec((1, 4 * hb, K), row_map),
                      pl.BlockSpec((1, hb, V), row_map), state_spec],
            out_specs=[pl.BlockSpec((1, hb, V), row_map), state_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, V), q.dtype),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # operands: slots, layer, x, v, states -> states is 4
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=STEP_KERNEL_NAME,
        interpret=interpret,
    )(slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      x, v.astype(f32), states)
    return o, states


def step_bytes(rows: int, n_head: int, k_dim: int, v_dim: int) -> int:
    """Bytes the kernel ``kda_step`` moves for ``rows`` rows of one layer:
    each row's state once each way, and its float32 rows (the decay,
    ``beta k``, k, q, the value) and output."""
    return rows * n_head * (2 * k_dim * v_dim * 4 + 4 * k_dim * 4
                            + v_dim * 4 + v_dim * 2)


def _substitute(A):
    """``(I + A)^-1`` of a strictly lower-triangular ``A`` [..., c, c] by
    forward substitution: row i of the inverse is ``e_i - sum_{j<i} A_ij
    row_j``."""
    c = A.shape[-1]
    eye = jnp.eye(c, dtype=A.dtype)
    rows = [jnp.broadcast_to(eye[0], A.shape[:-2] + (c,))]
    for i in range(1, c):
        prev = jnp.stack(rows, axis=-2)                     # [..., i, c]
        rows.append(eye[i] - jnp.einsum(
            "...j,...jc->...c", A[..., i, :i], prev, precision=_HI))
    return jnp.stack(rows, axis=-2)


def _inv_unit_lower(A, base: int):
    """``(I + A)^-1`` for a strictly lower-triangular ``A`` [..., C, C],
    ``C`` = ``base`` x a power of two: substitution on blocks of ``base``,
    then ``[[T11, 0], [-T22 A21 T11, T22]]`` block by block."""
    C = A.shape[-1]
    if C <= base:
        return _substitute(A)
    h = C // 2
    T11 = _inv_unit_lower(A[..., :h, :h], base)
    T22 = _inv_unit_lower(A[..., h:, h:], base)
    T21 = -jnp.matmul(jnp.matmul(T22, A[..., h:, :h], precision=_HI), T11,
                      precision=_HI)
    top = jnp.concatenate([T11, jnp.zeros_like(T11)], axis=-1)
    return jnp.concatenate(
        [top, jnp.concatenate([T21, T22], axis=-1)], axis=-2)


def chunk_shape(S: int, chunk: int = CHUNK, piece: int = PIECE):
    """``(tokens a chunk, chunks)`` the chunked form cuts ``S`` tokens
    into: ``chunk``, or for a short step the least ``piece`` x a power of
    two that holds it."""
    c = piece
    while c < min(S, chunk):
        c *= 2
    return c, -(-S // c)


def chunk_flops(tokens: int, n_head: int, k_dim: int, v_dim: int,
                chunk: int = CHUNK) -> int:
    """Multiply-adds x 2 of ``kda_chunk``'s products over ``tokens`` tokens
    of one layer at full chunks: the two tables against the keys (``A``
    and the query-key table: 2 x C x K a token), the solve counted as the
    product it replaces (C x C), ``T`` against ``[beta K e^G | beta V]``
    (C x (K + V)), and the chunk scan's four (``W S``, ``q S``: K x V
    each; the table against ``u``: C x V; the state's update: K x V)."""
    per_token = (2 * chunk * k_dim + chunk * chunk + chunk * (k_dim + v_dim)
                 + 3 * k_dim * v_dim + chunk * v_dim)
    return 2 * tokens * n_head * per_token


def kda_chunk(q, k, v, log_a, beta, state, valid, chunk: int = CHUNK,
              piece: int = PIECE):
    """A chunk of right-padded rows: ``q``, ``k`` [B, S, H, K], ``v`` [B,
    S, H, V], ``log_a`` [B, S, H, K] float32, ``beta`` [B, S, H] float32,
    ``state`` [B, H, K, V] float32 (the rows' states before the step:
    zeros where a sequence starts), ``valid`` [B, S] the real tokens.
    Returns (o [B, S, H, V] in q's dtype, the state after each row's last
    real token)."""
    B, S, H, K = q.shape
    V = v.shape[-1]
    f32 = jnp.float32
    dt = q.dtype
    C, n = chunk_shape(S, chunk, piece)
    P = C // piece
    with jax.named_scope("kda_chunk"):
        real = valid[..., None]
        beta = jnp.where(real, beta.astype(f32), 0.0)
        log_a = jnp.where(real[..., None], log_a.astype(f32), 0.0)
        pad = n * C - S
        if pad:
            widen = lambda a: jnp.pad(  # noqa: E731
                a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            q, k, v, log_a, beta = map(widen, (q, k, v, log_a, beta))
        # [B, n, C, H, .] -> heads lead the chunk: [B, n, H, C, .]
        cut = lambda a: jnp.moveaxis(  # noqa: E731
            a.reshape(B, n, C, *a.shape[2:]), 3, 2)
        q, k, v, log_a = map(cut, (q, k, v, log_a))
        beta = cut(beta)[..., None]                      # [B, n, H, C, 1]
        kf, qf = k.astype(f32), q.astype(f32)
        G = jnp.cumsum(log_a, axis=-2)                   # [B, n, H, C, K]
        # each token's G against its piece's first row, and each piece's
        # first row against every token of the chunk
        first = G.reshape(B, n, H, P, piece, K)[..., :1, :]
        own = jnp.exp(G - jnp.broadcast_to(
            first, (B, n, H, P, piece, K)).reshape(B, n, H, C, K))
        towards = jnp.exp(jnp.minimum(
            first - G[..., None, :, :], _EXP_CAP))       # [B,n,H,P,C,K]
        keys = (kf[..., None, :, :] * towards).astype(dt)
        left = jnp.concatenate([beta * kf * own, qf * own], axis=-2)
        # rows [beta k; q] of each piece against every key: [.., P, 2p, C]
        left = left.reshape(B, n, H, 2, P, piece, K).swapaxes(3, 4).reshape(
            B, n, H, P, 2 * piece, K).astype(dt)
        table = jnp.einsum("...pik,...pjk->...pij", left, keys,
                           preferred_element_type=f32)
        table = table.reshape(B, n, H, P, 2, piece, C).swapaxes(3, 4).reshape(
            B, n, H, 2, C, C)
        i = jnp.arange(C)
        A = jnp.where(i[None, :] < i[:, None], table[..., 0, :, :], 0.0)
        QK = jnp.where(i[None, :] <= i[:, None], table[..., 1, :, :], 0.0)
        T = _inv_unit_lower(A, piece)                    # [B, n, H, C, C]
        whole = jnp.exp(G)                               # e^{G_i}: <= 1
        rhs = jnp.concatenate(
            [beta * kf * whole, beta * v.astype(f32)], axis=-1).astype(dt)
        WU = jnp.einsum("...ij,...jd->...id", T.astype(dt), rhs,
                        preferred_element_type=f32)
        W, U0 = WU[..., :K], WU[..., K:]
        last = G[..., -1:, :]                            # [B, n, H, 1, K]
        k_end = (kf * jnp.exp(last - G)).astype(dt)
        q_in = qf * whole
        g_end = jnp.exp(last[..., 0, :])                 # [B, n, H, K]

        def one(S0, xs):
            W, U0, QK, q_in, k_end, g_end = xs
            u = U0 - jnp.einsum("bhck,bhkv->bhcv", W, S0, precision=_HI)
            o = jnp.einsum("bhck,bhkv->bhcv", q_in, S0, precision=_HI) \
                + jnp.einsum("bhij,bhjv->bhiv", QK.astype(dt), u.astype(dt),
                             preferred_element_type=f32)
            S1 = g_end[..., None] * S0 + jnp.einsum(
                "bhck,bhcv->bhkv", k_end, u.astype(dt),
                preferred_element_type=f32)
            return S1, o.astype(dt)

        lead = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
        state, o = lax.scan(one, state.astype(f32), tuple(
            lead(a) for a in (W, U0, QK, q_in, k_end, g_end)))
        # [n, B, H, C, V] -> [B, n x C, H, V]
        o = jnp.moveaxis(o, 0, 1).swapaxes(2, 3).reshape(B, n * C, H, V)
        return o[:, :S], state
