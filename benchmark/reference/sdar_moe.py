"""Plain reference of the SDAR-MoE family: its forward pass under the block
mask and its generation by diffusion over blocks, in straightforward
``jax.numpy``, float32 at the highest matmul precision, a Python loop over
the layers, no kernel, no cache, no sort, no grouped product and no
batching: one sequence at a time (``lax.map`` over the requests), a layer's
attention one block of queries at a time.

Follows the public ``sdar_moe`` configuration (JetLM SDAR-30B-A3B-Chat
``config.json``). Token embedding; per layer ``y = x + Attn(RMSNorm(x))``,
``out = y + Experts(RMSNorm(y))``; final RMSNorm; an untied head.

- ``Attn``: q / k / v projections without bias, 32 query heads over 4
  key/value heads of 128; ``qk_norm``: an RMSNorm over each head of q and of
  k; ``positional``: the whole head rotated, rotate-half form, plain ``theta
  ** (-2i / hd)``; softmax at scale ``head_dim ** -0.5`` written as a masked
  softmax; ``sees``: the query at ``p`` sees the key at ``t`` where ``t //
  block_length <= p // block_length`` (full inside a block, causal from
  block to block, the prompt's tokens too). Output projection.
- ``Experts``: ``route``: a softmax over all ``num_experts`` logits, the
  ``top_k`` largest, renormalised over them (``norm_topk_prob``); a loop
  (``lax.scan``) over all the experts, each computing every token as
  ``(silu(g W_gate) * (g W_up)) W_down`` and entering the sum under a weight
  that is 0 where the token did not choose it.
- ``generate``: the family's routine, greedy, a block at a time: a block's
  ids are the prompt's tail (first block only) and MASK; a pass computes
  the logits of the block's positions given everything before the block and
  the block as it stands, and fills ``fill_counts`` of the masked positions
  with the argmax of the logits AT them (``logit_position``: no shift),
  chosen by ``remasking``; when none is masked the block is final. Whether
  a position is masked is a flag a position, never a comparison of ids
  (``embed``: ``masked_is_positional``).
- ``logits_at``: the logits that CHOSE each generated token of a finished
  sequence under the ``sequential`` order, as the family trains: the clean
  sequence beside a copy whose every generated block stands as it stood
  before pass ``s``, a noisy block attending the clean blocks before it and
  itself.

Departures from the published description, each a reading of what the
configuration does not give (benchmark/configs/sdar-30b-a3b-chat-6l.json
``assumed`` gives the other reading of each), each ONE function here:
``qk_norm``, ``sees`` (block length; the prompt under the same mask),
``fill_counts`` (the schedule), ``embed`` (masked is positional), and that
the logits at a masked position choose that position's token.

Reads the program's parameter tree (``models/sdar_moe.py sdar_moe_init``)
and of its config only numbers. Each weight is cast to float32 where it is
used.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

ENGINE_MODEL = "sdar_moe"
Q_BLOCK = 256  # queries a block of attention: [H, 256, S] float32 scores
# what ``init_fn`` rounds once to the published checkpoint's dtype: every
# leaf with two or more axes; norm scales stay float32
PUBLISHED_DTYPE = jnp.bfloat16
# None: float32 at the highest precision. A control sets a narrower dtype
# (``jnp.float8_e4m3fn``): both operands of every matrix product are then
# cut to it first, which is how "the reference computed one precision
# lower" is read for the limit of ``reference_check``.
ROUND_TO = None
REMASKING = ("sequential", "low_confidence_static", "low_confidence_dynamic")


def config_class():
    from ray_tpu.models.sdar_moe import SdarMoeConfig

    return SdarMoeConfig


def init_fn():
    """The program's own initialiser, its matrix leaves rounded ONCE to
    bfloat16 inside the same jitted call (benchmark/reference/lfm2_moe.py
    ``init_fn`` and its reasons)."""
    from ray_tpu.models.sdar_moe import sdar_moe_init

    def init(key, cfg):
        return jax.tree.map(
            lambda a: a.astype(PUBLISHED_DTYPE) if a.ndim >= 2 else a,
            sdar_moe_init(key, cfg))

    return init


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _cut(a):
    """``a`` in float32, rounded to ``ROUND_TO``'s exponent and mantissa
    where a control sets one (``reduce_precision``: inside a jitted program
    the compiler keeps the excess precision of a pair of casts)."""
    a = _f32(a)
    if ROUND_TO is None:
        return a
    kind = jnp.finfo(ROUND_TO)
    return jax.lax.reduce_precision(a, kind.nexp, kind.nmant)


def _mm(x, w):
    return _cut(x) @ _cut(w)


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


def qk_norm(x, scale, cfg):
    """x [.., H, hd]: an RMSNorm over each head, before the rotary
    embedding (assumed; the other reading: none)."""
    return _rms_norm(x, scale, cfg.norm_eps)


def sees(pos, t, cfg):
    """Whether the query at ``pos`` sees the key at ``t``: every position
    up to the end of the query's own block (assumed: ``block_length``; the
    prompt under the same mask)."""
    return t // cfg.block_length <= pos // cfg.block_length


def fill_counts(block_length: int, steps) -> tuple:
    """How many masked positions each of a block's ``steps`` passes fills:
    ``block_length // steps``, a remainder to the first passes (None: as
    many passes as the block is long)."""
    steps = block_length if steps is None else steps
    return tuple(block_length // steps + (s < block_length % steps)
                 for s in range(steps))


def embed(params, tokens, masked, cfg):
    """The input rows: the token's embedding, the MASK token's where the
    position is flagged masked (a flag a position: an id equal to the
    mask's is an ordinary token)."""
    wte = _f32(params["wte"])
    x = wte[tokens]
    if masked is None:
        return x
    return jnp.where(masked[..., None], wte[cfg.mask_token_id], x)


def positional(x, pos, cfg):
    """x [S, H, hd] rotated by the angles of ``pos`` [S], the whole head,
    rotate-half."""
    hd = cfg.head_dim
    inv_freq = 1.0 / cfg.rope_theta ** (
        jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.outer(pos.astype(jnp.float32), inv_freq)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _qkv(h, lp, pos, cfg):
    """h [S, D] at positions ``pos`` [S] -> q [S, Hq, hd], k, v [S, Hkv,
    hd]."""
    S = h.shape[0]
    Hq, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    q = qk_norm(_mm(h, lp["wq"]).reshape(S, Hq, hd), lp["q_norm"], cfg)
    k = qk_norm(_mm(h, lp["wk"]).reshape(S, Hkv, hd), lp["k_norm"], cfg)
    v = _mm(h, lp["wv"]).reshape(S, Hkv, hd)
    return positional(q, pos, cfg), positional(k, pos, cfg), v


def _attend(q, k, v, seen, cfg):
    """q [Sq, Hq, hd] over keys k, v [Sk, Hkv, hd] where ``seen(rows)``
    gives the [rows, Sk] mask of a run of query rows: [Sq, Hq * hd], a
    block of queries at a time."""
    Sq, Hq, hd = q.shape
    k = jnp.repeat(k, Hq // k.shape[1], axis=1)
    v = jnp.repeat(v, Hq // v.shape[1], axis=1)
    qb = min(Q_BLOCK, Sq)
    blocks = -(-Sq // qb)
    q = jnp.pad(q, ((0, blocks * qb - Sq), (0, 0), (0, 0)))

    def one_block(j):
        qs = jax.lax.dynamic_slice_in_dim(q, j * qb, qb)
        s = jnp.einsum("qhd,khd->hqk", _cut(qs), _cut(k)) / math.sqrt(hd)
        mask = seen(jnp.minimum(j * qb + jnp.arange(qb), Sq - 1))
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _cut(p), _cut(v))

    a = jax.lax.map(one_block, jnp.arange(blocks)).reshape(-1, Hq, hd)[:Sq]
    return a.reshape(Sq, Hq * hd)


def route(g, lp, cfg):
    """The [S, E] weight of every expert for every token: 0 where the token
    did not choose the expert."""
    logits = _f32(g) @ _f32(lp["moe_route_w"])
    p = jax.nn.softmax(logits, axis=-1)
    kth = jnp.sort(logits, axis=-1)[..., -cfg.top_k][..., None]
    chosen = jnp.where(logits >= kth, p, 0.0)
    if cfg.norm_topk_prob:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return chosen


def expert(g, w_in, w_out):
    """One expert on g [S, D]: SwiGLU, gate first in ``w_in``."""
    gate, up = jnp.split(_mm(g, w_in), 2, axis=-1)
    return _mm(jax.nn.silu(gate) * up, w_out)


def experts(g, weights, lp):
    """What the experts add for g [S, D] under ``weights`` [S, E]."""
    def one_expert(out, e):
        w_in, w_out, weight = e  # this expert's matrices, cast where used
        return out + weight[..., None] * expert(g, w_in, w_out), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(g),
        (lp["moe_gmm_w_in"], lp["moe_gmm_w_out"],
         jnp.moveaxis(weights, -1, 0)))
    return out


def _ffn(x, lp, cfg):
    g = _rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    return x + experts(g, route(g, lp, cfg), lp)


def _hidden_one(params: dict, tokens, masked, cfg):
    """tokens [S] (``masked`` [S] bool or None) -> final hidden states
    [S, D], float32."""
    pos = jnp.arange(tokens.shape[0])
    x = embed(params, tokens, masked, cfg)
    for lp in params["layers"]:
        h = _rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(h, lp, pos, cfg)
        a = _attend(q, k, v, lambda rows: sees(
            rows[:, None], pos[None, :], cfg), cfg)
        x = _ffn(x + _mm(a, lp["wo"]), lp, cfg)
    return _rms_norm(x, params["ln_f_scale"], cfg.norm_eps)


def hidden(params: dict, tokens, cfg, masked=None):
    """tokens [B, S] -> final hidden states [B, S, D], float32, one
    sequence at a time. ``masked`` [B, S] bool: the positions that hold
    MASK whatever their id."""
    if masked is None:
        masked = jnp.zeros(tokens.shape, bool)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda tm: _hidden_one(params, tm[0], tm[1], cfg),
            (tokens, masked))


def logits(params: dict, tokens, cfg, masked=None):
    """Float32 logits [B, S, V] at every position, under the block mask."""
    with jax.default_matmul_precision("highest"):
        return _mm(hidden(params, tokens, cfg, masked), params["lm_head"])


# ------------------------------------------------------------- generation

_jit_logits = None


def _block_logits(params, seq, masked, cfg, pad_to):
    """Logits [B, V] of the last ``block_length`` positions of ``seq``."""
    global _jit_logits
    if _jit_logits is None:
        _jit_logits = jax.jit(logits, static_argnums=2)
    n = len(seq)
    size = max(pad_to or 0, n)
    tokens = np.zeros((1, size), np.int32)
    flags = np.zeros((1, size), bool)
    tokens[0, :n], flags[0, :n] = seq, masked
    out = _jit_logits(params, tokens, cfg, flags)
    return np.asarray(out[0, n - cfg.block_length: n], np.float32)


def generate(params, prompt, new: int, cfg, steps=None, remasking=None,
             pad_to=None):
    """The family's routine, greedy, a block at a time, every pass a whole
    forward over what stands so far (padded to ``pad_to`` positions, so
    that one compiled program serves: later positions see no earlier
    block's mask). ``steps`` / ``remasking``: None, the configuration's.
    Returns ``{"tokens": the ``new`` generated ids, "passes": for each the
    pass of its block that chose it, "logits": [new, V] the logits that
    did}``."""
    B = cfg.block_length
    counts = fill_counts(B, cfg.denoising_steps if steps is None else steps)
    mode = REMASKING.index(cfg.remasking if remasking is None else remasking)
    L = len(prompt)
    done = list(prompt[: L - L % B])          # committed blocks
    x = list(prompt[L - L % B:]) + [cfg.mask_token_id] * (B - L % B)
    masked = [False] * (L % B) + [True] * (B - L % B)
    tokens, passes, chose = [], [], []
    while len(tokens) < new:
        first = masked.index(True)            # the prompt's tail before it
        by_offset = {}
        for s, n in enumerate(counts):
            if not any(masked):
                break
            z = _block_logits(
                params, done + x, [False] * len(done) + masked, cfg, pad_to)
            zmax = z.max(axis=-1)
            confidence = np.exp(
                zmax - (zmax + np.log(np.exp(z - zmax[:, None]).sum(-1))))
            open_ = [o for o in range(B) if masked[o]]
            if mode == 0:
                chosen = open_[:n]
            else:
                ranked = sorted(open_, key=lambda o: (-confidence[o], o))
                chosen = ranked[:n]
                if mode == 2:
                    chosen += [o for o in ranked[n:]
                               if confidence[o] > cfg.confidence_threshold]
            for o in chosen:
                x[o], masked[o] = int(z[o].argmax()), False
                by_offset[o] = (s, z[o])
        assert not any(masked), "the schedule left a position masked"
        for o in range(first, B):
            tokens.append(x[o])
            passes.append(by_offset[o][0])
            chose.append(by_offset[o][1])
        done += x
        x, masked = [cfg.mask_token_id] * B, [True] * B
    return {"tokens": tokens[:new], "passes": passes[:new],
            "logits": np.stack(chose[:new])}


# ------------------------------------------- the logits that chose a token


def _schedule(index, masked0, counts):
    """For the masked position number ``index`` (left to right) of a block
    that began with ``masked0`` masked: (the pass that fills it under the
    sequential order, [T] how many are filled BEFORE each pass)."""
    left, end = masked0, jnp.zeros_like(masked0)
    which, before = jnp.zeros_like(index), []
    for n in counts:
        before.append(end)
        fill = jnp.minimum(n, left)
        left, end = left - fill, end + fill
        which = which + (index >= end)
    return which, jnp.stack(before)


def _chosen_rows(params: dict, tokens, positions, cfg):
    """One finished sequence ``tokens`` [S] (prompt, then the generated
    tokens; whatever lies behind them is not read), ``positions`` [P] =
    ``L + k - 1``: the final hidden rows [P, D] that chose each generated
    token ``k``, two streams through the layers. The CLEAN stream is the
    sequence as it ended. The NOISY stream holds, for each of the T passes
    of the schedule, the positions from the first generated block on as
    they stood BEFORE that pass (tokens filled by earlier passes, MASK
    elsewhere; the prompt's tail as it is): a noisy position attends the
    clean keys of the blocks before its own and the noisy keys of its own
    block in the same pass."""
    if cfg.remasking != "sequential":
        raise ValueError(
            "logits_at follows the sequential order: under a confidence "
            "order which pass chose a position is no function of the "
            "finished sequence")
    B = cfg.block_length
    counts = fill_counts(B, cfg.denoising_steps)
    T, P, S = len(counts), positions.shape[0], tokens.shape[0]
    L = positions[0] + 1
    first = L - L % B                      # the first generated block's start
    G = P + 2 * B                          # noisy positions: whole blocks
    npos = first + jnp.arange(G)
    # a noisy position's number among its block's masked, and their count
    head = jnp.where(npos // B == L // B, L % B, 0)
    index, masked0 = npos % B - head, B - head
    which, before = _schedule(index, masked0, counts)     # [G], [T, G]
    noisy_masked = (npos >= L)[None] & (index[None] >= before)   # [T, G]
    ids = tokens[jnp.minimum(npos, S - 1)]
    cpos = jnp.arange(S)
    xc = embed(params, tokens, None, cfg)
    xn = embed(params, jnp.broadcast_to(ids, (T, G)), noisy_masked, cfg)
    for lp in params["layers"]:
        hc = _rms_norm(xc, lp["attn_norm"], cfg.norm_eps)
        qc, kc, vc = _qkv(hc, lp, cpos, cfg)
        ac = _attend(qc, kc, vc, lambda rows: sees(
            rows[:, None], cpos[None, :], cfg), cfg)
        hn = _rms_norm(xn, lp["attn_norm"], cfg.norm_eps)
        an = []
        for s in range(T):
            qn, kn, vn = _qkv(hn[s], lp, npos, cfg)
            an.append(_attend(
                qn, jnp.concatenate([kc, kn]), jnp.concatenate([vc, vn]),
                lambda rows: jnp.concatenate([
                    cpos[None, :] // B < npos[rows][:, None] // B,
                    npos[None, :] // B == npos[rows][:, None] // B], axis=1),
                cfg))
        xc = _ffn(xc + _mm(ac, lp["wo"]), lp, cfg)
        xn = _ffn((xn + _mm(jnp.stack(an), lp["wo"])).reshape(T * G, -1),
                  lp, cfg).reshape(T, G, -1)
    xn = _rms_norm(xn, params["ln_f_scale"], cfg.norm_eps)
    at = positions + 1 - first             # the generated tokens' own rows
    return xn[which[at], at]


def logits_at(params: dict, tokens, positions, cfg):
    """Float32 logits [B, P, V]: row ``j`` of request ``i`` holds the
    logits that CHOSE generated token ``j``, under the runner's contract
    ``positions[i, j] = len(prompt_i) + j - 1`` (every generated token,
    ``every`` 1, so that ``positions[i, 0] + 1`` is the prompt's length).
    No shift: they are the logits AT the token's own position, of the pass
    that filled it (``_chosen_rows``)."""
    with jax.default_matmul_precision("highest"):
        def one(args):
            t, pos = args
            return _mm(_chosen_rows(params, t, pos, cfg), params["lm_head"])

        return jax.lax.map(one, (tokens, positions))
