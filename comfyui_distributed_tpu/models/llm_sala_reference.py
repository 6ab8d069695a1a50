"""The plain reference of ``models/llm_sala.py``: the whole forward pass in
straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — the linear-attention
recurrence as a ``lax.scan`` over the tokens one at a time, the sparse
layers' selection by a stable ``argsort`` of the block scores and their
attention as one masked softmax over all the rows; no cache, no chunks, no
kernels, no compressed buffer (a compressed key is the mean of its rows,
taken when asked for). It shares nothing with the served code but the
layout of the weight tree.

The equations (``D`` hidden, ``H`` heads of ``d``, ``G`` key/value heads,
ε = ``rms_norm_eps``, ``r = scale_depth / √mup_denominator``):

* ``x_0 = scale_emb · E[id]``; every layer ``h = x + r · Mix(RMSNorm(x))``,
  ``y = h + r · FFN(RMSNorm(h))``, ``FFN(x) = (silu(x W_g) ⊙ x W_u) W_d``;
  ``logits = W_head RMSNorm(y_L) / (D / dim_model_base)``; head untied.
* layer ``i`` is of the kind ``mixer_types[i]``.
* ``lightning-attn``: ``[q | k | v | g] = x W_in``, ``H`` heads each; ``q``,
  ``k`` RMS-normed per head (a weight of ``d`` each), turned by rope (half
  rotation over all ``d``, angle ``p · θ^(−2i/d)``, computed in float64);
  per head ``S_t = λ_h S_{t−1} + k_t v_tᵀ`` from ``S_{−1} = 0``, ``λ_h =
  exp(−2^(−8(h+1)/H))``; ``o_t = S_tᵀ q_t · d^(−½)``; ``o ←
  RMSNorm_d(o)`` per head ``⊙ σ(g)``; out ``= o W_o``.
* ``minicpm4`` (InfLLM-v2): ``[q | k | v | g] = x W_in``, ``H`` query heads
  over ``G`` key/value heads; ``q``, ``k`` RMS-normed per head; NO
  positional encoding; out ``= (Attn ⊙ σ(g)) W_o``. A sequence of at most
  ``dense_len`` positions: causal softmax over every row. A longer one: for
  the query at ``t`` and group ``g``, ``K̄_j = mean(K[stride·j : stride·j +
  kernel])`` for every ``j`` with ``stride·j + kernel ≤ t + 1``; ``p_h =
  softmax_j(q_h · K̄_j · d^(−½))``; ``s_g = Σ_{h∈g} p_h``; ``B_g[b] =
  max{s_g[j] : window j touches rows block·b … block·b + block − 1}``;
  block 0 and the ``window / block`` blocks ending at ``t``'s own are
  forced, blocks past its own excluded; the table is the ``topk + window /
  block`` best (ties to the lower index); softmax attention over the rows
  ``≤ t`` of the table's blocks.

Departures from the public description: none in the mathematics that the
row's ``config`` settles; what it does not (the sparse sizes, the decay
rates, where the output norm and gates sit) is set as the configuration's
file lists under ``assumed`` (cdtbench/configs/minicpm-sala.json).

``forward(cfg, params, ids)`` answers the float32 logits at every position
(or at ``positions``). ``total`` is the length the REQUEST will reach
(prompt + new tokens; default: ``len(ids)``): it decides dense or sparse
for every row, as the served model decides it a request. ``tables`` — one
``[G, T, blocks]`` bool array a sparse layer — holds the selection fixed to
someone else's; ``keep``, a dict, is filled with this pass's own
``scores`` and ``chosen`` a sparse layer (of every ``keep["every"]``-th
query where given). With ``block`` the same
functions are evaluated ``block`` rows at a time (a lightning layer hands
the next block its state, a sparse row sees all the rows below it either
way) for a prompt whose activations do not fit whole.
``cdtbench/reference/llm_sala_reference.py`` is a copy of this file
(``tests/test_llm_sala.py`` holds the two equal).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def rope_angles(cfg, lo: int, n: int):
    """``cos``, ``sin`` [n, d/2] of positions ``lo …``, from float64."""
    half = cfg.lightning_head_dim // 2
    freqs = cfg.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = np.arange(lo, lo + n, dtype=np.float64)[:, None] * freqs
    return jnp.asarray(np.cos(angle), F32), jnp.asarray(np.sin(angle), F32)


def _rope(x, cos, sin):
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def decay(cfg):
    h = np.arange(1, cfg.lightning_nh + 1, dtype=np.float64)
    return jnp.asarray(np.exp(-(2.0 ** (-8.0 * h / cfg.lightning_nh))), F32)


@functools.partial(jax.jit, static_argnums=0)
def lightning_rows(cfg, norm, p, cos, sin, state, x):
    """``x + r · Lightning(RMSNorm(x))`` for the rows ``x`` [n, D] that
    follow ``state`` [H, d, d]; also the state after them."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        n, H, d = x.shape[0], cfg.lightning_nh, cfg.lightning_head_dim
        eps = cfg.rms_norm_eps
        y = _rms(x, norm.astype(F32), eps) @ p["w_in"]
        q, k, v, gate = (y[:, j * H * d:(j + 1) * H * d] for j in range(4))
        q = _rope(_rms(q.reshape(n, H, d), p["q_norm"], eps), cos, sin)
        k = _rope(_rms(k.reshape(n, H, d), p["k_norm"], eps), cos, sin)
        lam = decay(cfg)[:, None, None]

        def token(S, row):
            q_t, k_t, v_t = row
            S = lam * S + k_t[:, :, None] * v_t[:, None, :]
            return S, jnp.einsum("hk,hkv->hv", q_t, S) * d ** -0.5

        state, o = jax.lax.scan(token, state, (q, k, v.reshape(n, H, d)))
        o = _rms(o, p["o_norm"], eps).reshape(n, H * d) * jax.nn.sigmoid(gate)
        return x + cfg.residual_scale * (o @ p["w_o"]), state


def _sparse_in(cfg, norm, p, x):
    H, G, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
               cfg.head_dim)
    n, eps = x.shape[0], cfg.rms_norm_eps
    y = _rms(x, norm.astype(F32), eps) @ p["w_in"]
    q = _rms(y[:, :H * d].reshape(n, H, d), p["q_norm"], eps)
    k = _rms(y[:, H * d:(H + G) * d].reshape(n, G, d), p["k_norm"], eps)
    v = y[:, (H + G) * d:(H + 2 * G) * d].reshape(n, G, d)
    return q, k, v, y[:, (H + 2 * G) * d:]


@functools.partial(jax.jit, static_argnums=0)
def sparse_keys(cfg, norm, p, x):
    """Every row's key and value [T, G, d] of one sparse layer."""
    with jax.default_matmul_precision("highest"):
        return _sparse_in(cfg, norm, _f32(p), x)[1:3]


def block_scores(cfg, q, k, lo):
    """Float32 ``[n, G, blocks]``: the rule's block scores of the queries
    ``q`` [n, H, d] at positions ``lo …`` against all keys ``k`` [T, G, d];
    forced blocks ``+∞``, blocks past a query's own ``−∞``."""
    n, H, d = q.shape
    T, G, _ = k.shape
    ks, st, bs = cfg.kernel_size, cfg.kernel_stride, cfg.block_size
    blocks = -(-T // bs)
    pos = lo + jnp.arange(n)
    windows = max((T - ks) // st + 1, 0)
    if windows:
        start = jnp.arange(windows) * st
        mean = jnp.stack([k[s:s + windows * st:st] for s in range(ks)]).mean(0)
        s = jnp.einsum("ngjd,wgd->ngjw", q.reshape(n, G, H // G, d), mean) \
            * d ** -0.5
        whole = (start[None, :] + ks <= pos[:, None] + 1)[:, None, None, :]
        prob = jnp.where(whole, jax.nn.softmax(
            jnp.where(whole, s, -1e30), axis=-1), 0.0).sum(2)   # [n,G,w]
        # a block is touched by the windows per·b − m + 1 … per·b + per − 1
        # (per = block / stride, m = kernel / stride): a max-pool of width
        # per + m − 1, stride per, padding m − 1 (5, 4, 1 as published)
        per, m = bs // st, ks // st
        width = per + m - 1
        right = (blocks - 1) * per + width - (m - 1) - windows
        score = jax.lax.reduce_window(
            prob, 0.0, jax.lax.max, (1, 1, width), (1, 1, per),
            ((0, 0), (0, 0), (m - 1, right)))
    else:
        score = jnp.zeros((n, G, blocks), F32)
    own = (pos // bs)[:, None, None]
    b = jnp.arange(blocks)[None, None, :]
    forced = (b < cfg.init_blocks) | (b > own - cfg.window_size // bs)
    return jnp.where(b > own, -jnp.inf, jnp.where(forced, jnp.inf, score))


def choose(cfg, score):
    """The table as a bool ``[n, G, blocks]``: the best ``topk + window /
    block`` blocks by a stable ``argsort`` (ties to the lower index),
    never an excluded one."""
    table = min(cfg.topk + cfg.window_size // cfg.block_size,
                score.shape[-1])
    order = jnp.argsort(-score, axis=-1, stable=True)[..., :table]
    picked = (order[..., None] == jnp.arange(score.shape[-1])).any(-2)
    return picked & (score > -jnp.inf)


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def sparse_rows(cfg, norm, p, k, v, n: int, sparse: bool, lo, x, table):
    """``x[lo:lo+n] + r · Attn(RMSNorm(x))[lo:lo+n]`` of one sparse layer
    over all keys ``k``, ``v`` [T, G, d]; ``table`` [n, G, blocks] bool
    holds the selection fixed (None: the rule's own). Also the scores and
    the table used (None, None for a dense sequence)."""
    with jax.default_matmul_precision("highest"):
        H, G, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
        T, bs = k.shape[0], cfg.block_size
        rows = jax.lax.dynamic_slice_in_dim(x, lo, n, 0)
        q, _, _, gate = _sparse_in(cfg, norm, _f32(p), rows)
        pos = lo + jnp.arange(n)
        seen = (jnp.arange(T)[None, :] <= pos[:, None])[:, None, :]
        score = None
        if sparse:
            score = block_scores(cfg, q, k, lo)
            if table is None:
                table = choose(cfg, score)
            seen = seen & jnp.repeat(table, bs, axis=-1)[:, :, :T]
        s = jnp.einsum("ngjd,tgd->ngjt", q.reshape(n, G, H // G, d), k) \
            * d ** -0.5
        prob = jax.nn.softmax(jnp.where(seen[:, :, None, :], s, -jnp.inf),
                              axis=-1)
        o = jnp.einsum("ngjt,tgd->ngjd", prob, v).reshape(n, H * d)
        out = rows + cfg.residual_scale * (
            (o * jax.nn.sigmoid(gate)) @ p["w_o"].astype(F32))
        return out, score, table


@functools.partial(jax.jit, static_argnums=0)
def ffn_rows(cfg, norm, ffn, h):
    """``h + r · FFN(RMSNorm(h))`` on the rows given."""
    with jax.default_matmul_precision("highest"):
        ffn = _f32(ffn)
        gu = _rms(h, norm.astype(F32), cfg.rms_norm_eps) @ ffn["w_gu"]
        half = gu.shape[-1] // 2
        return h + cfg.residual_scale * (
            (jax.nn.silu(gu[:, :half]) * gu[:, half:]) @ ffn["w_down"])


@functools.partial(jax.jit, static_argnums=0)
def head_forward(cfg, final_norm, head, h):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, final_norm.astype(F32), cfg.rms_norm_eps)
        return x @ head.astype(F32).T / cfg.logit_divisor


def forward(cfg, params, ids, positions=None, block: int | None = None,
            total: int | None = None, tables=None, keep: dict | None = None):
    """Float32 logits [T,V] (or at ``positions`` only) for the whole
    sequence ``ids`` [T]."""
    T = ids.shape[0]
    block = T if block is None else block
    sparse = (T if total is None else total) > cfg.dense_len
    x = params["embed"][ids].astype(F32) * cfg.scale_emb
    seen_sparse = 0
    for i, layer in enumerate(params["layers"]):
        parts = []
        if cfg.mixer_types[i] == "lightning-attn":
            H, d = cfg.lightning_nh, cfg.lightning_head_dim
            state = jnp.zeros((H, d, d), F32)
            for lo in range(0, T, block):
                cos, sin = rope_angles(cfg, lo, min(block, T - lo))
                rows, state = lightning_rows(
                    cfg, layer["norm1"], layer["attn"], cos, sin, state,
                    x[lo:lo + block])
                parts.append(rows)
        else:
            k, v = sparse_keys(cfg, layer["norm1"], layer["attn"], x)
            given = None if tables is None else tables[seen_sparse]
            scores, chosen = [], []
            for lo in range(0, T, block):
                n = min(block, T - lo)
                table = None if given is None else \
                    jnp.swapaxes(given[:, lo:lo + n], 0, 1)
                rows, score, table = sparse_rows(
                    cfg, layer["norm1"], layer["attn"], k, v, n, sparse,
                    lo, x, table)
                parts.append(rows)
                if keep is not None and sparse:
                    every = keep.get("every", 1)     # block % every == 0
                    scores.append(score[::every])
                    chosen.append(table[::every])
            if scores:
                keep.setdefault("scores", []).append(
                    jnp.swapaxes(jnp.concatenate(scores), 0, 1))
                keep.setdefault("chosen", []).append(
                    jnp.swapaxes(jnp.concatenate(chosen), 0, 1))
            seen_sparse += 1
        x = jnp.concatenate([ffn_rows(cfg, layer["norm2"], layer["ffn"], part)
                             for part in parts])
    if positions is not None:
        x = x[jnp.asarray(positions)]
    return head_forward(cfg, params["final_norm"], params["head"], x)
