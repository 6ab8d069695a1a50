"""The plain reference of ``models/llm_keye.py``: the whole forward pass of
the cut stack in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — every query head against every
key of its K/V head, the indexer's scores of every key below a query written
out, the selection by ``lax.top_k``, one masked softmax a head over the whole
sequence, the router's top experts by ``lax.top_k`` and every expert it is
given applied to every token by a loop and masked; no cache, no chunks, no
blocks of keys, no groups, no kernels, no threshold search. It shares nothing
with the served code but the layout of the weight tree, and it is given the
same experts (here: all of them) and the whole vocabulary.

ASSUMED lines (the row's ``config.json`` gives sizes and key names; these are
the family's conventions, listed in ``cdtbench/configs/keye-vl-2.0-30b-a3b.json``
under ``assumed``): (1) q and k are RMS-normed per head, a weight of
``head_dim`` each shared over heads (the Qwen3-MoE block has them
unconditionally); (2) text positions only, so the three ``mrope_section``
streams carry the same ``t`` and the rope is the one-dimensional one; (3) the
indexer's wiring is DeepSeek-Sparse-Attention's with ``sa_config``'s sizes:
``q_I`` from the NORMED STREAM (a grouped-query model has no query latent),
one LayerNormed index key a token (weight and bias, ε 1e-6), the rope on ALL
``indexer_head_dim`` dimensions in rotate-half pairs ``(i, i + d_I/2)``, ``w
= x W_Iw · J^(−½) · d_I^(−½)``; (4) the family's Hadamard rotation of
``q_I``/``k_I`` and their fp8 storage are a quantisation aid: left out; (5)
``q_chunk_size`` / ``kv_chunk_size`` are the published code's tiling of the
score computation, which changes no value: NOT READ; (6) the vision tower is
left out.

The equations (``D`` hidden, per token ``t`` unless said; ε =
``rms_norm_eps``; no bias but the index key's LayerNorm):

* ``h = x + Attn(RMSNorm(x))``, ``y = h + Experts(RMSNorm(h))``; ``logits =
  RMSNorm(y_L) W_headᵀ``; ``x_0 = E[id]``.
* attention: ``[q | k | v] = x W_in`` → ``H`` query heads, ``G`` key and
  ``G`` value heads of ``d``; ``q_h ← RoPE(RMSNorm_d(q_h))``, ``k_g ←
  RoPE(RMSNorm_d(k_g))``; query head ``h`` reads K/V head ``⌊h / (H/G)⌋``;
  ``s_h(t,j) = q_h(t) · k_g(j) · d^(−½)``; ``o = concat_h(Σ_{j∈S_t}
  softmax_{S_t}(s_h)(t,j) v_g(j)) W_o``.
* the indexer: ``q_I = x W_Iq`` → ``J`` heads of ``d_I``; ``k_I =
  LayerNorm(x W_Ik)``; both roped; ``w = x W_Iw · J^(−½) · d_I^(−½)``;
  ``I(t,j) = Σ_i w(t,i) · ReLU(q_I(t,i) · k_I(j))`` for ``j ≤ t``; ``S_t`` =
  the ``min(topk, t + 1)`` positions ``j ≤ t`` of largest ``I(t,j)``, ties
  to the lower ``j`` (``lax.top_k``'s rule); one set for all heads, a set of
  its own in every layer.
* RoPE: rotate-half — pairs ``(i, i + width/2)`` turn by ``t · θ^(−2i /
  width)``, ``width`` the head's (``d`` or ``d_I``), no scaling; the angles
  are made in float64 on the host (at position 65 535 a float32 product is
  off by parts in a thousand of a radian).
* expert layer: ``p = softmax(x W_r)`` over ALL the router's experts; the
  ``k`` largest (ties to the lower index); weights ``p_e / Σ_selected p``;
  ``y = Σ_{e ∈ selected ∩ held} w_e Expert_e(x)``, ``Expert_e(x) = (silu(x
  W_g,e) ⊙ x W_u,e) W_d,e``; no shared expert.

``forward(cfg, params, ids)`` answers the float32 logits at every position
(or at ``positions``). It runs layer by layer (jitted calls) so that at the
published widths only one layer's float32 attention weights and one
expert's exist at a time. With ``block`` the SAME functions are evaluated
for ``block`` query rows at a time (a row sees all the keys below it either
way, the experts are per row): for a prompt whose ``T×T`` does not fit.
``given(layer, lo, n)`` — a bool ``[n, T]`` — holds those rows' selection to
someone else's; ``tap(layer, lo, scores)`` is handed every block's own
scores ``[n, T]`` (``−inf`` past a row's position) as they are made.
``cdtbench/reference/llm_keye_reference.py`` is a copy of this file
(``tests/test_llm_keye.py`` holds the two equal).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def rope_angles(cfg, T: int, width: int):
    """``(cos, sin)`` [T, width/2] of ``t · θ^(−2i/width)``, float64 on the
    host, held float32."""
    half = width // 2
    freqs = cfg.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = np.arange(T, dtype=np.float64)[:, None] * freqs
    return jnp.asarray(np.cos(angle), F32), jnp.asarray(np.sin(angle), F32)


def _rope(x, cos, sin):
    """``x`` [T,heads,width] = ``[x₁ | x₂]`` → ``[x₁ cos − x₂ sin | x₂ cos +
    x₁ sin]``; ``cos``, ``sin`` [T, width/2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rows(a, lo, n):
    return jax.lax.dynamic_slice_in_dim(a, lo, n, 0)


@functools.partial(jax.jit, static_argnums=0)
def keys_of(cfg, norm, layer, x, rope, index_rope):
    """What every query of a layer reads of the sequence ``x`` [T,D]: the
    normed, roped keys [T,G,d], the values [T,G,d] and the roped index key
    [T,d_I]."""
    with jax.default_matmul_precision("highest"):
        p, ix = _f32(layer["attn"]), _f32(layer["indexer"])
        H, G, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
        T, di = x.shape[0], cfg.indexer_head_dim
        normed = _rms(x, norm.astype(F32), cfg.rms_norm_eps)
        y = normed @ p["w_in"][:, H * d:]
        k = _rope(_rms(y[:, :G * d].reshape(T, G, d), p["k_norm"],
                       cfg.rms_norm_eps), *rope)
        k_i = _layer_norm((normed @ ix["w_kw"])[:, :di], ix["k_norm"],
                          ix["k_bias"], cfg.index_norm_eps)
        return k, y[:, G * d:].reshape(T, G, d), \
            _rope(k_i[:, None], *index_rope)[:, 0]


def index_scores(cfg, ix, normed, rows, index_rope, k_i):
    """``I`` [n,T] of the queries at positions ``rows`` (``index_rope``
    their rows of the angles); ``−inf`` past a row's own position."""
    J, di = cfg.indexer_num_heads, cfg.indexer_head_dim
    q_i = _rope((normed @ ix["w_q"]).reshape(-1, J, di), *index_rope)
    w = (normed @ ix["w_kw"])[:, di:] / math.sqrt(J) / math.sqrt(di)

    def head(acc, args):
        q, wj = args
        return acc + wj[:, None] * jax.nn.relu(q @ k_i.T), None

    scores, _ = jax.lax.scan(
        head, jnp.zeros((rows.shape[0], k_i.shape[0]), F32),
        (jnp.swapaxes(q_i, 0, 1), w.T))
    seen = rows[:, None] >= jnp.arange(k_i.shape[0])[None, :]
    return jnp.where(seen, scores + 0.0, -jnp.inf)


def select(scores, topk: int):
    """The rows' own sets as a bool [n,T]: ``lax.top_k`` (ties to the lower
    position), less the places a short prefix leaves empty."""
    value, at = jax.lax.top_k(scores, min(topk, scores.shape[1]))
    row = jnp.arange(scores.shape[0])[:, None]
    return jnp.zeros(scores.shape, bool).at[row, at].set(value > -jnp.inf)


def attention(cfg, p, ix, normed, rows, rope, index_rope, keys, kept):
    """The attention's output for the ``n`` rows ``normed`` [n,D] at
    positions ``rows`` (``rope``, ``index_rope``: their rows of the
    angles), over the sequence's ``keys`` (:func:`keys_of`); ``kept`` [n,T]
    bool or None (the rows' own selection). Answers ``(out [n,D], scores
    [n,T])``."""
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    k, v, k_i = keys
    n = rows.shape[0]
    scores = index_scores(cfg, ix, normed, rows, index_rope, k_i)
    if kept is None:
        kept = select(scores, cfg.topk)
    q = (normed @ p["w_in"][:, :H * d]).reshape(n, H, d)
    q = _rope(_rms(q, p["q_norm"], cfg.rms_norm_eps), *rope)
    scale = 1.0 / math.sqrt(d)

    def head(args):
        qh, g = args
        s = (qh @ k[:, g].T) * scale
        a = jax.nn.softmax(jnp.where(kept, s, -jnp.inf), axis=-1)
        return a @ v[:, g]

    o = jax.lax.map(head, (jnp.swapaxes(q, 0, 1),
                           jnp.arange(H) // (H // G)))            # [H,n,d]
    return jnp.swapaxes(o, 0, 1).reshape(n, H * d) @ p["w_o"], scores


def experts(cfg, m, x):
    """The held experts' part of the routed result: every held expert on
    every token (one at a time), masked by the routing; and how many routed
    slots fell on held experts."""
    prob = jax.nn.softmax(x @ m["w_router"].astype(F32), axis=-1)
    top, at = jax.lax.top_k(prob, cfg.num_experts_per_tok)
    row = jnp.arange(x.shape[0])[:, None]
    weight = jnp.zeros(prob.shape, F32).at[row, at].set(
        top / top.sum(-1, keepdims=True))
    first, held = cfg.first_expert, cfg.num_experts

    def one(out, args):
        w_gu, w_down, w_e = args
        g, u = jnp.split(x @ w_gu.astype(F32), 2, axis=-1)
        return out + w_e[:, None] * ((jax.nn.silu(g) * u)
                                     @ w_down.astype(F32)), None

    out, _ = jax.lax.scan(one, jnp.zeros(x.shape, F32),
                          (m["e_gu"], m["e_down"],
                           weight[:, first:first + held].T))
    return out, ((at >= first) & (at < first + held)).sum()


@functools.partial(jax.jit, static_argnums=(0, 4))
def attention_rows(cfg, norm, layer, lo, n: int, x, rope, index_rope, keys,
                   kept=None):
    """``x[lo:lo+n] + Attn(RMSNorm(x))[lo:lo+n]`` of one layer, and those
    rows' own index scores."""
    with jax.default_matmul_precision("highest"):
        part = _rows(x, lo, n)
        normed = _rms(part, norm.astype(F32), cfg.rms_norm_eps)
        out, scores = attention(
            cfg, _f32(layer["attn"]), _f32(layer["indexer"]), normed,
            lo + jnp.arange(n), tuple(_rows(a, lo, n) for a in rope),
            tuple(_rows(a, lo, n) for a in index_rope), keys, kept)
        return part + out, scores


@functools.partial(jax.jit, static_argnums=0)
def expert_rows(cfg, norm, moe, h):
    """``h + Experts(RMSNorm(h))`` on the rows given, and the routed slots
    of those rows that fell on held experts."""
    with jax.default_matmul_precision("highest"):
        y, held = experts(cfg, moe, _rms(h, norm.astype(F32),
                                         cfg.rms_norm_eps))
        return h + y, held


@functools.partial(jax.jit, static_argnums=0)
def head_forward(cfg, final_norm, head, h):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, final_norm.astype(F32), cfg.rms_norm_eps)
        return x @ head.astype(F32).T


def forward(cfg, params, ids, positions=None, block: int | None = None,
            given=None, tap=None):
    """Float32 logits [T,V] (or at ``positions`` only) for the whole
    sequence ``ids`` [T], and per layer the routed slots that fell on held
    experts."""
    T = ids.shape[0]
    block = T if block is None else block
    rope = rope_angles(cfg, T, cfg.head_dim)
    index_rope = rope_angles(cfg, T, cfg.indexer_head_dim)
    x = params["embed"][ids].astype(F32)
    held = []
    for i, layer in enumerate(params["layers"]):
        keys = keys_of(cfg, layer["norm1"], layer, x, rope, index_rope)
        parts = []
        for lo in range(0, T, block):
            n = min(block, T - lo)
            h, scores = attention_rows(
                cfg, layer["norm1"], layer, lo, n, x, rope, index_rope, keys,
                None if given is None else given(i, lo, n))
            if tap is not None:
                tap(i, lo, scores)
            parts.append(expert_rows(cfg, layer["norm2"], layer["moe"], h))
        x = jnp.concatenate([part for part, _ in parts])
        held.append(sum(n for _, n in parts))
    if positions is not None:
        x = x[jnp.asarray(positions)]
    return head_forward(cfg, params["final_norm"], params["head"], x), held
