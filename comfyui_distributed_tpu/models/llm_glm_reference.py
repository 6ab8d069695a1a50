"""The plain reference of ``models/llm_glm.py``: the whole forward pass of
the cut stack in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — every head's keys and values
decompressed from the latent, the indexer's scores of every key below a
query written out, the selection by ``lax.top_k``, one masked softmax a
head over the whole sequence, every expert it is given applied to every
token by a loop and masked; no cache, no chunks, no blocks of keys, no
absorption, no groups, no kernels, no threshold search. It shares nothing
with the served code but the layout of the weight tree, and it is given the
same share of the experts and of the vocabulary (what the absent experts
would add is left out here as there).

The equations (``D`` hidden, per token ``t`` unless said; ε =
``rms_norm_eps``; no bias but the index key's LayerNorm):

* ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; ``logits =
  RMSNorm(y_L) W_headᵀ``; ``x_0 = E[id]``.
* attention: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` → heads ×
  ``[q_nope | q_rope]``; ``[c_kv | k_r] = x W_kva``; ``c = RMSNorm(c_kv)``;
  ``k_rope = RoPE(k_r)`` (one for all heads, not normed); ``q_rope =
  RoPE(q_rope)``; ``[k_nope | v]_h = c W_b,h``; ``s_h(t,j) = (q_nope,h ·
  k_nope,h,j + q_rope,h · k_rope,j) · (nope + rope)^(−½)``; ``o =
  concat_h(Σ_{j∈S_t} softmax_{S_t}(s_h)(t,j) v_h,j) W_o``.
* the indexer: ``q_I = c_q W_Iq`` → ``J`` heads of ``d_I``; ``k_I =
  LayerNorm(x W_Ik)`` (weight, bias, ε 1e-6); the FIRST ``rope`` dimensions
  of each turned by the same rope; ``w = x W_Iw · J^(−½) · d_I^(−½)``;
  ``I(t,j) = Σ_i w(t,i) · ReLU(q_I(t,i) · k_I(j))`` for ``j ≤ t``; ``S_t`` =
  the ``min(index_topk, t + 1)`` positions ``j ≤ t`` of largest ``I(t,j)``,
  ties to the lower ``j`` (``lax.top_k``'s rule); one set for all heads, a
  set of its own in every layer.
* RoPE: pairs ``(2i, 2i+1)`` turn by ``t · θ^(−2i/d)``, no scaling.
* dense FFN (layers below ``first_k_dense_replace``): ``(silu(x W_g) ⊙ x
  W_u) W_down``.
* expert layer: ``σ = sigmoid(x W_r)`` over ALL the router's experts; the
  ``k`` largest of ``σ + b``; weights ``σ_e / Σ_selected σ ·
  routed_scaling_factor``; ``y = Shared(x) + Σ_{e ∈ selected ∩ held} w_e
  Expert_e(x)``, experts and the shared expert SwiGLU.

Departures from the public description: the family's Hadamard rotation of
``q_I``/``k_I`` and their fp8 storage (a quantisation aid that leaves the
products unchanged in exact arithmetic) and the multi-token-prediction
module are left out, as the configuration's file lists under ``assumed``
and ``reduced`` (cdtbench/configs/glm-5.json); the served model departs
from this file nowhere.

``forward(cfg, params, ids)`` answers the float32 logits at every position
(or at ``positions``). It runs layer by layer (jitted calls) so that at the
published widths only one layer's float32 copy of the weights exists at a
time. With ``block`` the SAME functions are evaluated for ``block`` query
rows at a time (a row sees all the keys below it either way, the FFNs are
per row): for a prompt whose ``T×T`` does not fit. ``given(layer, lo, n)``
— a bool ``[n, T]`` — holds those rows' selection to someone else's;
``tap(layer, lo, scores)`` is handed every block's own scores ``[n, T]``
(``−inf`` past a row's position) as they are made.
``cdtbench/reference/llm_glm_reference.py`` is a copy of this file
(``tests/test_llm_glm.py`` holds the two equal).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _rope(cfg, x, positions):
    """Interleaved pairs over the first ``qk_rope_head_dim`` of the last
    axis; ``x`` [T,...,d] at ``positions`` [T]."""
    r = cfg.qk_rope_head_dim
    g = jnp.asarray([cfg.rope_theta ** (-2.0 * i / r)
                     for i in range(r // 2)], F32)
    ang = (positions.astype(F32)[:, None] * g).reshape(
        x.shape[0], *([1] * (x.ndim - 2)), r // 2)
    even, odd = x[..., 0:r:2], x[..., 1:r:2]
    out = x.at[..., 0:r:2].set(even * jnp.cos(ang) - odd * jnp.sin(ang))
    return out.at[..., 1:r:2].set(even * jnp.sin(ang) + odd * jnp.cos(ang))


def swiglu(ffn, x):
    g, u = jnp.split(x @ ffn["w_gu"], 2, axis=-1)
    return (jax.nn.silu(g) * u) @ ffn["w_down"]


@functools.partial(jax.jit, static_argnums=0)
def keys_of(cfg, norm, layer, x):
    """What every query of a layer reads of the sequence ``x`` [T,D]: the
    normed latent ``c`` [T,rank], the roped shared key [T,r] and the roped
    index key [T,d_I]."""
    with jax.default_matmul_precision("highest"):
        p, ix = _f32(layer["attn"]), _f32(layer["indexer"])
        rq, rank, di = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.index_head_dim
        t = jnp.arange(x.shape[0])
        normed = _rms(x, norm.astype(F32), cfg.rms_norm_eps)
        y = normed @ p["w_a"][:, rq:]
        c = _rms(y[:, :rank], p["c_norm"], cfg.rms_norm_eps)
        k_i = _layer_norm((normed @ ix["w_kw"])[:, :di], ix["k_norm"],
                          ix["k_bias"], cfg.index_norm_eps)
        return c, _rope(cfg, y[:, rank:], t), _rope(cfg, k_i, t)


def index_scores(cfg, ix, c_q, normed, rows, k_i):
    """``I`` [n,T] of the queries at positions ``rows``; ``−inf`` past a
    row's own position."""
    J, di = cfg.index_n_heads, cfg.index_head_dim
    q_i = _rope(cfg, (c_q @ ix["w_q"]).reshape(-1, J, di), rows)
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


def attention(cfg, p, ix, normed, rows, keys, kept):
    """The attention's output for the ``n`` rows ``normed`` [n,D] at
    positions ``rows``, over the sequence's ``keys`` (:func:`keys_of`);
    ``kept`` [n,T] bool or None (the rows' own selection). Answers ``(out
    [n,D], scores [n,T])``."""
    H, nope, rope, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    rq, rank = cfg.q_lora_rank, cfg.kv_lora_rank
    c, k_rope, k_i = keys
    n = rows.shape[0]
    c_q = _rms(normed @ p["w_a"][:, :rq], p["q_norm"], cfg.rms_norm_eps)
    scores = index_scores(cfg, ix, c_q, normed, rows, k_i)
    if kept is None:
        kept = select(scores, cfg.index_topk)
    q = c_q @ p["w_qb"]
    # W_qb's columns: every head's nope part, then every head's rope part
    q_nope = q[:, :H * nope].reshape(n, H, nope)
    q_rope = _rope(cfg, q[:, H * nope:].reshape(n, H, rope), rows)
    w_b = p["w_b"].reshape(rank, H, nope + dv)
    scale = 1.0 / math.sqrt(nope + rope)

    def head(args):
        qn, qr, w = args
        kv = c @ w                                           # [T, nope+dv]
        s = (qn @ kv[:, :nope].T + qr @ k_rope.T) * scale
        a = jax.nn.softmax(jnp.where(kept, s, -jnp.inf), axis=-1)
        return a @ kv[:, nope:]

    o = jax.lax.map(head, (jnp.swapaxes(q_nope, 0, 1),
                           jnp.swapaxes(q_rope, 0, 1),
                           jnp.swapaxes(w_b, 0, 1)))              # [H,n,dv]
    return jnp.swapaxes(o, 0, 1).reshape(n, H * dv) @ p["w_o"], scores


def experts(cfg, m, x):
    """The held experts' part of the routed result, plus the shared
    expert: every held expert on every token, masked by the routing."""
    s = jax.nn.sigmoid(x @ m["w_router"])
    biased = s + m["router_bias"]
    kth = jnp.sort(biased, axis=-1)[:, -cfg.num_experts_per_tok][:, None]
    selected = biased >= kth
    weight = jnp.where(selected, s, 0.0)
    weight = weight / weight.sum(-1, keepdims=True) \
        * cfg.routed_scaling_factor
    out = swiglu(m["shared"], x)
    for local in range(cfg.n_routed_experts):
        e = cfg.first_expert + local
        out = out + weight[:, e:e + 1] * swiglu(
            {"w_gu": m["e_gu"][local], "w_down": m["e_down"][local]}, x)
    held = selected[:, cfg.first_expert:cfg.first_expert
                    + cfg.n_routed_experts]
    return out, held.sum()


@functools.partial(jax.jit, static_argnums=(0, 4))
def attention_rows(cfg, norm, layer, lo, n: int, x, keys, kept=None):
    """``x[lo:lo+n] + Attn(RMSNorm(x))[lo:lo+n]`` of one layer, and those
    rows' own index scores."""
    with jax.default_matmul_precision("highest"):
        part = jax.lax.dynamic_slice_in_dim(x, lo, n, 0)
        normed = _rms(part, norm.astype(F32), cfg.rms_norm_eps)
        out, scores = attention(cfg, _f32(layer["attn"]),
                                _f32(layer["indexer"]), normed,
                                lo + jnp.arange(n), keys, kept)
        return part + out, scores


@functools.partial(jax.jit, static_argnums=0)
def ffn_rows(cfg, norm, ffn, h):
    """``h + FFN(RMSNorm(h))`` on the rows given — ``ffn`` a dense layer's
    SwiGLU or an expert layer's ``moe`` — and the routed slots of those
    rows that fell on held experts."""
    with jax.default_matmul_precision("highest"):
        ffn = _f32(ffn)
        x = _rms(h, norm.astype(F32), cfg.rms_norm_eps)
        if "w_router" in ffn:
            y, held = experts(cfg, ffn, x)
            return h + y, held
        return h + swiglu(ffn, x), jnp.zeros((), jnp.int32)


@functools.partial(jax.jit, static_argnums=0)
def head_forward(cfg, final_norm, head, h):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, final_norm.astype(F32), cfg.rms_norm_eps)
        return x @ head.astype(F32).T


def forward(cfg, params, ids, positions=None, block: int | None = None,
            given=None, tap=None):
    """Float32 logits [T,V] (or at ``positions`` only) for the whole
    sequence ``ids`` [T], and per layer the routed slots that fell on
    held experts (0 for a dense layer)."""
    T = ids.shape[0]
    block = T if block is None else block
    x = params["embed"][ids].astype(F32)
    held = []
    for i, layer in enumerate(params["layers"]):
        ffn = layer["moe" if i >= cfg.first_k_dense_replace else "ffn"]
        keys = keys_of(cfg, layer["norm1"], layer, x)
        parts = []
        for lo in range(0, T, block):
            n = min(block, T - lo)
            h, scores = attention_rows(
                cfg, layer["norm1"], layer, lo, n, x, keys,
                None if given is None else given(i, lo, n))
            if tap is not None:
                tap(i, lo, scores)
            parts.append(ffn_rows(cfg, layer["norm2"], ffn, h))
        x = jnp.concatenate([part for part, _ in parts])
        held.append(sum(n for _, n in parts))
    if positions is not None:
        x = x[jnp.asarray(positions)]
    return head_forward(cfg, params["final_norm"], params["head"], x), held
