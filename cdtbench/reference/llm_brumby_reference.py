"""The plain reference of ``models/llm_brumby.py``: the whole forward pass of
the cut stack in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — power retention as its
QUADRATIC form, a head's full ``[T, T]`` matrix ``(QKᵀ)²/d ⊙ exp(b_t − b_s)``
under the causal mask, its row sums and the quotient; no cache, no chunk, no
kernel, no ``φ`` and no state. The served program carries the same function
as a recurrence over ``φ(k)``: two different computations of one function,
which is what makes the comparison independent. It shares nothing with the
served code but the layout of the weight tree.

ASSUMED lines (the row's ``config.json`` gives sizes and key names; the
public description of power attention and its gated, recurrent form —
arXiv:2507.04239 — and the release note — the Qwen3-14B block with its
attention replaced by power retention — the mechanism; these are what neither
settles, listed word for word in ``cdtbench/configs/brumby-14b-base.json``
under ``assumed``): (1) the degree is 2; (2) q and k are RMS-normed per head,
one weight vector for all heads, as in the Qwen3-14B block; (3) rope is kept:
rotate-half on all 128 dimensions, θ 1e6; (4) one gate a K/V head a token,
``logsigmoid`` of a linear map WITH bias of the sublayer's normed input; (5)
no ε in the quotient; (6) no output gate and no output norm.

The equations (``D_m`` hidden, ``H`` heads over ``G`` K/V heads of ``d``, ``J
= H/G``; per token ``t``; ε = ``rms_norm_eps``):

* ``h_0 = Emb[id]``; ``h ← h + y`` at both sublayers, ``y`` the sublayer's
  output of ``RMSNorm(h)``; ``logits = RMSNorm(h_L) W_headᵀ``.
* retention: ``[q | k | v] = x W_in``; ``q ← RMSNorm(q; w_qn)``, ``k ←
  RMSNorm(k; w_kn)`` over ``d``; rope (pairs ``(i, i + d/2)``, angles ``t ·
  θ^(−2i/d)`` made in float64 on the host); ``log γ_t[g] = logsigmoid(x W_γ +
  b_γ)``, ``b_t = Σ_{u≤t} log γ_u``; ``A[t,s] = exp(b_t[g] − b_s[g]) · (q_t[h] ·
  k_s[g])² / d`` for ``s ≤ t``, ``g = h // J``; ``o_t[h] = Σ_s A[t,s] v_s[g] /
  Σ_s A[t,s]``; ``y = concat_h(o) W_o``.
* dense: ``y = (silu(x W_g) ⊙ x W_u) W_down``.

``forward(cfg, params, ids)`` answers the float32 logits at every position
(or at ``positions``). It runs layer by layer (jitted calls). With ``block``
the SAME functions are evaluated for ``block`` query rows at a time
(:func:`layer_rows`: a row sees all the keys below it either way).
``cdtbench/reference/llm_brumby_reference.py`` is a copy of this file
(``tests/test_llm_brumby.py`` holds the two equal).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope_angles(cfg, T: int):
    """``(cos, sin)`` [T, d/2] of ``t · θ^(−2i/d)``; float64 on the host,
    held float32."""
    half = cfg.head_dim // 2
    freqs = cfg.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = np.arange(T, dtype=np.float64)[:, None] * freqs
    return jnp.asarray(np.cos(angle), F32), jnp.asarray(np.sin(angle), F32)


def _rope(x, cos, sin):
    # ASSUMED (3): rope kept from the Qwen3-14B block, rotate-half on all of
    # the head (the row keeps ``rope_theta``; ``rope_scaling`` is null)
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _split(cfg, p, x, cos, sin):
    """q [T,H,d], k [T,G,d] (normed, roped), v [T,G,d], log γ [T,G]."""
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    T, eps = x.shape[0], cfg.rms_norm_eps
    y = x @ p["w_in"]
    # ASSUMED (2): per-head RMS norms on q and k, one weight for all heads
    q = _rms(y[:, :H * d].reshape(T, H, d), p["q_norm"], eps)
    k = _rms(y[:, H * d:(H + G) * d].reshape(T, G, d), p["k_norm"], eps)
    v = y[:, (H + G) * d:].reshape(T, G, d)
    # ASSUMED (4): one gate a K/V head, from the normed input, with a bias
    log_g = jax.nn.log_sigmoid(x @ p["w_gate"] + p["b_gate"])
    return _rope(q, cos, sin), _rope(k, cos, sin), v, log_g


@functools.partial(jax.jit, static_argnums=0)
def keys_values(cfg, layer, h, cos, sin):
    """What a layer keeps of the rows ``h`` [T,D_m]: k, v [T,G,d] and the
    log-gates [T,G] (their running sum is the caller's: it runs on from the
    rows before)."""
    with jax.default_matmul_precision("highest"):
        layer = _f32(layer)
        x = _rms(h, layer["norm1"], cfg.rms_norm_eps)
        _, k, v, log_g = _split(cfg, layer["attn"], x, cos, sin)
        return k, v, log_g


def retention(cfg, q, rows, k, v, b):
    """The function, as written: ``q`` [n,H,d] at positions ``rows`` [n]
    against ALL the kept ``k``, ``v`` [S,G,d] and running log-gates ``b``
    [S,G]. One K/V group at a time (a head's ``[n, S]`` matrix whole)."""
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    J = H // G
    seen = jnp.arange(k.shape[0])[None, :] <= rows[:, None]         # [n,S]
    out = []
    for g in range(G):
        # exp of a difference; a key past the query has weight 0 (its
        # difference is positive: masked BEFORE the exp)
        decay = jnp.exp(jnp.where(seen, b[rows, g][:, None] - b[None, :, g],
                                  -jnp.inf))
        for j in range(J):
            # ASSUMED (1): degree 2
            A = decay * (q[:, g * J + j] @ k[:, g].T) ** 2 / d
            # ASSUMED (5): no ε; (6): no output gate, no output norm
            out.append(A @ v[:, g] / A.sum(-1, keepdims=True))
    return jnp.stack(out, axis=1)                                   # [n,H,d]


def swiglu(ffn, x):
    gu = x @ ffn["w_gu"]
    F = gu.shape[-1] // 2
    return (jax.nn.silu(gu[:, :F]) * gu[:, F:]) @ ffn["w_down"]


@functools.partial(jax.jit, static_argnums=0)
def layer_rows(cfg, layer, h, rows, k, v, b, cos, sin):
    """The layer for the rows ``h`` [n,D_m] at positions ``rows`` against the
    kept ``k``, ``v``, ``b`` of every position (theirs among them)."""
    with jax.default_matmul_precision("highest"):
        layer = _f32(layer)
        x = _rms(h, layer["norm1"], cfg.rms_norm_eps)
        q, _, _, _ = _split(cfg, layer["attn"], x, cos, sin)
        o = retention(cfg, q, rows, k, v, b)
        h = h + o.reshape(h.shape[0], -1) @ layer["attn"]["w_o"]
        x = _rms(h, layer["norm2"], cfg.rms_norm_eps)
        return h + swiglu(layer["ffn"], x)


@functools.partial(jax.jit, static_argnums=0)
def head_forward(cfg, final_norm, head, h):
    with jax.default_matmul_precision("highest"):
        return _rms(h, final_norm.astype(F32), cfg.rms_norm_eps) \
            @ head.astype(F32).T


def embed(cfg, params, ids):
    return params["embed"][ids].astype(F32)


def forward(cfg, params, ids, positions=None, block: int | None = None):
    """Float32 logits ``[T, V]`` (or ``[len(positions), V]``) of the whole
    sequence ``ids``; ``block``: query rows a call of :func:`layer_rows`."""
    T = ids.shape[0]
    block = block or T
    cos, sin = rope_angles(cfg, T)
    h = embed(cfg, params, ids)
    for layer in params["layers"]:
        k, v, log_g = keys_values(cfg, layer, h, cos, sin)
        b = jnp.cumsum(log_g, axis=0)
        h = jnp.concatenate([layer_rows(
            cfg, layer, h[lo:lo + block], jnp.arange(lo, min(lo + block, T)),
            k, v, b, cos[lo:lo + block], sin[lo:lo + block])
            for lo in range(0, T, block)])
    if positions is not None:
        h = h[jnp.asarray(positions)]
    return head_forward(cfg, params["final_norm"], params["head"], h)
