"""The plain reference of ``models/llm_zaya.py``: the whole forward pass of
the cut stack in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — both convolutions as shifted
sums over the sequence, every query head against every key of its K/V head
under one masked softmax, the router's MLP and ONE dense product an expert
masked by the argmax; no cache, no chunks, no tails, no kernels, no
``expert_share``. It shares nothing with the served code but the layout of
the weight tree, and it is given the same experts (here: all of them, or the
``held`` range a share test cuts) and the whole vocabulary.

ASSUMED lines (the row's ``config.json`` gives sizes and key names, the two
public descriptions — CCA: arXiv:2510.04476; the router and the residual
scaling: arXiv:2511.17127 — the mechanisms; these are what neither settles,
listed in ``cdtbench/configs/zaya1-8b.json`` under ``assumed``): (1) the
temperature is one learned positive scalar a K/V head, stored as its
logarithm; (2) the L2 norm's epsilon, 1e-6, sits inside the root; (3) the
softmax scale: the logit of a pair is ``√d · q · k`` on unit vectors, times
the temperature; (4) which half of the value channels is the shifted one:
``[x[t] W_v1 | x[t−1] W_v2]`` viewed as K/V heads, so head 1 carries the
token before's; (5) the router's MLP is two hidden layers of
``router_hidden_size`` with bias and exact gelu and an output matrix without
bias; (6) the residual bias sits inside the scale, ``s ⊙ (· + b)``; (7) no
skip ("MoD") output: the row's ``config`` counts 16 experts and has no key
for one.

The equations (``D`` hidden, ``H`` heads over ``G`` K/V heads of ``d``, ``J
= H/G``; per token ``t``; ε = ``rms_norm_eps``):

* ``h ← s_h ⊙ (h + b_h) + s_y ⊙ (y + b_y)`` at both sublayers, ``y`` the
  sublayer's output of ``RMSNorm(h)``; ``logits = RMSNorm(h_L) Embᵀ``;
  ``h_0 = Emb[id]``.
* attention: ``z = [q̃ | k̃] = x W_qk``; ``c0[t] = a₀ ⊙ z[t−1] + a₁ ⊙ z[t] +
  b₀``; ``c1[t,n] = c0[t−1,n] B₀[n] + c0[t,n] B₁[n] + b₁[n]`` for each of
  the ``H + G`` heads ``n`` (``z[−1] = c0[−1] = 0``); ``q = c1_q + ½(q̄ +
  k̄_g)``, ``k = c1_k + ½(mean_{h∈g} q̄_h + k̄)`` with ``q̄``, ``k̄`` the
  UNconvolved latents as heads; ``q ← q/‖q‖``, ``k ← τ_g k/‖k‖``; rope on
  the first ``d · partial_rotary_factor`` dimensions (rotate-half pairs
  ``(i, i + rot/2)``, angles ``t · θ^(−2i/rot)`` made in float64 on the
  host); ``v[t] = [x[t] W_v1 | x[t−1] W_v2]`` as ``G`` heads; ``o[t,h] =
  Σ_{s≤t} softmax_s(√d q[t,h] · k[s,g]) v[s,g]``; ``y = o W_o``.
* experts: ``r = x W_d + b_d`` (``+ γ ⊙ r`` of the layer before, after its
  own add, for every layer but the first); ``u = RMSNorm(r)``; ``logits = W₃
  gelu(W₂ gelu(W₁ u + b₁) + b₂)``; ``p = softmax(logits)``; ``e* = argmax(p +
  β)``; ``y = p[e*] · (silu(x W_g[e*]) ⊙ x W_u[e*]) W_down[e*]``.

``forward(cfg, params, ids)`` answers the float32 logits at every position
(or at ``positions``). It runs layer by layer (jitted calls). With ``block``
the SAME functions are evaluated for ``block`` query rows at a time
(:func:`layer_rows`: a row sees all the keys below it either way; a block
is handed the two rows ahead of it, ``lead``, which the convolutions reach
back to). ``cdtbench/reference/llm_zaya_reference.py`` is a copy of this
file (``tests/test_llm_zaya.py`` holds the two equal).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
REACH = 2       # rows the two convolutions reach back: (2 − 1) + (2 − 1)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope_angles(cfg, T: int):
    """``(cos, sin)`` [T, rot/2] of ``t · θ^(−2i/rot)``, ``rot`` the roped
    part of a head; float64 on the host, held float32."""
    half = int(cfg.head_dim * cfg.partial_rotary_factor) // 2
    freqs = cfg.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = np.arange(T, dtype=np.float64)[:, None] * freqs
    return jnp.asarray(np.cos(angle), F32), jnp.asarray(np.sin(angle), F32)


def _rope(x, cos, sin):
    """The first ``2 · cos.shape[1]`` dimensions of ``x`` [T,heads,d] turn
    (``[x₁ | x₂] → [x₁ cos − x₂ sin | x₂ cos + x₁ sin]``), the rest pass."""
    half = cos.shape[1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _before(a):
    """``a[t−1]`` at row ``t``; nothing (zeros) ahead of the first row."""
    return jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]], axis=0)


def mixed(cfg, p, x, cos, sin, lead: int):
    """Steps 1–7 for consecutive rows ``x`` [lead + n, D] (normed), the
    first ``lead`` of them context only (a sequence's own first rows have
    ``lead`` 0: nothing is ahead of them): q [n,H,d], k [n,G,d] (normed,
    tempered, roped with the ``n`` rows' angles), v [n,G,d]."""
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    T, J = x.shape[0], cfg.num_attention_heads // cfg.num_key_value_heads
    z = x @ p["w_qk"]
    c0 = p["conv0_w"][0] * _before(z) + p["conv0_w"][1] * z + p["conv0_b"]

    def per_head(a, tap):
        return jnp.einsum("tnc,ncd->tnd", a.reshape(T, H + G, d),
                          p["conv1_w"][tap])

    c1 = per_head(_before(c0), 0) + per_head(c0, 1) \
        + p["conv1_b"].reshape(H + G, d)
    q_lat = z[:, :H * d].reshape(T, H, d)
    k_lat = z[:, H * d:].reshape(T, G, d)
    q = c1[:, :H] + 0.5 * (q_lat + jnp.repeat(k_lat, J, axis=1))
    k = c1[:, H:] + 0.5 * (q_lat.reshape(T, G, J, d).mean(2) + k_lat)
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + cfg.qk_norm_eps)
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + cfg.qk_norm_eps) \
        * jnp.exp(p["log_temp"])[:, None]
    vv = x @ p["w_v"]
    v = jnp.concatenate([vv[:, :d], _before(vv[:, d:])], 1).reshape(T, G, d)
    return _rope(q[lead:], cos, sin), _rope(k[lead:], cos, sin), v[lead:]


@functools.partial(jax.jit, static_argnums=(0, 5))
def keys_values(cfg, layer, x, cos, sin, lead: int = 0):
    """What every query of a layer reads of the rows ``x`` [lead + n, D] of
    the stream (the first ``lead`` context only): keys and values
    [n,G,d]."""
    with jax.default_matmul_precision("highest"):
        normed = _rms(x, layer["norm1"].astype(F32), cfg.rms_norm_eps)
        return mixed(cfg, _f32(layer["attn"]), normed, cos, sin, lead)[1:]


def attention(cfg, p, q, rows, k, v):
    """``q`` [n,H,d] at positions ``rows`` over the sequence's keys and
    values [T,G,d]: one masked softmax a head; [n, H·d]."""
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    seen = rows[:, None] >= jnp.arange(k.shape[0])[None, :]

    def head(args):
        qh, g = args
        s = (qh @ k[:, g].T) * math.sqrt(d)
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ v[:, g]

    o = jax.lax.map(head, (jnp.swapaxes(q, 0, 1), jnp.arange(H) // (H // G)))
    return jnp.swapaxes(o, 0, 1).reshape(q.shape[0], H * d) @ p["w_o"]


def router(cfg, p, x, before):
    """``(p [n,E], state r [n,R])``; ``before`` None for the first layer."""
    r = x @ p["w_down"] + p["b_down"]
    if before is not None:
        r = r + p["eda"] * before
    u = _rms(r, p["norm"], cfg.rms_norm_eps)
    a = jax.nn.gelu(u @ p["w1"] + p["b1"], approximate=False)
    a = jax.nn.gelu(a @ p["w2"] + p["b2"], approximate=False)
    return jax.nn.softmax(a @ p["w3"], axis=-1), r


def experts(cfg, m, prob, bias, x, held):
    """The ``held`` = (first, count) experts' part: each of them on every
    token (one at a time), masked by the argmax of ``prob + bias`` and
    weighted by ``prob`` itself; and how many tokens chose a held expert."""
    chosen = jnp.argmax(prob + bias, axis=-1)
    first, count = held
    weight = jnp.where(chosen[:, None] == jnp.arange(prob.shape[1]), prob,
                       0.0)[:, first:first + count]

    def one(out, args):
        w_gu, w_down, w_e = args
        g, u = jnp.split(x @ w_gu.astype(F32), 2, axis=-1)
        return out + w_e[:, None] * ((jax.nn.silu(g) * u)
                                     @ w_down.astype(F32)), None

    out, _ = jax.lax.scan(one, jnp.zeros(x.shape, F32),
                          (m["e_gu"][first:first + count],
                           m["e_down"][first:first + count], weight.T))
    return out, ((chosen >= first) & (chosen < first + count)).sum()


def _merge(h, y, p):
    return p["s_h"] * (h + p["b_h"]) + p["s_y"] * (y + p["b_y"])


@functools.partial(jax.jit, static_argnums=(0, 3, 10))
def layer_rows(cfg, layer, x, lead: int, rows, k, v, cos, sin, before,
               held):
    """One whole layer for the ``n`` rows at positions ``rows``: ``x``
    [lead + n, D] (the stream's rows, ``lead`` of context first), over the
    sequence's ``k``, ``v`` (:func:`keys_values`); ``before`` [n,R] the
    router state of the layer before (None: the first). Answers ``(stream
    [n,D], router state [n,R], tokens that chose a held expert)``."""
    with jax.default_matmul_precision("highest"):
        p = _f32({key: layer[key] for key in
                  ("attn", "router", "res_attn", "res_moe")})
        normed = _rms(x, layer["norm1"].astype(F32), cfg.rms_norm_eps)
        q = mixed(cfg, p["attn"], normed, cos, sin, lead)[0]
        h = _merge(x[lead:], attention(cfg, p["attn"], q, rows, k, v),
                   p["res_attn"])
        normed = _rms(h, layer["norm2"].astype(F32), cfg.rms_norm_eps)
        prob, state = router(cfg, p["router"], normed, before)
        y, n_held = experts(cfg, layer["moe"], prob, p["router"]["bias"],
                            normed, held)
        return _merge(h, y, p["res_moe"]), state, n_held


@functools.partial(jax.jit, static_argnums=0)
def head_forward(cfg, final_norm, embed, h):
    """The final norm and the TIED head: the embedding once more."""
    with jax.default_matmul_precision("highest"):
        x = _rms(h, final_norm.astype(F32), cfg.rms_norm_eps)
        return x @ embed.astype(F32).T


def forward(cfg, params, ids, positions=None, block: int | None = None,
            held=None):
    """Float32 logits [T,V] (or at ``positions`` only) for the whole
    sequence ``ids`` [T], and per layer the tokens that chose one of the
    ``held`` = (first, count) experts (None: all of them)."""
    T = ids.shape[0]
    block = T if block is None else max(block, REACH)
    held = (0, cfg.router_experts) if held is None else held
    cos, sin = rope_angles(cfg, T)
    x = params["embed"][ids].astype(F32)
    state, counts = None, []
    for layer in params["layers"]:
        k, v = keys_values(cfg, layer, x, cos, sin)
        parts = []
        for lo in range(0, T, block):
            n, lead = min(block, T - lo), min(lo, REACH)
            parts.append(layer_rows(
                cfg, layer, x[lo - lead:lo + n], lead, lo + jnp.arange(n),
                k, v, cos[lo:lo + n], sin[lo:lo + n],
                None if state is None else state[lo:lo + n], held))
        x = jnp.concatenate([part[0] for part in parts])
        state = jnp.concatenate([part[1] for part in parts])
        counts.append(sum(part[2] for part in parts))
    if positions is not None:
        x = x[jnp.asarray(positions)]
    return head_forward(cfg, params["final_norm"], params["embed"], x), counts
