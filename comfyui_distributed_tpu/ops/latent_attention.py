"""Multi-head latent attention (MLA) in its two forms.

The layer compresses keys and values into one latent ``c`` [512] a token
plus one rotary key ``k_rope`` [64] shared by the heads, and decompresses
per head with ``W_b``: ``[k_nope | v]_h = W_b,h c``. Prefill decompresses
and runs plain causal attention (:func:`mla_naive`). Decode never
decompresses: ``q_nope · (W_uk c) = (W_ukᵀ q_nope) · c`` and ``Σ p_t (W_uv
c_t) = W_uv Σ p_t c_t``, so a step reads the 576 cached values a token and
``W_b`` once (:func:`mla_absorbed_step`). The two must agree
(``tests/test_llm_hybrid.py``). Softmax is float32 in both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rope_interleaved(x, positions, theta: float):
    """Rotate pairs ``(x[2i], x[2i+1])`` of the last axis by ``pos ·
    theta^(−2i/d)``. ``x`` [T,...,d], ``positions`` [T]."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freq           # [T,d/2]
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), d // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def mla_naive(q_nope, q_rope, c, k_rope, w_b, scale: float, dtype):
    """Causal attention over decompressed keys and values. ``q_nope``
    [T,H,nope], ``q_rope`` [T,H,r] (roped), ``c`` [T,rank], ``k_rope``
    [T,r] (roped). Answers [T,H,v]."""
    T, H, nope = q_nope.shape
    kv = jnp.dot(c.astype(dtype), w_b.astype(dtype),
                 preferred_element_type=jnp.float32).reshape(T, H, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    s = (jnp.einsum("thd,shd->hts", q_nope.astype(dtype),
                    k_nope.astype(dtype),
                    preferred_element_type=jnp.float32)
         + jnp.einsum("thr,sr->hts", q_rope.astype(dtype),
                      k_rope.astype(dtype),
                      preferred_element_type=jnp.float32)) * scale
    t = jnp.arange(T)
    s = jnp.where(t[:, None] >= t[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hts,shv->thv", p.astype(dtype), v.astype(dtype),
                      preferred_element_type=jnp.float32)


def mla_absorbed_step(q_nope, q_rope, c_cache, kr_cache, pos, w_b,
                      scale: float, dtype):
    """One token against the latent cache, ``W_b`` absorbed into the
    query and the output. ``q_nope`` [H,nope], ``q_rope`` [H,r] (roped),
    ``c_cache`` [Tmax,rank], ``kr_cache`` [Tmax,r], both already holding
    position ``pos``; rows past ``pos`` are masked. Answers [H,v]."""
    H, nope = q_nope.shape
    w = w_b.reshape(w_b.shape[0], H, -1).astype(dtype)   # [rank,H,nope+v]
    q_c = jnp.einsum("hd,chd->hc", q_nope.astype(dtype), w[..., :nope],
                     preferred_element_type=jnp.float32)
    s = (jnp.einsum("hc,tc->ht", q_c.astype(dtype), c_cache.astype(dtype),
                    preferred_element_type=jnp.float32)
         + jnp.einsum("hr,tr->ht", q_rope.astype(dtype),
                      kr_cache.astype(dtype),
                      preferred_element_type=jnp.float32)) * scale
    s = jnp.where(jnp.arange(c_cache.shape[0])[None, :] <= pos, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("ht,tc->hc", p.astype(dtype), c_cache.astype(dtype),
                     preferred_element_type=jnp.float32)
    return jnp.einsum("hc,chv->hv", ctx.astype(dtype), w[..., nope:],
                      preferred_element_type=jnp.float32)
