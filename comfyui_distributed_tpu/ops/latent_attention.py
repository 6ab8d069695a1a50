"""Multi-head latent attention (MLA) in its two forms.

The layer compresses keys and values into one latent ``c`` [512] a token
plus one rotary key ``k_rope`` [64] shared by the heads, and decompresses
per head with ``W_b``: ``[k_nope | v]_h = W_b,h c``. Prefill decompresses
and runs plain causal attention (:func:`mla_naive`). Decode never
decompresses: ``q_nope · (W_uk c) = (W_ukᵀ q_nope) · c`` and ``Σ p_t (W_uv
c_t) = W_uv Σ p_t c_t``, so a step reads the 576 cached values a token and
``W_b`` once (:func:`mla_absorbed_step`). The two must agree
(``tests/test_llm_hybrid.py``). Softmax is float32 in both.

Grouped differential latent attention (GDLA) is the same compression with
``G`` key/value groups decompressed from the latent instead of one per
query head. A group serves ``J − 1`` signal heads and one noise head, and
a signal head's output is its attention less a gate ``λ`` times its
group's noise head's: ``o_s = A_s − λ_s A_noise``. Layers attend over
everything or over a window of the last ``W`` tokens. Prefill
(:func:`gdla_naive`) walks the queries in blocks, so a window layer never
forms a ``T×T`` score matrix; decode (:func:`gdla_absorbed_step`) subtracts
ON THE LATENT — a group's heads share ``W_uv`` — and decompresses the
difference once (``tests/test_llm_motif.py`` holds the two equal).
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


def gdla_naive(q_nope, q_rope, c, k_rope, w_uk, w_uv, lam, scale: float,
               dtype, window: int | None = None, block: int = 128):
    """``q_nope`` [T,G,J,nope], ``q_rope`` [T,G,J,r] (roped; a group's
    noise head is its last), ``c`` [T,rank], ``k_rope`` [T,r] (roped),
    ``w_uk`` [rank,G,nope], ``w_uv`` [rank,G,v], ``lam`` [T,G,J−1].
    Position ``t`` sees ``j ≤ t``, and ``j > t − window`` where there is
    a window. Answers the signal heads' outputs [T,G,J−1,v]."""
    T, G, J, nope = q_nope.shape
    B = window if window is not None else min(block, T)
    pad = -T % B
    lead = B if window is not None else 0      # a block sees the one before

    def padded(a, front=0):
        return jnp.pad(a, ((front, pad),) + ((0, 0),) * (a.ndim - 1))

    k_nope = jnp.einsum("tc,cgd->tgd", c.astype(dtype), w_uk.astype(dtype),
                        preferred_element_type=jnp.float32).astype(dtype)
    v = jnp.einsum("tc,cgv->tgv", c.astype(dtype), w_uv.astype(dtype),
                   preferred_element_type=jnp.float32).astype(dtype)
    q_nope, q_rope = padded(q_nope.astype(dtype)), padded(q_rope.astype(dtype))
    k_nope, v, k_rope = (padded(a, lead)
                         for a in (k_nope, v, k_rope.astype(dtype)))
    span = 2 * B if window is not None else T + pad

    def one_block(b):
        def rows(a, start, n):
            return jax.lax.dynamic_slice_in_dim(a, start, n, 0)

        start = b * B if window is not None else 0
        kn, kr, vv = (rows(a, start, span) for a in (k_nope, k_rope, v))
        s = (jnp.einsum("tgjd,sgd->gjts", rows(q_nope, b * B, B), kn,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("tgjr,sr->gjts", rows(q_rope, b * B, B), kr,
                          preferred_element_type=jnp.float32)) * scale
        t = b * B + jnp.arange(B)[:, None]
        j = start - lead + jnp.arange(span)[None, :]
        seen = (j <= t) & (j >= 0)
        if window is not None:
            seen &= j > t - window
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("gjts,sgv->tgjv", p.astype(dtype), vv,
                          preferred_element_type=jnp.float32)

    a = jax.lax.map(one_block, jnp.arange((T + pad) // B))
    a = a.reshape(T + pad, G, J, -1)[:T]
    return a[:, :, :J - 1] - lam[..., None] * a[:, :, J - 1:]


def gdla_absorbed_step(q_nope, q_rope, c_cache, kr_cache, valid, w_uk, w_uv,
                       lam, scale: float, dtype):
    """One token against a latent cache (a window layer's ring or a full
    layer's buffer: ``valid`` [S] says which rows hold a visible
    position), ``W_uk`` absorbed into the query. ``q_nope`` [G,J,nope],
    ``q_rope`` [G,J,r] (roped), ``lam`` [G,J−1]. The noise head's context
    is subtracted in the latent, then ``W_uv`` decompresses the
    difference. Answers [G,J−1,v]."""
    J = q_nope.shape[1]
    q_c = jnp.einsum("gjd,cgd->gjc", q_nope.astype(dtype), w_uk.astype(dtype),
                     preferred_element_type=jnp.float32)
    s = (jnp.einsum("gjc,tc->gjt", q_c.astype(dtype), c_cache.astype(dtype),
                    preferred_element_type=jnp.float32)
         + jnp.einsum("gjr,tr->gjt", q_rope.astype(dtype),
                      kr_cache.astype(dtype),
                      preferred_element_type=jnp.float32)) * scale
    p = jax.nn.softmax(jnp.where(valid, s, -jnp.inf), axis=-1)
    ctx = jnp.einsum("gjt,tc->gjc", p.astype(dtype), c_cache.astype(dtype),
                     preferred_element_type=jnp.float32)
    diff = ctx[:, :J - 1] - lam[..., None] * ctx[:, J - 1:]
    return jnp.einsum("gjc,cgv->gjv", diff.astype(dtype), w_uv.astype(dtype),
                      preferred_element_type=jnp.float32)
