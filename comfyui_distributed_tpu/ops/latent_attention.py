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

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry.device_scopes import device_scoped


def yarn_frequencies(d: int, theta: float, factor: float,
                     original_len: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0):
    """YaRN's frequency table for ``d`` rotary dimensions, float32 [d/2]:
    ``f_i = theta^(−2i/d)`` blended per frequency with ``f_i / factor``.
    ``corr(n) = d · ln(L₀ / (2π n)) / (2 ln theta)`` is the dimension that
    turns ``n`` times over the original length ``L₀``; dimensions below
    ``⌊corr(beta_fast)⌋`` keep their frequency, those above
    ``⌈corr(beta_slow)⌉`` are divided by ``factor``, a linear ramp between.
    Cos and sin are not scaled here (the softmax scale carries
    ``mscale²``: :func:`yarn_mscale`)."""
    def corr(n):
        return d * math.log(original_len / (2 * math.pi * n)) \
            / (2 * math.log(theta))

    low = min(max(math.floor(corr(beta_fast)), 0), d - 1)
    high = min(max(math.ceil(corr(beta_slow)), 0), d - 1)
    i = np.arange(d // 2, dtype=np.float64)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    freq = theta ** (-2.0 * i / d)
    return (freq * ((1.0 - ramp) + ramp / factor)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """``0.1 · mscale · ln(factor) + 1``: the softmax scale is multiplied
    by its square where ``mscale_all_dim`` is set."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_interleaved(x, positions, theta: float, freqs=None):
    """Rotate pairs ``(x[2i], x[2i+1])`` of the last axis by ``pos ·
    theta^(−2i/d)``, or by ``pos · freqs[i]`` where a table is given
    (:func:`yarn_frequencies`). ``x`` [T,...,d], ``positions`` [T]."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d) \
        if freqs is None else jnp.asarray(freqs, jnp.float32)
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
    """One token against the latent cache, ``W_b`` (2-D as stored, or in
    :func:`absorbed_form`) absorbed into query and output. ``q_nope``
    [H,nope], ``q_rope`` [H,r] (roped), ``c_cache`` [Tmax,rank], ``kr_cache``
    [Tmax,r] already hold ``pos``; rows past it are masked. Answers [H,v]."""
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


# --- prefill through the cache: blocked causal attention --------------------

# large-but-finite, as the flash kernels': −inf breaks max on a masked tile
_NEG = -1e30


def causal_blocked_lax(q_nope, q_rope, kv, k_rope, start, nope: int, dtype,
                       block_q: int, block_k: int):
    """The blocked causal schedule in ``jax.lax``: q blocks walked by
    ``lax.map``, each over the K blocks its rows can see (a loop whose
    count is the block's own), online softmax in float32. ``q_nope``
    [C,H,nope], ``q_rope`` [C,H,r] (roped), both already times the
    scale; ``kv`` [S,H,nope+v] decompressed rows; ``k_rope`` [S,r];
    ``C % block_q == 0``, ``S % block_k == 0``. The emulated path of
    ``flash_latent.latent_causal_mha`` (same blocks, same mask, same
    accumulation) and its rival on the chip. Answers [C,H,v]."""
    C, H, _ = q_nope.shape
    S, v = kv.shape[0], kv.shape[-1] - nope
    q_nope, q_rope = q_nope.astype(dtype), q_rope.astype(dtype)

    def rows(a, at, n):
        return jax.lax.dynamic_slice_in_dim(a, at, n, 0)

    def one_block(i):
        qn, qr = rows(q_nope, i * block_q, block_q), \
            rows(q_rope, i * block_q, block_q)
        row = start + i * block_q + jnp.arange(block_q)
        last = jnp.minimum((start + (i + 1) * block_q - 1) // block_k,
                           S // block_k - 1)

        def step(j, carry):
            m, l, acc = carry
            kvb, krb = rows(kv, j * block_k, block_k), \
                rows(k_rope, j * block_k, block_k)
            s = (jnp.einsum("thd,shd->hts", qn, kvb[..., :nope],
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("thr,sr->hts", qr, krb.astype(dtype),
                              preferred_element_type=jnp.float32))
            col = j * block_k + jnp.arange(block_k)
            s = jnp.where(col[None, None, :] <= row[None, :, None], s, _NEG)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            acc = acc * corr[..., None] + jnp.einsum(
                "hts,shv->htv", p.astype(dtype), kvb[..., nope:],
                preferred_element_type=jnp.float32)
            return m_new, l * corr + p.sum(-1), acc

        init = (jnp.full((H, block_q), _NEG, jnp.float32),
                jnp.zeros((H, block_q), jnp.float32),
                jnp.zeros((H, block_q, v), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, last + 1, step, init)
        return jnp.swapaxes(acc / l[..., None], 0, 1)        # [bq,H,v]

    out = jax.lax.map(one_block, jnp.arange(C // block_q))
    return out.reshape(C, H, v)


@device_scoped("llm_attn")
def mla_chunk_attention(q_nope, q_rope, c_cache, kr_cache, start, w_b,
                        scale: float, dtype, block_q: int, block_k: int,
                        kernel: str | None = None):
    """A chunk of ``C`` queries at positions ``start .. start+C−1``
    against a latent cache that already holds the chunk's own rows:
    position ``t`` sees cache rows ``j ≤ t``. ``q_nope`` [C,H,nope],
    ``q_rope`` [C,H,r] (roped), ``c_cache`` [S,rank], ``kr_cache`` [S,r].

    The rows ``< start+C`` are decompressed (``c W_b``: a head's ``[k_nope
    | v]``) into a workspace, ``C`` rows at a time and only as many as the
    chunk can see — what continuing from a cache costs over a whole-prompt
    pass — and the chunk runs blocked causal attention over it: nothing
    ``C × S`` exists. ``kernel``: ``pallas`` (``ops/flash_latent.py``: the
    default on a TPU where ``nope == v``), ``interpret`` (the same kernel
    in the Pallas interpreter) or ``lax`` (:func:`causal_blocked_lax`: the
    default elsewhere). Answers [C,H,v] in ``dtype``."""
    from . import flash_latent
    from .flash_attention import _platform

    C, H, nope = q_nope.shape
    S, rank = c_cache.shape
    v = w_b.shape[1] // H - nope
    if kernel is None:
        kernel = "pallas" if _platform() == "tpu" and nope == v else "lax"
    bq = math.gcd(C, block_q)
    step = math.lcm(C, block_k)
    S_pad = -(-S // step) * step
    c_pad = jnp.pad(c_cache, ((0, S_pad - S), (0, 0)))
    kr_pad = jnp.pad(kr_cache, ((0, S_pad - S), (0, 0))).astype(dtype)

    def fill(j, kv):
        rows = jax.lax.dynamic_slice_in_dim(c_pad, j * C, C, 0)
        return jax.lax.dynamic_update_slice_in_dim(
            kv, jnp.dot(rows.astype(dtype), w_b.astype(dtype),
                        preferred_element_type=jnp.float32).astype(dtype),
            j * C, 0)

    n_fill = jnp.minimum((start + 2 * C - 1) // C, S_pad // C)
    kv = jax.lax.fori_loop(0, n_fill, fill,
                           jnp.zeros((S_pad, w_b.shape[1]), dtype))
    q_nope = (q_nope * scale).astype(dtype)
    q_rope = (q_rope * scale).astype(dtype)
    if kernel == "lax":
        return causal_blocked_lax(q_nope, q_rope, kv.reshape(S_pad, H, -1),
                                  kr_pad, start, nope, dtype, bq,
                                  block_k).astype(dtype)
    if kernel == "pallas":
        from .attention import note_causal

        note_causal("latent_causal", H, nope + q_rope.shape[-1], C, S_pad,
                    dtype, bq, block_k)
    o = flash_latent.latent_causal_mha(
        q_nope.reshape(C, H * nope), jnp.swapaxes(q_rope, 0, 1), kv,
        kr_pad, start, num_heads=H, block_q=bq, block_k=block_k,
        interpret=kernel == "interpret")
    return o.reshape(C, H, v)


def absorbed_form(w_b, num_heads: int):
    """``W_b`` [rank, H·(nope+v)] as :func:`mla_absorbed_step` reads it:
    ``[rank, H, nope+v]``. The stored leaf is tiled eight RANK rows by 128
    columns and the step's two products want eight HEADS by 128, so the
    reshape moves every byte. Inside a token loop the compiler re-tiles
    the matrix at every token (16.8 MB read and written a sublayer at 64
    heads: PERF.md §6, PR 45); a program that loops over steps calls this
    ONCE ahead of its loop — the barrier keeps the reshape there — and the
    loop's body reads the copy. Prefill reads the leaf as it lies."""
    return jax.lax.optimization_barrier(
        w_b.reshape(w_b.shape[0], num_heads, -1))
