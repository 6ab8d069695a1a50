"""Causal attention of many query heads over ONE key/value head, through a
cache: the two attention layers of a state-space hybrid.

``q`` has ``H`` heads of ``d``; the cache holds one ``k`` and one ``v`` row
of ``d`` a token, shared by every head (multi-query attention); there is no
positional encoding, so a row of the cache is ``x W_k`` as written.

- :func:`causal_chunk` — a prefill chunk of ``C`` queries at positions
  ``start .. start+C−1`` against a cache that already holds the chunk's own
  rows. On a TPU the blocked kernel of ``ops/flash_latent.py``
  (``shared_kv_causal_mha``: K and V tiles that ignore the head index, the
  moving-diagonal schedule of the latent kernel): nothing ``C × S`` exists.
  Elsewhere the masked softmax over the cache, plainly (``lax``): what the
  kernel is held to.
- :func:`step` — one decoded token: one XLA step over the cache, rows past
  ``pos`` masked.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import flash_attention
from .flash_attention import NEG_INF


def _masked_softmax_rows(q, k, v, positions, dtype):
    """``q`` [C,H,d] (scaled), ``k``, ``v`` [S,d], ``positions`` [C]: every
    query over the rows ``≤`` its position; float32 [C,H,d]."""
    s = jnp.einsum("chd,sd->chs", q.astype(dtype), k.astype(dtype),
                   preferred_element_type=jnp.float32)
    seen = jnp.arange(k.shape[0])[None, :] <= positions[:, None]
    p = jax.nn.softmax(jnp.where(seen[:, None, :], s, NEG_INF), axis=-1)
    return jnp.einsum("chs,sd->chd", p.astype(dtype), v.astype(dtype),
                      preferred_element_type=jnp.float32)


def causal_chunk(q, k_cache, v_cache, start, scale: float, dtype,
                 block_q: int, block_k: int, kernel: str | None = None):
    """``q`` [C,H,d] at positions ``start ..``; ``k_cache``, ``v_cache``
    [S,d] with the chunk's rows written. ``kernel``: ``pallas`` (the
    default on a TPU), ``interpret`` (the same kernel in the Pallas
    interpreter) or ``lax`` (the default elsewhere). Answers [C,H,d] in
    ``dtype``."""
    from . import flash_latent

    C, H, d = q.shape
    S = k_cache.shape[0]
    if kernel is None:
        kernel = "pallas" if flash_attention._platform() == "tpu" else "lax"
    q = (q * scale).astype(dtype)
    if kernel == "lax":
        return _masked_softmax_rows(q, k_cache, v_cache,
                                    start + jnp.arange(C), dtype).astype(dtype)
    bq = math.gcd(C, block_q)
    S_pad = -(-S // block_k) * block_k
    k_pad = jnp.pad(k_cache, ((0, S_pad - S), (0, 0))).astype(dtype)
    v_pad = jnp.pad(v_cache, ((0, S_pad - S), (0, 0))).astype(dtype)
    if kernel == "pallas":
        from .attention import note_shared_kv_causal

        note_shared_kv_causal(H, d, C, S_pad, dtype, bq, block_k)
    o = flash_latent.shared_kv_causal_mha(
        q.reshape(C, H * d), k_pad, v_pad, start, num_heads=H, block_q=bq,
        block_k=block_k, interpret=kernel == "interpret")
    return o.reshape(C, H, d)


def step(q, k_cache, v_cache, pos, scale: float, dtype):
    """One token's ``q`` [H,d] at position ``pos`` over the cache rows ``≤
    pos`` (its own row written); float32 [H,d]."""
    return _masked_softmax_rows((q * scale)[None], k_cache, v_cache,
                                jnp.reshape(pos, (1,)), dtype)[0]
