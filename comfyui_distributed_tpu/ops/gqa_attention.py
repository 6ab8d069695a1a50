"""Causal attention of GROUPS of query heads over a key/value head each
(grouped-query attention), through a cache, whole or over a window.

``q`` has ``H`` heads of ``d``; the cache holds ``G`` key and ``G`` value
heads a token, ``[G, S, d]``; query head ``h`` reads head ``h // (H / G)``
(``G`` = 1: every head over ONE shared key/value head, a state-space
hybrid's attention layers). Positional encoding is the caller's (the rows
come roped, or not at all).

- :func:`causal_chunk` — a prefill chunk of ``C`` queries at positions
  ``start .. start+C−1`` against rows that already hold the chunk's own
  keys. ``window`` None: every row ``≤`` a query's position (a full layer
  over its buffer). ``window`` W: the ``W`` rows up to its own — the caller
  hands the rows in position order, ``[the ring as the last chunk left it ;
  the chunk's own]``, ``start`` the first query's row among THEM, and
  ``lowest`` the first row that holds a key at all (the ring is empty
  before the first chunk). On a TPU the blocked kernel of
  ``ops/flash_latent.py`` (K and V tiles indexed by the group, blocks
  outside the band skipped), under the name of what it serves —
  ``gqa_window_mha`` a band, ``shared_kv_causal_mha`` one shared head,
  ``gqa_causal_mha`` the rest (the ``attention:`` line says the tile and
  the rows of it a step takes at a time: ``2048/2048/128``): nothing ``C
  × S`` exists. Elsewhere the masked softmax, plainly (``lax``).
- :func:`step` — one decoded token: one XLA step over a ring or a buffer,
  the rows that hold no key yet masked.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import flash_attention
from .flash_attention import NEG_INF


def _masked_softmax_rows(q, k, v, seen, dtype):
    """``q`` [C,H,d] (scaled), ``k``, ``v`` [G,S,d], ``seen`` [C,S]: every
    query over the rows ``seen`` gives it; float32 [C,H,d]. A group's
    queries are stacked ``[G, C·H/G, d]`` so that both products are plain
    batched ones over the group (the batch axis leading on every side)."""
    C, H, d = q.shape
    G = k.shape[0]
    J = H // G
    stacked = jnp.swapaxes(q.reshape(C, G, J, d), 0, 1).reshape(G, C * J, d)
    s = jnp.einsum("gmd,gsd->gms", stacked.astype(dtype), k.astype(dtype),
                   preferred_element_type=jnp.float32)
    p = jax.nn.softmax(
        jnp.where(jnp.repeat(seen, J, axis=0), s, NEG_INF), axis=-1)
    o = jnp.einsum("gms,gsd->gmd", p.astype(dtype), v.astype(dtype),
                   preferred_element_type=jnp.float32)
    return jnp.swapaxes(o.reshape(G, C, J, d), 0, 1).reshape(C, H, d)


def causal_chunk(q, k, v, start, scale: float, dtype, block_q: int,
                 block_k: int, window: int | None = None, lowest=0,
                 kernel: str | None = None):
    """``q`` [C,H,d] at rows ``start ..`` of ``k``, ``v`` [G,S,d] (the
    chunk's own rows written). ``kernel``: ``pallas`` (the default on a
    TPU), ``interpret`` (the same kernel in the Pallas interpreter) or
    ``lax`` (the default elsewhere). Answers [C,H,d] in ``dtype``."""
    from . import flash_latent

    C, H, d = q.shape
    S = k.shape[1]
    if kernel is None:
        kernel = "pallas" if flash_attention._platform() == "tpu" else "lax"
    q = (q * scale).astype(dtype)
    if kernel == "lax":
        row = (start + jnp.arange(C))[:, None]
        col = jnp.arange(S)[None, :]
        seen = (col <= row) & (col >= lowest)
        if window is not None:
            seen &= col > row - window
        return _masked_softmax_rows(q, k, v, seen, dtype).astype(dtype)
    bq, bk = math.gcd(C, block_q), block_k
    pad = -S % bk       # none where the caller sized its rows to the block
    if pad:
        k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (k, v))
    tier = ("gqa_window" if window is not None
            else "shared_kv_causal" if k.shape[0] == 1 else "gqa_causal")
    if kernel == "pallas":
        from .attention import note_causal
        note_causal(tier, H, d, C, S + pad, dtype, bq, bk,
                    flash_latent.step_rows(bq))
    blocks = dict(num_heads=H, block_q=bq, block_k=bk,
                  interpret=kernel == "interpret")
    q, k, v = q.reshape(C, H * d), k.astype(dtype), v.astype(dtype)
    if tier == "gqa_window":
        o = flash_latent.gqa_window_mha(q, k, v, start, lowest,
                                        window=window, **blocks)
    elif tier == "shared_kv_causal":
        o = flash_latent.shared_kv_causal_mha(q, k[0], v[0], start, **blocks)
    else:
        o = flash_latent.gqa_causal_mha(q, k, v, start, **blocks)
    return o.reshape(C, H, d)


def step(q, k, v, valid, scale: float, dtype):
    """One token's ``q`` [H,d] over the rows of ``k``, ``v`` [G,S,d] that
    ``valid`` [S] says hold a key it sees (its own row written); float32
    [H,d]."""
    return _masked_softmax_rows((q * scale)[None], k, v, valid[None],
                                dtype)[0]
