"""Grouped-query causal attention whose KEYS are wider than its VALUES, with
a learned SINK in a window layer's softmax, through a cache.

``q`` has ``H`` heads of ``dk``; the cache holds ``G`` key heads of ``dk``
and ``G`` value heads of ``dv`` a token (``[G, S, dk]``, ``[G, S, dv]``);
query head ``h`` reads head ``h // (H / G)``; the output is ``dv`` wide.
The sink ``b`` [H] is one logit a head that joins the softmax's denominator
and adds nothing to the output: ``p_j = exp(s_j) / (exp(b_h) + Σ exp(s))``.
Positional encoding is the caller's. ``ops/gqa_attention.py`` is the same
attention at one width and without a sink; what is here that it lacks:

- :func:`causal_chunk` — a prefill chunk of ``C`` queries at rows ``start
  ..`` of a buffer that already holds the chunk's own keys, every row ``≤``
  a query's position. On a TPU ``flash_latent._gqa_kernel`` — the BODY the
  one-width callers run, rows parts and traced K extent and all — under a
  call of its own, ``gqa_wide_causal_mha``: the queries come head-major
  ``[H, C, dk]``, a tile's last dimension the whole key width (192 is no
  multiple of the 128 lanes, so a column block of ``[C, H·dk]`` is no legal
  tile), the output ``[C, H·dv]``. Elsewhere the masked softmax, plainly.
- :func:`band_chunk` — a WINDOW layer's chunk against ``[the ring as the
  last chunk left it ; the chunk's own rows]`` where the window is SHORTER
  than the chunk: queries in blocks of ``window`` rows, each block over the
  block of keys before it and its own (``2 · window`` keys), a batched
  product a K/V head — the band at 128 keys is 0.1% of a full layer's work
  at 128 k, and the blocked kernel under a band is twice as slow there: its
  grid steps cost more than the products (docs/kernels.md).
- :func:`step` — one decoded token over a ring or a buffer, the rows that
  hold no key masked, the sink joined.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention, flash_latent
from .flash_attention import NEG_INF


def _softmax_rows(q, k, v, seen, dtype, sink=None):
    """``q`` [..., M, dk] (scaled) over ``k`` [..., S, dk] and ``v`` [..., S,
    dv] under ``seen`` (broadcast to [..., M, S]), ``sink`` (broadcast to
    [..., M, 1]) one more term of the denominator; float32 [..., M, dv]."""
    s = jnp.einsum("...md,...sd->...ms", q.astype(dtype), k.astype(dtype),
                   preferred_element_type=jnp.float32)
    s = jnp.where(seen, s, NEG_INF)
    top = s.max(-1, keepdims=True)
    if sink is not None:
        top = jnp.maximum(top, sink)
    p = jnp.exp(s - top)
    total = p.sum(-1, keepdims=True)
    if sink is not None:
        total = total + jnp.exp(sink - top)
    return jnp.einsum("...ms,...sd->...md", (p / total).astype(dtype),
                      v.astype(dtype), preferred_element_type=jnp.float32)


def _stack_groups(q, G: int):
    """``q`` [C,H,d] → [G, C·J, d]: a group's queries stacked, row ``c·J +
    j`` the query of head ``g·J + j`` at row ``c``."""
    C, H, d = q.shape
    return jnp.swapaxes(q.reshape(C, G, H // G, d), 0, 1).reshape(G, -1, d)


def _unstack_groups(o, C: int):
    """[G, C·J, d] → [C, H, d]: :func:`_stack_groups` undone."""
    G, _, d = o.shape
    return jnp.swapaxes(o.reshape(G, C, -1, d), 0, 1).reshape(C, -1, d)


def _sink_rows(sink, G: int, rows: int):
    """The sink [H] as a column of :func:`_stack_groups`'s rows: [G, rows·J,
    1]."""
    return jnp.tile(sink.astype(jnp.float32).reshape(G, 1, -1),
                    (1, rows, 1)).reshape(G, -1, 1)


def _masked_rows(q, k, v, seen, dtype, sink=None):
    """``q`` [C,H,dk] (scaled), ``k`` [G,S,dk], ``v`` [G,S,dv], ``seen``
    [C,S]: every query over the rows ``seen`` gives it; float32 [C,H,dv]."""
    C, H, _ = q.shape
    G = k.shape[0]
    o = _softmax_rows(
        _stack_groups(q, G), k, v, jnp.repeat(seen, H // G, axis=0), dtype,
        None if sink is None else _sink_rows(sink, G, C))
    return _unstack_groups(o, C)


# --- the full layers' kernel: flash_latent's body under a call of its own ----


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def gqa_wide_causal_mha(q, k, v, start, block_q: int, block_k: int,
                        interpret: bool):
    """``flash_latent._gqa_kernel`` over ``q`` [H, C, dk] (head-major, times
    the softmax scale), ``k`` [G, S, dk] and ``v`` [G, S, dv]: every key ``≤``
    the query's position ``start + row`` (traced); ``C % block_q == 0``, ``S
    % block_k == 0``. The grid walks as far as the chunk's last row sees
    (``flash_latent.core_k_steps``), ``step_rows`` rows of a query tile a
    product. Answers [C, H·dv]."""
    (H, C, dk), (G, S, dv) = q.shape, v.shape
    per_group = H // G
    nk = S // block_k
    kernel = functools.partial(
        flash_latent._gqa_kernel, block_q=block_q, block_k=block_k,
        part=flash_latent.step_rows(block_q), num_k_blocks=nk, window=None,
        precision=flash_latent._precision_of(q.dtype))

    def kv_block(h, i, j, bounds_ref):
        last = flash_latent._last_block(bounds_ref[0], i, block_q, block_k,
                                        nk)
        return (h // per_group, jnp.minimum(j, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H, C // block_q,
              flash_latent.core_k_steps(start, C, block_k, nk)),
        in_specs=[pl.BlockSpec((None, block_q, dk),
                               lambda h, i, j, b: (h, i, 0)),
                  pl.BlockSpec((1, block_k, dk), kv_block),
                  pl.BlockSpec((1, block_k, dv), kv_block)],
        out_specs=pl.BlockSpec((block_q, dv), lambda h, i, j, b: (i, h)),
        scratch_shapes=flash_latent._running_scratch(block_q, dv))
    bounds = jnp.stack([jnp.asarray(start, jnp.int32), jnp.int32(0)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, H * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=flash_latent._VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(bounds, q, k, v)


def causal_chunk(q, k, v, start, scale: float, dtype, block_q: int,
                 block_k: int, kernel: str | None = None):
    """``q`` [C,H,dk] at rows ``start ..`` of ``k`` [G,S,dk], ``v`` [G,S,dv]
    (the chunk's own rows written). ``kernel``: ``pallas`` (the default on a
    TPU), ``interpret`` or ``lax`` (the default elsewhere). Answers
    [C,H,dv] in ``dtype``."""
    C, H, dk = q.shape
    S, dv = k.shape[1], v.shape[2]
    if kernel is None:
        kernel = "pallas" if flash_attention._platform() == "tpu" else "lax"
    q = (q * scale).astype(dtype)
    if kernel == "lax":
        seen = jnp.arange(S)[None, :] <= (start + jnp.arange(C))[:, None]
        return _masked_rows(q, k, v, seen, dtype).astype(dtype)
    bq, bk = math.gcd(C, block_q), block_k
    pad = -S % bk       # none where the caller sized its rows to the block
    if pad:
        k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (k, v))
    if kernel == "pallas":
        from .attention import note_causal
        note_causal("gqa_causal", H, dk, C, S + pad, dtype, bq, bk,
                    flash_latent.step_rows(bq), value_dim=dv)
    o = gqa_wide_causal_mha(jnp.swapaxes(q, 0, 1), k.astype(dtype),
                            v.astype(dtype), start, block_q=bq, block_k=bk,
                            interpret=kernel == "interpret")
    return o.reshape(C, H, dv)


# --- the window layers' band, block-local ------------------------------------


def band_chunk(q, k, v, lowest, window: int, scale: float, dtype,
               sink=None):
    """``q`` [C,H,dk] against ``k`` [G, window + C, dk] and ``v`` [G, window
    + C, dv] — ``[the ring in position order ; the chunk's own rows]`` — each
    query over the ``window`` rows up to its own (row ``window + c`` of
    them), none below row ``lowest`` (the ring is empty before the first
    chunk). Queries in blocks of ``window`` rows over the block before and
    their own: a batched product of ``[n, window·J, 2·window]`` logits a K/V
    head, the heads one after another (an eighth of the logits alive, and
    0.7 ms a layer a chunk faster at the served sizes than all at once).
    Answers [C,H,dv] in ``dtype``."""
    C, H, dk = q.shape
    G, dv, W = k.shape[0], v.shape[2], window
    n = -(-C // W)
    pad = n * W - C     # a short prompt's only chunk: rows no query sees
    q = jnp.pad((q * scale).astype(dtype), ((0, pad), (0, 0), (0, 0)))
    k, v = (jnp.pad(a.astype(dtype), ((0, 0), (0, pad), (0, 0)))
            for a in (k, v))
    r = jnp.arange(W)[:, None]
    c = jnp.arange(2 * W)[None, :]
    first = (jnp.arange(n) * W)[:, None, None]          # a pair's first row
    seen = (c > r) & (c <= r + W) & (first + c >= lowest)     # [n, W, 2W]
    J = H // G
    seen = jnp.repeat(seen, J, axis=1)

    def pairs(a):       # [(n+1)·W, d] → [n, 2W, d]: block i ; block i + 1
        blocks = a.reshape(n + 1, W, a.shape[-1])
        return jnp.concatenate([blocks[:-1], blocks[1:]], axis=1)

    def head(x):        # one K/V head: its queries [n, W·J, dk], its rows
        return _softmax_rows(x[0], pairs(x[1]), pairs(x[2]), seen, dtype,
                             x[3] if sink is not None else None)

    rows = (_stack_groups(q, G).reshape(G, n, W * J, dk), k, v)
    if sink is not None:
        rows += (_sink_rows(sink, G, W),)
    o = jax.lax.map(head, rows)
    return _unstack_groups(o.reshape(G, n * W * J, dv),
                           n * W)[:C].astype(dtype)


def step(q, k, v, valid, scale: float, dtype, sink=None):
    """One token's ``q`` [H,dk] over the rows of ``k`` [G,S,dk], ``v``
    [G,S,dv] that ``valid`` [S] says hold a key it sees (its own row
    written); float32 [H,dv]."""
    return _masked_rows((q * scale)[None], k, v, valid[None], dtype,
                        sink)[0]
