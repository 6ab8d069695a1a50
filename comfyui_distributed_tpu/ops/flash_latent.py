"""Pallas causal attention for a prefill walked in chunks through a cache.

Two kernels on one schedule. ``latent_causal_mha`` is the prefill side of
multi-head latent attention (``ops/latent_attention.py``): a chunk of
queries at positions ``start .. start+C−1`` against the rows ``j ≤`` each
query's position of a decompressed workspace. The other
(``ops/gqa_attention.py``) serves GROUPS of query heads over a key/value
head each and may see a BAND only, the last ``window`` keys of each query;
it is jitted under three names, so that a device trace tells its callers
apart: ``gqa_causal_mha``, ``gqa_window_mha`` and — one group, every query
head over ONE key/value head — ``shared_kv_causal_mha``. The schedule's
pieces — ``_last_block``, ``_on_visible_blocks``, ``_accumulate`` — are
shared, the logits, the mask and the K/V tiles are each kernel's own. What
the bidirectional kernels of ``flash_attention.py`` lack:

- a **causal mask** whose diagonal moves with ``start`` (a scalar the
  kernel prefetches: one compiled kernel serves every chunk of a scan).
  K blocks wholly above a q block's last row are neither fetched (their
  block index is clamped to the last needed one, so the pipeline re-uses
  the tile it holds) nor computed; only a block the diagonal crosses is
  masked;
- (``latent_causal_mha``) **unequal widths**: a query and key are ``nope
  + rope`` wide (128 + 64), a value ``v`` wide (128), the rope key ONE
  ``[S, rope]`` array shared by every head (two products into one float32
  tile); keys and values read from ONE workspace ``[S, H·(nope+v)]``: with
  ``nope == v`` the K tile of head ``h`` is column block ``2h`` and the V
  tile ``2h+1`` of the same array.

grid = (heads, C/block_q, K steps), K innermost; running max, sum and the
float32 accumulator live in VMEM scratch across K steps; queries come times
the softmax scale. The latent kernel walks ``S/block_k`` steps, the WHOLE
buffer: a block a q block does not see is skipped but still a grid step
(0.2–0.4 µs). The grouped-query names walk a TRACED extent (PR 63: from a
tile's first visible block as far as the chunk's last row sees), the query
tile ``step_rows`` rows a product. A visible step rewrites the running
tiles whole: each caller hands its kernel a ``(block_q, block_k)`` of its
own, a config field fixed by ``scripts/causal_tile_sweep.py`` (kernels.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _LANES, NEG_INF

_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _last_block(start, i, block_q: int, block_k: int, num_k_blocks: int):
    """The last K block a q block's rows can see."""
    return jnp.minimum((start + (i + 1) * block_q - 1) // block_k,
                       num_k_blocks - 1)


def _mask_above_diagonal(s, first_row, first_col):
    """Logits ``s`` of rows ``first_row ..`` against columns ``first_col
    ..`` with every column past its row's position at −inf."""
    row = first_row + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = first_col + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(col <= row, s, NEG_INF)


def _accumulate(s, v, m_ref, l_ref, acc_ref, precision):
    """One K block's logits ``s`` and values ``v`` into the running max,
    sum and float32 accumulator."""
    m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(v.dtype), v,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32,
                             precision=precision)
    acc_ref[:] = acc_ref[:] * corr + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)


def _on_visible_blocks(step, j, last, first_row, block_k: int, first=None,
                       edge=None):
    """Run ``step(masked)`` for K block ``j`` if the q block sees it:
    masked only where the diagonal crosses it (its last column lies past
    the q block's first row), skipped past ``last``. Under a band, also
    skipped below ``first`` and masked where the band's lower edge crosses
    it (its first column lies below ``edge``, the first column the q
    block's LAST row sees)."""
    crosses = (j + 1) * block_k - 1 > first_row
    seen = j <= last
    if first is not None:
        crosses |= j * block_k < edge
        seen &= j >= first
    pl.when(seen & crosses)(lambda: step(True))
    pl.when(seen & jnp.logical_not(crosses))(lambda: step(False))


def _init_running(j, m_ref, l_ref, acc_ref):
    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)


def _running_scratch(block_q: int, width: int):
    return [pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running sum
            pltpu.VMEM((block_q, width), jnp.float32)]    # output acc


def _precision_of(dtype):
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _latent_causal_kernel(start_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref,
                          o_ref, m_ref, l_ref, acc_ref, *, block_q: int,
                          block_k: int, num_k_blocks: int, precision):
    i, j = pl.program_id(1), pl.program_id(2)
    first_row = start_ref[0] + i * block_q
    last = _last_block(start_ref[0], i, block_q, block_k, num_k_blocks)

    _init_running(j, m_ref, l_ref, acc_ref)

    def step(masked: bool):
        nt = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(qn_ref[...], kn_ref[...], nt,
                                preferred_element_type=jnp.float32,
                                precision=precision)
        s += jax.lax.dot_general(qr_ref[0], kr_ref[...], nt,
                                 preferred_element_type=jnp.float32,
                                 precision=precision)
        if masked:
            s = _mask_above_diagonal(s, first_row, j * block_k)
        _accumulate(s, v_ref[...], m_ref, l_ref, acc_ref, precision)

    _on_visible_blocks(step, j, last, first_row, block_k)

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        o_ref[...] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_heads", "block_q",
                                             "block_k", "interpret"))
def latent_causal_mha(q_nope, q_rope, kv, k_rope, start, num_heads: int,
                      block_q: int, block_k: int, interpret: bool):
    """``q_nope`` [C, H·nope], ``q_rope`` [H, C, rope] (roped), both times
    the softmax scale; ``kv`` [S, H·(nope+v)] with ``nope == v``;
    ``k_rope`` [S, rope]; ``start`` the first query's position (traced).
    ``C % block_q == 0`` and ``S % block_k == 0``. Answers [C, H·v]."""
    C, S = q_nope.shape[0], kv.shape[0]
    H = num_heads
    nope, rope = q_nope.shape[1] // H, q_rope.shape[-1]
    nq, nk = C // block_q, S // block_k
    precision = _precision_of(q_nope.dtype)
    kernel = functools.partial(_latent_causal_kernel, block_q=block_q,
                               block_k=block_k, num_k_blocks=nk,
                               precision=precision)

    def k_block(i, j, start_ref):
        return jnp.minimum(j, _last_block(start_ref[0], i, block_q, block_k,
                                          nk))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H, nq, nk),
        in_specs=[
            pl.BlockSpec((block_q, nope), lambda h, i, j, s: (i, h)),
            pl.BlockSpec((1, block_q, rope), lambda h, i, j, s: (h, i, 0)),
            pl.BlockSpec((block_k, nope),
                         lambda h, i, j, s: (k_block(i, j, s), 2 * h)),
            pl.BlockSpec((block_k, rope),
                         lambda h, i, j, s: (k_block(i, j, s), 0)),
            pl.BlockSpec((block_k, nope),
                         lambda h, i, j, s: (k_block(i, j, s), 2 * h + 1)),
        ],
        out_specs=pl.BlockSpec((block_q, nope), lambda h, i, j, s: (i, h)),
        scratch_shapes=_running_scratch(block_q, nope))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, H * nope), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.reshape(start, (1,)).astype(jnp.int32), q_nope, q_rope, kv, k_rope,
      kv)


# --- groups of query heads over a key/value head each, whole or a band -----


def _first_column(row, window: "int | None", lowest):
    """The first column the query at position ``row`` sees: the band's
    lower edge (``window`` keys, its own included) or the lowest valid
    column, whichever is higher."""
    return lowest if window is None \
        else jnp.maximum(row - window + 1, lowest)


def _mask_outside_band(s, first_row, first_col, window: "int | None",
                       lowest):
    """As :func:`_mask_above_diagonal`, with every column below its row's
    first visible one (:func:`_first_column`) at −inf too."""
    row = first_row + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = first_col + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    seen = (col <= row) & (col >= _first_column(row, window, lowest))
    return jnp.where(seen, s, NEG_INF)


# rows of a query tile whose logits are one product (PR 63's sweep, PERF.md
# §6): the softmax of one part lies under the next part's product — 79 →
# 91% of the matrix units' peak at 2048 × 2048. 128 is the one length that
# wins at every tile read (64 reads 1% better at a 2048-key tile and 25%
# worse at a 1024-key one). Parts of the K tile read SLOWER at every tile
# (a rescale of the running tiles a part); these rescale nothing more
STEP_ROWS = 128


def step_rows(block_q: int) -> int:
    """Rows of the query tile the grouped-query kernel's step takes at a
    time: the tile it is handed is the only thing the rule looks at."""
    return STEP_ROWS if block_q % STEP_ROWS == 0 else block_q


def core_k_steps(start, chunk: int, block_k: int, num_k_blocks: int):
    """K blocks the attention kernel's grid walks for a chunk of ``chunk``
    queries at positions ``start …``: as far as the chunk's LAST row sees —
    a query tile's steps past its own last block are the diagonal's few,
    never the rest of a padded cache."""
    return jnp.minimum((start + chunk + block_k - 1) // block_k,
                       num_k_blocks)


def gqa_k_steps(start, lowest, chunk: int, window: "int | None",
                block_q: int, block_k: int, num_k_blocks: int):
    """K blocks the grouped-query kernel's grid walks a query tile, counted
    from the tile's first visible block: :func:`core_k_steps`, or under a
    band the widest tile's span."""
    if window is None:
        return core_k_steps(start, chunk, block_k, num_k_blocks)
    i = jnp.arange(chunk // block_q)
    first = _first_column(start + i * block_q, window, lowest) // block_k
    return jnp.max(_last_block(start, i, block_q, block_k, num_k_blocks)
                   - first) + 1


def _gqa_kernel(bounds_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                acc_ref, *, block_q: int, block_k: int, part: int,
                num_k_blocks: int, window: "int | None", precision):
    i, walked = pl.program_id(1), pl.program_id(2)
    start, lowest = bounds_ref[0], bounds_ref[1]
    first_row = start + i * block_q
    last = _last_block(start, i, block_q, block_k, num_k_blocks)
    first = _first_column(first_row, window, lowest) // block_k
    edge = _first_column(first_row + block_q - 1, window, lowest)
    j = first + walked              # the walk starts where the tile sees
    _init_running(walked, m_ref, l_ref, acc_ref)

    def step(masked: bool):
        def logits(n: int):
            s = jax.lax.dot_general(
                q_ref[pl.ds(n * part, part)], k_ref[0],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision)
            if masked:
                s = _mask_outside_band(s, first_row + n * part, j * block_k,
                                       window, lowest)
            return s

        # the query tile ``part`` rows at a time — each part a softmax of
        # its own over the whole K tile, nothing rescaled twice — the next
        # part's logit product set out before this part's softmax: the
        # vector work of one lies under the matrix products of the other
        s = logits(0)
        for n in range(block_q // part):
            ahead = logits(n + 1) if (n + 1) * part < block_q else None
            rows = pl.ds(n * part, part)
            _accumulate(s, v_ref[0], m_ref.at[rows], l_ref.at[rows],
                        acc_ref.at[rows], precision)
            s = ahead

    _on_visible_blocks(step, j, last, first_row, block_k, first, edge)

    # the grid's K axis reaches at least this far and may end here
    @pl.when(j == last)
    def _finalize():
        o_ref[...] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)


def gqa_call(q, k, v, start, lowest, k_steps, num_heads: int,
             window: "int | None", block_q: int, block_k: int, part: int,
             interpret: bool):
    """:func:`_gqa_mha` over a grid of ``k_steps`` K blocks a query tile —
    an int or a traced scalar that covers every tile's visible blocks,
    counted from the tile's first — by ``part`` rows of a query tile a
    product (``block_q % part == 0``)."""
    C, (G, S, d) = q.shape[0], k.shape
    per_group = num_heads // G
    nq, nk = C // block_q, S // block_k
    kernel = functools.partial(_gqa_kernel, block_q=block_q,
                               block_k=block_k, part=part, num_k_blocks=nk,
                               window=window,
                               precision=_precision_of(q.dtype))

    def kv_block(h, i, j, bounds_ref):
        start, lowest = bounds_ref[0], bounds_ref[1]
        first = _first_column(start + i * block_q, window, lowest) // block_k
        last = _last_block(start, i, block_q, block_k, nk)
        return (h // per_group, jnp.minimum(first + j, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_heads, nq, k_steps),
        in_specs=[pl.BlockSpec((block_q, d), lambda h, i, j, b: (i, h)),
                  pl.BlockSpec((1, block_k, d), kv_block),
                  pl.BlockSpec((1, block_k, d), kv_block)],
        out_specs=pl.BlockSpec((block_q, d), lambda h, i, j, b: (i, h)),
        scratch_shapes=_running_scratch(block_q, d))
    bounds = jnp.stack([jnp.asarray(start, jnp.int32),
                        jnp.asarray(lowest, jnp.int32)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, num_heads * d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(bounds, q, k, v)


def _gqa_mha(q, k, v, start, lowest, num_heads: int, window: "int | None",
             block_q: int, block_k: int, interpret: bool):
    """Causal attention of ``num_heads`` query heads over ``G`` key/value
    heads, query head ``h`` reading head ``h // (num_heads / G)``: the
    schedule above with K and V tiles indexed by the GROUP (no per-head
    copy of the cache exists) and, with ``window``, a band — a query sees
    the ``window`` keys up to its own, K blocks wholly below the band are
    neither fetched nor computed, the block its lower edge crosses is
    masked as the diagonal's is. The grid's K axis is a traced bound: a
    query tile walks from its first visible block as far as the chunk's
    last row sees (:func:`gqa_k_steps`), :func:`step_rows` rows of it a
    product. ``q`` [C, H·d] times
    the softmax scale; ``k``, ``v`` [G, S, d]; ``start`` the first query's
    position and ``lowest`` the first valid key row (both traced: rows
    below ``lowest`` hold nothing yet). ``C % block_q == 0``, ``S % block_k
    == 0``. Answers [C, H·d]."""
    steps = gqa_k_steps(start, lowest, q.shape[0], window, block_q, block_k,
                        k.shape[1] // block_k)
    return gqa_call(q, k, v, start, lowest, steps, num_heads, window,
                    block_q, block_k, step_rows(block_q), interpret)


# one body under three names, so that a device trace tells the full layers'
# kernel from the window layers' and both from a shared-K/V model's


@functools.partial(jax.jit, static_argnames=("num_heads", "block_q",
                                             "block_k", "interpret"))
def gqa_causal_mha(q, k, v, start, num_heads: int, block_q: int,
                   block_k: int, interpret: bool):
    """:func:`_gqa_mha` over every key ``≤`` the query's position."""
    return _gqa_mha(q, k, v, start, 0, num_heads, None, block_q, block_k,
                    interpret)


@functools.partial(jax.jit, static_argnames=("num_heads", "window",
                                             "block_q", "block_k",
                                             "interpret"))
def gqa_window_mha(q, k, v, start, lowest, num_heads: int, window: int,
                   block_q: int, block_k: int, interpret: bool):
    """:func:`_gqa_mha` over the last ``window`` keys of each query."""
    return _gqa_mha(q, k, v, start, lowest, num_heads, window, block_q,
                    block_k, interpret)


@functools.partial(jax.jit, static_argnames=("num_heads", "block_q",
                                             "block_k", "interpret"))
def shared_kv_causal_mha(q, k, v, start, num_heads: int, block_q: int,
                         block_k: int, interpret: bool):
    """:func:`gqa_causal_mha` where every query head reads ONE key/value
    head (multi-query attention): ``k``, ``v`` [S, d], the cache itself."""
    return _gqa_mha(q, k[None], v[None], start, 0, num_heads, None, block_q,
                    block_k, interpret)
