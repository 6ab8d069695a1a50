"""Grouped-query attention over the keys a learned indexer picks for each
query: the selection of ``ops/index_select_attention.py`` (its score kernel,
its exact threshold selection, its ``top_rows``) over a cache whose selected
rows are the K/V rows THEMSELVES — nothing is decompressed, no workspace is
filled.

The cache row of a token is ``[k (G·d) | v (G·d)]`` as the projections made
it (``G`` key/value heads of ``d``, keys normed and roped): one ``[S, 2·G·d]``
buffer a layer. Query head ``h`` reads head ``h // (H / G)``; every head of
every group attends over ONE set of positions a query.

* **prefill** (:func:`masked_chunk_gqa`): the blocked softmax of
  ``index_masked_mha`` under the selection's byte mask, with
  ``gqa_attention``'s grouping — one grid step is a (query tile, key tile) of
  one K/V head for the ``H / G`` query heads of that group: the K tile, the
  V tile and the ``[bq, bk]`` mask tile are read ONCE for all of them (the
  mask becomes one added bias a step), and both are read from the cache
  where they lie (a column block of the row buffer). The step is a flat list
  of PARTS — :func:`core_part` rows of one head, heads outer —, each one
  softmax over the whole K tile, the next part's ``q kᵀ`` set out ahead of a
  part's softmax (PR 65; ``flash_latent._gqa_kernel``'s schedule with a
  bias). Key tiles wholly past a query tile's last row are neither
  fetched nor computed, and the grid's K axis ends where the chunk's last
  row sees (``flash_latent.core_k_steps``), not with the padded cache.
* **decode** (:func:`gathered_step`): the query's ``topk`` rows gathered —
  one ``2·G·d``-wide row a kept position, 4 MiB a layer at 2048 rows of 2 KiB
  against the 134 MB a dense step would read at 65 536 — and
  ``gqa_attention.step`` over them, the places a short prefix leaves empty
  masked.
* **the scores** at this geometry (:func:`index_scores`): the kernel of
  ``index_select_attention`` under tiles of this module (a head of 64 is
  half the matrix unit's depth; GLM's 128-deep tiles are its own).

Products take ``dtype`` operands and accumulate in float32; the mask, the
running maxima and sums are float32 or integer work.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import gqa_attention, index_select_attention as index_ops
from .attention import note_causal
from .flash_attention import _LANES, NEG_INF
from .flash_latent import (_accumulate, _init_running, _last_block,
                           _precision_of, core_k_steps)
from .index_select_attention import _VMEM_LIMIT_BYTES, _kernel_of

# the tiles at the served sizes (a chunk of 4096 queries, 16 index heads of
# 64, 8 query heads of 128 a K/V head), fixed by measurement (PERF.md §6,
# PR 53: scripts/keye_sweep.py); a smaller call takes what divides it
INDEX_TILE = (512, 1024)      # (queries, keys) of a score step
CORE_TILE = (512, 2048)       # (queries, keys) of an attention step
# rows of ONE head a logit product of an attention step (PR 65's sweep,
# docs/kernels.md: a layer's prefill alone 0.2464 s a head at a time as it
# was, 0.2351 with the next head's product ahead, 0.2283 · 0.2171 · 0.2112 ·
# 0.2942 by 256 · 128 · 64 · 32 rows; ``flash_latent.step_rows``' 128 reads
# 2.8% behind 64 HERE and 18% ahead of it at a 1024-key tile — the constant
# belongs to ``CORE_TILE`` and is swept again with it)
CORE_PART = 64


def core_part(block_q: int) -> int:
    """Rows of one head a step's logit product takes: the tile the call is
    handed is the only thing the rule looks at (a tile ``CORE_PART`` does not
    divide — the tiny presets' — is taken a head at a time)."""
    return CORE_PART if block_q % CORE_PART == 0 else block_q


def index_scores(q_i, w, k_i, start, dtype, kernel: str | None = None,
                 tile: tuple = INDEX_TILE):
    """``index_select_attention.index_scores`` under ``tile``: the scores
    [C,S] float32 of ``C`` queries at positions ``start …`` (``q_i``
    [C,J,d], ``w`` [C,J] float32) against the index cache ``k_i`` [S,d]."""
    kernel = _kernel_of(kernel)
    if kernel == "lax":
        return index_ops.index_scores_lax(q_i, w, k_i, dtype)
    C, S = q_i.shape[0], k_i.shape[0]
    return index_ops.index_score_sums(
        jnp.swapaxes(q_i, 0, 1).astype(dtype), w.astype(jnp.float32),
        k_i.astype(dtype), start, block_q=math.gcd(C, tile[0]),
        block_k=math.gcd(S, tile[1]), interpret=kernel == "interpret")


# --- attention under the mask: prefill ---------------------------------------


def masked_gqa_lax(q, kv, keep, num_kv_heads: int, dtype):
    """``q`` [C,H,d] (times the scale), ``kv`` [S, 2·G·d] rows ``[k | v]``,
    ``keep`` [C,S] → softmax over the kept keys, [C,H,d] float32."""
    S, G = kv.shape[0], num_kv_heads
    k, v = (jnp.swapaxes(a.reshape(S, G, -1), 0, 1)
            for a in jnp.split(kv, 2, axis=1))
    return gqa_attention._masked_softmax_rows(q, k, v, keep != 0, dtype)


def _masked_gqa_kernel(start_ref, q_ref, k_ref, v_ref, keep_ref, o_ref,
                       m_ref, l_ref, acc_ref, *, block_q: int, block_k: int,
                       part: int, num_k_blocks: int, heads: int, precision):
    i, j = pl.program_id(1), pl.program_id(2)
    last = _last_block(start_ref[0], i, block_q, block_k, num_k_blocks)
    _init_running(j, m_ref, l_ref, acc_ref)
    d = k_ref.shape[1]
    # the step as a flat list of parts — ``part`` rows of one head, heads
    # outer —: (head, first row)
    parts = [(h, r) for h in range(heads) for r in range(0, block_q, part)]

    @pl.when(j <= last)
    def _step():
        k, v = k_ref[...], v_ref[...]
        # one bias for the group's heads. A row that has kept nothing yet
        # carries exp(0) sums of its masked logits; the first kept key's
        # rescale wipes them (every row keeps at least one key)
        bias = jnp.where(keep_ref[...].astype(jnp.int32) != 0, 0.0, NEG_INF)

        def logits(h: int, r: int):
            s = jax.lax.dot_general(
                q_ref[r:r + part, h * d:(h + 1) * d], k,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision)
            return s + bias[r:r + part]

        # each part a softmax of its own over the whole K tile (nothing
        # rescaled twice), the next part's logit product set out before this
        # part's softmax, across a head's boundary too: the vector work of
        # one lies under the matrix products of the other
        s = logits(*parts[0])
        for n, (h, r) in enumerate(parts):
            ahead = logits(*parts[n + 1]) if n + 1 < len(parts) else None
            rows = pl.ds(r, part)
            _accumulate(s, v, m_ref.at[h, rows], l_ref.at[h, rows],
                        acc_ref.at[h, rows], precision)
            s = ahead

    # the grid's K axis reaches at least this far and may end here
    @pl.when(j == last)
    def _finalize():
        for h in range(heads):
            o_ref[:, h * d:(h + 1) * d] = (
                acc_ref[h] / l_ref[h][:, :1]).astype(o_ref.dtype)


def masked_gqa_call(q, kv, keep, start, k_steps, num_heads: int,
                    num_kv_heads: int, block_q: int, block_k: int, part: int,
                    interpret: bool):
    """:func:`index_masked_gqa` over a grid of ``k_steps`` K blocks — an int
    or a traced scalar that covers every query tile's last visible block —
    by ``part`` rows of one head a product (``block_q % part == 0``)."""
    C, S = q.shape[0], kv.shape[0]
    H, G = num_heads, num_kv_heads
    d, per_group = q.shape[1] // H, H // G
    nq, nk = C // block_q, S // block_k
    kernel = functools.partial(_masked_gqa_kernel, block_q=block_q,
                               block_k=block_k, part=part, num_k_blocks=nk,
                               heads=per_group,
                               precision=_precision_of(q.dtype))

    def seen(i, j, start_ref):
        return jnp.minimum(j, _last_block(start_ref[0], i, block_q, block_k,
                                          nk))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, nq, k_steps),
        in_specs=[
            pl.BlockSpec((block_q, per_group * d),
                         lambda g, i, j, s: (i, g)),
            pl.BlockSpec((block_k, d), lambda g, i, j, s: (seen(i, j, s), g)),
            pl.BlockSpec((block_k, d),
                         lambda g, i, j, s: (seen(i, j, s), G + g)),
            pl.BlockSpec((block_q, block_k),
                         lambda g, i, j, s: (i, seen(i, j, s))),
        ],
        out_specs=pl.BlockSpec((block_q, per_group * d),
                               lambda g, i, j, s: (i, g)),
        scratch_shapes=[
            pltpu.VMEM((per_group, block_q, _LANES), jnp.float32),  # max
            pltpu.VMEM((per_group, block_q, _LANES), jnp.float32),  # sum
            pltpu.VMEM((per_group, block_q, d), jnp.float32)])       # acc
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, H * d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.reshape(start, (1,)).astype(jnp.int32), q, kv, kv, keep)


@functools.partial(jax.jit, static_argnames=("num_heads", "num_kv_heads",
                                             "block_q", "block_k",
                                             "interpret"))
def index_masked_gqa(q, kv, keep, start, num_heads: int, num_kv_heads: int,
                     block_q: int, block_k: int, interpret: bool):
    """``q`` [C, H·d] times the softmax scale, ``kv`` [S, 2·G·d] the cache
    rows ``[k | v]``, ``keep`` [C,S] int8 (it holds the causal rule: nothing
    past a query's position is kept), ``start`` the first query's position
    (traced: key tiles wholly past a query tile are neither fetched nor
    computed, and the grid's K axis ends where the chunk's last row sees:
    ``flash_latent.core_k_steps``). A step takes its query rows
    :func:`core_part` of one head a product. ``C % block_q == 0``, ``S %
    block_k == 0``. Answers [C, H·d]."""
    steps = core_k_steps(jnp.asarray(start, jnp.int32), q.shape[0], block_k,
                         kv.shape[0] // block_k)
    return masked_gqa_call(q, kv, keep, start, steps, num_heads,
                           num_kv_heads, block_q, block_k, core_part(block_q),
                           interpret)


def masked_chunk_gqa(q, kv_cache, keep, start, num_kv_heads: int,
                     scale: float, dtype, kernel: str | None = None,
                     tile: tuple = CORE_TILE):
    """A chunk of ``C`` queries ``q`` [C,H,d] at positions ``start …`` over
    the keys ``keep`` [C,S] int8 marks, of a cache ``kv_cache`` [S, 2·G·d]
    that already holds the chunk's own rows. ``kernel``: ``pallas`` (the
    default on a TPU), ``interpret`` or ``lax``. Answers [C,H,d] in
    ``dtype``."""
    kernel = _kernel_of(kernel)
    C, H, d = q.shape
    S = kv_cache.shape[0]
    q = (q * scale).astype(dtype)
    if kernel == "lax":
        return masked_gqa_lax(q, kv_cache, keep, num_kv_heads,
                              dtype).astype(dtype)
    bq, bk = math.gcd(C, tile[0]), math.gcd(S, tile[1])
    if kernel == "pallas":
        note_causal("index_select", H, d, C, S, dtype, bq, bk, core_part(bq))
    o = index_masked_gqa(q.reshape(C, H * d), kv_cache.astype(dtype), keep,
                         start, num_heads=H, num_kv_heads=num_kv_heads,
                         block_q=bq, block_k=bk,
                         interpret=kernel == "interpret")
    return o.reshape(C, H, d)


# --- decode: one row ---------------------------------------------------------


def gathered_step(q, kv_cache, rows, valid, num_kv_heads: int, scale: float,
                  dtype):
    """One token's ``q`` [H,d] over GIVEN rows of the cache: ``rows`` [k]
    int32 (``index_select_attention.top_rows``), ``valid`` [k] (which of
    them the query reads), ``kv_cache`` [S, 2·G·d]. The rows are gathered
    as they lie — key and value heads of a position together — and
    ``gqa_attention.step`` runs over them; float32 [H,d]."""
    kept = kv_cache[rows]                                    # [k, 2·G·d]
    k, v = (jnp.swapaxes(a.reshape(a.shape[0], num_kv_heads, -1), 0, 1)
            for a in jnp.split(kept, 2, axis=1))
    return gqa_attention.step(q, k, v, valid, scale, dtype)
