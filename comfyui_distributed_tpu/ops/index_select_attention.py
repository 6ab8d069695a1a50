"""Latent attention over the keys a learned indexer picks for each query.

A layer of this kind keeps, beside the latent cache of
``ops/latent_attention.py`` (``c`` and the roped ``k_rope``), an INDEX key
``k_I`` [d_I] a token. A query scores every key below it with a small
scorer of its own, ``I[t,s] = Σ_j w[t,j] · ReLU(q_I[t,j] · k_I[s])`` over
``J`` index heads, and attends — all heads alike — over the ``topk``
positions of largest score (all of them where ``t + 1 ≤ topk``; ties to the
lower position). Three pieces, each with a plain ``jax.lax`` form (the CPU's
and the tests' yardstick) and the form a TPU runs:

* **the scores** (:func:`index_scores`): one Pallas kernel over (query
  tile, key tile), the index heads a loop inside a step — a head's ``[bq,
  bk]`` product is rectified, weighted and added in VMEM, so the ``[J, C,
  S]`` products never exist and the ``[C, S]`` float32 scores are written
  once. Key tiles wholly past a query tile's last row are neither fetched
  nor computed (what they hold in the output is never read).
* **the selection** (:func:`select_keep`): EXACT, without a sort. A float32
  score maps to an int32 of the same order; the ``topk``-th largest value
  of a row is found by binary search over the 32 bits (a pass counts the
  keys at or above a candidate), then — only where more keys tie at that
  value than places are left — the last tied POSITION taken by a second
  search over the positions. The answer is a mask ``[C, S]`` int8, not a
  list: a row's scores stay in VMEM for all the passes (the kernel reads
  the scores once and writes a byte a pair). A pass walks the row block in
  column tiles and stops at the last one a row of the block can SEE (PR
  54): the order image, every count and the mask's work are for the
  columns below ``first + rows``, read off the prefetched position — chunk
  0 of a 64k brief visits one tile of seventeen, the prefill half the
  cache's columns — and the mask past them is written as zeros
  (:func:`select_columns` is the same rule on the host, for a counter).
* **the attention** over the kept keys. Prefill
  (:func:`masked_chunk_attention`) decompresses a GROUP of heads' keys and
  values at a time into a workspace — ONE kernel, ``index_fill_kv`` (PR 52):
  a tile of latent rows in, the group's keys ``[c W_k | k_rope]`` and values
  ``c W_v`` out in the layout the next kernel reads, float32 in VMEM and
  rounded once; rows past the chunk's end are never written, so nothing
  initialises the workspace — and runs a blocked softmax kernel under
  the mask (``index_masked_mha``: ``flash_latent.py``'s schedule, the byte
  mask for the diagonal, keys and values of their own widths; PR 60: a K
  tile by parts, a grid as far as the chunk sees). A query's keys are its
  own — with seeded weights the union over a tile of neighbours is the
  whole prefix — so there is no tile to skip below the diagonal and a list
  of rows to GATHER is 1152 bytes a row, 132 M rows a layer at 65 536
  tokens: the mask form is what a v5e can run (PERF.md §6, PR 51). Decode
  (:func:`index_step`, :func:`absorbed_rows_step`) is one row: ``top_k``, the
  ``topk`` latent rows gathered, ``W_b`` absorbed into query and output as
  ``latent_attention.mla_absorbed_step`` does over the whole cache.

Scores, the order image, counts and the mask are exact integer or float32
work; products take ``dtype`` operands and accumulate in float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import CAUSAL_TIER_REASONS, note_causal
from . import flash_attention
from .flash_attention import NEG_INF
from .flash_latent import (_accumulate, _init_running, _last_block,
                           _precision_of, _running_scratch, core_k_steps)

CAUSAL_TIER_REASONS.setdefault(
    "index_select", "chunked prefill over the keys an indexer kept")

_VMEM_LIMIT_BYTES = 100 * 1024 * 1024
_INT_MIN = -2 ** 31
# the tiles of the four kernels at the served sizes (a chunk of 4096 queries,
# 128-wide index heads, 256-wide keys and values), fixed by measurement
# (PERF.md §6, PRs 51, 52, 54 and 60); a smaller call takes what divides it
INDEX_TILE = (256, 1024)      # (queries, keys) of a score step
SELECT_ROWS = 64              # rows whose scores stay in VMEM together
SELECT_TILE = 4096            # columns of them a turn of a pass's loop counts
CORE_TILE = (2048, 2048)      # (queries, keys) of an attention step
CORE_PART = 512               # keys of it multiplied ahead of their softmax
HEADS_PER_PASS = 8            # heads decompressed into the workspace at once
FILL_ROWS = 1024              # workspace rows a fill step decompresses


# --- the scores --------------------------------------------------------------


def index_scores_lax(q_i, w, k_i, dtype):
    """``q_i`` [C,J,d], ``w`` [C,J] float32, ``k_i`` [S,d] → ``I`` [C,S]
    float32: every key, the caller masks. ``+ 0.0``: a sum of ``−0.0``
    terms is ``+0.0`` (the selection orders bit patterns)."""
    s = jnp.einsum("tjd,sd->tjs", q_i.astype(dtype), k_i.astype(dtype),
                   preferred_element_type=jnp.float32,
                   precision=_precision_of(jnp.dtype(dtype)))
    return (w[:, :, None] * jnp.maximum(s, 0.0)).sum(1) + 0.0


def _index_kernel(start_ref, q_ref, w_ref, k_ref, o_ref, *, block_q: int,
                  block_k: int, num_k_blocks: int, heads: int, precision):
    i, j = pl.program_id(0), pl.program_id(1)
    last = _last_block(start_ref[0], i, block_q, block_k, num_k_blocks)

    @pl.when(j <= last)
    def _step():
        k = k_ref[...]
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for h in range(heads):
            s = jax.lax.dot_general(q_ref[h], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=precision)
            acc = acc + w_ref[:, h:h + 1] * jnp.maximum(s, 0.0)
        o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def index_score_sums(q_i, w, k_i, start, block_q: int, block_k: int,
                     interpret: bool):
    """``q_i`` [J,C,d] (heads first), ``w`` [C,J] float32, ``k_i`` [S,d],
    ``start`` the first query's position (traced). ``C % block_q == 0``,
    ``S % block_k == 0``. Answers ``I`` [C,S] float32; a key tile wholly
    past a query tile's last row is left as it was allocated."""
    J, C, d = q_i.shape
    S = k_i.shape[0]
    nq, nk = C // block_q, S // block_k
    kernel = functools.partial(_index_kernel, block_q=block_q,
                               block_k=block_k, num_k_blocks=nk, heads=J,
                               precision=_precision_of(q_i.dtype))

    def seen(i, j, start_ref):
        return jnp.minimum(j, _last_block(start_ref[0], i, block_q, block_k,
                                          nk))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nq, nk),
        in_specs=[pl.BlockSpec((J, block_q, d), lambda i, j, s: (0, i, 0)),
                  pl.BlockSpec((block_q, J), lambda i, j, s: (i, 0)),
                  pl.BlockSpec((block_k, d),
                               lambda i, j, s: (seen(i, j, s), 0))],
        out_specs=pl.BlockSpec((block_q, block_k), lambda i, j, s: (i, j)))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, S), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.reshape(start, (1,)).astype(jnp.int32), q_i, w, k_i)


def _kernel_of(kernel: "str | None") -> str:
    # through the module: a tool that describes a chip replaces the function
    return kernel or ("pallas" if flash_attention._platform() == "tpu"
                      else "lax")


def index_scores(q_i, w, k_i, start, dtype, kernel: str | None = None):
    """The scores of ``C`` queries at positions ``start …`` against an index
    cache ``k_i`` [S,d] that already holds their own rows. ``q_i`` [C,J,d],
    ``w`` [C,J] float32. ``kernel``: ``pallas`` (the default on a TPU),
    ``interpret`` or ``lax``. Answers [C,S] float32; entries past a query's
    own position hold anything (:func:`select_keep` never reads them)."""
    kernel = _kernel_of(kernel)
    if kernel == "lax":
        return index_scores_lax(q_i, w, k_i, dtype)
    C, S = q_i.shape[0], k_i.shape[0]
    return index_score_sums(
        jnp.swapaxes(q_i, 0, 1).astype(dtype), w.astype(jnp.float32),
        k_i.astype(dtype), start, block_q=math.gcd(C, INDEX_TILE[0]),
        block_k=math.gcd(S, INDEX_TILE[1]), interpret=kernel == "interpret")


# --- the selection -----------------------------------------------------------


def order_key(scores):
    """Float32 → int32 of the same order (no NaN among them): the bits of a
    non-negative number as they are, of a negative one with all but the
    sign flipped."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _largest(ok, bits: int, init):
    """The largest int32 built from ``init`` by setting some of its low
    ``bits`` bits, highest first, for which ``ok`` holds (``ok`` holds of
    ``init`` and is monotone)."""
    def body(b, base):
        cand = base | jnp.left_shift(jnp.int32(1), bits - 1 - b)
        return jnp.where(ok(cand), cand, base)

    return jax.lax.fori_loop(0, bits, body, init)


def _search(count, n: int, topk: int, position_bits: int):
    """What decides the selection of ``n`` rows, each [n,1] int32: the
    ``topk``-th largest key of a row (``_INT_MIN`` where it has fewer
    admissible ones) and the last tied POSITION it takes. ``count(pred)``
    answers [n,1] int32: of each row, the columns where ``pred(key, col)``
    holds — over whatever columns the caller holds the row's admissible
    keys among."""
    def reaches(cand):
        return count(lambda key, col: key >= cand) >= topk

    zero = jnp.zeros((n, 1), jnp.int32)
    kth = _largest(reaches, 31,
                   jnp.where(reaches(zero), zero, jnp.int32(_INT_MIN)))
    # ≥ 1 places for the ties
    left = topk - count(lambda key, col: key > kth)

    def all_tied():
        return jnp.full_like(zero, 2 ** position_bits - 1)

    def some_tied():
        # the last position taken: the largest p with fewer than ``left``
        # ties below it
        return _largest(
            lambda p: count(lambda key, col: (key == kth) & (col < p)) < left,
            position_bits, zero)

    tied = count(lambda key, col: key == kth)
    return kth, jax.lax.cond(jnp.any(tied > left), some_tied, all_tied)


def _kept(key, col, kth, last):
    return (key > kth) | ((key == kth) & (col <= last))


def keep_of(key, col, topk: int, position_bits: int):
    """``key`` [n,S] int32 (a row's order images, ``_INT_MIN`` where the
    row may not look), ``col`` [n,S] or [1,S] positions. Answers the bool
    mask of each row's ``topk`` largest keys, ties to the lower position —
    every admissible key where a row has at most ``topk`` of them (the
    caller ands the admissible ones)."""
    def count(pred):
        return jnp.sum(pred(key, col).astype(jnp.int32), axis=1,
                       keepdims=True)

    return _kept(key, col, *_search(count, key.shape[0], topk,
                                    position_bits))


def select_keep_lax(scores, first_row, topk: int):
    """``scores`` [n,S] float32 of the queries at positions ``first_row …``
    → int8 [n,S]: 1 where the query attends."""
    n, S = scores.shape
    row = first_row + jnp.arange(n)[:, None]
    col = jnp.arange(S)[None, :]
    seen = col <= row
    key = jnp.where(seen, order_key(scores), jnp.int32(_INT_MIN))
    keep = keep_of(key, col, topk, max(S.bit_length(), 1))
    return (keep & seen).astype(jnp.int8)


def _select_visible(first, scores_of, o_ref, key_ref, *, topk: int,
                    position_bits: int, tile: int):
    """:func:`select_keep_lax` of the rows at positions ``first …`` over
    the column tiles they can SEE: tile ``t`` holds the positions ``t·tile
    …``, and no row looks past the last one's own — the tiles after that
    are never read, counted or compared, and their mask is zeros.
    ``scores_of(t, at)`` answers tile ``t``'s float32 scores (``at`` its
    columns in a row block); ``key_ref`` [rows, S] int32 is scratch."""
    rows, tiles = o_ref.shape[0], o_ref.shape[1] // tile
    row = first + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    visible = jnp.minimum((first + rows + tile - 1) // tile, tiles)
    lane = math.gcd(tile, 128)

    def columns(t, offset: int = 0, width: int = tile):
        """``width`` columns of tile ``t`` from its ``offset``-th: where
        they lie in the row block, and their positions."""
        at = pl.multiple_of(t * tile, tile) + offset
        return pl.ds(at, width), at + jax.lax.broadcasted_iota(
            jnp.int32, (rows, width), 1)

    @pl.loop(0, visible)
    def _image(t):
        at, col = columns(t)
        key_ref[:, at] = jnp.where(col <= row, order_key(scores_of(t, at)),
                                   jnp.int32(_INT_MIN))

    def count(pred):
        # a lane keeps its own running count through a row's tiles (plain
        # register adds); the lanes are summed once a pass
        def one(t, lanes):
            for g in range(0, tile, lane):
                at, col = columns(t, g, lane)
                lanes = lanes + pred(key_ref[:, at], col).astype(jnp.int32)
            return lanes

        return jnp.sum(jax.lax.fori_loop(
            0, visible, one, jnp.zeros((rows, lane), jnp.int32)),
            axis=1, keepdims=True)

    kth, last = _search(count, rows, topk, position_bits)

    @pl.loop(0, visible)
    def _mask(t):
        at, col = columns(t)
        keep = _kept(key_ref[:, at], col, kth, last) & (col <= row)
        o_ref[:, at] = keep.astype(jnp.int8)

    @pl.loop(visible, tiles)
    def _unseen(t):
        o_ref[:, columns(t)[0]] = jnp.zeros((rows, tile), jnp.int8)


def _select_kernel(start_ref, s_ref, o_ref, key_ref, *, rows: int, **rule):
    _select_visible(start_ref[0] + pl.program_id(0) * rows,
                    lambda t, at: s_ref[:, at], o_ref, key_ref, **rule)


@functools.partial(jax.jit, static_argnames=("topk", "rows", "tile",
                                             "interpret"))
def index_select_keep(scores, start, topk: int, rows: int, tile: int,
                      interpret: bool):
    """:func:`select_keep_lax` as a kernel: ``rows`` queries' scores in
    VMEM at a time, searched ``tile`` columns at a time as far as the
    step's last row sees (``start`` decides: chunk 0 of a long cache
    visits one tile in seventeen). ``scores`` [C,S] float32, ``C % rows ==
    0``, ``S % tile == 0``; the mask is whole: 0 in every column past a
    row's position."""
    C, S = scores.shape
    kernel = functools.partial(_select_kernel, rows=rows, topk=topk,
                               position_bits=max(S.bit_length(), 1),
                               tile=tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(C // rows,),
        in_specs=[pl.BlockSpec((rows, S), lambda i, s: (i, 0))],
        out_specs=pl.BlockSpec((rows, S), lambda i, s: (i, 0)),
        scratch_shapes=[pltpu.VMEM((rows, S), jnp.int32)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, S), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.reshape(start, (1,)).astype(jnp.int32), scores)


def select_tile(S: int) -> int:
    """The columns a pass of the selection visits at a time over a cache of
    ``S`` rows: ``SELECT_TILE`` where it divides them, else all of them."""
    return S if S % SELECT_TILE else SELECT_TILE


def select_columns(prompt_tokens: int, new_tokens: int, chunk: int,
                   call_rows: int) -> dict:
    """Columns the selection kernel's grid steps visit over one layer of a
    chunked prefill — ``searched`` — and what whole rows would be —
    ``cache`` —, by :func:`_select_visible`'s rule at :func:`select_keep`'s
    tiles: ``chunk`` tokens a chunk (a padded last chunk selects too),
    ``call_rows`` of them a call, a cache of whole chunks."""
    chunk = min(chunk, prompt_tokens)
    walked = -(-prompt_tokens // chunk) * chunk
    S = -(-max(prompt_tokens + new_tokens, walked) // chunk) * chunk
    rows, tile = math.gcd(chunk, call_rows), S    # the plain form's one step
    if rows % SELECT_ROWS == 0:
        rows, tile = SELECT_ROWS, select_tile(S)
    first = range(0, walked, rows)
    return {"searched": tile * sum(min(-(-(f + rows) // tile), S // tile)
                                   for f in first),
            "cache": S * len(first)}


def select_keep(scores, start, topk: int, kernel: str | None = None):
    """The mask of the queries at positions ``start …``: int8 [C,S], 1 on
    the ``min(topk, t + 1)`` positions ``s ≤ t`` of largest score, ties to
    the lower ``s``. ``kernel`` as :func:`index_scores`."""
    kernel = _kernel_of(kernel)
    C, S = scores.shape
    if kernel == "lax" or C % SELECT_ROWS:
        return select_keep_lax(scores, start, topk)
    return index_select_keep(scores, start, topk=topk, rows=SELECT_ROWS,
                             tile=select_tile(S),
                             interpret=kernel == "interpret")


# --- attention under the mask: prefill ---------------------------------------


def masked_attention_lax(q, k, v, keep, dtype):
    """``q`` [C,H,dk] (times the scale), ``k`` [S,H,dk], ``v`` [S,H,dv],
    ``keep`` [C,S] → softmax over the kept keys, [C,H,dv] float32."""
    precision = _precision_of(jnp.dtype(dtype))
    s = jnp.einsum("thd,shd->hts", q.astype(dtype), k.astype(dtype),
                   preferred_element_type=jnp.float32, precision=precision)
    p = jax.nn.softmax(jnp.where(keep[None] != 0, s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shv->thv", p.astype(dtype), v.astype(dtype),
                      preferred_element_type=jnp.float32,
                      precision=precision)


def _masked_kernel(start_ref, q_ref, k_ref, v_ref, keep_ref, o_ref, m_ref,
                   l_ref, acc_ref, *, block_q: int, block_k: int, part: int,
                   num_k_blocks: int, precision):
    i, j = pl.program_id(1), pl.program_id(2)
    last = _last_block(start_ref[0], i, block_q, block_k, num_k_blocks)
    _init_running(j, m_ref, l_ref, acc_ref)

    def logits(n: int):
        at = slice(n * part, (n + 1) * part)
        s = jax.lax.dot_general(q_ref[...], k_ref[at],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=precision)
        # a row that has kept nothing yet carries exp(0) sums of its
        # masked logits; the first kept key's rescale wipes them (every
        # row keeps at least one key)
        return jnp.where(keep_ref[:, at].astype(jnp.int32) != 0, s, NEG_INF)

    @pl.when(j <= last)
    def _step():
        # the K tile ``part`` keys at a time, in their order, the next
        # part's logit product set out before this part's softmax: the
        # vector work of one lies under the matrix products of the other
        s = logits(0)
        for n in range(block_k // part):
            ahead = logits(n + 1) if (n + 1) * part < block_k else None
            _accumulate(s, v_ref[n * part:(n + 1) * part], m_ref, l_ref,
                        acc_ref, precision)
            s = ahead

    # the grid's K axis reaches at least this far and may end here
    @pl.when(j == last)
    def _finalize():
        o_ref[...] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)


# The K blocks the kernel's grid walks for a chunk of queries at positions
# ``start …`` — as far as the chunk's LAST row sees: a query tile's steps
# past its own last block are the diagonal's few, never the rest of a
# padded cache — are :func:`core_k_steps`' to say: ``flash_latent``'s
# since PR 63, where the grouped-query kernel walks the same extent, and
# this module's by import (``scripts/index_select_sweep.py`` and the tests
# call it here).


def masked_mha_call(q, k, v, keep, start, k_steps, num_heads: int,
                    block_q: int, block_k: int, part: int, interpret: bool):
    """:func:`index_masked_mha` over a grid of ``k_steps`` K blocks: an int
    or a traced scalar that covers every query tile's last visible block."""
    C, S = q.shape[0], k.shape[0]
    H = num_heads
    dk, dv = q.shape[1] // H, v.shape[1] // H
    nq, nk = C // block_q, S // block_k
    kernel = functools.partial(_masked_kernel, block_q=block_q,
                               block_k=block_k, part=part, num_k_blocks=nk,
                               precision=_precision_of(q.dtype))

    def seen(i, j, start_ref):
        return jnp.minimum(j, _last_block(start_ref[0], i, block_q, block_k,
                                          nk))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H, nq, k_steps),
        in_specs=[
            pl.BlockSpec((block_q, dk), lambda h, i, j, s: (i, h)),
            pl.BlockSpec((block_k, dk),
                         lambda h, i, j, s: (seen(i, j, s), h)),
            pl.BlockSpec((block_k, dv),
                         lambda h, i, j, s: (seen(i, j, s), h)),
            pl.BlockSpec((block_q, block_k),
                         lambda h, i, j, s: (i, seen(i, j, s))),
        ],
        out_specs=pl.BlockSpec((block_q, dv), lambda h, i, j, s: (i, h)),
        scratch_shapes=_running_scratch(block_q, dv))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, H * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.reshape(start, (1,)).astype(jnp.int32), q, k, v, keep)


@functools.partial(jax.jit, static_argnames=("num_heads", "block_q",
                                             "block_k", "part", "interpret"))
def index_masked_mha(q, k, v, keep, start, num_heads: int, block_q: int,
                     block_k: int, part: int, interpret: bool):
    """``q`` [C, H·dk] times the softmax scale, ``k`` [S, H·dk], ``v``
    [S, H·dv], ``keep`` [C,S] int8 (it holds the causal rule: nothing past
    a query's position is kept), ``start`` the first query's position
    (traced: key tiles wholly past a query tile are neither fetched nor
    computed, and the grid's K axis ends where the chunk's last row sees:
    :func:`core_k_steps`). ``C % block_q == 0``, ``S % block_k == 0``,
    ``block_k % part == 0``. Answers [C, H·dv]."""
    steps = core_k_steps(jnp.asarray(start, jnp.int32), q.shape[0], block_k,
                         k.shape[0] // block_k)
    return masked_mha_call(q, k, v, keep, start, steps, num_heads, block_q,
                           block_k, part, interpret)


def _fill_kernel(n_ref, c_ref, kr_ref, wk_ref, wv_ref, k_ref, v_ref, *,
                 heads: int, nope: int, precision):
    @pl.when(pl.program_id(0) < n_ref[0])
    def _step():
        c = c_ref[...]
        dk, dv = k_ref.shape[1] // heads, v_ref.shape[1] // heads
        kr = kr_ref[...].astype(jnp.float32)
        is_rope = jax.lax.broadcasted_iota(jnp.int32, kr.shape, 1) >= nope
        for h in range(heads):
            k = jnp.dot(c, wk_ref[:, h * dk:(h + 1) * dk],
                        preferred_element_type=jnp.float32,
                        precision=precision)
            k_ref[:, h * dk:(h + 1) * dk] = jnp.where(
                is_rope, kr, k).astype(k_ref.dtype)
            v_ref[:, h * dv:(h + 1) * dv] = jnp.dot(
                c, wv_ref[:, h * dv:(h + 1) * dv],
                preferred_element_type=jnp.float32,
                precision=precision).astype(v_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_heads", "nope", "block_rows",
                                             "interpret"))
def index_fill_kv(c, kr_wide, w_k, w_v, n_rows, num_heads: int, nope: int,
                  block_rows: int, interpret: bool):
    """The workspace :func:`index_masked_mha` reads, of ``num_heads`` heads:
    ``c`` [S,rank] latent rows, ``kr_wide`` [S,dk] the rope key in a key's
    last columns (anything in its first ``nope``), ``w_k`` [rank, H·dk] a
    head's ``nope`` key columns (anything in the rope's place), ``w_v``
    [rank, H·dv], ``n_rows`` (traced, whole ``block_rows``, at least one)
    the rows to decompress. ``S % block_rows == 0``. Answers ``(k [S, H·dk],
    v [S, H·dv])``: a head's columns together, a key ``[c W_k | k_rope]``,
    each product accumulated in float32 and rounded once; rows at or past
    ``n_rows`` are never written and hold what they were allocated with."""
    S = c.shape[0]
    kernel = functools.partial(_fill_kernel, heads=num_heads, nope=nope,
                               precision=_precision_of(c.dtype))

    def seen(i, n_ref):
        # a tile past the filled rows stays on the last filled one: nothing
        # is fetched for it and its output block is not written back
        return (jnp.minimum(i, n_ref[0] - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(S // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, c.shape[1]), seen),
                  pl.BlockSpec((block_rows, kr_wide.shape[1]), seen),
                  pl.BlockSpec(w_k.shape, lambda i, n: (0, 0)),
                  pl.BlockSpec(w_v.shape, lambda i, n: (0, 0))],
        out_specs=[pl.BlockSpec((block_rows, w_k.shape[1]), seen),
                   pl.BlockSpec((block_rows, w_v.shape[1]), seen)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, w_k.shape[1]), c.dtype),
                   jax.ShapeDtypeStruct((S, w_v.shape[1]), c.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.reshape(n_rows // block_rows, (1,)).astype(jnp.int32), c, kr_wide,
      w_k, w_v)


def fill_operands(kr_cache, w_b, num_heads: int, nope: int, g: int, dtype):
    """What :func:`index_fill_kv` reads beside the latent rows, made once
    for all the groups of ``g`` heads: ``(kr_wide [S,dk], w_k [H/g, rank,
    g·dk], w_v [H/g, rank, g·v])`` in ``dtype`` — the rope key in a key's
    last columns, and a head's key columns with that place left empty."""
    rank, rope, groups = w_b.shape[0], kr_cache.shape[1], num_heads // g
    w = w_b.astype(dtype).reshape(rank, groups, g, -1)       # [.., nope+v]
    w_k = jnp.pad(w[..., :nope], ((0, 0),) * 3 + ((0, rope),))
    return (jnp.pad(kr_cache.astype(dtype), ((0, 0), (nope, 0))),
            jnp.moveaxis(w_k, 1, 0).reshape(groups, rank, -1),
            jnp.moveaxis(w[..., nope:], 1, 0).reshape(groups, rank, -1))


def masked_chunk_attention(q_nope, q_rope, c_cache, kr_cache, keep, start,
                           w_b, scale: float, dtype,
                           kernel: str | None = None,
                           heads_per_pass: int = HEADS_PER_PASS):
    """A chunk of ``C`` queries at positions ``start …`` over the keys
    ``keep`` [C,S] int8 marks, of a latent cache that already holds the
    chunk's own rows. ``q_nope`` [C,H,nope], ``q_rope`` [C,H,r] (roped),
    ``c_cache`` [S,rank], ``kr_cache`` [S,r], ``w_b`` [rank, H·(nope+v)].

    ``heads_per_pass`` heads at a time: ONE kernel (:func:`index_fill_kv`)
    decompresses their rows below the chunk's end into a workspace — a key
    ``[c W_k | k_rope]``, a value ``c W_v``, in the layout the attention
    kernel reads — and writes nothing past them (the attention kernel
    neither fetches nor computes a key tile past a query tile's last row);
    then the chunk runs the blocked softmax under the mask over it. Nothing
    ``C × S`` exists but the mask, and the workspace is a group's, not the
    layer's (4.3 GB at 64 heads × 65 536 rows). Answers [C,H,v] in
    ``dtype``."""
    kernel = _kernel_of(kernel)
    C, H, nope = q_nope.shape
    S = c_cache.shape[0]
    v = w_b.shape[1] // H - nope
    if kernel == "lax":
        kv = jnp.dot(c_cache.astype(dtype), w_b.astype(dtype),
                     preferred_element_type=jnp.float32,
                     precision=_precision_of(jnp.dtype(dtype))
                     ).reshape(S, H, nope + v)
        q = jnp.concatenate([q_nope, q_rope], -1) * scale
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            kr_cache[:, None].astype(jnp.float32),
            (S, H, kr_cache.shape[-1]))], -1)
        return masked_attention_lax(q, k, kv[..., nope:], keep,
                                    dtype).astype(dtype)
    g = math.gcd(H, heads_per_pass)
    rope = q_rope.shape[-1]
    if S % C:
        raise ValueError(f"a cache of {S} rows is not whole chunks of {C}")
    # a key tile divides the chunk: no tile the attention kernel fetches
    # holds a row the fill has not written
    bq, bk = math.gcd(C, CORE_TILE[0]), math.gcd(C, CORE_TILE[1])
    part = math.gcd(bk, CORE_PART)
    if kernel == "pallas":
        note_causal("index_select", H, nope + rope, C, S, dtype, bq, bk)
    q = (jnp.concatenate([q_nope, q_rope], -1) * scale).astype(dtype)
    q = jnp.swapaxes(q.reshape(C, H // g, g * (nope + rope)), 0, 1)
    c_cache = c_cache.astype(dtype)
    kr_wide, w_k, w_v = fill_operands(kr_cache, w_b, H, nope, g, dtype)
    n_rows = jnp.minimum((start + 2 * C - 1) // C * C, S)

    def one_group(xs):
        q_g, wk_g, wv_g = xs
        k_ws, v_ws = index_fill_kv(
            c_cache, kr_wide, wk_g, wv_g, n_rows, num_heads=g, nope=nope,
            block_rows=math.gcd(C, FILL_ROWS),
            interpret=kernel == "interpret")
        # the barrier keeps the kernel a call of its own: fused into the
        # write of the group's rows it loses its VMEM limit (16 MiB, 20 needed)
        return jax.lax.optimization_barrier(index_masked_mha(
            q_g, k_ws, v_ws, keep, start, num_heads=g, block_q=bq,
            block_k=bk, part=part, interpret=kernel == "interpret"))

    o = jax.lax.map(one_group, (q, w_k, w_v))              # [H/g, C, g·v]
    return jnp.swapaxes(o, 0, 1).reshape(C, H, v)


# --- decode: one row ---------------------------------------------------------


def top_rows(score, pos, topk: int):
    """One row's selection from its scores ``score`` [S]: ``(rows [k] int32,
    valid [k] bool)``, ``k = min(topk, S)`` — the positions ``≤ pos`` of
    largest score, ties to the lower one (``lax.top_k``'s rule); ``valid``
    marks the places a short prefix fills."""
    S = score.shape[0]
    score = jnp.where(jnp.arange(S) <= pos, score, -jnp.inf)
    value, rows = jax.lax.top_k(score, min(topk, S))
    return rows.astype(jnp.int32), value > -jnp.inf


def index_step(q_i, w, ki_cache, pos, topk: int, dtype):
    """One query at ``pos`` against the index cache (its own row written):
    ``q_i`` [J,d], ``w`` [J] float32, ``ki_cache`` [S,d]. Answers
    :func:`top_rows` of its scores."""
    return top_rows(index_scores_lax(q_i[None], w[None], ki_cache, dtype)[0],
                    pos, topk)


def absorbed_rows_step(q_nope, q_rope, c_rows, kr_rows, valid, w_b,
                       scale: float, dtype):
    """``latent_attention.mla_absorbed_step`` over GIVEN rows of the latent
    cache: ``c_rows`` [k,rank], ``kr_rows`` [k,r], ``valid`` [k] (which of
    them the query reads); ``q_nope`` [H,nope], ``q_rope`` [H,r] (roped);
    ``w_b`` 2-D as stored or in ``absorbed_form``. Answers [H,v]."""
    H, nope = q_nope.shape
    w = w_b.reshape(w_b.shape[0], H, -1).astype(dtype)   # [rank,H,nope+v]
    q_c = jnp.einsum("hd,chd->hc", q_nope.astype(dtype), w[..., :nope],
                     preferred_element_type=jnp.float32)
    s = (jnp.einsum("hc,tc->ht", q_c.astype(dtype), c_rows.astype(dtype),
                    preferred_element_type=jnp.float32)
         + jnp.einsum("hr,tr->ht", q_rope.astype(dtype),
                      kr_rows.astype(dtype),
                      preferred_element_type=jnp.float32)) * scale
    p = jax.nn.softmax(jnp.where(valid[None, :], s, -jnp.inf), axis=-1)
    ctx = jnp.einsum("ht,tc->hc", p.astype(dtype), c_rows.astype(dtype),
                     preferred_element_type=jnp.float32)
    return jnp.einsum("hc,chv->hv", ctx.astype(dtype), w[..., nope:],
                      preferred_element_type=jnp.float32)
