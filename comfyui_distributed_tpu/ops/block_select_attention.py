"""Causal attention over a SELECTION of key blocks (InfLLM-v2's rule):
every query chooses, per key/value group, which ``block_size``-row blocks
of the cache it reads, by scoring COMPRESSED keys.

For the query at position ``t`` and key/value group ``g`` (``J`` heads):

1. compressed key ``K̄_j = mean(K[stride·j : stride·j + kernel])`` for every
   ``j`` whose ``kernel`` rows all lie at or below ``t`` (``kernel = 2 ·
   stride``: a window every ``stride`` rows, each row in two);
2. ``p_h = softmax_j(q_h · K̄_j · scale)`` a head, ``s_g[j] = Σ_{h∈g} p_h[j]``;
3. block score ``B_g[b] = max`` of ``s_g`` over the windows that touch rows
   ``block·b … block·b + block − 1`` (``block / stride + 1`` of them);
4. block 0 (``init_blocks``) and the ``window / block`` blocks that end at
   the query's own are FORCED (``+∞``), blocks past its own excluded
   (``−∞``); the table is the top ``topk + window / block`` of ``B_g``;
5. softmax attention of each head over the rows ``≤ t`` of those blocks.

**The compressed-key cache** (:func:`compress_chunk`, :func:`compress_step`)
holds ``K̄_j`` at SLOT ``j + 1``: slot ``s`` is complete once row ``stride ·
(s + 1) − 1`` is written, a chunk that starts at ``start`` writes exactly
the slots ``start / stride …`` (slot 0 never holds a window), and block
``b`` is touched by the slots ``per·b … per·b + per`` (``per = block /
stride``). A padded chunk writes only the slots its ``n_valid`` rows
complete — the buffer advances at a ``stride``-th of the token rate, and a
window that spans two chunks is completed by the second.

**Scores and the selection are float32** (:func:`block_scores`,
:func:`select`); the selection is the ``table`` best blocks, ties to the
lower index (two neighbouring blocks share the window that straddles them,
so equal scores are the rule's own, not an accident of rounding). A
chunk's NEIGHBOURING queries have their group sums ``s_g`` made by a
two-pass Pallas kernel (:func:`block_score_sums`: a head row's maximum and
sum of exponentials over the slot tiles it can see, then the normalised
tiles summed over a group's heads — no per-head logit reaches HBM, and a
slot tile no query of the tile sees whole is neither fetched nor
computed); ``lax`` writes the softmax out plainly — the CPU's default, one
decoded token's form, and what the kernel is held to.

**The chunk form** (:func:`sparse_chunk`) serves ``block_q`` NEIGHBOURING
queries' ``J · block_q`` head rows a tile: what neighbours share is the
tile's UNION of blocks — an ascending list a tile, handed to the Pallas
kernel (:func:`block_select_mha`) as a scalar-prefetched table that its
own copies of K and V rows follow (``blocks_per_step`` blocks a grid step:
ONE stretch of the cache where the entries are consecutive, else a copy a
block); a per-(query, union entry) mask says which entries are each query's
own, and steps past a tile's count fetch nothing. Neighbours that
choose alike cost what they selected; neighbours that choose apart cost
their union, never more than the causal prefix. ``lax``: the masked
softmax over all rows, plainly — what the kernel is held to.
:func:`sparse_step` is one decoded token: it GATHERS its table's blocks.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention
from .flash_attention import _LANES, NEG_INF
from .flash_latent import (_VMEM_LIMIT_BYTES, _accumulate, _init_running,
                           _precision_of, _running_scratch)


class Selection(NamedTuple):
    """The sizes of the rule (a model config's fields)."""
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64

    @property
    def per(self) -> int:
        """Compressed slots a block."""
        return self.block_size // self.kernel_stride

    @property
    def local_blocks(self) -> int:
        return self.window_size // self.block_size

    @property
    def table(self) -> int:
        """Blocks a query reads at most: ``topk`` with the initial ones
        among them, the local ones on top."""
        return self.topk + self.local_blocks

    def check(self) -> None:
        if self.kernel_size != 2 * self.kernel_stride \
                or self.block_size % self.kernel_stride \
                or self.window_size % self.block_size:
            raise ValueError(
                "the compressed cache is written for windows of two "
                "strides and blocks of whole strides")


# --- the compressed-key cache ------------------------------------------------


def compress_chunk(kc, k_cache, k_chunk, start, n_valid, sel: Selection):
    """``kc`` [G, Sc, d] with the slots a chunk completes written:
    ``k_chunk`` [G, C, d] are rows ``start …`` (``start`` a multiple of the
    stride), ``k_cache`` [G, S, d] holds the rows below them, the first
    ``n_valid`` rows of the chunk are real."""
    G, C, d = k_chunk.shape
    st = sel.kernel_stride
    n = -(-C // st)
    prev = jax.lax.dynamic_slice(
        k_cache, (0, jnp.maximum(start - st, 0), 0), (G, st, d))
    rows = jnp.concatenate([prev, k_chunk], axis=1).astype(jnp.float32)
    rows = jnp.pad(rows, ((0, 0), (0, n * st - C), (0, 0)))
    halves = rows.reshape(G, n + 1, st, d).sum(axis=2)
    mean = (halves[:, :-1] + halves[:, 1:]) / sel.kernel_size
    slot = start // st + jnp.arange(n)
    done = (slot >= 1) & (st * (slot + 1) <= start + n_valid)
    at = (0, start // st, 0)
    old = jax.lax.dynamic_slice(kc, at, (G, n, d))
    return jax.lax.dynamic_update_slice(
        kc, jnp.where(done[None, :, None], mean.astype(kc.dtype), old), at)


def compress_step(kc, k_cache, pos, sel: Selection):
    """``kc`` after the token at ``pos`` wrote its key row into ``k_cache``
    [G, S, d]: one more slot where that row completes a window."""
    G, _, d = k_cache.shape
    st, ks = sel.kernel_stride, sel.kernel_size
    done = ((pos + 1) % st == 0) & (pos + 1 >= ks)
    rows = jax.lax.dynamic_slice(
        k_cache, (0, jnp.maximum(pos + 1 - ks, 0), 0), (G, ks, d))
    mean = rows.astype(jnp.float32).mean(axis=1, keepdims=True)
    at = (0, jnp.maximum((pos + 1) // st - 1, 0), 0)
    old = jax.lax.dynamic_slice(kc, at, (G, 1, d))
    return jax.lax.dynamic_update_slice(
        kc, jnp.where(done, mean.astype(kc.dtype), old), at)


# --- scores and the selection ------------------------------------------------


def _group_sums(q, kc, pos, stride: int):
    """``s_g`` written out: ``q`` [Q, H, d] (times the scale) at ``pos``
    against ``kc`` [G, Sc, d], float32 [G, Q, Sc]."""
    Q, H, d = q.shape
    G, Sc, _ = kc.shape
    heads = H // G
    # a group's head rows stacked [G, Q·J, d]: one plain product a group
    # whose minor axis is the slots (a [G,Q,J,Sc] einsum is laid out with
    # the queries minor on the chip, and its softmax crawls)
    qg = jnp.swapaxes(q.reshape(Q, G, heads, d), 0, 1).reshape(G, Q * heads, d)
    logits = jnp.einsum("gmd,gsd->gms", qg, kc,
                        preferred_element_type=jnp.float32)
    slot = jnp.arange(Sc)
    whole = (slot[None, :] >= 1) \
        & (stride * (slot[None, :] + 1) <= pos[:, None] + 1)  # [Q,Sc]
    whole = jnp.repeat(whole, heads, axis=0)[None]            # [1,Q·J,Sc]
    # the softmax written out, its two row reductions behind a barrier:
    # left to itself the chip's compiler turns ``x − max(x)`` and ``e /
    # sum(e)`` into reduce-windows as wide as two rows (1.9 s a layer a
    # prefill: PERF.md §6, PR 47). A row with no whole window reads 0.
    logits = jnp.where(whole, logits, NEG_INF)
    top = jax.lax.optimization_barrier(logits.max(axis=-1, keepdims=True))
    e = jnp.where(whole, jnp.exp(logits - top), 0.0)
    norm = jax.lax.optimization_barrier(e.sum(axis=-1, keepdims=True))
    return (e / jnp.maximum(norm, 1e-30)).reshape(G, Q, heads, Sc).sum(axis=2)


# the scoring kernel's tile: neighbouring queries (× a group's heads) and
# compressed slots, fixed by scripts/score_tile_sweep.py on the chip
SCORE_BLOCK_Q, SCORE_BLOCK_SLOTS = 128, 1408


def score_tiles(Q: int, Sc: int, block_q: int = SCORE_BLOCK_Q,
                block_slots: int = SCORE_BLOCK_SLOTS):
    """The scoring kernel's tile of ``Q`` neighbouring queries and ``Sc``
    slots: the asked sizes where they divide, else what does."""
    return math.gcd(Q, block_q), math.gcd(Sc, block_slots)


def last_slot_tile(first, block_q: int, block_slots: int, stride: int,
                   slot_tiles: int, clip=jnp.clip):
    """The last slot tile that holds a window WHOLE for any query of the
    tile whose first position is ``first`` (its last query sees the slots
    ``1 … (first + block_q) / stride − 1``); tile 0 where none does. The
    kernel's index map and body share it with the counter's rule (numpy:
    ``clip=np.clip``)."""
    return clip(((first + block_q) // stride - 1) // block_slots, 0,
                slot_tiles - 1)


def _score_sums_kernel(pos_ref, q_ref, k_ref, o_ref, m_ref, l_ref, *,
                       block_q: int, block_slots: int, heads: int,
                       stride: int, slot_tiles: int, precision):
    i, half, t = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    rows = heads * block_q
    first = pos_ref[0] + i * block_q
    seen = t <= last_slot_tile(first, block_q, block_slots, stride,
                               slot_tiles)
    # every slot of the tile whole for every query of it: slot 0 is not in
    # it and its last slot lies at or below the FIRST query's last
    clear = (t >= 1) & ((t + 1) * block_slots <= (first + 1) // stride)

    @pl.when((half == 0) & (t == 0))
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def logits():
        return jax.lax.dot_general(q_ref[0, 0], k_ref[0],
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32,
                                   precision=precision)

    def whole():
        slot = t * block_slots + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_slots), 1)
        # head-major rows: row j·block_q + n is head j of the tile's query n
        at = first + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) % block_q
        return (slot >= 1) & (stride * (slot + 1) <= at + 1)

    def statistics(masked: bool):
        s = logits()
        if masked:
            own = whole()
            s = jnp.where(own, s, NEG_INF)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        e = jnp.exp(s - m_new)
        if masked:
            e = jnp.where(own, e, 0.0)
        l_new = l_prev * jnp.exp(m_prev - m_new) \
            + jnp.sum(e, axis=-1, keepdims=True)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    def sums(masked: bool):
        p = jnp.exp(logits() - m_ref[:, :1]) \
            * (1.0 / jnp.maximum(l_ref[:, :1], 1e-30))
        if masked:
            p = jnp.where(whole(), p, 0.0)
        o_ref[0] = p.reshape(heads, block_q, block_slots).sum(axis=0)

    for n, body in enumerate((statistics, sums)):
        pl.when((half == n) & seen & clear)(
            functools.partial(body, False))
        pl.when((half == n) & seen & jnp.logical_not(clear))(
            functools.partial(body, True))

    @pl.when((half == 1) & jnp.logical_not(seen))
    def _unseen():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("block_q", "block_slots",
                                             "stride", "interpret"))
def block_score_sums(q, kc, start, block_q: int, block_slots: int,
                     stride: int, interpret: bool):
    """``q`` [G, tiles, J·block_q, d] (a tile's head rows, head-major,
    times the softmax scale) at positions ``start …`` against ``kc`` [G,
    Sc, d] (``Sc`` whole tiles of ``block_slots``): float32 ``[G, Q, Sc]``,
    each head row's softmax over the slots whose window is whole for its
    query, summed over the tile's ``J`` heads. grid = (G, tiles, 2, slot
    tiles): the first half walks the slot tiles for each row's running
    maximum and sum (VMEM scratch), the second walks them again and writes.
    A tile past the last one any query of the tile sees is skipped in both
    (its ``kc`` index repeats the last, so nothing is fetched) and written
    0."""
    G, tiles, rows, d = q.shape
    Sc = kc.shape[1]
    slot_tiles = Sc // block_slots
    kernel = functools.partial(
        _score_sums_kernel, block_q=block_q, block_slots=block_slots,
        heads=rows // block_q, stride=stride, slot_tiles=slot_tiles,
        precision=_precision_of(q.dtype))

    def last(i, pos_ref):
        return last_slot_tile(pos_ref[0] + i * block_q, block_q,
                              block_slots, stride, slot_tiles)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, tiles, 2, slot_tiles),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d),
                         lambda g, i, half, t, pos_ref: (g, i, 0, 0)),
            pl.BlockSpec((1, block_slots, d),
                         lambda g, i, half, t, pos_ref: (
                             g, jnp.minimum(t, last(i, pos_ref)), 0))],
        # the first half writes nothing: it stays on the tile the second
        # half writes first, so no block leaves VMEM before it is written
        out_specs=pl.BlockSpec(
            (1, block_q, block_slots),
            lambda g, i, half, t, pos_ref: (g, i, half * t)),
        scratch_shapes=[pltpu.VMEM((rows, _LANES), jnp.float32),   # max
                        pltpu.VMEM((rows, _LANES), jnp.float32)])  # sum
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, tiles * block_q, Sc),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.reshape(start, (1,)).astype(jnp.int32), q, kc)


def _head_major_tiles(q, G: int, block_q: int):
    """``q`` [Q, H, d] as ``[G, tiles, J·block_q, d]``: row ``j·block_q +
    n`` of a tile is head ``j`` (of the group's ``J``) of its query ``n``."""
    Q, H, d = q.shape
    J, tiles = H // G, Q // block_q
    return q.reshape(tiles, block_q, G, J, d).transpose(2, 0, 3, 1, 4) \
        .reshape(G, tiles, J * block_q, d)


def block_scores(q, kc, pos, scale: float, dtype, sel: Selection,
                 kernel: str | None = None, block_q: int = SCORE_BLOCK_Q,
                 block_slots: int = SCORE_BLOCK_SLOTS):
    """``q`` [Q, H, d] (normed, unscaled) at positions ``pos`` [Q] against
    the compressed cache ``kc`` [G, Sc, d] (``Sc`` a multiple of ``per``):
    float32 ``[G, Q, Sc / per]`` block scores, forced blocks ``+∞``, blocks
    past a query's own ``−∞``. ``kernel``: ``pallas`` (the default on a
    TPU) or ``interpret`` — the two-pass kernel, whose queries are
    NEIGHBOURS (``pos`` is ``pos[0] …``) — or ``lax`` (the default
    elsewhere)."""
    Q, H, d = q.shape
    G, Sc, _ = kc.shape
    per, st = sel.per, sel.kernel_stride
    nb = Sc // per
    if kernel is None:
        kernel = "pallas" if flash_attention._platform() == "tpu" else "lax"
    q = (q * scale).astype(dtype)
    if kernel == "lax":
        s = _group_sums(q, kc.astype(dtype), pos, st)
    else:
        bq, slots = score_tiles(Q, Sc, block_q, block_slots)
        s = block_score_sums(_head_major_tiles(q, G, bq), kc.astype(dtype),
                             pos[0], block_q=bq, block_slots=slots,
                             stride=st, interpret=kernel == "interpret")
    a = s.reshape(G, Q, nb, per)
    after = jnp.concatenate(
        [a[:, :, 1:, 0], jnp.zeros((G, Q, 1), jnp.float32)], axis=2)
    score = jnp.maximum(a.max(axis=-1), after)
    own = (pos // sel.block_size)[None, :, None]
    b = jnp.arange(nb)[None, None, :]
    forced = (b < sel.init_blocks) | (b > own - sel.local_blocks)
    return jnp.where(b > own, -jnp.inf, jnp.where(forced, jnp.inf, score))


def select(score, sel: Selection):
    """The blocks each (group, query) reads: ``[G, Q, nb]`` bool — the
    ``table`` best of the scores, never an excluded one. Neighbouring
    blocks SHARE the window that straddles them, so equal scores are
    common: among blocks tied at the last place the lower index wins, as
    ``lax.top_k`` and a stable ``argsort`` have it."""
    table = min(sel.table, score.shape[-1])
    kth = jax.lax.top_k(score, table)[0][..., -1:]
    above, tied = score > kth, score == kth
    room = table - above.sum(axis=-1, keepdims=True)
    first = jnp.cumsum(tied, axis=-1) <= room
    return (above | (tied & first)) & (score > -jnp.inf)


# --- the chunk form ------------------------------------------------------------


# a grid step of the table-driven kernel by how its K and V rows arrive
# (``cdt_llm_sparse_steps_total``'s labels, in the counts' order)
STEP_FETCHES = ("run", "blocks", "skipped")


def _visible(e, count, per_step: int):
    """Does step ``e`` of a tile whose union holds ``count`` blocks hold
    one? (Step 0 always: every query reads its own block.)"""
    return (e == 0) | (e * per_step < count)


def _is_run(first, last, per_step: int):
    """Are a step's ``per_step`` ascending entries CONSECUTIVE blocks?
    (Entries past a tile's count repeat the last, so a step the count cuts
    is never one.)"""
    return last - first == per_step - 1


def _kv_copies(act, union_ref, k_hbm, v_hbm, k_buf, v_buf, sem, tile, e,
               slot, *, block: int, per_step: int, tiles: int,
               union_len: int):
    """``act`` (start, or wait for) the copies that bring the K and V rows
    of step ``e`` of ``tile`` into buffer ``slot``: ONE copy each of
    ``per_step · block`` rows of the cache where the step's entries are a
    run, else a copy a block — either way the rows land where the step
    reads them, side by side."""
    base = tile * union_len + e * per_step
    g, first = tile // tiles, union_ref[base]

    def both(at, rows: int, to: int):
        at = pl.multiple_of(at * block, block)
        for n, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
            act(pltpu.make_async_copy(hbm.at[g, pl.ds(at, rows)],
                                      buf.at[slot, pl.ds(to, rows)],
                                      sem.at[n, slot]))

    run = _is_run(first, union_ref[base + per_step - 1], per_step)
    pl.when(run)(lambda: both(first, per_step * block, 0))

    @pl.when(jnp.logical_not(run))
    def _pieces():
        for r in range(per_step):
            both(union_ref[base + r], block, r * block)


def _spread_own(own, union_ref, base, *, block: int):
    """A step's per-query mask ``own`` [block_q, R] (1 where the query
    reads the union entry) spread over its blocks' columns ``[block_q, R ·
    block]``, and each column's position in the cache ``[1, R · block]``
    (``base``: the step's first entry in the flat table)."""
    block_q, per_step = own.shape
    width = per_step * block
    col = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    mine = jnp.zeros((block_q, width), jnp.float32)
    col_pos = jnp.zeros((1, width), jnp.int32)
    for r in range(per_step):
        here = (col // block) == r
        mine = jnp.where(here, own[:, r:r + 1], mine)
        col_pos = jnp.where(here, union_ref[base + r] * block + col % block,
                            col_pos)
    return mine, col_pos


def _own_logits(s, own, union_ref, base, first_row, *, block: int,
                heads: int):
    """The logits ``s`` [heads · block_q, R · block] (head-major) of a
    step with every column a row's QUERY may not read at ``NEG_INF``: it
    reads its own entries of the union and in them the rows at or below
    its position (``first_row`` the tile's first)."""
    mine, col_pos = _spread_own(own, union_ref, base, block=block)
    row_pos = first_row + jax.lax.broadcasted_iota(
        jnp.int32, (own.shape[0], 1), 0)
    seen = jnp.where(col_pos <= row_pos, mine, 0.0)
    seen = jnp.concatenate([seen] * heads, axis=0)            # head-major
    return jnp.where(seen > 0.5, s, NEG_INF)


def _block_select_kernel(union_ref, count_ref, pos_ref, q_ref, mask_ref,
                         k_hbm, v_hbm, o_ref, m_ref, l_ref, acc_ref, k_buf,
                         v_buf, sem, slot_ref, *, block_q: int, block: int,
                         per_step: int, heads: int, tiles: int,
                         all_tiles: int, union_len: int, precision):
    g, i, e = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    tile = g * tiles + i
    count = count_ref[tile]
    copies = functools.partial(
        _kv_copies, union_ref=union_ref, k_hbm=k_hbm, v_hbm=v_hbm,
        k_buf=k_buf, v_buf=v_buf, sem=sem, block=block, per_step=per_step,
        tiles=tiles, union_len=union_len)
    _init_running(e, m_ref, l_ref, acc_ref)

    @pl.when((tile == 0) & (e == 0))
    def _first():
        slot_ref[0] = 0
        copies(lambda c: c.start(), tile=tile, e=e, slot=0)

    @pl.when(_visible(e, count, per_step))
    def _step():
        # the NEXT visible step's rows set out before this one's are
        # waited for: the tile's next step, else the next tile's first
        slot = slot_ref[0]
        slot_ref[0] = 1 - slot
        more = (e + 1) * per_step < count

        @pl.when(more | (tile + 1 < all_tiles))
        def _next():
            copies(lambda c: c.start(), tile=jnp.where(more, tile, tile + 1),
                   e=jnp.where(more, e + 1, 0), slot=1 - slot)

        copies(lambda c: c.wait(), tile=tile, e=e, slot=slot)
        s = jax.lax.dot_general(q_ref[0, 0], k_buf[slot],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=precision)
        s = _own_logits(s, mask_ref[0, 0, 0], union_ref,
                        tile * union_len + e * per_step,
                        pos_ref[0] + i * block_q, block=block, heads=heads)
        _accumulate(s, v_buf[slot], m_ref, l_ref, acc_ref, precision)

    @pl.when(e == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def block_select_mha(q, k, v, union, count, mask, start, block: int,
                     interpret: bool):
    """``q`` [G, tiles, J·block_q, d] (a tile's head rows, head-major,
    times the softmax scale); ``k``, ``v`` [G, S, d] with ``S`` a multiple
    of ``block``; ``union`` [G, tiles, U] int32 — each tile's blocks,
    ascending, entries past ``count`` [G, tiles] repeating the last;
    ``mask`` [G, tiles, U / R, block_q, R] float32, 1 where the query reads
    the entry; ``start`` the first query's position. The table is
    prefetched and the K and V rows of grid step ``(g, i, e)`` follow it
    by the kernel's own copies (``k`` and ``v`` stay in HBM), twice
    buffered: the blocks ``union[g, i, e·R …]`` side by side, fetched as
    ONE stretch of ``R · block`` rows where they are consecutive and a
    block at a time where they are not; a step past the count fetches
    nothing, not even its mask. The steps run in the grid's order on one
    core (a step sets out the next one's copies). Answers as ``q``."""
    G, tiles, rows, d = q.shape
    _, _, steps, block_q, per_step = mask.shape
    U = union.shape[-1]
    kernel = functools.partial(
        _block_select_kernel, block_q=block_q, block=block,
        per_step=per_step, heads=rows // block_q, tiles=tiles,
        all_tiles=G * tiles, union_len=U, precision=_precision_of(q.dtype))

    def last_step(g, i, count_ref):
        return jnp.maximum(-(-count_ref[g * tiles + i] // per_step) - 1, 0)

    tile_spec = pl.BlockSpec((1, 1, rows, d),
                             lambda g, i, e, *_: (g, i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(G, tiles, steps),
        in_specs=[tile_spec,
                  pl.BlockSpec((1, 1, 1, block_q, per_step),
                               lambda g, i, e, union_ref, count_ref, *_: (
                                   g, i, jnp.minimum(
                                       e, last_step(g, i, count_ref)), 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tile_spec,
        scratch_shapes=_running_scratch(rows, d) + [
            pltpu.VMEM((2, per_step * block, d), k.dtype),
            pltpu.VMEM((2, per_step * block, d), v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),                  # (K|V, slot)
            pltpu.SMEM((1,), jnp.int32)])           # the slot to fill next
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(union.reshape(-1).astype(jnp.int32),
      count.reshape(-1).astype(jnp.int32),
      jnp.reshape(start, (1,)).astype(jnp.int32), q, mask, k, v)


def tile_unions(chosen, block_q: int, per_step: int):
    """From ``chosen`` [G, Q, nb]: each tile of ``block_q`` queries' union
    of blocks ``[G, tiles, U]`` (ascending; ``U`` = ``nb`` rounded up to
    the step; entries past the count repeat the last), the counts ``[G,
    tiles]``, the per-query mask ``[G, tiles, U / R, block_q, R]`` and the
    grid's steps by how their rows arrive, int32 ``[3]`` in
    ``STEP_FETCHES``' order (the kernel's own two rules over the table)."""
    G, Q, nb = chosen.shape
    tiles = Q // block_q
    U = -(-nb // per_step) * per_step
    own = chosen.reshape(G, tiles, block_q, nb)
    any_ = own.any(axis=2)
    count = any_.sum(axis=-1).astype(jnp.int32)
    order = jnp.argsort(~any_, axis=-1, stable=True).astype(jnp.int32)
    entry = jnp.minimum(jnp.arange(U), count[..., None] - 1)
    union = jnp.take_along_axis(order, jnp.maximum(entry, 0), axis=-1)
    mask = jnp.take_along_axis(own, union[:, :, None, :], axis=-1) \
        & (jnp.arange(U) < count[..., None])[:, :, None, :]
    mask = mask.reshape(G, tiles, block_q, U // per_step, per_step)
    by_step = union.reshape(G, tiles, U // per_step, per_step)
    visible = _visible(jnp.arange(U // per_step), count[..., None], per_step)
    runs = (visible & _is_run(by_step[..., 0], by_step[..., -1],
                              per_step)).sum()
    seen = visible.sum()
    fetches = jnp.stack([runs, seen - runs,
                         visible.size - seen]).astype(jnp.int32)
    return union, count, jnp.swapaxes(mask, 2, 3).astype(jnp.float32), fetches


def sparse_chunk(q, k, v, chosen, start, scale: float, dtype,
                 sel: Selection, block_q: int = 64, per_step: int = 16,
                 kernel: str | None = None):
    """``q`` [Q, H, d] at rows ``start …`` of ``k``, ``v`` [G, S, d] (their
    own rows written), each over the blocks ``chosen`` [G, Q, nb] gives it
    and in them the rows at or below its own. ``kernel``: ``pallas`` (the
    default on a TPU), ``interpret`` or ``lax`` (the default elsewhere).
    Answers [Q, H, d] in ``dtype`` and the kernel's grid steps by
    ``STEP_FETCHES`` (int32 ``[3]``; ``lax`` has no grid: zeros)."""
    Q, H, d = q.shape
    G, S, _ = k.shape
    J, bs = H // G, sel.block_size
    if kernel is None:
        kernel = "pallas" if flash_attention._platform() == "tpu" else "lax"
    q = (q * scale).astype(dtype)
    if kernel == "lax":
        from .gqa_attention import _masked_softmax_rows

        row = (start + jnp.arange(Q))[:, None]
        col = jnp.arange(S)[None, :]
        seen = jnp.repeat(chosen, bs, axis=-1)[:, :, :S] & (col <= row)[None]
        outs = [_masked_softmax_rows(q[:, g * J:(g + 1) * J], k[g:g + 1],
                                     v[g:g + 1], seen[g], dtype)
                for g in range(G)]
        return (jnp.concatenate(outs, axis=1).astype(dtype),
                jnp.zeros((len(STEP_FETCHES),), jnp.int32))
    bq = math.gcd(Q, block_q)
    tiles = Q // bq
    if kernel == "pallas":
        from .attention import note_causal

        note_causal("block_select", H, d, Q, S, dtype, bq, per_step * bs)
    union, count, mask, fetches = tile_unions(chosen, bq, per_step)
    o = block_select_mha(_head_major_tiles(q, G, bq), k.astype(dtype),
                         v.astype(dtype), union, count, mask, start,
                         block=bs, interpret=kernel == "interpret")
    return o.reshape(G, tiles, J, bq, d).transpose(1, 3, 0, 2, 4) \
        .reshape(Q, H, d), fetches


# --- one decoded token ---------------------------------------------------------


def sparse_step(q, k, v, kc, pos, scale: float, dtype, sel: Selection):
    """One token's ``q`` [H, d] (normed, unscaled) at ``pos`` over the
    blocks it selects of ``k``, ``v`` [G, S, d] (its own row written, ``kc``
    up to date): the table's blocks are GATHERED — ``table · block`` rows a
    group, whatever the cache holds (the two halves under the named scopes
    ``select`` and ``sparse_core``). Float32 [H, d], and the selection
    ``[G, blocks]`` bool (a parity tool's; nobody else computes it)."""
    H, d = q.shape
    G, S, _ = k.shape
    J, bs = H // G, sel.block_size
    with jax.named_scope("select"):
        # one query's 16 rows × the slots: the plain form (no kernel in
        # the token loop)
        score = block_scores(q[None], kc, jnp.reshape(pos, (1,)), scale,
                             dtype, sel, kernel="lax")[:, 0]     # [G, nb]
        top, table = jax.lax.top_k(score, min(sel.table, score.shape[-1]))
        held = top > -jnp.inf
    with jax.named_scope("sparse_core"):
        rows = (table[:, :, None] * bs + jnp.arange(bs)).reshape(G, -1)
        seen = jnp.repeat(held, bs, axis=-1) & (rows <= pos)
        kb, vb = (jax.vmap(lambda blocks, t: blocks[t])(
            a.reshape(G, S // bs, bs, d), table).reshape(G, -1, d)
            for a in (k, v))
        qg = (q * scale).reshape(G, J, d).astype(dtype)
        s = jnp.einsum("gjd,gsd->gjs", qg, kb.astype(dtype),
                       preferred_element_type=jnp.float32)
        p = jax.nn.softmax(jnp.where(seen[:, None], s, NEG_INF), axis=-1)
        o = jnp.einsum("gjs,gsd->gjd", p.astype(dtype), vb.astype(dtype),
                       preferred_element_type=jnp.float32)
    chosen = ((table[:, :, None] == jnp.arange(score.shape[-1]))
              & held[:, :, None]).any(axis=1)
    return o.reshape(H, d), chosen
