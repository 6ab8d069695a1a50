"""The packed flash call over TWO row segments (MMDiT joint attention).

A joint block projects its text rows and its image rows apart and attends
over both. Handing the one-segment call (``flash_attention.
_flash_mha_packed``) one ``[B, T+N, H·D]`` triple costs a concatenation of
q, k and v, their padding to block multiples and the way back — 205 MB a
block at SD3's 77 + 4096 rows, none of it mathematics (PERF.md §6, PR 41).
This call reads each segment where its projection wrote it and writes each
segment's answer where its output projection reads it:

- **Operands are column groups.** q, k and v of a segment arrive as
  :class:`ops.attention.Columns` — an array ``[B, N, n·H·D]`` and which
  ``H·D``-wide group of it — and the ``BlockSpec`` index maps pick the head group's
  lanes out of that array (``H·D`` is a multiple of 128). The ``qkv``
  product's own output is passed three times; nothing is sliced in HBM.
  What a model transforms between product and attention (qk-norm, rope) is
  a buffer of its own already, and is group 0 of 1.
- **K and V stay resident**, fetched once a (batch, head group): the image
  rows whole (no padding: the call is taken only where they are
  lane-aligned) and a text tile padded with ZEROS to a lane tile — a
  garbage row would survive the mask as ``0 × NaN`` in the value product.
  The tile's padding columns are masked as the one-segment call masks its
  K tail.
- **One call, two outputs.** The q axis of the grid walks the image's q
  blocks and then the text's (one for SD3's 77 → 80 rows): the same step
  body at two tile heights, each writing its own output. The index maps of
  the other segment's tiles stand still meanwhile, so nothing is fetched
  or written twice.

The softmax is the one-segment kernel's: f32 logits, max, exp, sum and
accumulator, MXU operands in the operand dtype, D=64 heads stacked two a
pass (``_stack_group_heads``). Keys enter in :func:`joint_slabs`' order,
text first, so results agree with the one-segment call to rounding.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (_LANES, _PACKED_BLOCK_Q, _PACKED_SLAB,
                              _PACKED_VMEM_BUDGET_BYTES,
                              _PACKED_VMEM_LIMIT_BYTES, _SUBLANES,
                              _mask_k_tail, _packed_group, _round_up,
                              _scale_folds_into_q, _stack_group_heads,
                              _unstack_group_heads)


# the body unrolls its softmax steps (SD3 three, FLUX five); a longer walk —
# 8192 image rows and up — is the one-segment kernel's, which loops
_JOINT_MAX_STEPS = 6


class JointPlan(NamedTuple):
    """Tiles of one two-segment call: q rows of an image step and of a text
    step, the text steps, the rows of the zero-padded text tile, and the
    softmax steps of one q tile (:func:`joint_slabs`)."""

    img_block_q: int
    txt_block_q: int
    txt_steps: int
    txt_rows: int
    slabs: tuple

    def label(self, img_len: int) -> str:
        return (f"{self.img_block_q}+{self.txt_block_q}/"
                f"{img_len}+{self.txt_rows}:k-resident")


def joint_plan(txt_len: int, img_len: int, head_dim: int,
               itemsize: int) -> Optional[JointPlan]:
    """The tiles of the two-segment call at this geometry, or None where it
    cannot be taken and the segments are concatenated for the one-segment
    call: image rows that are no whole lane tiles (their K tile would need
    padding, which is a copy), K/V that do not fit VMEM whole, or a walk
    of more softmax steps than the body unrolls."""
    if img_len % _LANES:
        return None
    sublanes = _SUBLANES * max(1, 4 // itemsize)
    img_block_q = max(b for b in range(_LANES, _PACKED_BLOCK_Q + 1, sublanes)
                      if img_len % b == 0)
    txt_steps = -(-txt_len // _PACKED_BLOCK_Q)
    txt_block_q = _round_up(-(-txt_len // txt_steps), sublanes)
    txt_rows = _round_up(max(txt_len, txt_steps * txt_block_q), _LANES)
    slabs = joint_slabs(txt_rows, img_len, head_dim)
    if len(slabs) > _JOINT_MAX_STEPS:
        return None
    if _joint_vmem_bytes(head_dim, img_block_q, txt_block_q,
                         txt_rows + img_len, slabs,
                         itemsize) > _PACKED_VMEM_BUDGET_BYTES:
        return None
    return JointPlan(img_block_q, txt_block_q, txt_steps, txt_rows, slabs)


def _joint_vmem_bytes(head_dim: int, img_block_q: int, txt_block_q: int,
                      kv_rows: int, slabs: tuple, itemsize: int) -> int:
    """Scoped VMEM of one grid step, as ``_packed_vmem_bytes`` counts the
    one-segment call's: double-buffered tiles of one head group — BOTH
    segments' q and out tiles and all the resident K/V rows, which the
    compiler counts to the byte — and what the body holds: the stacked q,
    the widest softmax step's logits (6 × itemsize a logit covers the
    compiler's stack), the f32 accumulator and its addend, once for the
    image step's tile height and once for the text step's (two bodies
    under ``pl.when``: the compiler's count at FLUX's 512-row text tile is
    past what the taller body alone explains; docs/kernels.md)."""
    width, heads = _packed_group(head_dim)
    widest = max(sum(r for _, _, r in pieces) for pieces in slabs)
    io = 2 * (2 * (img_block_q + txt_block_q) + 2 * kv_rows) * width * itemsize
    rows = heads * (img_block_q + txt_block_q)
    body = (rows * width * itemsize + rows * widest * 6 * itemsize
            + 2 * rows * width * 4)
    return io + body


# segments of a slab piece
_TXT, _IMG = 0, 1


def joint_slabs(txt_rows: int, img_len: int, head_dim: int) -> tuple:
    """The softmax steps of one q tile: each a tuple of ``(segment, start,
    rows)`` pieces of resident K/V whose logits share one max / exp / sum
    and one rescale of the accumulator. The text tile rides with the head
    of the image rows — two products, no step of its own — and the walk is
    cut every 1536 rows where two D=64 heads are stacked a pass (1024 q
    rows a tile) and every 1024 where the group is one head: SD3's 128 +
    4096 rows are steps of 1536, 1536 and 1152, FLUX's 512 + 4096 four of
    1024 and one of 512. Measured on the v5e against equal slabs, a text
    step of its own and eleven other lengths a model
    (``scripts/joint_slab_sweep.py``; the table: PERF.md §6, PR 41)."""
    slab = _PACKED_SLAB if _packed_group(head_dim)[1] > 1 else 1024
    first = min(max(slab - txt_rows, 0), img_len)
    steps = [((_TXT, 0, txt_rows),) + (((_IMG, 0, first),) if first else ())]
    for start in range(first, img_len, slab):
        steps.append(((_IMG, start, min(slab, img_len - start)),))
    return tuple(steps)


def _joint_kernel(tq_ref, iq_ref, tk_ref, ik_ref, tv_ref, iv_ref,
                  to_ref, io_ref, *, txt_len: int, img_steps: int,
                  slabs: tuple, head_dim: int, precision):
    """One (batch, head group, q tile) step: the q tile — an image block
    for the first ``img_steps`` steps of the q axis, a text block after —
    against the group's resident text and image K/V."""
    scale = head_dim ** -0.5
    k_refs, v_refs = (tk_ref, ik_ref), (tv_ref, iv_ref)

    def attend(q_ref, o_ref):
        block_q = q_ref.shape[1]
        q = q_ref[0]
        fold = _scale_folds_into_q(head_dim, q.dtype)
        if fold:
            q = (q.astype(jnp.float32) * scale).astype(q.dtype)
        qs = _stack_group_heads(q, head_dim)
        m = l = acc = None
        for pieces in slabs:
            logits = []
            for seg, start, rows in pieces:
                s = jax.lax.dot_general(
                    qs, k_refs[seg][0, pl.ds(start, rows), :],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=precision)
                if not fold:
                    s = s * scale
                if seg == _TXT:
                    s = _mask_k_tail(s, txt_len - start)
                logits.append(s)
            m_cur = functools.reduce(
                jnp.maximum,
                [jnp.max(s, axis=-1, keepdims=True) for s in logits])
            m_new = m_cur if m is None else jnp.maximum(m, m_cur)
            l_cur = pv = None
            for (seg, start, rows), s in zip(pieces, logits):
                p = jnp.exp(s - m_new)
                v = v_refs[seg][0, pl.ds(start, rows), :]
                l_p = jnp.sum(p, axis=-1, keepdims=True)
                pv_p = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=precision)
                l_cur = l_p if l_cur is None else l_cur + l_p
                pv = pv_p if pv is None else pv + pv_p
            if m is None:
                l, acc = l_cur, pv
            else:
                corr = jnp.exp(m - m_new)
                l, acc = l * corr + l_cur, acc * corr + pv
            m = m_new
        o_ref[0] = _unstack_group_heads(acc / l, block_q,
                                        head_dim).astype(o_ref.dtype)

    i = pl.program_id(2)
    pl.when(i < img_steps)(lambda: attend(iq_ref, io_ref))
    pl.when(i >= img_steps)(lambda: attend(tq_ref, to_ref))


def _pad_rows(x: jax.Array, rows: int) -> jax.Array:
    return x if x.shape[1] == rows else jnp.pad(
        x, ((0, 0), (0, rows - x.shape[1]), (0, 0)))


@functools.partial(jax.jit, static_argnames=("groups", "num_heads", "plan",
                                             "interpret"))
def _flash_mha_packed_joint(tq, tk, tv, iq, ik, iv, groups, num_heads: int,
                            plan: JointPlan, interpret: bool):
    """The two-segment packed call. The arrays that hold the text rows' and
    the image rows' q, k and v — the SAME array wherever one ``qkv``
    product holds several — and for each, in that order, which of how many
    ``H·D``-wide column groups of it: ``groups = ((index, count), …)``.
    Returns the text rows' and the image rows' answers, ``[B, T, H·D]``
    and ``[B, N, H·D]``. (The name's head is what the benchmark's
    ``attention_share_pct`` finds the packed kernels by.)"""
    B, N, _ = iq.shape
    T = tq.shape[1]
    HD = iq.shape[2] // groups[3][1]
    D = HD // num_heads
    W, _ = _packed_group(D)
    per = HD // W                       # head groups = lane blocks of one q
    img_steps = N // plan.img_block_q
    # zeros, not whatever lies past the rows (the module's docstring); one
    # array padded thrice is one pad once the compiler has merged them
    tq, tk, tv = (_pad_rows(a, plan.txt_rows) for a in (tq, tk, tv))
    precision = (jax.lax.Precision.HIGHEST if iq.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)

    def spec(rows, group, row_block):
        index, _ = group
        return pl.BlockSpec(
            (1, rows, W),
            lambda b, g, i: (b, row_block(i), index * per + g),
            memory_space=pltpu.VMEM)

    def img_q_block(i):
        return jnp.minimum(i, img_steps - 1)

    def txt_q_block(i):
        return jnp.maximum(i - img_steps, 0)

    def whole(i):
        return 0

    g_tq, g_tk, g_tv, g_iq, g_ik, g_iv = groups
    kernel = functools.partial(
        _joint_kernel, txt_len=T, img_steps=img_steps,
        slabs=plan.slabs, head_dim=D, precision=precision)
    vma = jax.typeof(iq).vma
    t_out, i_out = pl.pallas_call(
        kernel,
        grid=(B, per, img_steps + plan.txt_steps),
        in_specs=[spec(plan.txt_block_q, g_tq, txt_q_block),
                  spec(plan.img_block_q, g_iq, img_q_block),
                  spec(plan.txt_rows, g_tk, whole), spec(N, g_ik, whole),
                  spec(plan.txt_rows, g_tv, whole), spec(N, g_iv, whole)],
        out_specs=[spec(plan.txt_block_q, (0, 1), txt_q_block),
                   spec(plan.img_block_q, (0, 1), img_q_block)],
        out_shape=[
            jax.ShapeDtypeStruct(
                (B, plan.txt_steps * plan.txt_block_q, HD), iq.dtype,
                vma=vma),
            jax.ShapeDtypeStruct((B, N, HD), iq.dtype, vma=vma)],
        # the algorithm's cost over the joint rows, as the one-segment call
        # states it (utils/flops.py takes this number)
        cost_estimate=pl.CostEstimate(
            flops=4 * B * num_heads * (T + N) * (T + N) * D,
            transcendentals=B * num_heads * (T + N) * (T + N),
            bytes_accessed=4 * B * (T + N) * HD * iq.dtype.itemsize),
        compiler_params=pltpu.CompilerParams(
            # a segment's output tile stands still while the other
            # segment's steps run: the q axis revisits, so it is no
            # "parallel" axis (one core a v5e chip: nothing is lost)
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_PACKED_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(tq, iq, tk, ik, tv, iv)
    return t_out[:, :T], i_out


def flash_joint_attention(txt, img, num_heads: int, plan: JointPlan,
                          interpret: bool):
    """``txt`` / ``img``: a segment's ``(q, k, v)`` as
    ``ops.attention.Columns``."""
    cols = [*txt, *img]
    return _flash_mha_packed_joint(
        *(c.array for c in cols),
        groups=tuple((c.index, c.count) for c in cols),
        num_heads=num_heads, plan=plan, interpret=interpret)
