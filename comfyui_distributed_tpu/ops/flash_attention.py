"""Pallas flash attention for TPU.

The hot op of every model family here (SDXL UNet cross/self attention,
FLUX/WAN DiT joint attention) is bidirectional dense attention over
10³–10⁵ tokens. XLA's fused ``dot_product_attention`` is good; a pallas
kernel is better on two axes the compiler can't reach:

- **VMEM residency**: K/V stream through VMEM in ``block_k`` tiles while
  the O(N²) logits matrix never exists in HBM — at video sequence lengths
  (WAN: ~32k tokens) the materialized-logits path is HBM-bound and the
  streaming-softmax path is MXU-bound.
- **fp32 accumulation over bf16 MXU inputs**: QKᵀ and PV run on the MXU
  in bf16 with fp32 accumulators (``preferred_element_type``), matching
  flash-attention numerics exactly.

The reference has no analogue (its compute hot loop is ComfyUI's
``common_ksampler``, SURVEY §3.3); this kernel sits *under* the parity
surface as the execution engine's attention primitive.

Kernel structure. The classic (``bh``) tier is standard TPU flash
attention: grid = (batch·heads, Nq/block_q, Nk/block_k), K-blocks
innermost so the running max ``m``, denominator ``l`` and output
accumulator live in VMEM scratch across grid steps; the output block is
written once on the final K step.

The packed tier (``_flash_mha_packed``, the default wherever it is legal)
puts heads on the grid instead. Operands stay in the model's natural
``[B, N, H·D]`` layout and are tiled in 128-lane *groups* — two D=64 heads
or one D=128 head: grid = (batch, H·D/128, Nq/block_q, Nk/block_k), tiles
``(1, block_q, 128)`` and ``(1, block_k, 128)``. A K/V tile is one group
wide, not H·D wide, so ``block_k`` is chosen from the shape up to the whole
padded sequence (``_packed_blocks``): then K and V of a (batch, group) are
fetched once and stay resident in VMEM while the q blocks walk past them,
and no softmax state crosses a grid step. Inside a step K is read in
slabs of at most ``_PACKED_SLAB`` rows (unrolled up to three, looped
over beyond) with the online softmax carried in values. Only a sequence
whose K/V do not fit (tens of thousands of tokens) streams ``block_k``
chunks over the innermost grid axis, carrying ``m``/``l``/acc in scratch
as the other tiers do.

Sequence lengths are padded at trace time (K to a multiple of 128 when
resident, q to a multiple of the chosen ``block_q``) and the padding tail
is masked with a static-length comparison, in the packed tier only on
the slab that holds it — shapes stay static for XLA.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# lane width: scratch vectors m/l are stored lane-replicated (BQ, 128)
_LANES = 128
_SUBLANES = 8        # f32 sublane tile height — block_q granularity
NEG_INF = -1e30      # large-but-finite: -inf breaks max on fully-masked rows

_DEFAULT_BLOCK_Q = 256   # measured r04 at SDXL shapes (docs/roofline.md)
_DEFAULT_BLOCK_K = 512


def _check_block(name: str, value: int, multiple: int) -> None:
    if value <= 0 or value % multiple:
        raise ValueError(
            f"{name}={value} is not a legal flash block size: must be a "
            f"positive multiple of {multiple} (TPU "
            f"{'sublane' if multiple == _SUBLANES else 'lane'} tiling) — "
            "pallas would fail during Mosaic lowering otherwise")


def _check_blocks(block_q: Optional[int], block_k: Optional[int]) -> None:
    """Validate requested blocks (a caller's arguments; None
    = not requested, the tier applies its own): a non-positive or
    non-(8,128)-divisible value raises a descriptive ``ValueError``
    instead of letting pallas fail deep in lowering."""
    if block_q is not None:
        _check_block("block_q", block_q, _SUBLANES)
    if block_k is not None:
        _check_block("block_k", block_k, _LANES)


def resolve_flash_blocks(block_q: Optional[int] = None,
                         block_k: Optional[int] = None) -> tuple[int, int]:
    """Checked blocks of the classic tier: the measured defaults
    (256/512, r04) where nothing was requested."""
    _check_blocks(block_q, block_k)
    return (_DEFAULT_BLOCK_Q if block_q is None else block_q,
            _DEFAULT_BLOCK_K if block_k is None else block_k)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, kv_len: int, block_k: int, num_k_blocks: int,
                  scale: float, precision):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                   # [BQ, D]
    k = k_ref[0]                                   # [BK, D]
    v = v_ref[0]                                   # [BK, D]

    # [BQ, BK] logits in fp32 (bf16 inputs use the MXU natively)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    ) * scale

    # static-shape masking of the K padding tail (kv_len is a Python int)
    if kv_len % block_k != 0:
        base = j * block_k
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(base + col < kv_len, s, NEG_INF)

    m_prev = m_ref[:, :1]                          # [BQ, 1] (lane-replicated)
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)     # [BQ, 1]
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                         # [BQ, BK]
    corr = jnp.exp(m_prev - m_new)                 # [BQ, 1]
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)

    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    )                                              # [BQ, D]
    acc_ref[:] = acc_ref[:] * corr + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)            # fully-masked rows → 0
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _stack_group_heads(q, head_dim: int):
    """[BQ, W] q tile of one head group → [heads·BQ, W]: row block h
    keeps head h's lanes and zeroes the others, so ONE 128-deep QKᵀ and
    ONE 128-wide PV serve every head of the group — the MXU spends a
    128-deep, 128-wide pass on a D=64 head either way, and no operand is
    ever sliced at a 64-lane offset (measured on the v5e against 64-lane
    slices: PERF.md §6, PR 25). A zeroed lane adds an exact 0 to the f32
    logit. D ≥ 128 groups hold one head and pass through."""
    block_q, width = q.shape
    heads = width // head_dim
    if heads == 1:
        return q
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, width), 1)
    return jnp.concatenate(
        [jnp.where((lane >= h * head_dim) & (lane < (h + 1) * head_dim),
                   q, jnp.zeros_like(q)) for h in range(heads)], axis=0)


def _unstack_group_heads(o, block_q: int, head_dim: int):
    """[heads·BQ, W] → [BQ, W]: head h's lanes from row block h."""
    width = o.shape[1]
    out = o[:block_q]
    if width == head_dim:
        return out
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, width), 1)
    for h in range(1, width // head_dim):
        out = jnp.where(lane >= h * head_dim,
                        o[h * block_q:(h + 1) * block_q], out)
    return out


def _mask_k_tail(s, valid: int):
    """NEG_INF on columns ≥ ``valid`` (a Python int) of one slab's
    logits. Only the 128-column slabs that hold padding are touched: the
    compare-and-select is paid where a tail is, not on every logit."""
    if valid >= s.shape[1]:
        return s
    lo = max(valid, 0) // _LANES * _LANES
    tail = s[:, lo:]
    col = jax.lax.broadcasted_iota(jnp.int32, tail.shape, 1)
    tail = jnp.where(col < valid - lo, tail, NEG_INF)
    return tail if lo == 0 else jnp.concatenate([s[:, :lo], tail], axis=1)


def _scale_folds_into_q(head_dim: int, dtype) -> bool:
    """Whether 1/√D may multiply q instead of the logits: only where the
    result is bit-identical (a power of two — D=64's 0.125 — commutes
    with every rounding) or q is f32 (one f32 rounding ahead of the MXU's
    own split). A bf16 q under D=128's 0.0884 would take a second
    rounding, so those logits are scaled as before."""
    return (math.frexp(head_dim ** -0.5)[0] == 0.5
            or jnp.dtype(dtype).itemsize == 4)


def _flash_kernel_packed(q_ref, k_ref, v_ref, o_ref, *scratch,
                         kv_len: int, block_k: int, num_k_blocks: int,
                         slab: int, head_dim: int, precision):
    """One (batch, head group, q block, K chunk) step of the packed tier.
    Refs are [1, block, W] tiles of the natural [B, N, H·D] layout, W the
    group's lanes (``_packed_group``). The K chunk is walked in
    ``slab``-row pieces with the online softmax carried in values; f32
    logits, max, exp, sum and accumulator, MXU operands in the operand
    dtype. With one K chunk (K/V resident, the usual case) nothing else
    exists: no scratch, no state across grid steps, one normalisation at
    the end. With several, m/l/acc pass from step to step through
    scratch, and the tail mask is compiled only into the last."""
    block_q = q_ref.shape[1]
    scale = head_dim ** -0.5
    q = q_ref[0]
    fold = _scale_folds_into_q(head_dim, q.dtype)
    if fold:
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qs = _stack_group_heads(q, head_dim)
    valid_last = kv_len - (num_k_blocks - 1) * block_k

    def slab_step(state, start, rows: int, valid=None):
        """One K slab into the running (m, l, acc); ``m is None`` on the
        very first slab of a resident call, which needs no rescale."""
        m, l, acc = state
        k = k_ref[0, pl.ds(start, rows), :]
        v = v_ref[0, pl.ds(start, rows), :]
        s = jax.lax.dot_general(
            qs, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        if not fold:
            s = s * scale
        if valid is not None:
            s = _mask_k_tail(s, valid)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = m_cur if m is None else jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new)
        l_cur = jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        if m is None:
            return m_new, l_cur, pv
        corr = jnp.exp(m - m_new)
        return m_new, l * corr + l_cur, acc * corr + pv

    def accumulate(state, masked: bool):
        """The K chunk, slab by slab. The full slabs that hold no padding
        come first: up to ``_PACKED_UNROLL_SLABS`` of them are unrolled
        (SD3 has two and a tail: the form measured), more are looped
        over, so neither the compiled body nor its VMEM grows with the
        sequence. What is left — a shorter last slab, slabs with padding
        — is unrolled, and only slabs with padding are masked."""
        valid = valid_last if masked else block_k
        clean = min(block_k, valid) // slab
        if clean <= _PACKED_UNROLL_SLABS:
            for i in range(clean):
                state = slab_step(state, i * slab, slab)
        else:
            if state[0] is None:
                rows = qs.shape[0]
                state = (jnp.full((rows, 1), NEG_INF, jnp.float32),
                         jnp.zeros((rows, 1), jnp.float32),
                         jnp.zeros(qs.shape, jnp.float32))
            state = jax.lax.fori_loop(
                0, clean,
                lambda i, st: slab_step(
                    st, pl.multiple_of(i * slab, _LANES), slab),
                state)
        for start in range(clean * slab, block_k, slab):
            rows = min(slab, block_k - start)
            state = slab_step(state, start, rows,
                              valid - start if start + rows > valid
                              else None)
        return state

    def write_out(l, acc):
        o_ref[0] = _unstack_group_heads(acc / l, block_q,
                                        head_dim).astype(o_ref.dtype)

    if num_k_blocks == 1:
        _, l, acc = accumulate((None, None, None), masked=True)
        write_out(l, acc)
        return

    m_ref, l_ref, acc_ref = scratch
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def step(masked: bool):
        m, l, acc = accumulate((m_ref[:, :1], l_ref[:, :1], acc_ref[:]),
                               masked)
        m_ref[:] = jnp.broadcast_to(m, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l, l_ref.shape)
        acc_ref[:] = acc

    if valid_last < block_k:
        pl.when(j < num_k_blocks - 1)(lambda: step(False))
        pl.when(j == num_k_blocks - 1)(lambda: step(True))
    else:
        step(False)

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        write_out(l_ref[:, :1], acc_ref[:])


def _packed_scratch(block_q: int, head_dim: int) -> list:
    """m / l (lane-replicated) and the f32 accumulator of a streamed
    packed call, one row per (head of the group, q row)."""
    width, heads = _packed_group(head_dim)
    rows = heads * block_q
    return [pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, width), jnp.float32)]


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _platform() -> str:
    """Platform of the default backend. A backend that cannot initialise
    raises here: a missing chip is an error at the call site, never a
    quiet switch to interpret mode or to the XLA tier. The kernel
    dispatcher (``ops/attention.py``) reads the platform through this one
    function, which is also where an off-chip compile test steers it."""
    return jax.devices()[0].platform


def _on_tpu() -> bool:
    return _platform() == "tpu"


def _in_manual_trace(x) -> bool:
    """True when tracing inside ``shard_map`` (the aval carries varying
    manual axes)."""
    return bool(jax.typeof(x).vma)


def _flash_emulated(q, k, v, block_q: int, block_k: int):
    """The kernel's streaming-softmax algorithm in plain JAX ops.

    Used only where the pallas *interpreter* cannot run: inside a
    ``shard_map`` trace in interpret mode, JAX's HLO interpreter issues
    ``dynamic_slice`` calls whose index operands lack the varying manual
    axes of the data operand and trips ``check_vma`` (jax-ml/jax — the
    error itself suggests ``check_vma=False`` as the workaround, which we
    cannot impose on callers). This emulation runs the same block
    schedule, padding, NEG_INF tail masking and fp32 accumulation as
    ``_flash_kernel``, so CPU shard_map tests exercise the same math;
    compiled TPU runs still take the pallas path.
    """
    BH, Nq, D = q.shape
    _, Nk, _ = k.shape
    scale = 1.0 / (D ** 0.5)
    qp = _pad_to(q, 1, block_q)
    kp = _pad_to(k, 1, block_k)
    vp = _pad_to(v, 1, block_k)
    nkb = kp.shape[1] // block_k

    m = jnp.full((BH, qp.shape[1], 1), NEG_INF, jnp.float32)
    l = jnp.zeros((BH, qp.shape[1], 1), jnp.float32)
    acc = jnp.zeros((BH, qp.shape[1], D), jnp.float32)
    for j in range(nkb):  # static unroll — nkb is a Python int
        kb = jax.lax.dynamic_slice_in_dim(kp, j * block_k, block_k, 1)
        vb = jax.lax.dynamic_slice_in_dim(vp, j * block_k, block_k, 1)
        s = jax.lax.dot_general(
            qp, kb, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        if Nk % block_k != 0:
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where(j * block_k + col < Nk, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc = acc * corr + pv
        m = m_new
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l).astype(q.dtype)[:, :Nq]


def _pad_and_prepare(q, k, v, block_q: int, block_k: int):
    """Shared prologue of both pallas drivers: pad q/k/v sequence dims to
    block multiples, pick the matmul precision, and build the vma-aware
    output aval. f32 inputs ask for real f32 matmuls (3-pass bf16 on the
    MXU); bf16 inputs take the fast single-pass path — the production
    dtype. Inside shard_map the output must declare which mesh axes it
    varies over (check_vma) — it varies exactly like q does."""
    qp = _pad_to(q, 1, block_q)
    kp = _pad_to(k, 1, block_k)
    vp = _pad_to(v, 1, block_k)
    precision = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    out_sds = jax.ShapeDtypeStruct(qp.shape, q.dtype,
                                   vma=jax.typeof(qp).vma)
    return qp, kp, vp, precision, out_sds


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret"))
def _flash_mha(q, k, v, block_q: int, block_k: int, interpret: bool):
    BH, Nq, D = q.shape
    _, Nk, _ = k.shape
    scale = 1.0 / (D ** 0.5)

    qp, kp, vp, precision, out_sds = _pad_and_prepare(q, k, v, block_q,
                                                      block_k)
    nqb = qp.shape[1] // block_q
    nkb = kp.shape[1] // block_k

    kernel = functools.partial(
        _flash_kernel, kv_len=Nk, block_k=block_k, num_k_blocks=nkb,
        scale=scale, precision=precision)

    out = pl.pallas_call(
        kernel,
        grid=(BH, nqb, nkb),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=out_sds,
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running sum
            pltpu.VMEM((block_q, D), jnp.float32),        # output acc
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :Nq]


@functools.partial(jax.jit, static_argnames=("num_heads", "block_q",
                                             "block_k", "interpret"))
def _flash_mha_packed(q, k, v, num_heads: int, block_q: int, block_k: int,
                      interpret: bool):
    """Packed-heads pallas call: operands stay [B, N, H·D] — the QKV
    projection's own output layout, so no transpose ever happens at the
    custom-call boundary (the boundary relayout, not the kernel body, is
    what made the classic [B·H, N, D] call lose to XLA fused attention
    at SDXL sequence lengths — `docs/roofline.md` finding 1) — and the
    grid walks them in 128-lane head groups. ``block_k`` rows of K/V are
    one grid step's tile: the whole padded sequence when it fits
    (``_packed_blocks``), which makes the K/V index map constant along
    the q axis, so a (batch, group)'s K and V are fetched once.
    Legality: ``_packed_legal``."""
    B, Nq, HD = q.shape
    _, Nk, _ = k.shape
    D = HD // num_heads
    W, _ = _packed_group(D)

    qp, kp, vp, precision, out_sds = _pad_and_prepare(q, k, v, block_q,
                                                      block_k)
    nqb = qp.shape[1] // block_q
    nkb = kp.shape[1] // block_k

    kernel = functools.partial(
        _flash_kernel_packed, kv_len=Nk, block_k=block_k, num_k_blocks=nkb,
        slab=_packed_slab(block_k), head_dim=D, precision=precision)

    q_spec = pl.BlockSpec((1, block_q, W), lambda b, g, i, j: (b, i, g),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, block_k, W), lambda b, g, i, j: (b, j, g),
                           memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        kernel,
        grid=(B, HD // W, nqb, nkb),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=out_sds,
        scratch_shapes=_packed_scratch(block_q, D) if nkb > 1 else [],
        # the algorithm's cost, not the body's: stacked D=64 heads issue
        # 128-deep passes, and a looped slab has no static trip count to
        # read off the jaxpr (utils/flops.py takes this number)
        cost_estimate=pl.CostEstimate(
            flops=4 * B * num_heads * Nq * Nk * D,
            transcendentals=B * num_heads * Nq * Nk,
            bytes_accessed=(2 * q.size + k.size + v.size)
            * q.dtype.itemsize),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_PACKED_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :Nq]


# The packed call asks the compiler for its own scoped limit
# (`vmem_limit_bytes`; a v5e core has 128 MiB of VMEM) and plans its
# blocks inside a budget below it: the margin is for what the model
# cannot see of the compiler's body scratch.
_PACKED_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_PACKED_VMEM_BUDGET_BYTES = 48 * 1024 * 1024
# longest K slab one in-body softmax step takes: measured on the v5e at
# SD3's 4224 padded tokens, three slabs of 1408 beat one of 4224 by 11%
# and eleven of 384 by 8% (PERF.md §6, PR 25)
_PACKED_SLAB = 1536
# a K chunk of up to this many slabs is unrolled in the body; a longer one
# loops over its full slabs
_PACKED_UNROLL_SLABS = 3
# default q rows of one grid step, before rounding to the sequence
_PACKED_BLOCK_Q = 512


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _packed_group(head_dim: int) -> tuple[int, int]:
    """(lanes, heads) of one packed head group: the narrowest whole
    number of heads that fills whole 128-lane tiles — two D=64 heads or
    one D=128 head in 128 lanes."""
    width = head_dim * _LANES // math.gcd(head_dim, _LANES)
    return width, width // head_dim


def _packed_slab(block_k: int) -> int:
    """Rows of one in-body K slab: ``block_k`` cut into the fewest equal
    lane-aligned pieces no longer than ``_PACKED_SLAB``."""
    pieces = -(-block_k // _PACKED_SLAB)
    return _round_up(-(-block_k // pieces), _LANES)


def _packed_vmem_bytes(head_dim: int, block_q: int, block_k: int,
                       itemsize: int, streamed: bool = False) -> int:
    """Scoped VMEM of one packed-kernel grid step: double-buffered
    q/k/v/out tiles of one head group in the operand dtype (the declared
    part, which the compiler counts to the byte); what the body holds —
    the group's stacked q, one K slab's logits, the f32 accumulator and
    its PV addend; and, only when K streams over the grid, the m/l/acc
    scratch. The logits term is calibrated against the v5e compiler
    (docs/kernels.md): its stack for the unrolled slabs came to 9.5–10.5
    B a slab logit with bf16 operands and 18–19 B with f32 (HIGHEST
    splits the operands), and 4–5.5 B where the slabs are looped over;
    6 × itemsize covers every probe."""
    width, heads = _packed_group(head_dim)
    rows = heads * block_q
    io = 2 * (2 * block_q + 2 * block_k) * width * itemsize
    body = (rows * width * itemsize
            + rows * _packed_slab(block_k) * 6 * itemsize
            + 2 * rows * width * 4)
    scratch = (rows * width * 4 + 2 * rows * _LANES * 4) if streamed else 0
    return io + body + scratch


def _packed_blocks(q_len: int, kv_len: int, head_dim: int, itemsize: int = 2,
                   block_q: Optional[int] = None,
                   block_k: Optional[int] = None) -> tuple[int, int]:
    """(block_q, block_k) of the packed call. Requested blocks (a
    caller's arguments) keep their meaning — rows of one q
    tile and of one K/V tile — and win; what is not requested comes from
    the shape:

    - ``block_k``: the whole sequence padded to 128 when the VMEM model
      fits it (K/V resident, one K step), else the fewest equal
      lane-aligned chunks that fit;
    - ``block_q``: the sequence cut into the fewest pieces of at most
      ``_PACKED_BLOCK_Q`` rows, rounded up to the operand's sublane tile
      — SD3's 4173 tokens are 9 × 464 = 4176, not 17 × 256 = 4352.

    Raises when requested blocks exceed the VMEM budget: a block-tuning
    experiment must never measure other blocks than it asked for."""
    sublanes = _SUBLANES * max(1, 4 // itemsize)
    if block_q is None:
        pieces = -(-q_len // _PACKED_BLOCK_Q)
        block_q = _round_up(-(-q_len // pieces), sublanes)

    def need(bk: int) -> int:
        return _packed_vmem_bytes(head_dim, block_q, bk, itemsize,
                                  streamed=bk < kv_len)

    def fits(bk: int) -> bool:
        return need(bk) <= _PACKED_VMEM_BUDGET_BYTES

    if block_k is not None:
        if not fits(block_k):
            raise ValueError(
                f"packed flash blocks {block_q}/{block_k} at D={head_dim} "
                f"({itemsize}B operands) need {need(block_k) >> 20} MB of "
                f"VMEM; the budget is {_PACKED_VMEM_BUDGET_BYTES >> 20} MB")
        return block_q, block_k
    padded = _round_up(kv_len, _LANES)
    for chunks in range(1, padded // _LANES + 1):
        block_k = _round_up(-(-padded // chunks), _LANES)
        if fits(block_k):
            return block_q, block_k
    raise ValueError(
        f"packed flash attention infeasible at D={head_dim}, "
        f"block_q={block_q}: even a {_LANES}-row K tile exceeds the "
        f"{_PACKED_VMEM_BUDGET_BYTES >> 20} MB VMEM budget")


def _packed_legal(H: int, D: int) -> bool:
    """Pure geometric legality of the packed-heads layout: whole heads
    fill whole 128-lane groups. D % 64 confines the layout to the tested
    head-dim classes (64/128); e.g. H=128, D=16 would pack eight heads a
    group — a shape class never measured. No width ceiling: a tile is one
    group wide whatever H·D is (FLUX's 3072 is twenty-four groups)."""
    return (H * D) % _LANES == 0 and D % 64 == 0


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    layout: Optional[str] = None,
) -> jax.Array:
    """Exact bidirectional attention, [B,N,H,D] layout (matching
    ``ops.attention.full_attention``), computed by the pallas kernel.

    ``interpret=None`` compiles the kernel, except where the platform is
    positively ``cpu``: there the Pallas interpreter runs the same kernel
    code (the CPU tests).

    ``block_q``/``block_k`` are checked (``_check_blocks``:
    non-positive or non-(8,128)-divisible values raise a descriptive
    error instead of failing in lowering); where ``None``, the packed
    call derives them from the shape (``_packed_blocks`` — K resident
    where it fits) and the classic call takes 256/512 (measured r04).

    ``layout`` is the kernel I/O layout: ``"bh"`` the classic
    pre-transposed call; ``"packed"`` or ``None`` the packed call where
    the geometry is legal for it (``_packed_legal``), else the classic
    one. Nothing is decided here beyond legality: whether a site runs
    flash at all, and in which layout, is ``ops/attention.select_kernel``'s
    choice, which arrives as these arguments.
    """
    if interpret is None:
        interpret = _platform() == "cpu"
    B, Nq, H, D = q.shape
    _, Nk, _, _ = k.shape
    if layout in (None, "packed"):
        packed = _packed_legal(H, D)
    elif layout == "bh":
        packed = False
    else:
        raise ValueError(
            f"layout must be 'packed', 'bh', or None, got {layout!r}")
    emulated = interpret and _in_manual_trace(q)
    if packed and not emulated:
        _check_blocks(block_q, block_k)
        bq, bk = _packed_blocks(Nq, Nk, D, jnp.dtype(q.dtype).itemsize,
                                block_q, block_k)
        out = _flash_mha_packed(
            q.reshape(B, Nq, H * D), k.reshape(B, Nk, H * D),
            v.reshape(B, Nk, H * D), num_heads=H,
            block_q=bq, block_k=bk, interpret=interpret)
        return out.reshape(B, Nq, H, D)
    block_q, block_k = resolve_flash_blocks(block_q, block_k)

    # [B,N,H,D] → [B·H, N, D]
    def to_bh(x, n):
        return x.transpose(0, 2, 1, 3).reshape(B * H, n, D)
    if emulated:
        out = _flash_emulated(to_bh(q, Nq), to_bh(k, Nk), to_bh(v, Nk),
                              block_q=block_q, block_k=block_k)
    else:
        out = _flash_mha(to_bh(q, Nq), to_bh(k, Nk), to_bh(v, Nk),
                         block_q=block_q, block_k=block_k,
                         interpret=interpret)
    return out.reshape(B, H, Nq, D).transpose(0, 2, 1, 3)
