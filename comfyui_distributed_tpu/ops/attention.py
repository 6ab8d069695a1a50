"""Attention ops, including sequence-parallel variants.

The reference has NO attention-level sharding (SURVEY §5.7 — its only
long-input scaling is spatial tiling); for a TPU framework long-context is
first-class: DiT models attend over ~10⁴–10⁵ image/video tokens, and a
single chip runs out of HBM long before compute. Two standard schemes:

- **Ring attention** (`ring_attention`): K/V shards rotate around the mesh
  ring via ``ppermute`` while each shard's queries accumulate
  flash-style (running max / running sum), so no shard ever materializes
  the full sequence. Communication rides ICI neighbour links.
- **Ulysses** (`ulysses_attention`): ``all_to_all`` re-shards from
  sequence-sharded to head-sharded, runs dense local attention per head
  group, and re-shards back. Cheaper at moderate sequence lengths when
  heads divide evenly.

Both are exact (not approximations) and bitwise-stable in float32; tests
verify equality against dense attention.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.lax import axis_size as _axis_size

from ..telemetry.device_scopes import device_scope, device_scoped
from ..utils import constants
from .flash_attention import _on_tpu, _packed_blocks, _packed_legal
from .kernel_choice import GeometryKey, KernelChoice, itemsize_of

# the attention core's device scope round a whole dispatcher: the kernel
# call with its pads, transposes and casts is cdt.attn_core wherever it is
# called from, self and cross alike
_attn_core = device_scoped("attn_core")


def _pvary(x, axis):
    """Mark ``x`` axis-varying for shard_map's varying-manual-axes check."""
    return jax.lax.pcast(x, axis, to="varying")


# --- kernel-tier dispatch ----------------------------------------------------
# selections made at trace time, remembered for observability: the log
# line fires once per (geometry, choice), the counter feeds
# cdt_attn_kernel_selected (which chip_smoke.py checks), and
# selection_summary() lists them for the kernel tests.

import contextlib as _contextlib
import contextvars as _contextvars
import dataclasses as _dataclasses
import threading as _threading

# tp shard degree of the program currently being traced: a tp-sharded
# attention site runs H/tp heads per shard, and the kernel choice must
# resolve (and legality-check) THAT geometry, not the full-H one the
# model config states. Set by the dp×tp call wrappers
# (parallel/tensor.tp_fanout_call) and the warmup pass around tracing.
_TP_SHARDS: _contextvars.ContextVar = _contextvars.ContextVar(
    "cdt_attn_tp_shards", default=1)


@_contextlib.contextmanager
def tp_shard_scope(tp: int):
    """Trace-scope marker: attention sites traced inside this scope
    resolve their kernel by PER-SHARD geometry (heads/tp). No-op for
    tp <= 1."""
    token = _TP_SHARDS.set(max(int(tp), 1))
    try:
        yield
    finally:
        _TP_SHARDS.reset(token)


def current_tp_shards() -> int:
    return _TP_SHARDS.get()

_SELECTIONS: "dict[str, str]" = {}
_SELECTIONS_LOCK = _threading.Lock()


def _blocks_label(choice, kv_len: Optional[int] = None) -> str:
    """'<block_q>/<block_k>' of a choice, '' for a tier without blocks;
    a packed choice also says whether its K tile holds the whole sequence
    (``k-resident``: one K step, the one-pass softmax form) or the
    sequence streams through it (``k-streamed``)."""
    if choice.block_q is None:
        return ""
    label = f"{choice.block_q}/{choice.block_k}"
    if choice.tier == "packed" and kv_len is not None:
        label += (":k-resident" if choice.block_k >= kv_len
                  else ":k-streamed")
    return label


def _note_selection(geometry: str, choice,
                    kv_len: Optional[int] = None, blocks=None) -> None:
    blocks = blocks or _blocks_label(choice, kv_len)
    desc = choice.tier + (f":{blocks}" if blocks else "")
    with _SELECTIONS_LOCK:
        if _SELECTIONS.get(geometry) == desc:
            return
        _SELECTIONS[geometry] = desc
    from ..utils.logging import log

    why = f" ({choice.reason})" if choice.reason else ""
    log(f"attention: {geometry} → {desc} [{choice.source}]{why}")
    try:
        from ..telemetry import enabled as _tm_enabled
        from ..telemetry import metrics as _tm

        if _tm_enabled():
            _tm.ATTN_KERNEL_SELECTED.labels(
                tier=choice.tier, geometry=geometry, blocks=blocks).inc()
    except Exception:  # noqa: BLE001 — observability must not sink dispatch
        pass


# what each blocked causal prefill kernel of ops/flash_latent.py says of
# itself in the ``attention:`` line: tier → reason
CAUSAL_TIER_REASONS = {
    "latent_causal": "chunked prefill over a latent cache",
    "shared_kv_causal": "chunked prefill over one shared K/V head",
    "gqa_causal": "chunked prefill over grouped K/V heads",
    "gqa_window": "chunked prefill over grouped K/V heads, banded",
}


def note_causal(tier: str, num_heads: int, head_dim: int, q_len: int,
                kv_len: int, dtype, block_q: int, block_k: int,
                part: int = 0, value_dim: int = 0) -> None:
    """A blocked causal prefill kernel reports itself as the tiers do (the
    ``attention:`` line, ``cdt_attn_kernel_selected``): its caller names the
    tier, the blocks, ``/part`` the rows of a query tile a step takes at a
    time, ``value_dim`` where values are narrower than keys (``d192/128``)."""
    choice = KernelChoice(tier, block_q, block_k,
                          reason=CAUSAL_TIER_REASONS[tier])
    rows = f"/{part}" if 0 < part < block_q else ""
    _note_selection(GeometryKey.from_shape(
        num_heads, head_dim, q_len, kv_len, dtype).key_str().replace(
        ".q", f"/{value_dim}.q" if value_dim else ".q", 1), choice,
        blocks=_blocks_label(choice) + rows)


def _with_packed_blocks(choice, q_len: int, kv_len: int, head_dim: int,
                        dtype):
    """A packed choice with the blocks its call will run, derived from
    the shape (``flash_attention._packed_blocks``) — resolved here so that
    the selection log and counter show them, and handed to the call so
    that it cannot resolve others."""
    if choice.tier != "packed":
        return choice
    bq, bk = _packed_blocks(q_len, kv_len, head_dim, itemsize_of(dtype))
    return _dataclasses.replace(choice, block_q=bq, block_k=bk)


def selection_summary() -> str:
    """Compact 'geometry=tier' list of every kernel choice this process
    has traced (the kernel tests read it)."""
    with _SELECTIONS_LOCK:
        return ",".join(f"{g}={d}" for g, d in sorted(_SELECTIONS.items()))


def reset_selections() -> None:
    with _SELECTIONS_LOCK:
        _SELECTIONS.clear()


# Engagement floors of the pallas tiers, measured on the v5e. The packed
# layout beats XLA's fused attention from q = 832 up but not below: at
# SDXL's 32² level (B 2, H 20, D 64, bf16; PR 55's chip reading, PERF.md
# §6) XLA's lowering falls off a cliff between 800 and 832 tokens — the
# core alone 130 µs at 800, 368 µs at 832, 517 µs at 1024, against packed
# 157 / 163 / 188 µs; the whole site (projections + core) 1.9–2.1× behind
# packed at every length measured from 832 to 1024 (832, 864, 896, 936,
# 960, 988, 1008, 1024: SDXL's non-square ~1 MP sizes stand at 988–1008).
# Below the cliff XLA wins the core (576: 74 vs 99 µs, 768: 100 vs 126 µs)
# and ties the site; at 800 the two readings disagree (core 1.21× behind,
# site 0.86×), so the floor is the first length where both agree. Nor does
# packed win with a tiny K (r04, `scripts/mfu_probe.py`, docs/roofline.md
# finding 1a): at SDXL cross-attention (K = 77 text tokens in one
# mostly-padding tile) it measured behind XLA (1.20 vs 1.04 ms/64-op
# chain). The classic pre-transposed ([B·H,N,D]) call LOSES
# to XLA at SDXL lengths (flash-bh 0.1763 s/fwd vs XLA 0.1677 at 1024²;
# the trace shows the boundary relayout, not the kernel body, as the
# cost): at N <= a few K the O(N²) score matrix fits HBM comfortably and
# XLA fuses softmax into the matmuls, so its win is memory at long N
# (ring/SP sequences, video token counts).
PACKED_MIN_Q = 832
PACKED_MIN_KV = 256
BH_MIN_Q = 8192


def policy_choice(q_len: int, kv_len: int, num_heads: int, head_dim: int,
                  flash_only: bool = False):
    """The one rule, for every geometry, asked with the EXACT lengths
    the site runs: packed where the layout is legal and both of its
    floors hold; else the classic ``bh`` call
    from ``BH_MIN_Q`` up, where the streamed softmax's memory win still
    applies (packed-illegal widths, or a long q over a tiny K); else XLA.
    ``flash_only`` takes the XLA outcome away (a caller that was
    promised flash): ``bh`` at any length. Blocks are left to the shape
    (``_with_packed_blocks``) or the classic 256/512."""
    if (_packed_legal(num_heads, head_dim) and q_len >= PACKED_MIN_Q
            and kv_len >= PACKED_MIN_KV):
        return KernelChoice(
            "packed", reason="native packed layout (r04 finding 1a), blocks "
                             "from the shape: K resident where it fits "
                             "(PR 25)")
    if flash_only or q_len >= BH_MIN_Q:
        return KernelChoice(
            "bh", reason="packed illegal or below its floors: classic "
                         "call for the streamed softmax's memory win "
                         "(r04 gate)")
    return KernelChoice(
        "xla", reason="below packed floors (r04: XLA fused lowering wins "
                      "short sequences)")


def select_kernel(q_len: int, kv_len: int, num_heads: int, head_dim: int,
                  dtype="bfloat16", prefer_flash: bool = False,
                  segments=None):
    """Resolve the kernel tier + block config for one attention site. The
    ONLY code that chooses; nothing downstream decides again, and nothing
    — no file, no table, no other variable — can outrank it. In order:

    1. ``CDT_FLASH_ATTENTION=0`` → ``xla`` (the operator's way out);
    2. not on a TPU and not forced (``=1``) → ``xla``;
    3. the one policy (:func:`policy_choice`) at the site's exact lengths;
    4. an ``xla`` answer under ``prefer_flash`` or ``=1`` becomes the
       policy's flash answer.

    Packed blocks are resolved here, once, from the shape, so the log,
    the counter and the call agree (``segments``: a joint site's text and
    image rows, for its label). Same site ⇒ same choice.

    ``prefer_flash`` (memory-constrained callers, see ``full_attention``)
    outranks the policy's ``xla``: the floors optimize for time while the
    caller needs the streamed softmax to fit HBM.

    Mesh-aware: inside a :func:`tp_shard_scope` the head count is
    divided by the tp degree BEFORE the policy is asked — the per-shard
    geometry (H/tp heads) is what actually executes, and packed legality
    at the full H says nothing of H/tp."""
    # ONE definition of the per-shard rule (GeometryKey.shard): the label
    # and the policy's head count must never disagree about it
    gkey = GeometryKey.from_shape(num_heads, head_dim, q_len, kv_len,
                                  dtype).shard(current_tp_shards())
    num_heads = gkey.num_heads
    geometry = gkey.key_str()
    flag = constants.FLASH_ATTENTION.get()
    if flag is False:
        choice = KernelChoice("xla", source="env",
                              reason="CDT_FLASH_ATTENTION=0")
        _note_selection(geometry, choice)
        return choice
    forced = flag is True
    if not forced and not _on_tpu():
        # off-accelerator serving always takes XLA (interpret-mode pallas
        # is a test vehicle, not a CPU fallback); not recorded — CPU
        # hosts would flood the selection log with xla lines
        return KernelChoice("xla", reason="not on TPU")

    choice = policy_choice(q_len, kv_len, num_heads, head_dim)
    if choice.tier == "xla" and (forced or prefer_flash):
        choice = _dataclasses.replace(
            policy_choice(q_len, kv_len, num_heads, head_dim,
                          flash_only=True),
            reason="CDT_FLASH_ATTENTION=1" if forced
            else "prefer_flash (memory-constrained caller)")
    choice = _with_packed_blocks(choice, q_len, kv_len, head_dim, dtype)
    _note_selection(geometry, choice, kv_len,
                    _joint_blocks_label(choice, segments, head_dim, dtype))
    return choice


@_attn_core
def full_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   prefer_flash: bool = False, choice=None) -> jax.Array:
    """Dense [B,N,H,D] attention by the kernel ``select_kernel`` picks
    for the geometry (the one policy; XLA off-TPU). A caller that has
    already asked (``models/layers.py``) hands its ``choice`` in and is
    not asked about again.

    ``prefer_flash=True`` outranks the policy's floors (still TPU-only,
    still overridable by an explicit
    ``CDT_FLASH_ATTENTION``): set by memory-constrained callers — the
    fp8-resident offload executor's block programs OOM'd at compile with
    XLA attention (measured r04: 16.89 GB needed vs 15.75 HBM at FLUX's
    4608 tokens × 24 heads with 12 GB of weights resident) while flash's
    streamed softmax fits."""
    if choice is None:
        B, Nq, H, D = q.shape
        choice = select_kernel(int(Nq), int(k.shape[1]), int(H), int(D),
                               dtype=q.dtype, prefer_flash=prefer_flash)
    if choice.tier == "xla":
        return jax.nn.dot_product_attention(q, k, v)
    from .flash_attention import flash_attention

    return flash_attention(q, k, v, block_q=choice.block_q,
                           block_k=choice.block_k, layout=choice.tier)


def _flash_block(q, k, v, m, l, acc, scale):
    """One K/V block accumulation step of streaming-softmax attention.

    q: [B,Nq,H,D]; k,v: [B,Nk,H,D]; m,l: [B,H,Nq]; acc: [B,Nq,H,D].
    """
    # logits [B,H,Nq,Nk]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    m_new = jnp.maximum(m, s.max(axis=-1))
    corr = jnp.exp(m - m_new)                      # [B,H,Nq]
    p = jnp.exp(s - m_new[..., None])              # [B,H,Nq,Nk]
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, acc_new


def _ring_block() -> int:
    """K sub-block length for one ring hop's accumulation. The naive hop
    materializes [B, H, Nq, Nk_hop] fp32 logits — at video scale (e.g.
    WAN 32k tokens over 8 shards: 4k × 4k × H) that transient is the
    largest allocation in the program. Scanning the hop's K/V in
    sub-blocks bounds it at [B, H, Nq, block]; the accumulation is
    already streaming-softmax, so the identity is exact (floating-point
    round-off differs at the usual flash-blocking level). 0 disables
    sub-blocking (whole hop at once, the pre-r04 behavior)."""
    return constants.RING_BLOCK.get()


def _hop_attend(qf, k_cur, v_cur, m, l, acc, scale):
    """Accumulate one ring hop's K/V shard into the running softmax
    state, walking K sub-blocks so the logits transient stays bounded
    (`_ring_block`) for EVERY hop length — full blocks via a fori_loop
    of dynamic slices (no transposed copy of the hop shard), plus one
    remainder block when the length doesn't divide. Exact: each
    sub-block is one `_flash_block` step of the same streaming
    accumulation."""
    Nk = k_cur.shape[1]
    blk = _ring_block()
    if blk <= 0 or Nk <= blk:
        return _flash_block(qf, k_cur.astype(jnp.float32),
                            v_cur.astype(jnp.float32), m, l, acc, scale)

    def block_at(start, length):
        kb = jax.lax.dynamic_slice_in_dim(k_cur, start, length, 1)
        vb = jax.lax.dynamic_slice_in_dim(v_cur, start, length, 1)
        return kb.astype(jnp.float32), vb.astype(jnp.float32)

    def body(i, carry):
        m, l, acc = carry
        kb, vb = block_at(i * blk, blk)
        return _flash_block(qf, kb, vb, m, l, acc, scale)

    n_full = Nk // blk
    m, l, acc = jax.lax.fori_loop(0, n_full, body, (m, l, acc))
    rem = Nk - n_full * blk
    if rem:                                    # static remainder tail
        kb, vb = block_at(n_full * blk, rem)
        m, l, acc = _flash_block(qf, kb, vb, m, l, acc, scale)
    return m, l, acc


def _collective_quant() -> "str | None":
    """Wire format for rotating K/V payloads (``CDT_COLLECTIVE_QUANT``).
    ``None`` (the default) keeps the ring bit-exact; ``"int8"`` halves
    the per-hop ICI bytes with one quantization round of error per
    payload (``parallel/overlap.quant_error_bound``). Resolved at trace
    time, like every other kernel gate."""
    mode = constants.COLLECTIVE_QUANT.get()
    return None if mode == "none" else mode


def _ring_rotate(axis: str, n_shards: int, *payloads):
    """One ring hop of the K/V payload set — (tensor, scale) pairs when
    quantized (the scale rotates with its tensor), plain tensors
    otherwise."""
    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]
    return tuple(jax.lax.ppermute(p, axis, perm) for p in payloads)


@_attn_core
def ring_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    axis: str = constants.AXIS_SEQUENCE,
) -> jax.Array:
    """Exact attention with K/V sharded over ``axis``.

    Call inside ``shard_map``: every shard holds [B, N/s, H, D] of q/k/v;
    returns the local query shard's outputs [B, N/s, H, D]. The K/V pair
    makes ``s`` hops around the ring (``ppermute``) — the collective is
    already decomposed into per-block steps interleaved with the
    attention compute each arriving block unblocks, so XLA schedules
    hop ``i+1``'s neighbour transfer under hop ``i``'s FLOPs (the
    overlap schedule the fused-collective tiers borrow from here).

    Under ``CDT_COLLECTIVE_QUANT=int8`` each shard quantizes its K/V
    block ONCE and the int8 payload (+ absmax scale) rotates; every
    contribution carries exactly one quantization round
    (``absmax/254`` per element) regardless of ring length. Default is
    the bit-exact bf16/f32 ring.
    """
    n_shards = _axis_size(axis)
    B, Nq, H, D = q.shape
    scale = 1.0 / (D ** 0.5)
    qf = q.astype(jnp.float32)
    quant = _collective_quant()

    # initial carries must be marked axis-varying for the fori_loop carry
    # types to match (they mix with shard-varying q/k/v on step one)
    m0 = _pvary(jnp.full((B, H, Nq), -jnp.inf, jnp.float32), axis)
    l0 = _pvary(jnp.zeros((B, H, Nq), jnp.float32), axis)
    acc0 = _pvary(jnp.zeros((B, Nq, H, D), jnp.float32), axis)

    if quant == "int8":
        from ..parallel.overlap import wire_dequantize, wire_quantize

        kq, ks = wire_quantize(k)
        vq, vs = wire_quantize(v)

        def body(i, carry):
            m, l, acc, kq, ks, vq, vs = carry
            m, l, acc = _hop_attend(qf, wire_dequantize(kq, ks),
                                    wire_dequantize(vq, vs), m, l, acc,
                                    scale)
            kq, ks, vq, vs = _ring_rotate(axis, n_shards, kq, ks,
                                          vq, vs)
            return m, l, acc, kq, ks, vq, vs

        m, l, acc = jax.lax.fori_loop(
            0, n_shards, body, (m0, l0, acc0, kq, ks, vq, vs))[:3]
    else:
        def body(i, carry):
            m, l, acc, k_cur, v_cur = carry
            m, l, acc = _hop_attend(qf, k_cur, v_cur, m, l, acc, scale)
            k_nxt, v_nxt = _ring_rotate(axis, n_shards, k_cur, v_cur)
            return m, l, acc, k_nxt, v_nxt

        m, l, acc = jax.lax.fori_loop(
            0, n_shards, body, (m0, l0, acc0, k, v))[:3]
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


@_attn_core
def joint_ring_attention(
    q: jax.Array,
    txt_k: jax.Array, txt_v: jax.Array,
    img_k: jax.Array, img_v: jax.Array,
    axis: str = constants.AXIS_SEQUENCE,
) -> jax.Array:
    """Ring attention for MMDiT-style joint text+image sequences.

    Image K/V are sharded over ``axis`` and rotate around the ring; text
    K/V are short and replicated on every shard, folded in once as the
    first accumulation block (folding them per-hop would double-count).
    ``q`` may contain any mix of text/image queries — every query attends
    over the full joint sequence exactly.

    ``CDT_COLLECTIVE_QUANT=int8`` applies to the ROTATING image K/V only
    (one quantization round per payload); the replicated text block is
    never on the wire and stays exact.
    """
    n_shards = _axis_size(axis)
    B, Nq, H, D = q.shape
    scale = 1.0 / (D ** 0.5)
    qf = q.astype(jnp.float32)
    quant = _collective_quant()

    m0 = jnp.full((B, H, Nq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Nq), jnp.float32)
    acc0 = jnp.zeros((B, Nq, H, D), jnp.float32)
    # text block once (replicated on all shards)
    m0, l0, acc0 = _flash_block(
        qf, txt_k.astype(jnp.float32), txt_v.astype(jnp.float32),
        m0, l0, acc0, scale)
    m0 = _pvary(m0, axis)
    l0 = _pvary(l0, axis)
    acc0 = _pvary(acc0, axis)

    if quant == "int8":
        from ..parallel.overlap import wire_dequantize, wire_quantize

        kq, ks = wire_quantize(img_k)
        vq, vs = wire_quantize(img_v)

        def body(i, carry):
            m, l, acc, kq, ks, vq, vs = carry
            m, l, acc = _hop_attend(qf, wire_dequantize(kq, ks),
                                    wire_dequantize(vq, vs), m, l, acc,
                                    scale)
            kq, ks, vq, vs = _ring_rotate(axis, n_shards, kq, ks,
                                          vq, vs)
            return m, l, acc, kq, ks, vq, vs

        m, l, acc = jax.lax.fori_loop(
            0, n_shards, body, (m0, l0, acc0, kq, ks, vq, vs))[:3]
    else:
        def body(i, carry):
            m, l, acc, k_cur, v_cur = carry
            m, l, acc = _hop_attend(qf, k_cur, v_cur, m, l, acc, scale)
            k_nxt, v_nxt = _ring_rotate(axis, n_shards, k_cur, v_cur)
            return m, l, acc, k_nxt, v_nxt

        m, l, acc = jax.lax.fori_loop(
            0, n_shards, body, (m0, l0, acc0, img_k, img_v))[:3]
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    axis: str = constants.AXIS_SEQUENCE,
) -> jax.Array:
    """Exact attention via head redistribution.

    Inside ``shard_map`` with [B, N/s, H, D] shards: all_to_all to
    [B, N, H/s, D] (full sequence, head subset), dense local attention,
    all_to_all back. Requires ``H % axis_size == 0``.
    """
    n_shards = _axis_size(axis)
    H = q.shape[2]
    if H % n_shards:
        raise ValueError(
            f"ulysses needs heads ({H}) divisible by shards ({n_shards})")
    # [B, N/s, H, D] → [B, N, H/s, D]: split heads, concat sequence
    def to_heads(x):
        return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)

    def to_seq(x):
        return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)

    with device_scope("attn_core"):
        qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    out = full_attention(qh, kh, vh)
    with device_scope("attn_core"):
        return to_seq(out)


# --- two row segments: MMDiT joint attention ---------------------------------

from typing import NamedTuple as _NamedTuple


class Columns(_NamedTuple):
    """Columns ``[index·w, (index+1)·w)`` of ``array`` ``[B, N, count·w]``,
    not cut out: q, k or v inside a ``qkv`` product's output, which the
    two-segment kernel reads in place (``ops/flash_joint.py``)."""

    array: jax.Array
    index: int = 0
    count: int = 1

    @property
    def width(self) -> int:
        return self.array.shape[-1] // self.count

    def cut(self) -> jax.Array:
        """The columns as an array of their own ``[B, N, w]``."""
        if self.count == 1:
            return self.array
        return jax.lax.slice_in_dim(self.array, self.index * self.width,
                                    (self.index + 1) * self.width, axis=2)


def as_heads(x, num_heads: int) -> jax.Array:
    """``[B, N, H, D]`` of a :class:`Columns` (cut out) or of an array that
    is in that layout already."""
    if not isinstance(x, Columns):
        return x
    B, N, _ = x.array.shape
    return x.cut().reshape(B, N, num_heads, -1)


def _as_columns(x) -> Columns:
    if isinstance(x, Columns):
        return x
    B, N, H, D = x.shape
    return Columns(x.reshape(B, N, H * D))


def _joint_plan(choice, segments, head_dim: int, dtype):
    """Tiles of the two-segment packed call where a joint site takes it:
    the choice is ``packed`` with K/V resident over the joint rows, and the
    geometry is one ``flash_joint.joint_plan`` serves. Else None."""
    if segments is None or choice.tier != "packed":
        return None
    from .flash_joint import joint_plan

    txt_len, img_len = segments
    if choice.block_k < txt_len + img_len:
        return None
    return joint_plan(txt_len, img_len, head_dim, itemsize_of(dtype))


def _joint_blocks_label(choice, segments, head_dim: int, dtype):
    """'<image q rows>+<text q rows>/<image K rows>+<text K tile>:k-resident'
    where a joint site's call has tiles a segment, None where it is the
    one-segment call's label that holds."""
    plan = _joint_plan(choice, segments, head_dim, dtype)
    return plan and plan.label(segments[1])


def joint_attention(txt, img, num_heads: int, prefer_flash: bool = False,
                    choice=None) -> tuple[jax.Array, jax.Array]:
    """Attention of an MMDiT joint block: text rows and image rows, each
    query over every key of both. ``txt`` / ``img`` are the segment's
    ``(q, k, v)``, each ``[B, N, H, D]`` or — what no norm or rope touched —
    :class:`Columns` of the ``qkv`` product's output. Returns the text rows'
    and the image rows' answers, ``[B, T, H·D]`` and ``[B, N, H·D]``: what
    the two output projections read.

    ``select_kernel`` is asked once, with the JOINT lengths (a caller that
    has asked hands its ``choice`` in). On ``packed`` with K/V resident and
    lane-aligned image rows the two-segment kernel reads every operand
    where it lies (``ops/flash_joint.py``). Everywhere else — the ``xla``
    tier, a streamed K, ragged image rows, the Pallas interpreter inside
    ``shard_map`` — the segments are concatenated for
    :func:`full_attention` and its answer is split."""
    txt, img = ([_as_columns(x) for x in seg] for seg in (txt, img))
    T, N = txt[0].array.shape[1], img[0].array.shape[1]
    D = img[0].width // num_heads
    dtype = img[0].array.dtype
    if choice is None:
        choice = select_kernel(T + N, T + N, num_heads, D, dtype=dtype,
                               prefer_flash=prefer_flash, segments=(T, N))
    plan = _joint_plan(choice, (T, N), D, dtype)
    if plan is not None:
        from .flash_attention import _in_manual_trace, _platform
        from .flash_joint import flash_joint_attention

        interpret = _platform() == "cpu"
        if not (interpret and _in_manual_trace(img[0].array)):
            with device_scope("attn_core"):
                return flash_joint_attention(txt, img, num_heads, plan,
                                             interpret)
    with device_scope("attn_proj"):
        q, k, v = (jnp.concatenate([as_heads(t, num_heads),
                                    as_heads(i, num_heads)], axis=1)
                   for t, i in zip(txt, img))
    out = full_attention(q, k, v, choice=choice)
    with device_scope("attn_proj"):
        B = out.shape[0]
        return (out[:, :T].reshape(B, T, num_heads * D),
                out[:, T:].reshape(B, N, num_heads * D))


# a later kernel's tier, added where it moves no line of the code above
# (ops/block_select_attention.py: the K/V tile index comes from a
# prefetched table of each query tile's selected blocks)
CAUSAL_TIER_REASONS["block_select"] = (
    "chunked prefill over the key blocks each query selects")
