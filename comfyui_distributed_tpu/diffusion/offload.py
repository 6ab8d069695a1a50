"""Host-offloaded FLUX execution: stream transformer blocks through HBM.

A full FLUX-class DiT is ~12B params — ~24 GB of bf16 weights, more than
a v5e chip's 15.75 GB HBM. The reference sidesteps this with ComfyUI's
model offload machinery (``/root/reference/api/job_routes.py:160-203``
reaches into ``comfy.model_management``; lowvram streaming sits under
every node). The TPU-native equivalent here:

- params stay **host-pinned** (numpy); a configurable **resident set**
  (first blocks + all glue: embedders, final head) lives in HBM;
- the remaining blocks stream through a double-buffered window: the
  next block's weights start their async ``device_put`` before the
  current block's compute is dispatched, so transfer and MXU time
  overlap;
- every double block shares ONE compiled program (same shapes), every
  single block another — two block compiles total, not depth-many;
- each block's ~20 param leaves are **flattened into one contiguous
  buffer per dtype** at init, so streaming a block is ONE ``device_put``
  instead of ~20: ~1100 small puts per forward pay a fixed cost each,
  and fewer, larger DMAs keep the stream bandwidth-bound. The block
  programs slice the buffer back into leaves in-trace (static offsets —
  XLA sees views, not copies).

**fp8 weight residency (r04).** Streaming bf16 blocks moves ~13 GB per
step — bandwidth-bound on any link. The decisive optimization is the
same one the reference ecosystem
ships as its standard low-VRAM FLUX path (fp8 checkpoints): quantize
the block **kernels** to ``float8_e4m3fn`` with per-output-channel
absmax scales. At fp8 the full 12B block set is ~12 GB — it fits
RESIDENT in one v5e's HBM, so after a one-time upload the sampling loop
streams **zero** bytes. When every block of a kind is resident, the
forward collapses to ONE compiled program: ``lax.scan`` over the
stacked per-kind weight buffers (dequant happens in-trace per block —
an elementwise cast+mul XLA fuses into the first matmul's operand
read). Weights-only per-channel e4m3 carries ~0.1% relative output
error per matmul (noise averages over the 3072-wide contraction) —
numerically pinned by ``tests/test_offload.py``.

The python-level per-block loop remains the fallback whenever the
(possibly quantized) model still exceeds the resident budget: blocks
beyond the budget stream per step, at half the bytes under fp8.

Knobs: ``CDT_OFFLOAD=1`` enables the path in the flow pipeline /
bench; ``CDT_OFFLOAD_RESIDENT_GB`` caps the resident set (default 13);
``CDT_OFFLOAD_STREAM_DTYPE`` selects ``float8_e4m3fn`` (default — the
fits-in-HBM fast path) or ``native`` (exact bf16/f32 streaming).
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
from flax import linen as nn

from ..models.dit import (DiT, DiTConfig, DoubleBlock, MLPEmbedder,
                          Modulation, SingleBlock, _modulate, image_ids,
                          patchify, rope_freqs, sincos_2d, unpatchify)
from ..models.layers import timestep_embedding
from ..utils import constants

_GLUE_KEYS = ("img_in", "txt_in", "time_in", "vector_in", "guidance_in",
              "final_mod", "img_out")

_F8 = "float8_e4m3fn"
_F8_MAX = 448.0               # largest finite e4m3fn magnitude
_QUANT_MIN_SIZE = 4096        # only kernels are worth quantizing


def offload_enabled(default: bool = False) -> bool:
    """One definition of the CDT_OFFLOAD gate. Server paths default OFF
    (resident execution); the accelerator flux bench defaults ON (full
    depth cannot run any other way on one chip)."""
    v = constants.OFFLOAD.get()
    return default if v is None else v


def resident_budget_bytes() -> int:
    return int(constants.OFFLOAD_RESIDENT_GB.get() * (1 << 30))


def stream_dtype_default() -> str:
    """``float8_e4m3fn`` (default) or ``native``."""
    return constants.OFFLOAD_STREAM_DTYPE.get()


def ladder_mode() -> str:
    """How a FULLY-RESIDENT offloaded sample runs its sigma ladder:

    - ``"jit"`` (default): the whole ladder is ONE compiled program —
      fastest (no per-step dispatch), but not interruptible mid-run and
      recompiled per distinct step count;
    - ``"step"``: python loop over the single-forward program — one
      dispatch per step (µs on a real host), responsive to
      ``/distributed/interrupt`` between steps, no per-step-count
      recompiles. Streamed (partially-resident) executors always run
      per step."""
    return constants.OFFLOAD_LADDER.get()


def normalize_stream_dtype(sd: Optional[str]) -> str:
    """Canonical stream-dtype name — ONE definition, shared by the
    executor and every cache key built over it (aliased spellings must
    not build duplicate multi-GB executors). ``bfloat16``/``bf16`` are
    synonyms for ``native`` — "leave dtypes untouched, don't quantize" —
    NOT a cast: float32 params stream as float32 under every non-fp8
    spelling."""
    sd = sd or stream_dtype_default()
    if sd in ("fp8", "f8", "float8", _F8):
        return _F8
    if sd in ("native", "bfloat16", "bf16", "exact"):
        return "native"
    raise ValueError(f"unknown CDT_OFFLOAD_STREAM_DTYPE {sd!r} "
                     f"(use {_F8!r} or 'native')")


def _should_quantize_meta(shape, dtype, quantize: bool) -> bool:
    """ONE predicate for both the size planner and the packer — if these
    ever disagreed, ``plan_offload`` would mis-place blocks silently.
    Operates on (shape, dtype) so planning also works over ABSTRACT
    trees (``jax.eval_shape`` — plan a 14B model without materializing
    28 GB)."""
    dt = np.dtype(dtype)
    is_float = dt.kind == "f" or dt == ml_dtypes.bfloat16
    size = 1
    for s in shape:
        size *= int(s)
    return (quantize and len(shape) >= 2 and size >= _QUANT_MIN_SIZE
            and is_float)


def _should_quantize(a: np.ndarray, quantize: bool) -> bool:
    return _should_quantize_meta(a.shape, a.dtype, quantize)


def _leaf_packed_bytes(leaf, quantize: bool) -> int:
    """Packed size of one leaf WITHOUT packing it (placement planning
    must not materialize flat copies — peak-RSS discipline). ``leaf``
    only needs ``.shape``/``.dtype`` — ndarray, jax.Array, or
    ShapeDtypeStruct all work."""
    shape, dt = leaf.shape, np.dtype(leaf.dtype)
    size = 1
    for s in shape:
        size *= int(s)
    if _should_quantize_meta(shape, dt, quantize):
        return size + int(shape[-1]) * 4               # fp8 + f32 scales
    return size * dt.itemsize


def block_packed_bytes(blk, quantize: bool) -> int:
    return sum(_leaf_packed_bytes(l, quantize)
               for l in jax.tree_util.tree_leaves(blk))


def _kind_of(name: str) -> str:
    """Block kind = the prefix before the trailing index: ``double_3`` →
    ``double``, ``block_17`` → ``block``. Every block of a kind shares
    one flat layout and one compiled program."""
    return name.rsplit("_", 1)[0]


def plan_offload(params, budget: int,
                 stream_dtype: Optional[str] = None,
                 block_prefixes: tuple = ("double", "single"),
                 glue_keys: tuple = _GLUE_KEYS) -> dict:
    """Placement plan without building anything: which blocks would be
    resident vs streamed under ``budget``, and the per-step streamed
    byte count. ``bench.py`` uses this to run its host-RAM leak guard
    BEFORE the multi-GB executor build. ``block_prefixes`` order is
    execution order (FLUX: doubles then singles; WAN: ``("block",)``)."""
    quantize = normalize_stream_dtype(stream_dtype) == _F8
    inner = params["params"] if "params" in params else params
    order = []
    for prefix in block_prefixes:
        ns = [k for k in inner
              if k.startswith(prefix + "_")
              and k[len(prefix) + 1:].isdigit()]
        order += sorted(ns, key=lambda n: int(n.rsplit("_", 1)[1]))
    glue = {k: inner[k] for k in glue_keys if k in inner}
    used = tree_bytes(glue)
    resident, streamed, streamed_bytes = [], [], 0
    for name in order:
        size = block_packed_bytes(inner[name], quantize)
        if used + size <= budget:
            resident.append(name)
            used += size
        else:
            streamed.append(name)
            streamed_bytes += size
    return {"order": order, "resident": resident, "streamed": streamed,
            "resident_bytes": used, "streamed_bytes": streamed_bytes,
            "fully_resident": not streamed}


def tree_bytes(tree) -> int:
    return sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
               for l in jax.tree_util.tree_leaves(tree))


def materialize_host_params(abstract_tree, seed: int = 0):
    """ShapeDtypeStruct tree → host numpy tree (random normal ~N(0,0.02)
    — the bench path for models whose random init cannot fit on device;
    real weights arrive via the converter/orbax restore instead).
    ``default_rng`` draws float32 natively — a 12B-param tree fills in
    ~1 min on one core instead of several."""
    rng = np.random.default_rng(seed)

    def leaf(l):
        a = rng.standard_normal(l.shape, dtype=np.float32) * np.float32(0.02)
        return a.astype(l.dtype)

    return jax.tree_util.tree_map(leaf, abstract_tree)


def _flatten_block(blk, quantize: bool = False) -> tuple[dict, Any, tuple]:
    """Host-side: a block's param tree → ``({key: 1-D buffer}, treedef,
    metas)`` with ``metas[i] = (buf_key, offset, shape, scale_offset,
    out_dtype)`` in leaf order.

    Unquantized leaves pack into one buffer per dtype (``buf_key`` =
    dtype name, ``scale_offset`` = -1). With ``quantize=True``, float
    kernels (ndim≥2, ≥4096 elements) pack into an ``"float8_e4m3fn"``
    buffer with per-output-channel (last-axis) absmax scales appended to
    a float32 ``"scale"`` buffer; the in-trace unflatten dequantizes back
    to ``out_dtype``. Everything small (biases, norms, qk scales) stays
    exact in its native buffer."""
    leaves, treedef = jax.tree_util.tree_flatten(blk)
    chunks: dict[str, list] = {}
    offsets: dict[str, int] = {}
    metas = []
    for leaf in leaves:
        a = np.asarray(leaf)
        quant = _should_quantize(a, quantize)
        if quant:
            w = a.astype(np.float32)
            red = tuple(range(a.ndim - 1))          # all but output axis
            absmax = np.max(np.abs(w), axis=red)
            scale = np.where(absmax == 0.0, 1.0,
                             absmax / _F8_MAX).astype(np.float32)
            q = (w / scale).astype(ml_dtypes.float8_e4m3fn)
            off = offsets.get(_F8, 0)
            s_off = offsets.get("scale", 0)
            metas.append((_F8, off, a.shape, s_off, a.dtype.name))
            offsets[_F8] = off + int(a.size)
            offsets["scale"] = s_off + int(scale.size)
            chunks.setdefault(_F8, []).append(q.ravel())
            chunks.setdefault("scale", []).append(scale)
        else:
            dt = a.dtype.name
            off = offsets.get(dt, 0)
            metas.append((dt, off, a.shape, -1, dt))
            offsets[dt] = off + int(a.size)
            chunks.setdefault(dt, []).append(a.ravel())
    bufs = {dt: np.concatenate(cs) for dt, cs in chunks.items()}
    return bufs, treedef, tuple(metas)


def _unflatten_block(bufs, treedef, metas):
    """In-trace inverse of ``_flatten_block``: static-offset slices +
    reshapes — XLA treats them as views of the streamed buffer. fp8
    segments dequantize via cast + per-output-channel scale (fused by
    XLA into the consuming matmul's operand read)."""
    leaves = []
    for buf_key, off, shape, s_off, out_dtype in metas:
        n = 1
        for s in shape:
            n *= int(s)
        seg = jax.lax.slice(bufs[buf_key], (off,), (off + n,))
        seg = seg.reshape(shape)
        if s_off >= 0:
            scale = jax.lax.slice(bufs["scale"], (s_off,),
                                  (s_off + int(shape[-1]),))
            seg = (seg.astype(jnp.float32) * scale).astype(
                jnp.dtype(out_dtype))
        leaves.append(seg)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def quant_cache_dir() -> Optional[str]:
    """``CDT_OFFLOAD_CACHE_DIR``: directory for cached quantized flat
    blocks. Quantizing a 12B model costs ~5 single-core minutes on every
    process start; the cache cuts a warm executor build to a disk read."""
    return constants.OFFLOAD_CACHE_DIR.get() or None


def _params_fingerprint(inner, names) -> str:
    """Cheap content fingerprint of the block params: per leaf, shape +
    dtype + fnv1a64 of ≤4096 single bytes sampled at an even stride
    across the buffer (full hashing of 24 GB would cost more than it
    saves). Stale-cache safety, not cryptographic integrity: a swapped
    checkpoint with identical shapes whose changes all fall between the
    sampled bytes is the (documented) blind spot."""
    from ..native import hash64

    h = hash64(b"cdt-quant-cache-v1|e4m3-perchannel")
    for name in names:
        for leaf in jax.tree_util.tree_leaves(inner[name]):
            a = np.ascontiguousarray(leaf)
            raw = a.reshape(-1).view(np.uint8)
            stride = max(1, raw.size // 4096)
            sample = raw[::stride][:4096].tobytes()
            mix = hash64(f"{a.shape}|{a.dtype}".encode() + sample)
            h = (h ^ mix) * 1099511628211 & 0xFFFFFFFFFFFFFFFF
    return f"{h:016x}"


class _QuantCache:
    """Per-block ``.npy`` files + a JSON manifest, all inside a
    fingerprint-named subdirectory of the cache root — concurrent cold
    builds of *different* checkpoints sharing one ``CDT_OFFLOAD_CACHE_DIR``
    land in disjoint subdirs, so one can never validate the other's
    block files. Writes are tmp+rename atomic; a fingerprint mismatch
    or any unreadable/garbled entry falls back to re-quantizing (never
    fatal — construct via :func:`_open_quant_cache`)."""

    def __init__(self, root: str, fingerprint: str):
        import json
        import pathlib

        self.fingerprint = fingerprint
        self.dir = pathlib.Path(root) / fingerprint
        self.dir.mkdir(parents=True, exist_ok=True)   # may raise: see
        self.manifest = self.dir / "manifest.json"    # _open_quant_cache
        self.metas: dict[str, tuple] = {}
        self.valid = False
        try:
            m = json.loads(self.manifest.read_text())
            if m.get("fingerprint") == fingerprint:
                self.metas = {
                    kind: tuple((bk, off, tuple(shape), s_off, dt)
                                for bk, off, shape, s_off, dt in rows)
                    for kind, rows in m["metas"].items()}
                self.valid = True
        except (OSError, ValueError, KeyError, TypeError, AttributeError):
            pass

    def load(self, name: str) -> Optional[dict]:
        if not self.valid:
            return None
        out = {}
        rows = self.metas.get(_kind_of(name), ())
        keys = {bk for bk, *_ in rows}
        if any(s_off >= 0 for _, _, _, s_off, _ in rows):
            keys.add("scale")
        for key in keys:
            p = self.dir / f"{name}.{key.replace('/', '_')}.npy"
            try:
                arr = np.load(p)
                # np.save round-trips ml_dtypes bytes but loads them as
                # void ('|V1'/'|V2') — re-view as the real dtype, which
                # is the buffer key itself ('scale' buffers are f32)
                want = jnp.dtype("float32" if key == "scale" else key)
                if arr.dtype != want:
                    arr = arr.view(want)
                out[key] = arr
            except (OSError, ValueError, TypeError):
                return None
        return out or None

    def save(self, name: str, bufs: dict) -> None:
        import os as _os

        for key, arr in bufs.items():
            p = self.dir / f"{name}.{key.replace('/', '_')}.npy"
            tmp = p.with_suffix(".tmp.npy")
            try:
                np.save(tmp, arr)
                _os.replace(tmp, p)
            except OSError:
                return

    def finalize(self, metas_by_kind: dict) -> None:
        import json
        import os as _os

        payload = json.dumps({
            "fingerprint": self.fingerprint,
            "metas": {k: [[bk, off, list(shape), s_off, dt]
                          for bk, off, shape, s_off, dt in rows]
                      for k, rows in metas_by_kind.items()}})
        tmp = self.manifest.with_suffix(".tmp")
        try:
            tmp.write_text(payload)
            _os.replace(tmp, self.manifest)
        except OSError:
            pass
        self.metas = metas_by_kind
        self.valid = True


def _open_quant_cache(root: str, fingerprint: str) -> "Optional[_QuantCache]":
    """Never-fatal constructor: an unwritable/uncreatable cache dir
    (read-only mount, bad env var) degrades to no caching rather than
    failing the executor build."""
    try:
        return _QuantCache(root, fingerprint)
    except OSError:
        return None


class _Embed(nn.Module):
    """Pre-block glue of ``DiT.__call__`` with identical submodule names,
    so the full model's param tree slices straight in (equivalence is
    pinned by ``tests/test_offload.py``)."""

    config: DiTConfig

    @nn.compact
    def __call__(self, x, t, context, pooled, guidance):
        cfg = self.config
        dt = cfg.jnp_dtype
        B, H, W, _ = x.shape
        p = cfg.patch_size
        tokens = patchify(x.astype(dt), p)
        img = nn.Dense(cfg.hidden, dtype=dt, name="img_in")(tokens)
        if cfg.pos_embed != "rope":
            img = img + sincos_2d(H // p, W // p, cfg.hidden)[None].astype(dt)
        txt = nn.Dense(cfg.hidden, dtype=dt, name="txt_in")(
            context.astype(dt))
        vec = MLPEmbedder(cfg.hidden, dt, name="time_in")(
            timestep_embedding(t * 1000.0, 256).astype(dt))
        vec = vec + MLPEmbedder(cfg.hidden, dt, name="vector_in")(
            pooled.astype(dt))
        if cfg.guidance_embed:
            gvec = guidance if guidance is not None else jnp.full((B,), 3.5)
            vec = vec + MLPEmbedder(cfg.hidden, dt, name="guidance_in")(
                timestep_embedding(gvec * 1000.0, 256).astype(dt))
        return img, txt, vec


def _build_block_store(obj, params, budget: int,
                       stream_dtype: Optional[str],
                       block_prefixes: tuple, glue_keys: tuple,
                       expected_blocks: Optional[int] = None) -> None:
    """Shared executor substrate (FLUX and WAN): quantize/flatten the
    transformer blocks, decide residency under ``budget``, and upload.

    Fills on ``obj``: ``stream_dtype``, ``block_order``, ``resident``,
    ``streamed``, ``stacked``, ``_layout`` (per-kind ``(treedef,
    metas)``), ``glue`` (on device), ``resident_bytes``. Requires
    ``obj.device`` set. Packing is plan-first then one-block-at-a-time:
    peak host RSS stays ~one block (or one stack row-fill) above the
    params tree instead of a full flat copy of the model. With the
    ``CDT_OFFLOAD_CACHE_DIR`` quant cache, warm builds skip quantizing
    entirely."""
    sd = normalize_stream_dtype(stream_dtype)
    obj.stream_dtype = sd
    quantize = sd == _F8
    inner = params["params"] if "params" in params else params

    glue = {k: inner[k] for k in glue_keys if k in inner}
    plan = plan_offload(params, budget, sd, block_prefixes, glue_keys)
    if (expected_blocks is not None
            and len(plan["order"]) != expected_blocks):
        # a partially-restored/mis-converted checkpoint must fail LOUDLY
        # at build time, not execute fewer blocks and emit plausible
        # garbage
        raise ValueError(
            f"offload: params hold {len(plan['order'])} transformer "
            f"blocks ({block_prefixes}) but the config declares "
            f"{expected_blocks}")
    obj.block_order = plan["order"]
    obj.resident = {}
    obj.streamed = {}
    obj.stacked = {}
    # per-kind flat layout (identical across every block of a kind —
    # same module config, same shapes): treedef + (buf_key, offset,
    # shape, scale_off, out_dtype) per leaf, captured statically by
    # the block programs
    obj._layout = {}
    cache: Optional[_QuantCache] = None
    if quantize and quant_cache_dir() and obj.block_order:
        cache = _open_quant_cache(
            quant_cache_dir(),
            _params_fingerprint(inner, obj.block_order))

    def pack(name: str):
        """Cached-or-fresh flat buffers for one block; records the
        per-kind layout either way."""
        kind = _kind_of(name)
        if cache is not None and kind in cache.metas:
            bufs = cache.load(name)
            if bufs is not None:
                obj._layout.setdefault(
                    kind, (jax.tree_util.tree_structure(inner[name]),
                           cache.metas[kind]))
                return bufs
        bufs, treedef, metas = _flatten_block(inner[name],
                                              quantize=quantize)
        obj._layout.setdefault(kind, (treedef, metas))
        if cache is not None:
            cache.save(name, bufs)
        return bufs

    if plan["fully_resident"] and obj.block_order:
        # everything fits: upload per-kind STACKS (one put per buffer
        # key) and run the scan fast path — zero bytes streamed per
        # step, one dispatch per forward. Stacks are filled row by row
        # so only stack + one block are live.
        for kind in block_prefixes:
            names = [n for n in obj.block_order if _kind_of(n) == kind]
            if not names:
                continue
            rows: dict[str, np.ndarray] = {}
            for i, name in enumerate(names):
                bufs = pack(name)
                if not rows:
                    rows = {k: np.empty((len(names),) + v.shape, v.dtype)
                            for k, v in bufs.items()}
                for k, v in bufs.items():
                    rows[k][i] = v
            obj.stacked[kind] = jax.device_put(rows, obj.device)
            del rows
    else:
        for name in obj.block_order:
            bufs = pack(name)
            if name in set(plan["resident"]):
                obj.resident[name] = jax.device_put(bufs, obj.device)
            else:
                # host numpy: no device residency, fetched per step as
                # ONE put per flat buffer
                obj.streamed[name] = bufs
    if cache is not None and not cache.valid:
        cache.finalize({k: v[1] for k, v in obj._layout.items()})
    obj.glue = jax.device_put(glue, obj.device)
    obj.resident_bytes = plan["resident_bytes"]


def release_store(obj) -> None:
    """Free every device buffer an executor holds (stacked/resident
    blocks + glue) — the dual-expert video swap uploads the other
    expert into the same HBM. The executor object is dead afterwards;
    build a fresh one to run again."""
    for tree in (obj.stacked, obj.resident,
                 {"glue": getattr(obj, "glue", None)}):
        for leaf in jax.tree_util.tree_leaves(tree):
            if hasattr(leaf, "delete"):     # device arrays only;
                leaf.delete()               # idempotent on deleted ones
    obj.stacked = {}
    obj.resident = {}


class OffloadedFlux:
    """Single-device FLUX executor with host-resident streamed blocks.

    ``stream_dtype``: ``"float8_e4m3fn"`` (default via
    ``CDT_OFFLOAD_STREAM_DTYPE``) quantizes block kernels host-side; when
    the whole quantized block set fits ``resident_bytes`` the executor
    uploads per-kind STACKED buffers once and runs the forward as one
    compiled ``lax.scan`` program (``self.stacked``), eliminating both
    per-step streaming and per-block dispatch. ``"native"`` keeps exact
    dtypes (the r03 behavior)."""

    def __init__(self, dit: DiT, params, resident_bytes: Optional[int] = None,
                 device=None, stream_dtype: Optional[str] = None):
        import dataclasses as _dc

        # memory-starved by definition (weights fill HBM): the block
        # programs must use the pallas flash kernel — XLA's fused
        # attention OOM'd at compile here (r04: 16.89 GB vs 15.75 HBM
        # at 4608 tokens × 24 heads with the fp8 set resident). Applied
        # unconditionally: this single-device executor always runs
        # blocks with sp_axis=None, so even a "ring"-configured DiT
        # takes the dense branch here and needs the preference.
        self.cfg: DiTConfig = _dc.replace(dit.config, attn_backend="flash")
        self.device = device or jax.devices()[0]
        budget = (resident_budget_bytes() if resident_bytes is None
                  else int(resident_bytes))
        _build_block_store(self, params, budget, stream_dtype,
                           block_prefixes=("double", "single"),
                           glue_keys=_GLUE_KEYS,
                           expected_blocks=(self.cfg.depth_double
                                            + self.cfg.depth_single))

        cfg = self.cfg

        def embed_fn(gl, x, t, ctx, pl, g):
            return _Embed(cfg).apply(
                {"params": {k: gl[k] for k in
                            ("img_in", "txt_in", "time_in", "vector_in",
                             "guidance_in") if k in gl}},
                x, t, ctx, pl, g)

        self._embed = jax.jit(embed_fn)

        def dblock(bufs, img, txt, vec, pe_i, pe_t):
            bp = _unflatten_block(bufs, *self._layout["double"])
            return DoubleBlock(cfg).apply(
                {"params": bp}, img, txt, vec, None, pe_i, pe_t)

        def sblock(bufs, xcat, vec, pe_f, T):
            bp = _unflatten_block(bufs, *self._layout["single"])
            return SingleBlock(cfg).apply(
                {"params": bp}, xcat, vec, T, None, pe_f)

        self._dblock = jax.jit(dblock)
        self._sblock = jax.jit(sblock, static_argnames=("T",))

        def head_fn(gl, img, vec):
            dt = cfg.jnp_dtype
            sh, sc, _ = Modulation(1, cfg.hidden, dt).apply(
                {"params": gl["final_mod"]}, vec)
            img = _modulate(
                nn.LayerNorm(use_scale=False, use_bias=False,
                             dtype=dt).apply({}, img), sh, sc)
            return nn.Dense(cfg.patch_size ** 2 * cfg.in_channels,
                            dtype=jnp.float32).apply(
                {"params": gl["img_out"]}, img.astype(jnp.float32))

        self._head = jax.jit(head_fn)

        def fwd_resident(gl, dstack, sstack, x, t, ctx, pl, g,
                         pe_img, pe_txt, pe_full):
            """Whole forward as ONE program: glue embed → scan over the
            stacked double blocks → scan over the stacked single blocks
            → final head. Per-block dequant happens inside the scan
            bodies."""
            img, txt, vec = embed_fn(gl, x, t, ctx, pl, g)
            if dstack is not None:
                def dbody(carry, bufs):
                    im, tx = carry
                    return dblock(bufs, im, tx, vec, pe_img, pe_txt), None

                (img, txt), _ = jax.lax.scan(dbody, (img, txt), dstack)
            T = txt.shape[1]
            xcat = jnp.concatenate([txt, img], axis=1)
            if sstack is not None:
                def sbody(xc, bufs):
                    return sblock(bufs, xc, vec, pe_full, T), None

                xcat, _ = jax.lax.scan(sbody, xcat, sstack)
            return head_fn(gl, xcat[:, T:], vec)

        self._fwd_resident = jax.jit(fwd_resident)

        def ladder(gl, dstack, sstack, x, sigs, ctx, pl, g,
                   pe_img, pe_txt, pe_full, token, key, sampler):
            """The ENTIRE sigma ladder as one program (fully-resident
            only): sample()'s scan over steps wrapping fwd_resident's
            scan over blocks — zero per-step host dispatch, and since
            the whole thing is in-trace, EVERY registered sampler works
            (the python fallback is euler-only). In-trace progress via
            the same wrap_denoiser the compiled pipelines use."""
            from .progress import wrap_denoiser
            from .samplers import sample

            B, H, W, C = x.shape

            def den(xx, sigma):
                t = jnp.broadcast_to(sigma, (xx.shape[0],))
                out = fwd_resident(gl, dstack, sstack, xx, t, ctx, pl,
                                   g, pe_img, pe_txt, pe_full)
                return xx - sigma * unpatchify(out, (H, W),
                                               cfg.patch_size, C)

            d = den if token is None else wrap_denoiser(den, token, 0)
            return sample(sampler, d, x, sigs, key=key)

        self._ladder = jax.jit(ladder, static_argnames=("sampler",))

    def sample_resident(self, x, sigmas, context, pooled,
                        guidance=None, sampler: str = "euler",
                        key=None, progress_token=None):
        """Run the whole sigma ladder as ONE compiled program — valid
        only when fully resident (``self.stacked``). Removes the
        per-step python dispatch and supports every registered
        sampler (ancestral ones draw from ``key`` exactly like the dp
        path); math identical to the compiled pipelines (pinned by
        tests)."""
        if not self.stacked:
            raise RuntimeError(
                "sample_resident requires a fully-resident executor "
                "(self.stacked)")
        B, H, W, C = x.shape
        pe_img, pe_txt, pe_full = self._rope_tables(H, W,
                                                    context.shape[1])
        token = (None if progress_token is None
                 else jnp.asarray(progress_token, jnp.int32))
        if key is None:
            key = jax.random.key(0)
        return self._ladder(
            self.glue, self.stacked.get("double"),
            self.stacked.get("single"), jax.device_put(x, self.device),
            jnp.asarray(np.asarray(sigmas), jnp.float32),
            context, pooled, guidance, pe_img, pe_txt, pe_full, token,
            key, sampler)

    # --- forward -----------------------------------------------------------

    def _rope_tables(self, H: int, W: int, txt_len: int):
        """Cached per (H, W, txt_len): the tables are identical for every
        step of a sample, and the python loop can't hide the rebuild."""
        cfg = self.cfg
        if cfg.pos_embed != "rope":
            return None, None, None
        key = (H, W, txt_len)
        cached = getattr(self, "_pe_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        p = cfg.patch_size
        pe_img = rope_freqs(image_ids(H // p, W // p), cfg.axes_dim,
                            cfg.rope_theta)
        pe_txt = rope_freqs(jnp.zeros((txt_len, 3), jnp.int32),
                            cfg.axes_dim, cfg.rope_theta)
        pe_full = (jnp.concatenate([pe_txt[0], pe_img[0]], axis=0),
                   jnp.concatenate([pe_txt[1], pe_img[1]], axis=0))
        put = lambda pe: None if pe is None else jax.device_put(pe, self.device)
        out = (put(pe_img), put(pe_txt), put(pe_full))
        self._pe_cache = (key, out)
        return out

    def _fetch(self, name: str):
        if name in self.resident:
            return self.resident[name], False
        return jax.device_put(self.streamed[name], self.device), True

    def forward(self, x, t, context, pooled, guidance=None):
        """One velocity evaluation. Equivalent to ``DiT.apply``
        (sp_axis None) — pinned by tests (exact under ``native``, to
        quantization tolerance under fp8). Fully-resident executors run
        the single scan program; otherwise blocks stream through the
        double-buffered loop."""
        cfg = self.cfg
        B, H, W, C = x.shape
        pe_img, pe_txt, pe_full = self._rope_tables(H, W, context.shape[1])
        if self.stacked:
            out = self._fwd_resident(
                self.glue, self.stacked.get("double"),
                self.stacked.get("single"), x, t, context, pooled,
                guidance, pe_img, pe_txt, pe_full)
            return unpatchify(out, (H, W), cfg.patch_size, C)
        img, txt, vec = self._embed(
            self.glue, x, t, context, pooled,
            None if guidance is None else guidance)

        names = self.block_order
        # double-buffer: block i+1's weights start transferring before
        # block i's compute is dispatched
        cur, cur_streamed = self._fetch(names[0])
        xcat = None
        T = int(txt.shape[1])
        for i, name in enumerate(names):
            nxt = self._fetch(names[i + 1]) if i + 1 < len(names) else None
            if name.startswith("double"):
                img, txt = self._dblock(cur, img, txt, vec, pe_img, pe_txt)
                out = img
            else:
                if xcat is None:
                    xcat = jnp.concatenate([txt, img], axis=1)
                xcat = self._sblock(cur, xcat, vec, pe_full, T=T)
                out = xcat
            if cur_streamed:
                # BACKPRESSURE: without this barrier the python loop
                # enqueues the entire ladder's transfers ahead of the
                # device (30 steps × 24 GB of staged host buffers — a
                # measured 130 GB host OOM). Blocking on the block output
                # keeps at most cur (computing) + nxt (streaming) in
                # flight while still overlapping transfer with compute.
                jax.block_until_ready(out)
                for leaf in jax.tree_util.tree_leaves(cur):
                    leaf.delete()       # free HBM as soon as consumed
            if nxt is not None:
                cur, cur_streamed = nxt
        img = (xcat[:, T:] if xcat is not None else img)
        out = self._head(self.glue, img, vec)
        return unpatchify(out, (H, W), cfg.patch_size, C)

    def denoiser(self, context, pooled, guidance: float):
        g = jnp.full((context.shape[0],), float(guidance))

        def den(x, sigma):
            t = jnp.broadcast_to(jnp.asarray(sigma), (x.shape[0],))
            v = self.forward(x, t, context, pooled, g)
            return x - jnp.asarray(sigma) * v

        return den


_WAN_GLUE_KEYS = ("patch_embedding", "time_emb_0", "time_emb_2",
                  "time_proj_1", "text_emb_0", "text_emb_2",
                  "head_modulation", "head")


def i2v_input_concat(y, mask):
    """ONE definition of the WAN i2v model-input concat
    (``concat([x_t, mask, y])``) — used by the dp/sp denoiser
    (``VideoPipeline._i2v_inp_fn``), the streamed offload ladder, and
    the resident one-jit ladder, so the conditioning layout can never
    desynchronize between execution modes."""
    def inp_fn(x):
        return jnp.concatenate(
            [x, jnp.broadcast_to(mask, x.shape[:4] + (mask.shape[-1],)),
             jnp.broadcast_to(y, x.shape[:4] + (y.shape[-1],))], axis=-1)

    return inp_fn


class OffloadedWan:
    """Single-device WAN executor with host-resident/streamed blocks —
    the video-side counterpart of :class:`OffloadedFlux`, sharing the
    same substrate (``_build_block_store``): fp8(e4m3) per-channel
    weight quantization, fully-resident ``lax.scan`` fast path, streamed
    double-buffered fallback. This is how WAN-2.1/2.2 **14B** video
    models (28 GB bf16/expert — ~2× one chip's HBM) run on ONE chip:
    quantized, one expert resident at a time (~14 GB fp8; blocks past
    the budget stream per step). The reference covers this scale only
    via multi-GPU fan-out or ComfyUI lowvram streaming
    (``/root/reference/README.md:186-189``)."""

    def __init__(self, wan, params, resident_bytes: Optional[int] = None,
                 device=None, stream_dtype: Optional[str] = None):
        import dataclasses as _dc

        from ..models.wan import WanBlock, WanConfig  # noqa: F401

        # same OOM-measured necessity as OffloadedFlux: memory-starved
        # executors must prefer the pallas flash kernel
        self.cfg = _dc.replace(wan.config, attn_backend="flash")
        self.device = device or jax.devices()[0]
        budget = (resident_budget_bytes() if resident_bytes is None
                  else int(resident_bytes))
        _build_block_store(self, params, budget, stream_dtype,
                           block_prefixes=("block",),
                           glue_keys=_WAN_GLUE_KEYS,
                           expected_blocks=self.cfg.num_layers)

        cfg = self.cfg

        def embed_fn(gl, x, t, ctx_raw):
            sub = {k: gl[k] for k in
                   ("patch_embedding", "time_emb_0", "time_emb_2",
                    "time_proj_1", "text_emb_0", "text_emb_2")
                   if k in gl}
            return _WanEmbed(cfg).apply({"params": sub}, x, t, ctx_raw)

        def block_fn(bufs, tok, e0, ctx, pe):
            bp = _unflatten_block(bufs, *self._layout["block"])
            return WanBlock(cfg).apply({"params": bp}, tok, e0, ctx, pe,
                                       None)

        def head_fn(gl, tok, e, fhw, FHW):
            """Exact tail of ``WanModel.__call__`` (models/wan.py) over
            the glue params."""
            dt = cfg.jnp_dtype
            f, h, w = fhw
            F, H, W = FHW
            hm = (gl["head_modulation"].astype(jnp.float32)
                  + e.astype(jnp.float32)[:, None, :]).astype(dt)
            sh, sc = hm[:, 0][:, None, :], hm[:, 1][:, None, :]
            tok = nn.LayerNorm(use_scale=False, use_bias=False,
                               epsilon=cfg.eps, dtype=dt).apply(
                {}, tok) * (1 + sc) + sh
            pt, ph, pw = cfg.patch_size
            out = nn.Dense(pt * ph * pw * cfg.out_channels,
                           dtype=jnp.float32).apply(
                {"params": gl["head"]}, tok.astype(jnp.float32))
            B = tok.shape[0]
            o = cfg.out_channels
            out = out.reshape(B, f, h, w, pt, ph, pw, o)
            out = out.transpose(0, 1, 4, 2, 5, 3, 6, 7)
            return out.reshape(B, F, H, W, o)

        self._embed = jax.jit(embed_fn)
        self._block = jax.jit(block_fn)
        self._head = jax.jit(head_fn, static_argnames=("fhw", "FHW"))

        def fwd_resident(gl, bstack, x, t, ctx_raw, pe, fhw, FHW):
            tok, e0, e, ctx = embed_fn(gl, x, t, ctx_raw)

            def body(carry, bufs):
                return block_fn(bufs, carry, e0, ctx, pe), None

            tok, _ = jax.lax.scan(body, tok, bstack)
            return head_fn(gl, tok, e, fhw, FHW)

        self._fwd_resident = jax.jit(fwd_resident,
                                     static_argnames=("fhw", "FHW"))

        def wan_ladder(gl, bstack, x, sigs, ctx, gscale, pe, y, mask,
                       token, key, do_cfg, sampler):
            """Whole sigma ladder in one program (fully-resident only;
            any registered sampler). ``y``/``mask`` are TRACED i2v
            conditioning (None for t2v) — traced, not closure-captured,
            so a new start image never recompiles. CFG runs cond/uncond
            as two sequential in-trace forwards (same memory argument
            as ``denoiser``)."""
            from .progress import wrap_denoiser
            from .samplers import sample

            B, F, H, W, _ = x.shape
            pt, ph, pw = cfg.patch_size
            fhw, FHW = (F // pt, H // ph, W // pw), (F, H, W)

            inp = ((lambda xx: xx) if y is None
                   else i2v_input_concat(y, mask))

            def model_call(xx, sigma, c):
                t = jnp.broadcast_to(sigma, (xx.shape[0],))
                v = fwd_resident(gl, bstack, inp(xx), t, c, pe, fhw, FHW)
                return xx - sigma * v

            def den(xx, sigma):
                if not do_cfg:
                    return model_call(xx, sigma, ctx)
                cond = model_call(xx, sigma, ctx)
                uncond = model_call(xx, sigma, jnp.zeros_like(ctx))
                return uncond + gscale * (cond - uncond)

            d = den if token is None else wrap_denoiser(den, token, 0)
            return sample(sampler, d, x, sigs, key=key)

        self._ladder = jax.jit(wan_ladder,
                               static_argnames=("do_cfg", "sampler"))

    def sample_resident(self, x, sigmas, context,
                        guidance_scale: float = 1.0, y=None,
                        mask=None, sampler: str = "euler", key=None,
                        progress_token=None):
        """Run the whole sigma ladder as ONE compiled program — valid
        only when fully resident (``self.stacked``); any registered
        sampler (ancestral ones draw from ``key`` exactly like the dp
        path); math identical to the compiled pipelines (pinned by
        tests)."""
        if not self.stacked:
            raise RuntimeError(
                "sample_resident requires a fully-resident executor "
                "(self.stacked)")
        B, F, H, W, _ = x.shape
        pt, ph, pw = self.cfg.patch_size
        pe = self._pe_tables(F // pt, H // ph, W // pw)
        token = (None if progress_token is None
                 else jnp.asarray(progress_token, jnp.int32))
        if key is None:
            key = jax.random.key(0)
        return self._ladder(
            self.glue, self.stacked["block"],
            jax.device_put(x, self.device),
            jnp.asarray(np.asarray(sigmas), jnp.float32), context,
            jnp.float32(guidance_scale), pe, y, mask, token, key,
            do_cfg=float(guidance_scale) != 1.0, sampler=sampler)

    def _pe_tables(self, f: int, h: int, w: int):
        from ..models.wan import video_ids
        from ..models.dit import rope_freqs as _rope

        key = (f, h, w)
        cached = getattr(self, "_pe_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        pe = _rope(video_ids(f, h, w), self.cfg.axes_dim, 10000.0)
        pe = jax.device_put(pe, self.device)
        self._pe_cache = (key, pe)
        return pe

    def _fetch(self, name: str):
        if name in self.resident:
            return self.resident[name], False
        return jax.device_put(self.streamed[name], self.device), True

    def forward(self, x, t, context):
        """One velocity evaluation; equivalent to ``WanModel.apply``
        (sp_axis None, pooled ignored) — pinned by tests (exact under
        ``native``, to quantization tolerance under fp8)."""
        cfg = self.cfg
        B, F, H, W, C = x.shape
        pt, ph, pw = cfg.patch_size
        fhw = (F // pt, H // ph, W // pw)
        pe = self._pe_tables(*fhw)
        if self.stacked:
            return self._fwd_resident(
                self.glue, self.stacked["block"], x, t, context, pe,
                fhw=fhw, FHW=(F, H, W))
        tok, e0, e, ctx = self._embed(self.glue, x, t, context)
        names = self.block_order
        cur, cur_streamed = self._fetch(names[0])
        for i, name in enumerate(names):
            nxt = self._fetch(names[i + 1]) if i + 1 < len(names) else None
            tok = self._block(cur, tok, e0, ctx, pe)
            if cur_streamed:
                # same backpressure as OffloadedFlux.forward: at most
                # cur (computing) + nxt (streaming) in flight
                jax.block_until_ready(tok)
                for leaf in jax.tree_util.tree_leaves(cur):
                    leaf.delete()
            if nxt is not None:
                cur, cur_streamed = nxt
        return self._head(self.glue, tok, e, fhw=fhw, FHW=(F, H, W))

    def denoiser(self, context, guidance_scale: float = 1.0,
                 inp_fn=None):
        """CFG matching ``VideoPipeline._denoiser`` exactly, but with
        cond/uncond as two sequential forwards instead of a concat batch
        — per-token normalizations make them bit-equivalent while
        halving activation HBM (which is what this executor is short
        of). ``inp_fn`` transforms the latent before the model sees it
        (i2v mask+conditioning concat), mirroring the dp denoiser."""
        uncond_ctx = jnp.zeros_like(context)

        def model_call(x, sigma, ctx):
            t = jnp.broadcast_to(jnp.asarray(sigma), (x.shape[0],))
            inp = x if inp_fn is None else inp_fn(x)
            v = self.forward(inp, t, ctx)
            return x - jnp.asarray(sigma) * v

        if guidance_scale == 1.0:
            return lambda x, s: model_call(x, s, context)

        def denoise(x, sigma):
            cond = model_call(x, sigma, context)
            uncond = model_call(x, sigma, uncond_ctx)
            return uncond + guidance_scale * (cond - uncond)

        return denoise

    def release(self) -> None:
        """Free this expert's HBM for the dual-expert swap."""
        release_store(self)


class _WanEmbed(nn.Module):
    """Pre-block glue of ``WanModel.__call__`` with identical submodule
    names so the full model's param tree slices straight in (equivalence
    pinned by ``tests/test_offload.py``). Returns ``(tok, e0, e, ctx)``
    — ``e`` feeds the head modulation."""

    config: Any

    @nn.compact
    def __call__(self, x, t, context):
        cfg = self.config
        dt = cfg.jnp_dtype
        B = x.shape[0]
        tok = nn.Conv(cfg.dim, kernel_size=cfg.patch_size,
                      strides=cfg.patch_size, dtype=dt,
                      name="patch_embedding")(x.astype(dt))
        tok = tok.reshape(B, -1, cfg.dim)
        emb = timestep_embedding(t * 1000.0, cfg.freq_dim).astype(dt)
        e = nn.Dense(cfg.dim, dtype=dt, name="time_emb_0")(emb)
        e = nn.Dense(cfg.dim, dtype=dt, name="time_emb_2")(nn.silu(e))
        e0 = nn.Dense(cfg.dim * 6, dtype=dt, name="time_proj_1")(
            nn.silu(e)).reshape(B, 6, cfg.dim)
        ctx = nn.Dense(cfg.dim, dtype=dt, name="text_emb_0")(
            context.astype(dt))
        ctx = nn.Dense(cfg.dim, dtype=dt, name="text_emb_2")(
            nn.gelu(ctx, approximate=True))
        return tok, e0, e, ctx


def sample_euler_py(denoise, x, sigmas, on_step=None,
                    should_stop=None) -> jax.Array:
    """Python-level Euler ladder (exact math of ``samplers.sample``'s
    euler branch — pinned by tests). The streamed offloaded denoiser
    cannot live inside a ``lax.scan``, so the loop runs host-side; for
    20-50 steps the per-step dispatch cost is noise next to block
    streaming. ``on_step(sigma, x0)`` fires once per step with the
    denoised estimate — the host-side twin of the compiled samplers'
    in-trace progress callback
    (``cluster/progress.ProgressTracker.report``). ``should_stop()`` is
    checked before every step (the server's ``/distributed/interrupt``
    — the reference likewise interrupts between steps, not inside a
    dispatched kernel) and raises ``InterruptedError``."""
    sig = np.asarray(sigmas, np.float64)
    for i in range(len(sig) - 1):
        if should_stop is not None and should_stop():
            raise InterruptedError(
                f"offloaded sampling interrupted at step {i}/"
                f"{len(sig) - 1}")
        x0 = denoise(x, jnp.asarray(sig[i], jnp.float32))
        if should_stop is not None:
            # interruptibility requires per-step SYNCHRONIZATION: jax
            # dispatch is async, so without this block the loop would
            # enqueue the whole ladder in milliseconds and every
            # should_stop() check would pass before any device compute
            # ran (the check would be theater)
            jax.block_until_ready(x0)
        if on_step is not None:
            on_step(float(sig[i]), x0)
        if sig[i + 1] == 0.0:
            x = x0
        else:
            d = (x - x0) / sig[i]
            x = x + d * (sig[i + 1] - sig[i])
    return x
