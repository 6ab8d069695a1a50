"""Persistent per-shape attention-kernel autotuner.

``docs/roofline.md`` ended r05 with every workload pinned to a measured
attention config — but the measurements lived in a human's shell
history. This module makes them an artifact: the first time a (heads,
head_dim, N, dtype) geometry is met, a sweep walks the legal kernel
tiers and block sizes, and the winner persists to a tuning table that
``ops/attention.select_kernel`` consults ahead of its one policy — so
every new model generation lands on its best kernel config without code
edits, and a fleet shares one table the way it shares one XLA cache.

Layout of the decision data:

- **GeometryKey** — (num_heads, head_dim, q_bucket, kv_bucket, dtype);
  sequence lengths bucket to the next power of two so one entry serves
  a resolution family instead of every ±8-token variant compiling its
  own sweep.
- **KernelChoice** — (tier, block_q, block_k): tier is one of ``packed``
  ([B, N, H·D] native layout walked in 128-lane head groups; a row
  without blocks takes them from the shape — K resident where it fits),
  ``bh`` (classic [B·H, N, D] call), ``xla`` (the fused XLA lowering).
- **TuningTable** — two layers: the resolved table for the known model
  zoo shipped in-repo (``ops/attn_table_default.json``, rebakeable with
  ``scripts/autotune_sweep.py``) plus a local overlay persisted next to
  the XLA compilation cache, stored and atomically merged exactly like
  the shape catalog (``utils/jsonio.py``: tmp+rename writes, merge on
  save, corrupt files degrade to empty).

Sweeps run OFF the request path: ``diffusion/warmup.py`` tunes every
catalog geometry during the worker's AOT pass (the worker reports
``warming`` until its geometries are tuned), and the CLI pre-bakes
fleet images. On hardware the sweep times real candidates; off
hardware (``mode="dry"``) it writes what the dispatcher's one policy
(``ops/attention.policy_choice``) answers — same geometry + same table
⇒ same choice, always.

Knobs: ``CDT_ATTN_TABLE`` (local overlay path; default
beside the XLA cache: ``<cache dir>/attn_tuning.json``), ``CDT_ATTN_TUNE=0``
disables table lookups AND sweeps (the policy alone rules).
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from pathlib import Path
from typing import Callable, Iterable, Optional

from ..lint.lockorder import tracked_lock
from ..utils import constants
from ..utils.jsonio import atomic_write_json, read_json
from ..utils.logging import debug_log, log

TABLE_VERSION = 1
TIERS = ("packed", "bh", "xla")
# kernels that are no tier of the bidirectional dispatch (no table row, no
# policy arm chooses them) but report themselves the same way: the blocked
# causal kernels of a chunked prefill (ops/flash_latent.py), over a latent
# cache, over one shared key/value head, and over grouped key/value heads
# (whole, or the band of a window layer)
REPORTED_TIERS = TIERS + ("latent_causal", "shared_kv_causal", "gqa_causal",
                          "gqa_window", "block_select", "index_select")

# the in-repo resolved table for the known model zoo
_SHIPPED_PATH = Path(__file__).resolve().parent / "attn_table_default.json"

_DTYPE_NAMES = {"bfloat16": "bf16", "float32": "f32", "float16": "f16",
                "bf16": "bf16", "f32": "f32", "f16": "f16"}


def dtype_name(dtype) -> str:
    """Canonical short dtype tag for table keys ('bf16', 'f32', ...).
    Accepts numpy/jax dtypes, scalar types (``jnp.bfloat16``) and
    strings; already-short tags pass through."""
    import numpy as np

    try:
        name = np.dtype(dtype).name
    except TypeError:
        name = getattr(dtype, "name", None) or str(dtype)
    return _DTYPE_NAMES.get(name, name)


def itemsize_of(dtype) -> int:
    """Operand byte width for the VMEM working-set model. One
    definition — the dispatcher and the validator both key legality
    on it, and a drift between them would approve blocks the
    kernel can't fit."""
    return 4 if dtype_name(dtype) == "f32" else 2


def seq_bucket(n: int) -> int:
    """Next power of two ≥ n, floored at 128 — one table entry serves a
    resolution family (SDXL 4096 → 4096, WAN 14040 → 16384, a 77-token
    text context → 128) instead of every exact length sweeping anew."""
    b = 128
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass(frozen=True, order=True)
class GeometryKey:
    """One attention geometry as the dispatcher sees it at trace time."""

    num_heads: int
    head_dim: int
    q_bucket: int
    kv_bucket: int
    dtype: str = "bf16"

    def __post_init__(self):
        if self.num_heads <= 0 or self.head_dim <= 0:
            raise ValueError(f"bad geometry {self!r}")

    @classmethod
    def from_shape(cls, num_heads: int, head_dim: int, q_len: int,
                   kv_len: int, dtype="bfloat16") -> "GeometryKey":
        return cls(num_heads=int(num_heads), head_dim=int(head_dim),
                   q_bucket=seq_bucket(int(q_len)),
                   kv_bucket=seq_bucket(int(kv_len)),
                   dtype=dtype_name(dtype))

    def key_str(self) -> str:
        """Stable JSON map key / telemetry geometry label."""
        return (f"h{self.num_heads}.d{self.head_dim}.q{self.q_bucket}"
                f".kv{self.kv_bucket}.{self.dtype}")

    def shard(self, tp: int) -> "GeometryKey":
        """The PER-SHARD geometry a tp-sharded site executes: the
        Megatron column split lands on the head axis, so each shard
        runs H/tp heads of the same sequence. Table lookups and
        legality checks must key on THIS geometry — an entry tuned for
        the full H can pick blocks that are illegal (or slow) at H/tp.
        Indivisible head counts don't shard (the TP placement rules
        fall back to replication there too), so the key is unchanged.
        """
        if tp <= 1 or self.num_heads % tp:
            return self
        return dataclasses.replace(self, num_heads=self.num_heads // tp)

    @classmethod
    def from_key_str(cls, s: str) -> "GeometryKey":
        try:
            h, d, q, kv, dt = s.split(".")
            return cls(num_heads=int(h[1:]), head_dim=int(d[1:]),
                       q_bucket=int(q[1:]), kv_bucket=int(kv[2:]), dtype=dt)
        except (ValueError, IndexError):
            raise ValueError(f"malformed geometry key {s!r}") from None


@dataclasses.dataclass(frozen=True)
class KernelChoice:
    """A resolved kernel config: what ``full_attention`` should run."""

    tier: str
    block_q: Optional[int] = None      # None: tier has no blocks (xla),
    block_k: Optional[int] = None      # or packed derives them (shape)
    source: str = "default"            # default (policy)|env|table|sweep
    reason: str = ""

    def __post_init__(self):
        if self.tier not in REPORTED_TIERS:
            raise ValueError(f"unknown kernel tier {self.tier!r}; "
                             f"have {REPORTED_TIERS}")

    def to_dict(self) -> dict:
        d = {"tier": self.tier}
        if self.block_q is not None:
            d["block_q"] = self.block_q
        if self.block_k is not None:
            d["block_k"] = self.block_k
        if self.reason:
            d["reason"] = self.reason
        return d

    @classmethod
    def from_dict(cls, d: dict, source: str = "table") -> "KernelChoice":
        return cls(tier=str(d["tier"]),
                   block_q=(int(d["block_q"]) if d.get("block_q") is not None
                            else None),
                   block_k=(int(d["block_k"]) if d.get("block_k") is not None
                            else None),
                   source=source, reason=str(d.get("reason", "")))


def validate_entry(key: GeometryKey, choice: KernelChoice) -> list[str]:
    """Legality errors for one table entry (empty = legal). The shipped
    table's tier-1 test and the CLI both run every entry through this,
    so a bad bake fails fast instead of failing in Mosaic lowering on a
    serving host."""
    from . import flash_attention as fa

    errors: list[str] = []
    itemsize = itemsize_of(key.dtype)
    H, D = key.num_heads, key.head_dim
    if choice.tier == "xla":
        if choice.block_q is not None or choice.block_k is not None:
            errors.append("xla tier takes no block sizes")
        return errors
    bq, bk = choice.block_q, choice.block_k
    try:
        if choice.tier == "packed":        # its unset blocks stay unset
            fa._check_blocks(bq, bk)
        else:
            bq, bk = fa.resolve_flash_blocks(bq, bk)
    except ValueError as e:
        return [str(e)]
    if choice.tier == "packed":
        if not fa._packed_legal(H, D):
            errors.append(
                f"packed tier illegal at H={H}, D={D} ({key.dtype})")
        else:
            try:
                fa._packed_blocks(key.q_bucket, key.kv_bucket, D, itemsize,
                                  bq, bk)
            except ValueError as e:
                errors.append(str(e))
    return errors


def table_path() -> Path:
    env = constants.ATTN_TABLE.get()
    if env:
        return Path(env)
    from ..utils.compile_cache import cache_dir_default

    return Path(cache_dir_default()) / "attn_tuning.json"


class TuningTable:
    """Layered geometry → KernelChoice map.

    The shipped layer (in-repo, read-only) resolves the known model zoo;
    the local layer (next to the XLA cache) holds sweep results and
    overrides shipped entries on conflict — a fleet that re-swept a
    geometry on its own hardware generation trusts its own numbers.
    Thread-safe; persistence follows the shape-catalog contract (atomic
    tmp+rename, merge-on-save, corrupt files degrade to empty)."""

    def __init__(self, path: "Path | str | None" = None,
                 shipped: bool = True, autoload: bool = True):
        self.path = Path(path) if path is not None else table_path()
        self._lock = tracked_lock("autotune.table")
        self._shipped: dict[GeometryKey, KernelChoice] = {}
        self._local: dict[GeometryKey, KernelChoice] = {}
        if autoload:
            if shipped:
                self._shipped = self._load_file(_SHIPPED_PATH,
                                                source="table")
            self.load()

    @staticmethod
    def _load_file(path: Path, source: str) -> dict:
        raw = read_json(path)
        entries = raw.get("entries", {}) if isinstance(raw, dict) else {}
        out: dict[GeometryKey, KernelChoice] = {}
        if not isinstance(entries, dict):
            return out
        for ks, d in entries.items():
            try:
                out[GeometryKey.from_key_str(ks)] = \
                    KernelChoice.from_dict(d, source=source)
            except (KeyError, TypeError, ValueError):
                debug_log(f"attn table: skipping malformed entry "
                          f"{ks!r} in {path}")
        return out

    def __len__(self) -> int:
        with self._lock:
            return len(set(self._shipped) | set(self._local))

    def entries(self) -> dict[GeometryKey, KernelChoice]:
        """Effective view, local overriding shipped; sorted for
        deterministic walks."""
        with self._lock:
            merged = dict(self._shipped)
            merged.update(self._local)
        return dict(sorted(merged.items()))

    def lookup(self, num_heads: int, head_dim: int, q_len: int,
               kv_len: int, dtype="bfloat16") -> Optional[KernelChoice]:
        key = GeometryKey.from_shape(num_heads, head_dim, q_len, kv_len,
                                     dtype)
        with self._lock:
            return self._local.get(key) or self._shipped.get(key)

    def get(self, key: GeometryKey) -> Optional[KernelChoice]:
        with self._lock:
            return self._local.get(key) or self._shipped.get(key)

    def record(self, key: GeometryKey, choice: KernelChoice,
               save: bool = True) -> None:
        with self._lock:
            self._local[key] = choice
        if save:
            self.save()

    # --- persistence (local layer only — shipped is read-only) -------------

    def load(self) -> int:
        """Merge the on-disk local layer into memory. In-memory entries
        win on conflict (they are newer sweeps)."""
        loaded = self._load_file(self.path, source="table")
        added = 0
        with self._lock:
            for k, v in loaded.items():
                if k not in self._local:
                    self._local[k] = v
                    added += 1
        return added

    def save(self) -> bool:
        """Merge-write the local layer (re-load first so concurrent
        sweepers union; atomic tmp+rename)."""
        self.load()
        with self._lock:
            payload = {
                "version": TABLE_VERSION,
                "entries": {k.key_str(): v.to_dict()
                            for k, v in sorted(self._local.items())},
            }
        if atomic_write_json(self.path, payload):
            return True
        debug_log(f"attn table: save to {self.path} failed")
        return False


# --- process-global default table -------------------------------------------

_default: "TuningTable | None" = None
_default_lock = tracked_lock("autotune.default")


def tuning_enabled() -> bool:
    return constants.ATTN_TUNE.get()


def default_table() -> TuningTable:
    global _default
    with _default_lock:
        if _default is None:
            _default = TuningTable()
        return _default


def reset_default_table() -> None:
    """Test isolation: drop the cached instance so env-var paths
    re-resolve."""
    global _default
    with _default_lock:
        _default = None


def lookup(num_heads: int, head_dim: int, q_len: int, kv_len: int,
           dtype="bfloat16") -> Optional[KernelChoice]:
    """Table consultation for the dispatcher: None when tuning is
    disabled, the table is empty for this geometry, or the lookup itself
    fails (a corrupt table must never take attention down)."""
    if not tuning_enabled():
        return None
    try:
        return default_table().lookup(num_heads, head_dim, q_len, kv_len,
                                      dtype)
    except Exception as e:  # noqa: BLE001 — lookup is advisory
        debug_log(f"attn table: lookup failed: {e}")
        return None


# --- sweeping ----------------------------------------------------------------

BLOCK_Q_CANDIDATES = (128, 256, 512)

def candidates_for(key: GeometryKey) -> list[KernelChoice]:
    """Deterministic candidate list for one geometry: every legal
    (tier, block_q, block_k) worth timing, xla always last (the
    baseline). Order is fixed so timed ties and dry-mode policy picks
    are reproducible."""
    from . import flash_attention as fa
    from .attention import BH_MIN_Q, PACKED_MIN_KV, PACKED_MIN_Q

    H, D = key.num_heads, key.head_dim
    out: list[KernelChoice] = []
    # below the policy's floors XLA's fused lowering wins and the sweep
    # doesn't bother timing pallas tiers — they'd be legal but pointless
    long_enough = (key.q_bucket >= PACKED_MIN_Q
                   and key.kv_bucket >= PACKED_MIN_KV)
    if long_enough and fa._packed_legal(H, D):
        # the shape's blocks first, then each q block against the K tile
        # the shape gives it (K resident where it fits): short K chunks
        # measured behind the parent kernel (PERF.md §6, PR 25) and are
        # not offered
        out.append(KernelChoice("packed", source="sweep"))
        out.extend(KernelChoice("packed", bq, source="sweep")
                   for bq in BLOCK_Q_CANDIDATES)
    if key.q_bucket >= BH_MIN_Q or long_enough:
        for bq, bk in ((256, 512), (256, 1024), (512, 512)):
            out.append(KernelChoice("bh", bq, bk, source="sweep"))
    out.append(KernelChoice("xla", source="sweep"))
    return out


def _time_candidate(key: GeometryKey, choice: KernelChoice,
                    runs: int = 3) -> float:
    """Median seconds/op of one candidate on the live backend (chained
    scan so per-op time isn't swamped by dispatch overhead)."""
    import jax
    import jax.numpy as jnp

    from . import flash_attention as fa

    dt = {"bf16": jnp.bfloat16, "f32": jnp.float32,
          "f16": jnp.float16}[key.dtype]
    H, D = key.num_heads, key.head_dim
    B, Nq, Nk = 1, key.q_bucket, key.kv_bucket
    scan_len = 8

    q = jax.random.normal(jax.random.key(0), (B, Nq, H, D), dt)
    k = jax.random.normal(jax.random.key(1), (B, Nk, H, D), dt)
    v = jax.random.normal(jax.random.key(2), (B, Nk, H, D), dt)

    if choice.tier == "xla":
        def op(carry):
            return jax.nn.dot_product_attention(carry, k, v)
    else:
        def op(carry):
            return fa.flash_attention(
                carry, k, v, block_q=choice.block_q,
                block_k=choice.block_k, interpret=False,
                layout="packed" if choice.tier == "packed" else "bh")

    @jax.jit
    def run(seed, first):
        def body(carry, _):
            out = op(carry)
            return (first + out * (seed * 1e-6).astype(first.dtype)), None

        final, _ = jax.lax.scan(body, first, None, length=scan_len)
        return jnp.sum(final.astype(jnp.float32))

    import statistics

    float(run(jnp.float32(0.0), q))                # compile + warm
    times = []
    for i in range(runs):
        t0 = time.perf_counter()
        float(run(jnp.float32(i + 1.0), q))
        times.append(time.perf_counter() - t0)
    return statistics.median(times) / scan_len


@dataclasses.dataclass
class SweepEntry:
    key: GeometryKey
    choice: Optional[KernelChoice]
    outcome: str                  # swept | dry | cached | error
    seconds: float = 0.0
    detail: str = ""
    # candidates the legality model offered and the compiler (or the run)
    # refused, with its message: a refusal is a fault in the model
    refused: list = dataclasses.field(default_factory=list)

    def to_dict(self) -> dict:
        return {"geometry": self.key.key_str(),
                "choice": self.choice.to_dict() if self.choice else None,
                "outcome": self.outcome,
                "seconds": round(self.seconds, 3),
                "detail": self.detail,
                "refused": self.refused}


def sweep_geometry(key: GeometryKey, mode: str = "auto",
                   runs: int = 3) -> SweepEntry:
    """Resolve the best kernel config for one geometry.

    ``mode="timed"`` measures every candidate on the live backend (TPU);
    ``mode="dry"`` writes what the dispatcher's policy answers at the
    bucket's lengths (CPU-safe); ``mode="auto"`` picks timed on TPU, dry
    elsewhere. Per-geometry failures are recorded, never raised; a
    candidate that fails is logged with the compiler's message and kept
    in ``SweepEntry.refused``."""
    from .attention import policy_choice
    from .flash_attention import _on_tpu

    if mode == "auto":
        mode = "timed" if _on_tpu() else "dry"
    t0 = time.perf_counter()
    try:
        if mode == "dry":
            choice = policy_choice(key.q_bucket, key.kv_bucket,
                                   key.num_heads, key.head_dim)
            return SweepEntry(key, choice, "dry",
                              time.perf_counter() - t0)
        timings = []
        refused = []
        for cand in candidates_for(key):
            try:
                timings.append((_time_candidate(key, cand, runs), cand))
            except Exception as e:  # noqa: BLE001 — candidate isolation
                log(f"WARNING autotune: candidate {cand.tier} "
                    f"{cand.block_q}/{cand.block_k} refused on "
                    f"{key.key_str()}: {e}")
                refused.append({**cand.to_dict(), "error": str(e)})
        if not timings:
            return SweepEntry(key, None, "error",
                              time.perf_counter() - t0,
                              detail="every candidate failed",
                              refused=refused)
        best_t, best = min(timings, key=lambda tc: tc[0])
        best = dataclasses.replace(
            best, reason=f"timed sweep: {best_t * 1e6:.0f} us/op over "
                         f"{len(timings)} candidates")
        return SweepEntry(key, best, "swept", time.perf_counter() - t0,
                          refused=refused)
    except Exception as e:  # noqa: BLE001 — sweeps must never sink warmup
        return SweepEntry(key, None, "error", time.perf_counter() - t0,
                          detail=str(e))


def ensure_tuned(geometries: Iterable[GeometryKey],
                 table: Optional[TuningTable] = None, mode: str = "auto",
                 on_entry: Optional[Callable[[SweepEntry], None]] = None
                 ) -> list[SweepEntry]:
    """Sweep every geometry not already in the table; persist winners
    once at the end (one atomic merge-write). Already-tuned geometries
    report ``cached`` — same geometry + same table ⇒ same config, no
    re-sweep, which is what keeps the tuner off the request path after
    the first boot."""
    from ..telemetry import enabled as _tm_enabled
    from ..telemetry import metrics as _tm

    if table is None:
        table = default_table()
    report: list[SweepEntry] = []
    dirty = False
    for key in sorted(set(geometries)):
        existing = table.get(key)
        if existing is not None:
            entry = SweepEntry(key, existing, "cached")
        else:
            entry = sweep_geometry(key, mode=mode)
            if entry.choice is not None:
                table.record(key, entry.choice, save=False)
                dirty = True
            if _tm_enabled():
                _tm.AUTOTUNE_SWEEP_SECONDS.observe(entry.seconds)
        report.append(entry)
        if on_entry is not None:
            on_entry(entry)
    if dirty:
        table.save()
    return report


# --- geometry derivation (warmup + CLI) --------------------------------------


def parse_mesh_spec(spec: str) -> dict[str, int]:
    """CLI mesh shape: ``'dp4xtp2'`` / ``'tp=2'`` / ``'dp=2,tp=4'`` →
    ``{'dp': 4, 'tp': 2}``. Raises ``ValueError`` on malformed tokens."""
    import re

    axes: dict[str, int] = {}
    for tok in re.split(r"[x,]", spec.strip()):
        tok = tok.strip()
        if not tok:
            continue
        m = re.fullmatch(r"([a-z]+)=?(\d+)", tok)
        if not m:
            raise ValueError(f"malformed mesh token {tok!r} in {spec!r} "
                             "(want e.g. 'dp4xtp2' or 'tp=2')")
        axes[m.group(1)] = int(m.group(2))
    if not axes:
        raise ValueError(f"empty mesh spec {spec!r}")
    return axes


def _cfg_heads_dim(cfg) -> tuple[int, int]:
    heads = getattr(cfg, "num_heads", None) or getattr(cfg, "heads")
    width = getattr(cfg, "dim", None) or getattr(cfg, "hidden")
    head_dim = getattr(cfg, "head_dim", None) or width // heads
    return int(heads), int(head_dim)


def geometries_for_program(bundle, key) -> list[GeometryKey]:
    """Attention geometries one catalog program (``ProgramKey``) will
    trace — what the warmup pass hands to ``ensure_tuned`` so a worker
    reports ready only once its serving geometries are tuned. Geometry
    math mirrors the model definitions (UNet level downsampling, DiT
    patchify, WAN 3D-VAE temporal compression); unknown pipeline shapes
    raise — the caller records the error per program.

    Mesh-aware: a ``tp`` axis in ``key.mesh`` divides the head counts
    (``GeometryKey.shard``) — the per-shard geometry is what the traced
    kernels execute, so THAT is what must be tuned before warmup bakes
    kernel choices into the compiled programs. ``flow_sp`` programs run
    ring attention (their collective is the kernel schedule itself, not
    a table-dispatched tier), so they contribute no table geometries."""
    out: list[GeometryKey] = []
    text_len = int(bundle.preset.text.max_len)
    if key.pipeline == "flow_sp":
        return out
    if key.pipeline == "txt2img":
        cfg = bundle.pipeline.unet.config
        dt = cfg.dtype
        lat_h, lat_w = key.height // 8, key.width // 8
        for level, depth in enumerate(cfg.transformer_depth):
            if not depth:
                continue
            tokens = (lat_h >> level) * (lat_w >> level)
            ch = cfg.model_channels * cfg.channel_mult[level]
            heads = (cfg.num_heads if cfg.num_heads > 0
                     else ch // cfg.head_dim)
            head_dim = ch // heads
            out.append(GeometryKey.from_shape(heads, head_dim, tokens,
                                              tokens, dt))
            out.append(GeometryKey.from_shape(heads, head_dim, tokens,
                                              text_len, dt))
    elif key.pipeline in ("flow_dp", "flow_tp"):
        cfg = bundle.pipeline.dit.config
        heads, head_dim = _cfg_heads_dim(cfg)
        patch = int(getattr(cfg, "patch_size", 2))
        img_tokens = (key.height // 8 // patch) * (key.width // 8 // patch)
        joint = img_tokens + text_len
        out.append(GeometryKey.from_shape(heads, head_dim, joint, joint,
                                          cfg.dtype))
    elif key.pipeline == "video_dp":
        pipeline = bundle.pipeline
        cfg = pipeline.dit.config
        heads, head_dim = _cfg_heads_dim(cfg)
        patch = getattr(cfg, "patch_size", (1, 2, 2))
        if isinstance(patch, int):
            patch = (1, patch, patch)
        pt, ph, pw = patch
        frames = key.frames or 17
        padded = frames + (-(frames - 1)) % 4     # pad_frames_4n1
        tds = int(getattr(pipeline, "temporal_downscale", 1))
        lat_f = (padded - 1) // tds + 1
        tokens = ((lat_f // pt) * (key.height // 8 // ph)
                  * (key.width // 8 // pw))
        out.append(GeometryKey.from_shape(heads, head_dim, tokens, tokens,
                                          cfg.dtype))
        out.append(GeometryKey.from_shape(heads, head_dim, tokens,
                                          text_len, cfg.dtype))
    else:
        raise ValueError(f"no geometry recipe for pipeline "
                         f"{key.pipeline!r}")
    tp = dict(key.mesh).get(constants.AXIS_TENSOR, 1) if key.mesh else 1
    if tp > 1:
        out = [g.shard(tp) for g in out]
    return out


def model_zoo_geometries() -> dict[str, GeometryKey]:
    """The known model zoo's serving geometries (docs/roofline.md r05
    table) — what the shipped table resolves and what the CLI and the
    r07 bench A/B walk. Static so baking needs no checkpoints."""
    zoo = {
        # SDXL UNet at 1024²: 64²=4096 tokens @ 10 heads × 64, 32²=1024
        # tokens @ 20 × 64, plus the 77-token cross-attention contexts
        "sdxl_self64": GeometryKey.from_shape(10, 64, 4096, 4096),
        "sdxl_self32": GeometryKey.from_shape(20, 64, 1024, 1024),
        "sdxl_cross64": GeometryKey.from_shape(10, 64, 4096, 77),
        "sdxl_cross32": GeometryKey.from_shape(20, 64, 1024, 77),
        # FLUX-12B at 1024²: 4096 image + 512 text joint tokens,
        # 24 heads × 128 (H·D = 3072 — past the native packed ceiling)
        "flux_joint": GeometryKey.from_shape(24, 128, 4608, 4608),
        # WAN-1.3B t2v 33f 480p: 14040 spatio-temporal tokens,
        # 12 heads × 128, plus the 512-token text cross-attention
        "wan_self": GeometryKey.from_shape(12, 128, 14040, 14040),
        "wan_cross": GeometryKey.from_shape(12, 128, 14040, 512),
    }
    return zoo
