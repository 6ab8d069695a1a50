"""Shape-catalog registry: the set of compiled-program keys a worker
should be hot for.

Every distinct (pipeline family, model, resolution, step count, batch,
mesh) tuple is a separate XLA program — and every one a cold worker
meets on the request path costs a full compile (64.8 s at the seed,
13.9 s with the packed flash kernel, still fatal for rolling restarts).
The catalog makes that set *explicit* so the AOT warmup pass
(``diffusion/warmup.py``) can pre-compile it off the request path:

- **seeded** from the shipped ``workflows/`` catalog (the shapes the
  product demonstrably serves),
- **grown** from shapes observed at runtime (the sampler nodes call
  :func:`observe` on every execution),
- **persisted** next to the XLA compilation cache and merged across
  restarts/processes (union on load, atomic tmp+rename on save), so a
  fleet image pre-baked with ``scripts/warmup_catalog.py`` and a
  long-lived worker accumulate into the same file.

Reference analogue: none — ComfyUI's torch kernels are pre-built, so the
reference never needs to know its shape population. An XLA server does.

Knobs: ``CDT_SHAPE_CATALOG`` (path; default
beside the XLA cache: ``<cache dir>/shape_catalog.json``), ``CDT_SHAPE_OBSERVE=0``
disables runtime observation.
"""

from __future__ import annotations

import dataclasses
import json
import threading
from pathlib import Path
from typing import Iterable, Optional

from ..lint.lockorder import tracked_lock
from ..utils import constants
from ..utils.jsonio import atomic_write_json, read_json
from ..utils.logging import debug_log, log

CATALOG_VERSION = 1

# pipeline-family names match the telemetry ``pipeline`` label
# (telemetry/metrics.py) so warmup counters and step-time histograms
# join on the same vocabulary. flow_sp / flow_tp are the executed mesh
# tier's programs (docs/parallelism.md): same model, sequence-sharded
# (ring attention) and weight-sharded (Megatron dp×tp) placements.
PIPELINES = ("txt2img", "flow_dp", "video_dp", "flow_sp", "flow_tp")


@dataclasses.dataclass(frozen=True, order=True)
class ProgramKey:
    """One compiled program's identity, as the warmup pass sees it.

    ``mesh`` is a sorted tuple of (axis, size) pairs; the empty tuple
    means "this host's default mesh" — workflow-seeded entries use it so
    one catalog file serves fleets of different slice sizes. ``frames``
    is 0 for image pipelines.
    """

    pipeline: str
    model: str
    height: int
    width: int
    steps: int
    batch: int = 1
    frames: int = 0
    mesh: tuple = ()

    def __post_init__(self):
        if self.pipeline not in PIPELINES:
            raise ValueError(
                f"unknown pipeline family {self.pipeline!r}; "
                f"have {PIPELINES}")

    def to_dict(self) -> dict:
        return {"pipeline": self.pipeline, "model": self.model,
                "height": self.height, "width": self.width,
                "steps": self.steps, "batch": self.batch,
                "frames": self.frames,
                "mesh": [list(ax) for ax in self.mesh]}

    @classmethod
    def from_dict(cls, d: dict) -> "ProgramKey":
        return cls(pipeline=str(d["pipeline"]), model=str(d["model"]),
                   height=int(d["height"]), width=int(d["width"]),
                   steps=int(d["steps"]), batch=int(d.get("batch", 1)),
                   frames=int(d.get("frames", 0)),
                   mesh=tuple((str(a), int(n))
                              for a, n in d.get("mesh", ())))


def default_catalog_path() -> Path:
    """Next to the XLA cache by default: the two artifacts are one unit —
    the catalog names the programs, the cache holds their binaries."""
    env = constants.SHAPE_CATALOG.get()
    if env:
        return Path(env)
    from ..utils.compile_cache import cache_dir_default

    return Path(cache_dir_default()) / "shape_catalog.json"


class ShapeCatalog:
    """Deduplicated, persisted set of :class:`ProgramKey`.

    Thread-safe: runtime observation happens on the graph-executor
    thread while the warmup pass reads from an asyncio executor.
    """

    def __init__(self, path: "Path | str | None" = None,
                 autoload: bool = True):
        self.path = Path(path) if path is not None else default_catalog_path()
        self._keys: set[ProgramKey] = set()
        self._lock = tracked_lock("shape_catalog")
        if autoload:
            self.load()

    def __len__(self) -> int:
        return len(self._keys)

    def __contains__(self, key: ProgramKey) -> bool:
        return key in self._keys

    def entries(self) -> list[ProgramKey]:
        """Deterministic order (sorted dataclass) — the warmup pass and
        tests must walk the catalog identically on every host."""
        with self._lock:
            return sorted(self._keys)

    def add(self, key: ProgramKey) -> bool:
        """Add one key; returns True when it was new."""
        with self._lock:
            if key in self._keys:
                return False
            self._keys.add(key)
            return True

    def update(self, keys: Iterable[ProgramKey]) -> int:
        added = 0
        for k in keys:
            added += self.add(k)
        return added

    # --- persistence --------------------------------------------------------

    def load(self) -> int:
        """Merge the on-disk entries into memory (union — another process
        may have written since our last save). Unreadable/garbled files
        degrade to an empty load, never a crash."""
        raw = read_json(self.path)
        try:
            entries = raw.get("entries", [])
        except AttributeError:
            return 0
        added = 0
        for d in entries:
            try:
                added += self.add(ProgramKey.from_dict(d))
            except (KeyError, TypeError, ValueError):
                debug_log(f"shape catalog: skipping malformed entry {d!r}")
        return added

    def save(self) -> bool:
        """Merge-write: re-load the file first so concurrent writers
        (master + warmup CLI) union rather than clobber, then write
        atomically (tmp+rename). Never fatal."""
        self.load()
        with self._lock:
            payload = {"version": CATALOG_VERSION,
                       "entries": [k.to_dict() for k in sorted(self._keys)]}
        if atomic_write_json(self.path, payload):
            return True
        debug_log(f"shape catalog: save to {self.path} failed")
        return False

    # --- workflow seeding ---------------------------------------------------

    def seed_from_workflows(self, workflows_dir: "Path | str | None" = None
                            ) -> int:
        """Derive keys from the shipped workflow JSONs. Returns the number
        of NEW keys added."""
        if workflows_dir is None:
            env = constants.WORKFLOWS_DIR.get()
            workflows_dir = (Path(env) if env
                             else Path(__file__).resolve().parents[2]
                             / "workflows")
        d = Path(workflows_dir)
        if not d.is_dir():
            return 0
        added = 0
        for path in sorted(d.glob("*.json")):
            try:
                prompt = json.loads(path.read_text())
            except (OSError, ValueError):
                debug_log(f"shape catalog: unreadable workflow {path}")
                continue
            for key in keys_from_prompt(prompt):
                added += self.add(key)
        return added


# node class → (pipeline family, needs frames). TPUImg2Img/USDU tiles
# compile their own programs too, but their shapes derive from inputs
# the catalog can't know statically; runtime observation covers them.
_SAMPLER_NODES = {
    "TPUTxt2Img": ("txt2img", False),
    "TPUFlowTxt2Img": ("flow_dp", False),
    "TPUTxt2Video": ("video_dp", True),
}


def _literal_int(v, default=None) -> Optional[int]:
    """Workflow inputs may be node links (``[src_id, out_idx]``) — only
    literals are statically usable."""
    if isinstance(v, bool):
        return default
    if isinstance(v, (int, float)):
        return int(v)
    return default


def keys_from_prompt(prompt: dict) -> list[ProgramKey]:
    """Program keys statically derivable from one workflow/prompt dict.
    Sampler nodes whose geometry rides a link (dynamic width/steps) are
    skipped — runtime observation picks those up instead."""
    out = []
    nodes = {k: v for k, v in prompt.items()
             if isinstance(v, dict) and "class_type" in v}
    for node in nodes.values():
        family = _SAMPLER_NODES.get(node.get("class_type", ""))
        if family is None:
            continue
        pipeline, has_frames = family
        inputs = node.get("inputs", {})
        model = _resolve_model_name(inputs.get("model"), nodes)
        h = _literal_int(inputs.get("height"))
        w = _literal_int(inputs.get("width"))
        steps = _literal_int(inputs.get("steps"))
        if not model or None in (h, w, steps):
            continue
        frames = _literal_int(inputs.get("frames"), 0) if has_frames else 0
        batch = _literal_int(inputs.get("batch_per_device"), 1) or 1
        out.append(ProgramKey(pipeline=pipeline, model=model, height=h,
                              width=w, steps=steps, batch=batch,
                              frames=frames or 0))
    return out


def _resolve_model_name(link, nodes: dict) -> Optional[str]:
    """Follow a ``model`` input link to its CheckpointLoader's
    ``ckpt_name`` (one hop — the shipped workflows connect them
    directly)."""
    if not (isinstance(link, (list, tuple)) and len(link) == 2):
        return None
    src = nodes.get(str(link[0]))
    if src is None or src.get("class_type") != "CheckpointLoader":
        return None
    name = src.get("inputs", {}).get("ckpt_name")
    return name if isinstance(name, str) and name else None


# --- runtime observation ----------------------------------------------------

_default: "ShapeCatalog | None" = None
_default_lock = tracked_lock("shape_catalog.default")


def default_catalog() -> ShapeCatalog:
    """Process-global catalog instance (lazy; path re-resolved only at
    first use)."""
    global _default
    with _default_lock:
        if _default is None:
            _default = ShapeCatalog()
        return _default


def reset_default_catalog() -> None:
    """Test isolation: drop the cached instance so env-var paths
    re-resolve."""
    global _default
    with _default_lock:
        _default = None


def observe_cap() -> int:
    """Max catalog size runtime observation may grow to (workflow
    seeding and the CLI are exempt — they are operator-driven). Every
    entry costs an AOT compile on every future worker boot, so an
    unbounded user-driven (or hostile) resolution sweep must not turn
    the warmup pass into the new cold start."""
    return constants.SHAPE_CATALOG_MAX.get()


def observe(pipeline: str, model: str, height: int, width: int,
            steps: int, batch: int = 1, frames: int = 0) -> None:
    """Record a shape served on the request path. New keys persist
    immediately (one small JSON write) so the NEXT restart warms them;
    repeat shapes are a set lookup. Growth is capped
    (``CDT_SHAPE_CATALOG_MAX``, first-observed-wins). Never fatal, and
    a no-op under ``CDT_SHAPE_OBSERVE=0``."""
    if not constants.SHAPE_OBSERVE.get():
        return
    try:
        cat = default_catalog()
        cap = observe_cap()
        if cap and len(cat) >= cap:
            debug_log(f"shape catalog: at cap ({cap}); not observing "
                      f"({pipeline}, {model}, {height}x{width}, "
                      f"steps={steps}) — raise CDT_SHAPE_CATALOG_MAX or "
                      "add it via scripts/warmup_catalog.py --shape")
            return
        if cat.add(ProgramKey(pipeline=pipeline, model=model,
                              height=int(height), width=int(width),
                              steps=int(steps), batch=int(batch),
                              frames=int(frames))):
            cat.save()
            log(f"shape catalog: observed new program "
                f"({pipeline}, {model}, {height}x{width}, "
                f"steps={steps}) → {cat.path}")
    except Exception as e:  # noqa: BLE001 — observation must never sink a job
        debug_log(f"shape catalog: observe failed: {e}")
