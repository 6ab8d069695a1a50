"""AOT warmup pass: make a worker hot the moment it joins the fleet.

Walks the shape catalog (``cluster/shape_catalog.py``) and pre-lowers /
pre-compiles every program with ``jitted.lower(...).compile()`` — the
same AOT idiom ``bench.py`` uses for its compile measurement — entirely
off the request path. With a populated persistent XLA cache
(``utils/compile_cache.py``) each program resolves to a disk read
instead of a 13.9 s compile; the pass classifies every entry as
``cache_hit`` vs ``compiled`` by watching whether jax wrote new cache
artifacts, so the warm-restart win is *measured*, not assumed
(``cdt_warmup_programs_total``).

Arguments are lowered as ``jax.ShapeDtypeStruct`` templates: warmup
never allocates batch-sized activations and never executes a program —
it only traces and compiles.

A :class:`WarmupManager` owns the worker-visible state machine
(``cold → warming → ready``; ``error`` on a failed pass). The health
probe reports it, and ``cluster/dispatch.py`` prefers hot workers, so a
rolling restart drains traffic toward hosts that won't stall it.

Knobs: ``CDT_WARMUP=1`` warms on controller boot; ``CDT_WARMUP_MODELS``
(csv) restricts which catalog models warm (a CPU controller must not
try to build FLUX-12B).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from pathlib import Path
from typing import Callable, Iterable, Optional

from ..cluster.shape_catalog import ProgramKey, ShapeCatalog
from ..utils import constants
from ..utils.logging import debug_log, log

COLD, WARMING, READY, ERROR = "cold", "warming", "ready", "error"
_STATE_GAUGE = {COLD: 0.0, WARMING: 1.0, READY: 2.0, ERROR: -1.0}


@dataclasses.dataclass
class WarmupEntry:
    key: ProgramKey
    outcome: str          # cache_hit | compiled | error | skipped
    seconds: float = 0.0
    detail: str = ""

    def to_dict(self) -> dict:
        return {"program": self.key.to_dict(), "outcome": self.outcome,
                "seconds": round(self.seconds, 3), "detail": self.detail}


def _cache_artifacts(cache_dir: Optional[str]) -> set:
    if not cache_dir:
        return set()
    try:
        return {p.name for p in Path(cache_dir).iterdir() if p.is_file()}
    except OSError:
        return set()


def _abstract(shape, dtype="float32"):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _lower_segments(fns: dict, args: tuple, lengths) -> None:
    """Compile a served prep → segments → finish triple
    (``Txt2ImgPipeline.preemptible_fns``, ``FlowPipeline.segment_fns``).
    What one program hands the next (the carry, the flow lane's
    converted weights) is described with the sharding it is left in: a
    committed argument is part of what jit lowers, and the executable
    warmed here must be the one a request looks up."""
    import jax
    import jax.numpy as jnp

    def outputs(fn, *operands):
        """Compile ``fn``; its outputs, abstract, as it places them."""
        compiled = fn.jitted.lower(fn.weights, *operands).compile()
        return jax.tree.map(
            lambda leaf, sharding: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=sharding),
            jax.eval_shape(fn.jitted, fn.weights, *operands),
            compiled.output_shardings)

    carry = outputs(fns["prep"], *args)
    tail = (carry,)
    if "cast" in fns:      # the flow lane: shared by two segments or more
        shared = fns["cast"] is not None and len(lengths) > 1
        tail += (outputs(fns["cast"]) if shared else (),)
    for length in sorted(set(lengths)):
        outputs(fns["seg"](length), *args, _abstract((), jnp.int32), *tail)
    outputs(fns["fin"], carry)


def _cut(n_steps: int, length: int) -> list:
    """The segment lengths an ``n_steps`` ladder runs in, ``length`` at a
    time."""
    return [min(length, n_steps - start)
            for start in range(0, n_steps, length)]


def lower_program(bundle, key: ProgramKey, mesh) -> None:
    """Trace + XLA-compile ONE catalog program ahead of time. Shapes come
    from the preset's config (context length / dims) and the key's
    geometry; nothing executes and no batch-sized buffer is allocated.

    What is compiled IS what serves the key. ``txt2img`` (with
    preemption on, the default) and ``flow_dp`` are served as
    callback-free prep → segments → finish triples, which persist in
    the compile cache; the rest as one program in its ``progress=True``
    variant, since every sampler node runs with a live ProgressTracker
    and the progress token changes the traced HLO (those carry a host
    callback, are never persisted, and are warm only for the life of
    the process)."""
    import jax
    import jax.numpy as jnp

    prng = jax.eval_shape(jax.random.key, 0)      # a key's shape: nothing runs
    token = _abstract((), jnp.int32)
    if key.mesh:
        # mesh-tier program: the key names its own strategy mesh (sp /
        # dp×tp) over the SAME device set the host serves with
        from ..parallel.mesh import build_mesh

        mesh = build_mesh(dict(key.mesh),
                          devices=list(mesh.devices.flat))
    if key.pipeline == "txt2img":
        from .pipeline import GenerationSpec

        spec = GenerationSpec(height=key.height, width=key.width,
                              steps=key.steps,
                              per_device_batch=key.batch)
        text = bundle.preset.text
        ctx = _abstract((1, text.max_len, text.output_dim))
        adm = bundle.pipeline.unet.config.adm_in_channels
        y = _abstract((1, max(adm, 1)))
        if constants.PREEMPT.get():
            fns = bundle.pipeline.preemptible_fns(mesh, spec)
            _lower_segments(fns, (prng, ctx, ctx, y, y), _cut(
                fns["n_steps"], constants.PREEMPT_SEGMENT_STEPS.get()))
            return
        fn = bundle.pipeline.generate_fn(mesh, spec, progress=True)
        args = (prng, ctx, ctx, y, y, token)
    elif key.pipeline == "flow_dp":
        from .pipeline_flow import FlowSpec

        spec = FlowSpec(height=key.height, width=key.width,
                        steps=key.steps, per_device_batch=key.batch)
        from .samplers import equal_segment_steps

        cfg = bundle.pipeline.dit.config
        ctx = _abstract((1, bundle.preset.text.max_len, cfg.context_dim))
        pooled = _abstract((1, cfg.pooled_dim))
        fns = bundle.pipeline.segment_fns(mesh, spec)
        _lower_segments(fns, (prng, ctx, pooled), _cut(
            spec.steps, equal_segment_steps(
                spec.steps, constants.PREEMPT_SEGMENT_STEPS.get())))
        return
    elif key.pipeline == "video_dp":
        from .pipeline_video import VideoSpec

        spec = VideoSpec(frames=key.frames or 17, height=key.height,
                         width=key.width, steps=key.steps)
        fn = bundle.pipeline.generate_fn(mesh, spec, progress=True)
        cfg = bundle.pipeline.dit.config
        ctx = _abstract((1, bundle.preset.text.max_len, cfg.context_dim))
        pooled = _abstract((1, getattr(cfg, "pooled_dim", 768)))
        args = (prng, ctx, pooled, token)
    elif key.pipeline == "flow_sp":
        # mesh tier: single-image latency program — latent rows sharded
        # over sp, ring attention inside every block
        from .pipeline_flow import FlowSpec

        spec = FlowSpec(height=key.height, width=key.width,
                        steps=key.steps, per_device_batch=key.batch)
        fn = bundle.pipeline.generate_sp_fn(mesh, spec)
        cfg = bundle.pipeline.dit.config
        ctx = _abstract((1, bundle.preset.text.max_len, cfg.context_dim))
        pooled = _abstract((1, cfg.pooled_dim))
        args = (prng, ctx, pooled)
    elif key.pipeline == "flow_tp":
        # mesh tier: dp×tp weight-sharded program. The fanout wrapper's
        # key fold-in is part of the traced program, so AOT-lower with a
        # concrete folded key batch (tiny) and abstract conditioning;
        # tp_shard_scope makes the trace resolve PER-SHARD kernel
        # choices — the same scope the serving call runs under.
        from ..ops.attention import tp_shard_scope
        from .pipeline_flow import FlowSpec

        spec = FlowSpec(height=key.height, width=key.width,
                        steps=key.steps, per_device_batch=key.batch)
        fn = bundle.pipeline.generate_tp_fn(mesh, spec)
        cfg = bundle.pipeline.dit.config
        B = dict(key.mesh).get("dp", 1) * key.batch
        # keys must carry the SAME P(dp) placement the serving wrapper
        # commits (tp_fanout_call) — a differently-sharded argument
        # lowers a different executable, and the cache entry warmed
        # here would not be the one serving loads
        from jax.sharding import NamedSharding, PartitionSpec

        base = jax.random.key(0)
        keys = jax.device_put(
            jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.arange(B)),
            NamedSharding(mesh, PartitionSpec("dp")))
        ctx = _abstract((1, bundle.preset.text.max_len, cfg.context_dim))
        pooled = _abstract((1, cfg.pooled_dim))
        with tp_shard_scope(getattr(fn, "tp_shards", 1)):
            fn.jitted.lower(*fn.weights, keys, ctx, pooled).compile()
        return
    else:
        raise ValueError(f"no warmup recipe for pipeline {key.pipeline!r}")
    fn.jitted.lower(fn.weights, *args).compile()


def _mesh_matches(key: ProgramKey, mesh) -> bool:
    """Empty key.mesh = "whatever this host runs"; a concrete one must
    match exactly (a dp=8 program is not a dp=4 program) — OR be a
    mesh-tier strategy layout (sp / dp×tp) over the same device count,
    which warmup builds over the host's own devices
    (``lower_program``)."""
    if not key.mesh:
        return True
    if tuple(sorted(key.mesh)) == tuple(
            sorted((str(a), int(n)) for a, n in mesh.shape.items())):
        return True
    import math

    # strategy meshes may be submeshes (sp width is bounded by the
    # latent row count); lower_program builds them over the host's own
    # device list
    return (key.pipeline in ("flow_sp", "flow_tp")
            and math.prod(n for _, n in key.mesh) <= mesh.devices.size)


def mesh_tier_keys(keys: Iterable[ProgramKey], mesh) -> list[ProgramKey]:
    """The mesh-tier programs a catalog implies: for every flow_dp entry
    the host serves, an sp (single-image latency) and — when the mesh
    tier has a tp degree — a dp×tp (weight-sharded) variant on the same
    geometry, so the front door's default placements are hot from boot
    instead of compiling on first mesh-tier request. Gated by
    ``CDT_MESH_TIER``; a single-device host has no mesh tier.

    The tp degree is ``parallel/serving.derive_tp`` — i.e. the pinned
    ``CDT_MESH_TP`` at key-generation time (model bytes aren't known
    before bundles build, so the HBM-fit derivation can't run here);
    an unpinned fleet warms its tp programs on the second boot via the
    persistent compile cache after the first request resolves them."""
    from ..parallel import serving

    n = int(mesh.devices.size)
    if n < 2 or not serving.mesh_tier_enabled():
        return []
    tp = serving.derive_tp(n)
    while tp > 1 and n % tp:
        tp //= 2
    out: list[ProgramKey] = []
    for key in keys:
        if key.pipeline != "flow_dp":
            continue
        # sp needs latent rows (h/8/patch, patch=2 for the DiT family)
        # to divide the shard count; indivisible geometries stay dp-only
        sp = n
        while sp > 1 and (key.height // 16) % sp:
            sp //= 2
        if sp > 1:
            out.append(dataclasses.replace(
                key, pipeline="flow_sp", mesh=(("sp", sp),)))
        if tp > 1:
            out.append(dataclasses.replace(
                key, pipeline="flow_tp",
                mesh=(("dp", n // tp), ("tp", tp))))
    return out


def run_warmup(registry, mesh, keys: Iterable[ProgramKey],
               models: Optional[Iterable[str]] = None,
               on_entry: Optional[Callable[[WarmupEntry], None]] = None
               ) -> list[WarmupEntry]:
    """Warm every catalog program buildable on this host.

    ``models`` (or ``CDT_WARMUP_MODELS``) filters which model bundles are
    eligible — everything else is recorded ``skipped`` (warming is
    best-effort fleet prep, and a CPU smoke host must not materialize a
    14B checkpoint). With NO filter at all, only models already loaded
    in the registry (plus the tiny test presets) warm: the shipped
    workflow catalog references FLUX/WAN/SDXL, and an unqualified
    ``CDT_WARMUP=1`` must not random-initialize tens of GB on boot —
    pass ``CDT_WARMUP_MODELS=all`` (or an explicit list) to opt in.
    Per-entry failures are recorded, never raised: one bad catalog row
    must not leave the worker cold for the rest.

    One pass, a program at a time (:func:`_warm_one`): nothing stands
    between building a bundle and compiling its program since the
    attention tuning stage went (PR 55).
    """
    from ..telemetry import enabled as _tm_enabled
    from ..telemetry import metrics as _tm
    from ..utils.compile_cache import active_cache_dir

    if models is None:
        env = constants.WARMUP_MODELS.get()
        models = [m.strip() for m in env.split(",") if m.strip()] or None
    if models is not None and set(models) & {"all", "*"}:
        allowed = None                      # explicit everything
    elif models is not None:
        allowed = set(models)
    else:
        # safe default: what's already hot, plus presets cheap anywhere
        allowed = set(getattr(registry, "_cache", {})) | {
            m for m in getattr(registry, "available", list)()
            if "tiny" in m}
        log("warmup: no model filter — warming only loaded/tiny presets "
            f"({sorted(allowed)}); set CDT_WARMUP_MODELS=all to warm "
            "everything in the catalog")
    cache_dir = active_cache_dir()

    report: list[WarmupEntry] = []
    for key in keys:
        entry = _warm_one(registry, mesh, key, allowed, cache_dir)
        report.append(entry)
        if _tm_enabled():
            _tm.WARMUP_PROGRAMS.labels(outcome=entry.outcome).inc()
            if entry.outcome in ("cache_hit", "compiled"):
                _tm.WARMUP_SECONDS.observe(entry.seconds)
        if on_entry is not None:
            on_entry(entry)
    return report


def _warm_one(registry, mesh, key: ProgramKey, allowed: Optional[set],
              cache_dir: Optional[str]) -> WarmupEntry:
    """Build one catalog program's bundle, AOT-lower and compile it, and
    classify the outcome; a failure is the entry's, never raised."""
    if (allowed is not None and key.model not in allowed) \
            or not _mesh_matches(key, mesh):
        return WarmupEntry(key, "skipped",
                           detail="model filtered or mesh mismatch")
    t0 = time.perf_counter()
    try:
        # bundle build happens OUTSIDE the classification window: its own
        # init compiles (VAE/text) would otherwise write cache artifacts
        # and mislabel a disk-served target program "compiled"
        bundle = registry.get(key.model)
        before = _cache_artifacts(cache_dir)
        t0 = time.perf_counter()
        lower_program(bundle, key, mesh)
        seconds = time.perf_counter() - t0
        wrote = bool(_cache_artifacts(cache_dir) - before)
        # new cache artifacts ⇒ XLA actually compiled; none (with a cache
        # active) ⇒ the executable was deserialized from disk — the
        # warm-restart fast path this pass exists for
        return WarmupEntry(
            key, "compiled" if wrote or not cache_dir else "cache_hit",
            seconds)
    except Exception as e:  # noqa: BLE001 — per-entry isolation
        debug_log(f"warmup: {key} failed: {e}")
        return WarmupEntry(key, "error", time.perf_counter() - t0,
                           detail=str(e))


class WarmupManager:
    """Worker warmup state machine + pass runner.

    Built lazily off the controller (registry/mesh are properties that
    may themselves initialize jax — resolved only when a pass runs).
    State is what health probes report: ``cold`` (never warmed),
    ``warming`` (pass in flight), ``ready`` (pass finished), ``error``
    (pass itself crashed — per-program errors still end ``ready``).
    """

    def __init__(self, registry_fn: Callable, mesh_fn: Callable,
                 catalog: Optional[ShapeCatalog] = None):
        self._registry_fn = registry_fn
        self._mesh_fn = mesh_fn
        self._catalog = catalog
        self._state = COLD
        self._lock = threading.Lock()
        self._report: list[WarmupEntry] = []
        self._started_at: Optional[float] = None
        self._finished_at: Optional[float] = None

    @property
    def state(self) -> str:
        return self._state

    @property
    def catalog(self) -> ShapeCatalog:
        if self._catalog is None:
            from ..cluster.shape_catalog import default_catalog

            self._catalog = default_catalog()
        return self._catalog

    def _set_state(self, state: str) -> None:
        self._state = state
        try:
            from ..telemetry import enabled as _tm_enabled
            from ..telemetry import metrics as _tm

            if _tm_enabled():
                _tm.WARMUP_STATE.set(_STATE_GAUGE[state])
        except Exception:  # noqa: BLE001
            pass

    def run(self, models: Optional[Iterable[str]] = None,
            seed_workflows: bool = True,
            extra_keys: Optional[Iterable[ProgramKey]] = None) -> dict:
        """Execute one warmup pass synchronously (call from a thread
        executor — this compiles). Concurrent calls coalesce: a second
        caller returns the running/last report instead of doubling the
        compile load."""
        if not self._lock.acquire(blocking=False):
            return self.status()
        try:
            self._set_state(WARMING)
            self._started_at = time.monotonic()
            from ..utils.compile_cache import enable_compile_cache

            # persist EVERYTHING the pass compiles (min 0.0): a program
            # too cheap to cache is still a program the next restart
            # would recompile
            enable_compile_cache(min_compile_secs=0.0)
            cat = self.catalog
            if seed_workflows:
                cat.seed_from_workflows()
            keys = list(cat.entries())
            if extra_keys:
                known = set(keys)
                keys += [k for k in extra_keys if k not in known]
            # mesh tier: warm the sp / dp×tp variants of every flow
            # program the catalog serves (docs/parallelism.md) — the
            # default placements must be hot, not benchmark-only
            mesh = self._mesh_fn()
            tier = [k for k in mesh_tier_keys(keys, mesh)
                    if k not in set(keys)]
            keys += tier
            log(f"warmup: starting pass over {len(keys)} catalog "
                f"program(s) ({len(tier)} mesh-tier)")
            self._report = run_warmup(self._registry_fn(), self._mesh_fn(),
                                      keys, models=models)
            cat.save()
            self._finished_at = time.monotonic()
            self._set_state(READY)
            hits = sum(e.outcome == "cache_hit" for e in self._report)
            comp = sum(e.outcome == "compiled" for e in self._report)
            errs = sum(e.outcome == "error" for e in self._report)
            log(f"warmup: ready — {hits} cache hit(s), {comp} compiled, "
                f"{errs} error(s), "
                f"{sum(e.outcome == 'skipped' for e in self._report)} "
                f"skipped in "
                f"{self._finished_at - self._started_at:.1f}s")
        except Exception as e:  # noqa: BLE001 — boot must survive warmup
            self._finished_at = time.monotonic()
            self._set_state(ERROR)
            log(f"warmup: pass failed: {e}")
        finally:
            self._lock.release()
        return self.status()

    def status(self) -> dict:
        took = None
        if self._started_at is not None:
            took = (self._finished_at or time.monotonic()) - self._started_at
        counts: dict[str, int] = {}
        for e in self._report:
            counts[e.outcome] = counts.get(e.outcome, 0) + 1
        return {
            "state": self._state,
            "catalog_size": (len(self._catalog)
                            if self._catalog is not None else None),
            "outcomes": counts,
            "seconds": None if took is None else round(took, 3),
            "report": [e.to_dict() for e in self._report],
        }
