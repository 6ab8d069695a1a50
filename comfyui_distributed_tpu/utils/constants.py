"""Framework tunables: the typed ``CDT_*`` knob registry.

Parity with reference ``utils/constants.py:1-68`` (heartbeat cadence, payload
caps, orchestration concurrencies), re-keyed for the TPU build, and — since
ISSUE 12 — the single place every ``CDT_*`` environment knob is declared.

Design (docs/lint.md, rule K001):

- Every knob is declared ONCE here as a :class:`Knob` with a type, default,
  subsystem, and one-line doc. ``docs/knobs.md`` is generated from this
  registry and tier-1 asserts it is regeneration-clean, so the knob surface
  can never silently drift from the docs.
- Call sites read knobs through the registry (``constants.WARMUP.get()``),
  never via raw ``os.environ`` — cdtlint rule K001 machine-checks this.
- Parsing is once-per-value (cached against the raw string, so a
  monkeypatched env var re-parses) with validation: garbage raises a
  descriptive :class:`KnobError` at the first read instead of letting a
  typo'd knob silently fall back or crash something deep. The few hot-loop gate
  knobs whose warn-and-default behavior is a tested contract opt out via
  ``on_garbage="default"``.
- Import-time module constants (``HEARTBEAT_INTERVAL`` et al.) are kept for
  back-compat: values are read once at import; tests may monkeypatch the
  module attributes directly, exactly as before.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Optional


class KnobError(ValueError):
    """A ``CDT_*`` env knob holds a value that cannot be parsed or
    validated. Raised at the first read of the bad value — loud and
    early, instead of a silent fallback masking an operator typo."""


_warned_envs: set[str] = set()


def _warn_malformed(name: str, default) -> None:
    if name not in _warned_envs:
        _warned_envs.add(name)
        from .logging import log   # lazy: keep this module stdlib-only

        log(f"ignoring malformed {name}={os.environ.get(name)!r}; "
            f"using default {default}")


_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")
_UNSET = object()       # cache sentinel: distinguishes "never read" from None


class Knob:
    """One declared ``CDT_*`` knob: typed, documented, parse-once.

    ``kind``: ``int`` | ``float`` | ``bool`` | ``optbool`` | ``str`` |
    ``enum``. ``optbool`` is tri-state (unset/empty -> ``default``, which
    is usually ``None`` so the call site can apply context-dependent
    defaults). ``keep_empty`` returns ``""`` as-is instead of treating it
    as unset (for knobs where ``CDT_X=`` means "explicitly off" rather
    than "use the default"). ``on_garbage``: ``"raise"`` (default, the
    loud contract) or ``"default"`` (warn once + fall back — only for
    hot-loop gates whose fallback behavior is a tested contract).
    """

    __slots__ = ("name", "kind", "default", "subsystem", "help", "doc",
                 "choices", "keep_empty", "on_garbage", "validator",
                 "_cached_raw", "_cached_value")

    def __init__(self, name: str, kind: str, default, subsystem: str,
                 help: str, doc: str = "", choices: tuple = (),
                 keep_empty: bool = False, on_garbage: str = "raise",
                 validator: Optional[Callable[[Any], None]] = None):
        self.name = name
        self.kind = kind
        self.default = default
        self.subsystem = subsystem
        self.help = help
        self.doc = doc
        self.choices = choices
        self.keep_empty = keep_empty
        self.on_garbage = on_garbage
        self.validator = validator
        self._cached_raw = _UNSET
        self._cached_value = None

    # -- reads ---------------------------------------------------------

    def is_set(self) -> bool:
        return os.environ.get(self.name) is not None

    def get(self):
        """Parse-once-per-value read: the parsed result is cached against
        the raw string, so repeated reads are one dict lookup and a
        monkeypatched env var re-parses on the next read."""
        raw = os.environ.get(self.name)
        if raw == self._cached_raw:
            return self._cached_value
        value = self._parse(raw)
        # value BEFORE raw: a concurrent reader that matches the new raw
        # string must never see the previous value
        self._cached_value = value
        self._cached_raw = raw
        return value

    # -- parsing -------------------------------------------------------

    def _garbage(self, raw: str, why: str):
        if self.on_garbage == "default":
            _warn_malformed(self.name, self.default)
            return self.default
        raise KnobError(f"{self.name}={raw!r} {why}")

    def _parse(self, raw: Optional[str]):
        if raw is None:
            return self.default
        if raw.strip() == "" and not (self.keep_empty and raw == ""):
            return self.default
        if self.keep_empty and raw == "":
            # "" is meaningful for this knob: explicit-off for bools
            # (`CDT_TELEMETRY=` shell idiom), zero for numerics (the old
            # `int(env or 0)` idiom — e.g. "" lifts a cap), empty-path
            # for str knobs
            if self.kind in ("bool", "optbool"):
                return False
            if self.kind == "int":
                return 0
            if self.kind == "float":
                return 0.0
            return ""
        value: Any
        if self.kind == "int":
            try:
                value = int(raw.strip())
            except ValueError:
                return self._garbage(raw, "is not an integer")
        elif self.kind == "float":
            try:
                value = float(raw.strip())
            except ValueError:
                return self._garbage(raw, "is not a number")
        elif self.kind in ("bool", "optbool"):
            low = raw.strip().lower()
            if low in _TRUE:
                value = True
            elif low in _FALSE:
                value = False
            else:
                return self._garbage(
                    raw, f"is not a boolean (use one of {_TRUE + _FALSE})")
        elif self.kind == "enum":
            value = raw.strip().lower()
            if value not in self.choices:
                return self._garbage(
                    raw, f"is not one of {self.choices}")
        elif self.kind == "str":
            value = raw
        else:                                          # pragma: no cover
            raise AssertionError(f"unknown knob kind {self.kind!r}")
        if self.validator is not None:
            try:
                self.validator(value)
            except KnobError:
                raise
            except Exception as exc:
                return self._garbage(raw, str(exc))
        return value


class KnobRegistry:
    """Ordered declaration table. One instance (``KNOBS``) per process;
    ``docs/knobs.md`` and the K001 two-way sync check are generated from
    it."""

    def __init__(self):
        self._knobs: dict[str, Knob] = {}

    def declare(self, knob: Knob) -> Knob:
        if knob.name in self._knobs:
            raise KnobError(f"duplicate knob declaration: {knob.name}")
        if not knob.name.startswith("CDT_"):
            raise KnobError(f"knob names must start with CDT_: {knob.name}")
        self._knobs[knob.name] = knob
        return knob

    def get(self, name: str) -> Knob:
        try:
            return self._knobs[name]
        except KeyError:
            raise KnobError(
                f"{name} is not a declared knob — declare it in "
                "utils/constants.py (rule K001, docs/lint.md)") from None

    def __contains__(self, name: str) -> bool:
        return name in self._knobs

    def names(self) -> list[str]:
        return sorted(self._knobs)

    def all(self) -> list[Knob]:
        return [self._knobs[n] for n in sorted(self._knobs)]


KNOBS = KnobRegistry()


def knob(name: str) -> Knob:
    """Dynamic lookup (for sites resolving the knob name at runtime,
    e.g. the model-dir resolver in graph/nodes_builtin.py)."""
    return KNOBS.get(name)


def _k(name: str, kind: str, default, subsystem: str, help: str,
       **kw) -> Knob:
    return KNOBS.declare(Knob(name, kind, default, subsystem, help, **kw))


def knob_int(name, default, subsystem, help, **kw) -> Knob:
    return _k(name, "int", default, subsystem, help, **kw)


def knob_float(name, default, subsystem, help, **kw) -> Knob:
    return _k(name, "float", default, subsystem, help, **kw)


def knob_bool(name, default, subsystem, help, **kw) -> Knob:
    return _k(name, "bool", default, subsystem, help, **kw)


def knob_optbool(name, subsystem, help, **kw) -> Knob:
    return _k(name, "optbool", None, subsystem, help, **kw)


def knob_str(name, default, subsystem, help, **kw) -> Knob:
    return _k(name, "str", default, subsystem, help, **kw)


def knob_enum(name, default, choices, subsystem, help, **kw) -> Knob:
    return _k(name, "enum", default, subsystem, help, choices=choices, **kw)


# Legacy helpers, kept for back-compat with external callers; in-package
# reads go through declared knobs (rule K001 flags new uses).
def env_int(name: str, default: int) -> int:
    """Safe env-int read: a malformed value logs one warning and falls
    back to the default instead of raising mid-job (an env typo must not
    crash a worker's hot loop)."""
    try:
        return int(os.environ.get(name, default))
    except (TypeError, ValueError):
        _warn_malformed(name, default)
        return default


def env_float(name: str, default: float) -> float:
    """Safe env-float read; same malformed-value fallback as ``env_int``."""
    try:
        return float(os.environ.get(name, default))
    except (TypeError, ValueError):
        _warn_malformed(name, default)
        return default


# =========================================================================
# Knob declarations, grouped by subsystem. ``doc`` names the docs page
# that explains the subsystem; docs/knobs.md is GENERATED from this table
# (python -m comfyui_distributed_tpu.lint --write-knob-docs).
# =========================================================================

# --- cluster liveness (reference utils/constants.py:43-68) -----------------
# Workers heartbeat per processed shard; master requeues work of hosts silent
# longer than HEARTBEAT_TIMEOUT (reference upscale/job_timeout.py:17-150).
TILE_JOURNAL_DIR = knob_str(
    "CDT_TILE_JOURNAL_DIR", "", "cluster",
    "Crash-resume journal dir for long tile jobs (empty = disabled); "
    "completed tasks persist as CDTF frames and a restarted master resumes.",
    doc="docs/resilience.md").get()

# Activation rematerialization for the big-model presets (trade FLOPs for
# HBM headroom on large latents/frames); tiny test configs ignore it.
REMAT = knob_bool(
    "CDT_REMAT", False, "models",
    "Activation rematerialization for big-model presets (trade FLOPs for "
    "HBM headroom).", doc="docs/roofline.md").get()

HEARTBEAT_INTERVAL = knob_float(
    "CDT_HEARTBEAT_INTERVAL", 10.0, "cluster",
    "Worker heartbeat cadence (seconds).",
    doc="docs/resilience.md").get()
HEARTBEAT_TIMEOUT = knob_float(
    "CDT_HEARTBEAT_TIMEOUT", 60.0, "cluster",
    "Master evicts a worker silent longer than this (seconds).",
    doc="docs/resilience.md").get()

# --- payload caps ----------------------------------------------------------
MAX_PAYLOAD_SIZE = knob_int(
    "CDT_MAX_PAYLOAD_SIZE", 50 * 1024 * 1024, "cluster",
    "Per-route wire cap for tile uploads (bytes).", doc="docs/api.md").get()
MAX_AUDIO_PAYLOAD_BYTES = knob_int(
    "CDT_MAX_AUDIO_PAYLOAD_BYTES", 256 * 1024 * 1024, "cluster",
    "Wire cap for audio envelopes (bytes).", doc="docs/api.md").get()

# Max result items per flush from a worker host (reference MAX_BATCH=20).
MAX_BATCH = knob_int(
    "CDT_MAX_BATCH", 20, "cluster",
    "Max result items per flush from a worker host.",
    doc="docs/api.md").get()

# --- orchestration concurrencies (reference utils/config.py:22-45) ---------
WORKER_PROBE_CONCURRENCY = knob_int(
    "CDT_PROBE_CONCURRENCY", 10, "cluster",
    "Concurrent worker liveness probes during orchestration fan-out.").get()
WORKER_PREP_CONCURRENCY = knob_int(
    "CDT_PREP_CONCURRENCY", 4, "cluster",
    "Concurrent per-worker prompt preparations.").get()
MEDIA_SYNC_CONCURRENCY = knob_int(
    "CDT_MEDIA_SYNC_CONCURRENCY", 4, "cluster",
    "Concurrent media-sync uploads.").get()

# --- timeouts --------------------------------------------------------------
PROBE_TIMEOUT = knob_float(
    "CDT_PROBE_TIMEOUT", 5.0, "cluster",
    "Worker liveness probe timeout (seconds).").get()
DISPATCH_TIMEOUT = knob_float(
    "CDT_DISPATCH_TIMEOUT", 30.0, "cluster",
    "Prompt dispatch timeout (seconds).").get()
MEDIA_SYNC_TIMEOUT = knob_float(
    "CDT_MEDIA_SYNC_TIMEOUT", 120.0, "cluster",
    "Media sync transfer timeout (seconds).").get()
COLLECT_POLL_TIMEOUT = knob_float(
    "CDT_COLLECT_POLL_TIMEOUT", 5.0, "cluster",
    "Collector result-poll timeout (seconds).").get()
# On collector drain timeout, silent-but-busy workers are granted grace
# extensions of COLLECT_GRACE_S each, at most COLLECT_MAX_GRACE_ROUNDS times.
COLLECT_GRACE_S = knob_float(
    "CDT_COLLECT_GRACE_S", 30.0, "cluster",
    "Grace extension per round for silent-but-busy workers at collector "
    "drain (seconds).").get()
COLLECT_MAX_GRACE_ROUNDS = knob_int(
    "CDT_COLLECT_MAX_GRACE_ROUNDS", 20, "cluster",
    "Max collector grace extensions before giving up on a worker.").get()
JOB_INIT_GRACE = knob_float(
    "CDT_JOB_INIT_GRACE", 10.0, "cluster",
    "Grace for a freshly-dispatched job to appear in worker status "
    "(seconds).").get()
WORK_REQUEST_BUDGET = knob_float(
    "CDT_WORK_REQUEST_BUDGET", 30.0, "cluster",
    "Wall-clock budget for one worker work-request cycle (seconds).").get()

# --- retries (reference upscale/worker_comms.py:88-104) --------------------
SEND_MAX_RETRIES = knob_int(
    "CDT_SEND_MAX_RETRIES", 5, "resilience",
    "Attempt bound for result sends.", doc="docs/resilience.md").get()
SEND_BACKOFF_BASE = knob_float(
    "CDT_SEND_BACKOFF_BASE", 0.5, "resilience",
    "Base of the exponential full-jitter backoff (seconds).",
    doc="docs/resilience.md").get()
RETRY_CAP_S = knob_float(
    "CDT_RETRY_CAP_S", 5.0, "resilience",
    "Per-sleep ceiling for the unified RetryPolicy's backoff (seconds).",
    doc="docs/resilience.md").get()
# Prompt-dispatch re-sends (only for provably-unsent failures; deliberately
# smaller than SEND_MAX_RETRIES: a slow host should fail over quickly).
DISPATCH_MAX_RETRIES = knob_int(
    "CDT_DISPATCH_MAX_RETRIES", 3, "resilience",
    "Attempt bound for provably-unsent prompt dispatch re-sends.",
    doc="docs/resilience.md").get()

# --- resilience (cluster/resilience.py, docs/resilience.md) -----------------
BREAKER_FAIL_THRESHOLD = knob_int(
    "CDT_BREAKER_FAIL_THRESHOLD", 3, "resilience",
    "Consecutive failures before a worker's circuit breaker opens.",
    doc="docs/resilience.md").get()
BREAKER_RECOVERY_S = knob_float(
    "CDT_BREAKER_RECOVERY_S", 30.0, "resilience",
    "Open-state dwell before one half-open trial is admitted (seconds).",
    doc="docs/resilience.md").get()
MAX_TILE_REQUEUES = knob_int(
    "CDT_MAX_TILE_REQUEUES", 3, "resilience",
    "Poison-tile bound: requeues before a task dead-letters.",
    doc="docs/resilience.md").get()
FAULTS = knob_str(
    "CDT_FAULTS", "", "resilience",
    "Deterministic fault-plan spec (op@sel:kind[=value];... with seed=N) "
    "for the chaos harness.", doc="docs/resilience.md")

# --- mesh / sharding defaults ---------------------------------------------
# Axis names used across the framework. "dp" shards independent jobs/seeds
# (the reference's worker fan-out), "tp" shards model weights, "sp" shards
# the sequence/spatial axis (ring attention / tile axis).
AXIS_DATA = "dp"
AXIS_TENSOR = "tp"
AXIS_SEQUENCE = "sp"

# --- serving front door (cluster/frontdoor, docs/serving.md) ---------------
# Priority classes in strict order (first = most latency-sensitive; the
# lowest class sheds first under overload).
PRIORITY_CLASSES = ("interactive", "batch")
DEFAULT_PRIORITY = "interactive"
DEFAULT_TENANT = "default"
FRONTDOOR = knob_bool(
    "CDT_FRONTDOOR", True, "serving",
    "Kill switch: 0 restores the verbatim legacy queue route.",
    doc="docs/serving.md")
FD_WINDOW_MS = knob_float(
    "CDT_FD_WINDOW_MS", 25.0, "serving",
    "Coalescing window: how long a group waits for same-shape company "
    "before flushing (ms).", doc="docs/serving.md").get()
FD_MAX_BATCH = knob_int(
    "CDT_FD_MAX_BATCH", 8, "serving",
    "Largest microbatch one SPMD program executes.",
    doc="docs/serving.md").get()
FD_INFLIGHT = knob_int(
    "CDT_FD_INFLIGHT", 2, "serving",
    "Batch jobs the front door keeps in the prompt queue at once "
    "(continuous batching).", doc="docs/serving.md").get()
FD_SOFT_DEPTH = knob_int(
    "CDT_FD_SOFT_DEPTH", 64, "serving",
    "Depth past which admission answers 'queued' (accepted, fleet busy).",
    doc="docs/serving.md").get()
FD_SHED_DEPTH = knob_int(
    "CDT_FD_SHED_DEPTH", 256, "serving",
    "Depth past which requests are shed with 429 + Retry-After (lowest "
    "priority sheds at half).", doc="docs/serving.md").get()
FD_TENANT_RATE = knob_float(
    "CDT_FD_TENANT_RATE", 20.0, "serving",
    "Per-tenant token bucket: sustained requests/second.",
    doc="docs/serving.md").get()
FD_TENANT_BURST = knob_float(
    "CDT_FD_TENANT_BURST", 40.0, "serving",
    "Per-tenant token bucket: burst capacity.", doc="docs/serving.md").get()
FD_MAX_TENANTS = knob_int(
    "CDT_FD_MAX_TENANTS", 1024, "serving",
    "LRU cap on the per-tenant bucket map.", doc="docs/serving.md").get()
FD_RETRY_AFTER_S = knob_float(
    "CDT_FD_RETRY_AFTER_S", 2.0, "serving",
    "Base Retry-After for shed responses (scaled by overload ratio).",
    doc="docs/serving.md").get()
FD_MAX_WAIT_MS = knob_float(
    "CDT_FD_MAX_WAIT_MS", None, "serving",
    "Force-flush valve: max ms a ready group may wait for capacity "
    "(default: 20x the window).", doc="docs/serving.md")

# --- content-addressed cache (cluster/cache, docs/caching.md) ---------------
CACHE = knob_bool(
    "CDT_CACHE", True, "caching",
    "Kill switch for the content-addressed cache subsystem.",
    doc="docs/caching.md")
CACHE_DIR = knob_str(
    "CDT_CACHE_DIR", None, "caching",
    "Persisted-tier directory (default: content_cache next to the XLA "
    "cache; empty string = memory-only).", doc="docs/caching.md",
    keep_empty=True)
CACHE_COND_MAX_BYTES = knob_int(
    "CDT_CACHE_COND_MAX_BYTES", 256 * 1024 * 1024, "caching",
    "In-memory conditioning-tier LRU cap (bytes).",
    doc="docs/caching.md").get()
CACHE_RESULT_MAX_BYTES = knob_int(
    "CDT_CACHE_RESULT_MAX_BYTES", 1024 * 1024 * 1024, "caching",
    "In-memory result-tier LRU cap (bytes) — full decoded image batches; "
    "budget accordingly.", doc="docs/caching.md").get()
CACHE_DISK_MAX_BYTES = knob_int(
    "CDT_CACHE_DISK_MAX_BYTES", 4 * 1024 * 1024 * 1024, "caching",
    "Persisted-tier byte cap (oldest-first eviction).",
    doc="docs/caching.md").get()

# --- fleet-wide distributed cache (cluster/cache/fleet.py) ------------------
# Runtime-read (no .get() at import): the fleet tier is rebuilt per
# controller in tests/bench, so these must track the live environment.
FLEET_CACHE = knob_bool(
    "CDT_FLEET_CACHE", True, "caching",
    "Kill switch for the fleet cache tier (consistent-hash shards, remote "
    "fills, near tier); 0 restores strictly per-host PR 8 behavior.",
    doc="docs/caching.md")
FLEET_CACHE_VNODES = knob_int(
    "CDT_FLEET_CACHE_VNODES", 64, "caching",
    "Virtual nodes per worker on the consistent-hash ring (more = smoother "
    "shard balance, slower ring rebuild).", doc="docs/caching.md")
FLEET_CACHE_SEED = knob_str(
    "CDT_FLEET_CACHE_SEED", "cdt-fleet-ring-v1", "caching",
    "Ring placement seed — every worker in a fleet must share it or they "
    "disagree on shard ownership (a disagreement degrades to misses, "
    "never wrong bytes).", doc="docs/caching.md")
FLEET_CACHE_TIMEOUT_S = knob_float(
    "CDT_FLEET_CACHE_TIMEOUT_S", 2.0, "caching",
    "Remote-serve budget (seconds): a ring owner slower than this degrades "
    "to a local miss (recompute), never an error.", doc="docs/caching.md")
FLEET_CACHE_NEAR_MAX = knob_int(
    "CDT_FLEET_CACHE_NEAR_MAX", 64, "caching",
    "Mid-trajectory donor checkpoints the near tier keeps (LRU; only "
    "consulted by opt-in cache:\"near\" requests).",
    doc="docs/caching.md")

# --- elastic fleet (cluster/elastic, docs/elasticity.md) --------------------
AUTOSCALE = knob_bool(
    "CDT_AUTOSCALE", False, "elasticity",
    "Enable the telemetry-driven autoscaler policy loop.",
    doc="docs/elasticity.md")
SCALE_PROVIDER = knob_str(
    "CDT_SCALE_PROVIDER", "", "elasticity",
    "module:factory spec for a custom ScaleProvider (remote/tunnel "
    "capacity); empty = in-repo local process provider.",
    doc="docs/elasticity.md")
STEAL_SEED = knob_int(
    "CDT_STEAL_SEED", 0, "elasticity",
    "Seed for the deterministic cross-job steal scheduler's tie-breaks.",
    doc="docs/elasticity.md")
DRAIN_DEADLINE_S = knob_float(
    "CDT_DRAIN_DEADLINE_S", 120.0, "elasticity",
    "How long a draining worker may keep in-flight work before handback "
    "(seconds).", doc="docs/elasticity.md").get()
AUTOSCALE_INTERVAL_S = knob_float(
    "CDT_AUTOSCALE_INTERVAL_S", 5.0, "elasticity",
    "Autoscaler evaluation cadence (seconds).",
    doc="docs/elasticity.md").get()
AUTOSCALE_MIN = knob_int(
    "CDT_AUTOSCALE_MIN", 0, "elasticity",
    "Fleet envelope floor (managed workers).",
    doc="docs/elasticity.md").get()
AUTOSCALE_MAX = knob_int(
    "CDT_AUTOSCALE_MAX", 4, "elasticity",
    "Fleet envelope ceiling (managed workers).",
    doc="docs/elasticity.md").get()
AUTOSCALE_UP_DEPTH = knob_float(
    "CDT_AUTOSCALE_UP_DEPTH", 4.0, "elasticity",
    "Per-capacity-unit pressure above which the fleet scales up.",
    doc="docs/elasticity.md").get()
AUTOSCALE_DOWN_DEPTH = knob_float(
    "CDT_AUTOSCALE_DOWN_DEPTH", 0.5, "elasticity",
    "Pressure below which the fleet scales down.",
    doc="docs/elasticity.md").get()
AUTOSCALE_UP_STREAK = knob_int(
    "CDT_AUTOSCALE_UP_STREAK", 2, "elasticity",
    "Consecutive over-threshold ticks required to scale up (hysteresis).",
    doc="docs/elasticity.md").get()
AUTOSCALE_DOWN_STREAK = knob_int(
    "CDT_AUTOSCALE_DOWN_STREAK", 4, "elasticity",
    "Consecutive under-threshold ticks required to scale down.",
    doc="docs/elasticity.md").get()
AUTOSCALE_UP_COOLDOWN_S = knob_float(
    "CDT_AUTOSCALE_UP_COOLDOWN_S", 30.0, "elasticity",
    "Min seconds between scale-ups.", doc="docs/elasticity.md").get()
AUTOSCALE_DOWN_COOLDOWN_S = knob_float(
    "CDT_AUTOSCALE_DOWN_COOLDOWN_S", 120.0, "elasticity",
    "Min seconds between scale-downs (removing capacity is reluctant).",
    doc="docs/elasticity.md").get()

# --- step-granular preemption (cluster/preemption.py, docs/preemption.md) ---
PREEMPT = knob_bool(
    "CDT_PREEMPT", True, "preemption",
    "Step-granular preemption: run serving sampler loops in resumable "
    "segments and let higher-priority work (or a drain) preempt the "
    "running job at the next segment boundary (0 = monolithic scans, "
    "no preemption).", doc="docs/preemption.md")
PREEMPT_SEGMENT_STEPS = knob_int(
    "CDT_PREEMPT_SEGMENT_STEPS", 8, "preemption",
    "Denoise steps per resumable segment — the preemption granularity "
    "(smaller = faster preemption, more per-segment dispatch overhead).",
    doc="docs/preemption.md")
PREEMPT_MAX = knob_int(
    "CDT_PREEMPT_MAX", 4, "preemption",
    "Per-job preemption bound: past this many preemptions a job runs to "
    "completion (starvation guard).", doc="docs/preemption.md")
PREEMPT_RESUME_RETRIES = knob_int(
    "CDT_PREEMPT_RESUME_RETRIES", 2, "preemption",
    "Restore attempts before a checkpoint is dead-lettered and its job "
    "restarts from scratch (a checkpoint that cannot restore must not "
    "loop).", doc="docs/preemption.md")
PREEMPT_SWEEP_S = knob_float(
    "CDT_PREEMPT_SWEEP_S", 0.5, "preemption",
    "Queued-deadline sweep cadence (seconds): a job whose deadline "
    "passes while queued goes terminal 'expired' within one sweep, not "
    "only when a dispatch next touches it (0 = sweep off).",
    doc="docs/preemption.md")
CKPT_MEM_BYTES = knob_int(
    "CDT_CKPT_MEM_BYTES", 512 * 1024 * 1024, "preemption",
    "In-memory latent-checkpoint store cap (bytes, LRU; pinned = the "
    "currently-resuming entry).", doc="docs/preemption.md")
CKPT_DIR = knob_str(
    "CDT_CKPT_DIR", None, "preemption",
    "Optional persisted checkpoint tier directory (checksummed sidecar "
    "files; unset/empty = memory-only).", doc="docs/preemption.md",
    keep_empty=True)

# --- disaggregated stage-split serving (cluster/stages, docs/stages.md) -----
STAGES = knob_bool(
    "CDT_STAGES", True, "stages",
    "Kill switch for disaggregated stage-split serving: 0 restores the "
    "fused one-program-per-group path (encode + denoise + decode on one "
    "worker thread).", doc="docs/stages.md")
STAGE_ENCODE_WORKERS = knob_int(
    "CDT_STAGE_ENCODE_WORKERS", 2, "stages",
    "Encode-pool worker threads (graph prefix + text encode; host-side, "
    "fed through the conditioning cache).", doc="docs/stages.md")
STAGE_DECODE_WORKERS = knob_int(
    "CDT_STAGE_DECODE_WORKERS", 2, "stages",
    "Decode-pool worker threads (batched VAE decode + graph suffix).",
    doc="docs/stages.md")
STAGE_MAX_WORKERS = knob_int(
    "CDT_STAGE_MAX_WORKERS", 4, "stages",
    "Per-pool ceiling the stage rebalancer may grow encode/decode pools "
    "to on backlog (the denoise pool is always exactly one — it owns "
    "the mesh).", doc="docs/stages.md")
STAGE_SCALE_DEPTH = knob_float(
    "CDT_STAGE_SCALE_DEPTH", 8.0, "stages",
    "Queue depth per worker above which a host-side stage pool grows by "
    "one (its own queue-depth gauge, never another stage's).",
    doc="docs/stages.md")
STAGE_DECODE_BATCH = knob_int(
    "CDT_STAGE_DECODE_BATCH", 8, "stages",
    "Largest cross-request VAE decode batch one program executes.",
    doc="docs/stages.md")
STAGE_DECODE_WINDOW_MS = knob_float(
    "CDT_STAGE_DECODE_WINDOW_MS", 5.0, "stages",
    "Decode coalescing window: how long a latent waits for same-bucket "
    "company before the decode pool flushes the bucket (ms).",
    doc="docs/stages.md")
STAGE_SHED_DEPTH = knob_int(
    "CDT_STAGE_SHED_DEPTH", 128, "stages",
    "Per-stage backlog cap: stage queue depths past this read as "
    "overload (they feed the front door's admission depth).",
    doc="docs/stages.md")
STAGE_WIRE = knob_bool(
    "CDT_STAGE_WIRE", False, "stages",
    "Force every denoise-to-decode handoff through the checksummed "
    "latent wire format (cross-worker simulation / integrity "
    "validation; in-process handoffs otherwise skip serialization).",
    doc="docs/stages.md")
STAGE_STEAL = knob_bool(
    "CDT_STAGE_STEAL", True, "stages",
    "Cross-stage work stealing: an idle encode/decode worker serves the "
    "deepest sibling host-side stage queue (the denoise pool never "
    "steals — it owns the mesh).", doc="docs/stages.md")
STAGE_MAX_REDISPATCH = knob_int(
    "CDT_STAGE_MAX_REDISPATCH", 3, "stages",
    "Re-dispatch bound for work a dead stage worker was holding; past "
    "it the member errors loudly instead of ping-ponging.",
    doc="docs/stages.md")

# --- VAE decode tiling ------------------------------------------------------
# 3D-VAE decodes switch to spatially-tiled mode when the latent frame area
# exceeds this (latent pixels): a 480p WAN clip decode holds >31 GB of f32
# activations untiled. 0 disables the threshold (always whole-frame).
VAE_TILE_THRESHOLD = knob_int(
    "CDT_VAE_TILE_THRESHOLD", 48 * 48, "models",
    "Latent frame area past which 3D-VAE decodes tile spatially "
    "(0 = always whole-frame).").get()
VAE_TILE = knob_int(
    "CDT_VAE_TILE", 32, "models", "Spatial tile edge for tiled VAE decode "
    "(latent pixels).").get()
VAE_TILE_OVERLAP = knob_int(
    "CDT_VAE_TILE_OVERLAP", 8, "models",
    "Tile overlap for seam blending (latent pixels).").get()

# =========================================================================
# Runtime-read knobs: call sites hold the Knob and call .get() per read
# (parse-once-per-value keeps that a dict hit). Grouped by subsystem.
# =========================================================================

# --- identity / paths / boot (cluster/controller.py, workers/) --------------
IS_WORKER = knob_bool(
    "CDT_IS_WORKER", False, "workers",
    "Set by the launch builder in spawned worker processes.",
    doc="docs/deployment.md")
WORKER_ID = knob_str(
    "CDT_WORKER_ID", "", "workers",
    "This controller's worker id (set by the launch builder).",
    doc="docs/deployment.md")
WORKER_INDEX = knob_int(
    "CDT_WORKER_INDEX", 0, "workers",
    "This controller's worker index.", doc="docs/deployment.md")
MASTER_PORT = knob_str(
    "CDT_MASTER_PORT", "", "workers",
    "Master control-plane port a spawned worker reports ready to.",
    doc="docs/deployment.md")
MASTER_PID = knob_int(
    "CDT_MASTER_PID", 0, "workers",
    "Master PID the worker monitor polls (kills the worker when the "
    "master dies).", doc="docs/deployment.md")
PID_FILE = knob_str(
    "CDT_PID_FILE", "", "workers",
    "Where the worker monitor writes 'monitor_pid,worker_pid'.",
    doc="docs/deployment.md")
MONITOR_POLL = knob_float(
    "CDT_MONITOR_POLL", 2.0, "workers",
    "Worker-monitor master-liveness poll cadence (seconds).",
    doc="docs/deployment.md")
MESH_DEVICES = knob_int(
    "CDT_MESH_DEVICES", None, "workers",
    "Restrict a spawned controller to this many local chips.",
    doc="docs/deployment.md")
LOG_DIR = knob_str(
    "CDT_LOG_DIR", "logs", "workers",
    "Directory for per-worker log files.", doc="docs/deployment.md")
LOG_FILE = knob_str(
    "CDT_LOG_FILE", "", "workers",
    "This process's log file (set by the lifecycle launcher; the log "
    "route tails it).", doc="docs/deployment.md")
CONFIG_PATH = knob_str(
    "CDT_CONFIG_PATH", None, "cluster",
    "Cluster config JSON path override.", doc="docs/deployment.md")
CHECKPOINT_ROOT = knob_str(
    "CDT_CHECKPOINT_ROOT", None, "models",
    "Root directory for model checkpoints.", doc="docs/weights.md")
OUTPUT_DIR = knob_str(
    "CDT_OUTPUT_DIR", "output", "cluster",
    "Where finished images/videos land.")
INPUT_DIR = knob_str(
    "CDT_INPUT_DIR", "input", "cluster",
    "Input directory media sync mirrors into.")
DEBUG = knob_bool(
    "CDT_DEBUG", False, "cluster",
    "Verbose debug logging (config settings.debug can only add to it).")
AUTH_TOKEN = knob_str(
    "CDT_AUTH_TOKEN", None, "cluster",
    "Cluster auth token (wins over the config so operators can rotate "
    "without editing files).", doc="docs/api.md")
PROFILE_DIR = knob_str(
    "CDT_PROFILE_DIR", "/tmp/cdt_profile", "cluster",
    "Where /distributed/profile traces are written.", doc="docs/api.md")
WORKFLOWS_DIR = knob_str(
    "CDT_WORKFLOWS_DIR", None, "cluster",
    "Override for the shipped workflows/ directory.")
TELEMETRY = knob_bool(
    "CDT_TELEMETRY", True, "telemetry",
    "Kill switch for the telemetry subsystem (empty string = off, the "
    "shell `CDT_TELEMETRY=` idiom).", doc="docs/telemetry.md",
    keep_empty=True)
NO_NATIVE = knob_bool(
    "CDT_NO_NATIVE", False, "cluster",
    "Skip loading/building the native codec library.")
MAX_FRAME_RAW_BYTES = knob_int(
    "CDT_MAX_FRAME_RAW_BYTES", 1 << 30, "cluster",
    "Bound on the zlib expansion of one decoded CDTF frame (bytes).")

# --- model-file resolution (graph/nodes_builtin.py, models/) ----------------
UPSCALE_MODEL_DIR = knob_str(
    "CDT_UPSCALE_MODEL_DIR", None, "models",
    "Directory of RRDBNet upscaler .safetensors (falls back to "
    "CDT_CHECKPOINT_ROOT/upscalers).", doc="docs/weights.md")
CONTROLNET_DIR = knob_str(
    "CDT_CONTROLNET_DIR", None, "models",
    "Directory of ControlNet .safetensors (falls back to "
    "CDT_CHECKPOINT_ROOT/controlnet).", doc="docs/weights.md")
LORA_DIR = knob_str(
    "CDT_LORA_DIR", None, "models",
    "Directory of LoRA .safetensors (falls back to "
    "CDT_CHECKPOINT_ROOT/loras).", doc="docs/weights.md")
TOKENIZER_DIR = knob_str(
    "CDT_TOKENIZER_DIR", None, "models",
    "CLIP BPE tokenizer root (vocab.json + merges.txt).",
    doc="docs/weights.md")
T5_TOKENIZER_DIR = knob_str(
    "CDT_T5_TOKENIZER_DIR", None, "models",
    "HF T5/UMT5 tokenizer directory.", doc="docs/weights.md")

# --- multi-host bootstrap (parallel/bootstrap.py) ---------------------------
COORDINATOR = knob_str(
    "CDT_COORDINATOR", None, "parallel",
    "jax.distributed coordinator address.", doc="docs/deployment.md")
NUM_HOSTS = knob_int(
    "CDT_NUM_HOSTS", None, "parallel",
    "Process count for multi-host init.", doc="docs/deployment.md")
HOST_INDEX = knob_int(
    "CDT_HOST_INDEX", None, "parallel",
    "This host's process id for multi-host init.",
    doc="docs/deployment.md")

# --- executed mesh serving tier (parallel/, docs/parallelism.md) ------------
VIRTUAL_DEVICES = knob_int(
    "CDT_VIRTUAL_DEVICES", None, "parallel",
    "Create this many virtual CPU devices before jax initializes "
    "(--xla_force_host_platform_device_count); fails loudly if jax is "
    "already imported.", doc="docs/parallelism.md")
MESH_TIER = knob_bool(
    "CDT_MESH_TIER", True, "parallel",
    "Executed mesh serving tier: warm sp/dp-tp programs and prefer the "
    "mesh placement for batchable groups (0 = dp-only legacy tier).",
    doc="docs/parallelism.md")
MESH_TP = knob_int(
    "CDT_MESH_TP", 0, "parallel",
    "tp degree for the mesh serving tier (0 = derive from the mesh "
    "config / HBM fit).", doc="docs/parallelism.md")
MESH_OVERLAP = knob_bool(
    "CDT_MESH_OVERLAP", True, "parallel",
    "Overlap-schedule mesh collectives: decompose all-reduce/all-gather "
    "into per-block ppermute rings instead of one fused collective.",
    doc="docs/parallelism.md")
COLLECTIVE_QUANT = knob_enum(
    "CDT_COLLECTIVE_QUANT", "none", ("none", "int8"), "parallel",
    "Quantized-collective wire format (EQuARX-style bf16->int8); "
    "'none' (default) keeps every collective bit-exact.",
    doc="docs/parallelism.md")

# --- compile cache / shape catalog / warmup (PR 4) --------------------------
SHAPE_CATALOG = knob_str(
    "CDT_SHAPE_CATALOG", None, "warmup",
    "Shape-catalog JSON path (default: next to the XLA cache).",
    doc="docs/deployment.md")
SHAPE_OBSERVE = knob_bool(
    "CDT_SHAPE_OBSERVE", True, "warmup",
    "Record request-path shapes into the catalog.",
    doc="docs/deployment.md")
SHAPE_CATALOG_MAX = knob_int(
    "CDT_SHAPE_CATALOG_MAX", 128, "warmup",
    "Cap on runtime-observed catalog entries (each costs an AOT compile "
    "on every future boot); empty string or 0 = uncapped.",
    doc="docs/deployment.md", keep_empty=True)
WARMUP = knob_bool(
    "CDT_WARMUP", False, "warmup",
    "AOT-compile the shape catalog on controller boot (cold/warming/"
    "ready health gating).", doc="docs/deployment.md")
WARMUP_MODELS = knob_str(
    "CDT_WARMUP_MODELS", "", "warmup",
    "Comma list of models to warm ('all'/'*' = the full workflow "
    "catalog; default: loaded + tiny presets).", doc="docs/deployment.md")

# --- attention kernels (PR 5, docs/kernels.md) ------------------------------
FLASH_ATTENTION = knob_optbool(
    "CDT_FLASH_ATTENTION", "kernels",
    "Force the flash path on (1) or off (0); unset = the one policy "
    "(packed from its floors up on a TPU, XLA elsewhere).",
    doc="docs/kernels.md", on_garbage="default")
# Hot-loop gate knob: warn-and-default on garbage is a TESTED contract
# (an env typo must not crash the attention dispatch mid-job).
RING_BLOCK = knob_int(
    "CDT_RING_BLOCK", 1024, "kernels",
    "Ring-attention block size for the sp axis.",
    doc="docs/kernels.md", on_garbage="default")

# --- HBM residency / offload (cluster/residency.py, diffusion/offload.py) ---
HBM_BUDGET_GB = knob_float(
    "CDT_HBM_BUDGET_GB", 0.0, "residency",
    "HBM budget for the residency planner (GB; 0 = unlimited, planner "
    "off).", doc="docs/deployment.md")
OFFLOAD = knob_optbool(
    "CDT_OFFLOAD", "offload",
    "Force host-offloaded execution on/off; unset = per-preset default.",
    doc="docs/deployment.md")
OFFLOAD_RESIDENT_GB = knob_float(
    "CDT_OFFLOAD_RESIDENT_GB", 13.0, "offload",
    "HBM the offload executor may keep resident (GB).",
    doc="docs/deployment.md")
OFFLOAD_STREAM_DTYPE = knob_str(
    "CDT_OFFLOAD_STREAM_DTYPE", "float8_e4m3fn", "offload",
    "Stream dtype for offloaded blocks ('float8_e4m3fn' or 'native').",
    doc="docs/deployment.md")
OFFLOAD_LADDER = knob_enum(
    "CDT_OFFLOAD_LADDER", "jit", ("jit", "step"), "offload",
    "How a fully-resident offloaded sample runs its sigma ladder.",
    doc="docs/deployment.md")
OFFLOAD_CACHE_DIR = knob_str(
    "CDT_OFFLOAD_CACHE_DIR", None, "offload",
    "Quantized-block cache dir (cuts a warm 12B executor build to a "
    "disk read).", doc="docs/deployment.md")

# --- serving / caching / elastic runtime switches ---------------------------
TILES_PER_DEVICE = knob_int(
    "CDT_TILES_PER_DEVICE", 0, "tiles",
    "Override tiles-per-device for the tile engine (0 = computed).",
    on_garbage="default")
TILE_MASTER_HOLDBACK_S = knob_float(
    "CDT_TILE_MASTER_HOLDBACK_S", 0.0, "tiles",
    "Master holds back from taking tile work this long so remote "
    "workers win the race (0 = disabled).")
TILE_READY_POLLS = knob_int(
    "CDT_TILE_READY_POLLS", 120, "tiles",
    "Polls while waiting for a tile job to initialize.",
    on_garbage="default")

# --- tunnel (utils/tunnel.py, docs/cloud-presets.md) ------------------------
TUNNEL_START_TIMEOUT = knob_float(
    "CDT_TUNNEL_START_TIMEOUT", 30.0, "tunnel",
    "Seconds to wait for cloudflared to print its URL.",
    doc="docs/cloud-presets.md")
CLOUDFLARED_VERSION = knob_str(
    "CDT_CLOUDFLARED_VERSION", None, "tunnel",
    "cloudflared release to download ('latest' or a version; default: "
    "the pinned version).", doc="docs/cloud-presets.md")
CLOUDFLARED_SHA256 = knob_str(
    "CDT_CLOUDFLARED_SHA256", None, "tunnel",
    "Expected sha256 of the cloudflared download.",
    doc="docs/cloud-presets.md")
CLOUDFLARED_AUTO_DOWNLOAD = knob_bool(
    "CDT_CLOUDFLARED_AUTO_DOWNLOAD", True, "tunnel",
    "Allow downloading cloudflared when no binary is found.",
    doc="docs/cloud-presets.md")

# --- lint / testing / bench (docs/lint.md) ----------------------------------
LOCK_ORDER = knob_bool(
    "CDT_LOCK_ORDER", False, "lint",
    "Dev-mode runtime lock-order detector: record cross-registry lock "
    "acquisition order and fail loudly on an inversion.",
    doc="docs/lint.md")
LOOP_STALL = knob_bool(
    "CDT_LOOP_STALL", False, "lint",
    "Dev-mode event-loop stall sanitizer: sample the asyncio loop and "
    "record any callback that blocks it past CDT_LOOP_STALL_MS, with "
    "the offending stack.",
    doc="docs/lint.md")
LOOP_STALL_MS = knob_float(
    "CDT_LOOP_STALL_MS", 100.0, "lint",
    "Stall threshold (milliseconds) for the CDT_LOOP_STALL sanitizer: a "
    "loop callback running longer than this is recorded as a stall.",
    doc="docs/lint.md")
TEST_WATCHDOG_S = knob_float(
    "CDT_TEST_WATCHDOG_S", 300.0, "testing",
    "Per-test watchdog: dump all thread stacks (faulthandler) after this "
    "many seconds so a deadlock leaves evidence (0 = off).",
    doc="docs/lint.md")
CHAOS_SEED = knob_int(
    "CDT_CHAOS_SEED", 42, "testing",
    "Fixed seed for the chaos suite so failures replay exactly.",
    doc="docs/resilience.md")
PROBE_RUNS = knob_int(
    "CDT_PROBE_RUNS", None, "bench",
    "Override the timed-run count in scripts/mfu_probe.py.")
