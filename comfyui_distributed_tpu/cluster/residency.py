"""Multi-model HBM residency planner: one fleet serves every workload.

The alternative — per-model worker pools — wastes chips whenever the
traffic mix shifts (the reference's answer: one ComfyUI process per GPU
per model). Instead, a single worker keeps several model bundles
(SDXL bf16, FLUX fp8, WAN dual-expert) under a per-chip HBM budget and
swaps deterministically:

- :class:`ResidencyPlanner` is the pure policy core: registered entries
  with (bytes, priority, last-use); eviction order is **lowest priority
  first, then least-recently-used**, pinned entries are untouchable.
  Pure → unit-testable on CPU with synthetic budgets, and the same
  decisions replay identically on every host.
- :class:`BundleResidency` binds the planner to a ``ModelRegistry``:
  acquiring a bundle measures its parameter bytes, evicts victims
  (dropping them from the registry cache and releasing any offload
  executors' device buffers via ``diffusion/offload.release_store``),
  and touches the LRU clock. Per-request LoRA hot-patching
  (:meth:`BundleResidency.request`) pins the base bundle for the
  request's duration and patches a copy-on-write clone
  (``models/lora.apply_lora`` shares every untouched leaf), so serving
  a LoRA'd request never evicts — or duplicates — the base model.

Accounting is host-side planning, not an HBM allocator: bytes are the
packed parameter sizes (same arithmetic as ``diffusion/offload.py``'s
placement planner). Activations/workspace stay the caller's headroom to
budget, exactly as with ``CDT_OFFLOAD_RESIDENT_GB``.

Knobs: ``CDT_HBM_BUDGET_GB`` (0/unset = unlimited, planner inactive).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Callable, Optional

from ..lint.lockorder import tracked_lock
from ..utils.constants import HBM_BUDGET_GB
from ..utils.exceptions import DistributedError
from ..utils.logging import log


class ResidencyError(DistributedError):
    """A bundle cannot be made resident under the configured budget."""


def hbm_budget_bytes() -> int:
    """0 = unlimited (planner off)."""
    return int(HBM_BUDGET_GB.get() * (1 << 30))


@dataclasses.dataclass
class _Entry:
    name: str
    nbytes: int
    priority: int = 0
    last_use: int = 0
    pins: int = 0


class ResidencyPlanner:
    """Deterministic LRU/priority residency policy over named entries.

    ``on_evict(name)`` performs the actual release (drop registry cache,
    free device buffers); the planner only decides. Thread-safe — the
    graph-executor thread and warmup/executor threads share it.
    """

    def __init__(self, budget_bytes: int,
                 on_evict: Optional[Callable[[str], None]] = None):
        self.budget = int(budget_bytes)
        self.on_evict = on_evict
        self._entries: dict[str, _Entry] = {}
        self._clock = 0
        self._lock = tracked_lock("residency.planner", reentrant=True)

    # --- introspection ------------------------------------------------------

    def resident(self) -> list[str]:
        """Names in eviction order (first = next victim)."""
        with self._lock:
            return [e.name for e in self._victim_order()]

    def resident_bytes(self) -> int:
        with self._lock:
            return sum(e.nbytes for e in self._entries.values())

    def is_resident(self, name: str) -> bool:
        return name in self._entries

    # --- policy -------------------------------------------------------------

    def _victim_order(self) -> list[_Entry]:
        return sorted(self._entries.values(),
                      key=lambda e: (e.priority, e.last_use))

    def plan(self, name: str, nbytes: int) -> list[str]:
        """Victims that WOULD be evicted to fit ``name`` — without
        applying anything (capacity planning / dry runs). Raises
        :class:`ResidencyError` when no eviction sequence fits."""
        with self._lock:
            return self._plan_locked(name, int(nbytes))

    def _plan_locked(self, name: str, nbytes: int) -> list[str]:
        have = self._entries.get(name)
        used = sum(e.nbytes for e in self._entries.values()) \
            - (have.nbytes if have else 0)
        if self.budget <= 0 or used + nbytes <= self.budget:
            return []
        victims = []
        for e in self._victim_order():
            if e.name == name or e.pins > 0:
                continue
            victims.append(e.name)
            used -= e.nbytes
            if used + nbytes <= self.budget:
                return victims
        if nbytes > self.budget:
            raise ResidencyError(
                f"model {name!r} needs {nbytes / 1e9:.2f} GB but the HBM "
                f"budget is {self.budget / 1e9:.2f} GB "
                "(CDT_HBM_BUDGET_GB) — it can never be resident")
        pinned = [e.name for e in self._entries.values() if e.pins > 0]
        raise ResidencyError(
            f"cannot fit {name!r} ({nbytes / 1e9:.2f} GB): "
            f"{used / 1e9:.2f} GB held by pinned bundles {pinned} under a "
            f"{self.budget / 1e9:.2f} GB budget")

    def acquire(self, name: str, nbytes: int, priority: int = 0
                ) -> list[str]:
        """Make ``name`` resident: evict the planned victims (calling
        ``on_evict`` for each), then register/touch the entry. Returns
        the evicted names, in order."""
        with self._lock:
            victims = self._plan_locked(name, int(nbytes))
            for v in victims:
                self._evict_locked(v, reason="budget")
            e = self._entries.get(name)
            if e is None:
                e = self._entries[name] = _Entry(name, int(nbytes),
                                                 int(priority))
            else:
                e.nbytes = int(nbytes)
                e.priority = int(priority)
            self._clock += 1
            e.last_use = self._clock
            self._export_gauges()
            return victims

    def touch(self, name: str) -> None:
        with self._lock:
            e = self._entries.get(name)
            if e is not None:
                self._clock += 1
                e.last_use = self._clock

    def release(self, name: str) -> bool:
        """Manual eviction (e.g. ``/distributed/clear_memory``)."""
        with self._lock:
            if name not in self._entries:
                return False
            if self._entries[name].pins > 0:
                raise ResidencyError(
                    f"cannot release {name!r}: pinned by an in-flight "
                    "request")
            self._evict_locked(name, reason="manual")
            self._export_gauges()
            return True

    def _evict_locked(self, name: str, reason: str) -> None:
        self._entries.pop(name, None)
        log(f"residency: evicting {name!r} ({reason})")
        try:
            from ..telemetry import enabled as _tm_enabled
            from ..telemetry import metrics as _tm

            if _tm_enabled():
                _tm.RESIDENCY_EVICTIONS.labels(reason=reason).inc()
        except Exception:  # noqa: BLE001
            pass
        if self.on_evict is not None:
            self.on_evict(name)

    # --- pinning ------------------------------------------------------------

    def pin(self, name: str) -> None:
        with self._lock:
            e = self._entries.get(name)
            if e is None:
                raise ResidencyError(f"cannot pin non-resident {name!r}")
            e.pins += 1

    def unpin(self, name: str) -> None:
        with self._lock:
            e = self._entries.get(name)
            if e is not None and e.pins > 0:
                e.pins -= 1

    @contextlib.contextmanager
    def pinned(self, name: str):
        self.pin(name)
        try:
            yield
        finally:
            self.unpin(name)

    def _export_gauges(self) -> None:
        try:
            from ..telemetry import enabled as _tm_enabled
            from ..telemetry import metrics as _tm

            if _tm_enabled():
                _tm.RESIDENT_MODELS.set(len(self._entries))
                _tm.RESIDENT_BYTES.set(
                    sum(e.nbytes for e in self._entries.values()))
        except Exception:  # noqa: BLE001
            pass


def tp_shard_bytes(params, rules, tp: int) -> int:
    """PER-CHIP bytes of ``params`` under Megatron tp sharding: leaves
    the placement rules shard contribute ``nbytes/tp``, everything else
    (norms, embeddings, modulation — and any leaf whose dims don't
    divide) its full size. This is the tp-shard-granularity arithmetic
    the mesh serving tier plans HBM with: a 12B model at tp=4 costs each
    chip a quarter of its matmul weights plus the replicated glue, not
    the headline parameter count."""
    import jax

    from ..parallel.tensor import _path_str, spec_for_param

    total = 0

    def visit(path, leaf):
        nonlocal total
        nbytes = leaf.size * leaf.dtype.itemsize
        spec = spec_for_param(_path_str(path), leaf.shape, rules,
                              "tp", tp)
        total += nbytes // tp if any(d is not None for d in spec) \
            else nbytes

    jax.tree_util.tree_map_with_path(visit, params)
    return total


def _tp_rules_for(bundle):
    """The Megatron placement rule set this bundle's core model shards
    with — the same tables ``generate_tp_fn`` places weights by, so
    planning and placement can't disagree about what shards."""
    from ..parallel.tensor import (DIT_TP_RULES, UNET_TP_RULES,
                                   WAN_TP_RULES)

    pipe = bundle.pipeline
    if getattr(pipe, "unet", None) is not None:
        return UNET_TP_RULES
    dit = getattr(pipe, "dit", None)
    if dit is not None and type(dit).__name__.startswith("Wan"):
        return WAN_TP_RULES
    return DIT_TP_RULES


def bundle_bytes(bundle, tp_shards: int = 1) -> int:
    """Packed parameter bytes of a loaded ``ModelBundle`` — core params
    (+ the low-noise expert for dual-expert WAN), both VAE halves, and
    the active text stack. Same per-leaf arithmetic as the offload
    placement planner.

    ``tp_shards > 1`` plans at tp-shard granularity: the core model's
    rule-matched weights divide over the tp axis (``tp_shard_bytes``)
    while VAE/text — which serve replicated on every chip — count
    full-size."""
    from ..diffusion.offload import tree_bytes

    core = bundle._core_params()
    if bundle.kind == "llm":          # no VAE, no text tower
        return tree_bytes(core)
    low = getattr(bundle.pipeline, "dit_params_low", None)
    if tp_shards > 1:
        rules = _tp_rules_for(bundle)
        total = tp_shard_bytes(core, rules, tp_shards)
        if low is not None:
            total += tp_shard_bytes(low, rules, tp_shards)
    else:
        total = tree_bytes(core)
        if low is not None:
            total += tree_bytes(low)
    total += tree_bytes(bundle.pipeline.vae.enc_params)
    total += tree_bytes(bundle.pipeline.vae.dec_params)
    params = getattr(bundle.text_encoder, "params", None)
    if params is not None:
        total += tree_bytes(params)
    return total


class BundleResidency:
    """Planner ↔ registry binding (constructed by ``ModelRegistry`` when
    ``CDT_HBM_BUDGET_GB`` is set)."""

    def __init__(self, registry, budget_bytes: int,
                 estimator: Callable = bundle_bytes,
                 tp_shards: Optional[int] = None):
        """``tp_shards``: plan HBM at tp-shard granularity (per-chip
        slice of rule-matched weights + replicated glue). ``None``
        resolves per-acquire via ``tp_shards_fn`` — the controller sets
        it to the SERVING MESH's tp degree, the same axis that routes
        weight-sharded programs (``generate_microbatch``), so planned
        bytes can never diverge from held bytes. With neither set,
        planning stays whole-model (replicated serving)."""
        self._registry = registry
        self._estimator = estimator
        self._tp_shards = tp_shards
        # set post-construction by the controller (the mesh is built
        # lazily there); must mirror the mesh that shards weights
        self.tp_shards_fn: Optional[Callable[[], int]] = None
        self.planner = ResidencyPlanner(budget_bytes,
                                        on_evict=self._evict_bundle)

    def _evict_bundle(self, name: str) -> None:
        bundle = self._registry._cache.pop(name, None)
        if bundle is not None:
            bundle.release_device()

    def _resolve_tp(self) -> int:
        if self._tp_shards is not None:
            return max(1, int(self._tp_shards))
        from ..parallel.serving import mesh_tier_enabled

        if not mesh_tier_enabled() or self.tp_shards_fn is None:
            return 1
        try:
            return max(1, int(self.tp_shards_fn()))
        except Exception:  # noqa: BLE001 — planning must not sink a build
            return 1

    def measure(self, bundle) -> int:
        """Planner-relevant bytes for one bundle (tp-shard granularity
        when the mesh tier shards weights; custom estimators without a
        ``tp_shards`` kwarg keep their whole-model arithmetic)."""
        tp = self._resolve_tp()
        if tp > 1:
            try:
                return self._estimator(bundle, tp_shards=tp)
            except TypeError:
                pass
        return self._estimator(bundle)

    def note_use(self, name: str, bundle, priority: int = 0) -> list[str]:
        """Account a registry hit: first sight measures + acquires
        (evicting victims), repeats just touch the LRU clock.

        Sizing happens after the build (params exist to be measured);
        a build that transiently overlaps a victim is the documented
        cost of not materializing abstract trees twice.
        """
        if self.planner.is_resident(name):
            self.planner.touch(name)
            return []
        return self.planner.acquire(name, self.measure(bundle),
                                    priority=priority)

    @contextlib.contextmanager
    def request(self, name: str, lora_sd=None, **lora_kw):
        """Serve one request against ``name``, optionally hot-patched
        with a LoRA. The base bundle is pinned for the duration — a
        concurrent acquire of another model can evict any *other*
        bundle, never the one mid-request — and the LoRA patch is an
        ephemeral copy-on-write clone (shared leaves, fresh compile
        caches) that is never registered with the planner."""
        # get→pin is not atomic against a concurrent acquire evicting
        # this bundle in the gap — retry until a pin lands on a live
        # registration (bounded: eviction requires another thread
        # actively thrashing the budget)
        for _ in range(8):
            bundle = self._registry.get(name)
            try:
                self.planner.pin(name)
                break
            except ResidencyError:
                continue
        else:
            raise ResidencyError(
                f"could not pin {name!r}: concurrent acquires keep "
                "evicting it (budget thrash — raise CDT_HBM_BUDGET_GB)")
        try:
            if lora_sd is None:
                yield bundle
            else:
                from ..models.lora import apply_lora

                patched, _ = apply_lora(bundle, lora_sd, **lora_kw)
                yield patched
        finally:
            self.planner.unpin(name)


@contextlib.contextmanager
def pinned_bundle(bundle):
    """Pin a registry bundle for the duration of a generate call (no-op
    when no residency planner is attached). The sampler nodes wrap
    execution in this so a concurrent acquire — the warmup thread, a
    second model's request — can never ``release_device()`` the bundle
    mid-program."""
    res = getattr(bundle, "_residency", None)
    name = getattr(getattr(bundle, "preset", None), "name", None)
    if res is None or name is None:
        yield
        return
    try:
        res.planner.pin(name)
    except ResidencyError:
        # already evicted between fetch and pin: the caller's reference
        # keeps the host params alive — execution proceeds (re-uploading
        # as needed), it just lost the residency fast path
        yield
        return
    try:
        yield
    finally:
        res.planner.unpin(name)
