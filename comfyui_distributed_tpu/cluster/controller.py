"""The host controller: one per host, owning that host's chips.

Reference analogue: one ComfyUI instance (master or worker —
``distributed.py:1-51``). Role is determined by ``is_worker`` (env
``CDT_IS_WORKER``, parity with ``COMFYUI_IS_WORKER``, ``distributed.py:48``):
masters orchestrate and collect; workers execute dispatched prompts and
push results back. Both run the same code and the same HTTP app.
"""

from __future__ import annotations

import asyncio
import os
import platform
from pathlib import Path
from typing import Any, Optional

from ..utils import constants
from ..utils.config import ensure_config_exists, load_config, peek_setting
from ..utils.logging import log, set_debug_source
from ..workers.detection import detect_environment, get_machine_id as machine_id
from .collector_bridge import CollectorBridge
from .job_store import JobStore
from .orchestration import Orchestrator
from .runtime import PromptQueue

IS_WORKER_ENV = "CDT_IS_WORKER"


class Controller:
    def __init__(self, config_path: Optional[Path] = None,
                 mesh_devices: Optional[int] = None):
        ensure_config_exists(config_path)
        self.config_path = config_path
        if config_path is not None:
            # outbound peer calls must read the auth token from the SAME
            # config this controller enforces inbound (utils/network.py)
            from ..utils.network import set_auth_config_path

            set_auth_config_path(config_path)
        # wire the config's settings.debug flag into the TTL-cached log
        # gate (reference utils/logging.py:15-39) — without this only the
        # CDT_DEBUG env var could enable debug logging (the gate always
        # honors the env var on top of this source)
        set_debug_source(
            lambda: bool(peek_setting("debug", False, config_path)))
        self.is_worker = constants.IS_WORKER.get()
        self.store = JobStore()
        self.queue = PromptQueue(context_factory=self._execution_context)
        self.orchestrator = Orchestrator(self.store, self.queue,
                                         config_loader=self.load_config)
        # content-addressed cache (cluster/cache): conditioning + result
        # tiers and the in-flight coalescer; None under CDT_CACHE=0
        from .cache import build_cache_manager

        self.cache = build_cache_manager()
        # step-granular preemption (cluster/preemption.py): resumable
        # denoise segments + latent checkpoint parking; None under
        # CDT_PREEMPT=0 (monolithic sampler programs)
        from .preemption import build_preemption

        self.preemption = build_preemption(self.queue)
        self.queue.preemption = self.preemption
        # disaggregated stage-split serving (cluster/stages,
        # docs/stages.md): independent encode/denoise/decode pools for
        # front-door batch jobs; None under CDT_STAGES=0 (fused path)
        from .stages import build_stages

        self.stages = build_stages()
        self.queue.stages = self.stages
        # serving front door (cluster/frontdoor): admission control +
        # cross-user microbatching in front of the queue; None under
        # CDT_FRONTDOOR=0 (the API layer then serves the legacy path)
        from .frontdoor import build_frontdoor

        self.frontdoor = build_frontdoor(self.queue, self.orchestrator,
                                         config_loader=self.load_config,
                                         cache=self.cache,
                                         stages=self.stages)
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        self.bridge: Optional[CollectorBridge] = None
        self.tile_farm = None
        self._mesh = None
        self._mesh_devices = mesh_devices
        self._registry = None
        self.worker_id = constants.WORKER_ID.get()
        self.worker_index = constants.WORKER_INDEX.get()
        # fleet cache tier (cluster/cache/fleet.py): consistent-hash
        # shards over the configured hosts + drain handback + near tier;
        # None under CDT_FLEET_CACHE=0 or CDT_CACHE=0 (per-host only)
        if self.cache is not None:
            from .cache.fleet import build_fleet_cache

            self.cache.fleet = build_fleet_cache(
                self.cache, self.worker_id or "master",
                self._fleet_membership)
        from .progress import ProgressTracker
        self.progress = ProgressTracker()
        # AOT warmup state machine (diffusion/warmup.py): health probes
        # report cold/warming/ready and the dispatcher prefers hot hosts
        from ..diffusion.warmup import WarmupManager

        self.warmup = WarmupManager(lambda: self.model_registry,
                                    lambda: self.mesh)
        self._warmup_task = None
        # elastic fleet (cluster/elastic): drain coordination always;
        # the autoscaler loop only under CDT_AUTOSCALE=1. Built at
        # startup — the drain coordinator schedules asyncio tasks and
        # needs the serving loop.
        self.elastic = None

    def load_config(self) -> dict:
        return load_config(self.config_path)

    def _fleet_membership(self) -> dict:
        """Fleet-cache ring membership: every configured host id → base
        URL, plus this worker (URL None — it never probes itself). The
        fleet tier itself filters DRAIN-leaving workers, so this stays a
        plain config read."""
        from ..utils.network import build_host_url

        members: dict = {(self.worker_id or "master"): None}
        try:
            for h in self.load_config().get("hosts", []):
                hid = str(h.get("id") or "")
                if hid and hid not in members:
                    members[hid] = build_host_url(h) or None
        except Exception:  # noqa: BLE001 — a bad config is an empty fleet
            pass
        return members

    def host_by_id(self, host_id: str) -> Optional[dict]:
        """Config host entry for a worker/host id (busy-probe resolver)."""
        for h in self.load_config().get("hosts", []):
            if str(h.get("id")) == str(host_id):
                return h
        return None

    # --- lazily-built heavyweight state ------------------------------------

    @property
    def mesh(self):
        if self._mesh is None:
            import jax

            from ..parallel.mesh import mesh_from_config, build_mesh

            if self._mesh_devices:
                self._mesh = build_mesh(
                    {"dp": self._mesh_devices}, jax.devices()[: self._mesh_devices])
            else:
                self._mesh = mesh_from_config(self.load_config())
        return self._mesh

    @property
    def model_registry(self):
        if self._registry is None:
            from ..models.registry import ModelRegistry

            root = constants.CHECKPOINT_ROOT.get()
            self._registry = ModelRegistry(Path(root) if root else None)
            if self._registry.residency is not None:
                # HBM planning must match the mesh that actually shards
                # weights: the tp degree of THIS worker's serving mesh
                # (docs/parallelism.md), not a free-floating knob —
                # planned bytes and held bytes diverge otherwise
                self._registry.residency.tp_shards_fn = (
                    lambda: dict(self.mesh.shape).get(
                        constants.AXIS_TENSOR, 1))
        return self._registry

    def _execution_context(self) -> dict[str, Any]:
        ctx: dict[str, Any] = {
            "mesh": self.mesh,
            "model_registry": self.model_registry,
            "output_dir": constants.OUTPUT_DIR.get(),
            "input_dir": constants.INPUT_DIR.get(),
            "job_store": self.store,
            "is_worker": self.is_worker,
            "worker_id": self.worker_id,
            "worker_index": self.worker_index,
            "progress_tracker": self.progress,
            # content cache (cluster/cache): CLIPTextEncode reads it as a
            # hidden input; the microbatch executor serves/fills the
            # result tier through it
            "content_cache": self.cache,
        }
        if self.bridge is not None:
            ctx["collector_bridge"] = self.bridge
        if self.tile_farm is not None:
            ctx["tile_farm"] = self.tile_farm
        return ctx

    # --- lifecycle ----------------------------------------------------------

    async def startup(self) -> None:
        from .tile_farm import TileFarm

        self.loop = asyncio.get_running_loop()
        self.bridge = CollectorBridge(self.store, self.loop,
                                      host_resolver=self.host_by_id)
        self.tile_farm = TileFarm(self.store, self.loop)
        if self.cache is not None and self.cache.fleet is not None:
            # remote probes/fills bridge from worker threads onto this
            # loop; until attach the ladder degrades to local-only
            self.cache.fleet.attach_loop(self.loop)
        self.queue.start()
        if self.frontdoor is not None:
            self.frontdoor.start()
        from .elastic import build_elastic

        self.elastic = build_elastic(self)
        self.elastic.start()
        role = "worker" if self.is_worker else "master"
        log(f"controller up as {role} (machine {machine_id()})")
        if self.is_worker and self.worker_id:
            # self-report ready → master clears this worker's launching
            # flag (reference handshake, api/worker_routes.py:115-139);
            # reference kept so the task can't be GC'd before running
            self._ready_task = asyncio.ensure_future(self._report_ready())
        if constants.WARMUP.get():
            # AOT warmup off the request path: compiles run in their own
            # thread (NOT the graph-exec pool — a dispatched prompt must
            # not queue behind the whole catalog); health reports
            # "warming" until the pass finishes, so the master's
            # dispatcher steers work to already-hot peers meanwhile
            self._warmup_task = self.loop.run_in_executor(
                None, self.warmup.run)

    async def _report_ready(self) -> None:
        import aiohttp

        from ..utils.network import get_client_session

        master_port = constants.MASTER_PORT.get()
        if not master_port:
            return
        url = (f"http://127.0.0.1:{master_port}"
               "/distributed/worker/clear_launching")
        try:
            session = get_client_session()
            async with session.post(
                url, json={"worker_id": self.worker_id},
                timeout=aiohttp.ClientTimeout(total=constants.PROBE_TIMEOUT),
            ) as resp:
                await resp.read()
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
            pass                       # master gone or standalone worker

    async def shutdown(self) -> None:
        from ..utils.network import close_client_session

        if self.elastic is not None:
            await self.elastic.stop()
        if self.frontdoor is not None:
            await self.frontdoor.stop()
        if self.stages is not None:
            # stop the stage pools BEFORE the queue: leftover decode
            # items record interrupted history through the queue's
            # callbacks, which must still be alive
            self.stages.stop()
        await self.queue.stop()
        if self.cache is not None and self.cache.fleet is not None:
            self.cache.fleet.close()   # unsubscribe from the DRAIN feed
        self.progress.close()      # release the global progress sink
        await close_client_session()

    # --- health -------------------------------------------------------------

    def health(self) -> dict:
        return {
            "status": "ok",
            "role": "worker" if self.is_worker else "master",
            "queue_remaining": self.queue.queue_remaining,
            "executing": self.queue.executing,
            "machine_id": machine_id(),
            # cold | warming | ready | error — dispatch prefers hosts
            # that are not mid-warmup (cluster/dispatch.py)
            "warmup": self.warmup.state,
            # coalescing + queued depth the admission layer sheds on
            "frontdoor": (None if self.frontdoor is None
                          else {"depth": self.frontdoor.depth(),
                                "coalescing":
                                    self.frontdoor.batcher.pending_count}),
            # content-cache hit rate (cluster/cache, docs/caching.md) —
            # the signal that lets the autoscaler shrink a hot-cache fleet
            "cache": (None if self.cache is None
                      else {"hit_rate": round(self.cache.hit_rate(), 4),
                            "fleet_ring":
                                (len(self.cache.fleet.ring()[0])
                                 if self.cache.fleet is not None
                                 else 0)}),
            # per-stage pool backlog (cluster/stages, docs/stages.md)
            "stages": (None if self.stages is None
                       else self.stages.depths()),
        }

    def system_info(self) -> dict:
        """Parity: ``/distributed/system_info``
        (``api/worker_routes.py:393-430``) with TPU topology instead of a
        CUDA census."""
        from .. import native
        from ..parallel.mesh import device_census
        from ..utils.compile_cache import active_cache_dir

        return {
            "machine_id": machine_id(),
            "platform": platform.system().lower(),
            "path_separator": os.sep,
            "python": platform.python_version(),
            "is_docker": Path("/.dockerenv").exists(),
            "environment": detect_environment(),
            "devices": device_census(),
            "compile_cache_dir": active_cache_dir(),
            "native_codec": native.is_native(),
        }

    def clear_memory(self) -> dict:
        """Parity: ``/distributed/clear_memory`` (``api/job_routes.py:160-203``)
        — unload models + drop compiled programs. TPU equivalent: clear the
        model registry cache, JAX compilation caches, and live device
        buffers owned by caches."""
        import gc

        import jax

        self._registry = None
        self._mesh = None
        jax.clear_caches()
        gc.collect()
        return {"status": "cleared"}
