"""Acceptance test for the resilience layer (ISSUE 4): a 3-worker tile
farm over a REAL localhost HTTP server, under a seeded FaultPlan that

- kills 2 of the 3 workers mid-job (network partition: their pulls start
  dropping while they hold assignments — heartbeat silence follows),
- corrupts one tile payload on the wire (crc-rejected by the master, the
  sender's RetryPolicy re-sends intact bytes),

and must still complete **bit-identically** to the fault-free run, with
the dead workers' breakers reading ``open`` in ``/distributed/metrics``.
A second job with a deterministically-crashing tile then exercises the
poison path: the task exhausts ``max_requeues``, lands in the dead-letter
list surfaced by ``GET /distributed/job_status``, and the job finishes
instead of hanging.

Everything is in-process and seeded (no subprocesses, no SIGKILL racing)
— seconds, not minutes, so the chaos marker rides tier-1.
"""

import asyncio
import re

import numpy as np
import pytest

from comfyui_distributed_tpu.cluster.controller import Controller
from comfyui_distributed_tpu.cluster.faults import FaultPlan, FaultSession
from comfyui_distributed_tpu.cluster.job_store import JobStore
from comfyui_distributed_tpu.cluster.resilience import BREAKERS
from comfyui_distributed_tpu.cluster.tile_farm import TileFarm, assemble_tiles

pytestmark = pytest.mark.chaos

TOTAL, CHUNK = 12, 1


def make_proc(delay=0.0):
    """Deterministic on the GLOBAL tile index: whoever processes tile i
    must produce the same pixels, so requeue/corruption-retry are
    provably invisible in the output."""
    import time as _t

    def proc(start, end):
        if delay:
            _t.sleep(delay)
        return np.stack([np.full((4, 4, 3), float(i) * 1.5 + 0.25,
                                 np.float32)
                         for i in range(start, end)])
    return proc


def _serve_master():
    from aiohttp.test_utils import TestClient, TestServer

    from comfyui_distributed_tpu.api.app import create_app

    controller = Controller()
    return controller, TestClient(TestServer(create_app(controller)))


async def _doomed_worker(client, base, job_id, worker_id, seed):
    """A worker the seeded FaultPlan kills mid-job: its first two pulls
    succeed (it now HOLDS assignments), then its network partitions —
    every further call drops, and it never heartbeats again. Exactly the
    transient-host-loss shape pods see in production."""
    import aiohttp

    plan = FaultPlan.parse(
        f"seed={seed};request_work@2-999:drop;heartbeat@*:drop;"
        "submit@*:drop")
    session = FaultSession(client.session, plan)
    pulled = []
    for _ in range(4):
        try:
            async with session.post(
                    f"{base}/distributed/request_image",
                    json={"job_id": job_id, "worker_id": worker_id}) as r:
                body = await r.json()
                if body.get("task") is not None:
                    pulled.append(body["task"]["task_id"])
        except aiohttp.ClientConnectionError:
            return pulled                      # "killed" by the plan
    return pulled


class TestChaosAcceptance:
    def test_three_worker_farm_survives_seeded_faults(self, tmp_config,
                                                      fault_plan):
        # fault-free reference run (master alone, same process_fn)
        async def reference():
            store = JobStore()
            farm = TileFarm(store, asyncio.get_running_loop())
            results = await farm.master_run_async(
                "ref", total=TOTAL, process_fn=make_proc(), chunk=CHUNK,
                heartbeat_interval=0.2)
            return assemble_tiles(results, TOTAL, CHUNK)

        ref = asyncio.run(reference())

        # the global plan corrupts the surviving worker's FIRST tile
        # submit on the wire; its RetryPolicy must re-send intact bytes
        fault_plan("seed=42;submit@0:corrupt")

        async def chaotic():
            controller, client = _serve_master()
            async with client:
                base = f"http://127.0.0.1:{client.port}"
                farm_m = controller.tile_farm
                master_task = asyncio.create_task(farm_m.master_run_async(
                    "chaos3", total=TOTAL, process_fn=make_proc(delay=0.1),
                    chunk=CHUNK, heartbeat_interval=0.2,
                    worker_timeout=0.5))
                await asyncio.sleep(0.05)      # job seeded

                # w1 and w2 pull work, then their network partitions:
                # they die HOLDING assignments
                held1 = await _doomed_worker(client, base, "chaos3", "w1",
                                             seed=1)
                held2 = await _doomed_worker(client, base, "chaos3", "w2",
                                             seed=2)
                assert held1 and held2, "doomed workers never got work"

                # the survivor runs the real worker loop (its session is
                # wrapped by the active plan => submit[0] corrupted)
                farm_w = TileFarm(JobStore(), asyncio.get_running_loop())
                done = await farm_w.worker_run_async(
                    "chaos3", "w0", base, make_proc(), max_batch=1)

                results = await asyncio.wait_for(master_task, timeout=90)
                assert done > 0, "survivor never completed a task"

                # dead workers' breakers read OPEN in /distributed/metrics
                async with client.session.get(
                        f"{base}/distributed/metrics") as resp:
                    metrics_text = await resp.text()
                for dead in ("w1", "w2"):
                    assert re.search(
                        r'cdt_worker_breaker_state\{worker="%s"\} 2(\.0)?'
                        % dead, metrics_text), \
                        f"breaker for {dead} not open:\n" + "\n".join(
                            l for l in metrics_text.splitlines()
                            if "breaker" in l)
                assert BREAKERS.state("w1") == "open"
                assert BREAKERS.state("w2") == "open"
                # the survivor stayed admitted
                assert BREAKERS.state("w0") == "closed"
                return results

        results = asyncio.run(chaotic())
        # every task completed exactly once, bit-identical to fault-free
        out = assemble_tiles(results, TOTAL, CHUNK)
        np.testing.assert_array_equal(out, ref)

    def test_poison_tile_dead_letters_without_hanging(self, tmp_config,
                                                      monkeypatch):
        """A tile that deterministically crashes processing exhausts
        max_requeues, lands in the dead-letter list surfaced by
        GET /distributed/job_status, and the job still finishes."""
        from comfyui_distributed_tpu.utils import constants

        monkeypatch.setattr(constants, "MAX_TILE_REQUEUES", 2)
        attempts = {"poison": 0}

        def proc(start, end):
            if start <= 3 < end:               # global tile 3 is poison
                attempts["poison"] += 1
                raise RuntimeError("injected poison tile")
            return np.stack([np.full((4, 4, 3), float(i), np.float32)
                             for i in range(start, end)])

        async def body():
            controller, client = _serve_master()
            async with client:
                base = f"http://127.0.0.1:{client.port}"
                results = await asyncio.wait_for(
                    controller.tile_farm.master_run_async(
                        "poison", total=6, process_fn=proc, chunk=1,
                        heartbeat_interval=0.2),
                    timeout=60)                 # completes: no hang
                assert set(results) == {0, 1, 2, 4, 5}
                assert attempts["poison"] == 3  # max_requeues + 1

                # forensics survive job completion via the HTTP surface
                async with client.session.get(
                        f"{base}/distributed/job_status",
                        params={"job_id": "poison"}) as resp:
                    status = await resp.json()
                assert status["finished"] is True
                assert status["exists"] is False   # not pullable anymore
                (dead,) = status["dead_letter"]
                assert dead["task_id"] == 3
                assert dead["requeues"] == 3
                assert "poison" in dead["reason"]
                assert status["completed"] == 5 and status["total"] == 6
        asyncio.run(body())


class TestRollingRestart:
    """Seeded rolling-restart event (ISSUE 6): a worker dies mid-job
    holding an assignment; its warm-restarted replacement — same compile
    cache, same shape catalog — rejoins, reports ``ready`` after a pure
    cache-hit warmup pass (recompilation demonstrably skipped), and the
    job completes bit-identically with nothing dropped or dead-lettered.
    """

    def test_warm_restarted_worker_rejoins_without_dropping_jobs(
            self, tmp_config, tmp_path, monkeypatch):
        import jax

        from comfyui_distributed_tpu.cluster.shape_catalog import (
            ProgramKey, ShapeCatalog)
        from comfyui_distributed_tpu.diffusion.warmup import WarmupManager
        from comfyui_distributed_tpu.models.registry import ModelRegistry
        from comfyui_distributed_tpu.parallel import build_mesh
        from comfyui_distributed_tpu.utils import compile_cache as cc

        # session-persistent cache dir shared with tests/test_warmup.py:
        # whichever test runs first on a fresh machine pays the one cold
        # compile; every later pass is the cache-load path under test
        warm_cache = cc.cache_dir_default() + "_tests_warmup"
        saved_dir = jax.config.jax_compilation_cache_dir
        saved_min = jax.config.jax_persistent_cache_min_compile_time_secs
        saved_active = cc._active
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", warm_cache)
        monkeypatch.setenv("CDT_SHAPE_CATALOG",
                           str(tmp_path / "fleet_catalog.json"))
        try:
            catalog = ShapeCatalog(tmp_path / "fleet_catalog.json")
            catalog.add(ProgramKey("txt2img", "tiny", 32, 32, 1))
            catalog.save()
            mesh = build_mesh({"dp": 1}, jax.devices()[:1])

            # generation 1 warms (cold on a fresh machine, hit after) and
            # persists the catalog+cache the restart will reuse
            gen1 = WarmupManager(ModelRegistry, lambda: mesh,
                                 catalog=catalog)
            status1 = gen1.run(models=["tiny"], seed_workflows=False)
            assert status1["state"] == "ready"

            # big enough that the job is still mid-flight when the
            # restarted worker finishes its (seconds-long) warmup pass
            # and rejoins — the master alone grinds at 0.15 s/tile
            ROLL_TOTAL = 150

            # fault-free reference output
            async def reference():
                store = JobStore()
                farm = TileFarm(store, asyncio.get_running_loop())
                results = await farm.master_run_async(
                    "roll-ref", total=ROLL_TOTAL, process_fn=make_proc(),
                    chunk=CHUNK, heartbeat_interval=0.2)
                return assemble_tiles(results, ROLL_TOTAL, CHUNK)

            ref = asyncio.run(reference())

            async def rolling_restart():
                controller, client = _serve_master()
                async with client:
                    base = f"http://127.0.0.1:{client.port}"

                    # the warm-restarted replacement boots FIRST (rolling
                    # deploys bring the new generation up before draining
                    # the old one): same catalog, same compile cache ⇒
                    # warmup is pure cache hits — the "skips
                    # recompilation" acceptance, asserted
                    jax.clear_caches()   # a new process holds nothing
                    gen2 = WarmupManager(ModelRegistry, lambda: mesh,
                                         catalog=ShapeCatalog(
                                             tmp_path
                                             / "fleet_catalog.json"))
                    loop = asyncio.get_running_loop()
                    status2 = await loop.run_in_executor(
                        None, lambda: gen2.run(models=["tiny"],
                                               seed_workflows=False))
                    assert status2["state"] == "ready"
                    assert status2["outcomes"] == {"cache_hit": 1}, \
                        status2["outcomes"]

                    master_task = asyncio.create_task(
                        controller.tile_farm.master_run_async(
                            "roll", total=ROLL_TOTAL,
                            process_fn=make_proc(delay=0.15), chunk=CHUNK,
                            heartbeat_interval=0.2, worker_timeout=0.5))
                    await asyncio.sleep(0.05)

                    # the outgoing process: pulls work, then its network
                    # partitions while it HOLDS an assignment — the
                    # restart window of a rolling deploy
                    held = await _doomed_worker(client, base, "roll",
                                                "w-roll", seed=7)
                    assert held, "outgoing worker never got work"

                    # ...the (already-warm) replacement rejoins the SAME
                    # job under the same worker id, completing what the
                    # dead generation held
                    farm_w = TileFarm(JobStore(),
                                      asyncio.get_running_loop())
                    done = await farm_w.worker_run_async(
                        "roll", "w-roll", base, make_proc(),
                        max_batch=1)
                    results = await asyncio.wait_for(master_task,
                                                     timeout=90)
                    assert done > 0, "restarted worker did no work"

                    # nothing dropped, nothing dead-lettered
                    async with client.session.get(
                            f"{base}/distributed/job_status",
                            params={"job_id": "roll"}) as resp:
                        job = await resp.json()
                    assert job["finished"] is True
                    assert job["dead_letter"] == []
                    assert job["completed"] == ROLL_TOTAL
                    return results

            results = asyncio.run(rolling_restart())
            out = assemble_tiles(results, ROLL_TOTAL, CHUNK)
            np.testing.assert_array_equal(out, ref)
        finally:
            jax.config.update("jax_compilation_cache_dir", saved_dir)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", saved_min)
            cc._active = saved_active
