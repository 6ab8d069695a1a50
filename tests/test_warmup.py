"""AOT warmup pass (diffusion/warmup.py): CPU lower+compile without
execution, cache-hit vs compiled classification against the persistent
XLA cache, the warming→ready health state machine, the
/distributed/warmup route, and the dispatcher's hot-worker preference.

The acceptance claim under test: a warm restart (populated compile
cache + catalog) demonstrably skips recompilation — pass 2 after
``jax.clear_caches()`` classifies every program ``cache_hit``.
"""

import asyncio

import jax
import pytest

from comfyui_distributed_tpu.cluster.shape_catalog import (ProgramKey,
                                                           ShapeCatalog)
from comfyui_distributed_tpu.diffusion import warmup as wu
from comfyui_distributed_tpu.diffusion.warmup import (WarmupManager,
                                                      run_warmup)
from comfyui_distributed_tpu.models.registry import ModelRegistry
from comfyui_distributed_tpu.parallel import build_mesh
from comfyui_distributed_tpu.utils.compile_cache import cache_dir_default

# session-persistent (NOT per-test tmp): the cold compile happens once
# per machine; re-runs exercise the cache-hit path at disk-read cost —
# the same economics the subsystem exists to provide
_WARM_CACHE = cache_dir_default() + "_tests_warmup"


@pytest.fixture
def restore_cache_config():
    """enable_compile_cache mutates process-global jax config; the rest
    of the suite must keep conftest's cache dir + threshold."""
    from comfyui_distributed_tpu.utils import compile_cache as cc

    saved_dir = jax.config.jax_compilation_cache_dir
    saved_min = jax.config.jax_persistent_cache_min_compile_time_secs
    saved_active = cc._active
    yield
    jax.config.update("jax_compilation_cache_dir", saved_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      saved_min)
    cc._active = saved_active


def _tiny_catalog(tmp_path):
    cat = ShapeCatalog(tmp_path / "cat.json")
    cat.add(ProgramKey("txt2img", "tiny", 32, 32, 1))
    return cat


class TestAOTPass:
    def test_warm_restart_skips_recompilation(self, tmp_path, monkeypatch,
                                              restore_cache_config):
        from comfyui_distributed_tpu.utils.compile_cache import \
            enable_compile_cache

        assert enable_compile_cache(_WARM_CACHE, min_compile_secs=0.0)
        reg = ModelRegistry()
        mesh = build_mesh({"dp": 1}, jax.devices()[:1])
        keys = _tiny_catalog(tmp_path).entries()

        (first,) = run_warmup(reg, mesh, keys, models=["tiny"])
        # first run on a fresh machine compiles; re-runs hit the
        # session-persistent cache — both prove the program lowered
        assert first.outcome in ("compiled", "cache_hit")

        # the warm-restart claim: dropping every in-memory executable
        # (what a process restart does) and re-AOT-compiling must be
        # served from disk, not the compiler
        jax.clear_caches()
        (second,) = run_warmup(reg, mesh, keys, models=["tiny"])
        assert second.outcome == "cache_hit"
        assert second.seconds > 0

    def test_model_filter_skips(self, tmp_path, restore_cache_config):
        cat = ShapeCatalog(tmp_path / "cat.json")
        cat.add(ProgramKey("txt2img", "sdxl", 1024, 1024, 30))
        reg = ModelRegistry()
        mesh = build_mesh({"dp": 1}, jax.devices()[:1])
        (entry,) = run_warmup(reg, mesh, cat.entries(), models=["tiny"])
        assert entry.outcome == "skipped"
        # the filtered model was never built (an SDXL random-init on a
        # CPU test host would be the bug this filter prevents)
        assert "sdxl" not in reg._cache

    def test_env_filter(self, tmp_path, monkeypatch, restore_cache_config):
        monkeypatch.setenv("CDT_WARMUP_MODELS", "nothing-matches")
        cat = _tiny_catalog(tmp_path)
        reg = ModelRegistry()
        mesh = build_mesh({"dp": 1}, jax.devices()[:1])
        (entry,) = run_warmup(reg, mesh, cat.entries())
        assert entry.outcome == "skipped"

    def test_no_filter_defaults_to_safe_models(self, tmp_path,
                                               monkeypatch,
                                               restore_cache_config):
        """Unqualified CDT_WARMUP=1 must never random-initialize the
        big workflow-catalog models — only tiny/already-loaded presets
        warm without an explicit filter."""
        monkeypatch.delenv("CDT_WARMUP_MODELS", raising=False)
        monkeypatch.setattr(wu, "lower_program",
                            lambda bundle, key, mesh: None)
        cat = ShapeCatalog(tmp_path / "cat.json")
        cat.add(ProgramKey("txt2img", "sdxl", 1024, 1024, 30))
        cat.add(ProgramKey("txt2img", "tiny", 32, 32, 1))
        reg = ModelRegistry()
        mesh = build_mesh({"dp": 1}, jax.devices()[:1])
        by_model = {e.key.model: e
                    for e in run_warmup(reg, mesh, cat.entries())}
        assert by_model["sdxl"].outcome == "skipped"
        assert by_model["tiny"].outcome in ("compiled", "cache_hit")
        assert "sdxl" not in reg._cache

    def test_all_sentinel_unfilters(self, tmp_path, monkeypatch,
                                    restore_cache_config):
        monkeypatch.setattr(wu, "lower_program",
                            lambda bundle, key, mesh: None)
        built = []
        cat = ShapeCatalog(tmp_path / "cat.json")
        cat.add(ProgramKey("txt2img", "tiny", 32, 32, 1))
        reg = ModelRegistry()
        orig = reg.get
        monkeypatch.setattr(
            reg, "get", lambda n: (built.append(n), orig(n))[1])
        mesh = build_mesh({"dp": 1}, jax.devices()[:1])
        (entry,) = run_warmup(reg, mesh, cat.entries(), models=["all"])
        assert entry.outcome in ("compiled", "cache_hit")
        assert built == ["tiny"]

    def test_mesh_mismatch_skips(self, tmp_path, restore_cache_config):
        cat = ShapeCatalog(tmp_path / "cat.json")
        cat.add(ProgramKey("txt2img", "tiny", 32, 32, 1,
                           mesh=(("dp", 4),)))
        reg = ModelRegistry()
        mesh = build_mesh({"dp": 1}, jax.devices()[:1])
        (entry,) = run_warmup(reg, mesh, cat.entries(), models=["tiny"])
        assert entry.outcome == "skipped"

    def test_per_entry_error_isolation(self, tmp_path,
                                       restore_cache_config):
        """One bad row must not leave the rest of the catalog cold."""
        keys = [ProgramKey("txt2img", "no-such-model", 32, 32, 1),
                ProgramKey("txt2img", "tiny", 32, 32, 1)]
        reg = ModelRegistry()
        mesh = build_mesh({"dp": 1}, jax.devices()[:1])
        from comfyui_distributed_tpu.utils.compile_cache import \
            enable_compile_cache

        enable_compile_cache(_WARM_CACHE, min_compile_secs=0.0)
        bad, good = run_warmup(reg, mesh, keys,
                               models=["tiny", "no-such-model"])
        assert bad.outcome == "error" and "unknown model" in bad.detail
        assert good.outcome in ("compiled", "cache_hit")


class TestWarmupManager:
    def test_warming_to_ready_transition(self, tmp_path, monkeypatch,
                                         restore_cache_config):
        mgr = WarmupManager(lambda: ModelRegistry(),
                            lambda: build_mesh({"dp": 1},
                                               jax.devices()[:1]),
                            catalog=ShapeCatalog(tmp_path / "cat.json"))
        assert mgr.state == "cold"
        seen = {}

        def fake_pass(registry, mesh, keys, models=None, on_entry=None,
                      **kw):
            seen["state_during_pass"] = mgr.state
            return []

        monkeypatch.setattr(wu, "run_warmup", fake_pass)
        status = mgr.run(seed_workflows=False)
        assert seen["state_during_pass"] == "warming"
        assert mgr.state == "ready" and status["state"] == "ready"
        assert status["seconds"] >= 0

    def test_failed_pass_reports_error(self, tmp_path,
                                       restore_cache_config):
        def broken_registry():
            raise RuntimeError("no backend")

        mgr = WarmupManager(broken_registry, lambda: None,
                            catalog=ShapeCatalog(tmp_path / "cat.json"))
        status = mgr.run(seed_workflows=False)
        assert mgr.state == "error" and status["state"] == "error"

    def test_concurrent_run_coalesces(self, tmp_path, monkeypatch,
                                      restore_cache_config):
        mgr = WarmupManager(lambda: ModelRegistry(), lambda: None,
                            catalog=ShapeCatalog(tmp_path / "cat.json"))
        mgr._lock.acquire()          # simulate a pass in flight
        try:
            mgr._set_state("warming")
            status = mgr.run(seed_workflows=False)
            assert status["state"] == "warming"   # did not start a second
        finally:
            mgr._lock.release()

    def test_run_warms_real_catalog_program(self, tmp_path, monkeypatch,
                                            restore_cache_config):
        """End-to-end manager pass over a real tiny program, asserting
        telemetry counters move."""
        from comfyui_distributed_tpu.telemetry import REGISTRY

        REGISTRY.reset()
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", _WARM_CACHE)
        mgr = WarmupManager(lambda: ModelRegistry(),
                            lambda: build_mesh({"dp": 1},
                                               jax.devices()[:1]),
                            catalog=_tiny_catalog(tmp_path))
        status = mgr.run(models=["tiny"], seed_workflows=False)
        assert status["state"] == "ready"
        assert set(status["outcomes"]) <= {"compiled", "cache_hit"}
        snap = REGISTRY.snapshot()["cdt_warmup_programs_total"]
        assert sum(s["value"] for s in snap["series"]) == 1
        # catalog persisted next to the cache
        assert (tmp_path / "cat.json").exists()


    def test_status_is_state_outcomes_report(self, tmp_path, monkeypatch,
                                             restore_cache_config):
        """Warm-up is one pass over its programs: it reaches ``ready`` on
        them alone, and its status names no tuning stage."""
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", _WARM_CACHE)
        mgr = WarmupManager(lambda: ModelRegistry(),
                            lambda: build_mesh({"dp": 1},
                                               jax.devices()[:1]),
                            catalog=_tiny_catalog(tmp_path))
        status = mgr.run(models=["tiny"], seed_workflows=False)
        assert status["state"] == "ready"
        assert set(status) == {"state", "catalog_size", "outcomes",
                               "seconds", "report"}
        assert set(status["report"][0]) == {"program", "outcome", "seconds",
                                            "detail"}


class TestHealthAndRoute:
    def test_health_reports_warmup_state(self, tmp_config):
        from comfyui_distributed_tpu.cluster.controller import Controller

        c = Controller()
        assert c.health()["warmup"] == "cold"
        c.warmup._set_state("ready")
        assert c.health()["warmup"] == "ready"

    def test_warmup_route(self, tmp_config, tmp_path, monkeypatch,
                          restore_cache_config):
        from aiohttp.test_utils import TestClient, TestServer

        from comfyui_distributed_tpu.api.app import create_app
        from comfyui_distributed_tpu.cluster.controller import Controller

        monkeypatch.setenv("CDT_SHAPE_CATALOG",
                           str(tmp_path / "cat.json"))

        async def body():
            controller = Controller()
            client = TestClient(TestServer(create_app(controller)))
            async with client:
                resp = await client.get("/distributed/warmup")
                assert (await resp.json())["state"] == "cold"

                # models=[] → whole catalog skipped: exercises the full
                # route/manager/pass plumbing without compiling
                resp = await client.post(
                    "/distributed/warmup",
                    json={"models": [], "wait": True})
                body = await resp.json()
                assert body["state"] == "ready"
                assert set(body["outcomes"]) <= {"skipped"}

                resp = await client.get("/distributed/warmup")
                assert (await resp.json())["state"] == "ready"

                # worker state surfaced through the health probe
                resp = await client.get("/distributed/health")
                assert (await resp.json())["warmup"] == "ready"

                resp = await client.post(
                    "/distributed/warmup", json={"models": "oops"})
                assert resp.status == 400
        asyncio.run(body())


class TestDispatcherPreference:
    def _host(self, hid, depth, warmup):
        return {"id": hid, "_probe": {"queue_remaining": depth,
                                      "warmup": warmup}}

    def test_ready_preferred_over_warming_when_idle(self):
        from comfyui_distributed_tpu.cluster.dispatch import \
            select_least_busy_host

        warming = self._host("w1", 0, "warming")
        ready = self._host("w2", 0, "ready")
        for _ in range(8):   # round-robin must stay inside the hot set
            assert select_least_busy_host([warming, ready])["id"] == "w2"

    def test_warming_only_fleet_still_serves(self):
        from comfyui_distributed_tpu.cluster.dispatch import \
            select_least_busy_host

        warming = self._host("w1", 0, "warming")
        assert select_least_busy_host([warming])["id"] == "w1"

    def test_busy_tier_also_prefers_hot(self):
        from comfyui_distributed_tpu.cluster.dispatch import \
            select_least_busy_host

        warming_short = self._host("w1", 1, "warming")
        ready_long = self._host("w2", 3, "ready")
        assert select_least_busy_host(
            [warming_short, ready_long])["id"] == "w2"

    def test_legacy_probe_without_field_counts_hot(self):
        from comfyui_distributed_tpu.cluster.dispatch import \
            select_least_busy_host

        legacy = {"id": "w0", "_probe": {"queue_remaining": 0}}
        ready = self._host("w2", 0, "ready")
        picks = {select_least_busy_host([legacy, ready])["id"]
                 for _ in range(8)}
        assert picks == {"w0", "w2"}   # both in the hot round-robin
