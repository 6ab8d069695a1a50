"""Disaggregated stage-split serving (ISSUE 15, docs/stages.md).

Layers under test, cheap to expensive:

- the latent wire format (checksummed npz handoffs — the
  ``diffusion/checkpoint.py`` contract applied to decode handoffs);
- :class:`~comfyui_distributed_tpu.cluster.stages.pool.StagePool`
  mechanics: FIFO and bucketed take, the decode coalescing window,
  resize, shutdown leftovers, cross-stage stealing;
- the FleetSignals split (satellite bugfix): a decode backlog must
  NEVER scale up denoise chips (fake-clock autoscaler regression);
- the per-pool rebalancer (each pool grows on its own depth);
- the stage routes (``GET /distributed/stages``, the remote-decode
  ``POST /distributed/stages/decode``) over the real HTTP app;
- the chaos acceptance: a decode-pool worker dies holding BATCHED
  latents mid-job under the lock-order detector — the latents
  re-dispatch to a surviving decoder, output bit-identical, zero
  dead-letters, no breaker opens.

The bit-identity equivalence matrix (staged vs fused) lives in
tests/test_stages_equivalence.py.
"""

import asyncio
import threading
import time

import numpy as np
import pytest

from comfyui_distributed_tpu.cluster.stages import (LatentHandoff,
                                                    LatentWireError,
                                                    StageManager,
                                                    StageWorkerDeath,
                                                    build_stages)
from comfyui_distributed_tpu.cluster.stages.latents import (
    decode_array_payload, encode_array_payload)
from comfyui_distributed_tpu.cluster.stages.pool import StagePool


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def txt2img_prompt(seed: int, steps: int = 2, text: str = "x",
                   wh: int = 16) -> dict:
    return {
        "1": {"class_type": "CheckpointLoader",
              "inputs": {"ckpt_name": "tiny"}},
        "2": {"class_type": "CLIPTextEncode",
              "inputs": {"text": text, "clip": ["1", 1]}},
        "3": {"class_type": "CLIPTextEncode",
              "inputs": {"text": "", "clip": ["1", 1]}},
        "4": {"class_type": "TPUTxt2Img", "inputs": {
            "model": ["1", 0], "positive": ["2", 0], "negative": ["3", 0],
            "seed": seed, "steps": steps, "cfg": 2.0,
            "width": wh, "height": wh}},
    }


# --------------------------------------------------------------------------
# latent wire format
# --------------------------------------------------------------------------


class TestLatentWire:
    def _handoff(self):
        lat = np.arange(2 * 4 * 4 * 4, dtype=np.float32) \
            .reshape(2, 4, 4, 4)
        return LatentHandoff(prompt_id="p1", latents=lat,
                             meta={"model": "tiny", "seed": 7})

    def test_payload_round_trip_bit_exact(self):
        h = self._handoff()
        back = LatentHandoff.from_payload(h.to_payload())
        assert back.prompt_id == "p1"
        assert back.meta["model"] == "tiny"
        assert np.array_equal(back.latents, h.latents)
        assert back.latents.dtype == h.latents.dtype
        assert back.bucket_key() == h.bucket_key()

    def test_checksum_mismatch_rejected(self):
        payload = self._handoff().to_payload()
        payload["sha256"] = "0" * 64
        with pytest.raises(LatentWireError, match="CHECKSUM MISMATCH"):
            LatentHandoff.from_payload(payload)

    def test_missing_sha_rejected(self):
        payload = self._handoff().to_payload()
        del payload["sha256"]
        with pytest.raises(LatentWireError, match="no sha256"):
            LatentHandoff.from_payload(payload)

    def test_version_skew_rejected(self):
        import comfyui_distributed_tpu.cluster.stages.latents as mod

        h = self._handoff()
        h.version = 99
        payload = h.to_payload()
        with pytest.raises(LatentWireError, match="version"):
            mod.LatentHandoff.from_payload(payload)

    def test_garbage_payloads_rejected(self):
        with pytest.raises(LatentWireError):
            LatentHandoff.from_payload({"data": "!!!", "sha256": "x"})
        with pytest.raises(LatentWireError):
            LatentHandoff.from_payload("not a dict")

    def test_array_payload_round_trip(self):
        arr = np.random.default_rng(3).random((2, 8, 8, 3)) \
            .astype(np.float32)
        back = decode_array_payload(encode_array_payload(arr))
        assert np.array_equal(back, arr)
        bad = encode_array_payload(arr)
        bad["sha256"] = "0" * 64
        with pytest.raises(LatentWireError):
            decode_array_payload(bad)


# --------------------------------------------------------------------------
# stage pool mechanics
# --------------------------------------------------------------------------


class _Item:
    def __init__(self, key="k"):
        self.key = key
        self.redispatch = 0

    def bucket_key(self):
        return self.key


class TestStagePool:
    def test_fifo_runs_items_in_order(self):
        got, ev = [], threading.Event()

        def runner(items):
            got.extend(items)
            if len(got) == 3:
                ev.set()

        pool = StagePool("encode", 1, runner)
        for i in range(3):
            pool.put(i)
        assert ev.wait(5.0)
        assert got == [0, 1, 2]
        assert pool.stats()["done"] == 3
        pool.stop()

    def test_bucketed_take_coalesces_same_bucket(self):
        batches, ev = [], threading.Event()

        def runner(items):
            batches.append(list(items))
            if sum(len(b) for b in batches) >= 4:
                ev.set()

        pool = StagePool("decode", 1, runner,
                         batch_key=lambda it: it.bucket_key(),
                         max_batch=8, window_s=0.15)
        for it in [_Item("a"), _Item("a"), _Item("a"), _Item("b")]:
            pool.put(it)
        assert ev.wait(5.0)
        sizes = sorted(len(b) for b in batches)
        assert sizes == [1, 3], batches     # a-bucket coalesced, b solo
        pool.stop()

    def test_full_bucket_flushes_before_window(self):
        batches, ev = [], threading.Event()

        def runner(items):
            batches.append(len(items))
            ev.set()

        pool = StagePool("decode", 1, runner,
                         batch_key=lambda it: it.bucket_key(),
                         max_batch=2, window_s=30.0)   # window never hits
        pool.put(_Item("a"))
        pool.put(_Item("a"))
        assert ev.wait(5.0)
        assert batches == [2]
        pool.stop()

    def test_items_put_together_are_taken_together(self):
        """One ``put`` of several items is atomic: even with no window at
        all the worker cannot take the first without the second (two
        puts could be split by thread scheduling, which is what made a
        denoised group of 2 decode as 1 + 1 under load)."""
        batches, ev = [], threading.Event()

        def runner(items):
            batches.append([it.key for it in items])
            if sum(len(b) for b in batches) >= 3:
                ev.set()

        pool = StagePool("decode", 1, runner,
                         batch_key=lambda it: it.bucket_key(),
                         max_batch=8, window_s=0.0)
        pool.put(_Item("a"), _Item("a"), _Item("b"))
        assert ev.wait(5.0)
        assert sorted(batches) == [["a", "a"], ["b"]]
        pool.stop()

    def test_stop_returns_leftover_items(self):
        started = threading.Event()

        def runner(items):
            started.set()
            time.sleep(0.3)

        pool = StagePool("decode", 1, runner,
                         batch_key=lambda it: it.bucket_key(),
                         max_batch=1, window_s=0.0)
        pool.put(_Item("a"))
        assert started.wait(5.0)
        pool.put(_Item("b"))          # still queued when stop() lands
        leftovers = pool.stop()
        assert [it.key for it in leftovers] == ["b"]

    def test_resize_grows_and_shrinks_target(self):
        pool = StagePool("encode", 1, lambda items: None)
        pool.resize(3)
        assert pool.workers == 3
        pool.resize(1)
        assert pool.workers == 1
        pool.stop()

    def test_steal_serves_the_deeper_sibling(self):
        done, ev = [], threading.Event()

        def victim_runner(items):
            done.extend(items)
            if len(done) == 2:
                ev.set()

        victim = StagePool("decode", 0, victim_runner)   # NO workers
        thief = StagePool("encode", 1, lambda items: None,
                          steal=lambda pool: victim
                          if victim.depth() else None)
        victim.put("x")
        victim.put("y")
        thief.put("wake")             # give the thief a reason to spin
        assert ev.wait(5.0), "thief never served the victim's queue"
        assert sorted(done) == ["x", "y"]
        thief.stop()
        victim.stop()

    def test_worker_death_redispatches_items(self):
        """A runner raising StageWorkerDeath kills its thread; the held
        items re-enter through the redispatch hook and a respawned
        worker completes them."""
        attempts, done, ev = [], [], threading.Event()
        pool = {}

        def runner(items):
            attempts.append(list(items))
            if len(attempts) == 1:
                raise StageWorkerDeath("chaos")
            done.extend(items)
            ev.set()

        p = StagePool("decode", 1, runner,
                      batch_key=lambda it: it.bucket_key(),
                      max_batch=4, window_s=0.05,
                      redispatch=lambda items: [pool["p"].put(it)
                                                for it in items])
        pool["p"] = p
        p.put(_Item("a"))
        p.put(_Item("a"))
        assert ev.wait(5.0)
        assert len(attempts) == 2
        assert len(done) == 2
        p.stop()


# --------------------------------------------------------------------------
# FleetSignals split (satellite bugfix): decode backlog never scales
# denoise chips
# --------------------------------------------------------------------------


class TestSignalsSplit:
    def test_decode_backlog_never_scales_up_fleet(self, tmp_config):
        """Regression (fake clock): a huge decode-pool backlog with an
        empty denoise-facing queue must read as ZERO chip pressure —
        the autoscaler holds through every tick. Pre-split, the stage
        backlog was folded into one queue signal and would have
        scaled up denoise chips that then sat idle."""
        from comfyui_distributed_tpu.cluster.elastic.autoscaler import (
            AutoscalePolicy, Autoscaler, FleetSignals)

        ups = []

        class Provider:
            def list_workers(self):
                return {"w0": {"state": "active", "running": True}}

            def scale_up(self):
                ups.append(1)
                return "w1"

            def scale_down(self, wid):
                raise AssertionError("no scale-down expected")

        clock = {"t": 0.0}
        sig = FleetSignals(queue_depth=0, tile_depth=0, active_workers=1,
                           decode_depth=500, encode_depth=100)
        assert sig.work == 0
        assert sig.effective_work == 0
        scaler = Autoscaler(lambda: sig, Provider(),
                            AutoscalePolicy(min_workers=1, max_workers=4,
                                            scale_up_depth=2.0,
                                            up_streak=2,
                                            up_cooldown_s=0.0),
                            clock=lambda: clock["t"])
        for _ in range(10):
            clock["t"] += 5.0
            d = scaler.evaluate()
            assert d.direction != "up", d
        assert ups == []

    def test_denoise_queue_still_scales_up(self, tmp_config):
        """Control: the same harness with genuine denoise-facing depth
        does scale up — the split removed the false signal, not the
        true one."""
        from comfyui_distributed_tpu.cluster.elastic.autoscaler import (
            AutoscalePolicy, Autoscaler, FleetSignals)

        class Provider:
            def list_workers(self):
                return {"w0": {"state": "active", "running": True}}

            def scale_up(self):
                return "w1"

            def scale_down(self, wid):
                raise AssertionError("unexpected")

        clock = {"t": 0.0}
        sig = FleetSignals(queue_depth=20, tile_depth=0, active_workers=1,
                           decode_depth=500)
        scaler = Autoscaler(lambda: sig, Provider(),
                            AutoscalePolicy(max_workers=4,
                                            scale_up_depth=2.0,
                                            up_streak=2,
                                            up_cooldown_s=0.0),
                            clock=lambda: clock["t"])
        directions = []
        for _ in range(3):
            clock["t"] += 5.0
            directions.append(scaler.evaluate().direction)
        assert "up" in directions

    def test_frontdoor_depth_split(self, tmp_config):
        """fd.depth() (admission) includes the stage backlog;
        fd.denoise_depth() (the fleet signal) does not."""
        from comfyui_distributed_tpu.cluster.frontdoor import FrontDoor
        from comfyui_distributed_tpu.cluster.runtime import PromptQueue

        async def body():
            q = PromptQueue()

            class FakeStages:
                def depth(self):
                    return 7

                def depths(self):
                    return {"encode": 3, "denoise": 0, "decode": 4}

            fd = FrontDoor(q, orchestrator=None, stages=FakeStages())
            assert fd.depth() == fd.denoise_depth() + 7
            assert fd.stats()["stages"] == {"encode": 3, "denoise": 0,
                                            "decode": 4}
            await q.stop()
        run(body())


# --------------------------------------------------------------------------
# per-pool rebalance
# --------------------------------------------------------------------------


class TestRebalance:
    def test_pools_grow_on_their_own_depth_only(self, tmp_config,
                                                monkeypatch):
        monkeypatch.setenv("CDT_STAGE_SCALE_DEPTH", "2")
        monkeypatch.setenv("CDT_STAGE_MAX_WORKERS", "4")
        monkeypatch.setenv("CDT_STAGE_ENCODE_WORKERS", "1")
        monkeypatch.setenv("CDT_STAGE_DECODE_WORKERS", "1")
        mgr = StageManager()
        # swap no-op runners in and park both pools so queued items sit
        # still while rebalance() reads the depths
        mgr.decode.runner = lambda items: None
        mgr.encode.runner = lambda items: None
        try:
            mgr.decode.resize(0)
            mgr.encode.resize(0)
            for i in range(5):
                mgr.decode.put(_Item("a"))
            mgr.rebalance()
            # decode grew on ITS depth; encode (empty queue) stayed put
            assert mgr.decode.workers == 1
            assert mgr.encode.workers == 0
        finally:
            mgr.stop()

    def test_rebalance_respects_ceiling_and_shrinks_to_base(
            self, tmp_config, monkeypatch):
        monkeypatch.setenv("CDT_STAGE_SCALE_DEPTH", "1")
        monkeypatch.setenv("CDT_STAGE_MAX_WORKERS", "3")
        monkeypatch.setenv("CDT_STAGE_DECODE_WORKERS", "2")
        mgr = StageManager()
        mgr.decode.runner = lambda items: time.sleep(0.2)   # stay busy
        try:
            for i in range(40):
                mgr.decode.put(_Item(f"k{i}"))   # distinct buckets
            grown = []
            for _ in range(6):
                mgr.rebalance()
                grown.append(mgr.decode.workers)
                time.sleep(0.02)
            assert max(grown) == 3              # ceiling holds exactly
            # drained and idle: shrink back to the configured base
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline:
                mgr.rebalance()
                if mgr.decode.workers == 2 and mgr.decode.depth() == 0:
                    break
                time.sleep(0.05)
            assert mgr.decode.workers == 2
        finally:
            mgr.stop()


# --------------------------------------------------------------------------
# staged serving with REAL tiny models (manager + queue + routes)
# --------------------------------------------------------------------------


@pytest.fixture
def exec_context(tmp_config):
    from comfyui_distributed_tpu.cluster.cache import build_cache_manager
    from comfyui_distributed_tpu.models.registry import ModelRegistry
    from comfyui_distributed_tpu.parallel.mesh import build_mesh

    registry = ModelRegistry(None)
    mesh = build_mesh({"dp": 2})
    cache = build_cache_manager()
    return lambda: {"mesh": mesh, "model_registry": registry,
                    "content_cache": cache}


async def _wait_terminal(q, pid, timeout=300.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        e = q.history.get(pid)
        if e is not None and e.get("status") in ("success", "error",
                                                 "interrupted", "expired"):
            return e
        await asyncio.sleep(0.01)
    raise AssertionError(f"{pid} never terminal: {q.history.get(pid)}")


def _member(pid, seed, steps=2, text="x"):
    from comfyui_distributed_tpu.cluster.runtime import PromptJob

    return PromptJob(pid, txt2img_prompt(seed, steps, text),
                     priority="interactive")


class TestStagedServing:
    def test_group_runs_through_stages_and_frees_slot_at_denoise(
            self, tmp_config, exec_context, monkeypatch):
        """A batch group through the real pools: every member succeeds,
        the sampler batch is 2, the decode batch is 2, and the QUEUE
        SLOT frees at denoise-done (queue_remaining drops to 0 while
        decode may still be in flight — the pipelining the stage split
        exists for)."""
        from comfyui_distributed_tpu.cluster.runtime import PromptQueue

        monkeypatch.setenv("CDT_STAGE_DECODE_WINDOW_MS", "100")

        async def body():
            q = PromptQueue(context_factory=exec_context)
            q.stages = StageManager()
            try:
                members = [_member("s1", 41, text="a"),
                           _member("s2", 42, text="b")]
                q.enqueue_batch(members, {m.prompt_id: "4"
                                          for m in members})
                for m in members:
                    e = await _wait_terminal(q, m.prompt_id)
                    assert e["status"] == "success", e
                    assert e["batch_size"] == 2
                    assert e["decode_batch"] == 2
                    assert e["outputs"]
                assert q.queue_remaining == 0
                stats = q.stages.stats()
                assert stats["pools"]["denoise"]["done"] == 1
                assert stats["pools"]["decode"]["done"] == 2
                assert stats["pools"]["encode"]["done"] == 2
            finally:
                q.stages.stop()
                await q.stop()
        run(body())

    def test_encode_stage_serves_result_cache_without_mesh(
            self, tmp_config, exec_context):
        """A byte-identical re-submission answers from the completed-
        result tier IN THE ENCODE STAGE — the denoise pool never sees
        it (its done-count stays flat)."""
        from comfyui_distributed_tpu.cluster.frontdoor.classifier import \
            fingerprint
        from comfyui_distributed_tpu.cluster.runtime import PromptQueue

        async def body():
            q = PromptQueue(context_factory=exec_context)
            q.stages = StageManager()
            try:
                prompt = txt2img_prompt(77, 2, "cacheable")
                m1 = _member("c1", 77, text="cacheable")
                m1.fingerprint = fingerprint(prompt)
                q.enqueue_batch([m1], {"c1": "4"})
                first = await _wait_terminal(q, "c1")
                assert first["status"] == "success"
                denoise_done = q.stages.stats()["pools"]["denoise"]["done"]

                m2 = _member("c2", 77, text="cacheable")
                m2.fingerprint = fingerprint(prompt)
                q.enqueue_batch([m2], {"c2": "4"})
                second = await _wait_terminal(q, "c2")
                assert second["status"] == "success"
                assert second.get("cache") == "hit"
                stats = q.stages.stats()
                assert stats["cache_hits"] == 1
                assert stats["pools"]["denoise"]["done"] == denoise_done
                img1 = np.asarray(first["outputs"]["4"][0])
                img2 = np.asarray(second["outputs"]["4"][0])
                assert np.array_equal(img1, img2)
            finally:
                q.stages.stop()
                await q.stop()
        run(body())

    def test_kill_switch_restores_fused_path(self, tmp_config,
                                             monkeypatch):
        monkeypatch.setenv("CDT_STAGES", "0")
        assert build_stages() is None


class TestStageFailureIsolation:
    """Regressions: a failure anywhere in a stage worker must reach a
    terminal per-member history entry AND advance the group's stage
    barriers — the pool's runner barrier swallows escapes, so an
    unisolated exception would wedge the queue consumer forever on
    ``denoise_done``."""

    def test_cache_probe_failure_does_not_wedge_group(
            self, tmp_config, exec_context, monkeypatch):
        """An exception out of the encode stage's cached-suffix /
        cache-probe half (AFTER _prepare succeeded) errors that member
        terminally and the group still resolves; the consumer survives
        to serve the next group."""
        import comfyui_distributed_tpu.cluster.frontdoor.microbatch as mb
        from comfyui_distributed_tpu.cluster.runtime import PromptQueue

        booms = {"n": 0}
        orig = mb._serve_cached

        def boom(p, cache, results):
            if booms["n"] == 0:
                booms["n"] += 1
                raise RuntimeError("cache tier exploded mid-probe")
            return orig(p, cache, results)

        monkeypatch.setattr(mb, "_serve_cached", boom)

        async def body():
            q = PromptQueue(context_factory=exec_context)
            q.stages = StageManager()
            try:
                q.enqueue_batch([_member("i1", 81)], {"i1": "4"})
                e = await _wait_terminal(q, "i1")
                assert e["status"] == "error"
                assert "exploded" in e["error"]
                # the consumer is alive: a follow-up group completes
                q.enqueue_batch([_member("i2", 82)], {"i2": "4"})
                e2 = await _wait_terminal(q, "i2")
                assert e2["status"] == "success", e2
                assert q.queue_remaining == 0
            finally:
                q.stages.stop()
                await q.stop()
        run(body())

    def test_encode_redispatch_bound_fails_member_and_resolves_group(
            self, tmp_config, monkeypatch):
        """An encode item past the redispatch bound errors its member
        AND advances the encode barrier: denoise_done resolves instead
        of wedging the consumer (the _EncodeWork.fail bookkeeping)."""
        monkeypatch.setenv("CDT_STAGE_MAX_REDISPATCH", "0")
        mgr = StageManager()
        mgr.encode.resize(0)          # park the pool: drive redispatch

        class M:
            prompt_id = "r0"
            fingerprint = None

        async def body():
            loop = asyncio.get_running_loop()
            denoise_done = loop.create_future()
            entries = {}

            def record(member, entry, last):
                entries[member.prompt_id] = (entry, last)

            mgr.submit_group(None, [M()], {"r0": "4"}, {}, loop,
                             denoise_done, record)
            batch = mgr.encode.take_now()
            assert batch, "encode item never queued"
            mgr._redispatch_encode(batch)
            await asyncio.wait_for(denoise_done, timeout=5.0)
            # let the marshaled record callback land
            await asyncio.sleep(0)
            entry, last = entries["r0"]
            assert entry["status"] == "error"
            assert "redispatch bound" in entry["error"]
            assert last is True
        try:
            run(body())
        finally:
            mgr.stop()

    def test_wire_transfer_failure_errors_member_not_batch(
            self, tmp_config, exec_context, monkeypatch):
        """Under CDT_STAGE_WIRE=1 a wire-format failure on ONE handoff
        errors that member terminally; its batch-mates still decode to
        success (per-member transfer isolation in the decode stage)."""
        from comfyui_distributed_tpu.cluster.runtime import PromptQueue

        monkeypatch.setenv("CDT_STAGE_WIRE", "1")
        monkeypatch.setenv("CDT_STAGE_DECODE_WINDOW_MS", "200")
        orig = LatentHandoff.from_payload.__func__

        def poisoned(cls, obj):
            if isinstance(obj, dict) and obj.get("prompt_id") == "w1":
                raise LatentWireError("chaos: flipped bit on the wire")
            return orig(cls, obj)

        monkeypatch.setattr(LatentHandoff, "from_payload",
                            classmethod(poisoned))

        async def body():
            q = PromptQueue(context_factory=exec_context)
            q.stages = StageManager()
            try:
                members = [_member("w0", 91, text="wa"),
                           _member("w1", 92, text="wb")]
                q.enqueue_batch(members, {m.prompt_id: "4"
                                          for m in members})
                ok = await _wait_terminal(q, "w0")
                bad = await _wait_terminal(q, "w1")
                assert ok["status"] == "success", ok
                assert bad["status"] == "error"
                assert "flipped bit" in bad["error"]
                assert q.queue_remaining == 0
            finally:
                q.stages.stop()
                await q.stop()
        run(body())


class TestStageRoutes:
    def test_stats_route_and_remote_decode_bit_identical(self,
                                                         tmp_config):
        """GET /distributed/stages answers pool stats; POST
        /distributed/stages/decode decodes a wire-form handoff on the
        receiving worker BIT-identically to a local decode — the
        cross-worker decode-pool transport."""
        from aiohttp.test_utils import TestClient, TestServer

        from comfyui_distributed_tpu.api import create_app
        from comfyui_distributed_tpu.cluster.controller import Controller
        from comfyui_distributed_tpu.diffusion.pipeline import \
            GenerationSpec

        async def body():
            controller = Controller()
            client = TestClient(TestServer(create_app(controller)))
            await client.start_server()
            try:
                resp = await client.get("/distributed/stages")
                stats = await resp.json()
                assert stats["enabled"] is True
                assert set(stats["pools"]) == {"encode", "denoise",
                                               "decode"}

                bundle = controller.model_registry.get("tiny")
                mesh = controller.mesh
                spec = GenerationSpec(height=16, width=16, steps=2,
                                      guidance_scale=2.0)
                enc = bundle.text_encoder
                ctx, _ = enc.encode(["remote decode"])
                unc, _ = enc.encode([""])
                lats = bundle.pipeline.generate_latents(
                    mesh, spec, [5], [ctx], [unc])
                lat = np.asarray(lats[0])
                local = np.asarray(bundle.pipeline.decode_latents(
                    mesh, [lat])[0])
                handoff = LatentHandoff(prompt_id="r1", latents=lat,
                                        meta={"model": "tiny"})
                resp = await client.post("/distributed/stages/decode",
                                         json=handoff.to_payload())
                assert resp.status == 200, await resp.text()
                body_json = await resp.json()
                remote = decode_array_payload(body_json["images"])
                assert np.array_equal(remote, local)

                # corrupted payload is refused loudly, never decoded
                bad = handoff.to_payload()
                bad["sha256"] = "0" * 64
                resp = await client.post("/distributed/stages/decode",
                                         json=bad)
                assert resp.status == 400
            finally:
                await client.close()
                await controller.shutdown()
        run(body())


class TestLoadSmokeStagesGuard:
    def test_http_leg_fails_against_stages_disabled_server(
            self, monkeypatch):
        """Regression: the HTTP --stages leg must exit 1 when the
        server answers ``{"enabled": false}`` (CDT_STAGES=0) — a truthy
        stats dict used to pass the presence check vacuously without
        ever exercising the pools."""
        import importlib.util
        import sys as _sys
        from pathlib import Path

        spec = importlib.util.spec_from_file_location(
            "load_smoke_guard_test",
            Path(__file__).resolve().parent.parent / "scripts"
            / "load_smoke.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)

        canned = {"admitted": 2, "queued": 0, "completed": 2,
                  "errors": 0, "expired": 0,
                  "stages": {"enabled": False, "max_depths": {}}}

        async def fake_http(*a, **k):
            return dict(canned)

        monkeypatch.setattr(mod, "_run_http", fake_http)
        monkeypatch.setattr(_sys, "argv",
                            ["load_smoke.py", "--url", "http://x",
                             "--stages", "--n", "2"])
        assert mod.main() == 1

        # control: an enabled server with bounded backlogs passes
        canned["stages"] = {"enabled": True, "max_depths": {"decode": 1}}
        assert mod.main() == 0


# --------------------------------------------------------------------------
# chaos stage 8: decode-pool worker death holding batched latents
# --------------------------------------------------------------------------


class TestChaosDecodeWorkerDeath:
    @pytest.mark.chaos
    def test_decode_worker_death_redispatches_bit_identical(
            self, tmp_config, exec_context, monkeypatch):
        """Kill a decode-pool worker while it holds a BATCHED decode
        (3 latents, post-transfer) under the runtime lock-order
        detector. The latents re-dispatch to a surviving decoder, every
        member completes with output BIT-identical to the fused path,
        zero members dead-letter/error, no breaker opens, zero lock
        inversions."""
        from comfyui_distributed_tpu.cluster.resilience import BREAKERS
        from comfyui_distributed_tpu.cluster.runtime import PromptQueue
        from comfyui_distributed_tpu.lint import lockorder

        monkeypatch.setenv("CDT_STAGE_DECODE_WINDOW_MS", "200")
        monkeypatch.setenv("CDT_STAGE_DECODE_WORKERS", "2")
        lockorder.reset()
        lockorder.force_enabled(True)
        try:
            async def body():
                # fused reference first (stages off: bare queue)
                ref_q = PromptQueue(context_factory=exec_context)
                refs = {}
                for i, seed in enumerate((61, 62, 63)):
                    pid, _ = ref_q.enqueue(
                        txt2img_prompt(seed, 2, f"chaos{i}"))
                    e = await _wait_terminal(ref_q, pid)
                    assert e["status"] == "success", e
                    refs[seed] = np.asarray(e["outputs"]["4"][0])
                await ref_q.stop()

                q = PromptQueue(context_factory=exec_context)
                q.stages = StageManager()
                deaths = {"n": 0}

                def death_hook(items):
                    # fire exactly once, on the first batched pickup
                    if deaths["n"] == 0 and len(items) > 1:
                        deaths["n"] += 1
                        raise StageWorkerDeath("chaos: decode worker "
                                               "killed holding latents")

                q.stages._death_hook = death_hook
                try:
                    members = [_member(f"d{i}", seed, text=f"chaos{i}")
                               for i, seed in enumerate((61, 62, 63))]
                    q.enqueue_batch(members, {m.prompt_id: "4"
                                              for m in members})
                    for i, seed in enumerate((61, 62, 63)):
                        e = await _wait_terminal(q, f"d{i}")
                        assert e["status"] == "success", e
                        got = np.asarray(e["outputs"]["4"][0])
                        assert np.array_equal(got, refs[seed]), \
                            f"d{i} diverged after redispatch"
                    assert deaths["n"] == 1, "death hook never fired"
                    stats = q.stages.stats()
                    assert stats["redispatched"] >= 1
                    # zero dead-letters: no member errored
                    assert all(q.history[f"d{i}"]["status"] == "success"
                               for i in range(3))
                finally:
                    q.stages.stop()
                    await q.stop()

            run(body())
            # no breaker opened: worker death in a stage pool is
            # redispatch, never failure evidence
            for wid, b in getattr(BREAKERS, "_breakers", {}).items():
                assert getattr(b, "state", "closed") == "closed", wid
            lockorder.assert_clean()
        finally:
            lockorder.force_enabled(None)
            lockorder.reset()
