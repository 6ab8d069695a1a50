"""Collector bridge tests: envelope combine semantics + master drain loop
against in-process queues (the reference tests its collector the same way —
no cluster, AsyncMock HTTP; SURVEY §4)."""

import asyncio

import numpy as np
import pytest

from comfyui_distributed_tpu.cluster import CollectorBridge, JobStore
from comfyui_distributed_tpu.utils.audio_payload import encode_audio
from comfyui_distributed_tpu.utils.image import encode_image_b64


def run(coro):
    return asyncio.run(coro)


def img(value, hw=(4, 4)):
    return np.full((hw[0], hw[1], 3), value, np.float32)


def local_batch(how):
    """The master's own batch as a node may hand it to the collector."""
    if how == "none":
        return None
    x = np.stack([img(v) for v in (0.1, 0.35, 0.6, 0.85)])
    if how == "numpy":
        return x
    if how == "numpy_uint8":
        return (x * 255).astype(np.uint8)
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if how == "one_device":
        return jax.device_put(x, jax.devices()[0])
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))     # a fan-out's output
    return jax.device_put(x, NamedSharding(mesh, P("dp", None, None, None)))


def combine_as_before(local_images, per_worker, expected, delegate_only):
    """``_combine_images`` as it was before ISSUE 38: the reference for
    every collect a worker contributed to."""
    batches = []
    if local_images is not None and not delegate_only:
        local = np.asarray(local_images, dtype=np.float32)
        if local.size:
            batches.append(local)
    for w in expected:
        imgs = per_worker.get(w, {})
        for idx in sorted(imgs):
            batches.append(imgs[idx][None])
    if not batches:
        return local_images
    hw = batches[0].shape[1:3]
    return np.concatenate([b for b in batches if b.shape[1:3] == hw], axis=0)


class TestCombineImages:
    def test_master_first_then_worker_order(self):
        per_worker = {
            "w2": {0: img(0.8)},
            "w1": {1: img(0.4), 0: img(0.2)},
        }
        out = CollectorBridge._combine_images(
            img(0.1)[None], per_worker, expected=("w1", "w2"),
            delegate_only=False)
        assert out.shape == (4, 4, 4, 3)
        # master, w1[0], w1[1], w2[0] — enabled order + batch_idx order
        np.testing.assert_allclose(out[:, 0, 0, 0], [0.1, 0.2, 0.4, 0.8], atol=0.01)

    def test_delegate_only_master_excluded(self):
        out = CollectorBridge._combine_images(
            img(0.9)[None], {"w1": {0: img(0.3)}}, ("w1",), delegate_only=True)
        assert out.shape == (1, 4, 4, 3)
        np.testing.assert_allclose(out[0, 0, 0, 0], 0.3, atol=0.01)

    def test_mismatched_sizes_dropped(self):
        out = CollectorBridge._combine_images(
            img(0.1)[None], {"w1": {0: img(0.5, hw=(8, 8))}}, ("w1",), False)
        assert out.shape == (1, 4, 4, 3)

    def test_no_results_returns_local(self):
        local = img(0.5)[None]
        out = CollectorBridge._combine_images(local, {}, (), False)
        np.testing.assert_array_equal(out, local)

    @pytest.mark.parametrize("expected,per_worker", [
        ((), {}), (("w1", "w2"), {"w1": {}, "w2": {}}), (("w1",), {})])
    @pytest.mark.parametrize("how", ["numpy", "numpy_uint8", "one_device",
                                     "dp_rows", "none"])
    def test_nothing_to_concatenate_with_hands_the_batch_through(
            self, how, expected, per_worker):
        """ISSUE 38: with no worker's image the master's batch is returned
        as the OBJECT it came in — a device array stays on its chips,
        sharded as the program left it; nothing is copied or converted."""
        local = local_batch(how)
        out = CollectorBridge._combine_images(local, per_worker, expected,
                                              delegate_only=False)
        assert out is local
        if how == "dp_rows":
            assert len({s.device for s in out.addressable_shards}) == 4

    @pytest.mark.parametrize("how", ["numpy", "one_device", "dp_rows"])
    def test_a_delegated_master_with_no_results_keeps_its_placeholder(
            self, how):
        local = local_batch(how)
        assert CollectorBridge._combine_images(
            local, {"w1": {}}, ("w1",), delegate_only=True) is local

    @pytest.mark.parametrize("how", ["numpy", "numpy_uint8", "one_device",
                                     "dp_rows"])
    @pytest.mark.parametrize("case", ["worker", "delegate_only",
                                      "mismatched", "two_workers"])
    def test_with_a_workers_images_the_result_is_the_gathers(self, how, case):
        """A worker contributed: the path before ISSUE 38 (kept below as
        the reference), whatever the master's batch is made of."""
        local = local_batch(how)
        per_worker, expected, delegate_only = {
            "worker": ({"w1": {0: img(0.3)}}, ("w1",), False),
            "delegate_only": ({"w1": {0: img(0.3)}}, ("w1",), True),
            "mismatched": ({"w1": {0: img(0.5, hw=(8, 8)), 1: img(0.6)}},
                           ("w1",), False),
            "two_workers": ({"w2": {0: img(0.8)},
                             "w1": {1: img(0.4), 0: img(0.2)}, "w3": {}},
                            ("w1", "w2", "w3"), False),
        }[case]
        out = CollectorBridge._combine_images(local, per_worker, expected,
                                              delegate_only)
        want = combine_as_before(local, per_worker, expected, delegate_only)
        assert isinstance(out, np.ndarray) and out.dtype == want.dtype
        np.testing.assert_array_equal(out, want)

    def test_counts_which_path_ran(self):
        from comfyui_distributed_tpu import telemetry

        def counted() -> dict:
            series = telemetry.REGISTRY.snapshot().get(
                "cdt_collector_batches_total", {}).get("series", [])
            by_path = {s["labels"]["path"]: s["value"] for s in series}
            return {k: by_path.get(k, 0) for k in ("local", "gathered")}

        was = telemetry.enabled()
        telemetry.set_enabled(True)
        try:
            before = counted()
            CollectorBridge._combine_images(img(0.1)[None], {}, (), False)
            CollectorBridge._combine_images(
                local_batch("dp_rows"), {"w1": {}}, ("w1",), False)
            CollectorBridge._combine_images(
                img(0.1)[None], {"w1": {0: img(0.3)}}, ("w1",), False)
            after = counted()
            telemetry.set_enabled(False)
            CollectorBridge._combine_images(img(0.1)[None], {}, (), False)
            assert counted() == after
        finally:
            telemetry.set_enabled(was)
        assert {k: after[k] - before[k] for k in after} == {
            "local": 2, "gathered": 1}


class TestCombineAudio:
    def test_concat_along_samples(self):
        local = {"waveform": np.zeros((1, 2, 10), np.float32), "sample_rate": 8000}
        parts = {"w1": {"waveform": np.ones((1, 2, 5), np.float32), "sample_rate": 8000}}
        out = CollectorBridge._combine_audio(local, parts, ("w1",))
        assert out["waveform"].shape == (1, 2, 15)

    def test_channel_mismatch_truncates(self):
        local = {"waveform": np.zeros((1, 2, 4), np.float32), "sample_rate": 8000}
        parts = {"w1": {"waveform": np.ones((1, 1, 4), np.float32), "sample_rate": 8000}}
        out = CollectorBridge._combine_audio(local, parts, ("w1",))
        assert out["waveform"].shape == (1, 1, 8)

    def test_none_when_no_audio(self):
        assert CollectorBridge._combine_audio(None, {}, ()) is None


class TestCollectDrain:
    def test_collects_until_all_done(self):
        async def body():
            store = JobStore()
            bridge = CollectorBridge(store, asyncio.get_running_loop())

            async def worker_sends():
                await asyncio.sleep(0.05)
                for i in range(2):
                    await store.put_collector_result("j1", {
                        "worker_id": "w1", "batch_idx": i,
                        "image": encode_image_b64(img(0.5)),
                        "is_last": i == 1,
                    })
                await store.put_collector_result("j1", {
                    "worker_id": "w2", "batch_idx": 0,
                    "image": encode_image_b64(img(0.9)),
                    "audio": encode_audio({"waveform": np.zeros((1, 1, 8), np.float32),
                                           "sample_rate": 8000}),
                    "is_last": True,
                })

            await store.prepare_collector_job("j1", ("w1", "w2"))
            send_task = asyncio.ensure_future(worker_sends())
            images, audio = await bridge.collect_async(
                "j1", img(0.1)[None], None, ("w1", "w2"))
            await send_task
            assert images.shape == (4, 4, 4, 3)
            assert audio["waveform"].shape == (1, 1, 8)
            # job cleaned up after collection
            assert await store.get_collector_job("j1") is None
        run(body())

    def test_timeout_returns_partial(self):
        async def body():
            store = JobStore()
            bridge = CollectorBridge(store, asyncio.get_running_loop())
            await store.prepare_collector_job("j1", ("w1", "dead"))
            await store.put_collector_result("j1", {
                "worker_id": "w1", "batch_idx": 0,
                "image": encode_image_b64(img(0.7)), "is_last": True,
            })
            images, _ = await bridge.collect_async(
                "j1", img(0.2)[None], None, ("w1", "dead"), timeout=0.3)
            assert images.shape == (2, 4, 4, 3)   # master + w1; dead skipped
        run(body())

    def test_busy_probe_grace_extends_for_slow_worker(self, monkeypatch):
        """A slow-but-alive worker whose health probe reports queued work
        gets a deadline extension (reference busy-probe grace,
        nodes/collector.py:414-470) — its results are NOT dropped."""
        from comfyui_distributed_tpu.cluster import collector_bridge as cb
        from comfyui_distributed_tpu.utils import constants

        monkeypatch.setattr(constants, "COLLECT_GRACE_S", 0.5)
        probes = []

        async def fake_probe(host):
            probes.append(host)
            return {"queue_remaining": 1}

        monkeypatch.setattr(cb, "probe_host", fake_probe)

        async def body():
            store = JobStore()
            bridge = CollectorBridge(
                store, asyncio.get_running_loop(),
                host_resolver=lambda w: {"id": w, "address": "h:1"})
            await store.prepare_collector_job("j1", ("slow",))

            async def late_send():
                await asyncio.sleep(0.25)   # past the 0.1s base timeout
                await store.put_collector_result("j1", {
                    "worker_id": "slow", "batch_idx": 0,
                    "image": encode_image_b64(img(0.6)), "is_last": True,
                })

            task = asyncio.ensure_future(late_send())
            images, _ = await bridge.collect_async(
                "j1", img(0.2)[None], None, ("slow",), timeout=0.1)
            await task
            assert probes, "drain timeout should have probed the silent worker"
            assert images.shape == (2, 4, 4, 3)   # grace kept the results
        run(body())

    def test_dead_worker_gets_no_grace(self, monkeypatch):
        from comfyui_distributed_tpu.cluster import collector_bridge as cb

        async def fake_probe(host):
            return None                      # unreachable host

        monkeypatch.setattr(cb, "probe_host", fake_probe)

        async def body():
            store = JobStore()
            bridge = CollectorBridge(
                store, asyncio.get_running_loop(),
                host_resolver=lambda w: {"id": w, "address": "h:1"})
            await store.prepare_collector_job("j1", ("dead",))
            t0 = asyncio.get_running_loop().time()
            images, _ = await bridge.collect_async(
                "j1", img(0.2)[None], None, ("dead",), timeout=0.2)
            assert asyncio.get_running_loop().time() - t0 < 2.0
            assert images.shape == (1, 4, 4, 3)   # master only
        run(body())

    def test_empty_batch_worker_contributes_nothing(self):
        async def body():
            store = JobStore()
            bridge = CollectorBridge(store, asyncio.get_running_loop())
            await store.prepare_collector_job("j1", ("w1",))
            await store.put_collector_result("j1", {
                "worker_id": "w1", "batch_idx": -1, "image": "", "is_last": True,
            })
            images, _ = await bridge.collect_async(
                "j1", img(0.2)[None], None, ("w1",), timeout=1.0)
            assert images.shape == (1, 4, 4, 3)
        run(body())


class TestRuntimeQueue:
    def test_prompt_queue_executes_and_tracks(self):
        from comfyui_distributed_tpu.cluster import PromptQueue

        async def body():
            q = PromptQueue()
            pid, errs = q.enqueue({
                "1": {"class_type": "PrimitiveInt", "inputs": {"value": 7}},
                "2": {"class_type": "DistributedSeed", "inputs": {"seed": ["1", 0]}},
            })
            assert errs == []
            for _ in range(100):
                if pid in q.history:
                    break
                await asyncio.sleep(0.02)
            assert q.history[pid]["status"] == "success"
            assert q.history[pid]["outputs"]["2"] == (7,)
            assert q.queue_remaining == 0
            await q.stop()
        run(body())

    def test_invalid_prompt_rejected(self):
        from comfyui_distributed_tpu.cluster import PromptQueue

        async def body():
            q = PromptQueue()
            pid, errs = q.enqueue({"1": {"class_type": "Nope", "inputs": {}}})
            assert pid == "" and errs
            await q.stop()
        run(body())

    def test_node_exception_isolated(self):
        from comfyui_distributed_tpu.cluster import PromptQueue

        async def body():
            q = PromptQueue()
            pid, _ = q.enqueue({
                "1": {"class_type": "LoadImage", "inputs": {"image": "missing.png"}},
            })
            for _ in range(100):
                if pid in q.history:
                    break
                await asyncio.sleep(0.02)
            assert q.history[pid]["status"] == "error"
            assert "not found" in q.history[pid]["error"]
            await q.stop()
        run(body())
