"""HTTP control-plane tests.

Route-level coverage mirrors the reference's fake-request tests
(tests/api/*, SURVEY §4); the two-controller test at the bottom covers what
the reference never had: a real master↔worker HTTP round trip.
"""

import asyncio
import json

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from comfyui_distributed_tpu.api import create_app, parse_queue_request_payload
from comfyui_distributed_tpu.cluster.controller import Controller
from comfyui_distributed_tpu.utils.exceptions import ValidationError


def run(coro):
    return asyncio.run(coro)


def make_client():
    controller = Controller()
    app = create_app(controller)
    return controller, TestClient(TestServer(app))


class TestQueueRequestParsing:
    def test_minimal(self):
        p = parse_queue_request_payload({"prompt": {"1": {}}})
        assert p.prompt == {"1": {}}
        assert p.enabled_worker_ids is None

    def test_workers_legacy_alias(self):
        p = parse_queue_request_payload({"prompt": {"1": {}}, "workers": ["a"]})
        assert p.enabled_worker_ids == ("a",)

    def test_explicit_ids_win_over_alias(self):
        p = parse_queue_request_payload(
            {"prompt": {"1": {}}, "enabled_worker_ids": ["x"], "workers": ["y"]})
        assert p.enabled_worker_ids == ("x",)

    @pytest.mark.parametrize("bad", [
        {},
        {"prompt": []},
        {"prompt": {}},
        {"prompt": {"1": {}}, "enabled_worker_ids": "notalist"},
        {"prompt": {"1": {}}, "enabled_worker_ids": [1, 2]},
        {"prompt": {"1": {}}, "delegate_master": "yes"},
        {"prompt": {"1": {}}, "client_id": 5},
    ])
    def test_invalid_payloads(self, bad):
        with pytest.raises(ValidationError):
            parse_queue_request_payload(bad)


class TestRoutes:
    def test_health_and_probe(self, tmp_config):
        async def body():
            controller, client = make_client()
            async with client:
                resp = await client.get("/distributed/health")
                data = await resp.json()
                assert resp.status == 200
                assert data["role"] == "master"
                assert data["queue_remaining"] == 0
                resp = await client.get("/prompt")
                data = await resp.json()
                assert data["exec_info"]["queue_remaining"] == 0
        run(body())

    def test_prompt_post_validates(self, tmp_config):
        async def body():
            controller, client = make_client()
            async with client:
                resp = await client.post("/prompt", json={"prompt": {
                    "1": {"class_type": "Nope", "inputs": {}}}})
                assert resp.status == 400
                data = await resp.json()
                assert data["node_errors"]
                resp = await client.post("/prompt", json={"prompt": {
                    "1": {"class_type": "PrimitiveInt", "inputs": {"value": 1}}}})
                assert resp.status == 200
                assert (await resp.json())["prompt_id"].startswith("p_")
        run(body())

    def test_job_complete_validation_and_ingest(self, tmp_config):
        async def body():
            controller, client = make_client()
            async with client:
                resp = await client.post("/distributed/job_complete", json={})
                assert resp.status == 400
                await controller.store.prepare_collector_job("j1", ("w1",))
                resp = await client.post("/distributed/job_complete", json={
                    "job_id": "j1", "worker_id": "w1", "batch_idx": 0,
                    "image": "", "is_last": True})
                assert resp.status == 200
                job = await controller.store.get_collector_job("j1")
                assert job.results.qsize() == 1
        run(body())

    def test_prepare_job_route(self, tmp_config):
        async def body():
            controller, client = make_client()
            async with client:
                resp = await client.post("/distributed/prepare_job", json={
                    "job_id": "jx", "expected_workers": ["w1", "w2"]})
                assert resp.status == 200
                job = await controller.store.get_collector_job("jx")
                assert job.expected_workers == ("w1", "w2")
        run(body())

    def test_usdu_work_cycle_over_http(self, tmp_config):
        """heartbeat → request_image → submit_image → job_status, the whole
        pull cycle (reference tests/api/test_usdu_routes.py)."""
        from comfyui_distributed_tpu.utils.image import encode_image_b64

        async def body():
            controller, client = make_client()
            async with client:
                await controller.store.init_tile_job("t1", 2)
                resp = await client.post("/distributed/heartbeat", json={
                    "job_id": "t1", "worker_id": "w1"})
                assert (await resp.json())["status"] == "ok"
                resp = await client.post("/distributed/request_image", json={
                    "job_id": "t1", "worker_id": "w1"})
                task = (await resp.json())["task"]
                assert task["task_id"] == 0
                img = np.zeros((4, 4, 3), np.float32)
                resp = await client.post("/distributed/submit_image", json={
                    "job_id": "t1", "worker_id": "w1",
                    "task_id": task["task_id"], "image": encode_image_b64(img)})
                assert (await resp.json())["accepted"] == 1
                resp = await client.get("/distributed/job_status",
                                        params={"job_id": "t1"})
                st = await resp.json()
                assert st["completed"] == 1 and st["pending"] == 1
                resp = await client.get("/distributed/queue_status/t1")
                assert (await resp.json())["exists"] is True
        run(body())

    def test_submit_tiles_multipart(self, tmp_config):
        import aiohttp

        from comfyui_distributed_tpu.utils.image import encode_png

        async def body():
            controller, client = make_client()
            async with client:
                await controller.store.init_tile_job("t1", 2)
                await controller.store.request_work("t1", "w1")
                await controller.store.request_work("t1", "w1")
                form = aiohttp.FormData()
                form.add_field("tiles_metadata", json.dumps({
                    "job_id": "t1", "worker_id": "w1",
                    "tiles": [{"task_id": 0, "part": "tile_0"},
                              {"task_id": 1, "part": "tile_1"}]}))
                for i in range(2):
                    form.add_field(f"tile_{i}",
                                   encode_png(np.full((4, 4, 3), 0.5, np.float32)),
                                   content_type="image/png")
                resp = await client.post("/distributed/submit_tiles", data=form,
                                          headers={"X-CDT-Client": "1"})
                assert resp.status == 200
                assert (await resp.json())["accepted"] == 2
                assert controller.store.tile_jobs["t1"].is_complete()
        run(body())

    def test_config_crud(self, tmp_config):
        async def body():
            controller, client = make_client()
            async with client:
                resp = await client.post("/distributed/config/update_worker", json={
                    "id": "h1", "address": "http://10.0.0.5:8288", "enabled": True})
                assert resp.status == 200
                cfg = await (await client.get("/distributed/config")).json()
                assert cfg["hosts"][0]["id"] == "h1"
                assert cfg["hosts"][0]["type"] == "remote"   # normalized default
                resp = await client.post("/distributed/config/update_setting", json={
                    "key": "debug", "value": True})
                assert resp.status == 200
                resp = await client.post("/distributed/config/update_setting", json={
                    "key": "nope", "value": 1})
                assert resp.status == 400
                resp = await client.post("/distributed/config/update_setting", json={
                    "key": "worker_probe_concurrency", "value": "high"})
                assert resp.status == 400
                resp = await client.post("/distributed/config/update_mesh", json={
                    "shape": {"dp": 4, "tp": 2}})
                assert resp.status == 200
                resp = await client.post("/distributed/config/update_mesh", json={
                    "shape": {"dp": -1, "tp": -1}})
                assert resp.status == 400
                resp = await client.post("/distributed/config/delete_worker",
                                         json={"id": "h1"})
                assert resp.status == 200
                resp = await client.post("/distributed/config/delete_worker",
                                         json={"id": "h1"})
                assert resp.status == 404
        run(body())

    def test_media_sync_routes(self, tmp_config, tmp_path, monkeypatch):
        import aiohttp

        from comfyui_distributed_tpu.utils.image import encode_png

        monkeypatch.setenv("CDT_INPUT_DIR", str(tmp_path))

        async def body():
            controller, client = make_client()
            async with client:
                resp = await client.post("/distributed/check_file",
                                         json={"path": "a.png"})
                assert (await resp.json())["exists"] is False
                # upload then check
                form = aiohttp.FormData()
                png = encode_png(np.zeros((2, 2, 3), np.float32))
                form.add_field("image", png, filename="a.png",
                               content_type="image/png")
                resp = await client.post("/upload/image", data=form,
                                          headers={"X-CDT-Client": "1"})
                assert (await resp.json())["saved"] == ["a.png"]
                resp = await client.post("/distributed/check_file",
                                         json={"path": "a.png"})
                data = await resp.json()
                assert data["exists"] is True and len(data["md5"]) == 32
                resp = await client.post("/distributed/load_image",
                                         json={"path": "a.png"})
                assert (await resp.json())["image"].startswith("data:image/png;base64,")
                # traversal blocked
                resp = await client.post("/distributed/check_file",
                                         json={"path": "../../etc/passwd"})
                assert resp.status == 400
        run(body())

    def test_system_and_network_info(self, tmp_config):
        async def body():
            controller, client = make_client()
            async with client:
                info = await (await client.get("/distributed/system_info")).json()
                assert "machine_id" in info and len(info["devices"]) == 8
                net = await (await client.get("/distributed/network_info")).json()
                assert net["recommended_ip"]
        run(body())

    def test_device_routes_503_when_backend_hangs(self, tmp_config,
                                                  monkeypatch):
        """A device runtime that blocks jax.devices()/memory_stats() must
        not freeze the event loop: the info routes answer 503 within the
        deadline (utils/deadline.py), and no 200 payload stands in for a
        census that was not taken."""
        import threading
        import time as _time

        from comfyui_distributed_tpu.utils import deadline

        deadline.reset_gate()
        release = threading.Event()                # frees the stuck
                                                   # executor thread at exit

        async def body():
            controller, client = make_client()
            monkeypatch.setattr(
                type(controller), "system_info",
                lambda self: release.wait(30))     # simulated hang
            async with client:
                t0 = _time.monotonic()
                resp = await client.get("/distributed/system_info")
                assert _time.monotonic() - t0 < 10
                assert resp.status == 503
                assert "device backend" in (await resp.json())["error"]
                # gate now closed: subsequent calls short-circuit fast
                t0 = _time.monotonic()
                resp = await client.get("/distributed/network_info")
                assert _time.monotonic() - t0 < 2
                assert resp.status == 503
                resp = await client.get("/distributed/memory_stats")
                assert resp.status == 503
        try:
            run(body())
        finally:
            release.set()
            deadline.reset_gate()

    def test_system_info_503_when_backend_fails_to_initialise(
            self, tmp_config, monkeypatch):
        """A backend that raises at init (no chip) is a 503 too, with
        the runtime's own message — not a 200 and not a bare 500."""
        from comfyui_distributed_tpu.utils import deadline

        deadline.reset_gate()

        def no_backend(self):
            raise RuntimeError("Unable to initialize backend 'tpu'")

        async def body():
            controller, client = make_client()
            monkeypatch.setattr(type(controller), "system_info", no_backend)
            async with client:
                resp = await client.get("/distributed/system_info")
                assert resp.status == 503
                assert "Unable to initialize" in (await resp.json())["error"]
        run(body())
        assert deadline.gate_open()            # a fast failure is no stall

    def test_deadline_call_semantics(self):
        """Unit contract of utils/deadline.deadline_call: fast failures
        PROPAGATE (real diagnostics), stalls degrade, and the 2-permit
        semaphore bounds leaked threads even with the gate open."""
        import asyncio
        import threading

        from comfyui_distributed_tpu.utils import deadline

        deadline.reset_gate()
        release = threading.Event()

        async def body():
            # exception passthrough
            def boom():
                raise RuntimeError("real diagnostic")

            try:
                await deadline.deadline_call(boom, timeout_s=2.0)
                raise AssertionError("expected RuntimeError")
            except RuntimeError as e:
                assert "real diagnostic" in str(e)
            assert deadline.gate_open()        # failures don't close it

            # stall → fallback + gate closes; permits bound the leak
            stalled = await deadline.deadline_call(
                lambda: release.wait(30), timeout_s=0.3,
                cooldown_s=0.0, fallback="degraded")
            assert stalled == "degraded"
            # consume the second permit too (cooldown 0 keeps gate open)
            await deadline.deadline_call(
                lambda: release.wait(30), timeout_s=0.3,
                cooldown_s=0.0, fallback="degraded")
            # third call: both permits held by stuck threads → instant
            # fallback without spawning anything
            t0 = asyncio.get_event_loop().time()
            out = await deadline.deadline_call(
                lambda: "never runs", timeout_s=5.0, fallback="degraded")
            assert out == "degraded"
            assert asyncio.get_event_loop().time() - t0 < 0.2

        try:
            asyncio.run(body())
        finally:
            release.set()
            deadline.reset_gate()

    def test_profiler_and_observability_routes(self, tmp_config):
        async def body():
            controller, client = make_client()
            async with client:
                # memory stats: shape only (CPU backends report None)
                res = await (await client.get("/distributed/memory_stats")).json()
                assert len(res["devices"]) == 8
                # step times: empty history → empty list
                res = await (await client.get("/distributed/step_times")).json()
                assert res["prompts"] == []
                # profile start/stop round trip (CPU tracing works);
                # client "out" is a sandboxed NAME under CDT_PROFILE_DIR
                resp = await client.post("/distributed/profile/start",
                                         json={"out": "../../../etc/x"})
                data = await resp.json()
                assert resp.status == 200
                assert "/etc/" not in data["out"]
                assert data["out"].startswith("/tmp/cdt_profile")
                # double-start rejected
                resp = await client.post("/distributed/profile/start", json={})
                assert resp.status == 409
                resp = await client.post("/distributed/profile/stop", json={})
                assert resp.status == 200
                # double-stop rejected
                resp = await client.post("/distributed/profile/stop", json={})
                assert resp.status == 409
        run(body())

    def test_clear_launching_route(self, tmp_config):
        async def body():
            controller, client = make_client()
            async with client:
                resp = await client.post(
                    "/distributed/worker/clear_launching",
                    json={"worker_id": "w0"})
                data = await resp.json()
                assert resp.status == 200
                assert data["cleared"] is False   # flag was never set
                resp = await client.post(
                    "/distributed/worker/clear_launching", json={})
                assert resp.status == 400
        run(body())

    def test_local_worker_status_route(self, tmp_config):
        from comfyui_distributed_tpu.utils import config as config_mod

        async def body():
            # one configured local host that is offline
            config_mod.update_config(lambda c: c["hosts"].append(
                {"id": "w0", "address": "http://127.0.0.1:1",
                 "enabled": True, "type": "local"}))
            controller, client = make_client()
            async with client:
                resp = await client.get("/distributed/local-worker-status")
                data = await resp.json()
                assert resp.status == 200
                assert data["workers"]["w0"]["online"] is False
                assert data["workers"]["w0"]["managed"] is False
        run(body())

    def test_remote_worker_log_route(self, tmp_config):
        from comfyui_distributed_tpu.utils import config as config_mod
        from comfyui_distributed_tpu.utils.logging import log

        async def body():
            controller, client = make_client()
            async with client:
                # unknown host → 404
                resp = await client.get("/distributed/remote_worker_log/nope")
                assert resp.status == 404

            # a second controller acts as the remote peer; proxy its log
            peer = Controller()
            peer_server = TestServer(create_app(peer))
            await peer_server.start_server()
            log("remote-log-marker")
            config_mod.update_config(lambda c: c["hosts"].append(
                {"id": "peer",
                 "address": f"http://127.0.0.1:{peer_server.port}",
                 "enabled": True, "type": "remote"}))
            controller2, client2 = make_client()
            async with client2:
                resp = await client2.get("/distributed/remote_worker_log/peer")
                data = await resp.json()
                assert resp.status == 200
                assert "remote-log-marker" in data["log"]
                # unreachable peer → 502
                config_mod.update_config(lambda c: c["hosts"].append(
                    {"id": "gone", "address": "http://127.0.0.1:1",
                     "enabled": True, "type": "remote"}))
                resp = await client2.get("/distributed/remote_worker_log/gone")
                assert resp.status == 502
            await peer_server.close()
        run(body())

    def test_worker_ws_dispatch_channel(self, tmp_config):
        async def body():
            controller, client = make_client()
            async with client:
                ws = await client.ws_connect("/distributed/worker_ws")
                await ws.send_json({
                    "type": "dispatch_prompt",
                    "prompt": {"1": {"class_type": "PrimitiveInt",
                                     "inputs": {"value": 3}}},
                    "client_id": "t", "request_id": "r1",
                })
                ack = await ws.receive_json()
                assert ack["type"] == "dispatch_ack"
                assert ack["ok"] is True and ack["prompt_id"]
                assert ack["request_id"] == "r1"
                # invalid prompt → ack with node_errors, not a dropped socket
                await ws.send_json({"type": "dispatch_prompt",
                                    "prompt": {"1": {"class_type": "Nope",
                                                     "inputs": {}}}})
                ack = await ws.receive_json()
                assert ack["ok"] is False and ack["node_errors"]
                await ws.close()
        run(body())

    def test_dispatch_prompt_ws_master_side(self, tmp_config):
        """Master-side WS dispatch against a real worker_ws endpoint."""
        from comfyui_distributed_tpu.cluster.dispatch import dispatch_prompt_ws
        from comfyui_distributed_tpu.utils.exceptions import WorkerError

        async def body():
            worker = Controller()
            server = TestServer(create_app(worker))
            await server.start_server()
            host = {"id": "w0", "address": f"http://127.0.0.1:{server.port}"}
            ack = await dispatch_prompt_ws(
                host, {"1": {"class_type": "PrimitiveInt",
                             "inputs": {"value": 1}}})
            assert ack["ok"] is True
            with pytest.raises(WorkerError):
                await dispatch_prompt_ws(
                    host, {"1": {"class_type": "Nope", "inputs": {}}})
            await server.close()
        run(body())


class TestTwoControllerE2E:
    """Master + worker controllers over real HTTP: orchestrate fans out,
    the worker executes and pushes envelopes back, the master's collector
    combines master-first. The reference has no equivalent test (SURVEY §4
    'no end-to-end multi-process test')."""

    def test_distributed_roundtrip(self, tmp_config, monkeypatch):
        from comfyui_distributed_tpu.utils import config as config_mod

        async def body():
            # worker controller on its own port
            worker = Controller()
            worker.is_worker = True
            worker.worker_id = "w0"
            worker_server = TestServer(create_app(worker))
            await worker_server.start_server()
            wport = worker_server.port

            # master config points at the worker
            config_mod.update_config(lambda c: (
                c["hosts"].append({"id": "w0",
                                   "address": f"http://127.0.0.1:{wport}",
                                   "enabled": True, "type": "local"}),
                c["master"].update(host="127.0.0.1"),
            ))

            master = Controller()
            master_server = TestServer(create_app(master))
            await master_server.start_server()
            # worker callbacks must reach the master's real port
            config_mod.update_config(lambda c: c["master"].update(
                port=master_server.port))

            prompt = {
                "1": {"class_type": "DistributedEmptyImage",
                      "inputs": {"height": 4, "width": 4}},
                "2": {"class_type": "DistributedSeed", "inputs": {"seed": 5}},
                "3": {"class_type": "DistributedCollector",
                      "inputs": {"images": ["1", 0]}},
            }
            client = TestClient(master_server)
            async with client:
                resp = await client.post("/distributed/queue", json={
                    "prompt": prompt, "client_id": "e2e"})
                assert resp.status == 200
                data = await resp.json()
                assert data["worker_count"] == 1
                pid = data["prompt_id"]
                # wait for the master graph to finish collecting
                for _ in range(200):
                    if pid in master.queue.history:
                        break
                    await asyncio.sleep(0.05)
                assert pid in master.queue.history, "master prompt never finished"
                hist = master.queue.history[pid]
                assert hist["status"] == "success", hist
                # collector output: master's 0-batch + worker's 0-batch
                images = hist["outputs"]["3"][0]
                assert np.asarray(images).shape[0] == 0
                # worker side executed its pruned prompt
                assert len(worker.queue.history) == 1
                whist = next(iter(worker.queue.history.values()))
                assert whist["status"] == "success", whist
            await worker_server.close()
            await master_server.close()
        run(body())


def test_api_doc_covers_routes():
    """docs/api.md must mention every /distributed route (drift guard,
    same pattern as the nodes-doc guard)."""
    from pathlib import Path

    controller = Controller()
    app = create_app(controller)
    doc = (Path(__file__).resolve().parent.parent
           / "docs" / "api.md").read_text()
    missing = sorted({
        r.resource.canonical for r in app.router.routes()
        if r.resource is not None
        and r.resource.canonical.startswith("/distributed")
        and r.resource.canonical not in doc})
    assert not missing, f"docs/api.md missing routes: {missing}"
