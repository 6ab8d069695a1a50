"""The served request's host path on the profiler's clock (ISSUE 24).

- the annotator hook of ``telemetry/spans.py``: entered and left in order
  for nested synchronous spans, skipped inside a running asyncio task,
  and costing nothing that is recorded when it is not installed;
- one tiny-preset request through the controller's prompt queue: one tree
  with ``prompt.queued``, a ``node.<class_type>`` per graph node, the
  launch/wait pair under the sampler node, the PNG and write spans, and
  the dispatch histogram counting every program call;
- ``profile/start`` → one request → ``profile/stop`` on the CPU backend:
  the ``.xplane.pb`` holds the mirrored ``cdt.*`` spans and no
  Python-tracer flood.
"""

import asyncio
import threading

import pytest

from comfyui_distributed_tpu import telemetry
from comfyui_distributed_tpu.telemetry import spans

GRAPH_CLASSES = ("CheckpointLoader", "CLIPTextEncode", "DistributedSeed",
                 "TPUTxt2Img", "DistributedCollector", "SaveImage")


def tiny_graph(seed: int, out_dir: str) -> dict:
    return {
        "1": {"class_type": "CheckpointLoader",
              "inputs": {"ckpt_name": "tiny"}},
        "2": {"class_type": "CLIPTextEncode",
              "inputs": {"text": f"traced {seed}", "clip": ["1", 1]}},
        "3": {"class_type": "CLIPTextEncode",
              "inputs": {"text": "", "clip": ["1", 1]}},
        "4": {"class_type": "DistributedSeed", "inputs": {"seed": seed}},
        "5": {"class_type": "TPUTxt2Img", "inputs": {
            "model": ["1", 0], "positive": ["2", 0], "negative": ["3", 0],
            "seed": ["4", 0], "steps": 4, "cfg": 2.0,
            "width": 16, "height": 16}},
        "6": {"class_type": "DistributedCollector",
              "inputs": {"images": ["5", 0]}},
        "7": {"class_type": "SaveImage", "inputs": {
            "images": ["6", 0], "filename_prefix": f"t{seed}",
            "output_dir": out_dir}},
    }


class Recorder:
    """An annotator that writes down what a profiler would see."""

    def __init__(self):
        self.events = []

    def __call__(self, name):
        recorder = self

        class _Annotation:
            def __enter__(self):
                recorder.events.append(("enter", name,
                                        threading.get_ident()))

            def __exit__(self, *exc):
                recorder.events.append(("exit", name,
                                        threading.get_ident()))

        return _Annotation()


@pytest.fixture
def fresh_spans():
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    telemetry.SPAN_STORE.reset()
    yield
    spans.set_annotator(None)
    telemetry.SPAN_STORE.reset()
    telemetry.set_enabled(was)


class TestAnnotatorHook:
    def test_nested_sync_spans_enter_and_leave_in_order(self, fresh_spans):
        rec = Recorder()
        spans.set_annotator(rec)
        with telemetry.span("outer"):
            with telemetry.span("inner"):
                pass
            with pytest.raises(ValueError):
                with telemetry.span("failing"):
                    raise ValueError("x")
        assert [(kind, name) for kind, name, _ in rec.events] == [
            ("enter", "cdt.outer"), ("enter", "cdt.inner"),
            ("exit", "cdt.inner"), ("enter", "cdt.failing"),
            ("exit", "cdt.failing"), ("exit", "cdt.outer")]

    def test_spans_of_a_running_asyncio_task_are_not_mirrored(
            self, fresh_spans):
        rec = Recorder()
        spans.set_annotator(rec)

        def in_thread():
            with telemetry.span("threaded"):
                pass

        async def body():
            with telemetry.span("orchestrate") as ctx:
                # the executor thread runs no loop: mirrored, on its thread
                await asyncio.get_running_loop().run_in_executor(
                    None, in_thread)
                return ctx[0]

        trace_id = asyncio.run(body())
        assert [(kind, name) for kind, name, _ in rec.events] == [
            ("enter", "cdt.threaded"), ("exit", "cdt.threaded")]
        assert rec.events[0][2] != threading.get_ident()
        # the span store takes both, mirrored or not
        names = {s["name"] for s in telemetry.SPAN_STORE.spans(trace_id)}
        assert "orchestrate" in names

    def test_removed_annotator_mirrors_nothing_more(self, fresh_spans):
        rec = Recorder()
        spans.set_annotator(rec)
        with telemetry.span("while_on"):
            spans.set_annotator(None)       # profile/stop mid-span
            with telemetry.span("after_off"):
                pass
        assert [(kind, name) for kind, name, _ in rec.events] == [
            ("enter", "cdt.while_on"), ("exit", "cdt.while_on")]

    def test_without_annotator_a_span_records_what_it_did_before(
            self, fresh_spans):
        assert spans._ANNOTATOR is None
        with telemetry.span("plain", trace_id="exec_1", job_id="j1") as ctx:
            with telemetry.span("child", step=3):
                pass
        (child, plain) = telemetry.SPAN_STORE.spans("exec_1")
        assert ctx == ("exec_1", plain["span_id"])
        assert set(plain) == {"name", "trace_id", "span_id", "parent_id",
                              "start", "duration_s", "attrs"}
        assert plain["attrs"] == {"job_id": "j1"}
        assert plain["parent_id"] is None
        assert child["parent_id"] == plain["span_id"]
        assert child["attrs"] == {"step": "3"}
        assert 0.0 <= child["duration_s"] <= plain["duration_s"]
        assert telemetry.SPAN_STORE.resolve("j1") == "exec_1"

    def test_record_span_files_a_finished_wait(self, fresh_spans):
        with telemetry.span("root", trace_id="exec_2") as (_, root_id):
            telemetry.record_span("prompt.queued", 0.25, prompt_id="p9")
        telemetry.record_span("prompt.queued", 0.5, trace_id="exec_2",
                              parent_id="abcd")
        queued = [s for s in telemetry.SPAN_STORE.spans("exec_2")
                  if s["name"] == "prompt.queued"]
        assert [s["duration_s"] for s in queued] == [0.25, 0.5]
        assert [s["parent_id"] for s in queued] == [root_id, "abcd"]
        assert telemetry.SPAN_STORE.resolve("p9") == "exec_2"
        # it ended when it was recorded, and started that long before
        root = telemetry.SPAN_STORE.spans("exec_2")[1]
        assert queued[0]["start"] < root["start"]

    def test_timed_span_feeds_its_histogram_and_the_store(self, fresh_spans):
        seen = []

        class Child:
            def observe(self, value):
                seen.append(value)

        with spans.timed_span("program.launch", Child(), trace_id="exec_3",
                              pipeline="p"):
            pass
        (rec,) = telemetry.SPAN_STORE.spans("exec_3")
        assert rec["name"] == "program.launch"
        assert rec["attrs"] == {"pipeline": "p"}
        assert len(seen) == 1 and seen[0] >= rec["duration_s"]
        telemetry.set_enabled(False)
        with spans.timed_span("program.launch", Child()) as ctx:
            assert ctx is None
        assert len(seen) == 1


# --------------------------------------------------------------------------
# one tiny-preset request through the controller, then one under a profile
# --------------------------------------------------------------------------


def _series(snapshot, name):
    return snapshot.get(name, {}).get("series", [])


def _counts(snapshot, name, label):
    return {s["labels"][label]: s["count"] for s in _series(snapshot, name)}


async def _served(client, graph) -> str:
    resp = await client.post("/distributed/queue",
                             json={"prompt": graph, "client_id": "t"})
    data = await resp.json()
    assert resp.status == 200, data
    for _ in range(30000):
        got = await client.get(f"/distributed/history/{data['prompt_id']}")
        if got.status == 200:
            entry = await got.json()
            if entry.get("status") in ("success", "error"):
                assert entry["status"] == "success", entry
                return data["prompt_id"]
        await asyncio.sleep(0.01)
    raise AssertionError("the prompt never finished")


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A controller on one virtual device serves the tiny graph twice:
    once to compile, once inside profile/start … profile/stop. Answers
    the second request's span tree, the metric snapshots around it and
    the events of the profile it left."""
    from aiohttp.test_utils import TestClient, TestServer

    from comfyui_distributed_tpu.api.app import create_app
    from comfyui_distributed_tpu.cluster.controller import Controller
    from comfyui_distributed_tpu.utils import config as config_mod

    tmp = tmp_path_factory.mktemp("served")
    patch = pytest.MonkeyPatch()
    patch.setenv(config_mod.CONFIG_ENV, str(tmp / "config.json"))
    patch.setenv("CDT_PROFILE_DIR", str(tmp / "profile"))
    patch.setenv("CDT_CACHE_DIR", str(tmp / "content_cache"))
    patch.setenv("CDT_PREEMPT_SEGMENT_STEPS", "2")
    config_mod.invalidate_cache()
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    telemetry.REGISTRY.reset()
    telemetry.SPAN_STORE.reset()

    async def body():
        controller = Controller(mesh_devices=1)
        client = TestClient(TestServer(create_app(controller)))
        async with client:
            await _served(client, tiny_graph(1, str(tmp / "out")))
            before = telemetry.REGISTRY.snapshot()
            resp = await client.post("/distributed/profile/start",
                                     json={"out": "t"})
            assert resp.status == 200, await resp.text()
            installed = spans._ANNOTATOR
            prompt_id = await _served(client, tiny_graph(2, str(tmp / "out")))
            resp = await client.post("/distributed/profile/stop", json={})
            assert resp.status == 200, await resp.text()
            trace = await (await client.get(
                f"/distributed/trace/{prompt_id}")).json()
            await controller.queue.stop()
        return {"before": before, "after": telemetry.REGISTRY.snapshot(),
                "trace": trace, "installed": installed,
                "removed": spans._ANNOTATOR is None}

    try:
        out = asyncio.new_event_loop().run_until_complete(body())
        import jax

        (xplane,) = (tmp / "profile").rglob("*.xplane.pb")
        data = jax.profiler.ProfileData.from_file(str(xplane))
        out["host_lines"] = [
            [(ev.name, ev.start_ns, ev.duration_ns) for ev in line.events]
            for plane in data.planes for line in plane.lines]
        yield out
    finally:
        spans.set_annotator(None)
        telemetry.REGISTRY.reset()
        telemetry.SPAN_STORE.reset()
        telemetry.set_enabled(was)
        patch.undo()
        config_mod.invalidate_cache()


def _walk(nodes, parent=None):
    for node in nodes:
        yield node, parent
        yield from _walk(node["children"], node)


class TestServedRequestTree:
    def test_one_trace_holds_queue_wait_and_every_node(self, served):
        trace = served["trace"]
        assert {s["trace_id"] for s in trace["spans"]} == {trace["trace_id"]}
        names = [s["name"] for s in trace["spans"]]
        assert names.count("prompt.queued") == 1
        assert names.count("prompt.execute") == 1
        nodes = sorted(n for n in names if n.startswith("node."))
        assert nodes == sorted(
            ["node.CheckpointLoader", "node.CLIPTextEncode",
             "node.CLIPTextEncode", "node.DistributedSeed",
             "node.TPUTxt2Img", "node.DistributedCollector",
             "node.SaveImage"])
        assert {n[len("node."):] for n in nodes} == set(GRAPH_CLASSES)
        by_node = {(node["name"], node["attrs"].get("node_id")): parent
                   for node, parent in _walk(trace["tree"])
                   if node["name"].startswith("node.")}
        assert {p["name"] for p in by_node.values()} == {"prompt.execute"}

    def test_launch_and_wait_sit_under_the_sampler_node(self, served):
        parents: dict = {}
        for node, parent in _walk(served["trace"]["tree"]):
            parents.setdefault(node["name"], []).append(
                (node, parent["name"] if parent else None))
        sampler = "node.TPUTxt2Img"
        launches = parents["program.launch"]
        assert sorted((n["attrs"]["pipeline"], p) for n, p in launches) == [
            ("txt2img_fin", sampler), ("txt2img_prep", sampler),
            ("txt2img_seg", "pipeline_call"),
            ("txt2img_seg", "pipeline_call")]
        assert [p for _, p in parents["program.wait"]] == ["pipeline_call"] * 2
        assert [p for _, p in parents["pipeline_call"]] == [sampler] * 2
        # 4 steps in segments of 2: a boundary before each launch
        assert [(n["attrs"]["step"], p)
                for n, p in parents["segment.boundary"]] == [
                    ("0", sampler), ("2", sampler)]
        # the served lane carries no callback: one host-side delivery a
        # segment, on the executor's thread, straight under the node
        assert [(n["attrs"]["source"], p)
                for n, p in parents["progress.sink"]] == [
                    ("segment", sampler)] * 2
        assert "attn_kernels" not in parents["pipeline_call"][0][0]["attrs"]

    def test_png_and_write_sit_under_save_image(self, served):
        found = {node["name"]: (node, parent["name"])
                 for node, parent in _walk(served["trace"]["tree"])
                 if node["name"].startswith("image.")}
        assert found["image.encode_png"][1] == "node.SaveImage"
        assert found["image.write"][1] == "node.SaveImage"
        assert int(found["image.write"][0]["attrs"]["bytes"]) > 0

    def test_dispatch_histogram_counts_every_program_call(self, served):
        before = _counts(served["before"], "cdt_pipeline_dispatch_seconds",
                         "pipeline")
        after = _counts(served["after"], "cdt_pipeline_dispatch_seconds",
                        "pipeline")
        assert before == {"txt2img_prep": 1, "txt2img_seg": 2,
                          "txt2img_fin": 1}
        assert after == {"txt2img_prep": 2, "txt2img_seg": 4,
                         "txt2img_fin": 2}

    def test_execute_histogram_keeps_its_label_set(self, served):
        # prep and fin stay unlabelled and asynchronous: host_overhead_ms
        # and denoise_ms_per_step read what they read before
        assert _counts(served["after"], "cdt_pipeline_execute_seconds",
                       "pipeline") == {"txt2img_seg": 3}
        assert _counts(served["after"], "cdt_pipeline_compile_seconds",
                       "pipeline") == {"txt2img_seg": 1}

    def test_queue_wait_and_progress_are_observed_once_each(self, served):
        waits = _series(served["after"], "cdt_queue_wait_seconds")
        assert sum(s["count"] for s in waits) == 2
        (sink,) = _series(served["after"], "cdt_progress_callback_seconds")
        assert sink["count"] == 4           # 2 segments a request
        assert sink["sum"] > 0              # progress_ms is never null
        span_counts = _counts(served["after"], "cdt_span_seconds", "name")
        assert span_counts["prompt.queued"] == 2
        assert span_counts["node.CLIPTextEncode"] == 4
        assert span_counts["progress.sink"] == 4

    def test_the_stream_is_fed_by_segments_not_callbacks(self, served):
        fed = {s["labels"]["source"]: s["value"] for s in _series(
            served["after"], "cdt_progress_events_total")}
        assert fed == {"segment": 4}        # one a segment and chip


class TestProfileSession:
    def test_annotator_is_installed_for_the_session_only(self, served):
        import jax

        assert served["installed"] is jax.profiler.TraceAnnotation
        assert served["removed"]

    def test_xplane_holds_the_mirrored_spans_on_thread_lines(self, served):
        mirrored = [[name for name, _, _ in line if name.startswith("cdt.")]
                    for line in served["host_lines"]]
        main = max(mirrored, key=len)       # the graph executor's thread
        assert main.count("cdt.program.launch") == 4
        assert main.count("cdt.program.wait") == 2
        assert main.count("cdt.segment.boundary") == 2
        assert main.count("cdt.image.encode_png") == 1
        assert [n for n in main if n.startswith("cdt.node.")] == [
            "cdt.node.CheckpointLoader", "cdt.node.CLIPTextEncode",
            "cdt.node.CLIPTextEncode", "cdt.node.DistributedSeed",
            "cdt.node.TPUTxt2Img", "cdt.node.DistributedCollector",
            "cdt.node.SaveImage"]
        # the delivery after each segment, on the launching thread
        assert main.count("cdt.progress.sink") == 2
        everywhere = [n for line in mirrored for n in line]
        assert everywhere.count("cdt.progress.sink") == 2
        # the loop's own spans are not mirrored
        assert not {"cdt.orchestrate", "cdt.prompt.execute",
                    "cdt.prompt.queued"} & set(everywhere)

    def test_mirrored_spans_nest_on_the_profilers_clock(self, served):
        line = max(served["host_lines"],
                   key=lambda l: sum(n.startswith("cdt.") for n, _, _ in l))
        at = {}
        for name, start, dur in line:
            at.setdefault(name, []).append((start, start + dur))
        (node,) = at["cdt.node.TPUTxt2Img"]
        for inner in ("cdt.program.launch", "cdt.program.wait",
                      "cdt.segment.boundary"):
            for start, end in at[inner]:
                assert node[0] <= start <= end <= node[1], inner
        for (_, launched), (waiting, _) in zip(
                sorted(at["cdt.program.launch"])[1:3],
                sorted(at["cdt.program.wait"])):
            assert launched <= waiting

    def test_no_python_tracer_flood(self, served):
        events = [name for line in served["host_lines"]
                  for name, _, _ in line]
        # the Python tracer names its events "$file.py:123 function"
        assert not [n for n in events if n.startswith("$")]
        assert len(events) < 100_000

    @pytest.mark.parametrize("body,level", [({}, 0),
                                            ({"python_tracer": False}, 0),
                                            ({"python_tracer": True}, 1)])
    def test_python_tracer_is_an_option(self, tmp_config, monkeypatch,
                                        body, level):
        import jax

        from aiohttp.test_utils import TestClient, TestServer

        from comfyui_distributed_tpu.api.app import create_app
        from comfyui_distributed_tpu.cluster.controller import Controller

        asked = []
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda out, profiler_options=None: asked.append(
                profiler_options))
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)

        async def run():
            client = TestClient(TestServer(create_app(Controller())))
            async with client:
                bad = await client.post("/distributed/profile/start",
                                        json={"python_tracer": "yes"})
                assert bad.status == 400 and not asked
                ok = await client.post("/distributed/profile/start",
                                       json=body)
                assert ok.status == 200
                assert spans._ANNOTATOR is jax.profiler.TraceAnnotation
                await client.post("/distributed/profile/stop", json={})
                assert spans._ANNOTATOR is None

        asyncio.new_event_loop().run_until_complete(run())
        (options,) = asked
        assert options.python_tracer_level == level
        assert options.host_tracer_level >= 1
