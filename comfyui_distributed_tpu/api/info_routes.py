"""System/network info + log routes (parity: reference
``api/worker_routes.py:142-234,292-390,393-430``)."""

from __future__ import annotations

import asyncio
import json
import socket
from pathlib import Path

from aiohttp import web

from .. import telemetry
from ..utils import constants
from ..utils.exceptions import ValidationError


def _list_interfaces() -> list[dict]:
    """Best-effort NIC enumeration (reference enumerates NICs to recommend
    a private IP, ``api/worker_routes.py:142-234``)."""
    interfaces = []
    try:
        hostname = socket.gethostname()
        for info in socket.getaddrinfo(hostname, None, socket.AF_INET):
            ip = info[4][0]
            if ip not in (i["ip"] for i in interfaces):
                interfaces.append({"name": hostname, "ip": ip})
    except OSError:
        pass
    # always include loopback + best-effort outbound IP
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.connect(("10.255.255.255", 1))
        ip = s.getsockname()[0]
        s.close()
        if ip not in (i["ip"] for i in interfaces):
            interfaces.append({"name": "outbound", "ip": ip})
    except OSError:
        pass
    if not any(i["ip"] == "127.0.0.1" for i in interfaces):
        interfaces.append({"name": "lo", "ip": "127.0.0.1"})
    return interfaces


def _recommend_ip(interfaces: list[dict]) -> str:
    for i in interfaces:
        ip = i["ip"]
        if ip.startswith(("10.", "192.168.")) or ip.startswith("172."):
            return ip
    return interfaces[0]["ip"] if interfaces else "127.0.0.1"


def tail_file(path: Path, max_bytes: int = 64 * 1024) -> str:
    """Efficient reverse chunk read (reference
    ``api/worker_routes.py:292-325``)."""
    size = path.stat().st_size
    with open(path, "rb") as f:
        if size > max_bytes:
            f.seek(size - max_bytes)
        data = f.read()
    text = data.decode("utf-8", errors="replace")
    if size > max_bytes and "\n" in text:
        text = text.split("\n", 1)[1]     # drop the partial first line
    return text


def register(router, controller) -> None:
    from ..utils.deadline import deadline_call

    def no_backend(detail: str) -> web.HTTPServiceUnavailable:
        return web.HTTPServiceUnavailable(
            text=json.dumps({"error": f"device backend unavailable: "
                                      f"{detail}", "status": 503}),
            content_type="application/json")

    async def device_query(fn):
        """``fn()`` off the event loop with a deadline (utils/deadline.py).
        A census that raises or stalls is a 503: a host that cannot say
        what devices it has is not serving, and no payload pretends
        otherwise."""
        try:
            out = await deadline_call(fn, fallback=None)
        except RuntimeError as e:       # the backend failed to initialise
            raise no_backend(str(e)) from None
        if out is None:
            raise no_backend("no answer within the deadline")
        return out

    async def system_info(request):
        return web.json_response(
            await device_query(controller.system_info))

    async def network_info(request):
        interfaces = _list_interfaces()
        devices = await device_query(
            lambda: controller.system_info()["devices"])
        return web.json_response({
            "interfaces": interfaces,
            "recommended_ip": _recommend_ip(interfaces),
            "devices": devices,
        })

    async def local_log(request):
        """Tail this controller's log: the launcher-assigned file
        (CDT_LOG_FILE) when present, else the in-memory rolling buffer
        (reference serves the same buffer, ``api/worker_routes.py:348-390``)."""
        import os

        from ..utils.logging import get_log_buffer

        log_file = constants.LOG_FILE.get()
        if log_file and Path(log_file).is_file():
            loop = asyncio.get_running_loop()
            text = await loop.run_in_executor(
                None, tail_file, Path(log_file))
            return web.json_response({"log": text, "available": True})
        lines = get_log_buffer()
        return web.json_response(
            {"log": "\n".join(lines), "available": bool(lines)})

    # --- profiling / device observability ----------------------------------
    # The reference has no profiler (SURVEY §5.1: "no timing histograms,
    # no flamegraphs"); on TPU the right tool is jax.profiler — these
    # routes capture an XLA trace viewable in TensorBoard/Perfetto.
    profile_state = {"dir": None}

    async def profile_start(request):
        import jax

        if profile_state["dir"]:
            return web.json_response(
                {"error": f"trace already running → {profile_state['dir']}"},
                status=409)
        body = {}
        try:
            body = await request.json()
        except Exception:
            pass
        if not isinstance(body, dict):
            raise ValidationError("body must be a JSON object")
        if "out" in body and not isinstance(body["out"], str):
            raise ValidationError("'out' must be a string", field="out")
        if not isinstance(body.get("python_tracer", False), bool):
            raise ValidationError("'python_tracer' must be a boolean",
                                  field="python_tracer")
        import os
        import time as _t

        # "out" is a NAME under the profile root, never a client path —
        # same sandbox discipline as the media routes (an unauthenticated
        # peer must not direct filesystem writes)
        from ..utils.names import sanitize_name

        root = constants.PROFILE_DIR.get()
        name = sanitize_name(
            os.path.basename(str(body.get("out") or _t.strftime("%Y%m%d-%H%M%S"))),
            max_len=80, fallback="trace")
        out = os.path.join(root, name)
        # the Python tracer is off unless asked for: it adds millions of
        # host events a request (tens of MiB) that no reader of the trace
        # uses; the host tracer stays on and keeps the TraceAnnotations
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = int(body.get("python_tracer", False))
        try:
            jax.profiler.start_trace(out, profiler_options=options)
        except RuntimeError as e:
            return web.json_response({"error": str(e)}, status=409)
        # from here every synchronous span is mirrored into the trace as
        # an annotation ``cdt.<name>``, on the device operations' clock
        telemetry.set_annotator(jax.profiler.TraceAnnotation)
        profile_state["dir"] = out
        return web.json_response({"status": "tracing", "out": out})

    async def profile_stop(request):
        import jax

        if not profile_state["dir"]:
            return web.json_response({"error": "no trace running"}, status=409)
        out, profile_state["dir"] = profile_state["dir"], None
        telemetry.set_annotator(None)
        try:
            jax.profiler.stop_trace()
        except RuntimeError as e:
            return web.json_response({"error": str(e)}, status=409)
        return web.json_response({"status": "stopped", "out": out})

    async def memory_stats(request):
        """Per-device HBM/host memory stats (None on backends that don't
        report them, e.g. CPU)."""
        def census():
            import jax

            return [{"id": d.id, "kind": d.device_kind,
                     "stats": d.memory_stats()}
                    for d in jax.local_devices()]

        return web.json_response({"devices": await device_query(census)})

    # --- telemetry (docs/telemetry.md) -------------------------------------

    async def metrics_prometheus(request):
        """Prometheus text exposition of the process-global registry
        (``telemetry/export.py``) — scrape target for a Prometheus/
        VictoriaMetrics agent; one registry per host controller."""
        from ..telemetry import REGISTRY
        from ..telemetry.export import render_prometheus

        return web.Response(text=render_prometheus(REGISTRY.snapshot()),
                            content_type="text/plain", charset="utf-8")

    async def metrics_json(request):
        """Structured JSON form of the same snapshot (the dashboard's
        telemetry panel feed)."""
        from ..telemetry import REGISTRY
        from ..telemetry.export import render_json

        return web.json_response(render_json(REGISTRY.snapshot()))

    async def trace_tree(request):
        """Assembled span tree for a job: accepts a trace id (the
        orchestrator's exec_… id), a prompt id, or a tile job id. Spans
        from dispatched hosts join via the X-CDT-Trace header, so the
        master-side dispatch span and worker-side execution span share
        one trace."""
        from ..telemetry import SPAN_STORE

        job_id = request.match_info["job_id"]
        trace_id = SPAN_STORE.resolve(job_id)
        if trace_id is None:
            return web.json_response(
                {"error": f"no trace recorded for {job_id!r}"}, status=404)
        return web.json_response({
            "job_id": job_id,
            "trace_id": trace_id,
            "spans": SPAN_STORE.spans(trace_id),
            "tree": SPAN_STORE.tree(trace_id),
        })

    async def step_times(request):
        """Recent prompt durations — the step-time observability the
        reference's progress logs approximate."""
        hist = controller.queue.history
        recent = list(hist.items())[-50:]
        return web.json_response({"prompts": [
            {"prompt_id": pid, "status": h.get("status"),
             "duration_s": round(h.get("duration", 0.0), 3)}
            for pid, h in recent
        ]})

    async def sampling_progress(request):
        """Per-step progress of an in-flight sampling run (streamed out of
        the compiled scan via jax.debug.callback — the standalone
        equivalent of ComfyUI's executor progress hooks)."""
        pid = request.match_info["prompt_id"]
        snap = controller.progress.snapshot(pid)
        if snap is None:
            return web.json_response({"error": "unknown prompt"}, status=404)
        return web.json_response(snap)

    async def sampling_preview(request):
        """Live latent preview (linear latent→RGB approximation) of an
        in-flight run; 404 until the first step reports."""
        pid = request.match_info["prompt_id"]
        try:
            shard = int(request.query.get("shard", "0"))
        except ValueError:
            shard = 0
        png = controller.progress.preview_png(pid, shard)
        if png is None:
            return web.json_response({"error": "no preview yet"}, status=404)
        return web.Response(body=png, content_type="image/png")

    # --- shipped workflows --------------------------------------------------
    def _workflows_dir() -> Path:
        env = constants.WORKFLOWS_DIR.get()
        if env:
            return Path(env)
        # repo layout: workflows/ beside the package
        return Path(__file__).resolve().parents[2] / "workflows"

    async def list_workflows(request):
        d = _workflows_dir()
        names = sorted(p.stem for p in d.glob("*.json")) if d.is_dir() else []
        return web.json_response({"workflows": names})

    async def get_workflow(request):
        import json

        from ..utils.names import validate_name

        name = validate_name(request.match_info["name"], max_len=80)
        path = _workflows_dir() / f"{name}.json"
        if not path.is_file():
            return web.json_response(
                {"error": f"no workflow {name!r}"}, status=404)
        try:
            return web.json_response(json.loads(path.read_text()))
        except json.JSONDecodeError as e:
            return web.json_response(
                {"error": f"workflow {name!r} is invalid JSON: {e}"},
                status=500)

    async def object_info(request):
        """Node interface specs for the whole registry (the equivalent of
        ComfyUI's ``/object_info``, which the reference's graph-editor
        widgets read for free — here the dashboard's workflow parameter
        forms are generated from this, ``web/forms.js``)."""
        from ..graph.node import NODE_REGISTRY

        out = {}
        for name, cls in sorted(NODE_REGISTRY.items()):
            out[name] = {
                "required": dict(cls.INPUTS),
                "optional": dict(cls.OPTIONAL),
                "returns": list(cls.RETURNS),
                "output_node": bool(cls.OUTPUT_NODE),
                "category": cls.CATEGORY,
            }
        return web.json_response({"nodes": out})

    router.add_get("/distributed/object_info", object_info)
    router.add_get("/distributed/workflows", list_workflows)
    router.add_get("/distributed/workflows/{name}", get_workflow)
    router.add_get("/distributed/system_info", system_info)
    router.add_get("/distributed/network_info", network_info)
    router.add_get("/distributed/local_log", local_log)
    router.add_post("/distributed/profile/start", profile_start)
    router.add_post("/distributed/profile/stop", profile_stop)
    router.add_get("/distributed/memory_stats", memory_stats)
    router.add_get("/distributed/metrics", metrics_prometheus)
    router.add_get("/distributed/metrics.json", metrics_json)
    router.add_get("/distributed/trace/{job_id}", trace_tree)
    router.add_get("/distributed/step_times", step_times)
    router.add_get("/distributed/progress/{prompt_id}", sampling_progress)
    router.add_get("/distributed/preview/{prompt_id}", sampling_preview)
