"""The one ``serve`` child and the HTTP calls made to it.

Copied from ``chip_smoke.py`` (PR 21) so that the yardstick does not move
when that script does. This process never imports JAX: a chip belongs to
one process, and that process is the server.
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


class BenchFailure(Exception):
    """A phase of the run failed; the message says which and why."""


def say(msg: str) -> None:
    print(f"[cdtbench] {msg}", flush=True)


class Server:
    """The child process and the HTTP calls made to it."""

    def __init__(self, popen: subprocess.Popen, port: int, log_path: Path):
        self.popen, self.port, self.log_path = popen, port, log_path

    def request(self, path: str, body: dict | None = None,
                timeout: float = 60.0, missing_ok: bool = False):
        """GET (or POST ``body``) and parse the JSON answer. A non-2xx
        status, a refused connection or a dead child is a failure — but
        for a 404 where ``missing_ok`` (history of an unfinished prompt),
        which answers None."""
        if self.popen.poll() is not None:
            raise BenchFailure(
                f"the serve child exited early (code {self.popen.returncode}); "
                f"end of its log:\n{self.log_tail()}")
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}", data=data,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return json.loads(resp.read())
        except urllib.error.HTTPError as e:
            if e.code == 404 and missing_ok:
                return None
            raise BenchFailure(f"{path} answered {e.code}: "
                               f"{e.read()[:500]!r}") from None

    def log_tail(self, lines: int = 30) -> str:
        text = self.log_path.read_text(errors="replace")
        return "\n".join(text.splitlines()[-lines:])


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@contextlib.contextmanager
def serve(out_dir: Path, extra_env: dict | None = None,
          boot_timeout: float = 300.0):
    """Start the serve child with all of its state under ``out_dir``,
    wait until it answers ``/distributed/health``, and stop it by pid on
    the way out. ``extra_env`` is the configuration's own environment
    (documented ``CDT_*`` knobs); the XLA cache is not redirected: the
    child inherits ``JAX_COMPILATION_CACHE_DIR`` untouched, else the
    program keeps ``<checkout>/.cache/xla``."""
    if "jax" in sys.modules:
        raise BenchFailure(
            "this process has imported JAX: a parent that touched JAX "
            "holds the chip, and the serve child then fails or hangs")
    if not (ROOT / "comfyui_distributed_tpu").is_dir():
        raise BenchFailure("no comfyui_distributed_tpu package beside "
                           "cdtbench/: nothing to measure")
    shutil.rmtree(out_dir, ignore_errors=True)
    (out_dir / "input").mkdir(parents=True)
    port = free_port()
    env = dict(os.environ)
    env.update(CDT_CONFIG_PATH=str(out_dir / "config.json"),
               CDT_INPUT_DIR=str(out_dir / "input"),
               CDT_OUTPUT_DIR=str(out_dir / "output"),
               CDT_LOG_DIR=str(out_dir / "logs"),
               CDT_CACHE_DIR=str(out_dir / "content_cache"),
               CDT_SHAPE_CATALOG=str(out_dir / "shape_catalog.json"),
               CDT_PROFILE_DIR=str(out_dir / "profile"),
               PYTHONUNBUFFERED="1")
    env.update({k: str(v) for k, v in (extra_env or {}).items()})
    log_path = out_dir / "serve.log"
    with open(log_path, "wb") as log_file:
        popen = subprocess.Popen(
            [sys.executable, "-m", "comfyui_distributed_tpu", "serve",
             "--host", "127.0.0.1", "--port", str(port)],
            cwd=ROOT, env=env, stdout=log_file, stderr=subprocess.STDOUT)
    server = Server(popen, port, log_path)
    say(f"serve child pid {popen.pid} on port {port}, log {log_path}")
    try:
        deadline = time.monotonic() + boot_timeout
        while True:
            try:
                server.request("/distributed/health", timeout=5.0)
                break
            except (urllib.error.URLError, OSError):
                if time.monotonic() > deadline:
                    raise BenchFailure(
                        f"no answer from /distributed/health within "
                        f"{boot_timeout:.0f}s; end of the server log:\n"
                        f"{server.log_tail()}") from None
                time.sleep(0.25)
        yield server
    finally:
        popen.terminate()
        try:
            popen.wait(timeout=30)
        except subprocess.TimeoutExpired:
            popen.kill()
            popen.wait(timeout=30)
        say(f"serve child pid {popen.pid} stopped "
            f"(code {popen.returncode})")


def census(server: Server) -> dict:
    """Platform, kind and count from the server's own device census."""
    info = server.request("/distributed/system_info")
    devices = info["devices"]
    say(f"server census: {len(devices)} x {devices[0]['platform']} "
        f"({devices[0]['kind']}); compile cache {info['compile_cache_dir']}")
    return {"platform": devices[0]["platform"], "kind": devices[0]["kind"],
            "count": len(devices)}


def run_request(server: Server, graph: dict, timeout: float,
                poll_s: float = 0.01) -> dict:
    """POST one prompt to ``/distributed/queue`` and poll its history to
    the end. Answers the prompt id, the host-clock times of submission
    and completion (monotonic, and wall for the trace's clock) and the
    final status; raises nothing for a prompt that ends badly — the
    caller counts it as failed."""
    t0, w0 = time.monotonic(), time.time()
    record = {"posted": t0, "posted_wall": w0, "status": "error",
              "error": None, "prompt_id": None}
    try:
        answer = server.request("/distributed/queue", {"prompt": graph})
        if answer.get("node_errors") or not answer.get("prompt_id"):
            raise BenchFailure(f"queue rejected the prompt: {answer}")
        prompt_id = record["prompt_id"] = answer["prompt_id"]
        while True:
            entry = server.request(f"/distributed/history/{prompt_id}",
                                   missing_ok=True) or {}
            status = entry.get("status")
            if status is not None:
                record["status"] = status
                record["error"] = entry.get("error")
                break
            if time.monotonic() - t0 > timeout:
                record["status"] = "timeout"
                break
            time.sleep(poll_s)
    except (BenchFailure, urllib.error.URLError, OSError) as e:
        record["error"] = str(e)
    record["done"] = time.monotonic()
    record["done_wall"] = time.time()
    record["seconds"] = record["done"] - t0
    return record


def read_image(path: Path, height: int, width: int):
    """Decode a saved PNG (PIL + numpy: no JAX in this process) and hold
    it to what a generated image must be."""
    import numpy as np
    from PIL import Image

    if not path.is_file():
        raise BenchFailure(f"no image at {path}")
    image = np.asarray(Image.open(path))
    if image.shape != (height, width, 3):
        raise BenchFailure(f"{path.name}: shape {image.shape}, expected "
                           f"{(height, width, 3)}")
    if not np.isfinite(image.astype(np.float32)).all():
        raise BenchFailure(f"{path.name}: non-finite pixels")
    if image.min() == image.max():
        raise BenchFailure(f"{path.name}: constant image "
                           f"(every pixel {image.min()})")
    return image


def series(metrics: dict, name: str) -> list[dict]:
    return metrics.get(name, {}).get("series", [])
