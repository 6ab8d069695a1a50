#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one chip: the serving path
    python chip_smoke.py --chips 4  # one host of four: the mesh paths

**One chip.** This process never imports JAX: a chip belongs to one
process, and that process is the server. It starts ONE child,
``python -m comfyui_distributed_tpu serve``, with every path it writes
under ``chiprun_out/chip_smoke/``; waits for ``/distributed/health``; reads
the server's own device census; posts ``workflows/distributed-txt2img.json``
as shipped (SDXL preset at its published widths, 1024², 30 steps,
euler/karras, CFG; weights random from the registry's seed) once to compile
and three more times with other seeds; checks every saved image; prints
what ``/distributed/metrics.json`` says about weights, kernels, compiles
and the cache; and stops the child by pid.

**Four chips.** This process is the one that owns them. It runs only the
two default mesh placements and what each is compared with: SDXL seed
fan-out on ``dp=4`` against ``dp=1`` (image 0 bit-identical), and WAN-1.3B
t2v on ``sp=4`` ring attention against ``sp=1`` (within a tolerance set from
the dtype before the run).

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``,
with the device as JAX reports it. Anything short of that — a phase that
failed, a non-2xx answer, an exception in the server log, a child that
exits early, a device that is not a TPU — is a non-zero exit, and ``ok`` is
never true.
"""

from __future__ import annotations

import argparse
import contextlib
import copy
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

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"
WORKFLOW = ROOT / "workflows" / "distributed-txt2img.json"
SEEDS = (7, 11, 13, 17)          # the shipped seed first: it pays the compile
# SDXL's two self-attention sites at 1024² (64² and 32² tokens), as the
# tuning table keys them: the ones a Pallas tier must have served
SDXL_SELF_ATTENTION = ("h10.d64.q4096.kv4096.bf16",
                       "h20.d64.q1024.kv1024.bf16")


class SmokeFailure(Exception):
    """A phase of the smoke failed; the message says which and why."""


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# --- the serve child ---------------------------------------------------------


class Server:
    """The one child process and the HTTP calls made to it."""

    def __init__(self, popen: subprocess.Popen, port: int, log_path: Path):
        self.popen, self.port, self.log_path = popen, port, log_path

    def request(self, path: str, body: dict | None = None,
                timeout: float = 60.0, missing_ok: bool = False):
        """GET (or POST ``body``) and parse the JSON answer. A non-2xx
        status, a refused connection or a dead child is a failure — but
        for a 404 where ``missing_ok`` (history of an unfinished prompt),
        which answers None."""
        if self.popen.poll() is not None:
            raise SmokeFailure(
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
            raise SmokeFailure(f"{path} answered {e.code}: "
                               f"{e.read()[:500]!r}") from None

    def log_tail(self, lines: int = 30) -> str:
        text = self.log_path.read_text(errors="replace")
        return "\n".join(text.splitlines()[-lines:])


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@contextlib.contextmanager
def serve(out_dir: Path = OUT, boot_timeout: float = 300.0):
    """Start the serve child with all of its state under ``out_dir``,
    wait until it answers ``/distributed/health``, and stop it by pid on
    the way out."""
    if "jax" in sys.modules:
        raise SmokeFailure(
            "this process has imported JAX: a parent that touched JAX "
            "holds the chip, and the serve child then fails or hangs. "
            "Run the one-chip smoke from a process that stays off JAX.")
    if not (ROOT / "comfyui_distributed_tpu").is_dir():
        raise SmokeFailure(f"no comfyui_distributed_tpu package beside "
                           f"{Path(__file__).name}: nothing to start")
    shutil.rmtree(out_dir, ignore_errors=True)
    (out_dir / "input").mkdir(parents=True)
    port = free_port()
    env = dict(os.environ)
    # the checked-out tpu_cluster_config.json is git-ignored local state
    # and must not be read; results, logs and the CONTENT cache are this
    # run's own (a result served from an earlier run's disk cache would
    # prove nothing about the chip). The XLA cache is not redirected: it
    # lives where JAX_COMPILATION_CACHE_DIR or the checkout says.
    env.update(CDT_CONFIG_PATH=str(out_dir / "config.json"),
               CDT_INPUT_DIR=str(out_dir / "input"),
               CDT_OUTPUT_DIR=str(out_dir / "output"),
               CDT_LOG_DIR=str(out_dir / "logs"),
               CDT_CACHE_DIR=str(out_dir / "content_cache"),
               CDT_SHAPE_CATALOG=str(out_dir / "shape_catalog.json"),
               PYTHONUNBUFFERED="1")
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
                    raise SmokeFailure(
                        f"no answer from /distributed/health within "
                        f"{boot_timeout:.0f}s; end of the server log:\n"
                        f"{server.log_tail()}") from None
                time.sleep(0.5)
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
        f"({devices[0]['kind']}); compile cache {info['compile_cache_dir']}; "
        f"native codec {info['native_codec']}")
    return {"platform": devices[0]["platform"], "kind": devices[0]["kind"],
            "count": len(devices)}


# --- requests ----------------------------------------------------------------


def load_workflow(path: Path = WORKFLOW) -> dict:
    return json.loads(path.read_text())


def with_seed(workflow: dict, seed: int) -> dict:
    """The workflow as shipped but for the seed, and a file name of its
    own so that every request's image survives to be checked."""
    graph = copy.deepcopy(workflow)
    for node in graph.values():
        if node.get("class_type") == "DistributedSeed":
            node["inputs"]["seed"] = seed
        if node.get("class_type") == "SaveImage":
            node["inputs"]["filename_prefix"] = f"smoke_seed{seed}"
    return graph


def expected_size(workflow: dict) -> tuple[int, int]:
    sampler = next(n for n in workflow.values()
                   if n.get("class_type") == "TPUTxt2Img")
    return sampler["inputs"]["height"], sampler["inputs"]["width"]


def run_request(server: Server, graph: dict, timeout: float) -> float:
    """POST one prompt to ``/distributed/queue`` and poll its history to
    the end. Returns the wall time from submission to completion."""
    t0 = time.monotonic()
    answer = server.request("/distributed/queue", {"prompt": graph})
    if answer.get("node_errors"):
        raise SmokeFailure(f"queue rejected the prompt: {answer}")
    prompt_id = answer["prompt_id"]
    while True:
        entry = server.request(f"/distributed/history/{prompt_id}",
                               missing_ok=True) or {}
        status = entry.get("status")
        if status == "success":
            return time.monotonic() - t0
        if status is not None:
            raise SmokeFailure(f"prompt {prompt_id} ended {status!r}: "
                               f"{entry.get('error')}")
        if time.monotonic() - t0 > timeout:
            raise SmokeFailure(f"prompt {prompt_id} not finished after "
                               f"{timeout:.0f}s")
        time.sleep(0.1)


def read_image(path: Path, height: int, width: int):
    """Decode a saved PNG (stdlib + numpy: no JAX in this process) and
    hold it to what a generated image must be."""
    import numpy as np
    from PIL import Image

    if not path.is_file():
        raise SmokeFailure(f"no image at {path}")
    image = np.asarray(Image.open(path))
    if image.shape != (height, width, 3):
        raise SmokeFailure(f"{path.name}: shape {image.shape}, expected "
                           f"{(height, width, 3)}")
    if not np.isfinite(image.astype(np.float32)).all():
        raise SmokeFailure(f"{path.name}: non-finite pixels")
    if image.min() == image.max():
        raise SmokeFailure(f"{path.name}: constant image "
                           f"(every pixel {image.min()})")
    return image


def run_requests(server: Server, workflow: dict, seeds=SEEDS,
                 out_dir: Path = OUT, timeout: float = 900.0) -> list[float]:
    """One request to compile, then the rest one after another (so the
    run needs one R=1 program and no coalescence variant). Every image is
    checked; two seeds must differ."""
    import numpy as np

    height, width = expected_size(workflow)
    times, images = [], []
    for i, seed in enumerate(seeds):
        wall = run_request(server, with_seed(workflow, seed), timeout)
        image = read_image(
            out_dir / "output" / f"smoke_seed{seed}_00000.png", height, width)
        times.append(wall)
        images.append(image)
        say(f"request {i + 1}/{len(seeds)} seed {seed}: {wall:.2f} s wall"
            f"{' (includes compilation)' if i == 0 else ''}; image "
            f"{image.shape} mean {image.mean():.1f} std {image.std():.1f}")
    if np.array_equal(images[0], images[1]):
        raise SmokeFailure(f"seeds {seeds[0]} and {seeds[1]} gave the "
                           "same image")
    return times


# --- what the server measured ------------------------------------------------


def series(metrics: dict, name: str) -> list[dict]:
    return metrics.get(name, {}).get("series", [])


def report(server: Server, device: dict) -> None:
    """Print the server's own account of the run from
    ``/distributed/metrics.json`` and ``/distributed/memory_stats``, and
    fail where it contradicts what the run was meant to prove."""
    metrics = server.request("/distributed/metrics.json")["metrics"]

    for s in series(metrics, "cdt_model_weight_bytes"):
        lab = s["labels"]
        say(f"weights: model {lab['model']} held in {lab['dtype']}, "
            f"{s['value'] / 2**30:.2f} GiB resident; text tower "
            f"{lab['text_tower']}")
    for d in server.request("/distributed/memory_stats")["devices"]:
        stats = d["stats"] or {}
        say(f"device {d['id']} ({d['kind']}): "
            f"{stats.get('bytes_in_use', 0) / 2**30:.2f} GiB in use, peak "
            f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB of "
            f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB")

    selected = {s["labels"]["geometry"]: s["labels"]["tier"]
                for s in series(metrics, "cdt_attn_kernel_selected")}
    say("attention kernels: " + (", ".join(
        f"{g}={t}" for g, t in sorted(selected.items())) or "none recorded"))
    if device["platform"] == "tpu":
        # both of SDXL's self-attention sites stand at or past the packed
        # floors (ops/attention.policy_choice); `xla` (or nothing) there
        # means the dispatcher fell back
        for geometry in SDXL_SELF_ATTENTION:
            if selected.get(geometry) != "packed":
                raise SmokeFailure(
                    f"attention site {geometry}: the policy answers packed, "
                    f"the server served it with {selected.get(geometry)!r}")

    first = {s["labels"]["pipeline"]: s for s in
             series(metrics, "cdt_pipeline_compile_seconds")}
    for s in series(metrics, "cdt_pipeline_execute_seconds"):
        name = s["labels"]["pipeline"]
        c = first.get(name, {"count": 0, "sum": 0.0})
        say(f"program {name}: {c['count']} first calls in {c['sum']:.1f} s "
            f"(trace + compile + run), then {s['count']} calls in "
            f"{s['sum']:.2f} s")
    compiles = series(metrics, "cdt_xla_compile_seconds")
    if compiles:
        say(f"compiles: {compiles[0]['count']} executables in "
            f"{compiles[0]['sum']:.1f} s")
    cache = {s["labels"]["outcome"]: int(s["value"]) for s in
             series(metrics, "cdt_compile_cache_requests_total")}
    say(f"persistent cache: {cache.get('hit', 0)} hits, "
        f"{cache.get('miss', 0)} misses (written)")

    log_text = server.log_path.read_text(errors="replace")
    if "Traceback (most recent call last)" in log_text:
        raise SmokeFailure("an exception in the server log:\n"
                           + server.log_tail(40))


def verdict(device: dict | None, failure: str | None) -> tuple[str, int]:
    """The last line and the exit code. ``ok`` needs every phase to have
    passed AND a TPU under it."""
    ok = failure is None and device is not None \
        and device["platform"] == "tpu"
    return json.dumps({"ok": ok, "device": device}), 0 if ok else 1


def smoke_one_chip(workflow: dict | None = None, cpu_rehearsal: bool = False,
                   out_dir: Path = OUT) -> tuple[str, int]:
    """The default phase. The arguments are the test's, which hands over
    a tiny-preset copy of the workflow and lets the requests run off-chip;
    from the command line a device that is not a TPU ends the run before
    the first request (SDXL on a CPU proves nothing in any time worth
    waiting)."""
    device = failure = None
    try:
        with serve(out_dir) as server:
            device = census(server)
            if device["platform"] != "tpu" and not cpu_rehearsal:
                raise SmokeFailure(
                    f"JAX found no TPU (platform {device['platform']!r})")
            times = run_requests(server, workflow or load_workflow(),
                                 out_dir=out_dir)
            say("request wall times (s): "
                + ", ".join(f"{t:.2f}" for t in times))
            report(server, device)
    except SmokeFailure as e:
        failure = str(e)
        print(f"[chip_smoke] FAILED: {failure}", file=sys.stderr, flush=True)
    return verdict(device, failure)


# --- four chips --------------------------------------------------------------


def smoke_four_chips(n: int = 4, sdxl_preset: str = "sdxl",
                     image_hw: int = 1024, wan_tiny: bool = False
                     ) -> tuple[str, int]:
    """The mesh paths, in this process (it owns the chips; no child).
    The keyword arguments are the test's: a tiny geometry on virtual CPU
    devices."""
    device = failure = None
    try:
        import jax

        from comfyui_distributed_tpu.utils.compile_cache import \
            enable_compile_cache

        enable_compile_cache(min_compile_secs=0.0)
        devices = jax.devices()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        say(f"devices: {device}")
        if len(devices) < n:
            raise SmokeFailure(f"--chips {n} needs {n} devices, JAX found "
                               f"{len(devices)}")
        _dp_leg(n, sdxl_preset, image_hw)
        _sp_leg(n, wan_tiny)
    except SmokeFailure as e:
        failure = str(e)
        print(f"[chip_smoke] FAILED: {failure}", file=sys.stderr, flush=True)
    return verdict(device, failure)


def _placement_check(what: str, arrays, n: int) -> None:
    """Output and weight shards on ``n`` distinct devices, and every one
    of them used: code that has only seen virtual devices may put
    everything on the first."""
    import jax

    for name, array in arrays.items():
        on = {s.device for s in array.addressable_shards}
        if len(on) != n:
            raise SmokeFailure(f"{what}: {name} sits on {len(on)} "
                               f"device(s), expected {n}")
    peaks = []
    for d in jax.devices()[:n]:
        stats = d.memory_stats()
        if stats is None:              # the CPU backend of the rehearsal
            say(f"{what}: device {d.id} reports no memory statistics")
            continue
        peaks.append(stats["peak_bytes_in_use"])
        if not stats["peak_bytes_in_use"]:
            raise SmokeFailure(f"{what}: device {d.id} was never used "
                               "(peak_bytes_in_use is 0)")
    if peaks:
        say(f"{what}: peak bytes in use per device "
            + ", ".join(f"{p / 2**30:.2f} GiB" for p in peaks))


def _dp_leg(n: int, preset: str, hw: int, steps: int = 4) -> None:
    """The registry's SDXL bundle through plan_placement → mesh_for →
    pipe.generate on dp=n, and the same seed on dp=1: image 0 is the same
    image, bit for bit (the seed fan-out contract)."""
    import jax
    import numpy as np

    from comfyui_distributed_tpu.cluster.residency import bundle_bytes
    from comfyui_distributed_tpu.diffusion.pipeline import (GenerationSpec,
                                                            sdxl_adm)
    from comfyui_distributed_tpu.models.registry import ModelRegistry
    from comfyui_distributed_tpu.parallel import serving
    from comfyui_distributed_tpu.parallel.sharding import replicate

    bundle = ModelRegistry().get(preset)
    pipe = bundle.pipeline
    plan = serving.plan_placement(n, batch=n,
                                  param_bytes=bundle_bytes(bundle))
    if plan.strategy != "dp":
        raise SmokeFailure(f"seed fan-out expected the dp placement, the "
                           f"planner chose {plan.to_dict()}")
    mesh = serving.mesh_for(plan, jax.devices()[:n])
    mesh1 = serving.mesh_for(serving.plan_placement(1), jax.devices()[:1])
    say(f"dp leg: {preset} {hw}x{hw}, {steps} steps, placement "
        f"{plan.to_dict()}")

    ctx, pooled = bundle.text_encoder.encode(["a lighthouse at dawn"])
    unc, upooled = bundle.text_encoder.encode([""])
    y = uy = None
    if pipe.unet.config.adm_in_channels == 2816:
        y, uy = sdxl_adm(pooled, (hw, hw)), sdxl_adm(upooled, (hw, hw))
    spec = GenerationSpec(height=hw, width=hw, steps=steps,
                          guidance_scale=6.0)

    def run(m):
        t0 = time.monotonic()
        images = pipe.generate(m, spec, 7, ctx, unc, y, uy)
        images.block_until_ready()
        return images, time.monotonic() - t0

    # weights placed once, replicated over the mesh, as a server holds them
    pipe.unet_params = replicate(mesh, pipe.unet_params)
    fan, cold = run(mesh)
    fan, warm = run(mesh)
    say(f"dp={n}: {fan.shape} in {warm:.2f} s (first call {cold:.2f} s "
        "with compilation)")
    _placement_check(f"dp={n}", {
        "the image batch": fan,
        "a UNet weight": jax.tree_util.tree_leaves(pipe.unet_params)[0]}, n)
    if fan.shape != (n, hw, hw, 3):
        raise SmokeFailure(f"dp={n} gave {fan.shape}")
    fan = np.asarray(fan)
    if not np.isfinite(fan).all() or fan.min() == fan.max():
        raise SmokeFailure(f"dp={n}: images not finite or constant")
    if np.array_equal(fan[0], fan[1]):
        raise SmokeFailure(f"dp={n}: shards 0 and 1 drew the same image")

    pipe.unet_params = replicate(mesh1, pipe.unet_params)
    solo, _ = run(mesh1)
    solo = np.asarray(solo)
    differ = int((fan[0] != solo[0]).sum())
    say(f"dp={n} image 0 against dp=1: {differ} of {solo[0].size} values "
        f"differ, max |diff| {np.abs(fan[0] - solo[0]).max():.3g}")
    if differ:
        raise SmokeFailure(f"dp={n} image 0 is not bit-identical to dp=1")
    bundle.release_device()


def _sp_leg(n: int, tiny: bool, steps: int = 2) -> None:
    """WAN-1.3B t2v, one video over sp=n with the default ring
    collectives, against sp=1."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.diffusion.pipeline_video import (
        VideoPipeline, VideoSpec)
    from comfyui_distributed_tpu.models.wan import WanConfig, init_wan
    from comfyui_distributed_tpu.models.wan_vae import WanVAE3D, WanVAEConfig
    from comfyui_distributed_tpu.parallel import overlap, serving
    from comfyui_distributed_tpu.parallel.sharding import replicate

    if tiny:
        # 4x temporal compression like the real VAE, so 29 frames make 8
        cfg, ctx_len = WanConfig.tiny(), 16
        vae_cfg = WanVAEConfig.tiny(dim_mult=(1, 2, 2),
                                    temporal_downsample=(True, True))
        spec = VideoSpec(frames=29, height=16, width=16, steps=steps)
        dtype = None
    else:
        # 480x832 as the one-chip cell, but 29 frames: the sp path shards
        # LATENT frames, and 33 frames make 9, which four chips cannot
        # share. 29 make 8: 12 480 tokens, 3 120 a shard — not a multiple
        # of any block size, as 33 frames' 3 510 would not have been
        cfg, vae_cfg, ctx_len = WanConfig.wan_1_3b(), WanVAEConfig.wan(), 512
        spec = VideoSpec(frames=29, height=480, width=832, steps=steps)
        dtype = jnp.bfloat16
    plan = serving.plan_placement(n, batch=1, supports_sp=True)
    if plan.strategy != "sp" or not overlap.overlap_enabled():
        raise SmokeFailure(f"single-video latency expected the sp "
                           f"placement with ring collectives, got "
                           f"{plan.to_dict()}, overlap "
                           f"{overlap.overlap_enabled()}")
    mesh = serving.mesh_for(plan, jax.devices()[:n])
    mesh1 = serving.mesh_for(
        serving.PlacementPlan("sp", 1), jax.devices()[:1])

    vae = WanVAE3D(vae_cfg).init(jax.random.key(1), frames=5,
                                 image_hw=(vae_cfg.downscale * 4,) * 2)
    f_lat = vae_cfg.latent_frames(spec.padded_frames)
    model, params = init_wan(
        cfg, jax.random.key(0),
        sample_fhw=(f_lat, spec.height // vae_cfg.downscale,
                    spec.width // vae_cfg.downscale),
        context_len=ctx_len, param_dtype=dtype)
    pipe = VideoPipeline(model, params, vae)
    tokens = f_lat * (spec.height // vae_cfg.downscale // 2) \
        * (spec.width // vae_cfg.downscale // 2)
    compute = jnp.dtype(cfg.dtype)
    # set before the run, from the dtype: the two runs order their sums
    # differently (four partial softmaxes rotated round a ring against
    # one pass), and every later layer rounds to the compute dtype, so
    # they agree to some units of its precision per value on average; a
    # wrong shard order or a dropped hop is off by tenths
    # (bfloat16: eps 2^-7, so 0.031 and 0.25 on frames in [0, 1]; the
    # first run on four chips measured 0.0026 and 0.022)
    eps = float(jnp.finfo(compute).eps)
    tol_mean, tol_max = 4 * eps, 32 * eps
    say(f"sp leg: WAN {'tiny' if tiny else '1.3B'} t2v {spec.frames}x"
        f"{spec.height}x{spec.width}, {tokens} tokens, {tokens // n} a "
        f"shard, {steps} steps, compute dtype {compute.name}; placement "
        f"{plan.to_dict()}; tolerance on [0,1] frames: mean |diff| <= "
        f"{tol_mean:.3g}, max |diff| <= {tol_max:.3g}")
    ctx = jax.random.normal(jax.random.key(2), (1, ctx_len, cfg.text_dim))
    pooled = jnp.zeros((1, 16))

    def run(m):
        t0 = time.monotonic()
        video = pipe.generate_frames(m, spec, 7, ctx, pooled)
        video.block_until_ready()
        return video, time.monotonic() - t0

    pipe.dit_params = replicate(mesh, pipe.dit_params)
    ring, cold = run(mesh)
    ring, warm = run(mesh)
    say(f"sp={n}: {ring.shape} in {warm:.2f} s (first call {cold:.2f} s "
        "with compilation)")
    _placement_check(f"sp={n}", {
        "a DiT weight": jax.tree_util.tree_leaves(pipe.dit_params)[0]}, n)
    ring = np.asarray(ring, np.float32)
    if not np.isfinite(ring).all() or ring.min() == ring.max():
        raise SmokeFailure(f"sp={n}: frames not finite or constant")

    pipe.dit_params = replicate(mesh1, pipe.dit_params)
    solo, _ = run(mesh1)
    solo = np.asarray(solo, np.float32)
    if ring.shape != solo.shape:
        raise SmokeFailure(f"sp={n} gave {ring.shape}, sp=1 {solo.shape}")
    diff = np.abs(ring - solo)
    say(f"sp={n} against sp=1: mean |diff| {diff.mean():.3g}, max |diff| "
        f"{diff.max():.3g}")
    if diff.mean() > tol_mean or diff.max() > tol_max:
        raise SmokeFailure(f"sp={n} differs from sp=1 beyond the tolerance")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: the mesh paths on one four-chip host, in "
                             "this process; default 1: the serving path")
    args = parser.parse_args(argv)
    line, code = (smoke_four_chips() if args.chips == 4
                  else smoke_one_chip())
    print(line, flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
