#!/usr/bin/env python
"""Benchmark driver: SDXL-class txt2img throughput on the chip.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

Metric matches BASELINE.md: images/sec for SDXL 1024², 30 steps (per chip;
pod scaling multiplies by data-parallel width). The reference publishes no
numbers (BASELINE.json "published": {}), so ``vs_baseline`` falls back to
1.0 with an explicit ``vs_baseline_note`` when nothing is published.

One process, one backend: ``main()`` runs the workload in this process on
whatever JAX finds, and exits non-zero when that is not a TPU — a number
from a CPU run is not a result. The toy-shape CPU paths exist for the test
suite and are asked for explicitly with ``JAX_PLATFORMS=cpu``. This process
owns the chip; nothing here starts a child that needs it.

MFU comes from the analytic FLOP count of the whole generation program
divided by measured step time and chip peak (bf16); a chip that is not in
the peaks table is an error where MFU is requested, not a blank.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

# bf16 peak FLOP/s per chip, by device_kind substring (lowercase match).
_PEAK_BF16 = [
    ("v5 lite", 197e12),   # v5e reports "TPU v5 lite"
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6", 918e12),        # Trillium
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]


def _peak_flops(device_kind: str) -> float:
    """bf16 peak of one chip. An unknown kind raises: an MFU against a
    guessed peak is worse than none."""
    kind = device_kind.lower()
    for sub, peak in _PEAK_BF16:
        if sub in kind:
            return peak
    raise ValueError(
        f"no bf16 peak on record for device kind {device_kind!r}: add it "
        "to _PEAK_BF16 with its source before reporting MFU")


def _cost_analysis_flops(compiled) -> float | None:
    """Total FLOPs of the compiled program per XLA's cost model."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if ca:
            f = ca.get("flops")
            if f and f > 0:
                return float(f)
    except Exception:
        pass
    return None


def _enable_compile_cache() -> None:
    """Persistent XLA compilation cache via the ONE shared config path
    (``utils/compile_cache.enable_compile_cache`` — the same directory
    rule as the server and the warmup pass).
    ``min_compile_secs=0.0``: bench wants every program persisted."""
    from comfyui_distributed_tpu.utils.compile_cache import \
        enable_compile_cache

    enable_compile_cache(min_compile_secs=0.0)


def _analytic_flops(fn, *args, weights=None) -> float | None:
    """Analytic matmul+conv FLOPs of one ``fn(weights, *args)`` call via
    the jaxpr walk (``utils/flops.py``): the per-shard body is counted
    once = one CHIP's work. ``fn`` is a ``bind_weights`` wrapper
    (``.jitted``/``.weights``); pass ``weights`` to substitute abstract
    ShapeDtypeStructs (offload benches trace the equivalent resident
    program without materializing it). Diagnostics never sink a bench —
    failures return None."""
    try:
        from comfyui_distributed_tpu.utils.flops import estimate_flops

        w = fn.weights if weights is None else weights
        return estimate_flops(fn.jitted, w, *args)
    except Exception as e:
        print(f"[bench] analytic flops estimate failed: {e}", file=sys.stderr)
        return None


def _mfu_fields(per_chip_flops: float | None, median_s: float,
                on_accel: bool) -> dict:
    """Shared MFU accounting (r04 VERDICT weak #1: only the SDXL txt2img
    artifact carried ``mfu``): per-chip analytic FLOPs over the median
    wall-clock against the chip's bf16 peak. Emitted for every workload
    so regressions in any of them are visible release-over-release."""
    if not per_chip_flops:
        return {}
    import jax

    out = {
        "model_flops_per_chip": round(per_chip_flops),
        "flops_source": "analytic_jaxpr",
    }
    if on_accel:
        peak = _peak_flops(jax.devices()[0].device_kind)
        out["mfu"] = round(per_chip_flops / median_s / peak, 4)
        out["peak_flops_per_chip_bf16"] = peak
    return out


def _timed_runs(run_once, n_runs: int) -> tuple[list, float]:
    """Shared timing harness: run n times, return (sorted times, median)
    — one place for the measurement methodology (BASELINE protocol)."""
    times = []
    for i in range(n_runs):
        t0 = time.perf_counter()
        run_once(i)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times, times[len(times) // 2]


def run_benchmark(steps: int, runs: int | None) -> dict:
    """The actual measurement (single process, current JAX backend)."""
    import jax
    import jax.numpy as jnp

    _enable_compile_cache()
    platform = jax.devices()[0].platform
    on_accel = platform not in ("cpu",)

    from comfyui_distributed_tpu.diffusion.pipeline import (
        GenerationSpec, Txt2ImgPipeline, sdxl_adm)
    from comfyui_distributed_tpu.models.text import TextEncoder, TextEncoderConfig
    from comfyui_distributed_tpu.models.unet import UNetConfig, init_unet
    from comfyui_distributed_tpu.models.vae import AutoencoderKL, VAEConfig
    from comfyui_distributed_tpu.parallel import build_mesh

    if on_accel:
        # SDXL-base architecture, 1024² (latent 128²)
        unet_cfg = UNetConfig.sdxl()
        vae_cfg = VAEConfig.sdxl()
        text_cfg = TextEncoderConfig()
        spec = GenerationSpec(height=1024, width=1024, steps=steps,
                              guidance_scale=5.0, per_device_batch=1)
        lat_hw = (128, 128)
    else:
        unet_cfg = UNetConfig.tiny()
        vae_cfg = VAEConfig.tiny()
        text_cfg = TextEncoderConfig.tiny()
        spec = GenerationSpec(height=32, width=32, steps=steps,
                              guidance_scale=5.0, per_device_batch=1)
        lat_hw = (16, 16)

    key = jax.random.key(0)
    # bf16-resident weights on accel: halves per-step HBM weight traffic
    # (the UNet computes in bf16 regardless); cast fused into the init
    # program so the fp32 tree never fully materializes on device
    model, params = init_unet(
        unet_cfg, key, sample_shape=(*lat_hw, unet_cfg.in_channels),
        context_len=text_cfg.max_len,
        param_dtype=jnp.bfloat16 if on_accel else None)
    vae = AutoencoderKL(vae_cfg).init(
        jax.random.key(1),
        image_hw=(lat_hw[0] * vae_cfg.downscale, lat_hw[1] * vae_cfg.downscale))
    enc = TextEncoder(text_cfg).init(jax.random.key(2))
    pipe = Txt2ImgPipeline(model, params, vae)
    ctx, pooled = enc.encode(["benchmark prompt"])
    unc, upooled = enc.encode([""])

    n_dev = len(jax.devices())
    mesh = build_mesh({"dp": n_dev})

    y = uy = None
    if unet_cfg.adm_in_channels:
        if unet_cfg.adm_in_channels == 2816:
            y = sdxl_adm(pooled, (spec.height, spec.width))
            uy = sdxl_adm(upooled, (spec.height, spec.width))
        else:
            y = jnp.zeros((1, unet_cfg.adm_in_channels))
            uy = jnp.zeros_like(y)

    fn = pipe.generate_fn(mesh, spec)
    args = (jax.random.key(42), ctx, unc,
            y if y is not None else jnp.zeros((1, 1)),
            uy if uy is not None else jnp.zeros((1, 1)))

    # honesty flag for the cold-vs-warm fields below: the persistent
    # cache survives across runs BY DESIGN, so on a re-run the "cold"
    # compile below is really a cache load — the artifact says so
    # instead of overstating the delta
    from comfyui_distributed_tpu.utils.compile_cache import active_cache_dir

    cache_prepopulated = bool(os.listdir(active_cache_dir()))

    # compile (timed separately) + cost analysis for the MFU estimate.
    # Weights are explicit jit arguments (fn.weights) — passing them
    # through lower() keeps multi-GB params out of the lowered module.
    t0 = time.perf_counter()
    compiled = fn.jitted.lower(fn.weights, *args).compile()
    compile_s = time.perf_counter() - t0
    xla_flops = _cost_analysis_flops(compiled)

    # analytic matmul+conv count: XLA's TPU cost analysis drops conv
    # FLOPs that lower into custom fusions (~10× under for SDXL), which
    # would make the MFU figure meaningless. The jaxpr walk counts the
    # per-shard program (shard_map body once) = per-chip work.
    total_flops, flops_source = xla_flops, "xla_cost_analysis"
    try:
        from comfyui_distributed_tpu.utils.flops import estimate_flops

        # × n_dev: the walker counts the shard_map body once (= one
        # chip's work); the whole program runs it on every chip
        analytic = estimate_flops(fn.jitted, fn.weights, *args) * n_dev
        if analytic and (not xla_flops or analytic > xla_flops):
            total_flops, flops_source = analytic, "analytic_jaxpr"
    except Exception as e:  # diagnostics must never sink the benchmark
        print(f"[bench] analytic flops estimate failed: {e}",
              file=sys.stderr)

    # warm-restart probe (ISSUE 6): drop jax's in-memory executable
    # caches and AOT-compile the same program again — with the
    # persistent cache now populated this measures the cache-LOAD cost a
    # rolling restart pays, vs the full compile above. The gap is the
    # cold-start elimination win the warmup pass banks per shape.
    jax.clear_caches()
    t0 = time.perf_counter()
    fn.jitted.lower(fn.weights, *args).compile()
    warm_compile_s = time.perf_counter() - t0

    # warmup run (first execution pays allocator/init overhead)
    jax.block_until_ready(compiled(fn.weights, *args))

    # timed runs (median of 5 per protocol in BASELINE.md; 3 on cpu)
    runs = runs or (5 if on_accel else 3)
    times, median = _timed_runs(
        lambda i: jax.block_until_ready(compiled(fn.weights,
                                                 jax.random.key(i),
                                                 *args[1:])), runs)
    images = n_dev * spec.per_device_batch
    ips = images / median

    mfu = None
    flops_per_image = None
    peak = _peak_flops(jax.devices()[0].device_kind) if on_accel else None
    if total_flops:
        flops_per_image = total_flops / images
        if peak:
            mfu = total_flops / median / (peak * n_dev)

    baseline = None
    note = None
    try:
        with open(os.path.join(os.path.dirname(__file__), "BASELINE.json")) as f:
            baseline = json.load(f).get("published", {}).get("images_per_sec")
    except (OSError, json.JSONDecodeError):
        pass
    if baseline:
        vs = ips / baseline
    else:
        vs = 1.0
        note = "reference publishes no numbers (BASELINE.json published={})"

    result = {
        "metric": (f"sdxl_1024_{spec.steps}step_images_per_sec" if on_accel
                   else f"tiny_32_{spec.steps}step_images_per_sec_cpu"),
        "value": round(ips, 4),
        "unit": "images/sec",
        "vs_baseline": round(vs, 4),
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "devices": n_dev,
        "steps": spec.steps,
        "median_image_latency_s": round(median, 3),
        "median_step_time_s": round(median / spec.steps, 4),
        "compile_s": round(compile_s, 1),
        # cold vs warm-restart time-to-first-image: compile_s is the
        # cold path ONLY when compile_cache_prepopulated is false;
        # compile_warm_restart_s re-AOT-compiles after
        # jax.clear_caches() with the persistent cache populated — the
        # cost a restarted worker actually pays per shape
        "compile_cache_prepopulated": cache_prepopulated,
        "compile_warm_restart_s": round(warm_compile_s, 2),
        "ttfi_cold_s": round(compile_s + median, 2),
        "ttfi_warm_restart_s": round(warm_compile_s + median, 2),
        "run_times_s": [round(t, 3) for t in times],
    }
    if note:
        result["vs_baseline_note"] = note
    if flops_per_image:
        result["model_flops_per_image"] = round(flops_per_image)
        result["flops_source"] = flops_source
    if mfu is not None:
        result["mfu"] = round(mfu, 4)
        result["peak_flops_per_chip_bf16"] = peak
    return result


def run_usdu_benchmark(steps: int, runs: int | None) -> dict:
    """BASELINE's second headline: 4K Ultimate-SD-Upscale wall-clock
    (1024² → 4096², 512² tiles sharded over the mesh; tiny shapes on CPU)."""
    import jax
    import jax.numpy as jnp

    _enable_compile_cache()
    platform = jax.devices()[0].platform
    on_accel = platform not in ("cpu",)

    from comfyui_distributed_tpu.diffusion.pipeline import Txt2ImgPipeline
    from comfyui_distributed_tpu.models.text import TextEncoder, TextEncoderConfig
    from comfyui_distributed_tpu.models.unet import UNetConfig, init_unet
    from comfyui_distributed_tpu.models.vae import AutoencoderKL, VAEConfig
    from comfyui_distributed_tpu.parallel import build_mesh
    from comfyui_distributed_tpu.tiles.engine import TileUpscaler, UpscaleSpec

    if on_accel:
        unet_cfg, vae_cfg, text_cfg = (UNetConfig.sdxl(), VAEConfig.sdxl(),
                                       TextEncoderConfig())
        src_hw, lat_hw = (1024, 1024), (128, 128)
        spec = UpscaleSpec(scale=4.0, tile_w=512, tile_h=512, padding=32,
                           steps=steps, denoise=0.3, guidance_scale=5.0)
    else:
        unet_cfg, vae_cfg, text_cfg = (UNetConfig.tiny(), VAEConfig.tiny(),
                                       TextEncoderConfig.tiny())
        src_hw, lat_hw = (32, 32), (16, 16)
        spec = UpscaleSpec(scale=2.0, tile_w=32, tile_h=32, padding=4,
                           steps=min(steps, 4), denoise=0.3,
                           guidance_scale=1.0)

    model, params = init_unet(
        unet_cfg, jax.random.key(0),
        sample_shape=(*lat_hw, unet_cfg.in_channels),
        context_len=text_cfg.max_len,
        param_dtype=jnp.bfloat16 if on_accel else None)
    vae = AutoencoderKL(vae_cfg).init(
        jax.random.key(1),
        image_hw=(lat_hw[0] * vae_cfg.downscale, lat_hw[1] * vae_cfg.downscale))
    enc = TextEncoder(text_cfg).init(jax.random.key(2))
    pipe = Txt2ImgPipeline(model, params, vae)
    ctx, _ = enc.encode(["benchmark prompt"])
    unc, _ = enc.encode([""])

    n_dev = len(jax.devices())
    mesh = build_mesh({"dp": n_dev})
    ups = TileUpscaler(pipe)
    image = jax.random.uniform(jax.random.key(3), (1, *src_hw, 3))

    if on_accel:
        # Chunked farm path: the single-program engine batches ALL tiles
        # in one XLA program — right for a pod (tiles shard over chips),
        # an instant OOM for 64 4K-tiles on ONE chip. range_plan processes
        # `chunk = n_devices × tiles_per_device` tiles per dispatch (r04:
        # batching 8 tiles/device + async dispatch/fetch overlap cut the
        # 4K wall-clock 53.3 → 27.9 s — fewer dispatch RTTs, fuller MXU
        # at 512² tile shapes, transfers hidden behind compute; the
        # batch sweep plateaus from 4 through 16, 32 blows the compile
        # budget), exactly how the cross-host tile farm drives a host
        # (cluster/tile_farm.py).
        import numpy as _np

        plan = ups.range_plan(mesh, image[0], spec, 7, ctx, unc)
        T = plan.num_tiles

        def full_pass():
            # one wide range: run_range loops the compiled fixed-chunk
            # program internally, dispatching every sub-chunk before
            # fetching any result (compute/transfer overlap)
            tiles = plan.run_range(0, T)
            return jax.block_until_ready(ups.composite(tiles, plan))

        t0 = time.perf_counter()
        out = full_pass()                 # first pass pays the compile
        compile_s = time.perf_counter() - t0
        runs = runs or 2
        times, median = _timed_runs(lambda i: full_pass(), runs)
        # USEFUL-work MFU: fractional dispatches (T/chunk) so pad tiles
        # in a partial last chunk count as overhead, not work
        mfu_extra = {}
        if plan.flops_per_dispatch is not None:
            try:
                per_disp = plan.flops_per_dispatch()
            except Exception as e:   # diagnostics never sink a bench
                print(f"[bench] usdu flops estimate failed: {e}",
                      file=sys.stderr)
                per_disp = None
            if per_disp:
                mfu_extra = _mfu_fields(per_disp * (T / plan.chunk),
                                        median, on_accel)
                mfu_extra["tiles_per_sec"] = round(T / median, 2)
    else:
        mfu_extra = {}
        t0 = time.perf_counter()
        out = jax.block_until_ready(
            ups.upscale(mesh, image, spec, 7, ctx, unc))
        compile_s = time.perf_counter() - t0

        runs = runs or 2
        times, median = _timed_runs(
            lambda i: jax.block_until_ready(
                ups.upscale(mesh, image, spec, i, ctx, unc)), runs)
    grid = ups.grid_for(src_hw[0], src_hw[1], spec)

    return {
        **mfu_extra,
        "metric": ("sdxl_usdu_4k_wall_clock_s" if on_accel
                   else "tiny_usdu_wall_clock_s_cpu"),
        "value": round(median, 3),
        "unit": "seconds",
        "vs_baseline": 1.0,
        "vs_baseline_note": "reference publishes no numbers",
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "devices": n_dev,
        "steps": spec.steps,
        "tiles": grid.num_tiles,
        "output_hw": [int(src_hw[0] * spec.scale), int(src_hw[1] * spec.scale)],
        "compile_s": round(compile_s, 1),
        "run_times_s": [round(t, 3) for t in times],
    }


def run_flux_benchmark(steps: int, runs: int | None) -> dict:
    """BASELINE row 3: FLUX-class flow txt2img 1024². Full FLUX.1 is 12B
    params (24 GB bf16) — more than one v5e chip's 16 GB HBM. Default on
    accelerators: FULL depth with host-offloaded block streaming
    (``diffusion/offload.py``; CDT_OFFLOAD_RESIDENT_GB caps HBM
    residency). CDT_OFFLOAD=0 falls back to the bf16-resident half-depth
    surrogate; pods run dp×tp (``generate_tp_fn``, dry-run validated)."""
    import jax
    import jax.numpy as jnp

    _enable_compile_cache()
    platform = jax.devices()[0].platform
    on_accel = platform not in ("cpu",)

    from comfyui_distributed_tpu.diffusion.pipeline_flow import (
        FlowPipeline, FlowSpec)
    from comfyui_distributed_tpu.models.dit import DiTConfig, init_dit
    from comfyui_distributed_tpu.models.vae import AutoencoderKL, VAEConfig
    from comfyui_distributed_tpu.parallel import build_mesh

    if on_accel:
        from comfyui_distributed_tpu.diffusion.offload import offload_enabled

        if offload_enabled(default=True):   # full depth needs streaming
            return _run_flux_offloaded(steps, runs, platform)

    half_depth = False
    if on_accel:
        import dataclasses as _dc

        cfg = _dc.replace(DiTConfig.flux(), depth_double=10, depth_single=19)
        half_depth = True
        vae_cfg = VAEConfig(latent_channels=16, scaling_factor=0.3611,
                            shift_factor=0.1159)
        hw, lat_hw, ctx_len = (1024, 1024), (128, 128), 512
    else:
        cfg = DiTConfig.tiny(pos_embed="rope")
        vae_cfg = VAEConfig.tiny()
        hw, lat_hw, ctx_len = (32, 32), (16, 16), 16

    model, params = init_dit(cfg, jax.random.key(0), sample_hw=lat_hw,
                             context_len=ctx_len,
                             param_dtype=jnp.bfloat16 if on_accel else None)
    vae = AutoencoderKL(vae_cfg).init(
        jax.random.key(1),
        image_hw=(lat_hw[0] * vae_cfg.downscale,
                  lat_hw[1] * vae_cfg.downscale))
    pipe = FlowPipeline(model, params, vae)
    n_dev = len(jax.devices())
    mesh = build_mesh({"dp": n_dev})
    spec = FlowSpec(height=hw[0], width=hw[1], steps=steps)
    ctx = jnp.zeros((1, ctx_len, cfg.context_dim))
    pooled = jnp.zeros((1, cfg.pooled_dim))

    fn = pipe.generate_fn(mesh, spec)
    t0 = time.perf_counter()
    jax.block_until_ready(fn(jax.random.key(0), ctx, pooled))
    compile_s = time.perf_counter() - t0

    runs = runs or (5 if on_accel else 3)
    times, median = _timed_runs(
        lambda i: jax.block_until_ready(
            fn(jax.random.key(i + 1), ctx, pooled)), runs)
    mfu_extra = _mfu_fields(
        _analytic_flops(fn, jax.random.key(0), ctx, pooled),
        median, on_accel)
    out = {
        **mfu_extra,
        "metric": (f"flux_half_depth_1024_{steps}step_images_per_sec"
                   if on_accel
                   else f"flux_tiny_{steps}step_images_per_sec_cpu"),
        "value": round(n_dev / median, 4),
        "unit": "images/sec",
        "vs_baseline": 1.0,
        "vs_baseline_note": "reference publishes no numbers",
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "devices": n_dev, "steps": steps,
        "median_image_latency_s": round(median, 3),
        "compile_s": round(compile_s, 1),
        "run_times_s": [round(t, 3) for t in times],
    }
    if half_depth:
        out["note"] = ("full FLUX.1 (12B) exceeds one v5e chip's HBM; "
                       "pod runs use dp×tp (generate_tp_fn). This measures "
                       "the architecture at depth 10/19, bf16-resident "
                       "(CDT_OFFLOAD=0 fallback — the default flux metric "
                       "is full depth via host offload).")
    return out


def _rss_gb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS"):
                return int(line.split()[1]) / 1e6
    return 0.0


def _mem_available_gb() -> float:
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemAvailable"):
                return int(line.split()[1]) / 1e6
    return 0.0


def _probe_h2d_leak(dev) -> tuple[float, float]:
    """Warm host→device bandwidth + RSS-leak ratio of ONE 256 MB put: a
    transport that keeps a host copy of every device_put for the process
    lifetime shows as RSS growth per byte put (a local chip measures ~0).
    Shared by every offload bench."""
    import numpy as np

    import jax

    probe = np.ones((64, 1024, 1024), np.float32)      # 256 MB
    a = jax.device_put(probe, dev)
    a.block_until_ready()
    a.delete()
    rss0 = _rss_gb()
    t0 = time.perf_counter()
    b = jax.device_put(probe, dev)
    b.block_until_ready()
    h2d_gbps = 0.25 / (time.perf_counter() - t0)
    b.delete()
    leak_ratio = max(0.0, (_rss_gb() - rss0) / 0.25)
    del probe, a, b
    return h2d_gbps, leak_ratio


def _affordable_forwards_or_raise(leak_ratio: float, param_bytes: int,
                                  resident_bytes: int,
                                  streamed_gb: float) -> float:
    """Host-RAM budget under the put-leak, checked BEFORE any multi-GB
    build: leave a 12 GB floor, reserve the flat block copies
    (~param_bytes) and the leaked resident upload; the remainder funds
    streamed forwards. Returns the affordable forward count (``inf``
    when the transport doesn't leak or nothing streams); raises rather
    than starting a run that would OOM the host. ONE budget model for
    every offload bench (flux, wan14b)."""
    if leak_ratio <= 0.5:
        return float("inf")
    headroom = max(0.0, _mem_available_gb() - 12.0 - param_bytes / 1e9)
    upload_need = resident_bytes / 1e9 * (1.0 + leak_ratio)
    if headroom < upload_need:
        raise RuntimeError(
            f"offload bench: transfer leak ({leak_ratio:.2f} GB RSS/GB)"
            f" and only {_mem_available_gb():.0f} GB available — the "
            f"{upload_need:.0f} GB resident upload itself would OOM the"
            " host; refusing to start")
    if streamed_gb <= 0.05:
        return float("inf")
    fwds = (headroom - upload_need) / max(streamed_gb, 0.5)
    if fwds < 2:                             # can't even warmup + 1 step
        raise RuntimeError(
            f"offload bench: transfer leak ({leak_ratio:.2f} GB RSS/GB)"
            f" and only {_mem_available_gb():.0f} GB available — fewer "
            "than 2 affordable forwards; refusing to start a run that "
            "would OOM the host")
    return fwds


def _extrapolate_steps(lat1: float, s1: int, lat2: float, s2: int,
                       steps: int) -> tuple[float, float, dict]:
    """Two-point per-step linear extrapolation (exact for the offload
    ladders: every step streams identical bytes and runs the same
    compiled program). Returns (median, per_step, derivation)."""
    if s2 != s1:
        per_step = (lat2 - lat1) / (s2 - s1)
        overhead = max(0.0, lat1 - per_step * s1)
    else:                                    # tightest budget: conservative
        per_step, overhead = lat1 / s1, 0.0
    median = overhead + per_step * steps
    return median, per_step, {
        "derived": True,
        "measured_steps": [s1, s2],
        "measured_latencies_s": [round(lat1, 2), round(lat2, 2)],
        "fixed_overhead_s": round(overhead, 2),
        "method": ("per-step linear extrapolation: every step streams "
                   "identical bytes and runs the same compiled "
                   "program(s)"),
    }


def _run_flux_offloaded(steps: int, runs: int | None, platform: str) -> dict:
    """FULL-depth FLUX.1 (19/38, 12B params) on ONE chip (VERDICT r3
    item #2 — replaces the half-depth surrogate). Under the default fp8
    stream dtype the quantized block set fits HBM-resident: one upload,
    zero bytes streamed per step, one scanned program per forward —
    compute-bound. Under
    CDT_OFFLOAD_STREAM_DTYPE=native, exact bf16 blocks stream per step
    with double-buffered prefetch; the raw host→device bandwidth is
    measured so the transport share of the step time is explicit.

    TRANSFER-LEAK AWARENESS (r04): a 30-step full-depth image streams
    ~420 GB, so a transport that retains a host-side copy of every
    ``device_put`` (``scripts/offload_rss_probe.py`` measures it) would
    OOM the host mid-run. The bench probes for the leak; when present it
    measures full-depth steady-state latency at two small step counts
    that fit the RAM budget and derives the requested-step latency from
    the exact per-step linearity of the python-level euler ladder (every
    step streams the same bytes and runs the same two compiled block
    programs — there is no cross-step amortization to mis-extrapolate).
    Where the probe measures no leak (a local chip) the full run
    executes directly."""
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.diffusion.offload import (
        materialize_host_params, resident_budget_bytes, tree_bytes)
    from comfyui_distributed_tpu.diffusion.pipeline_flow import (
        FlowPipeline, FlowSpec)
    from comfyui_distributed_tpu.models.dit import DiTConfig, init_dit
    from comfyui_distributed_tpu.models.vae import AutoencoderKL, VAEConfig

    cfg = DiTConfig.flux()            # FULL depth: 19 double / 38 single
    lat_hw, ctx_len = (128, 128), 512
    print("[bench] flux-offload: materializing 12B host params",
          file=sys.stderr, flush=True)
    model, abstract = init_dit(cfg, jax.random.key(0), sample_hw=lat_hw,
                               context_len=ctx_len, abstract=True,
                               param_dtype=jnp.bfloat16)
    params = materialize_host_params(abstract, seed=0)
    param_bytes = tree_bytes(params)

    dev = jax.devices()[0]
    h2d_gbps, leak_ratio = _probe_h2d_leak(dev)
    leak = leak_ratio > 0.5

    print("[bench] flux-offload: building pipeline", file=sys.stderr,
          flush=True)
    vae_cfg = VAEConfig(latent_channels=16, scaling_factor=0.3611,
                        shift_factor=0.1159)
    vae = AutoencoderKL(vae_cfg).init(
        jax.random.key(1), image_hw=(1024, 1024))
    # PLAN placement from shapes alone BEFORE any multi-GB build: the
    # leak RAM-budget guard below must be able to refuse a run that
    # would OOM the host without first paying the upload
    from comfyui_distributed_tpu.diffusion.offload import plan_offload
    plan = plan_offload(params, resident_budget_bytes())
    streamed = plan["streamed_bytes"]
    streamed_gb = max(0.5, streamed / 1e9)

    # TOTAL forwards this process can afford under the leak, computed
    # ONCE, before the executor exists (afterwards MemAvailable already
    # reflects the ~param_bytes of flat copies the build allocates —
    # recomputing would double-count them): leave a 12 GB floor so the
    # host never OOMs again, and reserve the flat block copies
    # (~param_bytes of host numpy).
    budget_fwds = _affordable_forwards_or_raise(
        leak_ratio, param_bytes, plan["resident_bytes"],
        streamed_gb if streamed > 0 else 0.0)

    # the PRODUCT path end-to-end: generate_offloaded builds + caches the
    # streamed executor, so the bench measures exactly what users run.
    # Under the default fp8 stream dtype the quantized block set fits
    # HBM resident, the forward is one scanned program and NOTHING
    # streams per step — the leak-budget derivation below only applies
    # while per-step streaming remains.
    pipe = FlowPipeline(model, params, vae)
    ctx = jnp.zeros((1, ctx_len, cfg.context_dim))
    pooled = jnp.zeros((1, cfg.pooled_dim))
    print("[bench] flux-offload: quantizing + uploading resident set",
          file=sys.stderr, flush=True)
    t0 = time.perf_counter()
    off = pipe.offload_executor(resident_bytes=resident_budget_bytes())
    upload_s = time.perf_counter() - t0
    streamed = tree_bytes(off.streamed) if off.streamed else 0
    print(f"[bench] flux-offload: stream_dtype={off.stream_dtype} "
          f"resident={off.resident_bytes/1e9:.1f} GB "
          f"streamed/step={streamed/1e9:.1f} GB "
          f"(upload {upload_s:.0f}s)", file=sys.stderr, flush=True)

    def one_image(seed, n_steps):
        spec = FlowSpec(height=1024, width=1024, steps=n_steps)
        t0 = time.perf_counter()
        jax.block_until_ready(pipe.generate_offloaded(
            spec, seed, ctx, pooled,
            resident_bytes=resident_budget_bytes()))
        return time.perf_counter() - t0

    if leak and streamed > 0:
        for s1, s2 in ((1, 3), (1, 2), (1, 1)):
            if 1 + s1 + s2 <= budget_fwds:   # + 1-step warmup image
                break
        else:
            s1 = s2 = 1                      # budget 2: warmup + ONE timed
                                             # image; overhead folded into
                                             # per_step (conservative)
        print(f"[bench] flux-offload: transfer leak detected "
              f"({leak_ratio:.2f} GB RSS per GB streamed) — measuring "
              f"steps {s1} and {s2} within a {budget_fwds}-forward RAM "
              f"budget, deriving the {steps}-step latency",
              file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        one_image(0, 1)                   # warmup: compiles all programs
        compile_s = time.perf_counter() - t0
        lat1 = one_image(1, s1)
        lat2 = one_image(2, s2) if s2 != s1 else lat1
        median, per_step, derivation = _extrapolate_steps(
            lat1, s1, lat2, s2, steps)
        times = [lat1, lat2]
    else:
        print("[bench] flux-offload: warmup image (compiles + first "
              "stream)", file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        one_image(0, steps)
        compile_s = time.perf_counter() - t0
        runs = runs or (3 if streamed == 0 else 2)
        print(f"[bench] flux-offload: {runs} timed runs", file=sys.stderr,
              flush=True)
        times, median = _timed_runs(lambda i: one_image(i + 1, steps), runs)
        per_step = median / steps
        derivation = {"derived": False}

    # analytic FLOPs of the EQUIVALENT resident program (same model, same
    # step count; the offload executor runs the same math through block
    # programs) — traced with abstract weights so the 24 GB tree is
    # never duplicated
    from comfyui_distributed_tpu.parallel import build_mesh
    mfu_extra = {}
    try:
        fn_ref = pipe.generate_fn(
            build_mesh({"dp": 1}),
            FlowSpec(height=1024, width=1024, steps=steps))
        struct_w = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            fn_ref.weights)
        mfu_extra = _mfu_fields(
            _analytic_flops(fn_ref, jax.random.key(0), ctx, pooled,
                            weights=struct_w),
            median, True)
    except Exception as e:
        print(f"[bench] flux-offload mfu estimate failed: {e}",
              file=sys.stderr)

    return {
        **mfu_extra,
        "metric": f"flux_full_depth_offload_1024_{steps}step_images_per_sec",
        "value": round(1.0 / median, 5),
        "unit": "images/sec",
        "vs_baseline": 1.0,
        "vs_baseline_note": "reference publishes no numbers",
        "platform": platform,
        "device_kind": dev.device_kind,
        "devices": 1, "steps": steps,
        "median_image_latency_s": round(median, 2),
        "per_step_s": round(per_step, 2),
        "compile_s": round(compile_s, 1),
        "run_times_s": [round(t, 2) for t in times],
        "param_bytes": param_bytes,
        "resident_bytes": off.resident_bytes,
        "streamed_bytes_per_step": streamed,
        "stream_dtype": off.stream_dtype,
        "quantization": ("weights-only per-output-channel absmax "
                         "float8_e4m3fn (kernels only; biases/norms/"
                         "qk-scales exact)" if off.stream_dtype
                         != "native" else None),
        "fully_resident": bool(off.stacked),
        "weight_upload_s": round(upload_s, 1),
        "host_to_device_gbps": round(h2d_gbps, 2),
        "transfer_leak_gb_per_gb": round(leak_ratio, 2),
        **derivation,
        "note": ("FULL FLUX.1 depth (19/38, ~12B params) on one chip: "
                 "under the default fp8 stream dtype the quantized "
                 "block set lives HBM-resident (one upload, zero bytes "
                 "streamed per step, one scanned program per forward); "
                 "CDT_OFFLOAD_STREAM_DTYPE=native restores exact bf16 "
                 "block streaming, which moves streamed_bytes_per_step "
                 "over host_to_device_gbps every step."),
    }


def _run_wan_like(steps: int, runs: int | None, moe: bool) -> dict:
    """Shared body of the ``wan`` / ``wan22`` workloads: identical
    geometry, pipeline construction, timing protocol, and result shape,
    so (wan22 − wan) isolates exactly the dual-expert switch."""
    import jax
    import jax.numpy as jnp

    _enable_compile_cache()
    platform = jax.devices()[0].platform
    on_accel = platform not in ("cpu",)

    from comfyui_distributed_tpu.diffusion.pipeline_video import (
        VideoPipeline, VideoSpec)
    from comfyui_distributed_tpu.models.wan import WanConfig, init_wan
    from comfyui_distributed_tpu.models.wan_vae import (WanVAE3D,
                                                        WanVAEConfig)
    from comfyui_distributed_tpu.parallel import build_mesh

    if on_accel:
        # 1.3B-class config fits one v5e chip; 14B needs tp over a pod
        cfg, vae_cfg = WanConfig.wan_1_3b(), WanVAEConfig.wan()
        spec = VideoSpec(frames=33, height=480, width=832, steps=steps)
        ctx_len = 512
    else:
        cfg, vae_cfg = WanConfig.tiny(), WanVAEConfig.tiny()
        spec = VideoSpec(frames=5, height=16, width=16,
                         steps=min(steps, 2))
        ctx_len = 16

    n_dev = len(jax.devices())
    mesh = build_mesh({"dp": n_dev})
    vae = WanVAE3D(vae_cfg).init(jax.random.key(1), frames=5,
                                 image_hw=(vae_cfg.downscale * 4,) * 2)
    f_lat = vae_cfg.latent_frames(spec.padded_frames)
    sample_fhw = (f_lat, spec.height // vae_cfg.downscale,
                  spec.width // vae_cfg.downscale)
    dt = jnp.bfloat16 if on_accel else None
    model, params = init_wan(cfg, jax.random.key(0),
                             sample_fhw=sample_fhw,
                             context_len=ctx_len, param_dtype=dt)
    if moe:
        _, params_low = init_wan(cfg, jax.random.key(7),
                                 sample_fhw=sample_fhw,
                                 context_len=ctx_len, param_dtype=dt)
        pipe = VideoPipeline(model, params, vae,
                             dit_params_low=params_low,
                             expert_boundary=0.875)
        assert pipe.is_moe
    else:
        pipe = VideoPipeline(model, params, vae)
    ctx = jnp.zeros((1, ctx_len, cfg.text_dim))
    pooled = jnp.zeros((1, 16))

    fn = pipe.generate_fn(mesh, spec)
    t0 = time.perf_counter()
    jax.block_until_ready(fn(jax.random.key(0), ctx, pooled))
    compile_s = time.perf_counter() - t0

    runs = runs or (3 if on_accel else 2)
    times, median = _timed_runs(
        lambda i: jax.block_until_ready(
            fn(jax.random.key(i + 1), ctx, pooled)), runs)
    mfu_extra = _mfu_fields(
        _analytic_flops(fn, jax.random.key(0), ctx, pooled),
        median, on_accel)
    if moe:
        metric = ("wan22_moe_t2v_480p_33f_wall_clock_s" if on_accel
                  else "wan22_moe_tiny_t2v_wall_clock_s_cpu")
    else:
        metric = ("wan_t2v_480p_33f_wall_clock_s" if on_accel
                  else "wan_tiny_t2v_wall_clock_s_cpu")
    out = {
        **mfu_extra,
        "metric": metric,
        "value": round(median, 3),
        "unit": "seconds",
        "vs_baseline": 1.0,
        "vs_baseline_note": "reference publishes no numbers",
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "devices": n_dev, "steps": spec.steps,
        "frames": spec.padded_frames, "latent_frames": f_lat,
        "compile_s": round(compile_s, 1),
        "run_times_s": [round(t, 3) for t in times],
    }
    if moe:
        out["expert_boundary"] = 0.875
    return out


def run_wan_benchmark(steps: int, runs: int | None) -> dict:
    """BASELINE row 4: WAN t2v end-to-end (exact architecture over the 3D
    causal VAE; 33 frames 480×832 on accel, tiny shapes on CPU)."""
    return _run_wan_like(steps, runs, moe=False)


def run_wan14b_benchmark(steps: int, runs: int | None) -> dict:
    """WAN-2.1 **14B** t2v on ONE chip via the quantized offload
    executor (``diffusion/offload.OffloadedWan``) — the capability
    artifact for 'a 28 GB-bf16 expert on a 16 GB chip'. fp8(e4m3)
    residency holds ≥90% of the blocks in HBM (13 GB default budget);
    the overflow streams per step, so where the put-leak probe finds a
    leaky transport the latency is measured at two small step counts
    and extrapolated per-step (exact: the ladder streams identical
    bytes and runs the same program every step).

    Measured bound (r04, one 16 GB v5e): ≥12.4 GB resident OOMs at
    runtime (both ladder modes; the 33f×480×832 = 14k-token activations
    at dim 5120 need more headroom than residency leaves), so the
    budget must stay ≤11 GB and the overflow streams. No artifact of
    this workload exists yet (ROADMAP R1); the CPU tier and
    `tests/test_offload.py` keep the code path exercised meanwhile."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    _enable_compile_cache()
    platform = jax.devices()[0].platform
    on_accel = platform not in ("cpu",)

    from comfyui_distributed_tpu.diffusion.offload import (
        materialize_host_params, plan_offload, resident_budget_bytes,
        tree_bytes, _WAN_GLUE_KEYS)
    from comfyui_distributed_tpu.diffusion.pipeline_video import (
        VideoPipeline, VideoSpec)
    from comfyui_distributed_tpu.models.wan import WanConfig, init_wan
    from comfyui_distributed_tpu.models.wan_vae import (WanVAE3D,
                                                        WanVAEConfig)

    if on_accel:
        cfg, vae_cfg = WanConfig.wan_14b(), WanVAEConfig.wan()
        spec = VideoSpec(frames=33, height=480, width=832, steps=steps)
        ctx_len = 512
    else:                      # CI-exercisable tiny path
        cfg, vae_cfg = WanConfig.tiny(), WanVAEConfig.tiny()
        spec = VideoSpec(frames=5, height=16, width=16,
                         steps=min(steps, 2))
        ctx_len = 16

    vae = WanVAE3D(vae_cfg).init(jax.random.key(1), frames=5,
                                 image_hw=(vae_cfg.downscale * 4,) * 2)
    f_lat = vae_cfg.latent_frames(spec.padded_frames)
    print(f"[bench] wan14b: materializing {cfg.dim}-dim "
          f"{cfg.num_layers}-layer host params", file=sys.stderr,
          flush=True)
    model, abstract = init_wan(
        cfg, jax.random.key(0),
        sample_fhw=(f_lat, spec.height // vae_cfg.downscale,
                    spec.width // vae_cfg.downscale),
        context_len=ctx_len, abstract=True,
        param_dtype=jnp.bfloat16 if on_accel else None)
    params = materialize_host_params(abstract, seed=0)
    param_bytes = tree_bytes(params)
    plan = plan_offload(params, resident_budget_bytes(),
                        block_prefixes=("block",),
                        glue_keys=_WAN_GLUE_KEYS)
    streamed_gb = plan["streamed_bytes"] / 1e9
    if on_accel:
        # same leaky-transport discipline as _run_flux_offloaded:
        # probe, then refuse BEFORE paying the multi-GB quantize +
        # upload (warmup + measurement stream 16 step-forwards total)
        _, leak_ratio = _probe_h2d_leak(jax.devices()[0])
        # warmup (s1 + s2 steps) + two measured videos of s1/s2 steps
        fwds_needed = 2 * (2 + 6)
        budget = _affordable_forwards_or_raise(
            leak_ratio, param_bytes, plan["resident_bytes"], streamed_gb)
        if budget < fwds_needed:
            raise RuntimeError(
                f"wan14b: only {budget:.0f} affordable streamed "
                f"forwards under the transfer leak; need {fwds_needed}")
    pipe = VideoPipeline(model, params, vae)
    ctx = jnp.zeros((1, ctx_len, cfg.text_dim))

    def one_video(seed, n_steps):
        sp = dataclasses.replace(spec, steps=n_steps)
        t0 = time.perf_counter()
        jax.block_until_ready(pipe.generate_offloaded(sp, seed, ctx))
        return time.perf_counter() - t0

    print(f"[bench] wan14b: {param_bytes/1e9:.1f} GB params, plan: "
          f"{plan['resident_bytes']/1e9:.1f} GB resident / "
          f"{streamed_gb:.1f} GB streamed per step", file=sys.stderr,
          flush=True)
    derived = on_accel and streamed_gb > 0.05
    # the resident ladder compiles per sigma-ladder LENGTH (scan over
    # steps) — warm up at exactly the step counts that get timed
    s1, s2 = 2, 6
    t0 = time.perf_counter()
    if derived:
        one_video(0, s1)            # upload + compiles
        one_video(0, s2)
    else:
        one_video(0, spec.steps)
    compile_s = time.perf_counter() - t0
    if derived:
        # leaky-transport discipline (see _run_flux_offloaded): measure
        # two small step counts, derive the requested-step latency from
        # exact per-step linearity
        lat1, lat2 = one_video(1, s1), one_video(2, s2)
        median, per_step, derivation = _extrapolate_steps(
            lat1, s1, lat2, s2, spec.steps)
        times = [lat1, lat2]
    else:
        runs = runs or 2
        times, median = _timed_runs(
            lambda i: one_video(i + 1, spec.steps), runs)
        per_step = median / spec.steps
        derivation = {"derived": False}

    off = pipe.offload_executor()
    return {
        "metric": (f"wan14b_t2v_33f_480x832_{spec.steps}step_wall_s"
                   if on_accel else "wan14b_tiny_wall_s_cpu"),
        "value": round(median, 2),
        "unit": "seconds",
        "vs_baseline": 1.0,
        "vs_baseline_note": "reference publishes no numbers",
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "devices": 1, "steps": spec.steps,
        "per_step_s": round(per_step, 2),
        "compile_s": round(compile_s, 1),
        "run_times_s": [round(t, 2) for t in times],
        "param_bytes": param_bytes,
        "resident_bytes": off.resident_bytes,
        "streamed_bytes_per_step": (tree_bytes(off.streamed)
                                    if off.streamed else 0),
        "stream_dtype": off.stream_dtype,
        "fully_resident": bool(off.stacked),
        **derivation,
        "note": ("WAN 14B t2v (28 GB bf16 params — ~2x one chip's HBM) "
                 "on ONE chip via fp8(e4m3) weight residency; blocks "
                 "past the budget stream per step. Pods run dp x tp "
                 "instead; the WAN-2.2 dual-expert pair adds one HBM "
                 "swap per video."),
    }


def run_wan22_benchmark(steps: int, runs: int | None) -> dict:
    """WAN-2.2-style dual-expert (MoE) t2v: TWO DiTs — a high-noise
    expert for sigmas ≥ the 0.875 t2v boundary, a low-noise expert
    below — with the sigma ladder split inside ONE compiled program
    (``pipeline_video._sample_expert``). Same geometry, protocol, and
    result shape as ``wan`` (shared ``_run_wan_like`` body), so
    (wan22 − wan) isolates what the expert switch costs on hardware —
    measured r04: 32.49 vs 32.46 s, i.e. free. Both experts' weights
    ride as jit arguments (2× upload, bf16-resident — 1.3B-class pairs
    fit one chip; published 14B pairs need the offload executor's HBM
    swap or tp over a pod)."""
    return _run_wan_like(steps, runs, moe=True)


def run_serving_benchmark(steps: int, runs: int | None) -> dict:
    """Serving front door A/B (ISSUE 9, docs/serving.md): the same R
    requests executed (a) sequentially as R solo programs and (b) as one
    microbatched program (``generate_microbatch``), both warm — the
    speedup is the dispatch/scheduling overhead cross-user batching
    amortizes. Then an in-process front door is driven at fixed offered
    load (tiny preset, real controller + HTTP route) to measure p50/p99
    submit→terminal latency and achieved microbatch occupancy.

    On accel the program A/B uses the SDXL-base architecture at 1024²
    (the headline geometry); on CPU the tiny stack — flagged as usual so
    a toy line can't be mistaken for hardware numbers."""
    import jax
    import jax.numpy as jnp

    _enable_compile_cache()
    platform = jax.devices()[0].platform
    on_accel = platform not in ("cpu",)

    from comfyui_distributed_tpu.diffusion.pipeline import (
        GenerationSpec, Txt2ImgPipeline)
    from comfyui_distributed_tpu.models.text import (TextEncoder,
                                                     TextEncoderConfig)
    from comfyui_distributed_tpu.models.unet import UNetConfig, init_unet
    from comfyui_distributed_tpu.models.vae import AutoencoderKL, VAEConfig
    from comfyui_distributed_tpu.parallel import build_mesh

    if on_accel:
        unet_cfg, vae_cfg = UNetConfig.sdxl(), VAEConfig.sdxl()
        text_cfg = TextEncoderConfig()
        spec = GenerationSpec(height=1024, width=1024, steps=steps,
                              guidance_scale=5.0)
        lat_hw = (128, 128)
        batch_r = 4
    else:
        unet_cfg, vae_cfg = UNetConfig.tiny(), VAEConfig.tiny()
        text_cfg = TextEncoderConfig.tiny()
        spec = GenerationSpec(height=32, width=32, steps=min(steps, 4),
                              guidance_scale=5.0)
        lat_hw = (16, 16)
        batch_r = 4

    model, params = init_unet(
        unet_cfg, jax.random.key(0),
        sample_shape=(*lat_hw, unet_cfg.in_channels),
        context_len=text_cfg.max_len,
        param_dtype=jnp.bfloat16 if on_accel else None)
    vae = AutoencoderKL(vae_cfg).init(
        jax.random.key(1),
        image_hw=(lat_hw[0] * vae_cfg.downscale,
                  lat_hw[1] * vae_cfg.downscale))
    enc = TextEncoder(text_cfg).init(jax.random.key(2))
    pipe = Txt2ImgPipeline(model, params, vae)
    contexts, unconds = [], []
    for i in range(batch_r):
        c, _ = enc.encode([f"serving bench {i}"])
        u, _ = enc.encode([""])
        contexts.append(c)
        unconds.append(u)
    mesh = build_mesh({"dp": len(jax.devices())})
    seeds = list(range(100, 100 + batch_r))

    y = uy = None
    if unet_cfg.adm_in_channels:
        y = jnp.zeros((1, unet_cfg.adm_in_channels))
        uy = jnp.zeros_like(y)
    ys = None if y is None else [y] * batch_r
    uys = None if uy is None else [uy] * batch_r

    # warm both program shapes (solo + R-bucket), then time
    jax.block_until_ready(pipe.generate(mesh, spec, seeds[0], contexts[0],
                                        unconds[0], y, uy))
    jax.block_until_ready(pipe.generate_microbatch(
        mesh, spec, seeds, contexts, unconds, ys, uys)[0])

    reps = runs or (3 if on_accel else 2)
    seq_times, seq_median = _timed_runs(
        lambda i: [jax.block_until_ready(pipe.generate(
            mesh, spec, seeds[r], contexts[r], unconds[r], y, uy))
            for r in range(batch_r)], reps)
    mb_times, mb_median = _timed_runs(
        lambda i: jax.block_until_ready(pipe.generate_microbatch(
            mesh, spec, seeds, contexts, unconds, ys, uys)[-1]), reps)
    speedup = seq_median / mb_median if mb_median else None

    # fixed offered load against the real front door (tiny preset; the
    # controller path is identical on accel, only the model differs)
    serving = _serving_offered_load()

    return {
        "metric": ("serving_microbatch_speedup" if on_accel
                   else "serving_microbatch_speedup_cpu"),
        "value": round(speedup, 4) if speedup else None,
        "unit": "x (sequential wall / microbatched wall, same R requests)",
        "vs_baseline": 1.0,
        "vs_baseline_note": "no published serving baseline",
        "platform": platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", platform),
        "devices": len(jax.devices()),
        "steps": spec.steps,
        "microbatch_r": batch_r,
        "sequential_wall_s": round(seq_median, 3),
        "microbatch_wall_s": round(mb_median, 3),
        "sequential_times_s": [round(t, 3) for t in seq_times],
        "microbatch_times_s": [round(t, 3) for t in mb_times],
        "offered_load": serving,
    }


def _serving_offered_load(n: int = 16, concurrency: int = 16) -> dict:
    """Drive the real in-process controller (front door enabled) at a
    fixed offered load of same-and-mixed-shape tiny requests; report
    submit→terminal p50/p99 and the achieved mean microbatch size."""
    import asyncio

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "scripts"))
    try:
        import load_smoke
    except ImportError as e:
        return {"error": f"load_smoke unavailable: {e}"}

    # window sized for CPU program times so coalescing actually happens
    # at this offered load; knobs are instance attrs, set post-build
    os.environ.setdefault("CDT_CONFIG_PATH",
                          os.path.join(tempfile.mkdtemp(prefix="cdt_bench_"),
                                       "config.json"))
    reqs = load_smoke.build_workload(7, n, shapes=((32, 2), (48, 2)))
    try:
        stats = asyncio.run(load_smoke._run_in_process(
            reqs, concurrency, wait=True, timeout_s=1800.0))
    except Exception as e:  # noqa: BLE001 — offered-load leg is evidence
        return {"error": str(e)[:300]}
    return {
        "requests": n,
        "concurrency": concurrency,
        "admitted": stats.get("admitted", 0) + stats.get("queued", 0),
        "shed": stats.get("shed"),
        "completed": stats.get("completed"),
        "errors": stats.get("errors"),
        "latency_p50_s": stats.get("latency_p50_s"),
        "latency_p99_s": stats.get("latency_p99_s"),
        "mean_batch_size": (stats.get("metrics") or {}).get(
            "mean_batch_size"),
        "by_tenant": stats.get("by_tenant"),
    }


def run_elastic_benchmark(steps: int, runs: int | None) -> dict:
    """Elastic scale event A/B (ISSUE 10, docs/elasticity.md): a mixed
    two-job tile load driven over the real HTTP control plane — real
    pull/submit wire traffic, real drain route — run (a) with a static
    2-worker fleet and (b) with a fleet that scales up one worker mid-run
    (the steal scheduler hands it pending tiles; its arrival→first-result
    latency is the ``steal_pickup_s`` number) and gracefully drains
    another mid-run. Per-tile compute is one jitted matmul chain keyed on
    the GLOBAL tile index, so both runs must be bit-identical — the
    zero-loss check is part of the bench, not a separate test."""
    import asyncio

    import jax
    import jax.numpy as jnp
    import numpy as np

    _enable_compile_cache()
    platform = jax.devices()[0].platform
    on_accel = platform not in ("cpu",)

    from aiohttp.test_utils import TestClient, TestServer

    from comfyui_distributed_tpu.api.app import create_app
    from comfyui_distributed_tpu.cluster.controller import Controller
    from comfyui_distributed_tpu.cluster.job_store import JobStore
    from comfyui_distributed_tpu.cluster.tile_farm import (TileFarm,
                                                           assemble_tiles)

    os.environ.setdefault("CDT_CONFIG_PATH",
                          os.path.join(tempfile.mkdtemp(prefix="cdt_bench_"),
                                       "config.json"))
    inner_steps = max(2, min(int(steps), 8))

    @jax.jit
    def _tile_program(x):
        for _ in range(inner_steps):
            x = jnp.tanh(x @ x) + 0.1
        return x

    dim = 128 if on_accel else 32

    def make_proc(marker: float):
        def proc(start, end):
            out = []
            for i in range(start, end):
                x = jnp.full((dim, dim), 0.01 * (i + 1) + marker,
                             jnp.float32)
                out.append(np.asarray(jax.block_until_ready(
                    _tile_program(x))))
            return np.stack(out)
        return proc

    totals = {"sdxl": 24, "usdu": 16}
    procs = {"sdxl": make_proc(0.0), "usdu": make_proc(0.5)}
    # warm the program once so neither leg pays the compile
    jax.block_until_ready(_tile_program(jnp.zeros((dim, dim))))
    # pace each tile so the run is long enough for mid-run events to
    # land while work is pending (a real tile is a multi-second SPMD
    # program; this bench measures the CONTROL PLANE around it)
    pace_s = 0.05

    def paced(fn):
        def proc(start, end):
            time.sleep(pace_s * (end - start))
            return fn(start, end)
        return proc

    paced_procs = {jid: paced(fn) for jid, fn in procs.items()}

    def resolver_for(tag: str):
        """Steal grants carry the full job id ("{tag}-{kind}"); map it
        back to the kind's process_fn."""
        def resolve(job_id: str):
            prefix = f"{tag}-"
            if not job_id.startswith(prefix):
                return None
            return paced_procs.get(job_id[len(prefix):])
        return resolve

    async def drive(elastic: bool, tag: str) -> dict:
        # the lifecycle registry is process-global (like the breakers):
        # a drain from the previous leg must not carry into this one
        from comfyui_distributed_tpu.cluster.elastic.states import DRAIN

        DRAIN.reset()
        controller = Controller()
        client = TestClient(TestServer(create_app(controller)))
        await client.start_server()
        t0 = time.monotonic()
        pickup = {}
        try:
            base = f"http://127.0.0.1:{client.port}"
            loop = asyncio.get_running_loop()

            def steal_worker(wid, resolve=None):
                farm = TileFarm(JobStore(), loop)
                return farm.worker_steal_run_async(
                    wid, base, resolve or resolver_for(tag),
                    idle_polls=3, idle_interval=0.1)

            masters = [asyncio.create_task(
                controller.tile_farm.master_run_async(
                    f"{tag}-{jid}", total=total,
                    process_fn=paced_procs[jid], chunk=1,
                    heartbeat_interval=0.5, worker_timeout=30.0))
                for jid, total in totals.items()]
            await asyncio.sleep(0.05)
            workers = {w: asyncio.create_task(steal_worker(w))
                       for w in ("w0", "w1")}
            if elastic:
                await asyncio.sleep(0.3)
                # mid-run arrival: w2 steals from the open jobs; pickup
                # latency = arrival → its FIRST processed grant
                arrived = time.monotonic()
                first_grant: dict = {}

                base_resolve = resolver_for(tag)

                def recording_resolve(jid):
                    fn = base_resolve(jid)
                    if fn is None:
                        return None

                    def wrapped(start, end):
                        first_grant.setdefault("t", time.monotonic())
                        return fn(start, end)
                    return wrapped

                workers["w2"] = asyncio.create_task(
                    steal_worker("w2", recording_resolve))
                # mid-run graceful departure: drain w1
                async with client.session.post(
                        f"{base}/distributed/worker/w1/drain",
                        json={"deadline_s": 0.5,
                              "stop_process": False}) as r:
                    assert r.status == 200, await r.text()
            results = await asyncio.gather(*masters)
            done = await asyncio.gather(*workers.values())
            if elastic:
                done_by = dict(zip(workers, done))
                if first_grant.get("t"):
                    pickup["steal_pickup_s"] = round(
                        first_grant["t"] - arrived, 3)
                pickup["scaleup_tasks"] = sum(done_by["w2"].values())
            out = {}
            for (jid, total), res in zip(totals.items(), results):
                out[jid] = assemble_tiles(res, total, 1)
            status = {jid: await controller.store.job_status(f"{tag}-{jid}")
                      for jid in totals}
            dead = sum(len(s.get("dead_letter") or [])
                       for s in status.values())
            return {"wall_s": time.monotonic() - t0, "outputs": out,
                    "dead_letters": dead, **pickup}
        finally:
            await client.close()

    def one_rep(i: int) -> dict:
        async def body():
            static = await drive(elastic=False, tag=f"st{i}")
            elastic = await drive(elastic=True, tag=f"el{i}")
            identical = all(
                np.array_equal(static["outputs"][j], elastic["outputs"][j])
                for j in totals)
            return {
                "static_wall_s": round(static["wall_s"], 3),
                "elastic_wall_s": round(elastic["wall_s"], 3),
                "bit_identical": identical,
                "dead_letters": static["dead_letters"]
                + elastic["dead_letters"],
                "steal_pickup_s": elastic.get("steal_pickup_s"),
                "scaleup_tasks": elastic.get("scaleup_tasks", 0),
            }
        return asyncio.run(body())

    reps = runs or 2
    rep_results = [one_rep(i) for i in range(reps)]
    overheads = sorted(r["elastic_wall_s"] / r["static_wall_s"]
                       for r in rep_results)
    median = overheads[len(overheads) // 2]
    pickups = [r["steal_pickup_s"] for r in rep_results
               if r.get("steal_pickup_s") is not None]

    return {
        "metric": ("elastic_scale_event_overhead" if on_accel
                   else "elastic_scale_event_overhead_cpu"),
        "value": round(median, 4),
        "unit": "x (scale-event wall / static-fleet wall, same work)",
        "vs_baseline": 1.0,
        "vs_baseline_note": "no published elastic baseline",
        "platform": platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", platform),
        "devices": len(jax.devices()),
        "steps": inner_steps,
        "jobs": totals,
        "reps": rep_results,
        "steal_pickup_s_best": min(pickups) if pickups else None,
        "all_bit_identical": all(r["bit_identical"] for r in rep_results),
        "total_dead_letters": sum(r["dead_letters"] for r in rep_results),
    }


def _caching_collect_outputs(history: dict, pids: list) -> list:
    """Per-request list of terminal output arrays (sorted by node id) —
    the bit-identity evidence for the caching A/B."""
    import numpy as np

    out = []
    for pid in pids:
        entry = history.get(pid) or {}
        arrays = []
        for nid in sorted((entry.get("outputs") or {})):
            for v in entry["outputs"][nid]:
                if hasattr(v, "shape") and getattr(v, "ndim", 0) >= 3:
                    arrays.append(np.asarray(v))
        out.append(arrays)
    return out


async def _caching_drive(requests: list, cache_on: bool,
                         timeout_s: float) -> dict:
    """Drive one leg of the caching A/B: a REAL in-process controller +
    HTTP route, every request submitted concurrently, waited to terminal.
    Returns wall-clock, completion counts, per-request outputs, and the
    leg's cache stats."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from comfyui_distributed_tpu.api import create_app
    from comfyui_distributed_tpu.cluster.controller import Controller

    os.environ["CDT_CACHE"] = "1" if cache_on else "0"
    # fresh persisted tier per leg: the A/B measures THIS leg's cache,
    # not a previous run's leftovers
    os.environ["CDT_CACHE_DIR"] = tempfile.mkdtemp(prefix="cdt_bench_cc_")
    controller = Controller()
    client = TestClient(TestServer(create_app(controller)))
    await client.start_server()
    try:
        async def submit(payload):
            resp = await client.post("/distributed/queue", json=payload)
            body = await resp.json()
            return resp.status, body

        async def wait_done(pid):
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                entry = controller.queue.history.get(pid)
                if entry is not None:
                    return entry
                await asyncio.sleep(0.02)
            return {"status": "timeout"}

        # untimed warmup: build the model bundle + compile the program
        # OUTSIDE the measured window (both legs pay it identically; the
        # A/B measures serving throughput, not controller boot)
        warm = dict(requests[0])
        warm["prompt"] = json.loads(json.dumps(warm["prompt"]))
        sampler = next(v for v in warm["prompt"].values()
                       if v["class_type"] == "TPUTxt2Img")
        sampler["inputs"]["seed"] = 999983     # distinct fingerprint
        warm["cache"] = "bypass"
        _, wb = await submit(warm)
        if wb.get("prompt_id"):
            await wait_done(wb["prompt_id"])

        # two waves: wave-1 duplicates land while their twin is in
        # flight (coalescer traffic); wave-2 duplicates of completed
        # wave-1 requests exercise the completed-result tier. Identical
        # structure in both legs, so the A/B stays fair.
        split = max(1, (2 * len(requests)) // 3)
        t0 = time.perf_counter()
        pids: list = []
        entries: list = []
        for wave in (requests[:split], requests[split:]):
            if not wave:
                continue
            results = await asyncio.gather(*(submit(dict(p))
                                             for p in wave))
            wave_pids = [body.get("prompt_id", "") for _, body in results]
            pids.extend(wave_pids)
            entries.extend(await asyncio.gather(
                *(wait_done(p) for p in wave_pids if p)))
        wall = time.perf_counter() - t0
        coalesced = sum(1 for e in entries if e.get("coalesced_with"))
        completed = sum(1 for e in entries if e.get("status") == "success")
        cache_stats = (controller.cache.stats()
                       if controller.cache is not None else None)
        return {
            "wall_s": wall,
            "submitted": len(requests),
            "completed": completed,
            "statuses": sorted({e.get("status") for e in entries}),
            "coalesced": coalesced,
            "result_hits": ((cache_stats or {}).get("result") or {}).get(
                "hit", 0) + ((cache_stats or {}).get("result") or {}).get(
                "disk_hit", 0),
            "hit_rate": (cache_stats or {}).get("hit_rate"),
            "outputs": _caching_collect_outputs(controller.queue.history,
                                                pids),
        }
    finally:
        await client.close()


async def _caching_fleet_drive(waves: list, fleet_on: bool,
                               timeout_s: float) -> dict:
    """One leg of the fleet A/B (ISSUE 17, docs/caching.md): TWO real
    controllers over HTTP, each with its OWN disk tier. ``waves`` is a
    list of submission waves, each a list of ``(worker_idx, payload)``
    — a wave is submitted concurrently and fully drained before the
    next starts, so duplicate placement is CONTROLLED: a dup routed to
    the worker that computed the original is a per-host hit either
    way; a cross-routed dup is a recompute per-host but a ring serve
    with ``fleet_on``. Same waves, same routing — the A/B isolates the
    fleet tier."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from comfyui_distributed_tpu.api import create_app
    from comfyui_distributed_tpu.cluster.controller import Controller

    os.environ["CDT_CACHE"] = "1"
    os.environ["CDT_FLEET_CACHE"] = "1" if fleet_on else "0"
    names = ("wA", "wB")
    ctls, clients = [], []
    try:
        for name in names:
            os.environ["CDT_CACHE_DIR"] = tempfile.mkdtemp(
                prefix=f"cdt_bench_fleet_{name}_")
            ctl = Controller()
            client = TestClient(TestServer(create_app(ctl)))
            await client.start_server()
            ctls.append(ctl)
            clients.append(client)
        if fleet_on:
            urls = [str(c.make_url("")).rstrip("/") for c in clients]
            for i, ctl in enumerate(ctls):
                fleet = ctl.cache.fleet
                me, peer, peer_url = (names[i], names[1 - i],
                                      urls[1 - i])
                fleet.self_id = me
                fleet._membership = (lambda me=me, peer=peer, u=peer_url:
                                     {me: None, peer: u})
                with fleet._lock:
                    fleet._ring_cache = None

        async def submit(idx, payload):
            resp = await clients[idx % 2].post("/distributed/queue",
                                               json=payload)
            return idx % 2, await resp.json()

        n_requests = sum(len(w) for w in waves)
        template = waves[0][0][1]

        async def wait_done(idx, pid):
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                entry = ctls[idx].queue.history.get(pid)
                if entry is not None:
                    return entry
                await asyncio.sleep(0.02)
            return {"status": "timeout"}

        # untimed warmup on each controller (bundle build + compile)
        for i in range(2):
            warm = dict(template)
            warm["prompt"] = json.loads(json.dumps(warm["prompt"]))
            sampler = next(v for v in warm["prompt"].values()
                           if v["class_type"] == "TPUTxt2Img")
            sampler["inputs"]["seed"] = 999700 + i
            warm["cache"] = "bypass"
            _, wb = await submit(i, warm)
            if wb.get("prompt_id"):
                await wait_done(i, wb["prompt_id"])

        # each wave drains fully before the next submits (a dup wave
        # must see the originals' fills, and intra-wave keys are all
        # distinct so the coalescer can't mask the cache under test);
        # the fleet leg keeps its fire-and-forget fill drain INSIDE
        # the timed window (propagation is part of the serving
        # pipeline, not free)
        t0 = time.perf_counter()
        located: list = []
        entries: list = []
        for wave in waves:
            results = await asyncio.gather(
                *(submit(widx, dict(p)) for widx, p in wave))
            pairs = [(idx, body.get("prompt_id", ""))
                     for idx, body in results]
            located.extend(pairs)
            entries.extend(await asyncio.gather(
                *(wait_done(idx, pid) for idx, pid in pairs if pid)))
            if fleet_on:
                deadline = time.monotonic() + 10
                while (any(c.cache.fleet._pending for c in ctls)
                       and time.monotonic() < deadline):
                    await asyncio.sleep(0.02)
        wall = time.perf_counter() - t0
        outputs = []
        for idx, pid in located:
            outputs.extend(_caching_collect_outputs(
                ctls[idx].queue.history, [pid]))
        out = {
            "wall_s": wall,
            "submitted": n_requests,
            "completed": sum(1 for e in entries
                             if e.get("status") == "success"),
            "served": sum(1 for e in entries
                          if e.get("cache") == "hit"),
            "coalesced": sum(1 for e in entries
                             if e.get("coalesced_with")),
            "outputs": outputs,
        }
        if fleet_on:
            out["remote"] = {name: dict(ctl.cache.fleet.counts)
                             for name, ctl in zip(names, ctls)}
        return out
    finally:
        for client in clients:
            await client.close()


async def _caching_near_leg(steps: int, timeout_s: float) -> dict:
    """Near-tier evidence (ISSUE 17): a ``cache:"near"`` donor parks its
    midpoint; a seed re-roll of the same prompt resumes it for half the
    steps. Reports steps saved and the output delta vs the re-roll's
    OWN exact computation — the delta is nonzero BY DESIGN (the near
    serve re-noises the donor carry under the request's own seed;
    docs/caching.md documents the bound), which is why the tier is
    opt-in per request."""
    import asyncio

    import numpy as np
    from aiohttp.test_utils import TestClient, TestServer

    from comfyui_distributed_tpu.api import create_app
    from comfyui_distributed_tpu.cluster.controller import Controller

    os.environ["CDT_CACHE"] = "1"
    os.environ["CDT_FLEET_CACHE"] = "1"
    os.environ["CDT_CACHE_DIR"] = tempfile.mkdtemp(prefix="cdt_bench_near_")
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "scripts"))
    import load_smoke

    controller = Controller()
    client = TestClient(TestServer(create_app(controller)))
    await client.start_server()
    try:
        async def run_one(payload):
            resp = await client.post("/distributed/queue", json=payload)
            body = await resp.json()
            pid = body.get("prompt_id")
            deadline = time.monotonic() + timeout_s
            while pid and time.monotonic() < deadline:
                entry = controller.queue.history.get(pid)
                if entry is not None:
                    return entry
                await asyncio.sleep(0.02)
            return {"status": "timeout"}

        prompt = load_smoke.prompt_for(seed=51, text="near bench",
                                       wh=16, steps=steps)
        reroll = json.loads(json.dumps(prompt))
        next(v for v in reroll.values()
             if v["class_type"] == "TPUTxt2Img")["inputs"]["seed"] = 151

        donor = await run_one({"prompt": prompt, "client_id": "bench",
                               "cache": "near"})
        near = await run_one({"prompt": reroll, "client_id": "bench",
                              "cache": "near"})
        exact = await run_one({"prompt": reroll, "client_id": "bench",
                               "cache": "bypass"})
        tier = controller.cache.fleet.near.stats()

        def imgs(entry):
            return _caching_collect_outputs(
                {"x": entry}, ["x"])[0]

        delta = None
        if near.get("cache") == "near":
            pairs = list(zip(imgs(near), imgs(exact)))
            if pairs:
                delta = max(float(np.max(np.abs(
                    a.astype(np.float64) - b.astype(np.float64))))
                    for a, b in pairs)
        return {
            "donor_status": donor.get("status"),
            "near_served": near.get("cache") == "near",
            "reuse": tier.get("reuse", 0),
            "steps_saved": tier.get("steps_saved", 0),
            "total_steps": steps,
            # max|near - exact| over the re-roll's own from-scratch run,
            # in image units (0..1): bounded, never bit-identical
            "max_abs_delta_vs_exact": delta,
        }
    finally:
        await client.close()


def _caching_autoscaler_leg(hit_rate: float) -> dict:
    """Deterministic evidence that cache-hit pressure lowers the
    autoscaler's desired fleet size: the same deep queue evaluated cold
    (hit rate 0) vs hot (the measured rate). Fake clock + fake provider —
    the policy arithmetic is the thing under test."""
    import math

    from comfyui_distributed_tpu.cluster.elastic.autoscaler import (
        AutoscalePolicy, Autoscaler, FleetSignals)

    policy = AutoscalePolicy(min_workers=0, max_workers=8,
                             scale_up_depth=4.0, scale_down_depth=0.5,
                             up_streak=2, down_streak=4)

    class _Provider:
        def __init__(self):
            self.n = 0

        def list_workers(self):
            return {}

        def scale_up(self):
            self.n += 1
            return f"w{self.n}"

        def scale_down(self, wid):
            pass

    def leg(rate: float) -> dict:
        depth = 20
        sig = FleetSignals(queue_depth=depth, tile_depth=0,
                           active_workers=2, cache_hit_rate=rate)
        clock = {"t": 0.0}
        scaler = Autoscaler(lambda: sig, _Provider(), policy,
                            clock=lambda: clock["t"])
        decision = None
        # exactly up_streak ticks: the last one is the acting tick
        for _ in range(policy.up_streak):
            clock["t"] += 60.0
            decision = scaler.evaluate()
        pressure = sig.effective_work / (sig.active_workers + 1)
        return {
            "cache_hit_rate": round(rate, 4),
            "effective_work": round(sig.effective_work, 2),
            "pressure": round(pressure, 3),
            "decision": decision.direction,
            # capacity units needed to bring pressure under the scale-up
            # threshold — the policy's implied fleet size for this load
            "desired_workers": max(policy.min_workers, math.ceil(
                sig.effective_work / policy.scale_up_depth) - 1),
        }

    return {"cold": leg(0.0), "hot": leg(hit_rate)}


def run_caching_benchmark(steps: int, runs: int | None) -> dict:
    """Content-cache offered-load A/B (ISSUE 11, docs/caching.md): the
    SAME seeded dup-rate-0.75 workload (the acceptance floor is ≥0.5)
    driven through the real controller + HTTP route with the cache
    subsystem off, then on. The metric is completed-requests/sec;
    acceptance is ≥2× with every served image bit-identical to the
    uncached run, plus the autoscaler leg showing cache-hit pressure
    lowering the desired fleet size.

    ``CDT_FD_MAX_BATCH=1`` pins microbatching out of both legs so the
    A/B isolates the caching lever (the serving workload already covers
    batching); tiny preset on CPU, same controller path on accel."""
    import asyncio

    import jax

    _enable_compile_cache()
    platform = jax.devices()[0].platform

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "scripts"))
    import load_smoke

    os.environ.setdefault(
        "CDT_CONFIG_PATH",
        os.path.join(tempfile.mkdtemp(prefix="cdt_bench_"), "config.json"))
    os.environ["CDT_FD_MAX_BATCH"] = "1"
    # n is floored at 16 even under the CPU-fallback runs cap: the tiny
    # programs are cheap warm, and a 16-request mix is the smallest
    # workload where the seeded dup structure is meaningful
    n = max(16, runs or 16)
    # dup-rate 0.75 ≥ the 0.5 acceptance floor; 15% of dups are
    # seed-rerolled near-duplicates (conditioning-tier traffic), the
    # rest byte-identical (coalescer + result-tier traffic)
    dup_rate, near_fraction = 0.75, 0.15
    wh, leg_steps = 24, min(steps, 4)
    requests = load_smoke.build_workload(1, n, shapes=((wh, leg_steps),),
                                         dup_rate=dup_rate,
                                         near_fraction=near_fraction)
    unique_prints = len({json.dumps(r["prompt"], sort_keys=True)
                         for r in requests})

    import numpy as np

    # each leg warms its own controller (bundle build + compile) outside
    # the timed window; the persistent XLA cache makes the second leg's
    # warmup a cache load
    off = asyncio.run(_caching_drive(requests, cache_on=False,
                                     timeout_s=1800.0))
    on = asyncio.run(_caching_drive(requests, cache_on=True,
                                    timeout_s=1800.0))

    # fleet leg (ISSUE 17): dup-rate-0.75 at a HEAVIER shape than the
    # main leg — the harness costs ~0.5s/request regardless of outcome,
    # so the program must dominate for the wall ratio to measure the
    # cache (at production scale the sampler program IS the cost).
    # Duplicate PLACEMENT is controlled: wave 0 computes 6 uniques
    # round-robin, then three dup waves re-request every unique with
    # routing alternated cross/same/cross. Per-host, the first
    # cross-routed dup of each unique RECOMPUTES on the other worker
    # (and refills its local cache, serving the later waves) — the
    # per-host floor is every unique computed once PER WORKER it lands
    # on; the ring computes each unique once for the fleet.
    # Byte-identical dups only: near-dups are the near leg's job below.
    n_uniq, n_dup_waves = 6, 3
    fleet_wh, fleet_steps = 48, 8
    uniq = [load_smoke.prompt_for(seed=4200 + u, text=f"fleet bench {u}",
                                  wh=fleet_wh, steps=fleet_steps)
            for u in range(n_uniq)]
    fleet_waves = [[(u % 2, {"prompt": uniq[u], "client_id": "bench"})
                    for u in range(n_uniq)]]
    for w in range(1, n_dup_waves + 1):
        fleet_waves.append(
            [((u + w) % 2, {"prompt": uniq[u], "client_id": "bench"})
             for u in range(n_uniq)])
    n_fleet = sum(len(wv) for wv in fleet_waves)
    fleet_dup_rate = (n_fleet - n_uniq) / n_fleet
    cross_dups = sum(1 for w in range(1, n_dup_waves + 1)
                     for u in range(n_uniq) if (u + w) % 2 != u % 2)
    def _best_of_two(fleet_on: bool) -> dict:
        # this box shows multi-second scheduling stalls run-to-run;
        # min-wall of two fully independent reps (fresh controllers,
        # fresh cache dirs) keeps the A/B about the cache, not the box
        a = asyncio.run(_caching_fleet_drive(fleet_waves, fleet_on,
                                             timeout_s=1800.0))
        b = asyncio.run(_caching_fleet_drive(fleet_waves, fleet_on,
                                             timeout_s=1800.0))
        return a if a["wall_s"] <= b["wall_s"] else b

    per_host = _best_of_two(fleet_on=False)
    fleet_on_leg = _best_of_two(fleet_on=True)
    fleet_mismatch = 0
    fleet_compared = 0
    for a_arrays, b_arrays in zip(per_host["outputs"],
                                  fleet_on_leg["outputs"]):
        for a, b in zip(a_arrays, b_arrays):
            fleet_compared += 1
            if a.shape != b.shape or not np.array_equal(a, b):
                fleet_mismatch += 1
    ph_rps = (per_host["completed"] / per_host["wall_s"]
              if per_host["wall_s"] else None)
    fl_rps = (fleet_on_leg["completed"] / fleet_on_leg["wall_s"]
              if fleet_on_leg["wall_s"] else None)
    per_host.pop("outputs", None)
    fleet_on_leg.pop("outputs", None)
    fleet_leg = {
        "requests": n_fleet,
        "dup_rate": fleet_dup_rate,
        "cross_worker_dups": cross_dups,
        "shape": [fleet_wh, fleet_steps],
        "reps": 2,
        "per_host": per_host,
        "fleet": fleet_on_leg,
        "completed_rps_per_host": round(ph_rps, 4) if ph_rps else None,
        "completed_rps_fleet": round(fl_rps, 4) if fl_rps else None,
        "speedup": (round(fl_rps / ph_rps, 4)
                    if ph_rps and fl_rps else None),
        # every fleet-served image equals the per-host (recomputed)
        # leg's bytes — remote serves are EXACT-tier serves
        "bit_identical": fleet_mismatch == 0 and fleet_compared > 0,
        "outputs_compared": fleet_compared,
        "output_mismatches": fleet_mismatch,
    }

    near = asyncio.run(_caching_near_leg(steps=4, timeout_s=1800.0))

    # bit-identity: every request's served arrays in the cached leg must
    # equal the uncached leg's, byte for byte
    mismatches = 0
    compared = 0
    for a_arrays, b_arrays in zip(off["outputs"], on["outputs"]):
        for a, b in zip(a_arrays, b_arrays):
            compared += 1
            if a.shape != b.shape or not np.array_equal(a, b):
                mismatches += 1
    off_rps = off["completed"] / off["wall_s"] if off["wall_s"] else None
    on_rps = on["completed"] / on["wall_s"] if on["wall_s"] else None
    speedup = (on_rps / off_rps) if off_rps and on_rps else None

    autoscaler = _caching_autoscaler_leg(on.get("hit_rate") or dup_rate)

    off.pop("outputs", None)
    on.pop("outputs", None)
    return {
        "metric": ("caching_offered_load_speedup" if platform != "cpu"
                   else "caching_offered_load_speedup_cpu"),
        "value": round(speedup, 4) if speedup else None,
        "unit": "x (completed-requests/sec, cache+coalescing vs cache-off, "
                f"same dup-rate-{dup_rate} workload)",
        "vs_baseline": 1.0,
        "vs_baseline_note": "no published caching baseline",
        "platform": platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", platform),
        "devices": len(jax.devices()),
        "requests": n,
        "dup_rate": dup_rate,
        "unique_fingerprints": unique_prints,
        "shape": [wh, leg_steps],
        "fd_max_batch": 1,
        "cache_off": off,
        "cache_on": on,
        "completed_rps_off": round(off_rps, 4) if off_rps else None,
        "completed_rps_on": round(on_rps, 4) if on_rps else None,
        "bit_identical": mismatches == 0 and compared > 0,
        "outputs_compared": compared,
        "output_mismatches": mismatches,
        "autoscaler": autoscaler,
        "fleet": fleet_leg,
        "near": near,
    }


# denoise-program labels (bind_weights): what counts as "the mesh was
# denoising" in the stages A/B — fused programs (decode folded in,
# conservative for the staged claim) and the latent-only stage programs
_DENOISE_LABELS = frozenset({"txt2img", "txt2img_mb", "txt2img_mb_tp",
                             "txt2img_seg", "txt2img_lat",
                             "txt2img_lat_tp"})


def _denoise_program_seconds() -> float:
    """Cumulative wall-clock inside denoise programs (execute + compile)
    from the telemetry registry — callers take deltas around a leg."""
    from comfyui_distributed_tpu.telemetry.registry import REGISTRY

    snap = REGISTRY.snapshot()
    total = 0.0
    for fam_name in ("cdt_pipeline_execute_seconds",
                     "cdt_pipeline_compile_seconds"):
        for s in (snap.get(fam_name) or {}).get("series", []):
            if (s.get("labels") or {}).get("pipeline") in _DENOISE_LABELS:
                total += float(s.get("sum", 0.0))
    return total


async def _stages_drive(requests: list, staged: bool,
                        timeout_s: float) -> dict:
    """One leg of the stages A/B: the same seeded offered load through a
    REAL in-process controller + HTTP route, fused (CDT_STAGES=0) or
    disaggregated. Returns wall, latencies, per-request outputs, the
    denoise-program seconds spent, and the mesh-lane busy seconds the
    occupancy divides by (fused: the one graph-exec consumer; staged:
    the denoise pool)."""
    import asyncio
    import math

    import numpy as np
    from aiohttp.test_utils import TestClient, TestServer

    from comfyui_distributed_tpu.api import create_app
    from comfyui_distributed_tpu.cluster.controller import Controller

    os.environ["CDT_STAGES"] = "1" if staged else "0"
    controller = Controller()
    client = TestClient(TestServer(create_app(controller)))
    await client.start_server()
    try:
        async def submit(payload):
            resp = await client.post("/distributed/queue", json=payload)
            return resp.status, await resp.json()

        async def wait_done(pid):
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                entry = controller.queue.history.get(pid)
                if entry is not None:
                    return entry
                await asyncio.sleep(0.02)
            return {"status": "timeout"}

        async def drive_wave(wave):
            t_sub = {}

            async def one(payload):
                t0 = time.perf_counter()
                status, body = await submit(dict(payload))
                pid = body.get("prompt_id")
                if status != 200 or not pid:
                    return None, None, None
                entry = await wait_done(pid)
                return pid, entry, time.perf_counter() - t0

            return await asyncio.gather(*(one(p) for p in wave))

        # untimed warmup wave: the SAME shape/group structure with
        # re-rolled seeds, so every bucket program (latent, decode,
        # fused microbatch) compiles OFF the measured clock in both legs
        warm = []
        for r in requests:
            w = json.loads(json.dumps(r))
            sampler = next(v for v in w["prompt"].values()
                           if v["class_type"] == "TPUTxt2Img")
            sampler["inputs"]["seed"] += 100000
            warm.append(w)
        await drive_wave(warm)

        busy0 = (controller.stages.denoise.busy_seconds if staged
                 else controller.queue.busy_seconds)
        den0 = _denoise_program_seconds()
        t0 = time.perf_counter()
        results = await drive_wave(requests)
        wall = time.perf_counter() - t0
        den = _denoise_program_seconds() - den0
        busy = ((controller.stages.denoise.busy_seconds if staged
                 else controller.queue.busy_seconds) - busy0)

        outputs, lat, completed, errors = [], [], 0, 0
        for pid, entry, dt in results:
            entry = entry or {}
            if entry.get("status") == "success":
                completed += 1
                lat.append(dt)
            else:
                errors += 1
            arrays = []
            for nid in sorted(entry.get("outputs") or {}):
                for v in entry["outputs"][nid]:
                    if hasattr(v, "shape"):
                        arrays.append(np.asarray(v))
            outputs.append(arrays)
        lat.sort()

        def pct(q):
            return (round(lat[min(len(lat) - 1,
                                  max(0, math.ceil(q * len(lat)) - 1))], 4)
                    if lat else None)

        leg = {
            "staged": staged,
            "wall_s": round(wall, 3),
            "completed": completed,
            "errors": errors,
            "completed_rps": round(completed / wall, 4) if wall else None,
            "latency_p50_s": pct(0.50),
            "latency_p99_s": pct(0.99),
            "denoise_program_s": round(den, 4),
            "mesh_lane_busy_s": round(busy, 4),
            # THE acceptance number: the share of the mesh-owning
            # lane's busy time spent inside denoise programs. Fused,
            # the lane also encodes and decodes; staged, those moved to
            # their own pools (docs/stages.md)
            "denoise_occupancy": (round(den / busy, 4) if busy else None),
            "denoise_duty_of_wall": (round(den / wall, 4) if wall
                                     else None),
            "outputs": outputs,
        }
        if staged:
            stats = controller.stages.stats()
            leg["pools"] = stats["pools"]
            leg["redispatched"] = stats["redispatched"]
            sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                            "scripts"))
            import load_smoke

            from comfyui_distributed_tpu.telemetry.export import \
                render_json
            from comfyui_distributed_tpu.telemetry.registry import REGISTRY

            occ = load_smoke._occupancy_from_snapshot(
                render_json(REGISTRY.snapshot()))
            # the fused leg never observes cdt_decode_batch_size, so
            # the cumulative histogram is this leg's alone
            leg["mean_decode_batch"] = occ.get("mean_decode_batch")
            leg["mean_batch_size"] = occ.get("mean_batch_size")
        return leg
    finally:
        await client.close()


def run_stages_benchmark(steps: int, runs: int | None) -> dict:
    """Stage-split serving A/B (ISSUE 15, docs/stages.md): the SAME
    seeded mixed-shape offered load through the real controller + HTTP
    route with the fused path (CDT_STAGES=0), then disaggregated.
    Reported per leg: req/s, submit→terminal p50/p99, and the
    denoise-pool occupancy (share of the mesh lane's busy time spent in
    denoise programs — the number the stage split exists to raise);
    plus the decode batch-size histogram mean for the staged leg.
    Acceptance: staged occupancy strictly higher at the same offered
    load, mean decode batch > 1, outputs bit-identical across legs.

    CDT_CACHE=0 pins the content cache out of both legs so the A/B
    isolates the stage-split lever (the caching workload owns that
    one); tiny preset on CPU, same controller path on accel."""
    import asyncio

    import jax
    import numpy as np

    _enable_compile_cache()
    platform = jax.devices()[0].platform

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "scripts"))
    import load_smoke

    os.environ.setdefault(
        "CDT_CONFIG_PATH",
        os.path.join(tempfile.mkdtemp(prefix="cdt_bench_"), "config.json"))
    os.environ["CDT_CACHE"] = "0"
    n = max(16, runs or 16)
    requests = load_smoke.build_workload(7, n, shapes=((16, 2), (24, 2)))

    fused = asyncio.run(_stages_drive(requests, staged=False,
                                      timeout_s=1800.0))
    staged = asyncio.run(_stages_drive(requests, staged=True,
                                       timeout_s=1800.0))

    mismatches = compared = 0
    for a_arrays, b_arrays in zip(fused["outputs"], staged["outputs"]):
        for a, b in zip(a_arrays, b_arrays):
            compared += 1
            if a.shape != b.shape or not np.array_equal(a, b):
                mismatches += 1
    fused.pop("outputs", None)
    staged.pop("outputs", None)

    occ_f, occ_s = fused["denoise_occupancy"], staged["denoise_occupancy"]
    gain = (round(occ_s / occ_f, 4)
            if occ_f and occ_s else None)
    return {
        "metric": ("stages_denoise_occupancy_gain" if platform != "cpu"
                   else "stages_denoise_occupancy_gain_cpu"),
        "value": gain,
        "unit": "x (denoise-pool occupancy, disaggregated vs fused, "
                "same offered load)",
        "vs_baseline": 1.0,
        "vs_baseline_note": "no published stage-split baseline",
        "platform": platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", platform),
        "devices": len(jax.devices()),
        "requests": n,
        "shapes": [[16, 2], [24, 2]],
        "fused": fused,
        "staged": staged,
        "occupancy_fused": occ_f,
        "occupancy_staged": occ_s,
        "occupancy_strictly_higher": (occ_f is not None
                                      and occ_s is not None
                                      and occ_s > occ_f),
        "mean_decode_batch": staged.get("mean_decode_batch"),
        "bit_identical": mismatches == 0 and compared > 0,
        "outputs_compared": compared,
        "output_mismatches": mismatches,
    }


_WORKLOADS = {
    "txt2img": run_benchmark,
    "usdu": run_usdu_benchmark,
    "flux": run_flux_benchmark,
    "wan": run_wan_benchmark,
    "wan14b": run_wan14b_benchmark,
    "wan22": run_wan22_benchmark,
    "serving": run_serving_benchmark,
    "elastic": run_elastic_benchmark,
    "caching": run_caching_benchmark,
    "stages": run_stages_benchmark,
}


def _workload_fn(workload: str):
    return _WORKLOADS.get(workload, run_benchmark)


def _emit(result: dict, out: str | None) -> None:
    line = json.dumps(result)
    if out:
        with open(out, "w") as f:
            f.write(line + "\n")
    print(line)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default=None,
                        help="also write the JSON result to this path")
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--runs", type=int, default=None)
    parser.add_argument("--workload",
                        choices=["txt2img", "usdu", "flux", "wan",
                                 "wan14b", "wan22", "serving",
                                 "elastic", "caching", "stages"],
                        default="txt2img",
                        help="txt2img (SDXL images/sec), usdu (4K upscale "
                             "wall-clock), flux (flow images/sec), wan "
                             "(t2v wall-clock), wan14b (14B t2v via the "
                             "quantized offload executor), wan22 "
                             "(dual-expert MoE t2v, same geometry as "
                             "wan), serving (front-door "
                             "microbatch vs sequential + offered-load "
                             "latency, docs/serving.md), elastic "
                             "(scale-event overhead + steal pickup "
                             "latency, docs/elasticity.md), caching "
                             "(content-cache offered-load A/B at "
                             "dup-rate 0.75 + autoscaler pressure leg, "
                             "docs/caching.md)")
    cli = parser.parse_args()

    import jax

    platform = jax.devices()[0].platform    # raises when no backend starts
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS", "") != "cpu":
        sys.exit(f"[bench] no TPU: JAX found platform {platform!r}. A "
                 "benchmark number comes from a chip run; the toy-shape "
                 "dry run is asked for with JAX_PLATFORMS=cpu.")
    _emit(_workload_fn(cli.workload)(cli.steps, cli.runs), cli.out)


if __name__ == "__main__":
    main()
