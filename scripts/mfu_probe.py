#!/usr/bin/env python
"""SDXL MFU investigation harness (VERDICT r3 next #2).

Focused A/B experiments on the UNet denoiser forward — the 97%+ of the
txt2img step — instead of the whole pipeline, so one variant compiles in
seconds and the numbers isolate one question each:

    python scripts/mfu_probe.py forward          # flash on vs off
    python scripts/mfu_probe.py batch            # B=2 (CFG pair) vs B=4
    python scripts/mfu_probe.py attn             # attention microbench
    python scripts/mfu_probe.py trace            # profiler trace + op table

Run on the chip, as the one process that owns it (from the checkout's
root; through the chip tool where the chip is remote to the sandbox).
Results print as JSON lines for easy capture into docs/roofline.md.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _enable_cache() -> None:
    from comfyui_distributed_tpu.utils.compile_cache import \
        enable_compile_cache

    enable_compile_cache(min_compile_secs=0.0)


def _median_time(fn, *args, runs: int = 10) -> float:
    """Times ``fn(seed_scalar, *args)`` — the varying scalar makes every
    call a new computation, and ``float()`` forces execution +
    device→host fetch of a scalar."""
    import jax.numpy as jnp

    float(fn(jnp.float32(0.0), *args))        # warmup (compile + alloc)
    times = []
    from comfyui_distributed_tpu.utils import constants

    runs = constants.PROBE_RUNS.get() or runs
    for i in range(runs):
        t0 = time.perf_counter()
        float(fn(jnp.float32(i + 1), *args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def _build_unet():
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models.unet import UNetConfig, init_unet

    _enable_cache()

    cfg = UNetConfig.sdxl()
    model, params = init_unet(cfg, jax.random.key(0),
                              sample_shape=(128, 128, cfg.in_channels),
                              context_len=77, param_dtype=jnp.bfloat16)
    return cfg, model, params


def _unet_inputs(batch: int, cfg):
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.key(1), (batch, 128, 128,
                                              cfg.in_channels), jnp.bfloat16)
    t = jnp.full((batch,), 500, jnp.int32)
    ctx = jax.random.normal(jax.random.key(2), (batch, 77, cfg.context_dim),
                            jnp.bfloat16)
    y = (jax.random.normal(jax.random.key(3), (batch, cfg.adm_in_channels),
                           jnp.bfloat16)
         if cfg.adm_in_channels else None)
    return x, t, ctx, y


SCAN_LEN = 8     # forwards chained on-device per timed call: one
                 # dispatch amortizes over 8 UNet forwards, like the
                 # pipeline's compiled sampler scans


def _forward_fn(model):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fwd(seed, params, x, t, ctx, y):
        def body(carry, _):
            out = model.apply(params, carry, t, ctx, y)
            return carry * 0.5 + out.astype(carry.dtype) * 0.5, None

        # cast the seed perturbation to x's dtype: a strong f32 scalar
        # would promote the whole benchmarked stack out of bf16
        final, _ = jax.lax.scan(body, x + (seed * 1e-6).astype(x.dtype),
                                None, length=SCAN_LEN)
        return jnp.sum(final.astype(jnp.float32))

    return fwd


def _flops_of(fn, *args) -> float:
    try:
        import jax.numpy as jnp

        from comfyui_distributed_tpu.utils.flops import estimate_flops

        return float(estimate_flops(fn, jnp.float32(0.0), *args))
    except Exception as e:  # noqa: BLE001
        print(f"[probe] flops estimate failed: {e}", file=sys.stderr)
        return 0.0


def _peak() -> float:
    import jax

    kind = jax.devices()[0].device_kind.lower()
    return 394e12 if "v5p" in kind else 197e12      # bf16 peak


def exp_forward(flash: str | None = None) -> None:
    """UNet forward, CFG-shaped batch (B=2): pallas flash vs XLA
    dot_product_attention. CDT_FLASH_ATTENTION is read at trace time, so
    each variant jits fresh."""
    results = []
    for mode in ([flash] if flash else ["1", "0"]):
        os.environ["CDT_FLASH_ATTENTION"] = mode
        import jax

        cfg, model, params = _build_unet()
        fwd = _forward_fn(model)
        args = _unet_inputs(2, cfg)
        t = _median_time(fwd, params, *args)
        flops = _flops_of(fwd, params, *args)
        rec = {"exp": "forward", "flash": mode,
               "s_per_forward": round(t / SCAN_LEN, 5),
               "flops": flops, "mfu": round(flops / t / _peak(), 4)
               if flops else None}
        print(json.dumps(rec), flush=True)
        results.append(rec)
        # new trace next loop: clear the jit cache so select_kernel
        # re-evaluates
        fwd._clear_cache()


def exp_batch() -> None:
    """Per-device batch 1 vs 2 (UNet sees 2 vs 4 with CFG concat): where
    does the r02 batch-2 throughput regression come from?"""
    os.environ.setdefault("CDT_FLASH_ATTENTION", "1")
    cfg, model, params = _build_unet()
    fwd = _forward_fn(model)
    for b in (2, 4):
        args = _unet_inputs(b, cfg)
        t = _median_time(fwd, params, *args)
        flops = _flops_of(fwd, params, *args)
        print(json.dumps({
            "exp": "batch", "unet_batch": b,
            "s_per_forward": round(t / SCAN_LEN, 5),
            "s_per_cfg_image_step": round(t / SCAN_LEN / (b // 2), 5),
            "mfu": round(flops / t / _peak(), 4) if flops else None,
        }), flush=True)


def exp_attn() -> None:
    """Attention microbench: flash (auto layout) vs XLA. Shapes cover
    SDXL, the FLUX H·D=3072 width (packed: 24 head groups) and WAN's
    ~14k-token geometry."""
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.ops.flash_attention import flash_attention

    shapes = [
        ("self64", 2, 4096, 10, 64, 4096),
        ("self32", 2, 1024, 20, 64, 1024),
        ("cross32", 2, 1024, 20, 64, 77),
        ("self64_b4", 4, 4096, 10, 64, 4096),
        ("self32_b4", 4, 1024, 20, 64, 1024),
        ("flux3072", 1, 4608, 24, 128, 4608),
        ("wan14k", 1, 14040, 12, 128, 14040),
    ]
    ATTN_SCAN = 64   # attention ops chained on-device per timed call —
                     # a single op is ~µs, far below the cost of one
                     # dispatch, so unamortized timings measure the host

    def timed_attn(f):
        @jax.jit
        def run(seed, q, k, v):
            def body(carry, _):
                out = f(carry, k, v)
                return (q + out * (seed * 1e-6).astype(q.dtype)), None

            final, _ = jax.lax.scan(body, q, None, length=ATTN_SCAN)
            return jnp.sum(final.astype(jnp.float32))

        return run

    for name, b, nq, h, d, nk in shapes:
        # works for nq != nk too: attention output is q-shaped, so the
        # scan carry stays [B, Nq, H, D] while k/v stay fixed
        q = jax.random.normal(jax.random.key(0), (b, nq, h, d), jnp.bfloat16)
        k = jax.random.normal(jax.random.key(1), (b, nk, h, d), jnp.bfloat16)
        v = jax.random.normal(jax.random.key(2), (b, nk, h, d), jnp.bfloat16)
        t_flash = _median_time(
            timed_attn(functools.partial(flash_attention, interpret=False)),
            q, k, v) / ATTN_SCAN
        t_xla = _median_time(timed_attn(jax.nn.dot_product_attention),
                             q, k, v) / ATTN_SCAN
        flops = 4.0 * b * h * nq * nk * d          # fwd: QK^T + PV
        rec = {
            "exp": "attn", "shape": name,
            "flash_us": round(t_flash * 1e6, 1),
            "xla_us": round(t_xla * 1e6, 1),
            "flash_tflops": round(flops / t_flash / 1e12, 1),
            "xla_tflops": round(flops / t_xla / 1e12, 1),
        }
        print(json.dumps(rec), flush=True)


def exp_trace(out_dir: str = "/tmp/mfu_trace") -> None:
    """Profiler trace of 4 UNet forwards + a best-effort op-level table
    via tensorboard_plugin_profile."""
    import glob

    import jax

    import jax.numpy as jnp

    os.environ.setdefault("CDT_FLASH_ATTENTION", "1")
    cfg, model, params = _build_unet()
    fwd = _forward_fn(model)
    args = _unet_inputs(2, cfg)
    float(fwd(jnp.float32(0.0), params, *args))     # warmup/compile
    jax.profiler.start_trace(out_dir)
    for i in range(4):
        float(fwd(jnp.float32(i + 1.0), params, *args))
    jax.profiler.stop_trace()
    print(json.dumps({"exp": "trace", "dir": out_dir}), flush=True)

    xplanes = sorted(glob.glob(f"{out_dir}/**/*.xplane.pb", recursive=True))
    if not xplanes:
        print(json.dumps({"exp": "trace", "error": "no xplane captured"}))
        return
    try:
        from tensorboard_plugin_profile.convert import raw_to_tool_data

        data, _ = raw_to_tool_data.xspace_to_tool_data(
            [xplanes[-1]], "framework_op_stats", {})
        print(data[:8000] if isinstance(data, str) else str(data)[:8000])
    except Exception as e:  # noqa: BLE001
        print(json.dumps({"exp": "trace",
                          "parse_error": f"{type(e).__name__}: {e}"}))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("experiment",
                    choices=["forward", "batch", "attn", "trace"])
    ap.add_argument("--flash", choices=["0", "1"])
    cli = ap.parse_args()
    {"forward": lambda: exp_forward(cli.flash),
     "batch": exp_batch,
     "attn": exp_attn,
     "trace": exp_trace}[cli.experiment]()


if __name__ == "__main__":
    main()
