#!/usr/bin/env python
"""Pre-bake a compile cache + shape catalog for a fleet image.

Runs the same AOT warmup pass a worker runs at boot
(``diffusion/warmup.py``), but as a build step: point it at the cache
directory that ships in the image and every worker booted from that
image starts with ``cache_hit`` for the whole catalog — time-to-ready
drops from full-compile cost to cache-load cost.

    # bake the shipped-workflow catalog for the tiny smoke models
    JAX_COMPILATION_CACHE_DIR=/image/xla python scripts/warmup_catalog.py \
        --models tiny,flux-tiny

    # add explicit shapes beyond the workflow catalog
    python scripts/warmup_catalog.py --models sdxl \
        --shape txt2img:sdxl:1024x1024:30 --shape txt2img:sdxl:768x768:25

    # inspect what would warm, without compiling
    python scripts/warmup_catalog.py --dry-run

Exit status: 0 when every non-skipped program warmed (compiled or cache
hit), 1 when any errored — CI can gate an image build on it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def parse_shape(spec: str):
    """``pipeline:model:WxH:steps[:frames]`` → ProgramKey."""
    from comfyui_distributed_tpu.cluster.shape_catalog import ProgramKey

    parts = spec.split(":")
    if len(parts) not in (4, 5):
        raise argparse.ArgumentTypeError(
            f"bad --shape {spec!r} (want pipeline:model:WxH:steps"
            "[:frames])")
    pipeline, model, wh, steps = parts[:4]
    try:
        w, h = (int(x) for x in wh.lower().split("x"))
        return ProgramKey(pipeline=pipeline, model=model, height=h,
                          width=w, steps=int(steps),
                          frames=int(parts[4]) if len(parts) == 5 else 0)
    except ValueError as e:
        raise argparse.ArgumentTypeError(f"bad --shape {spec!r}: {e}")


def main() -> int:
    ap = argparse.ArgumentParser(
        description="AOT-compile the shape catalog into the persistent "
                    "XLA cache (fleet-image pre-bake)")
    ap.add_argument("--models", default=None,
                    help="csv of model presets eligible to warm "
                         "(default: CDT_WARMUP_MODELS, else everything)")
    ap.add_argument("--workflows-dir", default=None,
                    help="seed the catalog from this directory "
                         "(default: the shipped workflows/)")
    ap.add_argument("--shape", action="append", type=parse_shape,
                    default=[], metavar="P:M:WxH:S[:F]",
                    help="extra program key, e.g. txt2img:sdxl:1024x1024:30")
    ap.add_argument("--catalog", default=None,
                    help="catalog path (default: CDT_SHAPE_CATALOG or "
                         "next to the XLA cache)")
    ap.add_argument("--mesh-devices", type=int, default=None,
                    help="dp width to warm for (default: all devices)")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the catalog and exit without compiling")
    cli = ap.parse_args()

    from comfyui_distributed_tpu.cluster.shape_catalog import ShapeCatalog

    catalog = ShapeCatalog(cli.catalog) if cli.catalog else ShapeCatalog()
    catalog.seed_from_workflows(cli.workflows_dir)
    catalog.update(cli.shape)

    if cli.dry_run:
        print(json.dumps({"catalog": str(catalog.path),
                          "entries": [k.to_dict()
                                      for k in catalog.entries()]},
                         indent=1))
        return 0

    import jax

    from comfyui_distributed_tpu.diffusion.warmup import run_warmup
    from comfyui_distributed_tpu.models.registry import ModelRegistry
    from comfyui_distributed_tpu.parallel import build_mesh
    from comfyui_distributed_tpu.utils.compile_cache import (
        active_cache_dir, enable_compile_cache)

    enable_compile_cache(min_compile_secs=0.0)
    n = cli.mesh_devices or len(jax.devices())
    mesh = build_mesh({"dp": n}, jax.devices()[:n])
    models = ([m.strip() for m in cli.models.split(",") if m.strip()]
              if cli.models is not None else None)

    def progress(entry):
        print(f"[warmup] {entry.key.pipeline}:{entry.key.model} "
              f"{entry.key.width}x{entry.key.height} "
              f"steps={entry.key.steps} → {entry.outcome} "
              f"({entry.seconds:.1f}s)"
              + (f" — {entry.detail}" if entry.detail else ""),
              file=sys.stderr, flush=True)

    report = run_warmup(ModelRegistry(), mesh, catalog.entries(),
                        models=models, on_entry=progress)
    catalog.save()
    summary = {
        "cache_dir": active_cache_dir(),
        "catalog": str(catalog.path),
        "programs": len(report),
        "outcomes": {o: sum(e.outcome == o for e in report)
                     for o in ("cache_hit", "compiled", "error",
                               "skipped")},
        "report": [e.to_dict() for e in report],
    }
    print(json.dumps(summary, indent=1))
    return 1 if summary["outcomes"]["error"] else 0


if __name__ == "__main__":
    sys.exit(main())
