#!/usr/bin/env python
"""Are a preset's random weights, drawn a leaf at a time (``models/draw.py``),
the bits the old path gave — ``jax.jit`` of each module's whole ``init``, the
cast inside it? The old path is kept HERE, as the oracle.

Builds each preset's bundle twice, as the registry builds it — once with
``draw_params``, once with the oracle in its place — brings every tree either
makes to the host and compares them leaf by leaf, byte for byte. A line a
preset: trees, leaves, UNEQUAL leaves (with the first few paths), the seconds
of each build and the draw's leaves and programs; exit 1 if any leaf differs.

    python scripts/draw_parity.py [--presets sdxl,sd3-medium]

Run on the chip, as the one process that owns it (~10 min: the oracle's SDXL
program alone compiles for ~5). It fails without a TPU: the CPU's answer is
``tests/test_draw_params.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def jitted_init(module, rng, *args, param_dtype=None, abstract=False):
    """The initialiser as it was: ONE program, the cast inside it."""
    import jax

    from comfyui_distributed_tpu.models.draw import cast_float

    init = lambda *a: cast_float(module.init(*a), param_dtype)  # noqa: E731
    if abstract:
        return jax.eval_shape(init, rng, *args)
    return jax.jit(init)(rng, *args)


def trees_of(preset: str, make) -> tuple[list, float]:
    """Every tree ``make`` is asked for while ``preset``'s bundle is built,
    on the host, by path; and the build's seconds (the device's work too)."""
    import jax

    from comfyui_distributed_tpu.models import draw, registry

    trees = []

    def spy(*args, **kwargs):
        tree = make(*args, **kwargs)
        flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
        trees.append({jax.tree_util.keystr(p): leaf for p, leaf in flat})
        return tree

    shipped, draw.draw_params = draw.draw_params, spy
    try:
        t0 = time.perf_counter()
        bundle = registry.ModelBundle(registry.PRESETS[preset])
        jax.block_until_ready(bundle._core_params())
        seconds = time.perf_counter() - t0
    finally:
        draw.draw_params = shipped
    bundle.release_device()
    return trees, seconds


def unequal(drawn: list, jitted: list) -> list[str]:
    """Paths whose leaf is not the oracle's: dtype, shape, every byte."""
    import numpy as np

    if [sorted(t) for t in drawn] != [sorted(t) for t in jitted]:
        return ["<tree structure>"]
    return [f"{i}{path}" for i, (ours, theirs) in enumerate(zip(drawn, jitted))
            for path, a in ours.items()
            if (a.dtype, a.shape) != (theirs[path].dtype, theirs[path].shape)
            or not np.array_equal(
                np.ascontiguousarray(a).reshape(-1).view(np.uint8),
                np.ascontiguousarray(theirs[path]).reshape(-1).view(np.uint8))]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--presets", default="sdxl,sd3-medium")
    args = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        sys.exit("draw_parity: no TPU here; tests/test_draw_params.py is the "
                 "CPU's answer")
    from comfyui_distributed_tpu import telemetry
    from comfyui_distributed_tpu.models import draw
    from comfyui_distributed_tpu.telemetry import metrics as tm
    from comfyui_distributed_tpu.utils.compile_cache import \
        enable_compile_cache

    telemetry.set_enabled(True)
    enable_compile_cache()
    count = lambda metric: sum(s["value"] for _, s in metric.series())  # noqa: E731
    wrong = 0
    for preset in args.presets.split(","):
        leaves, programs = (count(tm.WEIGHTS_DRAWN_LEAVES),
                            count(tm.WEIGHTS_DRAW_PROGRAMS))
        drawn, draw_s = trees_of(preset, draw.draw_params)
        leaves, programs = (count(tm.WEIGHTS_DRAWN_LEAVES) - leaves,
                            count(tm.WEIGHTS_DRAW_PROGRAMS) - programs)
        jitted, jit_s = trees_of(preset, jitted_init)
        bad = unequal(drawn, jitted)
        wrong += len(bad)
        print(json.dumps({
            "preset": preset, "device": jax.devices()[0].device_kind,
            "trees": len(drawn), "leaves": sum(map(len, drawn)),
            "bytes": sum(a.nbytes for t in drawn for a in t.values()),
            "unequal": len(bad), "first_unequal": bad[:8],
            "drawn_leaves": leaves, "draw_programs": programs,
            "draw_build_s": round(draw_s, 2), "jit_build_s": round(jit_s, 2),
        }), flush=True)
    build = {}
    for labels, snap in tm.PROGRAM_BUILD_SECONDS.series():
        if labels["program"] in ("draw_leaf", "init_shapes", "<lambda>",
                                 "init"):
            build[f"{labels['program']}.{labels['phase']}"] = (
                round(snap["sum"], 2), snap["count"])
    print(json.dumps({"build_seconds_and_counts": build}), flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
