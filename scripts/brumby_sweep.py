#!/usr/bin/env python
"""What the pieces of ``brumby-14b-base``'s programs cost at the served
geometry (``ops/power_retention.py``, ``models/llm_brumby.py``; PERF.md §6,
PR 61): 40 query heads over 8 K/V heads of 128, a chunk of 4096 rows, the
state ``[8, 128, 8320]`` float32 a layer.

One run times, ALONE, on seeded random operands:

- ``chunk``: ``retention_chunk`` for one chunk — the ``lax`` walk and the
  Pallas kernel over ``--blocks`` — with each one's share of the matrix
  unit's peak by the state form's two products at the EXACT ``D`` (8256:
  ``kinds/brumby.py``'s count, the same whatever implements it);
- ``step``: ``retention_step`` a token in a scan of 64 — XLA's two fusions
  and the step kernel — with its share of the memory's peak by the state read
  and written once (ALONE the two read the other way round than inside
  ``llm_decode``, where the trace decides: docs/kernels.md);
- ``swiglu``: the 5120 × 17 408 SwiGLU at 4096 rows, and its share of the
  matrix unit's peak.

``φ`` is held in 65 lane tiles (``D`` 8320) and the normaliser as ``Z`` [128,
128] by one rule (the op's docstring); the kernel has no other ``D`` to time.
PR 61's calls also read a form that was NOT kept (docs/kernels.md): the
kernel's read split into groups of 13, 5 and 1 tiles.

    python scripts/brumby_sweep.py [--parts chunk,step,swiglu]
        [--blocks 128,256,512] [--reps 3] [--out chiprun_out/pr61]

Run on the chip, as the one process that owns it. It fails without a TPU: a
kernel's time on the CPU says nothing. No program reads this script's output.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from scripts.keye_sweep import timed  # noqa: E402

C, H, G, D_HEAD = 4096, 40, 8, 128
STEPS = 64            # tokens of the step's timing scan
HIDDEN, WIDTH = 5120, 17408
D_EXACT = D_HEAD * (D_HEAD + 1) // 2
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9
# the state form's two products a position a layer (kinds/brumby.py's count)
STATE_FLOPS = 2.0 * D_EXACT * (D_HEAD + 1) * (H + G)


def operands(rows: int):
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.ops import power_retention as P

    keys = jax.random.split(jax.random.key(61), 6)
    scale = D_HEAD ** -0.25
    return dict(
        S=jax.random.normal(keys[0], (G, D_HEAD, P.width(D_HEAD))),
        Z=jnp.eye(D_HEAD)[None] * jnp.ones((G, 1, 1)),
        q=jax.random.normal(keys[1], (rows, H, D_HEAD)) * scale,
        k=jax.random.normal(keys[2], (rows, G, D_HEAD)) * scale,
        v=jax.random.normal(keys[3], (rows, G, D_HEAD)),
        log_g=jax.nn.log_sigmoid(
            4.0 + jax.random.normal(keys[4], (rows, G))))


def sweep_chunk(blocks, reps: int) -> list:
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.ops import power_retention as P

    x = operands(C)
    lines, walked = [], None
    for form, block in [("lax", 256)] + [("pallas", b) for b in blocks]:
        fn = jax.jit(lambda S, Z, q, k, v, log_g, form=form, block=block:
                     P.retention_chunk(S, Z, q, k, v, log_g, C - 128,
                                       jnp.bfloat16, block, kernel=form))
        try:
            seconds = timed(fn, *x.values(), reps=reps)
            o, S, _ = fn(*x.values())
            walked = (o, S) if walked is None else walked
            line = {"ms": 1e3 * seconds,
                    "mxu_pct": 100 * C * STATE_FLOPS / PEAK_FLOPS / seconds,
                    # against the lax walk: the outputs' real rows, the state
                    "o_rel": float(jnp.linalg.norm((o - walked[0])[:C - 128])
                                   / jnp.linalg.norm(walked[0][:C - 128])),
                    "S_rel": float(jnp.linalg.norm(S - walked[1])
                                   / jnp.linalg.norm(walked[1]))}
        except Exception as e:  # noqa: BLE001 — the compiler's word
            line = {"refused": str(e).splitlines()[0][:300]}
        lines.append({"part": "chunk", "form": form, "block": block, **line})
        print(json.dumps(lines[-1]), flush=True)
    return lines


def sweep_step(reps: int) -> list:
    """``STEPS`` tokens through one layer's state in a scan (the carry stays
    where it lies, as in ``llm_decode``), a token's time the scan's over
    its length."""
    import jax

    from comfyui_distributed_tpu.ops import power_retention as P

    x = operands(STEPS)
    lines = []
    for form in ("lax", "pallas"):
        def walk(S, Z, q, k, v, log_g, form=form):
            def body(carry, row):
                S, Z, o = P.retention_step(*carry, *row, kernel=form)
                return (S, Z), o
            return jax.lax.scan(body, (S, Z), (q, k, v, log_g))

        seconds = timed(jax.jit(walk), *x.values(), reps=reps) / STEPS
        moved = 2 * 4 * (x["S"].size + x["Z"].size)
        lines.append({"part": "step", "form": form, "us": 1e6 * seconds,
                      "hbm_pct": 100 * moved / PEAK_BYTES / seconds})
        print(json.dumps(lines[-1]), flush=True)
    return lines


def sweep_swiglu(reps: int) -> list:
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models.llm_hybrid import _swiglu

    keys = jax.random.split(jax.random.key(3), 3)
    x = jax.random.normal(keys[0], (C, HIDDEN))
    ffn = {"w_gu": (jax.random.normal(keys[1], (HIDDEN, 2 * WIDTH))
                    / HIDDEN ** 0.5).astype(jnp.bfloat16),
           "w_down": (jax.random.normal(keys[2], (WIDTH, HIDDEN))
                      / WIDTH ** 0.5).astype(jnp.bfloat16)}
    seconds = timed(jax.jit(lambda x, ffn: _swiglu(x, ffn, jnp.bfloat16)),
                    x, ffn, reps=reps)
    line = {"part": "swiglu", "ms": 1e3 * seconds,
            "mxu_pct": 100 * C * 6.0 * HIDDEN * WIDTH / PEAK_FLOPS / seconds}
    print(json.dumps(line), flush=True)
    return [line]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--parts", default="chunk,step,swiglu")
    parser.add_argument("--blocks", default="128,256,512")
    parser.add_argument("--reps", type=int, default=3)
    parser.add_argument("--out", default="chiprun_out/pr61")
    args = parser.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("brumby_sweep: needs the chip; JAX found "
              f"{jax.devices()[0].platform}", file=sys.stderr)
        return 3
    parts, lines = args.parts.split(","), []
    if "chunk" in parts:
        lines += sweep_chunk([int(b) for b in args.blocks.split(",")],
                             args.reps)
    if "step" in parts:
        lines += sweep_step(args.reps)
    if "swiglu" in parts:
        lines += sweep_swiglu(args.reps)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / "brumby_sweep.json").write_text(json.dumps(lines, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
