#!/usr/bin/env python
"""What the pieces of ``zaya1-8b``'s prefill cost at the served geometry, by
tile and by form (``ops/expert_share.py``, ``ops/expert_stream.py``,
``ops/gqa_attention.py``, ``models/llm_zaya.py``; PERF.md §6, PR 57).
``keye_sweep.py`` is ``keye-vl-2.0-30b-a3b``'s; this is its sibling at 16 HELD
experts of 2048 × 2048 top 1 and 8 query heads over 2 K/V heads of 128.

One run times, ALONE, on seeded random operands (bfloat16):

- ``experts``: a chunk of 4096 rows × top 1 over 16 held experts — the rows
  drawn even over the experts (``even``), as a brief that cycles ~65 ids
  routes them (``skewed``: 64 distinct rows) and with ONE expert taking all
  (``one``) — through ``held_part_grouped`` (the loop) and
  ``held_part_streamed`` (the kernel, an expert's 24 MiB whole), over
  ``--expert-tiles``; beside the floors (4096 rows × 25.2 MFLOP at 197
  TFLOP/s; 16 experts × 25.2 MB at 819 GB/s). PR 57's call 2 also read the
  kernel with a second grid axis over the expert's inner width, in blocks of
  1024 and 512 columns: slower than whole at even and skewed routing, so
  that axis was not kept (PERF.md §6);
- ``core``: ``gqa_causal_mha`` for the chunks at ``--positions`` (a chunk of
  4096 queries at ``position × 4096`` against 131 072 rows) at 8 q / 2 kv
  (this model) and at 48 q / 8 kv (``trinity-large-preview``'s full layer),
  over ``--core-tiles``, with each one's share of the matrix unit's peak;
- ``mix``: ``llm_zaya._cca_mix`` (both convolutions, the q-k mean, the norms,
  the temperature, rope on half, the value shift) for one chunk, alone.

    python scripts/zaya_sweep.py [--parts experts,core,mix] [--reps 3]
        [--out chiprun_out/pr57]

Run on the chip, as the one process that owns it. It fails without a TPU: a
kernel's time on the CPU says nothing. No program reads this script's output.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from scripts.keye_sweep import tiles_of, timed  # noqa: E402

C, S, CHUNKS = 4096, 131072, 32
HIDDEN, WIDTH, EXPERTS = 2048, 2048, 16
GEOMETRIES = {"zaya_8q2kv": (8, 2), "trinity_48q8kv": (48, 8)}
D_HEAD = 128
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9


def routings(key):
    """``{name: idx [C,1]}``: even, a cycled brief's, one expert's."""
    import jax
    import jax.numpy as jnp

    even = jax.random.permutation(key, jnp.arange(C) % EXPERTS)
    of_row = jax.random.randint(key, (64,), 0, EXPERTS)
    return {"even": even[:, None].astype(jnp.int32),
            "skewed": of_row[jnp.arange(C) % 64][:, None].astype(jnp.int32),
            "one": jnp.full((C, 1), 3, jnp.int32)}


def sweep_experts(tiles, reps: int) -> list:
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.ops import expert_share

    keys = jax.random.split(jax.random.key(57), 5)
    x = jax.random.normal(keys[0], (C, HIDDEN), jnp.float32)
    e_gu = (jax.random.normal(keys[1], (EXPERTS, HIDDEN, 2 * WIDTH),
                              jnp.float32) / HIDDEN ** 0.5).astype(jnp.bfloat16)
    e_down = (jax.random.normal(keys[2], (EXPERTS, WIDTH, HIDDEN),
                                jnp.float32) / WIDTH ** 0.5).astype(jnp.bfloat16)
    w = jax.random.uniform(keys[3], (C, 1), jnp.float32, 0.2, 1.0)
    flops = C * 6.0 * HIDDEN * WIDTH
    lines = [{"part": "experts", "form": "floor",
              "products_ms": 1e3 * flops / PEAK_FLOPS,
              "bytes_ms": 1e3 * EXPERTS * 3 * HIDDEN * WIDTH * 2 / PEAK_BYTES}]
    forms = {"grouped": expert_share.held_part_grouped,
             "streamed": functools.partial(expert_share.held_part_streamed,
                                           kernel="pallas")}
    for name, idx in routings(keys[4]).items():
        for (tile,) in tiles:
            for form, held_part in forms.items():
                fn = jax.jit(lambda x, idx, w, e_gu, e_down, tile=tile,
                             held_part=held_part: held_part(
                    x, idx, w, e_gu, e_down, 0, jnp.bfloat16, tile=tile))
                try:
                    seconds = timed(fn, x, idx, w, e_gu, e_down, reps=reps)
                    rows = int(fn(x, idx, w, e_gu, e_down)[1])
                    line = {"ms": 1e3 * seconds, "rows_multiplied": rows,
                            "mxu_pct": 100 * flops / PEAK_FLOPS / seconds}
                except Exception as e:  # noqa: BLE001 — the compiler's word
                    line = {"refused": str(e).splitlines()[0][:200]}
                lines.append({"part": "experts", "routing": name,
                              "tile": tile, "form": form, **line})
                print(json.dumps(lines[-1]), flush=True)
    return lines


def sweep_core(positions, tiles, reps: int) -> list:
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.ops import flash_latent

    lines = []
    for name, (H, G) in GEOMETRIES.items():
        keys = jax.random.split(jax.random.key(H), 3)
        q = jax.random.normal(keys[0], (C, H * D_HEAD), jnp.bfloat16)
        k = jax.random.normal(keys[1], (G, S, D_HEAD), jnp.bfloat16)
        v = jax.random.normal(keys[2], (G, S, D_HEAD), jnp.bfloat16)
        for bq, bk in tiles:
            by_position = {}
            for position in positions:
                by_position[position] = timed(
                    lambda q, k, v, start: flash_latent.gqa_causal_mha(
                        q, k, v, start, num_heads=H, block_q=bq, block_k=bk,
                        interpret=False), q, k, v,
                    jnp.asarray(position * C, jnp.int32), reps=reps)
            at = sorted(by_position)
            layer = sum(by_position[min(at, key=lambda p: abs(p - c))]
                        for c in range(CHUNKS))
            pairs = S * (S + 1) / 2.0
            lines.append({
                "part": "core", "geometry": name, "tile": f"{bq}x{bk}",
                "ms_by_position": {p: 1e3 * s for p, s in by_position.items()},
                "layer_s_estimate": layer,
                "mxu_pct_estimate": 100 * 4 * D_HEAD * H * pairs
                / PEAK_FLOPS / layer})
            print(json.dumps(lines[-1]), flush=True)
    return lines


def sweep_mix(reps: int) -> list:
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import llm_zaya as M

    cfg = M.ZayaConfig.zaya_share()
    p = M.init_zaya(dataclasses.replace(cfg, num_hidden_layers=1,
                                        vocab_size=256), jax.random.key(0))
    attn, rope = p["layers"][0]["attn"], (p["rope"]["cos"][:C],
                                          p["rope"]["sin"][:C])
    keys = jax.random.split(jax.random.key(1), 2)
    z = jax.random.normal(keys[0], (C, cfg.latent_width), jnp.float32)
    vv = jax.random.normal(keys[1], (C, 2 * cfg.head_dim), jnp.float32)
    tails = jnp.zeros((cfg.tail_width,), jnp.float32)
    seconds = timed(jax.jit(lambda attn, z, vv, tails, rope: M._cca_mix(
        cfg, attn, z, vv, tails, rope)), attn, z, vv, tails, rope, reps=reps)
    line = {"part": "mix", "chunk_ms": 1e3 * seconds,
            "prefill_s": seconds * CHUNKS * cfg.num_hidden_layers}
    print(json.dumps(line), flush=True)
    return [line]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--parts", default="experts,core,mix")
    parser.add_argument("--positions", default="0,15,31")
    parser.add_argument("--expert-tiles", default="128,256")
    parser.add_argument("--core-tiles", default="2048x2048,1024x2048")
    parser.add_argument("--reps", type=int, default=3)
    parser.add_argument("--out", default="chiprun_out/pr57")
    args = parser.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("zaya_sweep: needs the chip; JAX found "
              f"{jax.devices()[0].platform}", file=sys.stderr)
        return 3
    parts, lines = args.parts.split(","), []
    if "experts" in parts:
        lines += sweep_experts(tiles_of(args.expert_tiles), args.reps)
    if "core" in parts:
        lines += sweep_core([int(p) for p in args.positions.split(",")],
                            tiles_of(args.core_tiles), args.reps)
    if "mix" in parts:
        lines += sweep_mix(args.reps)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / "zaya_sweep.json").write_text(json.dumps(lines, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
