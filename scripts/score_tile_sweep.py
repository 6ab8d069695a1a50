#!/usr/bin/env python
"""Which tile ``(block_q, block_slots)`` the selecting rewriter's scoring
kernel (``ops/block_select_attention.block_score_sums``) should be served
with, and what it takes off ``block_scores``' plain form.

One run times, at ``minicpm-sala``'s served geometry (512 neighbouring
queries of 32 heads over 2 K/V groups a call, 4224 compressed slots,
bfloat16), the eight calls of the chunks at the asked positions: the
kernel ALONE at every candidate pair (one compiled kernel a pair: the
position is the prefetched ``start``), and ``block_scores`` WHOLE (scores,
pooling, the forced and excluded blocks) in its plain form — the parent's
program — and with the kernel at each pair. It prints, a row: seconds
summed over the cell's prefill (16 chunks × 3 sparse layers; a sampled
position stands for the chunks nearest it), the slot tiles scored and
skipped by the rule, and the logit elements a second the kernel visits
(both halves count).

    python scripts/score_tile_sweep.py
        [--pairs 64x384,128x384] [--positions 0,7,15] [--reps 3]
        [--out chiprun_out/tile_sweep]

Run on the chip, as the one process that owns it. It fails without a TPU:
a tile's time on the CPU says nothing. The winner is written into
``block_scores``' defaults by hand (PERF.md §6, PR 48): no program reads
this script's output.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "minicpm-sala.brief64k-sdxl8"
PROMPT, NEW = 65536, 128
CANDIDATES = tuple((q, s) for q in (64, 128, 256) for s in (128, 384, 1408))


def time_calls(call, starts, reps: int) -> float:
    """Median over ``reps`` of the seconds the calls at ``starts`` take in
    a row (one wait at the end: a call is 0.2–2 ms)."""
    import jax

    seconds = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for start in starts:
            out = call(start)
        jax.block_until_ready(out)
        seconds.append(time.perf_counter() - t0)
    return statistics.median(seconds)


def weights_of(chunks: int, asked: str) -> dict:
    """``{position: chunks it stands for}``: the scores' cost is linear in
    the position, a sampled chunk stands for those nearest it."""
    at = sorted({int(p) for p in asked.split(",")})
    weights = {p: 0 for p in at}
    for c in range(chunks):
        weights[min(at, key=lambda p: abs(p - c))] += 1
    return weights


def sweep(pairs, asked: str, reps: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.models.llm_sala import SalaConfig
    from comfyui_distributed_tpu.ops import block_select_attention as bsa

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"a tile is timed on a TPU, not on {device.platform}")
    cfg = SalaConfig.sala_cut()
    sel, dtype = cfg.selection, jnp.dtype(cfg.dtype)
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    Q, C = cfg.select_rows, cfg.prefill_chunk_tokens
    Sc = cfg.cache_slots(cfg.cache_rows(PROMPT + NEW))
    layers, scale = len(cfg.sparse_layers), d ** -0.5
    weights = weights_of(PROMPT // C, asked)
    keys = jax.random.split(jax.random.key(0), 2)
    q = jax.random.normal(keys[0], (Q, H, d), jnp.float32)
    kc = jax.random.normal(keys[1], (G, Sc, d), dtype)

    def starts(p):
        return [jnp.int32(p * C + n * Q) for n in range(C // Q)]

    def whole(kernel, bq=bsa.SCORE_BLOCK_Q, slots=bsa.SCORE_BLOCK_SLOTS):
        f = jax.jit(lambda start: bsa.block_scores(
            q, kc, start + jnp.arange(Q), scale, dtype, sel, kernel, bq,
            slots))
        jax.block_until_ready(f(jnp.int32(0)))               # compiles
        return f

    def prefill_s(call):
        return layers * sum(w * time_calls(call, starts(p), reps)
                            for p, w in weights.items())

    plain = whole("lax")
    plain_s = prefill_s(plain)
    print(f"block_scores, plain: {plain_s:.4f} s a prefill", flush=True)
    rows = []
    for bq, slots in pairs or CANDIDATES:
        row = {"block_q": bq, "block_slots": slots}
        try:
            qt = bsa._head_major_tiles((q * scale).astype(dtype), G, bq)

            def alone(start, qt=qt, bq=bq, slots=slots):
                return bsa.block_score_sums(
                    qt, kc, start, block_q=bq, block_slots=slots,
                    stride=sel.kernel_stride, interpret=False)

            jax.block_until_ready(alone(jnp.int32(0)))       # compiles
            with_kernel = whole("pallas", bq, slots)
            worst = float(np.abs(
                np.nan_to_num(np.asarray(with_kernel(jnp.int32(7 * C))),
                              posinf=0.0, neginf=0.0)
                - np.nan_to_num(np.asarray(plain(jnp.int32(7 * C))),
                                posinf=0.0, neginf=0.0)).max())
            kernel_s, whole_s = prefill_s(alone), prefill_s(with_kernel)
        except Exception as e:  # noqa: BLE001 — the compiler's refusal
            row["refused"] = str(e)[:300]
            rows.append(row)
            print(f"{bq}x{slots}: refused {row['refused']}", flush=True)
            continue
        T = Sc // slots
        scored = sum(
            w * int(bsa.last_slot_tile(
                p * C + np.arange(0, C, bq), bq, slots, sel.kernel_stride,
                T, np.clip).sum() + C // bq)
            for p, w in weights.items()) * G * layers
        total = sum(weights.values()) * (C // bq) * T * G * layers
        row.update(
            kernel_s=kernel_s, block_scores_s=whole_s,
            scored_tiles=scored, skipped_tiles=total - scored,
            elements_per_s=2 * scored * bq * (H // G) * slots / kernel_s,
            worst_score_difference=worst)
        rows.append(row)
        print(f"{bq}x{slots}: kernel {kernel_s:.4f} s, block_scores "
              f"{whole_s:.4f} s a prefill (plain {plain_s:.4f}); worst "
              f"difference {worst:.2e}", flush=True)
    return {"cell": CELL, "positions": {str(p): w
                                        for p, w in weights.items()},
            "reps": reps, "plain_block_scores_s": plain_s,
            "device": {"platform": device.platform,
                       "kind": device.device_kind,
                       "count": jax.device_count()},
            "rows": rows}


def table(result: dict) -> str:
    """The result as PERF.md holds it."""
    lines = [f"`block_score_sums` ({result['cell']}; positions "
             f"{','.join(result['positions'])}; `block_scores` plain "
             f"{result['plain_block_scores_s']:.4f} s a prefill)",
             "| tile (queries × slots) | kernel alone, s a prefill | "
             "`block_scores` with it | scored tiles | skipped | G logit "
             "elements/s | worst difference |", "|---|---|---|---|---|---|---|"]
    for r in result["rows"]:
        tile = f"{r['block_q']} × {r['block_slots']}"
        if "refused" in r:
            lines.append(f"| {tile} | refused: {r['refused'][:80]} ||||||")
            continue
        lines.append(
            f"| {tile} | {r['kernel_s']:.4f} | {r['block_scores_s']:.4f} | "
            f"{r['scored_tiles']} | {r['skipped_tiles']} | "
            f"{r['elements_per_s'] / 1e9:.1f} | "
            f"{r['worst_score_difference']:.1e} |")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--pairs", help="block_q x block_slots, comma-separated "
                    "(default: the candidates)")
    ap.add_argument("--positions", default="0,7,15",
                    help="chunk positions, comma-separated")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/tile_sweep")
    args = ap.parse_args(argv)
    pairs = [tuple(int(n) for n in p.split("x"))
             for p in args.pairs.split(",")] if args.pairs else None
    result = sweep(pairs, args.positions, args.reps)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "block_score_sums.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(table(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
