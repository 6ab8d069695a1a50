#!/usr/bin/env python
"""What the three pieces of the index-selecting rewriter's prefill cost at
``glm-5``'s served geometry, by tile, and what the forms that did NOT ship
would cost (``ops/index_select_attention.py``; PERF.md §6, PR 51).

One run times, ALONE, on seeded random operands, for the chunks at the
asked positions (a chunk of 4096 queries at ``position × 4096`` against a
cache of 69 632 rows, bfloat16):

- ``index``: ``index_score_sums`` (32 index heads of 128) over the tiles of
  ``--index-tiles``;
- ``select``: ``index_select_keep`` (top 2048) at ``--select-rows``, and the
  same rule as XLA passes (``select_keep_lax``) on a slice of the rows;
- ``fill``: ``index_fill_kv`` (a group of 8 heads' keys and values out of
  the latent cache, PR 52) over the row tiles of ``--fill-rows``, beside the
  two forms that did not ship — PR 51's (two buffers of zeros a group, a
  float32 product a 4096-row step, slices, a concatenation, two updates) and
  the same arithmetic left to XLA without them (the buffers carried, a
  bfloat16 product with the rope key added in its fusion) — and beside the
  product's floor (16.35 TFLOP a layer at the chip's 197 TFLOP/s);
- ``core``: ``index_masked_mha`` over ``--heads`` heads of 256/256 under a
  mask of 2048 random kept keys a row, over ``--core-tiles``, with the
  workspace fill it needs (``masked_chunk_attention`` whole, 64 heads);
- ``gather``: the form that reads a LIST of rows instead of a mask, for
  ``--gather-rows`` queries: ``lax.top_k`` of their scores (the sort),
  the ``[rows, 2048, 576]`` gather of the latent cache, and the absorbed
  multi-query attention over it — scaled to a chunk for comparison.

It prints seconds a call and, summed over a 16-chunk prefill (a sampled
position stands for the chunks nearest it), seconds a layer.

    python scripts/index_select_sweep.py [--positions 0,7,15] [--reps 3]
        [--parts index,select,fill,core,gather]
        [--out chiprun_out/tile_sweep]

Run on the chip, as the one process that owns it. It fails without a TPU: a
kernel's time on the CPU says nothing. No program reads this script's
output.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

C, S, TOPK, CHUNKS = 4096, 69632, 2048, 16
J, DI, H, DK, DV, RANK, ROPE = 32, 128, 64, 256, 256, 512, 64
# c W_b over the rows each of the 16 chunks sees, a layer, and the chip's peak
FILL_FLOP = sum(C * n for n in range(1, CHUNKS + 1)) * RANK \
    * H * (DK - ROPE + DV) * 2
PEAK_FLOPS = 197e12


def timed(fn, *args, reps: int):
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def timed_in_place(fn, buffers: list, *args, reps: int):
    """:func:`timed` of a function that is GIVEN its ``len(buffers)`` output
    buffers (they are donated) and answers them first: ``buffers`` holds the
    newest."""
    import jax

    n, best = len(buffers), float("inf")
    buffers[:] = jax.block_until_ready(fn(*buffers, *args))[:n]
    for _ in range(reps):
        t0 = time.perf_counter()
        buffers[:] = jax.block_until_ready(fn(*buffers, *args))[:n]
        best = min(best, time.perf_counter() - t0)
    return best


def fill_as_pr51(c, kr, w, n_fill):
    """A group's workspace as ``masked_chunk_attention`` filled it before PR
    52. ``w`` [rank, g·(nope+v)]."""
    import jax
    import jax.numpy as jnp

    g, nope, bf = w.shape[1] // (DK - ROPE + DV), DK - ROPE, c.dtype

    def fill(j, ws):
        k_ws, v_ws = ws
        rows = jax.lax.dynamic_slice_in_dim(c, j * C, C, 0)
        kr_j = jax.lax.dynamic_slice_in_dim(kr, j * C, C, 0)
        kv = jnp.dot(rows, w, preferred_element_type=jnp.float32
                     ).reshape(C, g, nope + DV)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            kr_j[:, None].astype(jnp.float32), (C, g, ROPE))], -1)
        return (jax.lax.dynamic_update_slice_in_dim(
                    k_ws, k.reshape(C, -1).astype(bf), j * C, 0),
                jax.lax.dynamic_update_slice_in_dim(
                    v_ws, kv[..., nope:].reshape(C, -1).astype(bf), j * C, 0))

    return jax.lax.fori_loop(0, n_fill, fill,
                             (jnp.zeros((S, g * DK), bf),
                              jnp.zeros((S, g * DV), bf)))


def fill_as_xla(k_ws, v_ws, c, kr_wide, w_k, w_v, n_fill):
    """The kernel's arithmetic as XLA products: the buffers given (no
    zeros), bfloat16 out of each product's fusion, the rope key added there
    (``w_k``'s rope columns are zero, ``kr_wide``'s others)."""
    import jax
    import jax.numpy as jnp

    g = w_k.shape[1] // DK

    def fill(j, ws):
        k_ws, v_ws = ws
        rows = jax.lax.dynamic_slice_in_dim(c, j * C, C, 0)
        kr_j = jax.lax.dynamic_slice_in_dim(kr_wide, j * C, C, 0)
        k = jnp.dot(rows, w_k, preferred_element_type=jnp.float32) \
            + jnp.tile(kr_j, (1, g)).astype(jnp.float32)
        v = jnp.dot(rows, w_v, preferred_element_type=c.dtype)
        return (jax.lax.dynamic_update_slice_in_dim(
                    k_ws, k.astype(c.dtype), j * C, 0),
                jax.lax.dynamic_update_slice_in_dim(v_ws, v, j * C, 0))

    return jax.lax.fori_loop(0, n_fill, fill, (k_ws, v_ws))


def over_prefill(by_position: dict) -> float:
    """Seconds a layer: every chunk takes its nearest sampled position's."""
    at = sorted(by_position)
    return sum(by_position[min(at, key=lambda p: abs(p - c))]
               for c in range(CHUNKS))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--positions", default="0,7,15")
    parser.add_argument("--reps", type=int, default=3)
    parser.add_argument("--parts", default="index,select,fill,core,gather")
    parser.add_argument("--fill-rows", default="512,1024,2048")
    parser.add_argument("--index-tiles", default="256x1024,512x1024,256x2048")
    parser.add_argument("--select-rows", default="32")
    parser.add_argument("--core-tiles", default="1024x1024,2048x1024,1024x2048")
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--gather-rows", type=int, default=256)
    parser.add_argument("--out", default="chiprun_out/tile_sweep")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.ops import index_select_attention as ops

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"[sweep] needs the chip; JAX found {device.platform}",
              file=sys.stderr)
        return 3
    positions = [int(p) for p in args.positions.split(",")]
    parts = args.parts.split(",")
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.key(0), 12)
    q_i = jax.random.normal(keys[0], (J, C, DI), bf)
    w = jax.random.normal(keys[1], (C, J), jnp.float32) / 64.0
    k_i = jax.random.normal(keys[2], (S, DI), bf)
    report: dict = {"device": device.device_kind, "positions": positions}

    def tiles(text):
        return [tuple(int(x) for x in t.split("x")) for t in text.split(",")]

    if "index" in parts:
        for bq, bk in tiles(args.index_tiles):
            by = {p: timed(lambda s: ops.index_score_sums(
                q_i, w, k_i, s, block_q=bq, block_k=bk, interpret=False),
                jnp.int32(p * C), reps=args.reps) for p in positions}
            report[f"index.{bq}x{bk}"] = {"by_position": by,
                                          "layer_s": over_prefill(by)}
            print(f"[sweep] index {bq}x{bk}: {by} layer "
                  f"{over_prefill(by):.3f} s", flush=True)

    scores = None
    if "select" in parts or "gather" in parts:
        scores = ops.index_score_sums(
            q_i[:, :1024], w[:1024], k_i, jnp.int32(15 * C), block_q=256,
            block_k=1024, interpret=False)
    if "select" in parts:
        for rows in (int(r) for r in args.select_rows.split(",")):
            by = {p: 4 * timed(lambda s: ops.index_select_keep(
                scores, s, topk=TOPK, rows=rows, interpret=False),
                jnp.int32(p * C), reps=args.reps) for p in positions}
            report[f"select.rows{rows}"] = {"by_position": by,
                                            "layer_s": over_prefill(by)}
            print(f"[sweep] select rows {rows} (x4: a chunk): {by} layer "
                  f"{over_prefill(by):.3f} s", flush=True)
        lax_rows = 128
        t = timed(jax.jit(lambda sc, s: ops.select_keep_lax(sc, s, TOPK)),
                  scores[:lax_rows], jnp.int32(15 * C), reps=args.reps)
        report["select.lax"] = {"rows": lax_rows, "seconds": t,
                                "chunk_s": t * C / lax_rows}
        print(f"[sweep] select as XLA passes: {t:.4f} s for {lax_rows} rows "
              f"= {t * C / lax_rows:.3f} s a chunk", flush=True)
        same = bool(jnp.array_equal(
            ops.index_select_keep(scores[:lax_rows], jnp.int32(15 * C),
                                  topk=TOPK, rows=32, interpret=False),
            ops.select_keep_lax(scores[:lax_rows], jnp.int32(15 * C), TOPK)))
        report["select.kernel_equals_lax"] = same
        print(f"[sweep] the kernel's mask equals the XLA form's: {same}",
              flush=True)

    if "fill" in parts:
        g, nope = ops.HEADS_PER_PASS, DK - ROPE
        c = jax.random.normal(keys[9], (S, RANK), bf)
        kr = jax.random.normal(keys[10], (S, ROPE), bf)
        w_b = jax.random.normal(keys[11], (RANK, H * (nope + DV)), bf) / 22.0
        kr_wide, w_k, w_v = ops.fill_operands(kr, w_b, H, nope, g, bf)
        w_g = jnp.moveaxis(w_b.reshape(RANK, H // g, -1), 1, 0)
        floor = FILL_FLOP / PEAK_FLOPS
        report["fill.floor_layer_s"] = floor

        # an arm fills the workspace of every group of a layer's chunk in
        # ONE program, as the model's loop over the groups does, and hands
        # back a row of each (the buffers of eight separate calls cost more
        # to allocate than a short fill takes)
        def last_row(ws, n_fill):
            return tuple(jax.lax.dynamic_slice_in_dim(a, n_fill * C - 1, 1, 0)
                         for a in ws)

        def line(name, by):
            layer = over_prefill(by)
            report[f"fill.{name}"] = {"by_position": by, "layer_s": layer}
            print(f"[sweep] fill {name} (64 heads by {g}): {by} layer "
                  f"{layer:.3f} s; the product's floor {floor:.3f} s",
                  flush=True)

        for rows in (int(r) for r in args.fill_rows.split(",")):
            line(f"kernel.rows{rows}", {p: timed(jax.jit(
                lambda n: jax.lax.map(lambda w: last_row(ops.index_fill_kv(
                    c, kr_wide, *w, n * C, num_heads=g, nope=nope,
                    block_rows=rows, interpret=False), n), (w_k, w_v))),
                jnp.int32(p + 1), reps=args.reps) for p in positions})
        line("pr51", {p: timed(jax.jit(
            lambda n: jax.lax.map(lambda w: last_row(
                fill_as_pr51(c, kr, w, n), n), w_g)),
            jnp.int32(p + 1), reps=args.reps) for p in positions})

        def carried(k_ws, v_ws, n):
            def one(ws, w):
                ws = fill_as_xla(*ws, c, kr_wide, *w, n)
                return ws, last_row(ws, n)

            (k_ws, v_ws), rows = jax.lax.scan(one, (k_ws, v_ws), (w_k, w_v))
            return k_ws, v_ws, rows

        ws = [jnp.zeros((S, g * DK), bf), jnp.zeros((S, g * DV), bf)]
        xla = jax.jit(carried, donate_argnums=(0, 1))
        line("xla", {p: timed_in_place(xla, ws, jnp.int32(p + 1),
                                       reps=args.reps) for p in positions})
        w_g = w_g[0]
        k_ws, v_ws = ops.index_fill_kv(
            c, kr_wide, w_k[0], w_v[0], jnp.int32(16 * C), num_heads=g,
            nope=nope, block_rows=ops.FILL_ROWS, interpret=False)
        k_51, v_51 = jax.jit(fill_as_pr51)(c, kr, w_g, jnp.int32(16))
        same = bool(jnp.array_equal(k_ws[:16 * C], k_51[:16 * C])
                    & jnp.array_equal(v_ws[:16 * C], v_51[:16 * C]))
        report["fill.kernel_equals_pr51"] = same
        print(f"[sweep] the kernel's 65 536 rows equal PR 51's bit for bit: "
              f"{same}", flush=True)
        del ws, k_ws, v_ws, k_51, v_51

    if "core" in parts:
        g = args.heads
        q = jax.random.normal(keys[3], (C, g * DK), bf) / 16.0
        k = jax.random.normal(keys[4], (S, g * DK), bf)
        v = jax.random.normal(keys[5], (S, g * DV), bf)
        for bq, bk in tiles(args.core_tiles):
            by = {}
            for p in positions:
                seen = (p + 1) * C
                keep = (jax.random.uniform(keys[6], (C, S))
                        < TOPK / seen).astype(jnp.int8)
                keep = keep * (jnp.arange(S)[None, :]
                               <= p * C + jnp.arange(C)[:, None])
                keep = keep.at[:, 0].set(1).astype(jnp.int8)
                by[p] = (H // g) * timed(lambda s: ops.index_masked_mha(
                    q, k, v, keep, s, num_heads=g, block_q=bq, block_k=bk,
                    interpret=False), jnp.int32(p * C), reps=args.reps)
            report[f"core.{bq}x{bk}"] = {"by_position": by,
                                         "layer_s": over_prefill(by)}
            print(f"[sweep] core {bq}x{bk} (x{H // g}: 64 heads): {by} "
                  f"layer {over_prefill(by):.3f} s", flush=True)
        # whole, with the workspace fill: the op the model calls
        q_nope = jax.random.normal(keys[7], (C, H, DK - ROPE), bf)
        q_rope = jax.random.normal(keys[8], (C, H, ROPE), bf)
        c = jax.random.normal(keys[9], (S, RANK), bf)
        kr = jax.random.normal(keys[10], (S, ROPE), bf)
        w_b = jax.random.normal(keys[11], (RANK, H * (DK - ROPE + DV)),
                                bf) / 22.0
        by = {}
        for p in positions:
            keep = (jax.random.uniform(keys[6], (C, S))
                    < TOPK / ((p + 1) * C)).astype(jnp.int8)
            keep = (keep * (jnp.arange(S)[None, :]
                            <= p * C + jnp.arange(C)[:, None])
                    ).at[:, 0].set(1).astype(jnp.int8)
            by[p] = timed(jax.jit(
                lambda kp, s: ops.masked_chunk_attention(
                    q_nope, q_rope, c, kr, kp, s, w_b, 1 / 16.0, bf)),
                keep, jnp.int32(p * C), reps=args.reps)
        report["core.whole"] = {"by_position": by,
                                "layer_s": over_prefill(by)}
        print(f"[sweep] core whole (fill + 64 heads): {by} layer "
              f"{over_prefill(by):.3f} s", flush=True)

    if "gather" in parts:
        n = args.gather_rows
        rows_scores = jnp.where(
            jnp.arange(S)[None, :] <= 15 * C + jnp.arange(n)[:, None],
            scores[:n], -jnp.inf)
        sort_s = timed(jax.jit(lambda sc: jax.lax.top_k(sc, TOPK)[1]),
                       rows_scores, reps=args.reps)
        at = jax.lax.top_k(rows_scores, TOPK)[1]
        cache = jax.random.normal(keys[9], (S, RANK + ROPE), bf)
        gather_s = timed(jax.jit(lambda a: cache[a]), at, reps=args.reps)
        q_abs = jax.random.normal(keys[7], (n, H, RANK + ROPE), bf)

        def mqa(qa, a):
            rows = cache[a]                               # [n, k, 576]
            s = jnp.einsum("nhc,nkc->nhk", qa, rows,
                           preferred_element_type=jnp.float32)
            p = jax.nn.softmax(s, axis=-1).astype(bf)
            return jnp.einsum("nhk,nkc->nhc", p, rows[..., :RANK],
                              preferred_element_type=jnp.float32)

        mqa_s = timed(jax.jit(mqa), q_abs, at, reps=args.reps)
        scale = C / n
        report["gather"] = {
            "rows": n, "top_k_s": sort_s, "gather_s": gather_s,
            "gather_and_attend_s": mqa_s,
            "chunk_s": {"top_k": sort_s * scale, "gather": gather_s * scale,
                        "gather_and_attend": mqa_s * scale},
            "layer_s": {"top_k": sort_s * scale * CHUNKS,
                        "gather_and_attend": mqa_s * scale * CHUNKS}}
        print(f"[sweep] list form, {n} queries at the last chunk: top_k "
              f"{sort_s:.4f} s, gather {gather_s:.4f} s, gather + absorbed "
              f"attention {mqa_s:.4f} s; a layer (x{scale * CHUNKS:g}): "
              f"top_k {sort_s * scale * CHUNKS:.2f} s, gather + attend "
              f"{mqa_s * scale * CHUNKS:.2f} s", flush=True)

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / "index_select_sweep.json").write_text(json.dumps(report))
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
