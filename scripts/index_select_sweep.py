#!/usr/bin/env python
"""What the three pieces of the index-selecting rewriter's prefill cost at
``glm-5``'s served geometry, by tile, and what the forms that did NOT ship
would cost (``ops/index_select_attention.py``; PERF.md §6, PR 51).

One run times, ALONE, on seeded random operands, for the chunks at the
asked positions (a chunk of 4096 queries at ``position × 4096`` against a
cache of 69 632 rows, bfloat16):

- ``index``: ``index_score_sums`` (32 index heads of 128) over the tiles of
  ``--index-tiles``;
- ``select``: ``index_select_keep`` (top 2048) at ``--select-rows`` over the
  column tiles of ``--select-tiles`` — the shipped form (PR 54: a step
  searches the column tiles its rows can see, the row block auto-pipelined
  whole) — beside the other candidate (the scores left in HBM, a step
  copying in its visible tiles itself) and the whole-width kernel both
  replace (PR 51's: every pass over all 69 632 columns), in seconds a chunk
  and microseconds a grid step by position, each held to
  ``select_keep_lax``'s bits at every position; and the same rule as XLA
  passes on a slice of the rows;
- ``fill``: ``index_fill_kv`` (a group of 8 heads' keys and values out of
  the latent cache, PR 52) over the row tiles of ``--fill-rows``, beside the
  two forms that did not ship — PR 51's (two buffers of zeros a group, a
  float32 product a 4096-row step, slices, a concatenation, two updates) and
  the same arithmetic left to XLA without them (the buffers carried, a
  bfloat16 product with the rope key added in its fusion) — and beside the
  product's floor (16.35 TFLOP a layer at the chip's 197 TFLOP/s);
- ``core``: ``index_masked_mha`` over ``--heads`` heads of 256/256 under a
  mask of 2048 random kept keys a row, over ``--core-tiles`` (``BQxBK``, or
  ``BQxBK/PART``: the K tile's logits in products of ``PART`` keys, the
  next one set out ahead of a part's softmax) and, a tile, over the K
  extents of ``--core-extents`` — the grid's K axis ending where
  the chunk's last row sees (``chunk``: the shipped form, PR 60), at the
  nearest of four static lengths picked from the position by ``lax.switch``
  (``quarters``), or walking the whole padded cache at every chunk
  (``whole``: what shipped up to PR 59) — each with the grid steps that
  compute and the ones that do nothing (:func:`core_grid_steps`), and from
  two extents of one tile what ONE step of each kind costs; then the
  workspace fill it needs (``masked_chunk_attention`` whole, 64 heads);
- ``gather``: the form that reads a LIST of rows instead of a mask, for
  ``--gather-rows`` queries: ``lax.top_k`` of their scores (the sort),
  the ``[rows, 2048, 576]`` gather of the latent cache, and the absorbed
  multi-query attention over it — scaled to a chunk for comparison.

It prints seconds a call and, summed over a 16-chunk prefill (a sampled
position stands for the chunks nearest it), seconds a layer.

    python scripts/index_select_sweep.py [--positions 0,7,15] [--reps 3]
        [--parts index,select,fill,core,gather]
        [--core-positions all]
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


def timed_queue(fn, *args, calls: int, reps: int):
    """Seconds ``calls`` calls of ``fn`` take set out one behind the other
    (the chip is waited for once, so a short call is not its dispatch)."""
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready([fn(*args) for _ in range(calls)])
        best = min(best, time.perf_counter() - t0)
    return best


def select_whole_rows(scores, start, topk: int, rows: int,
                      interpret: bool = False):
    """The selection as PR 51 shipped it: ``rows`` whole rows of the cache
    in VMEM, every pass of the search over all their columns whatever the
    rows can see."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from comfyui_distributed_tpu.ops import index_select_attention as ops

    n, width = scores.shape
    bits = max(width.bit_length(), 1)

    def kernel(start_ref, s_ref, o_ref):
        row = start_ref[0] + pl.program_id(0) * rows \
            + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
        seen = col <= row
        key = jnp.where(seen, ops.order_key(s_ref[...]),
                        jnp.int32(ops._INT_MIN))
        o_ref[...] = (ops.keep_of(key, col, topk, bits)
                      & seen).astype(jnp.int8)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(n // rows,),
        in_specs=[pl.BlockSpec((rows, width), lambda i, s: (i, 0))],
        out_specs=pl.BlockSpec((rows, width), lambda i, s: (i, 0)))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, width), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=ops._VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.reshape(start, (1,)).astype(jnp.int32), scores)


def select_copied_tiles(scores, start, topk: int, rows: int, tile: int,
                        interpret: bool = False):
    """The shipped search with the scores left in HBM: a step sets out a
    copy of each column tile its rows can see and makes a tile's order image
    as the tile arrives; nothing past them is fetched."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from comfyui_distributed_tpu.ops import index_select_attention as ops

    n, width = scores.shape
    tiles, bits = width // tile, max(width.bit_length(), 1)

    def kernel(start_ref, s_hbm, o_ref, s_buf, key_ref, sems):
        i = pl.program_id(0)
        first = start_ref[0] + i * rows

        def copy(t):
            at = pl.ds(pl.multiple_of(t * tile, tile), tile)
            return pltpu.make_async_copy(
                s_hbm.at[pl.ds(pl.multiple_of(i * rows, rows), rows), at],
                s_buf.at[:, at], sems.at[t])

        @pl.loop(0, jnp.minimum((first + rows + tile - 1) // tile, tiles))
        def _fetch(t):
            copy(t).start()

        def arrived(t, at):
            copy(t).wait()
            return s_buf[:, at]

        ops._select_visible(first, arrived, o_ref, key_ref, topk=topk,
                            position_bits=bits, tile=tile)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(n // rows,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((rows, width), lambda i, s: (i, 0)),
        scratch_shapes=[pltpu.VMEM((rows, width), jnp.float32),
                        pltpu.VMEM((rows, width), jnp.int32),
                        pltpu.SemaphoreType.DMA((tiles,))])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, width), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=ops._VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.reshape(start, (1,)).astype(jnp.int32), scores)


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


CORE_EXTENTS = ("whole", "quarters", "chunk")


def quarter_lengths(num_k_blocks: int) -> list:
    """The four static K extents of the ``quarters`` form."""
    return [-(-num_k_blocks * n // 4) for n in (1, 2, 3, 4)]


def core_grid_steps(chunk: int, cache_rows: int, chunks: int, block_q: int,
                    block_k: int, extent: str) -> tuple:
    """``(visible, skipped)``: the grid steps ONE head's attention kernel
    takes over a prefill of ``chunks`` chunks of ``chunk`` queries against a
    cache of ``cache_rows`` rows at a ``block_q`` × ``block_k`` tile — those
    that multiply a K block (a query tile's blocks up to its last row's) and
    those past them that do nothing — by how far the grid's K axis goes."""
    nk = cache_rows // block_k
    visible = skipped = 0
    for start in range(0, chunks * chunk, chunk):
        reach = min(-(-(start + chunk) // block_k), nk)
        steps = {"whole": nk, "chunk": reach,
                 "quarters": min(n for n in quarter_lengths(nk)
                                 if n >= reach)}[extent]
        for first in range(start, start + chunk, block_q):
            seen = min((first + block_q - 1) // block_k, nk - 1) + 1
            visible, skipped = visible + seen, skipped + steps - seen
    return visible, skipped


def core_tile(text: str) -> tuple:
    """``BQxBK`` or ``BQxBK/PART`` → ``(block_q, block_k, part)``; no part:
    the whole K tile's logits in one product (the plain step)."""
    tile, _, part = text.partition("/")
    block_q, block_k = (int(x) for x in tile.split("x"))
    return block_q, block_k, int(part or block_k)


def core_form(extent: str, num_heads: int, block_q: int, block_k: int,
              part: int, interpret: bool = False):
    """``f(q, k, v, keep, start)``: the attention kernel with its grid's K
    axis as ``extent`` says (``chunk`` is ``ops.index_masked_mha`` itself)."""
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.ops import index_select_attention as ops

    tile = dict(num_heads=num_heads, block_q=block_q, block_k=block_k,
                part=part, interpret=interpret)
    if extent == "chunk":
        return lambda *a: ops.index_masked_mha(*a, **tile)

    def over(steps: int):
        return lambda *a: ops.masked_mha_call(*a, steps, **tile)

    def form(q, k, v, keep, start):
        nk = k.shape[0] // block_k
        if extent == "whole":
            return over(nk)(q, k, v, keep, start)
        lengths = quarter_lengths(nk)
        reach = ops.core_k_steps(start, q.shape[0], block_k, nk)
        return jax.lax.switch(
            jnp.searchsorted(jnp.asarray(lengths), reach),
            [over(n) for n in lengths], q, k, v, keep, start)

    return jax.jit(form)


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
    parser.add_argument("--select-rows", default="32,64")
    parser.add_argument("--select-tiles", default="2048,4096")
    parser.add_argument("--core-tiles", default="1024x1024,2048x1024,"
                        "4096x1024,2048x2048,4096x512,1024x2048,"
                        "1024x1024/512,1024x2048/1024,1024x2048/512,"
                        "2048x2048/1024,2048x2048/512,1024x4096/1024",
                        help="BQxBK[/PART]: a query tile, a K tile and the "
                             "keys of it whose logits are one product")
    parser.add_argument("--core-extents", default=",".join(CORE_EXTENTS))
    parser.add_argument("--core-positions", default="all",
                        help="'all' 16 chunks (the skipped steps differ at "
                             "every one) or a list as --positions")
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
        # of the cache's LAST 1024 positions: every column is a real score
        scores = ops.index_score_sums(
            q_i[:, :1024], w[:1024], k_i, jnp.int32(S - 1024), block_q=256,
            block_k=1024, interpret=False)
    if "select" in parts:
        calls = 32           # of 1024 rows in one program: 8 chunks' worth
        # a chunk's first and last 1024 rows at each position, as XLA passes
        plain = jax.jit(lambda sc, s: ops.select_keep_lax(sc, s, TOPK))
        wants = {s: plain(scores, jnp.int32(s))
                 for p in positions for s in (p * C, p * C + 3072)}

        def line(name, form, rows):
            """Seconds a chunk of 4096 queries by position — four calls of
            1024 rows, as the model makes them, ``calls`` of them behind
            ONE dispatch — and whether the form's mask is the XLA form's
            in a chunk's first and last 1024 rows."""
            walk = jax.jit(lambda sc, s: jax.lax.map(
                lambda j: form(sc, s + j * 1024)[:8, :128],
                jnp.arange(calls) % 4))
            by = {p: 4 * timed(walk, scores, jnp.int32(p * C),
                               reps=args.reps) / calls for p in positions}
            mask = jax.jit(form)
            same = all(bool(jnp.array_equal(mask(scores, jnp.int32(s)), want))
                       for s, want in wants.items())
            step = {p: round(1e6 * t * rows / C, 2) for p, t in by.items()}
            report[f"select.{name}"] = {
                "by_position": by, "layer_s": over_prefill(by),
                "step_us": step, "equals_lax": same}
            print(f"[sweep] select {name}: a chunk {by}, a {rows}-row step "
                  f"{step} us, layer {over_prefill(by):.4f} s; the XLA "
                  f"form's mask at every position: {same}", flush=True)

        for rows in (int(r) for r in args.select_rows.split(",")):
            for tile in (int(t) for t in args.select_tiles.split(",")):
                line(f"visible.rows{rows}.tile{tile}",
                     lambda sc, s: ops.index_select_keep(
                         sc, s, topk=TOPK, rows=rows, tile=tile,
                         interpret=False), rows)
                line(f"copied.rows{rows}.tile{tile}",
                     lambda sc, s: select_copied_tiles(sc, s, TOPK, rows,
                                                       tile), rows)
            line(f"whole.rows{rows}",
                 lambda sc, s: select_whole_rows(sc, s, TOPK, rows), rows)
        lax_rows = 128
        t = timed(plain, scores[:lax_rows], jnp.int32(15 * C),
                  reps=args.reps)
        report["select.lax"] = {"rows": lax_rows, "seconds": t,
                                "chunk_s": t * C / lax_rows}
        print(f"[sweep] select as XLA passes: {t:.4f} s for {lax_rows} rows "
              f"= {t * C / lax_rows:.3f} s a chunk", flush=True)
        same = bool(jnp.array_equal(
            ops.select_keep(scores[:lax_rows], jnp.int32(15 * C), TOPK),
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
        at = list(range(CHUNKS)) if args.core_positions == "all" \
            else [int(p) for p in args.core_positions.split(",")]
        core_tiles = [core_tile(t) for t in args.core_tiles.split(",")]
        forms = {(tile, extent): core_form(extent, g, *tile)
                 for tile in core_tiles
                 for extent in args.core_extents.split(",")}
        by = {form: {} for form in forms}
        for p in at:             # a position's mask once for all the forms
            keep = (jax.random.uniform(keys[6], (C, S))
                    < TOPK / ((p + 1) * C)).astype(jnp.int8)
            keep = keep * (jnp.arange(S)[None, :]
                           <= p * C + jnp.arange(C)[:, None])
            keep = keep.at[:, 0].set(1).astype(jnp.int8)
            for form, call in forms.items():
                by[form][p] = timed_queue(
                    call, q, k, v, keep, jnp.int32(p * C), calls=H // g,
                    reps=args.reps)
            del keep
        layer = {}
        for (tile, extent), by_p in by.items():
            bq, bk, part = tile
            name = f"{bq}x{bk}/{part}"
            visible, skipped = (H * n for n in core_grid_steps(
                C, S, CHUNKS, bq, bk, extent))
            layer[tile, extent] = (over_prefill(by_p), visible, skipped)
            report[f"core.{name}.{extent}"] = {
                "by_position": by_p, "layer_s": layer[tile, extent][0],
                "visible_steps": visible, "skipped_steps": skipped}
            print(f"[sweep] core {name} {extent} (x{H // g}: 64 heads): "
                  f"layer {layer[tile, extent][0]:.4f} s, {visible} "
                  f"visible + {skipped} skipped steps; by position "
                  f"{ {p: round(t, 4) for p, t in by_p.items()} }",
                  flush=True)
        if args.core_positions == "all":
            # two extents of one tile differ in skipped steps alone
            for tile in core_tiles:
                if not {(tile, "whole"), (tile, "chunk")} <= set(layer):
                    continue
                (t_w, visible, s_w), (t_c, _, s_c) = (
                    layer[tile, "whole"], layer[tile, "chunk"])
                bq, bk, part = tile
                skip_us = 1e6 * (t_w - t_c) / (s_w - s_c)
                step_us = (1e6 * t_c - skip_us * s_c) / visible
                peak_us = 1e6 * bq * bk * (DK + DV) * 2 / PEAK_FLOPS
                report[f"core.{bq}x{bk}/{part}.step_us"] = {
                    "skipped": skip_us, "visible": step_us,
                    "visible_at_peak": peak_us}
                print(f"[sweep] core {bq}x{bk}/{part}: a skipped step "
                      f"{skip_us:.3f} us, a visible one {step_us:.3f} us "
                      f"({100 * peak_us / step_us:.1f}% of the matrix "
                      f"units' peak)", flush=True)
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
