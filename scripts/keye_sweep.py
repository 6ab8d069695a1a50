#!/usr/bin/env python
"""What the pieces of ``keye-vl-2.0-30b-a3b``'s prefill and decode cost at
the served geometry, by tile and by form (``ops/index_gqa_attention.py``,
``ops/expert_share.py``; PERF.md §6, PR 53). ``index_select_sweep.py`` is
``glm-5``'s; this is its sibling at 16 index heads of 64, 32 query heads over
4 K/V heads of 128, and 128 HELD experts of width 768.

One run times, ALONE, on seeded random operands, for the chunks at the asked
positions (a chunk of 4096 queries at ``position × 4096`` against a cache of
69 632 rows, bfloat16):

- ``index``: ``index_score_sums`` over the tiles of ``--index-tiles``;
- ``select``: ``index_select_keep`` (top 2048, GLM's kernel as it is);
- ``core``: ``index_masked_gqa``'s call under a mask of ``min(2048, t + 1)``
  random kept keys a row, one line a FORM (PR 65): every tile of
  ``--core-tile`` by every part of ``--core-part`` (rows of one head a logit
  product, the next part's product set out ahead of a part's softmax;
  ``whole`` is a head at a time, the step as it was), over the grid of
  ``--core-extent`` (``chunk``: the K axis ends where the chunk sees, what
  ships; ``whole``: every block of the padded cache, as it was), and beside
  them PR 60's form at the shipped tile — the K TILE in parts of
  ``--core-kpart`` keys, a rescale of the running tiles a part
  (:func:`k_parts_kernel`, this script's own: the module ships one form);
- ``experts``: ``held_part_grouped`` at 128 held experts, 4096 rows × top 8
  = 32 768 slots a chunk, over ``--expert-tiles`` — the rows drawn even over
  the experts (``even``) and as a brief repeats its tokens (``skewed``: 256
  distinct rows) — beside the same tiles walked over rows gathered ONCE in
  expert order (``sorted``: what a form without a gather and a scatter-add a
  tile would cost), the ONE-kernel form that streams the experts' matrices
  (``streamed``: ``held_part_streamed``), and the product's floor (32 768 rows × 9.44 MFLOP at the
  chip's 197 TFLOP/s; 128 experts × 9.44 MB at 819 GB/s);
- ``decode``: one token's pieces — the scores of one row, ``lax.top_k`` of
  69 632 scores, the gather of 2048 rows and ``gqa_attention.step`` over
  them beside the dense step over the whole cache; ``held_part_token`` at 8
  held slots beside one gathered ``[8, D, 2F]`` product (the streaming
  kernel over 8 tiles of the token's row read 0.66 ms beside the loop's 0.79
  in PR 53's call 3 — sixteen rows push every 128 x 128 block of a matrix
  through the unit for nothing — and was not kept).

    python scripts/keye_sweep.py [--positions 0,7,15] [--reps 3]
        [--parts index,select,core,experts,decode] [--out chiprun_out/pr53]
        [--core-tile 512x2048,1024x2048] [--core-part whole,256,128,64]
        [--core-kpart 512,1024] [--core-extent chunk,whole]

Run on the chip, as the one process that owns it. It fails without a TPU: a
kernel's time on the CPU says nothing. No program reads this script's output.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

C, S, TOPK, CHUNKS = 4096, 69632, 2048, 16
J, DI, H, G, D_HEAD = 16, 64, 32, 4, 128
HIDDEN, WIDTH, EXPERTS, PER_TOKEN = 2048, 768, 128, 8
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9


def timed(fn, *args, reps: int):
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def a_layer(by_position: dict) -> float:
    """Seconds a layer a prefill: each of the 16 chunks stands by the
    sampled position nearest it."""
    at = sorted(by_position)
    return sum(by_position[min(at, key=lambda p: abs(p - c))]
               for c in range(CHUNKS))


def tiles_of(text: str) -> list:
    return [tuple(int(n) for n in t.split("x")) for t in text.split(",")]


def visible_steps(position: int, block_q: int, block_k: int) -> int:
    """(query tile, K tile) pairs of one K/V head the core multiplies for
    the chunk at ``position``: a query tile's blocks up to its last row's."""
    first = position * C
    return sum(min((first + (i + 1) * block_q - 1) // block_k,
                   S // block_k - 1) + 1 for i in range(C // block_q))


def core_forms(tiles, parts, kparts, extents, shipped) -> list:
    """``(block_q, block_k, part, kpart, extent)`` of every form asked for:
    rows parts that divide their tile at every tile, ``whole`` the tile
    itself; K parts at the shipped tile alone."""
    forms = [(bq, bk, bq if part == "whole" else int(part), None, extent)
             for bq, bk in tiles for part in parts for extent in extents
             if part == "whole" or (int(part) < bq and bq % int(part) == 0)]
    return forms + [(*shipped, shipped[0], kpart, extents[0])
                    for kpart in kparts if shipped[1] % kpart == 0]


def core_label(bq: int, bk: int, part: int, kpart, extent: str) -> str:
    return f"{bq}x{bk}" + (f"/{part}" if part < bq else "") \
        + (f"/k{kpart}" if kpart else "") \
        + ("" if extent == "chunk" else f".{extent}")


def k_parts_kernel(k_part: int):
    """PR 60's form of the step in this kernel's body (GLM's
    ``_masked_kernel``): the K tile ``k_part`` keys at a time in their
    order, a head after the other, the next part's logit product set out
    before a part's softmax — one more rescale of a head's running tiles a
    part. The sweep's losing arm; ``index_gqa_attention`` ships rows parts."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from comfyui_distributed_tpu.ops.flash_attention import NEG_INF
    from comfyui_distributed_tpu.ops.flash_latent import (
        _accumulate, _init_running, _last_block)

    def kernel(start_ref, q_ref, k_ref, v_ref, keep_ref, o_ref, m_ref, l_ref,
               acc_ref, *, block_q, block_k, part, num_k_blocks, heads,
               precision):
        i, j = pl.program_id(1), pl.program_id(2)
        last = _last_block(start_ref[0], i, block_q, block_k, num_k_blocks)
        _init_running(j, m_ref, l_ref, acc_ref)
        d = k_ref.shape[1]
        parts = [(h, slice(c, c + k_part)) for h in range(heads)
                 for c in range(0, block_k, k_part)]

        @pl.when(j <= last)
        def _step():
            bias = jnp.where(keep_ref[...].astype(jnp.int32) != 0, 0.0,
                             NEG_INF)

            def logits(h: int, at: slice):
                return jax.lax.dot_general(
                    q_ref[:, h * d:(h + 1) * d], k_ref[at],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=precision) + bias[:, at]

            s = logits(*parts[0])
            for n, (h, at) in enumerate(parts):
                ahead = logits(*parts[n + 1]) if n + 1 < len(parts) else None
                _accumulate(s, v_ref[at], m_ref.at[h], l_ref.at[h],
                            acc_ref.at[h], precision)
                s = ahead

        @pl.when(j == last)
        def _finalize():
            for h in range(heads):
                o_ref[:, h * d:(h + 1) * d] = (
                    acc_ref[h] / l_ref[h][:, :1]).astype(o_ref.dtype)

    return kernel


def sorted_grouped(x, idx, w, e_gu, e_down, tile: int):
    """``held_part_grouped``'s tiles over rows gathered ONCE in expert order
    (every expert's rows padded to whole tiles in one buffer), each tile a
    contiguous slice in and out, then ONE combine."""
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.ops.expert_share import gated_mlp, silu_gate

    T, k = idx.shape
    E = e_gu.shape[0]
    local = idx.reshape(-1)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    counts = (local[:, None] == jnp.arange(E)).sum(0).astype(jnp.int32)
    tiles = (counts + tile - 1) // tile
    tile_end, row_end = jnp.cumsum(tiles), jnp.cumsum(counts)
    n_max = T * k // tile + E                       # tiles, at the most
    # where slot ``order[r]`` (rank r within its expert) sits in the buffer
    expert = local[order]
    rank = jnp.arange(T * k) - (row_end - counts)[expert]
    place = (tile_end - tiles)[expert] * tile + rank
    token = order // k
    buf = jnp.zeros((n_max * tile, x.shape[1]), jnp.bfloat16).at[place].set(
        x.astype(jnp.bfloat16)[token])

    def body(t, out):
        e = (t >= tile_end).sum().astype(jnp.int32)
        rows = jax.lax.dynamic_slice_in_dim(buf, t * tile, tile, 0)
        y = gated_mlp(rows, jax.lax.dynamic_index_in_dim(e_gu, e, 0, False),
                      jax.lax.dynamic_index_in_dim(e_down, e, 0, False),
                      jnp.bfloat16, silu_gate)
        return jax.lax.dynamic_update_slice_in_dim(out, y, t * tile, 0)

    out = jax.lax.fori_loop(0, tile_end[-1], body,
                            jnp.zeros((n_max * tile, x.shape[1]),
                                      jnp.float32))
    y = out[place] * w.reshape(-1)[order][:, None]
    return jnp.zeros(x.shape, jnp.float32).at[token].add(y), \
        tile_end[-1] * tile


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--positions", default="0,7,15")
    parser.add_argument("--reps", type=int, default=3)
    parser.add_argument("--parts", default="index,select,core,experts,decode")
    parser.add_argument("--index-tiles",
                        default="256x1024,512x1024,1024x1024,512x2048")
    parser.add_argument("--core-tile", "--core-tiles", dest="core_tiles",
                        default="512x2048,1024x2048,256x2048,512x1024")
    parser.add_argument("--core-part", default="whole,256,128,64",
                        help="rows of one head a logit product")
    parser.add_argument("--core-kpart", default="512,1024",
                        help="keys of the shipped tile a product (PR 60's "
                             "form; empty: none)")
    parser.add_argument("--core-extent", default="chunk",
                        help="chunk (the grid ends where the chunk sees) "
                             "and/or whole (every block of the cache)")
    parser.add_argument("--expert-tiles", default="128,256,512")
    parser.add_argument("--out", default="chiprun_out/pr53")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("keye_sweep needs the chip", file=sys.stderr)
        return 3
    from comfyui_distributed_tpu.ops import (expert_share, gqa_attention,
                                             index_gqa_attention as gqa_ops,
                                             index_select_attention as ops)

    positions = [int(p) for p in args.positions.split(",")]
    parts = args.parts.split(",")
    bf = jnp.bfloat16
    key = jax.random.key(53)
    found: dict = {"positions": positions}
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    def say(name, value):
        found[name] = value
        print(f"[keye_sweep] {name}: {value}", flush=True)
        (out / "keye_sweep.json").write_text(json.dumps(found, indent=1))

    ks = jax.random.split(key, 12)
    k_i = jax.random.normal(ks[0], (S, DI), bf)
    q_i = jax.random.normal(ks[1], (J, C, DI), bf)
    w = jax.random.normal(ks[2], (C, J), jnp.float32) / 32.0
    kv = jax.random.normal(ks[3], (S, 2 * G * D_HEAD), bf)
    q = jax.random.normal(ks[4], (C, H * D_HEAD), bf) * D_HEAD ** -0.5

    if "index" in parts:
        for bq, bk in tiles_of(args.index_tiles):
            by = {p: timed(lambda s, bq=bq, bk=bk: ops.index_score_sums(
                q_i, w, k_i, s, block_q=bq, block_k=bk, interpret=False),
                jnp.int32(p * C), reps=args.reps) for p in positions}
            flop = CHUNKS * C * (CHUNKS * C + 1) / 2 * J * DI * 2
            say(f"index.{bq}x{bk}", {
                "s_a_call": by, "s_a_layer": a_layer(by),
                "mxu_pct": 100 * flop / PEAK_FLOPS / a_layer(by)})

    scores = jax.random.normal(ks[5], (1024, S), jnp.float32)
    if "select" in parts:
        by = {p: 4 * timed(lambda s: ops.index_select_keep(
            scores, s, topk=TOPK, rows=ops.SELECT_ROWS,
            tile=ops.select_tile(S), interpret=False),
            jnp.int32(p * C), reps=args.reps) for p in positions}
        say("select.kernel", {"s_a_chunk": by, "s_a_layer": a_layer(by)})

    if "core" in parts:
        @jax.jit
        def mask_at(start, key):
            # min(2048, t + 1) kept keys a row, below the row's position
            row = start + jnp.arange(C)[:, None]
            col = jnp.arange(S)[None, :]
            u = jax.random.uniform(key, (C, S))
            share = jnp.minimum(1.0, TOPK / (row + 1.0))
            return ((col <= row) & (u < share)).astype(jnp.int8)

        masks = {p: mask_at(jnp.int32(p * C), ks[6]) for p in positions}
        kernel_as_shipped = gqa_ops._masked_gqa_kernel
        table = ["| tile[/part] | ms at chunk "
                 + " · ".join(str(p) for p in positions)
                 + " | s a layer | % MXU, causal pairs | % MXU, its tiles |",
                 "|---|---|---|---|---|"]
        for bq, bk, part, kpart, extent in core_forms(
                tiles_of(args.core_tiles), args.core_part.split(","),
                [int(k) for k in args.core_kpart.split(",") if k],
                args.core_extent.split(","), gqa_ops.CORE_TILE):
            # a jit of its own a form: the call reads the kernel's body and
            # the grid's extent when it is traced
            gqa_ops._masked_gqa_kernel = k_parts_kernel(kpart) if kpart \
                else kernel_as_shipped

            # the operands are ARGUMENTS: closed over, 160 MB of them would
            # be constants of every form's executable
            @jax.jit
            def call(q, kv, keep, start, bq=bq, bk=bk, part=part,
                     extent=extent):
                steps = S // bk if extent == "whole" else \
                    gqa_ops.core_k_steps(start, C, bk, S // bk)
                return gqa_ops.masked_gqa_call(q, kv, keep, start, steps, H,
                                               G, bq, bk, part, False)

            name = core_label(bq, bk, part, kpart, extent)
            try:
                by = {p: timed(call, q, kv, masks[p], jnp.int32(p * C),
                               reps=args.reps) for p in positions}
            except Exception as e:  # noqa: BLE001 — a form the chip refuses
                say(f"core.{name}", {"refused": repr(e)[:300]})
                continue
            finally:
                gqa_ops._masked_gqa_kernel = kernel_as_shipped
            pairs = CHUNKS * C * (CHUNKS * C + 1) / 2 * H * 2 * D_HEAD * 2
            tiles = H * bq * bk * 2 * D_HEAD * 2 * sum(
                visible_steps(p, bq, bk) for p in range(CHUNKS))
            say(f"core.{name}", {
                "s_a_call": by, "s_a_layer": a_layer(by),
                "dense_mxu_pct": 100 * pairs / PEAK_FLOPS / a_layer(by),
                "tile_mxu_pct": 100 * tiles / PEAK_FLOPS / a_layer(by)})
            row = found[f"core.{name}"]
            table.append(
                f"| {name} | "
                + " · ".join(f"{1e3 * by[p]:.3f}" for p in positions)
                + f" | {row['s_a_layer']:.4f} | {row['dense_mxu_pct']:.1f} "
                  f"| {row['tile_mxu_pct']:.1f} |")
        (out / "keye_core_table.md").write_text("\n".join(table) + "\n")
        print("\n".join(table), flush=True)

    if "experts" in parts or "decode" in parts:
        e_gu = jax.random.normal(ks[7], (EXPERTS, HIDDEN, 2 * WIDTH), bf) \
            * HIDDEN ** -0.5
        e_down = jax.random.normal(ks[8], (EXPERTS, WIDTH, HIDDEN), bf) \
            * WIDTH ** -0.5
        w_router = jax.random.normal(ks[9], (HIDDEN, EXPERTS), bf) \
            * HIDDEN ** -0.5
        routing = expert_share.Routing(EXPERTS, PER_TOKEN, 1, 1, 1.0,
                                       score="softmax")
        x_even = jax.random.normal(ks[10], (C, HIDDEN), jnp.float32)
        x_skew = x_even[jax.random.randint(ks[11], (C,), 0, 256)]

    if "experts" in parts:
        floor = {"mxu_s": C * PER_TOKEN * 3 * HIDDEN * WIDTH * 2 / PEAK_FLOPS,
                 "hbm_s": EXPERTS * 3 * HIDDEN * WIDTH * 2 / PEAK_BYTES}
        say("experts.floor_a_chunk", floor)
        for name, x in (("even", x_even), ("skewed", x_skew)):
            idx, wts = jax.jit(lambda x, w_r: expert_share.route(
                x, w_r, None, routing))(x, w_router)
            experts_hit = int((jnp.bincount(idx.reshape(-1),
                                            length=EXPERTS) > 0).sum())
            # the weights are ARGUMENTS: closed over, 1.2 GB of them would
            # be constants of every executable (and of its cache entry)
            for tile in (int(t) for t in args.expert_tiles.split(",")):
                for form, fn in (
                        ("grouped", lambda x, idx, wts, gu, down, tile=tile:
                         expert_share.held_part_grouped(
                             x, idx, wts, gu, down, 0, bf, tile=tile)),
                        ("sorted", lambda x, idx, wts, gu, down, tile=tile:
                         sorted_grouped(x, idx, wts, gu, down, tile)),
                        ("streamed", lambda x, idx, wts, gu, down, tile=tile:
                         expert_share.held_part_streamed(
                             x, idx, wts, gu, down, 0, bf, tile=tile))):
                    fn = jax.jit(fn)
                    rows = int(fn(x, idx, wts, e_gu, e_down)[1])
                    s = timed(fn, x, idx, wts, e_gu, e_down, reps=args.reps)
                    say(f"experts.{name}.{form}.{tile}", {
                        "s_a_chunk": s, "s_a_layer": CHUNKS * s,
                        "rows_multiplied": rows, "experts_hit": experts_hit,
                        "us_a_tile": 1e6 * s / (rows // tile)})

    if "decode" in parts:
        pos = jnp.int32(CHUNKS * C + 63)
        score = jax.random.normal(ks[5], (S,), jnp.float32)
        q1 = jax.random.normal(ks[4], (H, D_HEAD), jnp.float32)
        say("decode.index_row", timed(jax.jit(
            lambda q, w_, k: ops.index_scores_lax(q, w_, k, bf)),
            q_i[:, :1].swapaxes(0, 1), w[:1], k_i, reps=args.reps))
        say("decode.top_k", timed(jax.jit(
            lambda s, p: ops.top_rows(s, p, TOPK)), score, pos,
            reps=args.reps))
        rows, valid = ops.top_rows(score, pos, TOPK)
        say("decode.gathered_step", timed(jax.jit(
            lambda r, v, kv: gqa_ops.gathered_step(q1, kv, r, v, G,
                                                   D_HEAD ** -0.5, bf)),
            rows, valid, kv, reps=args.reps))

        def dense_step(p, kv):
            k, v = (jnp.swapaxes(a.reshape(S, G, D_HEAD), 0, 1)
                    for a in jnp.split(kv, 2, axis=1))
            return gqa_attention.step(q1, k, v, jnp.arange(S) <= p,
                                      D_HEAD ** -0.5, bf)

        say("decode.dense_step", timed(jax.jit(dense_step), pos, kv,
                                       reps=args.reps))
        idx, wts = jax.jit(lambda x, w_r: expert_share.route(
            x, w_r, None, routing))(x_even[:1], w_router)
        say("decode.held_part_token", timed(jax.jit(
            lambda x, i, w_, gu, down: expert_share.held_part_token(
                x, i, w_, gu, down, 0, bf)), x_even[0], idx[0], wts[0],
            e_gu, e_down, reps=args.reps))

        def gathered_experts(x, i, w_, e_gu, e_down):
            gu = jnp.einsum("d,kdf->kf", x.astype(bf), e_gu[i],
                            preferred_element_type=jnp.float32)
            g, u = jnp.split(gu, 2, axis=-1)
            y = jnp.einsum("kf,kfd->kd", (jax.nn.silu(g) * u).astype(bf),
                           e_down[i], preferred_element_type=jnp.float32)
            return (w_[:, None] * y).sum(0)

        say("decode.gathered_experts", timed(
            jax.jit(gathered_experts), x_even[0], idx[0], wts[0], e_gu,
            e_down, reps=args.reps))
        say("decode.experts_floor_s",
            PER_TOKEN * 3 * HIDDEN * WIDTH * 2 / PEAK_BYTES)

    return 0


if __name__ == "__main__":
    sys.exit(main())
