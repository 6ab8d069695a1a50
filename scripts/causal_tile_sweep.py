#!/usr/bin/env python
"""Which tile ``(block_q, block_k)`` a causal prefill kernel of
``ops/flash_latent.py`` should be served with, read over a WHOLE prefill —
and, for the grouped-query body (PR 63), which STEP and which GRID: the
query tile ``part`` rows a product (``--parts``; ``none`` is the plain
step) and the grid's K axis the ``whole`` buffer or the traced bound the
kernel ships with (``--extent``; ``chunk``: a query tile walks from its
first visible block as far as the chunk's last row sees).

One run times one kernel alone at its model's served geometry, every
candidate form at every chunk position of the cell's prompt (one compiled
kernel a form: the position is the prefetched ``start``), and prints, a
form: seconds summed over the prefill (what decides), % of the MXU peak on
the counted pairs (each visible (query, key) pair once, as
``cdtbench/kinds/*.py: attention_core_flops`` counts them), visible and
skipped grid steps, and the µs a visible and a skipped step fitted on the
positions (``t = a·visible + b·skipped``, least squares).

    python scripts/causal_tile_sweep.py gqa_causal          # Trinity, full layer
    python scripts/causal_tile_sweep.py gqa_causal_zaya     # ZAYA, 8 q / 2 kv
    python scripts/causal_tile_sweep.py gqa_window          # Trinity, the band
    python scripts/causal_tile_sweep.py latent_causal       # Kimi
    python scripts/causal_tile_sweep.py shared_kv_causal    # Jamba
        [--pairs 1024x1024,2048x2048] [--parts none,512,256,128]
        [--extent whole,chunk] [--positions 0,1,3,7,15,31]
        [--vmem-mib 100] [--out chiprun_out/tile_sweep]

Run on the chip, as the one process that owns it (through the chip tool
where the chip is remote). It fails without a TPU: a tile's time on the
CPU says nothing. The winner is written into the model's config by hand
(PERF.md §6, PR 40): no program reads this script's output.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MXU_PEAK = 197e12          # bf16 FLOP/s of one TPU v5e chip (published)


@dataclasses.dataclass(frozen=True)
class Geometry:
    """A kernel as one cell's prefill calls it. ``rows`` is the K/V rows
    the kernel walks before its caller rounds them up to the K block;
    ``calls`` how many times a request runs the kernel at each chunk
    position (the layers of its kind)."""
    cell: str
    heads: int
    chunk: int
    chunks: int
    rows: int
    calls: int
    flops_per_pair: int                # a head: logit and value products
    shipped: tuple                     # the pair the model's config holds
    candidates: tuple
    window: int | None = None
    kv_heads: int = 1


def geometries() -> dict:
    from comfyui_distributed_tpu.models.llm_jamba import JambaConfig
    from comfyui_distributed_tpu.models.llm_kimi import KimiConfig
    from comfyui_distributed_tpu.models.llm_trinity import TrinityConfig
    from comfyui_distributed_tpu.models.llm_zaya import ZayaConfig

    up = tuple((q, k) for q in (1024, 2048, 4096) for k in (1024, 2048, 4096))
    t, k, j, z = (TrinityConfig.trinity_share(), KimiConfig.kimi_share(),
                  JambaConfig.jamba2_3b(), ZayaConfig.zaya_share())
    n_full = sum(t.is_full(i) for i in range(t.num_hidden_layers))
    return {
        "gqa_causal": Geometry(
            "trinity-large-preview.brief128k-sdxl8", t.num_attention_heads,
            t.prefill_chunk_tokens, 131072 // t.prefill_chunk_tokens,
            131072 + 128, n_full, 4 * t.head_dim,
            (t.attn_full_block_q, t.attn_full_block_k), up,
            kv_heads=t.num_key_value_heads),
        "gqa_causal_zaya": Geometry(
            "zaya1-8b.ctx128k-sdxl8", z.num_attention_heads,
            z.prefill_chunk_tokens, 131072 // z.prefill_chunk_tokens,
            130944 + 128, z.num_hidden_layers, 4 * z.head_dim,
            (z.attn_block_q, z.attn_block_k), up,
            kv_heads=z.num_key_value_heads),
        "gqa_window": Geometry(
            "trinity-large-preview.brief128k-sdxl8", t.num_attention_heads,
            t.prefill_chunk_tokens, 131072 // t.prefill_chunk_tokens,
            2 * t.sliding_window, t.num_hidden_layers - n_full,
            4 * t.head_dim, (t.attn_window_block_q, t.attn_window_block_k),
            ((1024, 1024), (1024, 512), (2048, 512), (512, 2048),
             (512, 1024), (2048, 1024), (1024, 2048)),
            window=t.sliding_window, kv_heads=t.num_key_value_heads),
        "latent_causal": Geometry(
            "kimi-k2.6.brief32k-sdxl8", k.num_attention_heads,
            k.prefill_chunk_tokens, 32768 // k.prefill_chunk_tokens,
            32768 + 128, k.num_hidden_layers,
            2 * (k.qk_nope_head_dim + k.qk_rope_head_dim + k.v_head_dim),
            (k.attn_block_q, k.attn_block_k), up),
        "shared_kv_causal": Geometry(
            "ai21-jamba2-3b.brief64k-sdxl8", j.num_attention_heads,
            j.prefill_chunk_tokens, 65536 // j.prefill_chunk_tokens,
            65536 + 128, len(j.attention_layers), 4 * j.head_dim,
            (j.attn_block_q, j.attn_block_k), up),
    }


def _bounds(g: Geometry, position: int) -> tuple:
    """``(start, lowest)`` of chunk ``position`` as the prefill hands them
    to the kernel: a full layer's rows are positions; a window layer's are
    ``[ring ; chunk]``, the ring empty ahead of chunk 0."""
    if g.window is None:
        return position * g.chunk, 0
    return g.window, max(g.window - position * g.chunk, 0)


def grid_steps(g: Geometry, position: int, block_q: int, block_k: int,
               rows: int, extent: str = "whole") -> tuple:
    """``(visible, skipped)`` grid steps of one call, by the kernel's own
    rule: a q block runs K blocks ``first .. last`` of the ``walked`` its
    grid gives it (``extent``: the ``whole`` buffer, or the grouped-query
    kernel's traced bound)."""
    from comfyui_distributed_tpu.ops import flash_latent as fl

    start, lowest = _bounds(g, position)
    nk = rows // block_k
    visible = 0
    for i in range(g.chunk // block_q):
        first = int(fl._first_column(start + i * block_q, g.window,
                                     lowest)) // block_k
        visible += int(fl._last_block(start, i, block_q, block_k, nk)) \
            - first + 1
    walked = nk if extent == "whole" else int(fl.gqa_k_steps(
        start, lowest, g.chunk, g.window, block_q, block_k, nk))
    return (visible * g.heads,
            g.heads * ((g.chunk // block_q) * walked - visible))


def counted_pairs(g: Geometry, position: int) -> int:
    """(query, key) pairs ONE head attends at chunk ``position``."""
    start, lowest = _bounds(g, position)
    pairs = 0
    for row in range(start, start + g.chunk):
        low = lowest if g.window is None \
            else max(row - g.window + 1, lowest)
        pairs += row - low + 1
    return pairs


def build_call(name: str, g: Geometry, block_q: int, block_k: int,
               part: int | None = None, extent: str = "chunk"):
    """``call(position) -> array`` of kernel ``name`` at the pair, on
    random bf16 operands of the served shapes, and the rows it walks. The
    grouped-query names run ``flash_latent.gqa_call`` by ``part`` rows of
    the query tile a product (None: the plain step) over the K ``extent``."""
    import math

    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models.llm_kimi import KimiConfig
    from comfyui_distributed_tpu.ops import flash_latent

    step = math.lcm(g.chunk, block_k) if name == "latent_causal" else block_k
    rows = -(-g.rows // step) * step
    keys = jax.random.split(jax.random.key(0), 4)
    d = g.flops_per_pair // 4      # the one-product kernels: 2·d + 2·d

    def normal(key, *shape):
        return jax.random.normal(key, shape, jnp.bfloat16)

    if name == "latent_causal":
        k = KimiConfig.kimi_share()
        nope, rope = k.qk_nope_head_dim, k.qk_rope_head_dim
        qn, qr = normal(keys[0], g.chunk, g.heads * nope), \
            normal(keys[1], g.heads, g.chunk, rope)
        kv, kr = normal(keys[2], rows, g.heads * (nope + k.v_head_dim)), \
            normal(keys[3], rows, rope)
        return rows, lambda p: flash_latent.latent_causal_mha(
            qn, qr, kv, kr, jnp.int32(_bounds(g, p)[0]), num_heads=g.heads,
            block_q=block_q, block_k=block_k, interpret=False)
    q = normal(keys[0], g.chunk, g.heads * d)
    kk, vv = (normal(key, g.kv_heads, rows, d) for key in keys[1:3])
    nk = rows // block_k

    @jax.jit
    def form(q, kk, vv, start, lowest):
        steps = nk if extent == "whole" else flash_latent.gqa_k_steps(
            start, lowest, g.chunk, g.window, block_q, block_k, nk)
        return flash_latent.gqa_call(
            q, kk, vv, start, lowest, steps, g.heads, g.window, block_q,
            block_k, part or block_q, False)

    # the bounds are on the device ahead of the timed calls: two scalars'
    # transfers a call outlast a short kernel
    bounds = {p: tuple(jnp.int32(b) for b in _bounds(g, p))
              for p in range(g.chunks)}
    return rows, lambda p: form(q, kk, vv, *bounds[p])


def time_call(call, position: int, reps: int, batch: int) -> float:
    """Median over ``reps`` of the mean seconds of ``batch`` calls in a
    row (one wait a batch: the kernels are 3–100 ms, a wait ~0.1 ms)."""
    import statistics

    import jax

    means = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(batch):
            out = call(position)
        jax.block_until_ready(out)
        means.append((time.perf_counter() - t0) / batch)
    return statistics.median(means)


def fit_steps(visible, skipped, seconds) -> tuple:
    """µs a visible and a skipped step: ``t = a·visible + b·skipped`` by
    least squares over the positions (None where they cannot be told
    apart: every position alike)."""
    import numpy as np

    A = np.array([visible, skipped], np.float64).T
    if np.linalg.matrix_rank(A) < 2:
        return None, None
    (a, b), *_ = np.linalg.lstsq(A, np.asarray(seconds, np.float64),
                                 rcond=None)
    return a * 1e6, b * 1e6


def positions_of(g: Geometry, asked: str | None) -> dict:
    """``{position: weight}``: every chunk once, or the asked positions
    each standing for the chunks up to the next one asked (a window
    layer's chunks past the first are one geometry: two positions)."""
    if g.window is not None and asked is None:
        asked = "0,1"
    if asked is None:
        return {p: 1 for p in range(g.chunks)}
    at = sorted({int(p) for p in asked.split(",")})
    ends = at[1:] + [g.chunks]
    if g.window is not None:
        return {p: e - p for p, e in zip(at, ends)}
    # a causal kernel's time is linear in the position: a sampled position
    # stands for the chunks nearest it
    weights = {p: 0 for p in at}
    for c in range(g.chunks):
        weights[min(at, key=lambda p: abs(p - c))] += 1
    return weights


def forms_of(name: str, pairs, parts, extents):
    """``(block_q, block_k, part, extent)`` of every form asked for: a part
    divides its query tile and is shorter (None: the plain step); the
    latent kernel has the plain step over the whole buffer only."""
    if name == "latent_causal":
        return [(bq, bk, None, "whole") for bq, bk in pairs]
    return [(bq, bk, part, extent) for bq, bk in pairs for part in parts
            if part is None or (part < bq and bq % part == 0)
            for extent in extents]


def label(block_q: int, block_k: int, part, extent: str) -> str:
    return f"{block_q}x{block_k}" + (f"/{part}" if part else "") \
        + f" {extent}"


def shipped_form(name: str, g: Geometry) -> list:
    """``[block_q, block_k, part, extent]`` the kernel ships with."""
    from comfyui_distributed_tpu.ops import flash_latent

    if name == "latent_causal":
        return [*g.shipped, None, "whole"]
    part = flash_latent.step_rows(g.shipped[0])
    return [*g.shipped, part if part < g.shipped[0] else None, "chunk"]


def sweep(name: str, pairs, parts, extents, asked: str | None, reps: int,
          vmem_mib) -> dict:
    import jax

    from comfyui_distributed_tpu.ops import flash_latent

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"a tile is timed on a TPU, not on {device.platform}")
    if vmem_mib:
        flash_latent._VMEM_LIMIT_BYTES = vmem_mib * 1024 * 1024
    g = geometries()[name]
    shipped = shipped_form(name, g)
    weights = positions_of(g, asked)
    pairs_counted = {p: counted_pairs(g, p) for p in weights}
    rows_out = []
    for block_q, block_k, part, extent in forms_of(
            name, pairs or g.candidates, parts or [shipped[2]], extents):
        row = {"block_q": block_q, "block_k": block_k, "part": part,
               "extent": extent}
        what = f"{name} {label(block_q, block_k, part, extent)}"
        try:
            rows, call = build_call(name, g, block_q, block_k, part, extent)
            jax.block_until_ready(call(0))                   # compiles
            batch = 8 if g.window is not None or g.heads <= 8 else 2
            secs = {p: time_call(call, p, reps, batch) for p in weights}
        except Exception as e:  # noqa: BLE001 — the compiler's refusal
            row["refused"] = str(e)[:300]
            rows_out.append(row)
            print(f"{what}: refused {row['refused']}", flush=True)
            continue
        steps = {p: grid_steps(g, p, block_q, block_k, rows, extent)
                 for p in weights}
        total = sum(weights[p] * secs[p] for p in weights) * g.calls
        flops = sum(weights[p] * pairs_counted[p] for p in weights) \
            * g.calls * g.heads * g.flops_per_pair
        a, b = fit_steps([steps[p][0] for p in weights],
                         [steps[p][1] for p in weights],
                         [secs[p] for p in weights])
        row.update(
            rows=rows, prefill_s=total,
            mxu_pct=100.0 * flops / MXU_PEAK / total,
            visible_steps=sum(weights[p] * steps[p][0]
                              for p in weights) * g.calls,
            skipped_steps=sum(weights[p] * steps[p][1]
                              for p in weights) * g.calls,
            us_visible_step=a, us_skipped_step=b,
            ms_by_position={str(p): secs[p] * 1e3 for p in weights},
            mxu_pct_by_position={
                str(p): 100.0 * pairs_counted[p] * g.heads * g.flops_per_pair
                / MXU_PEAK / secs[p] for p in weights})
        rows_out.append(row)
        print(f"{what}: {total:.4f} s a prefill, "
              f"{row['mxu_pct']:.1f}% of the MXU peak", flush=True)
    return {"kernel": name, "cell": g.cell, "shipped": shipped,
            "positions": {str(p): w for p, w in weights.items()},
            "calls_per_position": g.calls, "vmem_limit_mib": vmem_mib or
            flash_latent._VMEM_LIMIT_BYTES // 2 ** 20, "reps": reps,
            "device": {"platform": device.platform,
                       "kind": device.device_kind,
                       "count": jax.device_count()},
            "rows": rows_out}


def table(result: dict) -> str:
    """The result as PERF.md holds it."""
    def num(x, fmt):
        return "—" if x is None else format(x, fmt)

    def form(r):
        return [r["block_q"], r["block_k"], r["part"], r["extent"]]

    done = [r for r in result["rows"] if "refused" not in r]
    base = next((r["prefill_s"] for r in done
                 if form(r) == result["shipped"]), None)
    lines = [f"`{result['kernel']}` ({result['cell']}; positions "
             f"{','.join(result['positions'])}; VMEM limit "
             f"{result['vmem_limit_mib']} MiB)",
             "| tile[/part] extent | s a prefill | vs shipped | % MXU | ms "
             "first · last position | visible steps | skipped steps | "
             "µs visible | µs skipped |", "|---|---|---|---|---|---|---|---|---|"]
    for r in result["rows"]:
        tile = label(*form(r))
        if "refused" in r:
            lines.append(f"| {tile} | refused: {r['refused'][:80]} |||||||||")
            continue
        ms = list(r["ms_by_position"].values())
        rel = "" if base is None else f"{100 * (r['prefill_s'] / base - 1):+.1f}%"
        lines.append(
            f"| {tile} | {r['prefill_s']:.4f} | {rel} | {r['mxu_pct']:.1f} | "
            f"{ms[0]:.2f} · {ms[-1]:.2f} | {r['visible_steps']} | "
            f"{r['skipped_steps']} | {num(r['us_visible_step'], '.2f')} | "
            f"{num(r['us_skipped_step'], '.3f')} |")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("kernel", choices=["gqa_causal", "gqa_causal_zaya",
                                       "gqa_window", "latent_causal",
                                       "shared_kv_causal"])
    ap.add_argument("--pairs", help="block_q x block_k, comma-separated "
                    "(default: the kernel's candidates)")
    ap.add_argument("--parts", help="rows of a query tile a logit product, "
                    "comma-separated; none: the plain step (default: the "
                    "part the kernel ships with)")
    ap.add_argument("--extent", default="chunk", help="the grid's K axis, "
                    "comma-separated: whole, chunk (the shipped bound)")
    ap.add_argument("--positions", help="chunk positions, comma-separated "
                    "(default: every chunk of the cell's prompt)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--vmem-mib", type=int, default=None,
                    help="try the kernels under another VMEM limit")
    ap.add_argument("--out", default="chiprun_out/tile_sweep")
    args = ap.parse_args(argv)
    pairs = [tuple(int(n) for n in p.split("x"))
             for p in args.pairs.split(",")] if args.pairs else None
    parts = [None if p == "none" else int(p)
             for p in args.parts.split(",")] if args.parts else None
    result = sweep(args.kernel, pairs, parts, args.extent.split(","),
                   args.positions, args.reps, args.vmem_mib)
    os.makedirs(args.out, exist_ok=True)
    tag = args.kernel + (f".vmem{args.vmem_mib}" if args.vmem_mib else "")
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    print(table(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
