#!/usr/bin/env python
"""Which tile ``(block_q, block_k)`` a causal prefill kernel of
``ops/flash_latent.py`` should be served with, read over a WHOLE prefill.

One run times one kernel alone at its model's served geometry, every
candidate pair at every chunk position of the cell's prompt (one compiled
kernel a pair: the position is the prefetched ``start``), and prints, a
pair: seconds summed over the prefill (what decides), % of the MXU peak on
the counted pairs (each visible (query, key) pair once, as
``cdtbench/kinds/*.py: attention_core_flops`` counts them), visible and
skipped grid steps, and the µs a visible and a skipped step fitted on the
positions (``t = a·visible + b·skipped``, least squares).

    python scripts/causal_tile_sweep.py gqa_causal          # Trinity, full layer
    python scripts/causal_tile_sweep.py gqa_window          # Trinity, the band
    python scripts/causal_tile_sweep.py latent_causal       # Kimi
    python scripts/causal_tile_sweep.py shared_kv_causal    # Jamba
        [--pairs 1024x1024,2048x2048] [--positions 0,1,3,7,15,31]
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


def geometries() -> dict:
    from comfyui_distributed_tpu.models.llm_jamba import JambaConfig
    from comfyui_distributed_tpu.models.llm_kimi import KimiConfig
    from comfyui_distributed_tpu.models.llm_trinity import TrinityConfig

    up = tuple((q, k) for q in (1024, 2048, 4096) for k in (1024, 2048, 4096))
    t, k, j = (TrinityConfig.trinity_share(), KimiConfig.kimi_share(),
               JambaConfig.jamba2_3b())
    n_full = sum(t.is_full(i) for i in range(t.num_hidden_layers))
    return {
        "gqa_causal": Geometry(
            "trinity-large-preview.brief128k-sdxl8", t.num_attention_heads,
            t.prefill_chunk_tokens, 131072 // t.prefill_chunk_tokens,
            131072 + 128, n_full, 4 * t.head_dim,
            (t.attn_full_block_q, t.attn_full_block_k), up),
        "gqa_window": Geometry(
            "trinity-large-preview.brief128k-sdxl8", t.num_attention_heads,
            t.prefill_chunk_tokens, 131072 // t.prefill_chunk_tokens,
            2 * t.sliding_window, t.num_hidden_layers - n_full,
            4 * t.head_dim, (t.attn_window_block_q, t.attn_window_block_k),
            ((1024, 1024), (1024, 512), (2048, 512), (512, 2048),
             (512, 1024), (2048, 1024), (1024, 2048)),
            window=t.sliding_window),
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
               rows: int) -> tuple:
    """``(visible, skipped)`` grid steps of one call, by the kernel's own
    rule: a q block runs K blocks ``first .. last``."""
    from comfyui_distributed_tpu.ops.flash_latent import (_first_column,
                                                          _last_block)

    start, lowest = _bounds(g, position)
    nk = rows // block_k
    visible = 0
    for i in range(g.chunk // block_q):
        first = int(_first_column(start + i * block_q, g.window,
                                  lowest)) // block_k
        visible += int(_last_block(start, i, block_q, block_k, nk)) \
            - first + 1
    visible *= g.heads
    return visible, g.heads * (g.chunk // block_q) * nk - visible


def counted_pairs(g: Geometry, position: int) -> int:
    """(query, key) pairs ONE head attends at chunk ``position``."""
    start, lowest = _bounds(g, position)
    pairs = 0
    for row in range(start, start + g.chunk):
        low = lowest if g.window is None \
            else max(row - g.window + 1, lowest)
        pairs += row - low + 1
    return pairs


def build_call(name: str, g: Geometry, block_q: int, block_k: int):
    """``call(position) -> array`` of kernel ``name`` at the pair, on
    random bf16 operands of the served shapes, and the rows it walks."""
    import math

    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models.llm_kimi import KimiConfig
    from comfyui_distributed_tpu.ops import flash_latent

    step = math.lcm(g.chunk, block_k) if name == "latent_causal" else block_k
    rows = -(-g.rows // step) * step
    keys = jax.random.split(jax.random.key(0), 4)
    d = g.flops_per_pair // 4      # the one-product kernels: 2·d + 2·d
    blocks = dict(num_heads=g.heads, block_q=block_q, block_k=block_k,
                  interpret=False)

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
            qn, qr, kv, kr, jnp.int32(_bounds(g, p)[0]), **blocks)
    q = normal(keys[0], g.chunk, g.heads * d)
    if name == "shared_kv_causal":
        kk, vv = normal(keys[1], rows, d), normal(keys[2], rows, d)
        return rows, lambda p: flash_latent.shared_kv_causal_mha(
            q, kk, vv, jnp.int32(_bounds(g, p)[0]), **blocks)
    from comfyui_distributed_tpu.models.llm_trinity import TrinityConfig

    G = TrinityConfig.trinity_share().num_key_value_heads
    kk, vv = normal(keys[1], G, rows, d), normal(keys[2], G, rows, d)
    if g.window is None:
        return rows, lambda p: flash_latent.gqa_causal_mha(
            q, kk, vv, jnp.int32(_bounds(g, p)[0]), **blocks)
    return rows, lambda p: flash_latent.gqa_window_mha(
        q, kk, vv, *(jnp.int32(b) for b in _bounds(g, p)), window=g.window,
        **blocks)


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


def sweep(name: str, pairs, asked: str | None, reps: int, vmem_mib) -> dict:
    import jax

    from comfyui_distributed_tpu.ops import flash_latent

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"a tile is timed on a TPU, not on {device.platform}")
    if vmem_mib:
        flash_latent._VMEM_LIMIT_BYTES = vmem_mib * 1024 * 1024
    g = geometries()[name]
    weights = positions_of(g, asked)
    pairs_counted = {p: counted_pairs(g, p) for p in weights}
    rows_out = []
    for block_q, block_k in pairs or g.candidates:
        row = {"block_q": block_q, "block_k": block_k}
        try:
            rows, call = build_call(name, g, block_q, block_k)
            jax.block_until_ready(call(0))                   # compiles
            batch = 8 if g.window is not None else 2
            secs = {p: time_call(call, p, reps, batch) for p in weights}
        except Exception as e:  # noqa: BLE001 — the compiler's refusal
            row["refused"] = str(e)[:300]
            rows_out.append(row)
            print(f"{name} {block_q}x{block_k}: refused {row['refused']}",
                  flush=True)
            continue
        steps = {p: grid_steps(g, p, block_q, block_k, rows) for p in weights}
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
        print(f"{name} {block_q}x{block_k}: {total:.4f} s a prefill, "
              f"{row['mxu_pct']:.1f}% of the MXU peak", flush=True)
    return {"kernel": name, "cell": g.cell, "shipped": list(g.shipped),
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

    done = [r for r in result["rows"] if "refused" not in r]
    base = next((r["prefill_s"] for r in done
                 if [r["block_q"], r["block_k"]] == result["shipped"]), None)
    lines = [f"`{result['kernel']}` ({result['cell']}; positions "
             f"{','.join(result['positions'])}; VMEM limit "
             f"{result['vmem_limit_mib']} MiB)",
             "| tile | s a prefill | vs shipped | % MXU | ms first · last "
             "position | visible steps | skipped steps | µs visible | "
             "µs skipped |", "|---|---|---|---|---|---|---|---|---|"]
    for r in result["rows"]:
        tile = f"{r['block_q']} × {r['block_k']}"
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
    ap.add_argument("kernel", choices=["gqa_causal", "gqa_window",
                                       "latent_causal", "shared_kv_causal"])
    ap.add_argument("--pairs", help="block_q x block_k, comma-separated "
                    "(default: the kernel's candidates)")
    ap.add_argument("--positions", help="chunk positions, comma-separated "
                    "(default: every chunk of the cell's prompt)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--vmem-mib", type=int, default=None,
                    help="try the kernels under another VMEM limit")
    ap.add_argument("--out", default="chiprun_out/tile_sweep")
    args = ap.parse_args(argv)
    pairs = [tuple(int(n) for n in p.split("x"))
             for p in args.pairs.split(",")] if args.pairs else None
    result = sweep(args.kernel, pairs, args.positions, args.reps,
                   args.vmem_mib)
    os.makedirs(args.out, exist_ok=True)
    tag = args.kernel + (f".vmem{args.vmem_mib}" if args.vmem_mib else "")
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    print(table(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
