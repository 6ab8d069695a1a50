#!/usr/bin/env python
"""What the two attention cores of ``mimo-v2-flash``'s prefill cost at the
served geometry (``ops/gqa_sink_attention.py``, ``models/llm_mimo.py``;
PERF.md §6, PR 64): 64 query heads, keys 192 wide and values 128, one chunk of
4096 rows ALONE on seeded random operands, at chunks 0, 15 and 31 of a 131 072
token brief.

- ``full`` — a full layer (4 K/V heads, the buffer of 133 120 rows) through
  ``flash_latent._gqa_kernel`` in three forms of the 192-wide key, over
  ``--tiles``: ``whole`` (head-major queries, a tile's last dimension the 192;
  what ships), ``pad256`` (the same call on keys and queries padded to 256:
  +33% of K bytes in the cache) and ``split`` (the 64 roped and the 128 plain
  dimensions as two products into one logit tile, ``_latent_causal_kernel``'s
  way — a body of THIS script, kept by no program). Each with its share of the
  matrix units' peak by the EXACT count, 2 · (192 + 128) a pair a head
  (``cdtbench/kinds/mimo.py``): a contraction of 192 is two passes of a 128 ×
  128 unit, so 83.3% is what the hardware allows.
- ``band`` — a window layer (8 K/V heads, ``[ring ; chunk]`` = 4224 rows, a
  band of 128, the sink) as the block-local XLA form that ships
  (``band_chunk``: a K/V head at a time; PR 64's call 1 also read every head
  at once, 2.54 ms against 1.83) and as the blocked kernel under a band with
  the sink joined where it finalises (a call of THIS script), over
  ``--band-tiles``.

    python scripts/mimo_sweep.py [--parts full,band] [--tiles 2048x2048,...]
        [--band-tiles 128x128,...] [--reps 5] [--out chiprun_out/pr64]

Run on the chip, as the one process that owns it. It fails without a TPU: a
kernel's time on the CPU says nothing. No program reads this script's output.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from comfyui_distributed_tpu.ops import flash_latent as F  # noqa: E402
from comfyui_distributed_tpu.ops import gqa_sink_attention as A  # noqa: E402
from scripts.keye_sweep import tiles_of, timed  # noqa: E402

C, H, DK, DV, ROPE = 4096, 64, 192, 128, 64
G_FULL, G_WINDOW, WINDOW = 4, 8, 128
ROWS = 133120                     # 131 072 + 128 rounded up to 2048
CHUNKS = (0, 15, 31)
PEAK_FLOPS = 197e12
PAIR_FLOPS = 2.0 * (DK + DV) * H  # a (query, key) pair, every head
INTERPRET = False                 # the CPU test of the script's own kernels


def _operands(G: int, rows: int, dk: int = DK):
    keys = jax.random.split(jax.random.key(64), 4)
    return (jax.random.normal(keys[0], (C, H, dk), jnp.bfloat16),
            jax.random.normal(keys[1], (G, rows, dk), jnp.bfloat16),
            jax.random.normal(keys[2], (G, rows, DV), jnp.bfloat16),
            jax.random.normal(keys[3], (H,), jnp.float32))


# --- script-only forms: the split key, and the kernel under a band + sink ----


def _split_kernel(bounds_ref, qr_ref, qn_ref, kr_ref, kn_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, block_q, block_k, part,
                  num_k_blocks, precision):
    """``_gqa_kernel`` (no band) with the logit as TWO products: the roped 64
    dimensions and the plain 128."""
    i, j = pl.program_id(1), pl.program_id(2)
    start = bounds_ref[0]
    first_row = start + i * block_q
    last = F._last_block(start, i, block_q, block_k, num_k_blocks)
    F._init_running(j, m_ref, l_ref, acc_ref)
    nt = (((1,), (1,)), ((), ()))

    def step(masked: bool):
        def logits(n: int):
            rows = pl.ds(n * part, part)
            s = jax.lax.dot_general(qn_ref[rows], kn_ref[0], nt,
                                    preferred_element_type=jnp.float32,
                                    precision=precision)
            s += jax.lax.dot_general(qr_ref[rows], kr_ref[0], nt,
                                     preferred_element_type=jnp.float32,
                                     precision=precision)
            if masked:
                s = F._mask_above_diagonal(s, first_row + n * part,
                                           j * block_k)
            return s

        s = logits(0)
        for n in range(block_q // part):
            ahead = logits(n + 1) if (n + 1) * part < block_q else None
            rows = pl.ds(n * part, part)
            F._accumulate(s, v_ref[0], m_ref.at[rows], l_ref.at[rows],
                          acc_ref.at[rows], precision)
            s = ahead

    F._on_visible_blocks(step, j, last, first_row, block_k)

    @pl.when(j == last)
    def _finalize():
        o_ref[...] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k"))
def split_mha(q, k, v, start, block_q: int, block_k: int):
    """``q`` [H, C, 192], ``k`` [G, S, 192] handed as their 64 + 128 parts."""
    qr, qn, kr, kn = q[..., :ROPE], q[..., ROPE:], k[..., :ROPE], k[..., ROPE:]
    G, S = k.shape[:2]
    nk = S // block_k
    steps = F.core_k_steps(start, C, block_k, nk)
    kernel = functools.partial(
        _split_kernel, block_q=block_q, block_k=block_k,
        part=F.step_rows(block_q), num_k_blocks=nk,
        precision=F._precision_of(q.dtype))

    def kv(h, i, j, b):
        return (h // (H // G),
                jnp.minimum(j, F._last_block(b[0], i, block_q, block_k, nk)),
                0)

    def qb(h, i, j, b):
        return (h, i, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(H, C // block_q, steps),
        in_specs=[pl.BlockSpec((None, block_q, ROPE), qb),
                  pl.BlockSpec((None, block_q, DK - ROPE), qb),
                  pl.BlockSpec((1, block_k, ROPE), kv),
                  pl.BlockSpec((1, block_k, DK - ROPE), kv),
                  pl.BlockSpec((1, block_k, DV), kv)],
        out_specs=pl.BlockSpec((block_q, DV), lambda h, i, j, b: (i, h)),
        scratch_shapes=F._running_scratch(block_q, DV))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, H * DV), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=F._VMEM_LIMIT_BYTES),
        interpret=INTERPRET,
    )(jnp.stack([jnp.asarray(start, jnp.int32), jnp.int32(0)]), qr, qn, kr,
      kn, v)


def _sink_band_kernel(bounds_ref, sink_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                      l_ref, acc_ref, *, block_q, block_k, num_k_blocks,
                      window, **kw):
    """``_gqa_kernel`` under a band; where it finalised, the sink joins the
    denominator: ``acc / (l + exp(b − m))``."""
    F._gqa_kernel(bounds_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, block_q=block_q, block_k=block_k,
                  num_k_blocks=num_k_blocks, window=window, **kw)
    h, i, walked = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    start, lowest = bounds_ref[0], bounds_ref[1]
    first = F._first_column(start + i * block_q, window, lowest) // block_k
    last = F._last_block(start, i, block_q, block_k, num_k_blocks)

    @pl.when(first + walked == last)
    def _with_sink():
        total = l_ref[:, :1] + jnp.exp(sink_ref[h] - m_ref[:, :1])
        o_ref[...] = (acc_ref[:] / total).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k"))
def sink_band_mha(q, k, v, sink, lowest, block_q: int, block_k: int):
    """``q`` [H, C, 192] at rows ``WINDOW ..`` of ``k``, ``v`` [G, S, ·]."""
    G, S = k.shape[:2]
    nk = S // block_k
    start = WINDOW
    steps = F.gqa_k_steps(start, lowest, C, WINDOW, block_q, block_k, nk)
    kernel = functools.partial(
        _sink_band_kernel, block_q=block_q, block_k=block_k,
        part=F.step_rows(block_q), num_k_blocks=nk, window=WINDOW,
        precision=F._precision_of(q.dtype))

    def kv(h, i, j, b, s):
        first = F._first_column(b[0] + i * block_q, WINDOW, b[1]) // block_k
        return (h // (H // G), jnp.minimum(
            first + j, F._last_block(b[0], i, block_q, block_k, nk)), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(H, C // block_q, steps),
        in_specs=[pl.BlockSpec((None, block_q, DK),
                               lambda h, i, j, b, s: (h, i, 0)),
                  pl.BlockSpec((1, block_k, DK), kv),
                  pl.BlockSpec((1, block_k, DV), kv)],
        out_specs=pl.BlockSpec((block_q, DV), lambda h, i, j, b, s: (i, h)),
        scratch_shapes=F._running_scratch(block_q, DV))
    bounds = jnp.stack([jnp.int32(start), jnp.asarray(lowest, jnp.int32)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, H * DV), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=F._VMEM_LIMIT_BYTES),
        interpret=INTERPRET,
    )(bounds, sink, q, k, v)


# --- the two parts -----------------------------------------------------------


def sweep_full(tiles, reps: int) -> list:
    q, k, v, _ = _operands(G_FULL, ROWS)
    qh = jnp.swapaxes(q, 0, 1)
    wide = ((0, 0), (0, 0), (0, 256 - DK))
    forms = {
        "whole": (lambda s, bq, bk: A.gqa_wide_causal_mha(
            qh, k, v, s, block_q=bq, block_k=bk, interpret=False)),
        "pad256": (lambda s, bq, bk, qp=jnp.pad(qh, wide), kp=jnp.pad(k, wide):
                   A.gqa_wide_causal_mha(qp, kp, v, s, block_q=bq,
                                         block_k=bk, interpret=False)),
        "split": (lambda s, bq, bk: split_mha(qh, k, v, s, block_q=bq,
                                              block_k=bk))}
    lines, want = [], {}
    for form, fn in forms.items():
        for bq, bk in tiles:
            line = {"part": "full", "form": form, "tile": f"{bq}x{bk}"}
            try:
                total = 0.0
                for chunk in CHUNKS:
                    start = jnp.int32(chunk * C)
                    seconds = timed(fn, start, bq, bk, reps=reps)
                    pairs = C * (chunk * C) + C * (C + 1) / 2
                    line[f"chunk{chunk}_ms"] = 1e3 * seconds
                    line[f"chunk{chunk}_mxu_pct"] = \
                        100 * pairs * PAIR_FLOPS / PEAK_FLOPS / seconds
                    total += seconds
                    o = fn(start, bq, bk).astype(jnp.float32)
                    ref = want.setdefault(chunk, o)
                    line[f"chunk{chunk}_rel"] = float(
                        jnp.linalg.norm(o - ref) / jnp.linalg.norm(ref))
                # a layer a prefill: 32 chunks, linear in the chunk's index
                line["layer_s_estimate"] = 32 * total / len(CHUNKS)
            except Exception as e:  # noqa: BLE001 — the compiler's word
                line["refused"] = str(e).splitlines()[0][:300]
            lines.append(line)
            print(json.dumps(line), flush=True)
    return lines


def sweep_band(tiles, reps: int) -> list:
    q, k, v, sink = _operands(G_WINDOW, WINDOW + C)
    scale = DK ** -0.5
    pairs = C * WINDOW                      # past the first chunk
    lines = []
    xla = jax.jit(lambda lowest: A.band_chunk(q, k, v, lowest, WINDOW, scale,
                                              jnp.bfloat16, sink))
    want = xla(jnp.int32(0)).astype(jnp.float32).reshape(C, H * DV)
    seconds = timed(xla, jnp.int32(0), reps=reps)
    lines.append({"part": "band", "form": "xla_block_local",
                  "ms": 1e3 * seconds,
                  "mxu_pct": 100 * pairs * PAIR_FLOPS / PEAK_FLOPS / seconds})
    print(json.dumps(lines[-1]), flush=True)
    qh = jnp.swapaxes((q * scale).astype(jnp.bfloat16), 0, 1)
    for bq, bk in tiles:
        pad = -(WINDOW + C) % bk
        kp, vp = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (k, v))
        line = {"part": "band", "form": "kernel_sink", "tile": f"{bq}x{bk}"}
        try:
            fn = functools.partial(sink_band_mha, qh, kp, vp, sink,
                                   block_q=bq, block_k=bk)
            seconds = timed(fn, jnp.int32(0), reps=reps)
            o = fn(jnp.int32(0)).astype(jnp.float32)
            line.update(ms=1e3 * seconds,
                        mxu_pct=100 * pairs * PAIR_FLOPS / PEAK_FLOPS
                        / seconds,
                        rel=float(jnp.linalg.norm(o - want)
                                  / jnp.linalg.norm(want)))
        except Exception as e:  # noqa: BLE001 — the compiler's word
            line["refused"] = str(e).splitlines()[0][:300]
        lines.append(line)
        print(json.dumps(line), flush=True)
    return lines


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--parts", default="full,band")
    parser.add_argument("--tiles",
                        default="2048x2048,1024x2048,2048x1024,1024x1024")
    parser.add_argument("--band-tiles",
                        default="128x128,256x128,512x128,256x256,512x256")
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument("--out", default="chiprun_out/pr64")
    args = parser.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("mimo_sweep: needs the chip; JAX found "
              f"{jax.devices()[0].platform}", file=sys.stderr)
        return 3
    parts, lines = args.parts.split(","), []
    if "full" in parts:
        lines += sweep_full(tiles_of(args.tiles), args.reps)
    if "band" in parts:
        lines += sweep_band(tiles_of(args.band_tiles), args.reps)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / "mimo_sweep.json").write_text(json.dumps(lines, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
