#!/usr/bin/env python
"""How the two-segment packed call (``ops/flash_joint.py``) should walk its
resident K/V, read at a joint block's own geometry.

One run times, at one model's joint attention (text and image ``qkv``
products' outputs as the operands, as ``models/dit.DoubleBlock`` hands
them over):

- ``concat``: what the block did before PR 41 — cut q, k, v out of both
  products, concatenate, the one-segment packed call, cut the answer;
- the two-segment call under each candidate walk: the text tile riding
  with the first image slab (``ride``) or as a softmax step of its own
  (``own``), the rows cut into slabs of several lengths, and the image's q
  tile at several heights;

and prints, a candidate: µs a call (mean of ``--reps`` calls in one
dispatch queue), the largest difference from float32 softmax attention
over the concatenated rows, and the call's share of the MXU peak on the
counted pairs (``4·B·H·(T+N)²·D``).

    python scripts/joint_slab_sweep.py sd3      # B=2, 77 + 4096 rows, 24 × 64
    python scripts/joint_slab_sweep.py flux     # B=1, 512 + 4096 rows, 24 × 128
        [--reps 40] [--out chiprun_out/joint_slab_sweep]

Run on the chip, as the one process that owns it. It fails without a TPU:
a walk's time on the CPU says nothing. The winner is what
``flash_joint.joint_slabs`` / ``joint_plan`` derive (PERF.md §6, PR 41):
no program reads this script's output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MXU_PEAK = 197e12          # bf16 FLOP/s of one TPU v5e chip (published)

# model: batch, text rows, image rows, heads, head width, whether q and k
# are buffers of their own (qk-norm / rope) or column groups of ``qkv``
GEOMETRIES = {
    "sd3": (2, 77, 4096, 24, 64, False),
    "flux": (1, 512, 4096, 24, 128, True),
}


def walks(txt_rows: int, img_len: int, head_dim: int) -> dict:
    """name → softmax steps (``flash_joint.joint_slabs``' form)."""
    from comfyui_distributed_tpu.ops.flash_joint import (_IMG, _TXT,
                                                         joint_slabs)

    def image(start: int, slab: int):
        return [((_IMG, s, min(slab, img_len - s)),)
                for s in range(start, img_len, slab)]

    def ride(slab: int):
        first = slab - txt_rows
        return tuple([((_TXT, 0, txt_rows), (_IMG, 0, first))]
                     + image(first, slab))

    def own(slab: int):
        return tuple([((_TXT, 0, txt_rows),)] + image(0, slab))

    out = {"shipped": joint_slabs(txt_rows, img_len, head_dim)}
    for slab in (640, 768, 896, 1024, 1152, 1280, 1408, 1536, 1664, 1792,
                 2176):
        if slab > txt_rows:
            out[f"ride{slab}"] = ride(slab)
    for slab in (512, 768, 1024, 1280, 1408, 1536, 2048, 4096):
        out[f"own{slab}"] = own(slab)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("model", choices=sorted(GEOMETRIES))
    ap.add_argument("--reps", type=int, default=40)
    ap.add_argument("--out", default="chiprun_out/joint_slab_sweep")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        sys.exit("joint_slab_sweep: no TPU here; a walk's time on "
                 f"{jax.devices()[0].platform} says nothing")
    from comfyui_distributed_tpu.ops import flash_attention as fa
    from comfyui_distributed_tpu.ops import flash_joint as fj
    from comfyui_distributed_tpu.ops.attention import Columns

    B, T, N, H, D, own_qk = GEOMETRIES[args.model]
    HD = H * D
    keys = jax.random.split(jax.random.key(41), 6)

    def rnd(key, *shape):
        return jax.random.normal(key, shape, jnp.bfloat16)

    img_qkv, txt_qkv = rnd(keys[0], B, N, 3 * HD), rnd(keys[1], B, T, 3 * HD)
    if own_qk:
        extra = [rnd(k, B, n, HD) for k, n in zip(keys[2:], (T, T, N, N))]
        txt = [Columns(extra[0]), Columns(extra[1]), Columns(txt_qkv, 2, 3)]
        img = [Columns(extra[2]), Columns(extra[3]), Columns(img_qkv, 2, 3)]
    else:
        txt = [Columns(txt_qkv, g, 3) for g in range(3)]
        img = [Columns(img_qkv, g, 3) for g in range(3)]
    operands = [c.array for c in txt + img]

    def rebuild(arrays):
        cols = [c._replace(array=a) for c, a in zip(txt + img, arrays)]
        return cols[:3], cols[3:]

    @jax.jit
    def reference(arrays):
        t, i = rebuild(arrays)
        q, k, v = (jnp.concatenate([a.cut(), b.cut()], axis=1)
                   .reshape(B, T + N, H, D).astype(jnp.float32)
                   for a, b in zip(t, i))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * D ** -0.5
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        return out.reshape(B, T + N, HD)

    ref = reference(operands)

    @jax.jit
    def concat(arrays):
        t, i = rebuild(arrays)
        q, k, v = (jnp.concatenate([a.cut(), b.cut()], axis=1)
                   for a, b in zip(t, i))
        bq, bk = fa._packed_blocks(T + N, T + N, D, 2)
        out = fa._flash_mha_packed(q, k, v, num_heads=H, block_q=bq,
                                   block_k=bk, interpret=False)
        return out[:, :T], out[:, T:]

    def two_segment(plan):
        @jax.jit
        def call(arrays):
            t, i = rebuild(arrays)
            return fj.flash_joint_attention(t, i, H, plan, False)
        return call

    base = fj.joint_plan(T, N, D, 2)
    candidates = {"concat": concat}
    for name, slabs in walks(base.txt_rows, N, D).items():
        candidates[f"q{base.img_block_q}.{name}"] = two_segment(
            base._replace(slabs=slabs))
    for bq in (256, 1024):
        candidates[f"q{bq}.shipped"] = two_segment(
            base._replace(img_block_q=bq))

    flops = 4 * B * H * (T + N) ** 2 * D
    rows = []
    for name, fn in candidates.items():
        try:
            t_out, i_out = jax.block_until_ready(fn(operands))
        except Exception as e:  # noqa: BLE001 — the compiler's refusal
            rows.append({"candidate": name, "error": str(e)[:300]})
            print(f"{name:>18}  refused: {str(e)[:120]}", flush=True)
            continue
        err = float(jnp.max(jnp.abs(
            jnp.concatenate([t_out, i_out], axis=1).astype(jnp.float32)
            - ref)))
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(args.reps):
                out = fn(operands)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / args.reps
            best = dt if best is None else min(best, dt)
        rows.append({"candidate": name, "us_per_call": best * 1e6,
                     "max_abs_err": err,
                     "mxu_pct": 100 * flops / best / MXU_PEAK})
        print(f"{name:>18}  {best * 1e6:9.1f} us  err {err:.4f}  "
              f"{100 * flops / best / MXU_PEAK:5.1f}% of the MXU peak",
              flush=True)

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"{args.model}.json"), "w") as f:
        json.dump({"model": args.model, "device": jax.devices()[0].device_kind,
                   "geometry": GEOMETRIES[args.model], "reps": args.reps,
                   "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
