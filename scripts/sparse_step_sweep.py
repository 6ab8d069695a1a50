#!/usr/bin/env python
"""What a grid step of the selecting rewriter's table-driven sparse kernel
(``ops/block_select_attention.block_select_mha``) costs by its FORM, and
which half of the form holds the cost.

One run times the kernel ALONE at ``minicpm-sala``'s served geometry (512
neighbouring queries of 32 heads over 2 K/V groups a call — 16 tiles of 64
queries × 16 heads —, 65 664 cache rows in blocks of 64, 16 blocks a grid
step, bfloat16) on the eight calls of the chunks at the asked positions,
over three kinds of table:

- ``rule``: the rule's own (``block_scores`` → ``select``) on seeded random
  queries and compressed keys: neighbours choose apart, a tile's union is
  about the whole causal prefix and most steps hold sixteen CONSECUTIVE
  blocks;
- ``clustered``: all 64 queries of a tile choose the same 63 blocks beside
  the forced ones: unions of ~97 blocks, seven steps a tile;
- ``scattered``: only blocks an even distance below the tile's own are
  chosen, so no two blocks of a union are adjacent and no step is a run;

for six forms of the step:

- ``parent``: PR 49's kernel, kept here — 16 K and 16 V BlockSpecs a step
  whose index maps read the prefetched table (and a mask BlockSpec that
  moves at every step, skipped ones too), two 16-way concatenations; the
  mask spread by selects, concatenated over the heads and applied by a
  compare and a select (``bsa._own_logits``: the shipped kernel's too);
- ``bias``: the parent's fetch, the mask as one added bias (spread over a
  block's columns by a 0/1 product, the causal cut only in a step that
  holds a block at or past the tile's own);
- ``runs``: the SHIPPED kernel — its own copies (one stretch of rows where
  the step's blocks are consecutive, a copy a block where not; nothing in a
  skipped step), the parent's mask;
- ``both``: the shipped fetch under the added bias;
- ``bias_selects``: as ``both`` with the bias spread by the parent's
  selects instead of the product;
- ``floor``: the shipped fetch and NO mask (wrong answers: a time only).

It prints, a form and a kind: seconds summed over the cell's prefill (16
chunks × 3 sparse layers; a sampled position stands for the chunks nearest
it), the grid's steps by fetch (``tile_unions``' count: what
``cdt_llm_sparse_steps_total`` counts), the µs a visible and a skipped step
(``t = a·visible + b·skipped`` fitted over the positions, least squares)
and whether the answers are the parent's to the bit.

    python scripts/sparse_step_sweep.py
        [--forms parent,both] [--kinds rule] [--positions 0,7,15]
        [--reps 3] [--out chiprun_out/tile_sweep]

Run on the chip, as the one process that owns it. It fails without a TPU:
a step's time on the CPU says nothing. No program reads this script's
output (PERF.md §6, PR 50).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from comfyui_distributed_tpu.ops import block_select_attention as bsa  # noqa: E402
from comfyui_distributed_tpu.ops.block_select_attention import (  # noqa: E402
    NEG_INF, _VMEM_LIMIT_BYTES, _accumulate, _init_running, _kv_copies,
    _precision_of, _running_scratch, _visible)
from scripts.score_tile_sweep import time_calls, weights_of  # noqa: E402

CELL = "minicpm-sala.brief64k-sdxl8"
PROMPT, NEW = 65536, 128
FORMS = ("parent", "bias", "runs", "both", "bias_selects", "floor")
KINDS = ("rule", "clustered", "scattered")


# --- the step's forms ----------------------------------------------------------


def _fill_bias(bias_ref, own, union_ref, base, first_row, *, block: int):
    """What a step ADDS to the logits of each query's heads, into
    ``bias_ref`` [block_q, R · block]: 0 over the columns of the union
    entries ``own`` [block_q, R] marks as the query's (spread over each
    block's columns by a product of 0/1: exact), ``NEG_INF`` elsewhere —
    and, in them, ``NEG_INF`` over the rows past the query's position:
    only a step that holds a block at or past the FIRST query's own
    (``first_row``'s) needs that cut, every other entry lies wholly below
    every query of the tile. ``base``: the step's first entry in the
    flat table."""
    block_q, per_step = own.shape
    width = per_step * block
    entry = jax.lax.broadcasted_iota(jnp.int32, (per_step, width), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (per_step, width), 1)
    mine = jax.lax.dot_general(
        own.astype(jnp.bfloat16), (col // block == entry).astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    bias_ref[:] = jnp.where(mine > 0.5, 0.0, NEG_INF)

    @pl.when(union_ref[base + per_step - 1] >= first_row // block)
    def _cut():
        col = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        col_pos = jnp.zeros((1, width), jnp.int32)
        for r in range(per_step):
            col_pos = jnp.where(col // block == r,
                                union_ref[base + r] * block + col % block,
                                col_pos)
        row_pos = first_row + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        bias_ref[:] = jnp.where(col_pos <= row_pos, bias_ref[:], NEG_INF)


def _masked(form: str, s, bias_ref, mask_ref, union_ref, base, first_row, *,
            block: int, heads: int):
    """The logits ``s`` [heads · block_q, width] under the step's mask in
    ``form``: ``select`` (the shipped one), ``bias``, ``bias_selects`` or
    ``none``."""
    own = mask_ref[0, 0, 0]
    block_q = own.shape[0]
    if form == "select":
        return bsa._own_logits(s, own, union_ref, base, first_row,
                               block=block, heads=heads)
    if form == "bias":
        _fill_bias(bias_ref, own, union_ref, base, first_row, block=block)
        bias = bias_ref[:]
    elif form == "bias_selects":
        mine, col_pos = bsa._spread_own(own, union_ref, base, block=block)
        row_pos = first_row + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        bias = jnp.where((col_pos <= row_pos) & (mine > 0.5), 0.0, NEG_INF)
    else:
        return s
    # a masked logit is s + NEG_INF = NEG_INF in float32: the select's bits
    return (s.reshape(heads, block_q, -1) + bias[None]).reshape(s.shape)


def _specs_kernel(union_ref, count_ref, pos_ref, q_ref, *refs, mask: str,
                  block_q: int, block: int, per_step: int, heads: int,
                  tiles: int, union_len: int, precision):
    """PR 49's step: K and V a block a BlockSpec, concatenated."""
    k_refs, v_refs = refs[:per_step], refs[per_step:2 * per_step]
    mask_ref, o_ref, m_ref, l_ref, acc_ref, bias_ref = refs[2 * per_step:]
    g, i, e = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    tile = g * tiles + i
    _init_running(e, m_ref, l_ref, acc_ref)

    @pl.when(e * per_step < count_ref[tile])
    def _step():
        k = jnp.concatenate([r[0] for r in k_refs], axis=0)
        v = jnp.concatenate([r[0] for r in v_refs], axis=0)
        s = jax.lax.dot_general(q_ref[0, 0], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=precision)
        s = _masked(mask, s, bias_ref, mask_ref, union_ref,
                    tile * union_len + e * per_step,
                    pos_ref[0] + i * block_q, block=block, heads=heads)
        _accumulate(s, v, m_ref, l_ref, acc_ref, precision)

    @pl.when(e == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)


def _copies_kernel(union_ref, count_ref, pos_ref, q_ref, mask_ref, k_hbm,
                   v_hbm, o_ref, m_ref, l_ref, acc_ref, k_buf, v_buf,
                   bias_ref, sem, slot_ref, *, mask: str, block_q: int,
                   block: int, per_step: int, heads: int, tiles: int,
                   all_tiles: int, union_len: int, precision):
    """The shipped step (``bsa._block_select_kernel``: its copies, its
    two buffers) under the mask's form asked for."""
    g, i, e = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    tile = g * tiles + i
    count = count_ref[tile]
    copies = functools.partial(
        _kv_copies, union_ref=union_ref, k_hbm=k_hbm, v_hbm=v_hbm,
        k_buf=k_buf, v_buf=v_buf, sem=sem, block=block, per_step=per_step,
        tiles=tiles, union_len=union_len)
    _init_running(e, m_ref, l_ref, acc_ref)

    @pl.when((tile == 0) & (e == 0))
    def _first():
        slot_ref[0] = 0
        copies(lambda c: c.start(), tile=tile, e=e, slot=0)

    @pl.when(_visible(e, count, per_step))
    def _step():
        slot = slot_ref[0]
        slot_ref[0] = 1 - slot
        more = (e + 1) * per_step < count

        @pl.when(more | (tile + 1 < all_tiles))
        def _next():
            copies(lambda c: c.start(), tile=jnp.where(more, tile, tile + 1),
                   e=jnp.where(more, e + 1, 0), slot=1 - slot)

        copies(lambda c: c.wait(), tile=tile, e=e, slot=slot)
        s = jax.lax.dot_general(q_ref[0, 0], k_buf[slot],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=precision)
        s = _masked(mask, s, bias_ref, mask_ref, union_ref,
                    tile * union_len + e * per_step,
                    pos_ref[0] + i * block_q, block=block, heads=heads)
        _accumulate(s, v_buf[slot], m_ref, l_ref, acc_ref, precision)

    @pl.when(e == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "fetch", "mask_form",
                                             "interpret"))
def step_form_mha(q, k, v, union, count, mask, start, block: int,
                  fetch: str, mask_form: str, interpret: bool = False):
    """``bsa.block_select_mha``'s arguments and answers, the step's K/V
    rows by ``fetch`` (``specs``: PR 49's BlockSpec a block; ``copies``:
    the shipped kernel's own copies) and its mask in ``mask_form``
    (``select``, ``bias``, ``bias_selects``, ``none``). ``specs`` +
    ``select`` is PR 49's kernel, ``copies`` + ``select`` the shipped
    one."""
    G, tiles, rows, d = q.shape
    _, _, steps, block_q, per_step = mask.shape
    U = union.shape[-1]
    sizes = dict(mask=mask_form, block_q=block_q, block=block,
                 per_step=per_step, heads=rows // block_q, tiles=tiles,
                 union_len=U, precision=_precision_of(q.dtype))
    tile_spec = pl.BlockSpec((1, 1, rows, d),
                             lambda g, i, e, *_: (g, i, 0, 0))
    bias = pltpu.VMEM((block_q, per_step * block), jnp.float32)
    prefetched = (union.reshape(-1).astype(jnp.int32),
                  count.reshape(-1).astype(jnp.int32),
                  jnp.reshape(start, (1,)).astype(jnp.int32))
    if fetch == "specs":
        def kv_spec(r):
            return pl.BlockSpec(
                (1, block, d),
                lambda g, i, e, union_ref, *_: (
                    g, union_ref[(g * tiles + i) * U + e * per_step + r], 0))

        kernel = functools.partial(_specs_kernel, **sizes)
        in_specs = [tile_spec] + [kv_spec(r) for r in range(per_step)] * 2 \
            + [pl.BlockSpec((1, 1, 1, block_q, per_step),
                            lambda g, i, e, *_: (g, i, e, 0, 0))]
        scratch = _running_scratch(rows, d) + [bias]
        semantics = ("parallel", "parallel", "arbitrary")
        operands = (q, *([k] * per_step), *([v] * per_step), mask)
    else:
        def last_step(g, i, count_ref):
            return jnp.maximum(
                -(-count_ref[g * tiles + i] // per_step) - 1, 0)

        kernel = functools.partial(_copies_kernel, all_tiles=G * tiles,
                                   **sizes)
        in_specs = [
            tile_spec,
            pl.BlockSpec((1, 1, 1, block_q, per_step),
                         lambda g, i, e, union_ref, count_ref, *_: (
                             g, i, jnp.minimum(
                                 e, last_step(g, i, count_ref)), 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY)]
        scratch = _running_scratch(rows, d) + [
            pltpu.VMEM((2, per_step * block, d), k.dtype),
            pltpu.VMEM((2, per_step * block, d), v.dtype), bias,
            pltpu.SemaphoreType.DMA((2, 2)), pltpu.SMEM((1,), jnp.int32)]
        semantics = ("arbitrary", "arbitrary", "arbitrary")
        operands = (q, mask, k, v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(G, tiles, steps), in_specs=in_specs,
        out_specs=tile_spec, scratch_shapes=scratch)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*prefetched, *operands)


def parent_mha(q, k, v, union, count, mask, start, block: int,
               interpret: bool = False):
    """PR 49's ``block_select_mha``: what the shipped kernel's answers are
    held to, to the bit."""
    return step_form_mha(q, k, v, union, count, mask, start, block=block,
                         fetch="specs", mask_form="select",
                         interpret=interpret)


def form_call(form: str):
    """``(q, k, v, union, count, mask, start, block) -> answers`` of a
    form of the step; ``runs`` is the shipped kernel itself."""
    if form == "runs":
        return functools.partial(bsa.block_select_mha, interpret=False)
    fetch, mask_form = {"parent": ("specs", "select"),
                        "bias": ("specs", "bias"),
                        "both": ("copies", "bias"),
                        "bias_selects": ("copies", "bias_selects"),
                        "floor": ("copies", "none")}[form]
    return functools.partial(step_form_mha, fetch=fetch, mask_form=mask_form)


# --- the tables ------------------------------------------------------------------


def chosen_of(kind: str, key, q, kc, start, scale, dtype, sel, block_q: int):
    """``chosen`` [G, Q, nb] of ``kind`` for the ``Q`` queries at ``start
    …`` (``lone``: a query's own block and nothing else)."""
    G, Sc, _ = kc.shape
    Q, nb = q.shape[0], Sc // sel.per
    pos = start + jnp.arange(Q)
    if kind == "rule":
        return bsa.select(bsa.block_scores(q, kc, pos, scale, dtype, sel),
                          sel)
    own = (pos // sel.block_size)[None, :, None]
    b = jnp.arange(nb)[None, None, :]
    if kind == "lone":                      # one step a tile: the own block
        return jnp.broadcast_to(b == own, (G, Q, nb))
    tile_own = ((start + jnp.arange(Q) // block_q * block_q)
                // sel.block_size)[None, :, None]
    if kind == "clustered":
        # a score a (group, TILE, block): the tile's queries choose alike
        score = jax.random.uniform(key, (G, Q // block_q, nb))
        score = jnp.repeat(score, block_q, axis=1)
        forced = (b < sel.init_blocks) | (b > tile_own - sel.local_blocks)
    else:
        score = jax.random.uniform(key, (G, Q, nb))
        score = jnp.where((tile_own - b) % 2 == 0, score, -jnp.inf)
        forced = (b == own) | (b == tile_own % 2)
    score = jnp.where(b > own, -jnp.inf, jnp.where(forced, jnp.inf, score))
    return bsa.select(score, sel)


# --- the sweep -------------------------------------------------------------------


def fit_steps(rows: list) -> tuple:
    """``(µs a visible step, µs a skipped step)``: least squares of ``t =
    a·visible + b·skipped`` over ``rows`` of ``(seconds, visible,
    skipped)``."""
    import numpy as np

    t, steps = (np.asarray([r[0] for r in rows]),
                np.asarray([r[1:] for r in rows], float))
    (a, b), *_ = np.linalg.lstsq(steps, t, rcond=None)
    return a * 1e6, b * 1e6


def sweep(forms, kinds, asked: str, reps: int) -> dict:
    import numpy as np

    from comfyui_distributed_tpu.models.llm_sala import SalaConfig

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"a step is timed on a TPU, not on {device.platform}")
    cfg = SalaConfig.sala_cut()
    sel, dtype = cfg.selection, jnp.dtype(cfg.dtype)
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    Q, C = cfg.select_rows, cfg.prefill_chunk_tokens
    bq, R = cfg.sparse_block_q, cfg.sparse_blocks_per_step
    S = cfg.cache_rows(PROMPT + NEW)
    layers, scale = len(cfg.sparse_layers), d ** -0.5
    weights = weights_of(PROMPT // C, asked)
    keys = jax.random.split(jax.random.key(0), 5)
    q = jax.random.normal(keys[0], (Q, H, d), jnp.float32)
    kc = jax.random.normal(keys[1], (G, cfg.cache_slots(S), d), dtype)
    k = jax.random.normal(keys[2], (G, S, d), dtype)
    v = jax.random.normal(keys[3], (G, S, d), dtype)
    qt = bsa._head_major_tiles((q * scale).astype(dtype), G, bq)
    calls = {form: form_call(form) for form in forms}

    @functools.partial(jax.jit, static_argnames="kind")
    def tables(kind, key, start):
        chosen = chosen_of(kind, key, q, kc, start, scale, dtype, sel, bq)
        return bsa.tile_unions(chosen, bq, R)

    def timed_at(kind, p):
        """A chunk's eight calls at position ``p`` under every form:
        ``({form: seconds}, steps by fetch, {form: the parent's bits?})``."""
        starts = [jnp.int32(p * C + n * Q) for n in range(C // Q)]
        made = [tables(kind, jax.random.fold_in(keys[4], p * 8 + n), start)
                for n, start in enumerate(starts)]
        fetches = np.sum([np.asarray(m[3]) for m in made], axis=0)
        seconds, equal, want = {}, {}, None
        for form, call in calls.items():
            def one(n, call=call):
                union, count, mask, _ = made[n]
                return call(qt, k, v, union, count, mask, starts[n],
                            block=sel.block_size)

            got = np.asarray(jax.block_until_ready(one(0)))  # compiles
            if form == "parent":
                want = got
            elif want is not None and form != "floor":
                equal[form] = bool((got == want).all())
            seconds[form] = time_calls(one, range(len(made)), reps)
        return seconds, fetches, equal

    # one visible step a tile and 64 skipped: what tells the two apart in
    # every kind's fit (a kind's own positions are nearly collinear)
    lone_s, lone_steps, _ = timed_at("lone", max(weights))
    rows = []
    for kind in kinds:
        at = {p: timed_at(kind, p) for p in weights}
        steps = layers * sum(w * at[p][1] for p, w in weights.items())
        for form in forms:
            fit = [(lone_s[form], lone_steps[:2].sum(), lone_steps[2])] + [
                (at[p][0][form], at[p][1][:2].sum(), at[p][1][2])
                for p in weights]
            visible_us, skipped_us = fit_steps(fit)
            equal = [at[p][2].get(form) for p in weights]
            row = {"kind": kind, "form": form,
                   "prefill_s": layers * sum(w * at[p][0][form]
                                             for p, w in weights.items()),
                   "steps": dict(zip(bsa.STEP_FETCHES, steps.tolist())),
                   "visible_step_us": visible_us,
                   "skipped_step_us": skipped_us,
                   "equal_to_parent": None if None in equal else all(equal)}
            rows.append(row)
            print(f"{kind} {form}: {row['prefill_s']:.4f} s a prefill, "
                  f"{visible_us:.2f} us a visible step, {skipped_us:.2f} "
                  f"skipped; steps {row['steps']}; the parent's bits: "
                  f"{row['equal_to_parent']}", flush=True)
    return {"cell": CELL, "positions": {str(p): w
                                        for p, w in weights.items()},
            "reps": reps,
            "device": {"platform": device.platform,
                       "kind": device.device_kind,
                       "count": jax.device_count()},
            "rows": rows}


def table(result: dict) -> str:
    """The result as PERF.md holds it."""
    lines = [f"`block_select_mha` alone ({result['cell']}; positions "
             f"{','.join(result['positions'])})",
             "| table | form | s a prefill | us a visible step | us a "
             "skipped step | steps run / blocks / skipped | the parent's "
             "bits |", "|---|---|---|---|---|---|---|"]
    for r in result["rows"]:
        s = r["steps"]
        lines.append(
            f"| {r['kind']} | {r['form']} | {r['prefill_s']:.4f} | "
            f"{r['visible_step_us']:.2f} | {r['skipped_step_us']:.2f} | "
            f"{s['run']} / {s['blocks']} / {s['skipped']} | "
            f"{ {True: 'yes', False: 'NO', None: '-'}[r['equal_to_parent']]}"
            " |")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--kinds", default=",".join(KINDS))
    ap.add_argument("--positions", default="0,7,15",
                    help="chunk positions, comma-separated")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/tile_sweep")
    args = ap.parse_args(argv)
    result = sweep(args.forms.split(","), args.kinds.split(","),
                   args.positions, args.reps)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "block_select_mha.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(table(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
