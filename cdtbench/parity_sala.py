"""The builder's parity check of a ``sala`` cell, on the chip:

    python -m cdtbench.parity_sala --workload <cell> [--seeds 1,2] [--degrade none,state_bf16]

builds the cell's language model as the registry does (its preset, the
registry's seed), runs the SAME bound ``llm_prefill`` ``serve`` runs for the
cell's graph and an ``llm_decode`` of the same steps (the ids drawn are the
served program's), and holds the model to the float32 reference
(``cdtbench/reference/llm_sala_reference.py``, a copy of the repo's),
teacher-forced on those ids and walked ``REFERENCE_BLOCK`` rows at a time.

**A selection is discrete.** With seeded weights and a cycled brief many
block scores lie near the last place of a table, and bfloat16 rounding
moves them across it — an argmax's trouble, one level down — so the logits
of two passes that chose differently are not comparable to rounding. Three
comparisons, each with its tolerance and reason in
``reference/<config>.parity.json`` and ``cdtbench/SALA.md``:

(a) **the tables themselves**: the served functions are walked once more
    with ``keep_tables`` (``llm_sala.prefill_chunk`` chunk by chunk — the
    function ``llm_prefill`` scans — then ``decode_step`` on the drawn ids;
    the walk's logits are held to the served programs' own), and on every
    ``TABLE_EVERY``-th query the share of (query, group, layer) tables that
    agree with the reference's own is reported; for every block in one
    table and not the other, the reference's score of it against the score
    at the table's last place, as a relative gap: a gap beyond rounding is a
    fault of the rule (a dropped forced block reads ``inf``).
(b) **logits with the reference GIVEN the system's tables**, at the last
    prompt position and every ``TAP_EVERY``-th decoded position through the
    cache: the precision comparison, on the logits the SERVED programs
    produced (the walk's beside them: another compilation of the same
    functions, a rounding apart), as tight as the other rewriters'.
(c) **logits with the reference's own tables**: reported, not limited.

``--degrade`` (several, comma-separated, share one process and the
reference's (c) walk) runs the model below what the configuration states;
those arms must FAIL (b): ``state_bf16`` (a lightning state rounded to
bfloat16 wherever it is handed on, between chunks and between tokens) and
``weights_fp8`` (every matrix in fp8 e4m3). Both are built HERE, around the
served code. ``--compile-only`` compiles both programs for a described v5e
instead (no chip needed, nothing runs) and prints their memory. Not part of
a measured run.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from cdtbench import workload as W  # noqa: E402
from cdtbench.kinds.sala import request_sizes  # noqa: E402
from cdtbench.parity import compare, summary, verdict  # noqa: E402
from cdtbench.parity_jamba import lowered_weights  # noqa: E402
from cdtbench.parity_kimi import compile_only  # noqa: E402

HERE = Path(__file__).resolve().parent
DEGRADE = ("none", "state_bf16", "weights_fp8")
TAP_EVERY = 16            # decoded positions compared (the served taps: 128)
TABLE_EVERY = 16          # queries whose tables are compared
REFERENCE_BLOCK = 128     # rows of the reference at a time


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "cdtbench_llm_sala_reference",
        HERE / "reference" / "llm_sala_reference.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@contextlib.contextmanager
def state_in_bf16():
    """The served model with every lightning state rounded to bfloat16 as
    it comes from and goes back to the cache: wrapped around the two
    functions of ``ops/lightning_attention`` the model calls, while the
    walk's functions are traced."""
    import jax

    from comfyui_distributed_tpu.ops import lightning_attention as ops

    def low(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    chunk, step = ops.lightning_chunk, ops.lightning_step

    def low_chunk(S, *a, **kw):
        o, S = chunk(low(S), *a, **kw)
        return o, low(S)

    def low_step(S, *a, **kw):
        S, o = step(low(S), *a, **kw)
        return low(S), o

    ops.lightning_chunk, ops.lightning_step = low_chunk, low_step
    try:
        yield
    finally:
        ops.lightning_chunk, ops.lightning_step = chunk, step


def walk(cfg, params, ids, n_prompt: int, lowered):
    """The served model's own functions over ``ids`` (the prompt, then the
    drawn ids teacher-forced) with their tables kept: the logits at the
    last prompt position and at every decoded position ``[1 + new, V]``,
    and per sparse layer the tables ``[G, T, blocks]`` bool (None for a
    dense request)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.models import llm_sala

    total = len(ids)
    chunk = min(cfg.prefill_chunk_tokens, n_prompt)
    n = -(-n_prompt // chunk)
    with lowered():                      # the first calls trace and compile
        step = jax.jit(lambda w, c, i, s, v: llm_sala.prefill_chunk(
            cfg, w, c, i, s, v, keep_tables=True))
        token = jax.jit(lambda w, c, t, p: llm_sala.decode_step(
            cfg, w, c, t, p, keep_tables=True))
        cache = cfg.model.empty_cache(cfg, max(total, n * chunk))
        padded = np.pad(np.asarray(ids[:n_prompt]), (0, n * chunk - n_prompt))
        tables, rows = None, []
        for i in range(n):
            valid = min(chunk, n_prompt - i * chunk)
            logits, cache, _, _, kept = step(
                params, cache, jnp.asarray(padded[i * chunk:(i + 1) * chunk],
                                           jnp.int32), i * chunk, valid)
            if kept is not None:
                kept = [np.asarray(t[:, :valid]) for t in kept]
                tables = [[t] for t in kept] if tables is None else \
                    [a + [t] for a, t in zip(tables, kept)]
        rows.append(np.asarray(logits))
        for j in range(n_prompt, total):
            logits, cache, _, kept = token(
                params, cache, jnp.asarray(ids[j], jnp.int32), j)
            rows.append(np.asarray(logits))
            if kept is not None:
                for a, t in zip(tables, kept):
                    a.append(np.asarray(t)[:, None])
    if tables is not None:
        tables = [np.concatenate(a, axis=1) for a in tables]
    # rows[0] saw ids[:n_prompt]; rows[1 + i] saw the drawn id i too
    return np.stack(rows), tables


def table_readings(tables, keep, every: int) -> dict:
    """Comparison (a): the system's tables against the reference's own, on
    every ``every``-th query."""
    import numpy as np

    tables_n = agree_n = blocks_off = 0
    gaps = []
    for got, want, score in zip(tables, keep["chosen"], keep["scores"]):
        want, score = np.asarray(want), np.asarray(score, np.float64)
        # the system's buffer is whole lanes of slots: blocks past the rows
        got = np.asarray(got)[:, ::every]
        assert not got[..., want.shape[-1]:].any()
        got = got[..., :want.shape[-1]]
        same = (got == want).all(axis=-1)
        tables_n += same.size
        agree_n += int(same.sum())
        # the score at the table's last place, a table
        last = np.where(want, score, np.inf).min(axis=-1, keepdims=True)
        off = got != want
        blocks_off += int(off.sum())
        with np.errstate(invalid="ignore", divide="ignore"):
            gap = np.abs(score - last) / np.abs(last)
        gaps.append(gap[off])
    gaps = np.concatenate(gaps) if gaps else np.zeros((0,))
    finite = gaps[np.isfinite(gaps)]
    return {"tables": tables_n, "agree_pct": 100.0 * agree_n / tables_n,
            "blocks_off": blocks_off,
            "gap_not_finite": int(gaps.size - finite.size),
            "gap_median": float(np.median(finite)) if finite.size else 0.0,
            "gap_p99": float(np.quantile(finite, 0.99)) if finite.size
            else 0.0,
            "gap_max": float(gaps.max()) if gaps.size else 0.0}


def rows_of(got, want, positions, n_prompt: int) -> list:
    out = []
    for j, pos in enumerate(positions):
        what = "last prompt position" if pos == n_prompt - 1 \
            else f"decode step {pos - n_prompt}"
        out.append(dict(position=int(pos), what=what,
                        **compare(got[j], want[j])))
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="20261002")
    parser.add_argument("--degrade", default="none")
    parser.add_argument("--rehearse", action="store_true",
                        help="the tiny preset and the rehearsal sizes (CPU)")
    parser.add_argument("--compile-only", action="store_true")
    parser.add_argument("--topology", default="v5e:2x2")
    args = parser.parse_args(argv)
    arms = args.degrade.split(",")
    if any(a not in DEGRADE for a in arms):
        parser.error(f"--degrade takes {DEGRADE}")

    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.diffusion.pipeline_llm import LLMPipeline
    from comfyui_distributed_tpu.graph.nodes_builtin import rewrite_prompt_ids
    from comfyui_distributed_tpu.models.registry import PRESETS

    cell = W.assemble(args.workload, rehearsal=args.rehearse)
    cfg = PRESETS[cell.preset].llm
    n_prompt, new_tokens = request_sizes(cell)
    temperature = float(cell.graph[cell.traffic["nodes"]["prompt"][0]]
                        ["inputs"]["temperature"])
    if args.compile_only:
        return compile_only(cfg, n_prompt, new_tokens, args.topology)
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        print(f"[parity] needs the chip; JAX found {device.platform}",
              file=sys.stderr)
        return 3
    limits = json.loads((HERE / "reference"
                         / f"{cell.config['name']}.parity.json").read_text())
    reference = load_reference()
    block = REFERENCE_BLOCK if not args.rehearse else 8
    every = TABLE_EVERY if not args.rehearse else 2
    total = n_prompt + new_tokens
    params = cfg.model.init(cfg, jax.random.key(0))   # the registry's seed
    pipe = LLMPipeline(cfg, params)
    prefill = pipe.programs(n_prompt, new_tokens)[0]
    decode = pipe.decode_fn(n_prompt, new_tokens, tap_every=TAP_EVERY)
    taps = [i for i in range(new_tokens) if (i + 1) % TAP_EVERY == 0]
    positions = [n_prompt - 1] + [n_prompt + i for i in taps]
    picked = [0] + [1 + i for i in taps]      # rows of the walk's logits
    results, faults = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        prompt_ids = rewrite_prompt_ids(f"parity prompt of seed {seed}",
                                        n_prompt, cfg.vocab_size)
        timings = {}
        for attempt in ("first", "second"):       # the first call compiles
            t0 = time.monotonic()
            logits, cache, *_ = prefill(jnp.asarray(prompt_ids, jnp.int32))
            jax.block_until_ready(logits)
            timings[f"prefill_{attempt}"] = time.monotonic() - t0
            t0 = time.monotonic()
            out, tap_logits, _, finite = decode(
                logits, cache, jax.random.key(int(seed)),
                jnp.asarray(temperature, jnp.float32))
            jax.block_until_ready(tap_logits)
            timings[f"decode_{attempt}"] = time.monotonic() - t0
        del cache
        served = np.concatenate([np.asarray(logits)[None],
                                 np.asarray(tap_logits)])
        ids = np.concatenate([np.asarray(prompt_ids), np.asarray(out)])
        t0 = time.monotonic()
        keep = {"every": every}
        own = np.asarray(reference.forward(
            cfg, params, jnp.asarray(ids, jnp.int32), positions, block=block,
            total=total, keep=keep))
        timings["reference_own_tables"] = time.monotonic() - t0
        for arm in arms:
            lowered = state_in_bf16 if arm == "state_bf16" \
                else contextlib.nullcontext
            t0 = time.monotonic()
            weights = lowered_weights(params, arm) \
                if arm == "weights_fp8" else params
            got, tables = walk(cfg, weights, ids, n_prompt, lowered)
            got = got[picked]
            timings[f"walk_{arm}"] = time.monotonic() - t0
            t0 = time.monotonic()
            given = np.asarray(reference.forward(
                cfg, params, jnp.asarray(ids, jnp.int32), positions,
                block=block, total=total,
                tables=None if tables is None
                else [jnp.asarray(t[..., :-(-total // cfg.block_size)])
                      for t in tables]))
            timings[f"reference_given_{arm}"] = time.monotonic() - t0
            # the stated precision is held on what the SERVED programs
            # produced (the walk, another compilation of the same
            # functions, gives the tables and is compared beside them);
            # an arm below it exists only as a walk
            mine = served if arm == "none" else got
            result = {
                "seed": seed, "arm": arm, "finite": bool(finite),
                "rows": rows_of(mine, given, positions, n_prompt),
                "rows_own_tables": rows_of(mine, own, positions, n_prompt),
                "tables": None if tables is None
                else table_readings(tables, keep, every)}
            if arm == "none":
                result["rows_walk"] = rows_of(got, given, positions,
                                              n_prompt)
                result["walk_vs_served_rel_l2"] = max(
                    compare(a, b)["rel_l2"] for a, b in zip(got, served))
            result["faults"] = verdict(result["rows"], limits["limits"]) \
                + ([] if result["finite"] else ["a non-finite logit"])
            if result["tables"] is not None:
                gap = limits["table_gap_limit"]["limit"]
                if not result["tables"]["gap_max"] <= gap:
                    result["faults"].append(
                        f"table gap {result['tables']['gap_max']:.3e} "
                        f"over {gap:g}")
            if arm == "none":
                faults += result["faults"]
            results.append(result)
            for name in ("rows", "rows_own_tables", "rows_walk"):
                for row in result.get(name, ()):
                    print(f"[parity] seed {seed} {arm} {name} pos "
                          f"{row['position']:5d} ({row['what']}): rel_l2 "
                          f"{row['rel_l2']:.3e}  max_abs {row['max_abs']:.3e}"
                          f"  ref std {row['ref_std']:.3f}  argmax "
                          f"{'same' if row['same_argmax'] else 'differs'}")
            print(f"[parity] seed {seed} {arm}: tables {result['tables']} "
                  f"faults {result['faults']}", flush=True)
        print(f"[parity] seed {seed}: seconds "
              f"{ {k: round(v, 2) for k, v in timings.items()} }", flush=True)
    out_dir = W.ROOT / "chiprun_out" / "cdtbench" / cell.name
    out_dir.mkdir(parents=True, exist_ok=True)
    line = {"workload": cell.name, "degrade": arms,
            "device": {"platform": device.platform,
                       "kind": device.device_kind},
            "sizes": {"prompt_tokens": n_prompt, "new_tokens": new_tokens,
                      "tap_every": TAP_EVERY, "table_every": every},
            "inside_tolerances": not faults, "faults": faults,
            "readings": {f"{x['seed']}.{x['arm']}": {
                "given_tables": summary(x["rows"]),
                "own_tables": summary(x["rows_own_tables"]),
                "walk_vs_served_rel_l2": x.get("walk_vs_served_rel_l2"),
                "tables": x["tables"]} for x in results},
            "results": results}
    (out_dir / f"parity.{'+'.join(arms)}.json").write_text(json.dumps(line))
    print(json.dumps({k: v for k, v in line.items() if k != "results"}))
    return 0 if not faults else 1


if __name__ == "__main__":
    sys.exit(main())
