"""The builder's parity check of a ``jamba`` cell, on the chip:

    python -m cdtbench.parity_jamba --workload <cell> [--seeds 1,2] [--degrade ...]

builds the cell's language model as the registry does (its preset, the
registry's seed), runs the SAME bound ``llm_prefill`` ``serve`` runs for the
cell's graph (the prompt walked in chunks through the cache: recurrent
states, convolution tails and K/V rows) and an ``llm_decode`` of the same
steps, and holds what they produced to the float32 reference
(``cdtbench/reference/llm_jamba_reference.py``, a copy of the repo's): the
reference is teacher-forced on the ids the program drew, layer by layer and
``REFERENCE_BLOCK`` rows at a time so that it fits, and the logits are
compared at the last prompt position and at the tapped decode steps.
Logits, not ids: with random weights the largest logit changes on rounding.

**What is compared in decode.** As ``parity_kimi.py``: the served
``llm_decode`` taps every 128th step's logits and this cell samples 128
tokens, one row; the tool binds the same decode function with a tap spacing
of its own, ``TAP_EVERY`` = 16: eight rows. The ids drawn are the served
program's (the taps only read the carry).

The tolerances, each with its reason, are data:
``reference/<config>.parity.json`` (each seed is held to them alone).
``--degrade`` runs the program below what the configuration states (the
reference stays as it is); those runs must FAIL on every seed. Three arms
lower a precision — ``state_bf16`` (the recurrent state rounded to bfloat16
wherever it is handed on: between chunks and between tokens), ``dt_bf16``
(``Δ`` rounded to bfloat16 ahead of the scan) and ``weights_fp8`` (every
matrix in fp8 e4m3) — and three leave out mathematics — ``no_norms`` (the
dt/B/C norms), ``no_d`` (``D ⊙ u``) and ``no_conv_bias``. All six are built
HERE, around the served code (the served model has no switch for them).
``--compile-only`` compiles both programs for a described v5e instead (no
chip needed, nothing runs) and prints their memory. Not part of a measured
run.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from cdtbench import workload as W  # noqa: E402
from cdtbench.kinds.jamba import request_sizes  # noqa: E402
from cdtbench.parity import compare, summary, verdict  # noqa: E402
from cdtbench.parity_kimi import compile_only, programs  # noqa: E402

HERE = Path(__file__).resolve().parent
DEGRADE = ("none", "state_bf16", "dt_bf16", "weights_fp8", "no_norms",
           "no_d", "no_conv_bias")
TAP_EVERY = 16            # this tool's decode taps (the served: 128)
REFERENCE_BLOCK = 512     # rows of the reference at a time


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "cdtbench_llm_jamba_reference",
        HERE / "reference" / "llm_jamba_reference.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _bf16(x):
    """Rounded to bfloat16's 8 bits of mantissa (``reduce_precision``, not
    a cast there and back: the TPU compiler drops that pair)."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@contextlib.contextmanager
def scan_in_bf16(what: str):
    """The served model with the scan's state (``state_bf16``: as it comes
    from and goes back to the cache) or its ``Δ`` (``dt_bf16``) rounded to
    bfloat16, prefill and decode: wrapped around the two functions of
    ``ops/selective_scan`` the model calls, while the programs are traced."""
    from comfyui_distributed_tpu.ops import selective_scan as ops

    chunk, step = ops.scan_chunk, ops.scan_step

    def low(fn):
        def lowered(h, u, dt, *rest, **kw):
            if what == "dt_bf16":
                return fn(h, u, _bf16(dt), *rest, **kw)
            y, h = fn(_bf16(h), u, dt, *rest, **kw)
            return y, _bf16(h)
        return lowered

    ops.scan_chunk, ops.scan_step = low(chunk), low(step)
    try:
        yield
    finally:
        ops.scan_chunk, ops.scan_step = chunk, step


@contextlib.contextmanager
def without_scan_norms(cfg):
    """The served model with the norms of ``δ``, ``B`` and ``C`` left out
    (their weights still multiply): ``llm_jamba.rms_norm`` passes a vector
    of one of those three widths through, while the programs are traced."""
    from comfyui_distributed_tpu.models import llm_jamba

    widths = {cfg.mamba_dt_rank, cfg.mamba_d_state}
    assert cfg.hidden_size not in widths
    normed = llm_jamba.rms_norm

    def left_out(x, weight, eps):
        if x.shape[-1] in widths:
            return x * weight
        return normed(x, weight, eps)

    llm_jamba.rms_norm = left_out
    try:
        yield
    finally:
        llm_jamba.rms_norm = normed


def lowered_weights(params, what: str):
    """``params`` as an arm holds them: every matrix in fp8 (the model
    casts what it holds to bfloat16 before a product), or one leaf of every
    Mamba layer zeroed (``D``: no ``D ⊙ u``; the convolution's bias)."""
    import jax
    import jax.numpy as jnp

    if what == "weights_fp8":
        return jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float8_e4m3fn)
            if a.dtype == jnp.bfloat16 else a, params)
    leaf = {"no_d": "d", "no_conv_bias": "conv_b"}[what]
    return {**params, "mamba": [
        {**run, "ssm": {**run["ssm"], leaf: jnp.zeros_like(run["ssm"][leaf])}}
        for run in params["mamba"]]}


def run_once(cfg, params, bound, reference, prompt_ids, new_tokens, seed,
             temperature) -> dict:
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    prefill, decode = bound
    timings = {}
    for attempt in ("first", "second"):           # the first call compiles
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
    n_prompt = len(prompt_ids)
    ids = np.concatenate([np.asarray(prompt_ids), np.asarray(out)])
    taps = [i for i in range(new_tokens) if (i + 1) % TAP_EVERY == 0]
    positions = [n_prompt - 1] + [n_prompt + i for i in taps]
    t0 = time.monotonic()
    want = np.asarray(reference.forward(
        cfg, params, jnp.asarray(ids, jnp.int32), positions,
        block=REFERENCE_BLOCK))
    timings["reference"] = time.monotonic() - t0
    rows = [dict(position=positions[0], what="last prompt position",
                 **compare(logits, want[0]))]
    for j, i in enumerate(taps):
        rows.append(dict(position=n_prompt + i, what=f"decode step {i}",
                         **compare(tap_logits[j], want[j + 1])))
    return {"seed": seed, "finite": bool(finite), "rows": rows,
            "seconds": timings}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="20260930")
    parser.add_argument("--degrade", default="none", choices=DEGRADE)
    parser.add_argument("--rehearse", action="store_true",
                        help="the tiny preset and the rehearsal sizes (CPU)")
    parser.add_argument("--compile-only", action="store_true")
    parser.add_argument("--topology", default="v5e:2x2")
    args = parser.parse_args(argv)

    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

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
    params = cfg.model.init(cfg, jax.random.key(0))   # the registry's seed
    served = lowered_weights(params, args.degrade) \
        if args.degrade in ("weights_fp8", "no_d", "no_conv_bias") else params
    lowered = {"state_bf16": lambda: scan_in_bf16("state_bf16"),
               "dt_bf16": lambda: scan_in_bf16("dt_bf16"),
               "no_norms": lambda: without_scan_norms(cfg)}.get(
                   args.degrade, contextlib.nullcontext)
    bound = programs(LLMPipeline(cfg, served), n_prompt, new_tokens)
    results, faults = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        prompt_ids = rewrite_prompt_ids(f"parity prompt of seed {seed}",
                                        n_prompt, cfg.vocab_size)
        with lowered():        # the first call traces and compiles
            result = run_once(cfg, params, bound, reference, prompt_ids,
                              new_tokens, seed, temperature)
        result["faults"] = verdict(result["rows"], limits["limits"]) \
            + ([] if result["finite"] else ["a non-finite logit"])
        faults += result["faults"]
        results.append(result)
        for row in result["rows"]:
            print(f"[parity] seed {seed} pos {row['position']:5d} "
                  f"({row['what']}): rel_l2 {row['rel_l2']:.3e}  max_abs "
                  f"{row['max_abs']:.3e}  ref std {row['ref_std']:.3f}  "
                  f"argmax {'same' if row['same_argmax'] else 'differs'}")
        print(f"[parity] seed {seed}: seconds "
              f"{ {k: round(v, 2) for k, v in result['seconds'].items()} }",
              flush=True)
    out_dir = W.ROOT / "chiprun_out" / "cdtbench" / cell.name
    out_dir.mkdir(parents=True, exist_ok=True)
    line = {"workload": cell.name, "degrade": args.degrade,
            "device": {"platform": device.platform,
                       "kind": device.device_kind},
            "sizes": {"prompt_tokens": n_prompt, "new_tokens": new_tokens,
                      "tap_every": TAP_EVERY},
            "inside_tolerances": not faults, "faults": faults,
            "readings": {x["seed"]: summary(x["rows"]) for x in results},
            "results": results}
    (out_dir / f"parity.{args.degrade}.json").write_text(json.dumps(line))
    print(json.dumps({k: v for k, v in line.items() if k != "results"}))
    return 0 if not faults else 1


if __name__ == "__main__":
    sys.exit(main())
