"""The builder's parity check of a ``kimi`` cell, on the chip:

    python -m cdtbench.parity_kimi --workload <cell> [--seeds 1,2] [--degrade ...]

builds the cell's language model as the registry does (its preset, the
registry's seed), runs the SAME bound ``llm_prefill`` ``serve`` runs for the
cell's graph (the prompt walked in chunks through the latent cache) and an
``llm_decode`` of the same steps, and holds what they produced to the
float32 reference (``cdtbench/reference/llm_kimi_reference.py``, a copy of
the repo's): the reference is teacher-forced on the ids the program drew,
layer by layer and ``REFERENCE_BLOCK`` query rows at a time so that it
fits, and the logits are compared at the last prompt position and at the
tapped decode steps. Logits, not ids: with random weights the largest logit
changes on rounding.

**What is compared in decode.** The served ``llm_decode`` taps every 128th
step's logits (``pipeline_llm.TAP_EVERY``) and this cell samples 128 tokens:
ONE row, which cannot carry a "closest row" limit. So the tool binds the
same decode function (``LLMPipeline.decode_fn``, same steps, same sampling,
same cache) with a tap spacing of its own, ``TAP_EVERY`` = 16 here: eight
rows. The served program's spacing is not changed for it; the ids drawn are
the served program's (the taps only read the carry).

The tolerances, each with its reason, are data:
``reference/<config>.parity.json`` (each seed is held to them alone).
``--degrade`` runs the program below what the configuration states (the
reference stays as it is); those runs must FAIL on every seed. Two arms
lower a precision — ``cache_fp8`` (the latent cache, ``c`` and the roped
key, rounded to fp8 e4m3 wherever attention reads it) and ``experts_fp8``
(the experts' weights in fp8) — and two leave out mathematics —
``plain_rope`` (θ's frequencies in place of YaRN's table) and ``no_mscale``
(the softmax scale without ``mscale²``). All four are built HERE, around
the served code (the served model has no switch for them).
``--compile-only`` compiles both programs for a described v5e instead (no
chip needed, nothing runs) and prints their memory. Not part of a
measured run.

``parity.py`` is the ``llm`` kind's tool and is not edited by a PR that adds
a cell; what the tools share is imported from it and from
``parity_motif.py``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from cdtbench import workload as W  # noqa: E402
from cdtbench.kinds.kimi import request_sizes  # noqa: E402
from cdtbench.parity import compare, summary, verdict  # noqa: E402
from cdtbench.parity_motif import experts_in_fp8  # noqa: E402

HERE = Path(__file__).resolve().parent
DEGRADE = ("none", "cache_fp8", "experts_fp8", "plain_rope", "no_mscale")
TAP_EVERY = 16            # this tool's decode taps (the served: 128)
REFERENCE_BLOCK = 2048    # query rows of the reference at a time


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "cdtbench_llm_kimi_reference",
        HERE / "reference" / "llm_kimi_reference.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@contextlib.contextmanager
def cache_in_fp8():
    """The served model with the latent cache rounded to fp8 (e4m3: 4
    exponent bits, 3 of mantissa) wherever attention reads it, prefill and
    decode: wrapped around the two functions of ``ops/latent_attention``
    the model calls, while the programs are traced. ``reduce_precision``,
    not a cast there and back: the TPU compiler drops that pair."""
    import jax

    from comfyui_distributed_tpu.ops import latent_attention as ops

    def rounded(x):
        return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)

    chunk, step = ops.mla_chunk_attention, ops.mla_absorbed_step

    def low_chunk(q_nope, q_rope, c_cache, kr_cache, *rest, **kw):
        return chunk(q_nope, q_rope, rounded(c_cache), rounded(kr_cache),
                     *rest, **kw)

    def low_step(q_nope, q_rope, c_cache, kr_cache, *rest, **kw):
        return step(q_nope, q_rope, rounded(c_cache), rounded(kr_cache),
                    *rest, **kw)

    ops.mla_chunk_attention, ops.mla_absorbed_step = low_chunk, low_step
    try:
        yield
    finally:
        ops.mla_chunk_attention, ops.mla_absorbed_step = chunk, step


def left_out(cfg, what: str):
    """``cfg`` with one piece of the mathematics left out: the same sizes
    (the weights and the reference are the stated configuration's)."""
    import math

    from comfyui_distributed_tpu.ops.latent_attention import yarn_mscale

    d = cfg.qk_rope_head_dim
    if what == "plain_rope":
        table = [cfg.rope_theta ** (-2.0 * i / d) for i in range(d // 2)]
        members = {"rope_freqs": property(lambda self: table)}
    else:
        assert what == "no_mscale"
        m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
        bare = cfg.softmax_scale / (m * m)
        assert abs(bare - 1 / math.sqrt(cfg.qk_nope_head_dim + d)) < 1e-9
        members = {"softmax_scale": property(lambda self: bare)}
    lowered = dataclasses.dataclass(frozen=True)(
        type(f"{type(cfg).__name__}_{what}", (type(cfg),), members))
    return lowered(**dataclasses.asdict(cfg))


def programs(pipe, n_prompt: int, new_tokens: int):
    """The served ``llm_prefill`` and a decode of this tool's tap spacing."""
    return (pipe.programs(n_prompt, new_tokens)[0],
            pipe.decode_fn(n_prompt, new_tokens, tap_every=TAP_EVERY))


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
        logits, cache, held_prefill, rows = prefill(
            jnp.asarray(prompt_ids, jnp.int32))
        jax.block_until_ready(logits)
        timings[f"prefill_{attempt}"] = time.monotonic() - t0
        t0 = time.monotonic()
        out, tap_logits, held_decode, finite = decode(
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
    want, held = reference.forward(cfg, params, jnp.asarray(ids, jnp.int32),
                                   positions, block=REFERENCE_BLOCK)
    want = np.asarray(want)
    timings["reference"] = time.monotonic() - t0
    rows_cmp = [dict(position=positions[0], what="last prompt position",
                     **compare(logits, want[0]))]
    for j, i in enumerate(taps):
        rows_cmp.append(dict(position=n_prompt + i, what=f"decode step {i}",
                             **compare(tap_logits[j], want[j + 1])))
    # both count the same tokens: the prompt and every drawn token's forward
    held_ref = [int(h) for h in held[cfg.first_k_dense_replace:]]
    held_got = (np.asarray(held_prefill) + np.asarray(held_decode)).tolist()
    return {"seed": seed, "finite": bool(finite), "rows": rows_cmp,
            "held_slots_program": held_got, "held_slots_reference": held_ref,
            "expert_rows_prefill": np.asarray(rows).tolist(),
            "held_slots_prefill": np.asarray(held_prefill).tolist(),
            "seconds": timings}


def compile_only(cfg, n_prompt: int, new_tokens: int, topology: str) -> int:
    import time

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from comfyui_distributed_tpu.diffusion.pipeline_llm import LLMPipeline
    from comfyui_distributed_tpu.ops import flash_attention

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology)
    chip = SingleDeviceSharding(topo.devices[0])
    # the dispatch reads the platform through this one function: the
    # described chip takes the Pallas kernel, as the real one will
    flash_attention._platform = lambda: "tpu"

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    weights = place(cfg.model.init(cfg, None, abstract=True))
    pipe = LLMPipeline(cfg, weights)
    prefill, decode = pipe.programs(n_prompt, new_tokens)
    ids = jax.ShapeDtypeStruct((n_prompt,), jnp.int32, sharding=chip)
    logits, cache, *_ = jax.eval_shape(prefill.jitted, weights, ids)
    key = jax.eval_shape(lambda: jax.random.key(0))
    report = {}
    for name, fn, args in (
            ("llm_prefill", prefill.jitted, (weights, ids)),
            ("llm_decode", decode.jitted,
             (weights, place(logits), place(cache), place(key),
              jax.ShapeDtypeStruct((), jnp.float32, sharding=chip)))):
        t0 = time.monotonic()
        compiled = fn.lower(*args).compile()
        mem = compiled.memory_analysis()
        report[name] = {
            "compile_here_s": round(time.monotonic() - t0, 1),
            "arguments_gib": round(mem.argument_size_in_bytes / 2**30, 3),
            "temporaries_gib": round(mem.temp_size_in_bytes / 2**30, 3),
            "outputs_gib": round(mem.output_size_in_bytes / 2**30, 3)}
    print(json.dumps({"topology": topology, "programs": report,
                      "note": "compiled off-chip for a described device; "
                              "nothing ran"}))
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="20260927")
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
    served = left_out(cfg, args.degrade) \
        if args.degrade in ("plain_rope", "no_mscale") else cfg
    pipe = LLMPipeline(served, experts_in_fp8(params)
                       if args.degrade == "experts_fp8" else params)
    lowered = cache_in_fp8 if args.degrade == "cache_fp8" \
        else contextlib.nullcontext
    bound = programs(pipe, n_prompt, new_tokens)
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
        print(f"[parity] seed {seed}: held slots program "
              f"{result['held_slots_program']} reference "
              f"{result['held_slots_reference']}; prefill multiplied "
              f"{result['expert_rows_prefill']} rows for "
              f"{result['held_slots_prefill']} held slots; seconds "
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
