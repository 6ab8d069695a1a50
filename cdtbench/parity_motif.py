"""The builder's parity check of a ``motif`` cell, on the chip:

    python -m cdtbench.parity_motif --workload <cell> [--seeds 1,2] [--degrade ...]

builds the cell's language model as the registry does (its preset, the
registry's seed), runs the SAME two bound programs ``serve`` runs for the
cell's graph (``llm_prefill`` + ``llm_decode`` at the graph's prompt and
new-token counts), and holds what they produced to the float32 reference
(``cdtbench/reference/llm_motif_reference.py``, a copy of the repo's): the
reference is teacher-forced on the ids the program drew, layer by layer so
that it fits, and the logits are compared where ``llm_decode`` returns them
(every 128th step) and at the last prompt position. Logits, not ids: with
random weights the largest logit changes on rounding.

The tolerances, each with its reason, are data:
``reference/<config>.parity.json`` (each seed is held to them alone).
``--degrade streams_bf16`` / ``experts_fp8`` runs the program one precision
below what the configuration states (the reference stays as it is): those
runs must FAIL, and the limits lie between their readings and the stated
precision's. The lower precisions are built HERE, around the served code
(the four streams and every mHC coefficient rounded to bfloat16 in every
layer; the experts' weights cast to fp8): the served model has no switch
for them. ``--compile-only`` compiles both programs for a described v5e
instead (no chip needed, nothing runs). Not part of a measured run.

``parity.py`` is the ``llm`` kind's tool and is not edited by a PR that adds
a cell; what the two share is imported from it.
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
from cdtbench.kinds.motif import request_sizes  # noqa: E402
from cdtbench.parity import compare, summary, verdict  # noqa: E402

HERE = Path(__file__).resolve().parent
DEGRADE = ("none", "streams_bf16", "experts_fp8")


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "cdtbench_llm_motif_reference",
        HERE / "reference" / "llm_motif_reference.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@contextlib.contextmanager
def streams_in_bfloat16():
    """The served model with its four streams and every mHC coefficient
    rounded to bfloat16 after every sublayer: wrapped around the module's
    own ``hyper_connect`` / ``hc_coefficients`` while the programs are
    traced. ``reduce_precision``, not a cast there and back: the TPU
    compiler drops that pair."""
    import jax

    from comfyui_distributed_tpu.models import llm_motif

    def rounded(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    coefficients, connect = llm_motif.hc_coefficients, llm_motif.hyper_connect

    def low_coefficients(cfg, p, X):
        return tuple(rounded(c) for c in coefficients(cfg, p, rounded(X)))

    def low_connect(cfg, p, X, sublayer):
        out, extra = connect(cfg, p, X, sublayer)
        return rounded(out), extra

    llm_motif.hc_coefficients, llm_motif.hyper_connect = (low_coefficients,
                                                          low_connect)
    try:
        yield
    finally:
        llm_motif.hc_coefficients, llm_motif.hyper_connect = (coefficients,
                                                              connect)


def experts_in_fp8(params):
    """The model casts what it holds to bfloat16 before a product."""
    import jax.numpy as jnp

    def fp8(layer):
        if "moe" not in layer:
            return layer
        moe = {**layer["moe"], **{
            name: layer["moe"][name].astype(jnp.float8_e4m3fn)
            for name in ("e_gu", "e_down")}}
        return {**layer, "moe": moe}

    return {**params, "layers": [fp8(x) for x in params["layers"]]}


def run_once(cfg, params, pipe, reference, prompt_ids, new_tokens, seed,
             temperature) -> dict:
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.diffusion.pipeline_llm import TAP_EVERY

    timings = {}
    for attempt in ("first", "second"):           # the first call compiles
        t0 = time.monotonic()
        out = pipe.generate(prompt_ids, new_tokens, seed, temperature)
        jax.block_until_ready(out["tap_logits"])
        timings[attempt] = time.monotonic() - t0
    n_prompt = len(prompt_ids)
    ids = np.concatenate([np.asarray(prompt_ids), out["ids"]])
    taps = [i for i in range(new_tokens) if (i + 1) % TAP_EVERY == 0]
    positions = [n_prompt - 1] + [n_prompt + i for i in taps]
    t0 = time.monotonic()
    want, held = reference.forward(cfg, params, jnp.asarray(ids, jnp.int32),
                                   positions)
    want = np.asarray(want)
    timings["reference"] = time.monotonic() - t0
    rows = [dict(position=positions[0], what="last prompt position",
                 **compare(out["prefill_logits"], want[0]))]
    for j, i in enumerate(taps):
        rows.append(dict(position=n_prompt + i, what=f"decode step {i}",
                         **compare(out["tap_logits"][j], want[j + 1])))
    # both count the same tokens: the prompt and every drawn token's forward
    held_ref = [int(h) for h in held[cfg.n_dense_first_layers:]]
    held_got = (out["held_prefill"] + out["held_decode"]).tolist()
    return {"seed": seed, "finite": out["finite"], "rows": rows,
            "held_slots_program": held_got, "held_slots_reference": held_ref,
            "seconds": timings}


def compile_only(cfg, n_prompt: int, new_tokens: int, topology: str) -> int:
    import time

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from comfyui_distributed_tpu.diffusion.pipeline_llm import LLMPipeline

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology)
    chip = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    weights = place(cfg.model.init(cfg, None, abstract=True))
    pipe = LLMPipeline(cfg, weights)
    prefill, decode = pipe.programs(n_prompt, new_tokens)
    ids = jax.ShapeDtypeStruct((n_prompt,), jnp.int32, sharding=chip)
    logits, cache, _ = jax.eval_shape(prefill.jitted, weights, ids)
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
    pipe = LLMPipeline(cfg, experts_in_fp8(params)
                       if args.degrade == "experts_fp8" else params)
    lowered = streams_in_bfloat16 if args.degrade == "streams_bf16" \
        else contextlib.nullcontext
    results, faults = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        prompt_ids = rewrite_prompt_ids(f"parity prompt of seed {seed}",
                                        n_prompt, cfg.vocab_size)
        with lowered():        # the first call traces and compiles
            result = run_once(cfg, params, pipe, reference, prompt_ids,
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
              f"{result['held_slots_reference']}; seconds "
              f"{ {k: round(v, 2) for k, v in result['seconds'].items()} }")
    out_dir = W.ROOT / "chiprun_out" / "cdtbench" / cell.name
    out_dir.mkdir(parents=True, exist_ok=True)
    line = {"workload": cell.name, "degrade": args.degrade,
            "device": {"platform": device.platform,
                       "kind": device.device_kind},
            "sizes": {"prompt_tokens": n_prompt, "new_tokens": new_tokens},
            "inside_tolerances": not faults, "faults": faults,
            "readings": {x["seed"]: summary(x["rows"]) for x in results},
            "results": results}
    (out_dir / f"parity.{args.degrade}.json").write_text(json.dumps(line))
    print(json.dumps({k: v for k, v in line.items() if k != "results"}))
    return 0 if not faults else 1


if __name__ == "__main__":
    sys.exit(main())
