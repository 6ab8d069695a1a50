"""The builder's parity check of a ``trinity`` cell, on the chip:

    python -m cdtbench.parity_trinity --workload <cell> [--seeds 1,2] [--degrade a,b]

builds the cell's language model as the registry does (its preset, the
registry's seed), runs the SAME bound ``llm_prefill`` ``serve`` runs for the
cell's graph (the prompt walked in chunks of the window through rings and
the full layer's buffer) and an ``llm_decode`` of the same steps, and holds
what they produced to the float32 reference
(``cdtbench/reference/llm_trinity_reference.py``, a copy of the repo's): the
reference is teacher-forced on the ids the program drew, and the logits are
compared at the last prompt position and at the tapped decode steps. Logits,
not ids: with random weights the largest logit changes on rounding.

**How the reference is walked.** Attention is causal, so the reference's
prompt rows do not depend on what is drawn after them: per seed the tool
walks the prompt ONCE (layer by layer, ``REFERENCE_BLOCK`` query rows at a
time through ``layer_rows``, every layer kind under its whole mask) and
keeps each layer's float32 keys and values of the prompt rows on the host.
Every run of that seed — the stated precision and each ``--degrade`` arm,
whose drawn ids differ — then evaluates only the rows it compares (the last
prompt position and the drawn tokens) against those keys and values plus
their own: the same functions of the same reference, on the rows needed.
``tests/test_llm_trinity.py`` holds the walk equal to ``reference.forward``.

**What is compared in decode.** As ``parity_kimi.py``: the served
``llm_decode`` taps every 128th step's logits and this cell samples 128
tokens, one row; the tool binds the same decode function with a tap spacing
of its own, ``TAP_EVERY`` = 16: eight rows. The ids drawn are the served
program's (the taps only read the carry).

The tolerances, each with its reason, are data:
``reference/<config>.parity.json`` (each seed is held to them alone).
``--degrade`` (one arm or several, comma-separated, in ONE process so that
they share the prompt walk) runs the program below what the configuration
states (the reference stays as it is); those runs must FAIL on every seed.
Three arms lower a precision — ``kv_fp8`` (the K/V rows rounded to fp8 e4m3
wherever attention reads them), ``stream_bf16`` (the residual stream rounded
to bfloat16 after every sublayer) and ``weights_fp8`` (every matrix in fp8
e4m3) — and seven leave out mathematics, one at a time — ``rope_on_full``
(rope put on the full layer too), ``band_wide`` (a window layer's band
widened to all of ``[ring ; chunk]``), ``no_gate`` (the sigmoid output
gate), ``no_sandwich`` (the two norms AFTER the sublayers), ``no_qk_norm``,
``no_bias`` (the router's selection bias) and ``no_mup`` (the embedding's
multiplier). All ten are built HERE, around the served code (the served
model has no switch for them). ``--compile-only`` compiles both programs
for a described v5e instead (no chip needed, nothing runs) and prints their
memory. Not part of a measured run.
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
from cdtbench.kinds.trinity import request_sizes  # noqa: E402
from cdtbench.parity import compare, summary, verdict  # noqa: E402
from cdtbench.parity_kimi import compile_only, programs  # noqa: E402

HERE = Path(__file__).resolve().parent
LOWER = ("kv_fp8", "stream_bf16", "weights_fp8")
LEFT_OUT = ("rope_on_full", "band_wide", "no_gate", "no_sandwich",
            "no_qk_norm", "no_bias", "no_mup")
DEGRADE = ("none",) + LOWER + LEFT_OUT
TAP_EVERY = 16            # this tool's decode taps (the served: 128)
REFERENCE_BLOCK = 1024    # query rows of the reference at a time


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "cdtbench_llm_trinity_reference",
        HERE / "reference" / "llm_trinity_reference.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# --- the arms: the served code with one thing lowered or left out -----------


@contextlib.contextmanager
def _patched(module, **members):
    """``module``'s named members replaced while the programs are traced."""
    kept = {name: getattr(module, name) for name in members}
    for name, value in members.items():
        setattr(module, name, value)
    try:
        yield
    finally:
        for name, value in kept.items():
            setattr(module, name, value)


def lowered(cfg, arm: str):
    """The context in which ``arm``'s programs are traced (the weights'
    arms — ``weights_fp8``, ``no_bias`` — change no code:
    :func:`lowered_weights`)."""
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import llm_trinity as M
    from comfyui_distributed_tpu.ops import gqa_attention as ops

    def fp8(x):      # not a cast there and back: the TPU compiler drops it
        return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)

    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    chunk, step = ops.causal_chunk, ops.step
    if arm == "kv_fp8":
        return _patched(
            ops,
            causal_chunk=lambda q, k, v, *a, **kw: chunk(q, fp8(k), fp8(v),
                                                         *a, **kw),
            step=lambda q, k, v, *a, **kw: step(q, fp8(k), fp8(v), *a, **kw))
    if arm == "band_wide":
        def wide(*a, window=None, **kw):
            return chunk(*a, window=None if window is None else 2 * window,
                         **kw)
        return _patched(ops, causal_chunk=wide)
    add_normed, attn_in, attn_out = M._add_normed, M._attn_in, M._attn_out
    if arm == "stream_bf16":
        return _patched(M, _add_normed=lambda *a: bf16(add_normed(*a)))
    if arm == "no_sandwich":
        return _patched(M, _add_normed=lambda h, y, weight, eps: h + y)
    if arm == "no_gate":
        return _patched(M, _attn_out=lambda c, p, o, gate: attn_out(
            c, p, o, jnp.full_like(gate, 1e4)))          # σ(1e4) = 1
    if arm == "no_qk_norm":
        normed = M.rms_norm

        def per_head_left_out(x, weight, eps):
            if x.ndim == 3 and x.shape[-1] == cfg.head_dim:
                return x.astype(jnp.float32) * weight
            return normed(x, weight, eps)
        return _patched(M, rms_norm=per_head_left_out)
    if arm == "no_mup":
        embed = M._embed
        return _patched(M, _embed=lambda c, params, ids: embed(
            c, params, ids) / c.embed_scale)
    if arm == "rope_on_full":
        rows, seen = M._rope_rows, {}

        def keep(params, start, n):
            seen["rope"] = rows(params, start, n)
            return seen["rope"]
        return _patched(
            M, _rope_rows=keep,
            _attn_in=lambda c, p, x, rope: attn_in(
                c, p, x, seen["rope"] if rope is None else rope))
    return contextlib.nullcontext()


def lowered_weights(params, arm: str):
    """``params`` as an arm holds them: every matrix in fp8 (the model
    casts what it holds to bfloat16 before a product), or every router's
    selection bias zeroed; any other arm holds them as they are."""
    import jax
    import jax.numpy as jnp

    if arm == "weights_fp8":
        return jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float8_e4m3fn)
            if a.dtype == jnp.bfloat16 else a, params)
    if arm == "no_bias":
        return {**params, "layers": [
            {**layer, "moe": {**layer["moe"], "router_bias":
                              jnp.zeros_like(layer["moe"]["router_bias"])}}
            if "moe" in layer else layer for layer in params["layers"]]}
    return params


# --- the reference, walked once a prompt ------------------------------------


def prompt_walk(reference, cfg, params, prompt_ids, block: int) -> list:
    """Per layer the float32 keys and values ``[T,G,d]`` of the prompt's
    rows (host arrays): the reference's layers applied to ALL the prompt's
    rows, ``block`` query rows at a time; the last layer's rows are not
    needed for them."""
    import jax.numpy as jnp
    import numpy as np

    T = len(prompt_ids)
    cos, sin = reference.rope_angles(cfg, T)
    x = reference.embed(cfg, params, jnp.asarray(prompt_ids, jnp.int32))
    walk = []
    for i, layer in enumerate(params["layers"]):
        sliding, moe = reference.layer_kind(cfg, i)
        k, v = reference.keys_values(cfg, sliding, layer, x, cos, sin)
        walk.append((np.asarray(k), np.asarray(v)))
        if i + 1 == len(params["layers"]):
            break
        # block by block to the host: two copies of the rows never share
        # the device with the layer's float32 weights
        parts = [np.asarray(reference.layer_rows(
            cfg, sliding, moe, layer, x[lo:lo + block],
            jnp.arange(lo, min(lo + block, T)), k, v, cos[lo:lo + block],
            sin[lo:lo + block])[0]) for lo in range(0, T, block)]
        del x, k, v
        x = jnp.asarray(np.concatenate(parts))
    return walk


def tail_logits(reference, cfg, params, walk: list, ids, n_prompt: int,
                positions: list):
    """The reference's logits at ``positions`` (all ``≥ n_prompt − 1``)
    of the sequence ``ids`` whose first ``n_prompt`` are the walked prompt:
    the rows from the last prompt position on through every layer, against
    the walked keys and values and their own."""
    import jax.numpy as jnp
    import numpy as np

    first = n_prompt - 1
    rows = jnp.arange(first, len(ids))
    cos, sin = (a[first:] for a in reference.rope_angles(cfg, len(ids)))
    x = reference.embed(cfg, params, jnp.asarray(ids[first:], jnp.int32))
    for i, layer in enumerate(params["layers"]):
        sliding, moe = reference.layer_kind(cfg, i)
        k, v = reference.keys_values(cfg, sliding, layer, x, cos, sin)
        k, v = (jnp.concatenate([jnp.asarray(w[:first]), a])
                for w, a in zip(walk[i], (k, v)))
        x, _ = reference.layer_rows(cfg, sliding, moe, layer, x, rows, k, v,
                                    cos, sin)
        del k, v
    at = jnp.asarray([p - first for p in positions])
    return np.asarray(reference.head_forward(
        cfg, params["final_norm"], params["head"], x[at]))


def run_once(cfg, params, bound, reference, walk, prompt_ids, new_tokens,
             seed, temperature) -> dict:
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
    want = tail_logits(reference, cfg, params, walk, ids, n_prompt,
                       positions)
    timings["reference_tail"] = time.monotonic() - t0
    rows_cmp = [dict(position=positions[0], what="last prompt position",
                     **compare(logits, want[0]))]
    for j, i in enumerate(taps):
        rows_cmp.append(dict(position=n_prompt + i, what=f"decode step {i}",
                             **compare(tap_logits[j], want[j + 1])))
    return {"seed": seed, "finite": bool(finite), "rows": rows_cmp,
            "expert_rows_prefill": np.asarray(rows).tolist(),
            "held_slots_prefill": np.asarray(held_prefill).tolist(),
            "held_slots_decode": np.asarray(held_decode).tolist(),
            "seconds": timings}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="20260930")
    parser.add_argument("--degrade", default="none",
                        help="one arm or several, comma-separated, of "
                        + ", ".join(DEGRADE))
    parser.add_argument("--rehearse", action="store_true",
                        help="the tiny preset and the rehearsal sizes (CPU)")
    parser.add_argument("--compile-only", action="store_true")
    parser.add_argument("--topology", default="v5e:2x2")
    args = parser.parse_args(argv)
    arms = args.degrade.split(",")
    if set(arms) - set(DEGRADE):
        parser.error(f"--degrade: {sorted(set(arms) - set(DEGRADE))} not of "
                     f"{DEGRADE}")

    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import time

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
    seeds = [int(s) for s in args.seeds.split(",")]
    prompts = {seed: rewrite_prompt_ids(f"parity prompt of seed {seed}",
                                        n_prompt, cfg.vocab_size)
               for seed in seeds}
    walks = {}
    out_dir = W.ROOT / "chiprun_out" / "cdtbench" / cell.name
    out_dir.mkdir(parents=True, exist_ok=True)
    wrong = 0
    for arm in arms:
        bound = programs(LLMPipeline(cfg, lowered_weights(params, arm)),
                         n_prompt, new_tokens)
        results, faults = [], []
        for seed in seeds:
            if seed not in walks:
                t0 = time.monotonic()
                walks[seed] = prompt_walk(reference, cfg, params,
                                          prompts[seed], REFERENCE_BLOCK)
                print(f"[parity] seed {seed}: the reference walked the "
                      f"prompt in {time.monotonic() - t0:.1f} s", flush=True)
            with lowered(cfg, arm):   # the first call traces and compiles
                result = run_once(cfg, params, bound, reference,
                                  walks[seed], prompts[seed], new_tokens,
                                  seed, temperature)
            result["faults"] = verdict(result["rows"], limits["limits"]) \
                + ([] if result["finite"] else ["a non-finite logit"])
            faults += result["faults"]
            results.append(result)
            for row in result["rows"]:
                print(f"[parity] {arm} seed {seed} pos {row['position']:6d} "
                      f"({row['what']}): rel_l2 {row['rel_l2']:.3e}  "
                      f"max_abs {row['max_abs']:.3e}  ref std "
                      f"{row['ref_std']:.3f}  argmax "
                      f"{'same' if row['same_argmax'] else 'differs'}")
            print(f"[parity] {arm} seed {seed}: prefill multiplied "
                  f"{result['expert_rows_prefill']} rows for "
                  f"{result['held_slots_prefill']} held slots; seconds "
                  f"{ {k: round(v, 2) for k, v in result['seconds'].items()} }",
                  flush=True)
        del bound
        line = {"workload": cell.name, "degrade": arm,
                "device": {"platform": device.platform,
                           "kind": device.device_kind},
                "sizes": {"prompt_tokens": n_prompt,
                          "new_tokens": new_tokens, "tap_every": TAP_EVERY},
                "inside_tolerances": not faults, "faults": faults,
                "seeds_failed": sum(bool(x["faults"]) for x in results),
                "readings": {x["seed"]: summary(x["rows"]) for x in results},
                "results": results}
        (out_dir / f"parity.{arm}.json").write_text(json.dumps(line))
        print(json.dumps({k: v for k, v in line.items() if k != "results"}),
              flush=True)
        # the stated precision must pass on every seed, an arm fail on each
        wrong += bool(faults) if arm == "none" \
            else line["seeds_failed"] != len(seeds)
    return 0 if not wrong else 1


if __name__ == "__main__":
    sys.exit(main())
