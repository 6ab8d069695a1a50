"""The builder's parity check of a ``longcat`` cell, on the chip:

    python -m cdtbench.parity_longcat --workload <cell> [--seeds 1,2] [--degrade a,b]

builds the cell's language model as the registry does (its preset, the
registry's seed), runs the SAME bound ``llm_prefill`` ``serve`` runs for the
cell's graph (the prompt walked in chunks through both latent caches of
every double layer) and an ``llm_decode`` of the same steps, and holds what
they produced to the float32 reference
(``cdtbench/reference/llm_longcat_reference.py``, a copy of the repo's): the
reference is teacher-forced on the ids the program drew, and the logits are
compared at the last prompt position and at the tapped decode steps. Logits,
not ids: with random weights the largest logit changes on rounding.

**How the reference is walked** (as ``parity_trinity.py``). Attention is
causal, so the reference's prompt rows do not depend on what is drawn after
them: per seed the tool walks the prompt ONCE (sublayer by sublayer,
``REFERENCE_BLOCK`` query rows at a time through ``sublayer_rows``) and
keeps each attention sublayer's float32 keys — the latent ``c`` and the
roped shared key — of the prompt rows on the host. Every run of that seed —
the stated precision and each ``--degrade`` arm, whose drawn ids differ —
then evaluates only the rows it compares (the last prompt position and the
drawn tokens) against those keys plus their own: the same functions of the
same reference, on the rows needed. ``tests/test_llm_longcat.py`` holds the
walk equal to ``reference.forward``.

**What is compared in decode.** The served ``llm_decode`` taps every 128th
step's logits and this cell samples 256 tokens: two rows. The tool binds the
same decode function (``LLMPipeline.decode_fn``: same steps, sampling and
cache) with ``parity_kimi``'s tap spacing, 16: sixteen rows. The ids drawn
are the served program's (the taps only read the carry).

The tolerances, each with its reason, are data:
``reference/<config>.parity.json`` (each seed is held to them alone).
``--degrade`` (one arm or several, comma-separated, in ONE process so that
they share the prompt walk) runs the program below or beside what the
configuration states (the reference stays as it is); those runs must FAIL
on every seed. Two arms lower a precision — ``cache_fp8`` (both latent
caches rounded to fp8 e4m3 wherever attention reads them) and
``weights_fp8`` (every matrix in fp8 e4m3) — and five leave out or change
mathematics, one at a time — ``no_identity`` (the identity experts' part
dropped), ``normalised`` (the chosen weights normalised), ``sigmoid`` (a
sigmoid for the router's softmax), ``branch_from_second`` (the expert
branch fed from the SECOND sublayer's post-attention norm) and
``no_kv_scale`` (the latent's ``√(D / kv_lora)`` left out). All seven are
built HERE, around the served code (the served model has no switch for
them). ``--compile-only`` compiles both programs for a described v5e
instead (no chip needed, nothing runs) and prints their memory. Not part of
a measured run.
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
from cdtbench.kinds.longcat import request_sizes  # noqa: E402
from cdtbench.parity import compare, summary, verdict  # noqa: E402
from cdtbench.parity_kimi import (TAP_EVERY, cache_in_fp8,  # noqa: E402
                                  compile_only, programs)
from cdtbench.parity_trinity import _patched  # noqa: E402

HERE = Path(__file__).resolve().parent
LOWER = ("cache_fp8", "weights_fp8")
LEFT_OUT = ("no_identity", "normalised", "sigmoid", "branch_from_second",
            "no_kv_scale")
DEGRADE = ("none",) + LOWER + LEFT_OUT
REFERENCE_BLOCK = 2048    # query rows of the reference at a time


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "cdtbench_llm_longcat_reference",
        HERE / "reference" / "llm_longcat_reference.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# --- the arms: the served code with one thing lowered or changed ------------


def lowered_config(cfg, arm: str):
    """``cfg`` as an arm's programs are traced with: the same sizes (the
    weights and the reference are the stated configuration's), one value
    of the model changed."""
    if arm == "no_kv_scale":
        return dataclasses.replace(cfg, mla_scale_kv_lora=False)
    if arm not in ("normalised", "sigmoid"):
        return cfg
    routing = dataclasses.replace(
        cfg.routing, **({"normalised": True} if arm == "normalised"
                        else {"score": "sigmoid"}))
    changed = dataclasses.dataclass(frozen=True)(
        type(f"{type(cfg).__name__}_{arm}", (type(cfg),),
             {"routing": property(lambda self: routing)}))
    return changed(**dataclasses.asdict(cfg))


def lowered(arm: str):
    """The context in which ``arm``'s programs are traced."""
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import llm_longcat as M
    from comfyui_distributed_tpu.ops import expert_share

    if arm == "cache_fp8":
        return cache_in_fp8()
    if arm == "no_identity":
        part = expert_share.zero_part

        def dropped(x, *a, **kw):
            mix, count = part(x, *a, **kw)
            return jnp.zeros_like(mix), count
        return _patched(expert_share, zero_part=dropped)
    if arm == "branch_from_second":
        return _patched(M, BRANCH_SUBLAYER=1)
    return contextlib.nullcontext()


def lowered_weights(params, arm: str):
    """``params`` as an arm holds them: every matrix (the leaves of two
    or more axes: projections, experts, router, embedding and head; not
    the norms' weights or the selection bias) in fp8, which the model
    casts to its ``dtype`` before a product; any other arm holds them as
    they are."""
    import jax
    import jax.numpy as jnp

    if arm != "weights_fp8":
        return params
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float8_e4m3fn) if a.ndim >= 2 else a, params)


# --- the reference, walked once a prompt ------------------------------------


def _sublayers(params):
    """``(sublayer, the layer's expert branch or None, is the layer's
    last)`` in order: the branch leaves from a layer's first sublayer."""
    for layer in params["layers"]:
        for i, sub in enumerate(layer["sub"]):
            yield sub, layer["moe"] if i == 0 else None, i == 1


def prompt_walk(reference, cfg, params, prompt_ids, block: int) -> list:
    """Per attention sublayer the float32 keys of the prompt's rows (host
    arrays ``(c [T,rank], k_rope [T,rope])``): the reference's sublayers
    applied to ALL the prompt's rows, ``block`` query rows at a time; the
    last sublayer's rows are not needed for them."""
    import jax.numpy as jnp
    import numpy as np

    T = len(prompt_ids)
    t = jnp.arange(T)
    h = reference.embed(params, jnp.asarray(prompt_ids, jnp.int32))
    n_sub = 2 * len(params["layers"])
    walk = []
    for sub, moe, last in _sublayers(params):
        c, k_rope = reference.latents(cfg, sub, h, t)
        walk.append((np.asarray(c), np.asarray(k_rope)))
        if len(walk) == n_sub:
            break
        parts = [reference.sublayer_rows(cfg, sub, moe, h[lo:lo + block],
                                         t[lo:lo + block], c, k_rope)[:2]
                 for lo in range(0, T, block)]
        h = jnp.concatenate([part[0] for part in parts])
        if moe is not None:
            branch = jnp.concatenate([part[1] for part in parts])
        if last:
            h = h + branch
    return walk


def tail_logits(reference, cfg, params, walk: list, ids, n_prompt: int,
                positions: list):
    """The reference's logits at ``positions`` (all ``≥ n_prompt − 1``)
    of the sequence ``ids`` whose first ``n_prompt`` are the walked prompt:
    the rows from the last prompt position on through every sublayer,
    against the walked keys and their own."""
    import jax.numpy as jnp
    import numpy as np

    first = n_prompt - 1
    rows = jnp.arange(first, len(ids))
    h = reference.embed(params, jnp.asarray(ids[first:], jnp.int32))
    for (sub, moe, last), kept in zip(_sublayers(params), walk):
        c, k_rope = (jnp.concatenate([jnp.asarray(w[:first]), own])
                     for w, own in zip(kept, reference.latents(cfg, sub, h,
                                                               rows)))
        h, m = reference.sublayer_rows(cfg, sub, moe, h, rows, c, k_rope)[:2]
        if moe is not None:
            branch = m
        if last:
            h = h + branch
    at = jnp.asarray([p - first for p in positions])
    return np.asarray(reference.head_forward(
        cfg, params["final_norm"], params["head"], h[at]))


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
        logits, cache, slots_prefill, rows = prefill(
            jnp.asarray(prompt_ids, jnp.int32))
        jax.block_until_ready(logits)
        timings[f"prefill_{attempt}"] = time.monotonic() - t0
        t0 = time.monotonic()
        out, tap_logits, slots_decode, finite = decode(
            logits, cache, jax.random.key(int(seed)),
            jnp.asarray(temperature, jnp.float32))
        jax.block_until_ready(tap_logits)
        timings[f"decode_{attempt}"] = time.monotonic() - t0
    del cache
    n_prompt, layers = len(prompt_ids), len(params["layers"])
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
    slots_prefill, slots_decode = (np.asarray(slots_prefill).tolist(),
                                   np.asarray(slots_decode).tolist())
    return {"seed": seed, "finite": bool(finite), "rows": rows_cmp,
            "expert_rows_prefill": np.asarray(rows).tolist(),
            "held_slots_prefill": slots_prefill[:layers],
            "zero_slots_prefill": slots_prefill[layers:],
            "held_slots_decode": slots_decode[:layers],
            "zero_slots_decode": slots_decode[layers:],
            "seconds": timings}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="20261001")
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
        bound = programs(LLMPipeline(lowered_config(cfg, arm),
                                     lowered_weights(params, arm)),
                         n_prompt, new_tokens)
        results, faults = [], []
        for seed in seeds:
            if seed not in walks:
                t0 = time.monotonic()
                walks[seed] = prompt_walk(reference, cfg, params,
                                          prompts[seed], REFERENCE_BLOCK)
                print(f"[parity] seed {seed}: the reference walked the "
                      f"prompt in {time.monotonic() - t0:.1f} s", flush=True)
            with lowered(arm):        # the first call traces and compiles
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
                  f"{result['held_slots_prefill']} held slots, "
                  f"{result['zero_slots_prefill']} on identity experts; "
                  f"decode {result['held_slots_decode']} held, "
                  f"{result['zero_slots_decode']} identity; seconds "
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
