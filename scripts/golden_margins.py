#!/usr/bin/env python
"""Choose a rewriter cell's golden REQUEST (``cdtbench/KIMI.md``, "What
``correct`` sees"): of ``--candidates`` seeds, the one whose deciding draws
have the widest smallest margin.

SDXL's random UNet sees its conditioning, and the stand-in text encoder keeps
the first 76 words of the rewrite: one id flipped by a legitimate rounding
change re-draws every later id and with them the image. So the golden request
is the one LEAST likely to flip: the prompt is one text (one ``llm_prefill``
serves every candidate), and for every candidate seed the served programs'
own logits (a decode bound with a tap at every step) give every draw's
margin — top-1 minus top-2 of ``logits + temperature · gumbel(fold_in(key(
seed), i))`` for the 76 draws that reach the text encoder. The recomputed
argmax must equal the id the program drew.

    python scripts/golden_margins.py --workload <cell> --prompt "<text>"
        [--first 20261002] [--candidates 96] [--out chiprun_out/pr53]

Run on the chip, as the one process that owns it. No program reads this
script's output; the chosen seed is written into ``goldens/<cell>.json`` by
hand, with the margins found.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

DECIDING = 76          # hash_tokenize: max_len 77 less the end token


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--prompt", required=True)
    parser.add_argument("--first", type=int, default=20261002)
    parser.add_argument("--candidates", type=int, default=96)
    parser.add_argument("--out", default="chiprun_out/pr53")
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from cdtbench import workload as W
    from cdtbench.kinds.llm import request_sizes
    from comfyui_distributed_tpu.diffusion.pipeline_llm import LLMPipeline
    from comfyui_distributed_tpu.graph.nodes_builtin import rewrite_prompt_ids
    from comfyui_distributed_tpu.models.registry import PRESETS

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("golden_margins needs the chip", file=sys.stderr)
        return 3
    cell = W.assemble(args.workload, rehearsal=args.rehearse)
    cfg = PRESETS[cell.preset].llm
    n_prompt, new_tokens = request_sizes(cell)
    temperature = float(cell.graph[cell.traffic["nodes"]["prompt"][0]]
                        ["inputs"]["temperature"])
    deciding = min(DECIDING, new_tokens)
    pipe = LLMPipeline(cfg, cfg.model.init(cfg, jax.random.key(0)))
    prefill = pipe.programs(n_prompt, new_tokens)[0]
    decode = pipe.decode_fn(n_prompt, new_tokens, tap_every=1)
    ids = rewrite_prompt_ids(args.prompt, n_prompt, cfg.vocab_size)
    first_logits, cache, *_ = prefill(jnp.asarray(ids, jnp.int32))

    @jax.jit
    def margins(seed, first_logits, taps, drawn):
        logits = jnp.concatenate([first_logits[None], taps[:deciding - 1]])
        key = jax.random.key(seed)

        def one(i, row):
            noisy = row + temperature * jax.random.gumbel(
                jax.random.fold_in(key, i), row.shape, jnp.float32)
            top = jax.lax.top_k(noisy, 2)
            return top[0][0] - top[0][1], top[1][0]

        gap, token = jax.vmap(one)(jnp.arange(deciding), logits)
        return gap, (token == drawn[:deciding]).all()

    found = []
    for seed in range(args.first, args.first + args.candidates):
        out, taps, _, finite = decode(first_logits, cache,
                                      jax.random.key(seed),
                                      jnp.asarray(temperature, jnp.float32))
        gap, same = margins(seed, first_logits, taps, out)
        gap = np.asarray(gap)
        found.append({"seed": seed, "smallest_margin": float(gap.min()),
                      "at_draw": int(gap.argmin()),
                      "recomputed_ids_equal": bool(same),
                      "finite": bool(finite)})
        print(f"[golden_margins] {found[-1]}", flush=True)
    ok = [f for f in found if f["recomputed_ids_equal"] and f["finite"]]
    ranked = sorted(ok, key=lambda f: -f["smallest_margin"])
    answer = {"workload": cell.name, "prompt": args.prompt,
              "candidates": len(found), "usable": len(ok),
              "best": ranked[:3],
              "median_smallest_margin": float(np.median(
                  [f["smallest_margin"] for f in ok])) if ok else None,
              "all": found}
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "golden_margins.json").write_text(json.dumps(answer, indent=1))
    print(json.dumps({k: v for k, v in answer.items() if k != "all"}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
