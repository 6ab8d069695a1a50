#!/usr/bin/env python
"""Which large copies the compiler left in a language model's two programs.

Compiles ``llm_prefill`` and ``llm_decode`` of a preset off-chip, for a
described v5e, from abstract weights at the sizes given (the recipe of
``cdtbench/parity_kimi.compile_only``: 2–7 s a program, nothing runs), and
lists every ``copy`` whose result is at least ``--min-mib`` by the
computation that holds it. A copy in a while body (the token loop of
``llm_decode``, the chunk scan of ``llm_prefill``) is paid at every trip;
one in the entry computation once a request.

    python scripts/loop_copies.py longcat-flash-omni 16384 256
    python scripts/loop_copies.py kimi-k2.6 32768 128 [--min-mib 1]
        [--topology v5e:2x2] [--programs llm_decode]

No program reads this script's output (PERF.md §6, PR 45).
"""

from __future__ import annotations

import argparse
import math
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_COMPUTATION = re.compile(r"^(?:ENTRY )?(%?[\w.\-]+) \(.*\) -> .* \{$")
_COPY = re.compile(r"= (\w+)\[([\d,]*)\](\{[^ ]*\})? copy\(")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "bf16": 2, "f16": 2,
          "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}


def computations(text: str) -> dict:
    """``{name: [instruction lines]}`` of a compiled module's text."""
    out, name = {}, None
    for line in map(str.strip, text.splitlines()):
        head = _COMPUTATION.match(line)
        if name is None and head:
            name = head.group(1)
            out[name] = []
        elif line == "}":
            name = None
        elif name is not None:
            out[name].append(line)
    return out


def large_copies(text: str, min_bytes: int = 2 ** 20) -> list:
    """``[(computation, in a while body, bytes, result shape with its
    layout)]`` of every ``copy`` instruction of a compiled module's text
    whose result holds at least ``min_bytes``, in the text's order."""
    bodies = set(re.findall(r" while\(.*body=(%?[\w.\-]+)", text))
    found = []
    for name, lines in computations(text).items():
        for dtype, dims, layout in (copy.groups() for copy in
                                    map(_COPY.search, lines) if copy):
            size = _BYTES[dtype] * math.prod(int(d) for d in dims.split(",")
                                             if d)
            if size >= min_bytes:
                found.append((name, name in bodies, size,
                              f"{dtype}[{dims}]{layout or ''}"))
    return found


def compiled_programs(cfg, prompt_tokens: int, new_tokens: int, chip,
                      names=("llm_prefill", "llm_decode")) -> dict:
    """``{name: compiled}`` of the two programs for the described ``chip``
    (a ``SingleDeviceSharding``), weights and inputs abstract."""
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.diffusion.pipeline_llm import LLMPipeline

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    weights = place(cfg.model.init(cfg, None, abstract=True))
    prefill, decode = LLMPipeline(cfg, weights).programs(prompt_tokens,
                                                         new_tokens)
    ids = jax.ShapeDtypeStruct((prompt_tokens,), jnp.int32, sharding=chip)
    logits, cache, *_ = jax.eval_shape(prefill.jitted, weights, ids)
    arguments = {
        "llm_prefill": (prefill.jitted, (weights, ids)),
        "llm_decode": (decode.jitted, (
            weights, place(logits), place(cache),
            place(jax.eval_shape(lambda: jax.random.key(0))),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=chip)))}
    return {name: arguments[name][0].lower(*arguments[name][1]).compile()
            for name in names}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("preset")
    parser.add_argument("prompt_tokens", type=int)
    parser.add_argument("new_tokens", type=int)
    parser.add_argument("--min-mib", type=float, default=1.0)
    parser.add_argument("--topology", default="v5e:2x2")
    parser.add_argument("--programs", default="llm_prefill,llm_decode")
    args = parser.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from comfyui_distributed_tpu.models.registry import PRESETS
    from comfyui_distributed_tpu.ops import flash_attention

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    # the dispatch reads the platform through this one function: the
    # described chip takes the Pallas kernels, as the real one will
    flash_attention._platform = lambda: "tpu"
    programs = compiled_programs(
        PRESETS[args.preset].llm, args.prompt_tokens, args.new_tokens,
        SingleDeviceSharding(topo.devices[0]), args.programs.split(","))
    for name, compiled in programs.items():
        copies = large_copies(compiled.as_text(),
                              int(args.min_mib * 2 ** 20))
        print(f"{name}: {len(copies)} copies of {args.min_mib} MiB or more, "
              f"{sum(size for *_, size, _ in copies) / 1e6:.2f} MB")
        for computation, looped, size, shape in copies:
            print(f"  {size / 1e6:9.2f} MB  {shape}  in {computation} "
                  f"({'while body' if looped else 'once'})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
