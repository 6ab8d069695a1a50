#!/usr/bin/env python
"""Which large copies the compiler left in a language model's two programs.

Compiles ``llm_prefill`` and ``llm_decode`` of a preset off-chip, for a
described v5e, from abstract weights at the sizes given (the recipe of
``cdtbench/parity_kimi.compile_only``: 2–7 s a program, nothing runs), and
lists every instruction that only moves at least ``--min-mib`` by the
computation that holds it: each ``copy``, each ``slice`` that stands alone
or in a fusion of nothing but slices (PR 46: the two halves of a Mamba
layer's ``[u | z]`` product, cut apart for a Pallas operand) and each
``broadcast``. One in a while body (the token loop of ``llm_decode``, the
chunk and layer scans of ``llm_prefill``) is paid at every trip; one in the
entry computation once a request.

    python scripts/loop_copies.py longcat-flash-omni 16384 256
    python scripts/loop_copies.py kimi-k2.6 32768 128 [--min-mib 1]
        [--topology v5e:2x2] [--programs llm_decode]

No program reads this script's output (PERF.md §6, PRs 45 and 46);
``tests/test_chip_compile.py`` calls ``large_moves`` / ``large_copies``.
"""

from __future__ import annotations

import argparse
import math
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_COMPUTATION = re.compile(r"^(?:ENTRY )?(%?[\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(
    r"^(?:ROOT )?(%[\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\](\{[^ ]*\})?")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "bf16": 2, "f16": 2,
          "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
# what a fusion may hold beside its slices and still be nothing but a copy
_PLUMBING = {"parameter", "slice", "bitcast", "tuple"}


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


def _instructions(lines) -> list:
    """``[(name, result type, opcode, the rest of the line)]``."""
    return [m.groups() for m in map(_INSTRUCTION.match, lines) if m]


def _size(dtype: str, dims: str) -> int:
    return _BYTES[dtype] * math.prod(int(d) for d in dims.split(",") if d)


def large_moves(text: str, min_bytes: int = 2 ** 20) -> list:
    """``[(kind, computation, in a while body, bytes, result shape with its
    layout, source)]`` of every instruction of a compiled module's text that
    only MOVES at least ``min_bytes``, in the text's order: a ``copy``, a
    ``broadcast``, a ``slice`` that stands alone in its computation, on the
    core or as an asynchronous ``slice-start`` (one fused into the
    operation that reads it moves nothing), and each
    ``slice`` of a fusion that holds nothing else (``slice`` / ``bitcast``
    / ``tuple``: what XLA makes of a sliced operand of a Pallas call, which
    has to be an array of its own). ``source`` is, for a slice, the array it
    cuts and its bounds (``f32[4096,10240][0:4096],[5120:10240]``); the
    computation of a fusion's slice is the one that calls the fusion."""
    bodies = set(re.findall(r" while\(.*body=(%?[\w.\-]+)", text))
    parsed = {name: _instructions(lines)
              for name, lines in computations(text).items()}
    fused = set(re.findall(r" fusion\(.*calls=(%?[\w.\-]+)", text))
    found = []

    def note(kind, where, result, source=""):
        dtype, dims, layout = _SHAPE.search(result).groups()
        if _size(dtype, dims) >= min_bytes:
            found.append((kind, where, where in bodies, _size(dtype, dims),
                          f"{dtype}[{dims}]{layout or ''}", source))

    def cut(types, rest):
        operand = re.match(r"(%[\w.\-]+)", rest).group(1)
        dtype, dims, _ = _SHAPE.search(types[operand]).groups()
        bounds = re.search(r"slice=\{([^}]*)\}", rest).group(1)
        return f"{dtype}[{dims}]{bounds.replace(' ', '')}"

    for name, instructions in parsed.items():
        if name in fused:
            continue
        types = {n: t for n, t, _, _ in instructions}
        for _, result, opcode, rest in instructions:
            if opcode in ("copy", "broadcast"):
                note(opcode, name, result)
            elif opcode == "slice":
                note("slice", name, result, cut(types, rest))
            elif opcode == "slice-start":   # ((operand), result, context)
                note("slice", name, result.split("), ", 1)[1],
                     cut(types, rest))
            elif opcode == "fusion":
                body = parsed.get(re.search(r"calls=(%?[\w.\-]+)",
                                            rest).group(1), [])
                if {op for _, _, op, _ in body} <= _PLUMBING:
                    inner = {n: t for n, t, _, _ in body}
                    for _, result, op, rest in body:
                        if op == "slice":
                            note("slice", name, result, cut(inner, rest))
    return found


def large_copies(text: str, min_bytes: int = 2 ** 20) -> list:
    """``[(computation, in a while body, bytes, result shape with its
    layout)]`` of the ``copy`` instructions among :func:`large_moves`."""
    return [move[1:5] for move in large_moves(text, min_bytes)
            if move[0] == "copy"]


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
        moves = large_moves(compiled.as_text(), int(args.min_mib * 2 ** 20))
        for kind in ("copy", "slice", "broadcast"):
            rows = [move[1:] for move in moves if move[0] == kind]
            print(f"{name}: {len(rows)} {kind} of {args.min_mib} MiB or "
                  f"more, {sum(row[2] for row in rows) / 1e6:.2f} MB")
            for computation, looped, size, shape, source in rows:
                print(f"  {size / 1e6:9.2f} MB  {shape}  in {computation} "
                      f"({'while body' if looped else 'once'})"
                      + (f"  of {source}" if source else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
