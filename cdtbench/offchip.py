"""Compile a cell's denoise program for a described v5e, off the chip.

    JAX_PLATFORMS=cpu python -m cdtbench.offchip --workload sdxl-base.solo30

Nothing runs: the TPU compiler is asked whether the program compiles at the
cell's real size, and ``memory_analysis()`` is set beside the resident
weights and the driver's floor (25% of one chip's memory). A compile that
passes is not a chip run, and no time comes from here.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cdtbench import workload as W  # noqa: E402

GIB = 2**30
FLOOR_SHARE = 0.25


def _abstract(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def build_program(cell: W.Cell, devices):
    """The cell's denoise program and its abstract arguments on a mesh of
    ``devices`` — the program ``serve`` runs for the cell's graph, built by
    ``kinds/<kind>.py`` of the configuration's ``kind``."""
    import importlib

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from comfyui_distributed_tpu.models.registry import PRESETS
    from comfyui_distributed_tpu.models.vae import AutoencoderKL
    from comfyui_distributed_tpu.parallel.mesh import build_mesh

    preset = PRESETS[cell.preset]
    mesh = build_mesh({"dp": len(devices)}, devices)
    rep = NamedSharding(mesh, P())
    vae = AutoencoderKL(preset.vae).init(jax.random.key(1), image_hw=(64, 64))
    key = jax.eval_shape(lambda: jax.random.key(0))
    common = {
        "ctx": jax.ShapeDtypeStruct(
            (1, preset.text.max_len, preset.text.output_dim), jnp.float32,
            sharding=rep),
        "key": jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep),
        "token": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)}
    kind = importlib.import_module(f"cdtbench.kinds.{preset.kind}")
    fn, args, what = kind.denoise_program(cell, preset, mesh, rep, vae,
                                          common)
    weights = _abstract(fn.weights, rep)
    weight_bytes = sum(
        a.size * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(weights))
    return fn.jitted, (weights, *args), weight_bytes, what


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--topology", default="v5e:2x2")
    parser.add_argument("--hbm-gib", type=float, default=16.0,
                        help="one chip's memory, for the floor")
    args = parser.parse_args(argv)

    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    # the one place the kernels ask where they are: steered here, in the
    # script, since jax.devices() still says cpu (as tests/test_chip_compile)
    from comfyui_distributed_tpu.ops import flash_attention

    flash_attention._platform = lambda: "tpu"
    cell = W.assemble(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    devices = list(topo.devices)[:cell.chips]
    t0 = time.monotonic()
    jitted, call_args, weight_bytes, what = build_program(cell, devices)
    lowered = jitted.lower(*call_args)
    compiled = lowered.compile()
    took = time.monotonic() - t0
    mem = compiled.memory_analysis()
    floor = FLOOR_SHARE * args.hbm_gib
    arguments = mem.argument_size_in_bytes / GIB
    temporaries = mem.temp_size_in_bytes / GIB
    outputs = mem.output_size_in_bytes / GIB
    total = arguments + temporaries + outputs
    print(json.dumps({
        "workload": cell.name, "program": what, "topology": args.topology,
        "devices": len(devices), "compile_here_s": round(took, 1),
        "weights_gib_per_device": round(weight_bytes / GIB, 3),
        "arguments_gib": round(arguments, 3),
        "temporaries_gib": round(temporaries, 3),
        "outputs_gib": round(outputs, 3),
        "program_total_gib": round(total, 3),
        "floor_gib": floor, "over_floor": total >= floor,
        "fits_chip": total <= args.hbm_gib,
        "note": "compiled off-chip for a described device; nothing ran"}))
    return 0 if total >= floor and total <= args.hbm_gib else 1


if __name__ == "__main__":
    sys.exit(main())
