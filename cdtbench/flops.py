"""Peaks of the chips, and the operations one denoise step needs.

The peaks table is keyed by ``device_kind`` as JAX reports it; a device
that is not in it is an error, not a default. The operation count walks
the jaxpr of ONE model call at the cell's shapes (the walk copied from
``comfyui_distributed_tpu/utils/flops.py``, PR 23): matrix multiplications
and convolutions, algorithmic operations only — traced on the CPU, where
attention takes the plain path, so a kernel's recomputation is not counted.
The model call is the program's own, so the walk is the builder's tool and
not part of a run: its result is pinned as data in the configuration's file
(``step_flops``), and a run reads only that, so ``denoise_mfu_pct``'s
numerator cannot move with the program.
"""

from __future__ import annotations

import math

# device_kind substring -> (bf16 FLOP/s, HBM bytes/s, HBM bytes), one chip.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 819 GB/s, 16 GB.
PEAKS = {
    "TPU v5 lite": (197e12, 819e9, 16e9),
    "TPU v5e": (197e12, 819e9, 16e9),
}


def peak_flops(device_kind: str) -> float:
    for kind, (flops, _, _) in PEAKS.items():
        if kind.lower() in device_kind.lower():
            return flops
    raise ValueError(f"no peak on record for device kind {device_kind!r}: "
                     "add it to cdtbench/flops.py PEAKS with its source")


def _dot_flops(eqn) -> float:
    a, b = eqn.invars[0].aval, eqn.invars[1].aval
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    contract = math.prod(a.shape[i] for i in lc) if lc else 1
    batch = math.prod(a.shape[i] for i in lb) if lb else 1
    m = math.prod(a.shape[i] for i in range(len(a.shape))
                  if i not in lc and i not in lb)
    n = math.prod(b.shape[i] for i in range(len(b.shape))
                  if i not in rc and i not in rb)
    return 2.0 * batch * m * n * contract


def _conv_flops(eqn) -> float:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    out = eqn.outvars[0].aval
    dn = eqn.params["dimension_numbers"]
    groups = (eqn.params.get("feature_group_count", 1)
              * eqn.params.get("batch_group_count", 1))
    k_spatial = math.prod(rhs.shape[i] for i in dn.rhs_spec[2:])
    c_in = lhs.shape[dn.lhs_spec[1]]
    return 2.0 * out.size * k_spatial * c_in / max(groups, 1)


def jaxpr_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += _dot_flops(eqn)
        elif name == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif name == "scan":
            total += eqn.params["length"] * jaxpr_flops(
                eqn.params["jaxpr"].jaxpr)
        elif name == "pallas_call":
            gm = eqn.params.get("grid_mapping")
            grid = math.prod(gm.grid) if gm is not None and gm.grid else 1
            sub = eqn.params.get("jaxpr")
            if sub is not None:
                total += grid * jaxpr_flops(
                    sub.jaxpr if hasattr(sub, "jaxpr") else sub)
        elif name == "while":
            total += jaxpr_flops(eqn.params["body_jaxpr"].jaxpr)
        elif name == "cond":
            total += max((jaxpr_flops(b.jaxpr)
                          for b in eqn.params["branches"]), default=0.0)
        else:
            for key in ("jaxpr", "call_jaxpr"):
                sub = eqn.params.get(key)
                if sub is not None:
                    total += jaxpr_flops(
                        sub.jaxpr if hasattr(sub, "jaxpr") else sub)
                    break
    return total


def step_flops(config: dict, height: int, width: int, batch: int) -> float:
    """Operations of one denoise step on one chip: one model call at the
    batch the step really runs (doubled under CFG), built by
    ``kinds/<kind>.py`` from the sizes in the configuration's own file.
    The builder's tool: a run reads the count PINNED in the configuration's
    file (``step_flops``), which ``tests/test_workload.py`` holds to this."""
    import importlib

    import jax

    down = 2 ** (len(config["vae"]["channel_mult"]) - 1)
    kind = importlib.import_module(f"cdtbench.kinds.{config['kind']}")
    fn, args = kind.step_call(config, height // down, width // down, batch)
    return jaxpr_flops(jax.make_jaxpr(fn)(*args).jaxpr)


def main(argv=None) -> int:
    """``JAX_PLATFORMS=cpu python -m cdtbench.flops --workload <cell>``
    prints the key and the count to pin in the configuration's file."""
    import argparse

    from cdtbench import workload as W

    parser = argparse.ArgumentParser(description=main.__doc__)
    parser.add_argument("--workload", required=True)
    cell = W.assemble(parser.parse_args(argv).workload)
    height, width = cell.image_hw
    print(f'"{cell.step_key}": '
          f"{step_flops(cell.config, height, width, cell.step_batch)!r}")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
