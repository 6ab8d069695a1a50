"""Analytic FLOP counting by walking a jaxpr.

XLA's ``compiled.cost_analysis()`` on TPU reports near-zero FLOPs for
convolutions that lower into custom fusions, which makes benchmark MFU
numbers meaningless (observed: SDXL counted at ~10× under its analytic
FLOPs). This walks the traced jaxpr instead and counts the two op
families that carry essentially all diffusion-model FLOPs:

- ``dot_general``: 2 · batch · M · N · K
- ``conv_general_dilated``: 2 · out_elements · K_spatial · C_in / groups

Control-flow bodies (scan/while/cond/pjit/remat/custom_jvp…) are
recursed into, with scan bodies multiplied by their trip count — so a
30-step sampler scan counts 30×. Elementwise/normalization work is
ignored (<1% for these models). Counts are *algorithmic* FLOPs — what
MFU conventionally divides by — not whatever XLA rewrites them into.
"""

from __future__ import annotations

import math

import jax
import numpy as np


def _dot_flops(eqn) -> float:
    a, b = eqn.invars[0].aval, eqn.invars[1].aval
    dims = eqn.params["dimension_numbers"]
    (lc, rc), (lb, rb) = dims
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


def _jaxpr_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += _dot_flops(eqn)
        elif name == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif name == "scan":
            total += eqn.params["length"] * _jaxpr_flops(
                eqn.params["jaxpr"].jaxpr)
        elif name == "pallas_call":
            # the kernel body runs once PER GRID STEP — counting it once
            # undercounts flash attention ~1000× (bq·bk block vs full N²)
            # A kernel that states its algorithmic cost is taken at its
            # word: its body may issue more than the algorithm needs
            # (the packed flash kernel's stacked D=64 heads) or loop
            # without a static trip count.
            cost = eqn.params.get("cost_estimate")
            if cost is not None:
                total += cost.flops
                continue
            gm = eqn.params.get("grid_mapping")
            grid = math.prod(gm.grid) if gm is not None and gm.grid else 1
            sub = eqn.params.get("jaxpr")
            if sub is not None:
                total += grid * _jaxpr_flops(
                    sub.jaxpr if hasattr(sub, "jaxpr") else sub)
        elif name == "while":
            # trip count unknowable statically; count one iteration
            total += _jaxpr_flops(eqn.params["body_jaxpr"].jaxpr)
        elif name == "cond":
            branches = [_jaxpr_flops(b.jaxpr)
                        for b in eqn.params["branches"]]
            total += max(branches) if branches else 0.0
        else:
            for key in ("jaxpr", "call_jaxpr"):
                sub = eqn.params.get(key)
                if sub is not None:
                    total += _jaxpr_flops(
                        sub.jaxpr if hasattr(sub, "jaxpr") else sub)
                    break
    return total


def estimate_flops(fn, *args, **kwargs) -> float:
    """Analytic matmul+conv FLOPs of one call of ``fn(*args)``.

    Tracing is abstract (no execution, no device); args may be concrete
    arrays or ``jax.ShapeDtypeStruct``s."""
    closed = jax.make_jaxpr(fn, **kwargs)(*args)
    return _jaxpr_flops(closed.jaxpr)


def shape_args(*specs) -> tuple:
    """Convenience: (shape, dtype) pairs → ShapeDtypeStructs."""
    return tuple(jax.ShapeDtypeStruct(s, np.dtype(d)) for s, d in specs)
