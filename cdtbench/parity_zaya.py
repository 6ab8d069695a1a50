"""The builder's parity check of a ``zaya`` cell, on the chip:

    python -m cdtbench.parity_zaya --workload <cell> [--seeds 1,2] [--degrade a,b]

builds the cell's language model as the registry does (its preset, the
registry's seed), runs the SAME bound ``llm_prefill`` ``serve`` runs for the
cell's graph (the prompt walked in chunks through the K/V rows and the three
convolution tails, the last chunk padded) and an ``llm_decode`` of the same
steps, and holds what they produced to the float32 reference
(``cdtbench/reference/llm_zaya_reference.py``, a copy of the repo's): the
reference is teacher-forced on the ids the program drew, and the logits are
compared at the last prompt position and at the tapped decode steps. Logits,
not ids: with random weights the largest logit changes on rounding.

**How the reference is walked** is ``parity_trinity.py``'s way (its ``main``
and ``run_once`` run with this module's reference, arms, walk and tail in
place of its own; its ``kv_fp8`` and ``weights_fp8`` arms are used as they
are): per seed the prompt is
walked ONCE, layer by layer, ``parity_trinity.REFERENCE_BLOCK`` query rows at a time
through ``layer_rows``, and each layer's float32 keys and values of the
prompt rows are kept on the host — and, because this model's keys reach back
two rows through its convolutions, the two rows of the stream that ENTER each
layer ahead of the last prompt position (:func:`prompt_walk`). Every run of
that seed then evaluates only the rows it compares (the last prompt position
and the drawn tokens; the router's state is a row's own, from layer to
layer) against those keys and values plus their own (:func:`tail_logits`).
``tests/test_llm_zaya.py`` holds the walk equal to ``reference.forward``.

The tolerances, each with its reason, are data:
``reference/<config>.parity.json`` (each seed is held to them alone).
``--degrade`` (one arm or several, comma-separated, in ONE process so that
they share the prompt walk) runs the program below what the configuration
states (the reference stays as it is); those runs must FAIL on every seed.
Three arms lower a precision — ``kv_fp8``, ``stream_bf16`` (the residual
stream rounded to bfloat16 after every sublayer's merge) and ``weights_fp8``
(``parity_trinity``'s) — and two leave out mathematics: ``no_eda`` (the
router state of the layer before is not added) and ``no_temp`` (every key
temperature 1). ``--compile-only`` compiles both programs for a described
v5e instead (no chip needed, nothing runs) and prints their memory. Not part
of a measured run.
"""

from __future__ import annotations

import contextlib
import importlib.util
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from cdtbench import parity_trinity as PT  # noqa: E402
from cdtbench.parity_trinity import (  # noqa: E402  its arms, as they are
    lowered as trinity_lowered, lowered_weights as trinity_lowered_weights)

HERE = Path(__file__).resolve().parent
LEFT_OUT = {"no_eda": ("router", "eda", 0.0),
            "no_temp": ("attn", "log_temp", 0.0)}
DEGRADE = ("none",) + PT.LOWER + tuple(LEFT_OUT)


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "cdtbench_llm_zaya_reference",
        HERE / "reference" / "llm_zaya_reference.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def lowered(cfg, arm: str):
    """The context in which ``arm``'s programs are traced."""
    import jax

    from comfyui_distributed_tpu.models import llm_zaya as M

    if arm == "stream_bf16":
        merge = M._residual
        return PT._patched(M, _residual=lambda *a: jax.lax.reduce_precision(
            merge(*a), exponent_bits=8, mantissa_bits=7))
    if arm == "kv_fp8":
        return trinity_lowered(cfg, arm)
    return contextlib.nullcontext()


def lowered_weights(params, arm: str):
    """``params`` as an arm holds them: a left-out arm's one leaf at the
    value that takes its mathematics out, in every layer."""
    import jax.numpy as jnp

    if arm not in LEFT_OUT:
        return trinity_lowered_weights(params, arm)
    part, leaf, value = LEFT_OUT[arm]
    return {**params, "layers": [
        {**layer, part: {**layer[part], leaf: jnp.full_like(
            layer[part][leaf], value)}} for layer in params["layers"]]}


def prompt_walk(reference, cfg, params, prompt_ids, block: int) -> list:
    """Per layer ``(k, v, lead)``: the float32 keys and values ``[T,G,d]`` of
    the prompt's rows and the ``REACH`` rows of the stream that enter the
    layer ahead of the LAST prompt row (host arrays) — the reference's layers
    applied to all the prompt's rows, ``block`` query rows at a time."""
    import jax.numpy as jnp
    import numpy as np

    T, reach = len(prompt_ids), reference.REACH
    everyone = (0, cfg.router_experts)
    cos, sin = reference.rope_angles(cfg, T)
    x = params["embed"][jnp.asarray(prompt_ids, jnp.int32)].astype(
        jnp.float32)
    state, walk = None, []
    for i, layer in enumerate(params["layers"]):
        k, v = reference.keys_values(cfg, layer, x, cos, sin)
        walk.append((np.asarray(k), np.asarray(v),
                     np.asarray(x[T - 1 - reach:T - 1])))
        if i + 1 == len(params["layers"]):
            break
        parts = []
        for lo in range(0, T, block):
            n, lead = min(block, T - lo), min(lo, reach)
            out = reference.layer_rows(
                cfg, layer, x[lo - lead:lo + n], lead, lo + jnp.arange(n), k,
                v, cos[lo:lo + n], sin[lo:lo + n],
                None if state is None else state[lo:lo + n], everyone)
            parts.append((np.asarray(out[0]), np.asarray(out[1])))
        del x, k, v
        x = jnp.asarray(np.concatenate([p[0] for p in parts]))
        state = jnp.asarray(np.concatenate([p[1] for p in parts]))
    return walk


def tail_logits(reference, cfg, params, walk: list, ids, n_prompt: int,
                positions: list):
    """The reference's logits at ``positions`` (all ``≥ n_prompt − 1``) of
    the sequence ``ids`` whose first ``n_prompt`` are the walked prompt."""
    import jax.numpy as jnp
    import numpy as np

    first, reach = n_prompt - 1, reference.REACH
    rows = jnp.arange(first, len(ids))
    cos, sin = (a[first:] for a in reference.rope_angles(cfg, len(ids)))
    x = params["embed"][jnp.asarray(ids[first:], jnp.int32)].astype(
        jnp.float32)
    state = None
    for layer, (k_walk, v_walk, lead) in zip(params["layers"], walk):
        rows_in = jnp.concatenate([jnp.asarray(lead), x])
        k, v = reference.keys_values(cfg, layer, rows_in, cos, sin, reach)
        k, v = (jnp.concatenate([jnp.asarray(w[:first]), a])
                for w, a in ((k_walk, k), (v_walk, v)))
        x, state, _ = reference.layer_rows(
            cfg, layer, rows_in, reach, rows, k, v, cos, sin, state,
            (0, cfg.router_experts))
    at = jnp.asarray([p - first for p in positions])
    return np.asarray(reference.head_forward(
        cfg, params["final_norm"], params["embed"], x[at]))


def main(argv=None) -> int:
    """``parity_trinity``'s command line and loop — the cell's sizes, the
    programs, one walk a seed shared by the arms, the verdicts and the
    ``parity.<arm>.json`` files — over THIS module's reference, arms, walk
    and tail."""
    with PT._patched(PT, DEGRADE=DEGRADE, load_reference=load_reference,
                     lowered=lowered, lowered_weights=lowered_weights,
                     prompt_walk=prompt_walk, tail_logits=tail_logits):
        return PT.main(argv)


if __name__ == "__main__":
    sys.exit(main())
