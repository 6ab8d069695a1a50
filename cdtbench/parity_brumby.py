"""The builder's parity check of a ``brumby`` cell, on the chip:

    python -m cdtbench.parity_brumby --workload <cell> [--seeds 1,2] [--degrade a,b]

builds the cell's language model as the registry does (its preset, the
registry's seed), runs the SAME bound ``llm_prefill`` ``serve`` runs for the
cell's graph (the prompt walked in chunks through the states, the last chunk
padded under a gate that is data) and an ``llm_decode`` of the same steps,
and holds what they produced to the float32 reference
(``cdtbench/reference/llm_brumby_reference.py``, a copy of the repo's): the
reference is teacher-forced on the ids the program drew, and the logits are
compared at the last prompt position and at the tapped decode steps. Logits,
not ids: with random weights the largest logit changes on rounding. The
program carries a RECURRENCE over ``φ(k)``; the reference evaluates the
QUADRATIC form — every query row against every key below it, no ``φ`` and no
state: two computations of one function.

**How the reference is walked** is ``parity_trinity.py``'s way (its ``main``
and ``run_once`` run with this module's reference, arms, walk and tail in
place of its own; its ``weights_fp8`` arm is used as it is): per seed the
prompt is walked ONCE, layer by layer, ``parity_trinity.REFERENCE_BLOCK``
query rows at a time through ``layer_rows``, and each layer's float32 keys,
values and RUNNING LOG-GATES of the prompt rows are kept on the host
(:func:`prompt_walk`). Every run of that seed then evaluates only the rows it
compares (the last prompt position and the drawn tokens) against those plus
their own (:func:`tail_logits`). ``tests/test_llm_brumby.py`` holds the walk
equal to ``reference.forward``.

The tolerances, each with its reason, are data:
``reference/<config>.parity.json`` (each seed is held to them alone).
``--degrade`` (one arm or several, comma-separated, in ONE process so that
they share the prompt walk) runs the program below what the configuration
states (the reference stays as it is); those runs must FAIL on every seed.
Three arms lower a precision — ``state_bf16`` (the carried ``S`` and ``Z``
rounded to bfloat16 wherever they are handed on: after every block of the
prefill's walk and after every decoded token), ``stream_bf16`` (the residual
stream rounded to bfloat16 after every sublayer's merge) and ``weights_fp8``
(``parity_trinity``'s) — and one leaves out mathematics: ``no_gate`` (γ ≡ 1:
nothing is ever forgotten). ``--compile-only`` compiles both programs for a
described v5e instead (no chip needed, nothing runs) and prints their memory.
Not part of a measured run.
"""

from __future__ import annotations

import contextlib
import importlib.util
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from cdtbench import parity_trinity as PT  # noqa: E402
from cdtbench.parity_trinity import (  # noqa: E402  its arm, as it is
    lowered_weights as trinity_lowered_weights)

HERE = Path(__file__).resolve().parent
LOWER = ("state_bf16", "stream_bf16", "weights_fp8")
# a left-out arm's leaves of every layer's "attn", at the value that takes
# its mathematics out: logsigmoid(0 · x + 1e4) = 0, γ = 1
LEFT_OUT = {"no_gate": {"w_gate": 0.0, "b_gate": 1e4}}
DEGRADE = ("none",) + LOWER + tuple(LEFT_OUT)


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "cdtbench_llm_brumby_reference",
        HERE / "reference" / "llm_brumby_reference.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def lowered(cfg, arm: str):
    """The context in which ``arm``'s programs are traced."""
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import llm_brumby as M
    from comfyui_distributed_tpu.ops import power_retention as ops

    def bf16(x):     # not a cast there and back: the TPU compiler drops it
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    if arm == "stream_bf16":
        ffn = M._ffn          # in: the stream after the retention's merge
        return PT._patched(M, _ffn=lambda c, layer, h: bf16(
            ffn(c, layer, bf16(h))))
    if arm == "state_bf16":
        chunk, step = ops.retention_chunk, ops.retention_step

        def chunk_rounded(S, Z, q, k, v, log_g, n_valid, dtype, block,
                          **kw):
            """The served walk a block at a call, the state rounded
            between the calls."""
            out = []
            for lo in range(0, q.shape[0], block):
                rows = slice(lo, lo + block)
                o, S, Z = chunk(S, Z, q[rows], k[rows], v[rows], log_g[rows],
                                jnp.clip(n_valid - lo, 0, block), dtype,
                                block, **kw)
                S, Z = bf16(S), bf16(Z)
                out.append(o)
            return jnp.concatenate(out), S, Z

        def step_rounded(*a):
            S, Z, o = step(*a)
            return bf16(S), bf16(Z), o
        return PT._patched(ops, retention_chunk=chunk_rounded,
                           retention_step=step_rounded)
    return contextlib.nullcontext()


def lowered_weights(params, arm: str):
    """``params`` as an arm holds them: a left-out arm's leaves at the values
    that take its mathematics out, in every layer."""
    import jax.numpy as jnp

    if arm not in LEFT_OUT:
        return trinity_lowered_weights(params, arm)
    return {**params, "layers": [
        {**layer, "attn": {**layer["attn"], **{
            leaf: jnp.full_like(layer["attn"][leaf], value)
            for leaf, value in LEFT_OUT[arm].items()}}}
        for layer in params["layers"]]}


def prompt_walk(reference, cfg, params, prompt_ids, block: int) -> list:
    """Per layer ``(k, v, b)``: the float32 keys and values ``[T,G,d]`` and
    the running log-gates ``[T,G]`` of the prompt's rows (host arrays) — the
    reference's layers applied to all the prompt's rows, ``block`` query rows
    at a time; the last layer's rows are not needed for them."""
    import jax.numpy as jnp
    import numpy as np

    T = len(prompt_ids)
    cos, sin = reference.rope_angles(cfg, T)
    x = reference.embed(cfg, params, jnp.asarray(prompt_ids, jnp.int32))
    walk = []
    for i, layer in enumerate(params["layers"]):
        k, v, log_g = reference.keys_values(cfg, layer, x, cos, sin)
        b = jnp.cumsum(log_g, axis=0)
        walk.append((np.asarray(k), np.asarray(v), np.asarray(b)))
        if i + 1 == len(params["layers"]):
            break
        # block by block to the host: two copies of the rows never share
        # the device with the layer's float32 weights
        parts = [np.asarray(reference.layer_rows(
            cfg, layer, x[lo:lo + block], jnp.arange(lo, min(lo + block, T)),
            k, v, b, cos[lo:lo + block], sin[lo:lo + block]))
            for lo in range(0, T, block)]
        del x, k, v, b
        x = jnp.asarray(np.concatenate(parts))
    return walk


def tail_logits(reference, cfg, params, walk: list, ids, n_prompt: int,
                positions: list):
    """The reference's logits at ``positions`` (all ``≥ n_prompt − 1``) of
    the sequence ``ids`` whose first ``n_prompt`` are the walked prompt: the
    rows from the last prompt position on through every layer, against the
    walked keys, values and running log-gates and their own (the running sum
    goes on from the row before the first of them)."""
    import jax.numpy as jnp
    import numpy as np

    first = n_prompt - 1
    rows = jnp.arange(first, len(ids))
    cos, sin = (a[first:] for a in reference.rope_angles(cfg, len(ids)))
    x = reference.embed(cfg, params, jnp.asarray(ids[first:], jnp.int32))
    for layer, (k_walk, v_walk, b_walk) in zip(params["layers"], walk):
        k, v, log_g = reference.keys_values(cfg, layer, x, cos, sin)
        before = jnp.asarray(b_walk[first - 1]) if first else 0.0
        k, v, b = (jnp.concatenate([jnp.asarray(w[:first]), a])
                   for w, a in ((k_walk, k), (v_walk, v),
                                (b_walk, before + jnp.cumsum(log_g, axis=0))))
        x = reference.layer_rows(cfg, layer, x, rows, k, v, b, cos, sin)
        del k, v, b
    at = jnp.asarray([p - first for p in positions])
    return np.asarray(reference.head_forward(
        cfg, params["final_norm"], params["head"], x[at]))


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
