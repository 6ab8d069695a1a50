"""The builder's parity check of a ``mimo`` cell, on the chip:

    python -m cdtbench.parity_mimo --workload <cell> [--seeds 1,2] [--degrade a,b]

builds the cell's language model as the registry does (its preset, the
registry's seed), runs the SAME bound ``llm_prefill`` ``serve`` runs for the
cell's graph (the prompt walked in chunks of 4096 through five 128-row rings
and two buffers) and an ``llm_decode`` of the same steps, and holds what they
produced to the float32 reference
(``cdtbench/reference/llm_mimo_reference.py``, a copy of the repo's): the
reference is teacher-forced on the ids the program drew, and the logits are
compared at the last prompt position and at the
tapped decode steps. Logits, not ids: with random weights the largest logit
changes on rounding.

**How the reference is walked** is ``parity_trinity.py``'s way, with its own
code: this model's reference has that one's signatures (``rope_angles`` holds
the two kinds' angles side by side), so ``parity_trinity``'s ``main``,
``run_once``, ``prompt_walk`` and ``tail_logits`` run over it as they are, with
this module's reference and arms in place of its own — per seed the prompt is
walked ONCE, layer by layer, ``parity_trinity.REFERENCE_BLOCK`` query rows at a
time through ``layer_rows``, every layer kind under its whole mask, and each
layer's float32 keys and values of the prompt rows are kept on the host; every
run of that seed then evaluates only the rows it compares against those keys
and values plus their own. ``tests/test_llm_mimo.py`` holds the walk equal to
``reference.forward``.

The tolerances, each with its reason, are data:
``reference/<config>.parity.json`` (each seed is held to them alone).
``--degrade`` (one arm or several, comma-separated, in ONE process so that
they share the prompt walk) runs the program below or beside what the
configuration states (the reference stays as it is); those runs must FAIL on
every seed. One arm lowers a precision — ``kv_fp8`` (the K/V rows rounded to
fp8 e4m3 wherever attention reads them) — and five change mathematics, one at
a time: ``no_sink`` (every sink at −1e30: nothing joins the denominator),
``window_256`` (a window layer's prefill band 256 keys wide, as far as ``[ring
; chunk]`` holds them), ``one_theta`` (the full layers' rope table on the
window layers too), ``no_value_scale`` (values times 1) and ``rope_all_192``
(every dimension of a head turned, the 32 angles repeated three times). All six
are built HERE, around the served code (the served model has no switch for
them). ``--compile-only`` compiles both programs for a described v5e instead
(no chip needed, nothing runs) and prints their memory. Not part of a measured
run.
"""

from __future__ import annotations

import contextlib
import importlib.util
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from cdtbench import parity_trinity as PT  # noqa: E402

HERE = Path(__file__).resolve().parent
ARMS = ("no_sink", "window_256", "one_theta", "no_value_scale", "kv_fp8",
        "rope_all_192")
DEGRADE = ("none",) + ARMS


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "cdtbench_llm_mimo_reference",
        HERE / "reference" / "llm_mimo_reference.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def lowered(cfg, arm: str):
    """The context in which ``arm``'s programs are traced (the weights' arms
    — ``no_sink``, ``one_theta`` — change no code: :func:`lowered_weights`)."""
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import llm_mimo as M
    from comfyui_distributed_tpu.ops import gqa_sink_attention as ops

    def fp8(x):      # not a cast there and back: the TPU compiler drops it
        return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)

    if arm == "kv_fp8":
        def rounded(fn):
            return lambda q, k, v, *a, **kw: fn(q, fp8(k), fp8(v), *a, **kw)
        return PT._patched(ops, causal_chunk=rounded(ops.causal_chunk),
                           band_chunk=rounded(ops.band_chunk),
                           step=rounded(ops.step))
    if arm == "window_256":
        band = ops.band_chunk

        def wide(q, k, v, lowest, window, *a, **kw):
            ahead = ((0, 0), (window, 0), (0, 0))   # rows no query may see
            return band(q, jnp.pad(k, ahead), jnp.pad(v, ahead),
                        lowest + window, 2 * window, *a, **kw)
        return PT._patched(ops, band_chunk=wide)
    if arm == "no_value_scale":
        return PT._patched(M, _scaled=lambda cfg, v: v)
    if arm == "rope_all_192":
        def whole(cfg, x, cos, sin):
            times = x.shape[-1] // (2 * cos.shape[-1])
            cos, sin = (jnp.tile(a, (1, times))[:, None] for a in (cos, sin))
            x1, x2 = jnp.split(x, 2, axis=-1)
            return jnp.concatenate([x1 * cos - x2 * sin,
                                    x2 * cos + x1 * sin], -1)
        return PT._patched(M, _rope=whole)
    return contextlib.nullcontext()


def lowered_weights(params, arm: str):
    """``params`` as an arm holds them: every sink where ``exp`` of it is 0,
    or the full layers' rope table in the window layers' place."""
    import jax.numpy as jnp

    if arm == "no_sink":
        return {**params, "layers": [
            {**layer, "attn": {**layer["attn"], "sink": jnp.full_like(
                layer["attn"]["sink"], -1e30)}}
            if "sink" in layer["attn"] else layer
            for layer in params["layers"]]}
    if arm == "one_theta":
        return {**params, "rope": {**params["rope"],
                                   "window": params["rope"]["full"]}}
    return params


def main(argv=None) -> int:
    """``parity_trinity``'s command line and loop — the cell's sizes, the
    programs, one walk a seed shared by the arms, the verdicts and the
    ``parity.<arm>.json`` files — over THIS module's reference and arms."""
    with PT._patched(PT, DEGRADE=DEGRADE, load_reference=load_reference,
                     lowered=lowered, lowered_weights=lowered_weights):
        return PT.main(argv)


if __name__ == "__main__":
    sys.exit(main())
