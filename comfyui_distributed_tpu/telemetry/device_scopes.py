"""Device scopes: the layer names of the chip's busy time.

``span()`` names what the HOST does; ``device_scope(layer)`` is its
device-side twin. It is ``jax.named_scope("cdt.<layer>")``: while a
program is TRACED for compilation, every operation recorded inside the
block carries ``cdt.<layer>`` in its name stack, the compiler copies the
stack into the instruction's ``op_name``, and the TPU profiler writes it
into the trace as the operation's ``tf_op``. ``cdtbench/device_layers.py``
reads that back and sums the chip's seconds, operations and bytes per
layer. Same ``cdt.`` prefix as the mirrored host spans
(``spans.ANNOTATION_PREFIX``).

A scope is trace-time metadata: nothing runs at a call and no numerics,
shape or fusion input changes. A compiled program keeps the names it was
compiled with, so ``utils/compile_cache.py`` puts them into the persistent
cache's key: a trace shows the running code's scopes, never those of the
checkout that filled the cache. One set of layers for every model, at most 16
(and 16 there are: a seventeenth replaces one);
a name that is not registered is refused when the program is traced.
Scopes do not nest: the reader takes the innermost ``cdt.*`` component,
so an operation belongs under exactly one (``tests/test_device_scopes.py``
walks every served program's jaxpr and holds the matrix products to
that). A flax module's own path stays below the scope as detail.

To add a layer: a row here (name, what belongs to it), the ``with
device_scope(...)`` blocks where its operations are traced, a row in
``docs/telemetry.md`` "Device scopes" and PERF.md §3 naming the metric
that reads it, and — where a metric is wanted — a ``layer_metrics``
pair over ``cdtbench/device_layers.py``.
"""

from __future__ import annotations

import functools

from .spans import ANNOTATION_PREFIX

MAX_DEVICE_LAYERS = 16

# (layer, what belongs to it) — one line each
DEVICE_LAYERS: tuple[tuple[str, str], ...] = (
    ("resnet", "ResBlock: both convolutions, GroupNorms, time_proj, skip; "
               "Down/Upsample; the UNet's conv_in, skip concats, conv_out"),
    ("attn_proj", "to_q/k/v/out, proj_in/out, the DiT's *_qkv / *_proj "
                  "with qk-norm, rope, the joint concat and the residual"),
    ("attn_core", "the call ops/attention dispatches (Pallas or XLA) with "
                  "its pads, transposes and casts, self and cross alike"),
    ("ffn", "GEGLU, the DiT's *_mlp_up / *_mlp_down with the gated residual"),
    ("norm_mod", "LayerNorms, adaLN modulation, time / ADM / pooled "
                 "embedders, the DiT's patch, position and output embedders"),
    ("sampler", "the step's own arithmetic, sigma tables, CFG combine, "
                "noise draws, the segment's carry and progress tap"),
    ("vae_decode", "the VAE decoder and the clip to [0, 1]"),
    ("llm_attn", "a language model's attention: projections, short "
                 "convolutions, rope, cache write and read, the KDA / MLA / "
                 "GDLA core, the output projection and its residual add"),
    ("llm_ssm", "a state-space mixer whole: in_proj, the causal depthwise "
                "convolution, x_proj / dt_proj and their three norms, the "
                "selective scan, the gate, out_proj and its residual add"),
    ("llm_router", "router logits, selection, weights, the slot counters"),
    ("llm_experts", "the held routed experts, all three forms (dense-masked, "
                    "grouped, per token) and their combine; the identity "
                    "experts' mix and an expert branch's join"),
    ("llm_shared_ffn", "the shared expert and the dense layers' FFN, with "
                       "the sum that joins them to the residual stream"),
    ("llm_mix", "the multi-stream residual: Sinkhorn rounds, gates, the mix "
                "and the streams' merge"),
    ("llm_norm", "a language model's sublayer norms: the RMS norm of the "
                 "residual stream ahead of attention and of the FFN"),
    ("llm_head", "both ends of the vocabulary: embedding lookup, final norm, "
                 "logits"),
    ("llm_sample", "Gumbel noise / threefry, argmax, the token carry, taps, "
                   "the prefill scan's chunk bookkeeping"),
)

_NAMES = frozenset(name for name, _ in DEVICE_LAYERS)


def _registered(layer: str) -> str:
    if layer not in _NAMES:
        raise ValueError(
            f"device layer {layer!r} is not registered: add it to "
            "telemetry/device_scopes.py DEVICE_LAYERS (at most "
            f"{MAX_DEVICE_LAYERS}) or use one of {sorted(_NAMES)}")
    return ANNOTATION_PREFIX + layer


def device_scope(layer: str):
    """``jax.named_scope("cdt.<layer>")`` for a registered layer; raises
    ``ValueError`` for any other name, when the program is traced."""
    import jax          # the telemetry package imports without JAX

    return jax.named_scope(_registered(layer))


def device_scoped(layer: str):
    """Decorator: the whole function is traced under ``device_scope(layer)``
    (a dispatcher or a kernel wrapper that is one layer wherever it is
    called from). The function must not call another scoped one."""
    _registered(layer)           # an unregistered name fails at import

    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with device_scope(layer):
                return fn(*args, **kwargs)
        return scoped
    return wrap
