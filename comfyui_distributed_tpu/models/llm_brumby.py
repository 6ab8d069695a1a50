"""An eleventh prompt rewriter, for a brief that fills its context: POWER
RETENTION on every layer — a gated, normalised linear recurrence over the
degree-2 symmetric power of every key — in the block of a dense transformer
(per-head RMS norms on q and k, rope on the whole head, grouped K/V heads, a
SwiGLU, an untied head). No layer attends to a key: the cache is recurrent
leaves ONLY.

Layer ``l``: ``h ← h + Retention(RMSNorm(h))``, ``h ← h + SwiGLU(RMSNorm(h))``.
**Retention**: ``[q | k | v] = x W_in`` as ``num_attention_heads`` query heads
over ``num_key_value_heads`` K/V heads; q and k RMS-normed per head (one
weight vector for all heads) and turned by rope (half rotation over the whole
head, the angles from ``llm_trinity``'s float64 host table); a gate a K/V
head a token, ``log γ = logsigmoid(x W_γ + b_γ)``; with ``b_t`` the running
sum of ``log γ``, query head ``h`` of group ``g = h // J`` reads

    o_t = Σ_{s≤t} A[t,s] v_s / Σ_{s≤t} A[t,s],   A[t,s] = e^{b_t − b_s} (q_t·k_s)² / d

— every weight ``≥ 0``, a convex combination — which the program carries as
the recurrence ``S_t = γ_t S_{t−1} + φ(k_t) v_tᵀ``, ``z_t = γ_t z_{t−1} +
φ(k_t)``, ``o_t = φ(q_t)ᵀS_t / φ(q_t)ᵀz_t`` with ``φ(a)·φ(b) = (a·b)²``
(``ops/power_retention.py``: both forms, what is held and why); no output
gate, no output norm; ``W_o``.

The cache is TWO recurrent leaves a layer and nothing else — ``state`` ``[kv
heads, head_dim, D]`` and ``norm`` ``[kv heads, head_dim, head_dim]``, float32
(the running log-gate is folded in): no row, no ring, no index, and
``max_len`` sizes nothing — :func:`empty_cache` answers the same leaves for
1 k and 32 k positions. :func:`prefill_chunk` is the continuation
``llm_prefill`` scans (``llm_model.chunked_prefill``): its contract for a
recurrent leaf — a padded chunk hands back the state as token ``n_valid − 1``
left it — binds the WHOLE cache here, under a gate that is data (a padded
row's gate counts as 1 and its key as 0). One plain named scope below
``cdt.llm_attn``: ``llm_retention`` (the gate's sums, ``φ``, the products
inside a block, the state's read and update, the quotient). Conventions are
``llm_hybrid.py``'s: weights held in ``dtype``, products on ``dtype``
operands accumulated in float32; residual stream, norms, rope, gates, the
states and logits float32; one sequence, no batch axis.
``models/llm_brumby_reference.py`` is the plain float32 statement — the
quadratic form, with no ``φ`` and no state.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..ops import power_retention
from ..telemetry.device_scopes import device_scope
from .llm_hybrid import (_const, _dot, _embed, _is_leaf, _normal, _pre_norm,
                         _swiglu, count_params, init_tree, logits_of,
                         rms_norm)
from .llm_jamba import _no_held
from .llm_model import LLMModel, chunked_prefill
from .llm_trinity import _rope, _rope_rows, rope_table
from .llm_zaya import _away


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    """Field names are the published ``config.json``'s; ``num_hidden_layers``
    is the depth kept."""
    hidden_size: int = 5120
    num_hidden_layers: int = 6
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 17408
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    vocab_size: int = 151936
    dtype: str = "bfloat16"
    # the schedule of the chunked prefill; sizes of the program, not options
    # of a request: the chunk, and the rows a block of the retention walk
    prefill_chunk_tokens: int = 4096
    retention_block: int = 256

    @classmethod
    def brumby_stage(cls) -> "BrumbyConfig":
        """Brumby-14B-Base at its published widths: a six-layer pipeline
        stage of the 40 layers, all 40 / 8 heads, the whole SwiGLU and the
        whole vocabulary at both ends."""
        return cls()

    @classmethod
    def tiny(cls, **kw) -> "BrumbyConfig":
        """The CPU tests' size, float32: every mechanism, small widths, 3
        query heads a K/V head, chunks and blocks a test prompt spans
        several of."""
        base = dict(
            hidden_size=32, num_hidden_layers=3, num_attention_heads=6,
            num_key_value_heads=2, head_dim=8, intermediate_size=48,
            max_position_embeddings=128, vocab_size=64, dtype="float32",
            prefill_chunk_tokens=16, retention_block=8)
        return cls(**{**base, **kw})

    @property
    def model(self) -> LLMModel:
        return MODEL

    @property
    def state_width(self) -> int:
        """``D`` as held (``power_retention.width``)."""
        return power_retention.width(self.head_dim)

    moe_layers = ()                   # no expert layer: nothing is routed
    stream_mixes_per_token = 0        # one residual stream, nothing mixed
    min_prompt_tokens = 1

    def attended_keys(self, prompt_tokens: int, new_tokens: int) -> dict:
        """No layer attends to a key. What a request's retention layers did
        is POSITIONS FOLDED into their states, by phase: tokens × layers (the
        heads are the reader's) — linear in the tokens where every other
        model's count is pairs."""
        n = self.num_hidden_layers
        return {("retention", "prefill"): n * prompt_tokens,
                ("retention", "decode"): n * new_tokens}


# --- weights ---------------------------------------------------------------


def _shapes(cfg: BrumbyConfig) -> dict:
    """Every drawn leaf as ``(shape, dtype name, init)``."""
    D, wd, F = cfg.hidden_size, cfg.dtype, cfg.intermediate_size
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    one = _const(1.0)
    layer = {
        "norm1": ((D,), "float32", one),
        "norm2": ((D,), "float32", one),
        "attn": {
            # [q (H·d) | k (G·d) | v (G·d)]: W_q, W_k, W_v side by side
            "w_in": ((D, (H + 2 * G) * d), wd, _normal()),
            # drawn AWAY from 1: leaving the head norms out moves the logits
            "q_norm": ((d,), "float32", _away(0.1, 1.0)),
            "k_norm": ((d,), "float32", _away(0.1, 1.0)),
            # γ = σ(x W_γ + b_γ) between ~0.9 and ~0.999: a state that
            # remembers tens to thousands of tokens (at b_γ 0 it would
            # remember two, and no test would see the state)
            "w_gate": ((D, G), wd, _normal(0.5 / math.sqrt(D))),
            "b_gate": ((G,), "float32", _away(1.0, 4.0)),
            "w_o": ((H * d, D), wd, _normal())},
        "ffn": {"w_gu": ((D, 2 * F), wd, _normal()),
                "w_down": ((F, D), wd, _normal())}}
    # untied ends, drawn as ``llm_sala`` draws its own: h₀ and the logits of
    # unit scale (no multiplier at either end here)
    return {"embed": ((cfg.vocab_size, D), wd, _normal(1.0)),
            "head": ((cfg.vocab_size, D), wd, _normal(1.0 / math.sqrt(D))),
            "final_norm": ((D,), "float32", one),
            "layers": [layer] * cfg.num_hidden_layers}


def init_brumby(cfg: BrumbyConfig, key, abstract: bool = False):
    """The drawn weights (an ``_away`` leaf a normal of its std moved to its
    mean, ``llm_zaya``'s way) and, beside them, the rope table
    (``llm_trinity.rope_table``: a leaf, not a literal of the programs)."""
    specs = _shapes(cfg)
    tree = init_tree(jax.tree_util.tree_map(
        lambda s: (s[0], s[1], _normal(s[2][2])) if s[2][0] == "about" else s,
        specs, is_leaf=_is_leaf), key, abstract)
    if not abstract:
        tree = jax.tree_util.tree_map(
            lambda s, leaf: leaf + s[2][1] if s[2][0] == "about" else leaf,
            specs, tree, is_leaf=_is_leaf)
    rows = (cfg.max_position_embeddings, cfg.head_dim // 2)
    tree["rope"] = {k: jax.ShapeDtypeStruct(rows, jnp.float32)
                    for k in ("cos", "sin")} if abstract else rope_table(cfg)
    return tree


def param_count(cfg: BrumbyConfig) -> int:
    return count_params(_shapes(cfg))


# --- pieces shared by prefill and decode -----------------------------------


def _retention_in(cfg: BrumbyConfig, p, x, rope):
    """From the normed rows ``x`` [T,D]: q [T,H,d] and k [T,G,d] (normed per
    head, roped, divided by ``d^¼``: the function's ``1/√d`` on the pair), v
    [T,G,d] and the log-gate [T,G] ``≤ 0``."""
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    T, eps, dtype = x.shape[0], cfg.rms_norm_eps, jnp.dtype(cfg.dtype)
    y = _dot(x, p["w_in"], dtype)
    q = rms_norm(y[:, :H * d].reshape(T, H, d), p["q_norm"], eps)
    k = rms_norm(y[:, H * d:(H + G) * d].reshape(T, G, d), p["k_norm"], eps)
    v = y[:, (H + G) * d:].reshape(T, G, d)
    with jax.named_scope("llm_retention"):
        log_g = jax.nn.log_sigmoid(_dot(x, p["w_gate"], dtype) + p["b_gate"])
    scale = d ** -0.25
    return _rope(q, *rope) * scale, _rope(k, *rope) * scale, v, log_g


def _ffn(cfg: BrumbyConfig, layer, h):
    x = _pre_norm(h, layer["norm2"], cfg.rms_norm_eps)
    with device_scope("llm_shared_ffn"):
        return h + _swiglu(x, layer["ffn"], jnp.dtype(cfg.dtype))


# --- prefill ---------------------------------------------------------------


def empty_cache(cfg: BrumbyConfig, max_len: int) -> dict:
    """Per layer a state and its normaliser — zeros: before the first token
    nothing is remembered. ``max_len`` sizes nothing."""
    del max_len
    G, d, n = cfg.num_key_value_heads, cfg.head_dim, cfg.num_hidden_layers
    # a leaf a layer: a stacked leaf would be copied whole a token
    return {"state": [jnp.zeros((G, d, cfg.state_width), jnp.float32)
                      for _ in range(n)],
            "norm": [jnp.zeros((G, d, d), jnp.float32) for _ in range(n)]}


def cache_kinds(cfg: BrumbyConfig, cache: dict) -> dict:
    return {"state": [cache["state"], cache["norm"]]}


def prefill_chunk(cfg: BrumbyConfig, params, cache: dict, ids, start, n_valid,
                  all_logits: bool = False, kernel: str | None = None):
    """``ids`` [C] at positions ``start .. start+C−1``, of which the first
    ``n_valid`` are the prompt's (the rest pad its last chunk: the states
    stand where token ``n_valid − 1`` left them). Continues from ``cache``.
    Answers ``(logits, cache, held, rows)`` as ``llm_kimi.prefill_chunk``:
    ``held`` and ``rows`` are empty (no expert layer). ``kernel`` names the
    form of the retention walk (``pallas``, ``interpret``, ``lax``; None: the
    platform's)."""
    dtype, C = jnp.dtype(cfg.dtype), ids.shape[0]
    with device_scope("llm_attn"):
        rope = _rope_rows(params, start, C)
    cache = {k: list(v) for k, v in cache.items()}
    h = _embed(params, ids)
    for i, layer in enumerate(params["layers"]):
        x = _pre_norm(h, layer["norm1"], cfg.rms_norm_eps)
        p = layer["attn"]
        with device_scope("llm_attn"):
            q, k, v, log_g = _retention_in(cfg, p, x, rope)
            with jax.named_scope("llm_retention"):
                o, cache["state"][i], cache["norm"][i] = \
                    power_retention.retention_chunk(
                        cache["state"][i], cache["norm"][i], q, k, v, log_g,
                        n_valid, dtype, cfg.retention_block, kernel=kernel)
            h = h + _dot(o.reshape(C, -1), p["w_o"], dtype)
        h = _ffn(cfg, layer, h)
    with device_scope("llm_head"):
        last = h if all_logits else h[n_valid - 1]
    return logits_of(cfg, params, last), cache, _no_held(), _no_held()


def prefill(cfg: BrumbyConfig, params, ids, max_len: int,
            all_logits: bool = False, chunk: int | None = None,
            kernel: str | None = None):
    """The whole prompt ``ids`` [T], walked in chunks through the cache;
    answers as ``llm_hybrid.prefill``: ``(logits, cache, held)``."""
    return chunked_prefill(MODEL, cfg, params, ids, max_len, all_logits,
                           chunk, kernel=kernel)[:3]


# --- decode ----------------------------------------------------------------


def decode_step(cfg: BrumbyConfig, params, cache: dict, token, pos):
    """One token ``token`` (scalar id) at position ``pos``: gate, ``φ(k)``,
    every state read and written once, the SwiGLU, the head; answers as
    ``llm_hybrid.decode_step`` (``held`` empty)."""
    dtype = jnp.dtype(cfg.dtype)
    with device_scope("llm_attn"):
        rope = _rope_rows(params, pos, 1)
    cache = {k: list(v) for k, v in cache.items()}
    h = _embed(params, token)
    for i, layer in enumerate(params["layers"]):
        x = _pre_norm(h, layer["norm1"], cfg.rms_norm_eps)
        p = layer["attn"]
        with device_scope("llm_attn"):
            q, k, v, log_g = _retention_in(cfg, p, x[None], rope)
            with jax.named_scope("llm_retention"):
                cache["state"][i], cache["norm"][i], o = \
                    power_retention.retention_step(
                        cache["state"][i], cache["norm"][i], q[0], k[0],
                        v[0], log_g[0])
            h = h + _dot(o.reshape(-1), p["w_o"], dtype)
        h = _ffn(cfg, layer, h)
    return logits_of(cfg, params, h), cache, _no_held()


MODEL = LLMModel(init_brumby, prefill, decode_step, empty_cache, cache_kinds,
                 prefill_chunk)
