"""A fourth prompt rewriter, whole on one chip, for the longest briefs:
Mamba-1 selective-scan layers with an attention layer every fourteenth, a
dense SwiGLU FFN on every layer (no expert layer at all) and a head tied
to the embedding.

Layer ``i`` is attention when ``i % attn_layer_period == attn_layer_offset``
and a Mamba mixer otherwise. Pre-norm residual blocks (``h = x +
Mix(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``), a final RMS norm, logits
``h Eᵀ`` with the embedding ``E``. No positional encoding of any kind.

**Mamba mixer** (``d_inner = mamba_expand · hidden_size``): ``[u | z] = x
W_in``; ``u ← silu(causal depthwise conv(u) + b_conv)``; ``[δ | B | C] = u
W_x``; ``δ``, ``B``, ``C`` each RMS-normed with a weight of its own; ``Δ =
softplus(δ W_dt + b_dt)``; ``A = −exp(A_log)``; the recurrence and the gate
are ``ops/selective_scan.py``'s, and so is a prefill chunk's convolution
(both read their half of ``[u | z]`` where ``W_in``'s product wrote it);
out ``= y W_out``. **Attention**:
``num_attention_heads`` query heads over ``num_key_value_heads`` = 1 key
and value head (``ops/gqa_attention.py``: one group), scale ``d^−½``, no
bias.

The cache is what a token leaves behind: a state ``[d_inner, d_state]``
float32 and the convolution's last ``d_conv − 1`` inputs a Mamba layer
(``recurrent``), a key and a value row an attention layer (``full``) — 1
KiB a token for the whole model at its published widths.

The Mamba layers' weights are STACKED, one stack a run of consecutive
Mamba layers (the runs the attention layers cut the depth into: 0–6, 8–20,
22–27 as published), and a run is ``lax.scan`` of ONE layer function over
its stack: the program holds one traced body a run, not one a layer.
:func:`prefill_chunk` is the continuation ``llm_prefill`` scans
(``llm_model.chunked_prefill``): the carry is the recurrent state, the
convolution tails and the K/V rows together; a padded last chunk advances
none of the recurrent ones (``Δ = 0`` past ``n_valid``; the tail is cut at
``n_valid``). :func:`decode_step` is one token through the same weights.
Conventions are ``llm_hybrid.py``'s: weights held in ``dtype``, products on
``dtype`` operands accumulated in float32; residual stream, norms,
softmax, ``Δ``, the state and logits float32; one sequence, no batch axis.
``models/llm_jamba_reference.py`` is the plain float32 statement.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..ops import gqa_attention, selective_scan
from ..telemetry.device_scopes import device_scope
from .llm_hybrid import (_const, _dot, _embed, _normal, _pre_norm, _swiglu,
                         count_params, init_tree, rms_norm)
from .llm_model import LLMModel, chunked_prefill


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    """Field names are the published ``config.json``'s; nothing is cut."""
    hidden_size: int = 2560
    num_hidden_layers: int = 28
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    intermediate_size: int = 8192
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    rms_norm_eps: float = 1e-6
    vocab_size: int = 65536
    dtype: str = "bfloat16"
    # the schedule of the chunked prefill, fixed here by measurement
    # (PERF.md §6, PR 37; the tile the smallest sum over the 16 chunks of
    # a 64k prefill, PR 40); sizes of the program, not options of a request
    prefill_chunk_tokens: int = 4096
    attn_block_q: int = 2048
    attn_block_k: int = 2048

    @classmethod
    def jamba2_3b(cls) -> "JambaConfig":
        """AI21-Jamba2-3B whole: 28 layers, 65 536 vocabulary rows."""
        return cls()

    @classmethod
    def tiny(cls, **kw) -> "JambaConfig":
        """The CPU tests' size, float32: one period of fourteen with its
        attention layer, so both kinds of run (before and after it), a
        tied head, chunks and blocks a test prompt spans several of."""
        base = dict(
            hidden_size=32, num_hidden_layers=14, attn_layer_period=14,
            attn_layer_offset=7, intermediate_size=48,
            num_attention_heads=4, mamba_d_state=4, mamba_dt_rank=6,
            vocab_size=64, dtype="float32", prefill_chunk_tokens=16,
            attn_block_q=8, attn_block_k=8)
        return cls(**{**base, **kw})

    @property
    def model(self) -> LLMModel:
        return MODEL

    def __post_init__(self):
        if self.num_key_value_heads != 1:
            raise ValueError("one shared key/value head is what the "
                             "cache holds a row of")

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def is_attention(self, i: int) -> bool:
        return i % self.attn_layer_period == self.attn_layer_offset

    @property
    def attention_layers(self) -> list[int]:
        return [i for i in range(self.num_hidden_layers)
                if self.is_attention(i)]

    @property
    def mamba_runs(self) -> list[int]:
        """Lengths of the runs of consecutive Mamba layers, one ahead of
        each attention layer and one after the last (a run may be empty)."""
        edges = [-1] + self.attention_layers + [self.num_hidden_layers]
        return [b - a - 1 for a, b in zip(edges, edges[1:])]

    @property
    def scan_layers_per_token(self) -> int:
        return sum(self.mamba_runs)

    moe_layers = ()                   # no expert layer: nothing is routed
    stream_mixes_per_token = 0        # one residual stream, nothing mixed
    min_prompt_tokens = 1


# --- weights ---------------------------------------------------------------


def _shapes(cfg: JambaConfig) -> dict:
    """Every leaf as ``(shape, dtype name, init)``; a Mamba run's leaves
    carry the run's length ahead of the layer's own shape."""
    D, Di, wd = cfg.hidden_size, cfg.d_inner, cfg.dtype
    N, R, K = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
    Hd = cfg.num_attention_heads * cfg.head_dim
    one = _const(1.0)

    def block(L: tuple) -> dict:
        F = cfg.intermediate_size
        return {"norm1": (L + (D,), "float32", one),
                "norm2": (L + (D,), "float32", one),
                "ffn": {"w_gu": (L + (D, 2 * F), wd, _normal()),
                        "w_down": (L + (F, D), wd, _normal())}}

    def mamba(n: int) -> dict:
        L = (n,)
        return {**block(L), "ssm": {
            "w_in": (L + (D, 2 * Di), wd, _normal()),          # [u | z]
            "conv_w": (L + (K, Di), "float32", _normal()),
            "conv_b": (L + (Di,), "float32", _normal(0.1)),
            "w_x": (L + (Di, R + 2 * N), wd, _normal()),       # [δ | B | C]
            "dt_norm": (L + (R,), "float32", one),
            "b_norm": (L + (N,), "float32", one),
            "c_norm": (L + (N,), "float32", one),
            "w_dt": (L + (R, Di), wd, _normal()),
            # softplus(N(0,1) − 4): steps of ~0.002 to ~0.1, so that with
            # A = −1 .. −N a state remembers from ten to thousands of tokens
            "b_dt": (L + (Di,), "float32", _const(-4.0)),
            "a_log": (L + (Di, N), "float32",
                      _const(tuple(math.log(n + 1.0) for n in range(N)))),
            "d": (L + (Di,), "float32", one),
            "w_out": (L + (Di, D), wd, _normal())}}

    attention = {**block(()), "attn": {
        # [q (H·d) | k (d) | v (d)]
        "w_qkv": ((D, Hd + 2 * cfg.head_dim), wd, _normal()),
        "w_o": ((Hd, D), wd, _normal())}}
    return {"embed": ((cfg.vocab_size, D), wd, _normal(1.0 / math.sqrt(D))),
            "final_norm": ((D,), "float32", one),
            "mamba": [mamba(n) for n in cfg.mamba_runs],
            "attn": [attention for _ in cfg.attention_layers]}


def init_jamba(cfg: JambaConfig, key, abstract: bool = False):
    return init_tree(_shapes(cfg), key, abstract)


def param_count(cfg: JambaConfig) -> int:
    return count_params(_shapes(cfg))


# --- pieces shared by prefill and decode -----------------------------------


def _scan_inputs(cfg: JambaConfig, p, u):
    """From the convolved ``u`` [..., d_inner]: ``Δ`` [..., d_inner], ``B``
    and ``C`` [..., N] (each normed), and ``A`` [d_inner, N]."""
    R, N, eps = cfg.mamba_dt_rank, cfg.mamba_d_state, cfg.rms_norm_eps
    dtype = jnp.dtype(cfg.dtype)
    low = _dot(u, p["w_x"], dtype)
    delta = rms_norm(low[..., :R], p["dt_norm"], eps)
    B = rms_norm(low[..., R:R + N], p["b_norm"], eps)
    C = rms_norm(low[..., R + N:], p["c_norm"], eps)
    dt = jax.nn.softplus(_dot(delta, p["w_dt"], dtype) + p["b_dt"])
    return dt, B, C, -jnp.exp(p["a_log"])


def _ffn(cfg: JambaConfig, layer, h):
    x = _pre_norm(h, layer["norm2"], cfg.rms_norm_eps)
    with device_scope("llm_shared_ffn"):
        return h + _swiglu(x, layer["ffn"], jnp.dtype(cfg.dtype))


def _qkv(cfg: JambaConfig, p, x):
    H, d = cfg.num_attention_heads, cfg.head_dim
    y = _dot(x, p["w_qkv"], jnp.dtype(cfg.dtype))
    return (y[..., :H * d].reshape(*y.shape[:-1], H, d),
            y[..., H * d:H * d + d], y[..., H * d + d:])


def logits_of(cfg: JambaConfig, params, h):
    """Final norm and the tied head (the embedding, read once more)."""
    dtype = jnp.dtype(cfg.dtype)
    with device_scope("llm_head"):
        x = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        return jnp.einsum("...d,vd->...v", x.astype(dtype),
                          params["embed"].astype(dtype),
                          preferred_element_type=jnp.float32)


def _walk(cfg: JambaConfig, params, cache: dict, h, mamba, attention):
    """``h`` through every layer, the cache's leaves with it: each run of
    Mamba layers a ``lax.scan`` of ``mamba(layer, h, tail, state) -> (h,
    tail, state)`` over the run's stacked weights and its slice of the
    recurrent leaves, ``attention(layer, h, k_rows, v_rows) -> (h, k_rows,
    v_rows)`` between two runs. Answers ``(h, cache)``."""
    def runs(x):
        at, out = 0, []
        for n in cfg.mamba_runs:
            out.append(x[at:at + n])
            at += n
        return out

    def body(h, xs):
        h, tail, state = mamba(xs[0], h, xs[1], xs[2])
        return h, (tail, state)

    with device_scope("llm_ssm"):
        tails, states = runs(cache["conv"]), runs(cache["ssm"])
    k_rows, v_rows = list(cache["k"]), list(cache["v"])
    for r, stack in enumerate(params["mamba"]):
        if r:
            h, k_rows[r - 1], v_rows[r - 1] = attention(
                params["attn"][r - 1], h, k_rows[r - 1], v_rows[r - 1])
        if tails[r].shape[0]:
            h, (tails[r], states[r]) = jax.lax.scan(
                body, h, (stack, tails[r], states[r]))
    with device_scope("llm_ssm"):
        return h, {"ssm": jnp.concatenate(states),
                   "conv": jnp.concatenate(tails), "k": k_rows, "v": v_rows}


def _no_held():
    """No expert layer: the held-slot counts have no entry."""
    return jnp.zeros((0,), jnp.int32)


# --- prefill ---------------------------------------------------------------


def empty_cache(cfg: JambaConfig, max_len: int) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    L, Di = cfg.scan_layers_per_token, cfg.d_inner
    rows = [jnp.zeros((max_len, cfg.head_dim), dtype)
            for _ in cfg.attention_layers]
    return {"ssm": jnp.zeros((L, Di, cfg.mamba_d_state), jnp.float32),
            "conv": jnp.zeros((L, cfg.mamba_d_conv - 1, Di), jnp.float32),
            "k": rows, "v": list(rows)}


def cache_kinds(cfg: JambaConfig, cache: dict) -> dict:
    return {"recurrent": [cache["ssm"], cache["conv"]],
            "full": [cache["k"], cache["v"]]}


def _mamba_chunk(cfg: JambaConfig, layer, h, tail, state, n_valid, kernel):
    """One Mamba layer over a chunk ``h`` [C, D] from ``(tail, state)``;
    answers ``(h, tail, state)`` after token ``n_valid − 1``."""
    dtype, Di, K = jnp.dtype(cfg.dtype), cfg.d_inner, cfg.mamba_d_conv
    C, p = h.shape[0], layer["ssm"]
    x = _pre_norm(h, layer["norm1"], cfg.rms_norm_eps)
    with device_scope("llm_ssm"):
        # uz whole to both kernels: each reads its half where the product
        # wrote it (a sliced operand of a Pallas call is a copy)
        uz = _dot(x, p["w_in"], dtype)
        u = selective_scan.conv_chunk(tail, uz, p["conv_w"], p["conv_b"],
                                      kernel)
        # the inputs of tokens n_valid − (K−1) .. n_valid − 1: of the old
        # tail and the K−1 rows of uz that end at n_valid (not of the whole
        # [tail; u half], which the compiler would fetch to take three rows)
        last = min(K - 1, C)
        lo = jnp.maximum(n_valid - last, 0)
        tail = jax.lax.dynamic_slice_in_dim(jnp.concatenate(
            [tail, jax.lax.dynamic_slice(uz, (lo, 0), (last, Di))], 0),
            n_valid - lo, K - 1, 0)
        dt, B, Cm, A = _scan_inputs(cfg, p, u)
        y, state = selective_scan.scan_chunk(state, u, dt, uz, B, Cm, A,
                                             p["d"], n_valid, kernel)
        h = h + _dot(y, p["w_out"], dtype)
    return _ffn(cfg, layer, h), tail, state


def _attention_chunk(cfg: JambaConfig, layer, h, k_cache, v_cache, start,
                     kernel):
    dtype = jnp.dtype(cfg.dtype)
    x = _pre_norm(h, layer["norm1"], cfg.rms_norm_eps)
    with device_scope("llm_attn"):
        q, k, v = _qkv(cfg, layer["attn"], x)
        k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(dtype),
                                               (start, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(dtype),
                                               (start, 0))
        o = gqa_attention.causal_chunk(
            q, k_cache[None], v_cache[None], start, cfg.head_dim ** -0.5,
            dtype, cfg.attn_block_q, cfg.attn_block_k, kernel=kernel)
        h = h + _dot(o.reshape(o.shape[0], -1), layer["attn"]["w_o"], dtype)
    return _ffn(cfg, layer, h), k_cache, v_cache


def prefill_chunk(cfg: JambaConfig, params, cache: dict, ids, start, n_valid,
                  all_logits: bool = False, kernel: str | None = None):
    """``ids`` [C] at positions ``start .. start+C−1``, of which the first
    ``n_valid`` are the prompt's (the rest pad its last chunk: the
    recurrent states and convolution tails stand where token ``n_valid −
    1`` left them, and nothing reads the K/V rows they write). Continues
    from ``cache``. Answers ``(logits, cache, held, rows)`` as
    ``llm_kimi.prefill_chunk``: ``held`` and ``rows`` are empty (no expert
    layer). ``kernel`` names the form of both kernels (``pallas``,
    ``interpret``, ``lax``; None: the platform's)."""
    h, cache = _walk(
        cfg, params, cache, _embed(params, ids),
        lambda layer, h, tail, state: _mamba_chunk(cfg, layer, h, tail,
                                                   state, n_valid, kernel),
        lambda layer, h, k, v: _attention_chunk(cfg, layer, h, k, v, start,
                                                kernel))
    with device_scope("llm_head"):
        last = h if all_logits else h[n_valid - 1]
    return logits_of(cfg, params, last), cache, _no_held(), _no_held()


def prefill(cfg: JambaConfig, params, ids, max_len: int,
            all_logits: bool = False, chunk: int | None = None,
            kernel: str | None = None):
    """The whole prompt ``ids`` [T], walked in chunks through the cache;
    answers as ``llm_hybrid.prefill``: ``(logits, cache, held)``."""
    return chunked_prefill(MODEL, cfg, params, ids, max_len, all_logits,
                           chunk, kernel=kernel)[:3]


# --- decode ----------------------------------------------------------------


def _mamba_token(cfg: JambaConfig, layer, h, tail, state):
    dtype, Di, p = jnp.dtype(cfg.dtype), cfg.d_inner, layer["ssm"]
    x = _pre_norm(h, layer["norm1"], cfg.rms_norm_eps)
    with device_scope("llm_ssm"):
        uz = _dot(x, p["w_in"], dtype)
        window = jnp.concatenate([tail, uz[None, :Di]], 0)
        u = jax.nn.silu((window * p["conv_w"]).sum(0) + p["conv_b"])
        dt, B, Cm, A = _scan_inputs(cfg, p, u)
        y, state = selective_scan.scan_step(state, u, dt, uz[Di:], B, Cm, A,
                                            p["d"])
        h = h + _dot(y, p["w_out"], dtype)
    return _ffn(cfg, layer, h), window[1:], state


def _attention_token(cfg: JambaConfig, layer, h, k_cache, v_cache, pos):
    dtype = jnp.dtype(cfg.dtype)
    x = _pre_norm(h, layer["norm1"], cfg.rms_norm_eps)
    with device_scope("llm_attn"):
        q, k, v = _qkv(cfg, layer["attn"], x)
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k[None].astype(dtype), (pos, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v[None].astype(dtype), (pos, 0))
        o = gqa_attention.step(q, k_cache[None], v_cache[None],
                               jnp.arange(k_cache.shape[0]) <= pos,
                               cfg.head_dim ** -0.5, dtype)
        h = h + _dot(o.reshape(-1), layer["attn"]["w_o"], dtype)
    return _ffn(cfg, layer, h), k_cache, v_cache


def decode_step(cfg: JambaConfig, params, cache: dict, token, pos):
    """One token ``token`` (scalar id) at position ``pos`` through the
    cache; answers as ``llm_hybrid.decode_step`` (``held`` empty)."""
    h, cache = _walk(
        cfg, params, cache, _embed(params, token),
        lambda layer, h, tail, state: _mamba_token(cfg, layer, h, tail,
                                                   state),
        lambda layer, h, k, v: _attention_token(cfg, layer, h, k, v, pos))
    return logits_of(cfg, params, h), cache, _no_held()


MODEL = LLMModel(init_jamba, prefill, decode_step, empty_cache, cache_kinds,
                 prefill_chunk)
