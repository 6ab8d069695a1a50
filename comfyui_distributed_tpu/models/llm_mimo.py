"""A twelfth prompt rewriter, built to make a long brief cheap to KEEP:
five window layers of 128 keys (a learned sink in their softmax) to one full
layer, the two kinds with their own K/V head count and rope base, heads 192
wide for q·k and 128 for v, token-routed experts with no shared one.

Kept layer ``i`` is of the kind ``layer_types[i]``. A block is pre-norm:
``x ← x + Attn(RMSNorm(x))``, ``x ← x + FFN(RMSNorm(x))``; a final RMS
norm, an untied head. **Attention**: ``q = a W_q``, ``[k | v] = a W_kv`` —
``num_attention_heads`` query heads and ``G`` key heads of ``head_dim``,
``G`` value heads of ``v_head_dim``, ``G`` = ``num_key_value_heads`` on a
full layer and ``swa_num_key_value_heads`` on a window one; ``v`` times
``attention_value_scale``; the first ``rotary_dim`` dimensions of q and k
turn by rope (half rotation within them; ``rope_theta`` on a full layer,
``swa_rope_theta`` on a window one: two float64-made tables,
``llm_trinity.rope_table``), the rest pass; query head ``h`` reads key/value
head ``h // (heads / G)``; scale ``head_dim^−½``; a full layer sees every
key below, a window layer the ``sliding_window`` keys up to its own, and a
window layer's softmax has one more term in its denominator, the head's
learned sink (``ops/gqa_sink_attention.py``). **FFN**: the first
``num_dense_layers`` are a dense SwiGLU, the rest ``ops/expert_share.py``'s
expert layer (sigmoid scores, a selection bias, one group, the weights
normalised, this chip's share of the experts) — nothing beside it. The
vocabulary may be a slice.

The cache is two kinds of leaf in one carry: a full layer's K and V buffer
(``[G, rows, head_dim]`` and ``[G, rows, v_head_dim]``, a row a position)
and a window layer's RING of ``sliding_window`` rows (slot ``position %
window``; rows are stored roped and scaled). :func:`prefill_chunk` is the
continuation ``llm_prefill`` scans (``llm_model.chunked_prefill``); the ring
is SHORTER than the chunk (``prefill_chunk_tokens`` a multiple of the
window, chunks aligned): a full layer writes the chunk at its rows and
attends over the buffer up to them; a window layer attends over ``[the ring
as the last chunk left it ; its own K/V]`` under the band and then writes
the LAST ``window`` of its ``n_valid`` rows to their slots — of a padded
last chunk the rows before ``n_valid``, not its tail, and where fewer than a
window are valid the ring keeps the rest. :func:`decode_step` is one token
through ring and buffer. Conventions are ``llm_hybrid.py``'s: weights held
in ``dtype``, products on ``dtype`` operands accumulated in float32;
residual stream, norms, rope, softmax, router scores and logits float32;
K/V rows ``dtype``; one sequence, no batch axis.
``models/llm_mimo_reference.py`` is the plain float32 statement all three
are held to.
"""

from __future__ import annotations

import dataclasses
import types

import jax
import jax.numpy as jnp

from ..ops import expert_share, gqa_sink_attention
from ..telemetry.device_scopes import device_scope
from .llm_hybrid import (_ACT, _const, _count_held, _dot, _embed, _normal,
                         _pre_norm, _stack_counts, _swiglu, count_params,
                         init_tree, logits_of)
from .llm_model import LLMModel, chunked_prefill
from .llm_trinity import FULL, SLIDING, _rope_rows, _rows, rope_table

KINDS = ("full", "window")


@dataclasses.dataclass(frozen=True)
class MimoConfig:
    """Field names are the published ``config.json``'s. ``n_routed_experts``
    is how many experts are HELD here (``router_experts`` is the layer's
    count, the router's width), ``vocab_size`` how many rows of the
    vocabulary, ``num_hidden_layers`` / ``layer_types`` the depth kept (the
    published ``hybrid_layer_pattern``'s first seven, by name)."""
    hidden_size: int = 4096
    num_hidden_layers: int = 7
    num_dense_layers: int = 1
    layer_types: tuple = (FULL, SLIDING, SLIDING, SLIDING, SLIDING, FULL,
                          SLIDING)
    intermediate_size: int = 16384
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    swa_num_key_value_heads: int = 8
    head_dim: int = 192
    v_head_dim: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 5000000.0
    swa_rope_theta: float = 10000.0
    sliding_window: int = 128
    attention_value_scale: float = 0.707
    max_position_embeddings: int = 262144
    layernorm_epsilon: float = 1e-5
    router_experts: int = 256
    n_routed_experts: int = 16
    first_expert: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    vocab_size: int = 19072
    dtype: str = "bfloat16"
    # the schedule of the chunked prefill: a chunk is many windows; the full
    # layers' tile is the sweep's (scripts/mimo_sweep.py; PERF.md §6, PR 64);
    # sizes of the program, not options of a request
    prefill_chunk_tokens: int = 4096
    attn_block_q: int = 2048
    attn_block_k: int = 2048
    expert_tile: int = expert_share.GROUP_TILE

    @classmethod
    def mimo_stage(cls) -> "MimoConfig":
        """MiMo-V2-Flash at its published widths: one chip's share of a
        16-chip expert group (experts 0–15 of 256, an eighth of the
        vocabulary), published layers 0–6: the dense layer and one whole
        window/full period of expert layers."""
        return cls()

    @classmethod
    def tiny(cls, **kw) -> "MimoConfig":
        """The CPU tests' size, float32: seven layers in the published
        pattern, 4 and 2 query heads a key/value head by kind, keys wider
        than values, rope on a third of a head, a window the chunk holds
        four times, a router wider than the experts held, a full-layer tile
        of two q blocks under a K block longer than the chunk."""
        base = dict(
            hidden_size=32, intermediate_size=48, num_attention_heads=8,
            num_key_value_heads=2, swa_num_key_value_heads=4, head_dim=12,
            v_head_dim=8, sliding_window=4, max_position_embeddings=96,
            router_experts=16, n_routed_experts=4, num_experts_per_tok=2,
            moe_intermediate_size=16, vocab_size=64, dtype="float32",
            prefill_chunk_tokens=16, attn_block_q=8, attn_block_k=32,
            expert_tile=2)
        return cls(**{**base, **kw})

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types names every kept layer's kind")
        if self.prefill_chunk_tokens % self.sliding_window:
            raise ValueError(
                "a window layer's ring lies in position order at a chunk's "
                "start: the chunk is a multiple of the window")

    @property
    def model(self) -> LLMModel:
        return MODEL

    def is_full(self, i: int) -> bool:
        return self.layer_types[i] == FULL

    def is_moe(self, i: int) -> bool:
        return i >= self.num_dense_layers

    def kv_heads(self, i: int) -> int:
        return self.num_key_value_heads if self.is_full(i) \
            else self.swa_num_key_value_heads

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor) // 2 * 2

    @property
    def rms_norm_eps(self) -> float:
        return self.layernorm_epsilon

    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    @property
    def moe_layers(self) -> list[int]:
        return [i for i in range(self.num_hidden_layers) if self.is_moe(i)]

    @property
    def routing(self) -> expert_share.Routing:
        return expert_share.Routing(self.router_experts,
                                    self.num_experts_per_tok, 1, 1, 1.0)

    @property
    def routed_slots_per_token(self) -> int:
        return self.num_experts_per_tok * len(self.moe_layers)

    stream_mixes_per_token = 0        # one residual stream, nothing mixed
    min_prompt_tokens = 1

    def attended_keys(self, prompt_tokens: int, new_tokens: int) -> dict:
        """(query, key) pairs ONE head attends in a request, by kind of
        layer and phase, summed over the layers of the kind: a full layer
        every key below the query, a window layer at most the window."""
        T, W = prompt_tokens, self.sliding_window
        n_full = sum(self.is_full(i) for i in range(self.num_hidden_layers))
        n_window = self.num_hidden_layers - n_full
        seen = min(T, W)
        ends = range(T + 1, T + new_tokens + 1)
        return {
            ("full", "prefill"): n_full * (T * (T + 1) // 2),
            ("window", "prefill"): n_window * (
                seen * (seen + 1) // 2 + (T - seen) * W),
            ("full", "decode"): n_full * sum(ends),
            ("window", "decode"): n_window * sum(min(e, W) for e in ends)}


# --- weights ---------------------------------------------------------------


def _shapes(cfg: MimoConfig) -> dict:
    """Every drawn leaf as ``(shape, dtype name, init)``."""
    D, wd = cfg.hidden_size, cfg.dtype
    H, dk, dv = cfg.num_attention_heads, cfg.head_dim, cfg.v_head_dim
    one = _const(1.0)

    def ffn(width):
        return {"w_gu": ((D, 2 * width), wd, _normal()),
                "w_down": ((width, D), wd, _normal())}

    layers = []
    for i in range(cfg.num_hidden_layers):
        G = cfg.kv_heads(i)
        layer = {"norm_in": ((D,), "float32", one),
                 "norm_mlp_in": ((D,), "float32", one),
                 "attn": {
                     "w_q": ((D, H * dk), wd, _normal()),
                     # [k (G·dk) | v (G·dv)]
                     "w_kv": ((D, G * (dk + dv)), wd, _normal()),
                     "w_o": ((H * dv, D), wd, _normal())}}
        if not cfg.is_full(i):
            # the learned sink, a logit a head: of the softmax's own scale,
            # so that leaving it out moves every window layer's output
            layer["attn"]["sink"] = ((H,), "float32", _normal(1.0))
        if cfg.is_moe(i):
            F = cfg.moe_intermediate_size
            layer["moe"] = {
                "w_router": ((D, cfg.router_experts), wd, _normal()),
                "router_bias": ((cfg.router_experts,), "float32",
                                _normal(0.02)),
                "e_gu": ((cfg.n_routed_experts, D, 2 * F), wd, _normal()),
                "e_down": ((cfg.n_routed_experts, F, D), wd, _normal())}
        else:
            layer["ffn"] = ffn(cfg.intermediate_size)
        layers.append(layer)
    return {"embed": ((cfg.vocab_size, D), wd, _normal(1.0)),
            "head": ((cfg.vocab_size, D), wd, _normal(D ** -0.5)),
            "final_norm": ((D,), "float32", one),
            "layers": layers}


def _rope_of(cfg: MimoConfig, kind: str):
    """What ``llm_trinity.rope_table`` reads of a config, for one kind of
    layer: the rotary width as its head and the kind's base."""
    return types.SimpleNamespace(
        head_dim=cfg.rotary_dim,
        max_position_embeddings=cfg.max_position_embeddings,
        rope_theta=cfg.rope_theta if kind == "full" else cfg.swa_rope_theta)


def init_mimo(cfg: MimoConfig, key, abstract: bool = False):
    """The drawn weights and, beside them, a rope table a kind of layer
    (leaves, not literals of the programs: 2 × 2 × 32 MiB at the published
    positions)."""
    tree = init_tree(_shapes(cfg), key, abstract)
    rows = (cfg.max_position_embeddings, cfg.rotary_dim // 2)
    tree["rope"] = {kind: {k: jax.ShapeDtypeStruct(rows, jnp.float32)
                           for k in ("cos", "sin")} if abstract
                    else rope_table(_rope_of(cfg, kind)) for kind in KINDS}
    return tree


def param_count(cfg: MimoConfig) -> int:
    return count_params(_shapes(cfg))


# --- pieces shared by prefill and decode -----------------------------------


def _rope(cfg: MimoConfig, x, cos, sin):
    """Half rotation within the first ``rotary_dim`` dimensions of ``x``
    [T,heads,head_dim]; ``cos``, ``sin`` [T,rotary_dim/2]; the rest pass."""
    r = cfg.rotary_dim
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _scaled(cfg: MimoConfig, v):
    """``attention_value_scale`` on the values as the cache holds them."""
    return v * cfg.attention_value_scale


def _attn_in(cfg: MimoConfig, i: int, p, x, rope):
    """From the normed rows ``x`` [T,D] of layer ``i``: q [T,H,dk] and k
    [T,G,dk] roped by ``rope`` — the layer kind's ``(cos, sin)`` rows —,
    v [T,G,dv] scaled."""
    H, G = cfg.num_attention_heads, cfg.kv_heads(i)
    dk, dv = cfg.head_dim, cfg.v_head_dim
    T, dtype = x.shape[0], jnp.dtype(cfg.dtype)
    q = _rope(cfg, _dot(x, p["w_q"], dtype).reshape(T, H, dk), *rope)
    y = _dot(x, p["w_kv"], dtype)
    k = _rope(cfg, y[:, :G * dk].reshape(T, G, dk), *rope)
    return q, k, _scaled(cfg, y[:, G * dk:].reshape(T, G, dv))


def _attn_out(cfg: MimoConfig, p, o):
    return _dot(o.reshape(*o.shape[:-2], -1), p["w_o"], jnp.dtype(cfg.dtype))


def _kind_rope(params, start, n: int) -> dict:
    return {kind: _rope_rows({"rope": params["rope"][kind]}, start, n)
            for kind in KINDS}


# --- prefill ---------------------------------------------------------------


def empty_cache(cfg: MimoConfig, max_len: int) -> dict:
    """Per layer a K and a V: a ring of ``sliding_window`` rows for a window
    layer, for a full layer ``max_len`` rows rounded up to the K block (its
    blocked kernel then reads the buffer as it is)."""
    dtype = jnp.dtype(cfg.dtype)
    bk = cfg.attn_block_k
    rows = [-(-max_len // bk) * bk if cfg.is_full(i) else cfg.sliding_window
            for i in range(cfg.num_hidden_layers)]
    return {"k": [jnp.zeros((cfg.kv_heads(i), r, cfg.head_dim), dtype)
                  for i, r in enumerate(rows)],
            "v": [jnp.zeros((cfg.kv_heads(i), r, cfg.v_head_dim), dtype)
                  for i, r in enumerate(rows)]}


def cache_kinds(cfg: MimoConfig, cache: dict) -> dict:
    def of(full):
        return [cache[k][i] for k in ("k", "v")
                for i in range(cfg.num_hidden_layers)
                if cfg.is_full(i) == full]

    return {"window": of(False), "full": of(True)}


def _ring_after(ring, rows, start, n_valid):
    """The ring ``[G, W, d]`` once the chunk's first ``n_valid`` rows
    (``rows`` [G, C, d], positions ``start ..``) are written: slot ``j``
    holds the newest position ``≡ j (mod W)`` not past the last valid one —
    a row of this chunk where there is one, else what the ring held."""
    W, C = ring.shape[1], rows.shape[1]
    last = start + n_valid - 1
    newest = last - (last - jnp.arange(W)) % W
    taken = jnp.take(rows, jnp.clip(newest - start, 0, C - 1), axis=1)
    return jnp.where((newest >= start)[None, :, None], taken, ring)


def _ffn(cfg: MimoConfig, layer, i: int, h, valid):
    """``h + FFN(RMSNorm(h))`` for chunk rows ``h`` [C,D]; ``(h, held,
    rows)``, the counts None for a dense layer."""
    dtype = jnp.dtype(cfg.dtype)
    x = _pre_norm(h, layer["norm_mlp_in"], cfg.rms_norm_eps)
    if not cfg.is_moe(i):
        with device_scope("llm_shared_ffn"):
            return h + _swiglu(x, layer["ffn"], dtype), None, None
    m = layer["moe"]
    idx, w = expert_share.route(x, m["w_router"], m["router_bias"],
                                cfg.routing)
    y, n_rows = expert_share.held_part(
        x, idx, w, m["e_gu"], m["e_down"], cfg.first_expert, dtype,
        cfg.routing, _ACT, valid=valid, tile=cfg.expert_tile)
    with device_scope("llm_router"):
        real = jnp.where(valid[:, None], idx, -1)
        n_rows = n_rows.astype(jnp.int32)
    with device_scope("llm_experts"):
        h = h + y
    return h, _count_held(cfg, real), n_rows


def prefill_chunk(cfg: MimoConfig, params, cache: dict, ids, start,
                  n_valid, all_logits: bool = False,
                  kernel: str | None = None):
    """``ids`` [C] at positions ``start .. start+C−1`` (``start`` a multiple
    of the chunk and so of the window), of which the first ``n_valid`` are
    the prompt's (the rest pad its last chunk: they route to no expert,
    nothing reads what they write into a full layer's buffer, and none of
    them reaches a ring). Continues from ``cache``. Answers ``(logits,
    cache, held, rows)`` as ``llm_kimi.prefill_chunk``."""
    dtype = jnp.dtype(cfg.dtype)
    C, W = ids.shape[0], cfg.sliding_window
    scale = cfg.head_dim ** -0.5
    with device_scope("llm_attn"):
        rope = _kind_rope(params, start, C)
        # the ring's rows sit below the chunk's own: before position 0
        # there is none
        lowest = jnp.maximum(W - start, 0)
    with device_scope("llm_router"):
        valid = jnp.arange(C) < n_valid
    cache = {k: list(v) for k, v in cache.items()}
    held, rows = [], []
    h = _embed(params, ids)
    for i, layer in enumerate(params["layers"]):
        full = cfg.is_full(i)
        x = _pre_norm(h, layer["norm_in"], cfg.rms_norm_eps)
        with device_scope("llm_attn"):
            q, k, v = _attn_in(cfg, i, layer["attn"], x,
                               rope["full" if full else "window"])
            k, v = _rows(k, dtype), _rows(v, dtype)
            if full:
                k, v = (jax.lax.dynamic_update_slice(cache[n][i], a,
                                                     (0, start, 0))
                        for n, a in (("k", k), ("v", v)))
                cache["k"][i], cache["v"][i] = k, v
                with jax.named_scope("llm_full_core"):
                    o = gqa_sink_attention.causal_chunk(
                        q, k, v, start, scale, dtype, cfg.attn_block_q,
                        cfg.attn_block_k, kernel=kernel)
            else:
                with jax.named_scope("llm_swa_core"):
                    o = gqa_sink_attention.band_chunk(
                        q, jnp.concatenate([cache["k"][i], k], axis=1),
                        jnp.concatenate([cache["v"][i], v], axis=1), lowest,
                        W, scale, dtype, layer["attn"]["sink"])
                for n, a in (("k", k), ("v", v)):
                    cache[n][i] = _ring_after(cache[n][i], a, start, n_valid)
            y = _attn_out(cfg, layer["attn"], o)
        with device_scope("llm_norm"):
            h = h + y
        h, n_held, n_rows = _ffn(cfg, layer, i, h, valid)
        if n_held is not None:
            held.append(n_held)
            rows.append(n_rows)
    with device_scope("llm_head"):
        last = h if all_logits else h[n_valid - 1]
    logits = logits_of(cfg, params, last)
    return logits, cache, _stack_counts(held), _stack_counts(rows)


def prefill(cfg: MimoConfig, params, ids, max_len: int,
            all_logits: bool = False, kernel: str | None = None):
    """The whole prompt ``ids`` [T], walked in chunks through the cache;
    answers as ``llm_hybrid.prefill``: ``(logits, cache, held)``."""
    return chunked_prefill(MODEL, cfg, params, ids, max_len, all_logits,
                           kernel=kernel)[:3]


# --- decode ----------------------------------------------------------------


def decode_step(cfg: MimoConfig, params, cache: dict, token, pos):
    """One token ``token`` (scalar id) at position ``pos`` through ring and
    buffer; answers as ``llm_hybrid.decode_step``."""
    dtype = jnp.dtype(cfg.dtype)
    W = cfg.sliding_window
    scale = cfg.head_dim ** -0.5
    with device_scope("llm_attn"):
        rope = _kind_rope(params, pos, 1)
    cache = {k: list(v) for k, v in cache.items()}
    held = []
    h = _embed(params, token)
    for i, layer in enumerate(params["layers"]):
        full = cfg.is_full(i)
        x = _pre_norm(h, layer["norm_in"], cfg.rms_norm_eps)
        with device_scope("llm_attn"):
            q, k, v = _attn_in(cfg, i, layer["attn"], x[None],
                               rope["full" if full else "window"])
            slot = pos if full else pos % W
            k, v = (jax.lax.dynamic_update_slice(
                cache[n][i], _rows(a, dtype), (0, slot, 0))
                for n, a in (("k", k), ("v", v)))
            cache["k"][i], cache["v"][i] = k, v
            # a ring slot j holds a position ≤ pos once j ≤ pos: all of
            # them after the first lap; every position in it is in the band
            with jax.named_scope("llm_full_core" if full
                                 else "llm_swa_core"):
                o = gqa_sink_attention.step(
                    q[0], k, v, jnp.arange(k.shape[1]) <= pos, scale, dtype,
                    None if full else layer["attn"]["sink"])
            y = _attn_out(cfg, layer["attn"], o)
        with device_scope("llm_norm"):
            h = h + y
        x = _pre_norm(h, layer["norm_mlp_in"], cfg.rms_norm_eps)
        if cfg.is_moe(i):
            m = layer["moe"]
            idx, w = expert_share.route(x[None], m["w_router"],
                                        m["router_bias"], cfg.routing)
            y = expert_share.held_part_token(
                x, idx[0], w[0], m["e_gu"], m["e_down"], cfg.first_expert,
                dtype, _ACT)
            held.append(_count_held(cfg, idx))
            with device_scope("llm_experts"):
                h = h + y
        else:
            with device_scope("llm_shared_ffn"):
                h = h + _swiglu(x[None], layer["ffn"], dtype)[0]
    return logits_of(cfg, params, h), cache, _stack_counts(held)


MODEL = LLMModel(init_mimo, prefill, decode_step, empty_cache, cache_kinds,
                 prefill_chunk)
