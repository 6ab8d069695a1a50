"""A fifth prompt rewriter, for the longest briefs: grouped-query attention
over window and full layers mixed, a gated sandwich-norm block, and
token-routed experts beside a shared one.

Kept layer ``i`` is of the kind ``layer_types[i]``. A block has FOUR norms
(a sandwich): ``x ← x + RMSNorm(Attn(RMSNorm(x)))``, ``x ← x +
RMSNorm(FFN(RMSNorm(x)))``; a final RMS norm, an untied head; the embedding
times ``√hidden_size`` (``mup_enabled``). **Attention**: ``[q | k | v | g] =
a W_in`` — ``num_attention_heads`` query heads, ``num_key_value_heads`` key
and value heads of ``head_dim``, a gate as wide as the queries; q and k RMS
normed per head (one weight of ``head_dim`` each); on a ``sliding_attention``
layer q and k turn by rope (half rotation over the whole head, the angles
from a float64 table made on the host: :func:`rope_table`) and a query
sees the ``sliding_window`` keys up to its own; a ``full_attention`` layer
has NO positional encoding and sees every key below; query head ``h`` reads
key/value head ``h // (heads / kv heads)``; scale ``head_dim^−½``; ``o =
(attn ⊙ σ(g)) W_o`` (``ops/gqa_attention.py``). **FFN**: the first
``num_dense_layers`` are a dense SwiGLU, the rest ``ops/expert_share.py``'s
expert layer (sigmoid scores, a selection bias, one group, the weights
normalised and times ``route_scale``, this chip's share of the experts)
beside one shared expert. The vocabulary may be a slice.

The cache is two kinds of leaf in one carry: a full layer's K and V buffer
``[kv heads, rows, head_dim]`` (a row a position) and a window layer's RING
of ``sliding_window`` rows (slot ``position % window``; rows are stored
roped). :func:`prefill_chunk` is the continuation ``llm_prefill`` scans
(``llm_model.chunked_prefill``), ``prefill_chunk_tokens`` = the window and
chunks aligned: a full layer writes the chunk at its rows and attends over
the buffer up to them; a window layer attends over ``[the ring as the last
chunk left it ; its own K/V]`` (banded) and then its own rows ARE the new
ring — only the ``n_valid`` of them that are the prompt's: a padded row at
position ``p`` would land on the slot of ``p − window``, a row the first
decoded token still needs. :func:`prefill` is that scan under
``llm_hybrid.prefill``'s signature. :func:`decode_step` is one token
through ring and buffer, reading only the held experts it selected.
Conventions are ``llm_hybrid.py``'s: weights held in ``dtype``, products on
``dtype`` operands accumulated in float32; residual stream, norms, rope,
softmax, gates, router scores and logits float32; K/V rows ``dtype``; one
sequence, no batch axis. ``models/llm_trinity_reference.py`` is the plain
float32 statement all three are held to.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import expert_share, gqa_attention
from ..telemetry.device_scopes import device_scope
from .llm_hybrid import (_ACT, _const, _count_held, _dot, _normal, _pre_norm,
                         _stack_counts, _swiglu, count_params, init_tree,
                         logits_of, rms_norm)
from .llm_model import LLMModel, chunked_prefill

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class TrinityConfig:
    """Field names are the published ``config.json``'s. ``num_experts`` is
    how many experts are HELD here (``router_experts`` is the layer's
    count, the router's width), ``vocab_size`` how many rows of the
    vocabulary, ``num_hidden_layers`` / ``layer_types`` the depth kept."""
    hidden_size: int = 3072
    num_hidden_layers: int = 5
    num_dense_layers: int = 1
    layer_types: tuple = (SLIDING, SLIDING, FULL, SLIDING, SLIDING)
    intermediate_size: int = 12288
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    rope_theta: float = 10000.0
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True
    router_experts: int = 256
    num_experts: int = 16
    first_expert: int = 0
    num_experts_per_tok: int = 4
    route_scale: float = 2.448
    moe_intermediate_size: int = 3072
    vocab_size: int = 25024
    dtype: str = "bfloat16"
    # the schedule of the chunked prefill: the chunk IS the window (the
    # ring's contract, below); a tile a KERNEL, each the smallest sum over
    # the 32 chunks of a 128k prefill (scripts/causal_tile_sweep.py;
    # PERF.md §6, PR 40) — the full layer walks the whole buffer and wants
    # large tiles, the band sees 8192 rows and computes more masked pairs
    # under them; sizes of the program, not options of a request
    prefill_chunk_tokens: int = 4096
    attn_full_block_q: int = 2048
    attn_full_block_k: int = 2048
    attn_window_block_q: int = 1024
    attn_window_block_k: int = 1024
    expert_tile: int = expert_share.GROUP_TILE

    @classmethod
    def trinity_share(cls) -> "TrinityConfig":
        """Trinity-Large-Preview at its published widths: one chip's share
        of a 16-chip expert group (experts 0–15 of 256, an eighth of the
        vocabulary), published layers 5–9: the last dense layer and one
        whole window/full period of expert layers."""
        return cls()

    @classmethod
    def tiny(cls, **kw) -> "TrinityConfig":
        """The CPU tests' size, float32: every mechanism, small widths, 3
        query heads a key/value head, a window (and so a chunk) the tests
        outrun many times, a router wider than the experts held, a chunk
        that sits exactly at the grouped form's edge as the served one, a
        full-layer tile (two q blocks under a K block LONGER than the
        chunk) and a band tile (one q block over K blocks of half a chunk)
        that differ as the served ones do."""
        base = dict(
            hidden_size=32, intermediate_size=48, num_attention_heads=6,
            num_key_value_heads=2, head_dim=8, sliding_window=8,
            max_position_embeddings=96, router_experts=16, num_experts=4,
            num_experts_per_tok=2, moe_intermediate_size=16, vocab_size=64,
            dtype="float32", prefill_chunk_tokens=8, attn_full_block_q=4,
            attn_full_block_k=16, attn_window_block_q=8,
            attn_window_block_k=4, expert_tile=2)
        return cls(**{**base, **kw})

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types names every kept layer's kind")
        if self.prefill_chunk_tokens != self.sliding_window:
            raise ValueError(
                "a window layer's ring is written a chunk at a time: the "
                "chunk is the window")

    @property
    def model(self) -> LLMModel:
        return MODEL

    def is_full(self, i: int) -> bool:
        return self.layer_types[i] == FULL

    def is_moe(self, i: int) -> bool:
        return i >= self.num_dense_layers

    @property
    def moe_layers(self) -> list[int]:
        return [i for i in range(self.num_hidden_layers) if self.is_moe(i)]

    @property
    def routing(self) -> expert_share.Routing:
        return expert_share.Routing(self.router_experts,
                                    self.num_experts_per_tok, 1, 1,
                                    self.route_scale)

    @property
    def routed_slots_per_token(self) -> int:
        return self.num_experts_per_tok * len(self.moe_layers)

    stream_mixes_per_token = 0        # one residual stream, nothing mixed
    min_prompt_tokens = 1

    @property
    def embed_scale(self) -> float:
        return math.sqrt(self.hidden_size) if self.mup_enabled else 1.0

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


def rope_table(cfg: TrinityConfig) -> dict:
    """``cos`` and ``sin`` of ``p · θ^(−2k/d)`` for every position and
    ``k < d/2``, made in float64 ON THE HOST and held float32: at position
    131 071 a float32 product of the two is off by up to 8e-3 rad."""
    half = cfg.head_dim // 2
    freqs = cfg.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = np.arange(cfg.max_position_embeddings,
                      dtype=np.float64)[:, None] * freqs
    return {"cos": jnp.asarray(np.cos(angle), jnp.float32),
            "sin": jnp.asarray(np.sin(angle), jnp.float32)}


def _shapes(cfg: TrinityConfig) -> dict:
    """Every drawn leaf as ``(shape, dtype name, init)``."""
    D, wd = cfg.hidden_size, cfg.dtype
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    one = _const(1.0)

    def ffn(width):
        return {"w_gu": ((D, 2 * width), wd, _normal()),
                "w_down": ((width, D), wd, _normal())}

    layers = []
    for i in range(cfg.num_hidden_layers):
        layer = {"norm_in": ((D,), "float32", one),
                 "norm_attn_out": ((D,), "float32", one),
                 "norm_mlp_in": ((D,), "float32", one),
                 "norm_mlp_out": ((D,), "float32", one),
                 "attn": {
                     # [q (H·d) | k (G·d) | v (G·d) | gate (H·d)]
                     "w_in": ((D, 2 * (H + G) * d), wd, _normal()),
                     "q_norm": ((d,), "float32", one),
                     "k_norm": ((d,), "float32", one),
                     "w_o": ((H * d, D), wd, _normal())}}
        if cfg.is_moe(i):
            F = cfg.moe_intermediate_size
            layer["moe"] = {
                "w_router": ((D, cfg.router_experts), wd, _normal()),
                "router_bias": ((cfg.router_experts,), "float32",
                                _normal(0.02)),
                "shared": ffn(F),
                "e_gu": ((cfg.num_experts, D, 2 * F), wd, _normal()),
                "e_down": ((cfg.num_experts, F, D), wd, _normal())}
        else:
            layer["ffn"] = ffn(cfg.intermediate_size)
        layers.append(layer)
    # the embedding's std makes h₀ of unit scale AFTER the muP multiplier
    return {"embed": ((cfg.vocab_size, D), wd,
                      _normal(1.0 / cfg.embed_scale)),
            "head": ((cfg.vocab_size, D), wd, _normal(1.0 / math.sqrt(D))),
            "final_norm": ((D,), "float32", one),
            "layers": layers}


def init_trinity(cfg: TrinityConfig, key, abstract: bool = False):
    """The drawn weights and, beside them, the rope table (a leaf, not a
    literal of the programs: 2 × 64 MiB at the published positions)."""
    tree = init_tree(_shapes(cfg), key, abstract)
    rows = (cfg.max_position_embeddings, cfg.head_dim // 2)
    tree["rope"] = {k: jax.ShapeDtypeStruct(rows, jnp.float32)
                    for k in ("cos", "sin")} if abstract else rope_table(cfg)
    return tree


def param_count(cfg: TrinityConfig) -> int:
    return count_params(_shapes(cfg))


# --- pieces shared by prefill and decode -----------------------------------


def _embed(cfg: TrinityConfig, params, ids):
    with device_scope("llm_head"):
        return params["embed"][ids].astype(jnp.float32) * cfg.embed_scale


def _rope(x, cos, sin):
    """Half rotation: ``x`` [T,heads,d] = ``[x₁ | x₂]`` turns to ``[x₁ cos −
    x₂ sin | x₂ cos + x₁ sin]``; ``cos``, ``sin`` [T,d/2]."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rope_rows(params, start, n: int):
    return tuple(jax.lax.dynamic_slice_in_dim(params["rope"][k], start, n)
                 for k in ("cos", "sin"))


def _attn_in(cfg: TrinityConfig, p, x, rope):
    """From the normed rows ``x`` [T,D]: q [T,H,d] and k [T,G,d] (normed
    per head, roped where ``rope`` — a layer's ``(cos, sin)`` rows — is
    given), v [T,G,d] and the raw output gate [T,H·d]."""
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    T = x.shape[0]
    y = _dot(x, p["w_in"], jnp.dtype(cfg.dtype))
    q = rms_norm(y[:, :H * d].reshape(T, H, d), p["q_norm"],
                 cfg.rms_norm_eps)
    k = rms_norm(y[:, H * d:(H + G) * d].reshape(T, G, d), p["k_norm"],
                 cfg.rms_norm_eps)
    v = y[:, (H + G) * d:(H + 2 * G) * d].reshape(T, G, d)
    if rope is not None:
        q, k = _rope(q, *rope), _rope(k, *rope)
    return q, k, v, y[:, (H + 2 * G) * d:]


def _attn_out(cfg: TrinityConfig, p, o, gate):
    """``(o ⊙ σ(gate)) W_o``: the output gate and the projection."""
    o = o.reshape(*o.shape[:-2], -1).astype(jnp.float32) \
        * jax.nn.sigmoid(gate)
    return _dot(o, p["w_o"], jnp.dtype(cfg.dtype))


def _add_normed(h, y, weight, eps: float):
    """``h + RMSNorm(y)``: the sandwich's second norm and the residual."""
    with device_scope("llm_norm"):
        return h + rms_norm(y, weight, eps)


def _rows(a, dtype):
    """Keys or values [T,G,d] as the cache holds them: [G,T,d]."""
    return jnp.swapaxes(a, 0, 1).astype(dtype)


# --- prefill ---------------------------------------------------------------


def empty_cache(cfg: TrinityConfig, max_len: int) -> dict:
    """Per layer a K and a V: a ring of ``sliding_window`` rows for a window
    layer, for a full layer ``max_len`` rows rounded up to the FULL layer's
    K block (its blocked kernel then reads the buffer as it is)."""
    dtype = jnp.dtype(cfg.dtype)
    G, d, bk = cfg.num_key_value_heads, cfg.head_dim, cfg.attn_full_block_k
    rows = [-(-max_len // bk) * bk if cfg.is_full(i) else cfg.sliding_window
            for i in range(cfg.num_hidden_layers)]
    return {"k": [jnp.zeros((G, r, d), dtype) for r in rows],
            "v": [jnp.zeros((G, r, d), dtype) for r in rows]}


def cache_kinds(cfg: TrinityConfig, cache: dict) -> dict:
    def of(full):
        return [cache[k][i] for k in ("k", "v")
                for i in range(cfg.num_hidden_layers)
                if cfg.is_full(i) == full]

    return {"window": of(False), "full": of(True)}


def _ffn(cfg: TrinityConfig, layer, i: int, h, valid):
    """``h + RMSNorm(FFN(RMSNorm(h)))`` for chunk rows ``h`` [C,D];
    ``(h, held, rows)``, the counts None for a dense layer."""
    dtype = jnp.dtype(cfg.dtype)
    x = _pre_norm(h, layer["norm_mlp_in"], cfg.rms_norm_eps)
    held = n_rows = None
    if cfg.is_moe(i):
        m = layer["moe"]
        idx, w = expert_share.route(x, m["w_router"], m["router_bias"],
                                    cfg.routing)
        y, n_rows = expert_share.held_part(
            x, idx, w, m["e_gu"], m["e_down"], cfg.first_expert, dtype,
            cfg.routing, _ACT, valid=valid, tile=cfg.expert_tile)
        with device_scope("llm_shared_ffn"):
            y = y + _swiglu(x, m["shared"], dtype)
        with device_scope("llm_router"):
            real = jnp.where(valid[:, None], idx, -1)
            n_rows = n_rows.astype(jnp.int32)
        held = _count_held(cfg, real)
    else:
        with device_scope("llm_shared_ffn"):
            y = _swiglu(x, layer["ffn"], dtype)
    return _add_normed(h, y, layer["norm_mlp_out"], cfg.rms_norm_eps), \
        held, n_rows


def prefill_chunk(cfg: TrinityConfig, params, cache: dict, ids, start,
                  n_valid, all_logits: bool = False,
                  kernel: str | None = None):
    """``ids`` [C] at positions ``start .. start+C−1`` (``start`` a multiple
    of the window, ``C`` the window — or the whole of a shorter prompt), of
    which the first ``n_valid`` are the prompt's (the rest pad its last
    chunk: they route to no expert, nothing reads what they write into a
    full layer's buffer, and they write NOTHING into a ring). Continues
    from ``cache``. Answers ``(logits, cache, held, rows)`` as
    ``llm_kimi.prefill_chunk``."""
    dtype = jnp.dtype(cfg.dtype)
    C, W = ids.shape[0], cfg.sliding_window
    if C > W:
        raise ValueError(f"a chunk of {C} rows outruns the window {W}")
    scale = cfg.head_dim ** -0.5
    with device_scope("llm_attn"):
        rope = _rope_rows(params, start, C)
        # the ring's rows sit below the chunk's own: before position 0
        # there is none
        lowest = jnp.maximum(W - start, 0)
    with device_scope("llm_router"):
        valid = jnp.arange(C) < n_valid
    cache = {k: list(v) for k, v in cache.items()}
    held, rows = [], []
    h = _embed(cfg, params, ids)
    for i, layer in enumerate(params["layers"]):
        full = cfg.is_full(i)
        x = _pre_norm(h, layer["norm_in"], cfg.rms_norm_eps)
        with device_scope("llm_attn"):
            q, k, v, gate = _attn_in(cfg, layer["attn"], x,
                                     None if full else rope)
            k, v = _rows(k, dtype), _rows(v, dtype)
            if full:
                k, v = (jax.lax.dynamic_update_slice(cache[n][i], a,
                                                     (0, start, 0))
                        for n, a in (("k", k), ("v", v)))
                cache["k"][i], cache["v"][i] = k, v
                o = gqa_attention.causal_chunk(
                    q, k, v, start, scale, dtype, cfg.attn_full_block_q,
                    cfg.attn_full_block_k, kernel=kernel)
            else:
                o = gqa_attention.causal_chunk(
                    q, jnp.concatenate([cache["k"][i], k], axis=1),
                    jnp.concatenate([cache["v"][i], v], axis=1), W, scale,
                    dtype, cfg.attn_window_block_q, cfg.attn_window_block_k,
                    window=W, lowest=lowest, kernel=kernel)
                # the chunk's rows ARE the new ring, where they are the
                # prompt's: slot = position − start
                for n, a in (("k", k), ("v", v)):
                    ring = cache[n][i]
                    cache[n][i] = ring.at[:, :C].set(jnp.where(
                        valid[None, :, None], a, ring[:, :C]))
            y = _attn_out(cfg, layer["attn"], o, gate)
        h = _add_normed(h, y, layer["norm_attn_out"], cfg.rms_norm_eps)
        h, n_held, n_rows = _ffn(cfg, layer, i, h, valid)
        if n_held is not None:
            held.append(n_held)
            rows.append(n_rows)
    with device_scope("llm_head"):
        last = h if all_logits else h[n_valid - 1]
    logits = logits_of(cfg, params, last)
    return logits, cache, _stack_counts(held), _stack_counts(rows)


def prefill(cfg: TrinityConfig, params, ids, max_len: int,
            all_logits: bool = False, kernel: str | None = None):
    """The whole prompt ``ids`` [T], walked in chunks of the window through
    the cache; answers as ``llm_hybrid.prefill``: ``(logits, cache,
    held)``."""
    return chunked_prefill(MODEL, cfg, params, ids, max_len, all_logits,
                           kernel=kernel)[:3]


# --- decode ----------------------------------------------------------------


def decode_step(cfg: TrinityConfig, params, cache: dict, token, pos):
    """One token ``token`` (scalar id) at position ``pos`` through ring
    and buffer; answers as ``llm_hybrid.decode_step``."""
    dtype = jnp.dtype(cfg.dtype)
    W = cfg.sliding_window
    scale = cfg.head_dim ** -0.5
    with device_scope("llm_attn"):
        rope = _rope_rows(params, pos, 1)
    cache = {k: list(v) for k, v in cache.items()}
    held = []
    h = _embed(cfg, params, token)
    for i, layer in enumerate(params["layers"]):
        full = cfg.is_full(i)
        x = _pre_norm(h, layer["norm_in"], cfg.rms_norm_eps)
        with device_scope("llm_attn"):
            q, k, v, gate = _attn_in(cfg, layer["attn"], x[None],
                                     None if full else rope)
            slot = pos if full else pos % W
            k, v = (jax.lax.dynamic_update_slice(
                cache[n][i], _rows(a, dtype), (0, slot, 0))
                for n, a in (("k", k), ("v", v)))
            cache["k"][i], cache["v"][i] = k, v
            # a ring slot j holds a position ≤ pos once j ≤ pos: all of
            # them after the first lap; every position in it is in the band
            o = gqa_attention.step(q[0], k, v,
                                   jnp.arange(k.shape[1]) <= pos, scale,
                                   dtype)
            y = _attn_out(cfg, layer["attn"], o, gate[0])
        h = _add_normed(h, y, layer["norm_attn_out"], cfg.rms_norm_eps)
        x = _pre_norm(h, layer["norm_mlp_in"], cfg.rms_norm_eps)
        if cfg.is_moe(i):
            m = layer["moe"]
            idx, w = expert_share.route(x[None], m["w_router"],
                                        m["router_bias"], cfg.routing)
            y = expert_share.held_part_token(
                x, idx[0], w[0], m["e_gu"], m["e_down"], cfg.first_expert,
                dtype, _ACT)
            with device_scope("llm_shared_ffn"):
                y = y + _swiglu(x[None], m["shared"], dtype)[0]
            held.append(_count_held(cfg, idx))
        else:
            with device_scope("llm_shared_ffn"):
                y = _swiglu(x[None], layer["ffn"], dtype)[0]
        h = _add_normed(h, y, layer["norm_mlp_out"], cfg.rms_norm_eps)
    return logits_of(cfg, params, h), cache, _stack_counts(held)


MODEL = LLMModel(init_trinity, prefill, decode_step, empty_cache, cache_kinds,
                 prefill_chunk)
