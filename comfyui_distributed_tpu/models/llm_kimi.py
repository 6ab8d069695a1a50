"""A third prompt rewriter, for long briefs: multi-head latent attention
with a low-rank query and YaRN-scaled rotary keys on every layer, a prefill
that walks the prompt in chunks THROUGH the latent cache, and routed experts
computed by group.

Pre-norm residual blocks (``h = x + Attn(RMSNorm(x))``, ``y = h +
FFN(RMSNorm(h))``). Attention: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb``
(heads × ``[nope | rope]``); ``[c_kv | k_r] = x W_kva``, ``c =
RMSNorm(c_kv)``; the rope parts turn by YaRN's frequency table
(``ops/latent_attention.yarn_frequencies``), the shared rope key is not
normed, there is no gate; keys and values decompress per head from the
latent, ``[k_nope | v]_h = c W_b,h``; the softmax scale is ``(nope +
rope)^(−½) · mscale²``. The cache is ``c`` and the roped ``k_r``: 576
values a token a layer. The first ``first_k_dense_replace`` layers have a
dense SwiGLU FFN, the rest ``ops/expert_share.py``'s expert layer (sigmoid
scores, a selection bias, one group, this chip's share of the experts)
beside one shared expert. The vocabulary may be a slice.

Three paths share the weights. :func:`prefill_chunk` is the continuation
``llm_prefill`` scans (``llm_model.chunked_prefill``): ``C`` tokens at
positions ``start ..`` write their latents into the cache and attend over
the cache rows up to their own (``mla_chunk_attention``: the prefix
decompressed into a workspace, blocked causal attention — a Pallas kernel
on the chip — so nothing ``T×T`` exists), their FFN intermediates are
``C`` rows tall, and their routed experts take the form the rows call for
(``expert_share.held_part``: grouped at the served chunk). :func:`prefill`
is that scan under ``llm_hybrid.prefill``'s signature. :func:`decode_step`
is one token through the absorbed form (``mla_absorbed_step``), reading
only the held experts it selected. Conventions are ``llm_hybrid.py``'s:
weights held in ``dtype``, products on ``dtype`` operands accumulated in
float32; residual stream, norms, softmax, router scores and logits
float32; one sequence, no batch axis. ``models/llm_kimi_reference.py`` is
the plain float32 statement all three are held to.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..ops import expert_share, latent_attention as mla_ops
from ..telemetry.device_scopes import device_scope
from .llm_hybrid import (_ACT, _const, _count_held, _dot, _embed, _normal,
                         _pre_norm, _stack_counts, _swiglu, count_params,
                         init_tree, logits_of, rms_norm)
from .llm_model import LLMModel, chunked_prefill


@dataclasses.dataclass(frozen=True)
class KimiConfig:
    """Field names are the published ``config.json``'s. ``n_routed_experts``
    is how many experts are HELD here (``router_experts`` is the layer's
    count, the router's width), ``vocab_size`` how many rows of the
    vocabulary, ``num_hidden_layers`` the depth kept."""
    hidden_size: int = 7168
    num_hidden_layers: int = 5
    first_k_dense_replace: int = 1
    intermediate_size: int = 18432
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 50000.0
    rope_factor: float = 64.0
    rope_original_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rms_norm_eps: float = 1e-5
    router_experts: int = 384
    n_routed_experts: int = 12
    first_expert: int = 0
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.827
    moe_intermediate_size: int = 2048
    vocab_size: int = 20480
    dtype: str = "bfloat16"
    # the schedule of the chunked prefill, fixed here by measurement
    # (PERF.md §6, PR 32; the tile the smallest sum over the 8 chunks of a
    # 32k prefill, PR 40: this kernel's two logit products want a taller q
    # tile and NOT a longer K tile); sizes of the program, not options of
    # a request
    prefill_chunk_tokens: int = 4096
    attn_block_q: int = 2048
    attn_block_k: int = 1024
    expert_tile: int = expert_share.GROUP_TILE

    @classmethod
    def kimi_share(cls) -> "KimiConfig":
        """Kimi-K2.6's language model at its published widths: one chip's
        share of a 32-chip expert group (experts 0–11 of 384, an eighth of
        the vocabulary), the dense layer and four expert layers."""
        return cls()

    @classmethod
    def tiny(cls, **kw) -> "KimiConfig":
        """The CPU tests' size, float32: every mechanism, small widths, a
        YaRN table whose original length the tests outrun, chunks, blocks
        and tiles a test prompt spans several of."""
        base = dict(
            hidden_size=32, intermediate_size=48, num_attention_heads=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8, rope_factor=8.0,
            rope_original_len=16, router_experts=16, n_routed_experts=4,
            num_experts_per_tok=4, moe_intermediate_size=16, vocab_size=64,
            dtype="float32", prefill_chunk_tokens=16, attn_block_q=8,
            attn_block_k=8, expert_tile=4)
        return cls(**{**base, **kw})

    @property
    def model(self) -> LLMModel:
        return MODEL

    def is_moe(self, i: int) -> bool:
        return i >= self.first_k_dense_replace

    @property
    def moe_layers(self) -> list[int]:
        return [i for i in range(self.num_hidden_layers) if self.is_moe(i)]

    @property
    def num_experts(self) -> int:          # held, as the other two call it
        return self.n_routed_experts

    @property
    def routing(self) -> expert_share.Routing:
        return expert_share.Routing(
            self.router_experts, self.num_experts_per_tok, 1, 1,
            self.routed_scaling_factor)

    @property
    def routed_slots_per_token(self) -> int:
        return self.num_experts_per_tok * len(self.moe_layers)

    stream_mixes_per_token = 0        # one residual stream, nothing mixed
    min_prompt_tokens = 1

    @property
    def rope_freqs(self):
        return mla_ops.yarn_frequencies(
            self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
            self.rope_original_len, self.rope_beta_fast,
            self.rope_beta_slow)

    @property
    def softmax_scale(self) -> float:
        m = mla_ops.yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return m * m / math.sqrt(self.qk_nope_head_dim
                                 + self.qk_rope_head_dim)


# --- weights ---------------------------------------------------------------


def _shapes(cfg: KimiConfig) -> dict:
    """Every leaf as ``(shape, dtype name, init)``."""
    D, H, wd = cfg.hidden_size, cfg.num_attention_heads, cfg.dtype
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    one = _const(1.0)

    def ffn(width):
        return {"w_gu": ((D, 2 * width), wd, _normal()),
                "w_down": ((width, D), wd, _normal())}

    layers = []
    for i in range(cfg.num_hidden_layers):
        layer = {"norm1": ((D,), "float32", one),
                 "norm2": ((D,), "float32", one),
                 "attn": {
                     # [c_q (r_q) | c_kv (rank) | k_rope]: W_qa and W_kva
                     "w_a": ((D, cfg.q_lora_rank + cfg.kv_lora_rank + rope),
                             wd, _normal()),
                     "q_norm": ((cfg.q_lora_rank,), "float32", one),
                     "c_norm": ((cfg.kv_lora_rank,), "float32", one),
                     # [every head's nope | every head's rope]
                     "w_qb": ((cfg.q_lora_rank, H * (nope + rope)), wd,
                              _normal()),
                     # per head [k_nope | v]
                     "w_b": ((cfg.kv_lora_rank, H * (nope + cfg.v_head_dim)),
                             wd, _normal()),
                     "w_o": ((H * cfg.v_head_dim, D), wd, _normal())}}
        if cfg.is_moe(i):
            F = cfg.moe_intermediate_size
            layer["moe"] = {
                "w_router": ((D, cfg.router_experts), wd, _normal()),
                "router_bias": ((cfg.router_experts,), "float32",
                                _normal(0.02)),
                "shared": ffn(F),
                "e_gu": ((cfg.n_routed_experts, D, 2 * F), wd, _normal()),
                "e_down": ((cfg.n_routed_experts, F, D), wd, _normal())}
        else:
            layer["ffn"] = ffn(cfg.intermediate_size)
        layers.append(layer)
    return {"embed": ((cfg.vocab_size, D), wd, _normal(1.0)),
            "head": ((cfg.vocab_size, D), wd, _normal(1.0 / math.sqrt(D))),
            "final_norm": ((D,), "float32", one),
            "layers": layers}


def init_kimi(cfg: KimiConfig, key, abstract: bool = False):
    return init_tree(_shapes(cfg), key, abstract)


def param_count(cfg: KimiConfig) -> int:
    return count_params(_shapes(cfg))


# --- pieces shared by prefill and decode -----------------------------------


def _split_in(cfg: KimiConfig, p, y, positions):
    """From ``x W_a`` [T,·]: the queries ``[T,H,nope]`` and (roped)
    ``[T,H,rope]``, the normed latent and the roped shared key."""
    rq, rank = cfg.q_lora_rank, cfg.kv_lora_rank
    H, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    dtype = jnp.dtype(cfg.dtype)
    T = y.shape[0]
    q = _dot(rms_norm(y[:, :rq], p["q_norm"], cfg.rms_norm_eps), p["w_qb"],
             dtype)
    c = rms_norm(y[:, rq:rq + rank], p["c_norm"], cfg.rms_norm_eps)
    freqs = cfg.rope_freqs
    kr = mla_ops.rope_interleaved(y[:, rq + rank:], positions,
                                  cfg.rope_theta, freqs)
    q_rope = mla_ops.rope_interleaved(q[:, H * nope:].reshape(T, H, -1),
                                      positions, cfg.rope_theta, freqs)
    return q[:, :H * nope].reshape(T, H, nope), q_rope, c, kr


def _attn_out(p, o, dtype):
    return _dot(o.reshape(*o.shape[:-2], -1), p["w_o"], dtype)


# --- prefill ---------------------------------------------------------------


def empty_cache(cfg: KimiConfig, max_len: int) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    n = cfg.num_hidden_layers
    return {"c": [jnp.zeros((max_len, cfg.kv_lora_rank), dtype)] * n,
            "kr": [jnp.zeros((max_len, cfg.qk_rope_head_dim), dtype)] * n}


def cache_kinds(cfg: KimiConfig, cache: dict) -> dict:
    return {"full": [cache["c"], cache["kr"]]}


def prefill_chunk(cfg: KimiConfig, params, cache: dict, ids, start, n_valid,
                  all_logits: bool = False, kernel: str | None = None):
    """``ids`` [C] at positions ``start .. start+C−1``, of which the first
    ``n_valid`` are the prompt's (the rest pad its last chunk: they route
    to no expert and nothing reads what they write). Continues from
    ``cache`` — every row below ``start`` as earlier chunks left it.
    Answers ``(logits, cache, held, rows)``: the logits of row ``n_valid −
    1`` [V] (of every row [C,V] with ``all_logits``), and per expert layer
    the routed slots that fell on held experts and the rows the experts'
    form multiplied for them."""
    dtype = jnp.dtype(cfg.dtype)
    C = ids.shape[0]
    with device_scope("llm_attn"):
        positions = start + jnp.arange(C)
    with device_scope("llm_router"):
        valid = jnp.arange(C) < n_valid
    cache = {k: list(v) for k, v in cache.items()}
    held, rows = [], []
    h = _embed(params, ids)
    for i, layer in enumerate(params["layers"]):
        p = layer["attn"]
        x = _pre_norm(h, layer["norm1"], cfg.rms_norm_eps)
        with device_scope("llm_attn"):
            q_nope, q_rope, c, kr = _split_in(
                cfg, p, _dot(x, p["w_a"], dtype), positions)
            cache["c"][i] = jax.lax.dynamic_update_slice(
                cache["c"][i], c.astype(dtype), (start, 0))
            cache["kr"][i] = jax.lax.dynamic_update_slice(
                cache["kr"][i], kr.astype(dtype), (start, 0))
        o = mla_ops.mla_chunk_attention(                   # cdt.llm_attn
            q_nope, q_rope, cache["c"][i], cache["kr"][i], start, p["w_b"],
            cfg.softmax_scale, dtype, cfg.attn_block_q, cfg.attn_block_k,
            kernel)
        with device_scope("llm_attn"):
            h = h + _attn_out(p, o, dtype)
        x = _pre_norm(h, layer["norm2"], cfg.rms_norm_eps)
        if cfg.is_moe(i):
            m = layer["moe"]
            idx, w = expert_share.route(x, m["w_router"], m["router_bias"],
                                        cfg.routing)
            y, n_rows = expert_share.held_part(
                x, idx, w, m["e_gu"], m["e_down"], cfg.first_expert, dtype,
                cfg.routing, _ACT, valid=valid, tile=cfg.expert_tile)
            with device_scope("llm_shared_ffn"):
                h = h + y + _swiglu(x, m["shared"], dtype)
            with device_scope("llm_router"):
                real = jnp.where(valid[:, None], idx, -1)
                n_rows = n_rows.astype(jnp.int32)
            held.append(_count_held(cfg, real))
            rows.append(n_rows)
        else:
            with device_scope("llm_shared_ffn"):
                h = h + _swiglu(x, layer["ffn"], dtype)
    with device_scope("llm_head"):
        last = h if all_logits else h[n_valid - 1]
    logits = logits_of(cfg, params, last)
    return logits, cache, _stack_counts(held), _stack_counts(rows)


def prefill(cfg: KimiConfig, params, ids, max_len: int,
            all_logits: bool = False, chunk: int | None = None,
            kernel: str | None = None):
    """The whole prompt ``ids`` [T], walked in chunks through the cache;
    answers as ``llm_hybrid.prefill``: ``(logits, cache, held)``."""
    return chunked_prefill(MODEL, cfg, params, ids, max_len, all_logits,
                           chunk, kernel=kernel)[:3]


# --- decode ----------------------------------------------------------------


def decode_step(cfg: KimiConfig, params, cache: dict, token, pos):
    """One token ``token`` (scalar id) at position ``pos`` through the
    cache; answers as ``llm_hybrid.decode_step``."""
    dtype = jnp.dtype(cfg.dtype)
    with device_scope("llm_attn"):
        positions = jnp.reshape(pos, (1,))
    cache = {k: list(v) for k, v in cache.items()}
    held = []
    h = _embed(params, token)
    for i, layer in enumerate(params["layers"]):
        p = layer["attn"]
        x = _pre_norm(h, layer["norm1"], cfg.rms_norm_eps)
        with device_scope("llm_attn"):
            q_nope, q_rope, c, kr = _split_in(
                cfg, p, _dot(x[None], p["w_a"], dtype), positions)
            cache["c"][i] = jax.lax.dynamic_update_slice(
                cache["c"][i], c.astype(dtype), (pos, 0))
            cache["kr"][i] = jax.lax.dynamic_update_slice(
                cache["kr"][i], kr.astype(dtype), (pos, 0))
            o = mla_ops.mla_absorbed_step(
                q_nope[0], q_rope[0], cache["c"][i], cache["kr"][i], pos,
                p["w_b"], cfg.softmax_scale, dtype)
            h = h + _attn_out(p, o, dtype)
        x = _pre_norm(h, layer["norm2"], cfg.rms_norm_eps)
        if cfg.is_moe(i):
            m = layer["moe"]
            idx, w = expert_share.route(x[None], m["w_router"],
                                        m["router_bias"], cfg.routing)
            y = expert_share.held_part_token(
                x, idx[0], w[0], m["e_gu"], m["e_down"], cfg.first_expert,
                dtype, _ACT)
            with device_scope("llm_shared_ffn"):
                h = h + y + _swiglu(x[None], m["shared"], dtype)[0]
            held.append(_count_held(cfg, idx))
        else:
            with device_scope("llm_shared_ffn"):
                h = h + _swiglu(x[None], layer["ffn"], dtype)[0]
    return logits_of(cfg, params, h), cache, _stack_counts(held)


def decode_weights(cfg: KimiConfig, params):
    """``params`` as a token loop hands them to every :func:`decode_step`:
    each layer's ``w_b`` in the absorbed step's form, made once ahead of
    the loop (``latent_attention.absorbed_form``)."""
    def formed(p):
        return {**p, "w_b": mla_ops.absorbed_form(p["w_b"],
                                                  cfg.num_attention_heads)}

    return {**params, "layers": [{**layer, "attn": formed(layer["attn"])}
                                 for layer in params["layers"]]}


MODEL = LLMModel(init_kimi, prefill, decode_step, empty_cache, cache_kinds,
                 prefill_chunk, decode_weights)
