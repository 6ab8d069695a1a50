"""An eighth prompt rewriter, for long briefs: multi-head latent attention
whose every query reads only the ``index_topk`` keys a learned INDEXER picks
for it, a prefill that walks the prompt in chunks through the latent cache
and an index-key cache beside it, and routed experts computed by group.

Pre-norm residual blocks as ``llm_kimi.py``'s, and the same latent
attention up to its widths (a value wider than a nope key, plain rope at
``rope_theta``, scale ``(nope + rope)^(−½)``): ``c_q = RMSNorm(x W_qa)``,
``q = c_q W_qb``; ``[c_kv | k_r] = x W_kva``, ``c = RMSNorm(c_kv)``;
``[k_nope | v]_h = c W_b,h``. **The indexer** of a layer: ``q_I = c_q
W_Iq`` (``index_n_heads`` × ``index_head_dim``), ``k_I = LayerNorm(x
W_Ik)`` (weight and bias), the first ``qk_rope_head_dim`` of each turned by
the same interleaved rope, ``w = x W_Iw · index_n_heads^(−½) ·
index_head_dim^(−½)`` (float32); ``I[t,s] = Σ_j w[t,j] · ReLU(q_I[t,j] ·
k_I[s])`` for ``s ≤ t``; the query at ``t`` attends — all heads alike —
over the ``min(index_topk, t + 1)`` positions of largest ``I``, ties to the
lower position. There is ONE attention path: a position below
``index_topk`` reads its whole prefix by the same rule. The Hadamard
rotation and fp8 storage the family gives ``q_I``/``k_I`` are a
quantisation aid and are left out (index keys held in ``dtype``). The
first ``first_k_dense_replace`` layers have a dense SwiGLU FFN, the rest
``ops/expert_share.py``'s expert layer beside one shared expert; the
vocabulary may be a slice. No multi-token-prediction module.

The cache is TWO kinds of leaf a layer, written together and read by
different parts of a program: the latent rows ``c`` and roped ``k_rope``
(``latent``: what attention reads, of the rows a query kept) and the index
key ``k_I`` (``index``: what the scorer reads, of every row below the
query). :func:`prefill_chunk` is the continuation ``llm_prefill`` scans
(``llm_model.chunked_prefill``): scores, an exact selection as a mask and
blocked attention under it (``ops/index_select_attention.py``), each under
a named scope of its own below ``cdt.llm_attn`` (``llm_index``,
``llm_select``, ``llm_sparse_attn``). :func:`decode_step` is one token:
``lax.top_k`` of its scores, the kept latent rows gathered, ``W_b``
absorbed. Conventions are ``llm_hybrid.py``'s: weights held in ``dtype``,
products on ``dtype`` operands accumulated in float32; residual stream,
norms, rope, softmax, router scores, ``w``, the index scores, the
selection and logits float32; the cache rows ``dtype``.
``models/llm_glm_reference.py`` is the plain float32 statement.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import expert_share, index_select_attention as index_ops
from ..ops import latent_attention as mla_ops
from ..telemetry.device_scopes import device_scope
from .llm_hybrid import (_ACT, _const, _count_held, _dot, _embed, _normal,
                         _pre_norm, _stack_counts, _swiglu, count_params,
                         init_tree, logits_of, rms_norm)
from .llm_kimi import _attn_out, _split_in
from .llm_model import LLMModel, chunked_prefill


@dataclasses.dataclass(frozen=True)
class GlmConfig:
    """Field names are the published ``config.json``'s. ``n_routed_experts``
    is how many experts are HELD here (``router_experts`` is the layer's
    count, the router's width), ``vocab_size`` how many rows of the
    vocabulary, ``num_hidden_layers`` / ``first_k_dense_replace`` the depth
    kept."""
    hidden_size: int = 6144
    num_hidden_layers: int = 5
    first_k_dense_replace: int = 1
    intermediate_size: int = 12288
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-5
    index_norm_eps: float = 1e-6
    router_experts: int = 256
    n_routed_experts: int = 8
    first_expert: int = 0
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    moe_intermediate_size: int = 2048
    vocab_size: int = 19360
    dtype: str = "bfloat16"
    # the schedule of the chunked prefill: sizes of the program, not options
    # of a request — the chunk, and how many of its queries score and select
    # at once (what bounds the float32 [rows, cache rows] scores); the
    # kernels' tiles are the ops' own constants
    prefill_chunk_tokens: int = 4096
    select_rows: int = 1024
    expert_tile: int = expert_share.GROUP_TILE

    @classmethod
    def glm_share(cls) -> "GlmConfig":
        """GLM-5's language model at its published widths: one chip's share
        of a 32-chip expert group (experts 0–7 of 256, an eighth of the
        vocabulary), one dense layer and four expert layers."""
        return cls()

    @classmethod
    def tiny(cls, **kw) -> "GlmConfig":
        """The CPU tests' size, float32: every mechanism, small widths, a
        value wider than a nope key, an ``index_topk`` a test prompt
        outruns, chunks and tiles a test prompt spans several of."""
        base = dict(
            hidden_size=32, intermediate_size=48, num_attention_heads=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4,
            index_head_dim=16, index_topk=12, router_experts=16,
            n_routed_experts=4, num_experts_per_tok=4,
            moe_intermediate_size=16, vocab_size=64, dtype="float32",
            prefill_chunk_tokens=16, select_rows=8, expert_tile=4)
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
    def num_experts(self) -> int:          # held, as the others call it
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
    rope_freqs = None                 # plain rope at rope_theta: no table

    @property
    def softmax_scale(self) -> float:
        return 1.0 / math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim)

    @property
    def index_weight_scale(self) -> float:
        return 1.0 / math.sqrt(self.index_n_heads * self.index_head_dim)

    def attended_keys(self, prompt_tokens: int, new_tokens: int) -> dict:
        """(query, key) pairs ONE head attends in a request, by phase,
        summed over the layers (every one selects): ``min(index_topk, t +
        1)`` a query at position ``t``."""
        read = np.minimum(np.arange(prompt_tokens + new_tokens,
                                    dtype=np.int64) + 1, self.index_topk)
        n = self.num_hidden_layers
        return {("sparse", "prefill"): n * int(read[:prompt_tokens].sum()),
                ("sparse", "decode"): n * int(read[prompt_tokens:].sum())}

    def select_columns(self, prompt_tokens: int, new_tokens: int) -> dict:
        """Columns the prefill's selection steps visit in a request and
        the columns whole cache rows would be (``searched``, ``cache``),
        summed over the layers: ``index_select_attention.select_columns``."""
        one = index_ops.select_columns(prompt_tokens, new_tokens,
                                       self.prefill_chunk_tokens,
                                       self.select_rows)
        return {kind: self.num_hidden_layers * n for kind, n in one.items()}


# --- weights ---------------------------------------------------------------


def _shapes(cfg: GlmConfig) -> dict:
    """Every leaf as ``(shape, dtype name, init)``."""
    D, H, wd = cfg.hidden_size, cfg.num_attention_heads, cfg.dtype
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    J, di = cfg.index_n_heads, cfg.index_head_dim
    one, zero = _const(1.0), _const(0.0)

    def ffn(width):
        return {"w_gu": ((D, 2 * width), wd, _normal()),
                "w_down": ((width, D), wd, _normal())}

    layers = []
    for i in range(cfg.num_hidden_layers):
        layer = {"norm1": ((D,), "float32", one),
                 "norm2": ((D,), "float32", one),
                 # llm_kimi's leaves under llm_kimi's names
                 "attn": {
                     "w_a": ((D, cfg.q_lora_rank + cfg.kv_lora_rank + rope),
                             wd, _normal()),
                     "q_norm": ((cfg.q_lora_rank,), "float32", one),
                     "c_norm": ((cfg.kv_lora_rank,), "float32", one),
                     "w_qb": ((cfg.q_lora_rank, H * (nope + rope)), wd,
                              _normal()),
                     "w_b": ((cfg.kv_lora_rank, H * (nope + cfg.v_head_dim)),
                             wd, _normal()),
                     "w_o": ((H * cfg.v_head_dim, D), wd, _normal())},
                 "indexer": {
                     "w_q": ((cfg.q_lora_rank, J * di), wd, _normal()),
                     # [k_I (di) | w (J)]: W_Ik and W_Iw, both read from x
                     "w_kw": ((D, di + J), wd, _normal()),
                     "k_norm": ((di,), "float32", one),
                     "k_bias": ((di,), "float32", zero)}}
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


def init_glm(cfg: GlmConfig, key, abstract: bool = False):
    return init_tree(_shapes(cfg), key, abstract)


def param_count(cfg: GlmConfig) -> int:
    return count_params(_shapes(cfg))


# --- pieces shared by prefill and decode -----------------------------------


def layer_norm(x, weight, bias, eps: float):
    x = x.astype(jnp.float32)
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def _rope_head(cfg: GlmConfig, x, positions):
    """The first ``qk_rope_head_dim`` of the last axis turned (interleaved
    pairs), the rest as they are. ``x`` [T,...,d]."""
    r = cfg.qk_rope_head_dim
    turned = mla_ops.rope_interleaved(x[..., :r], positions, cfg.rope_theta)
    return jnp.concatenate([turned, x[..., r:].astype(jnp.float32)], -1)


def _index_in(cfg: GlmConfig, p, a, y, x, positions):
    """The indexer's side of a layer's input: from ``y = x W_a`` (its
    ``c_q`` columns normed again as ``_split_in`` norms them: one value, the
    compiler keeps one) and the normed rows ``x`` [T,D]: ``q_I`` [T,J,d]
    and ``k_I`` [T,d] (roped), ``w`` [T,J] float32."""
    dtype = jnp.dtype(cfg.dtype)
    J, di, T = cfg.index_n_heads, cfg.index_head_dim, y.shape[0]
    c_q = rms_norm(y[:, :cfg.q_lora_rank], a["q_norm"], cfg.rms_norm_eps)
    q_i = _rope_head(cfg, _dot(c_q, p["w_q"], dtype).reshape(T, J, di),
                     positions)
    kw = _dot(x, p["w_kw"], dtype)
    k_i = _rope_head(cfg, layer_norm(kw[:, :di], p["k_norm"], p["k_bias"],
                                     cfg.index_norm_eps), positions)
    return q_i, k_i, kw[:, di:] * cfg.index_weight_scale


def _write(buffer, rows, at):
    return jax.lax.dynamic_update_slice(buffer, rows.astype(buffer.dtype),
                                        (at, 0))


# --- prefill ---------------------------------------------------------------


def empty_cache(cfg: GlmConfig, max_len: int) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    n = cfg.num_hidden_layers
    return {"c": [jnp.zeros((max_len, cfg.kv_lora_rank), dtype)] * n,
            "kr": [jnp.zeros((max_len, cfg.qk_rope_head_dim), dtype)] * n,
            "ki": [jnp.zeros((max_len, cfg.index_head_dim), dtype)] * n}


def cache_kinds(cfg: GlmConfig, cache: dict) -> dict:
    return {"latent": [cache["c"], cache["kr"]], "index": cache["ki"]}


def _selected_attention(cfg: GlmConfig, p, q_nope, q_rope, q_i, w, c, kr, ki,
                        start, kernel, keep_masks: bool):
    """The three new pieces for a chunk whose rows the caches ``c``,
    ``kr``, ``ki`` [S,·] already hold: ``select_rows`` queries score and
    select at a time, then the chunk attends under the whole mask. Answers
    ``(o [C,H,v], the mask [C,S] int8 or None)``."""
    dtype, C = jnp.dtype(cfg.dtype), q_nope.shape[0]
    S = -(-c.shape[0] // C) * C          # whole chunks: the kernels' tiles
    c, kr, ki = (jnp.pad(a, ((0, S - a.shape[0]), (0, 0)))
                 for a in (c, kr, ki))
    n = math.gcd(C, cfg.select_rows)

    def some(xs):
        q_n, w_n, first = xs
        with jax.named_scope("llm_index"):
            scores = index_ops.index_scores(q_n, w_n, ki, first, dtype,
                                            kernel)
        with jax.named_scope("llm_select"):
            # the barrier keeps the kernel a call of its own: fused into the
            # write of its rows it loses its VMEM limit (16 MiB, ~60 needed).
            # ``first`` is all the kernel needs to stop at the columns these
            # rows can see (PR 54)
            return jax.lax.optimization_barrier(index_ops.select_keep(
                scores, first, cfg.index_topk, kernel))

    keep = jax.lax.map(some, (q_i.reshape(C // n, n, *q_i.shape[1:]),
                              w.reshape(C // n, n, -1),
                              start + jnp.arange(C // n) * n)).reshape(C, S)
    with jax.named_scope("llm_sparse_attn"):
        o = index_ops.masked_chunk_attention(
            q_nope, q_rope, c, kr, keep, start, p["w_b"], cfg.softmax_scale,
            dtype, kernel)
    return o, (keep if keep_masks else None)


def prefill_chunk(cfg: GlmConfig, params, cache: dict, ids, start, n_valid,
                  all_logits: bool = False, kernel: str | None = None,
                  keep_masks: bool = False):
    """``ids`` [C] at positions ``start .. start+C−1``, of which the first
    ``n_valid`` are the prompt's (the rest pad its last chunk: they route
    to no expert and nothing reads what they write). Continues from
    ``cache``. Answers ``(logits, cache, held, rows)`` as
    ``llm_kimi.prefill_chunk``. ``kernel`` names the form of the three
    attention kernels (``pallas``, ``interpret``, ``lax``; None: the
    platform's). ``keep_masks`` (a parity tool's) appends every layer's
    selection ``[C, cache rows in whole chunks]`` int8."""
    dtype = jnp.dtype(cfg.dtype)
    C = ids.shape[0]
    with device_scope("llm_attn"):
        positions = start + jnp.arange(C)
    with device_scope("llm_router"):
        valid = jnp.arange(C) < n_valid
    cache = {k: list(v) for k, v in cache.items()}
    held, rows, masks = [], [], []
    h = _embed(params, ids)
    for i, layer in enumerate(params["layers"]):
        p = layer["attn"]
        x = _pre_norm(h, layer["norm1"], cfg.rms_norm_eps)
        with device_scope("llm_attn"):
            y = _dot(x, p["w_a"], dtype)
            q_nope, q_rope, c, kr = _split_in(cfg, p, y, positions)
            q_i, k_i, w = _index_in(cfg, layer["indexer"], p, y, x,
                                    positions)
            cache["c"][i] = _write(cache["c"][i], c, start)
            cache["kr"][i] = _write(cache["kr"][i], kr, start)
            cache["ki"][i] = _write(cache["ki"][i], k_i, start)
            o, keep = _selected_attention(
                cfg, p, q_nope, q_rope, q_i, w, cache["c"][i],
                cache["kr"][i], cache["ki"][i], start, kernel, keep_masks)
            masks.append(keep)
            h = h + _attn_out(p, o, dtype)
        x = _pre_norm(h, layer["norm2"], cfg.rms_norm_eps)
        if cfg.is_moe(i):
            m = layer["moe"]
            idx, wts = expert_share.route(x, m["w_router"], m["router_bias"],
                                          cfg.routing)
            y, n_rows = expert_share.held_part(
                x, idx, wts, m["e_gu"], m["e_down"], cfg.first_expert, dtype,
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
    out = (logits_of(cfg, params, last), cache, _stack_counts(held),
           _stack_counts(rows))
    return out + (masks,) if keep_masks else out


def prefill(cfg: GlmConfig, params, ids, max_len: int,
            all_logits: bool = False, chunk: int | None = None,
            kernel: str | None = None):
    """The whole prompt ``ids`` [T], walked in chunks through the cache;
    answers as ``llm_hybrid.prefill``: ``(logits, cache, held)``."""
    return chunked_prefill(MODEL, cfg, params, ids, max_len, all_logits,
                           chunk, kernel=kernel)[:3]


# --- decode ----------------------------------------------------------------


def decode_step(cfg: GlmConfig, params, cache: dict, token, pos,
                keep_rows: bool = False):
    """One token ``token`` (scalar id) at position ``pos`` through the
    caches; answers as ``llm_hybrid.decode_step``. ``keep_rows`` (a parity
    tool's) appends every layer's ``(rows, valid)``."""
    dtype = jnp.dtype(cfg.dtype)
    with device_scope("llm_attn"):
        positions = jnp.reshape(pos, (1,))
    cache = {k: list(v) for k, v in cache.items()}
    held, kept = [], []
    h = _embed(params, token)
    for i, layer in enumerate(params["layers"]):
        p = layer["attn"]
        x = _pre_norm(h, layer["norm1"], cfg.rms_norm_eps)
        with device_scope("llm_attn"):
            y = _dot(x[None], p["w_a"], dtype)
            q_nope, q_rope, c, kr = _split_in(cfg, p, y, positions)
            q_i, k_i, w = _index_in(cfg, layer["indexer"], p, y, x[None],
                                    positions)
            cache["c"][i] = _write(cache["c"][i], c, pos)
            cache["kr"][i] = _write(cache["kr"][i], kr, pos)
            cache["ki"][i] = _write(cache["ki"][i], k_i, pos)
            with jax.named_scope("llm_index"):
                chosen, real = index_ops.index_step(
                    q_i[0], w[0], cache["ki"][i], pos, cfg.index_topk, dtype)
            kept.append((chosen, real))
            with jax.named_scope("llm_sparse_attn"):
                o = index_ops.absorbed_rows_step(
                    q_nope[0], q_rope[0], cache["c"][i][chosen],
                    cache["kr"][i][chosen], real, p["w_b"],
                    cfg.softmax_scale, dtype)
            h = h + _attn_out(p, o, dtype)
        x = _pre_norm(h, layer["norm2"], cfg.rms_norm_eps)
        if cfg.is_moe(i):
            m = layer["moe"]
            idx, wts = expert_share.route(x[None], m["w_router"],
                                          m["router_bias"], cfg.routing)
            y = expert_share.held_part_token(
                x, idx[0], wts[0], m["e_gu"], m["e_down"], cfg.first_expert,
                dtype, _ACT)
            with device_scope("llm_shared_ffn"):
                h = h + y + _swiglu(x[None], m["shared"], dtype)[0]
            held.append(_count_held(cfg, idx))
        else:
            with device_scope("llm_shared_ffn"):
                h = h + _swiglu(x[None], layer["ffn"], dtype)[0]
    out = (logits_of(cfg, params, h), cache, _stack_counts(held))
    return out + (kept,) if keep_rows else out


def decode_weights(cfg: GlmConfig, params):
    """``params`` as a token loop hands them to every :func:`decode_step`:
    each layer's ``w_b`` in the absorbed step's form, made once ahead of
    the loop (``latent_attention.absorbed_form``)."""
    def formed(p):
        return {**p, "w_b": mla_ops.absorbed_form(p["w_b"],
                                                  cfg.num_attention_heads)}

    return {**params, "layers": [{**layer, "attn": formed(layer["attn"])}
                                 for layer in params["layers"]]}


MODEL = LLMModel(init_glm, prefill, decode_step, empty_cache, cache_kinds,
                 prefill_chunk, decode_weights)
