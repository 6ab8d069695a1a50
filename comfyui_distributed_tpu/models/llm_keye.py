"""A ninth prompt rewriter, for long briefs: grouped-query attention whose
every query reads only the ``topk`` keys a learned INDEXER picks for it —
the K/V rows themselves, no latent —, a prefill that walks the prompt in
chunks through the K/V cache and an index-key cache beside it, and an expert
layer that holds EVERY expert of its router.

Pre-norm residual blocks, ``h += Attn(RMSNorm(h))``, ``h +=
Experts(RMSNorm(h))``; a final RMS norm and an untied head over the whole
vocabulary. **Attention**: ``[q | k | v] = x W_in`` — ``num_attention_heads``
query heads over ``num_key_value_heads`` key and value heads of
``head_dim``, no bias, no gate; q and k RMS-normed per head (one weight of
``head_dim`` each) and turned by rope (half rotation over the whole head,
``llm_trinity``'s, the angles from a float64 table made on the host); query
head ``h`` reads K/V head ``h // (heads / kv heads)``; scale
``head_dim^−½``. **The indexer** of a layer: ``q_I = x W_Iq``
(``indexer_num_heads`` × ``indexer_head_dim``, from the normed stream),
``k_I = LayerNorm(x W_Ik)`` (weight and bias; one index key a token), both
turned by the same rope over their whole width, ``w = x W_Iw ·
indexer_num_heads^(−½) · indexer_head_dim^(−½)`` (float32); ``I[t,s] = Σ_j
w[t,j] · ReLU(q_I[t,j] · k_I[s])`` for ``s ≤ t``; the query at ``t`` attends
— all heads alike — over the ``min(topk, t + 1)`` positions of largest
``I``, ties to the lower position. There is ONE attention path: a position
below ``topk`` reads its whole prefix by the same rule. The Hadamard
rotation and fp8 storage the family gives ``q_I``/``k_I`` are a quantisation
aid and are left out (index keys held in ``dtype``). **Experts**, every
layer: a softmax router over ``router_experts`` with no bias, the top
``num_experts_per_tok`` normalised, no shared expert, through
``ops/expert_share.py`` told which experts it holds — here all of them.

The cache is TWO kinds of leaf a layer: the row ``[k | v]`` of a position
(``kv``: what attention reads, of the rows a query kept) and the index key
``k_I`` (``index``: what the scorer reads, of every row below the query).
:func:`prefill_chunk` is the continuation ``llm_prefill`` scans
(``llm_model.chunked_prefill``): scores, an exact selection as a mask
(``ops/index_select_attention.py``) and blocked grouped-query attention
under it (``ops/index_gqa_attention.py``), each under ``llm_glm``'s named
scopes below ``cdt.llm_attn`` (``llm_index``, ``llm_select``,
``llm_sparse_attn``). :func:`decode_step` is one token: ``lax.top_k`` of its
scores, the kept rows gathered. Conventions are ``llm_hybrid.py``'s:
weights held in ``dtype``, products on ``dtype`` operands accumulated in
float32; residual stream, norms, rope, softmax, router probabilities,
``w``, the index scores, the selection and logits float32; the cache rows
``dtype``. ``models/llm_keye_reference.py`` is the plain float32 statement.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import expert_share, index_gqa_attention as gqa_ops
from ..ops import index_select_attention as index_ops
from ..telemetry.device_scopes import device_scope
from .llm_glm import _write, layer_norm
from .llm_hybrid import (_ACT, _const, _count_held, _dot, _embed, _normal,
                         _pre_norm, _stack_counts, count_params, init_tree,
                         logits_of, rms_norm)
from .llm_model import LLMModel, chunked_prefill
from .llm_trinity import _rope, _rope_rows, rope_table


@dataclasses.dataclass(frozen=True)
class KeyeConfig:
    """Field names are the published ``config.json``'s (``indexer_*`` and
    ``topk`` its ``sa_config``'s). ``num_experts`` is how many experts are
    HELD here (``router_experts`` is the layer's count, the router's width:
    the same number — this chip holds them all), ``num_hidden_layers`` the
    depth kept."""
    hidden_size: int = 2048
    num_hidden_layers: int = 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    topk: int = 2048
    rope_theta: float = 10000000.0
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    index_norm_eps: float = 1e-6
    router_experts: int = 128
    num_experts: int = 128
    first_expert: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    vocab_size: int = 151936
    dtype: str = "bfloat16"
    # the schedule of the chunked prefill: sizes of the program, not options
    # of a request — the chunk, how many of its queries score and select at
    # once (what bounds the float32 [rows, cache rows] scores) and the rows
    # of one grouped expert product (PERF.md §6, PR 53); the attention
    # kernels' tiles are ops/index_gqa_attention.py's constants
    prefill_chunk_tokens: int = 4096
    select_rows: int = 1024
    expert_tile: int = 256

    @classmethod
    def keye_share(cls) -> "KeyeConfig":
        """Keye-VL-2.0-30B-A3B's language model at its published widths:
        one pipeline stage of four layers, every one of a layer's 128
        experts and the whole vocabulary."""
        return cls()

    @classmethod
    def tiny(cls, **kw) -> "KeyeConfig":
        """The CPU tests' size, float32: every mechanism, small widths, 3
        query heads a K/V head, a ``topk`` a test prompt outruns, chunks and
        tiles a test prompt spans several of, every expert held."""
        base = dict(
            hidden_size=32, num_hidden_layers=2, num_attention_heads=6,
            num_key_value_heads=2, head_dim=8, indexer_num_heads=4,
            indexer_head_dim=8, topk=12, max_position_embeddings=96,
            router_experts=8, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=16, vocab_size=64, dtype="float32",
            prefill_chunk_tokens=16, select_rows=8, expert_tile=4)
        return cls(**{**base, **kw})

    @property
    def model(self) -> LLMModel:
        return MODEL

    @property
    def moe_layers(self) -> list[int]:
        return list(range(self.num_hidden_layers))

    @property
    def routing(self) -> expert_share.Routing:
        return expert_share.Routing(self.router_experts,
                                    self.num_experts_per_tok, 1, 1, 1.0,
                                    score="softmax")

    @property
    def routed_slots_per_token(self) -> int:
        return self.num_experts_per_tok * self.num_hidden_layers

    stream_mixes_per_token = 0        # one residual stream, nothing mixed
    min_prompt_tokens = 1

    @property
    def index_weight_scale(self) -> float:
        return 1.0 / math.sqrt(self.indexer_num_heads
                               * self.indexer_head_dim)

    def attended_keys(self, prompt_tokens: int, new_tokens: int) -> dict:
        """(query, key) pairs ONE head attends in a request, by phase,
        summed over the layers (every one selects): ``min(topk, t + 1)`` a
        query at position ``t``."""
        read = np.minimum(np.arange(prompt_tokens + new_tokens,
                                    dtype=np.int64) + 1, self.topk)
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


def _shapes(cfg: KeyeConfig) -> dict:
    """Every drawn leaf as ``(shape, dtype name, init)``."""
    D, wd, F = cfg.hidden_size, cfg.dtype, cfg.moe_intermediate_size
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    J, di = cfg.indexer_num_heads, cfg.indexer_head_dim
    one, zero = _const(1.0), _const(0.0)
    layer = {
        "norm1": ((D,), "float32", one),
        "norm2": ((D,), "float32", one),
        "attn": {
            # [q (H·d) | k (G·d) | v (G·d)]
            "w_in": ((D, (H + 2 * G) * d), wd, _normal()),
            "q_norm": ((d,), "float32", one),
            "k_norm": ((d,), "float32", one),
            "w_o": ((H * d, D), wd, _normal())},
        # llm_glm's leaves under llm_glm's names
        "indexer": {
            "w_q": ((D, J * di), wd, _normal()),
            # [k_I (di) | w (J)]: W_Ik and W_Iw, both read from x
            "w_kw": ((D, di + J), wd, _normal()),
            "k_norm": ((di,), "float32", one),
            "k_bias": ((di,), "float32", zero)},
        "moe": {
            "w_router": ((D, cfg.router_experts), wd, _normal()),
            "e_gu": ((cfg.num_experts, D, 2 * F), wd, _normal()),
            "e_down": ((cfg.num_experts, F, D), wd, _normal())}}
    return {"embed": ((cfg.vocab_size, D), wd, _normal(1.0)),
            "head": ((cfg.vocab_size, D), wd, _normal(1.0 / math.sqrt(D))),
            "final_norm": ((D,), "float32", one),
            "layers": [layer] * cfg.num_hidden_layers}


def init_keye(cfg: KeyeConfig, key, abstract: bool = False):
    """The drawn weights and, beside them, the rope table of the attention
    heads (``llm_trinity.rope_table``: a leaf, not a literal of the
    programs); the indexer's narrower heads read every second column of it
    (``θ^(−i/32) = θ^(−2i/64)``)."""
    tree = init_tree(_shapes(cfg), key, abstract)
    rows = (cfg.max_position_embeddings, cfg.head_dim // 2)
    tree["rope"] = {k: jax.ShapeDtypeStruct(rows, jnp.float32)
                    for k in ("cos", "sin")} if abstract else rope_table(cfg)
    return tree


def param_count(cfg: KeyeConfig) -> int:
    return count_params(_shapes(cfg))


# --- pieces shared by prefill and decode -----------------------------------


def _index_rope(cfg: KeyeConfig, rope):
    """The indexer's ``(cos, sin)`` rows from the attention heads': pair
    ``i`` of ``indexer_head_dim / 2`` turns by ``θ^(−2i / indexer_head_dim)``,
    which is column ``i · head_dim / indexer_head_dim`` of the table."""
    step = cfg.head_dim // cfg.indexer_head_dim
    return tuple(a[:, ::step] for a in rope)


def _attn_in(cfg: KeyeConfig, p, x, rope):
    """From the normed rows ``x`` [T,D]: q [T,H,d] and k [T,G,d] (normed per
    head, roped), v [T,G,d]."""
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    T = x.shape[0]
    y = _dot(x, p["w_in"], jnp.dtype(cfg.dtype))
    q = rms_norm(y[:, :H * d].reshape(T, H, d), p["q_norm"],
                 cfg.rms_norm_eps)
    k = rms_norm(y[:, H * d:(H + G) * d].reshape(T, G, d), p["k_norm"],
                 cfg.rms_norm_eps)
    return _rope(q, *rope), _rope(k, *rope), \
        y[:, (H + G) * d:].reshape(T, G, d)


def _index_in(cfg: KeyeConfig, p, x, rope):
    """The indexer's side of a layer's input, from the normed rows ``x``
    [T,D]: ``q_I`` [T,J,d] and ``k_I`` [T,d] (roped), ``w`` [T,J] float32."""
    dtype = jnp.dtype(cfg.dtype)
    J, di, T = cfg.indexer_num_heads, cfg.indexer_head_dim, x.shape[0]
    rope = _index_rope(cfg, rope)
    q_i = _rope(_dot(x, p["w_q"], dtype).reshape(T, J, di), *rope)
    kw = _dot(x, p["w_kw"], dtype)
    k_i = _rope(layer_norm(kw[:, :di], p["k_norm"], p["k_bias"],
                           cfg.index_norm_eps)[:, None], *rope)[:, 0]
    return q_i, k_i, kw[:, di:] * cfg.index_weight_scale


def _kv_rows(k, v):
    """Keys and values [T,G,d] as the cache holds a position: [T, 2·G·d]."""
    T = k.shape[0]
    return jnp.concatenate([k.reshape(T, -1), v.reshape(T, -1)], axis=1)


def _attn_out(p, o, dtype):
    return _dot(o.reshape(*o.shape[:-2], -1), p["w_o"], dtype)


# --- prefill ---------------------------------------------------------------


def empty_cache(cfg: KeyeConfig, max_len: int) -> dict:
    """Per layer the K/V rows and the index keys, ``max_len`` rows rounded
    up to whole chunks (the kernels' tiles then read the buffers as they
    are)."""
    dtype = jnp.dtype(cfg.dtype)
    n, C = cfg.num_hidden_layers, cfg.prefill_chunk_tokens
    rows = -(-max_len // C) * C
    width = 2 * cfg.num_key_value_heads * cfg.head_dim
    return {"kv": [jnp.zeros((rows, width), dtype)] * n,
            "ki": [jnp.zeros((rows, cfg.indexer_head_dim), dtype)] * n}


def cache_kinds(cfg: KeyeConfig, cache: dict) -> dict:
    return {"kv": cache["kv"], "index": cache["ki"]}


def _selected_attention(cfg: KeyeConfig, q, q_i, w, kv, ki, start, kernel,
                        keep_masks: bool):
    """The three pieces for a chunk whose rows the caches ``kv``, ``ki``
    [S,·] already hold: ``select_rows`` queries score and select at a time,
    then the chunk attends under the whole mask. Answers ``(o [C,H,d], the
    mask [C,S] int8 or None)``."""
    dtype, C = jnp.dtype(cfg.dtype), q.shape[0]
    S = -(-kv.shape[0] // C) * C         # whole chunks: the kernels' tiles
    if S != kv.shape[0]:                 # a prompt shorter than a chunk
        kv, ki = (jnp.pad(a, ((0, S - a.shape[0]), (0, 0)))
                  for a in (kv, ki))
    n = math.gcd(C, cfg.select_rows)

    def some(xs):
        q_n, w_n, first = xs
        with jax.named_scope("llm_index"):
            scores = gqa_ops.index_scores(q_n, w_n, ki, first, dtype, kernel)
        with jax.named_scope("llm_select"):
            # the barrier keeps the kernel a call of its own, as llm_glm's
            return jax.lax.optimization_barrier(index_ops.select_keep(
                scores, first, cfg.topk, kernel))

    keep = jax.lax.map(some, (q_i.reshape(C // n, n, *q_i.shape[1:]),
                              w.reshape(C // n, n, -1),
                              start + jnp.arange(C // n) * n)).reshape(C, S)
    with jax.named_scope("llm_sparse_attn"):
        o = gqa_ops.masked_chunk_gqa(q, kv, keep, start,
                                     cfg.num_key_value_heads,
                                     cfg.head_dim ** -0.5, dtype, kernel)
    return o, (keep if keep_masks else None)


def prefill_chunk(cfg: KeyeConfig, params, cache: dict, ids, start, n_valid,
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
        rope = _rope_rows(params, start, C)
    with device_scope("llm_router"):
        valid = jnp.arange(C) < n_valid
    cache = {k: list(v) for k, v in cache.items()}
    held, rows, masks = [], [], []
    h = _embed(params, ids)
    for i, layer in enumerate(params["layers"]):
        x = _pre_norm(h, layer["norm1"], cfg.rms_norm_eps)
        with device_scope("llm_attn"):
            q, k, v = _attn_in(cfg, layer["attn"], x, rope)
            q_i, k_i, w = _index_in(cfg, layer["indexer"], x, rope)
            cache["kv"][i] = _write(cache["kv"][i], _kv_rows(k, v), start)
            cache["ki"][i] = _write(cache["ki"][i], k_i, start)
            o, keep = _selected_attention(cfg, q, q_i, w, cache["kv"][i],
                                          cache["ki"][i], start, kernel,
                                          keep_masks)
            masks.append(keep)
            h = h + _attn_out(layer["attn"], o, dtype)
        x = _pre_norm(h, layer["norm2"], cfg.rms_norm_eps)
        m = layer["moe"]
        idx, wts = expert_share.route(x, m["w_router"], None, cfg.routing)
        y, n_rows = expert_share.held_part_by_shape(
            x, idx, wts, m["e_gu"], m["e_down"], cfg.first_expert, dtype,
            cfg.routing, _ACT, valid=valid, tile=cfg.expert_tile,
            kernel=kernel)
        with device_scope("llm_experts"):
            h = h + y
        with device_scope("llm_router"):
            real = jnp.where(valid[:, None], idx, -1)
            n_rows = n_rows.astype(jnp.int32)
        held.append(_count_held(cfg, real))
        rows.append(n_rows)
    with device_scope("llm_head"):
        last = h if all_logits else h[n_valid - 1]
    out = (logits_of(cfg, params, last), cache, _stack_counts(held),
           _stack_counts(rows))
    return out + (masks,) if keep_masks else out


def prefill(cfg: KeyeConfig, params, ids, max_len: int,
            all_logits: bool = False, chunk: int | None = None,
            kernel: str | None = None):
    """The whole prompt ``ids`` [T], walked in chunks through the cache;
    answers as ``llm_hybrid.prefill``: ``(logits, cache, held)``."""
    return chunked_prefill(MODEL, cfg, params, ids, max_len, all_logits,
                           chunk, kernel=kernel)[:3]


# --- decode ----------------------------------------------------------------


def decode_step(cfg: KeyeConfig, params, cache: dict, token, pos,
                keep_rows: bool = False):
    """One token ``token`` (scalar id) at position ``pos`` through the
    caches; answers as ``llm_hybrid.decode_step``. ``keep_rows`` (a parity
    tool's) appends every layer's ``(rows, valid)``."""
    dtype = jnp.dtype(cfg.dtype)
    with device_scope("llm_attn"):
        rope = _rope_rows(params, pos, 1)
    cache = {k: list(v) for k, v in cache.items()}
    held, kept = [], []
    h = _embed(params, token)
    for i, layer in enumerate(params["layers"]):
        x = _pre_norm(h, layer["norm1"], cfg.rms_norm_eps)
        with device_scope("llm_attn"):
            q, k, v = _attn_in(cfg, layer["attn"], x[None], rope)
            q_i, k_i, w = _index_in(cfg, layer["indexer"], x[None], rope)
            cache["kv"][i] = _write(cache["kv"][i], _kv_rows(k, v), pos)
            cache["ki"][i] = _write(cache["ki"][i], k_i, pos)
            with jax.named_scope("llm_index"):
                chosen, real = index_ops.index_step(
                    q_i[0], w[0], cache["ki"][i], pos, cfg.topk, dtype)
            kept.append((chosen, real))
            with jax.named_scope("llm_sparse_attn"):
                o = gqa_ops.gathered_step(
                    q[0], cache["kv"][i], chosen, real,
                    cfg.num_key_value_heads, cfg.head_dim ** -0.5, dtype)
            h = h + _attn_out(layer["attn"], o, dtype)
        x = _pre_norm(h, layer["norm2"], cfg.rms_norm_eps)
        m = layer["moe"]
        idx, wts = expert_share.route(x[None], m["w_router"], None,
                                      cfg.routing)
        y = expert_share.held_part_token(
            x, idx[0], wts[0], m["e_gu"], m["e_down"], cfg.first_expert,
            dtype, _ACT)
        with device_scope("llm_experts"):
            h = h + y
        held.append(_count_held(cfg, idx))
    out = (logits_of(cfg, params, h), cache, _stack_counts(held))
    return out + (kept,) if keep_rows else out


MODEL = LLMModel(init_keye, prefill, decode_step, empty_cache, cache_kinds,
                 prefill_chunk)
