"""A sixth prompt rewriter: a DOUBLE layer — two latent attentions and two
dense FFNs in series with ONE routed-expert branch beside them — and a
softmax router a third of whose outputs are identity experts.

A layer takes the stream ``h`` through two sublayers and joins the branch
after the second (shortcut-connected experts)::

    for i in (0, 1):
        a = h + MLA_i(RMSNorm_in,i(h))
        y = RMSNorm_post,i(a)
        if i == 0:  m = MoE(y)
        h = a + SwiGLU_i(y)
    h = h + m

so the branch's consumer is three sublayers after its input, and nothing
between them waits for it: it is written where it is read from and added
where it joins, with no barrier, and the compiler places it.

Attention is ``llm_kimi.py``'s at the same head sizes (64 heads, ``q_lora``
1536, latent 512, 128 + 64 wide keys, 128 values) with plain rope and two
scales of its own: ``q = (RMSNorm(x W_qa) W_qb) · √(D / q_lora)`` and ``c =
RMSNorm(c_kv) · √(D / kv_lora)`` (``mla_scale_q_lora`` /
``mla_scale_kv_lora``); the roped shared key is neither normed nor scaled.
The cache is ``c`` and the roped ``k_r`` of BOTH sublayers: two latent
leaves a layer, 576 values a token each. ``MoE(y)``: ``s = softmax(y W_r)``
float32 over all ``experts + zero_experts`` outputs, the ``moe_topk``
largest of ``s + bias``, weights ``routed_scaling_factor · s_e`` NOT
normalised; a chosen expert below ``router_experts`` is a SwiGLU of width
``expert_ffn_hidden_size`` — computed if this chip holds it
(``ops/expert_share.py``: the held part) and left out if not — and one at
or above it is the identity, ``w_e · y``, computed here for every token
(``expert_share.zero_part``). No shared expert. The vocabulary may be a
slice.

The three paths are ``llm_kimi.py``'s: :func:`prefill_chunk` is the
continuation ``llm_prefill`` scans (``mla_chunk_attention`` over the cache
rows up to the chunk's own: the blocked causal kernel on the chip),
:func:`prefill` that scan, :func:`decode_step` one token through the
absorbed form. Both programs hand back, per expert layer, the slots that
fell on HELD experts and then the slots that fell on IDENTITY experts: one
vector ``[held … | zero …]`` of ``2 · layers`` counts (``pipeline_llm``
splits it by ``routing.zero_experts``). Conventions are ``llm_hybrid.py``'s:
weights held in ``dtype``, products on ``dtype`` operands accumulated in
float32; residual stream, norms, softmax, router scores and logits float32;
one sequence, no batch axis. ``models/llm_longcat_reference.py`` is the
plain float32 statement all three are held to.
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

# the sublayer whose post-attention norm the expert branch reads: the first
# (the architecture's, not an option: the branch joins after the second FFN)
BRANCH_SUBLAYER = 0


@dataclasses.dataclass(frozen=True)
class LongcatConfig:
    """Field names are the published ``config.json``'s. ``n_routed_experts``
    is how many experts are HELD here (``router_experts`` is the layer's
    count of real experts: the router's width is that plus
    ``zero_expert_num``), ``vocab_size`` how many rows of the vocabulary,
    ``num_layers`` the DOUBLE layers kept."""
    hidden_size: int = 6144
    num_layers: int = 4
    ffn_hidden_size: int = 12288
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-5
    router_experts: int = 512
    n_routed_experts: int = 8
    first_expert: int = 0
    zero_expert_num: int = 256
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    expert_ffn_hidden_size: int = 2048
    vocab_size: int = 16384
    dtype: str = "bfloat16"
    # the schedule of the chunked prefill: Kimi's kernel at Kimi's head
    # sizes, so Kimi's measured chunk and tile (PERF.md §6, PRs 32, 40);
    # sizes of the program, not options of a request
    prefill_chunk_tokens: int = 4096
    attn_block_q: int = 2048
    attn_block_k: int = 1024
    expert_tile: int = expert_share.GROUP_TILE

    @classmethod
    def longcat_share(cls) -> "LongcatConfig":
        """LongCat-Flash-Omni's language model at its published widths:
        one chip's share of a 64-chip expert group (experts 0–7 of 512,
        all 256 identity experts, an eighth of the vocabulary), double
        layers 0–3."""
        return cls()

    @classmethod
    def tiny(cls, **kw) -> "LongcatConfig":
        """The CPU tests' size, float32: every mechanism, small widths,
        chunks, blocks and tiles a test prompt spans several of."""
        base = dict(
            hidden_size=32, num_layers=2, ffn_hidden_size=48,
            num_attention_heads=4, q_lora_rank=8, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            rope_theta=1e4, router_experts=16, n_routed_experts=4,
            zero_expert_num=8, moe_topk=6, expert_ffn_hidden_size=16,
            vocab_size=64, dtype="float32", prefill_chunk_tokens=16,
            attn_block_q=8, attn_block_k=8, expert_tile=4)
        return cls(**{**base, **kw})

    @property
    def model(self) -> LLMModel:
        return MODEL

    @property
    def moe_layers(self) -> list[int]:      # every double layer has one
        return list(range(self.num_layers))

    @property
    def num_experts(self) -> int:          # held, as the others call it
        return self.n_routed_experts

    @property
    def routing(self) -> expert_share.Routing:
        return expert_share.Routing(
            self.router_experts, self.moe_topk, 1, 1,
            self.routed_scaling_factor, score="softmax", normalised=False,
            zero_experts=self.zero_expert_num)

    @property
    def routed_slots_per_token(self) -> int:
        return self.moe_topk * self.num_layers

    stream_mixes_per_token = 0        # one residual stream, nothing mixed
    min_prompt_tokens = 1

    @property
    def q_scale(self) -> float:
        return math.sqrt(self.hidden_size / self.q_lora_rank) \
            if self.mla_scale_q_lora else 1.0

    @property
    def kv_scale(self) -> float:
        return math.sqrt(self.hidden_size / self.kv_lora_rank) \
            if self.mla_scale_kv_lora else 1.0

    @property
    def softmax_scale(self) -> float:
        return 1.0 / math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim)


# --- weights ---------------------------------------------------------------


def _shapes(cfg: LongcatConfig) -> dict:
    """Every leaf as ``(shape, dtype name, init)``."""
    D, H, wd = cfg.hidden_size, cfg.num_attention_heads, cfg.dtype
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    F, outputs = cfg.expert_ffn_hidden_size, cfg.routing.outputs
    one = _const(1.0)
    # the two scales restore unit variance from a draw at the model's ONE
    # width-wide std, 1/sqrt(D) — that is what they are for — so W_qb and
    # W_kvb are drawn there where their scale is on (at 1/sqrt(fan-in) the
    # scaled logits would have std 6.9 and a 16k-key softmax be an argmax)
    wide = _normal(1.0 / math.sqrt(D))

    def sublayer():
        return {"norm_in": ((D,), "float32", one),
                "norm_post": ((D,), "float32", one),
                "attn": {
                    # [c_q (r_q) | c_kv (rank) | k_rope]: W_qa and W_kva
                    "w_a": ((D, cfg.q_lora_rank + cfg.kv_lora_rank + rope),
                            wd, _normal()),
                    "q_norm": ((cfg.q_lora_rank,), "float32", one),
                    "c_norm": ((cfg.kv_lora_rank,), "float32", one),
                    # [every head's nope | every head's rope]
                    "w_qb": ((cfg.q_lora_rank, H * (nope + rope)), wd,
                             wide if cfg.mla_scale_q_lora else _normal()),
                    # per head [k_nope | v]
                    "w_b": ((cfg.kv_lora_rank, H * (nope + cfg.v_head_dim)),
                            wd, wide if cfg.mla_scale_kv_lora else _normal()),
                    "w_o": ((H * cfg.v_head_dim, D), wd, _normal())},
                "ffn": {"w_gu": ((D, 2 * cfg.ffn_hidden_size), wd, _normal()),
                        "w_down": ((cfg.ffn_hidden_size, D), wd, _normal())}}

    layers = [{"sub": [sublayer(), sublayer()],
               "moe": {
                   # [real experts | identity experts]
                   "w_router": ((D, outputs), wd, _normal()),
                   # a tenth of the scores' spread, as the sigmoid routers'
                   # 0.02 is of theirs: a softmax over ``outputs`` scores
                   # ~1/outputs, and a bias of 0.02 would BE the choice
                   "router_bias": ((outputs,), "float32",
                                   _normal(0.1 / outputs)),
                   "e_gu": ((cfg.n_routed_experts, D, 2 * F), wd, _normal()),
                   "e_down": ((cfg.n_routed_experts, F, D), wd, _normal())}}
              for _ in range(cfg.num_layers)]
    return {"embed": ((cfg.vocab_size, D), wd, _normal(1.0)),
            "head": ((cfg.vocab_size, D), wd, _normal(1.0 / math.sqrt(D))),
            "final_norm": ((D,), "float32", one),
            "layers": layers}


def init_longcat(cfg: LongcatConfig, key, abstract: bool = False):
    return init_tree(_shapes(cfg), key, abstract)


def param_count(cfg: LongcatConfig) -> int:
    return count_params(_shapes(cfg))


# --- pieces shared by prefill and decode -----------------------------------


def _split_in(cfg: LongcatConfig, p, y, positions):
    """From ``x W_a`` [T,·]: the scaled queries ``[T,H,nope]`` and (roped)
    ``[T,H,rope]``, the normed and scaled latent and the roped shared
    key."""
    rq, rank = cfg.q_lora_rank, cfg.kv_lora_rank
    H, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    T = y.shape[0]
    q = _dot(rms_norm(y[:, :rq], p["q_norm"], cfg.rms_norm_eps), p["w_qb"],
             jnp.dtype(cfg.dtype)) * cfg.q_scale
    c = rms_norm(y[:, rq:rq + rank], p["c_norm"], cfg.rms_norm_eps) \
        * cfg.kv_scale
    kr = mla_ops.rope_interleaved(y[:, rq + rank:], positions,
                                  cfg.rope_theta)
    q_rope = mla_ops.rope_interleaved(q[:, H * nope:].reshape(T, H, -1),
                                      positions, cfg.rope_theta)
    return q[:, :H * nope].reshape(T, H, nope), q_rope, c, kr


def _write(cfg: LongcatConfig, cache: dict, j: int, c, kr, at):
    """Rows ``at ..`` of sublayer ``j``'s two latent leaves."""
    dtype = jnp.dtype(cfg.dtype)
    cache["c"][j] = jax.lax.dynamic_update_slice(
        cache["c"][j], c.astype(dtype), (at, 0))
    cache["kr"][j] = jax.lax.dynamic_update_slice(
        cache["kr"][j], kr.astype(dtype), (at, 0))


def _attn_out(p, o, dtype):
    return _dot(o.reshape(*o.shape[:-2], -1), p["w_o"], dtype)


# --- prefill ---------------------------------------------------------------


def empty_cache(cfg: LongcatConfig, max_len: int) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    n = 2 * cfg.num_layers              # a leaf pair an attention sublayer
    return {"c": [jnp.zeros((max_len, cfg.kv_lora_rank), dtype)] * n,
            "kr": [jnp.zeros((max_len, cfg.qk_rope_head_dim), dtype)] * n}


def cache_kinds(cfg: LongcatConfig, cache: dict) -> dict:
    return {"full": [cache["c"], cache["kr"]]}


def _layer_chunk(cfg: LongcatConfig, layer, cache: dict, n: int, h,
                 positions, start, valid, kernel):
    """Double layer ``n`` on a chunk's rows ``h`` [C,D]: both sublayers
    write their latents into ``cache`` (its lists, in place) and attend
    over the rows up to their own; the expert branch leaves after the
    first attention and joins after the second FFN. Answers ``(h, held,
    zero, rows multiplied)``."""
    dtype = jnp.dtype(cfg.dtype)
    for i, sub in enumerate(layer["sub"]):
        p, j = sub["attn"], 2 * n + i
        x = _pre_norm(h, sub["norm_in"], cfg.rms_norm_eps)
        with device_scope("llm_attn"):
            q_nope, q_rope, c, kr = _split_in(
                cfg, p, _dot(x, p["w_a"], dtype), positions)
            _write(cfg, cache, j, c, kr, start)
        o = mla_ops.mla_chunk_attention(                   # cdt.llm_attn
            q_nope, q_rope, cache["c"][j], cache["kr"][j], start, p["w_b"],
            cfg.softmax_scale, dtype, cfg.attn_block_q, cfg.attn_block_k,
            kernel)
        with device_scope("llm_attn"):
            a = h + _attn_out(p, o, dtype)
        y = _pre_norm(a, sub["norm_post"], cfg.rms_norm_eps)
        if i == BRANCH_SUBLAYER:        # the shortcut branch leaves here
            m = layer["moe"]
            idx, w = expert_share.route(y, m["w_router"], m["router_bias"],
                                        cfg.routing)
            part, rows = expert_share.held_part(
                y, idx, w, m["e_gu"], m["e_down"], cfg.first_expert, dtype,
                cfg.routing, _ACT, valid=valid, tile=cfg.expert_tile)
            mix, zero = expert_share.zero_part(y, idx, w, cfg.routing, valid)
            with device_scope("llm_experts"):
                branch = part + mix
            with device_scope("llm_router"):
                real = jnp.where(valid[:, None], idx, -1)
                rows = rows.astype(jnp.int32)
            held = _count_held(cfg, real)
        with device_scope("llm_shared_ffn"):
            h = a + _swiglu(y, sub["ffn"], dtype)
    with device_scope("llm_experts"):   # … and joins here
        return h + branch, held, zero, rows


def prefill_chunk(cfg: LongcatConfig, params, cache: dict, ids, start,
                  n_valid, all_logits: bool = False,
                  kernel: str | None = None):
    """``ids`` [C] at positions ``start .. start+C−1``, of which the first
    ``n_valid`` are the prompt's (the rest pad its last chunk: they count
    in no slot and nothing reads what they write). Continues from
    ``cache``. Answers ``(logits, cache, counts, rows)``: the logits of row
    ``n_valid − 1`` [V] (of every row [C,V] with ``all_logits``); per
    expert layer the routed slots that fell on held experts and then those
    that fell on identity experts (``[held … | zero …]``); and the rows the
    held experts' form multiplied."""
    with device_scope("llm_attn"):
        positions = start + jnp.arange(ids.shape[0])
    with device_scope("llm_router"):
        valid = jnp.arange(ids.shape[0]) < n_valid
    cache = {k: list(v) for k, v in cache.items()}
    held, zero, rows = [], [], []
    h = _embed(params, ids)
    for n, layer in enumerate(params["layers"]):
        h, *counts = _layer_chunk(cfg, layer, cache, n, h, positions, start,
                                  valid, kernel)
        for kept, count in zip((held, zero, rows), counts):
            kept.append(count)
    with device_scope("llm_head"):
        last = h if all_logits else h[n_valid - 1]
    logits = logits_of(cfg, params, last)
    return logits, cache, _stack_counts(held + zero), _stack_counts(rows)


def prefill(cfg: LongcatConfig, params, ids, max_len: int,
            all_logits: bool = False, chunk: int | None = None,
            kernel: str | None = None):
    """The whole prompt ``ids`` [T], walked in chunks through the cache;
    answers as ``llm_hybrid.prefill``: ``(logits, cache, counts)``."""
    return chunked_prefill(MODEL, cfg, params, ids, max_len, all_logits,
                           chunk, kernel=kernel)[:3]


# --- decode ----------------------------------------------------------------


def _layer_token(cfg: LongcatConfig, layer, cache: dict, n: int, h,
                 positions, pos):
    """Double layer ``n`` on one token's row ``h`` [D] through the cache
    (its lists, in place); answers ``(h, held, zero)``."""
    dtype = jnp.dtype(cfg.dtype)
    for i, sub in enumerate(layer["sub"]):
        p, j = sub["attn"], 2 * n + i
        x = _pre_norm(h, sub["norm_in"], cfg.rms_norm_eps)
        with device_scope("llm_attn"):
            q_nope, q_rope, c, kr = _split_in(
                cfg, p, _dot(x[None], p["w_a"], dtype), positions)
            _write(cfg, cache, j, c, kr, pos)
            o = mla_ops.mla_absorbed_step(
                q_nope[0], q_rope[0], cache["c"][j], cache["kr"][j], pos,
                p["w_b"], cfg.softmax_scale, dtype)
            a = h + _attn_out(p, o, dtype)
        y = _pre_norm(a, sub["norm_post"], cfg.rms_norm_eps)
        if i == BRANCH_SUBLAYER:
            m = layer["moe"]
            idx, w = expert_share.route(y[None], m["w_router"],
                                        m["router_bias"], cfg.routing)
            part = expert_share.held_part_token(
                y, idx[0], w[0], m["e_gu"], m["e_down"], cfg.first_expert,
                dtype, _ACT)
            mix, zero = expert_share.zero_part(y[None], idx, w, cfg.routing)
            with device_scope("llm_experts"):
                branch = part + mix[0]
            held = _count_held(cfg, idx)
        with device_scope("llm_shared_ffn"):
            h = a + _swiglu(y[None], sub["ffn"], dtype)[0]
    with device_scope("llm_experts"):
        return h + branch, held, zero


def decode_step(cfg: LongcatConfig, params, cache: dict, token, pos):
    """One token ``token`` (scalar id) at position ``pos`` through the
    cache; answers ``(logits [V], cache, counts)``, the counts as
    :func:`prefill_chunk`'s."""
    with device_scope("llm_attn"):
        positions = jnp.reshape(pos, (1,))
    cache = {k: list(v) for k, v in cache.items()}
    held, zero = [], []
    h = _embed(params, token)
    for n, layer in enumerate(params["layers"]):
        h, held_n, zero_n = _layer_token(cfg, layer, cache, n, h, positions,
                                         pos)
        held.append(held_n)
        zero.append(zero_n)
    return logits_of(cfg, params, h), cache, _stack_counts(held + zero)


def decode_weights(cfg: LongcatConfig, params):
    """``params`` as a token loop hands them to every :func:`decode_step`:
    each attention sublayer's ``w_b`` in the absorbed step's form, made
    once ahead of the loop (``latent_attention.absorbed_form``)."""
    def formed(p):
        return {**p, "w_b": mla_ops.absorbed_form(p["w_b"],
                                                  cfg.num_attention_heads)}

    return {**params, "layers": [
        {**layer, "sub": [{**sub, "attn": formed(sub["attn"])}
                          for sub in layer["sub"]]}
        for layer in params["layers"]]}


MODEL = LLMModel(init_longcat, prefill, decode_step, empty_cache, cache_kinds,
                 prefill_chunk, decode_weights)
