"""A hybrid language model: KDA linear attention, MLA latent attention and
token-routed experts in one stack — the farm's prompt rewriter.

Pre-norm residual blocks (``h = x + Mix(RMSNorm(x))``, ``y = h +
FFN(RMSNorm(h))``). Layer ``i`` is MLA when ``(i+1) % layer_group_size ==
0`` and KDA otherwise; the first ``first_k_dense_replace`` layers have a
dense SwiGLU FFN, the rest the expert layer (``ops/expert_share.py``: this
chip's share of the experts, the router at its full width). The vocabulary
may be a slice: embedding and head hold ``vocab_size`` rows, and ids,
logits and sampling are over the slice.

Weights are plain pytrees held in ``dtype`` (bfloat16 when served); matrix
products take ``dtype`` operands and accumulate in float32; the residual
stream, norms, gates, the KDA state, softmax, router scores and logits are
float32. Two paths share the weights: :func:`prefill` (a whole prompt:
chunked KDA, MLA over decompressed keys and values; fills the cache) and
:func:`decode_step` (one token: the recurrence, the absorbed MLA, only the
selected held experts read). ``models/llm_reference.py`` is the plain
float32 statement of the same mathematics that the tests hold both to.

One sequence at a time: no batch axis anywhere (a rewrite is one stream).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..ops import delta_rule, expert_share, latent_attention as mla_ops
from ..telemetry.device_scopes import device_scope
from .llm_model import LLMModel


@dataclasses.dataclass(frozen=True)
class LLMConfig:
    """Field names are the published ``config.json``'s where it has one.
    ``num_experts`` is how many experts are HELD here (``router_experts``
    is the layer's count, the router's width), ``vocab_size`` how many
    rows of the vocabulary, ``num_hidden_layers`` the depth kept."""
    hidden_size: int = 2560
    num_hidden_layers: int = 8
    layer_group_size: int = 6
    first_k_dense_replace: int = 2
    intermediate_size: int = 6144
    num_attention_heads: int = 32
    head_dim: int = 128                   # KDA: d_k = d_v
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6000000.0
    rms_norm_eps: float = 1e-6
    router_experts: int = 512
    num_experts: int = 16
    first_expert: int = 0
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    vocab_size: int = 19648
    dtype: str = "bfloat16"

    @classmethod
    def ling_flash_share(cls) -> "LLMConfig":
        """Ling-3.0-flash's language model at its published widths: one
        chip's share of a 32-chip expert group (experts 0–15 of 512, an
        eighth of the vocabulary), the two dense layers and one period."""
        return cls()

    @classmethod
    def tiny(cls, **kw) -> "LLMConfig":
        """The CPU tests' size, float32: every mechanism, small widths."""
        base = dict(
            hidden_size=32, num_hidden_layers=8, intermediate_size=48,
            num_attention_heads=2, head_dim=8, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            router_experts=32, num_experts=8, num_experts_per_tok=4,
            n_group=4, topk_group=2, moe_intermediate_size=16,
            moe_shared_expert_intermediate_size=16, vocab_size=64,
            dtype="float32")
        return cls(**{**base, **kw})

    @property
    def model(self) -> LLMModel:
        return MODEL

    def is_mla(self, i: int) -> bool:
        return (i + 1) % self.layer_group_size == 0

    def is_moe(self, i: int) -> bool:
        return i >= self.first_k_dense_replace

    @property
    def kda_layers(self) -> list[int]:
        return [i for i in range(self.num_hidden_layers)
                if not self.is_mla(i)]

    @property
    def mla_layers(self) -> list[int]:
        return [i for i in range(self.num_hidden_layers) if self.is_mla(i)]

    @property
    def moe_layers(self) -> list[int]:
        return [i for i in range(self.num_hidden_layers) if self.is_moe(i)]

    @property
    def routing(self) -> expert_share.Routing:
        return expert_share.Routing(
            self.router_experts, self.num_experts_per_tok, self.n_group,
            self.topk_group, self.routed_scaling_factor)

    @property
    def kda_width(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def routed_slots_per_token(self) -> int:
        return self.num_experts_per_tok * len(self.moe_layers)

    stream_mixes_per_token = 0        # one residual stream, nothing mixed

    @property
    def min_prompt_tokens(self) -> int:
        return self.short_conv_kernel_size


# --- weights ---------------------------------------------------------------


def _normal(std=None):
    """A normal draw; ``None``: 1/sqrt(fan-in), the second-to-last axis."""
    return ("normal", std)


def _const(value):
    return ("const", value)


def _shapes(cfg: LLMConfig) -> dict:
    """Every leaf as ``(shape, dtype name, init)``."""
    D, H, kw = cfg.hidden_size, cfg.num_attention_heads, cfg.kda_width
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    wd = cfg.dtype
    K = cfg.short_conv_kernel_size
    one = _const(1.0)

    def ffn(width):
        return {"w_gu": ((D, 2 * width), wd, _normal()),
                "w_down": ((width, D), wd, _normal())}

    layers = []
    for i in range(cfg.num_hidden_layers):
        layer = {"norm1": ((D,), "float32", one),
                 "norm2": ((D,), "float32", one)}
        if cfg.is_mla(i):
            layer["mla"] = {
                # [q (H·192) | c (rank) | k_rope | head-wise gate (H)]
                "w_in": ((D, H * qk + cfg.kv_lora_rank
                          + cfg.qk_rope_head_dim + H), wd, _normal()),
                "c_norm": ((cfg.kv_lora_rank,), "float32", one),
                "q_norm": ((qk,), "float32", one),
                "kr_norm": ((cfg.qk_rope_head_dim,), "float32", one),
                "w_b": ((cfg.kv_lora_rank,
                         H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), wd,
                        _normal()),
                "w_o": ((H * cfg.v_head_dim, D), wd, _normal())}
        else:
            layer["kda"] = {
                # [q | k | v | decay gate (each H·128) | beta (H) | gate (H)]
                "w_in": ((D, 4 * kw + 2 * H), wd, _normal()),
                "conv": ((3, K, kw), "float32", _normal(1.0 / math.sqrt(K))),
                # exp(a_log)·(W_g x + g_bias): the heads' memories run
                # from a token or two to thousands of tokens (the gate's
                # bound is kda_lower_bound a token)
                "a_log": ((H,), "float32", ("linspace", -1.0, 1.0)),
                "g_bias": ((kw,), "float32", _const(-4.0)),
                "o_norm": ((cfg.head_dim,), "float32", one),
                "w_o": ((kw, D), wd, _normal())}
        if cfg.is_moe(i):
            F = cfg.moe_intermediate_size
            layer["moe"] = {
                "w_router": ((D, cfg.router_experts), wd, _normal()),
                "router_bias": ((cfg.router_experts,), "float32",
                                _normal(0.02)),
                "shared": ffn(cfg.moe_shared_expert_intermediate_size),
                "e_gu": ((cfg.num_experts, D, 2 * F), wd, _normal()),
                "e_down": ((cfg.num_experts, F, D), wd, _normal())}
        else:
            layer["ffn"] = ffn(cfg.intermediate_size)
        layers.append(layer)
    return {"embed": ((cfg.vocab_size, D), wd, _normal(1.0)),
            "head": ((cfg.vocab_size, D), wd, _normal(1.0 / math.sqrt(D))),
            "final_norm": ((D,), "float32", one),
            "layers": layers}


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def _draw(key, spec):
    shape, dtype, (how, *args) = spec
    if how == "linspace":
        return jnp.linspace(args[0], args[1], shape[0], dtype=dtype)
    if how == "const":      # a scalar, or one value for each of the last axis
        return jnp.broadcast_to(jnp.asarray(args[0], dtype), shape)
    std = args[0] if args[0] is not None else 1.0 / math.sqrt(shape[-2])
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_tree(specs, key, abstract: bool = False):
    """A weight tree from its ``(shape, dtype name, init)`` leaves: random
    from ``key``, built on the device leaf by leaf (``abstract``: a
    ShapeDtypeStruct tree, for off-chip compiles)."""
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=_is_leaf)
    if abstract:
        return jax.tree_util.tree_unflatten(treedef, [
            jax.ShapeDtypeStruct(s[0], jnp.dtype(s[1])) for s in leaves])
    keys = jax.random.split(key, len(leaves))
    draw = jax.jit(_draw, static_argnums=1)
    return jax.tree_util.tree_unflatten(
        treedef, [draw(k, s) for k, s in zip(keys, leaves)])


def count_params(specs) -> int:
    leaves = jax.tree_util.tree_leaves(specs, is_leaf=_is_leaf)
    return sum(math.prod(s[0]) for s in leaves)


def init_llm(cfg: LLMConfig, key, abstract: bool = False):
    return init_tree(_shapes(cfg), key, abstract)


def param_count(cfg: LLMConfig) -> int:
    return count_params(_shapes(cfg))


# --- pieces shared by prefill and decode -----------------------------------


def rms_norm(x, weight, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def _dot(x, w, dtype):
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


def _l2(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-12)


def _kda_gates(cfg: LLMConfig, p, qkv, g_raw, beta_raw):
    """After the convolution: heads split, q and k normalised, the decay
    and the write strength. ``qkv`` [...,3,kw] → q,k,v [...,H,dk]."""
    H, dk = cfg.num_attention_heads, cfg.head_dim
    qkv = jax.nn.silu(qkv)
    q, k, v = (qkv[..., j, :].reshape(*qkv.shape[:-2], H, dk)
               for j in range(3))
    a = jnp.exp(p["a_log"])[:, None]
    g = cfg.kda_lower_bound * jax.nn.sigmoid(
        a * (g_raw + p["g_bias"]).reshape(*g_raw.shape[:-1], H, dk))
    return _l2(q), _l2(k), v, g, jax.nn.sigmoid(beta_raw)


def _kda_out(cfg: LLMConfig, p, o, gate_raw, dtype):
    o = rms_norm(o, p["o_norm"], cfg.rms_norm_eps) \
        * jax.nn.sigmoid(gate_raw)[..., None]
    return _dot(o.reshape(*o.shape[:-2], -1), p["w_o"], dtype)


def _split_kda_in(cfg: LLMConfig, y):
    kw, H = cfg.kda_width, cfg.num_attention_heads
    qkv = y[..., :3 * kw].reshape(*y.shape[:-1], 3, kw)
    g_raw = y[..., 3 * kw:4 * kw]
    return qkv, g_raw, y[..., 4 * kw:4 * kw + H], y[..., 4 * kw + H:]


def _split_mla_in(cfg: LLMConfig, p, y, positions):
    """q (normed, roped), the normed latent, the normed roped shared key,
    the head-wise gate."""
    H, nope, r = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                  cfg.qk_rope_head_dim)
    rank, eps = cfg.kv_lora_rank, cfg.rms_norm_eps
    q = y[..., :H * (nope + r)].reshape(*y.shape[:-1], H, nope + r)
    q = rms_norm(q, p["q_norm"], eps)
    at = H * (nope + r)
    c = rms_norm(y[..., at:at + rank], p["c_norm"], eps)
    kr = rms_norm(y[..., at + rank:at + rank + r], p["kr_norm"], eps)
    gate = y[..., at + rank + r:]
    q_rope = mla_ops.rope_interleaved(q[..., nope:], positions,
                                      cfg.rope_theta)
    kr = mla_ops.rope_interleaved(kr, positions, cfg.rope_theta)
    return q[..., :nope], q_rope, c, kr, gate


def _mla_out(p, o, gate_raw, dtype):
    o = o * jax.nn.sigmoid(gate_raw)[..., None]
    return _dot(o.reshape(*o.shape[:-2], -1), p["w_o"], dtype)


def _mla_scale(cfg: LLMConfig) -> float:
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


_ACT = expert_share.silu_gate        # every FFN here is SwiGLU


def _swiglu(x, ffn, dtype):
    return expert_share.gated_mlp(x, ffn["w_gu"], ffn["w_down"], dtype, _ACT)


def _count_held(cfg: LLMConfig, idx):
    with device_scope("llm_router"):
        return expert_share.held_slots(
            idx, cfg.first_expert, cfg.num_experts).sum().astype(jnp.int32)


def _stack_counts(held):
    with device_scope("llm_router"):
        return jnp.stack(held) if held else jnp.zeros((0,), jnp.int32)


def _pre_norm(h, weight, eps: float):
    """A sublayer's RMS norm of the residual stream."""
    with device_scope("llm_norm"):
        return rms_norm(h, weight, eps)


def _embed(params, ids):
    with device_scope("llm_head"):
        return params["embed"][ids].astype(jnp.float32)


def logits_of(cfg: LLMConfig, params, h):
    """Final norm and the (sliced) head; float32."""
    dtype = jnp.dtype(cfg.dtype)
    with device_scope("llm_head"):
        x = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        return jnp.einsum("...d,vd->...v", x.astype(dtype),
                          params["head"].astype(dtype),
                          preferred_element_type=jnp.float32)


# --- prefill ---------------------------------------------------------------


def empty_cache(cfg: LLMConfig, max_len: int) -> dict:
    H, dk, K = cfg.num_attention_heads, cfg.head_dim, \
        cfg.short_conv_kernel_size
    dtype = jnp.dtype(cfg.dtype)
    return {
        "S": [jnp.zeros((H, dk, dk), jnp.float32) for _ in cfg.kda_layers],
        "conv": [jnp.zeros((K - 1, 3, cfg.kda_width), dtype)
                 for _ in cfg.kda_layers],
        "c": [jnp.zeros((max_len, cfg.kv_lora_rank), dtype)
              for _ in cfg.mla_layers],
        "kr": [jnp.zeros((max_len, cfg.qk_rope_head_dim), dtype)
               for _ in cfg.mla_layers]}


def cache_kinds(cfg: LLMConfig, cache: dict) -> dict:
    return {"recurrent": [cache["S"], cache["conv"]],
            "full": [cache["c"], cache["kr"]]}


def prefill(cfg: LLMConfig, params, ids, max_len: int,
            all_logits: bool = False):
    """The whole prompt ``ids`` [T] at once. Answers ``(logits, cache,
    held)``: the last position's logits [V] (every position's with
    ``all_logits``), the cache sized for ``max_len`` positions, and per
    expert layer the count of routed slots that fell on held experts."""
    dtype = jnp.dtype(cfg.dtype)
    T = ids.shape[0]
    K = cfg.short_conv_kernel_size
    chunk = math.gcd(T, 64)
    with device_scope("llm_attn"):
        positions = jnp.arange(T)
        cache = empty_cache(cfg, max_len)
    held = []
    h = _embed(params, ids)
    kda_at = mla_at = 0
    for i, layer in enumerate(params["layers"]):
        x = _pre_norm(h, layer["norm1"], cfg.rms_norm_eps)
        if cfg.is_mla(i):
            p = layer["mla"]
            with device_scope("llm_attn"):
                q_nope, q_rope, c, kr, gate = _split_mla_in(
                    cfg, p, _dot(x, p["w_in"], dtype), positions)
                o = mla_ops.mla_naive(q_nope, q_rope, c, kr, p["w_b"],
                                      _mla_scale(cfg), dtype)
                h = h + _mla_out(p, o, gate, dtype)
                cache["c"][mla_at] = cache["c"][mla_at].at[:T].set(
                    c.astype(dtype))
                cache["kr"][mla_at] = cache["kr"][mla_at].at[:T].set(
                    kr.astype(dtype))
            mla_at += 1
        else:
            p = layer["kda"]
            with device_scope("llm_attn"):
                qkv, g_raw, beta_raw, gate = _split_kda_in(
                    cfg, _dot(x, p["w_in"], dtype))
                # the cache keeps the convolution's inputs as the served
                # dtype holds them, so prefill convolves what decode will see
                qkv = qkv.astype(dtype)
                padded = jnp.concatenate(
                    [jnp.zeros((K - 1, 3, cfg.kda_width), dtype), qkv]
                ).astype(jnp.float32)
                conv = sum(padded[j:j + T] * p["conv"][:, j]
                           for j in range(K))
                q, k, v, g, beta = _kda_gates(cfg, p, conv, g_raw, beta_raw)
                o, S = delta_rule.kda_chunked(
                    q, k, v, g, beta, cache["S"][kda_at],
                    1.0 / math.sqrt(cfg.head_dim), chunk)
                h = h + _kda_out(cfg, p, o, gate, dtype)
                cache["S"][kda_at] = S
                cache["conv"][kda_at] = qkv[T - (K - 1):]
            kda_at += 1
        x = _pre_norm(h, layer["norm2"], cfg.rms_norm_eps)
        if cfg.is_moe(i):
            m = layer["moe"]
            idx, w = expert_share.route(x, m["w_router"], m["router_bias"],
                                        cfg.routing)
            # the form the rows call for: dense-masked at a few hundred
            y, _ = expert_share.held_part(
                x, idx, w, m["e_gu"], m["e_down"], cfg.first_expert, dtype,
                cfg.routing, _ACT)
            with device_scope("llm_shared_ffn"):
                h = h + y + _swiglu(x, m["shared"], dtype)
            held.append(_count_held(cfg, idx))
        else:
            with device_scope("llm_shared_ffn"):
                h = h + _swiglu(x, layer["ffn"], dtype)
    logits = logits_of(cfg, params, h if all_logits else h[-1])
    return logits, cache, _stack_counts(held)


# --- decode ----------------------------------------------------------------


def decode_step(cfg: LLMConfig, params, cache: dict, token, pos):
    """One token ``token`` (scalar id) at position ``pos`` through the
    cache. Answers ``(logits [V] f32, cache, held [expert layers])``."""
    dtype = jnp.dtype(cfg.dtype)
    K = cfg.short_conv_kernel_size
    with device_scope("llm_attn"):
        positions = jnp.reshape(pos, (1,))
    cache = {k: list(v) for k, v in cache.items()}
    held = []
    h = _embed(params, token)
    kda_at = mla_at = 0
    for i, layer in enumerate(params["layers"]):
        x = _pre_norm(h, layer["norm1"], cfg.rms_norm_eps)
        if cfg.is_mla(i):
            p = layer["mla"]
            with device_scope("llm_attn"):
                q_nope, q_rope, c, kr, gate = _split_mla_in(
                    cfg, p, _dot(x[None], p["w_in"], dtype), positions)
                c_cache = jax.lax.dynamic_update_slice(
                    cache["c"][mla_at], c.astype(dtype), (pos, 0))
                kr_cache = jax.lax.dynamic_update_slice(
                    cache["kr"][mla_at], kr.astype(dtype), (pos, 0))
                o = mla_ops.mla_absorbed_step(
                    q_nope[0], q_rope[0], c_cache, kr_cache, pos, p["w_b"],
                    _mla_scale(cfg), dtype)
                h = h + _mla_out(p, o, gate[0], dtype)
            cache["c"][mla_at], cache["kr"][mla_at] = c_cache, kr_cache
            mla_at += 1
        else:
            p = layer["kda"]
            with device_scope("llm_attn"):
                qkv, g_raw, beta_raw, gate = _split_kda_in(
                    cfg, _dot(x, p["w_in"], dtype))
                window = jnp.concatenate(
                    [cache["conv"][kda_at],
                     qkv.astype(dtype)[None]])                  # [K,3,kw]
                conv = (window.astype(jnp.float32)
                        * jnp.swapaxes(p["conv"], 0, 1)).sum(0)
                q, k, v, g, beta = _kda_gates(cfg, p, conv, g_raw, beta_raw)
                S, o = delta_rule.kda_step(
                    cache["S"][kda_at], q, k, v, g, beta,
                    1.0 / math.sqrt(cfg.head_dim))
                h = h + _kda_out(cfg, p, o, gate, dtype)
                cache["S"][kda_at] = S
                cache["conv"][kda_at] = window[1:]
            kda_at += 1
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


MODEL = LLMModel(init_llm, prefill, decode_step, empty_cache, cache_kinds)
