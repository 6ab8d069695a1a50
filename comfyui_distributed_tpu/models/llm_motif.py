"""A second prompt rewriter: grouped differential latent attention (GDLA)
over window and full layers, a four-stream hyper-connection residual (mHC)
and PolyNorm experts.

The carry between layers is ``n`` streams ``X`` [n,D]. Around every
sublayer ``F`` (attention and FFN each own ``γ, Φ, α, b``) the streams are
mixed per token: ``x̃ = RMSNorm_γ(vec X)``, ``[u_pre|u_post|u_res] = x̃ Φ``,
``H_pre = σ(α_pre u_pre + b_pre)``, ``H_post = 2σ(α_post u_post +
b_post)``, ``H_res = Sinkhorn(exp(α_res mat(u_res) + B_res))`` (rounds of:
rows by their sums, columns by their sums), ``y = F(RMSNorm(Σ_j H_pre[j]
X[j]))``, ``X'[i] = Σ_j H_res[i,j] X[j] + H_post[i] y``, clipped. Streams
start as copies of the embedding and are summed before the head.

Attention (``ops/latent_attention.py``: ``gdla_*``): queries from a
low-rank ``c_q``; keys and values decompressed per GROUP from one cached
latent ``c`` [rank] plus one roped key [r] a token; a group serves its
signal heads and one noise head, ``o_s = A_s − λ_s A_noise`` with ``λ =
σ(x W_λ)``; an element-wise output gate ``σ(x W_g)``. Kept layer ``i`` is a
full layer when ``(i+1) % sliding_window_period == 0`` and attends over
the last ``sliding_window`` tokens otherwise: a window layer's cache is a
ring of that many rows (slot ``pos % window``), a full layer's a buffer of
``max_len`` rows — two kinds of cache in one carry.

FFNs are gated MLPs under PolyNorm (:func:`poly_norm_gate`); the first
``n_dense_first_layers`` are dense, the rest ``ops/expert_share.py``'s
expert layer (an ungrouped sigmoid router, no selection bias, this chip's
share of the experts). The vocabulary may be a slice, as in
``llm_hybrid.py``, whose conventions this module keeps: plain pytrees
held in ``dtype``, matrix products on ``dtype`` operands accumulated in
float32; the streams, every mHC coefficient and Sinkhorn round, norms,
PolyNorm, softmax, ``λ`` and the gates, router scores and logits float32;
:func:`prefill` / :func:`decode_step` / :func:`empty_cache` with the same
signatures; one sequence, no batch axis. ``models/llm_motif_reference.py``
is the plain float32 statement both paths are held to.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..ops import expert_share, latent_attention as mla_ops
from ..telemetry.device_scopes import device_scope, device_scoped
from .llm_hybrid import (_const, _count_held, _dot, _embed, _normal,
                         _pre_norm, _stack_counts, count_params, init_tree,
                         logits_of, rms_norm)
from .llm_model import LLMModel


@dataclasses.dataclass(frozen=True)
class MotifConfig:
    """Field names are the published ``config.json``'s. ``num_experts`` is
    how many experts are HELD here (``router_experts`` is the layer's
    count), ``vocab_size`` how many rows of the vocabulary,
    ``num_hidden_layers`` the depth kept."""
    hidden_size: int = 4096
    num_hidden_layers: int = 5
    n_dense_first_layers: int = 1
    intermediate_size: int = 12288
    num_attention_heads: int = 80          # signal + noise
    num_key_value_heads: int = 16          # groups
    num_noise_heads: int = 16              # one a group
    head_dim: int = 192                    # nope + rope
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    sliding_window: int = 128
    sliding_window_period: int = 4
    mhc_expansion_rate: int = 4
    mhc_sinkhorn_iters: int = 20
    router_experts: int = 384
    num_experts: int = 48
    first_expert: int = 0
    experts_top_k: int = 8
    route_scale: float = 2.0
    moe_intermediate_size: int = 1280
    polynorm_output_scale: float = 0.5
    polynorm_bias_clamp: float = 0.5
    hidden_clamp: float = 1e6
    vocab_size: int = 27520
    dtype: str = "bfloat16"

    @classmethod
    def motif_share(cls) -> "MotifConfig":
        """Motif-3-Beta at its published widths: one chip's share of an
        8-chip expert group (experts 0–47 of 384, an eighth of the
        vocabulary), one dense layer and one whole window/full period."""
        return cls()

    @classmethod
    def tiny(cls, **kw) -> "MotifConfig":
        """The CPU tests' size, float32: every mechanism, small widths, a
        router wider than the experts held, a window the tests outrun."""
        base = dict(
            hidden_size=32, intermediate_size=48, num_attention_heads=10,
            num_key_value_heads=2, num_noise_heads=2, head_dim=12,
            q_lora_rank=24, kv_lora_rank=16, qk_rope_head_dim=4,
            v_head_dim=8, sliding_window=4, router_experts=16,
            num_experts=8, experts_top_k=4, moe_intermediate_size=16,
            vocab_size=64, dtype="float32")
        return cls(**{**base, **kw})

    @property
    def model(self) -> LLMModel:
        return MODEL

    def is_full(self, i: int) -> bool:
        return (i + 1) % self.sliding_window_period == 0

    def is_moe(self, i: int) -> bool:
        return i >= self.n_dense_first_layers

    @property
    def moe_layers(self) -> list[int]:
        return [i for i in range(self.num_hidden_layers) if self.is_moe(i)]

    @property
    def routing(self) -> expert_share.Routing:
        return expert_share.Routing(self.router_experts, self.experts_top_k,
                                    1, 1, self.route_scale)

    @property
    def routed_slots_per_token(self) -> int:
        return self.experts_top_k * len(self.moe_layers)

    @property
    def stream_mixes_per_token(self) -> int:
        return 2 * self.num_hidden_layers

    min_prompt_tokens = 1

    @property
    def qk_nope_head_dim(self) -> int:
        return self.head_dim - self.qk_rope_head_dim

    @property
    def signal_heads(self) -> int:
        return self.num_attention_heads - self.num_noise_heads

    @property
    def hc_outputs(self) -> int:
        n = self.mhc_expansion_rate
        return 2 * n + n * n


# --- weights ---------------------------------------------------------------


_POLY = _const((1 / 3, 1 / 3, 1 / 3, 0.0))       # PolyNorm's w₁ w₂ w₃ b


def _shapes(cfg: MotifConfig) -> dict:
    """Every leaf as ``(shape, dtype name, init)``."""
    D, n, wd = cfg.hidden_size, cfg.mhc_expansion_rate, cfg.dtype
    G, nope, rope = (cfg.num_key_value_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim)
    gate = cfg.signal_heads * cfg.v_head_dim
    one = _const(1.0)

    def hc():
        return {"gamma": ((n * D,), "float32", one),
                "phi": ((n * D, cfg.hc_outputs), wd, _normal()),
                "alpha": ((3,), "float32", _const(0.01)),
                # mhc_identity_init false: drawn, not set to favour H = I
                "bias": ((cfg.hc_outputs,), "float32", _normal(1.0)),
                "norm": ((D,), "float32", one)}

    def ffn(width):
        return {"w_gu": ((D, 2 * width), wd, _normal()),
                "w_down": ((width, D), wd, _normal()),
                "poly": ((4,), "float32", _POLY)}

    layers = []
    for i in range(cfg.num_hidden_layers):
        layer = {"attn_hc": hc(), "ffn_hc": hc(), "attn": {
            # [c_q (r_q) | c (rank) | k_rope | λ (signal heads) | gate]
            "w_in": ((D, cfg.q_lora_rank + cfg.kv_lora_rank + rope
                      + cfg.signal_heads + gate), wd, _normal()),
            "q_norm": ((cfg.q_lora_rank,), "float32", one),
            "c_norm": ((cfg.kv_lora_rank,), "float32", one),
            "w_uq": ((cfg.q_lora_rank,
                      cfg.num_attention_heads * cfg.head_dim), wd,
                     _normal()),
            # per group [k_nope | v]
            "w_b": ((cfg.kv_lora_rank, G * (nope + cfg.v_head_dim)), wd,
                    _normal()),
            "w_o": ((gate, D), wd, _normal())}}
        if cfg.is_moe(i):
            F = cfg.moe_intermediate_size
            layer["moe"] = {
                "w_router": ((D, cfg.router_experts), wd, _normal()),
                "shared": ffn(F),
                "e_gu": ((cfg.num_experts, D, 2 * F), wd, _normal()),
                "e_down": ((cfg.num_experts, F, D), wd, _normal()),
                "e_poly": ((cfg.num_experts, 4), "float32", _POLY)}
        else:
            layer["ffn"] = ffn(cfg.intermediate_size)
        layers.append(layer)
    return {"embed": ((cfg.vocab_size, D), wd, _normal(1.0)),
            "head": ((cfg.vocab_size, D), wd, _normal(1.0 / math.sqrt(D))),
            "final_norm": ((D,), "float32", one),
            "layers": layers}


def init_motif(cfg: MotifConfig, key, abstract: bool = False):
    return init_tree(_shapes(cfg), key, abstract)


def param_count(cfg: MotifConfig) -> int:
    return count_params(_shapes(cfg))


# --- pieces shared by prefill and decode -----------------------------------


def sinkhorn(m, iters: int):
    """``iters`` rounds on positive ``m`` [...,n,n]: every row by its sum,
    then every column by its sum. Float32."""
    for _ in range(iters):
        m = m / m.sum(-1, keepdims=True)
        m = m / m.sum(-2, keepdims=True)
    return m


def hc_coefficients(cfg: MotifConfig, p, X):
    """``X`` [...,n,D] → ``(H_pre [...,n], H_post [...,n], H_res
    [...,n,n])``, float32."""
    n = cfg.mhc_expansion_rate
    with device_scope("llm_mix"):
        flat = X.reshape(*X.shape[:-2], n * X.shape[-1])
        u = jnp.dot(rms_norm(flat, p["gamma"], cfg.rms_norm_eps),
                    p["phi"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
        a, b = p["alpha"], p["bias"]
        pre = jax.nn.sigmoid(a[0] * u[..., :n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * u[..., n:2 * n] + b[n:2 * n])
        res = sinkhorn(jnp.exp(
            a[2] * u[..., 2 * n:].reshape(*u.shape[:-1], n, n)
            + b[2 * n:].reshape(n, n)), cfg.mhc_sinkhorn_iters)
    return pre, post, res


def hyper_connect(cfg: MotifConfig, p, X, sublayer):
    """One sublayer under the mixed residual. ``sublayer(x [...,D]) ->
    (y [...,D], extra)``; answers ``(X', extra)``. The sublayer opens its
    own device scopes; what is traced here is the mixer's and the norm's."""
    pre, post, res = hc_coefficients(cfg, p, X)
    with device_scope("llm_mix"):
        merged = (pre[..., None] * X).sum(-2)
    x = _pre_norm(merged, p["norm"], cfg.rms_norm_eps)
    y, extra = sublayer(x)
    with device_scope("llm_mix"):
        mixed = (res[..., None] * X[..., None, :, :]).sum(-2)
        out = mixed + post[..., None] * y[..., None, :]
        return jnp.clip(out, -cfg.hidden_clamp, cfg.hidden_clamp), extra


def poly_norm_gate(cfg: MotifConfig):
    """PolyNorm as a gated MLP's activation: ``scale · P(g) ⊙ u`` with
    ``P(z) = w₁N(z³) + w₂N(z²) + w₃N(z) + clip(b)``, ``N(u) = u /
    √(mean(u²) + eps)`` over the width; ``params`` [...,4] = ``w₁ w₂ w₃
    b``. Float32."""
    eps, clamp = cfg.rms_norm_eps, cfg.polynorm_bias_clamp

    def norm(z):
        return z * jax.lax.rsqrt((z * z).mean(-1, keepdims=True) + eps)

    def act(g, u, params):
        w1, w2, w3, b = (params[..., k:k + 1] for k in range(4))
        p = (w1 * norm(g * g * g) + w2 * norm(g * g) + w3 * norm(g)
             + jnp.clip(b, -clamp, clamp))
        return cfg.polynorm_output_scale * p * u

    return act


def _mlp(cfg, x, ffn, dtype):
    return expert_share.gated_mlp(x, ffn["w_gu"], ffn["w_down"], dtype,
                                  poly_norm_gate(cfg), ffn["poly"])


def _split_in(cfg: MotifConfig, p, y, positions):
    """From ``x W_in`` [T,·]: the queries grouped ``[T,G,J,·]`` (nope and
    roped rope parts, a group's noise head last), the normed latent, the
    roped shared key, ``λ`` [T,G,J−1] and the output gate [T,signal·v]."""
    rq, rank, rope = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    G, S, eps = cfg.num_key_value_heads, cfg.signal_heads, cfg.rms_norm_eps
    dtype = jnp.dtype(cfg.dtype)
    T = y.shape[0]
    q = _dot(rms_norm(y[:, :rq], p["q_norm"], eps), p["w_uq"], dtype)
    q = q.reshape(T, cfg.num_attention_heads, cfg.head_dim)
    # head h < S: signal, group h // (J−1); head S + g: group g's noise
    q = jnp.concatenate([q[:, :S].reshape(T, G, -1, cfg.head_dim),
                         q[:, S:, None]], axis=2)
    c = rms_norm(y[:, rq:rq + rank], p["c_norm"], eps)
    at = rq + rank + rope
    kr = mla_ops.rope_interleaved(y[:, rq + rank:at], positions,
                                  cfg.rope_theta)
    nope = cfg.qk_nope_head_dim
    q_rope = mla_ops.rope_interleaved(
        q[..., nope:].reshape(T, -1, rope), positions,
        cfg.rope_theta).reshape(T, G, -1, rope)
    lam = jax.nn.sigmoid(y[:, at:at + S]).reshape(T, G, -1)
    return q[..., :nope], q_rope, c, kr, lam, y[:, at + S:]


def _w_b(cfg: MotifConfig, p):
    w = p["w_b"].reshape(cfg.kv_lora_rank, cfg.num_key_value_heads, -1)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _attn_out(p, o, gate_raw, dtype):
    o = o.reshape(*o.shape[:-3], -1) * jax.nn.sigmoid(gate_raw)
    return _dot(o, p["w_o"], dtype)


def _scale(cfg: MotifConfig) -> float:
    return 1.0 / math.sqrt(cfg.head_dim)


# --- prefill ---------------------------------------------------------------


def empty_cache(cfg: MotifConfig, max_len: int) -> dict:
    """Per layer the latent and the roped key: a ring of
    ``sliding_window`` rows for a window layer, ``max_len`` rows for a
    full layer."""
    dtype = jnp.dtype(cfg.dtype)
    rows = [max_len if cfg.is_full(i) else cfg.sliding_window
            for i in range(cfg.num_hidden_layers)]
    return {"c": [jnp.zeros((r, cfg.kv_lora_rank), dtype) for r in rows],
            "kr": [jnp.zeros((r, cfg.qk_rope_head_dim), dtype) for r in rows]}


def cache_kinds(cfg: MotifConfig, cache: dict) -> dict:
    def of(full):
        return [cache[k][i] for k in ("c", "kr")
                for i in range(cfg.num_hidden_layers)
                if cfg.is_full(i) == full]

    return {"window": of(False), "full": of(True)}


def prefill(cfg: MotifConfig, params, ids, max_len: int,
            all_logits: bool = False):
    """The whole prompt ``ids`` [T] at once; answers as
    ``llm_hybrid.prefill``: ``(logits, cache, held)``."""
    dtype = jnp.dtype(cfg.dtype)
    T, W = ids.shape[0], cfg.sliding_window
    with device_scope("llm_attn"):
        positions = jnp.arange(T)
        cache = empty_cache(cfg, max_len)
    held = []
    e = _embed(params, ids)
    with device_scope("llm_mix"):
        X = jnp.broadcast_to(e[:, None], (T, cfg.mhc_expansion_rate,
                                          cfg.hidden_size))
    for i, layer in enumerate(params["layers"]):
        p = layer["attn"]
        full = cfg.is_full(i)

        @device_scoped("llm_attn")
        def attention(x):
            q_nope, q_rope, c, kr, lam, gate = _split_in(
                cfg, p, _dot(x, p["w_in"], dtype), positions)
            o = mla_ops.gdla_naive(q_nope, q_rope, c, kr, *_w_b(cfg, p), lam,
                                   _scale(cfg), dtype,
                                   window=None if full else W)
            return _attn_out(p, o, gate, dtype), (c, kr)

        X, (c, kr) = hyper_connect(cfg, layer["attn_hc"], X, attention)
        with device_scope("llm_attn"):
            if full:
                rows = slots = positions
            else:              # the ring: position t lives in slot t % W
                rows = jnp.arange(max(0, T - W), T)
                slots = rows % W
            cache["c"][i] = cache["c"][i].at[slots].set(
                c[rows].astype(dtype))
            cache["kr"][i] = cache["kr"][i].at[slots].set(
                kr[rows].astype(dtype))
        if cfg.is_moe(i):
            m = layer["moe"]

            def experts(x):
                idx, w = expert_share.route(x, m["w_router"], None,
                                            cfg.routing)
                y, _ = expert_share.held_part(
                    x, idx, w, m["e_gu"], m["e_down"], cfg.first_expert,
                    dtype, cfg.routing, poly_norm_gate(cfg), m["e_poly"],
                    expert_chunk=math.gcd(cfg.num_experts, 8))
                with device_scope("llm_shared_ffn"):
                    return y + _mlp(cfg, x, m["shared"], dtype), idx

            X, idx = hyper_connect(cfg, layer["ffn_hc"], X, experts)
            held.append(_count_held(cfg, idx))
        else:
            X, _ = hyper_connect(
                cfg, layer["ffn_hc"], X, device_scoped("llm_shared_ffn")(
                    lambda x: (_mlp(cfg, x, layer["ffn"], dtype), None)))
    with device_scope("llm_mix"):
        h = X.sum(-2)
        h = h if all_logits else h[-1]
    logits = logits_of(cfg, params, h)
    return logits, cache, _stack_counts(held)


# --- decode ----------------------------------------------------------------


def decode_step(cfg: MotifConfig, params, cache: dict, token, pos):
    """One token ``token`` (scalar id) at position ``pos`` through the
    cache; answers as ``llm_hybrid.decode_step``."""
    dtype = jnp.dtype(cfg.dtype)
    W = cfg.sliding_window
    with device_scope("llm_attn"):
        positions = jnp.reshape(pos, (1,))
    cache = {k: list(v) for k, v in cache.items()}
    held = []
    e = _embed(params, token)
    with device_scope("llm_mix"):
        X = jnp.broadcast_to(e, (cfg.mhc_expansion_rate, cfg.hidden_size))
    for i, layer in enumerate(params["layers"]):
        p = layer["attn"]
        with device_scope("llm_attn"):
            slot = pos if cfg.is_full(i) else pos % W

        @device_scoped("llm_attn")
        def attention(x):
            q_nope, q_rope, c, kr, lam, gate = _split_in(
                cfg, p, _dot(x[None], p["w_in"], dtype), positions)
            c_cache = jax.lax.dynamic_update_slice(
                cache["c"][i], c.astype(dtype), (slot, 0))
            kr_cache = jax.lax.dynamic_update_slice(
                cache["kr"][i], kr.astype(dtype), (slot, 0))
            # a ring slot j holds a position ≤ pos once j ≤ pos: all of
            # them after the first lap
            valid = jnp.arange(c_cache.shape[0]) <= pos
            o = mla_ops.gdla_absorbed_step(
                q_nope[0], q_rope[0], c_cache, kr_cache, valid,
                *_w_b(cfg, p), lam[0], _scale(cfg), dtype)
            return _attn_out(p, o, gate[0], dtype), (c_cache, kr_cache)

        X, (cache["c"][i], cache["kr"][i]) = hyper_connect(
            cfg, layer["attn_hc"], X, attention)
        if cfg.is_moe(i):
            m = layer["moe"]

            def experts(x):
                idx, w = expert_share.route(x[None], m["w_router"], None,
                                            cfg.routing)
                y = expert_share.held_part_token(
                    x, idx[0], w[0], m["e_gu"], m["e_down"],
                    cfg.first_expert, dtype, poly_norm_gate(cfg),
                    m["e_poly"])
                with device_scope("llm_shared_ffn"):
                    return y + _mlp(cfg, x[None], m["shared"], dtype)[0], idx

            X, idx = hyper_connect(cfg, layer["ffn_hc"], X, experts)
            held.append(_count_held(cfg, idx))
        else:
            X, _ = hyper_connect(
                cfg, layer["ffn_hc"], X, device_scoped("llm_shared_ffn")(
                    lambda x: (_mlp(cfg, x[None], layer["ffn"], dtype)[0],
                               None)))
    with device_scope("llm_mix"):
        h = X.sum(-2)
    return logits_of(cfg, params, h), cache, _stack_counts(held)


MODEL = LLMModel(init_motif, prefill, decode_step, empty_cache, cache_kinds)
