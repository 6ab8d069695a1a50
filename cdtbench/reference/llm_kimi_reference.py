"""The plain reference of ``models/llm_kimi.py``: the whole forward pass of
the cut stack in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — every head's keys and values
decompressed from the latent, one ``T×T`` causal softmax a head, every
expert it is given applied to every token by a loop and masked; no cache,
no chunks, no blocks of keys, no absorption, no groups, no kernels. It
shares nothing with the served code but the layout of the weight tree, and
it is given the same share of the experts and of the vocabulary (what the
absent experts would add is left out here as there).

The equations (``D`` hidden, per token ``t`` unless said; ε =
``rms_norm_eps``; no bias anywhere):

* ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; ``logits =
  RMSNorm(y_L) W_headᵀ``; ``x_0 = E[id]``.
* attention: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` → heads ×
  ``[q_nope | q_rope]``; ``[c_kv | k_r] = x W_kva``; ``c = RMSNorm(c_kv)``;
  ``k_rope = RoPE(k_r)`` (one for all heads, not normed); ``q_rope =
  RoPE(q_rope)``; ``[k_nope | v]_h = c W_b,h``; ``s_h(t,j) = (q_nope,h ·
  k_nope,h,j + q_rope,h · k_rope,j) · scale`` for ``j ≤ t``; ``o =
  concat_h(Σ_j softmax_j(s_h)(t,j) v_h,j) W_o``.
* YaRN on the ``d`` rope dimensions (pairs ``(2i, 2i+1)`` turn by ``t ·
  g_i``): ``f_i = θ^(−2i/d)``; ``corr(n) = d · ln(L₀ / (2π n)) / (2 ln
  θ)``; ``low = ⌊corr(β_fast)⌋``, ``high = ⌈corr(β_slow)⌉``, clamped to
  ``[0, d−1]``; ``ramp_i = clip((i − low) / (high − low), 0, 1)``; ``g_i =
  f_i · ((1 − ramp_i) + ramp_i / s)``; cos and sin are not scaled
  (``mscale / mscale_all_dim`` = 1); ``scale = (nope + rope)^(−½) · m²``,
  ``m = 0.1 · mscale_all_dim · ln s + 1``.
* dense FFN (layers below ``first_k_dense_replace``): ``(silu(x W_g) ⊙ x
  W_u) W_down``.
* expert layer: ``σ = sigmoid(x W_r)`` over ALL the router's experts; the
  ``k`` largest of ``σ + b``; weights ``σ_e / Σ_selected σ ·
  routed_scaling_factor``; ``y = Shared(x) + Σ_{e ∈ selected ∩ held} w_e
  Expert_e(x)``, experts and the shared expert SwiGLU.

What the published ``config.json`` does not settle is set as the
configuration's file lists under ``assumed``
(cdtbench/configs/kimi-k2.6.json); the served model departs from this
file nowhere.

``forward(cfg, params, ids)`` answers the float32 logits at every position
(or at ``positions``). It runs layer by layer (one jitted call each) so
that at the published widths only one layer's float32 copy of the weights
exists at a time. With ``block`` the SAME functions are evaluated for
``block`` query rows at a time (a row of attention sees all the keys below
it either way, the FFNs are per row): for a prompt whose ``T×T`` does not
fit. ``cdtbench/reference/llm_kimi_reference.py`` is a copy of this file
(``tests/test_llm_kimi.py`` holds the two equal).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_table(cfg):
    """``g_i``, ``i < d/2``, and the softmax scale."""
    d, theta, s = cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor

    def corr(n):
        return d * math.log(cfg.rope_original_len / (2 * math.pi * n)) \
            / (2 * math.log(theta))

    low = min(max(math.floor(corr(cfg.rope_beta_fast)), 0), d - 1)
    high = min(max(math.ceil(corr(cfg.rope_beta_slow)), 0), d - 1)
    g = []
    for i in range(d // 2):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        g.append(theta ** (-2.0 * i / d) * ((1.0 - ramp) + ramp / s))
    m = 0.1 * cfg.rope_mscale_all_dim * math.log(s) + 1.0
    scale = m * m / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    return jnp.asarray(g, F32), scale


def _rope(x, positions, g):
    """Interleaved pairs; ``x`` [T,...,d] at ``positions`` [T]."""
    ang = (positions.astype(F32)[:, None] * g).reshape(
        x.shape[0], *([1] * (x.ndim - 2)), g.shape[0])
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.zeros_like(x)
    out = out.at[..., 0::2].set(even * jnp.cos(ang) - odd * jnp.sin(ang))
    return out.at[..., 1::2].set(even * jnp.sin(ang) + odd * jnp.cos(ang))


def swiglu(ffn, x):
    g, u = jnp.split(x @ ffn["w_gu"], 2, axis=-1)
    return (jax.nn.silu(g) * u) @ ffn["w_down"]


def attention(cfg, p, x, lo=0, n: int | None = None):
    """Rows ``lo .. lo+n−1`` of the attention's output over the sequence
    ``x`` [T,D] (all of them by default): a row sees every ``j ≤``
    itself."""
    T = x.shape[0]
    n = T if n is None else n
    H, nope, rope, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    rq, rank = cfg.q_lora_rank, cfg.kv_lora_rank
    g, scale = yarn_table(cfg)
    t = jnp.arange(T)
    y = x @ p["w_a"]
    c = _rms(y[:, rq:rq + rank], p["c_norm"], cfg.rms_norm_eps)
    k_rope = _rope(y[:, rq + rank:], t, g)
    rows = lo + jnp.arange(n)
    q = _rms(y[rows, :rq], p["q_norm"], cfg.rms_norm_eps) @ p["w_qb"]
    # W_qb's columns: every head's nope part, then every head's rope part
    q_nope = q[:, :H * nope].reshape(n, H, nope)
    q_rope = _rope(q[:, H * nope:].reshape(n, H, rope), rows, g)
    w_b = p["w_b"].reshape(rank, H, nope + dv)
    seen = rows[:, None] >= t[None, :]

    def head(args):
        qn, qr, w = args
        kv = c @ w                                           # [T, nope+dv]
        s = (qn @ kv[:, :nope].T + qr @ k_rope.T) * scale
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return a @ kv[:, nope:]

    o = jax.lax.map(head, (jnp.swapaxes(q_nope, 0, 1),
                           jnp.swapaxes(q_rope, 0, 1),
                           jnp.swapaxes(w_b, 0, 1)))              # [H,n,dv]
    return jnp.swapaxes(o, 0, 1).reshape(n, H * dv) @ p["w_o"]


def experts(cfg, m, x):
    """The held experts' part of the routed result, plus the shared
    expert: every held expert on every token, masked by the routing."""
    s = jax.nn.sigmoid(x @ m["w_router"])
    biased = s + m["router_bias"]
    kth = jnp.sort(biased, axis=-1)[:, -cfg.num_experts_per_tok][:, None]
    selected = biased >= kth
    weight = jnp.where(selected, s, 0.0)
    weight = weight / weight.sum(-1, keepdims=True) \
        * cfg.routed_scaling_factor
    out = swiglu(m["shared"], x)
    for local in range(cfg.n_routed_experts):
        e = cfg.first_expert + local
        out = out + weight[:, e:e + 1] * swiglu(
            {"w_gu": m["e_gu"][local], "w_down": m["e_down"][local]}, x)
    held = selected[:, cfg.first_expert:cfg.first_expert
                    + cfg.n_routed_experts]
    return out, held.sum()


@functools.partial(jax.jit, static_argnums=(0, 4))
def attention_rows(cfg, norm, p, lo, n: int, x):
    """``x[lo:lo+n] + Attn(RMSNorm(x))[lo:lo+n]`` of one layer."""
    with jax.default_matmul_precision("highest"):
        normed = _rms(x, norm.astype(F32), cfg.rms_norm_eps)
        return jax.lax.dynamic_slice_in_dim(x, lo, n, 0) \
            + attention(cfg, _f32(p), normed, lo, n)


@functools.partial(jax.jit, static_argnums=0)
def ffn_rows(cfg, norm, ffn, h):
    """``h + FFN(RMSNorm(h))`` on the rows given — ``ffn`` a dense layer's
    SwiGLU or an expert layer's ``moe`` — and the routed slots of those
    rows that fell on held experts."""
    with jax.default_matmul_precision("highest"):
        ffn = _f32(ffn)
        x = _rms(h, norm.astype(F32), cfg.rms_norm_eps)
        if "w_router" in ffn:
            y, held = experts(cfg, ffn, x)
            return h + y, held
        return h + swiglu(ffn, x), jnp.zeros((), jnp.int32)


@functools.partial(jax.jit, static_argnums=0)
def head_forward(cfg, final_norm, head, h):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, final_norm.astype(F32), cfg.rms_norm_eps)
        return x @ head.astype(F32).T


def forward(cfg, params, ids, positions=None, block: int | None = None):
    """Float32 logits [T,V] (or at ``positions`` only) for the whole
    sequence ``ids`` [T], and per layer the routed slots that fell on
    held experts (0 for a dense layer)."""
    T = ids.shape[0]
    block = T if block is None else block
    x = params["embed"][ids].astype(F32)
    held = []
    for i, layer in enumerate(params["layers"]):
        ffn = layer["moe" if i >= cfg.first_k_dense_replace else "ffn"]
        parts = [ffn_rows(cfg, layer["norm2"], ffn, attention_rows(
            cfg, layer["norm1"], layer["attn"], lo, min(block, T - lo), x))
            for lo in range(0, T, block)]
        x = jnp.concatenate([part for part, _ in parts])
        held.append(sum(n for _, n in parts))
    if positions is not None:
        x = x[jnp.asarray(positions)]
    return head_forward(cfg, params["final_norm"], params["head"], x), held
