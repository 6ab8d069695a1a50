"""The plain reference of ``models/llm_motif.py``: the whole forward pass of
the cut stack in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — every head's keys and values
decompressed from the latent, one ``T×T`` softmax a head under the layer's
mask, the noise head subtracted AFTER decompression, the four streams mixed
by the equations as written, every held expert applied to every token and
masked; no cache, no ring, no absorption, no blocks, no chunks, no kernels.
It shares nothing with the served code but the layout of the weight tree,
and it is given the same share of the experts and of the vocabulary (what
the absent experts would add is left out here as there).

The equations (``D`` hidden, ``n`` streams, per token unless said):

* streams ``X_0 = [e]·n``, ``e = E[id]``; ``logits = RMSNorm(Σ_j X_L[j])
  W_head``.
* around every sublayer ``F`` (its own ``γ, Φ, α, b``): ``x̃ =
  RMSNorm_γ(vec X)``; ``[u_pre|u_post|u_res] = x̃ Φ``; ``H_pre = σ(α_pre
  u_pre + b_pre)``; ``H_post = 2σ(α_post u_post + b_post)``; ``H_res =
  Sinkhorn(exp(α_res mat(u_res) + B_res))``, each round rows by their sums
  then columns by their sums; ``y = F(RMSNorm(Σ_j H_pre[j] X[j]))``;
  ``X'[i] = Σ_j H_res[i,j] X[j] + H_post[i] y``, clipped to
  ``±hidden_clamp``.
* GDLA: ``c_q = RMSNorm(x W_dq)``, ``q = c_q W_uq`` (heads × (nope|rope),
  RoPE on the rope part); ``[c|k^r] = x W_dkv``, ``c ← RMSNorm(c)``, ``k^r
  ← RoPE(k^r)``; group ``g``: ``k_g = [c W_uk,g | k^r]``, ``v_g = c
  W_uv,g``; signal head ``h`` uses group ``h // (signal heads a group)``,
  noise head ``S + g`` group ``g``; ``A_h = softmax_{j ∈ vis(t)}(q_h·k_g /
  √head_dim) v_g``, ``vis(t) = {j ≤ t}`` on a full layer (``(i+1) % period
  == 0``), ``{t − window < j ≤ t}`` else; ``λ = σ(x W_λ)``; ``o_s = A_s −
  λ_s A_{S + s // (signal heads a group)}``; ``out = (o ⊙ σ(x W_g)) W_o``.
* PolyNorm MLP: ``N(u) = u/√(mean(u²)+eps)``; ``P(z) = w₁N(z³) + w₂N(z²) +
  w₃N(z) + clip(b, ±bias_clamp)``; ``MLP(x) = (scale · P(x W_gate) ⊙ x
  W_up) W_down``.
* expert layer: ``s = σ(x W_r)``; the ``top_k`` largest; weights
  ``route_scale · s_e / Σ_sel s``; ``y = Shared(x) + Σ_{e ∈ sel ∩ held} w_e
  Expert_e(x)``.

What the published ``config.json`` does not settle is set as the
configuration's file lists under ``assumed``
(cdtbench/configs/motif-3-beta.json); the served model departs from this
file nowhere.

``forward(cfg, params, ids)`` answers the float32 logits at every position
(or at ``positions``). It runs layer by layer (one jitted call each) so that
at the published widths only one layer's float32 copy of the weights exists
at a time. ``cdtbench/reference/llm_motif_reference.py`` is a copy of this
file (``tests/test_llm_motif.py`` holds the two equal).
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


def _rope(x, theta):
    """Interleaved pairs; ``x`` [T,...,d] at positions 0..T−1."""
    T, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = (jnp.arange(T, dtype=F32)[:, None] * inv).reshape(
        T, *([1] * (x.ndim - 2)), d // 2)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.zeros_like(x)
    out = out.at[..., 0::2].set(even * jnp.cos(ang) - odd * jnp.sin(ang))
    return out.at[..., 1::2].set(even * jnp.sin(ang) + odd * jnp.cos(ang))


def poly_mlp(cfg, ffn, x, poly=None):
    poly = ffn["poly"] if poly is None else poly
    g, u = jnp.split(x @ ffn["w_gu"], 2, axis=-1)

    def n(z):
        return z / jnp.sqrt(jnp.mean(z * z, axis=-1, keepdims=True)
                            + cfg.rms_norm_eps)

    p = poly[0] * n(g ** 3) + poly[1] * n(g ** 2) + poly[2] * n(g) \
        + jnp.clip(poly[3], -cfg.polynorm_bias_clamp,
                   cfg.polynorm_bias_clamp)
    return (cfg.polynorm_output_scale * p * u) @ ffn["w_down"]


def gdla(cfg, p, x, full: bool):
    """Grouped differential latent attention over the sequence ``x``
    [T,D]."""
    T = x.shape[0]
    H, S, G = (cfg.num_attention_heads,
               cfg.num_attention_heads - cfg.num_noise_heads,
               cfg.num_key_value_heads)
    per_group = S // G
    rope, nope, dv = (cfg.qk_rope_head_dim,
                      cfg.head_dim - cfg.qk_rope_head_dim, cfg.v_head_dim)
    rq, rank = cfg.q_lora_rank, cfg.kv_lora_rank
    y = x @ p["w_in"]
    c_q = _rms(y[:, :rq], p["q_norm"], cfg.rms_norm_eps)
    c = _rms(y[:, rq:rq + rank], p["c_norm"], cfg.rms_norm_eps)
    at = rq + rank + rope
    k_rope = _rope(y[:, rq + rank:at], cfg.rope_theta)
    lam = jax.nn.sigmoid(y[:, at:at + S])
    gate = jax.nn.sigmoid(y[:, at + S:])
    q = (c_q @ p["w_uq"]).reshape(T, H, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:],
                                              cfg.rope_theta)], axis=-1)
    kv = (c @ p["w_b"]).reshape(T, G, nope + dv)
    t = jnp.arange(T)
    seen = t[:, None] >= t[None, :]
    if not full:
        seen &= t[None, :] > t[:, None] - cfg.sliding_window
    heads = []
    for h in range(H):
        g = h // per_group if h < S else h - S
        k = jnp.concatenate([kv[:, g, :nope], k_rope], axis=-1)
        s = q[:, h] @ k.T / math.sqrt(nope + rope)
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        heads.append(a @ kv[:, g, nope:])
    out = [heads[s] - lam[:, s:s + 1] * heads[S + s // per_group]
           for s in range(S)]
    return (jnp.concatenate(out, axis=-1) * gate) @ p["w_o"]


def experts(cfg, m, x):
    """The held experts' part of the routed result, plus the shared
    expert: every held expert on every token, masked by the routing."""
    s = jax.nn.sigmoid(x @ m["w_router"])
    kth = jnp.sort(s, axis=-1)[:, -cfg.experts_top_k][:, None]
    selected = s >= kth
    weight = jnp.where(selected, s, 0.0)
    weight = weight / weight.sum(-1, keepdims=True) * cfg.route_scale
    out = poly_mlp(cfg, m["shared"], x)
    for local in range(cfg.num_experts):
        e = cfg.first_expert + local
        out = out + weight[:, e:e + 1] * poly_mlp(
            cfg, {"w_gu": m["e_gu"][local], "w_down": m["e_down"][local]},
            x, m["e_poly"][local])
    held = selected[:, cfg.first_expert:cfg.first_expert + cfg.num_experts]
    return out, held.sum()


def sinkhorn(m, iters):
    for _ in range(iters):
        m = m / m.sum(-1, keepdims=True)        # each row by its sum
        m = m / m.sum(-2, keepdims=True)        # each column by its sum
    return m


def hyper_connect(cfg, p, X, sublayer):
    """``X`` [T,n,D] through one sublayer under the mixed residual."""
    T, n, D = X.shape
    u = _rms(X.reshape(T, n * D), p["gamma"], cfg.rms_norm_eps) @ p["phi"]
    a, b = p["alpha"], p["bias"]
    h_pre = jax.nn.sigmoid(a[0] * u[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * u[:, n:2 * n] + b[n:2 * n])
    h_res = sinkhorn(jnp.exp(a[2] * u[:, 2 * n:].reshape(T, n, n)
                             + b[2 * n:].reshape(n, n)),
                     cfg.mhc_sinkhorn_iters)
    x = _rms(jnp.einsum("tj,tjd->td", h_pre, X), p["norm"],
             cfg.rms_norm_eps)
    y, extra = sublayer(x)
    out = jnp.einsum("tij,tjd->tid", h_res, X) \
        + h_post[:, :, None] * y[:, None, :]
    return jnp.clip(out, -cfg.hidden_clamp, cfg.hidden_clamp), extra


@functools.partial(jax.jit, static_argnums=(0, 1))
def layer_forward(cfg, i: int, layer, X):
    """Layer ``i`` on the streams ``X`` [T,n,D] (float32)."""
    with jax.default_matmul_precision("highest"):
        layer = _f32(layer)
        full = (i + 1) % cfg.sliding_window_period == 0
        X, _ = hyper_connect(
            cfg, layer["attn_hc"], X,
            lambda x: (gdla(cfg, layer["attn"], x, full), None))
        if i >= cfg.n_dense_first_layers:
            return hyper_connect(cfg, layer["ffn_hc"], X,
                                 lambda x: experts(cfg, layer["moe"], x))
        return hyper_connect(
            cfg, layer["ffn_hc"], X,
            lambda x: (poly_mlp(cfg, layer["ffn"], x),
                       jnp.zeros((), jnp.int32)))


@functools.partial(jax.jit, static_argnums=0)
def head_forward(cfg, final_norm, head, h):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, final_norm.astype(F32), cfg.rms_norm_eps)
        return x @ head.astype(F32).T


def forward(cfg, params, ids, positions=None):
    """Float32 logits [T,V] (or at ``positions`` only) for the whole
    sequence ``ids`` [T], and per layer the routed slots that fell on
    held experts (0 for a dense layer)."""
    e = params["embed"][ids].astype(F32)
    X = jnp.stack([e] * cfg.mhc_expansion_rate, axis=1)
    held = []
    for i, layer in enumerate(params["layers"]):
        X, n = layer_forward(cfg, i, layer, X)
        held.append(n)
    h = X.sum(axis=1)
    if positions is not None:
        h = h[jnp.asarray(positions)]
    return head_forward(cfg, params["final_norm"], params["head"], h), held
