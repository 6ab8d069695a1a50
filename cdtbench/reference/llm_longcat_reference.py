"""The plain reference of ``models/llm_longcat.py``: the whole forward pass
of the cut stack in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — every head's keys and values
decompressed from the latent, one causal softmax a head over all the keys
below a row, every expert it is given applied to every token by a loop and
masked, the identity experts' weights summed and multiplied out; no cache,
no chunks, no blocks of keys, no absorption, no groups, no kernels. It
shares nothing with the served code but the layout of the weight tree, and
it is given the same share of the experts and of the vocabulary (what the
absent experts would add is left out here as there).

The equations (``D`` hidden, per token ``t`` unless said; ε =
``rms_norm_eps``; no bias in any projection):

* a DOUBLE layer takes ``h``: for ``i`` in (0, 1): ``a = h +
  MLA_i(RMSNorm_in,i(h))``; ``y = RMSNorm_post,i(a)``; if ``i = 0``: ``m =
  MoE(y)``; ``h = a + SwiGLU_i(y)``; then ``h = h + m``. ``logits =
  RMSNorm(h_L) W_headᵀ``; ``h_0 = E[id]``.
* ``MLA(x)``: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb · √(D / r_q)`` →
  heads × ``[q_nope | q_rope]``; ``[c_kv | k_r] = x W_kva``; ``c =
  RMSNorm(c_kv) · √(D / r_kv)``; ``k_rope = RoPE(k_r)`` (one for all heads,
  neither normed nor scaled); ``q_rope = RoPE(q_rope)``; ``[k_nope | v]_h =
  c W_b,h``; ``s_h(t,j) = (q_nope,h · k_nope,h,j + q_rope,h · k_rope,j) ·
  (nope + rope)^(−½)`` for ``j ≤ t``; ``o = concat_h(Σ_j softmax_j(s_h)(t,j)
  v_h,j) W_o``. RoPE turns pairs ``(2i, 2i+1)`` by ``t · θ^(−2i/d)``.
* ``SwiGLU(x) = (silu(x W_g) ⊙ x W_u) W_down``.
* ``MoE(y)``: ``s = softmax(y W_r)`` over ALL the router's outputs (``E``
  real experts, then ``Z`` identity experts); the ``k`` largest of ``s +
  b``; weights ``w_e = routed_scaling_factor · s_e``, not normalised; ``m =
  Σ_{e < E, chosen, held} w_e SwiGLU_e(y) + (Σ_{e ≥ E, chosen} w_e) · y``.

What the published ``config.json`` does not settle is set as the
configuration's file lists under ``assumed``
(cdtbench/configs/longcat-flash-omni.json); the served model departs from
this file nowhere.

``forward(cfg, params, ids)`` answers the float32 logits at every position
(or at ``positions``). It is made of the pieces a walk needs — ``latents``
(a sublayer's keys), ``sublayer_rows`` (its output for some query rows
against given keys), ``head_forward`` — one jitted call each, so that at
the published widths only one sublayer's float32 copy of the weights
exists at a time. With ``block`` the SAME functions are evaluated for
``block`` query rows at a time (a row of attention sees all the keys below
it either way, the FFNs and the experts are per row): for a prompt whose
``T×T`` does not fit. ``cdtbench/reference/llm_longcat_reference.py`` is a
copy of this file (``tests/test_llm_longcat.py`` holds the two equal).
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


def _rope(x, positions, theta):
    """Interleaved pairs; ``x`` [T,...,d] at ``positions`` [T]."""
    d = x.shape[-1]
    g = jnp.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)], F32)
    ang = (positions.astype(F32)[:, None] * g).reshape(
        x.shape[0], *([1] * (x.ndim - 2)), g.shape[0])
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.zeros_like(x)
    out = out.at[..., 0::2].set(even * jnp.cos(ang) - odd * jnp.sin(ang))
    return out.at[..., 1::2].set(even * jnp.sin(ang) + odd * jnp.cos(ang))


def swiglu(ffn, x):
    g, u = jnp.split(x @ ffn["w_gu"], 2, axis=-1)
    return (jax.nn.silu(g) * u) @ ffn["w_down"]


def _scales(cfg):
    return (math.sqrt(cfg.hidden_size / cfg.q_lora_rank)
            if cfg.mla_scale_q_lora else 1.0,
            math.sqrt(cfg.hidden_size / cfg.kv_lora_rank)
            if cfg.mla_scale_kv_lora else 1.0)


def keys(cfg, p, x, positions):
    """The latent ``c`` [T,rank] (normed, scaled) and the roped shared key
    [T,rope] of the normed rows ``x`` at ``positions``."""
    rq, rank = cfg.q_lora_rank, cfg.kv_lora_rank
    y = x @ p["w_a"][:, rq:]
    c = _rms(y[:, :rank], p["c_norm"], cfg.rms_norm_eps) * _scales(cfg)[1]
    return c, _rope(y[:, rank:], positions, cfg.rope_theta)


def attention(cfg, p, x, rows, c, k_rope):
    """The attention's output for the normed query rows ``x`` [n,D] at
    positions ``rows`` against the keys ``c`` / ``k_rope`` of positions
    ``0 .. S−1``: a row sees every ``j ≤`` itself."""
    n = x.shape[0]
    H, nope, rope, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    rq, rank = cfg.q_lora_rank, cfg.kv_lora_rank
    scale = 1.0 / math.sqrt(nope + rope)
    q = _rms(x @ p["w_a"][:, :rq], p["q_norm"], cfg.rms_norm_eps) \
        @ p["w_qb"] * _scales(cfg)[0]
    # W_qb's columns: every head's nope part, then every head's rope part
    q_nope = q[:, :H * nope].reshape(n, H, nope)
    q_rope = _rope(q[:, H * nope:].reshape(n, H, rope), rows, cfg.rope_theta)
    w_b = p["w_b"].reshape(rank, H, nope + dv)
    seen = rows[:, None] >= jnp.arange(c.shape[0])[None, :]

    def head(args):
        qn, qr, w = args
        kv = c @ w                                           # [S, nope+dv]
        s = (qn @ kv[:, :nope].T + qr @ k_rope.T) * scale
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return a @ kv[:, nope:]

    o = jax.lax.map(head, (jnp.swapaxes(q_nope, 0, 1),
                           jnp.swapaxes(q_rope, 0, 1),
                           jnp.swapaxes(w_b, 0, 1)))              # [H,n,dv]
    return jnp.swapaxes(o, 0, 1).reshape(n, H * dv) @ p["w_o"]


def experts(cfg, m, y):
    """The expert branch of the rows ``y``: the held experts' part of the
    routed result (every held expert on every token, masked by the
    routing) plus the identity experts' part; and how many slots fell on
    held and on identity experts."""
    E = cfg.router_experts
    s = jax.nn.softmax(y @ m["w_router"], axis=-1)
    biased = s + m["router_bias"]
    kth = jnp.sort(biased, axis=-1)[:, -cfg.moe_topk][:, None]
    selected = biased >= kth
    weight = jnp.where(selected, s, 0.0) * cfg.routed_scaling_factor
    out = weight[:, E:].sum(-1, keepdims=True) * y
    for local in range(cfg.n_routed_experts):
        e = cfg.first_expert + local
        out = out + weight[:, e:e + 1] * swiglu(
            {"w_gu": m["e_gu"][local], "w_down": m["e_down"][local]}, y)
    held = selected[:, cfg.first_expert:cfg.first_expert
                    + cfg.n_routed_experts]
    return out, held.sum(), selected[:, E:].sum()


@functools.partial(jax.jit, static_argnums=0)
def latents(cfg, sub, h, positions):
    """A sublayer's keys from the stream rows ``h`` entering it."""
    with jax.default_matmul_precision("highest"):
        x = _rms(h, sub["norm_in"].astype(F32), cfg.rms_norm_eps)
        return keys(cfg, _f32(sub["attn"]), x, positions)


@functools.partial(jax.jit, static_argnums=0)
def sublayer_rows(cfg, sub, moe, h, rows, c, k_rope):
    """One sublayer on the stream rows ``h`` [n,D] at positions ``rows``
    against its keys of positions ``0 .. S−1``: ``a = h + MLA(norm_in(h))``,
    ``y = norm_post(a)``, answers ``(a + SwiGLU(y), MoE(y), held, zero)``
    — the branch and its counts zero where ``moe`` is None (the second
    sublayer has none)."""
    with jax.default_matmul_precision("highest"):
        sub = _f32(sub)
        x = _rms(h, sub["norm_in"], cfg.rms_norm_eps)
        a = h + attention(cfg, sub["attn"], x, rows, c, k_rope)
        y = _rms(a, sub["norm_post"], cfg.rms_norm_eps)
        nothing = jnp.zeros((), jnp.int32)
        branch, held, zero = (jnp.zeros_like(h), nothing, nothing) \
            if moe is None else experts(cfg, _f32(moe), y)
        return a + swiglu(sub["ffn"], y), branch, held, zero


@functools.partial(jax.jit, static_argnums=0)
def head_forward(cfg, final_norm, head, h):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, final_norm.astype(F32), cfg.rms_norm_eps)
        return x @ head.astype(F32).T


def embed(params, ids):
    return params["embed"][ids].astype(F32)


def forward(cfg, params, ids, positions=None, block: int | None = None):
    """Float32 logits [T,V] (or at ``positions`` only) for the whole
    sequence ``ids`` [T], and per double layer the routed slots that fell
    on held experts and on identity experts."""
    T = ids.shape[0]
    block = T if block is None else block
    t = jnp.arange(T)
    h = embed(params, ids)
    held, zero = [], []
    for layer in params["layers"]:
        for i, sub in enumerate(layer["sub"]):
            c, k_rope = latents(cfg, sub, h, t)
            parts = [sublayer_rows(cfg, sub, layer["moe"] if i == 0 else None,
                                   h[lo:lo + block], t[lo:lo + block], c,
                                   k_rope) for lo in range(0, T, block)]
            h = jnp.concatenate([part[0] for part in parts])
            if i == 0:
                branch = jnp.concatenate([part[1] for part in parts])
                held.append(sum(part[2] for part in parts))
                zero.append(sum(part[3] for part in parts))
        h = h + branch
    if positions is not None:
        h = h[jnp.asarray(positions)]
    return head_forward(cfg, params["final_norm"], params["head"], h), \
        held, zero
