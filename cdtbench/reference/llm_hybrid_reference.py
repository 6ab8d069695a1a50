"""The plain reference of ``models/llm_hybrid.py``: the whole forward pass
of the cut stack in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — the KDA recurrence token by
token, MLA by decompressing every key and value, every held expert applied
to every token and masked, no cache, no kernels, no chunking, no
absorption. It shares nothing with the served code but the layout of the
weight tree, and it is given the same share of the experts and of the
vocabulary (what the absent experts would add is left out here as there).

It follows the published ``config.json`` of Ling-3.0-flash-VL's language
model; what the config does not settle is set as the configuration's file
lists under ``assumed`` (cdtbench/configs/ling-3.0-flash-vl.json), and the
served model departs from this file nowhere.

``forward(cfg, params, ids)`` answers the float32 logits at every
position. It runs layer by layer (one jitted call each) so that at the
published widths only one layer's float32 copy of the weights exists at a
time. ``cdtbench/reference/llm_hybrid_reference.py`` is a copy of this
file (``tests/test_llm_hybrid.py`` holds the two equal).
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


def _swiglu(x, w_gu, w_down):
    g, u = jnp.split(x @ w_gu, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w_down


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


def kda(cfg, p, x):
    """KDA over the sequence ``x`` [T,D], one token after another."""
    T = x.shape[0]
    H, dk, kw = cfg.num_attention_heads, cfg.head_dim, \
        cfg.num_attention_heads * cfg.head_dim
    K = cfg.short_conv_kernel_size
    y = x @ p["w_in"]
    streams = [y[:, j * kw:(j + 1) * kw] for j in range(3)]
    g_raw = y[:, 3 * kw:4 * kw]
    beta = jax.nn.sigmoid(y[:, 4 * kw:4 * kw + H])
    gate = jax.nn.sigmoid(y[:, 4 * kw + H:])
    q, k, v = [], [], []
    for j, (stream, into) in enumerate(zip(streams, (q, k, v))):
        padded = jnp.concatenate([jnp.zeros((K - 1, kw), F32), stream])
        conv = sum(padded[tap:tap + T] * p["conv"][j, tap]
                   for tap in range(K))
        into.append(jax.nn.silu(conv).reshape(T, H, dk))
    q, k, v = q[0], k[0], v[0]
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-12)
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-12)
    g = cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(p["a_log"])[None, :, None]
        * (g_raw + p["g_bias"]).reshape(T, H, dk))

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, :, None] * S
        S = S - b_t[:, None, None] * k_t[:, :, None] \
            * jnp.einsum("hk,hkv->hv", k_t, S)[:, None, :] \
            + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t) / math.sqrt(dk)

    _, o = jax.lax.scan(token, jnp.zeros((H, dk, dk), F32),
                        (q, k, v, g, beta))
    o = _rms(o, p["o_norm"], cfg.rms_norm_eps) * gate[:, :, None]
    return o.reshape(T, kw) @ p["w_o"]


def mla(cfg, p, x):
    """MLA over ``x`` [T,D]: decompress, then causal softmax attention."""
    T = x.shape[0]
    H, nope, r, rank, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                            cfg.qk_rope_head_dim, cfg.kv_lora_rank,
                            cfg.v_head_dim)
    y = x @ p["w_in"]
    q = _rms(y[:, :H * (nope + r)].reshape(T, H, nope + r), p["q_norm"],
             cfg.rms_norm_eps)
    at = H * (nope + r)
    c = _rms(y[:, at:at + rank], p["c_norm"], cfg.rms_norm_eps)
    k_rope = _rms(y[:, at + rank:at + rank + r], p["kr_norm"],
                  cfg.rms_norm_eps)
    gate = jax.nn.sigmoid(y[:, at + rank + r:])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:],
                                              cfg.rope_theta)], -1)
    k_rope = _rope(k_rope, cfg.rope_theta)
    kv = (c @ p["w_b"]).reshape(T, H, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope[:, None], (T, H, r))], -1)
    s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(nope + r)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("hts,shv->thv", jax.nn.softmax(s, -1), kv[..., nope:])
    return (o * gate[:, :, None]).reshape(T, H * dv) @ p["w_o"]


def experts(cfg, m, x):
    """The held experts' part of the routed result, plus the shared
    expert: every held expert on every token, masked by the routing."""
    T, E = x.shape[0], cfg.router_experts
    s = jax.nn.sigmoid(x @ m["w_router"])
    choose = s + m["router_bias"]
    groups = choose.reshape(T, cfg.n_group, E // cfg.n_group)
    group_score = jnp.sort(groups, axis=-1)[..., -2:].sum(-1)
    nth = jnp.sort(group_score, axis=-1)[:, -cfg.topk_group][:, None]
    allowed = jnp.repeat(group_score >= nth, E // cfg.n_group, axis=1)
    choose = jnp.where(allowed, choose, -jnp.inf)
    kth = jnp.sort(choose, axis=-1)[:, -cfg.num_experts_per_tok][:, None]
    selected = choose >= kth
    weight = jnp.where(selected, s, 0.0)
    weight = weight / weight.sum(-1, keepdims=True) \
        * cfg.routed_scaling_factor
    out = _swiglu(x, m["shared"]["w_gu"], m["shared"]["w_down"])
    for local in range(cfg.num_experts):
        e = cfg.first_expert + local
        out = out + weight[:, e:e + 1] * _swiglu(x, m["e_gu"][local],
                                                 m["e_down"][local])
    held = selected[:, cfg.first_expert:cfg.first_expert + cfg.num_experts]
    return out, held.sum()


@functools.partial(jax.jit, static_argnums=(0, 1))
def layer_forward(cfg, i: int, layer, h):
    """Layer ``i`` on the residual stream ``h`` [T,D] (float32)."""
    with jax.default_matmul_precision("highest"):
        layer = _f32(layer)
        x = _rms(h, layer["norm1"], cfg.rms_norm_eps)
        if (i + 1) % cfg.layer_group_size == 0:
            h = h + mla(cfg, layer["mla"], x)
        else:
            h = h + kda(cfg, layer["kda"], x)
        x = _rms(h, layer["norm2"], cfg.rms_norm_eps)
        if i >= cfg.first_k_dense_replace:
            y, held = experts(cfg, layer["moe"], x)
            return h + y, held
        return h + _swiglu(x, layer["ffn"]["w_gu"],
                           layer["ffn"]["w_down"]), jnp.zeros((), jnp.int32)


@functools.partial(jax.jit, static_argnums=0)
def head_forward(cfg, final_norm, head, h):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, final_norm.astype(F32), cfg.rms_norm_eps)
        return x @ head.astype(F32).T


def forward(cfg, params, ids, positions=None):
    """Float32 logits [T,V] (or at ``positions`` only) for the whole
    sequence ``ids`` [T], and per layer the routed slots that fell on
    held experts (0 for a dense layer)."""
    h = params["embed"][ids].astype(F32)
    held = []
    for i, layer in enumerate(params["layers"]):
        h, n = layer_forward(cfg, i, layer, h)
        held.append(n)
    if positions is not None:
        h = h[jnp.asarray(positions)]
    return head_forward(cfg, params["final_norm"], params["head"], h), held
