"""The plain reference of ``models/llm_mimo.py``: the whole forward pass of
the cut stack in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — every query head against its
group's keys under the layer kind's whole ``T×T`` mask, the sink one more
term of a window layer's denominator, every expert it is given applied to
every token by a loop and masked; no cache, no ring, no chunks, no blocks of
keys, no kernels. It shares nothing with the served code but the layout of
the weight tree, and it is given the same share of the experts and of the
vocabulary (what the absent experts would add is left out here as there).

The equations (``D`` hidden; per token ``t`` unless said; ε =
``rms_norm_eps``; no bias anywhere; ``N`` = RMSNorm with a weight of its own
each time). Layer ``l`` is of the kind κ = ``layer_types[l]`` ∈ {full,
window}: ``G`` = ``num_key_value_heads`` / ``swa_num_key_value_heads`` key
and value heads, θ = ``rope_theta`` / ``swa_rope_theta``.

* ``x_0 = E[id]``; per layer ``x ← x + Attn(N(x))``, ``x ← x + FFN(N(x))``;
  ``logits = N(x_L) W_headᵀ`` over the held slice of the vocabulary.
* attention on ``a = N(x)``: ``q = a W_q``, ``[k | v] = a W_kv`` — ``H``
  query heads and ``G`` key heads of ``head_dim`` (192), ``G`` value heads of
  ``v_head_dim`` (128); ``v ← attention_value_scale · v``. The FIRST
  ``rotary_dim`` (64 = 0.334 × 192, rounded down to even) dimensions of q and
  k turn by rope, half rotation within them: ``[x₁ | x₂] → [x₁ cos − x₂ sin |
  x₂ cos + x₁ sin]`` with the angle ``t · θ^(−2i/rotary_dim)``, ``i <
  rotary_dim/2`` (made in float64 on the host); the other dimensions pass.
  Head ``h`` reads key/value head ``h // (H/G)``. ``s_h(t,j) = q_h,t · k_j /
  √head_dim`` for ``j ≤ t`` and, on a window layer, ``t − j <
  sliding_window`` (that many keys, the query's own included).
* full: ``p = softmax_j(s)``. window (``add_swa_attention_sink_bias``):
  ``p(t,j) = exp(s(t,j)) / (exp(b_h) + Σ_j' exp(s(t,j')))`` — the sink
  ``b_h``, one learned scalar a head, joins the denominator and adds nothing
  to the output. ``o = concat_h Σ_j p_h(t,j) v_j``; ``Attn = o W_o``.
* dense FFN (layers below ``num_dense_layers``): ``(silu(x W_g) ⊙ x W_u)
  W_down``.
* expert layer: ``r = sigmoid(x W_r)`` over ALL the router's experts; the
  ``k`` largest of ``r + c`` (``c``: the correction bias, selection only);
  weights ``r_e / Σ_selected r`` (``norm_topk_prob``; ``routed_scaling_factor``
  null: times 1); ``y = Σ_{e ∈ selected ∩ held} w_e Expert_e(x)``, SwiGLU; no
  shared expert.

What the published ``config.json`` does not settle is set as the
configuration's file lists under ``assumed``
(cdtbench/configs/mimo-v2-flash.json); the served model departs from this
file nowhere.

``forward(cfg, params, ids)`` answers the float32 logits at every position
(or at ``positions``). It runs layer by layer (jitted calls) so that at the
published widths only one layer's float32 copy of the weights exists at a
time. With ``block`` the SAME functions are evaluated for ``block`` query
rows at a time: for a prompt whose ``T×T`` does not fit. A layer is
:func:`layer_rows` — some of its rows — given the keys and values
:func:`keys_values` makes of ALL its input rows: a tool that needs a few rows
of a long sequence calls the two itself.
``cdtbench/reference/llm_mimo_reference.py`` is a copy of this file
(``tests/test_llm_mimo.py`` holds the two equal).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layer_kind(cfg, i: int) -> tuple:
    """``(window, moe)`` of kept layer ``i``."""
    return (cfg.layer_types[i] == "sliding_attention",
            i >= cfg.num_dense_layers)


def kv_heads(cfg, window: bool) -> int:
    return cfg.swa_num_key_value_heads if window else cfg.num_key_value_heads


def rope_angles(cfg, T: int):
    """``(cos, sin)`` [T, 2, rotary_dim/2] of ``t · θ^(−2i/rotary_dim)`` — the
    full layers' θ at index 0, the window layers' at 1: float64 on the host,
    then float32."""
    half = cfg.rotary_dim // 2
    theta = np.asarray([cfg.rope_theta, cfg.swa_rope_theta], np.float64)
    freqs = theta[:, None] ** (-np.arange(half, dtype=np.float64) / half)
    angle = np.arange(T, dtype=np.float64)[:, None, None] * freqs
    return jnp.asarray(np.cos(angle), F32), jnp.asarray(np.sin(angle), F32)


def _rope(cfg, window: bool, x, cos, sin):
    """``x`` [T,heads,head_dim], ``cos``/``sin`` [T,2,rotary_dim/2]: half
    rotation within the first ``rotary_dim`` dimensions under the layer
    kind's angles, the rest pass."""
    r = cfg.rotary_dim
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    cos, sin = cos[:, int(window), None, :], sin[:, int(window), None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def swiglu(ffn, x):
    g, u = jnp.split(x @ ffn["w_gu"], 2, axis=-1)
    return (jax.nn.silu(g) * u) @ ffn["w_down"]


def _queries(cfg, a, w_q):
    """``a`` [n,D] through ``W_q``: [n,H,dk]."""
    return (a @ w_q).reshape(a.shape[0], cfg.num_attention_heads,
                             cfg.head_dim)


def _keys_values(cfg, window: bool, a, w_kv):
    """``a`` [n,D] through ``W_kv`` = ``[k | v]``: k [n,G,dk], v [n,G,dv]
    times ``attention_value_scale``."""
    G, dk, dv = kv_heads(cfg, window), cfg.head_dim, cfg.v_head_dim
    n = a.shape[0]
    y = a @ w_kv
    return (y[:, :G * dk].reshape(n, G, dk),
            y[:, G * dk:].reshape(n, G, dv) * F32(cfg.attention_value_scale))


@functools.partial(jax.jit, static_argnums=(0, 1))
def keys_values(cfg, window: bool, layer, x, cos, sin):
    """The key (roped) [n,G,dk] and the value (scaled) [n,G,dv] of a layer's
    input rows ``x`` [n,D]; ``cos``, ``sin`` [n,2,rotary_dim/2] the rows'
    angles (:func:`rope_angles`)."""
    with jax.default_matmul_precision("highest"):
        a = _rms(x, layer["norm_in"].astype(F32), cfg.rms_norm_eps)
        k, v = _keys_values(cfg, window, a,
                            layer["attn"]["w_kv"].astype(F32))
        return _rope(cfg, window, k, cos, sin), v


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def layer_rows(cfg, window: bool, moe: bool, layer, h, rows, k, v, cos, sin):
    """One layer's output for its input rows ``h`` [n,D] at positions
    ``rows`` [n] (``cos``, ``sin`` [n,2,rotary_dim/2] their angles), given the
    keys [T,G,dk] and values [T,G,dv] of ALL positions ``0 .. T−1``; and the
    routed slots of those rows that fell on held experts (0 for a dense
    layer)."""
    with jax.default_matmul_precision("highest"):
        eps = cfg.rms_norm_eps
        H, G = cfg.num_attention_heads, kv_heads(cfg, window)
        p = _f32(layer["attn"])
        n = h.shape[0]
        a = _rms(h, layer["norm_in"].astype(F32), eps)
        q = _rope(cfg, window, _queries(cfg, a, p["w_q"]), cos, sin)
        t = jnp.arange(k.shape[0])
        seen = t[None, :] <= rows[:, None]
        if window:
            seen &= rows[:, None] - t[None, :] < cfg.sliding_window

        def head(args):
            q_h, i = args                         # [n,dk], the head's index
            g = i // (H // G)
            s = q_h @ k[:, g].T / jnp.sqrt(F32(cfg.head_dim))
            top = jnp.max(jnp.where(seen, s, -jnp.inf), -1, keepdims=True)
            e = jnp.where(seen, jnp.exp(s - top), 0.0)
            total = e.sum(-1, keepdims=True)
            if window:      # the sink: a term of the denominator alone
                total = total + jnp.exp(p["sink"][i] - top)
            return (e / total) @ v[:, g]

        o = jax.lax.map(head, (jnp.swapaxes(q, 0, 1), jnp.arange(H)))
        o = jnp.swapaxes(o, 0, 1).reshape(n, H * cfg.v_head_dim)
        h = h + o @ p["w_o"]
        m = _rms(h, layer["norm_mlp_in"].astype(F32), eps)
        if moe:
            f, held = experts(cfg, _f32(layer["moe"]), m)
        else:
            f, held = swiglu(_f32(layer["ffn"]), m), jnp.zeros((), jnp.int32)
        return h + f, held


def experts(cfg, m, x):
    """The held experts' part of the routed result: every held expert on
    every token, masked by the routing."""
    r = jax.nn.sigmoid(x @ m["w_router"])
    biased = r + m["router_bias"]
    kth = jnp.sort(biased, axis=-1)[:, -cfg.num_experts_per_tok][:, None]
    selected = biased >= kth
    weight = jnp.where(selected, r, 0.0)
    weight = weight / weight.sum(-1, keepdims=True)
    out = jnp.zeros_like(x)
    for local in range(cfg.n_routed_experts):
        e = cfg.first_expert + local
        out = out + weight[:, e:e + 1] * swiglu(
            {"w_gu": m["e_gu"][local], "w_down": m["e_down"][local]}, x)
    held = selected[:, cfg.first_expert:cfg.first_expert
                    + cfg.n_routed_experts]
    return out, held.sum()


@functools.partial(jax.jit, static_argnums=0)
def head_forward(cfg, final_norm, head, h):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, final_norm.astype(F32), cfg.rms_norm_eps)
        return x @ head.astype(F32).T


def embed(cfg, params, ids):
    return params["embed"][ids].astype(F32)


def forward(cfg, params, ids, positions=None, block: int | None = None):
    """Float32 logits [T,V] (or at ``positions`` only) for the whole
    sequence ``ids`` [T], and per layer the routed slots that fell on held
    experts (0 for a dense layer)."""
    T = ids.shape[0]
    block = T if block is None else block
    cos, sin = rope_angles(cfg, T)
    x = embed(cfg, params, ids)
    held = []
    for i, layer in enumerate(params["layers"]):
        window, moe = layer_kind(cfg, i)
        k, v = keys_values(cfg, window, layer, x, cos, sin)
        parts = [layer_rows(cfg, window, moe, layer, x[lo:lo + block],
                            jnp.arange(lo, min(lo + block, T)), k, v,
                            cos[lo:lo + block], sin[lo:lo + block])
                 for lo in range(0, T, block)]
        x = jnp.concatenate([part for part, _ in parts])
        held.append(sum(n for _, n in parts))
    if positions is not None:
        x = x[jnp.asarray(positions)]
    return head_forward(cfg, params["final_norm"], params["head"], x), held
