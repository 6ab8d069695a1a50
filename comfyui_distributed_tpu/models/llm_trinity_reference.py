"""The plain reference of ``models/llm_trinity.py``: the whole forward pass
of the cut stack in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — every query head against its
group's keys under the layer kind's whole ``T×T`` mask, every expert it is
given applied to every token by a loop and masked; no cache, no ring, no
chunks, no blocks of keys, no groups of rows, no kernels. It shares nothing
with the served code but the layout of the weight tree, and it is given the
same share of the experts and of the vocabulary (what the absent experts
would add is left out here as there).

The equations (``D`` hidden, per token ``t`` unless said; ε =
``rms_norm_eps``; no bias anywhere; ``N`` = RMSNorm with a weight of its
own each time):

* ``x_0 = E[id] · √D`` (``mup_enabled``); per layer ``x ← x + N(Attn(N(x)))``,
  ``x ← x + N(FFN(N(x)))``; ``logits = N(x_L) W_headᵀ``.
* attention on ``a = N(x)``: ``[q | k | v | g] = a W_in`` — ``H`` query
  heads, ``G`` key and value heads of ``d``, a gate ``H·d`` wide; ``q ←
  N_d(q)``, ``k ← N_d(k)`` per head (one weight [d] each); on a
  ``sliding_attention`` layer q and k turn by rope over all ``d``
  dimensions, half rotation: ``[x₁ | x₂] → [x₁ cos − x₂ sin | x₂ cos + x₁
  sin]`` with the angle ``t · θ^(−2i/d)``, ``i < d/2`` (made in float64 on
  the host); a ``full_attention`` layer has NO positional encoding. Head
  ``h`` reads key/value head ``h // (H/G)``. ``s_h(t,j) = q_h,t · k_j /
  √d`` for ``j ≤ t`` and, on a sliding layer, ``t − j < sliding_window``
  (that many keys, the query's own included); ``o = (concat_h Σ_j
  softmax_j(s_h)(t,j) v_j ⊙ σ(g)) W_o``.
* dense FFN (layers below ``num_dense_layers``): ``(silu(x W_g) ⊙ x W_u)
  W_down``.
* expert layer: ``σ = sigmoid(x W_r)`` over ALL the router's experts; the
  ``k`` largest of ``σ + b``; weights ``σ_e / Σ_selected σ · route_scale``
  (the public code adds 1e-20 to that sum: not here, nor in the served
  router); ``y = Shared(x) + Σ_{e ∈ selected ∩ held} w_e Expert_e(x)``,
  experts and the shared expert SwiGLU.

What the published ``config.json`` does not settle is set as the
configuration's file lists under ``assumed``
(cdtbench/configs/trinity-large-preview.json); the served model departs
from this file nowhere.

``forward(cfg, params, ids)`` answers the float32 logits at every position
(or at ``positions``). It runs layer by layer (jitted calls) so that at the
published widths only one layer's float32 copy of the weights exists at a
time. With ``block`` the SAME functions are evaluated for ``block`` query
rows at a time (a row of attention sees the keys its mask gives it either
way, the FFNs are per row): for a prompt whose ``T×T`` does not fit. A
layer is :func:`layer_rows` — some of its rows — given the keys and values
:func:`keys_values` makes of ALL its input rows: a tool that needs a few
rows of a long sequence calls the two itself.
``cdtbench/reference/llm_trinity_reference.py`` is a copy of this file
(``tests/test_llm_trinity.py`` holds the two equal).
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


def rope_angles(cfg, T: int):
    """``(cos, sin)`` [T, d/2] of ``t · θ^(−2i/d)``: float64 on the host,
    then float32."""
    half = cfg.head_dim // 2
    freqs = cfg.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = np.arange(T, dtype=np.float64)[:, None] * freqs
    return jnp.asarray(np.cos(angle), F32), jnp.asarray(np.sin(angle), F32)


def _rope(x, cos, sin):
    """``x`` [T,heads,d], ``cos``/``sin`` [T,d/2]: half rotation."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(ffn, x):
    g, u = jnp.split(x @ ffn["w_gu"], 2, axis=-1)
    return (jax.nn.silu(g) * u) @ ffn["w_down"]


def _split(cfg, y):
    """``a W_in`` [n,·] → q [n,H,d], k [n,G,d], v [n,G,d], gate [n,H·d]."""
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    n = y.shape[0]
    return (y[:, :H * d].reshape(n, H, d),
            y[:, H * d:(H + G) * d].reshape(n, G, d),
            y[:, (H + G) * d:(H + 2 * G) * d].reshape(n, G, d),
            y[:, (H + 2 * G) * d:])


@functools.partial(jax.jit, static_argnums=(0, 1))
def keys_values(cfg, sliding: bool, layer, x, cos, sin):
    """The key (normed, roped on a sliding layer) and the value, [n,G,d]
    each, of a layer's input rows ``x`` [n,D]; ``cos``, ``sin`` [n,d/2] the
    rows' angles."""
    with jax.default_matmul_precision("highest"):
        p = _f32(layer["attn"])
        a = _rms(x, layer["norm_in"].astype(F32), cfg.rms_norm_eps)
        _, k, v, _ = _split(cfg, a @ p["w_in"])
        k = _rms(k, p["k_norm"], cfg.rms_norm_eps)
        return (_rope(k, cos, sin) if sliding else k), v


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def layer_rows(cfg, sliding: bool, moe: bool, layer, h, rows, k, v, cos,
               sin):
    """One layer's output for its input rows ``h`` [n,D] at positions
    ``rows`` [n] (``cos``, ``sin`` [n,d/2] their angles), given the keys
    and values [T,G,d] of ALL positions ``0 .. T−1``; and the routed slots
    of those rows that fell on held experts (0 for a dense layer)."""
    with jax.default_matmul_precision("highest"):
        eps = cfg.rms_norm_eps
        H, G, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
        p = _f32(layer["attn"])
        n = h.shape[0]
        a = _rms(h, layer["norm_in"].astype(F32), eps)
        q, _, _, gate = _split(cfg, a @ p["w_in"])
        q = _rms(q, p["q_norm"], eps)
        if sliding:
            q = _rope(q, cos, sin)
        t = jnp.arange(k.shape[0])
        seen = t[None, :] <= rows[:, None]
        if sliding:
            seen &= rows[:, None] - t[None, :] < cfg.sliding_window

        def head(args):
            q_h, i = args                         # [n,d], the head's index
            g = i // (H // G)
            s = q_h @ k[:, g].T / jnp.sqrt(F32(d))
            w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return w @ v[:, g]

        o = jax.lax.map(head, (jnp.swapaxes(q, 0, 1), jnp.arange(H)))
        o = jnp.swapaxes(o, 0, 1).reshape(n, H * d) * jax.nn.sigmoid(gate)
        h = h + _rms(o @ p["w_o"], layer["norm_attn_out"].astype(F32), eps)
        m = _rms(h, layer["norm_mlp_in"].astype(F32), eps)
        if moe:
            f, held = experts(cfg, _f32(layer["moe"]), m)
        else:
            f, held = swiglu(_f32(layer["ffn"]), m), jnp.zeros((), jnp.int32)
        return h + _rms(f, layer["norm_mlp_out"].astype(F32), eps), held


def experts(cfg, m, x):
    """The held experts' part of the routed result, plus the shared
    expert: every held expert on every token, masked by the routing."""
    s = jax.nn.sigmoid(x @ m["w_router"])
    biased = s + m["router_bias"]
    kth = jnp.sort(biased, axis=-1)[:, -cfg.num_experts_per_tok][:, None]
    selected = biased >= kth
    weight = jnp.where(selected, s, 0.0)
    weight = weight / weight.sum(-1, keepdims=True) * cfg.route_scale
    out = swiglu(m["shared"], x)
    for local in range(cfg.num_experts):
        e = cfg.first_expert + local
        out = out + weight[:, e:e + 1] * swiglu(
            {"w_gu": m["e_gu"][local], "w_down": m["e_down"][local]}, x)
    held = selected[:, cfg.first_expert:cfg.first_expert + cfg.num_experts]
    return out, held.sum()


@functools.partial(jax.jit, static_argnums=0)
def head_forward(cfg, final_norm, head, h):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, final_norm.astype(F32), cfg.rms_norm_eps)
        return x @ head.astype(F32).T


def embed(cfg, params, ids):
    scale = np.sqrt(cfg.hidden_size) if cfg.mup_enabled else 1.0
    return params["embed"][ids].astype(F32) * F32(scale)


def layer_kind(cfg, i: int) -> tuple:
    """``(sliding, moe)`` of kept layer ``i``."""
    return (cfg.layer_types[i] == "sliding_attention",
            i >= cfg.num_dense_layers)


def forward(cfg, params, ids, positions=None, block: int | None = None):
    """Float32 logits [T,V] (or at ``positions`` only) for the whole
    sequence ``ids`` [T], and per layer the routed slots that fell on
    held experts (0 for a dense layer)."""
    T = ids.shape[0]
    block = T if block is None else block
    cos, sin = rope_angles(cfg, T)
    x = embed(cfg, params, ids)
    held = []
    for i, layer in enumerate(params["layers"]):
        sliding, moe = layer_kind(cfg, i)
        k, v = keys_values(cfg, sliding, layer, x, cos, sin)
        parts = [layer_rows(cfg, sliding, moe, layer, x[lo:lo + block],
                            jnp.arange(lo, min(lo + block, T)), k, v,
                            cos[lo:lo + block], sin[lo:lo + block])
                 for lo in range(0, T, block)]
        x = jnp.concatenate([part for part, _ in parts])
        held.append(sum(n for _, n in parts))
    if positions is not None:
        x = x[jnp.asarray(positions)]
    return head_forward(cfg, params["final_norm"], params["head"], x), held
