"""The plain reference of ``models/llm_jamba.py``: the whole forward pass in
straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — the recurrence as a
``lax.scan`` over the tokens one at a time, attention as one causal softmax
over all the keys, the convolution as four shifted sums; no cache, no
chunks, no kernels, no stacked walk. It shares nothing with the served code
but the layout of the weight tree.

The equations (``D`` hidden, ``d = mamba_expand · D``, ``N`` states, ``K``
the convolution's taps, ``R`` the rank of ``Δ``; ε = ``rms_norm_eps``):

* layer ``i`` is attention when ``i % attn_layer_period ==
  attn_layer_offset``, a Mamba mixer otherwise; ``h = x +
  Mix(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, ``FFN(x) = (silu(x W_g)
  ⊙ x W_u) W_down`` on every layer (``num_experts`` 1: no router);
  ``logits = RMSNorm(y_L) Eᵀ`` with the embedding ``E`` (a tied head);
  ``x_0 = E[id]``. No positional encoding.
* Mamba mixer: ``[u | z] = x W_in``; ``u_t ← silu(Σ_{j<K} w_j ⊙ u_{t−K+1+j}
  + b_conv)`` (inputs before the sequence are zero); ``[δ | B | C] = u
  W_x``; ``δ ← RMSNorm(δ)``, ``B ← RMSNorm(B)``, ``C ← RMSNorm(C)``, each
  with its weight; ``Δ = softplus(δ W_dt + b_dt)``; ``A = −exp(A_log)``;
  ``h_t = exp(Δ_t ⊙ A) ⊙ h_{t−1} + (Δ_t ⊙ u_t) ⊗ B_t`` from ``h_{−1} =
  0``; ``y_t = h_t C_t + D ⊙ u_t``; out ``= (y ⊙ silu(z)) W_out``. No bias
  but the convolution's and ``Δ``'s.
* attention: ``q = x W_q`` → ``H`` heads of ``D/H``; ``k = x W_k``, ``v = x
  W_v`` → ONE head each, shared by all ``H``; ``s_h(t,j) = q_h,t · k_j ·
  (D/H)^(−½)`` for ``j ≤ t``; ``o = concat_h(Σ_j softmax_j(s_h)(t,j) v_j)
  W_o``. No bias, no window.

Departures from the public ``jamba`` modelling code: none in the
mathematics; ``W_q``, ``W_k``, ``W_v`` are read as column blocks of one
stored ``w_qkv`` and the FFN's gate and up as halves of one ``w_gu`` (the
same function of random weights), and what ``config.json`` does not settle
is set as the configuration's file lists under ``assumed``
(cdtbench/configs/ai21-jamba2-3b.json).

``forward(cfg, params, ids)`` answers the float32 logits at every position
(or at ``positions``). It runs layer by layer (one jitted call each) so
that only one layer's float32 copy of the weights exists at a time. With
``block`` the SAME functions are evaluated for ``block`` rows at a time —
a Mamba layer hands the next block what the recurrence itself carries
(its state and its last ``K − 1`` inputs, exactly what the token loop
holds between two tokens), a row of attention sees all the keys below it
either way — for a prompt whose activations do not fit whole.
``cdtbench/reference/llm_jamba_reference.py`` is a copy of this file
(``tests/test_llm_jamba.py`` holds the two equal).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def layer_weights(cfg, params, i: int):
    """Layer ``i``'s own leaves out of the tree's stacks."""
    seen_attention = sum(1 for j in range(i) if cfg.is_attention(j))
    if cfg.is_attention(i):
        return params["attn"][seen_attention]
    first = 0 if not seen_attention else \
        [j for j in range(i) if cfg.is_attention(j)][-1] + 1
    return jax.tree_util.tree_map(lambda a: a[i - first],
                                  params["mamba"][seen_attention])


@functools.partial(jax.jit, static_argnums=0)
def mamba_rows(cfg, norm, p, before, state, x):
    """``x + Mamba(RMSNorm(x))`` for the rows ``x`` [n, D] that follow
    ``before`` (the mixer's last ``K − 1`` inputs to the convolution) and
    ``state`` [d, N]; also what the rows after these follow."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        n, d = x.shape[0], cfg.d_inner
        N, R, K = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
        eps = cfg.rms_norm_eps
        uz = _rms(x, norm.astype(F32), eps) @ p["w_in"]
        inputs = jnp.concatenate([before, uz[:, :d]], 0)
        u = jax.nn.silu(sum(inputs[j:j + n] * p["conv_w"][j]
                            for j in range(K)) + p["conv_b"])
        low = u @ p["w_x"]
        delta = _rms(low[:, :R], p["dt_norm"], eps)
        B = _rms(low[:, R:R + N], p["b_norm"], eps)
        C = _rms(low[:, R + N:], p["c_norm"], eps)
        dt = jax.nn.softplus(delta @ p["w_dt"] + p["b_dt"])
        A = -jnp.exp(p["a_log"])

        def token(h, row):
            u_t, dt_t, B_t, C_t = row
            h = jnp.exp(dt_t[:, None] * A) * h \
                + (dt_t * u_t)[:, None] * B_t[None, :]
            return h, (h * C_t[None, :]).sum(-1) + p["d"] * u_t

        state, y = jax.lax.scan(token, state, (u, dt, B, C))
        out = (y * jax.nn.silu(uz[:, d:])) @ p["w_out"]
        return x + out, inputs[n:], state


@functools.partial(jax.jit, static_argnums=(0, 4))
def attention_rows(cfg, norm, p, lo, n: int, x):
    """``x[lo:lo+n] + Attn(RMSNorm(x))[lo:lo+n]`` of one layer."""
    with jax.default_matmul_precision("highest"):
        H, hd = cfg.num_attention_heads, cfg.head_dim
        w = p["w_qkv"].astype(F32)
        normed = _rms(x, norm.astype(F32), cfg.rms_norm_eps)
        rows = jax.lax.dynamic_slice_in_dim(normed, lo, n, 0)
        q = (rows @ w[:, :H * hd]).reshape(n, H, hd)
        k, v = normed @ w[:, H * hd:H * hd + hd], normed @ w[:, H * hd + hd:]
        s = jnp.einsum("nhd,jd->nhj", q, k) * hd ** -0.5
        seen = jnp.arange(x.shape[0])[None, :] <= (lo + jnp.arange(n))[:, None]
        prob = jax.nn.softmax(jnp.where(seen[:, None, :], s, -jnp.inf), -1)
        o = jnp.einsum("nhj,jd->nhd", prob, v).reshape(n, H * hd)
        return jax.lax.dynamic_slice_in_dim(x, lo, n, 0) \
            + o @ p["w_o"].astype(F32)


@functools.partial(jax.jit, static_argnums=0)
def ffn_rows(cfg, norm, ffn, h):
    """``h + FFN(RMSNorm(h))`` on the rows given."""
    with jax.default_matmul_precision("highest"):
        ffn = _f32(ffn)
        x = _rms(h, norm.astype(F32), cfg.rms_norm_eps)
        gu = x @ ffn["w_gu"]
        half = gu.shape[-1] // 2
        return h + (jax.nn.silu(gu[:, :half]) * gu[:, half:]) @ ffn["w_down"]


@functools.partial(jax.jit, static_argnums=0)
def head_forward(cfg, final_norm, embed, h):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, final_norm.astype(F32), cfg.rms_norm_eps)
        return x @ embed.astype(F32).T


def forward(cfg, params, ids, positions=None, block: int | None = None):
    """Float32 logits [T,V] (or at ``positions`` only) for the whole
    sequence ``ids`` [T]."""
    T = ids.shape[0]
    block = T if block is None else block
    x = params["embed"][ids].astype(F32)
    for i in range(cfg.num_hidden_layers):
        layer = layer_weights(cfg, params, i)
        parts = []
        if cfg.is_attention(i):
            for lo in range(0, T, block):
                parts.append(attention_rows(cfg, layer["norm1"],
                                            layer["attn"], lo,
                                            min(block, T - lo), x))
        else:
            before = jnp.zeros((cfg.mamba_d_conv - 1, cfg.d_inner), F32)
            state = jnp.zeros((cfg.d_inner, cfg.mamba_d_state), F32)
            for lo in range(0, T, block):
                rows, before, state = mamba_rows(
                    cfg, layer["norm1"], layer["ssm"], before, state,
                    x[lo:lo + block])
                parts.append(rows)
        x = jnp.concatenate([ffn_rows(cfg, layer["norm2"], layer["ffn"], part)
                             for part in parts])
    if positions is not None:
        x = x[jnp.asarray(positions)]
    return head_forward(cfg, params["final_norm"], params["embed"], x)
