"""The gated delta rule with a per-channel decay (KDA's recurrence), in the
two forms a served language model needs: one token at a time for decode,
and a chunk at a time for prefill. Both compute, per head,

    S_t = (I − β_t k_t k_tᵀ) diag(exp g_t) S_{t−1} + β_t k_t v_tᵀ
    o_t = S_tᵀ q_t · scale

with ``S ∈ R^{d_k×d_v}`` and ``g_t ≤ 0`` a log-decay per key channel. They
must agree (``tests/test_llm_hybrid.py``); everything here is float32 —
the state is the one thing a recurrent layer cannot afford to round.

The chunked form writes ``S_t = diag(γ_t) S_0 + Σ_{i≤t} diag(γ_t/γ_i) k_i
w_iᵀ`` (``γ_t = exp Σ_{j≤t} g_j`` inside the chunk), which turns the
pseudo-values ``w`` into the solution of a unit lower-triangular system
and the outputs into two matrix products. The decay ratios are formed as
``exp(Γ_t − Γ_i)`` with the difference taken first: ``exp(−Γ_i)`` alone
overflows float32 at the configured lower bound of the gate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular


def kda_step(S, q, k, v, g, beta, scale: float):
    """One token. ``S`` [H,dk,dv]; ``q``,``k``,``g`` [H,dk]; ``v`` [H,dv];
    ``beta`` [H]. Answers ``(S_t, o_t [H,dv])``."""
    S = S * jnp.exp(g)[..., None]
    err = v - jnp.einsum("hk,hkv->hv", k, S)
    S = S + jnp.einsum("hk,hv->hkv", k * beta[:, None], err)
    return S, jnp.einsum("hk,hkv->hv", q, S) * scale


def kda_chunked(q, k, v, g, beta, S0, scale: float, chunk: int):
    """A whole sequence, ``chunk`` tokens at a time. ``q``,``k``,``g``
    [T,H,dk]; ``v`` [T,H,dv]; ``beta`` [T,H]; ``S0`` [H,dk,dv]; ``T`` a
    multiple of ``chunk``. Answers ``(o [T,H,dv], S_T)``."""
    T, H, _ = q.shape
    if T % chunk:
        raise ValueError(f"{T} tokens do not divide into chunks of {chunk}")

    def chunks(x):                         # [T,H,...] -> [n,H,C,...]
        return jnp.swapaxes(x.reshape(T // chunk, chunk, *x.shape[1:]), 1, 2)

    t = jnp.arange(chunk)
    incl = t[:, None] >= t[None, :]
    strict = t[:, None] > t[None, :]

    def body(S, xs):
        qc, kc, vc, gc, bc = xs            # [H,C,dk] [H,C,dk] [H,C,dv] .. [H,C]
        G = jnp.cumsum(gc, axis=1)
        ratio = jnp.exp(jnp.where(incl[None, :, :, None],
                                  G[:, :, None, :] - G[:, None, :, :],
                                  -jnp.inf))                     # [H,t,i,dk]
        A = jnp.where(strict, (kc[:, :, None] * kc[:, None] * ratio).sum(-1),
                      0.0)
        B = (qc[:, :, None] * kc[:, None] * ratio).sum(-1)      # i <= t
        gam = jnp.exp(G)
        rhs = bc[..., None] * (vc - jnp.einsum("hck,hkv->hcv", kc * gam, S))
        M = jnp.eye(chunk, dtype=S.dtype) + bc[..., None] * A
        W = solve_triangular(M, rhs, lower=True, unit_diagonal=True)
        o = (jnp.einsum("hck,hkv->hcv", qc * gam, S)
             + jnp.einsum("hti,hiv->htv", B, W))
        to_end = jnp.exp(G[:, -1:] - G)
        S = gam[:, -1][..., None] * S + jnp.einsum("hck,hcv->hkv",
                                                   kc * to_end, W)
        return S, o * scale

    S, o = jax.lax.scan(body, S0, tuple(map(chunks, (q, k, v, g, beta))))
    return jnp.swapaxes(o, 1, 2).reshape(T, H, v.shape[-1]), S
