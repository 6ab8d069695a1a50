"""Decayed linear attention (Lightning Attention's recurrence), in the two
forms a served language model needs: one token at a time for decode, and a
chunk at a time for a prefill walked through the cache. Both compute, per
head ``h`` with a FIXED decay ``λ_h = exp(−s_h)``,

    S_t = λ_h S_{t−1} + k_t v_tᵀ            S ∈ R^{d×d}, float32
    o_t = S_tᵀ q_t · scale

(the tree's other linear recurrences — ``ops/delta_rule.py``: a learned gate a
channel, no feature map, ``d × d``, no normaliser, a triangular solve;
``ops/power_retention.py``: a gate from the token, ``φ`` of degree 2, ``d(d+1)/2
× d``, a normalising sum; here: a fixed decay, none, ``d × d``, none, no solve).
The chunk form cuts the chunk into blocks of ``block``
rows and writes, for row ``i`` of a block that starts from ``S_0``,

    o_i = scale · ( Σ_{j≤i} λ^{i−j} (q_i·k_j) v_j  +  λ^{i+1} q_iᵀ S_0 )
    S_B = λ^{B} S_0 + Σ_j λ^{B−1−j} k_j v_jᵀ

so that everything is a matrix product: ``(QKᵀ ⊙ D)V`` inside a block,
``(Λ ⊙ Q) S_0`` from the blocks before, and ONE ``KᵀV`` a block for the
state. Every block's three products are batched over (block, head); only
the ``[H,d,d]`` states are walked in sequence, an elementwise scan. Every
decay ratio is ``exp`` of a DIFFERENCE taken first (``exp(−s·(i−j))``):
``λ^{−j}`` alone overflows float32 for the fast heads (``s`` ≈ 0.84 a
token), and their ratios underflow to an exact 0, which is right.

A padded chunk (``n_valid`` of its rows are the prompt's) leaves the state
as token ``n_valid − 1`` left it: a block's state update weighs row ``j`` by
``λ^{n−1−j}`` with ``n`` the block's valid rows and 0 past them, and decays
``S_0`` by ``λ^n`` (``llm_model.chunked_prefill``'s contract for a recurrent
leaf). Conventions are ``models/llm_hybrid.py``'s: products on ``dtype``
operands accumulated in float32 — but the state is never rounded: it is
carried in float32 and read (``q S``) at the highest precision.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256     # rows a block of the chunk form: [H,B,B] decay mask, 2 MiB
_HIGHEST = jax.lax.Precision.HIGHEST


def slopes(num_heads: int) -> np.ndarray:
    """``s_h = 2^(−8(h+1)/H)``: Lightning-Attention-2's decay rates, ``λ_h =
    exp(−s_h)`` — from 0.43 a token (head 0 of 32) to 0.996 (head 31)."""
    h = np.arange(1, num_heads + 1, dtype=np.float64)
    return (2.0 ** (-8.0 * h / num_heads)).astype(np.float32)


def lightning_step(S, q, k, v, s, scale: float):
    """One token. ``S`` [H,d,d] float32; ``q``, ``k``, ``v`` [H,d]; ``s``
    [H] the decay rates. Answers ``(S_t, o_t [H,d])``, float32."""
    S = S * jnp.exp(-s)[:, None, None] + jnp.einsum(
        "hk,hv->hkv", k.astype(jnp.float32), v.astype(jnp.float32))
    o = jnp.einsum("hk,hkv->hv", q.astype(jnp.float32), S,
                   precision=_HIGHEST)
    return S, o * scale


def lightning_chunk(S0, q, k, v, s, scale: float, n_valid, dtype,
                    block: int = BLOCK):
    """A chunk of ``C`` rows from the state ``S0`` [H,d,d] float32. ``q``,
    ``k``, ``v`` [C,H,d]; ``s`` [H]; the first ``n_valid`` rows are real
    (traced). Answers ``(o [C,H,d] float32, S)``: ``o``'s rows past
    ``n_valid`` hold nothing anyone reads, ``S`` is the state after row
    ``n_valid − 1``."""
    C, H, d = q.shape
    B = math.gcd(C, block)
    n = C // B
    s = jnp.asarray(s, jnp.float32)
    qb, kb, vb = (x.reshape(n, B, H, d) for x in (q, k, v))
    i = jnp.arange(B)
    # inside a block: (Q Kᵀ ⊙ D) V with D[h,i,j] = λ_h^(i−j) for j ≤ i
    lag = (i[:, None] - i[None, :]).astype(jnp.float32)
    D = jnp.exp(jnp.where(lag >= 0, -s[:, None, None] * lag, -jnp.inf))
    a = jnp.einsum("nihd,njhd->nhij", qb.astype(dtype), kb.astype(dtype),
                   preferred_element_type=jnp.float32)
    o = jnp.einsum("nhij,njhd->nihd", (a * D).astype(dtype),
                   vb.astype(dtype), preferred_element_type=jnp.float32)
    # a block's own addition to the state, its rows decayed to the block's
    # last VALID row (none past it)
    rows = jnp.clip(n_valid - jnp.arange(n) * B, 0, B)              # [n]
    left = (rows[:, None] - 1 - i[None, :]).astype(jnp.float32)[..., None]
    w = jnp.exp(jnp.where(left >= 0, -left * s, -jnp.inf))         # [n,B,H]
    kw = kb.astype(jnp.float32) * w[..., None]
    U = jnp.einsum("njhk,njhv->nhkv", kw.astype(dtype), vb.astype(dtype),
                   preferred_element_type=jnp.float32)
    keep = jnp.exp(-s[None, :] * rows[:, None].astype(jnp.float32))  # [n,H]

    def walk(S, xs):
        U_n, keep_n = xs
        return S * keep_n[:, None, None] + U_n, S

    S, starts = jax.lax.scan(walk, S0, (U, keep))
    # from the blocks before: (Λ ⊙ Q) S_0, Λ[h,i] = λ_h^(i+1); the state
    # read whole, at the highest precision
    reach = jnp.exp(-s[None, :] * (i[:, None] + 1.0))                # [B,H]
    o = o + jnp.einsum("nihk,nhkv->nihv",
                       qb.astype(jnp.float32) * reach[None, :, :, None],
                       starts, precision=_HIGHEST)
    return o.reshape(C, H, d) * scale, S
