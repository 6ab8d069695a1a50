"""A token-routed expert layer that is told which experts it holds.

Expert parallelism divides a layer's experts over chips: every chip routes
every token over ALL the experts (the router keeps its published width and
its experts per token) and computes the part of the result that ITS
experts give, ``Σ_{e ∈ held ∩ selected} w_e · SwiGLU_e(x)``. The exchange
that would add the other chips' parts is not here, and nothing stands in
for it: on one chip the partial result is what goes on
(``tests/test_llm_hybrid.py`` ties the shares to the uncut layer).

Routing (sigmoid scores, group-limited top-k): the selection runs on
``score + bias``, the combine weights on the bare scores of the selected
experts, normalised and scaled. Scores are float32.

Two forms of the experts' part: :func:`held_part_dense` applies every held
expert to every token and masks (prefill: a few hundred tokens keep the
matrix units busy), :func:`held_part_token` reads only the held experts
one token selected (decode: the bytes are the cost).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Routing:
    experts: int          # the router's width: all experts of the layer
    per_token: int
    groups: int
    groups_kept: int
    scaling: float
    group_top: int = 2    # a group's score is the sum of its best two


def route(x, w_router, bias, r: Routing):
    """``x`` [T,D] → ``(idx [T,k] int32, weights [T,k] f32)`` over all
    ``r.experts``. ``bias`` moves the selection only."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               w_router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    sel = s + bias.astype(jnp.float32)
    T = x.shape[0]
    per_group = r.experts // r.groups
    grouped = sel.reshape(T, r.groups, per_group)
    group_score = jax.lax.top_k(grouped, r.group_top)[0].sum(-1)
    kept = jax.lax.top_k(group_score, r.groups_kept)[1]           # [T,gk]
    group_ok = jnp.zeros((T, r.groups), bool).at[
        jnp.arange(T)[:, None], kept].set(True)
    masked = jnp.where(jnp.repeat(group_ok, per_group, axis=1), sel,
                       -jnp.inf)
    idx = jax.lax.top_k(masked, r.per_token)[1]
    w = jnp.take_along_axis(s, idx, axis=1)
    w = w / w.sum(-1, keepdims=True) * r.scaling
    return idx.astype(jnp.int32), w


def swiglu(x, w_gu, w_down, dtype):
    """``(silu(x W_g) ⊙ x W_u) W_down`` with ``W_gu = [W_g | W_u]``."""
    gu = jnp.dot(x.astype(dtype), w_gu.astype(dtype),
                 preferred_element_type=jnp.float32)
    g, u = jnp.split(gu, 2, axis=-1)
    return jnp.dot((jax.nn.silu(g) * u).astype(dtype), w_down.astype(dtype),
                   preferred_element_type=jnp.float32)


def held_slots(idx, first: int, held: int):
    """Which routed slots fell on the experts ``[first, first+held)``."""
    return (idx >= first) & (idx < first + held)


def held_part_dense(x, idx, w, e_gu, e_down, first: int, dtype):
    """``x`` [T,D]; ``e_gu`` [E_held,D,2F]; ``e_down`` [E_held,F,D].
    Every held expert on every token, combined with the routing weight
    (zero where the token did not select it). Answers [T,D] f32."""
    held = e_gu.shape[0]
    local = idx - first                                          # [T,k]
    combine = jnp.where(
        held_slots(idx, first, held)[..., None]
        & (local[..., None] == jnp.arange(held)), w[..., None], 0.0
    ).sum(1)                                                     # [T,E_held]
    gu = jnp.einsum("td,edf->etf", x.astype(dtype), e_gu.astype(dtype),
                    preferred_element_type=jnp.float32)
    g, u = jnp.split(gu, 2, axis=-1)
    y = jnp.einsum("etf,efd->etd", (jax.nn.silu(g) * u).astype(dtype),
                   e_down.astype(dtype), preferred_element_type=jnp.float32)
    return jnp.einsum("etd,te->td", y, combine)


def held_part_token(x, idx, w, e_gu, e_down, first: int, dtype):
    """One token: ``x`` [D], ``idx``/``w`` [k]. A loop over the held
    experts this token selected, and over nothing else: an absent slot
    costs no read of any weight. Answers [D] f32."""
    held = held_slots(idx, first, e_gu.shape[0])
    order = jnp.argsort(~held, stable=True)        # held slots first

    def body(j, acc):
        slot = order[j]
        e = idx[slot] - first
        y = swiglu(x[None], jax.lax.dynamic_index_in_dim(e_gu, e, 0, False),
                   jax.lax.dynamic_index_in_dim(e_down, e, 0, False), dtype)
        return acc + w[slot] * y[0]

    return jax.lax.fori_loop(0, held.sum(), body,
                             jnp.zeros(x.shape, jnp.float32))
