"""A token-routed expert layer that is told which experts it holds.

Expert parallelism divides a layer's experts over chips: every chip routes
every token over ALL the experts (the router keeps its published width and
its experts per token) and computes the part of the result that ITS
experts give, ``Σ_{e ∈ held ∩ selected} w_e · MLP_e(x)``. The exchange
that would add the other chips' parts is not here, and nothing stands in
for it: on one chip the partial result is what goes on
(``tests/test_llm_hybrid.py`` ties the shares to the uncut layer).

Routing (sigmoid scores, or a softmax over all the router's outputs;
top-k, group-limited where the model groups its experts): the selection
runs on ``score + bias``, the combine weights on the bare scores of the
selected experts, normalised where the model normalises them, and scaled.
Scores are float32. The experts are gated MLPs ``(act(x W_g, x W_u))
W_down``; the model passes its activation (``silu_gate`` here; a model may
bring its own, with per-expert parameters).

A router may have a THIRD place a slot can fall: ``zero_experts`` identity
experts, its outputs ``[experts, experts + zero_experts)``, which have no
weights and cost no product — ``w_e · x``. They are neither held nor
absent: every chip computes them for the tokens whose stream it has
(:func:`zero_part`), and no form of the held part ever sees one (an index
``≥ experts`` lies outside every share).

Three forms of the experts' part. :func:`held_part_dense` applies every
held expert to every token and masks (a prefill of a few hundred tokens: one
large product keeps the matrix units busy, and a held expert expects so few
rows that gathering them would multiply mostly padding).
:func:`held_part_grouped` gathers the rows routed to each held expert in
expert order and multiplies them in tiles of :data:`GROUP_TILE` rows, a
tile against one expert (a prefill of thousands of tokens: the dense form
would multiply ``experts / per_token`` times the routed rows).
:func:`held_part_token` reads only the held experts one token selected
(decode: the bytes are the cost). A prefill takes the form
:func:`prefill_form` names from the rows it sees — one rule, no flag —
through :func:`held_part`.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..telemetry.device_scopes import device_scope, device_scoped


@dataclasses.dataclass(frozen=True)
class Routing:
    experts: int          # the router's width: all experts of the layer
    per_token: int
    groups: int           # 1: ungrouped, the best ``per_token`` of all
    groups_kept: int
    scaling: float
    group_top: int = 2    # a group's score is the sum of its best two
    # values of the MODEL (a request has no say in them):
    score: str = "sigmoid"     # or "softmax", over all the outputs
    normalised: bool = True    # the chosen weights sum to ``scaling``
    zero_experts: int = 0      # identity experts, after the real ones

    @property
    def outputs(self) -> int:
        """The router's whole width."""
        return self.experts + self.zero_experts


@device_scoped("llm_router")
def route(x, w_router, bias, r: Routing):
    """``x`` [T,D] → ``(idx [T,k] int32, weights [T,k] f32)`` over all
    ``r.outputs`` (an index ``≥ r.experts`` is an identity expert): the
    LINEAR router, ``logits = x · w_router``, of :func:`route_logits`.
    ``bias`` (None: the router has none) moves the selection only."""
    return _select(
        jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST), bias, r)


@device_scoped("llm_router")
def route_logits(logits, bias, r: Routing):
    """:func:`route` for a model that made its router's ``logits`` [T,
    ``r.outputs``] float32 itself (an MLP, a state carried between layers):
    the scores, the selection on ``score + bias``, the combine weights and
    what the slot counters read stay in this one place."""
    return _select(logits, bias, r)


def _select(logits, bias, r: Routing):
    """Scores, selection and combine weights from a router's logits."""
    score = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}[r.score]
    s = score(logits)
    sel = s if bias is None else s + bias.astype(jnp.float32)
    if r.groups == 1:
        return _combine(s, jax.lax.top_k(sel, r.per_token)[1], r)
    T = logits.shape[0]
    per_group = r.outputs // r.groups
    grouped = sel.reshape(T, r.groups, per_group)
    group_score = jax.lax.top_k(grouped, r.group_top)[0].sum(-1)
    kept = jax.lax.top_k(group_score, r.groups_kept)[1]           # [T,gk]
    group_ok = jnp.zeros((T, r.groups), bool).at[
        jnp.arange(T)[:, None], kept].set(True)
    masked = jnp.where(jnp.repeat(group_ok, per_group, axis=1), sel,
                       -jnp.inf)
    return _combine(s, jax.lax.top_k(masked, r.per_token)[1], r)


def _combine(s, idx, r: Routing):
    w = jnp.take_along_axis(s, idx, axis=1)
    if r.normalised:
        w = w / w.sum(-1, keepdims=True)
    return idx.astype(jnp.int32), w * r.scaling


def silu_gate(g, u, _=None):
    """SwiGLU's gate: ``silu(g) ⊙ u``. An activation is ``act(g, u,
    params) -> [..., F]`` in float32; ``params`` are its own (None here)."""
    return jax.nn.silu(g) * u


def gated_mlp(x, w_gu, w_down, dtype, act, act_params=None):
    """``act(x W_g, x W_u) W_down`` with ``W_gu = [W_g | W_u]``."""
    gu = jnp.dot(x.astype(dtype), w_gu.astype(dtype),
                 preferred_element_type=jnp.float32)
    g, u = jnp.split(gu, 2, axis=-1)
    return jnp.dot(act(g, u, act_params).astype(dtype), w_down.astype(dtype),
                   preferred_element_type=jnp.float32)


def swiglu(x, w_gu, w_down, dtype):
    """``(silu(x W_g) ⊙ x W_u) W_down``."""
    return gated_mlp(x, w_gu, w_down, dtype, silu_gate)


def held_slots(idx, first: int, held: int):
    """Which routed slots fell on the experts ``[first, first+held)`` (a
    share lies below the router's ``experts``: never an identity one)."""
    return (idx >= first) & (idx < first + held)


def zero_part(x, idx, w, r: Routing, valid=None):
    """The identity experts' part, ``(Σ_{chosen e ≥ r.experts} w_e) · x``
    [T,D] f32 — no weight is read and nothing is multiplied but a row by a
    scalar — and how many slots (of rows ``valid`` says are real; None:
    all) fell on them. Computed where the token's stream is: by every
    chip alike, once."""
    zero = idx >= r.experts
    with device_scope("llm_experts"):
        mix = jnp.where(zero, w, 0.0).sum(-1, keepdims=True) \
            * x.astype(jnp.float32)
    with device_scope("llm_router"):
        if valid is not None:
            zero &= valid[:, None]
        return mix, zero.sum().astype(jnp.int32)


@device_scoped("llm_experts")
def held_part_dense(x, idx, w, e_gu, e_down, first: int, dtype,
                    act=silu_gate, act_params=None,
                    expert_chunk: int | None = None):
    """``x`` [T,D]; ``e_gu`` [E_held,D,2F]; ``e_down`` [E_held,F,D];
    ``act_params`` None or [E_held,...]. Every held expert on every
    token, combined with the routing weight (zero where the token did not
    select it). ``expert_chunk``: that many experts at a time, so that the
    float32 ``[E_held,T,2F]`` is never whole. Answers [T,D] f32."""
    held = e_gu.shape[0]
    local = idx - first                                          # [T,k]
    combine = jnp.where(
        held_slots(idx, first, held)[..., None]
        & (local[..., None] == jnp.arange(held)), w[..., None], 0.0
    ).sum(1)                                                     # [T,E_held]

    def part(e_gu, e_down, params, combine):
        gu = jnp.einsum("td,edf->etf", x.astype(dtype), e_gu.astype(dtype),
                        preferred_element_type=jnp.float32)
        g, u = jnp.split(gu, 2, axis=-1)
        if params is not None:
            params = params[:, None]                 # over the tokens
        y = jnp.einsum("etf,efd->etd", act(g, u, params).astype(dtype),
                       e_down.astype(dtype),
                       preferred_element_type=jnp.float32)
        return jnp.einsum("etd,te->td", y, combine)

    if expert_chunk is None or expert_chunk >= held:
        return part(e_gu, e_down, act_params, combine)
    chunks = jax.tree_util.tree_map(
        lambda a: a.reshape(held // expert_chunk, expert_chunk,
                            *a.shape[1:]),
        (e_gu, e_down, act_params, combine.T))

    def body(acc, chunk):
        e_gu, e_down, params, combine_t = chunk
        return acc + part(e_gu, e_down, params, combine_t.T), None

    return jax.lax.scan(body, jnp.zeros(x.shape, jnp.float32), chunks)[0]


GROUP_TILE = 128      # rows of one grouped product: the MXU's height


def prefill_form(rows: int, r: Routing, tile: int = GROUP_TILE) -> str:
    """``grouped`` where a held expert expects (``rows · per_token /
    outputs``, routing even over the router's whole width: a slot on an
    identity expert is no held expert's) at least half a tile of rows, ``dense``
    below that: there most of every tile would be padding, and each of
    them still reads its expert's weights. EXACTLY at half a tile (4096
    rows × top 4 ÷ 256 experts = 64: the fifth rewriter's chunk) even
    routing would make the grouped form multiply 2.0 rows a routed row;
    served, it multiplied 1.18 (PERF.md §6, PR 39: a brief repeats its
    tokens, so the experts that are chosen at all get thousands of rows a
    chunk and only each one's last tile is padded), where the dense form
    multiplies ``held experts × rows`` whatever is routed: 64 a routed
    row there."""
    return "grouped" if 2 * rows * r.per_token >= tile * r.outputs \
        else "dense"


def held_part(x, idx, w, e_gu, e_down, first: int, dtype, r: Routing,
              act=silu_gate, act_params=None, expert_chunk: int | None = None,
              valid=None, tile: int = GROUP_TILE):
    """A prefill's held part in the form :func:`prefill_form` names for
    its ``x.shape[0]`` rows. Answers ``(y [T,D] f32, rows multiplied)``:
    the second is what the form costs (``E_held · T`` dense, the tiles'
    rows grouped), beside the held slots it was needed for."""
    if prefill_form(x.shape[0], r, tile) == "grouped":
        return held_part_grouped(x, idx, w, e_gu, e_down, first, dtype, act,
                                 act_params, valid, tile)
    if valid is not None:
        with device_scope("llm_experts"):
            w = jnp.where(valid[:, None], w, 0.0)
    y = held_part_dense(x, idx, w, e_gu, e_down, first, dtype, act,
                        act_params, expert_chunk)
    return y, jnp.asarray(e_gu.shape[0] * x.shape[0], jnp.int32)


@device_scoped("llm_experts")
def held_part_grouped(x, idx, w, e_gu, e_down, first: int, dtype,
                      act=silu_gate, act_params=None, valid=None,
                      tile: int = GROUP_TILE):
    """What :func:`held_part_dense` computes, by group: the routed slots
    that fell on held experts (of rows ``valid`` says are real; None: all)
    are put in expert order, each expert's rows are cut into tiles of
    ``tile`` rows (its last one padded, an expert with no rows has none)
    and a loop walks exactly the tiles there are: gather the tile's rows
    of ``x``, one gated MLP against that expert's weights, scatter-add
    times the routing weight. Shapes are static (a tile), the count of
    tiles is the data's: no slot is ever dropped, whatever the skew.
    Answers ``([T,D] f32, rows multiplied = tiles · tile)``."""
    T, k = idx.shape
    E = e_gu.shape[0]
    held = held_slots(idx, first, E)
    if valid is not None:
        held &= valid[:, None]
    local = jnp.where(held, idx - first, E).reshape(-1)      # E: not ours
    token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    weight = w.reshape(-1)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    counts = (local[:, None] == jnp.arange(E)).sum(0).astype(jnp.int32)
    tiles = (counts + tile - 1) // tile
    tile_end, row_end = jnp.cumsum(tiles), jnp.cumsum(counts)

    def one(a, e):
        return jax.lax.dynamic_index_in_dim(a, e, 0, False)

    def body(t, out):
        e = (t >= tile_end).sum().astype(jnp.int32)     # the tile's expert
        at = (t - (tile_end[e] - tiles[e])) * tile + jnp.arange(tile)
        real = at < counts[e]
        slot = order[jnp.clip(row_end[e] - counts[e] + at, 0, T * k - 1)]
        rows = token[slot]
        y = gated_mlp(x[rows], one(e_gu, e), one(e_down, e), dtype, act,
                      None if act_params is None else one(act_params, e))
        return out.at[rows].add(
            jnp.where(real, weight[slot], 0.0)[:, None] * y)

    n_tiles = tile_end[-1]
    out = jax.lax.fori_loop(0, n_tiles, body,
                            jnp.zeros(x.shape, jnp.float32))
    return out, n_tiles * tile


@device_scoped("llm_experts")
def held_part_token(x, idx, w, e_gu, e_down, first: int, dtype,
                    act=silu_gate, act_params=None):
    """One token: ``x`` [D], ``idx``/``w`` [k]. A loop over the held
    experts this token selected, and over nothing else: an absent slot
    costs no read of any weight. Answers [D] f32."""
    held = held_slots(idx, first, e_gu.shape[0])
    order = jnp.argsort(~held, stable=True)        # held slots first

    def one(a, e):
        return jax.lax.dynamic_index_in_dim(a, e, 0, False)

    def body(j, acc):
        slot = order[j]
        e = idx[slot] - first
        y = gated_mlp(x[None], one(e_gu, e), one(e_down, e), dtype, act,
                      None if act_params is None else one(act_params, e))
        return acc + w[slot] * y[0]

    return jax.lax.fori_loop(0, held.sum(), body,
                             jnp.zeros(x.shape, jnp.float32))


# --- the grouped form as one streaming kernel (PR 53) --------------------------


def streamed_form(rows: int, held: int, r: Routing, tile: int) -> bool:
    """Whether a prefill of ``rows`` rows over ``held`` held experts takes
    :func:`held_part_streamed` in place of :func:`held_part_grouped`: where
    the share is the router's WHOLE width (every routed slot is held, so the
    rows in expert order are ``rows · per_token`` whatever the routing and
    the static tile bound wastes at most one tile an expert) and every
    expert expects at least a whole tile of rows (below that each tile would
    be mostly padding AND meet a new expert: the loop's dense cousin is the
    better form there). One rule from the shapes, no flag; a share of a
    wider router keeps the loop (its five modules call :func:`held_part`).
    Served at two geometries, both with 4096-row chunks and tiles of 256
    rows: 128 experts of 2048 × 768 at top 8 (32 768 slots a chunk, 256 an
    expert: the kernel 3.07 ms a chunk a layer as served, where the loop's
    tile read 63 µs, ~12 ms a chunk — PERF.md §6, PR 53) and 16 experts of 2048 × 2048 at top 1, EXACTLY at the edge
    (4096 slots = 256 · 16; an expert's matrices are 24 MiB, twice buffered
    48 of the kernel's 64: they still fit whole beside a 256-row tile): the
    kernel 1.74 / 2.08 / 1.91 ms a chunk at even routing / a cycled brief's
    / one expert taking all, the loop 2.40 / 3.16 / 2.43; at tiles of 128
    rows 2.03 / 2.11 / 1.86 against 3.04 / 3.48 / 3.24 (PERF.md §6, PR 57;
    the floors there are 0.52 ms of products and 0.49 ms of bytes)."""
    return held == r.outputs and rows * r.per_token >= tile * r.outputs


def held_part_streamed(x, idx, w, e_gu, e_down, first: int, dtype,
                       act=silu_gate, valid=None, tile: int = GROUP_TILE,
                       kernel: "str | None" = None):
    """What :func:`held_part_grouped` computes, as ONE kernel over all the
    tiles (``ops/expert_stream.py``): the held slots are put in expert order,
    each expert's rows padded to whole tiles, the rows of ``x`` gathered
    ONCE into that order; the kernel walks the tiles with the experts'
    matrices streamed in behind a prefetched tile → expert table; each slot
    then reads its row of the result back (a gather, no scatter-add) and the
    ``per_token`` rows of a token are added times their routing weights. No
    slot is dropped, whatever the skew: the tile bound ``T · k / tile + E``
    holds for any routing. ``kernel``: ``pallas`` (the default on a TPU),
    ``interpret``, or ``lax`` (elsewhere: the loop, which this is held to).
    Answers ``([T,D] f32, rows multiplied = tiles · tile)``."""
    from . import flash_attention
    from .expert_stream import expert_tiles_mlp

    kernel = kernel or ("pallas" if flash_attention._platform() == "tpu"
                        else "lax")
    if kernel == "lax":
        return held_part_grouped(x, idx, w, e_gu, e_down, first, dtype, act,
                                 None, valid, tile)
    T, k = idx.shape
    E = e_gu.shape[0]
    with device_scope("llm_experts"):
        held = held_slots(idx, first, E)
        if valid is not None:
            held &= valid[:, None]
        local = jnp.where(held, idx - first, E).reshape(-1)   # E: not ours
        order = jnp.argsort(local, stable=True).astype(jnp.int32)
        counts = (local[:, None] == jnp.arange(E)).sum(0).astype(jnp.int32)
        tiles = (counts + tile - 1) // tile
        # an expert's first row among the sorted slots, and in the buffer
        tile_end = jnp.cumsum(tiles)
        row_start = jnp.cumsum(counts) - counts
        buf_start = (tile_end - tiles) * tile
        n_tiles = tile_end[-1]
        n_max = T * k // tile + E                    # tiles, at the most
        # where each slot's row sits in the buffer (a slot not held: nowhere)
        e_of = jnp.minimum(local, E - 1)
        rank = jnp.zeros((T * k,), jnp.int32).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32)) - row_start[e_of]
        place = jnp.where(local < E, buf_start[e_of] + rank, n_max * tile)
        token = jnp.arange(T * k, dtype=jnp.int32) // k
        source = jnp.zeros((n_max * tile,), jnp.int32).at[place].set(
            token, mode="drop")
        tile_expert = jnp.minimum(
            (jnp.arange(n_max)[:, None] >= tile_end).sum(1), E - 1)
        y = expert_tiles_mlp(x.astype(dtype)[source], tile_expert, n_tiles,
                             e_gu.astype(dtype), e_down.astype(dtype),
                             tile=tile, act=act,
                             interpret=kernel == "interpret")
        # a slot not held reads a row no tile wrote: dropped, not weighted
        mine = jnp.where((local < E)[:, None],
                         y[jnp.minimum(place, n_max * tile - 1)]
                         * w.reshape(-1, 1), 0.0)
        return mine.reshape(T, k, -1).sum(1), n_tiles * tile


def held_part_by_shape(x, idx, w, e_gu, e_down, first: int, dtype,
                       r: Routing, act=silu_gate, valid=None,
                       tile: int = GROUP_TILE, kernel: "str | None" = None):
    """:func:`held_part` for a module whose share may be the whole router:
    the streamed kernel where :func:`streamed_form` says so, else what
    :func:`held_part` takes (``prefill_form`` names both ``grouped``: rows
    in expert order, in tiles)."""
    if prefill_form(x.shape[0], r, tile) == "grouped" \
            and streamed_form(x.shape[0], e_gu.shape[0], r, tile):
        return held_part_streamed(x, idx, w, e_gu, e_down, first, dtype, act,
                                  valid, tile, kernel)
    return held_part(x, idx, w, e_gu, e_down, first, dtype, r, act,
                     valid=valid, tile=tile)

