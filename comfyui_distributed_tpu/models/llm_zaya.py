"""A tenth prompt rewriter, for a brief that fills its context: attention
that runs INSIDE a compressed latent whose queries and keys pass two causal
convolutions (Compressed Convolutional Attention), a top-1 router that is an
MLP carrying its state from layer to layer, every expert of every layer held,
a residual stream scaled and shifted at every sublayer, and a head tied to
the embedding.

Layer ``l`` is two sublayers, ``h ← s_h ⊙ (h + b_h) + s_y ⊙ (y + b_y)`` each
(four learned vectors a sublayer), ``y`` the sublayer's output of
``RMSNorm(h)``. **Attention (CCA)**: ``z = [q̃ | k̃] = x W_qk`` — a query
latent of ``heads × head_dim`` and a key latent of ``kv heads × head_dim``;
a depthwise causal convolution of ``cca_time0`` taps over the sequence, then
one of ``cca_time1`` taps GROUPED by head (a full ``head_dim × head_dim`` mix
inside each of the ``heads + kv heads`` heads at each tap); to the result the
mean of the UNconvolved query and key latents of the same K/V group is
added; q and k are L2-normed per head, k times a learned temperature a K/V
head, and turned by rope on the FIRST ``partial_rotary_factor`` of their
dimensions (the angles from ``llm_trinity``'s float64 host table); the
logit of a pair is ``√d · q · k``; the values are ``[x[t] W_v1 | x[t−1]
W_v2]`` viewed as K/V heads (a value shift); query head ``h`` reads K/V head
``h // (heads / kv heads)``; the output projection leaves from the
``heads × head_dim`` latent. **Experts**: ``r = x W_d + b_d (+ γ ⊙ r`` of
the layer before``)``, an MLP of two hidden layers over ``RMSNorm(r)`` gives
the logits; softmax over all the experts, the top ONE by ``p + β``, its
weight ``p`` itself (not normalised) — through ``ops/expert_share.py``
(:func:`expert_share.route_logits`: the model hands in logits it made) told
that it holds every expert.

The cache is TWO kinds of leaf a layer: the K and V rows ``[kv heads, rows,
head_dim]`` (``kv``: the keys normed, tempered and roped) and three
recurrent TAILS in one float32 vector (``tails``: the last token's ``z``,
its first convolution's output ``c0`` and its ``x W_v2``).
:func:`prefill_chunk` is the continuation ``llm_prefill`` scans
(``llm_model.chunked_prefill``): the convolutions read ``[tail ; chunk]``,
the chunk's rows are written and ``ops/gqa_attention.causal_chunk`` attends
over the buffer; a PADDED chunk hands back the tails as token ``n_valid − 1``
left them (the recurrent-leaf contract, here inside an attention layer).
Plain named scopes below ``cdt.llm_attn``: ``llm_cca_mix`` (everything
between the latent projections and the core) and ``llm_cca_core``.
Conventions are ``llm_hybrid.py``'s: weights held in ``dtype``, products on
``dtype`` operands accumulated in float32; residual stream, norms, the
depthwise convolution, rope, softmax, the router (its products at
``Precision.HIGHEST``) and logits float32; K/V rows ``dtype``; one sequence,
no batch axis. ``models/llm_zaya_reference.py`` is the plain float32
statement.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..ops import expert_share, gqa_attention
from ..telemetry.device_scopes import device_scope
from .llm_hybrid import (_ACT, _const, _count_held, _dot, _embed, _is_leaf,
                         _normal, _pre_norm, _stack_counts, count_params,
                         init_tree, rms_norm)
from .llm_model import LLMModel, chunked_prefill
from .llm_trinity import _rope_rows, rope_table

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    """Field names are the published ``config.json``'s. ``num_experts`` is
    how many experts are HELD here (``router_experts`` is the layer's count,
    the router's width: the same number — this chip holds them all),
    ``num_hidden_layers`` the depth kept."""
    hidden_size: int = 2048
    num_hidden_layers: int = 10
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5000000.0
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    qk_norm_eps: float = 1e-6
    router_hidden_size: int = 256
    router_experts: int = 16
    num_experts: int = 16
    first_expert: int = 0
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    vocab_size: int = 262272
    dtype: str = "bfloat16"
    # the schedule of the chunked prefill: sizes of the program, not options
    # of a request — the chunk, the causal kernel's tiles (the fifth
    # rewriter's full layer's, re-read at 8 q / 2 kv: PERF.md §6, PR 57) and
    # the rows of one grouped expert product
    prefill_chunk_tokens: int = 4096
    attn_block_q: int = 2048
    attn_block_k: int = 2048
    expert_tile: int = 256

    @classmethod
    def zaya_share(cls) -> "ZayaConfig":
        """ZAYA1-8B at its published widths: the first of four pipeline
        stages of ten whole layers, every one of a layer's 16 experts and
        the whole vocabulary."""
        return cls()

    @classmethod
    def tiny(cls, **kw) -> "ZayaConfig":
        """The CPU tests' size, float32: every mechanism, small widths, 3
        query heads a K/V head, chunks and tiles a test prompt spans several
        of, every expert held."""
        base = dict(
            hidden_size=32, num_hidden_layers=3, num_attention_heads=6,
            num_key_value_heads=2, head_dim=8, max_position_embeddings=96,
            router_hidden_size=12, router_experts=4, num_experts=4,
            moe_intermediate_size=16, vocab_size=64, dtype="float32",
            prefill_chunk_tokens=16, attn_block_q=8, attn_block_k=16,
            expert_tile=4)
        return cls(**{**base, **kw})

    def __post_init__(self):
        if self.cca_time0 != 2 or self.cca_time1 != 2:
            raise ValueError("the tails hold ONE row a convolution: two taps")

    @property
    def model(self) -> LLMModel:
        return MODEL

    @property
    def q_width(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def latent_width(self) -> int:
        """``z``: the query latent beside the key latent."""
        return (self.num_attention_heads + self.num_key_value_heads) \
            * self.head_dim

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def tail_width(self) -> int:
        """``[z | c0 | x W_v2]`` of the last token."""
        return 2 * self.latent_width + self.head_dim

    @property
    def moe_layers(self) -> list[int]:
        return list(range(self.num_hidden_layers))

    @property
    def routing(self) -> expert_share.Routing:
        return expert_share.Routing(self.router_experts,
                                    self.num_experts_per_tok, 1, 1, 1.0,
                                    score="softmax", normalised=False)

    @property
    def routed_slots_per_token(self) -> int:
        return self.num_experts_per_tok * self.num_hidden_layers

    stream_mixes_per_token = 0        # one residual stream, nothing mixed
    min_prompt_tokens = 1

    def attended_keys(self, prompt_tokens: int, new_tokens: int) -> dict:
        """(query, key) pairs ONE head attends in a request, by phase,
        summed over the layers: every key at or below the query."""
        T, n = prompt_tokens, self.num_hidden_layers
        ends = range(T + 1, T + new_tokens + 1)
        return {("cca", "prefill"): n * (T * (T + 1) // 2),
                ("cca", "decode"): n * sum(ends)}


# --- weights ---------------------------------------------------------------


def _away(std: float, mean: float = 0.0):
    """A parameter that a usual initialisation leaves at ``mean`` (1 or 0),
    drawn AWAY from it: leaving its mathematics out then moves the logits."""
    return ("about", mean, std)


def _shapes(cfg: ZayaConfig) -> dict:
    """Every drawn leaf as ``(shape, dtype name, init)``."""
    D, wd, F = cfg.hidden_size, cfg.dtype, cfg.moe_intermediate_size
    d, Z, R = cfg.head_dim, cfg.latent_width, cfg.router_hidden_size
    N = Z // d
    one = _const(1.0)

    def residual():
        return {"s_h": ((D,), "float32", _away(0.1, 1.0)),
                "b_h": ((D,), "float32", _away(0.02)),
                "s_y": ((D,), "float32", _away(0.1, 1.0)),
                "b_y": ((D,), "float32", _away(0.02))}

    layer = {
        "norm1": ((D,), "float32", one),
        "norm2": ((D,), "float32", one),
        "res_attn": residual(),
        "res_moe": residual(),
        "attn": {
            # z = [q̃ (H·d) | k̃ (G·d)]; [W_v1 | W_v2]
            "w_qk": ((D, Z), wd, _normal()),
            "w_v": ((D, 2 * d), wd, _normal()),
            # depthwise taps [t−1, t]; grouped taps [tap, head, in, out]
            "conv0_w": ((2, Z), "float32", _away(0.5, 0.5)),
            "conv0_b": ((Z,), "float32", _away(0.1)),
            "conv1_w": ((2, N, d, d), wd, _normal()),
            "conv1_b": ((Z,), "float32", _away(0.1)),
            "log_temp": ((cfg.num_key_value_heads,), "float32",
                         _away(0.3, 0.7)),
            "w_o": ((cfg.q_width, D), wd, _normal())},
        "router": {
            "w_down": ((D, R), wd, _normal()),
            "b_down": ((R,), "float32", _away(0.1)),
            "eda": ((R,), "float32", _away(0.3, 0.5)),
            "norm": ((R,), "float32", one),
            "w1": ((R, R), "float32", _normal()),
            "b1": ((R,), "float32", _away(0.1)),
            "w2": ((R, R), "float32", _normal()),
            "b2": ((R,), "float32", _away(0.1)),
            # std 2/√R: logits a few units apart, so that the weight p[e*]
            # of the expert chosen is not 1/E for every token
            "w3": ((R, cfg.router_experts), "float32",
                   _normal(2.0 / math.sqrt(R))),
            "bias": ((cfg.router_experts,), "float32", _away(0.05))},
        "moe": {
            "e_gu": ((cfg.num_experts, D, 2 * F), wd, _normal()),
            "e_down": ((cfg.num_experts, F, D), wd, _normal())}}
    # the embedding IS the head: std 1/√D gives the tied logits unit scale
    return {"embed": ((cfg.vocab_size, D), wd, _normal(1.0 / math.sqrt(D))),
            "final_norm": ((D,), "float32", one),
            "layers": [layer] * cfg.num_hidden_layers}


def init_zaya(cfg: ZayaConfig, key, abstract: bool = False):
    """The drawn weights (an ``_away`` leaf a normal of its std moved to its
    mean) and, beside them, the rope table of the roped part of a head
    (``llm_trinity.rope_table`` at ``rotary_dim``: a leaf, not a literal of
    the programs)."""
    specs = _shapes(cfg)
    tree = init_tree(jax.tree_util.tree_map(
        lambda s: (s[0], s[1], _normal(s[2][2])) if s[2][0] == "about" else s,
        specs, is_leaf=_is_leaf), key, abstract)
    if not abstract:
        tree = jax.tree_util.tree_map(
            lambda s, leaf: leaf + s[2][1] if s[2][0] == "about" else leaf,
            specs, tree, is_leaf=_is_leaf)
    rows = (cfg.max_position_embeddings, cfg.rotary_dim // 2)
    tree["rope"] = {k: jax.ShapeDtypeStruct(rows, jnp.float32)
                    for k in ("cos", "sin")} if abstract else rope_table(
        dataclasses.replace(cfg, head_dim=cfg.rotary_dim))
    return tree


def param_count(cfg: ZayaConfig) -> int:
    return count_params(_shapes(cfg))


# --- pieces shared by prefill and decode -----------------------------------


def _split_tails(cfg: ZayaConfig, tails):
    Z = cfg.latent_width
    return tails[:Z], tails[Z:2 * Z], tails[2 * Z:]


def _shift(tail, a):
    """``a`` [T,W] a row later: ``[tail ; a[:-1]]``."""
    return jnp.concatenate([tail[None], a[:-1]], axis=0)


def _l2(x, eps: float):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def _cca_mix(cfg: ZayaConfig, p, z, vv, tails, rope):
    """Everything between the latent projections and the core, for rows
    ``z`` [T, H·d + G·d] and ``vv = x [W_v1 | W_v2]`` [T, 2d] that follow the
    token whose ``tails`` are given: q [T,H,d] and k [G,T,d] (convolved,
    mean added, normed, k tempered, both roped on their first
    ``rotary_dim``), v [G,T,d] (the second head a token late) — k and v as
    the cache holds them — and the first convolution's output ``c0`` [T, ·]
    (a tail of the next token). A head is 128 LANES of the flat latent: every
    head is cut out, mixed, normed and roped as a ``[T, d]`` column block
    and nothing is laid out ``[T, heads, d]`` (alone 1.28 -> 1.16 ms a chunk;
    inside the program the two forms read the same: PERF.md §6, PR 57)."""
    dtype = jnp.dtype(cfg.dtype)
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    J, half = H // G, cfg.rotary_dim // 2
    tail_z, tail_c0, tail_v = _split_tails(cfg, tails)
    c0 = p["conv0_w"][0] * _shift(tail_z, z) + p["conv0_w"][1] * z \
        + p["conv0_b"]
    before, w1 = _shift(tail_c0, c0), p["conv1_w"].astype(dtype)
    cos, sin = rope

    def cut(a, n):
        return a[:, n * d:(n + 1) * d]

    def mixed(n):
        """Head ``n``'s second convolution: both taps in ONE product."""
        taps = jnp.concatenate([cut(before, n), cut(c0, n)], axis=1)
        return jnp.dot(taps.astype(dtype), w1[:, n].reshape(2 * d, d),
                       preferred_element_type=jnp.float32) \
            + cut(p["conv1_b"][None], n)

    def roped(a, scale=None):
        a = _l2(a, cfg.qk_norm_eps)
        if scale is not None:
            a = a * scale
        x1, x2 = a[:, :half], a[:, half:2 * half]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin, a[:, 2 * half:]], 1)

    q = jnp.concatenate(
        [roped(mixed(h) + 0.5 * (cut(z, h) + cut(z, H + h // J)))
         for h in range(H)], axis=1)
    temp = jnp.exp(p["log_temp"])
    k = jnp.stack([roped(
        mixed(H + g) + 0.5 * (sum(cut(z, g * J + j) for j in range(J)) / J
                              + cut(z, H + g)), temp[g]) for g in range(G)])
    v = jnp.stack([vv[:, :d], _shift(tail_v, vv[:, d:])])
    return q.reshape(-1, H, d), k, v, c0


def _residual(h, y, p):
    """``s_h ⊙ (h + b_h) + s_y ⊙ (y + b_y)``: a sublayer's merge."""
    with device_scope("llm_norm"):
        return p["s_h"] * (h + p["b_h"]) + p["s_y"] * (y + p["b_y"])


def _router_logits(cfg: ZayaConfig, p, x, before):
    """The router's MLP on the normed rows ``x`` [T,D] with the state
    ``before`` [T,R] of the layer before (None: the first layer): ``(logits
    [T,E], state [T,R])``, float32, products at the highest precision."""
    def dot(a, w):
        return jnp.dot(a, w.astype(jnp.float32), precision=_HIGHEST)

    with device_scope("llm_router"):
        r = dot(x.astype(jnp.float32), p["w_down"]) + p["b_down"]
        if before is not None:
            r = r + p["eda"] * before
        u = rms_norm(r, p["norm"], cfg.rms_norm_eps)
        a = jax.nn.gelu(dot(u, p["w1"]) + p["b1"], approximate=False)
        a = jax.nn.gelu(dot(a, p["w2"]) + p["b2"], approximate=False)
        return dot(a, p["w3"]), r


def logits_of(cfg: ZayaConfig, params, h):
    """Final norm and the tied head (the embedding, read once more)."""
    dtype = jnp.dtype(cfg.dtype)
    with device_scope("llm_head"):
        x = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        return jnp.einsum("...d,vd->...v", x.astype(dtype),
                          params["embed"].astype(dtype),
                          preferred_element_type=jnp.float32)


# --- prefill ---------------------------------------------------------------


def empty_cache(cfg: ZayaConfig, max_len: int) -> dict:
    """Per layer a K and a V of ``max_len`` rows rounded up to the kernel's
    K block (it then reads the buffer as it is), and the tails (zeros: the
    token before the first is nothing)."""
    dtype = jnp.dtype(cfg.dtype)
    G, d, bk = cfg.num_key_value_heads, cfg.head_dim, cfg.attn_block_k
    n, rows = cfg.num_hidden_layers, -(-max_len // bk) * bk
    return {"k": [jnp.zeros((G, rows, d), dtype)] * n,
            "v": [jnp.zeros((G, rows, d), dtype)] * n,
            "tails": [jnp.zeros((cfg.tail_width,), jnp.float32)] * n}


def cache_kinds(cfg: ZayaConfig, cache: dict) -> dict:
    return {"kv": [cache["k"], cache["v"]], "tails": cache["tails"]}


def prefill_chunk(cfg: ZayaConfig, params, cache: dict, ids, start, n_valid,
                  all_logits: bool = False, kernel: str | None = None):
    """``ids`` [C] at positions ``start .. start+C−1``, of which the first
    ``n_valid`` are the prompt's (the rest pad its last chunk: they route to
    no expert, nothing reads the K/V rows they write, and the tails stand
    where token ``n_valid − 1`` left them). Continues from ``cache``.
    Answers ``(logits, cache, held, rows)`` as ``llm_kimi.prefill_chunk``.
    ``kernel`` names the form of the attention and expert kernels
    (``pallas``, ``interpret``, ``lax``; None: the platform's)."""
    dtype = jnp.dtype(cfg.dtype)
    C = ids.shape[0]
    with device_scope("llm_attn"):
        rope = _rope_rows(params, start, C)
    with device_scope("llm_router"):
        valid = jnp.arange(C) < n_valid
    cache = {k: list(v) for k, v in cache.items()}
    held, rows = [], []
    state = None
    h = _embed(params, ids)
    for i, layer in enumerate(params["layers"]):
        p = layer["attn"]
        x = _pre_norm(h, layer["norm1"], cfg.rms_norm_eps)
        with device_scope("llm_attn"):
            z, vv = _dot(x, p["w_qk"], dtype), _dot(x, p["w_v"], dtype)
            with jax.named_scope("llm_cca_mix"):
                q, k, v, c0 = _cca_mix(cfg, p, z, vv, cache["tails"][i], rope)
                cache["tails"][i] = jnp.concatenate([
                    jax.lax.dynamic_index_in_dim(a, n_valid - 1, 0, False)
                    for a in (z, c0, vv[:, cfg.head_dim:])])
                k, v = (jax.lax.dynamic_update_slice(
                    cache[n][i], a.astype(dtype), (0, start, 0))
                    for n, a in (("k", k), ("v", v)))
                cache["k"][i], cache["v"][i] = k, v
            with jax.named_scope("llm_cca_core"):
                o = gqa_attention.causal_chunk(
                    q, k, v, start, math.sqrt(cfg.head_dim), dtype,
                    cfg.attn_block_q, cfg.attn_block_k, kernel=kernel)
            y = _dot(o.reshape(C, -1), p["w_o"], dtype)
        h = _residual(h, y, layer["res_attn"])
        x = _pre_norm(h, layer["norm2"], cfg.rms_norm_eps)
        logits, state = _router_logits(cfg, layer["router"], x, state)
        idx, w = expert_share.route_logits(logits, layer["router"]["bias"],
                                           cfg.routing)
        m = layer["moe"]
        y, n_rows = expert_share.held_part_by_shape(
            x, idx, w, m["e_gu"], m["e_down"], cfg.first_expert, dtype,
            cfg.routing, _ACT, valid=valid, tile=cfg.expert_tile,
            kernel=kernel)
        h = _residual(h, y, layer["res_moe"])
        with device_scope("llm_router"):
            real = jnp.where(valid[:, None], idx, -1)
            n_rows = n_rows.astype(jnp.int32)
        held.append(_count_held(cfg, real))
        rows.append(n_rows)
    with device_scope("llm_head"):
        last = h if all_logits else h[n_valid - 1]
    return logits_of(cfg, params, last), cache, _stack_counts(held), \
        _stack_counts(rows)


def prefill(cfg: ZayaConfig, params, ids, max_len: int,
            all_logits: bool = False, chunk: int | None = None,
            kernel: str | None = None):
    """The whole prompt ``ids`` [T], walked in chunks through the cache;
    answers as ``llm_hybrid.prefill``: ``(logits, cache, held)``."""
    return chunked_prefill(MODEL, cfg, params, ids, max_len, all_logits,
                           chunk, kernel=kernel)[:3]


# --- decode ----------------------------------------------------------------


def decode_step(cfg: ZayaConfig, params, cache: dict, token, pos):
    """One token ``token`` (scalar id) at position ``pos``: both
    convolutions from the tails, one step over the buffer, the router's MLP
    on one row, the ONE expert chosen; answers as
    ``llm_hybrid.decode_step``."""
    dtype = jnp.dtype(cfg.dtype)
    with device_scope("llm_attn"):
        rope = _rope_rows(params, pos, 1)
    cache = {k: list(v) for k, v in cache.items()}
    held = []
    state = None
    h = _embed(params, token)
    for i, layer in enumerate(params["layers"]):
        p = layer["attn"]
        x = _pre_norm(h, layer["norm1"], cfg.rms_norm_eps)
        with device_scope("llm_attn"):
            z, vv = _dot(x[None], p["w_qk"], dtype), \
                _dot(x[None], p["w_v"], dtype)
            with jax.named_scope("llm_cca_mix"):
                q, k, v, c0 = _cca_mix(cfg, p, z, vv, cache["tails"][i], rope)
                cache["tails"][i] = jnp.concatenate(
                    [z[0], c0[0], vv[0, cfg.head_dim:]])
                k, v = (jax.lax.dynamic_update_slice(
                    cache[n][i], a.astype(dtype), (0, pos, 0))
                    for n, a in (("k", k), ("v", v)))
                cache["k"][i], cache["v"][i] = k, v
            with jax.named_scope("llm_cca_core"):
                o = gqa_attention.step(
                    q[0], k, v, jnp.arange(k.shape[1]) <= pos,
                    math.sqrt(cfg.head_dim), dtype)
            y = _dot(o.reshape(-1), p["w_o"], dtype)
        h = _residual(h, y, layer["res_attn"])
        x = _pre_norm(h, layer["norm2"], cfg.rms_norm_eps)
        logits, state = _router_logits(cfg, layer["router"], x[None], state)
        idx, w = expert_share.route_logits(logits, layer["router"]["bias"],
                                           cfg.routing)
        m = layer["moe"]
        y = expert_share.held_part_token(
            x, idx[0], w[0], m["e_gu"], m["e_down"], cfg.first_expert, dtype,
            _ACT)
        h = _residual(h, y, layer["res_moe"])
        held.append(_count_held(cfg, idx))
    return logits_of(cfg, params, h), cache, _stack_counts(held)


MODEL = LLMModel(init_zaya, prefill, decode_step, empty_cache, cache_kinds,
                 prefill_chunk)
