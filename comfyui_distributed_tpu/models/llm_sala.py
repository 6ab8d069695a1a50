"""A seventh prompt rewriter, for the longest briefs: decayed linear
attention (``lightning-attn``) on three layers of four and grouped-query
attention over a SELECTION of key blocks (``minicpm4``: InfLLM-v2) on the
fourth, a dense SwiGLU FFN on every layer (no expert layer), muP scales on
the stream and an untied head.

Kept layer ``i`` is of the kind ``mixer_types[i]``. ``h_0 = scale_emb ·
E[id]``; every sublayer ``h ← h + r · f(RMSNorm(h))`` with ``r =
scale_depth / √mup_denominator`` (the PUBLISHED depth's, whatever is kept);
logits ``W_head RMSNorm(h) / (hidden_size / dim_model_base)``.
**Lightning**: ``[q | k | v | g] = x W_in`` as ``lightning_nh`` heads; q, k
RMS-normed per head and turned by rope (half rotation over the whole head,
the angles from a float64 table made on the host: :func:`rope_table`); the
recurrence and its chunk form are ``ops/lightning_attention.py``'s; ``o ←
RMSNorm(o)`` per head ``⊙ σ(g)``, ``W_o``. **Sparse**: ``num_attention_heads``
query heads over ``num_key_value_heads`` key/value heads, q and k RMS-normed
per head, NO positional encoding, ``o ⊙ σ(g)``, ``W_o``. A REQUEST of at
most ``dense_len`` positions (prompt + new tokens) attends causally over
every row (``ops/gqa_attention.py``); a longer one by the rule of
``ops/block_select_attention.py`` — compressed keys, block scores, forced
initial and local blocks, the best ``topk + window / block`` blocks — for
EVERY query of the request. The request's length is the cache's (its rows
are made for prompt + new tokens), so prefill and decode decide alike.

The cache is THREE kinds of leaf in one carry: a sparse layer's K and V
buffer ``[kv heads, rows, head_dim]`` (``sparse_kv``), its compressed-key
buffer ``[kv heads, rows / stride (in whole lanes), head_dim]``
(``sparse_index``: empty for a dense request; one more slot every
``kernel_stride`` tokens), and a lightning layer's state ``[heads, d, d]``
float32 (``linear``).
:func:`prefill_chunk` is the continuation ``llm_prefill`` scans
(``llm_model.chunked_prefill``): a padded last chunk advances neither the
states nor the compressed buffers past token ``n_valid − 1`` (the K/V rows
it writes past it are read by nobody before a decode step rewrites them).
:func:`decode_step` is one token through the same three. Conventions are
``llm_hybrid.py``'s: weights held in ``dtype``, products on ``dtype``
operands accumulated in float32; residual stream, norms, rope, softmax,
gates, block scores, the selection, the states and logits float32; K/V and
compressed rows ``dtype``; one sequence, no batch axis.
``models/llm_sala_reference.py`` is the plain float32 statement.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import block_select_attention as select_ops
from ..ops import gqa_attention, lightning_attention
from ..telemetry.device_scopes import device_scope
from .llm_hybrid import (_const, _dot, _normal, _pre_norm, _swiglu,
                         count_params, init_tree, rms_norm)
from .llm_model import LLMModel, chunked_prefill
from .llm_jamba import _no_held
from .llm_trinity import _rope, _rope_rows, _rows

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
# published layers 6–17 of 32: three whole periods at the published 1 : 3
_KEPT = (LIGHTNING,) * 3 + (SPARSE,) + (LIGHTNING,) * 6 + (SPARSE,) * 2


@dataclasses.dataclass(frozen=True)
class SalaConfig:
    """Field names are the published ``config.json``'s (the sparse sizes
    the family's ``sparse_config``'s). ``num_hidden_layers`` /
    ``mixer_types`` are the depth kept."""
    hidden_size: int = 4096
    num_hidden_layers: int = 12
    mixer_types: tuple = _KEPT
    intermediate_size: int = 16384
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    rope_theta: float = 10000.0
    max_position_embeddings: int = 524288
    rms_norm_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    mup_denominator: int = 32
    dim_model_base: int = 256
    vocab_size: int = 73448
    # the sparse layers' rule (assumed: the family's sparse_config)
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192
    dtype: str = "bfloat16"
    # positions the rope table holds (a leaf: 2 × 32 MiB; the published
    # 524 288 would be 2 × 128 MiB of a chip that has none to spare)
    rope_positions: int = 131072
    # the schedule of the chunked prefill; sizes of the program, not options
    # of a request: the chunk; the dense branch's tile; how many queries
    # score and select at once (what bounds the float32 [groups, rows,
    # slots] sums, the tables' sort and their masks); a sparse tile's
    # neighbouring queries and the K blocks a grid step reads (the scoring
    # kernel's tile is the op's own constant); the chunk form's block of
    # the linear recurrence
    prefill_chunk_tokens: int = 4096
    attn_block_q: int = 2048
    attn_block_k: int = 2048
    select_rows: int = 512
    sparse_block_q: int = 64
    sparse_blocks_per_step: int = 16
    lightning_block: int = 256

    @classmethod
    def sala_cut(cls) -> "SalaConfig":
        """MiniCPM-SALA at its published widths, published layers 6–17
        (3 sparse + 9 lightning), the whole vocabulary."""
        return cls()

    @classmethod
    def tiny(cls, **kw) -> "SalaConfig":
        """The CPU tests' size, float32: one period and a sparse pair, 3
        query heads a key/value head, a ``dense_len`` a test prompt sits
        on either side of, more blocks than a table holds, chunks a
        compressed window straddles."""
        base = dict(
            hidden_size=32, num_hidden_layers=5,
            mixer_types=(LIGHTNING, LIGHTNING, LIGHTNING, SPARSE, SPARSE),
            intermediate_size=48, num_attention_heads=6,
            num_key_value_heads=2, head_dim=8, lightning_nh=4,
            lightning_nkv=4, lightning_head_dim=8,
            max_position_embeddings=256, vocab_size=64, kernel_size=4,
            kernel_stride=2, block_size=8, window_size=16, topk=3,
            dense_len=32, dtype="float32", rope_positions=256,
            prefill_chunk_tokens=16, attn_block_q=8, attn_block_k=8,
            select_rows=8, sparse_block_q=8, sparse_blocks_per_step=2,
            lightning_block=4)
        return cls(**{**base, **kw})

    def __post_init__(self):
        if len(self.mixer_types) != self.num_hidden_layers:
            raise ValueError("mixer_types names every kept layer's kind")
        if self.lightning_nkv != self.lightning_nh:
            raise ValueError("a lightning layer's state is a head's own")
        self.selection.check()
        if self.dense_len % self.prefill_chunk_tokens \
                or self.prefill_chunk_tokens % self.block_size:
            raise ValueError(
                "dense_len is whole chunks and a chunk whole blocks: a "
                "request's cache rows say which side of dense_len it is on")

    @property
    def model(self) -> LLMModel:
        return MODEL

    @property
    def selection(self) -> select_ops.Selection:
        return select_ops.Selection(
            self.kernel_size, self.kernel_stride, self.block_size,
            self.init_blocks, self.window_size, self.topk)

    def is_sparse(self, i: int) -> bool:
        return self.mixer_types[i] == SPARSE

    @property
    def sparse_layers(self) -> list[int]:
        return [i for i in range(self.num_hidden_layers)
                if self.is_sparse(i)]

    @property
    def lightning_layers(self) -> list[int]:
        return [i for i in range(self.num_hidden_layers)
                if not self.is_sparse(i)]

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.mup_denominator)

    @property
    def logit_divisor(self) -> float:
        return self.hidden_size / self.dim_model_base

    def cache_rows(self, max_len: int) -> int:
        """Rows a sparse layer's buffer holds for a request that reaches
        ``max_len`` positions: whole blocks."""
        return -(-max_len // self.block_size) * self.block_size

    def cache_slots(self, rows: int) -> int:
        """Slots a sparse layer's compressed-key buffer holds beside
        ``rows`` K/V rows: one every ``kernel_stride`` rows, rounded up to
        the chip's 128 lanes — the slots are the minor axis of the float32
        scores, and an axis of 4104 makes the compiler lay the scores out
        rows-minor and walk its softmax as a window of 8207 (1.9 s a
        layer a prefill: PERF.md §6, PR 47). The slots past the rows never
        hold a window."""
        return -(-(rows // self.kernel_stride) // 128) * 128

    def reads_selection(self, rows: int) -> bool:
        """Does a request whose cache has ``rows`` rows pass ``dense_len``?
        (``rows`` is prompt + new tokens rounded up to a block, or to the
        chunks that walked it: both sides of ``dense_len`` stay there.)"""
        return rows > self.dense_len

    moe_layers = ()                   # no expert layer: nothing is routed
    stream_mixes_per_token = 0        # one residual stream, nothing mixed
    min_prompt_tokens = 1

    def _blocks_read(self, prompt_tokens: int, new_tokens: int) -> dict:
        """Per phase, ``(blocks read, forced among them, rows read)`` of
        ONE (sparse layer, key/value group), summed over the phase's
        queries, by the rule."""
        total, sel = prompt_tokens + new_tokens, self.selection
        pos = np.arange(total, dtype=np.int64)
        own = pos // sel.block_size
        if self.reads_selection(self.cache_rows(total)):
            read = np.minimum(own + 1, sel.table)
            forced = np.minimum(own + 1, sel.local_blocks) \
                + np.where(own >= sel.local_blocks, sel.init_blocks, 0)
            rows = (read - 1) * sel.block_size + pos % sel.block_size + 1
        else:
            read = forced = own + 1
            rows = pos + 1
        T = prompt_tokens
        return {"prefill": (read[:T].sum(), forced[:T].sum(), rows[:T].sum()),
                "decode": (read[T:].sum(), forced[T:].sum(), rows[T:].sum())}

    def attended_keys(self, prompt_tokens: int, new_tokens: int) -> dict:
        """(query, key) pairs ONE head attends in a request, by kind of
        layer and phase, summed over the layers of the kind: a sparse layer
        the rows at or below the query in the blocks it selects (every
        row, for a request within ``dense_len``), a lightning layer every
        key below the query (through its state)."""
        T, n = prompt_tokens, prompt_tokens + new_tokens
        read = self._blocks_read(prompt_tokens, new_tokens)
        causal = {"prefill": T * (T + 1) // 2,
                  "decode": n * (n + 1) // 2 - T * (T + 1) // 2}
        out = {}
        for phase in ("prefill", "decode"):
            out[("sparse", phase)] = len(self.sparse_layers) \
                * int(read[phase][2])
            out[("lightning", phase)] = len(self.lightning_layers) \
                * causal[phase]
        return out

    def selected_blocks(self, prompt_tokens: int, new_tokens: int) -> dict:
        """Blocks the sparse layers' queries read in a request, by kind:
        ``forced`` (the initial and local ones) and ``chosen`` (by score),
        over every (layer, key/value group, query)."""
        read = self._blocks_read(prompt_tokens, new_tokens)
        n = len(self.sparse_layers) * self.num_key_value_heads
        forced = sum(int(r[1]) for r in read.values())
        return {"forced": n * forced,
                "chosen": n * (sum(int(r[0]) for r in read.values())
                               - forced)}

    def scored_slot_tiles(self, prompt_tokens: int, new_tokens: int) -> dict:
        """(query tile, slot tile) pairs the prefill's scoring kernel meets
        in a request, over every (sparse layer, key/value group): ``scored``
        (some query of the tile sees a window of the slot tile whole) and
        ``skipped`` (none does: neither fetched nor computed). By the
        kernel's own rule at its own tiles, for every chunk the prefill
        walks (a padded chunk's rows score too); none for a request within
        ``dense_len``, and decode scores in the plain form."""
        chunk = min(self.prefill_chunk_tokens, prompt_tokens)
        walked = -(-prompt_tokens // chunk) * chunk
        rows = self.cache_rows(max(prompt_tokens + new_tokens, walked))
        if not self.reads_selection(rows):
            return {"scored": 0, "skipped": 0}
        slots = self.cache_slots(rows)
        block_q, block_slots = select_ops.score_tiles(
            math.gcd(chunk, self.select_rows), slots)
        first = np.arange(0, walked, block_q, dtype=np.int64)
        scored = int((select_ops.last_slot_tile(
            first, block_q, block_slots, self.kernel_stride,
            slots // block_slots, np.clip) + 1).sum())
        n = len(self.sparse_layers) * self.num_key_value_heads
        return {"scored": n * scored,
                "skipped": n * (len(first) * (slots // block_slots) - scored)}


# --- weights ---------------------------------------------------------------


def rope_table(cfg: SalaConfig) -> dict:
    """``cos`` and ``sin`` of ``p · θ^(−2k/d)`` for the first
    ``rope_positions`` positions, made in float64 ON THE HOST and held
    float32 (``llm_trinity.rope_table``'s reason)."""
    half = cfg.lightning_head_dim // 2
    freqs = cfg.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = np.arange(cfg.rope_positions, dtype=np.float64)[:, None] * freqs
    return {"cos": jnp.asarray(np.cos(angle), jnp.float32),
            "sin": jnp.asarray(np.sin(angle), jnp.float32)}


def _shapes(cfg: SalaConfig) -> dict:
    """Every drawn leaf as ``(shape, dtype name, init)``."""
    D, wd, F = cfg.hidden_size, cfg.dtype, cfg.intermediate_size
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    Hl, dl = cfg.lightning_nh, cfg.lightning_head_dim
    one = _const(1.0)
    layers = []
    for i in range(cfg.num_hidden_layers):
        if cfg.is_sparse(i):
            # [q (H·d) | k (G·d) | v (G·d) | gate (H·d)]
            attn = {"w_in": ((D, 2 * (H + G) * d), wd, _normal()),
                    "q_norm": ((d,), "float32", one),
                    "k_norm": ((d,), "float32", one),
                    "w_o": ((H * d, D), wd, _normal())}
        else:
            # [q | k | v | gate], Hl·dl each
            attn = {"w_in": ((D, 4 * Hl * dl), wd, _normal()),
                    "q_norm": ((dl,), "float32", one),
                    "k_norm": ((dl,), "float32", one),
                    "o_norm": ((dl,), "float32", one),
                    "w_o": ((Hl * dl, D), wd, _normal())}
        layers.append({"norm1": ((D,), "float32", one),
                       "norm2": ((D,), "float32", one), "attn": attn,
                       "ffn": {"w_gu": ((D, 2 * F), wd, _normal()),
                               "w_down": ((F, D), wd, _normal())}})
    # the embedding's std makes h₀ of unit scale AFTER scale_emb; the
    # head's makes the logits of unit scale after the divisor
    return {"embed": ((cfg.vocab_size, D), wd, _normal(1.0 / cfg.scale_emb)),
            "head": ((cfg.vocab_size, D), wd,
                     _normal(cfg.logit_divisor / math.sqrt(D))),
            "final_norm": ((D,), "float32", one),
            "layers": layers}


def init_sala(cfg: SalaConfig, key, abstract: bool = False):
    """The drawn weights and, beside them, the rope table (a leaf, not a
    literal of the programs)."""
    tree = init_tree(_shapes(cfg), key, abstract)
    rows = (cfg.rope_positions, cfg.lightning_head_dim // 2)
    tree["rope"] = {k: jax.ShapeDtypeStruct(rows, jnp.float32)
                    for k in ("cos", "sin")} if abstract else rope_table(cfg)
    return tree


def param_count(cfg: SalaConfig) -> int:
    return count_params(_shapes(cfg))


# --- pieces shared by prefill and decode -----------------------------------


def _embed(cfg: SalaConfig, params, ids):
    with device_scope("llm_head"):
        return params["embed"][ids].astype(jnp.float32) * cfg.scale_emb


def logits_of(cfg: SalaConfig, params, h):
    """Final norm, the untied head and muP's divisor; float32."""
    dtype = jnp.dtype(cfg.dtype)
    with device_scope("llm_head"):
        x = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        return jnp.einsum("...d,vd->...v", x.astype(dtype),
                          params["head"].astype(dtype),
                          preferred_element_type=jnp.float32) \
            / cfg.logit_divisor


def _lightning_in(cfg: SalaConfig, p, x, rope):
    """From the normed rows ``x`` [T,D]: q and k [T,H,d] (normed, roped), v
    [T,H,d] and the raw output gate [T,H·d]."""
    H, d, eps = cfg.lightning_nh, cfg.lightning_head_dim, cfg.rms_norm_eps
    T = x.shape[0]
    y = _dot(x, p["w_in"], jnp.dtype(cfg.dtype))
    q, k, v, gate = (y[:, j * H * d:(j + 1) * H * d] for j in range(4))
    q = _rope(rms_norm(q.reshape(T, H, d), p["q_norm"], eps), *rope)
    k = _rope(rms_norm(k.reshape(T, H, d), p["k_norm"], eps), *rope)
    return q, k, v.reshape(T, H, d), gate


def _lightning_out(cfg: SalaConfig, p, o, gate):
    """``(RMSNorm(o) per head ⊙ σ(gate)) W_o``."""
    o = rms_norm(o, p["o_norm"], cfg.rms_norm_eps)
    o = o.reshape(*o.shape[:-2], -1) * jax.nn.sigmoid(gate)
    return _dot(o, p["w_o"], jnp.dtype(cfg.dtype))


def _sparse_in(cfg: SalaConfig, p, x):
    """From the normed rows ``x`` [T,D]: q [T,H,d] and k [T,G,d] (normed
    per head, no positional encoding), v [T,G,d], the raw gate [T,H·d]."""
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    T, eps = x.shape[0], cfg.rms_norm_eps
    y = _dot(x, p["w_in"], jnp.dtype(cfg.dtype))
    q = rms_norm(y[:, :H * d].reshape(T, H, d), p["q_norm"], eps)
    k = rms_norm(y[:, H * d:(H + G) * d].reshape(T, G, d), p["k_norm"], eps)
    v = y[:, (H + G) * d:(H + 2 * G) * d].reshape(T, G, d)
    return q, k, v, y[:, (H + 2 * G) * d:]


def _sparse_out(cfg: SalaConfig, p, o, gate):
    o = o.reshape(*o.shape[:-2], -1).astype(jnp.float32) \
        * jax.nn.sigmoid(gate)
    return _dot(o, p["w_o"], jnp.dtype(cfg.dtype))


def _ffn(cfg: SalaConfig, layer, h):
    x = _pre_norm(h, layer["norm2"], cfg.rms_norm_eps)
    with device_scope("llm_shared_ffn"):
        return h + cfg.residual_scale * _swiglu(x, layer["ffn"],
                                                jnp.dtype(cfg.dtype))


# --- prefill ---------------------------------------------------------------


def empty_cache(cfg: SalaConfig, max_len: int) -> dict:
    """Per sparse layer a K and a V buffer of ``cache_rows(max_len)`` rows
    and — for a request past ``dense_len`` — a compressed-key buffer of a
    slot every ``kernel_stride`` rows; per lightning layer a state."""
    dtype = jnp.dtype(cfg.dtype)
    G, d = cfg.num_key_value_heads, cfg.head_dim
    rows = cfg.cache_rows(max_len)
    slots = cfg.cache_slots(rows) if cfg.reads_selection(rows) else 0
    n, Hl, dl = (len(cfg.sparse_layers), cfg.lightning_nh,
                 cfg.lightning_head_dim)
    return {"k": [jnp.zeros((G, rows, d), dtype) for _ in range(n)],
            "v": [jnp.zeros((G, rows, d), dtype) for _ in range(n)],
            "kc": [jnp.zeros((G, slots, d), dtype) for _ in range(n)],
            # a leaf a layer: a stacked leaf would be copied whole a token
            "state": [jnp.zeros((Hl, dl, dl), jnp.float32)
                      for _ in cfg.lightning_layers]}


def cache_kinds(cfg: SalaConfig, cache: dict) -> dict:
    return {"sparse_kv": [cache["k"], cache["v"]],
            "sparse_index": cache["kc"], "linear": cache["state"]}


def _selected_rows(cfg: SalaConfig, q, k, v, kc, start, kernel, keep):
    """The rule for a chunk's queries ``q`` [C,H,d] at rows ``start …``:
    ``select_rows`` of them score, select and attend at a time (the
    float32 sums, the sort and the masks of more would not fit beside the
    model); ``kernel`` names the form of BOTH kernels — the scores' and
    the table-driven one. Answers the attention [C,H,d], the sparse
    kernel's grid steps by ``STEP_FETCHES`` [3] and, where ``keep``, the
    tables [G,C,blocks]."""
    dtype, sel = jnp.dtype(cfg.dtype), cfg.selection
    C, H, d = q.shape
    scale = cfg.head_dim ** -0.5
    n = math.gcd(C, cfg.select_rows)

    def some(xs):
        q_n, first = xs
        with jax.named_scope("select"):
            chosen = select_ops.select(select_ops.block_scores(
                q_n, kc, first + jnp.arange(n), scale, dtype, sel,
                kernel), sel)
        with jax.named_scope("sparse_core"):
            o, fetches = select_ops.sparse_chunk(
                q_n, k, v, chosen, first, scale, dtype, sel,
                cfg.sparse_block_q, cfg.sparse_blocks_per_step, kernel)
        return o, fetches, (chosen if keep else None)

    o, fetches, chosen = jax.lax.map(some, (q.reshape(C // n, n, H, d),
                                            start + jnp.arange(C // n) * n))
    if keep:
        chosen = jnp.swapaxes(chosen, 0, 1)
        chosen = chosen.reshape(chosen.shape[0], C, chosen.shape[-1])
    return o.reshape(C, H, d), fetches.sum(axis=0), chosen


def prefill_chunk(cfg: SalaConfig, params, cache: dict, ids, start, n_valid,
                  all_logits: bool = False, kernel: str | None = None,
                  keep_tables: bool = False):
    """``ids`` [C] at positions ``start .. start+C−1``, of which the first
    ``n_valid`` are the prompt's (the rest pad its last chunk: the states
    and the compressed buffers stand where token ``n_valid − 1`` left
    them, and nothing reads the K/V rows they write). Continues from
    ``cache``. Answers ``(logits, cache, held, rows)`` as
    ``llm_kimi.prefill_chunk``: ``held`` is empty (no expert layer) and in
    the ``rows`` place stand the sparse kernel's grid steps by
    ``STEP_FETCHES``, summed over the sparse layers (empty for a dense
    request). ``kernel`` names the form of the attention kernels
    (``pallas``, ``interpret``, ``lax``; None: the platform's).
    ``keep_tables`` (a parity tool's) appends the sparse layers'
    selections ``[G, C, blocks]``, None for a dense request."""
    dtype, C = jnp.dtype(cfg.dtype), ids.shape[0]
    rows = cache["k"][0].shape[1] if cache["k"] else 0
    if rows > cfg.rope_positions:
        raise ValueError(f"{rows} positions outrun the rope table's "
                         f"{cfg.rope_positions}")
    selects = cfg.reads_selection(rows)
    with device_scope("llm_attn"):
        rope = _rope_rows(params, start, C)
    cache = {k: list(v) for k, v in cache.items()}
    tables, steps = [], []
    h = _embed(cfg, params, ids)
    at_sparse = at_lightning = 0
    for i, layer in enumerate(params["layers"]):
        x = _pre_norm(h, layer["norm1"], cfg.rms_norm_eps)
        p = layer["attn"]
        with device_scope("llm_attn"):
            if cfg.is_sparse(i):
                j, at_sparse = at_sparse, at_sparse + 1
                q, k, v, gate = _sparse_in(cfg, p, x)
                k, v = _rows(k, dtype), _rows(v, dtype)
                k_all, v_all = (jax.lax.dynamic_update_slice(
                    cache[n][j], a, (0, start, 0))
                    for n, a in (("k", k), ("v", v)))
                if selects:
                    with jax.named_scope("select"):
                        kc = select_ops.compress_chunk(
                            cache["kc"][j], cache["k"][j], k, start,
                            n_valid, cfg.selection)
                    o, fetches, chosen = _selected_rows(
                        cfg, q, k_all, v_all, kc, start, kernel, keep_tables)
                    cache["kc"][j] = kc
                    steps.append(fetches)
                    tables.append(chosen)
                else:
                    o = gqa_attention.causal_chunk(
                        q, k_all, v_all, start, cfg.head_dim ** -0.5, dtype,
                        cfg.attn_block_q, cfg.attn_block_k, kernel=kernel)
                cache["k"][j], cache["v"][j] = k_all, v_all
                y = _sparse_out(cfg, p, o, gate)
            else:
                j, at_lightning = at_lightning, at_lightning + 1
                q, k, v, gate = _lightning_in(cfg, p, x, rope)
                with jax.named_scope("lightning"):
                    o, cache["state"][j] = lightning_attention.lightning_chunk(
                        cache["state"][j], q, k, v,
                        lightning_attention.slopes(cfg.lightning_nh),
                        cfg.lightning_head_dim ** -0.5, n_valid, dtype,
                        cfg.lightning_block)
                y = _lightning_out(cfg, p, o, gate)
            h = h + cfg.residual_scale * y
        h = _ffn(cfg, layer, h)
    with device_scope("llm_head"):
        last = h if all_logits else h[n_valid - 1]
    with device_scope("llm_sample"):
        # in the place of the rows an expert layer multiplied (none here)
        steps = sum(steps) if steps else _no_held()
    out = (logits_of(cfg, params, last), cache, _no_held(), steps)
    return out + ((tables if selects else None),) if keep_tables else out


def prefill(cfg: SalaConfig, params, ids, max_len: int,
            all_logits: bool = False, chunk: int | None = None,
            kernel: str | None = None):
    """The whole prompt ``ids`` [T], walked in chunks through the cache;
    answers as ``llm_hybrid.prefill``: ``(logits, cache, held)``."""
    return chunked_prefill(MODEL, cfg, params, ids, max_len, all_logits,
                           chunk, kernel=kernel)[:3]


# --- decode ----------------------------------------------------------------


def decode_step(cfg: SalaConfig, params, cache: dict, token, pos,
                keep_tables: bool = False):
    """One token ``token`` (scalar id) at position ``pos`` through the
    buffers, the compressed buffers and the states; answers as
    ``llm_hybrid.decode_step`` (``held`` empty)."""
    dtype, sel = jnp.dtype(cfg.dtype), cfg.selection
    rows = cache["k"][0].shape[1] if cache["k"] else 0
    selects = cfg.reads_selection(rows)
    scale = cfg.head_dim ** -0.5
    with device_scope("llm_attn"):
        rope = _rope_rows(params, pos, 1)
    cache = {k: list(v) for k, v in cache.items()}
    tables = []
    h = _embed(cfg, params, token)
    at_sparse = at_lightning = 0
    for i, layer in enumerate(params["layers"]):
        x = _pre_norm(h, layer["norm1"], cfg.rms_norm_eps)
        p = layer["attn"]
        with device_scope("llm_attn"):
            if cfg.is_sparse(i):
                j, at_sparse = at_sparse, at_sparse + 1
                q, k, v, gate = _sparse_in(cfg, p, x[None])
                k, v = (jax.lax.dynamic_update_slice(
                    cache[n][j], _rows(a, dtype), (0, pos, 0))
                    for n, a in (("k", k), ("v", v)))
                cache["k"][j], cache["v"][j] = k, v
                if selects:
                    with jax.named_scope("select"):
                        kc = select_ops.compress_step(cache["kc"][j], k, pos,
                                                      sel)
                    cache["kc"][j] = kc
                    o, table = select_ops.sparse_step(q[0], k, v, kc, pos,
                                                      scale, dtype, sel)
                    tables.append(table)
                else:
                    o = gqa_attention.step(q[0], k, v,
                                           jnp.arange(rows) <= pos, scale,
                                           dtype)
                y = _sparse_out(cfg, p, o, gate[0])
            else:
                j, at_lightning = at_lightning, at_lightning + 1
                q, k, v, gate = _lightning_in(cfg, p, x[None], rope)
                with jax.named_scope("lightning"):
                    cache["state"][j], o = lightning_attention.lightning_step(
                        cache["state"][j], q[0], k[0], v[0],
                        lightning_attention.slopes(cfg.lightning_nh),
                        cfg.lightning_head_dim ** -0.5)
                y = _lightning_out(cfg, p, o, gate[0])
            h = h + cfg.residual_scale * y
        h = _ffn(cfg, layer, h)
    out = (logits_of(cfg, params, h), cache, _no_held())
    return out + ((tables if selects else None),) if keep_tables else out


MODEL = LLMModel(init_sala, prefill, decode_step, empty_cache, cache_kinds,
                 prefill_chunk)
