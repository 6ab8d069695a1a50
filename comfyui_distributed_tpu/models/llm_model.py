"""A language model as the prompt rewriter's pipeline sees it: a value.

``diffusion/pipeline_llm.py``, ``LLMBundle`` and the nodes know no model
by name; they take an :class:`LLMModel` — the functions every model module
gives under the same signatures — from the model's config
(``config.model``), and the config for the sizes.
"""

from __future__ import annotations

from typing import Callable, NamedTuple


class LLMModel(NamedTuple):
    init: Callable          # (cfg, key, abstract=False) -> weights
    prefill: Callable       # (cfg, weights, ids [T], max_len) ->
    #                         (last logits [V], cache, held [expert layers])
    decode_step: Callable   # (cfg, weights, cache, token, pos) ->
    #                         (logits [V], cache, held [expert layers])
    # ``held`` has one count an expert layer — ``cfg.moe_layers`` — and is
    # an empty int32 vector for a model that has none (its config then
    # needs no ``routing``, ``num_experts`` or ``expert_tile``); a model
    # whose router has identity experts (``routing.zero_experts``) appends
    # the slots that fell on those, again one count an expert layer:
    # ``[held … | zero …]``
    empty_cache: Callable   # (cfg, max_len) -> the decode carry's state
    cache_kinds: Callable   # (cfg, cache) -> {kind of layer: its leaves}
    # the prefill's continuation, for a model whose prompt is walked in
    # chunks through the cache (None: ``prefill`` takes it whole):
    # (cfg, weights, cache, ids [C], start, n_valid, all_logits=False) ->
    # (logits, cache, held [expert layers], rows [expert layers]); a chunk
    # is ``cfg.prefill_chunk_tokens`` long (a RING in the cache ties it to
    # its length), ``start`` a multiple (a padded one: ``chunked_prefill``)
    prefill_chunk: "Callable | None" = None
    # (cfg, weights) -> weights in the form ``decode_step`` reads fastest,
    # made ONCE ahead of a token loop (None: the loop reads them as stored)
    decode_weights: "Callable | None" = None


def cache_bytes(model: LLMModel, cfg, max_len: int) -> dict:
    """Bytes of one request's cache by kind of layer, from the shapes
    ``empty_cache`` would make (nothing is allocated)."""
    import jax

    cache = jax.eval_shape(lambda: model.empty_cache(cfg, max_len))
    return {kind: sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(leaves))
            for kind, leaves in model.cache_kinds(cfg, cache).items()}


def chunked_prefill(model: LLMModel, cfg, weights, ids, max_len: int,
                    all_logits: bool = False, chunk: "int | None" = None,
                    **kw):
    """A prompt ``ids`` [T] through ``model.prefill_chunk``, ``chunk``
    tokens (``cfg.prefill_chunk_tokens``) at a time: ONE scan whose carry is
    the cache, so nothing the size of the prompt exists but the cache and the
    ids. The last chunk is padded (the cache has rows for it):
    ``prefill_chunk`` is told how many of its rows are the prompt's
    (``n_valid``) and owes the carry this — rows of a full-length cache past
    ``n_valid`` may hold anything (nothing reads them before a decode step
    rewrites them), but a RECURRENT leaf (a state, a convolution's tail) must
    come back as token ``n_valid − 1`` left it. The rows of a RING (slot
    ``position % length``) are one: a padded row would overwrite the slot of a
    row the next token still sees, so a padded chunk writes only its
    ``n_valid`` rows there — and where the ring is SHORTER than the chunk, the
    LAST ``length`` of them (the rows before ``n_valid``, not the chunk's tail).
    Answers ``(logits, cache, held, rows)``: the last position's logits [V] (every position's [T,V] with ``all_logits``), held slots
    and rows multiplied per expert layer summed over the chunks."""
    import jax
    import jax.numpy as jnp

    from ..telemetry.device_scopes import device_scope

    T = ids.shape[0]
    chunk = min(chunk or cfg.prefill_chunk_tokens, T)
    n = -(-T // chunk)
    # the scan's own bookkeeping (the empty cache, the padded ids, what is
    # kept of the chunks' answers) counts with the token carry
    with device_scope("llm_sample"):
        cache = model.empty_cache(cfg, max(max_len, n * chunk))
        padded = jnp.pad(ids, (0, n * chunk - T)).reshape(n, chunk)
        chunks = jnp.arange(n)

    def body(cache, xs):
        i, chunk_ids = xs
        with device_scope("llm_sample"):
            start = i * chunk
            n_valid = jnp.minimum(chunk, T - start)
        logits, cache, held, rows = model.prefill_chunk(
            cfg, weights, cache, chunk_ids, start, n_valid, all_logits,
            **kw)
        return cache, (logits, held, rows)

    cache, (logits, held, rows) = jax.lax.scan(body, cache, (chunks, padded))
    with device_scope("llm_sample"):
        logits = (logits.reshape(n * chunk, -1)[:T] if all_logits
                  else logits[-1])
        return logits, cache, held.sum(0), rows.sum(0)
