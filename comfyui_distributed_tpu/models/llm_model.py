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
    empty_cache: Callable   # (cfg, max_len) -> the decode carry's state
    cache_kinds: Callable   # (cfg, cache) -> {kind of layer: its leaves}


def cache_bytes(model: LLMModel, cfg, max_len: int) -> dict:
    """Bytes of one request's cache by kind of layer, from the shapes
    ``empty_cache`` would make (nothing is allocated)."""
    import jax

    cache = jax.eval_shape(lambda: model.empty_cache(cfg, max_len))
    return {kind: sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(leaves))
            for kind, leaves in model.cache_kinds(cfg, cache).items()}
