"""The prompt rewriter's two programs: ``llm_prefill`` and ``llm_decode``.

Both are bound with ``bind_weights`` under a label, like every denoise
program, so they are timed and spanned the same way
(``cdt_pipeline_{compile,execute,dispatch}_seconds{pipeline}``,
``program.launch`` / ``program.wait``). ``llm_decode`` is the whole decode
loop in ONE program — ``samplers.token_program`` scanned by
``run_segment`` — with no host round trip a token and no host callback,
so both programs persist in the XLA cache.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..models.llm_model import cache_bytes, chunked_prefill
from ..ops.expert_share import prefill_form
from .pipeline import bind_weights, cached_build
from .samplers import run_segment, token_program

TAP_EVERY = 128     # llm_decode returns every 128th step's logits


def _slot_counts(cfg) -> int:
    """How many counts both programs hand back: the held slots of every
    expert layer and, where the router has identity experts, the slots on
    those (``[held … | zero …]``)."""
    layers = len(cfg.moe_layers)
    return layers * 2 if layers and cfg.routing.zero_experts else layers


class LLMPipeline:
    """Any language model that gives ``models/llm_model.LLMModel``'s
    functions: ``config.model`` is that value, ``config`` its sizes."""

    def __init__(self, config, params):
        self.config = config
        self.model = config.model
        self.params = params

    def step(self, weights, state, token, pos):
        """One decoded token: what ``llm_decode`` scans."""
        return self.model.decode_step(self.config, weights, state, token,
                                      pos)

    def prefill_plan(self, prompt_tokens: int) -> tuple:
        """``(chunk, chunks, form)``: how ``llm_prefill`` walks a prompt of
        this length (whole: one chunk) and the form its expert layers
        take for the rows a call of them sees — None for a model that has
        no expert layer (its config then has no ``routing`` to read)."""
        cfg, whole = self.config, self.model.prefill_chunk is None
        chunk = prompt_tokens if whole \
            else min(cfg.prefill_chunk_tokens, prompt_tokens)
        if not cfg.moe_layers:
            form = None
        elif whole:
            form = prefill_form(chunk, cfg.routing)
        else:
            form = prefill_form(chunk, cfg.routing, cfg.expert_tile)
        return chunk, -(-prompt_tokens // chunk), form

    def prefill_fn(self, prompt_tokens: int, new_tokens: int):
        """``(ids [prompt_tokens]) -> (last logits [V], cache, held)``; a
        model that gives ``prefill_chunk`` is walked through the cache in
        chunks inside this one program, and also answers the rows its
        expert layers multiplied (``held`` and the rows have one entry an
        expert layer: none for a model without one)."""
        cfg, max_len = self.config, prompt_tokens + new_tokens
        if self.model.prefill_chunk is not None:
            # the continuation, scanned: (..., cache, held, rows multiplied)
            def llm_prefill(weights, ids):
                return chunked_prefill(self.model, cfg, weights, ids,
                                       max_len)

            return bind_weights(
                jax.jit(llm_prefill), self.params, label="llm_prefill",
                span_attrs={"chunk": self.prefill_plan(prompt_tokens)[0]})
        prefill = self.model.prefill

        def llm_prefill(weights, ids):
            return prefill(cfg, weights, ids, max_len)

        return bind_weights(jax.jit(llm_prefill), self.params,
                            label="llm_prefill")

    def decode_fn(self, prompt_tokens: int, new_tokens: int,
                  tap_every: int = TAP_EVERY):
        """``(logits, cache, key, temperature) -> (ids [new_tokens], tap
        logits [new_tokens // tap_every, V], held slots per expert layer
        (an empty vector where the model has none), finite)``:
        ``new_tokens`` steps of the token program in one scan, over the
        model's ``decode_weights`` where it gives that form.
        ``tap_every`` is the served program's unless a parity tool builds
        a decode of its own to compare more rows."""
        n_counts = _slot_counts(self.config)

        def llm_decode(weights, logits, cache, key, temperature):
            if self.model.decode_weights is not None:
                weights = self.model.decode_weights(self.config, weights)

            def forward(state, token, i):
                return self.step(weights, state, token, prompt_tokens + i)

            prog = token_program(forward, new_tokens, key, temperature,
                                 tap_every, n_counts)
            carry = run_segment(prog, prog.init((logits, cache)), 0,
                                new_tokens)
            return prog.extract(carry), carry[3], carry[4], carry[5]

        return bind_weights(jax.jit(llm_decode), self.params,
                            label="llm_decode", steps=new_tokens)

    def programs(self, prompt_tokens: int, new_tokens: int):
        return cached_build(
            self, ("llm", prompt_tokens, new_tokens),
            lambda: (self.prefill_fn(prompt_tokens, new_tokens),
                     self.decode_fn(prompt_tokens, new_tokens)))

    def generate(self, ids, new_tokens: int, seed: int,
                 temperature: float) -> dict:
        """``ids`` (the prompt: any sequence of host ints, copied to the
        device as it is where it is an int32 array) → exactly
        ``new_tokens`` drawn ids and what the programs say of themselves,
        fetched to the host (the tap logits stay on the device)."""
        prefill, decode = self.programs(len(ids), int(new_tokens))
        _, chunks, form = self.prefill_plan(len(ids))
        layers = len(self.config.moe_layers)
        logits, cache, slots_prefill, *rows = prefill(
            jnp.asarray(np.asarray(ids, np.int32)))
        # a chunked prefill counts the rows its experts multiplied (none
        # without an expert layer: a block-selecting model's chunks count
        # their sparse kernel's grid steps by fetch in that place); the
        # whole-prompt form multiplies every held expert by every token
        counted = np.asarray(rows[0]) if rows else np.zeros((0,), np.int64)
        if not layers:
            rows, steps = 0, counted
        elif rows:
            rows, steps = int(counted.sum()), counted[:0]
        else:
            rows = len(ids) * self.config.num_experts * layers
            steps = counted
        out, taps, slots_decode, finite = decode(
            logits, cache, jax.random.key(int(seed)),
            jnp.asarray(temperature, jnp.float32))
        slots_prefill, slots_decode = (np.asarray(slots_prefill),
                                       np.asarray(slots_decode))
        # ``finite`` covers prefill's logits too: step 0 draws from them
        return {"ids": np.asarray(out), "prefill_logits": logits,
                # shapes only (eval_shape): nothing is allocated or run
                "cache_bytes": cache_bytes(self.model, self.config,
                                           len(ids) + int(new_tokens)),
                "tap_logits": taps, "finite": bool(finite),
                # per expert layer; ``zero_*`` empty without identity experts
                "held_prefill": slots_prefill[:layers],
                "held_decode": slots_decode[:layers],
                "zero_prefill": slots_prefill[layers:],
                "zero_decode": slots_decode[layers:],
                "prefill_chunks": chunks, "prefill_form": form,
                "rows_prefill": rows, "sparse_steps": steps}
