"""Per-participant RNG derivation.

Parity: the reference's ``DistributedSeed`` node gives worker ``N`` the seed
``seed + N + 1`` while the master keeps ``seed`` (``nodes/utilities.py:52-75``)
so every participant samples a different image. The TPU-native version derives
statistically independent keys with ``jax.random.fold_in`` — inside a sharded
computation via ``lax.axis_index``, or host-side for a whole batch at once.

fold_in is used instead of additive offsets because nearby integer seeds do
not guarantee independent streams; fold_in does, and it composes with JAX's
key semantics under jit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..telemetry.device_scopes import device_scope


def seed_to_key(seed: int) -> jax.Array:
    return jax.random.key(jnp.uint32(seed))


def participant_key(base_key: jax.Array, axis: str) -> jax.Array:
    """Per-participant key *inside* a ``shard_map``/``pmap`` over ``axis``.

    Index 0 (the reference's "master") folds in 0, worker ``N`` folds in
    ``N`` — preserving the reference's deterministic master-first ordering
    (``nodes/collector.py:252-295``) without special-casing the master.
    """
    with device_scope("sampler"):
        return jax.random.fold_in(base_key, jax.lax.axis_index(axis))


def participant_keys(base_key: jax.Array, n: int) -> jax.Array:
    """Host-side: stacked keys for ``n`` participants; row ``i`` equals what
    ``participant_key`` yields at mesh index ``i``."""
    return jax.vmap(lambda i: jax.random.fold_in(base_key, i))(jnp.arange(n))


def participant_seeds(seed: int, n: int) -> list[int]:
    """Plain-integer view for UIs/logs: the reference's visible seed list
    (master = seed, worker N = seed + N + 1, ``nodes/utilities.py:52-75``).
    Kept for API/display parity only — sampling uses fold_in keys."""
    return [seed] + [seed + i + 1 for i in range(n - 1)]
