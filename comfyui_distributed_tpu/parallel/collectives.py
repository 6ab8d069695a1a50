"""Collective wrappers — the in-graph replacements for the reference's
HTTP result plumbing.

Reference mapping (SURVEY §2.10):
- Collector gather (worker POSTs base64-PNG envelopes to master
  ``/distributed/job_complete``, master drains an asyncio queue,
  ``nodes/collector.py:143-178,381-499``) → ``gather_batch`` (all_gather
  over ICI, zero serialization, deterministic participant order).
- Tile submission (chunked multipart POSTs, ``upscale/worker_comms.py:16-108``)
  → tiles simply live in the sharded output array.

These helpers are meant to be called *inside* ``shard_map``-decorated
functions; they are thin by design so XLA can fuse and schedule them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size as _axis_size

from ..utils import constants


def gather_batch(x: jax.Array, axis: str = constants.AXIS_DATA) -> jax.Array:
    """All-gather shards along dim 0, concatenated in participant order.

    Participant order is mesh-index order: index 0 first — the same
    deterministic "master first, then workers in enabled order" contract as
    the reference's ``_reorder_and_combine_tensors``
    (``nodes/collector.py:252-295``).

    Under ``CDT_MESH_OVERLAP`` (default on) the gather is the ring
    decomposition (``parallel/overlap.all_gather_ring``): n-1 per-block
    ppermute hops whose already-arrived blocks unblock downstream
    compute while later hops are in flight. Bit-exact either way —
    gathering moves bytes, never recomputes them.

    Note: under ``jax.shard_map`` the gathered value is equal on every shard
    but is still *tracked* as axis-varying, so callers that declare it
    replicated via ``out_specs=P(None, ...)`` must pass ``check_vma=False``.
    """
    from .overlap import all_gather_ring, overlap_enabled

    if overlap_enabled():
        return all_gather_ring(x, axis, dim=0)
    return jax.lax.all_gather(x, axis, axis=0, tiled=True)


def mean_over(x: jax.Array, axis: str) -> jax.Array:
    """Cross-shard mean; the overlap-scheduled ring under
    ``CDT_MESH_OVERLAP`` (see ``sum_over``)."""
    from .overlap import overlap_enabled

    if overlap_enabled():
        return sum_over(x, axis) / _axis_size(axis)
    return jax.lax.pmean(x, axis)


def sum_over(x: jax.Array, axis: str) -> jax.Array:
    """Cross-shard sum. ``CDT_MESH_OVERLAP`` (default on) routes it
    through the ring reduce-scatter + all-gather decomposition
    (``parallel/overlap.all_reduce`` — per-block ppermute steps XLA can
    overlap with the compute each block unblocks; the opt-in
    ``CDT_COLLECTIVE_QUANT`` int8 wire rides the same path); otherwise
    one fused ``psum``."""
    from .overlap import all_reduce, overlap_enabled

    if overlap_enabled():
        return all_reduce(x, axis)
    return jax.lax.psum(x, axis)


def shard_index(axis: str) -> jax.Array:
    return jax.lax.axis_index(axis)


def ring_shift(x: jax.Array, axis: str, shift: int = 1) -> jax.Array:
    """Rotate shards around the ring: shard i receives shard i-shift.

    Building block for ring attention / ring-overlapped pipelines; compiles
    to ``ppermute`` which XLA maps onto ICI neighbour links.
    """
    n = _axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis, perm)


def all_to_all_heads(x: jax.Array, axis: str, split_dim: int, concat_dim: int) -> jax.Array:
    """All-to-all used by Ulysses-style sequence parallelism: redistribute
    from sequence-sharded to head-sharded layout (and back)."""
    return jax.lax.all_to_all(x, axis, split_axis=split_dim, concat_axis=concat_dim, tiled=True)
