"""Overlap-scheduled and quantized mesh collectives.

One fused ``psum``/``all_gather`` is a barrier: every byte must land
before ANY dependent compute starts. The executed mesh tier
(docs/parallelism.md) instead decomposes its collectives into per-block
``ppermute`` ring steps — the dependency structure then lets XLA's
latency-hiding scheduler run each hop's neighbour transfer concurrently
with the compute the previously-arrived blocks already unblocked
(T3-style fine-grained compute/communication overlap, arXiv 2401.16677).
The ring order is fixed (shard 0 → 1 → … → n-1 → 0), so results are
deterministic run-to-run and host-to-host.

On top of the ring decomposition rides an opt-in quantized wire format
(EQuARX, arXiv 2506.17615): payloads cross the interconnect as int8 with
a per-tensor absmax scale, halving bf16 collective bytes. The default
(``CDT_COLLECTIVE_QUANT=none``) keeps every collective bit-exact; the
``int8`` tier's error is bounded and documented per function.

Every function here is meant to be called INSIDE ``shard_map`` — the
same contract as ``parallel/collectives.py``.

Knobs: ``CDT_MESH_OVERLAP`` (default on — ring decomposition),
``CDT_COLLECTIVE_QUANT`` (``none``/``int8``, default ``none``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.lax import axis_size as _axis_size

from ..utils import constants


def overlap_enabled() -> bool:
    return constants.MESH_OVERLAP.get()


def collective_quant_mode() -> str:
    """``none`` (bit-exact, the default) or ``int8``."""
    return constants.COLLECTIVE_QUANT.get()


def quant_error_bound(absmax: float, hops: int = 1) -> float:
    """Worst-case per-element absolute error of the int8 wire format.

    One quantization round is absmax-scaled round-to-nearest:
    ``scale = absmax / 127``, so ``|x - deq(q)| <= scale/2 = absmax/254``.
    A payload re-quantized on every ring hop (reduce-scatter partials)
    compounds at most ``hops`` rounds; payloads quantized once and
    rotated as int8 (all-gather, ring-attention K/V) hold at one round
    regardless of ring length.
    """
    return hops * absmax / 254.0


# --- int8 wire format --------------------------------------------------------


def wire_quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-tensor absmax int8 quantization of a collective payload.

    Returns ``(q, scale)`` with ``q`` int8 and ``scale`` a float32
    scalar; ``dequantize(q, scale)`` is within ``absmax/254`` of ``x``
    per element (see :func:`quant_error_bound`). An all-zero payload
    quantizes to scale 0 and dequantizes exactly.
    """
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = absmax / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / safe), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def wire_dequantize(q: jax.Array, scale: jax.Array,
                    dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


# --- ring decompositions -----------------------------------------------------


def _right_perm(n: int) -> list[tuple[int, int]]:
    return [(j, (j + 1) % n) for j in range(n)]


def _chunks(x: jax.Array, n: int, dim: int) -> jax.Array:
    """[n, ...chunk...] stack of ``x`` split ``n``-ways along ``dim``."""
    if x.shape[dim] % n:
        raise ValueError(
            f"ring collective: dim {dim} of shape {x.shape} must divide "
            f"over {n} shards")
    return jnp.stack(jnp.split(x, n, axis=dim))


def _take(chunks: jax.Array, j: jax.Array, n: int) -> jax.Array:
    return jax.lax.dynamic_index_in_dim(chunks, jnp.mod(j, n), 0,
                                        keepdims=False)


def reduce_scatter_ring(x: jax.Array, axis: str, dim: int = 0,
                        quant: Optional[str] = None) -> jax.Array:
    """Ring reduce-scatter: shard ``i`` ends with chunk ``i`` of the
    cross-shard sum of ``x`` (split ``n``-ways along ``dim``).

    ``n-1`` per-block ppermute steps, each carrying one chunk-sized
    payload; the blocks not in flight stay available to downstream
    compute, which is the whole point of the decomposition. Accumulation
    is float32 in ring order (deterministic).

    ``quant="int8"`` quantizes every hop's partial-sum payload for the
    wire; error compounds at most ``(n-1) * absmax / 254`` per element
    (:func:`quant_error_bound` with ``hops=n-1``).
    """
    n = _axis_size(axis)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis)
    chunks = _chunks(x.astype(jnp.float32), n, dim)
    perm = _right_perm(n)
    carry = _take(chunks, idx - 1, n)
    for t in range(1, n):
        if quant == "int8":
            q, scale = wire_quantize(carry)
            q = jax.lax.ppermute(q, axis, perm)
            scale = jax.lax.ppermute(scale, axis, perm)
            carry = wire_dequantize(q, scale)
        else:
            carry = jax.lax.ppermute(carry, axis, perm)
        carry = carry + _take(chunks, idx - 1 - t, n)
    return carry.astype(x.dtype)


def all_gather_ring(x: jax.Array, axis: str, dim: int = 0,
                    quant: Optional[str] = None) -> jax.Array:
    """Ring all-gather: every shard ends with the shards' ``x`` blocks
    concatenated in shard order along ``dim``.

    ``n-1`` per-block ppermute hops instead of one fused all-gather —
    block ``t`` arrives at hop ``t`` and immediately unblocks whatever
    consumes it while later hops are still in flight.

    ``quant="int8"`` quantizes each shard's block ONCE and rotates the
    int8 payload, so every remote block carries exactly one quantization
    round (``absmax/254``); the local block stays exact.
    """
    n = _axis_size(axis)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis)
    perm = [(j, (j - 1) % n) for j in range(n)]     # receive from i+1
    if quant == "int8":
        q, scale = wire_quantize(x)
        collected = [x.astype(jnp.float32)]
        for _ in range(1, n):
            q = jax.lax.ppermute(q, axis, perm)
            scale = jax.lax.ppermute(scale, axis, perm)
            collected.append(wire_dequantize(q, scale))
    else:
        carry = x
        collected = [carry]
        for _ in range(1, n):
            carry = jax.lax.ppermute(carry, axis, perm)
            collected.append(carry)
    # collected[t] holds shard (idx+t) % n's block; roll to absolute order
    stacked = jnp.stack(collected)
    rolled = jnp.roll(stacked, idx, axis=0)
    return jnp.concatenate(
        [rolled[t] for t in range(n)], axis=dim).astype(x.dtype)


def _scatter_dim(shape: tuple, n: int) -> Optional[int]:
    for d, s in enumerate(shape):
        if s >= n and s % n == 0:
            return d
    return None


def all_reduce(x: jax.Array, axis: str,
               quant: Optional[str] = None,
               overlap: Optional[bool] = None) -> jax.Array:
    """Cross-shard sum with the mesh tier's scheduling policy.

    Default (``CDT_MESH_OVERLAP=1``): reduce-scatter + all-gather over
    per-block ppermute rings — 2(n-1) chunk transfers XLA can overlap
    with the compute each finished block unblocks, vs one fused barrier.
    ``CDT_MESH_OVERLAP=0`` (or a shape with no shard-divisible dim)
    falls back to one ``psum``.

    ``quant`` defaults to ``CDT_COLLECTIVE_QUANT``; ``"int8"`` halves
    bf16 wire bytes with error bounded by ``quant_error_bound(absmax,
    hops=n-1)`` from the reduce-scatter plus one round from the gather.
    The ``none`` default is bit-exact with respect to this function's
    own f32 ring order (deterministic, and on a 1-shard axis the input
    passes through untouched).
    """
    n = _axis_size(axis)
    if n == 1:
        return x
    if quant is None:
        quant = collective_quant_mode()
        quant = None if quant == "none" else quant
    if overlap is None:
        overlap = overlap_enabled()
    dim = _scatter_dim(x.shape, n)
    if not overlap or dim is None:
        out = jax.lax.psum(x, axis)
        return out
    scattered = reduce_scatter_ring(x, axis, dim=dim, quant=quant)
    return all_gather_ring(scattered, axis, dim=dim, quant=quant)
