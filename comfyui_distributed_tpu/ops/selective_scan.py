"""Mamba-1's selective scan: a recurrence with no matrix form.

``h_t = exp(Δ_t ⊙ A) ⊙ h_{t−1} + (Δ_t ⊙ u_t) ⊗ B_t``, ``y_t = h_t C_t + D ⊙
u_t``, gated ``y_t ⊙ silu(z_t)``: the state is ``[d_inner, N]`` a layer and
its decay differs for every (channel, state) pair and every token, so a
chunk of tokens is not a pair of matrix products (``ops/delta_rule.py``'s
KDA chunk is): it is ``d_inner · N`` multiply-adds and as many ``exp`` a
token, on the vector unit. Everything here is float32 — "the state is the
one thing a recurrent layer cannot afford to round" (``delta_rule.py``).

Two forms, equal to each other and to the reference:

- :func:`scan_step` — one token from a state: what decode runs (XLA).
- :func:`scan_chunk` — ``T`` tokens from a given state to the state after
  them: what a prefill chunk runs. On a TPU it is the Pallas kernel below
  (inside the jitted :func:`selective_scan`, so the trace shows
  ``selective_scan.*``); elsewhere a plain ``lax.scan`` of
  :func:`scan_step` over the tokens. One rule (``flash_attention._platform``), no
  switch.

Rows ``≥ n_valid`` of a padded chunk get ``Δ = 0``: decay 1, input 0, the
state stands (their ``y`` is ``D ⊙ u`` gated: nothing reads it).

**The kernel.** grid = (T / block_t, d_inner / block_d), the channel axis
innermost. The whole layer's state ``[N, d_inner]`` float32 (327 KB at
5120 × 16) lives in VMEM scratch across the grid: states on sublanes,
channels on the 128 lanes, so a step of 128 channels is two vregs, ``Δ_t``
and ``u_t`` are one row broadcast down the sublanes, and ``h · C`` is a
sublane reduce. ``B_t`` and ``C_t`` vary down the sublanes and are constant
along the lanes: they come in already broadcast, ``[T, N, 128]`` (made once
a layer a chunk by XLA, 8 KB a token; the block's index does not change
along the inner channel axis, so it is fetched once a time block). ``u``,
``Δ``, ``z`` are read once and ``y`` written once, in their natural
``[T, d_inner]`` layout; the state is read from ``h0`` at the first grid
step and written to the output at the last. Inside a grid step the tokens
are a ``fori_loop``; ``block_d / 128`` lane tiles give the scheduler
independent chains to interleave. ``D ⊙ u`` and the gate are applied to
the whole ``[block_t, block_d]`` tile after the loop.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention
from .flash_attention import _LANES

_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# the kernel's schedule, fixed here by measurement (PERF.md §6, PR 37)
BLOCK_T = 256
BLOCK_D = 512
UNROLL = 8          # tokens a loop trip: one sublane tile of rows


def scan_step(h, u, dt, z, B, C, A, D):
    """One token. ``h`` [d, N] the state before it; ``u``, ``dt`` (Δ, after
    its softplus), ``z`` [d]; ``B``, ``C`` [N]; ``A`` [d, N] (negative);
    ``D`` [d]. Answers ``(y [d], h [d, N])``, float32."""
    h = jnp.exp(dt[:, None] * A) * h + (dt * u)[:, None] * B[None, :]
    y = (h * C[None, :]).sum(-1) + D * u
    return y * jax.nn.silu(z), h


def _scan_lax(h0, u, dt, z, B, C, A, D):
    def body(h, xs):
        y, h = scan_step(h, *xs, A, D)
        return h, y

    h, y = jax.lax.scan(body, h0, (u, dt, z, B, C))
    return y, h


def _scan_kernel(u_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref, h0_ref,
                 y_ref, ht_ref, h_ref, *, block_t: int, lanes: int,
                 tiles: int, unroll: int):
    i, g = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _load():
        h_ref[g] = h0_ref[...]

    a = a_ref[...]                                    # [N, block_d]
    row_of = jax.lax.broadcasted_iota(jnp.int32, (unroll, lanes), 0)
    cols = [slice(j * lanes, (j + 1) * lanes) for j in range(tiles)]

    def tokens(k, h):
        # eight tokens a trip: Mosaic loads and stores whole sublane tiles
        # at a traced row, so Δ, u and y move as [8, lanes] and a token is
        # one static row of the tile
        rows = pl.ds(pl.multiple_of(k * unroll, unroll), unroll)
        h, ys = list(h), []
        for j, sl in enumerate(cols):
            dts = dt_ref[rows, sl]
            xs = dts * u_ref[rows, sl]
            y = jnp.zeros_like(dts)
            for r in range(unroll):
                b, c = b_ref[k * unroll + r], c_ref[k * unroll + r]
                dt = dts[r:r + 1]                     # [1, lanes]
                h[j] = jnp.exp(dt * a[:, sl]) * h[j] + xs[r:r + 1] * b
                y = jnp.where(row_of == r,
                              jnp.sum(h[j] * c, axis=0, keepdims=True), y)
            y_ref[rows, sl] = y
        return tuple(h)

    state = h_ref[g]
    h = jax.lax.fori_loop(0, block_t // unroll, tokens,
                          tuple(state[:, sl] for sl in cols))
    for j, sl in enumerate(cols):
        h_ref[g, :, sl] = h[j]
    z = z_ref[...]
    y_ref[...] = (y_ref[...] + d_ref[...] * u_ref[...]) * (
        z * jax.nn.sigmoid(z))

    @pl.when(i == pl.num_programs(0) - 1)
    def _store():
        ht_ref[...] = h_ref[g]


@functools.partial(jax.jit, static_argnames=("block_t", "block_d", "unroll",
                                             "interpret"))
def selective_scan(h0, u, dt, z, B, C, A, D, block_t: int = BLOCK_T,
                   block_d: int = BLOCK_D, unroll: int = UNROLL,
                   interpret: bool = False):
    """The Pallas form of :func:`scan_chunk` (``dt`` already masked).
    ``T % block_t == 0``, ``block_t % unroll == 0``, ``d % block_d == 0``
    and ``block_d`` a multiple of the lane tile (128, or ``d`` where it is
    narrower)."""
    T, d = u.shape
    N = A.shape[1]
    lanes = min(_LANES, block_d)
    nt, ng = T // block_t, d // block_d
    kernel = functools.partial(_scan_kernel, block_t=block_t, lanes=lanes,
                               tiles=block_d // lanes, unroll=unroll)
    rows = pl.BlockSpec((block_t, block_d), lambda i, g: (i, g))
    wide = pl.BlockSpec((block_t, N, lanes), lambda i, g: (i, 0, 0))
    state = pl.BlockSpec((N, block_d), lambda i, g: (0, g))
    y, ht = pl.pallas_call(
        kernel, grid=(nt, ng),
        in_specs=[rows, rows, rows, wide, wide, state,
                  pl.BlockSpec((1, block_d), lambda i, g: (0, g)), state],
        out_specs=[rows, state],
        out_shape=[jax.ShapeDtypeStruct((T, d), jnp.float32),
                   jax.ShapeDtypeStruct((N, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((ng, N, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(u, dt, z, jnp.broadcast_to(B[:, :, None], (T, N, lanes)),
      jnp.broadcast_to(C[:, :, None], (T, N, lanes)), A.T, D[None, :], h0.T)
    return y, ht.T


def scan_chunk(h0, u, dt, z, B, C, A, D, n_valid=None,
               kernel: str | None = None):
    """``T`` tokens from the state ``h0`` [d, N]: ``u``, ``dt``, ``z``
    [T, d], ``B``, ``C`` [T, N], of which the first ``n_valid`` (traced;
    None: all) are real. Answers ``(y [T, d], h [d, N])`` — the state after
    token ``n_valid − 1``. ``kernel``: ``pallas`` (the default on a TPU),
    ``interpret`` (the same kernel in the Pallas interpreter) or ``lax``
    (the default elsewhere)."""
    f32 = jnp.float32
    h0, u, dt, z, B, C, A, D = (x.astype(f32)
                                for x in (h0, u, dt, z, B, C, A, D))
    if n_valid is not None:
        dt = jnp.where(jnp.arange(u.shape[0])[:, None] < n_valid, dt, 0.0)
    if kernel is None:
        kernel = "pallas" if flash_attention._platform() == "tpu" else "lax"
    if kernel == "lax":
        return _scan_lax(h0, u, dt, z, B, C, A, D)
    T, d = u.shape
    block_d = BLOCK_D if d % BLOCK_D == 0 else d
    block_t = BLOCK_T if T % BLOCK_T == 0 else T
    return selective_scan(h0, u, dt, z, B, C, A, D, block_t=block_t,
                          block_d=block_d, unroll=math.gcd(UNROLL, block_t),
                          interpret=kernel == "interpret")
