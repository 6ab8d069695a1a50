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

**Operands as the products wrote them (PR 46).** The mixer's input product
writes ``uz = [u | z]``, ``[T, 2·d_inner]`` float32, and two consumers each
want a half. An operand of a Pallas call has to be an array of its own: a
call handed ``uz[:, d:]`` makes XLA materialise that half — and, in the same
fusion, the other — ahead of it (168 MB read and written a layer a chunk at
the served widths: 0.153 s of a 65 536-token prefill). So both kernels here
take ``uz`` WHOLE and pick their half with the ``BlockSpec``'s index map:
:func:`scan_chunk` reads the gate from the last ``d`` columns of its ``z``
operand, :func:`conv_chunk` its inputs from the first ``d`` of its ``x``.
That needs a lane-tiled ``block_d`` and a half that starts on a block's edge
(5120 / 512 and 1024: yes; the narrow widths of the CPU tests: no, they
slice first) — a rule on the operand's shape, no option.

**The scan kernel.** grid = (T / block_t, d_inner / block_d), the channel axis
innermost. The whole layer's state ``[N, d_inner]`` float32 (327 KB at
5120 × 16) lives in VMEM scratch across the grid: states on sublanes,
channels on the 128 lanes, so a step of 128 channels is two vregs, ``Δ_t``
and ``u_t`` are one row broadcast down the sublanes, and ``h · C`` is a
sublane reduce. ``B_t`` and ``C_t`` vary down the sublanes and are constant
along the lanes: they come in transposed, ``[N, T]`` (64 B a token each),
and at the first channel block of every time block the kernel spreads each
token's column along the lanes into a ``[block_t, N, 128]`` VMEM scratch
that all the time block's channel blocks then read (until PR 46 XLA made
that array in HBM, 8 KB a token: 0.047 s a prefill for the broadcasts, and
the kernel read it back). ``u``, ``Δ``, ``z`` are read once and ``y``
written once, in their natural ``[T, d_inner]`` layout; the state is read
from ``h0`` at the first grid step and written to the output at the last.
Inside a grid step the tokens are a ``fori_loop``; ``block_d / 128`` lane
tiles give the scheduler independent chains to interleave. ``D ⊙ u`` and
the gate are applied to the whole ``[block_t, block_d]`` tile after the
loop.

**The convolution kernel** (:func:`conv_chunk`, jitted as
:func:`causal_conv_silu`; PR 46): the mixer's causal depthwise convolution
over time (K taps a channel), its bias and its silu, one pass: ``uz``'s left
half read once from HBM, ``u`` written once. grid = (d_inner / block_d,
T / block_t), time innermost; a VMEM scratch holds one sublane tile of rows
ahead of the block — the tail handed in at the first time block, the
previous block's last rows after it — and an inner loop walks it 64 rows a
trip: one window loaded from a sublane-tile boundary, the K taps static
slices of it (a loop, not the block unrolled: the kernel compiles in 0.2 s
where the unrolled 1024 × 1024 block took 2.7, at each of three call sites
of every warm set-up). The same products and sums in the same order as the
``lax`` form (four shifted slices, XLA), bit for bit on the chip and in the
interpreter. XLA's own fusion of that form reads its input K times, so the
compiler staged the half in VMEM for it, which is what the split copy was
for; left alone with ``uz`` in HBM it read it K times from there (+0.14 s).
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
CONV_BLOCK_T = 1024  # the convolution's blocks (PERF.md §6, PR 46)
CONV_BLOCK_D = 1024
_SUBLANES = 8
_CONV_ROWS = 64     # rows a trip of the convolution's inner loop


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


def _form(kernel: str | None) -> str:
    """``kernel`` or, for None, the platform's: ``pallas`` on a TPU, ``lax``
    elsewhere."""
    if kernel is None:
        return "pallas" if flash_attention._platform() == "tpu" else "lax"
    return kernel


def _columns(x, d: int, block_d: int, start: int):
    """The ``d`` columns of ``x`` from ``start`` as a Pallas operand blocked
    ``block_d`` wide: ``(operand, the columns' offset in blocks)``. ``x``
    itself where the columns start on a block's edge and blocks are whole
    lane tiles; else the columns sliced off, a copy."""
    if x.shape[1] == d:
        return x, 0
    if start % block_d or block_d % _LANES:
        return x[:, start:start + d], 0
    return x, start // block_d


def _scan_kernel(u_ref, dt_ref, z_ref, bt_ref, ct_ref, a_ref, d_ref, h0_ref,
                 y_ref, ht_ref, h_ref, b_ref, c_ref, *, block_t: int,
                 lanes: int, tiles: int, unroll: int):
    i, g = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _load():
        h_ref[g] = h0_ref[...]

    @pl.when(g == 0)
    def _spread():
        # B_t, C_t along the lanes, once a time block for all its channel
        # blocks: b_ref[t] is the [N, lanes] tile the token loop reads
        for wide_ref, thin in ((b_ref, bt_ref[...]), (c_ref, ct_ref[...])):
            for t in range(block_t):
                wide_ref[t] = jnp.broadcast_to(thin[:, t:t + 1],
                                               wide_ref.shape[1:])

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
    narrower). ``z`` may be wider than ``u``: the gate is its last ``d``
    columns (:func:`_columns`)."""
    T, d = u.shape
    N = A.shape[1]
    lanes = min(_LANES, block_d)
    nt, ng = T // block_t, d // block_d
    z, skip = _columns(z, d, block_d, z.shape[1] - d)
    kernel = functools.partial(_scan_kernel, block_t=block_t, lanes=lanes,
                               tiles=block_d // lanes, unroll=unroll)
    rows = pl.BlockSpec((block_t, block_d), lambda i, g: (i, g))
    gate = pl.BlockSpec((block_t, block_d),
                        lambda i, g: (i, g + skip))
    thin = pl.BlockSpec((N, block_t), lambda i, g: (0, i))
    state = pl.BlockSpec((N, block_d), lambda i, g: (0, g))
    y, ht = pl.pallas_call(
        kernel, grid=(nt, ng),
        in_specs=[rows, rows, gate, thin, thin, state,
                  pl.BlockSpec((1, block_d), lambda i, g: (0, g)), state],
        out_specs=[rows, state],
        out_shape=[jax.ShapeDtypeStruct((T, d), jnp.float32),
                   jax.ShapeDtypeStruct((N, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((ng, N, block_d), jnp.float32),
                        pltpu.VMEM((block_t, N, lanes), jnp.float32),
                        pltpu.VMEM((block_t, N, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(u, dt, z, B.T, C.T, A.T, D[None, :], h0.T)
    return y, ht.T


def _conv_lax(tail, x, w, b):
    padded = jnp.concatenate([tail, x], 0)
    return jax.nn.silu(sum(padded[j:j + x.shape[0]] * w[j]
                           for j in range(w.shape[0])) + b)


def _conv_kernel(x_ref, tail_ref, w_ref, b_ref, o_ref, ext_ref, *,
                 block_t: int, taps: int):
    i = pl.program_id(1)

    # ext = [one sublane tile ending in the rows before this block; the
    # block]: tap j of row t is ext[8 − (taps − 1) + j + t]
    @pl.when(i == 0)
    def _first():
        ext_ref[0:_SUBLANES] = tail_ref[...]

    @pl.when(i > 0)
    def _carry():
        ext_ref[0:_SUBLANES] = ext_ref[block_t:block_t + _SUBLANES]

    ext_ref[_SUBLANES:] = x_ref[...]
    first = _SUBLANES - (taps - 1)
    step = math.gcd(block_t, _CONV_ROWS)

    def rows(k, carry):
        # a window from a sublane tile's edge; the taps static slices of it
        base = pl.multiple_of(k * step, step)
        win = ext_ref[pl.ds(base, step + _SUBLANES), :]
        acc = win[first:first + step] * w_ref[0:1]
        for j in range(1, taps):
            acc = acc + win[first + j:first + j + step] * w_ref[j:j + 1]
        v = acc + b_ref[...]
        o_ref[pl.ds(base, step), :] = v * jax.nn.sigmoid(v)
        return carry

    jax.lax.fori_loop(0, block_t // step, rows, 0)


@functools.partial(jax.jit, static_argnames=("block_t", "block_d",
                                             "interpret"))
def causal_conv_silu(tail, x, w, b, block_t: int = CONV_BLOCK_T,
                     block_d: int = CONV_BLOCK_D, interpret: bool = False):
    """The Pallas form of :func:`conv_chunk`. ``T % block_t == 0``,
    ``d % block_d == 0``. ``x`` may be wider than ``w``: the inputs are its
    first ``d`` columns (:func:`_columns`)."""
    taps, d = w.shape
    T = x.shape[0]
    x, _ = _columns(x, d, block_d, 0)
    return pl.pallas_call(
        functools.partial(_conv_kernel, block_t=block_t, taps=taps),
        grid=(d // block_d, T // block_t),
        in_specs=[pl.BlockSpec((block_t, block_d), lambda g, i: (i, g)),
                  pl.BlockSpec((_SUBLANES, block_d), lambda g, i: (0, g)),
                  pl.BlockSpec((taps, block_d), lambda g, i: (0, g)),
                  pl.BlockSpec((1, block_d), lambda g, i: (0, g))],
        out_specs=pl.BlockSpec((block_t, block_d), lambda g, i: (i, g)),
        out_shape=jax.ShapeDtypeStruct((T, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((_SUBLANES + block_t, block_d),
                                   jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x, jnp.pad(tail, ((_SUBLANES - (taps - 1), 0), (0, 0))), w,
      b[None, :])


def conv_chunk(tail, x, w, b, kernel: str | None = None):
    """The mixer's causal depthwise convolution and its silu over a chunk:
    ``tail`` [K−1, d] the inputs of the K−1 tokens before it, the chunk's
    inputs the first ``d`` columns of ``x`` [T, ≥ d] (the ``[u | z]``
    product whole: the kernel reads its left half in place), ``w`` [K, d],
    ``b`` [d]. Answers ``u`` [T, d], float32. ``kernel`` as
    :func:`scan_chunk`'s."""
    f32 = jnp.float32
    tail, x, w, b = (a.astype(f32) for a in (tail, x, w, b))
    kernel = _form(kernel)
    T, d = x.shape[0], w.shape[1]
    if kernel == "lax":
        return _conv_lax(tail, x[:, :d], w, b)
    return causal_conv_silu(
        tail, x, w, b, block_t=CONV_BLOCK_T if T % CONV_BLOCK_T == 0 else T,
        block_d=CONV_BLOCK_D if d % CONV_BLOCK_D == 0 else d,
        interpret=kernel == "interpret")


def scan_chunk(h0, u, dt, z, B, C, A, D, n_valid=None,
               kernel: str | None = None):
    """``T`` tokens from the state ``h0`` [d, N]: ``u``, ``dt`` [T, d], the
    gate the last ``d`` columns of ``z`` [T, ≥ d] (the ``[u | z]`` product
    whole: the kernel reads its right half in place), ``B``, ``C`` [T, N],
    of which the first ``n_valid`` (traced; None: all) are real. Answers ``(y [T, d], h [d, N])`` — the state after
    token ``n_valid − 1``. ``kernel``: ``pallas`` (the default on a TPU),
    ``interpret`` (the same kernel in the Pallas interpreter) or ``lax``
    (the default elsewhere)."""
    f32 = jnp.float32
    h0, u, dt, z, B, C, A, D = (x.astype(f32)
                                for x in (h0, u, dt, z, B, C, A, D))
    if n_valid is not None:
        dt = jnp.where(jnp.arange(u.shape[0])[:, None] < n_valid, dt, 0.0)
    kernel = _form(kernel)
    T, d = u.shape
    if kernel == "lax":
        return _scan_lax(h0, u, dt, z[:, z.shape[1] - d:], B, C, A, D)
    block_d = BLOCK_D if d % BLOCK_D == 0 else d
    block_t = BLOCK_T if T % BLOCK_T == 0 else T
    return selective_scan(h0, u, dt, z, B, C, A, D, block_t=block_t,
                          block_d=block_d, unroll=math.gcd(UNROLL, block_t),
                          interpret=kernel == "interpret")
