"""Power retention of degree 2: a gated, NORMALISED linear recurrence over
the symmetric square of every key, in the two forms a served language model
needs — one token at a time for decode, a chunk at a time for a prefill
walked through the cache. Per K/V head, with a gate ``γ_t = exp(log_g_t)``
that comes from the token,

    S_t = γ_t S_{t−1} + φ(k_t) v_tᵀ        S ∈ R^{D×d_v}, float32 (held as Sᵀ)
    z_t = γ_t z_{t−1} + φ(k_t)              z ∈ R^{D},     float32
    o_t = φ(q_t)ᵀ S_t / φ(q_t)ᵀ z_t        (every query head of the group)

with ``φ(a)·φ(b) = (a·b)²``: the same function as the quadratic form ``o_t =
Σ_s A[t,s] v_s / Σ_s A[t,s]``, ``A[t,s] = exp(b_t − b_s)(q_t·k_s)²``, ``b`` the
running sum of ``log_g`` — which is how ``models/llm_brumby_reference.py``
states it, with no ``φ`` and no state. ``q`` and ``k`` come in divided by
``d^¼`` (the caller's), which puts the function's ``1/√d`` on the pair.
``ops/lightning_attention.py`` (a fixed decay a head, the identity for ``φ``,
``d × d``, no normaliser) and ``ops/delta_rule.py`` are the other linear
recurrences of the tree.

**What is held, and in which coordinates** (one rule, from what each is
contracted with — ``docs/kernels.md`` has the readings):

- ``S`` is contracted with ``φ(q)`` on the MXU and its other axis is 128
  wide, so its cost is ``D``'s and ``φ`` is kept as SHORT as whole lane tiles
  allow: tile ``r`` of ``φ(a)`` is ``a ⊙ roll(a, r)`` for ``r = 0 … d/2`` —
  ``d/2 + 1`` tiles of ``d`` lanes, ``D = d(d/2 + 1)`` (8320 at ``d`` 128,
  where the exact ``d(d+1)/2`` is 8256: tile ``d/2`` meets each of its pairs
  twice and so carries weight 1 where tiles ``1 … d/2 − 1`` carry ``√2``). A
  tile is ONE lane rotation and one product of a ``[rows, d]`` tile: nothing
  is gathered.
- ``z`` is contracted with ``φ(q)`` to ONE number a row: in ``φ``'s
  coordinates that is a multiply-add on every one of the ``D`` columns on
  the vector unit. ``φ(q)·z = qᵀ Z q`` with ``Z = Σ_s w_s k_s k_sᵀ`` — ``z``
  in the coordinates of the WHOLE outer product (``d × d``: 64 KiB a head
  beside ``S``'s 4.26 MB) — is one ``d``-wide product and a row sum. So the
  normaliser is held as ``Z`` [d, d].

**The chunk form** cuts a chunk into blocks of ``block`` rows; with ``c_i``
a block's own running log-gate (``c_{−1} = 0``), from ``(S_0, Z_0)``:

    o_i ∝ Σ_{j≤i} e^{c_i − c_j} (q_i·k_j)² [v_j | 1] + e^{c_i} φ(q_i)ᵀ[S_0 | z_0]
    [S | z]_B = e^{c_{B−1}} [S_0 | z_0] + Σ_j e^{c_{B−1} − c_j} φ(k_j) [v_j | 1]ᵀ

Every ratio is ``exp`` of a DIFFERENCE taken first, and every difference is
``≤ 0`` (``lightning_attention.py``'s rule). The blocks are WALKED: a block
expands its own rows' ``φ`` where they are consumed, and nothing
``[chunk, heads, D]`` or ``[blocks, heads, D, d_v]`` exists. On a TPU the
walk is the Pallas kernel below; elsewhere a ``lax.scan`` over the blocks.
One rule (``selective_scan._form``), no switch.

``S`` is held TRANSPOSED, ``[K/V heads, d_v, D]``: ``D`` lies on the lanes,
where ``φ``'s tiles are made, so that a token's read ``Σ_D φ(q)_D S_D`` is a
row times a tile and a lane sum on the vector unit (decode: float32
throughout), and both of a block's products take ``φ`` as it is built, rows
by ``D`` (prefill).

**The chunk kernel.** grid = (K/V heads, blocks), the blocks innermost. A head's
``Sᵀ`` (4.26 MB float32) is the kernel's OUTPUT block, resident in VMEM across
its blocks: read from HBM at the head's first block and written back once, at
its last. A grid step builds ``φ`` of its ``J · block`` query rows (the ``J``
query heads of the group stacked) tile by tile into a VMEM scratch —
``[J · block, D]`` in the operand type, 21 MB at 5 × 256 rows — and reads the
state with ONE product ``[J · block, D] × [d_v, D]ᵀ``; ``φ(k)`` goes into the
same scratch and the state's update is ``[d_v, block] × [block, D]``. First
kernel of the tree whose MXU operand is made from its input by an outer
product inside the kernel. Operands go to the MXU in ``dtype`` (the state is
cast for the read, never for the carry), accumulation and everything else is
float32.

**The step kernel** (decode). grid = (K/V heads,): a head's ``Sᵀ`` comes in
once, every query head of the group reads it (a row of ``φ(q)`` down the
sublanes, a lane sum), the update is written over it, and it goes out once:
68 MB a layer a token, where XLA's two fusions — the read, then the update —
move 102 (``docs/kernels.md``). float32 throughout.

A padded chunk (``n_valid`` of its rows are the prompt's): a padded row's
``log_g`` counts as 0 and its key as 0 — the gate is DATA, so masking by
position alone (Lightning's way) would let the padded rows' gates decay the
state — and ``(S, Z)`` come back as row ``n_valid − 1`` left them
(``llm_model.chunked_prefill``'s contract for a recurrent leaf).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .selective_scan import _form

BLOCK = 256     # rows a block of the chunk form (docs/kernels.md: the sweep)
_HIGHEST = jax.lax.Precision.HIGHEST
_VMEM_LIMIT_BYTES = 100 * 1024 * 1024
_ROOT2 = math.sqrt(2.0)
_NT = (((1,), (1,)), ((), ()))      # [m,k] × [n,k]ᵀ


def tiles(d: int) -> int:
    """Lane tiles ``φ`` of a ``d``-wide head is held in."""
    return d // 2 + 1


def width(d: int) -> int:
    """``D`` as held: whole tiles (8320 at 128; the exact count is 8256)."""
    return d * tiles(d)


def phi(a):
    """``φ`` as the state holds it: ``a`` [..., d] → [..., D], tile ``r`` is
    ``w_r · a ⊙ roll(a, r)``."""
    d = a.shape[-1]
    half = d // 2
    return jnp.concatenate(
        [a * a] + [_ROOT2 * a * jnp.roll(a, r, -1) for r in range(1, half)]
        + [a * jnp.roll(a, half, -1)], axis=-1)


def phi_exact(a):
    """``φ`` as it is written down: the ``d`` squares, then ``√2 a_i a_j`` for
    ``i < j`` — ``d(d+1)/2`` columns. What :func:`phi` is held equal to; no
    program reads it."""
    d = a.shape[-1]
    i, j = jnp.triu_indices(d, 1)
    return jnp.concatenate([a * a, _ROOT2 * a[..., i] * a[..., j]], axis=-1)


def _step_kernel(s_ref, phiq_ref, phik_ref, v_ref, g_ref, out_ref, num_ref):
    S = s_ref[0]                                             # [d_v, D]
    for j in range(phiq_ref.shape[1]):
        num_ref[0, :, j:j + 1] = jnp.sum(S * phiq_ref[0, j:j + 1, :], axis=1,
                                         keepdims=True)
    out_ref[0] = g_ref[0] * S + v_ref[0] * phik_ref[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def retention_read_update(S, phiq, phik, v, g, interpret: bool = False):
    """The Pallas form of :func:`retention_step`'s pass over the state:
    ``(g S + v φ(k)ᵀ, Σ_D φ(q)_D S_D [G, d_v, J])`` — the read takes the OLD
    state. ``S`` [G, d_v, D] is updated where it lies."""
    G, dv, D = S.shape
    J = phiq.shape[1]
    state = pl.BlockSpec((1, dv, D), lambda i: (i, 0, 0))
    return pl.pallas_call(
        _step_kernel, grid=(G,),
        in_specs=[state, pl.BlockSpec((1, J, D), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, 1, D), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, dv, 1), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0))],
        out_specs=[state, pl.BlockSpec((1, dv, J), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(S.shape, jnp.float32),
                   jax.ShapeDtypeStruct((G, dv, J), jnp.float32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(S, phiq, phik[:, None, :], v[:, :, None], g[:, None, None])


def retention_step(S, Z, q, k, v, log_g, kernel: str | None = None):
    """One token. ``S`` [G, d_v, D] and ``Z`` [G, d, d] float32; ``q`` [H, d]
    and ``k`` [G, d] divided by ``d^¼``; ``v`` [G, d_v]; ``log_g`` [G] ``≤ 0``.
    Answers ``(S_t, Z_t, o_t [H, d_v])``, float32. ``φ(q)ᵀ S_t = γ φ(q)ᵀ
    S_{t−1} + (q·k)² v`` takes the OLD state, which the update reads too: a
    multiply and a lane sum (five rows a head are no matrix product worth
    the MXU's weight loads). ``kernel`` as :func:`retention_chunk`'s."""
    f32 = jnp.float32
    q, k, v = (x.astype(f32) for x in (q, k, v))
    G, d = k.shape
    g = jnp.exp(log_g.astype(f32))
    qg = q.reshape(G, -1, d)
    pair = jnp.einsum("gjd,gd->gj", qg, k, precision=_HIGHEST) ** 2
    kernel = _form(kernel)
    if kernel == "lax":
        read = (phi(qg)[:, :, None] * S[:, None]).sum(-1)       # [G,J,d_v]
        S = g[:, None, None] * S + v[:, :, None] * phi(k)[:, None]
    else:
        S, read = retention_read_update(S, phi(qg), phi(k), v, g,
                                        interpret=kernel == "interpret")
        read = jnp.swapaxes(read, 1, 2)
    num = g[:, None, None] * read + pair[..., None] * v[:, None]
    den = g[:, None] * jnp.einsum("gjd,gde,gje->gj", qg, Z, qg,
                                  precision=_HIGHEST) + pair
    Z = g[:, None, None] * Z + k[:, :, None] * k[:, None]
    o = num / jnp.where(den > 0, den, 1.0)[..., None]
    return S, Z, o.reshape(q.shape[0], -1)


def _block_lax(carry, xs, dtype):
    """One block of the ``lax`` walk: ``q`` [B,G,J,d], ``k`` [B,G,d], ``v``
    [B,G,d_v], ``c`` [B,G] (the block's running log-gate)."""
    S, Z = carry
    q, k, v, c = xs
    f32 = jnp.float32
    B = q.shape[0]
    i = jnp.arange(B)
    decay = jnp.where((i[:, None] >= i[None, :])[..., None],
                      jnp.exp(jnp.minimum(c[:, None] - c[None, :], 0.0)), 0.0)
    s = jnp.einsum("igjd,kgd->gjik", q.astype(dtype), k.astype(dtype),
                   preferred_element_type=f32)
    A = s * s * jnp.moveaxis(decay, 2, 0)[:, None]              # [G,J,B,B]
    num = jnp.einsum("gjik,kgv->igjv", A.astype(dtype), v.astype(dtype),
                     preferred_element_type=f32)
    den = jnp.moveaxis(A.sum(-1), 2, 0)                         # [B,G,J]
    reach = jnp.exp(c)[:, :, None]
    # the K/V head leads both operands: a plain batched product (the CPU
    # backend has no bfloat16 kernel for the other order)
    far = jnp.einsum("gijD,gvD->gijv",
                     jnp.moveaxis(phi(q), 1, 0).astype(dtype),
                     S.astype(dtype), preferred_element_type=f32)
    num = num + reach[..., None] * jnp.moveaxis(far, 0, 1)
    den = den + reach * jnp.einsum("igjd,gde,igje->igj", q, Z, q,
                                   precision=_HIGHEST)
    w = jnp.exp(c[-1][None] - c)                                # [B,G]
    keep = jnp.exp(c[-1])[:, None, None]
    S = keep * S + jnp.einsum(
        "kgv,kgD->gvD", (v * w[..., None]).astype(dtype),
        phi(k).astype(dtype), preferred_element_type=f32)
    Z = keep * Z + jnp.einsum("kgd,kge->gde", k * w[..., None], k,
                              precision=_HIGHEST)
    return (S, Z), num / jnp.where(den > 0, den, 1.0)[..., None]


def _phi_tile(a, a2, r: int, half: int):
    """Tile ``r`` of ``φ`` from ``a`` [rows, d] and ``a2 = 2^¼ a``, float32:
    one lane rotation, one product."""
    if r == 0:
        return a * a
    if r == half:
        return a * pltpu.roll(a, r, 1)
    return a2 * pltpu.roll(a2, r, 1)


def _chunk_kernel(q_ref, k_ref, v_ref, ccol_ref, crow_ref, s0_ref, z0_ref,
                  o_ref, s_ref, z_ref, phi_ref, *, heads: int, d: int,
                  dv: int, dtype):
    f32 = jnp.float32
    B, n_tiles = k_ref.shape[0], d // 2 + 1
    root = 2.0 ** 0.25

    @pl.when(pl.program_id(1) == 0)
    def _load():
        s_ref[...] = s0_ref[...]
        z_ref[...] = z0_ref[...]

    c_col, c_row = ccol_ref[0], crow_ref[0]                  # [B,1], [1,B]
    # the block's last running log-gate is its least (every gate is ≤ 0)
    c_last = jnp.min(c_row, axis=1, keepdims=True)           # [1,1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
    decay = jnp.where(rows >= cols,
                      jnp.exp(jnp.minimum(c_col - c_row, 0.0)), 0.0)
    reach = jnp.exp(c_col)
    k, v = k_ref[...], v_ref[...]
    k_op, v_op = k.astype(dtype), v.astype(dtype)
    Z0 = z_ref[0]
    # from the blocks before: φ of the group's rows against the state
    for j in range(heads):
        a = q_ref[:, j * d:(j + 1) * d]
        a2 = a * root
        for r in range(n_tiles):
            phi_ref[j * B:(j + 1) * B, r * d:(r + 1) * d] = _phi_tile(
                a, a2, r, d // 2).astype(dtype)
    far = jax.lax.dot_general(phi_ref[...], s_ref[0].astype(dtype), _NT,
                              preferred_element_type=f32)
    for j in range(heads):
        a = q_ref[:, j * d:(j + 1) * d]
        s = jax.lax.dot_general(a.astype(dtype), k_op, _NT,
                                preferred_element_type=f32)
        A = s * s * decay
        num = jnp.dot(A.astype(dtype), v_op, preferred_element_type=f32) \
            + reach * far[j * B:(j + 1) * B]
        den = A.sum(-1, keepdims=True) + reach * (
            jnp.dot(a, Z0, preferred_element_type=f32,
                    precision=_HIGHEST) * a).sum(-1, keepdims=True)
        o_ref[:, j * dv:(j + 1) * dv] = num / jnp.where(den > 0, den, 1.0)
    # the state after the block: φ(k) into the scratch's first rows
    k2 = k * root
    for r in range(n_tiles):
        phi_ref[0:B, r * d:(r + 1) * d] = _phi_tile(
            k, k2, r, d // 2).astype(dtype)
    keep, w = jnp.exp(c_last), jnp.exp(c_last - c_col)
    s_ref[0] = keep * s_ref[0] + jnp.dot(
        (v * w).T.astype(dtype), phi_ref[0:B, :], preferred_element_type=f32)
    z_ref[0] = keep * Z0 + jnp.dot((k * w).T, k, preferred_element_type=f32,
                                   precision=_HIGHEST)


@functools.partial(jax.jit, static_argnames=("heads", "dtype", "block",
                                             "interpret"))
def power_retention(S0, Z0, q, k, v, c, heads: int, dtype, block: int,
                    interpret: bool = False):
    """The Pallas form of :func:`retention_chunk`'s walk. ``q`` [C, H·d],
    ``k`` [C, G·d], ``v`` [C, G·d_v] float32 (a head a column block: no
    operand is laid out again), ``c`` [C, G] every block's own running
    log-gate; ``heads`` query heads a K/V head; ``C % block == 0``."""
    G, dv, D = S0.shape
    d = Z0.shape[1]
    C = q.shape[0]
    kernel = functools.partial(_chunk_kernel, heads=heads, d=d, dv=dv,
                               dtype=jnp.dtype(dtype))
    ct = c.T
    state = pl.BlockSpec((1, dv, D), lambda g, n: (g, 0, 0))
    norm = pl.BlockSpec((1, d, d), lambda g, n: (g, 0, 0))
    o, S, Z = pl.pallas_call(
        kernel, grid=(G, C // block),
        in_specs=[pl.BlockSpec((block, heads * d), lambda g, n: (n, g)),
                  pl.BlockSpec((block, d), lambda g, n: (n, g)),
                  pl.BlockSpec((block, dv), lambda g, n: (n, g)),
                  pl.BlockSpec((1, block, 1), lambda g, n: (g, n, 0)),
                  pl.BlockSpec((1, 1, block), lambda g, n: (g, 0, n)),
                  state, norm],
        out_specs=[pl.BlockSpec((block, heads * dv), lambda g, n: (n, g)),
                   state, norm],
        out_shape=[jax.ShapeDtypeStruct((C, G * heads * dv), jnp.float32),
                   jax.ShapeDtypeStruct(S0.shape, jnp.float32),
                   jax.ShapeDtypeStruct(Z0.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads * block, D), jnp.dtype(dtype))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(q, k, v, ct[:, :, None], ct[:, None, :], S0, Z0)
    return o, S, Z


def retention_chunk(S0, Z0, q, k, v, log_g, n_valid, dtype,
                    block: int = BLOCK, kernel: str | None = None):
    """A chunk of ``C`` rows from ``(S0 [G,d_v,D], Z0 [G,d,d])`` float32.
    ``q`` [C,H,d] and ``k`` [C,G,d] divided by ``d^¼``; ``v`` [C,G,d_v];
    ``log_g`` [C,G]; the first ``n_valid`` rows are real (traced). Answers
    ``(o [C,H,d_v] float32, S, Z)``: ``o``'s rows past ``n_valid`` hold
    nothing anyone reads, the state is the one after row ``n_valid − 1``.
    ``kernel``: ``pallas`` (the default on a TPU), ``interpret`` (the same
    kernel in the Pallas interpreter) or ``lax`` (the default elsewhere)."""
    f32 = jnp.float32
    C, H, d = q.shape
    G, dv = k.shape[1], v.shape[2]
    B = math.gcd(C, block)
    valid = (jnp.arange(C) < n_valid)[:, None]
    log_g = jnp.where(valid, log_g.astype(f32), 0.0)
    k = jnp.where(valid[..., None], k.astype(f32), 0.0)
    q, v = q.astype(f32), v.astype(f32)
    c = jnp.cumsum(log_g.reshape(C // B, B, G), axis=1)
    kernel = _form(kernel)
    if kernel != "lax":
        o, S, Z = power_retention(
            S0, Z0, q.reshape(C, H * d), k.reshape(C, G * d),
            v.reshape(C, G * dv), c.reshape(C, G), heads=H // G,
            dtype=jnp.dtype(dtype).name, block=B,
            interpret=kernel == "interpret")
        return o.reshape(C, H, dv), S, Z
    n = C // B
    (S, Z), o = jax.lax.scan(
        functools.partial(_block_lax, dtype=jnp.dtype(dtype)), (S0, Z0),
        (q.reshape(n, B, G, H // G, d), k.reshape(n, B, G, d),
         v.reshape(n, B, G, dv), c))
    return o.reshape(C, H, dv), S, Z
