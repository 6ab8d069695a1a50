"""The grouped expert product as ONE kernel: tiles of rows in expert order
against the expert each tile belongs to, the experts' matrices STREAMED.

``expert_share.held_part_grouped`` walks its tiles in an XLA loop: each
iteration slices one expert's three matrices out of the stack (a copy of
9.4 MB at 2048 × 768 before the products read them again), and nothing of
the next tile's weights moves while this tile multiplies. Where a chip holds
so many experts that every tile meets another expert — all 128 of a router,
256 rows each a chunk — that loop is most of the layer (PERF.md §6, PR 53:
63 µs a tile where the products need 12 and the matrices' bytes 11.5).

Here the rows are gathered ONCE into expert order (each expert's rows padded
to whole tiles) and a Pallas grid walks the tiles: the tile → expert table is
prefetched as scalars and the weights' block index follows it, so the
pipeline fetches the next expert's ``[D, 2F]`` and ``[F, D]`` under the
current tile's products, reads them where they lie, and re-reads nothing
while consecutive tiles stay with one expert. Tiles past the last real one
(the grid is the static bound ``rows · per_token / tile + experts``) stay on
the last block and compute nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _tile_kernel(expert_ref, n_ref, x_ref, gu_ref, down_ref, o_ref, *, act,
                 precision):
    @pl.when(pl.program_id(0) < n_ref[0])
    def _step():
        gu = jnp.dot(x_ref[...], gu_ref[0],
                     preferred_element_type=jnp.float32, precision=precision)
        half = gu.shape[1] // 2
        h = act(gu[:, :half], gu[:, half:], None).astype(x_ref.dtype)
        o_ref[...] = jnp.dot(h, down_ref[0],
                             preferred_element_type=jnp.float32,
                             precision=precision)


@functools.partial(jax.jit, static_argnames=("tile", "act", "interpret"))
def expert_tiles_mlp(rows, tile_expert, n_tiles, e_gu, e_down, tile: int,
                     act, interpret: bool):
    """``rows`` [N·tile, D] in expert order (``dtype`` of the weights),
    ``tile_expert`` [N] int32 the expert of each tile (any valid expert past
    ``n_tiles``), ``n_tiles`` how many tiles are real (traced), ``e_gu``
    [E, D, 2F], ``e_down`` [E, F, D]. Answers ``act(x W_g, x W_u) W_down`` a
    row, [N·tile, D] float32; rows of tiles at or past ``n_tiles`` hold
    anything."""
    n = tile_expert.shape[0]
    D = rows.shape[1]
    precision = (jax.lax.Precision.HIGHEST if rows.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    kernel = functools.partial(_tile_kernel, act=act, precision=precision)

    def at(t, n_ref):
        return jnp.minimum(t, n_ref[0] - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n,),
        in_specs=[
            pl.BlockSpec((tile, D), lambda t, e, n_: (at(t, n_), 0)),
            pl.BlockSpec((1,) + e_gu.shape[1:],
                         lambda t, e, n_: (e[at(t, n_)], 0, 0)),
            pl.BlockSpec((1,) + e_down.shape[1:],
                         lambda t, e, n_: (e[at(t, n_)], 0, 0))],
        out_specs=pl.BlockSpec((tile, D), lambda t, e, n_: (at(t, n_), 0)))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(rows.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(tile_expert.astype(jnp.int32),
      jnp.reshape(jnp.maximum(n_tiles, 1), (1,)).astype(jnp.int32), rows,
      e_gu, e_down)
