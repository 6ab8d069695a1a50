"""SPMD tiled application of a learned upscaler (RRDBNet class).

The reference gets this from ComfyUI's ``ImageUpscaleWithModel`` (tiled
torch loop on one GPU, feeding ``upscaled_image`` into USDU —
``/root/reference/nodes/distributed_upscale.py:84-91``). TPU-first
redesign: the tile batch is sharded over the mesh's data axis inside one
``shard_map`` program — every chip convolves its tile block on the MXU,
and the feather-normalized composite runs as XLA scatter ops. Because a
k× upscale scales the whole grid geometry linearly, the output composite
reuses the same static-grid machinery at k× coordinates.

Compiled programs are cached by value (mesh/config/shape/tiling — same
discipline as ``TileUpscaler._cached_upscale_fn``) with params passed as
arguments, so repeated node executions re-trace nothing and weights are
never baked into executables as constants.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..diffusion.pipeline import Txt2ImgPipeline
from ..ops.blend import composite_tiles, extract_tiles, feather_mask
from ..utils import constants
from .grid import compute_tile_grid, pad_count_to

_CACHE_MAX = 8
_fn_cache: dict = {}


def _build_fn(mesh: Mesh, model, config, in_shape, tile: int, padding: int,
              axis: str):
    B, H, W, _ = in_shape
    s = config.scale
    grid = compute_tile_grid(W, H, tile, tile, padding)
    out_grid = compute_tile_grid(W * s, H * s, tile * s, tile * s,
                                 padding * s)
    assert out_grid.num_tiles == grid.num_tiles
    masks = feather_mask(out_grid, feather=max(1, (padding * s) // 2))

    n_shards = mesh.shape[axis]
    total = B * grid.num_tiles
    padded = pad_count_to(total, n_shards)

    sharded = shard_map(
        lambda params, tiles: model.apply(params, tiles),
        mesh=mesh,
        in_specs=(P(), P(axis, None, None, None)),
        out_specs=P(axis, None, None, None),
    )

    def run(params, images):
        all_tiles = jnp.concatenate(
            [extract_tiles(images[b], grid) for b in range(B)], axis=0)
        if padded > total:
            pad = jnp.zeros((padded - total,) + all_tiles.shape[1:],
                            all_tiles.dtype)
            all_tiles = jnp.concatenate([all_tiles, pad], axis=0)
        done = sharded(params, all_tiles)[:total]
        outs = [
            composite_tiles(
                done[b * grid.num_tiles:(b + 1) * grid.num_tiles],
                masks, out_grid)
            for b in range(B)
        ]
        return jnp.stack(outs, axis=0)

    return jax.jit(run)


def tiled_model_upscale(
    mesh: Mesh,
    bundle,                      # models.upscaler.UpscalerBundle
    images: jax.Array,           # [B, H, W, C] in [0,1]
    tile: int = 256,
    padding: int = 16,
    axis: str = constants.AXIS_DATA,
) -> jax.Array:
    """Upscale ``images`` by the bundle's scale, tile-sharded over ``axis``.

    Deterministic and shard-count invariant: tiles are keyed by global
    index and composited in grid order regardless of which chip computed
    them.
    """
    B, H, W, _ = images.shape
    s = bundle.scale
    # x2/x1 checkpoints run a pixel-unshuffle stem: every crop dimension
    # must divide by the unshuffle factor, so align the geometry and
    # edge-pad the image, cropping the output back at the end
    f = {4: 1, 2: 2, 1: 4}.get(s, 1)
    tile = max(f, (tile // f) * f)
    padding = (padding // f) * f
    pad_h = (-H) % f
    pad_w = (-W) % f
    if pad_h or pad_w:
        images = jnp.pad(images, ((0, 0), (0, pad_h), (0, pad_w), (0, 0)),
                         mode="edge")

    from ..diffusion.pipeline import cached_build

    key = (Txt2ImgPipeline._mesh_cache_key(mesh), bundle.model.config,
           images.shape, tile, padding, axis)
    fn = cached_build(
        _fn_cache, key,
        lambda: _build_fn(mesh, bundle.model, bundle.model.config,
                          images.shape, tile, padding, axis),
        _CACHE_MAX)
    return fn(bundle.params, images)[:, :H * s, :W * s, :]
