"""Sharded tile upscaler — distributed Ultimate-SD-Upscale, TPU-native.

Reference flow (SURVEY §3.3): master seeds an HTTP pull queue of tile IDs;
worker processes pull tile IDs, VAE-encode → ksample → decode each tile,
POST PNGs back; master blends sequentially and re-processes stragglers
(``upscale/modes/static.py``, ``upscale/tile_ops.py``).

TPU-native flow — ONE compiled SPMD program per (image size, spec):
  resize → extract all crops (static origins) → pad tile count to the shard
  multiple → ``shard_map`` img2img over the tile axis (each shard processes
  ``T/n`` tiles; per-tile noise keys derive from the *global* tile index so
  results are identical for any shard count) → feather-mask normalized
  composite. There is no pull queue, no heartbeat, no requeue *inside* the
  program — host-level failure handling lives in ``cluster/`` and operates
  at whole-program granularity (static shapes are what make TPUs fast;
  SURVEY §7 "hard parts" #2).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..diffusion.guidance import cfg_denoiser
from ..diffusion.pipeline import (GenerationSpec, Txt2ImgPipeline,
                                  bind_weights, make_sigma_ladder)
from ..diffusion.samplers import sample
from ..ops.blend import composite_tiles, extract_tiles, feather_mask
from ..ops.resize import upscale_image
from ..utils import constants
from .grid import TileGrid, compute_tile_grid, pad_count_to


@dataclasses.dataclass(frozen=True)
class UpscaleSpec:
    scale: float = 2.0
    tile_w: int = 512
    tile_h: int = 512
    padding: int = 32
    feather: Optional[int] = None     # None → padding
    steps: int = 20
    denoise: float = 0.3
    sampler: str = "euler"
    scheduler: str = "karras"
    guidance_scale: float = 5.0
    resize_method: str = "lanczos3"

    def generation_spec(self) -> GenerationSpec:
        return GenerationSpec(
            steps=self.steps,
            denoise=self.denoise,
            sampler=self.sampler,
            scheduler=self.scheduler,
            guidance_scale=self.guidance_scale,
        )


class TileUpscaler:
    """Drives a ``Txt2ImgPipeline``'s model stack over a sharded tile axis."""

    _CACHE_MAX = 8

    def __init__(self, pipeline: Txt2ImgPipeline):
        self.pipeline = pipeline
        self._fn_cache: dict = {}

    def _cached_upscale_fn(self, mesh: Mesh, image_hw, spec: UpscaleSpec,
                          batch: int, axis: str, with_spatial: bool,
                          with_control: bool = False):
        """Compiled-program cache (same value-keyed discipline as
        ``Txt2ImgPipeline._cached_fn``): dynamic per-image farming calls
        upscale() once per image — without this it would re-trace and
        re-compile the identical program every time."""
        from ..diffusion.pipeline import cached_build

        key = (Txt2ImgPipeline._mesh_cache_key(mesh), tuple(image_hw), spec,
               batch, axis, with_spatial, with_control)
        return cached_build(
            self, key,
            lambda: self.upscale_fn(mesh, tuple(image_hw), spec, batch=batch,
                                    axis=axis, with_spatial=with_spatial,
                                    with_control=with_control),
            self._CACHE_MAX)

    def grid_for(self, image_h: int, image_w: int, spec: UpscaleSpec) -> TileGrid:
        out_h = int(round(image_h * spec.scale))
        out_w = int(round(image_w * spec.scale))
        return compute_tile_grid(out_w, out_h, spec.tile_w, spec.tile_h, spec.padding)

    def _img2img_tiles(self, tiles, key, context, uncond_context, y, uncond_y,
                       spec: UpscaleSpec, sigmas, global_idx,
                       tile_masks=None, hint_tiles=None, weights=None):
        """img2img a [n, ch, cw, C] tile batch on one shard.

        Per-tile noise keys fold in the *global* tile index, so the output
        for tile i never depends on which shard processed it — the property
        that lets host-level requeue re-shard freely (reference analogue:
        tiles carry global IDs through the queue, ``upscale/job_store.py``).

        ``tile_masks`` ([n, ch, cw, 1], optional) is this shard's slice of
        the spatial conditioning map, already cropped per tile with the
        same grid as the image — the engine's analogue of the reference's
        per-tile conditioning crop (``utils/usdu_utils.py`` ``crop_cond``
        at ``:506``): mask 1 = denoise, 0 = keep the source pixels.
        """
        pipe = self.pipeline
        vae = pipe.vae
        n = tiles.shape[0]
        latents = vae.encode(
            tiles * 2.0 - 1.0,
            params=None if weights is None else weights["vae_enc"])

        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(global_idx)
        noise = jax.vmap(
            lambda k, lat: jax.random.normal(k, lat.shape, lat.dtype)
        )(keys, latents)
        noised = latents + noise * sigmas[0]

        gspec = spec.generation_spec()
        bc = lambda a: jnp.broadcast_to(a, (n,) + a.shape[1:])
        if gspec.guidance_scale != 1.0:
            denoise_fn = cfg_denoiser(
                lambda ctx, yy: pipe._denoiser(ctx, yy, hint=hint_tiles,
                                               weights=weights),
                bc(context), bc(uncond_context), gspec.guidance_scale,
                None if y is None else bc(y),
                None if uncond_y is None else bc(uncond_y),
            )
        else:
            denoise_fn = pipe._denoiser(bc(context),
                                        None if y is None else bc(y),
                                        hint=hint_tiles, weights=weights)
        # sampler key uses a sentinel fold well above any global tile index
        x0 = sample(gspec.sampler, denoise_fn, noised, sigmas,
                    key=jax.random.fold_in(key, jnp.uint32(0xFFFFFFFF)))
        out = vae.decode(
            x0, params=None if weights is None else weights["vae_dec"])
        out = jnp.clip(out / 2.0 + 0.5, 0.0, 1.0)
        if tile_masks is not None:
            out = tiles * (1.0 - tile_masks) + out * tile_masks
        return out

    def upscale_fn(self, mesh: Mesh, image_hw: tuple[int, int], spec: UpscaleSpec,
                   batch: int = 1, axis: str = constants.AXIS_DATA,
                   with_spatial: bool = False, with_control: bool = False):
        """Compile the full upscale: (images, key, ctx, unc, y, unc_y
        [, spatial]) → upscaled images [B, H·s, W·s, C].

        With ``with_spatial`` the last argument is a spatial conditioning
        map [B, H·s, W·s, 1] (denoise mask: 1 = regenerate, 0 = keep). It
        is cropped per tile with the image's own grid — seam-free region
        control matching the reference's conditioning-crop semantics
        (``utils/usdu_utils.py:506``, ``utils/crop_model_patch.py:9-114``).
        """
        H, W = image_hw
        grid = self.grid_for(H, W, spec)
        n_shards = mesh.shape[axis]
        total = batch * grid.num_tiles
        padded = pad_count_to(total, n_shards)
        per_shard = padded // n_shards
        sigmas = make_sigma_ladder(spec.generation_spec(), self.pipeline.schedule)
        masks = feather_mask(grid, spec.feather)
        has_y = self.pipeline.unet.config.adm_in_channels > 0
        # control hints live in the hint stem's space (latent-res × 8):
        # the hint grid is the image grid scaled by 8/vae_downscale, so
        # every tile's hint crop aligns exactly with its image crop — the
        # reference's per-tile ControlNet crop (usdu_utils.py:506)
        hf = 8 // self.pipeline.vae.config.downscale if with_control else 1
        hint_grid = grid if hf == 1 else compute_tile_grid(
            grid.image_w * hf, grid.image_h * hf,
            grid.tile_w * hf, grid.tile_h * hf, grid.padding * hf)

        def process_shard(weights, tiles, stiles, htiles, key, context,
                          uncond_context, y, uncond_y):
            # tiles: [per_shard, ch, cw, C] block of this shard
            shard_i = jax.lax.axis_index(axis)
            global_idx = shard_i * per_shard + jnp.arange(per_shard)
            return self._img2img_tiles(
                tiles, key, context, uncond_context,
                y if has_y else None, uncond_y if has_y else None,
                spec, sigmas, global_idx,
                tile_masks=stiles if with_spatial else None,
                hint_tiles=htiles if with_control else None,
                weights=weights,
            )

        sharded = shard_map(
            process_shard,
            mesh=mesh,
            in_specs=(P(),
                      P(axis, None, None, None), P(axis, None, None, None),
                      P(axis, None, None, None),
                      P(), P(None, None, None),
                      P(None, None, None), P(None, None), P(None, None)),
            out_specs=P(axis, None, None, None),
        )

        def tile_and_pad(per_image_fn, arrs):
            stacked = jnp.concatenate(
                [per_image_fn(a) for a in arrs], axis=0)
            if padded > total:
                pad = jnp.zeros((padded - total,) + stacked.shape[1:],
                                stacked.dtype)
                stacked = jnp.concatenate([stacked, pad], axis=0)
            return stacked

        def run(weights, images, key, context, uncond_context, y, uncond_y,
                spatial=None, hint=None):
            up = upscale_image(images, spec.scale, spec.resize_method)
            all_tiles = tile_and_pad(lambda im: extract_tiles(im, grid),
                                     [up[b] for b in range(batch)])
            if with_spatial:
                stiles = tile_and_pad(lambda m: extract_tiles(m, grid),
                                      [spatial[b] for b in range(batch)])
            else:
                stiles = jnp.ones(all_tiles.shape[:3] + (1,), all_tiles.dtype)
            if with_control:
                htiles = tile_and_pad(
                    lambda m: extract_tiles(m, hint_grid),
                    [hint[b] for b in range(batch)])
            else:
                htiles = jnp.zeros(
                    (all_tiles.shape[0], 8, 8, 1), all_tiles.dtype)
            done = sharded(weights, all_tiles, stiles, htiles, key, context,
                           uncond_context, y, uncond_y)
            done = done[:total]
            outs = [
                composite_tiles(
                    done[b * grid.num_tiles:(b + 1) * grid.num_tiles], masks, grid
                )
                for b in range(batch)
            ]
            return jnp.stack(outs, axis=0)

        jitted = jax.jit(run)
        weights = self.pipeline._weights(img2img=True)

        return bind_weights(jitted, weights, mesh=mesh)

    def upscale(
        self,
        mesh: Mesh,
        images: jax.Array,
        spec: UpscaleSpec,
        seed: int,
        context: jax.Array,
        uncond_context: jax.Array,
        y: Optional[jax.Array] = None,
        uncond_y: Optional[jax.Array] = None,
        axis: str = constants.AXIS_DATA,
        spatial_cond: Optional[jax.Array] = None,
        control_hint: Optional[jax.Array] = None,
    ) -> jax.Array:
        """``spatial_cond``: [B, H, W, 1] (input res) or [B, H·s, W·s, 1]
        (output res) region mask, cropped per tile inside the program.
        ``control_hint``: [B, h, w, C] control map for the pipeline's
        ControlNet (``with_control`` clone), cropped per tile in the hint
        stem's space — the reference's per-tile ControlNet crop."""
        B, H, W, _ = images.shape
        with_control = (control_hint is not None
                        and getattr(self.pipeline, "_control", None) is not None)
        fn = self._cached_upscale_fn(mesh, (H, W), spec, batch=B, axis=axis,
                                     with_spatial=spatial_cond is not None,
                                     with_control=with_control)
        adm = self.pipeline.unet.config.adm_in_channels
        if y is None:
            y = jnp.zeros((1, max(adm, 1)), jnp.float32)
        if uncond_y is None:
            uncond_y = jnp.zeros_like(y)
        args = (images, jax.random.key(seed), context, uncond_context, y, uncond_y)
        grid = self.grid_for(H, W, spec)
        if spatial_cond is not None:
            if spatial_cond.shape[1:3] != (grid.image_h, grid.image_w):
                spatial_cond = jax.image.resize(
                    spatial_cond.astype(jnp.float32),
                    (B, grid.image_h, grid.image_w, spatial_cond.shape[-1]),
                    method="bilinear")
        if with_control:
            hb = control_hint.shape[0]
            if hb not in (1, B):
                raise ValueError(
                    f"control hint batch {hb} incompatible with image "
                    f"batch {B} (must be 1 or {B})")
            hfac = 8 // self.pipeline.vae.config.downscale
            target = (grid.image_h * hfac, grid.image_w * hfac)
            if control_hint.shape[1:3] != target:
                # resize per image — never interpolate across the batch dim
                control_hint = jax.image.resize(
                    control_hint.astype(jnp.float32),
                    (hb, *target, control_hint.shape[-1]), method="bilinear")
            if hb == 1 and B > 1:
                control_hint = jnp.broadcast_to(
                    control_hint, (B, *control_hint.shape[1:]))
        # None is an empty pytree under jit; unused trailing inputs cost
        # nothing when the matching with_* flag compiled them out
        return fn(*args, spatial_cond,
                  control_hint if with_control else None)

    # --- cross-host farm support -------------------------------------------

    @staticmethod
    def tiles_per_device_default(tile_w: int, tile_h: int) -> int:
        """Per-device tile batch for the farm's fixed-chunk program.

        Batch-1 tiles under-fill the MXU badly: a 512² tile is a 64²
        latent whose self-attention blocks run at 1024/256 tokens —
        matmuls far below the 128×128 systolic tile at batch 1. Measured
        on the v5e chip (r04, `benchmarks/r04_tpu_usdu.json`): batching
        tiles per dispatch cuts the 4K USDU wall-clock vs the one-tile
        chunks r02 shipped. Memory bounds the batch: activations scale
        with tile area, so the default halves as tiles grow past 512².
        ``CDT_TILES_PER_DEVICE`` overrides.
        """
        from ..utils.constants import TILES_PER_DEVICE

        env = TILES_PER_DEVICE.get()
        if env > 0:
            return env
        if jax.devices()[0].platform == "cpu":
            return 1     # tests/tiny stacks: don't pad tiny jobs 8-wide
        area = tile_w * tile_h
        if area <= 512 * 512:
            return 8
        if area <= 1024 * 1024:
            return 4
        return 1

    def range_plan(
        self,
        mesh: Mesh,
        image: jax.Array,
        spec: UpscaleSpec,
        seed: int,
        context: jax.Array,
        uncond_context: jax.Array,
        y: Optional[jax.Array] = None,
        uncond_y: Optional[jax.Array] = None,
        axis: str = constants.AXIS_DATA,
        spatial_cond: Optional[jax.Array] = None,
        tiles_per_device: Optional[int] = None,
    ) -> "TileRangePlan":
        """Prepare arbitrary-range tile processing for the cross-host farm
        (``cluster/tile_farm.py``): resize + extract all crops once, and
        compile ONE fixed-chunk SPMD program reused for every pulled task.

        Per-tile noise keys fold the *global* tile index exactly as
        ``upscale_fn`` does, so any host processing any range produces the
        same tiles the single-program path would — the shard-count /
        host-assignment invariance that makes requeue safe (the reference
        gets this from tile IDs travelling through its HTTP queue,
        ``upscale/job_store.py:34-80``). Results are also invariant to
        ``tiles_per_device`` (the per-dispatch tile batch) for the same
        reason; it is purely a throughput/memory knob.
        """
        H, W, _ = image.shape
        grid = self.grid_for(H, W, spec)
        n_shards = mesh.shape[axis]
        if tiles_per_device is None:
            tiles_per_device = self.tiles_per_device_default(
                spec.tile_w, spec.tile_h)
        # never compile a chunk wider than the job itself — a 4-tile job
        # on an 8-device host must not pad (and denoise) 60 zero tiles
        per_job = -(-grid.num_tiles // n_shards)
        per_shard = max(1, min(tiles_per_device, per_job))
        chunk = n_shards * per_shard
        sigmas = make_sigma_ladder(spec.generation_spec(), self.pipeline.schedule)
        has_y = self.pipeline.unet.config.adm_in_channels > 0
        if y is None:
            adm = self.pipeline.unet.config.adm_in_channels
            y = jnp.zeros((1, max(adm, 1)), jnp.float32)
        if uncond_y is None:
            uncond_y = jnp.zeros_like(y)

        @jax.jit
        def prepare(img):
            up = upscale_image(img[None], spec.scale, spec.resize_method)[0]
            return extract_tiles(up, grid)

        all_tiles = prepare(image)              # [T, ch, cw, C]
        use_spatial = spatial_cond is not None
        if use_spatial:
            # same per-tile crop as the image (reference crop_cond
            # semantics, usdu_utils.py:506), resized to the output grid
            smap = jnp.asarray(spatial_cond, jnp.float32)
            if smap.ndim == 2:
                smap = smap[..., None]
            if smap.shape[:2] != (grid.image_h, grid.image_w):
                smap = jax.image.resize(
                    smap, (grid.image_h, grid.image_w, smap.shape[-1]),
                    method="bilinear")
            all_stiles = extract_tiles(smap, grid)
        else:
            all_stiles = jnp.ones(all_tiles.shape[:3] + (1,), all_tiles.dtype)

        def process_shard(weights, tiles, stiles, start, key, ctx, unc,
                          yy, uyy):
            shard_i = jax.lax.axis_index(axis)
            global_idx = start + shard_i * per_shard + jnp.arange(per_shard)
            return self._img2img_tiles(
                tiles, key, ctx, unc,
                yy if has_y else None, uyy if has_y else None,
                spec, sigmas, global_idx,
                tile_masks=stiles if use_spatial else None,
                weights=weights,
            )

        jitted = jax.jit(shard_map(
            process_shard,
            mesh=mesh,
            in_specs=(P(),
                      P(axis, None, None, None), P(axis, None, None, None),
                      P(), P(), P(None, None, None),
                      P(None, None, None), P(None, None), P(None, None)),
            out_specs=P(axis, None, None, None),
        ))
        sharded = bind_weights(jitted, self.pipeline._weights(img2img=True),
                               mesh=mesh)
        key = jax.random.key(seed)

        def run_one(start: int, end: int):
            seg = all_tiles[start:end]
            sseg = all_stiles[start:end]
            if seg.shape[0] < chunk:
                pad = jnp.zeros((chunk - seg.shape[0],) + seg.shape[1:],
                                seg.dtype)
                seg = jnp.concatenate([seg, pad], axis=0)
                spad = jnp.ones((chunk - sseg.shape[0],) + sseg.shape[1:],
                                sseg.dtype)
                sseg = jnp.concatenate([sseg, spad], axis=0)
            return sharded(seg, sseg, jnp.int32(start), key, context,
                           uncond_context, y, uncond_y)[: end - start]

        _empty_spec: list = []   # cached eval_shape result for empty ranges

        def flops_per_dispatch() -> float:
            """Analytic matmul+conv FLOPs of ONE fixed-chunk dispatch,
            per-shard body counted once (= one chip's work) — the MFU
            accounting hook for the USDU bench (r04 VERDICT weak #1:
            only SDXL txt2img carried an mfu field)."""
            from ..utils.flops import estimate_flops

            seg = jax.ShapeDtypeStruct(
                (chunk,) + tuple(all_tiles.shape[1:]), all_tiles.dtype)
            sseg = jax.ShapeDtypeStruct(
                (chunk,) + tuple(all_stiles.shape[1:]), all_stiles.dtype)
            return estimate_flops(sharded, seg, sseg, jnp.int32(0), key,
                                  context, uncond_context, y, uncond_y)

        def run_range(start: int, end: int):
            """Process [start, end) with the compiled fixed-chunk program.

            Ranges wider than this host's chunk loop over sub-chunks, so
            a farm task sized by the MASTER's chunk still runs correctly
            on a worker whose own chunk differs (fewer local devices, a
            different ``CDT_TILES_PER_DEVICE``) — chunk mismatch costs
            only padding, never correctness. All sub-chunks are
            dispatched before any result is fetched: JAX dispatch is
            async, so chunk i's device→host transfer overlaps chunk
            i+1's compute."""
            import numpy as np

            if start >= end:
                # zero-width task (e.g. a requeue race handed out an
                # empty range): no-op instead of crashing the worker on
                # np.concatenate([]) — shape/dtype from the compiled
                # program's own output spec so the two paths can't
                # drift. The abstract trace is cached after the first
                # empty call (and never paid by plans that only run
                # real ranges).
                if not _empty_spec:
                    seg = jax.ShapeDtypeStruct(
                        (chunk,) + tuple(all_tiles.shape[1:]),
                        all_tiles.dtype)
                    sseg = jax.ShapeDtypeStruct(
                        (chunk,) + tuple(all_stiles.shape[1:]),
                        all_stiles.dtype)
                    _empty_spec.append(jax.eval_shape(
                        sharded, seg, sseg, jnp.int32(0), key, context,
                        uncond_context, y, uncond_y))
                out = _empty_spec[0]
                return np.zeros((0,) + tuple(out.shape[1:]),
                                dtype=out.dtype)
            outs = [run_one(s, min(s + chunk, end))
                    for s in range(start, end, chunk)]       # all async
            return np.concatenate([np.asarray(o) for o in outs], axis=0)

        def source_range(start: int, end: int):
            import numpy as np

            return np.asarray(all_tiles[start:end], np.float32)

        return TileRangePlan(grid=grid, chunk=chunk, run_range=run_range,
                             feather=spec.feather,
                             flops_per_dispatch=flops_per_dispatch,
                             source_range=source_range)

    def composite(self, tiles, plan: "TileRangePlan"):
        """Blend a complete [T, ch, cw, C] tile set into the output image
        (same normalized feather composite the single-program path uses)."""
        masks = feather_mask(plan.grid, plan.feather)
        return composite_tiles(jnp.asarray(tiles), masks, plan.grid)


@dataclasses.dataclass
class TileRangePlan:
    """Host-side handle the farm drivers use: tile geometry + the compiled
    fixed-chunk range processor."""

    grid: TileGrid
    chunk: int
    run_range: "callable"
    feather: Optional[int]
    flops_per_dispatch: Optional["callable"] = None
    # degraded fallback for dead-lettered farm tasks: the plain-resized
    # source crops, no diffusion (cluster/tile_farm.assemble_tiles)
    source_range: Optional["callable"] = None

    @property
    def num_tiles(self) -> int:
        return self.grid.num_tiles
