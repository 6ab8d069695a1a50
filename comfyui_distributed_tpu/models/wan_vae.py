"""WAN-geometry 3D causal video VAE (flax).

The reference free-rides on ComfyUI for video VAEs (SURVEY "external
substrate"); the WAN family compresses video 4× in time and 8× in space
through a *causal* 3D conv stack, which is what makes its 4n+1 frame
rule work: ``T`` pixel frames ↔ ``(T-1)/4 + 1`` latent frames, with the
first frame compressed alone (so single images are valid 1-frame
videos). This module implements that geometry TPU-natively:

- causal 3D convs (time padded front-only with edge replication — no
  future leakage, so prefix decodes are consistent with full decodes);
- channel-RMS norms, SiLU residual blocks, single-head spatial
  attention in the bottleneck;
- temporal downsample = stride-2 causal conv (``ceil(T/2)``); temporal
  upsample = per-frame frame-pair expansion minus the leading duplicate
  (``2T-1``) — exact inverses over the 4n+1 family.

The ~4× shorter latent frame axis is a direct transformer-sequence
reduction for ``WanModel`` — the dominant video-generation cost.

Weight portability for published WAN VAE checkpoints is **not yet
wired** (the official stack's streaming-cache forward has extra
chunk-boundary semantics); the architecture is init-compatible with the
geometry and ships behind the same ``encode``/``decode`` interface as
``AutoencoderKL`` so it slots into ``VideoPipeline`` unchanged.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn


@dataclasses.dataclass(frozen=True)
class WanVAEConfig:
    in_channels: int = 3
    latent_channels: int = 16
    base_dim: int = 96
    dim_mult: tuple[int, ...] = (1, 2, 4, 4)
    num_res_blocks: int = 2
    # one entry per downsample transition (len(dim_mult) - 1): True adds
    # stride-2 temporal compression to that spatial downsample
    temporal_downsample: tuple[bool, ...] = (False, True, True)
    scaling_factor: float = 1.0
    dtype: str = "float32"

    @classmethod
    def wan(cls, dtype: str = "bfloat16") -> "WanVAEConfig":
        # bf16 compute: a 33×480×832 decode holds multiple ~[33,480,832,96]
        # activation buffers — f32 needs >31 GB HBM (observed OOM on v5e),
        # bf16 halves it; combined with decode_tiled it fits one chip
        return cls(dtype=dtype)

    @classmethod
    def tiny(cls, **kw) -> "WanVAEConfig":
        base = dict(latent_channels=4, base_dim=16, dim_mult=(1, 2),
                    num_res_blocks=1, temporal_downsample=(True,))
        base.update(kw)
        return cls(**base)

    @property
    def downscale(self) -> int:
        """Spatial compression (one stride-2 per dim transition)."""
        return 2 ** (len(self.dim_mult) - 1)

    @property
    def temporal_downscale(self) -> int:
        return 2 ** sum(self.temporal_downsample)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    def latent_frames(self, frames: int) -> int:
        """4n+1 pixel frames → n+1 latent frames (causal: first alone)."""
        return (frames - 1) // self.temporal_downscale + 1

    def pixel_frames(self, latent_frames: int) -> int:
        return (latent_frames - 1) * self.temporal_downscale + 1


def _tile_starts(full: int, t: int, step: int) -> list[int]:
    """Origin-anchored tile starts with the last start clamped to
    ``full - t`` so the final tile never runs past the edge."""
    if full <= t:
        return [0]
    out = list(range(0, full - t, step)) + [full - t]
    return sorted(set(out))


def _pair_feathers(starts_list: list[int], t: int):
    """Per-tile (lo, hi) feather widths in latent units: each side
    feathers over the ACTUAL overlap with its neighbor. The last start is
    clamped (``_tile_starts``), so its overlap with the previous tile can
    exceed the nominal ``overlap`` — feathering only the nominal width
    would leave a weight-1/weight-1 band that hard-averages (visible seam
    at the final row/column)."""
    ovs = [starts_list[i - 1] + t - starts_list[i]
           for i in range(1, len(starts_list))]
    return [0] + ovs, ovs + [0]


def _axis_ramp(n_lat: int, lo_o: int, hi_o: int, *, scale: int) -> np.ndarray:
    """Per-pixel weight along one axis of a decoded tile; ramps multiply
    so an extra-wide lo/hi pair composes instead of one overwriting the
    other."""
    n = n_lat * scale
    wgt = np.ones((n,), np.float32)
    o = min(lo_o, n_lat) * scale
    if o:
        wgt[:o] *= np.linspace(1.0 / (o + 1), 1.0, o, dtype=np.float32)
    o = min(hi_o, n_lat) * scale
    if o:                  # guard: wgt[-0:] is the WHOLE array
        wgt[-o:] *= np.linspace(1.0, 1.0 / (o + 1), o, dtype=np.float32)
    return wgt


def _pad_time_causal(x: jax.Array, n: int) -> jax.Array:
    """Front-pad the frame axis with ``n`` copies of the first frame."""
    if n == 0:
        return x
    first = jnp.repeat(x[:, :1], n, axis=1)
    return jnp.concatenate([first, x], axis=1)


class CausalConv3d(nn.Module):
    """[B,T,H,W,C] conv: causal (front-padded) in time, SAME in space."""

    features: int
    kernel: tuple[int, int, int] = (3, 3, 3)
    time_stride: int = 1
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kt, kh, kw = self.kernel
        x = _pad_time_causal(x, kt - 1)
        return nn.Conv(
            self.features, self.kernel,
            strides=(self.time_stride, 1, 1),
            padding=[(0, 0), (kh // 2, kh // 2), (kw // 2, kw // 2)],
            dtype=self.dtype, name="conv")(x)


class ChannelRMSNorm(nn.Module):
    """L2-normalize the channel axis × √C × learned gamma (WAN's norm)."""

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        c = x.shape[-1]
        g = self.param("gamma", nn.initializers.ones, (c,))
        xf = x.astype(jnp.float32)
        n = xf * jax.lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + 1e-12)
        return (n * (c ** 0.5)).astype(x.dtype) * g.astype(x.dtype)


class ResBlock3d(nn.Module):
    features: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        h = ChannelRMSNorm(name="norm1")(x)
        h = CausalConv3d(self.features, dtype=self.dtype,
                         name="conv1")(nn.silu(h))
        h = ChannelRMSNorm(name="norm2")(h)
        h = CausalConv3d(self.features, dtype=self.dtype,
                         name="conv2")(nn.silu(h))
        if x.shape[-1] != self.features:
            x = nn.Dense(self.features, dtype=self.dtype, name="skip")(x)
        return x + h


class SpatialAttention(nn.Module):
    """Single-head per-frame spatial self-attention (bottleneck only)."""

    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        B, T, H, W, C = x.shape
        h = ChannelRMSNorm(name="norm")(x).reshape(B * T, H * W, C)
        qkv = nn.Dense(C * 3, dtype=self.dtype, name="qkv")(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        s = jnp.einsum("bqc,bkc->bqk", q, k) / (C ** 0.5)
        out = jnp.einsum("bqk,bkc->bqc", jax.nn.softmax(s, axis=-1), v)
        out = nn.Dense(C, dtype=self.dtype, name="proj")(out)
        return x + out.reshape(B, T, H, W, C)


class _Downsample(nn.Module):
    features: int
    temporal: bool
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        B, T, H, W, C = x.shape
        # spatial: stride-2 conv per frame (zero-pad bottom/right, WAN style)
        h = x.reshape(B * T, H, W, C)
        h = jnp.pad(h, ((0, 0), (0, 1), (0, 1), (0, 0)))
        h = nn.Conv(self.features, (3, 3), strides=(2, 2), padding="VALID",
                    dtype=self.dtype, name="space")(h)
        h = h.reshape(B, T, H // 2, W // 2, self.features)
        if self.temporal:
            # stride-2 causal conv: T → ceil(T/2), frame 0 kept alone
            h = _pad_time_causal(h, 1)
            h = nn.Conv(self.features, (2, 1, 1), strides=(2, 1, 1),
                        padding="VALID", dtype=self.dtype, name="time")(h)
        return h


class _Upsample(nn.Module):
    features: int
    temporal: bool
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        if self.temporal:
            # every latent frame expands to a frame pair; the leading
            # duplicate is dropped: T → 2T-1 (inverse of ceil(T/2))
            B, T, H, W, C = x.shape
            h = CausalConv3d(C * 2, (3, 1, 1), dtype=self.dtype,
                             name="time")(x)
            h = jnp.moveaxis(h.reshape(B, T, H, W, 2, C), 4, 2)
            x = h.reshape(B, 2 * T, H, W, C)[:, 1:]
        B, T, H, W, C = x.shape
        h = x.reshape(B * T, H, W, C)
        h = jax.image.resize(h, (B * T, H * 2, W * 2, C), "nearest")
        h = nn.Conv(self.features, (3, 3), padding="SAME", dtype=self.dtype,
                    name="space")(h)
        return h.reshape(B, T, H * 2, W * 2, self.features)


class WanVAEEncoder(nn.Module):
    config: WanVAEConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        dt = cfg.jnp_dtype
        dims = [cfg.base_dim * m for m in cfg.dim_mult]
        h = CausalConv3d(dims[0], dtype=dt, name="conv_in")(x.astype(dt))
        for level, dim in enumerate(dims):
            for i in range(cfg.num_res_blocks):
                h = ResBlock3d(dim, dt, name=f"down_{level}_res_{i}")(h)
            if level < len(dims) - 1:
                h = _Downsample(dims[level + 1],
                                cfg.temporal_downsample[level], dt,
                                name=f"down_{level}_ds")(h)
        h = ResBlock3d(dims[-1], dt, name="mid_res1")(h)
        h = SpatialAttention(dt, name="mid_attn")(h)
        h = ResBlock3d(dims[-1], dt, name="mid_res2")(h)
        h = ChannelRMSNorm(name="norm_out")(h)
        h = CausalConv3d(cfg.latent_channels * 2, dtype=dt,
                         name="conv_out")(nn.silu(h))
        return nn.Dense(cfg.latent_channels * 2, dtype=jnp.float32,
                        name="quant")(h.astype(jnp.float32))


class WanVAEDecoder(nn.Module):
    """``stage`` (static) splits the decoder for tiled decode:

    - ``"head"``: post-quant → conv_in → mid blocks (incl. the GLOBAL
      SpatialAttention) at latent resolution — cheap, always whole-frame,
      so tiling never changes the attention statistics;
    - ``"tail"``: the upsampling stack + output conv — the memory-heavy
      part (activations grow ×downscale² per level), safe to run on
      spatial tiles because every op is a local conv;
    - ``"all"``: both (the normal whole-frame decode; init uses this so
      the param tree is identical regardless of how apply is staged).
    """

    config: WanVAEConfig

    @nn.compact
    def __call__(self, z: jax.Array, stage: str = "all") -> jax.Array:
        cfg = self.config
        dt = cfg.jnp_dtype
        dims = [cfg.base_dim * m for m in cfg.dim_mult]
        h = z
        if stage in ("all", "head"):
            zq = nn.Dense(cfg.latent_channels, dtype=jnp.float32,
                          name="post_quant")(z.astype(jnp.float32))
            h = CausalConv3d(dims[-1], dtype=dt, name="conv_in")(
                zq.astype(dt))
            h = ResBlock3d(dims[-1], dt, name="mid_res1")(h)
            h = SpatialAttention(dt, name="mid_attn")(h)
            h = ResBlock3d(dims[-1], dt, name="mid_res2")(h)
            if stage == "head":
                return h
        h = h.astype(dt)
        for level in reversed(range(len(dims))):
            for i in range(cfg.num_res_blocks + 1):
                h = ResBlock3d(dims[level], dt,
                               name=f"up_{level}_res_{i}")(h)
            if level > 0:
                h = _Upsample(dims[level - 1],
                              cfg.temporal_downsample[level - 1], dt,
                              name=f"up_{level}_us")(h)
        h = ChannelRMSNorm(name="norm_out")(h)
        h = CausalConv3d(cfg.in_channels, dtype=dt,
                         name="conv_out")(nn.silu(h))
        return h.astype(jnp.float32)


class WanVAE3D:
    """Host wrapper matching ``AutoencoderKL``'s interface over video
    tensors [B,T,H,W,C] — ``VideoPipeline`` drives either transparently."""

    def __init__(self, config: WanVAEConfig, enc_params=None,
                 dec_params=None):
        self.config = config
        self.encoder = WanVAEEncoder(config)
        self.decoder = WanVAEDecoder(config)
        self.enc_params = enc_params
        self.dec_params = dec_params
        # jit once (params are traced args, so weight swaps don't stale it);
        # inside an outer jit these inline, standalone calls compile once
        self._enc_fn = jax.jit(self.encoder.apply)
        self._dec_fn = jax.jit(self.decoder.apply,
                               static_argnames=("stage",))

    def init(self, rng: jax.Array, frames: int = 5,
             image_hw: tuple[int, int] = (32, 32)) -> "WanVAE3D":
        from .draw import draw_params

        cfg = self.config
        H, W = image_hw
        k1, k2 = jax.random.split(rng)
        vid = jnp.zeros((1, frames, H, W, cfg.in_channels))
        lat = jnp.zeros((1, cfg.latent_frames(frames), H // cfg.downscale,
                         W // cfg.downscale, cfg.latent_channels))
        self.enc_params = draw_params(self.encoder, k1, vid)
        self.dec_params = draw_params(self.decoder, k2, lat)
        return self

    def encode(self, video: jax.Array, params=None) -> jax.Array:
        """[B,T,H,W,C] → latents; a rank-4 [B,H,W,C] image is treated as
        a 1-frame video (the causal design's single-image case) and the
        frame axis squeezed back out. ``params`` overrides the bundled
        encoder params (pipelines pass weights as jit arguments)."""
        single = video.ndim == 4
        if single:
            video = video[:, None]
        moments = self._enc_fn(
            self.enc_params if params is None else params, video)
        mean, _ = jnp.split(moments, 2, axis=-1)
        lat = mean * self.config.scaling_factor
        return lat[:, 0] if single else lat

    def decode(self, latents: jax.Array, params=None) -> jax.Array:
        single = latents.ndim == 4
        if single:
            latents = latents[:, None]
        out = self._dec_fn(self.dec_params if params is None else params,
                           latents / self.config.scaling_factor)
        return out[:, 0] if single else out

    def decode_tiled(self, latents: jax.Array, params=None,
                     tile: int = 32, overlap: int = 8) -> jax.Array:
        """Spatially-tiled decode: bound decoder activation memory for
        large clips (the ComfyUI analogue is ``VAEDecodeTiled``; the
        reference free-rides on it for big decodes — a 480p whole-frame
        f32 decode needs >31 GB of activations on one chip).

        Two stages (``WanVAEDecoder.stage``): the mid blocks — including
        the decoder's GLOBAL spatial attention — run whole-frame at cheap
        latent resolution, so tiling never changes attention statistics;
        only the memory-heavy local-conv upsampling stack runs per tile.
        Tiles overlap and blend with a linear feather; residual error is
        confined to conv-halo bands at tile seams (same approximation
        contract as ComfyUI's VAEDecodeTiled). The temporal axis stays
        whole, so causal state is exact. Tile positions are static, so
        this traces cleanly inside an outer jit, where XLA schedules the
        tile decodes sequentially — exactly the memory bound we want.
        """
        B, f, h, w, c = latents.shape
        if h <= tile and w <= tile:
            return self.decode(latents, params=params)
        if overlap >= tile:
            # env-configurable (CDT_VAE_TILE*) — fail fast with a clear
            # message instead of a trace-time shape error / step-1 blowup
            raise ValueError(
                f"vae tile overlap ({overlap}) must be smaller than the "
                f"tile ({tile})")
        p = self.dec_params if params is None else params
        head = self._dec_fn(p, latents / self.config.scaling_factor,
                            stage="head")          # [B,f,h,w,dims[-1]]
        s = self.config.downscale
        step = max(1, tile - overlap)
        # per-axis tile size: an axis smaller than `tile` is untiled, so
        # every extracted tile has identical shape — the lax.map below
        # requires it
        th, tw = min(tile, h), min(tile, w)

        ys = _tile_starts(h, th, step)
        xs = _tile_starts(w, tw, step)
        ylo, yhi = _pair_feathers(ys, th)
        xlo, xhi = _pair_feathers(xs, tw)
        ramp = functools.partial(_axis_ramp, scale=s)
        positions = [(y0, x0) for y0 in ys for x0 in xs]
        pos_feather = [(ylo[iy], yhi[iy], xlo[ix], xhi[ix])
                       for iy in range(len(ys)) for ix in range(len(xs))]
        tiles_in = jnp.stack(
            [head[:, :, y0:y0 + th, x0:x0 + tw, :] for y0, x0 in positions])

        # lax.map = hard sequentialization: unrolled tile decodes leave
        # XLA free to interleave them, and their remat/norm temporaries
        # then coexist (observed: 12 unrolled 480p tiles → 33 GB HBM).
        # Mapped, one tile's activations live at a time.
        tiles_out = jax.lax.map(
            lambda ht: self._dec_fn(p, ht, stage="tail").astype(
                jnp.float32),
            tiles_in)                      # [N,B,F,th·s,tw·s,3]

        F_out = (f - 1) * self.config.temporal_downscale + 1
        acc = jnp.zeros((B, F_out, h * s, w * s, self.config.in_channels),
                        jnp.float32)
        wsum = jnp.zeros((h * s, w * s, 1), jnp.float32)
        for i, (y0, x0) in enumerate(positions):
            f_ylo, f_yhi, f_xlo, f_xhi = pos_feather[i]
            wy = ramp(th, f_ylo, f_yhi)
            wx = ramp(tw, f_xlo, f_xhi)
            wgt = jnp.asarray(wy[:, None, None] * wx[None, :, None])
            acc = acc.at[:, :, y0 * s:(y0 + th) * s,
                         x0 * s:(x0 + tw) * s, :].add(tiles_out[i] * wgt)
            wsum = wsum.at[y0 * s:(y0 + th) * s,
                           x0 * s:(x0 + tw) * s, :].add(wgt)
        return acc / wsum
