"""AutoencoderKL (latent ↔ pixel codec) in flax.

Supplies the VAEEncode/VAEDecode capability the reference obtains from
ComfyUI (invoked per tile at ``upscale/tile_ops.py:157-287``). Standard
KL-autoencoder topology (SD family): conv stem, residual stages with
downsample, mid attention block, mirrored decoder.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from .layers import GroupNorm32


@dataclasses.dataclass(frozen=True)
class VAEConfig:
    in_channels: int = 3
    latent_channels: int = 4
    base_channels: int = 128
    channel_mult: tuple[int, ...] = (1, 2, 4, 4)
    num_res_blocks: int = 2
    scaling_factor: float = 0.13025      # SDXL VAE; SD1.5 uses 0.18215
    shift_factor: float = 0.0            # FLUX ae: 0.1159
    dtype: str = "bfloat16"

    @classmethod
    def sdxl(cls) -> "VAEConfig":
        return cls()

    @classmethod
    def tiny(cls, dtype: str = "bfloat16") -> "VAEConfig":
        """2× downscale toy VAE for tests (8× in real configs)."""
        return cls(base_channels=16, channel_mult=(1, 2), num_res_blocks=1,
                   scaling_factor=1.0, dtype=dtype)

    @property
    def jnp_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.dtype)

    @property
    def downscale(self) -> int:
        return 2 ** (len(self.channel_mult) - 1)


# LDM's AutoencoderKL normalizes with eps=1e-6 (vs the UNet's 1e-5) —
# weight parity requires matching it
_VAE_EPS = 1e-6


class _VAEResBlock(nn.Module):
    out_channels: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        h = GroupNorm32(epsilon=_VAE_EPS)(x)
        h = nn.silu(h)
        h = nn.Conv(self.out_channels, (3, 3), padding=1, dtype=self.dtype, name="conv1")(h)
        h = GroupNorm32(epsilon=_VAE_EPS)(h)
        h = nn.silu(h)
        h = nn.Conv(self.out_channels, (3, 3), padding=1, dtype=self.dtype, name="conv2")(h)
        if x.shape[-1] != self.out_channels:
            x = nn.Conv(self.out_channels, (1, 1), dtype=self.dtype, name="skip")(x)
        return x + h


class _VAEAttention(nn.Module):
    """LDM AttnBlock: single-head attention with biased q/k/v/proj (the
    checkpoint stores them as 1×1 convs; Dense is the same linear map)."""

    dtype: jnp.dtype

    @nn.compact
    def __call__(self, h: jax.Array) -> jax.Array:
        B, N, C = h.shape
        q = nn.Dense(C, dtype=self.dtype, name="to_q")(h)
        k = nn.Dense(C, dtype=self.dtype, name="to_k")(h)
        v = nn.Dense(C, dtype=self.dtype, name="to_v")(h)
        s = jnp.einsum("bqc,bkc->bqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / (C ** 0.5)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        out = jnp.einsum("bqk,bkc->bqc", p, v)
        return nn.Dense(C, dtype=self.dtype, name="to_out")(out)


class _MidBlock(nn.Module):
    channels: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = _VAEResBlock(self.channels, self.dtype, name="res1")(x)
        B, H, W, C = x.shape
        h = GroupNorm32(epsilon=_VAE_EPS)(x).reshape(B, H * W, C)
        h = _VAEAttention(self.dtype, name="attn")(h)
        x = x + h.reshape(B, H, W, C)
        return _VAEResBlock(self.channels, self.dtype, name="res2")(x)


class Encoder(nn.Module):
    config: VAEConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        dt = cfg.jnp_dtype
        h = nn.Conv(cfg.base_channels, (3, 3), padding=1, dtype=dt, name="conv_in")(
            x.astype(dt)
        )
        for level, mult in enumerate(cfg.channel_mult):
            ch = cfg.base_channels * mult
            for i in range(cfg.num_res_blocks):
                h = _VAEResBlock(ch, dt, name=f"down_{level}_res_{i}")(h)
            if level < len(cfg.channel_mult) - 1:
                # LDM downsamples with asymmetric (0,1) padding — weight
                # parity requires the exact same spatial alignment
                h = nn.Conv(ch, (3, 3), strides=2, padding=((0, 1), (0, 1)),
                            dtype=dt, name=f"down_{level}_ds")(h)
        h = _MidBlock(h.shape[-1], dt, name="mid")(h)
        h = GroupNorm32(epsilon=_VAE_EPS, name="norm_out")(h)
        h = nn.silu(h)
        # 2×latent: mean and logvar
        h = nn.Conv(cfg.latent_channels * 2, (3, 3), padding=1, dtype=jnp.float32,
                    name="conv_out")(h.astype(jnp.float32))
        return nn.Conv(cfg.latent_channels * 2, (1, 1), dtype=jnp.float32,
                       name="quant_conv")(h)


class Decoder(nn.Module):
    config: VAEConfig

    @nn.compact
    def __call__(self, z: jax.Array) -> jax.Array:
        cfg = self.config
        dt = cfg.jnp_dtype
        z = nn.Conv(cfg.latent_channels, (1, 1), dtype=jnp.float32,
                    name="post_quant_conv")(z.astype(jnp.float32))
        ch = cfg.base_channels * cfg.channel_mult[-1]
        h = nn.Conv(ch, (3, 3), padding=1, dtype=dt, name="conv_in")(z.astype(dt))
        h = _MidBlock(ch, dt, name="mid")(h)
        for level in reversed(range(len(cfg.channel_mult))):
            ch = cfg.base_channels * cfg.channel_mult[level]
            for i in range(cfg.num_res_blocks + 1):
                h = _VAEResBlock(ch, dt, name=f"up_{level}_res_{i}")(h)
            if level > 0:
                B, H, W, C = h.shape
                h = jax.image.resize(h, (B, H * 2, W * 2, C), method="nearest")
                h = nn.Conv(C, (3, 3), padding=1, dtype=dt, name=f"up_{level}_us")(h)
        h = GroupNorm32(epsilon=_VAE_EPS, name="norm_out")(h)
        h = nn.silu(h)
        return nn.Conv(cfg.in_channels, (3, 3), padding=1, dtype=jnp.float32,
                       name="conv_out")(h.astype(jnp.float32))


class AutoencoderKL:
    """Bundled encoder/decoder with scaling-factor handling.

    ``encode`` returns scaled latents (mode of the posterior — diffusion
    inference never needs the sample noise); ``decode`` maps scaled latents
    back to [-1, 1] pixels.
    """

    def __init__(self, config: VAEConfig, enc_params=None, dec_params=None):
        self.config = config
        self.encoder = Encoder(config)
        self.decoder = Decoder(config)
        self.enc_params = enc_params
        self.dec_params = dec_params

    def init(self, rng: jax.Array, image_hw: tuple[int, int] = (64, 64)) -> "AutoencoderKL":
        H, W = image_hw
        cfg = self.config
        k1, k2 = jax.random.split(rng)
        img = jnp.zeros((1, H, W, cfg.in_channels))
        lat = jnp.zeros((1, H // cfg.downscale, W // cfg.downscale, cfg.latent_channels))
        from .draw import draw_params   # on a comment's old line (D17)
        self.enc_params = draw_params(self.encoder, k1, img)
        self.dec_params = draw_params(self.decoder, k2, lat)
        return self

    def encode(self, images: jax.Array, params=None) -> jax.Array:
        """``params`` overrides the bundled encoder params — pipelines pass
        weights as jit ARGUMENTS (closure capture would embed multi-GB
        constants into the lowered MLIR; see pipeline ``_weights``).
        The apply is jitted with params as an argument (``jit_apply``):
        eager (node-level) calls get one program instead of per-op
        dispatch, and inside an outer jit the call inlines."""
        from .layers import jit_apply

        moments = jit_apply(self, self.encoder, "_enc_fn")(
            self.enc_params if params is None else params, images)
        mean, _logvar = jnp.split(moments, 2, axis=-1)
        return (mean - self.config.shift_factor) * self.config.scaling_factor

    def decode(self, latents: jax.Array, params=None) -> jax.Array:
        from ..telemetry.device_scopes import device_scope
        from .layers import jit_apply

        with device_scope("vae_decode"):
            return jit_apply(self, self.decoder, "_dec_fn")(
                self.dec_params if params is None else params,
                latents / self.config.scaling_factor
                + self.config.shift_factor)
