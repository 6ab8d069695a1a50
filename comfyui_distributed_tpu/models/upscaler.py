"""RRDBNet (ESRGAN-family) learned upscaler in flax.

The reference's upscale workflows run an ESRGAN-class model before tile
diffusion (``/root/reference/workflows/distributed-upscale.json`` —
``UpscaleModelLoader`` → ``ImageUpscaleWithModel`` feeding
``UltimateSDUpscaleDistributed``'s ``upscaled_image`` input,
``nodes/distributed_upscale.py:84-91``); ComfyUI supplies the model zoo.
A standalone framework owns that capability: this is the standard RRDBNet
topology every published ESRGAN/Real-ESRGAN ``.safetensors``/``.pth``
checkpoint (4x-UltraSharp, RealESRGAN_x4plus, …) maps onto, so converted
weights drop straight in (``convert.convert_upscaler``).

TPU notes: convs compute in bf16 on the MXU (params stay f32); the whole
forward is one fused XLA program. Real-ESRGAN x2 checkpoints use a
pixel-unshuffle stem (input space-to-depth by 2, then a 4× trunk) — that
is reproduced exactly so their weights convert.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import linen as nn


@dataclasses.dataclass(frozen=True)
class UpscalerConfig:
    scale: int = 4                    # output scale of the checkpoint
    in_channels: int = 3
    out_channels: int = 3
    num_feat: int = 64
    num_block: int = 23
    grow_ch: int = 32
    dtype: str = "bfloat16"

    @classmethod
    def esrgan_x4(cls) -> "UpscalerConfig":
        return cls()

    @classmethod
    def realesrgan_x2(cls) -> "UpscalerConfig":
        # x2 models keep the 4× trunk behind a pixel-unshuffle stem
        return cls(scale=2)

    @classmethod
    def tiny(cls, scale: int = 2) -> "UpscalerConfig":
        return cls(scale=scale, num_feat=8, num_block=2, grow_ch=4)

    @property
    def jnp_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.dtype)


def _lrelu(x):
    return nn.leaky_relu(x, negative_slope=0.2)


class _DenseBlock(nn.Module):
    """Residual dense block: 5 convs, each seeing all prior features."""

    num_feat: int
    grow_ch: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        conv = lambda ch, name: nn.Conv(ch, (3, 3), padding=1,
                                        dtype=self.dtype, name=name)
        x1 = _lrelu(conv(self.grow_ch, "conv1")(x))
        x2 = _lrelu(conv(self.grow_ch, "conv2")(jnp.concatenate([x, x1], -1)))
        x3 = _lrelu(conv(self.grow_ch, "conv3")(jnp.concatenate([x, x1, x2], -1)))
        x4 = _lrelu(conv(self.grow_ch, "conv4")(
            jnp.concatenate([x, x1, x2, x3], -1)))
        x5 = conv(self.num_feat, "conv5")(
            jnp.concatenate([x, x1, x2, x3, x4], -1))
        return x + 0.2 * x5


class _RRDB(nn.Module):
    num_feat: int
    grow_ch: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        h = _DenseBlock(self.num_feat, self.grow_ch, self.dtype, name="rdb1")(x)
        h = _DenseBlock(self.num_feat, self.grow_ch, self.dtype, name="rdb2")(h)
        h = _DenseBlock(self.num_feat, self.grow_ch, self.dtype, name="rdb3")(h)
        return x + 0.2 * h


def _nearest_x2(x):
    B, H, W, C = x.shape
    x = jnp.broadcast_to(x[:, :, None, :, None, :], (B, H, 2, W, 2, C))
    return x.reshape(B, 2 * H, 2 * W, C)


def _pixel_unshuffle(x, factor: int):
    """NHWC pixel-unshuffle with torch's output channel order
    ``c·f² + fy·f + fx`` — required for weight portability (the stem
    conv's input channels are laid out this way in checkpoints)."""
    B, H, W, C = x.shape
    x = x.reshape(B, H // factor, factor, W // factor, factor, C)
    return x.transpose(0, 1, 3, 5, 2, 4).reshape(
        B, H // factor, W // factor, C * factor * factor)


class RRDBNet(nn.Module):
    """[B,H,W,3] in [0,1] → [B,H·s,W·s,3]."""

    config: UpscalerConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        dt = cfg.jnp_dtype
        conv = lambda ch, name: nn.Conv(ch, (3, 3), padding=1,
                                        dtype=dt, name=name)
        h = x.astype(dt)
        if cfg.scale == 2:
            h = _pixel_unshuffle(h, 2)
        elif cfg.scale == 1:
            h = _pixel_unshuffle(h, 4)
        feat = conv(cfg.num_feat, "conv_first")(h)
        body = feat
        for i in range(cfg.num_block):
            body = _RRDB(cfg.num_feat, cfg.grow_ch, dt, name=f"body_{i}")(body)
        feat = feat + conv(cfg.num_feat, "conv_body")(body)
        # trunk is always 4×: two nearest-neighbour ×2 hops
        feat = _lrelu(conv(cfg.num_feat, "conv_up1")(_nearest_x2(feat)))
        feat = _lrelu(conv(cfg.num_feat, "conv_up2")(_nearest_x2(feat)))
        out = nn.Conv(cfg.out_channels, (3, 3), padding=1,
                      dtype=jnp.float32, name="conv_last")(
            _lrelu(conv(cfg.num_feat, "conv_hr")(feat)))
        return jnp.clip(out.astype(jnp.float32), 0.0, 1.0)


@dataclasses.dataclass
class UpscalerBundle:
    """Module + params + the checkpoint's scale, as flowing through the
    graph from ``UpscaleModelLoader`` to ``ImageUpscaleWithModel``."""

    model: RRDBNet
    params: dict
    name: str = "upscaler"

    @property
    def scale(self) -> int:
        return self.model.config.scale

    def apply(self, images: jax.Array) -> jax.Array:
        return self.model.apply(self.params, images)


def init_upscaler(config: UpscalerConfig, rng: jax.Array,
                  sample_hw: tuple[int, int] = (32, 32)) -> UpscalerBundle:
    from .draw import draw_params

    model = RRDBNet(config)
    x = jnp.zeros((1, *sample_hw, config.in_channels), jnp.float32)
    params = draw_params(model, rng, x)
    return UpscalerBundle(model, params)
