"""WAN-class video DiT (text→video / image→video family).

Covers BASELINE's "WAN-2.2 14B t2v" config family: flow-matching DiT over
spatio-temporal tokens. Geometry: latent video [B,F,h,w,C] patchified
per-frame (p×p spatial, temporal patch 1), tokens ordered frame-major, 3-D
axial sincos positions (t,h,w). Transformer blocks are the same MMDiT
double/single blocks as the image DiT (``models/dit.py``) — they are
geometry-agnostic — so sequence parallelism (ring attention over the
``sp`` axis) works over *frames*: each shard owns a contiguous frame
block, the TPU-native form of the reference's temporal chunking
(``upscale/modes/dynamic.py`` per-image queue + ImageBatchDivider,
SURVEY §5.7).

The reference's WAN-specific 4n+1 frame-batch rule
(``nodes/distributed_upscale.py:131-142``) is provided as padding helpers.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.lax import axis_size as _axis_size
from flax import linen as nn

from .dit import DiTConfig, DoubleBlock, Modulation, SingleBlock, _modulate
from .layers import timestep_embedding


def pad_frames_4n1(frames: int) -> int:
    """Smallest 4n+1 ≥ frames (reference video-model constraint)."""
    if frames <= 1:
        return 1
    return ((frames - 2) // 4 + 1) * 4 + 1


def validate_frames_4n1(frames: int) -> bool:
    return frames >= 1 and (frames - 1) % 4 == 0


@dataclasses.dataclass(frozen=True)
class VideoDiTConfig:
    patch_size: int = 2
    in_channels: int = 16
    hidden: int = 5120               # WAN-14B class
    depth_double: int = 20
    depth_single: int = 20
    heads: int = 40
    context_dim: int = 4096
    pooled_dim: int = 768
    dtype: str = "bfloat16"
    remat: bool = False              # recompute block activations (HBM relief)

    @classmethod
    def wan(cls) -> "VideoDiTConfig":
        from ..utils import constants

        return cls(remat=constants.REMAT)

    @classmethod
    def tiny(cls) -> "VideoDiTConfig":
        return cls(patch_size=2, in_channels=4, hidden=64, depth_double=1,
                   depth_single=1, heads=4, context_dim=32, pooled_dim=16)

    def as_dit_config(self, dtype: Optional[str] = None) -> DiTConfig:
        return DiTConfig(
            patch_size=self.patch_size, in_channels=self.in_channels,
            hidden=self.hidden, depth_double=self.depth_double,
            depth_single=self.depth_single, heads=self.heads,
            context_dim=self.context_dim, pooled_dim=self.pooled_dim,
            guidance_embed=False, dtype=dtype or self.dtype,
            remat=self.remat)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)


def patchify_video(x: jax.Array, p: int) -> jax.Array:
    """[B,F,H,W,C] → [B, F·(H/p)·(W/p), p·p·C], frame-major order."""
    B, F, H, W, C = x.shape
    x = x.reshape(B, F, H // p, p, W // p, p, C)
    x = x.transpose(0, 1, 2, 4, 3, 5, 6)
    return x.reshape(B, F * (H // p) * (W // p), p * p * C)


def unpatchify_video(tokens: jax.Array, fhw: tuple[int, int, int], p: int,
                     c: int) -> jax.Array:
    F, H, W = fhw
    B = tokens.shape[0]
    x = tokens.reshape(B, F, H // p, W // p, p, p, c)
    x = x.transpose(0, 1, 2, 4, 3, 5, 6)
    return x.reshape(B, F, H, W, c)


def sincos_3d(f: int, h: int, w: int, dim: int) -> jax.Array:
    """Axial 3-D position table [f·h·w, dim]: time/row/col chunks."""
    def axis_table(n, d):
        pos = jnp.arange(n, dtype=jnp.float32)
        freqs = jnp.exp(-math.log(10000.0) *
                        jnp.arange(d // 2, dtype=jnp.float32) / max(d // 2, 1))
        args = pos[:, None] * freqs[None]
        return jnp.concatenate([jnp.sin(args), jnp.cos(args)], axis=-1)

    dt_ = dim // 4                       # quarter for time, rest split h/w
    dh = (dim - dt_) // 2
    dw = dim - dt_ - dh
    tt = axis_table(f, dt_)              # [f, dt]
    th = axis_table(h, dh)
    tw = axis_table(w, dw)
    out = jnp.concatenate([
        jnp.repeat(tt, h * w, axis=0),
        jnp.tile(jnp.repeat(th, w, axis=0), (f, 1)),
        jnp.tile(tw, (f * h, 1)),
    ], axis=-1)
    return out


class VideoDiT(nn.Module):
    """x[B,F,h,w,C], t[B], context[B,T,ctx], pooled[B,P] → velocity."""

    config: VideoDiTConfig

    @nn.compact
    def __call__(self, x, t, context, pooled, sp_axis: Optional[str] = None):
        cfg = self.config
        dcfg = cfg.as_dit_config()
        dt = cfg.jnp_dtype
        B, F, H, W, C = x.shape
        p = cfg.patch_size

        tokens = patchify_video(x.astype(dt), p)
        img = nn.Dense(cfg.hidden, dtype=dt, name="img_in")(tokens)
        if sp_axis is None:
            pos = sincos_3d(F, H // p, W // p, cfg.hidden)
        else:
            n_sh = _axis_size(sp_axis)
            idx = jax.lax.axis_index(sp_axis)
            pos_full = sincos_3d(F * n_sh, H // p, W // p, cfg.hidden)
            per = pos_full.shape[0] // n_sh
            pos = jax.lax.dynamic_slice_in_dim(pos_full, idx * per, per, axis=0)
        img = img + pos[None].astype(dt)

        txt = nn.Dense(cfg.hidden, dtype=dt, name="txt_in")(context.astype(dt))
        vec = nn.Dense(cfg.hidden, dtype=dt, name="t_in")(
            timestep_embedding(t * 1000.0, 256).astype(dt))
        vec = vec + nn.Dense(cfg.hidden, dtype=dt, name="pool_in")(
            pooled.astype(dt))
        vec = nn.Dense(cfg.hidden, dtype=dt, name="vec_mlp")(nn.silu(vec))

        DBlock = (nn.remat(DoubleBlock, static_argnums=(4,))
                  if dcfg.remat else DoubleBlock)
        SBlock = (nn.remat(SingleBlock, static_argnums=(3, 4))
                  if dcfg.remat else SingleBlock)
        for i in range(cfg.depth_double):
            img, txt = DBlock(dcfg, name=f"double_{i}")(img, txt, vec, sp_axis)
        xcat = jnp.concatenate([txt, img], axis=1)
        T = txt.shape[1]
        for i in range(cfg.depth_single):
            xcat = SBlock(dcfg, name=f"single_{i}")(xcat, vec, T, sp_axis)
        img = xcat[:, T:]

        sh, sc, _ = Modulation(1, cfg.hidden, dt, name="final_mod")(vec)
        img = _modulate(
            nn.LayerNorm(use_scale=False, use_bias=False, dtype=dt)(img), sh, sc)
        out = nn.Dense(p * p * C, dtype=jnp.float32,
                       kernel_init=nn.initializers.zeros, name="img_out")(
            img.astype(jnp.float32))
        return unpatchify_video(out, (F, H, W), p, C)


def init_video_dit(config: VideoDiTConfig, rng: jax.Array,
                   sample_fhw: tuple[int, int, int] = (5, 8, 8),
                   context_len: int = 16, abstract: bool = False):
    from .draw import draw_params

    model = VideoDiT(config)
    f, h, w = sample_fhw
    x = jnp.zeros((1, f, h, w, config.in_channels))
    args = (rng, x, jnp.zeros((1,)),
            jnp.zeros((1, context_len, config.context_dim)),
            jnp.zeros((1, config.pooled_dim)))
    return model, draw_params(model, *args, abstract=abstract)
