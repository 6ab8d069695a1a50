"""SDXL-class latent UNet in flax.

Architecture follows the latent-diffusion UNet family (what the reference
drives through ComfyUI's ``comfy.samplers``/``common_ksampler`` — SURVEY
"external substrate") with SDXL's layout expressible via config: per-level
transformer depth, cross-attention dim, optional label/ADM embedding for
SDXL micro-conditioning.

Presets: ``UNetConfig.sdxl()`` reproduces SDXL-base's shape
(320·[1,2,4], transformer depths [0,2,10], ctx 2048, adm 2816);
``UNetConfig.tiny()`` is a 2-level toy for tests and CPU dry-runs.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..telemetry.device_scopes import device_scope
from .layers import (
    GroupNorm32,
    ResBlock,
    SpatialTransformer,
    Downsample,
    Upsample,
    timestep_embedding,
)


@dataclasses.dataclass(frozen=True)
class UNetConfig:
    in_channels: int = 4
    out_channels: int = 4
    model_channels: int = 320
    channel_mult: tuple[int, ...] = (1, 2, 4)
    num_res_blocks: int = 2
    # transformer depth per resolution level; 0 = conv-only level
    transformer_depth: tuple[int, ...] = (0, 2, 10)
    num_heads: int = -1            # -1: derive from head_dim
    head_dim: int = 64
    context_dim: int = 2048
    adm_in_channels: int = 0       # SDXL: 2816 (pooled text + size conds)
    dtype: str = "bfloat16"
    # activation rematerialization: recompute block activations in the
    # backward/later passes instead of keeping them in HBM — trades FLOPs
    # for memory headroom on big latents (CDT_REMAT=1 flips the presets)
    remat: bool = False

    @classmethod
    def sdxl(cls) -> "UNetConfig":
        from ..utils import constants

        # 2816 = 1280 pooled CLIP-G + 6×256 Fourier size/crop conds —
        # without label_emb a real SDXL checkpoint cannot convert
        # (label_emb.* keys would be unconsumed) and micro-conds are lost
        return cls(remat=constants.REMAT, adm_in_channels=2816)

    @classmethod
    def sd15(cls) -> "UNetConfig":
        from ..utils import constants

        return cls(
            remat=constants.REMAT,
            channel_mult=(1, 2, 4, 4),
            transformer_depth=(1, 1, 1, 0),
            context_dim=768,
            head_dim=-1,
            num_heads=8,
        )

    @classmethod
    def tiny(cls, dtype: str = "bfloat16") -> "UNetConfig":
        """2-level toy UNet for tests: ~0.5M params, still exercises every
        block type (res, self/cross attention, up/down, skip concat)."""
        return cls(
            model_channels=32,
            channel_mult=(1, 2),
            num_res_blocks=1,
            transformer_depth=(0, 1),
            context_dim=32,
            head_dim=16,
            adm_in_channels=8,
            dtype=dtype,
        )

    @property
    def jnp_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.dtype)

    def heads_for(self, channels: int) -> int:
        if self.num_heads > 0:
            return self.num_heads
        return max(1, channels // self.head_dim)


class UNet2D(nn.Module):
    """Latent UNet: x[B,H,W,C_in], t[B], context[B,N,ctx], y[B,adm] → eps."""

    config: UNetConfig

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        t: jax.Array,
        context: Optional[jax.Array] = None,
        y: Optional[jax.Array] = None,
        control: Optional[tuple] = None,
    ) -> jax.Array:
        """``control``: optional ``(down_residuals, mid_residual)`` from a
        ControlNet (``models/controlnet.py``) — one residual per skip in
        push order, added when each skip is popped, plus one added to the
        middle state (LDM ``cldm`` semantics)."""
        cfg = self.config
        dt = cfg.jnp_dtype
        time_dim = cfg.model_channels * 4

        with device_scope("norm_mod"):
            emb = timestep_embedding(t, cfg.model_channels)
            emb = nn.Dense(time_dim, dtype=dt, name="time_1")(emb.astype(dt))
            emb = nn.Dense(time_dim, dtype=dt, name="time_2")(nn.silu(emb))
            if cfg.adm_in_channels:
                assert y is not None, "config.adm_in_channels set but y not given"
                yemb = nn.Dense(time_dim, dtype=dt, name="label_1")(y.astype(dt))
                yemb = nn.Dense(time_dim, dtype=dt, name="label_2")(nn.silu(yemb))
                emb = emb + yemb
            if context is not None:
                context = context.astype(dt)

        Res = nn.remat(ResBlock) if cfg.remat else ResBlock
        Attn = nn.remat(SpatialTransformer) if cfg.remat else SpatialTransformer

        with device_scope("resnet"):
            x = x.astype(dt)
            h = nn.Conv(cfg.model_channels, (3, 3), padding=1, dtype=dt, name="conv_in")(x)
        skips = [h]

        # --- down path ---
        for level, mult in enumerate(cfg.channel_mult):
            ch = cfg.model_channels * mult
            for i in range(cfg.num_res_blocks):
                h = Res(ch, dt, name=f"down_{level}_res_{i}")(h, emb)
                if cfg.transformer_depth[level]:
                    h = Attn(
                        cfg.heads_for(ch),
                        cfg.transformer_depth[level],
                        dt,
                        name=f"down_{level}_attn_{i}",
                    )(h, context)
                skips.append(h)
            if level < len(cfg.channel_mult) - 1:
                h = Downsample(ch, dt, name=f"down_{level}_ds")(h)
                skips.append(h)

        # --- middle ---
        mid_ch = cfg.model_channels * cfg.channel_mult[-1]
        h = Res(mid_ch, dt, name="mid_res_1")(h, emb)
        if cfg.transformer_depth[-1]:
            h = Attn(
                cfg.heads_for(mid_ch), cfg.transformer_depth[-1], dt, name="mid_attn"
            )(h, context)
        h = Res(mid_ch, dt, name="mid_res_2")(h, emb)

        if control is not None:
            down_res, mid_res = control
            assert len(down_res) == len(skips), (
                f"control carries {len(down_res)} skip residuals, "
                f"UNet has {len(skips)}")
            with device_scope("resnet"):
                h = h + mid_res.astype(h.dtype)
                skips = [s + r.astype(s.dtype)
                         for s, r in zip(skips, down_res)]

        # --- up path ---
        for level in reversed(range(len(cfg.channel_mult))):
            ch = cfg.model_channels * cfg.channel_mult[level]
            for i in range(cfg.num_res_blocks + 1):
                with device_scope("resnet"):
                    h = jnp.concatenate([h, skips.pop()], axis=-1)
                h = Res(ch, dt, name=f"up_{level}_res_{i}")(h, emb)
                if cfg.transformer_depth[level]:
                    h = Attn(
                        cfg.heads_for(ch),
                        cfg.transformer_depth[level],
                        dt,
                        name=f"up_{level}_attn_{i}",
                    )(h, context)
            if level > 0:
                h = Upsample(ch, dt, name=f"up_{level}_us")(h)

        with device_scope("resnet"):
            h = GroupNorm32(name="norm_out")(h)
            h = nn.silu(h)
            h = nn.Conv(
                cfg.out_channels, (3, 3), padding=1, dtype=jnp.float32, name="conv_out"
            )(h.astype(jnp.float32))
        return h


def init_unet(
    config: UNetConfig,
    rng: jax.Array,
    sample_shape: tuple[int, int, int] = (64, 64, 4),
    context_len: int = 77,
    abstract: bool = False,
    param_dtype=None,
):
    """Initialize params with a canonical dummy batch; returns (module, params).

    ``abstract=True`` returns a ShapeDtypeStruct tree (conversion template
    — no multi-GB random init when every leaf is about to be replaced).
    ``param_dtype`` (e.g. ``jnp.bfloat16``) casts float params INSIDE each
    leaf's draw, so peak device memory is the cast tree plus one leaf —
    never the full fp32 tree (an SDXL fp32 init plus a post-hoc cast
    transiently needs 15.6 GB; cast leaf by leaf it's ~5.5 GB, and
    inference weights want bf16 residency anyway)."""
    from .draw import draw_params

    model = UNet2D(config)
    H, W, C = sample_shape
    x = jnp.zeros((1, H, W, C), jnp.float32)
    t = jnp.zeros((1,), jnp.float32)
    ctx = jnp.zeros((1, context_len, config.context_dim), jnp.float32)
    y = jnp.zeros((1, config.adm_in_channels), jnp.float32) if config.adm_in_channels else None
    # neither eager flax init (each initialiser op its own tiny executable:
    # tens of seconds even at toy sizes) nor jit of the whole init (ONE
    # program holding 1 752 draws and the forward: 299 s to compile, 67 s
    # to read back): one small jitted draw a distinct (initialiser, shape,
    # dtype, cast), every leaf under the key flax gives it (models/draw.py)
    params = draw_params(model, rng, x, t, ctx, y, param_dtype=param_dtype,
                         abstract=abstract)
    return model, params
