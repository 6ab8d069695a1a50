"""ControlNet (LDM ``cldm`` architecture) in flax.

The reference gets ControlNet from ComfyUI core and its USDU path crops
control hints per tile (``/root/reference/utils/usdu_utils.py:506``
``crop_cond``, ``utils/crop_model_patch.py`` — SURVEY §7 hard-part #3).
A standalone framework owns the model: this is the published ControlNet
topology — an exact copy of the UNet encoder + middle (so SD1.5/SDXL
control checkpoints convert via the same walk the UNet converter uses,
``convert._unet_down_layout``), an 8-conv hint stem (image-res hint →
/8 latent res), one zero-init 1×1 conv per skip connection, and a middle
output zero-conv. Outputs are residuals the UNet adds to its skips and
middle state (``models/unet.py`` ``control=`` hook).

TPU notes: bf16 trunk on the MXU like the UNet; the whole control pass
fuses into the same XLA program as the denoise step. The hint stem is
recomputed per step inside the sampler scan — it is ~8 thin convs
(<1% of step FLOPs), and keeping ``__call__`` single-method keeps the
module compact and the converter template exact.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from .layers import (
    Downsample,
    GroupNorm32,
    ResBlock,
    SpatialTransformer,
    timestep_embedding,
)
from .unet import UNetConfig

# hint-stem channel ladder (published cldm: 16,16,32,32,96,96,256 → model_ch)
_HINT_CHANNELS = (16, 16, 32, 32, 96, 96, 256)
_HINT_STRIDES = (1, 1, 2, 1, 2, 1, 2)


class ControlNet(nn.Module):
    """x[B,h,w,C], t[B], context, y, hint[B,H,W,3] → (skip residuals, mid)."""

    config: UNetConfig
    hint_channels: int = 3

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        t: jax.Array,
        context: Optional[jax.Array],
        y: Optional[jax.Array],
        hint: jax.Array,
    ) -> tuple[list[jax.Array], jax.Array]:
        cfg = self.config
        dt = cfg.jnp_dtype
        time_dim = cfg.model_channels * 4
        assert hint.shape[-1] == self.hint_channels, (
            f"hint has {hint.shape[-1]} channels, module expects "
            f"{self.hint_channels}")

        emb = timestep_embedding(t, cfg.model_channels)
        emb = nn.Dense(time_dim, dtype=dt, name="time_1")(emb.astype(dt))
        emb = nn.Dense(time_dim, dtype=dt, name="time_2")(nn.silu(emb))
        if cfg.adm_in_channels:
            assert y is not None, "config.adm_in_channels set but y not given"
            yemb = nn.Dense(time_dim, dtype=dt, name="label_1")(y.astype(dt))
            yemb = nn.Dense(time_dim, dtype=dt, name="label_2")(nn.silu(yemb))
            emb = emb + yemb

        # hint stem: image-res control map → latent-res features
        g = hint.astype(dt)
        for j, (ch, stride) in enumerate(zip(_HINT_CHANNELS, _HINT_STRIDES)):
            g = nn.silu(nn.Conv(ch, (3, 3), strides=stride, padding=1,
                                dtype=dt, name=f"hint_{j}")(g))
        g = nn.Conv(cfg.model_channels, (3, 3), padding=1, dtype=dt,
                    name=f"hint_{len(_HINT_CHANNELS)}")(g)

        x = x.astype(dt)
        if context is not None:
            context = context.astype(dt)

        zero = lambda i, h: nn.Conv(
            h.shape[-1], (1, 1), dtype=jnp.float32, name=f"zero_{i}",
            kernel_init=nn.initializers.zeros,
        )(h.astype(jnp.float32))

        h = nn.Conv(cfg.model_channels, (3, 3), padding=1, dtype=dt,
                    name="conv_in")(x)
        h = h + g
        outs = [zero(0, h)]
        zi = 1

        for level, mult in enumerate(cfg.channel_mult):
            ch = cfg.model_channels * mult
            for i in range(cfg.num_res_blocks):
                h = ResBlock(ch, dt, name=f"down_{level}_res_{i}")(h, emb)
                if cfg.transformer_depth[level]:
                    h = SpatialTransformer(
                        cfg.heads_for(ch), cfg.transformer_depth[level], dt,
                        name=f"down_{level}_attn_{i}")(h, context)
                outs.append(zero(zi, h))
                zi += 1
            if level < len(cfg.channel_mult) - 1:
                h = Downsample(ch, dt, name=f"down_{level}_ds")(h)
                outs.append(zero(zi, h))
                zi += 1

        mid_ch = cfg.model_channels * cfg.channel_mult[-1]
        h = ResBlock(mid_ch, dt, name="mid_res_1")(h, emb)
        if cfg.transformer_depth[-1]:
            h = SpatialTransformer(
                cfg.heads_for(mid_ch), cfg.transformer_depth[-1], dt,
                name="mid_attn")(h, context)
        h = ResBlock(mid_ch, dt, name="mid_res_2")(h, emb)
        mid = nn.Conv(mid_ch, (1, 1), dtype=jnp.float32, name="mid_out",
                      kernel_init=nn.initializers.zeros)(
            h.astype(jnp.float32))
        return outs, mid


_uid_counter = itertools.count()


@dataclasses.dataclass
class ControlNetBundle:
    """Module + params + the conditioning-dict payload contract: a
    conditioning entry carries ``{"model": bundle, "hint": [B,H,W,3],
    "strength": float}`` under its ``"control"`` key (ControlNetApply).

    ``uid`` is a process-unique token for compile-clone caches (``id()``
    is recycled after GC and would alias stale compiled programs)."""

    model: ControlNet
    params: dict
    name: str = "controlnet"
    uid: int = dataclasses.field(default_factory=_uid_counter.__next__)

    def apply(self, x, t, context, y, hint):
        return self.model.apply(self.params, x, t, context, y, hint)


def init_controlnet(
    config: UNetConfig,
    rng: jax.Array,
    sample_shape: tuple[int, int, int] = (64, 64, 4),
    context_len: int = 77,
    hint_channels: int = 3,
) -> ControlNetBundle:
    from .draw import draw_params

    model = ControlNet(config, hint_channels=hint_channels)
    H, W, C = sample_shape
    down = 8  # hint stem downscale (three stride-2 convs)
    x = jnp.zeros((1, H, W, C), jnp.float32)
    t = jnp.zeros((1,), jnp.float32)
    ctx = jnp.zeros((1, context_len, config.context_dim), jnp.float32)
    y = (jnp.zeros((1, config.adm_in_channels), jnp.float32)
         if config.adm_in_channels else None)
    hint = jnp.zeros((1, H * down, W * down, hint_channels), jnp.float32)
    params = draw_params(model, rng, x, t, ctx, y, hint)
    return ControlNetBundle(model, params)
