"""Denoiser construction: model parameterizations + classifier-free guidance.

``eps_denoiser`` adapts an eps-prediction UNet to the k-diffusion contract
(c_in scaling + sigma→timestep lookup); ``flow_denoiser`` adapts a
velocity-prediction rectified-flow model. ``cfg_denoiser`` batches the
cond/uncond passes into ONE model call (batch-dim concat) so the MXU sees a
2× batch instead of two launches.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..telemetry.device_scopes import device_scope
from .schedules import NoiseSchedule
from .samplers import Denoiser

# model(x, t, context, y) -> prediction
ModelFn = Callable[..., jax.Array]


def eps_denoiser(
    model_fn: ModelFn,
    schedule: NoiseSchedule,
    context: jax.Array,
    y: Optional[jax.Array] = None,
) -> Denoiser:
    """eps-pred VP model → x0 denoiser: D(x,σ) = x − σ·eps(x·c_in, t(σ))."""

    def denoise(x: jax.Array, sigma: jax.Array) -> jax.Array:
        with device_scope("sampler"):
            c_in = 1.0 / jnp.sqrt(sigma ** 2 + 1.0)
            t = schedule.timestep_for_sigma(sigma)
            t_b = jnp.broadcast_to(t, (x.shape[0],))
            x_in = x * c_in
        eps = model_fn(x_in, t_b, context, y)
        with device_scope("sampler"):
            return x - sigma * eps

    return denoise


def flow_denoiser(
    model_fn: ModelFn,
    context: jax.Array,
    y: Optional[jax.Array] = None,
) -> Denoiser:
    """Rectified-flow velocity model → x0 denoiser: D(x,σ) = x − σ·v(x, σ)."""

    def denoise(x: jax.Array, sigma: jax.Array) -> jax.Array:
        with device_scope("sampler"):
            t_b = jnp.broadcast_to(sigma, (x.shape[0],))
        v = model_fn(x, t_b, context, y)
        with device_scope("sampler"):
            return x - sigma * v

    return denoise


def cfg_denoiser(
    make_denoiser: Callable[[jax.Array, Optional[jax.Array]], Denoiser],
    context: jax.Array,
    uncond_context: jax.Array,
    guidance_scale: float,
    y: Optional[jax.Array] = None,
    uncond_y: Optional[jax.Array] = None,
) -> Denoiser:
    """Classifier-free guidance with a single doubled-batch model call.

    ``make_denoiser(context, y)`` builds the underlying denoiser; both
    conditionings are stacked along batch so one forward serves both.
    """
    with device_scope("sampler"):
        ctx2 = jnp.concatenate([context, uncond_context], axis=0)
        y2 = None
        if y is not None:
            y2 = jnp.concatenate([y, uncond_y if uncond_y is not None else jnp.zeros_like(y)], axis=0)
    inner = make_denoiser(ctx2, y2)

    def denoise(x: jax.Array, sigma: jax.Array) -> jax.Array:
        with device_scope("sampler"):
            x2 = jnp.concatenate([x, x], axis=0)
        out = inner(x2, sigma)
        with device_scope("sampler"):
            cond, uncond = jnp.split(out, 2, axis=0)
            return uncond + guidance_scale * (cond - uncond)

    return denoise
