"""Shared building blocks for the model zoo.

Design notes (TPU):
- compute in ``bfloat16`` (param storage ``float32``): MXU native dtype;
- GroupNorm in float32 for numerical stability, cast back after;
- attention runs the kernel ``ops/attention.select_kernel`` picks for the
  site: a Pallas flash tier on the TPU where one wins, else
  ``jax.nn.dot_product_attention`` (XLA's fused lowering);
- all shapes static; no python control flow depends on values.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..telemetry.device_scopes import device_scope


def jit_apply(owner, module, attr: str = "_apply", **jit_kwargs):
    """Lazily-jitted ``module.apply`` cached on ``owner`` under ``attr``.

    Params stay an ARGUMENT of the jitted function (never a closure
    constant) and eager per-op dispatch is replaced by one compiled
    program. Shared by every encoder/VAE wrapper."""
    fn = getattr(owner, attr, None)
    if fn is None:
        fn = jax.jit(module.apply, **jit_kwargs)
        setattr(owner, attr, fn)
    return fn


def timestep_embedding(t: jax.Array, dim: int, max_period: float = 10000.0) -> jax.Array:
    """Sinusoidal timestep embedding, [B] -> [B, dim] (DDPM convention)."""
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None, :]
    emb = jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
    if dim % 2:
        emb = jnp.pad(emb, ((0, 0), (0, 1)))
    return emb


class GroupNorm32(nn.Module):
    """GroupNorm computed in float32, output cast to the input dtype."""

    num_groups: int = 32
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        orig = x.dtype
        x = x.astype(jnp.float32)
        groups = min(self.num_groups, x.shape[-1])
        x = nn.GroupNorm(num_groups=groups, epsilon=self.epsilon, dtype=jnp.float32)(x)
        return x.astype(orig)


class TimestepEmbedSequential(nn.Module):
    """Apply a list of blocks, feeding time/context only to those that take it."""

    blocks: tuple

    def __call__(self, x, emb=None, context=None):
        for block in self.blocks:
            if isinstance(block, ResBlock):
                x = block(x, emb)
            elif isinstance(block, SpatialTransformer):
                x = block(x, context)
            else:
                x = block(x)
        return x


class ResBlock(nn.Module):
    """GN→SiLU→conv, time-embedding shift, GN→SiLU→conv, residual.

    Matches the standard latent-diffusion ResBlock topology so published
    UNet weights can be mapped onto it.
    """

    out_channels: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, emb: jax.Array) -> jax.Array:
        with device_scope("resnet"):
            h = GroupNorm32()(x)
            h = nn.silu(h)
            h = nn.Conv(self.out_channels, (3, 3), padding=1, dtype=self.dtype, name="conv1")(h)
            emb_out = nn.Dense(self.out_channels, dtype=self.dtype, name="time_proj")(nn.silu(emb))
            h = h + emb_out[:, None, None, :]
            h = GroupNorm32()(h)
            h = nn.silu(h)
            h = nn.Conv(self.out_channels, (3, 3), padding=1, dtype=self.dtype, name="conv2")(h)
            if x.shape[-1] != self.out_channels:
                x = nn.Conv(self.out_channels, (1, 1), dtype=self.dtype, name="skip")(x)
            return x + h


class Attention(nn.Module):
    """Multi-head attention over [B, N, C] with optional cross context.

    The kernel dispatcher (``ops/attention.select_kernel`` — the one
    policy, nothing ahead of it) is asked once a site; then
    plain ``nn.Dense`` projections and ``full_attention`` with the choice
    (the packed Pallas kernel at SDXL's 64² and 32² self-attention, XLA
    at its cross-attention)."""

    num_heads: int
    head_dim: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, context: Optional[jax.Array] = None) -> jax.Array:
        ctx = x if context is None else context
        inner = self.num_heads * self.head_dim
        B, N, C = x.shape
        M = ctx.shape[1]
        from ..ops.attention import full_attention, select_kernel

        choice = select_kernel(int(N), int(M), self.num_heads,
                               self.head_dim, dtype=self.dtype)
        with device_scope("attn_proj"):
            q = nn.Dense(inner, use_bias=False, dtype=self.dtype, name="to_q")(x)
            k = nn.Dense(inner, use_bias=False, dtype=self.dtype, name="to_k")(ctx)
            v = nn.Dense(inner, use_bias=False, dtype=self.dtype, name="to_v")(ctx)
            q = q.reshape(B, N, self.num_heads, self.head_dim)
            k = k.reshape(B, M, self.num_heads, self.head_dim)
            v = v.reshape(B, M, self.num_heads, self.head_dim)
        out = full_attention(q, k, v, choice=choice)    # cdt.attn_core
        with device_scope("attn_proj"):
            out = out.reshape(B, N, inner)
            return nn.Dense(C, dtype=self.dtype, name="to_out")(out)


class _ProjParams(nn.Module):
    """``kernel`` [in, out] and ``bias`` [out] under the param paths, and
    from the initialisers, of ``nn.Dense(features)``, for a layer whose
    caller applies the weight in parts: checkpoints keep loading into the
    exact tree ``nn.Dense`` would own."""

    features: int

    @nn.compact
    def __call__(self, in_features: int) -> tuple[jax.Array, jax.Array]:
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (in_features, self.features))
        return kernel, self.param("bias", nn.initializers.zeros_init(),
                                  (self.features,))


class GEGLU(nn.Module):
    """``proj_out(value · gelu(gate))`` with value and gate the two halves
    of ONE ``proj_in`` weight (LDM's layout), applied as two products.

    Split out of one 8·dim-wide product, value j and gate j are never in
    one output tile, and the TPU compiler then computes the exact gelu
    (``erfc``, 64 vector instructions an element) on ``proj_out``'s operand
    path, where the matrix unit waits for all of it. As the value and the
    gate product of the weight's halves — the same dot products, bit for
    bit — the gelu is the epilogue of a product's own output fusion and
    ``proj_out`` reads a plain operand (PERF.md §6, PR 35)."""

    mult: int = 4
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        dim = x.shape[-1]
        inner = dim * self.mult
        with device_scope("ffn"):
            kernel, bias = _ProjParams(2 * inner, name="proj_in")(dim)
            x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias,
                                                      dtype=self.dtype)
            contract = (((x.ndim - 1,), (0,)), ((), ()))     # nn.Dense's
            with jax.named_scope("value"):
                h = jax.lax.dot_general(x, kernel[:, :inner], contract)
                h = h + bias[:inner]
            with jax.named_scope("gate"):
                gate = jax.lax.dot_general(x, kernel[:, inner:], contract)
                gate = gate + bias[inner:]
            # LDM's GEGLU uses exact (erf) gelu; flax defaults to tanh approx
            h = h * nn.gelu(gate, approximate=False)
            return nn.Dense(dim, dtype=self.dtype, name="proj_out")(h)


class TransformerBlock(nn.Module):
    """LN→self-attn, LN→cross-attn, LN→GEGLU-FF, all residual (LDM layout)."""

    num_heads: int
    head_dim: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, context: Optional[jax.Array]) -> jax.Array:
        # each residual add goes with the product it follows (the compiler
        # fuses it into that product's epilogue)
        attn1 = Attention(self.num_heads, self.head_dim, self.dtype, name="attn1")
        attn2 = Attention(self.num_heads, self.head_dim, self.dtype, name="attn2")
        ff = GEGLU(dtype=self.dtype, name="ff")
        with device_scope("norm_mod"):
            h = nn.LayerNorm(dtype=self.dtype)(x)
        h = attn1(h)
        with device_scope("attn_proj"):
            x = x + h
        with device_scope("norm_mod"):
            h = nn.LayerNorm(dtype=self.dtype)(x)
        h = attn2(h, context)
        with device_scope("attn_proj"):
            x = x + h
        with device_scope("norm_mod"):
            h = nn.LayerNorm(dtype=self.dtype)(x)
        h = ff(h)
        with device_scope("ffn"):
            return x + h


class SpatialTransformer(nn.Module):
    """Project [B,H,W,C] to tokens, run transformer blocks, project back."""

    num_heads: int
    depth: int = 1
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, context: Optional[jax.Array]) -> jax.Array:
        B, H, W, C = x.shape
        head_dim = C // self.num_heads
        with device_scope("norm_mod"):
            h = GroupNorm32()(x)
        with device_scope("attn_proj"):
            h = nn.Dense(C, dtype=self.dtype, name="proj_in")(h.reshape(B, H * W, C))
        for i in range(self.depth):
            h = TransformerBlock(self.num_heads, head_dim, self.dtype, name=f"block_{i}")(
                h, context
            )
        with device_scope("attn_proj"):
            h = nn.Dense(C, dtype=self.dtype, name="proj_out")(h)
            return x + h.reshape(B, H, W, C)


class Downsample(nn.Module):
    out_channels: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        with device_scope("resnet"):
            return nn.Conv(self.out_channels, (3, 3), strides=2, padding=1, dtype=self.dtype)(x)


class Upsample(nn.Module):
    out_channels: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        B, H, W, C = x.shape
        with device_scope("resnet"):
            x = jax.image.resize(x, (B, H * 2, W * 2, C), method="nearest")
            return nn.Conv(self.out_channels, (3, 3), padding=1, dtype=self.dtype)(x)
