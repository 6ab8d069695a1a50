"""FLUX/SD3-class rectified-flow MMDiT.

Covers the BASELINE "FLUX.1-dev txt2img" config family — double-stream
(image/text) transformer blocks followed by single-stream blocks — AND the
SD3/SD3.5 family (``sd3_medium``/``sd35_large`` presets): joint-only
depth (``depth_single=0``), learned cropped position table, optional
qk-norm, no distilled-guidance embedder. Both share adaLN-Zero modulation
from (timestep, pooled text[, guidance]), patchified latents, and velocity
prediction for flow matching. The reference runs these models through
ComfyUI; here the architecture is native and **sequence-parallel
capable**: ``attn_backend="ring"`` runs joint attention with image tokens
sharded over the ``sp`` mesh axis (``ops/attention.joint_ring_attention``)
— the capability the reference entirely lacks (SURVEY §2.10: SP/CP
absent).

Positional encoding: selectable per config —

- ``pos_embed="sincos"``: axial 2-D sinusoidal added to patch embeddings
  (simple, fine for from-scratch training);
- ``pos_embed="rope"`` (the FLUX preset's default): 3-axis rotary
  embeddings applied to q/k per head exactly in FLUX's layout (axis 0 =
  text/time slot, axes 1-2 = patch row/col; ``rope_axes_dim`` must sum
  to ``head_dim``) — the form real FLUX checkpoints require, so weight
  porting needs no architectural surgery;
- ``pos_embed="learned"`` (the SD3 presets' default): a trained
  ``pos_embed_max_size²``-entry table added to patch embeddings after a
  CENTER crop to the sample's patch grid — SD3's exact scheme, so its
  checkpoints port table-intact and any resolution ≤ the table's square
  samples without interpolation.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.lax import axis_size as _axis_size
from flax import linen as nn

from ..ops.attention import (Columns, as_heads, full_attention,
                             joint_attention, joint_ring_attention)
from ..utils import constants
from ..telemetry.device_scopes import device_scope
from .layers import timestep_embedding


@dataclasses.dataclass(frozen=True)
class DiTConfig:
    patch_size: int = 2
    in_channels: int = 16            # FLUX VAE: 16 latent channels
    hidden: int = 3072
    depth_double: int = 19
    depth_single: int = 38
    heads: int = 24
    context_dim: int = 4096          # T5 features
    pooled_dim: int = 768            # CLIP pooled
    guidance_embed: bool = True      # FLUX-dev distilled guidance input
    dtype: str = "bfloat16"
    attn_backend: str = "dense"      # "dense" | "ring" | "flash"
                                     # ("flash" = dense compute with the
                                     # pallas kernel preferred regardless
                                     # of the seq-length gate — required
                                     # by the memory-starved offload
                                     # executor, ops/attention.py)
    pos_embed: str = "sincos"        # "sincos" | "rope" | "learned"
    pos_embed_max_size: int = 0      # "learned": side of the square table
    qk_norm: bool = True             # RMS qk-norm (FLUX, SD3.5; SD3-medium
                                     # checkpoints have no norm scales)
    remat: bool = False              # recompute block activations (HBM relief)
    rope_theta: float = 10000.0
    rope_axes_dim: Optional[tuple[int, int, int]] = None   # None → derived

    @classmethod
    def flux(cls) -> "DiTConfig":
        from ..utils import constants

        # FLUX.1: head_dim 128 = 16 (txt/time axis) + 56 (row) + 56 (col)
        return cls(pos_embed="rope", rope_axes_dim=(16, 56, 56),
                   remat=constants.REMAT)

    @classmethod
    def sd3_medium(cls) -> "DiTConfig":
        """SD3-medium (2B): 24 joint blocks, width 1536, no qk-norm."""
        from ..utils import constants

        return cls(hidden=1536, depth_double=24, depth_single=0, heads=24,
                   context_dim=4096, pooled_dim=2048, guidance_embed=False,
                   pos_embed="learned", pos_embed_max_size=192,
                   qk_norm=False, remat=constants.REMAT)

    @classmethod
    def sd35_large(cls) -> "DiTConfig":
        """SD3.5-large (8B): 38 joint blocks, width 2432, RMS qk-norm."""
        from ..utils import constants

        return cls(hidden=2432, depth_double=38, depth_single=0, heads=38,
                   context_dim=4096, pooled_dim=2048, guidance_embed=False,
                   pos_embed="learned", pos_embed_max_size=192,
                   qk_norm=True, remat=constants.REMAT)

    @classmethod
    def tiny(cls, attn_backend: str = "dense",
             pos_embed: str = "sincos", **kw) -> "DiTConfig":
        base = dict(patch_size=2, in_channels=4, hidden=64, depth_double=2,
                    depth_single=2, heads=4, context_dim=32, pooled_dim=16,
                    attn_backend=attn_backend, pos_embed=pos_embed)
        base.update(kw)
        return cls(**base)

    @classmethod
    def sd3_tiny(cls, attn_backend: str = "dense") -> "DiTConfig":
        """SD3-shaped tiny: joint-only depth, learned cropped pos table."""
        return cls.tiny(attn_backend, pos_embed="learned",
                        pos_embed_max_size=12, depth_double=2,
                        depth_single=0, qk_norm=False)

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def axes_dim(self) -> tuple[int, int, int]:
        """Per-axis RoPE widths (must sum to head_dim, all even)."""
        if self.rope_axes_dim is not None:
            return self.rope_axes_dim
        d0 = max(2, (self.head_dim // 8) // 2 * 2)
        rest = self.head_dim - d0
        dh = (rest // 2) // 2 * 2
        return (d0, dh, rest - dh)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)


def patchify(x: jax.Array, p: int) -> jax.Array:
    """[B,H,W,C] → [B, (H/p)(W/p), p·p·C]."""
    B, H, W, C = x.shape
    x = x.reshape(B, H // p, p, W // p, p, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(B, (H // p) * (W // p), p * p * C)


def unpatchify(tokens: jax.Array, hw: tuple[int, int], p: int, c: int) -> jax.Array:
    B = tokens.shape[0]
    h, w = hw[0] // p, hw[1] // p
    x = tokens.reshape(B, h, w, p, p, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(B, hw[0], hw[1], c)


def sincos_2d(h: int, w: int, dim: int) -> jax.Array:
    """Axial 2-D sinusoidal position table [h·w, dim]."""
    def axis_table(n, d):
        pos = jnp.arange(n, dtype=jnp.float32)
        freqs = jnp.exp(-math.log(10000.0) * jnp.arange(d // 2, dtype=jnp.float32)
                        / (d // 2))
        args = pos[:, None] * freqs[None]
        return jnp.concatenate([jnp.sin(args), jnp.cos(args)], axis=-1)

    dh = dim // 2
    th = axis_table(h, dh)                      # [h, dh]
    tw = axis_table(w, dim - dh)                # [w, dim-dh]
    grid = jnp.concatenate([
        jnp.repeat(th, w, axis=0),
        jnp.tile(tw, (h, 1)),
    ], axis=-1)
    return grid


def rope_freqs(ids: jax.Array, axes_dim: tuple[int, ...],
               theta: float) -> tuple[jax.Array, jax.Array]:
    """FLUX multi-axis RoPE table.

    ``ids``: [N, n_axes] integer positions per token (txt tokens all-zero,
    img tokens (0, row, col)). Returns (cos, sin), each [N, head_dim/2]:
    axis a contributes ``axes_dim[a]/2`` rotation frequencies, concatenated
    in axis order — FLUX's EmbedND layout.
    """
    parts_cos, parts_sin = [], []
    for a, d in enumerate(axes_dim):
        half = d // 2
        freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / d))
        args = ids[:, a].astype(jnp.float32)[:, None] * freqs[None]
        parts_cos.append(jnp.cos(args))
        parts_sin.append(jnp.sin(args))
    return (jnp.concatenate(parts_cos, axis=-1),
            jnp.concatenate(parts_sin, axis=-1))


def apply_rope(x: jax.Array, pe: tuple[jax.Array, jax.Array]) -> jax.Array:
    """Rotate q/k pairs: x [B, N, heads, head_dim], pe ([N, hd/2], [N, hd/2])."""
    cos, sin = pe
    cos = cos[None, :, None, :].astype(jnp.float32)
    sin = sin[None, :, None, :].astype(jnp.float32)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def image_ids(h: int, w: int, row_offset: int = 0) -> jax.Array:
    """[h·w, 3] FLUX image token ids: (0, row, col)."""
    rows = jnp.repeat(jnp.arange(h) + row_offset, w)
    cols = jnp.tile(jnp.arange(w), (h,))
    return jnp.stack([jnp.zeros_like(rows), rows, cols], axis=-1)


class MLPEmbedder(nn.Module):
    """FLUX conditioning embedder: Dense → silu → Dense (in_layer/out_layer).

    Matches the checkpoint layout of FLUX's ``time_in``/``vector_in``/
    ``guidance_in`` MLPs so published weights convert without surgery.
    """

    hidden: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        with device_scope("norm_mod"):
            h = nn.Dense(self.hidden, dtype=self.dtype, name="in_layer")(x)
            return nn.Dense(self.hidden, dtype=self.dtype, name="out_layer")(nn.silu(h))


class Modulation(nn.Module):
    """adaLN-Zero: conditioning vector → (shift, scale, gate) × n."""

    n_outputs: int
    hidden: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, vec: jax.Array) -> tuple[jax.Array, ...]:
        with device_scope("norm_mod"):
            out = nn.Dense(self.hidden * 3 * self.n_outputs, dtype=self.dtype,
                           kernel_init=nn.initializers.zeros, name="mod")(nn.silu(vec))
            return tuple(jnp.split(out[:, None, :], 3 * self.n_outputs, axis=-1))


def _modulate(x, shift, scale):
    return x * (1 + scale) + shift


def _norm_modulate(x, shift, scale, dt):
    """A block's scale-free LayerNorm and its adaLN modulation."""
    with device_scope("norm_mod"):
        return _modulate(nn.LayerNorm(use_scale=False, use_bias=False,
                                      dtype=dt)(x), shift, scale)


class _QKV(nn.Module):
    hidden: int
    heads: int
    dtype: jnp.dtype
    qk_norm: bool = True

    @nn.compact
    def __call__(self, x, cut: bool = True):
        """q, k, v ``[B, N, heads, hd]``. ``cut=False`` (a joint block's
        segment, for ``ops.attention.joint_attention``) leaves what nothing
        below touches — v, and q and k without qk-norm — as ``Columns`` of
        the product's own output: the kernel reads them there."""
        B, N, _ = x.shape
        with device_scope("attn_proj"):
            qkv = nn.Dense(self.hidden * 3, dtype=self.dtype, name="qkv")(x)
            hd = self.hidden // self.heads
            q, k, v = (Columns(qkv, g, 3) for g in range(3))
            if cut:
                q, k, v = (as_heads(c, self.heads) for c in (q, k, v))
            if not self.qk_norm:
                # SD3-medium: raw q/k (its checkpoints carry no norm scales)
                return q, k, v
            # qk-norm (learned-scale RMS over head_dim) as in FLUX's QKNorm /
            # SD3.5's ln_q/ln_k — the scales land from checkpoints'
            # {query,key}_norm.scale / ln_{q,k}.weight entries
            qs = self.param("q_scale", nn.initializers.ones, (hd,), jnp.float32)
            ks = self.param("k_scale", nn.initializers.ones, (hd,), jnp.float32)
            q = _rms(as_heads(q, self.heads)) * qs.astype(self.dtype)
            k = _rms(as_heads(k, self.heads)) * ks.astype(self.dtype)
            return q, k, v


def _rms(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x.astype(jnp.float32) ** 2, -1,
                                      keepdims=True) + eps).astype(x.dtype)


class DoubleBlock(nn.Module):
    """Separate image/text streams with joint attention (MMDiT)."""

    config: DiTConfig

    @nn.compact
    def __call__(self, img, txt, vec, sp_axis: Optional[str],
                 pe_img=None, pe_txt=None):
        cfg = self.config
        dt = cfg.jnp_dtype
        i_sh1, i_sc1, i_g1, i_sh2, i_sc2, i_g2 = Modulation(2, cfg.hidden, dt,
                                                            name="img_mod")(vec)
        t_sh1, t_sc1, t_g1, t_sh2, t_sc2, t_g2 = Modulation(2, cfg.hidden, dt,
                                                            name="txt_mod")(vec)

        img_n = _norm_modulate(img, i_sh1, i_sc1, dt)
        txt_n = _norm_modulate(txt, t_sh1, t_sc1, dt)
        # one chip: the segments go to the attention as they lie (q, k, v
        # inside the products' outputs where nothing touches them); the ring
        # takes arrays
        cut = sp_axis is not None
        iq, ik, iv = _QKV(cfg.hidden, cfg.heads, dt, cfg.qk_norm,
                          name="img_qkv")(img_n, cut)
        tq, tk, tv = _QKV(cfg.hidden, cfg.heads, dt, cfg.qk_norm,
                          name="txt_qkv")(txt_n, cut)
        if pe_img is not None:
            with device_scope("attn_proj"):
                iq, ik = (apply_rope(as_heads(x, cfg.heads), pe_img)
                          for x in (iq, ik))
                tq, tk = (apply_rope(as_heads(x, cfg.heads), pe_txt)
                          for x in (tq, tk))
        if sp_axis is None:
            t_out, i_out = joint_attention(                # cdt.attn_core
                (tq, tk, tv), (iq, ik, iv), cfg.heads,
                prefer_flash=cfg.attn_backend == "flash")
        else:
            with device_scope("attn_proj"):
                q = jnp.concatenate([tq, iq], axis=1)
            out = joint_ring_attention(q, tk, tv, ik, iv, sp_axis)
            T = txt.shape[1]
            with device_scope("attn_proj"):
                B = img.shape[0]
                i_out = out[:, T:].reshape(B, -1, cfg.hidden)
                t_out = out[:, :T].reshape(B, T, cfg.hidden)
        with device_scope("attn_proj"):
            img = img + i_g1 * nn.Dense(cfg.hidden, dtype=dt, name="img_proj")(i_out)
            txt = txt + t_g1 * nn.Dense(cfg.hidden, dtype=dt, name="txt_proj")(t_out)

        img_m = _norm_modulate(img, i_sh2, i_sc2, dt)
        txt_m = _norm_modulate(txt, t_sh2, t_sc2, dt)
        with device_scope("ffn"):
            img_h = nn.Dense(cfg.hidden * 4, dtype=dt, name="img_mlp_up")(img_m)
            img = img + i_g2 * nn.Dense(cfg.hidden, dtype=dt,
                                        name="img_mlp_down")(nn.gelu(img_h))
            txt_h = nn.Dense(cfg.hidden * 4, dtype=dt, name="txt_mlp_up")(txt_m)
            txt = txt + t_g2 * nn.Dense(cfg.hidden, dtype=dt,
                                        name="txt_mlp_down")(nn.gelu(txt_h))
        return img, txt


class SingleBlock(nn.Module):
    """Merged-stream block (FLUX single blocks)."""

    config: DiTConfig

    @nn.compact
    def __call__(self, x, vec, txt_len: int, sp_axis: Optional[str],
                 pe_full=None):
        cfg = self.config
        dt = cfg.jnp_dtype
        sh, sc, g = Modulation(1, cfg.hidden, dt, name="mod")(vec)
        xn = _norm_modulate(x, sh, sc, dt)
        q, k, v = _QKV(cfg.hidden, cfg.heads, dt, cfg.qk_norm, name="qkv")(xn)
        if pe_full is not None:
            with device_scope("attn_proj"):
                q, k = apply_rope(q, pe_full), apply_rope(k, pe_full)
        if sp_axis is None:
            out = full_attention(q, k, v,                  # cdt.attn_core
                                 prefer_flash=cfg.attn_backend == "flash")
        else:
            # txt tokens lead the sequence on every shard
            with device_scope("attn_proj"):
                tk, ik = k[:, :txt_len], k[:, txt_len:]
                tv, iv = v[:, :txt_len], v[:, txt_len:]
            out = joint_ring_attention(q, tk, tv, ik, iv, sp_axis)
        B, N, _, _ = out.shape
        # one product takes the attention's output and the MLP's: four
        # fifths of its rows are the MLP's, so it counts as the FFN
        with device_scope("ffn"):
            out = out.reshape(B, N, cfg.hidden)
            mlp_in = nn.Dense(cfg.hidden * 4, dtype=dt, name="mlp_up")(xn)
            fused = jnp.concatenate([out, nn.gelu(mlp_in)], axis=-1)
            return x + g * nn.Dense(cfg.hidden, dtype=dt, name="out")(fused)


class DiT(nn.Module):
    """x[B,h,w,C], t[B] (flow time in [0,1]), context[B,T,ctx],
    pooled[B,P], guidance[B] → velocity [B,h,w,C]."""

    config: DiTConfig

    @nn.compact
    def __call__(self, x, t, context, pooled, guidance=None,
                 sp_axis: Optional[str] = None):
        cfg = self.config
        dt = cfg.jnp_dtype
        B, H, W, C = x.shape
        p = cfg.patch_size

        with device_scope("norm_mod"):
            tokens = patchify(x.astype(dt), p)
            img = nn.Dense(cfg.hidden, dtype=dt, name="img_in")(tokens)
            pe_img = pe_txt = pe_full = None
            if cfg.pos_embed == "rope":
                # per-head rotary positions (FLUX layout); in sp mode the row
                # ids are offset by this shard's global row-block start so a
                # sharded run rotates identically to the unsharded one
                if sp_axis is None:
                    ids_img = image_ids(H // p, W // p)
                else:
                    idx = jax.lax.axis_index(sp_axis)
                    ids_img = image_ids(H // p, W // p,
                                        row_offset=idx * (H // p))
                ids_txt = jnp.zeros((context.shape[1], 3), jnp.int32)
                pe_img = rope_freqs(ids_img, cfg.axes_dim, cfg.rope_theta)
                pe_txt = rope_freqs(ids_txt, cfg.axes_dim, cfg.rope_theta)
                pe_full = (jnp.concatenate([pe_txt[0], pe_img[0]], axis=0),
                           jnp.concatenate([pe_txt[1], pe_img[1]], axis=0))
            elif cfg.pos_embed == "learned":
                # SD3: trained (max × max) table, CENTER-cropped to the patch
                # grid; in sp mode each shard crops its own row block of the
                # global grid so the sharded run adds identical positions
                m = cfg.pos_embed_max_size
                table = self.param("pos_emb", nn.initializers.normal(0.01),
                                   (m * m, cfg.hidden)).reshape(m, m, cfg.hidden)
                hp, wp = H // p, W // p
                n_sh = 1 if sp_axis is None else _axis_size(sp_axis)
                gh = hp * n_sh                       # global patch rows
                if gh > m or wp > m:
                    raise ValueError(
                        f"sample grid {gh}×{wp} exceeds the learned position "
                        f"table ({m}×{m}) — SD3-family models cannot sample "
                        "beyond pos_embed_max_size patches per side")
                top, left = (m - gh) // 2, (m - wp) // 2
                rows = table[:, left:left + wp]
                if sp_axis is None:
                    pos = rows[top:top + hp]
                else:
                    idx = jax.lax.axis_index(sp_axis)
                    pos = jax.lax.dynamic_slice_in_dim(
                        rows, top + idx * hp, hp, axis=0)
                img = img + pos.reshape(hp * wp, cfg.hidden)[None].astype(dt)
            elif sp_axis is None:
                pos = sincos_2d(H // p, W // p, cfg.hidden)
                img = img + pos[None].astype(dt)
            else:
                # x is this shard's row block of the global image: build the
                # global position table and slice this shard's rows
                n_sh = _axis_size(sp_axis)
                idx = jax.lax.axis_index(sp_axis)
                pos_full = sincos_2d((H * n_sh) // p, W // p, cfg.hidden)
                per = pos_full.shape[0] // n_sh
                pos = jax.lax.dynamic_slice_in_dim(pos_full, idx * per, per, axis=0)
                img = img + pos[None].astype(dt)

            txt = nn.Dense(cfg.hidden, dtype=dt, name="txt_in")(context.astype(dt))

        # FLUX conditioning vector: summed MLPEmbedder outputs (time_in /
        # vector_in / guidance_in) — the exact functional form of the
        # published checkpoints, so weights port without surgery
        # (MLPEmbedder opens cdt.norm_mod itself; the sums go with it)
        with device_scope("norm_mod"):
            t_emb = timestep_embedding(t * 1000.0, 256).astype(dt)
            pooled = pooled.astype(dt)
        vec = MLPEmbedder(cfg.hidden, dt, name="time_in")(t_emb)
        pooled_vec = MLPEmbedder(cfg.hidden, dt, name="vector_in")(pooled)
        with device_scope("norm_mod"):
            vec = vec + pooled_vec
        if cfg.guidance_embed:
            with device_scope("norm_mod"):
                gvec = guidance if guidance is not None else jnp.full((B,), 3.5)
                g_emb = timestep_embedding(gvec * 1000.0, 256).astype(dt)
            g_vec = MLPEmbedder(cfg.hidden, dt, name="guidance_in")(g_emb)
            with device_scope("norm_mod"):
                vec = vec + g_vec

        DBlock = (nn.remat(DoubleBlock, static_argnums=(4,))
                  if cfg.remat else DoubleBlock)
        SBlock = (nn.remat(SingleBlock, static_argnums=(3, 4))
                  if cfg.remat else SingleBlock)
        for i in range(cfg.depth_double):
            img, txt = DBlock(cfg, name=f"double_{i}")(
                img, txt, vec, sp_axis, pe_img, pe_txt)
        with device_scope("attn_proj"):
            xcat = jnp.concatenate([txt, img], axis=1)
        T = txt.shape[1]
        for i in range(cfg.depth_single):
            xcat = SBlock(cfg, name=f"single_{i}")(xcat, vec, T, sp_axis,
                                                   pe_full)
        sh, sc, _ = Modulation(1, cfg.hidden, dt, name="final_mod")(vec)
        img = _norm_modulate(xcat[:, T:], sh, sc, dt)
        with device_scope("norm_mod"):
            out = nn.Dense(p * p * C, dtype=jnp.float32,
                           kernel_init=nn.initializers.zeros, name="img_out")(
                img.astype(jnp.float32))
            # in sp mode (H, W) is the local row block — output stays local,
            # so the sampler update is shard-local too
            return unpatchify(out, (H, W), p, C)


def init_dit(config: DiTConfig, rng: jax.Array,
             sample_hw: tuple[int, int] = (32, 32), context_len: int = 16,
             abstract: bool = False, param_dtype=None):
    """``abstract=True`` returns a ShapeDtypeStruct tree instead of
    materialized random params — the shape template weight conversion
    needs without paying a 12B-param random init (FLUX-size presets).
    ``param_dtype`` casts float params inside each leaf's draw
    (``models/draw.py``) — bf16 residency is what lets a
    FLUX-class model fit accelerator HBM at all."""
    from .draw import draw_params

    model = DiT(config)
    h, w = sample_hw
    x = jnp.zeros((1, h, w, config.in_channels))
    t = jnp.zeros((1,))
    ctx = jnp.zeros((1, context_len, config.context_dim))
    pooled = jnp.zeros((1, config.pooled_dim))
    params = draw_params(model, rng, x, t, ctx, pooled,
                         param_dtype=param_dtype, abstract=abstract)
    return model, params
