"""WAN 2.x video DiT — the exact published architecture, flax-native.

``models/video_dit.py`` keeps the generic MMDiT-over-frames stack; this
module is the weight-faithful WAN t2v transformer (Wan-2.1/2.2 family)
so published checkpoints convert without surgery:

- Conv3d patch embedding (temporal patch 1, spatial 2×2);
- N identical blocks: self-attention with 3-axis rotary embeddings and
  **full-dim** learned-scale qk RMSNorm, cross-attention to UMT5 text
  (no RoPE), tanh-GELU FFN; modulation = a learned per-block ``[1,6,dim]``
  parameter **added** to the shared time projection, chunked into
  shift/scale/gate for the attention and FFN branches;
- head: LayerNorm + linear with a learned ``[1,2,dim]`` shift/scale
  modulation over the *unprojected* time embedding.

The reference runs WAN through ComfyUI (SURVEY "external substrate");
here the stack is native and sequence-parallel: ``sp_axis`` shards the
frame axis — self-attention runs as ring attention over the shards with
frame-offset RoPE ids (exact), cross-attention is token-local and needs
no collective. This is the capability the reference lacks entirely
(SURVEY §2.10/§5.7: no sequence/context parallelism).

Converter: :func:`convert_wan` (official ``blocks.N.*`` layout, bare or
under ``model.diffusion_model.``). Differential test:
``tests/test_wan.py`` against a torch replica of the published forward.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from ..ops.attention import full_attention, ring_attention
from .dit import apply_rope, rope_freqs
from .layers import timestep_embedding


@dataclasses.dataclass(frozen=True)
class WanConfig:
    patch_size: tuple[int, int, int] = (1, 2, 2)
    in_channels: int = 16
    out_channels: int = 16
    dim: int = 5120
    ffn_dim: int = 13824
    num_layers: int = 40
    num_heads: int = 40
    text_dim: int = 4096
    freq_dim: int = 256
    eps: float = 1e-6
    cross_attn_norm: bool = True
    dtype: str = "bfloat16"
    remat: bool = False
    attn_backend: str = "dense"    # "dense" | "flash" — "flash" prefers
                                   # the pallas kernel regardless of the
                                   # seq-length gate (memory-starved
                                   # offload executors; ops/attention.py)

    @classmethod
    def wan_14b(cls) -> "WanConfig":
        from ..utils import constants

        return cls(remat=constants.REMAT)

    @classmethod
    def wan_1_3b(cls) -> "WanConfig":
        from ..utils import constants

        return cls(dim=1536, ffn_dim=8960, num_layers=30, num_heads=12,
                   remat=constants.REMAT)

    @classmethod
    def tiny(cls, **kw) -> "WanConfig":
        base = dict(in_channels=4, out_channels=4, dim=48, ffn_dim=96,
                    num_layers=2, num_heads=4, text_dim=32, freq_dim=32,
                    dtype="float32")
        base.update(kw)
        return cls(**base)

    @property
    def head_dim(self) -> int:
        return self.dim // self.num_heads

    @property
    def axes_dim(self) -> tuple[int, int, int]:
        """Per-axis RoPE widths over (frame, row, col) — WAN's split:
        2·(d/6) each for rows/cols, the remainder for time."""
        d = self.head_dim
        dh = 2 * (d // 6)
        return (d - 2 * dh, dh, dh)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)


def video_ids(f: int, h: int, w: int, frame_offset: int = 0) -> jax.Array:
    """[f·h·w, 3] (frame, row, col) token ids, frame-major."""
    fs = jnp.repeat(jnp.arange(f) + frame_offset, h * w)
    rows = jnp.tile(jnp.repeat(jnp.arange(h), w), (f,))
    cols = jnp.tile(jnp.arange(w), (f * h,))
    return jnp.stack([fs, rows, cols], axis=-1)


class WanRMSNorm(nn.Module):
    """Full-width RMS norm with learned scale (WAN's qk norm)."""

    eps: float

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        w = self.param("weight", nn.initializers.ones, (x.shape[-1],))
        var = jnp.mean(x.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
        return (x * jax.lax.rsqrt(var + self.eps)).astype(x.dtype) \
            * w.astype(x.dtype)


class WanSelfAttention(nn.Module):
    config: WanConfig

    @nn.compact
    def __call__(self, x, pe, sp_axis: Optional[str]):
        cfg = self.config
        dt = cfg.jnp_dtype
        B, N, _ = x.shape
        q = WanRMSNorm(cfg.eps, name="norm_q")(
            nn.Dense(cfg.dim, dtype=dt, name="q")(x))
        k = WanRMSNorm(cfg.eps, name="norm_k")(
            nn.Dense(cfg.dim, dtype=dt, name="k")(x))
        v = nn.Dense(cfg.dim, dtype=dt, name="v")(x)
        shape = (B, N, cfg.num_heads, cfg.head_dim)
        q = apply_rope(q.reshape(shape), pe)
        k = apply_rope(k.reshape(shape), pe)
        v = v.reshape(shape)
        if sp_axis is None:
            out = full_attention(q, k, v,
                                 prefer_flash=cfg.attn_backend == "flash")
        else:
            out = ring_attention(q, k, v, sp_axis)
        return nn.Dense(cfg.dim, dtype=dt, name="o")(
            out.reshape(B, N, cfg.dim))


class WanCrossAttention(nn.Module):
    """Text cross-attention (no RoPE). Context is replicated per shard,
    queries are token-local — sp needs no collective here."""

    config: WanConfig

    @nn.compact
    def __call__(self, x, context):
        cfg = self.config
        dt = cfg.jnp_dtype
        B, N, _ = x.shape
        T = context.shape[1]
        q = WanRMSNorm(cfg.eps, name="norm_q")(
            nn.Dense(cfg.dim, dtype=dt, name="q")(x))
        k = WanRMSNorm(cfg.eps, name="norm_k")(
            nn.Dense(cfg.dim, dtype=dt, name="k")(context))
        v = nn.Dense(cfg.dim, dtype=dt, name="v")(context)
        out = full_attention(q.reshape(B, N, cfg.num_heads, cfg.head_dim),
                             k.reshape(B, T, cfg.num_heads, cfg.head_dim),
                             v.reshape(B, T, cfg.num_heads, cfg.head_dim))
        return nn.Dense(cfg.dim, dtype=dt, name="o")(
            out.reshape(B, N, cfg.dim))


class WanBlock(nn.Module):
    config: WanConfig

    @nn.compact
    def __call__(self, x, e0, context, pe, sp_axis: Optional[str]):
        """x [B,N,dim]; e0 [B,6,dim] (shared time projection)."""
        cfg = self.config
        dt = cfg.jnp_dtype
        mod = self.param("modulation", nn.initializers.normal(0.02),
                         (1, 6, cfg.dim))
        m = (mod.astype(jnp.float32) + e0.astype(jnp.float32)).astype(dt)
        m0, m1, m2, m3, m4, m5 = [m[:, i][:, None, :] for i in range(6)]

        ln = dict(use_scale=False, use_bias=False, epsilon=cfg.eps, dtype=dt)
        y = WanSelfAttention(cfg, name="self_attn")(
            nn.LayerNorm(**ln)(x) * (1 + m1) + m0, pe, sp_axis)
        x = x + y * m2
        h = x
        if cfg.cross_attn_norm:
            h = nn.LayerNorm(epsilon=cfg.eps, dtype=dt, name="norm3")(x)
        x = x + WanCrossAttention(cfg, name="cross_attn")(h, context)
        y = nn.LayerNorm(**ln)(x) * (1 + m4) + m3
        y = nn.Dense(cfg.ffn_dim, dtype=dt, name="ffn_0")(y)
        y = nn.Dense(cfg.dim, dtype=dt, name="ffn_2")(
            nn.gelu(y, approximate=True))
        return x + y * m5


class WanModel(nn.Module):
    """x[B,F,h,w,C], t[B] (flow time in [0,1]), context[B,T,text_dim]
    → velocity [B,F,h,w,out]. ``pooled`` is accepted and ignored (WAN has
    no pooled-vector conditioning) so the video pipeline drives either
    architecture unchanged."""

    config: WanConfig
    # tensor-parallel rule family (parallel/tensor.py): separate q/k/v/o +
    # ffn_0/ffn_2 naming — NOT the MMDiT fused-qkv layout
    tp_family = "wan"

    @nn.compact
    def __call__(self, x, t, context, pooled=None,
                 sp_axis: Optional[str] = None):
        cfg = self.config
        dt = cfg.jnp_dtype
        B, F, H, W, C = x.shape
        pt, ph, pw = cfg.patch_size

        tok = nn.Conv(cfg.dim, kernel_size=cfg.patch_size,
                      strides=cfg.patch_size, dtype=dt,
                      name="patch_embedding")(x.astype(dt))
        f, h, w = F // pt, H // ph, W // pw
        tok = tok.reshape(B, f * h * w, cfg.dim)

        if sp_axis is None:
            ids = video_ids(f, h, w)
        else:
            idx = jax.lax.axis_index(sp_axis)
            ids = video_ids(f, h, w, frame_offset=idx * f)
        pe = rope_freqs(ids, cfg.axes_dim, 10000.0)

        emb = timestep_embedding(t * 1000.0, cfg.freq_dim).astype(dt)
        e = nn.Dense(cfg.dim, dtype=dt, name="time_emb_0")(emb)
        e = nn.Dense(cfg.dim, dtype=dt, name="time_emb_2")(nn.silu(e))
        e0 = nn.Dense(cfg.dim * 6, dtype=dt, name="time_proj_1")(
            nn.silu(e)).reshape(B, 6, cfg.dim)

        ctx = nn.Dense(cfg.dim, dtype=dt, name="text_emb_0")(
            context.astype(dt))
        ctx = nn.Dense(cfg.dim, dtype=dt, name="text_emb_2")(
            nn.gelu(ctx, approximate=True))

        Block = (nn.remat(WanBlock, static_argnums=(4,))
                 if cfg.remat else WanBlock)
        for i in range(cfg.num_layers):
            tok = Block(cfg, name=f"block_{i}")(tok, e0, ctx, pe, sp_axis)

        head_mod = self.param("head_modulation",
                              nn.initializers.normal(0.02), (1, 2, cfg.dim))
        hm = (head_mod.astype(jnp.float32)
              + e.astype(jnp.float32)[:, None, :]).astype(dt)
        sh, sc = hm[:, 0][:, None, :], hm[:, 1][:, None, :]
        tok = nn.LayerNorm(use_scale=False, use_bias=False, epsilon=cfg.eps,
                           dtype=dt)(tok) * (1 + sc) + sh
        out = nn.Dense(pt * ph * pw * cfg.out_channels, dtype=jnp.float32,
                       name="head")(tok.astype(jnp.float32))

        # unpatchify: tokens frame-major; WAN head features are ordered
        # (pt, ph, pw, c) — channel LAST (`view(*v, *patch_size, c)` in the
        # published unpatchify) — so head weights map verbatim
        o = cfg.out_channels
        out = out.reshape(B, f, h, w, pt, ph, pw, o)
        out = out.transpose(0, 1, 4, 2, 5, 3, 6, 7)   # B f pt h ph w pw c
        return out.reshape(B, F, H, W, o)


def init_wan(config: WanConfig, rng: jax.Array,
             sample_fhw: tuple[int, int, int] = (5, 8, 8),
             context_len: int = 16, abstract: bool = False,
             param_dtype=None):
    """``param_dtype`` casts float params inside each leaf's draw
    (``models/draw.py``) — a 14B WAN never fits as fp32."""
    from .draw import draw_params

    model = WanModel(config)
    f, h, w = sample_fhw
    args = (rng, jnp.zeros((1, f, h, w, config.in_channels)),
            jnp.zeros((1,)),
            jnp.zeros((1, context_len, config.text_dim)),
            jnp.zeros((1, 16)))
    return model, draw_params(model, *args, param_dtype=param_dtype,
                              abstract=abstract)


# ---------------------------------------------------------------------------
# converter (official Wan2.x layout)
# ---------------------------------------------------------------------------

WAN_PREFIXED = "model.diffusion_model."


def convert_wan(sd, template, config: WanConfig, prefix: str = "") -> dict:
    """Official WAN t2v state dict → :class:`WanModel` params.

    Key walk: ``patch_embedding``, ``{text,time}_embedding.{0,2}``,
    ``time_projection.1``, ``blocks.N.{self_attn,cross_attn}.{q,k,v,o}``
    (+ full-dim ``norm_q``/``norm_k`` scales), ``blocks.N.norm3``,
    ``blocks.N.ffn.{0,2}``, per-block ``modulation`` ``[1,6,dim]``,
    ``head.{head,modulation}``. i2v-specific keys (``k_img``/``img_emb``)
    raise a targeted error until the image-conditioned variant lands.
    """
    from .convert import ConversionError, _Filler, _lin

    if any(".k_img." in k or k.startswith(f"{prefix}img_emb.") for k in sd):
        raise ConversionError(
            "WAN i2v checkpoint (image-conditioned cross-attention) is not "
            "yet supported — use a t2v checkpoint")
    p = prefix
    f = _Filler(sd, template["params"])

    def conv3d(w):
        return np.asarray(w, np.float32).transpose(2, 3, 4, 1, 0)

    f.put(f"{p}patch_embedding.weight", "patch_embedding/kernel", conv3d)
    f.put(f"{p}patch_embedding.bias", "patch_embedding/bias")
    f.linear(f"{p}text_embedding.0", "text_emb_0")
    f.linear(f"{p}text_embedding.2", "text_emb_2")
    f.linear(f"{p}time_embedding.0", "time_emb_0")
    f.linear(f"{p}time_embedding.2", "time_emb_2")
    f.linear(f"{p}time_projection.1", "time_proj_1")

    for i in range(config.num_layers):
        src, dst = f"{p}blocks.{i}", f"block_{i}"
        f.put(f"{src}.modulation", f"{dst}/modulation")
        for attn in ("self_attn", "cross_attn"):
            for proj in ("q", "k", "v", "o"):
                f.linear(f"{src}.{attn}.{proj}", f"{dst}/{attn}/{proj}")
            f.put(f"{src}.{attn}.norm_q.weight",
                  f"{dst}/{attn}/norm_q/weight")
            f.put(f"{src}.{attn}.norm_k.weight",
                  f"{dst}/{attn}/norm_k/weight")
        if config.cross_attn_norm:
            f.norm(f"{src}.norm3", f"{dst}/norm3")
        f.linear(f"{src}.ffn.0", f"{dst}/ffn_0")
        f.linear(f"{src}.ffn.2", f"{dst}/ffn_2")

    f.put(f"{p}head.head.weight", "head/kernel", _lin)
    f.put(f"{p}head.head.bias", "head/bias")
    f.put(f"{p}head.modulation", "head_modulation")
    tree = f.finish(expect_prefix=p)
    if not p:
        leftover = [k for k in sd if k not in f.used]
        if leftover:
            raise ConversionError(
                f"unconsumed WAN keys: {leftover[:8]}"
                f"{'…' if len(leftover) > 8 else ''}")
    return {"params": tree}
