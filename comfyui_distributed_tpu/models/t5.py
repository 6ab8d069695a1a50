"""T5-family text encoder (flax) + weight converter.

FLUX conditions on T5-XXL last-hidden features (context_dim 4096) and
WAN-class video models on UMT5-XXL; the reference gets both for free from
ComfyUI's text-encoder loaders (SURVEY "external substrate"). This module
owns them natively:

- :class:`T5Encoder` — encoder-only stack: relative-position-bias
  attention (shared-first-layer for T5 v1.1, per-layer for UMT5),
  pre-RMSNorm, un-scaled dot-product scores (T5 folds the 1/√d into its
  init), gated-GELU feed-forward.
- :func:`convert_t5` — HF ``T5EncoderModel``/``UMT5EncoderModel`` state
  dicts → these params, template-driven with the same
  shape/coverage guarantees as ``models/convert.py``.
- :class:`FluxTextStack` — the conditioning pair FLUX checkpoints assume
  (T5 context + CLIP-L pooled), ``TextEncoder``-compatible via
  :class:`clip.CLIPConditioner`-style ``encode``.

Differential tests: ``tests/test_t5.py`` requires exact output parity
against ``transformers`` T5/UMT5 encoders with random weights.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn


@dataclasses.dataclass(frozen=True)
class T5Config:
    vocab_size: int = 32128
    d_model: int = 4096
    d_ff: int = 10240
    num_layers: int = 24
    num_heads: int = 64
    d_kv: int = 64
    rel_buckets: int = 32
    rel_max_distance: int = 128
    layer_norm_eps: float = 1e-6
    per_layer_rel_bias: bool = False     # UMT5: every layer owns a table
    max_len: int = 512
    dtype: str = "float32"

    @classmethod
    def xxl(cls) -> "T5Config":
        """google/t5-v1_1-xxl encoder — FLUX's text tower."""
        return cls()

    @classmethod
    def umt5_xxl(cls) -> "T5Config":
        """google/umt5-xxl encoder — WAN-class video models' text tower."""
        return cls(vocab_size=256384, per_layer_rel_bias=True, max_len=512)

    @classmethod
    def tiny(cls, **kw) -> "T5Config":
        base = dict(vocab_size=128, d_model=32, d_ff=64, num_layers=2,
                    num_heads=4, d_kv=8, rel_buckets=8, rel_max_distance=16,
                    max_len=16)
        base.update(kw)
        return cls(**base)


def _rel_bucket(rel: jax.Array, num_buckets: int, max_distance: int) -> jax.Array:
    """T5 bidirectional relative-position bucketing (HF semantics)."""
    num_buckets //= 2
    ret = (rel > 0).astype(jnp.int32) * num_buckets
    n = jnp.abs(rel)
    max_exact = num_buckets // 2
    is_small = n < max_exact
    # avoid log(0); is_small branch covers n < max_exact anyway
    nf = jnp.maximum(n, 1).astype(jnp.float32)
    val_large = max_exact + (
        jnp.log(nf / max_exact) / np.log(max_distance / max_exact)
        * (num_buckets - max_exact)).astype(jnp.int32)
    val_large = jnp.minimum(val_large, num_buckets - 1)
    return ret + jnp.where(is_small, n, val_large)


class _T5LayerNorm(nn.Module):
    """RMS norm, no bias, no mean subtraction (T5 style)."""

    eps: float

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("weight", nn.initializers.ones, (x.shape[-1],))
        var = jnp.mean(x.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
        return (x * jax.lax.rsqrt(var + self.eps)).astype(x.dtype) * scale


class _T5Attention(nn.Module):
    config: T5Config

    @nn.compact
    def __call__(self, x: jax.Array, bias: jax.Array,
                 mask: Optional[jax.Array]) -> jax.Array:
        cfg = self.config
        inner = cfg.num_heads * cfg.d_kv
        B, N, _ = x.shape
        shape = (B, N, cfg.num_heads, cfg.d_kv)
        q = nn.Dense(inner, use_bias=False, name="q")(x).reshape(shape)
        k = nn.Dense(inner, use_bias=False, name="k")(x).reshape(shape)
        v = nn.Dense(inner, use_bias=False, name="v")(x).reshape(shape)
        # T5 does NOT scale scores: 1/√d is folded into the init
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) + bias
        if mask is not None:
            s = s + mask
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, N, inner)
        return nn.Dense(cfg.d_model, use_bias=False, name="o")(out)


class _T5FF(nn.Module):
    """Gated-GELU feed forward (T5 v1.1 / UMT5)."""

    config: T5Config

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        g = nn.Dense(cfg.d_ff, use_bias=False, name="wi_0")(x)
        u = nn.Dense(cfg.d_ff, use_bias=False, name="wi_1")(x)
        return nn.Dense(cfg.d_model, use_bias=False, name="wo")(
            nn.gelu(g, approximate=True) * u)


class T5Encoder(nn.Module):
    """tokens [B,N] (+ optional attn_mask [B,N]) → last hidden [B,N,d]."""

    config: T5Config

    @nn.compact
    def __call__(self, tokens: jax.Array,
                 attn_mask: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        B, N = tokens.shape
        x = nn.Embed(cfg.vocab_size, cfg.d_model, name="shared")(tokens)

        pos = jnp.arange(N)
        rel = pos[None, :] - pos[:, None]              # memory - query
        buckets = _rel_bucket(rel, cfg.rel_buckets, cfg.rel_max_distance)
        mask = None
        if attn_mask is not None:
            mask = (1.0 - attn_mask[:, None, None, :].astype(jnp.float32)) * -1e9

        def bias_table(name):
            emb = nn.Embed(cfg.rel_buckets, cfg.num_heads, name=name)
            return emb(buckets).transpose(2, 0, 1)[None]   # [1,H,Nq,Nk]

        shared_bias = None
        for i in range(cfg.num_layers):
            if cfg.per_layer_rel_bias:
                bias = bias_table(f"rel_bias_{i}")
            else:
                if shared_bias is None:
                    shared_bias = bias_table("rel_bias")
                bias = shared_bias
            h = _T5LayerNorm(cfg.layer_norm_eps, name=f"ln_attn_{i}")(x)
            x = x + _T5Attention(cfg, name=f"attn_{i}")(h, bias, mask)
            h = _T5LayerNorm(cfg.layer_norm_eps, name=f"ln_ff_{i}")(x)
            x = x + _T5FF(cfg, name=f"ff_{i}")(h)
        return _T5LayerNorm(cfg.layer_norm_eps, name="final_ln")(x)


@dataclasses.dataclass
class T5Model:
    """Host wrapper: module + params."""

    config: T5Config
    params: Optional[dict] = None

    def __post_init__(self):
        self.module = T5Encoder(self.config)

    def init(self, rng: jax.Array, abstract: bool = False) -> "T5Model":
        from .draw import draw_params

        toks = jnp.zeros((1, self.config.max_len), jnp.int32)
        # abstract: a shape template only (conversion about to replace
        # every leaf — a T5-XXL random init alone is ~19 GB)
        self.params = draw_params(self.module, rng, toks, abstract=abstract)
        return self

    def __call__(self, tokens: jax.Array, attn_mask=None) -> jax.Array:
        from .layers import jit_apply

        return jit_apply(self, self.module)(self.params, tokens, attn_mask)


# ---------------------------------------------------------------------------
# converter (HF T5EncoderModel / UMT5EncoderModel state dicts)
# ---------------------------------------------------------------------------

def convert_t5(sd, template, config: T5Config) -> dict:
    """HF ``T5EncoderModel``/``UMT5EncoderModel`` state dict → params."""
    from .convert import ConversionError, _Filler

    f = _Filler(sd, template["params"])
    f.put("shared.weight", "shared/embedding")
    if "encoder.embed_tokens.weight" in sd:       # tied copy HF also emits
        f.used.add("encoder.embed_tokens.weight")
    for i in range(config.num_layers):
        blk = f"encoder.block.{i}.layer"
        for proj in ("q", "k", "v", "o"):
            f.put(f"{blk}.0.SelfAttention.{proj}.weight",
                  f"attn_{i}/{proj}/kernel",
                  lambda w: np.asarray(w, np.float32).T)
        f.put(f"{blk}.0.layer_norm.weight", f"ln_attn_{i}/weight")
        bias_key = f"{blk}.0.SelfAttention.relative_attention_bias.weight"
        if config.per_layer_rel_bias:
            f.put(bias_key, f"rel_bias_{i}/embedding")
        elif i == 0:
            f.put(bias_key, "rel_bias/embedding")
        for proj in ("wi_0", "wi_1", "wo"):
            f.put(f"{blk}.1.DenseReluDense.{proj}.weight",
                  f"ff_{i}/{proj}/kernel",
                  lambda w: np.asarray(w, np.float32).T)
        f.put(f"{blk}.1.layer_norm.weight", f"ln_ff_{i}/weight")
    f.put("encoder.final_layer_norm.weight", "final_ln/weight")
    tree = f.finish()
    leftover = [k for k in sd if k not in f.used]
    if leftover:
        raise ConversionError(
            f"unconsumed T5 keys: {leftover[:8]}"
            f"{'…' if len(leftover) > 8 else ''}")
    return {"params": tree}


def load_t5_tokenizer(tok_dir=None):
    """SentencePiece tokenizer for T5, loaded via ``transformers`` from
    ``CDT_T5_TOKENIZER_DIR`` (the ``spiece.model``/``tokenizer.json`` every
    T5 distribution ships). Returns None when unavailable — callers fall
    back to hash tokens exactly like the CLIP path."""
    from ..utils import constants

    tok_dir = tok_dir or constants.T5_TOKENIZER_DIR.get()
    if not tok_dir:
        return None
    try:
        from transformers import AutoTokenizer

        return AutoTokenizer.from_pretrained(tok_dir)
    except Exception as e:                        # noqa: BLE001
        from ..utils.logging import log

        log(f"WARNING: T5 tokenizer load failed ({e}); hash fallback in use")
        return None


def t5_token_ids(cfg: T5Config, tok, texts, count: bool = True):
    """Strings → (ids [B,max_len], mask [B,max_len]): SentencePiece when a
    tokenizer is loaded, deterministic hash fallback (with </s> framing so
    masking works) otherwise. ``count=False`` skips the degradation
    counter (cache key-signature tokenization)."""
    if tok is not None:
        enc = tok(list(texts), padding="max_length", truncation=True,
                  max_length=cfg.max_len, return_tensors="np")
        return (jnp.asarray(enc["input_ids"], jnp.int32),
                jnp.asarray(enc["attention_mask"], jnp.int32))
    if count:
        from .clip import _count_hash_tokenization

        _count_hash_tokenization("t5")
    import hashlib

    def fallback(text):
        ids = [int.from_bytes(
            hashlib.blake2s(w.encode(), digest_size=4).digest(),
            "little") % (cfg.vocab_size - 2) + 2
            for w in text.lower().split()][: cfg.max_len - 1]
        ids = ids + [1]                           # </s>
        mask = [1] * len(ids) + [0] * (cfg.max_len - len(ids))
        return ids + [0] * (cfg.max_len - len(ids)), mask

    pairs = [fallback(t) for t in texts]
    return (jnp.asarray([p[0] for p in pairs], jnp.int32),
            jnp.asarray([p[1] for p in pairs], jnp.int32))


class UMT5Conditioner:
    """WAN-class conditioning: UMT5 last-hidden context only (the model
    has no pooled-vector input — ``WanModel`` ignores ``pooled``, which is
    returned as zeros purely for ``TextEncoder.encode`` API parity)."""

    def __init__(self, t5: T5Model, tok=None, pooled_dim: int = 768):
        self.t5 = t5
        self.pooled_dim = pooled_dim
        self.tok = tok if tok is not None else load_t5_tokenizer()
        if self.tok is None:
            from ..utils.logging import log

            log("WARNING: no T5 tokenizer (CDT_T5_TOKENIZER_DIR) — text is "
                "hash-tokenized; conditioning will not reflect the prompt")

    @classmethod
    def init_random(cls, rng: jax.Array, tiny: bool = False,
                    abstract_t5: bool = False) -> "UMT5Conditioner":
        cfg = (T5Config.tiny(per_layer_rel_bias=True) if tiny
               else T5Config.umt5_xxl())
        return cls(T5Model(cfg).init(rng, abstract=abstract_t5))

    def token_signature(self, texts) -> tuple[list, str]:
        """Conditioning-cache key material (cluster/cache): ids+mask and
        the real-vs-hash mode, so a degraded (vocab-less) worker can
        never poison the shared tier."""
        ids, mask = t5_token_ids(self.t5.config, self.tok,
                                 [str(t) for t in texts], count=False)
        return ([ids.tolist(), mask.tolist()],
                f"t5={'sp' if self.tok is not None else 'hash'}")

    @property
    def tokenization_mode(self) -> str:
        return "sp" if self.tok is not None else "hash"

    def encode(self, texts) -> tuple[jax.Array, jax.Array]:
        texts = [str(t) for t in texts]
        ids, mask = t5_token_ids(self.t5.config, self.tok, texts)
        context = self.t5(ids, mask)
        return context, jnp.zeros((len(texts), self.pooled_dim),
                                  context.dtype)


class FluxTextStack:
    """The conditioning pair FLUX checkpoints assume: T5 last-hidden
    context + CLIP-L pooled vector.

    ``encode(texts)`` → ``context [B, T, d_model]``, ``pooled [B, 768]`` —
    drop-in for ``TextEncoder.encode`` so pipelines and graph nodes work
    unchanged (reference analogue: ComfyUI's DualCLIPLoader wiring).
    """

    def __init__(self, t5: T5Model, clip_l, t5_tok=None, clip_tok=None):
        self.t5 = t5
        self.clip_l = clip_l
        self.t5_tok = t5_tok if t5_tok is not None else load_t5_tokenizer()
        if clip_tok is None:
            from .clip import validate_tokenizer_vocab
            from .tokenizer import load_sd_tokenizers

            # tokenize to the TOWER's context length (its position table
            # only covers config.max_len), and refuse a mismatched vocab
            clip_tok, _ = load_sd_tokenizers(max_len=clip_l.config.max_len)
            if clip_tok is not None:
                validate_tokenizer_vocab(clip_tok, clip_l.config, "clip_l")
        self.clip_tok = clip_tok
        from ..utils.logging import log

        if self.t5_tok is None:
            log("WARNING: no T5 tokenizer (CDT_T5_TOKENIZER_DIR) — text is "
                "hash-tokenized; conditioning will not reflect the prompt")
        if self.clip_tok is None:
            log("WARNING: no CLIP vocab at CDT_TOKENIZER_DIR — the pooled "
                "vector is hash-tokenized and will not reflect the prompt")

    @classmethod
    def init_random(cls, rng: jax.Array, tiny: bool = False,
                    abstract_t5: bool = False) -> "FluxTextStack":
        from .clip import CLIPTextConfig, CLIPTextModel

        k1, k2 = jax.random.split(rng)
        t5_cfg = T5Config.tiny() if tiny else T5Config.xxl()
        clip_cfg = CLIPTextConfig.tiny() if tiny else CLIPTextConfig.clip_l()
        return cls(T5Model(t5_cfg).init(k1, abstract=abstract_t5),
                   CLIPTextModel(clip_cfg).init(k2))

    def token_signature(self, texts) -> tuple[list, str]:
        from .clip import tokenize_ids

        texts = [str(t) for t in texts]
        ids, mask = t5_token_ids(self.t5.config, self.t5_tok, texts,
                                 count=False)
        cfg = self.clip_l.config
        toks = tokenize_ids(texts, self.clip_tok, cfg, cfg.eot_token_id,
                            count=False)
        mode = (f"t5={'sp' if self.t5_tok is not None else 'hash'},"
                f"l={'bpe' if self.clip_tok is not None else 'hash'}")
        return [ids.tolist(), mask.tolist(), toks.tolist()], mode

    @property
    def tokenization_mode(self) -> str:
        return ("real" if (self.t5_tok is not None
                           and self.clip_tok is not None) else "hash")

    def encode(self, texts) -> tuple[jax.Array, jax.Array]:
        from .clip import tokenize_ids

        texts = [str(t) for t in texts]
        ids, mask = t5_token_ids(self.t5.config, self.t5_tok, texts)
        context = self.t5(ids, mask)
        cfg = self.clip_l.config
        toks = tokenize_ids(texts, self.clip_tok, cfg, cfg.eot_token_id,
                            tower="clip_l")
        pooled = self.clip_l(toks)["pooled"]
        return context, pooled


class SD3TextStack:
    """SD3-family tri-encoder conditioning (CLIP-L + CLIP-G + T5-XXL).

    SD3's contract (matching sd3's own inference wiring the reference
    inherits via ComfyUI's sd3_clip):

    - ``context`` = sequence concat of the zero-padded CLIP block and the
      T5 block: ``pad(concat_feat(L.penultimate, G.penultimate), d_t5)``
      followed by T5 last-hidden — ``[B, 77 + T5_len, 4096]`` at full
      size;
    - ``pooled`` = ``concat(L.projected, G.projected)`` — ``[B, 2048]``.

    ``encode(texts)`` is drop-in for ``TextEncoder.encode`` so pipelines
    and graph nodes work unchanged.
    """

    def __init__(self, clip_l, clip_g, t5: T5Model, t5_tok=None,
                 tok_l=None, tok_g=None):
        from ..utils.logging import log
        from .clip import validate_tokenizer_vocab
        from .tokenizer import CLIPBPETokenizer, load_sd_tokenizers

        self.clip_l = clip_l
        self.clip_g = clip_g
        self.t5 = t5
        self.t5_tok = t5_tok if t5_tok is not None else load_t5_tokenizer()
        if (tok_l is None) != (tok_g is None):
            # a single explicit tokenizer would crash vocab validation on
            # the None twin (advisor r05) — require the pair, loudly
            raise ValueError(
                "SD3TextStack needs both tok_l and tok_g (or neither, to "
                "auto-load from CDT_TOKENIZER_DIR); got only "
                f"{'tok_l' if tok_g is None else 'tok_g'}")
        if tok_l is None and tok_g is None:
            tok_l, _ = load_sd_tokenizers(max_len=clip_l.config.max_len)
            if tok_l is not None:
                tok_g = CLIPBPETokenizer.from_env(
                    max_len=clip_g.config.max_len, pad_token_id=0)
        self.tok_l, self.tok_g = tok_l, tok_g
        if self.tok_l is not None:
            validate_tokenizer_vocab(self.tok_l, clip_l.config, "clip_l")
            if self.tok_g is None:
                log("WARNING: no tokenizer for the clip_g tower; it "
                    "falls back to hash tokenization")
            else:
                validate_tokenizer_vocab(self.tok_g, clip_g.config,
                                         "clip_g")
        else:
            log("WARNING: no CLIP vocab at CDT_TOKENIZER_DIR — text is "
                "hash-tokenized; conditioning will not reflect the prompt")
        if self.t5_tok is None:
            log("WARNING: no T5 tokenizer (CDT_T5_TOKENIZER_DIR) — the T5 "
                "context block is hash-tokenized")

    @classmethod
    def init_random(cls, rng: jax.Array, tiny: bool = False,
                    abstract_t5: bool = False) -> "SD3TextStack":
        import dataclasses

        from .clip import CLIPTextConfig, CLIPTextModel

        k1, k2, k3 = jax.random.split(rng, 3)
        if tiny:
            # concat widths (16+16) == T5-tiny d_model, projections 8+8
            # == the sd3-tiny preset's pooled_dim
            cfg_l = CLIPTextConfig.tiny(width=16, heads=2, projection_dim=8)
            cfg_g = CLIPTextConfig.tiny(width=16, heads=2, act="gelu",
                                        projection_dim=8)
            t5_cfg = T5Config.tiny()
        else:
            cfg_l = dataclasses.replace(CLIPTextConfig.clip_l(),
                                        projection_dim=768)
            cfg_g = CLIPTextConfig.clip_g()
            t5_cfg = T5Config.xxl()
        return cls(CLIPTextModel(cfg_l).init(k1),
                   CLIPTextModel(cfg_g).init(k2),
                   T5Model(t5_cfg).init(k3, abstract=abstract_t5))

    def token_signature(self, texts) -> tuple[list, str]:
        from .clip import tokenize_ids

        texts = [str(t) for t in texts]
        l_cfg, g_cfg = self.clip_l.config, self.clip_g.config
        toks_l = tokenize_ids(texts, self.tok_l, l_cfg, l_cfg.eot_token_id,
                              count=False)
        toks_g = tokenize_ids(texts, self.tok_g, g_cfg, 0, count=False)
        ids, mask = t5_token_ids(self.t5.config, self.t5_tok, texts,
                                 count=False)
        mode = (f"l={'bpe' if self.tok_l is not None else 'hash'},"
                f"g={'bpe' if self.tok_g is not None else 'hash'},"
                f"t5={'sp' if self.t5_tok is not None else 'hash'}")
        return [toks_l.tolist(), toks_g.tolist(), ids.tolist(),
                mask.tolist()], mode

    @property
    def tokenization_mode(self) -> str:
        return ("real" if (self.tok_l is not None and self.tok_g is not None
                           and self.t5_tok is not None) else "hash")

    def encode(self, texts) -> tuple[jax.Array, jax.Array]:
        from .clip import tokenize_ids

        texts = [str(t) for t in texts]
        l_cfg, g_cfg = self.clip_l.config, self.clip_g.config
        out_l = self.clip_l(tokenize_ids(texts, self.tok_l, l_cfg,
                                         l_cfg.eot_token_id,
                                         tower="clip_l"))
        out_g = self.clip_g(tokenize_ids(texts, self.tok_g, g_cfg, 0,
                                         tower="clip_g"))
        clip_ctx = jnp.concatenate(
            [out_l["penultimate"], out_g["penultimate"]], axis=-1)
        d = self.t5.config.d_model
        if clip_ctx.shape[-1] > d:
            raise ValueError(
                f"CLIP concat width {clip_ctx.shape[-1]} exceeds the T5 "
                f"d_model {d} — the stack's towers are mismatched")
        clip_ctx = jnp.pad(
            clip_ctx, ((0, 0), (0, 0), (0, d - clip_ctx.shape[-1])))
        ids, mask = t5_token_ids(self.t5.config, self.t5_tok, texts)
        t5_ctx = self.t5(ids, mask)
        context = jnp.concatenate(
            [clip_ctx, t5_ctx.astype(clip_ctx.dtype)], axis=1)
        pooled = jnp.concatenate(
            [out_l["projected"], out_g["projected"]], axis=-1)
        return context, pooled
