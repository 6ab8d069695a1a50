"""Text conditioning encoders.

The reference obtains CLIP conditioning from ComfyUI's loader nodes; this
module supplies a native flax encoder with the same *interface* (sequence
context + pooled vector) so pipelines are weight-source-agnostic: load real
CLIP weights into it when available, or run random-init for benchmarks.

Tokenization is a deterministic stable-hash fallback (zero-egress
environments have no vocab files); swap in a real tokenizer by passing
``tokenize_fn``.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from .layers import Attention


def _stable_hash_token(word: str, vocab_size: int) -> int:
    h = hashlib.blake2s(word.encode("utf-8"), digest_size=4).digest()
    return int.from_bytes(h, "little") % (vocab_size - 2) + 2   # 0=pad, 1=eot


def hash_tokenize(text: str, max_len: int, vocab_size: int) -> list[int]:
    toks = [_stable_hash_token(w, vocab_size) for w in text.lower().split()]
    toks = toks[: max_len - 1] + [1]
    return toks + [0] * (max_len - len(toks))


@dataclasses.dataclass(frozen=True)
class TextEncoderConfig:
    vocab_size: int = 49408
    max_len: int = 77
    width: int = 768
    layers: int = 4
    heads: int = 12
    output_dim: int = 2048        # cross-attention context dim (SDXL: 2048)
    pooled_dim: int = 1280        # pooled vector dim (SDXL: 1280)
    dtype: str = "bfloat16"

    @classmethod
    def tiny(cls) -> "TextEncoderConfig":
        return cls(vocab_size=1024, max_len=16, width=32, layers=1, heads=2,
                   output_dim=32, pooled_dim=16)


class TextTransformer(nn.Module):
    config: TextEncoderConfig

    @nn.compact
    def __call__(self, tokens: jax.Array) -> tuple[jax.Array, jax.Array]:
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        x = nn.Embed(cfg.vocab_size, cfg.width, dtype=dt, name="tok_emb")(tokens)
        pos = self.param(
            "pos_emb", nn.initializers.normal(0.01), (cfg.max_len, cfg.width)
        )
        x = x + pos[None, : x.shape[1]].astype(dt)
        head_dim = cfg.width // cfg.heads
        for i in range(cfg.layers):
            x = x + Attention(cfg.heads, head_dim, dt, name=f"attn_{i}")(
                nn.LayerNorm(dtype=dt)(x)
            )
            h = nn.LayerNorm(dtype=dt)(x)
            h = nn.Dense(cfg.width * 4, dtype=dt, name=f"mlp_{i}_up")(h)
            x = x + nn.Dense(cfg.width, dtype=dt, name=f"mlp_{i}_down")(nn.gelu(h))
        x = nn.LayerNorm(dtype=dt, name="final_ln")(x)
        context = nn.Dense(cfg.output_dim, dtype=jnp.float32, name="ctx_proj")(
            x.astype(jnp.float32)
        )
        # pool at the EOT position (token id 1), CLIP-style
        eot = jnp.argmax((tokens == 1).astype(jnp.int32), axis=1)
        pooled_src = x[jnp.arange(x.shape[0]), eot]
        pooled = nn.Dense(cfg.pooled_dim, dtype=jnp.float32, name="pool_proj")(
            pooled_src.astype(jnp.float32)
        )
        return context, pooled


class TextEncoder:
    """Host-facing wrapper: strings → (context [B,N,D], pooled [B,P])."""

    def __init__(
        self,
        config: TextEncoderConfig,
        params=None,
        tokenize_fn: Optional[Callable[[str], Sequence[int]]] = None,
    ):
        self.config = config
        self.module = TextTransformer(config)
        self.params = params
        # tokenization mode for the conditioning cache key
        # (cluster/cache/conditioning.py): this encoder hash-tokenizes BY
        # DESIGN (random-init benchmark stack), which is not the degraded
        # "hash" fallback of the real CLIP/T5 stacks — hence the distinct
        # mode name, so its entries may still persist
        self._tokenize_mode = "custom" if tokenize_fn else "hash-native"
        self._tokenize = tokenize_fn or (
            lambda s: hash_tokenize(s, config.max_len, config.vocab_size)
        )

    def init(self, rng: jax.Array) -> "TextEncoder":
        from .draw import draw_params

        tokens = jnp.zeros((1, self.config.max_len), jnp.int32)
        self.params = draw_params(self.module, rng, tokens)
        return self

    def tokenize(self, texts: Sequence[str]) -> jax.Array:
        return jnp.asarray([list(self._tokenize(t)) for t in texts], jnp.int32)

    def token_signature(self, texts: Sequence[str]) -> tuple[list, str]:
        """(token ids as nested lists, tokenization mode) — the
        conditioning cache's key material (cluster/cache)."""
        return ([list(self._tokenize(str(t))) for t in texts],
                self._tokenize_mode)

    def encode(self, texts: Sequence[str]) -> tuple[jax.Array, jax.Array]:
        from .layers import jit_apply

        return jit_apply(self, self.module)(self.params,
                                            self.tokenize(texts))
