"""Weight-faithful CLIP text encoders (SDXL/SD1.5 conditioning).

The reference free-rides on ComfyUI's CLIP loaders for conditioning
(SURVEY "external substrate"); this module owns it. Unlike
``models/text.py`` (a generic encoder for random-init benchmarks), these
modules reproduce the *exact* CLIP text-transformer computation so
published checkpoints load and match:

- pre-LN residual blocks with a **causal** attention mask,
- ``quick_gelu`` (CLIP-L) or ``gelu`` (CLIP-G) MLP activation,
- EOT pooling at ``argmax(tokens == eot_token_id)``,
- optional ``text_projection`` (CLIP-G pooled output),
- penultimate-layer hidden states (what SDXL/SD conditioning consumes:
  sgm's FrozenCLIPEmbedder uses ``hidden_states[-2]`` with no final LN).

Numerics are validated against ``transformers.CLIPTextModel`` in
``tests/test_clip.py``.

SDXL's conditioning contract (matching sgm/ComfyUI):
``context = concat(L.penultimate[768], G.penultimate[1280]) = 2048``,
``pooled = G.final EOT @ text_projection = 1280``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

NEG_INF = -1e9


@dataclasses.dataclass(frozen=True)
class CLIPTextConfig:
    vocab_size: int = 49408
    max_len: int = 77
    width: int = 768
    layers: int = 12
    heads: int = 12
    intermediate: int = 3072
    act: str = "quick_gelu"            # CLIP-L; CLIP-G uses "gelu"
    eot_token_id: int = 49407
    projection_dim: int = 0            # 0 = no text_projection head
    layer_norm_eps: float = 1e-5
    dtype: str = "float32"             # conditioning runs once; keep f32

    @classmethod
    def clip_l(cls) -> "CLIPTextConfig":
        """openai/clip-vit-large-patch14 text tower (SD1.5 + SDXL ctx)."""
        return cls()

    @classmethod
    def clip_g(cls) -> "CLIPTextConfig":
        """OpenCLIP bigG-14 text tower (SDXL's second encoder)."""
        return cls(width=1280, layers=32, heads=20, intermediate=5120,
                   act="gelu", projection_dim=1280)

    @classmethod
    def tiny(cls, **kw) -> "CLIPTextConfig":
        base = dict(vocab_size=128, max_len=16, width=32, layers=2, heads=2,
                    intermediate=64, eot_token_id=127)
        base.update(kw)
        return cls(**base)


def quick_gelu(x: jax.Array) -> jax.Array:
    return x * jax.nn.sigmoid(1.702 * x)


class _CLIPAttention(nn.Module):
    config: CLIPTextConfig

    @nn.compact
    def __call__(self, x: jax.Array, mask: jax.Array) -> jax.Array:
        cfg = self.config
        head_dim = cfg.width // cfg.heads
        B, N, _ = x.shape
        q = nn.Dense(cfg.width, name="q_proj")(x)
        k = nn.Dense(cfg.width, name="k_proj")(x)
        v = nn.Dense(cfg.width, name="v_proj")(x)
        q = q.reshape(B, N, cfg.heads, head_dim)
        k = k.reshape(B, N, cfg.heads, head_dim)
        v = v.reshape(B, N, cfg.heads, head_dim)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (head_dim ** 0.5)
        s = s + mask[None, None]
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, N, cfg.width)
        return nn.Dense(cfg.width, name="out_proj")(out)


class _CLIPLayer(nn.Module):
    config: CLIPTextConfig

    @nn.compact
    def __call__(self, x: jax.Array, mask: jax.Array) -> jax.Array:
        cfg = self.config
        # HF/OpenCLIP "gelu" is the exact erf form (flax defaults to tanh)
        act = quick_gelu if cfg.act == "quick_gelu" else (
            lambda x: nn.gelu(x, approximate=False))
        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="ln1")(x)
        x = x + _CLIPAttention(cfg, name="attn")(h, mask)
        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="ln2")(x)
        h = nn.Dense(cfg.intermediate, name="fc1")(h)
        h = nn.Dense(cfg.width, name="fc2")(act(h))
        return x + h


class CLIPTextTransformer(nn.Module):
    """Returns every view SD-family conditioning needs in one pass."""

    config: CLIPTextConfig

    @nn.compact
    def __call__(self, tokens: jax.Array) -> dict[str, jax.Array]:
        cfg = self.config
        B, N = tokens.shape
        x = nn.Embed(cfg.vocab_size, cfg.width, name="tok_emb")(tokens)
        pos = self.param("pos_emb", nn.initializers.normal(0.01),
                         (cfg.max_len, cfg.width))
        x = x + pos[None, :N]
        mask = jnp.triu(jnp.full((N, N), NEG_INF, x.dtype), k=1)

        penultimate = x
        for i in range(cfg.layers):
            if i == cfg.layers - 1:
                penultimate = x            # input of the last layer = output
            x = _CLIPLayer(cfg, name=f"layer_{i}")(x, mask)

        last = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="final_ln")(x)
        eot = jnp.argmax((tokens == cfg.eot_token_id).astype(jnp.int32), axis=1)
        pooled = last[jnp.arange(B), eot]
        out = {"last_hidden": last, "penultimate": penultimate,
               "pooled": pooled}
        if cfg.projection_dim:
            out["projected"] = nn.Dense(cfg.projection_dim, use_bias=False,
                                        name="text_projection")(pooled)
        return out


@dataclasses.dataclass
class CLIPTextModel:
    """Host wrapper: module + params."""

    config: CLIPTextConfig
    params: Optional[dict] = None

    def __post_init__(self):
        self.module = CLIPTextTransformer(self.config)

    def init(self, rng: jax.Array) -> "CLIPTextModel":
        from .draw import draw_params

        toks = jnp.zeros((1, self.config.max_len), jnp.int32)
        self.params = draw_params(self.module, rng, toks)
        return self

    def __call__(self, tokens: jax.Array) -> dict[str, jax.Array]:
        from .layers import jit_apply

        return jit_apply(self, self.module)(self.params, tokens)


class SDXLTextStack:
    """The dual-encoder conditioning stack SDXL checkpoints ship.

    ``encode(tokens_l, tokens_g)`` →
    ``context [B,77,2048]`` (concat of both penultimates) and
    ``pooled [B,1280]`` (G's projected EOT) — matching sgm's
    ``GeneralConditioner`` wiring that the reference inherits via ComfyUI.
    """

    def __init__(self, clip_l: CLIPTextModel, clip_g: CLIPTextModel):
        assert clip_g.config.projection_dim, "CLIP-G needs text_projection"
        self.clip_l = clip_l
        self.clip_g = clip_g

    @classmethod
    def init_random(cls, rng: jax.Array, tiny: bool = False) -> "SDXLTextStack":
        k1, k2 = jax.random.split(rng)
        if tiny:
            cfg_l = CLIPTextConfig.tiny()
            cfg_g = CLIPTextConfig.tiny(width=48, heads=2, act="gelu",
                                        projection_dim=48)
        else:
            cfg_l, cfg_g = CLIPTextConfig.clip_l(), CLIPTextConfig.clip_g()
        return cls(CLIPTextModel(cfg_l).init(k1), CLIPTextModel(cfg_g).init(k2))

    def encode_tokens(self, tokens_l: jax.Array,
                      tokens_g: jax.Array) -> tuple[jax.Array, jax.Array]:
        out_l = self.clip_l(tokens_l)
        out_g = self.clip_g(tokens_g)
        context = jnp.concatenate(
            [out_l["penultimate"], out_g["penultimate"]], axis=-1)
        return context, out_g["projected"]


def validate_tokenizer_vocab(tok, cfg: CLIPTextConfig, name: str) -> None:
    """Refuse a CDT_TOKENIZER_DIR vocab that does not match a tower's
    config: a mismatch would not fail loudly downstream — out-of-range ids
    CLAMP in ``nn.Embed`` and a wrong EOT id silently pools position 0."""
    if tok.eot_id != cfg.eot_token_id or len(tok.vocab) > cfg.vocab_size:
        raise ValueError(
            f"CDT_TOKENIZER_DIR vocab does not match the {name} tower: "
            f"vocab has {len(tok.vocab)} entries with EOT id {tok.eot_id}, "
            f"config expects vocab_size<={cfg.vocab_size} / "
            f"eot_token_id={cfg.eot_token_id}")


def _count_hash_tokenization(tower: str) -> None:
    """Export the hash-fallback usage as telemetry: the boot-time warning
    is one log line on one host, but fleet-wide conditioning degradation
    must be visible in ``/distributed/metrics``
    (``cdt_hash_tokenization_total{tower}``)."""
    try:
        from .. import telemetry
        from ..telemetry import metrics as _tm

        if telemetry.enabled():
            _tm.HASH_TOKENIZATION.labels(tower=tower).inc()
    except Exception:  # noqa: BLE001 — telemetry is never load-bearing
        pass


def tokenize_ids(texts, tok, cfg, pad_id: int, tower: str = "clip",
                 count: bool = True) -> jax.Array:
    """Strings → [B, max_len] int32 ids: real BPE when a tokenizer is
    loaded, deterministic hash fallback (correct SOT/EOT framing so EOT
    pooling works) otherwise. ``count=False`` skips the degradation
    counter — key-signature tokenization must not double-count the
    encode that follows it."""
    if tok is not None:
        return jnp.asarray([tok.encode(t) for t in texts], jnp.int32)
    if count:
        _count_hash_tokenization(tower)
    import hashlib

    def fallback(text: str) -> list[int]:
        ids = []
        for w in text.lower().split():
            h = hashlib.blake2s(w.encode(), digest_size=4).digest()
            ids.append(int.from_bytes(h, "little")
                       % (cfg.vocab_size - 2) + 1)
        ids = ids[: cfg.max_len - 2]
        out = [0] + ids + [cfg.eot_token_id]
        return out + [pad_id] * (cfg.max_len - len(out))
    return jnp.asarray([fallback(t) for t in texts], jnp.int32)


class CLIPConditioner:
    """``TextEncoder``-compatible adapter (strings → context, pooled) over
    the weight-faithful CLIP stack, so graph nodes (``CLIPTextEncode``)
    work unchanged whichever encoder a bundle carries.

    Tokenizers come from ``CDT_TOKENIZER_DIR`` (standard vocab.json +
    merges.txt). Without one, a deterministic hash fallback keeps the
    stack runnable (correct SOT/EOT framing so pooling works) — outputs
    are then *not* meaningful text conditioning, and a warning says so.
    """

    def __init__(self, stack, kind: str = "sdxl", tok_l=None, tok_g=None):
        from ..utils.logging import log
        from .tokenizer import load_sd_tokenizers

        self.stack = stack
        self.kind = kind
        if kind == "sdxl" and (tok_l is None) != (tok_g is None):
            # a single explicit tokenizer would crash vocab validation on
            # the None twin (advisor r05) — require the pair, loudly
            raise ValueError(
                "CLIPConditioner(kind='sdxl') needs both tok_l and tok_g "
                "(or neither, to auto-load from CDT_TOKENIZER_DIR); got "
                f"only {'tok_l' if tok_g is None else 'tok_g'}")
        if tok_l is None and tok_g is None:
            # tokenize each tower to ITS context length — the position
            # tables only cover cfg.max_len, so a 77-padded sequence would
            # not even shape-check against a shorter tower (e.g. the tiny
            # test configs at max_len=16)
            from .tokenizer import CLIPBPETokenizer

            cfg_l = stack.clip_l.config if kind == "sdxl" else stack.config
            tok_l, _ = load_sd_tokenizers(max_len=cfg_l.max_len)
            if kind == "sdxl" and tok_l is not None:
                tok_g = CLIPBPETokenizer.from_env(
                    max_len=stack.clip_g.config.max_len, pad_token_id=0)
        self.tok_l, self.tok_g = tok_l, tok_g
        if self.tok_l is not None:
            towers = [("clip_l", self.tok_l,
                       stack.clip_l.config if kind == "sdxl" else stack.config)]
            if kind == "sdxl":
                towers.append(("clip_g", self.tok_g, stack.clip_g.config))
            for name, tok, cfg in towers:
                if tok is None:
                    # env-derived asymmetry (vocab present for one tower
                    # only): that tower falls back to hash tokenization —
                    # say so instead of crashing on None.eot_id
                    log(f"WARNING: no tokenizer for the {name} tower; "
                        "it falls back to hash tokenization")
                    continue
                validate_tokenizer_vocab(tok, cfg, name)
        if self.tok_l is None:
            log("WARNING: no CLIP vocab at CDT_TOKENIZER_DIR — text is "
                "hash-tokenized; conditioning will not reflect the prompt")

    def _ids(self, texts, tok, cfg, pad_id: int, tower: str):
        return tokenize_ids(texts, tok, cfg, pad_id, tower=tower)

    def token_signature(self, texts) -> tuple[list, str]:
        """(token ids per tower, real-vs-hash mode) — the conditioning
        cache's key material (``cluster/cache/conditioning.py``). Keying
        on the MODE is load-bearing: a worker whose vocab failed to load
        computes different keys than a healthy one, so its degraded
        embeddings can never poison the shared tier."""
        texts = [str(t) for t in texts]
        if self.kind == "sdxl":
            l_cfg = self.stack.clip_l.config
            g_cfg = self.stack.clip_g.config
            sig = [
                tokenize_ids(texts, self.tok_l, l_cfg, l_cfg.eot_token_id,
                             count=False).tolist(),
                tokenize_ids(texts, self.tok_g, g_cfg, 0,
                             count=False).tolist(),
            ]
            mode = (f"l={'bpe' if self.tok_l is not None else 'hash'},"
                    f"g={'bpe' if self.tok_g is not None else 'hash'}")
            return sig, mode
        cfg = self.stack.config
        sig = [tokenize_ids(texts, self.tok_l, cfg, cfg.eot_token_id,
                            count=False).tolist()]
        return sig, f"l={'bpe' if self.tok_l is not None else 'hash'}"

    @property
    def tokenization_mode(self) -> str:
        """Degradation summary for the result-cache key: "bpe" when every
        tower has a real tokenizer, "hash" otherwise."""
        toks = [self.tok_l] + ([self.tok_g] if self.kind == "sdxl" else [])
        return "bpe" if all(t is not None for t in toks) else "hash"

    def encode(self, texts) -> tuple[jax.Array, jax.Array]:
        texts = [str(t) for t in texts]
        if self.kind == "sdxl":
            l_cfg = self.stack.clip_l.config
            g_cfg = self.stack.clip_g.config
            toks_l = self._ids(texts, self.tok_l, l_cfg, l_cfg.eot_token_id,
                               tower="clip_l")
            toks_g = self._ids(texts, self.tok_g, g_cfg, 0, tower="clip_g")
            return self.stack.encode_tokens(toks_l, toks_g)
        cfg = self.stack.config
        toks = self._ids(texts, self.tok_l, cfg, cfg.eot_token_id,
                         tower="clip_l")
        out = self.stack(toks)
        # SD1.5 convention: final hidden states + EOT pooled
        return out["last_hidden"], out["pooled"]
