"""Named model registry: checkpoint name → assembled pipeline stack.

The reference resolves model names through ComfyUI's ``folder_paths`` and
ships them to workers by name (``nodes/utilities.py:164-224``,
``DistributedModelName``). Here a name maps to (architecture preset,
optional orbax checkpoint dir). Without a checkpoint the stack is
random-initialized — enough for benchmarks, tests, and architecture work;
drop real weights into the checkpoint dir to get real outputs.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Callable, Optional

import jax

from ..utils.exceptions import ValidationError
from ..utils.logging import log
from .text import TextEncoder, TextEncoderConfig
from .unet import UNetConfig, init_unet
from .vae import AutoencoderKL, VAEConfig


@dataclasses.dataclass(frozen=True)
class ModelPreset:
    name: str
    unet: "UNetConfig | None"
    vae: VAEConfig
    text: TextEncoderConfig
    sample_hw: tuple[int, int] = (128, 128)   # init-time latent H,W
    dit: "object | None" = None               # DiTConfig for flow models
    video: "object | None" = None             # VideoDiTConfig for t2v models
    clip: "str | None" = None   # real text stack: "sdxl" | "clip-l" | "flux" (T5+CLIP-L)
    # WAN-2.2 dual-expert (MoE) models: sigma boundary between the
    # high-noise and low-noise expert DiTs (t2v 0.875, i2v 0.9); None =
    # single-expert
    moe_boundary: "float | None" = None
    # dtype the denoiser's float weights are HELD in on the device (None =
    # as initialised/converted, float32). The UNets compute in bfloat16
    # whatever they are held in, and XLA hoists the per-use casts out of
    # the sampler loop: held in float32, SDXL's segment program is 9.57 GiB
    # of arguments plus 4.85 GiB of temporaries (the bf16 copy) on a 16 GB
    # chip; held in bfloat16 it is 4.79 + 0.56 (docs/weights.md). The tiny
    # test presets stay float32: their parity tolerances are float32's.
    param_dtype: "str | None" = None
    # config of a language model (models/llm_hybrid.py, llm_motif.py,
    # llm_kimi.py, llm_jamba.py, llm_trinity.py, llm_longcat.py; its
    # ``.model`` gives the functions): such a preset has no denoiser, VAE
    # or text tower, and is loaded by LLMLoader
    llm: "object | None" = None

    @property
    def kind(self) -> str:
        if self.llm is not None:
            return "llm"
        if self.video is not None:
            return "video"
        return "dit" if self.dit is not None else "unet"


def _flux_preset():
    from .dit import DiTConfig

    return ModelPreset(
        "flux", unet=None,
        vae=VAEConfig(latent_channels=16, scaling_factor=0.3611,
                      shift_factor=0.1159),
        text=TextEncoderConfig(output_dim=4096, pooled_dim=768),
        sample_hw=(32, 32), dit=DiTConfig.flux(), clip="flux")


def _flux_tiny_preset():
    from .dit import DiTConfig

    return ModelPreset(
        "flux-tiny", unet=None, vae=VAEConfig.tiny(),
        text=TextEncoderConfig.tiny(),
        sample_hw=(8, 8), dit=DiTConfig.tiny())


def _sd3_medium_preset():
    from .dit import DiTConfig

    # SD3's 16-ch KL-VAE (downscale 8); conditioning = CLIP-L/G + T5-XXL
    # via the sd3 tri-encoder stack (build_clip_stack kind="sd3")
    return ModelPreset(
        "sd3-medium", unet=None,
        vae=VAEConfig(latent_channels=16, scaling_factor=1.5305,
                      shift_factor=0.0609),
        text=TextEncoderConfig(output_dim=4096, pooled_dim=2048),
        sample_hw=(128, 128), dit=DiTConfig.sd3_medium(), clip="sd3")


def _sd35_large_preset():
    import dataclasses as _dc

    from .dit import DiTConfig

    base = _sd3_medium_preset()
    return _dc.replace(base, name="sd35-large", dit=DiTConfig.sd35_large())


def _sd3_tiny_preset():
    from .dit import DiTConfig

    return ModelPreset(
        "sd3-tiny", unet=None, vae=VAEConfig.tiny(),
        text=TextEncoderConfig.tiny(), sample_hw=(8, 8),
        dit=DiTConfig.sd3_tiny(), clip="sd3")


def _wan_preset():
    from .wan import WanConfig
    from .wan_vae import WanVAEConfig

    # WAN t2v (exact published architecture): 16-ch video latents from
    # the 3D causal VAE (4× temporal compression), UMT5-width context
    return ModelPreset(
        "wan", unet=None, vae=WanVAEConfig.wan(),
        text=TextEncoderConfig(output_dim=4096, pooled_dim=768),
        sample_hw=(60, 104),             # 480×832 / 8
        video=WanConfig.wan_14b(), clip="umt5")


def _wan_tiny_preset():
    from .wan import WanConfig

    return ModelPreset(
        "wan-tiny", unet=None, vae=VAEConfig.tiny(),
        text=TextEncoderConfig.tiny(),
        sample_hw=(8, 8), video=WanConfig.tiny())


def _wan_i2v_preset():
    from .wan import WanConfig
    from .wan_vae import WanVAEConfig

    # WAN 2.2-style i2v: first frame conditions via latent concat —
    # in_channels 36 = 16 noise + 4 mask (one per compressed pixel
    # frame) + 16 conditioning latents; no CLIP-vision branch
    return ModelPreset(
        "wan-i2v", unet=None, vae=WanVAEConfig.wan(),
        text=TextEncoderConfig(output_dim=4096, pooled_dim=768),
        sample_hw=(60, 104),
        video=dataclasses.replace(WanConfig.wan_14b(), in_channels=36),
        clip="umt5")


def _wan_i2v_tiny_preset():
    from .wan import WanConfig
    from .wan_vae import WanVAEConfig

    # tiny arithmetic: 4 noise + 2 mask (2× temporal VAE) + 4 cond = 10
    return ModelPreset(
        "wan-i2v-tiny", unet=None, vae=WanVAEConfig.tiny(),
        text=TextEncoderConfig.tiny(), sample_hw=(8, 8),
        video=WanConfig.tiny(in_channels=10))


def _wan_tiny_3d_preset():
    from .wan import WanConfig
    from .wan_vae import WanVAEConfig

    # tiny real-geometry stack: 3D causal VAE (2× temporal here) + WAN
    # transformer — the full video architecture at test scale
    return ModelPreset(
        "wan-tiny-3d", unet=None, vae=WanVAEConfig.tiny(),
        text=TextEncoderConfig.tiny(),
        sample_hw=(8, 8), video=WanConfig.tiny())


def _wan22_t2v_preset():
    from .wan import WanConfig
    from .wan_vae import WanVAEConfig

    # WAN-2.2 14B t2v IS a two-expert model: high-noise + low-noise DiTs
    # switched at timestep boundary 0.875·1000 (the published release
    # ships two transformer safetensors). Same architecture per expert as
    # wan-14b; the pipeline runs the sigma ladder in two segments.
    return ModelPreset(
        "wan-2.2-t2v", unet=None, vae=WanVAEConfig.wan(),
        text=TextEncoderConfig(output_dim=4096, pooled_dim=768),
        sample_hw=(60, 104),
        video=WanConfig.wan_14b(), clip="umt5", moe_boundary=0.875)


def _wan22_tiny_preset():
    from .wan import WanConfig

    return ModelPreset(
        "wan-2.2-tiny", unet=None, vae=VAEConfig.tiny(),
        text=TextEncoderConfig.tiny(),
        sample_hw=(8, 8), video=WanConfig.tiny(), moe_boundary=0.875)


def _wan_mmdit_preset():
    from .video_dit import VideoDiTConfig

    # the generic MMDiT-over-frames stack (pre-WAN-parity architecture,
    # kept for from-scratch work and as the video-sp reference design)
    return ModelPreset(
        "video-mmdit", unet=None,
        vae=VAEConfig(latent_channels=16, scaling_factor=0.3611),
        text=TextEncoderConfig(output_dim=4096, pooled_dim=768),
        sample_hw=(60, 104), video=VideoDiTConfig.wan())


def _llm_preset(name: str, family: str, tiny: bool = False):
    """A language model of ``family``: at its published widths (this
    chip's share, or the whole model where it fits), or its tiny float32
    form for the CPU."""
    if family == "sala":
        from .llm_sala import SalaConfig

        share = SalaConfig.tiny if tiny else SalaConfig.sala_cut
    elif family == "longcat":
        from .llm_longcat import LongcatConfig

        share = LongcatConfig.tiny if tiny else LongcatConfig.longcat_share
    elif family == "trinity":
        from .llm_trinity import TrinityConfig

        share = TrinityConfig.tiny if tiny else TrinityConfig.trinity_share
    elif family == "jamba":
        from .llm_jamba import JambaConfig

        share = JambaConfig.tiny if tiny else JambaConfig.jamba2_3b
    elif family == "motif":
        from .llm_motif import MotifConfig

        share = MotifConfig.tiny if tiny else MotifConfig.motif_share
    elif family == "kimi":
        from .llm_kimi import KimiConfig

        share = KimiConfig.tiny if tiny else KimiConfig.kimi_share
    else:
        from .llm_hybrid import LLMConfig

        share = LLMConfig.tiny if tiny else LLMConfig.ling_flash_share
    return ModelPreset(name, unet=None, vae=None, text=None, llm=share())


PRESETS: dict[str, ModelPreset] = {
    "sdxl": ModelPreset("sdxl", UNetConfig.sdxl(), VAEConfig.sdxl(),
                        TextEncoderConfig(), clip="sdxl",
                        param_dtype="bfloat16"),
    "sd15": ModelPreset("sd15", UNetConfig.sd15(),
                        VAEConfig(scaling_factor=0.18215),
                        TextEncoderConfig(output_dim=768, pooled_dim=768),
                        clip="clip-l", param_dtype="bfloat16"),
    "tiny": ModelPreset("tiny", UNetConfig.tiny(), VAEConfig.tiny(),
                        TextEncoderConfig.tiny(), sample_hw=(8, 8)),
    "flux": _flux_preset(),
    "flux-tiny": _flux_tiny_preset(),
    "sd3-medium": _sd3_medium_preset(),
    "sd35-large": _sd35_large_preset(),
    "sd3-tiny": _sd3_tiny_preset(),
    "wan": _wan_preset(),
    "wan-tiny": _wan_tiny_preset(),
    "wan-tiny-3d": _wan_tiny_3d_preset(),
    "wan-i2v": _wan_i2v_preset(),
    "wan-i2v-tiny": _wan_i2v_tiny_preset(),
    "wan-2.2-t2v": _wan22_t2v_preset(),
    "wan-2.2-tiny": _wan22_tiny_preset(),
    "video-mmdit": _wan_mmdit_preset(),
    "ling-3.0-flash-vl": _llm_preset("ling-3.0-flash-vl", "hybrid"),
    "ling-tiny": _llm_preset("ling-tiny", "hybrid", tiny=True),
    "motif-3-beta": _llm_preset("motif-3-beta", "motif"),
    "motif-tiny": _llm_preset("motif-tiny", "motif", tiny=True),
    "kimi-k2.6": _llm_preset("kimi-k2.6", "kimi"),
    "kimi-tiny": _llm_preset("kimi-tiny", "kimi", tiny=True),
    "ai21-jamba2-3b": _llm_preset("ai21-jamba2-3b", "jamba"),
    "jamba-tiny": _llm_preset("jamba-tiny", "jamba", tiny=True),
    "trinity-large-preview": _llm_preset("trinity-large-preview", "trinity"),
    "trinity-tiny": _llm_preset("trinity-tiny", "trinity", tiny=True),
    "longcat-flash-omni": _llm_preset("longcat-flash-omni", "longcat"),
    "longcat-tiny": _llm_preset("longcat-tiny", "longcat", tiny=True),
    "minicpm-sala": _llm_preset("minicpm-sala", "sala"),
    "sala-tiny": _llm_preset("sala-tiny", "sala", tiny=True),
}


def _weights_tag(ckpt: "Path | None", seed: int = 0) -> str:
    """Weights-provenance tag the cache keys carry: random-init weights
    are pinned to (seed, jax version) — deterministic per jax build
    only; checkpoint-backed ones to the checkpoint path + mtime, so
    swapping weights in place invalidates the shared tiers naturally."""
    if ckpt is None:
        import jax

        return f"seed{seed}:jax{jax.__version__}"
    try:
        return f"ckpt:{Path(ckpt).name}:{int(Path(ckpt).stat().st_mtime)}"
    except OSError:
        return f"ckpt:{ckpt}"


def _encoder_identity(preset_name: str, stack: str, ckpt: "Path | None",
                      seed: int = 0) -> str:
    """Identity string the conditioning cache keys on
    (``cluster/cache/conditioning.py``)."""
    return f"{preset_name}/{stack}/{_weights_tag(ckpt, seed)}"


class ModelBundle:
    """Loaded stack: pipeline + text encoder, built lazily and cached."""

    def __init__(self, preset: ModelPreset, checkpoint_dir: Optional[Path] = None,
                 seed: int = 0, abstract_core: bool = False):
        """``abstract_core=True`` builds the core model's params as a
        ShapeDtypeStruct template instead of random weights — for
        conversion flows where every leaf is about to be overwritten
        (a FLUX-size random init alone is ~48 GB of wasted fp32)."""
        self.preset = preset
        self.clip_stack = None      # built lazily (real-weight path only)
        self._weights_source = None   # set by the checkpoint loaders
        self._init_seed = int(seed)
        k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
        img_hw = (preset.sample_hw[0] * preset.vae.downscale,
                  preset.sample_hw[1] * preset.vae.downscale)
        from .wan_vae import WanVAE3D, WanVAEConfig

        if isinstance(preset.vae, WanVAEConfig):
            vae = WanVAE3D(preset.vae).init(k2, frames=5, image_hw=img_hw)
        else:
            vae = AutoencoderKL(preset.vae).init(k2, image_hw=img_hw)
        self.text_encoder = TextEncoder(preset.text).init(k3)
        if preset.kind == "video":
            from ..diffusion.pipeline_video import VideoPipeline
            from .wan import WanConfig, init_wan

            if isinstance(preset.video, WanConfig):
                model, params = init_wan(
                    preset.video, k1,
                    sample_fhw=(5, *preset.sample_hw),
                    context_len=preset.text.max_len, abstract=abstract_core)
            else:
                from .video_dit import init_video_dit

                model, params = init_video_dit(
                    preset.video, k1,
                    sample_fhw=(5, *preset.sample_hw),
                    context_len=preset.text.max_len, abstract=abstract_core)
            params_low = None
            if preset.moe_boundary is not None:
                if not isinstance(preset.video, WanConfig):
                    raise ValidationError(
                        f"preset {preset.name!r}: moe_boundary is only "
                        "supported for WAN-architecture video models")
                # the low-noise expert is a SECOND full DiT of the same
                # architecture (WAN-2.2's high/low pair)
                _, params_low = init_wan(
                    preset.video, jax.random.fold_in(k1, 1),
                    sample_fhw=(5, *preset.sample_hw),
                    context_len=preset.text.max_len,
                    abstract=abstract_core)
            self.pipeline = VideoPipeline(
                model, params, vae, dit_params_low=params_low,
                expert_boundary=preset.moe_boundary)
        elif preset.kind == "dit":
            from ..diffusion.pipeline_flow import FlowPipeline
            from .dit import init_dit

            model, params = init_dit(preset.dit, k1,
                                     sample_hw=preset.sample_hw,
                                     context_len=preset.text.max_len,
                                     abstract=abstract_core)
            self.pipeline = FlowPipeline(model, params, vae)
        else:
            from ..diffusion.pipeline import Txt2ImgPipeline

            model, params = init_unet(
                preset.unet, k1,
                sample_shape=(*preset.sample_hw, preset.unet.in_channels),
                context_len=preset.text.max_len, abstract=abstract_core,
                param_dtype=preset.param_dtype,
            )
            self.pipeline = Txt2ImgPipeline(model, params, vae)
        if checkpoint_dir is not None:
            p = Path(checkpoint_dir)
            hi = p.parent / f"{p.name}.high.safetensors"
            lo = p.parent / f"{p.name}.low.safetensors"
            # NOT with_suffix: dotted preset names ("wan-2.2-t2v") would
            # have ".2-t2v" treated as the suffix and silently miss
            single = p.parent / f"{p.name}.safetensors"
            if p.is_dir():
                self._load_checkpoint(p)
            elif preset.moe_boundary is not None and hi.is_file() \
                    and lo.is_file():
                # WAN-2.2 releases ship TWO transformer files; drop them
                # as `<name>.high.safetensors` + `<name>.low.safetensors`
                self.load_safetensors_moe(hi, lo)
            elif preset.moe_boundary is not None and (hi.is_file()
                                                      or lo.is_file()):
                # one expert present, one missing/misnamed: serving random
                # weights for the other expert would generate noise with
                # no diagnostic
                missing = lo if hi.is_file() else hi
                raise ValidationError(
                    f"dual-expert checkpoint incomplete: {missing} not "
                    "found (need both .high.safetensors and "
                    ".low.safetensors)")
            elif single.is_file():
                # drop `<name>.safetensors` next to the orbax dirs and the
                # published checkpoint converts on first load
                self.load_safetensors_checkpoint(single)
        self._stamp_text_encoder()

    def _stamp_text_encoder(self) -> None:
        """Give the active text encoder its conditioning-cache identity
        (``cluster/cache/conditioning.py``). Re-stamped whenever the
        encoder object OR the weights behind it change (clip-stack
        build, every checkpoint loader, standalone text-encoder files);
        LoRA-patched clones are deliberately NOT stamped — an
        unidentified encoder is never cached."""
        stack = self.preset.clip if self.clip_stack is not None else "text"
        self.text_encoder._cdt_encoder_id = _encoder_identity(
            self.preset.name, stack or "text", self._weights_source,
            seed=self._init_seed)

    def text_tower(self) -> str:
        """Which stack encodes prompts: the preset's real one once a
        checkpoint built it (``sdxl`` = CLIP-L + bigG, ...), else the
        random-weight stand-in ``TextEncoder``."""
        return (self.preset.clip if self.clip_stack is not None
                else "stand-in")

    def weights_identity(self) -> str:
        """Provenance of this bundle's CORE (denoiser) weights — the
        result-cache key carries it so an in-place checkpoint swap (same
        ``ckpt_name``, new bytes, new mtime) can never serve a stale
        persisted image (``cluster/frontdoor/microbatch.py``)."""
        return f"{self.preset.name}/{_weights_tag(self._weights_source, self._init_seed)}"

    @property
    def kind(self) -> str:
        return self.preset.kind

    def _core_params(self):
        if self.kind in ("dit", "video"):
            return self.pipeline.dit_params
        return self.pipeline.unet_params

    def _set_core_params(self, params) -> None:
        if self.kind in ("dit", "video"):
            self.pipeline.dit_params = params
        else:
            self.pipeline.unet_params = params

    def build_clip_stack(self, tiny: bool = False,
                         abstract_t5: bool = False):
        """Instantiate the weight-faithful text stack for this preset and
        swap the bundle's text encoder to it (``models/clip.py`` /
        ``models/t5.py``). ``abstract_t5=True`` leaves the (XXL-size) T5
        params as a ShapeDtypeStruct template for callers about to
        restore or convert real weights over them."""
        from .clip import (CLIPConditioner, CLIPTextConfig, CLIPTextModel,
                           SDXLTextStack)

        if self.clip_stack is not None:
            return self.clip_stack
        kind = self.preset.clip
        if kind is None:
            raise ValidationError(
                f"preset {self.preset.name!r} has no real-CLIP stack")
        key = jax.random.key(0)
        if kind == "sdxl":
            self.clip_stack = SDXLTextStack.init_random(key, tiny=tiny)
        elif kind == "flux":
            from .t5 import FluxTextStack

            self.clip_stack = FluxTextStack.init_random(
                key, tiny=tiny, abstract_t5=abstract_t5)
            self.text_encoder = self.clip_stack    # encode()-compatible
            self._stamp_text_encoder()
            return self.clip_stack
        elif kind == "umt5":
            from .t5 import UMT5Conditioner

            self.clip_stack = UMT5Conditioner.init_random(
                key, tiny=tiny, abstract_t5=abstract_t5)
            self.text_encoder = self.clip_stack
            self._stamp_text_encoder()
            return self.clip_stack
        elif kind == "sd3":
            from .t5 import SD3TextStack

            self.clip_stack = SD3TextStack.init_random(
                key, tiny=tiny, abstract_t5=abstract_t5)
            self.text_encoder = self.clip_stack
            self._stamp_text_encoder()
            return self.clip_stack
        else:
            cfg = CLIPTextConfig.tiny() if tiny else CLIPTextConfig.clip_l()
            self.clip_stack = CLIPTextModel(cfg).init(key)
        self.text_encoder = CLIPConditioner(self.clip_stack, kind=kind)
        self._stamp_text_encoder()
        return self.clip_stack

    def _state_entries(self) -> dict:
        state = {
            "core": self._core_params(),
            "vae_enc": self.pipeline.vae.enc_params,
            "vae_dec": self.pipeline.vae.dec_params,
        }
        if getattr(self.pipeline, "dit_params_low", None) is not None:
            state["core_low"] = self.pipeline.dit_params_low
        if self.clip_stack is not None:
            if self.preset.clip == "sdxl":
                state["clip_l"] = self.clip_stack.clip_l.params
                state["clip_g"] = self.clip_stack.clip_g.params
            elif self.preset.clip == "flux":
                state["clip_l"] = self.clip_stack.clip_l.params
                state["t5"] = self.clip_stack.t5.params
            elif self.preset.clip == "sd3":
                state["clip_l"] = self.clip_stack.clip_l.params
                state["clip_g"] = self.clip_stack.clip_g.params
                state["t5"] = self.clip_stack.t5.params
            elif self.preset.clip == "umt5":
                state["t5"] = self.clip_stack.t5.params
            else:
                state["clip_l"] = self.clip_stack.params
        else:
            state["text"] = self.text_encoder.params
        return state

    def _apply_entries(self, restored: dict) -> None:
        self._set_core_params(restored["core"])
        if "core_low" in restored:
            self.pipeline.dit_params_low = restored["core_low"]
        self.pipeline.vae.enc_params = restored["vae_enc"]
        self.pipeline.vae.dec_params = restored["vae_dec"]
        if "clip_l" in restored:
            if self.preset.clip == "sdxl":
                self.clip_stack.clip_l.params = restored["clip_l"]
                self.clip_stack.clip_g.params = restored["clip_g"]
            elif self.preset.clip == "flux":
                self.clip_stack.clip_l.params = restored["clip_l"]
                self.clip_stack.t5.params = restored["t5"]
            elif self.preset.clip == "sd3":
                self.clip_stack.clip_l.params = restored["clip_l"]
                self.clip_stack.clip_g.params = restored["clip_g"]
                self.clip_stack.t5.params = restored["t5"]
            else:
                self.clip_stack.params = restored["clip_l"]
        elif "t5" in restored:                     # umt5-only stack
            self.clip_stack.t5.params = restored["t5"]
        if "text" in restored:
            self.text_encoder.params = restored["text"]

    def _load_checkpoint(self, ckpt: Path) -> None:
        import json

        import orbax.checkpoint as ocp

        ckpt = Path(ckpt)
        state_dir = ckpt / "state"
        if not state_dir.exists():
            raise ValidationError(
                f"{ckpt} is not a converted checkpoint (no state/ dir); "
                "re-run `python -m comfyui_distributed_tpu convert`")
        manifest = {}
        mf = ckpt / "cdt_manifest.json"
        self._weights_source = ckpt
        if mf.is_file():
            manifest = json.loads(mf.read_text())
        saved_arch = manifest.get("arch")
        if saved_arch and saved_arch != self._arch_fingerprint():
            raise ValidationError(
                f"checkpoint {ckpt} was saved with architecture "
                f"{saved_arch} but the current preset resolves to "
                f"{self._arch_fingerprint()}; a mismatched positional "
                "encoding restores byte-compatibly yet generates garbage — "
                "re-convert the checkpoint for this preset")
        if {"clip_l", "t5"} & set(manifest.get("entries", [])):
            # abstract T5 targets: orbax restores over ShapeDtypeStructs,
            # so a T5-XXL restore never pays a ~19 GB random init first
            self.build_clip_stack(tiny=bool(manifest.get("tiny_clip")),
                                  abstract_t5="t5" in manifest["entries"])
        targets = self._state_entries()
        if manifest.get("entries"):
            targets = {k: v for k, v in targets.items()
                       if k in manifest["entries"]}
        with ocp.StandardCheckpointer() as ckptr:
            restored = ckptr.restore(state_dir.resolve(), targets)
        self._apply_entries(restored)
        # the encoder's weights just changed provenance: a stale
        # random-init identity here would let this bundle share cache
        # entries with a genuinely random-init twin
        self._stamp_text_encoder()
        log(f"loaded checkpoint {ckpt}")

    def save_checkpoint(self, ckpt: Path) -> None:
        """Persist the stack with orbax (enables real-weight workflows:
        convert → save once → every controller restores). A small manifest
        records which entries exist so restore can rebuild the right
        text-encoder stack."""
        import json

        import orbax.checkpoint as ocp

        ckpt = Path(ckpt)
        state = self._state_entries()
        with ocp.StandardCheckpointer() as ckptr:
            ckptr.save((ckpt / "state").resolve(), state)
        tiny_clip = False
        if self.clip_stack is not None:
            if self.preset.clip == "umt5":
                tiny_clip = self.clip_stack.t5.config.d_model < 256
            else:
                cl = (self.clip_stack.clip_l
                      if self.preset.clip in ("sdxl", "flux", "sd3")
                      else self.clip_stack)
                tiny_clip = cl.config.width < 256
        ckpt.mkdir(parents=True, exist_ok=True)
        (ckpt / "cdt_manifest.json").write_text(json.dumps(
            {"preset": self.preset.name, "entries": sorted(state),
             "tiny_clip": tiny_clip,
             "arch": self._arch_fingerprint()}))
        log(f"saved checkpoint {ckpt}")

    def _arch_fingerprint(self) -> dict:
        """Architecture facts that change SEMANTICS without changing the
        param tree (a rope↔sincos flip restores byte-compatibly but
        generates garbage); recorded at save, validated at load."""
        core = (self.preset.dit or self.preset.video or self.preset.unet)
        fp: dict = {"kind": self.kind}
        for field in ("pos_embed", "rope_theta", "rope_axes_dim"):
            if hasattr(core, field):
                v = getattr(core, field)
                fp[field] = list(v) if isinstance(v, tuple) else v
        return fp

    def load_safetensors_checkpoint(self, path: Path) -> None:
        """Convert a published single-file ``.safetensors`` checkpoint
        (SDXL/SD1.5/FLUX layout) into this bundle in place."""
        from .convert import convert_checkpoint

        if self.preset.clip not in (None, "flux", "umt5", "sd3"):
            # FLUX/WAN/SD3 single files carry only the transformer; the
            # (large) T5 stacks are built on demand by
            # load_text_encoder_files — pre-building here would
            # materialize ~19-23 GB of random fp32 T5 weights and, worse,
            # let save_checkpoint persist them as if they were real
            self.build_clip_stack()
        self._weights_source = Path(path)
        convert_checkpoint(path, self)
        if self.preset.param_dtype is not None:
            # the converter fills float32 on the host; hold what the
            # preset says (an orbax restore already lands in the dtype of
            # the tree it restores over)
            from .draw import cast_float

            self._set_core_params(cast_float(
                self._core_params(), self.preset.param_dtype))
        self._stamp_text_encoder()

    def load_safetensors_moe(self, high: Path, low: Path) -> None:
        """Convert a WAN-2.2 dual-expert release: the high-noise
        transformer file into the main params and the low-noise file into
        ``dit_params_low`` (both shape-checked against this preset's
        architecture; the experts are architecturally identical)."""
        from .convert import convert_checkpoint

        if self.preset.moe_boundary is None:
            raise ValidationError(
                f"preset {self.preset.name!r} is not a dual-expert model; "
                "use load_safetensors_checkpoint for single-transformer "
                "releases")
        self._weights_source = Path(high)
        convert_checkpoint(Path(high), self)
        hi_params = self.pipeline.dit_params
        # the low expert converts against the low template in the same
        # code path, then the trees swap back into place
        self.pipeline.dit_params = self.pipeline.dit_params_low
        try:
            convert_checkpoint(Path(low), self)
            self.pipeline.dit_params_low = self.pipeline.dit_params
        finally:
            self.pipeline.dit_params = hi_params
        self._stamp_text_encoder()

    def load_text_encoder_files(self, t5: Optional[Path] = None,
                                clip_l: Optional[Path] = None,
                                clip_g: Optional[Path] = None) -> None:
        """Convert the standalone text-encoder ``.safetensors`` files
        FLUX/SD3 distributions ship (``t5xxl_*.safetensors`` in HF T5
        layout, ``clip_l.safetensors``/``clip_g.safetensors`` in HF
        ``text_model.*`` layout) into this bundle's conditioning stack."""
        from .convert import convert_clip_hf, load_safetensors
        from .t5 import convert_t5

        if self.preset.clip not in ("flux", "umt5", "sd3"):
            raise ValidationError(
                "separate text-encoder files are a flux/wan/sd3-stack "
                f"feature; preset {self.preset.name!r} bundles its "
                "encoders in the single-file checkpoint")
        if self.clip_stack is None:
            from .t5 import FluxTextStack, SD3TextStack, UMT5Conditioner

            # T5-XXL random init is ~19 GB; skip it when the converter is
            # about to overwrite every leaf
            if self.preset.clip == "flux":
                self.clip_stack = FluxTextStack.init_random(
                    jax.random.key(0), abstract_t5=t5 is not None)
            elif self.preset.clip == "sd3":
                self.clip_stack = SD3TextStack.init_random(
                    jax.random.key(0), abstract_t5=t5 is not None)
            else:
                self.clip_stack = UMT5Conditioner.init_random(
                    jax.random.key(0), abstract_t5=t5 is not None)
            self.text_encoder = self.clip_stack
        if t5 is not None:
            self.clip_stack.t5.params = convert_t5(
                load_safetensors(Path(t5)), self.clip_stack.t5.params,
                self.clip_stack.t5.config)
        if clip_l is not None:
            if self.preset.clip not in ("flux", "sd3"):
                raise ValidationError(
                    "clip_l is part of the flux/sd3 stacks only")
            self.clip_stack.clip_l.params = convert_clip_hf(
                load_safetensors(Path(clip_l)),
                self.clip_stack.clip_l.params, self.clip_stack.clip_l.config)
        if clip_g is not None:
            if self.preset.clip != "sd3":
                raise ValidationError("clip_g is part of the sd3 stack only")
            self.clip_stack.clip_g.params = convert_clip_hf(
                load_safetensors(Path(clip_g)),
                self.clip_stack.clip_g.params, self.clip_stack.clip_g.config)
        if self._weights_source is None and t5 is not None:
            self._weights_source = Path(t5)
        self._stamp_text_encoder()

    def release_device(self) -> None:
        """Drop everything this bundle holds ON DEVICE so its HBM can be
        reused (residency-planner eviction, ``cluster/residency.py``):
        offload executors' stacked/resident blocks are freed explicitly
        (``diffusion/offload.release_store``), and every pipeline compile
        cache is cleared so no jitted closure keeps device arrays alive —
        the weights' placed copies on a mesh's other chips among them:
        they live only as long as a program bound to them
        (``parallel/sharding.replicate``).
        Host-side params (numpy/orbax trees) survive — re-acquiring the
        bundle re-uploads, it does not re-convert."""
        from ..diffusion.offload import release_store

        # every program cache a pipeline keeps is a dict attribute named
        # *_cache (or _control_clones): found by that, not by a list
        for name, cache in vars(self.pipeline).items():
            if not (isinstance(cache, dict)
                    and name.endswith(("_cache", "_clones"))):
                continue
            for v in cache.values():
                if hasattr(v, "stacked") and hasattr(v, "resident"):
                    release_store(v)
            cache.clear()

    def load_vae_file(self, path: Path) -> None:
        """Convert a standalone VAE ``.safetensors`` into this bundle.

        Detects the three published layouts: LDM-embedded
        (``first_stage_model.*``), standalone SD VAE (bare keys with
        ``quant_conv``), and BFL ``ae.safetensors`` (bare keys, no quant
        convs — FLUX's 16-channel KL-VAE)."""
        from .convert import ConversionError, convert_vae, load_safetensors
        from .wan_vae import WanVAEConfig

        if isinstance(self.preset.vae, WanVAEConfig):
            raise ConversionError(
                "WAN 3D-causal-VAE weight portability is not yet wired "
                "(models/wan_vae.py) — the preset's VAE keeps its current "
                "weights; --vae applies to image-VAE presets only")
        sd = load_safetensors(Path(path))
        if any(k.startswith("first_stage_model.") for k in sd):
            prefix, qc = "first_stage_model.", True
        elif "quant_conv.weight" in sd:
            prefix, qc = "", True
        else:
            prefix, qc = "", False
        enc, dec = convert_vae(sd, self.pipeline.vae.enc_params,
                               self.pipeline.vae.dec_params,
                               self.preset.vae, prefix=prefix,
                               quant_convs=qc)
        self.pipeline.vae.enc_params = enc
        self.pipeline.vae.dec_params = dec


class LLMBundle:
    """A loaded language model (``preset.kind == "llm"``): random weights
    from the registry's seed (drawn by the model the preset's config
    names), held on the device in the preset's dtype, and the pipeline
    that binds its two programs. It shares the registry's
    cache, lock and residency accounting with ``ModelBundle`` and has no
    VAE or text tower of its own."""

    kind = "llm"
    text_encoder = None

    def __init__(self, preset: ModelPreset,
                 checkpoint_dir: Optional[Path] = None, seed: int = 0):
        from ..diffusion.pipeline_llm import LLMPipeline

        if checkpoint_dir is not None and Path(checkpoint_dir).exists():
            raise ValidationError(
                f"preset {preset.name!r}: loading a language model's "
                "checkpoint is not wired; it runs on seeded random weights")
        self.preset = preset
        self._init_seed = int(seed)
        self.pipeline = LLMPipeline(
            preset.llm, preset.llm.model.init(preset.llm,
                                              jax.random.key(seed)))

    def _core_params(self):
        return self.pipeline.params

    def text_tower(self) -> str:
        return "none"

    def weights_identity(self) -> str:
        return f"{self.preset.name}/{_weights_tag(None, self._init_seed)}"

    def release_device(self) -> None:
        cache = getattr(self.pipeline, "_fn_cache", None)
        if cache:
            cache.clear()


def _note_weights(name: str, bundle: "ModelBundle") -> int:
    """Say what was just put on the device: bytes (answered too), the
    dtype the denoiser is held in, and which text tower will serve
    prompts."""
    from ..cluster.residency import bundle_bytes
    from ..telemetry import enabled as _tm_enabled
    from ..telemetry import metrics as _tm

    nbytes = bundle_bytes(bundle)
    dtype = str(jax.tree_util.tree_leaves(bundle._core_params())[0].dtype)
    log(f"model {name!r}: {nbytes / 2**30:.2f} GiB of weights, denoiser "
        f"held in {dtype}, text tower {bundle.text_tower()}")
    if _tm_enabled():
        _tm.MODEL_WEIGHT_BYTES.labels(
            model=name, dtype=dtype,
            text_tower=bundle.text_tower()).set(float(nbytes))
    return nbytes


class ModelRegistry:
    def __init__(self, checkpoint_root: Optional[Path] = None,
                 hbm_budget_bytes: Optional[int] = None):
        """``hbm_budget_bytes`` (default: ``CDT_HBM_BUDGET_GB``) attaches
        the multi-model residency planner (``cluster/residency.py``):
        cached bundles then live under a per-chip HBM budget with
        LRU/priority eviction instead of accumulating until OOM."""
        self.checkpoint_root = Path(checkpoint_root) if checkpoint_root else None
        self._cache: dict[str, ModelBundle] = {}
        # registry access is lock-serialized: the stage-split encode
        # pool resolves bundles from N worker threads concurrently, and
        # an unguarded check-then-build would construct two bundles of
        # the same preset — distinct pipeline objects whose members then
        # never stack in one microbatch (cluster/stages, docs/stages.md)
        from ..lint.lockorder import tracked_lock

        self._lock = tracked_lock("model.registry", reentrant=True)
        self.residency = None
        if hbm_budget_bytes is None:
            from ..cluster.residency import hbm_budget_bytes as _budget

            hbm_budget_bytes = _budget()
        if hbm_budget_bytes and hbm_budget_bytes > 0:
            from ..cluster.residency import BundleResidency

            self.residency = BundleResidency(self, hbm_budget_bytes)

    def available(self) -> list[str]:
        return sorted(PRESETS)

    def get(self, name: str) -> ModelBundle:
        with self._lock:
            if name not in self._cache:
                preset = PRESETS.get(name)
                if preset is None:
                    raise ValidationError(f"unknown model {name!r}; have {self.available()}")
                ckpt = self.checkpoint_root / name if self.checkpoint_root else None
                self._cache[name] = _build_bundle(name, preset, ckpt)
            bundle = self._cache[name]
            if self.residency is not None:
                try:
                    self.residency.note_use(name, bundle)
                except Exception:
                    # an unplaceable bundle must not squat in the cache
                    # (permanently over budget, unevictable because it was
                    # never registered) — drop it and re-raise
                    self._cache.pop(name, None)
                    bundle.release_device()
                    raise
                # back-ref so holders (sampler nodes) can pin the bundle
                # for the duration of a generate call without reaching
                # the registry (cluster/residency.pinned_bundle)
                bundle._residency = self.residency
            return bundle


def _build_bundle(name: str, preset, ckpt) -> ModelBundle:
    """Construct a preset's bundle under a ``weights.init`` span (attrs
    ``model``, ``bytes``) whose seconds, net of the programs built inside,
    are ``cdt_weights_seconds{model, phase=init}``. HOST seconds: an
    initialiser still running on the device when the constructor returns
    overlaps what the host does next, and is not waited for here — what a
    first program still waits for of it is that program's ``first_run``."""
    from ..telemetry.build import weights_span

    build = LLMBundle if preset.kind == "llm" else ModelBundle
    with weights_span("init", name) as attrs:
        bundle = build(preset, ckpt)
        attrs(bytes=_note_weights(name, bundle))
    return bundle


def _glm_preset(name: str, tiny: bool = False):
    """The ``glm`` family (models/llm_glm.py), registered at the END of
    this file: a program's cache key holds the lines its operations were
    traced at (ROADMAP D17), and a preset added above would move them."""
    from .llm_glm import GlmConfig

    share = GlmConfig.tiny if tiny else GlmConfig.glm_share
    return ModelPreset(name, unet=None, vae=None, text=None, llm=share())


PRESETS["glm-5"] = _glm_preset("glm-5")
PRESETS["glm-tiny"] = _glm_preset("glm-tiny", tiny=True)


def _keye_preset(name: str, tiny: bool = False):
    """The ``keye`` family (models/llm_keye.py), registered at the END of
    this file for ``_glm_preset``'s reason."""
    from .llm_keye import KeyeConfig

    share = KeyeConfig.tiny if tiny else KeyeConfig.keye_share
    return ModelPreset(name, unet=None, vae=None, text=None, llm=share())


PRESETS["keye-vl-2.0-30b-a3b"] = _keye_preset("keye-vl-2.0-30b-a3b")
PRESETS["keye-tiny"] = _keye_preset("keye-tiny", tiny=True)


def _zaya_preset(name: str, tiny: bool = False):
    """The ``zaya`` family (models/llm_zaya.py), registered at the END of
    this file for ``_glm_preset``'s reason."""
    from .llm_zaya import ZayaConfig

    share = ZayaConfig.tiny if tiny else ZayaConfig.zaya_share
    return ModelPreset(name, unet=None, vae=None, text=None, llm=share())


PRESETS["zaya1-8b"] = _zaya_preset("zaya1-8b")
PRESETS["zaya-tiny"] = _zaya_preset("zaya-tiny", tiny=True)


def _brumby_preset(name: str, tiny: bool = False):
    """The ``brumby`` family (models/llm_brumby.py), registered at the END of
    this file for ``_glm_preset``'s reason."""
    from .llm_brumby import BrumbyConfig

    stage = BrumbyConfig.tiny if tiny else BrumbyConfig.brumby_stage
    return ModelPreset(name, unet=None, vae=None, text=None, llm=stage())


PRESETS["brumby-14b-base"] = _brumby_preset("brumby-14b-base")
PRESETS["brumby-tiny"] = _brumby_preset("brumby-tiny", tiny=True)


def _mimo_preset(name: str, tiny: bool = False):
    """The ``mimo`` family (models/llm_mimo.py), registered at the END of
    this file for ``_glm_preset``'s reason."""
    from .llm_mimo import MimoConfig

    stage = MimoConfig.tiny if tiny else MimoConfig.mimo_stage
    return ModelPreset(name, unet=None, vae=None, text=None, llm=stage())


PRESETS["mimo-v2-flash"] = _mimo_preset("mimo-v2-flash")
PRESETS["mimo-tiny"] = _mimo_preset("mimo-tiny", tiny=True)
