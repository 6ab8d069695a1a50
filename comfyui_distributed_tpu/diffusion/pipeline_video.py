"""Text→video pipeline (WAN-class) with dp fan-out and frame sharding.

Parity targets (BASELINE): ``distributed-wan-2.2_14b_t2v.json`` — the
reference generates one video per worker with seed offsets and divides
frame batches afterwards (``ImageBatchDivider``); here:

- ``generate_fn``: dp fan-out — n seed-varied videos in one program;
- ``generate_frames_fn``: ONE video's frames sharded over ``sp`` (ring
  attention over the spatio-temporal token sequence) — single-video
  latency scaling the reference cannot express.

VAE: either the image ``AutoencoderKL`` applied per frame, or the
WAN-geometry 3D causal VAE (``models/wan_vae.WanVAE3D``) — with the 3D
VAE the DiT runs on a 4×-shorter latent frame axis (the 4n+1 rule's
origin), a direct transformer-sequence reduction. The 4n+1 frame rule
helpers live in ``models/video_dit.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..models.vae import AutoencoderKL
from ..models.video_dit import VideoDiT, pad_frames_4n1
from ..parallel.rng import participant_key
from ..utils import constants
from .pipeline import bind_weights
from .samplers import sample
from .schedules import sigmas_flow


@dataclasses.dataclass(frozen=True)
class VideoSpec:
    frames: int = 17               # will be padded to 4n+1
    height: int = 480
    width: int = 832
    steps: int = 20
    shift: float = 3.0
    guidance_scale: float = 1.0    # CFG (WAN uses real CFG, not distilled)
    sampler: str = "euler"

    @property
    def padded_frames(self) -> int:
        return pad_frames_4n1(self.frames)


class VideoPipeline:
    """``dit_params_low``/``expert_boundary`` enable WAN-2.2-style
    dual-expert (MoE) sampling: the published 14B t2v/i2v models are TWO
    DiTs — a high-noise expert for timesteps ≥ boundary·1000 and a
    low-noise expert below (t2v boundary 0.875, i2v 0.9). The sigma
    ladder splits at the boundary and each segment runs its expert's
    weights — two clean sampler scans, the XLA-friendly form of
    ComfyUI's two-KSampler-pass graph (no weight-sized ``lax.cond``)."""

    def __init__(self, dit: VideoDiT, dit_params, vae: AutoencoderKL,
                 dit_params_low=None, expert_boundary: Optional[float] = None):
        self.dit = dit
        self.dit_params = dit_params
        self.dit_params_low = dit_params_low
        self.expert_boundary = expert_boundary
        self.vae = vae

    @property
    def is_moe(self) -> bool:
        return (self.dit_params_low is not None
                and self.expert_boundary is not None)

    def _expert_split(self, sigmas) -> int:
        """Number of leading sampler steps the HIGH-noise expert takes:
        a step is 'high' when its current sigma ≥ boundary (flow sigmas
        ARE normalized timesteps: sigma = t/1000)."""
        import numpy as np

        cur = np.asarray(sigmas)[:-1]            # per-step current sigmas
        return int(np.sum(cur >= self.expert_boundary))

    @staticmethod
    def _progress_den(build_den, token, shard_index):
        """Shared progress interposition for every generate_* factory:
        ``build_den(params) -> denoiser``, wrapped with the traced token
        when progress is on — one definition so the token plumbing can't
        drift between the four execution modes."""
        def make_den(params):
            den = build_den(params)
            if token is not None:
                from .progress import wrap_denoiser

                den = wrap_denoiser(den, token, shard_index)
            return den

        return make_den

    def _sample_expert(self, spec: "VideoSpec", make_den, x, sigmas, key,
                       weights):
        """Run the sampler with expert switching. ``make_den(params)``
        builds the (possibly progress-wrapped) denoiser for one expert's
        weights; single-expert pipelines take one scan as before."""
        if not self.is_moe:
            return sample(spec.sampler, make_den(weights["dit"]), x,
                          sigmas, key=key)
        split = self._expert_split(sigmas)
        steps = int(sigmas.shape[0]) - 1
        if split <= 0:
            return sample(spec.sampler, make_den(weights["dit_low"]), x,
                          sigmas, key=key)
        if split >= steps:
            return sample(spec.sampler, make_den(weights["dit"]), x,
                          sigmas, key=key)
        x_mid = sample(spec.sampler, make_den(weights["dit"]), x,
                       sigmas[: split + 1], key=key)
        # distinct fold for the low segment so ancestral samplers never
        # reuse the high segment's noise draws
        return sample(spec.sampler, make_den(weights["dit_low"]), x_mid,
                      sigmas[split:], key=jax.random.fold_in(key, 0x10E))

    @property
    def temporal_downscale(self) -> int:
        return getattr(self.vae.config, "temporal_downscale", 1)

    def latent_frames(self, spec: "VideoSpec") -> int:
        """DiT frame-axis length: padded pixel frames compressed by the
        VAE's temporal factor (1 for the per-frame image VAE)."""
        return (spec.padded_frames - 1) // self.temporal_downscale + 1

    def _weights(self) -> dict:
        """Explicit jit-argument weight pytree (closure capture would
        serialize the params into the lowered module — 28 GB of MLIR for
        WAN-14B; see ``Txt2ImgPipeline._weights``)."""
        w = {"dit": self.dit_params, "vae_dec": self.vae.dec_params}
        if self.dit_params_low is not None:
            w["dit_low"] = self.dit_params_low
        return w

    def decode_frames(self, latents: jax.Array, vae_params=None) -> jax.Array:
        """[B,f,h,w,c] → [B,F,H,W,3]: whole-clip decode through a 3D
        causal VAE, per-frame decode through the image VAE. Large frames
        switch to spatially-tiled decode (``WanVAE3D.decode_tiled``) —
        a 480p whole-frame f32 decode needs >31 GB of activations."""
        if self.temporal_downscale > 1:
            thresh = constants.VAE_TILE_THRESHOLD
            if thresh and latents.shape[2] * latents.shape[3] > thresh:
                frames = self.vae.decode_tiled(
                    latents, params=vae_params, tile=constants.VAE_TILE,
                    overlap=constants.VAE_TILE_OVERLAP)
            else:
                frames = self.vae.decode(latents, params=vae_params)
            return jnp.clip(frames / 2.0 + 0.5, 0.0, 1.0)
        B, F = latents.shape[:2]
        flat = latents.reshape((B * F,) + latents.shape[2:])
        frames = self.vae.decode(flat, params=vae_params)
        frames = jnp.clip(frames / 2.0 + 0.5, 0.0, 1.0)
        return frames.reshape((B, F) + frames.shape[1:])

    def _denoiser(self, context, pooled, guidance_scale, sp_axis=None,
                  inp_fn=None, params=None):
        """``inp_fn`` optionally transforms the latent before the model
        sees it (i2v concatenates mask + conditioning channels); the CFG
        machinery is shared so t2v/i2v guidance can never diverge.
        ``params`` overrides ``self.dit_params`` (tp mode passes the
        tp-sharded tree so GSPMD sees the placements)."""
        wts = self.dit_params if params is None else params

        def model_call(x, sigma, ctx, pl):
            t = jnp.broadcast_to(sigma, (x.shape[0],))
            inp = x if inp_fn is None else inp_fn(x)
            v = self.dit.apply(wts, inp, t, ctx, pl,
                               sp_axis=sp_axis)
            return x - sigma * v

        if guidance_scale == 1.0:
            return lambda x, s: model_call(x, s, context, pooled)

        uncond_ctx = jnp.zeros_like(context)
        uncond_pl = jnp.zeros_like(pooled)

        def denoise(x, sigma):
            x2 = jnp.concatenate([x, x], axis=0)
            ctx2 = jnp.concatenate([context, uncond_ctx], axis=0)
            pl2 = jnp.concatenate([pooled, uncond_pl], axis=0)
            out = model_call(x2, sigma, ctx2, pl2)
            cond, uncond = jnp.split(out, 2, axis=0)
            return uncond + guidance_scale * (cond - uncond)

        return denoise

    def generate_fn(self, mesh: Mesh, spec: VideoSpec,
                    axis: str = constants.AXIS_DATA,
                    progress: bool = False):
        """dp fan-out: each shard samples a full (seed-varied) video.
        ``progress`` threads a traced token through the program and
        streams per-step x0 previews (``diffusion/progress``) — t2v jobs
        are the longest-running work the framework does, exactly where
        the reference's per-step ComfyUI progress matters most."""
        sigmas = sigmas_flow(spec.steps, spec.shift)
        ds = self.vae.config.downscale
        F = self.latent_frames(spec)
        lat = (F, spec.height // ds, spec.width // ds, self.dit.config.in_channels)

        def per_shard(weights, key, context, pooled, token=None):
            k = participant_key(key, axis)
            x = jax.random.normal(k, (1,) + lat, jnp.float32)
            make_den = self._progress_den(
                lambda p: self._denoiser(context, pooled,
                                         spec.guidance_scale, params=p),
                token, jax.lax.axis_index(axis))
            x0 = self._sample_expert(spec, make_den, x, sigmas, k, weights)
            return self.decode_frames(x0, vae_params=weights["vae_dec"])

        in_specs = (P(), P(), P(None, None, None), P(None, None))
        if progress:
            in_specs += (P(),)          # traced int32 token, replicated
        f = shard_map(
            per_shard, mesh=mesh, in_specs=in_specs,
            out_specs=P(axis, None, None, None, None),
        )
        jitted = jax.jit(f)
        weights = self._weights()

        return bind_weights(jitted, weights, label="video_dp",
                            steps=spec.steps, mesh=mesh)

    _CACHE_MAX = 4

    # --- host offload (expert too large for one chip, no pod) -------------

    def offload_executor(self, which: str = "high",
                         resident_bytes: Optional[int] = None,
                         stream_dtype: Optional[str] = None):
        """Build-or-fetch the cached ``OffloadedWan`` executor for one
        expert (``"high"`` = ``dit_params``, ``"low"`` =
        ``dit_params_low``)."""
        from .offload import OffloadedWan, normalize_stream_dtype
        from .pipeline import cached_build

        src = (self.dit_params if which == "high"
               else self.dit_params_low)
        if src is None:
            raise ValueError(f"no params for expert {which!r}")
        sd = normalize_stream_dtype(stream_dtype)
        return cached_build(
            self, ("offload", which, resident_bytes, sd, id(src)),
            lambda: OffloadedWan(self.dit, src,
                                 resident_bytes=resident_bytes,
                                 stream_dtype=sd),
            self._CACHE_MAX)

    def _evict_offload(self, which: str) -> None:
        """Release an expert's HBM and drop it from the executor cache —
        the dual-expert swap needs the space for the other expert."""
        cache = getattr(self, "_fn_cache", {})
        for key in [k for k in cache
                    if k[0] == "offload" and k[1] == which]:
            cache.pop(key).release()

    def generate_offloaded(self, spec: VideoSpec, seed: int,
                           context: jax.Array,
                           pooled: Optional[jax.Array] = None,
                           resident_bytes: Optional[int] = None,
                           stream_dtype: Optional[str] = None,
                           on_step=None,
                           progress_token=None,
                           should_stop=None) -> jax.Array:
        """ONE t2v video on ONE device with quantized/streamed expert
        weights (``diffusion/offload.py:OffloadedWan``) — the
        single-chip answer to WAN-14B's 28 GB-per-expert (×2 for the
        2.2 dual-expert pair). Seed derivation matches dp shard 0, so
        offloaded == sharded run. Dual-expert jobs run the high-noise
        segment, then RELEASE that expert's HBM and upload the low
        expert (one swap per video; the low expert stays cached for the
        next video, the high one re-uploads — with
        ``CDT_OFFLOAD_CACHE_DIR`` the re-quantize is skipped). i2v:
        ``generate_offloaded_i2v``."""
        return self._offloaded_sample(
            spec, seed, context, None, None,
            self.dit.config.in_channels, resident_bytes, stream_dtype,
            on_step, progress_token, should_stop)

    def generate_offloaded_i2v(self, spec: VideoSpec, seed: int,
                               image: jax.Array, context: jax.Array,
                               pooled: Optional[jax.Array] = None,
                               resident_bytes: Optional[int] = None,
                               stream_dtype: Optional[str] = None,
                               on_step=None,
                           progress_token=None,
                           should_stop=None) -> jax.Array:
        """Offloaded i2v: the same quantized-resident ladder with the
        first-frame conditioning concat (``i2v_condition`` → mask+y)
        applied per model call, exactly like ``_denoiser_i2v``."""
        if image.shape[0] != 1:
            raise ValueError("offloaded generation is single-video "
                             "(batch 1)")
        y, mask = self.i2v_condition(image, spec)
        c = getattr(self.dit.config, "out_channels",
                    self.dit.config.in_channels)
        return self._offloaded_sample(spec, seed, context, y, mask, c,
                                      resident_bytes, stream_dtype,
                                      on_step, progress_token,
                                      should_stop)

    def _offloaded_sample(self, spec: VideoSpec, seed: int, context,
                          y, mask, lat_channels: int, resident_bytes,
                          stream_dtype, on_step, progress_token=None,
                          should_stop=None) -> jax.Array:
        from .offload import ladder_mode, sample_euler_py

        if context.shape[0] != 1:
            raise ValueError("offloaded generation is single-video "
                             "(batch 1)")
        if ladder_mode() == "step" and spec.sampler != "euler":
            # fail BEFORE any expert quantize/upload — decidable from
            # the env + spec alone
            raise ValueError(
                "the per-step offloaded ladder supports euler only "
                f"(got {spec.sampler!r}); fully-resident executors "
                "with CDT_OFFLOAD_LADDER=jit run every sampler")
        sigmas = sigmas_flow(spec.steps, spec.shift)
        ds = self.vae.config.downscale
        lat = (self.latent_frames(spec), spec.height // ds,
               spec.width // ds, lat_channels)
        # same key derivation as dp shard 0 (noise AND ancestral draws);
        # the low segment folds 0x10E exactly like _sample_expert
        key = jax.random.fold_in(jax.random.key(seed), 0)
        x = jax.random.normal(key, (1,) + lat, jnp.float32)

        def run(which, x0, sig, seg_key):
            off = self.offload_executor(which, resident_bytes,
                                        stream_dtype)
            if off.stacked and ladder_mode() == "jit":
                # fully resident: the whole segment ladder is ONE
                # compiled program supporting EVERY registered sampler
                # (in-trace progress via the token)
                return off.sample_resident(
                    x0, sig, context, spec.guidance_scale, y, mask,
                    sampler=spec.sampler, key=seg_key,
                    progress_token=progress_token)
            if spec.sampler != "euler":
                raise ValueError(
                    "the per-step offloaded ladder supports euler only "
                    f"(got {spec.sampler!r}); fully-resident executors "
                    "with CDT_OFFLOAD_LADDER=jit run every sampler")
            inp_fn = None if y is None else self._i2v_inp_fn(y, mask)
            den = off.denoiser(context, spec.guidance_scale,
                               inp_fn=inp_fn)
            return sample_euler_py(den, jax.device_put(x0, off.device),
                                   sig, on_step=on_step,
                                   should_stop=should_stop)

        if not self.is_moe:
            x0 = run("high", x, sigmas, key)
        else:
            split = self._expert_split(sigmas)
            steps = int(sigmas.shape[0]) - 1
            if split <= 0:
                x0 = run("low", x, sigmas, key)
            elif split >= steps:
                x0 = run("high", x, sigmas, key)
            else:
                x_mid = run("high", x, sigmas[: split + 1], key)
                jax.block_until_ready(x_mid)
                if should_stop is not None and should_stop():
                    # free host-side boundary — honor an interrupt here
                    # even in jit ladder mode rather than uploading +
                    # running the whole low-expert segment first
                    raise InterruptedError(
                        "offloaded MoE sampling interrupted at the "
                        "expert boundary")
                self._evict_offload("high")     # HBM for the low expert
                x0 = run("low", x_mid, sigmas[split:],
                         jax.random.fold_in(key, 0x10E))
        return self.decode_frames(x0)

    def _cached_fn(self, mesh: Mesh, spec: VideoSpec, mode: str = "dp",
                   progress: bool = False,
                   axis: Optional[str] = None):
        """Value-keyed compile cache across node executions (same
        discipline as ``FlowPipeline._cached_fn`` — a WAN compile is far
        too expensive to pay per prompt)."""
        from .pipeline import cached_build, mesh_cache_key

        if mode in ("sp", "i2v-sp"):
            axis = axis or constants.AXIS_SEQUENCE
        else:
            axis = axis or constants.AXIS_DATA
        builder = {"dp": self.generate_fn,
                   "sp": self.generate_frames_fn,
                   "i2v": self.generate_i2v_fn,
                   "i2v-sp": self.generate_i2v_frames_fn}[mode]
        key = (mesh_cache_key(mesh), spec, mode, progress, axis)
        return cached_build(
            self, key, lambda: builder(mesh, spec, axis=axis,
                                       progress=progress),
            self._CACHE_MAX)

    @staticmethod
    def _token_args(args: list, progress_token) -> list:
        """Single place that knows the token's wire form (trailing int32
        scalar) — the nodes never marshal it themselves."""
        if progress_token is not None:
            args.append(jnp.asarray(progress_token, jnp.int32))
        return args

    def generate(self, mesh: Mesh, spec: VideoSpec, seed: int,
                 context: jax.Array, pooled: jax.Array,
                 progress_token=None) -> jax.Array:
        fn = self._cached_fn(mesh, spec, "dp",
                             progress=progress_token is not None)
        return fn(*self._token_args(
            [jax.random.key(seed), context, pooled], progress_token))

    def generate_frames(self, mesh: Mesh, spec: VideoSpec, seed: int,
                        context: jax.Array, pooled: jax.Array,
                        progress_token=None) -> jax.Array:
        """Public sp entry (ONE video, frame blocks sharded): cached
        compile + progress token, mirroring ``generate``."""
        fn = self._cached_fn(mesh, spec, "sp",
                             progress=progress_token is not None)
        return fn(*self._token_args(
            [jax.random.key(seed), context, pooled], progress_token))

    # -- dp×tp: the WAN-14B enabler --------------------------------------

    def generate_tp_fn(self, mesh: Mesh, spec: VideoSpec,
                       dp_axis: str = constants.AXIS_DATA,
                       tp_axis: str = constants.AXIS_TENSOR):
        """Seeds over ``dp`` AND weights over ``tp`` in one jit. A 14B
        WAN DiT is ~28 GB of bf16 weights — more than a v5e chip's HBM —
        so tp sharding is what makes BASELINE's ``wan-2.2 14B t2v over
        pod`` row runnable at all (the reference requires every GPU to
        hold the whole model, README.md:186-189). Megatron column/row
        rules per model family (``parallel/tensor.py``); GSPMD inserts
        the all-reduces."""
        from ..parallel.tensor import (DIT_TP_RULES, WAN_TP_RULES,
                                       require_tp_match, shard_params,
                                       tp_fanout_call)

        # models declare their rule family (WanModel.tp_family = "wan");
        # MMDiT-style video DiTs use the image-DiT fused-qkv rules
        family = getattr(self.dit, "tp_family", "dit")
        rules = WAN_TP_RULES if family == "wan" else DIT_TP_RULES
        sigmas = sigmas_flow(spec.steps, spec.shift)
        ds = self.vae.config.downscale
        F = self.latent_frames(spec)
        lat = (F, spec.height // ds, spec.width // ds,
               self.dit.config.in_channels)
        B = mesh.shape[dp_axis]
        require_tp_match(self.dit_params, mesh, rules, tp_axis, family)
        # tp-placed params travel as ARGUMENTS (committed sharded arrays),
        # never closure constants (see _weights). Both experts of a
        # WAN-2.2 MoE shard over tp — per-chip resident weights stay
        # 2·(params/tp_degree), which is what makes the dual-14B config
        # placeable at all.
        weights = {"dit": shard_params(self.dit_params, mesh, rules,
                                       tp_axis)}
        if self.dit_params_low is not None:
            weights["dit_low"] = shard_params(self.dit_params_low, mesh,
                                              rules, tp_axis)
        vae_dec = self.vae.dec_params

        def run(weights, vae_dec, keys, context, pooled):
            noise = jax.vmap(
                lambda k: jax.random.normal(k, lat, jnp.float32))(keys)
            bc = lambda a: jnp.broadcast_to(a, (B,) + a.shape[1:])
            make_den = lambda p: self._denoiser(
                bc(context), bc(pooled), spec.guidance_scale, params=p)
            x0 = self._sample_expert(spec, make_den, noise, sigmas,
                                     keys[0], weights)
            return self.decode_frames(x0, vae_params=vae_dec)

        return tp_fanout_call(jax.jit(run), (weights, vae_dec), mesh,
                              dp_axis, B)

    # -- image→video (WAN-2.2-style latent-concat conditioning) ----------

    def i2v_condition(self, image: jax.Array,
                      spec: VideoSpec) -> tuple[jax.Array, jax.Array]:
        """First-frame conditioning for i2v models.

        ``image`` [B,H,W,3] in [0,1] → ``y`` (the causal VAE encoding of
        the image followed by blank frames) and ``mask`` (one channel per
        compressed-away pixel frame, published WAN polarity: **1 where
        content is given** — the first latent frame — 0 where the model
        must generate). The model input per step is
        ``concat([x_t, mask, y])``, matching the i2v in_channels
        arithmetic (e.g. 16+4+16=36 at 4× temporal)."""
        B, H, W, _ = image.shape
        F = spec.padded_frames
        vid = jnp.concatenate(
            [image[:, None] * 2.0 - 1.0,
             jnp.zeros((B, F - 1, H, W, image.shape[-1]))], axis=1)
        y = self.vae.encode(vid)
        td = max(self.temporal_downscale, 1)
        mask = jnp.zeros(y.shape[:4] + (td,), y.dtype)
        return y, mask.at[:, 0].set(1.0)

    @staticmethod
    def _i2v_inp_fn(y, mask):
        """The i2v model-input concat — ONE definition shared with both
        offloaded ladders (``diffusion/offload.i2v_input_concat``)."""
        from .offload import i2v_input_concat

        return i2v_input_concat(y, mask)

    def _denoiser_i2v(self, context, pooled, y, mask, guidance_scale,
                      sp_axis=None, params=None):
        return self._denoiser(context, pooled, guidance_scale,
                              sp_axis=sp_axis,
                              inp_fn=self._i2v_inp_fn(y, mask),
                              params=params)

    def generate_i2v_fn(self, mesh: Mesh, spec: VideoSpec,
                        axis: str = constants.AXIS_DATA,
                        progress: bool = False):
        """dp fan-out of seed-varied i2v samples from one start image
        (the conditioning latents replicate across shards)."""
        sigmas = sigmas_flow(spec.steps, spec.shift)
        ds = self.vae.config.downscale
        F = self.latent_frames(spec)
        c = getattr(self.dit.config, "out_channels",
                    self.dit.config.in_channels)
        lat = (F, spec.height // ds, spec.width // ds, c)

        def per_shard(weights, key, context, pooled, y, mask, token=None):
            k = participant_key(key, axis)
            x = jax.random.normal(k, (1,) + lat, jnp.float32)
            make_den = self._progress_den(
                lambda p: self._denoiser_i2v(context, pooled, y, mask,
                                             spec.guidance_scale, params=p),
                token, jax.lax.axis_index(axis))
            x0 = self._sample_expert(spec, make_den, x, sigmas, k, weights)
            return self.decode_frames(x0, vae_params=weights["vae_dec"])

        in_specs = (P(), P(), P(None, None, None), P(None, None),
                    P(None, None, None, None, None),
                    P(None, None, None, None, None))
        if progress:
            in_specs += (P(),)
        f = shard_map(
            per_shard, mesh=mesh, in_specs=in_specs,
            out_specs=P(axis, None, None, None, None),
        )
        jitted = jax.jit(f)
        weights = self._weights()

        return bind_weights(jitted, weights, label="video_i2v",
                            steps=spec.steps, mesh=mesh)

    def generate_i2v(self, mesh: Mesh, spec: VideoSpec, seed: int,
                     image: jax.Array, context: jax.Array,
                     pooled: jax.Array, progress_token=None) -> jax.Array:
        y, mask = self.i2v_condition(image, spec)
        fn = self._cached_fn(mesh, spec, "i2v",
                             progress=progress_token is not None)
        return fn(*self._token_args(
            [jax.random.key(seed), context, pooled, y, mask],
            progress_token))

    def generate_i2v_frames(self, mesh: Mesh, spec: VideoSpec, seed: int,
                            image: jax.Array, context: jax.Array,
                            pooled: jax.Array,
                            progress_token=None) -> jax.Array:
        """Public i2v sp entry: cached compile + progress token."""
        y, mask = self.i2v_condition(image, spec)
        fn = self._cached_fn(mesh, spec, "i2v-sp",
                             progress=progress_token is not None)
        return fn(*self._token_args(
            [jax.random.key(seed), context, pooled, y, mask],
            progress_token))

    def generate_i2v_frames_fn(self, mesh: Mesh, spec: VideoSpec,
                               axis: str = constants.AXIS_SEQUENCE,
                               progress: bool = False):
        """ONE i2v sample with latent frame blocks sharded over ``axis``:
        ring attention spans the full sequence; each shard sees its own
        slice of the conditioning latents/mask (frame-aligned, so the
        concat happens shard-locally with no collective)."""
        n_sh = mesh.shape[axis]
        F = self.latent_frames(spec)
        if F % n_sh:
            raise ValueError(
                f"latent frame count {F} must divide over {n_sh} shards")
        sigmas = sigmas_flow(spec.steps, spec.shift)
        ds = self.vae.config.downscale
        lat_h, lat_w = spec.height // ds, spec.width // ds
        c = getattr(self.dit.config, "out_channels",
                    self.dit.config.in_channels)
        per = F // n_sh

        def per_shard(weights, key, context, pooled, y_sh, mask_sh,
                      token=None):
            idx = jax.lax.axis_index(axis)
            full = jax.random.normal(key, (1, F, lat_h, lat_w, c),
                                     jnp.float32)
            x = jax.lax.dynamic_slice_in_dim(full, idx * per, per, axis=1)
            make_den = self._progress_den(
                lambda p: self._denoiser_i2v(context, pooled, y_sh, mask_sh,
                                             spec.guidance_scale,
                                             sp_axis=axis, params=p),
                token, idx)
            # per-shard sampler key: ancestral samplers must inject
            # DIFFERENT noise into each frame block (deterministic
            # samplers ignore the key, so sp==unsharded still holds)
            return self._sample_expert(spec, make_den, x, sigmas,
                                       jax.random.fold_in(key, idx), weights)

        in_specs = (P(), P(), P(None, None, None), P(None, None),
                    P(None, axis), P(None, axis))
        if progress:
            in_specs += (P(),)
        f = shard_map(
            per_shard, mesh=mesh, in_specs=in_specs,
            out_specs=P(None, axis, None, None, None),
            check_vma=False,
        )

        def run(weights, key, context, pooled, y, mask, *token):
            return self.decode_frames(f(weights, key, context, pooled,
                                        y, mask, *token),
                                      vae_params=weights["vae_dec"])

        jitted = jax.jit(run)
        weights = self._weights()

        return bind_weights(jitted, weights, label="video_i2v_sp",
                            steps=spec.steps, mesh=mesh)

    def generate_frames_fn(self, mesh: Mesh, spec: VideoSpec,
                           axis: str = constants.AXIS_SEQUENCE,
                           progress: bool = False):
        """ONE video, frame blocks sharded over ``axis``; joint ring
        attention spans the full spatio-temporal sequence so motion stays
        globally coherent (this is exact attention, not windowed)."""
        n_sh = mesh.shape[axis]
        F = self.latent_frames(spec)
        if F % n_sh:
            raise ValueError(
                f"latent frame count {F} must divide over {n_sh} shards "
                f"(choose frames so the compressed 4n+1 count ≡ 0 mod "
                f"shards)")
        sigmas = sigmas_flow(spec.steps, spec.shift)
        ds = self.vae.config.downscale
        lat_h, lat_w = spec.height // ds, spec.width // ds
        c = self.dit.config.in_channels
        per = F // n_sh

        def per_shard(weights, key, context, pooled, token=None):
            idx = jax.lax.axis_index(axis)
            full = jax.random.normal(key, (1, F, lat_h, lat_w, c), jnp.float32)
            x = jax.lax.dynamic_slice_in_dim(full, idx * per, per, axis=1)
            make_den = self._progress_den(
                lambda p: self._denoiser(context, pooled,
                                         spec.guidance_scale,
                                         sp_axis=axis, params=p),
                token, idx)
            # fold the shard index so ancestral samplers draw distinct
            # noise per frame block (deterministic samplers ignore it)
            return self._sample_expert(spec, make_den, x, sigmas,
                                       jax.random.fold_in(key, idx), weights)

        in_specs = (P(), P(), P(None, None, None), P(None, None))
        if progress:
            in_specs += (P(),)
        f = shard_map(
            per_shard, mesh=mesh, in_specs=in_specs,
            out_specs=P(None, axis, None, None, None),
            check_vma=False,
        )

        def run(weights, key, context, pooled, *token):
            latents = f(weights, key, context, pooled, *token)
            return self.decode_frames(latents, vae_params=weights["vae_dec"])

        jitted = jax.jit(run)
        weights = self._weights()

        return bind_weights(jitted, weights, label="video_sp",
                            steps=spec.steps, mesh=mesh)
