"""Sharded text→image pipeline — the framework's "distributed txt2img".

Reference parity (SURVEY §3.2): the reference dispatches the same workflow
to N worker processes with per-worker seed offsets and gathers PNG envelopes
over HTTP. Here the whole fan-out is ONE SPMD program: ``shard_map`` over
the ``dp`` mesh axis, per-shard ``fold_in`` of the seed (DistributedSeed
parity), per-shard sampling + VAE decode, and the sharded output array *is*
the collected batch (Collector parity) — materializing it performs the
all-gather over ICI. No serialization, no control-plane round trips inside
the step.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.layers import timestep_embedding
from ..models.unet import UNet2D, UNetConfig
from ..models.vae import AutoencoderKL
from ..parallel.rng import participant_key
from ..parallel.sharding import mesh_cache_key, replicate
from ..telemetry.device_scopes import device_scope
from ..utils import constants
from .guidance import cfg_denoiser, eps_denoiser
from .samplers import sample
from .schedules import (NoiseSchedule, sigmas_beta, sigmas_exponential,
                        sigmas_karras, sigmas_linear_quadratic,
                        sigmas_normal, sigmas_sgm_uniform, vp_schedule)


@dataclasses.dataclass(frozen=True)
class GenerationSpec:
    height: int = 1024
    width: int = 1024
    steps: int = 30
    sampler: str = "euler"
    scheduler: str = "karras"  # karras | normal | exponential |
    #                            sgm_uniform | beta | linear_quadratic
    guidance_scale: float = 5.0
    per_device_batch: int = 1
    denoise: float = 1.0           # <1.0: img2img partial ladder (tile engine)


def cached_build(holder, key, builder, max_entries: int = 8):
    """Value-keyed compile cache shared by every pipeline/tile engine.

    ``holder`` is an object (cache lives on its ``_fn_cache`` attribute)
    or a dict (module-level caches). One definition so the eviction
    policy (FIFO at ``max_entries``) and key hygiene can't drift between
    the five call sites that used to hand-roll this."""
    cache = holder if isinstance(holder, dict) \
        else getattr(holder, "_fn_cache", None)
    if cache is None:
        cache = {}
        holder._fn_cache = cache
    fn = cache.get(key)
    if fn is None:
        if len(cache) >= max_entries:
            cache.pop(next(iter(cache)))
        fn = builder()
        cache[key] = fn
    return fn


def bind_weights(jitted, weights, label: "str | None" = None,
                 steps: "int | None" = None, name: "str | None" = None,
                 mesh: "Mesh | None" = None,
                 span_attrs: "dict | None" = None):
    """Wrap a jitted function whose LEADING argument is the weight pytree:
    the returned callable supplies it automatically, while ``.jitted`` /
    ``.weights`` expose the raw jit object for AOT use
    (``bench.py``: ``fn.jitted.lower(fn.weights, *args)``). One shared
    definition — every pipeline factory returns this shape.

    ``mesh``: the mesh of a program that reads ``weights`` replicated
    (``shard_map`` with the weights under ``P()``). The tree is placed on
    it HERE, once, by ``parallel/sharding.replicate`` — which hands every
    program of one mesh the same placed leaves, and on a one-device mesh
    the caller's own — so that no call reshards it: leaves that live on
    one chip, handed to a four-chip program as they are, are copied to
    the other three at EVERY call and dropped after it. ``.weights`` is
    the placed tree, and it lives as long as the wrapper does.

    Every call's LAUNCH is timed: the host time inside ``jitted(...)``
    until JAX hands back the not-yet-ready result, as a ``program.launch``
    span and ``cdt_pipeline_dispatch_seconds{pipeline}``. The series is
    named by ``label``, else by ``name`` (a program that must stay
    asynchronous gets a name and no label), else ``unnamed``.

    ``label`` opts the wrapper into completion timing as well: each call
    is waited for (``block_until_ready`` under a ``program.wait`` span —
    callers materialize the output immediately anyway) and recorded as
    ``cdt_pipeline_compile_seconds{pipeline=label}`` on the first call
    (trace + build + first run: ``_first_call``, at the file's end, also
    records it net of the build) vs ``cdt_pipeline_execute_seconds``
    after; with ``steps`` the per-step quotient also lands in
    ``cdt_sampler_step_seconds``. ``span_attrs``: more attributes of the
    ``pipeline_call`` span (a ``chunk``). Disabled: the old one-liner."""
    from ..telemetry import enabled as _tm_enabled

    if mesh is not None:
        weights = replicate(mesh, weights)
    state = {"first": True}
    series = label or name or "unnamed"

    def call(*args, **kw):
        if not _tm_enabled():
            return jitted(weights, *args, **kw)
        from ..telemetry import metrics as _tm
        from ..telemetry.spans import span, timed_span

        launch = timed_span(
            "program.launch",
            _tm.PIPELINE_DISPATCH_SECONDS.labels(pipeline=series),
            pipeline=series)
        if label is None:
            with launch:
                return jitted(weights, *args, **kw)
        # step-time telemetry only: never feeds the program or keys
        t0 = time.perf_counter()  # cdtlint: disable=D001
        with span("pipeline_call", pipeline=label, **(span_attrs or {})):
            with launch:
                out = jitted(weights, *args, **kw)
            with span("program.wait", pipeline=label):
                jax.block_until_ready(out)
        dt = time.perf_counter() - t0  # cdtlint: disable=D001
        if state["first"]:
            state["first"] = False
            _first_call(label, t0, dt)
        else:
            _tm.PIPELINE_EXECUTE_SECONDS.labels(pipeline=label).observe(dt)
        if steps:
            _tm.SAMPLER_STEP_SECONDS.labels(pipeline=label).observe(
                dt / steps)
        return out

    call.jitted = jitted
    call.weights = weights
    return call


def make_sigma_ladder(spec: GenerationSpec, schedule: NoiseSchedule) -> jax.Array:
    n = max(1, round(spec.steps * spec.denoise))
    if spec.scheduler == "karras":
        smin = float(schedule.sigmas[0])
        smax = float(schedule.sigmas[-1])
        full = sigmas_karras(spec.steps, smin, smax)
    elif spec.scheduler == "normal":
        full = sigmas_normal(spec.steps, schedule)
    elif spec.scheduler == "exponential":
        full = sigmas_exponential(spec.steps, float(schedule.sigmas[0]),
                                  float(schedule.sigmas[-1]))
    elif spec.scheduler == "sgm_uniform":
        full = sigmas_sgm_uniform(spec.steps, schedule)
    elif spec.scheduler == "beta":
        full = sigmas_beta(spec.steps, schedule)
    elif spec.scheduler == "linear_quadratic":
        full = sigmas_linear_quadratic(
            spec.steps, sigma_max=float(schedule.sigmas[-1]))
    else:
        raise ValueError(f"unknown scheduler {spec.scheduler!r}")
    # partial denoise keeps the *tail* of the ladder (img2img convention)
    return full[-(n + 1):]


def sdxl_adm(
    pooled: jax.Array,
    orig_size: tuple[int, int],
    crop: tuple[int, int] = (0, 0),
    target_size: Optional[tuple[int, int]] = None,
) -> jax.Array:
    """SDXL micro-conditioning vector: pooled text ⊕ 6×256-dim Fourier
    embeddings of (orig_h, orig_w, crop_top, crop_left, tgt_h, tgt_w)."""
    target_size = target_size or orig_size
    vals = [orig_size[0], orig_size[1], crop[0], crop[1], target_size[0], target_size[1]]
    embs = [
        timestep_embedding(jnp.full((pooled.shape[0],), float(v)), 256) for v in vals
    ]
    return jnp.concatenate([pooled] + embs, axis=-1)


def inpaint_denoiser(base, src: jax.Array, noise: jax.Array,
                     mask: jax.Array):
    """ComfyUI ``KSamplerX0Inpaint`` semantics (mask: 1 = regenerate).

    Both sides of every model call are composited: the sampler *input* is
    recomposited with the source latent re-noised at the CURRENT sigma —
    using the same fixed ``noise`` draw as the run's initial noising — and
    the denoised *output* is pinned to the source in unmasked regions.
    Input-side recompositing is what keeps ancestral/SDE samplers on the
    reference trajectory near mask boundaries; output-side pinning alone
    only hides the drift for fully-unmasked pixels."""

    def denoise(xx, sigma):
        with device_scope("sampler"):
            xx = xx * mask + (src + noise * sigma) * (1.0 - mask)
        x0 = base(xx, sigma)
        with device_scope("sampler"):
            return x0 * mask + src * (1.0 - mask)

    return denoise


class Txt2ImgPipeline:
    """Bundle of UNet + VAE + schedule with compiled sharded generation.

    ``generate_fn(mesh, spec)`` returns a jitted SPMD function
    ``(key, context, uncond_context, y, uncond_y) -> images`` where images
    is a globally-sharded ``[n_dp · per_device_batch, H, W, 3]`` array in
    [0, 1] (ComfyUI IMAGE layout, ``utils/image.py:8-24`` in the reference).
    """

    def __init__(
        self,
        unet: UNet2D,
        unet_params,
        vae: AutoencoderKL,
        schedule: NoiseSchedule | None = None,
    ):
        self.unet = unet
        self.unet_params = unet_params
        self.vae = vae
        self.schedule = schedule or vp_schedule()

    @property
    def latent_channels(self) -> int:
        return self.unet.config.in_channels

    def _weights(self, img2img: bool = False) -> dict:
        """Weight pytree passed as a jit ARGUMENT. Closing over params
        instead would embed them as lowering constants — for SDXL that is
        >5 GB serialized into the MLIR module (each leaf fetched to host
        first), which makes lowering take minutes and bloats every
        executable."""
        w = {"unet": self.unet_params, "vae_dec": self.vae.dec_params}
        if img2img:
            w["vae_enc"] = self.vae.enc_params
        control_cfg = getattr(self, "_control", None)
        if control_cfg is not None:
            w["control"] = control_cfg[0].params
        return w

    def _denoiser(self, context, y, hint=None, weights=None):
        """``hint``: control map [B,H,W,C] when this pipeline carries a
        ControlNet (``with_control``); residuals are scaled and fed into
        the UNet's control hook every step. Under CFG's batch-dim concat
        the hint tiles to the doubled batch, so control conditions the
        cond AND uncond passes (A1111 convention). ``weights``: explicit
        param pytree (``_weights``) when called under jit."""
        control_cfg = getattr(self, "_control", None)

        def model_fn(x, t, ctx, y_):
            control = None
            if control_cfg is not None and hint is not None:
                cn, strength = control_cfg
                cn_params = (cn.params if weights is None
                             else weights["control"])
                hf = hint.astype(jnp.float32)
                if hf.shape[0] != x.shape[0]:
                    if x.shape[0] % hf.shape[0]:
                        raise ValueError(
                            f"control hint batch {hf.shape[0]} does not "
                            f"divide model batch {x.shape[0]}")
                    hf = jnp.concatenate(
                        [hf] * (x.shape[0] // hf.shape[0]), axis=0)
                down, mid = cn.model.apply(cn_params, x, t, ctx, y_, hf)
                control = ([d * strength for d in down], mid * strength)
            unet_params = (self.unet_params if weights is None
                           else weights["unet"])
            return self.unet.apply(unet_params, x, t, ctx, y_,
                                   control=control)

        return eps_denoiser(model_fn, self.schedule, context, y)

    def with_control(self, cn_bundle, strength: float = 1.0):
        """Clone carrying a ControlNet (fresh compile caches; the base
        pipeline is untouched — same discipline as LoRA patching).
        Clones are memoized per (cn uid, strength) so repeated node
        executions reuse their compiled programs."""
        import copy as _copy

        cache = getattr(self, "_control_clones", None)
        if cache is None:
            cache = self._control_clones = {}
        key = (getattr(cn_bundle, "uid", id(cn_bundle)), float(strength))
        clone = cache.get(key)
        if clone is None:
            if len(cache) >= 4:
                cache.pop(next(iter(cache)))
            clone = _copy.copy(self)
            clone._control = (cn_bundle, float(strength))
            clone._fn_cache = {}
            clone._i2i_cache = {}
            clone._control_clones = {}
            cache[key] = clone
        return clone

    def _build_sampling(self, key, context, uncond_context, y, uncond_y,
                        spec: GenerationSpec, batch: int, sigmas: jax.Array,
                        init_latent: Optional[jax.Array] = None,
                        hint: Optional[jax.Array] = None,
                        progress=None, weights=None,
                        inpaint_mask: Optional[jax.Array] = None):
        """Everything before the sampler scan: noise draw + denoiser
        closure. Returns ``(denoise, x, k_samp)``. ONE definition shared
        by the monolithic ``_sample_and_decode`` and the preemptible
        segment programs (``preemptible_fns``) — the key split, noise
        draw, and guidance wiring must be byte-for-byte the same math on
        both paths or checkpoint/resume loses bit-identity."""
        bc = lambda a: (None if a is None
                        else jnp.broadcast_to(a, (batch,) + a.shape[1:]))
        with device_scope("sampler"):
            k_noise, k_samp = jax.random.split(key)
            if init_latent is None:
                lat_h = spec.height // self.vae.config.downscale
                lat_w = spec.width // self.vae.config.downscale
                noise = jax.random.normal(
                    k_noise, (batch, lat_h, lat_w, self.latent_channels),
                    jnp.float32,
                )
                x = noise * sigmas[0]
            else:
                noise = jax.random.normal(k_noise, init_latent.shape,
                                          jnp.float32)
                x = init_latent + noise * sigmas[0]
            context, y = bc(context), bc(y)
            if spec.guidance_scale != 1.0:
                uncond_context, uncond_y = bc(uncond_context), bc(uncond_y)

        if spec.guidance_scale != 1.0:
            denoise = cfg_denoiser(
                lambda ctx, yy: self._denoiser(ctx, yy, hint=hint,
                                               weights=weights),
                context, uncond_context, spec.guidance_scale, y, uncond_y)
        else:
            denoise = self._denoiser(context, y, hint=hint, weights=weights)
        if inpaint_mask is not None and init_latent is not None:
            denoise = inpaint_denoiser(denoise, init_latent, noise,
                                       inpaint_mask)
        if progress is not None:
            from .progress import wrap_denoiser

            denoise = wrap_denoiser(denoise, progress[0], progress[1])
        return denoise, x, k_samp

    def _sample_and_decode(self, key, context, uncond_context, y, uncond_y,
                           spec: GenerationSpec, batch: int, sigmas: jax.Array,
                           init_latent: Optional[jax.Array] = None,
                           hint: Optional[jax.Array] = None,
                           progress=None, weights=None,
                           inpaint_mask: Optional[jax.Array] = None):
        """Single-shard work: noise → sampler scan → VAE decode.

        ``init_latent`` switches to img2img: the source latent is noised
        to the (partial) ladder's head instead of starting from pure
        noise (k-diffusion img2img convention). ``hint`` feeds the
        pipeline's ControlNet (``with_control``). ``progress`` is an
        optional ``(token, shard_index)`` pair that streams per-step x0
        previews to the host (``diffusion/progress.wrap_denoiser``).
        ``inpaint_mask`` (latent-res [.,h,w,1], 1 = regenerate) applies
        ComfyUI's KSamplerX0Inpaint semantics on both sides of each model
        call: the sampler *input* is recomposited with the source latent
        re-noised at the current sigma (same fixed noise draw as the
        initial noising), and the denoised *output* is pinned to the
        source in unmasked regions — so ancestral/SDE samplers track the
        reference trajectory at mask boundaries, not just at the end."""
        x0 = self._sample_latent(
            key, context, uncond_context, y, uncond_y, spec, batch, sigmas,
            init_latent=init_latent, hint=hint, progress=progress,
            weights=weights, inpaint_mask=inpaint_mask)
        return self._decode_latent(
            x0, None if weights is None else weights["vae_dec"])

    def _sample_latent(self, key, context, uncond_context, y, uncond_y,
                       spec: GenerationSpec, batch: int, sigmas: jax.Array,
                       init_latent: Optional[jax.Array] = None,
                       hint: Optional[jax.Array] = None,
                       progress=None, weights=None,
                       inpaint_mask: Optional[jax.Array] = None):
        """The sampling half of :meth:`_sample_and_decode`: noise →
        sampler scan → final latent ``x0`` (no VAE). ONE definition for
        the fused path and the stage-split denoise programs
        (``latent_microbatch_fn``) — the split must be a pure program
        boundary, never a second copy of the math (docs/stages.md)."""
        denoise, x, k_samp = self._build_sampling(
            key, context, uncond_context, y, uncond_y, spec, batch, sigmas,
            init_latent=init_latent, hint=hint, progress=progress,
            weights=weights, inpaint_mask=inpaint_mask)
        return sample(spec.sampler, denoise, x, sigmas, key=k_samp)

    def _decode_latent(self, x0, vae_params):
        """The decode half: VAE decode + the [0,1] clip. Shared by the
        fused path, the preemptible ``fin`` program, and the decode
        pool's batched program (``decode_fn``) so the image math cannot
        drift between the serving tiers."""
        images = self.vae.decode(x0, params=vae_params)   # cdt.vae_decode
        with device_scope("vae_decode"):
            return jnp.clip(images / 2.0 + 0.5, 0.0, 1.0)

    def generate_fn(self, mesh: Mesh, spec: GenerationSpec,
                    axis: str = constants.AXIS_DATA,
                    progress: bool = False):
        """Compile the SPMD generator over ``mesh[axis]``.

        Every shard derives its own key via ``fold_in(key, axis_index)`` —
        shard 0 is the reference's "master", shard N its worker N
        (``nodes/utilities.py:52-75``) — then samples and decodes its own
        ``per_device_batch`` images. Output dim 0 is sharded over ``axis``
        in participant order (Collector ordering contract,
        ``nodes/collector.py:252-295``).
        """
        has_y = self.unet.config.adm_in_channels > 0
        has_control = getattr(self, "_control", None) is not None
        # ladder is built eagerly (host-side) so it's a compile-time constant
        sigmas = make_sigma_ladder(spec, self.schedule)

        def shard_body(weights, key, context, uncond_context, y, uncond_y,
                       hint=None, token=None):
            k = participant_key(key, axis)
            prog = ((token, jax.lax.axis_index(axis))
                    if token is not None else None)
            return self._sample_and_decode(
                k, context, uncond_context,
                y if has_y else None, uncond_y if has_y else None,
                spec, spec.per_device_batch, sigmas, hint=hint,
                progress=prog, weights=weights,
            )

        # weights lead the argument list (replicated pytree — P() broadcasts
        # over its leaves); passing them as arguments keeps multi-GB params
        # OUT of the lowered module (see _weights)
        # shard_body's trailing defaults (hint=None, token=None) bind the
        # shorter arities directly; only progress-WITHOUT-control needs a
        # wrapper, because there the 7th positional must skip `hint`
        per_shard = shard_body
        in_specs = (P(), P(), P(None, None, None), P(None, None, None),
                    P(None, None), P(None, None))
        if has_control and progress:
            in_specs += (P(None, None, None, None), P())
        elif has_control:
            # control hint rides as a replicated trailing argument
            in_specs += (P(None, None, None, None),)
        elif progress:
            # progress token: replicated int32 scalar, traced so one
            # compiled program serves every run
            per_shard = (lambda w, key, c, u, y_, uy, token:
                         shard_body(w, key, c, u, y_, uy, None, token))
            in_specs += (P(),)
        f = shard_map(
            per_shard, mesh=mesh, in_specs=in_specs,
            out_specs=P(axis, None, None, None),
        )
        jitted = jax.jit(f)
        weights = self._weights()

        return bind_weights(jitted, weights, label="txt2img",
                            steps=len(sigmas) - 1, mesh=mesh)

    def img2img_fn(self, mesh: Mesh, spec: GenerationSpec,
                   axis: str = constants.AXIS_DATA,
                   with_mask: bool = False):
        """Compile the SPMD img2img program over ``mesh[axis]``.

        The source batch is replicated; every shard encodes it, noises it
        at the partial ladder's head (``spec.denoise`` sets the fraction)
        with its participant-folded key, samples the tail, and decodes —
        N seed-varied edits of the same source in one step-time (the
        img2img analogue of the reference's seed-offset fan-out).

        ``with_mask`` adds a trailing image-res mask input [B,H,W,1]
        (1 = repaint): the program downsamples it to latent resolution
        and applies ComfyUI KSamplerX0Inpaint semantics on every model
        call — the sampler input is recomposited with the source latent
        re-noised at the current sigma, and the denoised output is
        pinned to the source (``inpaint_denoiser``)."""
        has_y = self.unet.config.adm_in_channels > 0
        has_control = getattr(self, "_control", None) is not None
        sigmas = make_sigma_ladder(spec, self.schedule)

        base_specs = (P(), P(None, None, None, None), P(),
                      P(None, None, None),
                      P(None, None, None), P(None, None), P(None, None))

        def shard_body(weights, images, key, context, uncond_context, y,
                       uncond_y, hint=None, mask=None):
            k = participant_key(key, axis)
            images = images.astype(jnp.float32)
            lat = self.vae.encode(images * 2.0 - 1.0,
                                  params=weights["vae_enc"])
            m = None
            if mask is not None:
                m = jax.image.resize(
                    mask.astype(jnp.float32),
                    (lat.shape[0], lat.shape[1], lat.shape[2], 1),
                    method="bilinear")
            out = self._sample_and_decode(
                k, context, uncond_context,
                y if has_y else None, uncond_y if has_y else None,
                spec, images.shape[0], sigmas, init_latent=lat,
                hint=hint, weights=weights, inpaint_mask=m,
            )
            if mask is not None:
                # pixel-level composite: the latent pinning keeps seams
                # coherent, but the VAE decoder's global mid-attention
                # still bleeds repainted content everywhere — unmasked
                # pixels must be EXACTLY the source (the final composite
                # every inpainting UI performs)
                out = images * (1.0 - mask) + out * mask
            return out

        # shard_body's trailing defaults bind the shorter arities
        # directly; mask-without-control needs a wrapper to skip `hint`
        per_shard = shard_body
        in_specs = base_specs
        if has_control:
            in_specs += (P(None, None, None, None),)
        if with_mask:
            if not has_control:
                per_shard = (lambda w, im, key, c, u, y_, uy, mask:
                             shard_body(w, im, key, c, u, y_, uy,
                                        None, mask))
            in_specs += (P(None, None, None, None),)
        f = shard_map(
            per_shard, mesh=mesh, in_specs=in_specs,
            out_specs=P(axis, None, None, None),
        )
        jitted = jax.jit(f)
        weights = self._weights(img2img=True)

        return bind_weights(jitted, weights, label="img2img",
                            steps=len(sigmas) - 1, mesh=mesh)

    def img2img(
        self,
        mesh: Mesh,
        spec: GenerationSpec,
        seed: int,
        images: jax.Array,
        context: jax.Array,
        uncond_context: jax.Array,
        y: Optional[jax.Array] = None,
        uncond_y: Optional[jax.Array] = None,
        hint: Optional[jax.Array] = None,
        mask: Optional[jax.Array] = None,
    ) -> jax.Array:
        """One-shot img2img (value-keyed compile cache). ``mask``
        [B,H,W,1] or [B,H,W] (1 = repaint) switches to inpainting."""
        if mask is not None:
            mask = jnp.asarray(mask, jnp.float32)
            if mask.ndim == 3:
                mask = mask[..., None]
        if not hasattr(self, "_i2i_cache"):
            self._i2i_cache: "dict[tuple, Any]" = {}
        key = (self._mesh_cache_key(mesh), spec, tuple(images.shape),
               None if hint is None else tuple(hint.shape),
               mask is not None)
        fn = self._i2i_cache.get(key)
        if fn is None:
            if len(self._i2i_cache) >= self._CACHE_MAX:
                self._i2i_cache.pop(next(iter(self._i2i_cache)))
            fn = self.img2img_fn(mesh, spec, with_mask=mask is not None)
            self._i2i_cache[key] = fn
        if y is None:
            adm = self.unet.config.adm_in_channels
            y = jnp.zeros((1, max(adm, 1)), jnp.float32)
        if uncond_y is None:
            uncond_y = jnp.zeros_like(y)
        args = [jnp.asarray(images, jnp.float32), jax.random.key(seed),
                context, uncond_context, y, uncond_y]
        if getattr(self, "_control", None) is not None:
            if hint is None:
                raise ValueError("pipeline carries a ControlNet but no "
                                 "hint was given")
            args.append(jnp.asarray(hint, jnp.float32))
        if mask is not None:
            args.append(mask)
        return fn(*args)

    def generate(
        self,
        mesh: Mesh,
        spec: GenerationSpec,
        seed: int,
        context: jax.Array,
        uncond_context: jax.Array,
        y: Optional[jax.Array] = None,
        uncond_y: Optional[jax.Array] = None,
        hint: Optional[jax.Array] = None,
        progress_token: Optional[int] = None,
    ) -> jax.Array:
        """Convenience one-shot generate (compiles on first distinct spec).
        ``progress_token``: a ``ProgressTracker.start`` token — enables
        per-step x0 streaming (one extra compiled variant, shared by every
        tokened run)."""
        fn = self._cached_fn(mesh, spec, hint=hint,
                             progress=progress_token is not None)
        if y is None:
            adm = self.unet.config.adm_in_channels
            y = jnp.zeros((1, max(adm, 1)), jnp.float32)
        if uncond_y is None:
            uncond_y = jnp.zeros_like(y)
        key = jax.random.key(seed)
        args = [key, context, uncond_context, y, uncond_y]
        if getattr(self, "_control", None) is not None:
            if hint is None:
                raise ValueError("pipeline carries a ControlNet but no "
                                 "hint was given")
            args.append(jnp.asarray(hint, jnp.float32))
        if progress_token is not None:
            args.append(jnp.asarray(progress_token, jnp.int32))
        return fn(*args)

    _CACHE_MAX = 8

    # back-compat alias — the shared definition lives at module level
    _mesh_cache_key = staticmethod(mesh_cache_key)

    def _cached_fn(self, mesh: Mesh, spec: GenerationSpec, hint=None,
                   progress: bool = False):
        key = (self._mesh_cache_key(mesh), spec,
               None if hint is None else tuple(hint.shape), progress)
        return cached_build(
            self, key, lambda: self.generate_fn(mesh, spec,
                                                progress=progress),
            self._CACHE_MAX)

    # --- step-granular preemption (docs/preemption.md) ----------------------

    def preemptible_fns(self, mesh: Mesh, spec: GenerationSpec,
                        axis: str = constants.AXIS_DATA):
        """The solo generator split at segment boundaries: three compiled
        SPMD pieces over the same shard math as :meth:`generate_fn` —

        - ``prep(key, ctx, unc, y, uy) -> carry``: participant key
          fold-in + noise draw + the sampler's ``init``;
        - ``seg(L)(key, ctx, unc, y, uy, start, carry) -> (carry, sigma,
          x0)``: ``L`` denoise steps from traced global index ``start``
          (one compiled program per distinct length serves every
          offset), with the last step's sigma and x0 estimate (one
          latent a shard) as outputs for the progress stream — no host
          callback, so the program persists in the compile cache;
        - ``fin(carry) -> images``: output-slot extract + VAE decode.

        The carry rides shard_map per the sampler contract
        (``diffusion/samplers.py``): state-shaped leaves shard over
        ``axis``, step-derived scalars replicate. Between segments the
        carry can be materialized to host numpy (a
        :class:`~..diffusion.checkpoint.LatentCheckpoint`) and resumed
        on any worker with the same dp width — bit-identically, because
        every step applies the same closure at the same global index
        (tested: ``tests/test_checkpoint.py``,
        ``tests/test_preemption.py``)."""
        return cached_build(
            self, ("segments", mesh_cache_key(mesh), spec, axis),
            lambda: self._build_preemptible(mesh, spec, axis),
            self._CACHE_MAX)

    def _build_preemptible(self, mesh: Mesh, spec: GenerationSpec,
                           axis: str) -> dict:
        """What :meth:`preemptible_fns` caches, one per (mesh, spec)."""
        from .progress import DenoiserTap
        from .samplers import carry_structure, extract_output, make_program
        from .samplers import run_segment as _run_segment

        has_y = self.unet.config.adm_in_channels > 0
        sigmas = make_sigma_ladder(spec, self.schedule)
        n = len(sigmas) - 1
        B = spec.per_device_batch
        lat_h = spec.height // self.vae.config.downscale
        lat_w = spec.width // self.vae.config.downscale
        x_shape = (B, lat_h, lat_w, self.latent_channels)
        x_struct = jax.ShapeDtypeStruct(x_shape, jnp.float32)
        carry_struct = carry_structure(spec.sampler, x_struct)
        carry_specs = tuple(
            P(axis, *(None,) * (len(leaf.shape) - 1))
            if tuple(leaf.shape) == x_shape else P()
            for leaf in carry_struct)
        base_specs = (P(), P(), P(None, None, None), P(None, None, None),
                      P(None, None), P(None, None))
        weights = self._weights()

        def build_program(weights, key, context, uncond, y, uy,
                          token=None):
            k = participant_key(key, axis)
            # in-trace progress rides exactly like generate_fn's token
            # variant: each denoise call streams its x0 preview — the
            # callback only OBSERVES, so bit-identity is untouched
            prog_pair = ((token, jax.lax.axis_index(axis))
                         if token is not None else None)
            denoise, x, k_samp = self._build_sampling(
                k, context, uncond,
                y if has_y else None, uy if has_y else None,
                spec, B, sigmas, progress=prog_pair, weights=weights)
            # the tap costs a program nothing unless run_segment is asked
            # to emit what it kept
            tap = DenoiserTap(denoise)
            return make_program(spec.sampler, tap, sigmas,
                                key=k_samp), x, tap

        def prep_body(weights, key, context, uncond, y, uy):
            prog, x, _ = build_program(weights, key, context, uncond, y, uy)
            return prog.init(x)

        prep = bind_weights(jax.jit(shard_map(
            prep_body, mesh=mesh, in_specs=base_specs,
            out_specs=carry_specs)), weights, name="txt2img_prep",
            mesh=mesh)

        def make_seg(length: int, with_token: bool):
            if with_token:
                # the callback-carrying form: no lane of serve runs it
                # (it is never persisted and launches in ~0.055 s); kept
                # for the off-chip description in cdtbench/kinds/unet.py
                def seg_body(weights, key, context, uncond, y, uy,
                             start, carry, token):
                    prog, _, _ = build_program(weights, key, context,
                                               uncond, y, uy, token=token)
                    return _run_segment(prog, tuple(carry), start,
                                        length)

                in_specs = base_specs + (P(), carry_specs, P())
                out_specs = carry_specs
            else:
                # the served form: no host effect; the last step's x0
                # (first batch element of each shard) and its sigma
                # leave as outputs beside the carry, which gains no leaf
                def seg_body(weights, key, context, uncond, y, uy,
                             start, carry):
                    prog, _, tap = build_program(weights, key, context,
                                                 uncond, y, uy)
                    carry, (sigma, x0) = _run_segment(
                        prog, tuple(carry), start, length, tap=tap)
                    return carry, sigma, x0

                in_specs = base_specs + (P(), carry_specs)
                out_specs = (carry_specs, P(),
                             P(axis, *(None,) * (len(x_shape) - 1)))
            return bind_weights(jax.jit(shard_map(
                seg_body, mesh=mesh, in_specs=in_specs,
                out_specs=out_specs)), weights,
                label="txt2img_seg", steps=length, mesh=mesh)

        def fin_body(weights, carry):
            x0 = extract_output(spec.sampler, tuple(carry))
            return self._decode_latent(x0, weights["vae_dec"])

        fin = bind_weights(jax.jit(shard_map(
            fin_body, mesh=mesh, in_specs=(P(), carry_specs),
            out_specs=P(axis, None, None, None))), weights,
            name="txt2img_fin", mesh=mesh)

        segs: "dict[tuple, Any]" = {}

        def seg(length: int, with_token: bool = False):
            fn = segs.get((length, with_token))
            if fn is None:
                fn = segs[(length, with_token)] = make_seg(length,
                                                           with_token)
            return fn

        n_dp = dict(mesh.shape)[axis]
        global_shapes = tuple(
            (n_dp * B,) + tuple(leaf.shape[1:])
            if tuple(leaf.shape) == x_shape else tuple(leaf.shape)
            for leaf in carry_struct)
        return {"prep": prep, "seg": seg, "fin": fin, "n_steps": n,
                "carry_shapes": global_shapes}

    def checkpoint_identity(self, mesh: Mesh, spec: GenerationSpec,
                            seed: int,
                            axis: str = constants.AXIS_DATA,
                            conditioning=None) -> dict:
        """The run-identity dict a checkpoint must match to resume this
        exact trajectory (validated by ``LatentCheckpoint.validate_meta``
        — a mismatch is a restore failure, never a silent wrong image).
        ``conditioning`` (the (context, uncond, y, uy) tuple) binds the
        checkpoint to the PROMPT CONTENT: without it, a different prompt
        with coincidentally equal sampler/geometry/seed could resume
        someone else's half-denoised latent into a blended image."""
        identity = {
            "sampler": spec.sampler, "scheduler": spec.scheduler,
            "steps": int(spec.steps), "height": int(spec.height),
            "width": int(spec.width), "cfg": float(spec.guidance_scale),
            "per_device_batch": int(spec.per_device_batch),
            "seed": int(seed), "n_dp": int(dict(mesh.shape)[axis]),
        }
        if conditioning is not None:
            identity["conditioning"] = _conditioning_digest(*conditioning)
        return identity

    def generate_preemptible(
        self,
        mesh: Mesh,
        spec: GenerationSpec,
        seed: int,
        context: jax.Array,
        uncond_context: jax.Array,
        y: Optional[jax.Array] = None,
        uncond_y: Optional[jax.Array] = None,
        *,
        segment_steps: Optional[int] = None,
        should_preempt=None,
        resume=None,
        on_step=None,
    ) -> dict:
        """Run the solo generation in resumable K-step segments.

        Between segments ``should_preempt()`` is consulted (cheap host
        callback; returns a reason string or None). On preemption the
        FULL sampler carry is materialized and returned as
        ``{"checkpoint": LatentCheckpoint, "reason": str}`` — nothing is
        decoded, nothing is lost. ``resume`` restores a prior
        checkpoint (identity-validated; a mismatch raises
        :class:`~.checkpoint.CheckpointRestoreError` toward the bounded
        resume-retry machinery). Completion returns
        ``{"images": array}`` — bit-identical to :meth:`generate` for
        the same inputs, interrupted or not.

        At least one segment always runs per invocation, so a
        preempt-storm cannot live-lock a job into never advancing.

        ``on_step(sigma, x0, calls=, shard=)`` is the host-side progress
        reporter (``_ProgressScope.on_step``): after every segment it is
        handed, per dp shard, the x0 estimate of the segment's last step
        and the model calls the segment made — read from the segment
        program's outputs, never through a host callback."""
        import numpy as np

        from ..utils import constants as _c
        from .checkpoint import CheckpointRestoreError, LatentCheckpoint
        from .progress import deliver_segment, segment_calls

        seg_steps = max(1, int(segment_steps
                               or _c.PREEMPT_SEGMENT_STEPS.get()))
        bundle = self.preemptible_fns(mesh, spec)
        n = bundle["n_steps"]
        if y is None:
            adm = self.unet.config.adm_in_channels
            y = jnp.zeros((1, max(adm, 1)), jnp.float32)
        if uncond_y is None:
            uncond_y = jnp.zeros_like(y)
        args = (jax.random.key(seed), context, uncond_context, y, uncond_y)
        identity = self.checkpoint_identity(
            mesh, spec, seed,
            conditioning=(context, uncond_context, y, uncond_y))

        resume_t0 = None
        if resume is not None:
            resume.validate_meta(identity)
            got = tuple(tuple(np.asarray(leaf).shape)
                        for leaf in resume.carry)
            if got != bundle["carry_shapes"]:
                raise CheckpointRestoreError(
                    f"checkpoint carry shapes {got} do not match this "
                    f"program's {bundle['carry_shapes']}")
            if not 0 <= resume.step <= n:
                raise CheckpointRestoreError(
                    f"checkpoint step {resume.step} outside ladder "
                    f"0..{n}")
            # resume latency: device upload + the first segment program
            resume_t0 = time.perf_counter()  # cdtlint: disable=D001
            carry = tuple(jnp.asarray(leaf) for leaf in resume.carry)
            start = int(resume.step)
        else:
            carry = bundle["prep"](*args)
            start = 0

        from ..telemetry.spans import span

        done_here = 0
        while start < n:
            # the host work between two launches that is not the launch:
            # the preempt check, the scalar uploads, the program lookup
            with span("segment.boundary", step=start):
                if done_here > 0 and should_preempt is not None:
                    reason = should_preempt()
                    if reason:
                        leaves = tuple(np.asarray(leaf)
                                       for leaf in jax.device_get(carry))
                        ckpt = LatentCheckpoint(
                            sampler=spec.sampler, step=start,
                            total_steps=n, carry=leaves, meta=identity)
                        return {"checkpoint": ckpt, "reason": reason,
                                "step": start}
                length = min(seg_steps, n - start)
                seg = bundle["seg"](length)
                at = jnp.int32(start)
            carry, sigma, previews = seg(*args, at, carry)
            # materialize: the segment boundary IS the preemption point —
            # an unbounded dispatch pipeline would make it meaningless
            jax.block_until_ready(carry)
            if on_step is not None:
                deliver_segment(on_step, sigma, previews, segment_calls(
                    spec.sampler, start, length, n))
            if resume_t0 is not None:
                from .. import telemetry
                if telemetry.enabled():
                    from ..telemetry import metrics as _tm
                    _tm.RESUME_SECONDS.observe(
                        time.perf_counter() - resume_t0)  # cdtlint: disable=D001
                resume_t0 = None
            start += length
            done_here += length
        return {"images": bundle["fin"](carry), "step": n}

    # --- near-tier trajectory reuse (cluster/cache/fleet.py) ---------------

    def near_fn(self, mesh: Mesh, spec: GenerationSpec,
                axis: str = constants.AXIS_DATA):
        """Compile the trajectory-reuse program: a replicated donor
        LATENT (a mid-trajectory sampler state from the fleet cache's
        near tier) is re-noised at the partial ladder's head with each
        shard's own participant-folded key, then the remaining tail is
        sampled and decoded. This is :meth:`img2img_fn`'s math with the
        VAE encode replaced by the donor latent — ``spec.denoise``
        (remaining/total) selects the tail. Deliberately NOT
        bit-identical to a from-scratch run: the donor state stands in
        for a clean init, and the fresh draw re-rolls the trajectory
        under the request's own seed (docs/caching.md, "Fleet tier")."""
        has_y = self.unet.config.adm_in_channels > 0
        sigmas = make_sigma_ladder(spec, self.schedule)

        def shard_body(weights, latent, key, context, uncond_context, y,
                       uncond_y):
            k = participant_key(key, axis)
            return self._sample_and_decode(
                k, context, uncond_context,
                y if has_y else None, uncond_y if has_y else None,
                spec, latent.shape[0], sigmas,
                init_latent=latent.astype(jnp.float32), weights=weights,
            )

        in_specs = (P(), P(None, None, None, None), P(),
                    P(None, None, None), P(None, None, None),
                    P(None, None), P(None, None))
        f = shard_map(shard_body, mesh=mesh, in_specs=in_specs,
                      out_specs=P(axis, None, None, None))
        return bind_weights(jax.jit(f), self._weights(),
                            label="txt2img_near",
                            steps=len(sigmas) - 1, mesh=mesh)

    def generate_near(
        self,
        mesh: Mesh,
        spec: GenerationSpec,
        seed: int,
        latent: jax.Array,
        context: jax.Array,
        uncond_context: jax.Array,
        y: Optional[jax.Array] = None,
        uncond_y: Optional[jax.Array] = None,
    ) -> jax.Array:
        """One-shot near-tier generation from a donor latent
        (value-keyed compile cache; ``spec.denoise`` must carry the
        remaining-step fraction)."""
        key = ("near", self._mesh_cache_key(mesh), spec,
               tuple(latent.shape))
        fn = cached_build(self, key,
                          lambda: self.near_fn(mesh, spec),
                          self._CACHE_MAX)
        if y is None:
            adm = self.unet.config.adm_in_channels
            y = jnp.zeros((1, max(adm, 1)), jnp.float32)
        if uncond_y is None:
            uncond_y = jnp.zeros_like(y)
        return fn(jnp.asarray(latent, jnp.float32), jax.random.key(seed),
                  context, uncond_context, y, uncond_y)

    # --- cross-request microbatching (cluster/frontdoor) -------------------

    def microbatch_fn(self, mesh: Mesh, spec: GenerationSpec,
                      n_requests: int, axis: str = constants.AXIS_DATA):
        """Compile ONE SPMD program executing ``n_requests`` independent
        generations (stacked seeds + per-request conditioning) in a single
        dispatch — the front door's cross-user microbatch.

        Bit-identity contract: each request's subgraph is the *solo*
        program's math, unrolled — per-request ``fold_in`` of its own
        seed, per-request noise draw with the solo tensor shapes, and a
        trailing concat along the batch axis. Stacking requests *inside*
        the matmul batch dimension instead (one ``[R·B, …]`` UNet call)
        is NOT used: XLA's reduction strategy changes with the batch
        extent, which breaks the bit-identical-to-solo guarantee the
        demux relies on (measured on CPU: ~1e-2 drift after 3 steps).
        The unrolled form keeps every per-request tensor shape equal to
        the solo program's, so XLA computes identical values while still
        amortizing dispatch, scheduling the independent subgraphs inside
        one executable, and emitting one sharded output.

        Output rows are shard-major then request-major then batch:
        request ``r`` occupies rows ``[i·R·B + r·B, i·R·B + (r+1)·B)`` of
        each shard block ``i`` (see :func:`demux_microbatch`).

        Only deterministic samplers are microbatchable: stochastic
        samplers draw step noise shaped by the whole batch from one key
        (``samplers.py``), which cannot reproduce N solo runs.
        """
        if spec.sampler not in DETERMINISTIC_SAMPLERS:
            raise ValueError(
                f"sampler {spec.sampler!r} is stochastic — microbatching "
                f"requires one of {sorted(DETERMINISTIC_SAMPLERS)}")
        if getattr(self, "_control", None) is not None:
            raise ValueError("microbatching does not support ControlNet "
                             "pipelines (per-request hints are not stacked)")
        has_y = self.unet.config.adm_in_channels > 0
        sigmas = make_sigma_ladder(spec, self.schedule)
        R, B = int(n_requests), spec.per_device_batch

        def shard_body(weights, seeds, contexts, uncond_contexts, ys, uys):
            outs = []
            for r in range(R):
                k = participant_key(jax.random.key(seeds[r]), axis)
                outs.append(self._sample_and_decode(
                    k, contexts[r:r + 1], uncond_contexts[r:r + 1],
                    ys[r:r + 1] if has_y else None,
                    uys[r:r + 1] if has_y else None,
                    spec, B, sigmas, weights=weights))
            return jnp.concatenate(outs, axis=0)

        f = shard_map(
            shard_body, mesh=mesh,
            in_specs=(P(), P(), P(None, None, None), P(None, None, None),
                      P(None, None), P(None, None)),
            out_specs=P(axis, None, None, None),
        )
        return bind_weights(jax.jit(f), self._weights(),
                            label="txt2img_mb", steps=len(sigmas) - 1,
                            mesh=mesh)

    def microbatch_tp_fn(self, mesh: Mesh, spec: GenerationSpec,
                         n_requests: int,
                         dp_axis: str = constants.AXIS_DATA,
                         tp_axis: str = constants.AXIS_TENSOR):
        """Mesh-tier microbatch: the SAME unrolled per-request subgraphs
        as :meth:`microbatch_fn`, executed on a dp×tp mesh — UNet
        weights shard over ``tp`` (Megatron column/row rules,
        ``parallel/tensor.py``) and the dp seed fan-out is a vmapped
        per-shard fold-in GSPMD partitions over ``dp``, so each device
        computes the solo program's local shapes while holding 1/tp of
        the weights. This is what lets a microbatched group serve models
        too large to replicate — the mesh tier as the front door's
        default placement, not a benchmark mode.

        Equivalence contract — WEAKER than :meth:`microbatch_fn`'s:
        key derivation (``fold_in(key(seed), i)`` per dp shard) and the
        unrolled per-request structure match the solo path exactly, but
        tp splits matmul contractions and the vmapped dp fan-out
        re-batches ops, both of which reassociate float sums — outputs
        track solo runs to the repo's 2e-4 sharding tolerance (f32),
        NOT bit-identically (tested:
        ``test_mesh_serving.TestMeshTierMicrobatch``). The
        content-addressed result cache stays sound because its keys
        include ``execution_signature(mesh)`` — entries never span
        placements — and ``CDT_MESH_TIER=0`` restores the bit-identical
        replicated-weights path on any mesh. Output row order matches
        :func:`demux_microbatch` (shard-major, request, batch)."""
        if spec.sampler not in DETERMINISTIC_SAMPLERS:
            raise ValueError(
                f"sampler {spec.sampler!r} is stochastic — microbatching "
                f"requires one of {sorted(DETERMINISTIC_SAMPLERS)}")
        if getattr(self, "_control", None) is not None:
            raise ValueError("microbatching does not support ControlNet "
                             "pipelines (per-request hints are not stacked)")
        from ..ops.attention import tp_shard_scope
        from ..parallel.tensor import (UNET_TP_RULES, require_tp_match,
                                       shard_params)

        has_y = self.unet.config.adm_in_channels > 0
        sigmas = make_sigma_ladder(spec, self.schedule)
        R, B = int(n_requests), spec.per_device_batch
        shape = dict(mesh.shape)
        n_dp, tp = shape[dp_axis], shape[tp_axis]
        # same fail-fast as generate_tp_fn: a model matching no rule
        # would silently serve the "tp" path fully replicated and OOM
        # as an opaque allocator error at the scale this tier exists for
        require_tp_match(self.unet_params, mesh, UNET_TP_RULES, tp_axis,
                         "unet")
        # tp-placed weights ride as committed sharded ARGUMENTS (vae/
        # norm leaves match no rule and replicate); GSPMD propagates the
        # layouts and inserts the row-parallel all-reduces. ONE sharded
        # copy per mesh, shared across every (spec, bucket) program —
        # a fresh copy per cache entry would multiply per-chip HBM by
        # the entry count on exactly the models this tier exists for
        if not hasattr(self, "_tp_weights_cache"):
            self._tp_weights_cache: "dict[tuple, Any]" = {}
        weights = cached_build(
            self._tp_weights_cache, (mesh_cache_key(mesh), tp_axis),
            lambda: shard_params(self._weights(), mesh, UNET_TP_RULES,
                                 tp_axis), 2)

        def run(weights, seeds, contexts, uncond_contexts, ys, uys):
            # traced inside the tp scope so every attention site resolves
            # its PER-SHARD (H/tp) kernel choice (ops/attention.py)
            with tp_shard_scope(tp):
                def per_dp(i):
                    outs = []
                    for r in range(R):
                        k = jax.random.fold_in(
                            jax.random.key(seeds[r]), i)
                        outs.append(self._sample_and_decode(
                            k, contexts[r:r + 1],
                            uncond_contexts[r:r + 1],
                            ys[r:r + 1] if has_y else None,
                            uys[r:r + 1] if has_y else None,
                            spec, B, sigmas, weights=weights))
                    return jnp.concatenate(outs, axis=0)

                out = jax.vmap(per_dp)(jnp.arange(n_dp))
            out = jax.lax.with_sharding_constraint(
                out, NamedSharding(mesh, P(dp_axis, None, None, None,
                                           None)))
            return out.reshape((n_dp * R * B,) + out.shape[2:])

        return bind_weights(jax.jit(run), weights, label="txt2img_mb_tp",
                            steps=len(sigmas) - 1)

    def _stack_requests(self, seeds, contexts, uncond_contexts, ys, uys):
        """Pad a request list to the next power-of-two bucket and stack
        the per-request inputs for a microbatched program (compile-count
        bound: programs exist only for R ∈ {1, 2, 4, 8, …}; pad slots
        repeat request 0 and are dropped at demux). One definition for
        the fused and latent (stage-split) microbatch entry points."""
        R = len(seeds)
        if not (R == len(contexts) == len(uncond_contexts)):
            raise ValueError("seeds/contexts/uncond_contexts length mismatch")
        adm = self.unet.config.adm_in_channels

        def norm_y(y):
            return (jnp.zeros((1, max(adm, 1)), jnp.float32)
                    if y is None else jnp.asarray(y, jnp.float32))

        ys = [norm_y(y) for y in (ys or [None] * R)]
        uys = [norm_y(y) for y in (uys or [None] * R)]
        bucket = 1
        while bucket < R:
            bucket *= 2
        pad = bucket - R
        seeds_arr = jnp.asarray(list(seeds) + [seeds[0]] * pad, jnp.int32)
        ctx = jnp.concatenate(list(contexts) + [contexts[0]] * pad, axis=0)
        unc = jnp.concatenate(
            list(uncond_contexts) + [uncond_contexts[0]] * pad, axis=0)
        y_s = jnp.concatenate(ys + [ys[0]] * pad, axis=0)
        uy_s = jnp.concatenate(uys + [uys[0]] * pad, axis=0)
        return bucket, seeds_arr, ctx, unc, y_s, uy_s

    def _microbatch_dispatch(self, mesh, spec, seeds, contexts,
                             uncond_contexts, ys, uys, latent: bool):
        """Shared bucket/cache/route/demux core of
        :meth:`generate_microbatch` and :meth:`generate_latents`."""
        bucket, seeds_arr, ctx, unc, y_s, uy_s = self._stack_requests(
            seeds, contexts, uncond_contexts, ys, uys)
        if not hasattr(self, "_mb_cache"):
            self._mb_cache: "dict[tuple, Any]" = {}
        key = (self._mesh_cache_key(mesh), spec, bucket,
               tuple(ctx.shape[1:]), tuple(unc.shape[1:]),
               tuple(y_s.shape[1:]), latent)
        # mesh tier: a tp axis in the serving mesh routes the group to
        # the tp-sharded program (docs/parallelism.md) — same unrolled
        # subgraphs, weights sharded instead of replicated.
        # CDT_MESH_TIER=0 keeps the replicated-weights fan-out (the
        # shard_map program ignores the tp axis).
        from ..parallel.serving import mesh_tier_enabled

        tp = dict(mesh.shape).get(constants.AXIS_TENSOR, 1)
        use_tp = tp > 1 and mesh_tier_enabled()
        key += (use_tp,)
        if latent:
            build = (lambda: self.latent_microbatch_tp_fn(mesh, spec, bucket)
                     if use_tp
                     else self.latent_microbatch_fn(mesh, spec, bucket))
        else:
            build = (lambda: self.microbatch_tp_fn(mesh, spec, bucket)
                     if use_tp else self.microbatch_fn(mesh, spec, bucket))
        fn = cached_build(self._mb_cache, key, build, self._CACHE_MAX)
        out = fn(seeds_arr, ctx, unc, y_s, uy_s)
        return demux_microbatch(out, mesh, bucket,
                                spec.per_device_batch)[:len(seeds)]

    def generate_microbatch(
        self,
        mesh: Mesh,
        spec: GenerationSpec,
        seeds: "list[int]",
        contexts: "list[jax.Array]",
        uncond_contexts: "list[jax.Array]",
        ys: "list[Optional[jax.Array]] | None" = None,
        uys: "list[Optional[jax.Array]] | None" = None,
    ) -> "list[jax.Array]":
        """Execute N same-shape requests as one microbatched program and
        demux: returns one ``[n_dp · per_device_batch, H, W, 3]`` array
        per request, each bit-identical to
        ``generate(mesh, spec, seeds[r], contexts[r], …)``.

        Group size is bucketed to the next power of two (compile-count
        bound: programs exist only for R ∈ {2, 4, 8, …}); the pad slots
        repeat request 0 and their outputs are dropped at demux. Every
        request's context/uncond/y must share one shape — the front
        door's batcher sub-groups by shape before calling."""
        return self._microbatch_dispatch(mesh, spec, seeds, contexts,
                                         uncond_contexts, ys, uys,
                                         latent=False)

    # --- stage-split serving (cluster/stages, docs/stages.md) ---------------

    def latent_microbatch_fn(self, mesh: Mesh, spec: GenerationSpec,
                             n_requests: int,
                             axis: str = constants.AXIS_DATA):
        """:meth:`microbatch_fn` stopped at the final latent: the same
        unrolled per-request sampling subgraphs (same fold-in, same
        noise draw, same solo tensor shapes), NO VAE decode. This is the
        denoise pool's program in stage-split serving — the decode pool
        finishes the request with :meth:`decode_latents`, and the pair
        is bit-identical to the fused program (the PR 14 seg/fin
        precedent: a materialized program boundary on the x0 latent
        preserves every byte; tested in
        ``tests/test_stages_equivalence.py``).

        Output: ``[n_dp · R · B, lat_h, lat_w, latent_channels]`` f32,
        row order per :func:`demux_microbatch`. The weight pytree
        carries the UNet only — the decode pool holds the VAE, which is
        exactly the residency win the stage split exists for."""
        if spec.sampler not in DETERMINISTIC_SAMPLERS:
            raise ValueError(
                f"sampler {spec.sampler!r} is stochastic — microbatching "
                f"requires one of {sorted(DETERMINISTIC_SAMPLERS)}")
        if getattr(self, "_control", None) is not None:
            raise ValueError("microbatching does not support ControlNet "
                             "pipelines (per-request hints are not stacked)")
        has_y = self.unet.config.adm_in_channels > 0
        sigmas = make_sigma_ladder(spec, self.schedule)
        R, B = int(n_requests), spec.per_device_batch

        def shard_body(weights, seeds, contexts, uncond_contexts, ys, uys):
            outs = []
            for r in range(R):
                k = participant_key(jax.random.key(seeds[r]), axis)
                outs.append(self._sample_latent(
                    k, contexts[r:r + 1], uncond_contexts[r:r + 1],
                    ys[r:r + 1] if has_y else None,
                    uys[r:r + 1] if has_y else None,
                    spec, B, sigmas, weights=weights))
            return jnp.concatenate(outs, axis=0)

        f = shard_map(
            shard_body, mesh=mesh,
            in_specs=(P(), P(), P(None, None, None), P(None, None, None),
                      P(None, None), P(None, None)),
            out_specs=P(axis, None, None, None),
        )
        return bind_weights(jax.jit(f), {"unet": self.unet_params},
                            label="txt2img_lat", steps=len(sigmas) - 1,
                            mesh=mesh)

    def latent_microbatch_tp_fn(self, mesh: Mesh, spec: GenerationSpec,
                                n_requests: int,
                                dp_axis: str = constants.AXIS_DATA,
                                tp_axis: str = constants.AXIS_TENSOR):
        """Mesh-tier denoise-only microbatch: :meth:`microbatch_tp_fn`
        stopped at the final latent. Same equivalence contract as the
        fused tp program (the repo 2e-4 f32 sharding tolerance, NOT
        bit-identity — docs/parallelism.md); ``CDT_MESH_TIER=0``
        restores the bit-identical replicated path."""
        if spec.sampler not in DETERMINISTIC_SAMPLERS:
            raise ValueError(
                f"sampler {spec.sampler!r} is stochastic — microbatching "
                f"requires one of {sorted(DETERMINISTIC_SAMPLERS)}")
        if getattr(self, "_control", None) is not None:
            raise ValueError("microbatching does not support ControlNet "
                             "pipelines (per-request hints are not stacked)")
        from ..ops.attention import tp_shard_scope
        from ..parallel.tensor import (UNET_TP_RULES, require_tp_match,
                                       shard_params)

        has_y = self.unet.config.adm_in_channels > 0
        sigmas = make_sigma_ladder(spec, self.schedule)
        R, B = int(n_requests), spec.per_device_batch
        shape = dict(mesh.shape)
        n_dp, tp = shape[dp_axis], shape[tp_axis]
        require_tp_match(self.unet_params, mesh, UNET_TP_RULES, tp_axis,
                         "unet")
        if not hasattr(self, "_tp_weights_cache"):
            self._tp_weights_cache: "dict[tuple, Any]" = {}
        weights = cached_build(
            self._tp_weights_cache, (mesh_cache_key(mesh), tp_axis),
            lambda: shard_params(self._weights(), mesh, UNET_TP_RULES,
                                 tp_axis), 2)

        def run(weights, seeds, contexts, uncond_contexts, ys, uys):
            with tp_shard_scope(tp):
                def per_dp(i):
                    outs = []
                    for r in range(R):
                        k = jax.random.fold_in(
                            jax.random.key(seeds[r]), i)
                        outs.append(self._sample_latent(
                            k, contexts[r:r + 1],
                            uncond_contexts[r:r + 1],
                            ys[r:r + 1] if has_y else None,
                            uys[r:r + 1] if has_y else None,
                            spec, B, sigmas, weights=weights))
                    return jnp.concatenate(outs, axis=0)

                out = jax.vmap(per_dp)(jnp.arange(n_dp))
            out = jax.lax.with_sharding_constraint(
                out, NamedSharding(mesh, P(dp_axis, None, None, None,
                                           None)))
            return out.reshape((n_dp * R * B,) + out.shape[2:])

        return bind_weights(jax.jit(run), weights,
                            label="txt2img_lat_tp",
                            steps=len(sigmas) - 1)

    def generate_latents(
        self,
        mesh: Mesh,
        spec: GenerationSpec,
        seeds: "list[int]",
        contexts: "list[jax.Array]",
        uncond_contexts: "list[jax.Array]",
        ys: "list[Optional[jax.Array]] | None" = None,
        uys: "list[Optional[jax.Array]] | None" = None,
    ) -> "list[jax.Array]":
        """:meth:`generate_microbatch` for the stage-split denoise pool:
        one ``[n_dp · per_device_batch, lat_h, lat_w, C]`` latent per
        request, each carrying exactly the bytes the fused program would
        have fed its VAE. Feed the results (possibly coalesced across
        groups) to :meth:`decode_latents`."""
        return self._microbatch_dispatch(mesh, spec, seeds, contexts,
                                         uncond_contexts, ys, uys,
                                         latent=True)

    def decode_fn(self, mesh: Mesh, n_items: int,
                  axis: str = constants.AXIS_DATA):
        """Compile ONE batched VAE decode program: ``n_items`` latents
        (stacked on a leading axis, each ``[n_dp · B, h, w, C]``) decode
        as unrolled per-item subgraphs — per-shard shapes equal to the
        fused program's decode, so the images are bit-identical to the
        fused path while the decode pool amortizes one program over
        every concurrent request in the shape bucket
        (docs/stages.md)."""

        def shard_body(weights, lats):
            # lats per shard: [R, B, h, w, C]; each item decodes at the
            # solo shape — stacking into the conv batch dim instead
            # would reassociate reductions (the microbatch_fn lesson)
            outs = [self._decode_latent(lats[r], weights["vae_dec"])
                    for r in range(int(n_items))]
            return jnp.concatenate(outs, axis=0)

        f = shard_map(
            shard_body, mesh=mesh,
            in_specs=(P(), P(None, axis, None, None, None)),
            out_specs=P(axis, None, None, None),
        )
        return bind_weights(jax.jit(f), {"vae_dec": self.vae.dec_params},
                            label="vae_decode_batch", mesh=mesh)

    def decode_latents(self, mesh: Mesh, latents: "list",
                       per_device_batch: "int | None" = None) -> "list":
        """Decode N final latents (any mix of requests sharing one shape
        bucket) in one batched program; returns one image array per
        latent, bit-identical to the fused path's decode of the same
        bytes. Batch count is bucketed to the next power of two (pad
        repeats item 0, dropped at demux) so compile count stays
        bounded however the decode pool's windows land."""
        R = len(latents)
        if R == 0:
            return []
        lats = [jnp.asarray(lat, jnp.float32) for lat in latents]
        first = tuple(lats[0].shape)
        for lat in lats[1:]:
            if tuple(lat.shape) != first:
                raise ValueError(
                    f"decode batch mixes latent shapes {first} and "
                    f"{tuple(lat.shape)} — bucket by shape first")
        n_dp = dict(mesh.shape)[constants.AXIS_DATA]
        if first[0] % n_dp:
            raise ValueError(
                f"latent rows {first[0]} not divisible by mesh dp width "
                f"{n_dp}")
        B = (first[0] // n_dp if per_device_batch is None
             else int(per_device_batch))
        bucket = 1
        while bucket < R:
            bucket *= 2
        stacked = jnp.stack(lats + [lats[0]] * (bucket - R), axis=0)
        if not hasattr(self, "_dec_cache"):
            self._dec_cache: "dict[tuple, Any]" = {}
        key = (self._mesh_cache_key(mesh), bucket, first)
        fn = cached_build(self._dec_cache, key,
                          lambda: self.decode_fn(mesh, bucket),
                          self._CACHE_MAX)
        out = fn(stacked)
        return demux_microbatch(out, mesh, bucket, B)[:R]


# samplers whose trajectory is a pure function of (noise, conditioning):
# their compiled step never consumes the sampling key, so N solo runs can
# be replayed exactly inside one microbatched program. The stochastic
# families (euler_ancestral, lcm, dpmpp_sde, ddim with eta>0) draw
# batch-shaped step noise from a single key and are excluded.
DETERMINISTIC_SAMPLERS = frozenset({"euler", "heun", "dpmpp_2m", "ddim"})


def _conditioning_digest(*arrays) -> str:
    """Content digest of a conditioning tuple (shape + dtype + bytes per
    tensor; None slots pinned) — the checkpoint-identity component that
    ties a parked latent to its PROMPT, not just its geometry."""
    import hashlib

    import numpy as np

    h = hashlib.sha256()
    for a in arrays:
        if a is None:
            h.update(b"|none")
            continue
        arr = np.asarray(a)
        h.update(f"|{arr.shape}:{arr.dtype}:".encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def demux_microbatch(out: jax.Array, mesh: Mesh, n_requests: int,
                     per_device_batch: int,
                     axis: str = constants.AXIS_DATA) -> "list[jax.Array]":
    """Split a microbatched program's output back into per-request arrays
    matching each request's solo output row order (shard-major, batch-
    minor — the Collector ordering contract ``generate_fn`` documents)."""
    n_dp = dict(mesh.shape)[axis]
    R, B = int(n_requests), int(per_device_batch)
    if out.shape[0] != n_dp * R * B:
        raise ValueError(
            f"microbatch output has {out.shape[0]} rows, expected "
            f"n_dp({n_dp}) · R({R}) · B({B}) = {n_dp * R * B}")
    per_request = []
    for r in range(R):
        blocks = [out[i * R * B + r * B: i * R * B + (r + 1) * B]
                  for i in range(n_dp)]
        per_request.append(jnp.concatenate(blocks, axis=0))
    return per_request


def _first_call(label: str, t0: float, dt: float) -> None:
    """A labelled program's first call (``bind_weights``): its whole wall
    time as ``cdt_pipeline_compile_seconds``, and what is left of it once
    the build seconds JAX reported meanwhile are taken out, as
    ``cdt_program_build_seconds{phase=first_run}``. Down here, and not
    beside its one caller, because the compile cache's key holds the line
    every operation above is traced at (``utils/compile_cache.py``)."""
    from ..telemetry.build import first_call

    first_call(label, t0, dt)
