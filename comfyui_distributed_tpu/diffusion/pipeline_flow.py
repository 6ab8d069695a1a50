"""Rectified-flow pipeline (FLUX-class DiT) with two sharding modes.

1. ``generate_fn`` — data-parallel seed fan-out over ``dp`` (the same
   contract as ``Txt2ImgPipeline``: BASELINE's "8 seed-varied images per
   step-time"). ``serve`` runs this mode as ``segment_fns``' prep →
   segments → finish (``generate_segmented``): the same math with no host
   callback, progress read from each segment's outputs.
2. ``generate_sp_fn`` — ONE image's tokens sharded over ``sp`` with ring
   attention: the sampler's whole scan runs with every shard holding a row
   block of the latent — single-image latency scales with chip count,
   which the reference explicitly cannot do (``README.md:191-194``: "does
   not speed up the generation of a single image").
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..models.dit import DiT, DiTConfig
from ..models.vae import AutoencoderKL
from ..parallel.rng import participant_key
from ..telemetry.device_scopes import device_scope
from ..utils import constants
from .pipeline import bind_weights
from .samplers import sample
from .schedules import sigmas_flow


@dataclasses.dataclass(frozen=True)
class FlowSpec:
    height: int = 1024
    width: int = 1024
    steps: int = 28
    shift: float = 3.0              # resolution-dependent sigma shift
    guidance: float = 3.5           # distilled guidance (FLUX-dev)
    cfg: float = 1.0                # true classifier-free guidance scale
                                    # (SD3-family; 1.0 = off, FLUX-dev
                                    # bakes guidance into `guidance`)
    sampler: str = "euler"
    per_device_batch: int = 1


def _read_as(fn, params, *args) -> list:
    """For each leaf of ``params`` (abstract shapes do), the ONE dtype
    every use of it inside ``fn(params, *args)`` first converts it to —
    a model computing in bf16 reads its float32-held weights so — or None
    where some use takes the leaf as it is held. Converting such a leaf
    ahead of the program is the program's own arithmetic, to the bit."""
    closed = jax.make_jaxpr(fn)(params, *args)
    leaves = closed.jaxpr.invars[:len(jax.tree.leaves(params))]
    uses: dict = {id(v): set() for v in leaves}
    for eqn in closed.jaxpr.eqns:
        to = (jnp.dtype(eqn.params["new_dtype"])
              if eqn.primitive.name == "convert_element_type" else None)
        for v in eqn.invars:
            if id(v) in uses:
                uses[id(v)].add(to)
    for v in closed.jaxpr.outvars:
        if id(v) in uses:
            uses[id(v)].add(None)
    plan = []
    for v in leaves:
        (to,) = uses[id(v)] if len(uses[id(v)]) == 1 else (None,)
        plan.append(None if to == v.aval.dtype else to)
    return plan


class FlowPipeline:
    def __init__(self, dit: DiT, dit_params, vae: AutoencoderKL):
        self.dit = dit
        self.dit_params = dit_params
        self.vae = vae

    def _weights(self) -> dict:
        """Explicit jit-argument weight pytree (closure capture would embed
        the params as lowered-module constants — 24 GB of MLIR for FLUX;
        see ``Txt2ImgPipeline._weights``)."""
        return {"dit": self.dit_params, "vae_dec": self.vae.dec_params}

    def _denoiser(self, context, pooled, guidance, sp_axis=None,
                  weights=None, cfg: float = 1.0, uncond_context=None,
                  uncond_pooled=None):
        """``cfg != 1.0`` (SD3-family true CFG) batches the cond/uncond
        passes into one doubled-batch model call (``guidance.cfg_denoiser``
        — same discipline as the UNet path); FLUX-dev keeps cfg=1.0 and
        its distilled ``guidance`` input."""
        dit_params = (self.dit_params if weights is None
                      else weights["dit"])

        def make(ctx, pl):
            def denoise(x, sigma):
                with device_scope("sampler"):
                    t = jnp.broadcast_to(sigma, (x.shape[0],))
                    g = jnp.full((x.shape[0],), guidance)
                v = self.dit.apply(dit_params, x, t, ctx, pl, g,
                                   sp_axis=sp_axis)
                with device_scope("sampler"):
                    return x - sigma * v
            return denoise

        if cfg == 1.0:
            return make(context, pooled)
        if uncond_context is None:
            # silently sampling WITHOUT guidance a caller asked for would
            # quietly produce the wrong image — fail loudly instead
            raise ValueError(
                f"cfg={cfg} requires negative conditioning: pass "
                "uncond_context (and uncond_pooled) through generate/"
                "generate_sp, or wire the FlowSampler node's 'negative' "
                "input; FLUX-dev distilled guidance wants cfg=1.0 with "
                "the 'guidance' field instead")
        from .guidance import cfg_denoiser

        return cfg_denoiser(make, context, uncond_context, cfg,
                            y=pooled, uncond_y=uncond_pooled)

    def _build_sampling(self, key, context, pooled, spec: FlowSpec,
                        batch: int, lat_hw, sp_axis=None, weights=None,
                        uncond_context=None, uncond_pooled=None):
        """Everything before the sampler scan: the noise draw and the
        (guided) denoiser closure. ONE definition for the monolithic
        ``_sample_and_decode`` and the served segment programs
        (``segment_fns``), so the two cannot drift apart."""
        lat_h, lat_w = lat_hw
        c = self.dit.config.in_channels
        bc = lambda a: (None if a is None
                        else jnp.broadcast_to(a, (batch,) + a.shape[1:]))
        with device_scope("sampler"):
            x = jax.random.normal(key, (batch, lat_h, lat_w, c), jnp.float32)
            context, pooled = bc(context), bc(pooled)
            uncond_context = bc(uncond_context)
            uncond_pooled = bc(uncond_pooled)
        den = self._denoiser(context, pooled, spec.guidance, sp_axis,
                             weights=weights, cfg=spec.cfg,
                             uncond_context=uncond_context,
                             uncond_pooled=uncond_pooled)
        return den, x

    def _decode_latent(self, x0, weights=None):
        images = self.vae.decode(                          # cdt.vae_decode
            x0, params=None if weights is None else weights["vae_dec"])
        with device_scope("vae_decode"):
            return jnp.clip(images / 2.0 + 0.5, 0.0, 1.0)

    def _sample_and_decode(self, key, context, pooled, spec: FlowSpec,
                           batch: int, sigmas, lat_hw, sp_axis=None,
                           decode: bool = True, weights=None,
                           progress=None, uncond_context=None,
                           uncond_pooled=None):
        den, x = self._build_sampling(
            key, context, pooled, spec, batch, lat_hw, sp_axis=sp_axis,
            weights=weights, uncond_context=uncond_context,
            uncond_pooled=uncond_pooled)
        if progress is not None:
            from .progress import wrap_denoiser

            den = wrap_denoiser(den, progress[0], progress[1])
        x0 = sample(spec.sampler, den, x, sigmas, key=key)
        if not decode:
            return x0
        return self._decode_latent(x0, weights)

    # --- mode 1: dp seed fan-out -------------------------------------------

    def generate_fn(self, mesh: Mesh, spec: FlowSpec,
                    axis: str = constants.AXIS_DATA,
                    progress: bool = False):
        sigmas = sigmas_flow(spec.steps, spec.shift)
        ds = self.vae.config.downscale
        lat_hw = (spec.height // ds, spec.width // ds)
        # spec.cfg != 1.0 (SD3-family true CFG) adds replicated
        # uncond_context/uncond_pooled inputs; arity is a function of
        # spec.cfg alone, so the compile cache (keyed on spec) stays
        # consistent
        use_cfg = spec.cfg != 1.0

        def shard_body(weights, key, context, pooled, uncond_context=None,
                       uncond_pooled=None, token=None):
            k = participant_key(key, axis)
            prog = ((token, jax.lax.axis_index(axis))
                    if token is not None else None)
            return self._sample_and_decode(k, context, pooled, spec,
                                           spec.per_device_batch, sigmas,
                                           lat_hw, weights=weights,
                                           progress=prog,
                                           uncond_context=uncond_context,
                                           uncond_pooled=uncond_pooled)

        per_shard = shard_body
        in_specs = (P(), P(), P(None, None, None), P(None, None))
        if use_cfg:
            in_specs += (P(None, None, None), P(None, None))
        if progress:
            if not use_cfg:
                # the 5th positional must skip the uncond slots
                per_shard = (lambda w, key, c, pl, token:
                             shard_body(w, key, c, pl, None, None, token))
            in_specs += (P(),)     # traced int32 token, replicated
        f = shard_map(
            per_shard, mesh=mesh, in_specs=in_specs,
            out_specs=P(axis, None, None, None),
        )
        jitted = jax.jit(f)
        weights = self._weights()

        return bind_weights(jitted, weights, label="flow_dp",
                            steps=spec.steps, mesh=mesh)

    _CACHE_MAX = 8

    def _cached_fn(self, mesh: Mesh, spec: FlowSpec,
                   progress: bool = False, mode: str = "dp",
                   axis: Optional[str] = None):
        """Value-keyed compile cache (same discipline as
        ``Txt2ImgPipeline._cached_fn`` — without it every node execution
        re-traces the whole sampler). Serves BOTH execution modes: ``dp``
        seed fan-out and ``sp`` ring-attention sharding share the cache,
        keyed by mode so a workflow that alternates between them never
        thrashes recompiles."""
        from .pipeline import cached_build, mesh_cache_key

        if mode == "sp":
            # normalize the key: default axis resolves BEFORE keying so
            # axis=None and axis="sp" hit the same compiled program, and
            # sp has no progress path — a progress=True key would memoize
            # a fn that silently drops it
            axis = axis or constants.AXIS_SEQUENCE
            if progress:
                raise NotImplementedError(
                    "progress streaming is not wired through sp mode")

        def build():
            if mode == "sp":
                return self.generate_sp_fn(mesh, spec, axis=axis)
            return self.generate_fn(mesh, spec, progress=progress)

        key = (mesh_cache_key(mesh), spec, progress, mode, axis)
        return cached_build(self, key, build, self._CACHE_MAX)

    def generate(self, mesh: Mesh, spec: FlowSpec, seed: int,
                 context: jax.Array, pooled: jax.Array,
                 progress_token=None,
                 uncond_context: Optional[jax.Array] = None,
                 uncond_pooled: Optional[jax.Array] = None) -> jax.Array:
        """One-shot generate; ``progress_token`` enables per-step x0
        streaming (``cluster/progress.ProgressTracker.start``).
        ``uncond_context``/``uncond_pooled`` carry the negative
        conditioning when ``spec.cfg != 1.0`` (SD3-family true CFG) —
        required then, ignored otherwise."""
        self._require_uncond(spec, uncond_context)
        fn = self._cached_fn(mesh, spec,
                             progress=progress_token is not None)
        args = [jax.random.key(seed), context, pooled]
        if spec.cfg != 1.0:
            if uncond_pooled is None:
                uncond_pooled = jnp.zeros_like(pooled)
            args += [uncond_context, uncond_pooled]
        if progress_token is not None:
            args.append(jnp.asarray(progress_token, jnp.int32))
        return fn(*args)

    # --- mode 1, as serve runs it: callback-free segments ------------------

    def segment_fns(self, mesh: Mesh, spec: FlowSpec,
                    axis: str = constants.AXIS_DATA):
        """The ``dp`` generator as the triple ``Txt2ImgPipeline.
        preemptible_fns`` cuts the UNet lane into, over the same shard
        math as :meth:`generate_fn` (bit-identical to it: a scan cut into
        segments is the scan, ``tests/test_segment_progress.py``):

        - ``prep(key, ctx, pooled[, unc, unc_pooled]) -> carry``:
          participant key fold-in + noise + the sampler's ``init``;
        - ``seg(L)(key, ..., start, carry) -> (carry, sigma, x0)``: ``L``
          steps from the traced global index ``start``, labelled
          ``flow_dp``; the last step's sigma and x0 (one latent a shard)
          are outputs for the progress stream;
        - ``fin(carry) -> images``: output-slot extract + VAE decode;
        - ``cast() -> leaves``: the DiT's weights as its forward pass
          reads them, where that is not how they are held (``sd3-medium``
          is held in float32 and computes in bfloat16). XLA hoists those
          conversions out of a program's step loop, so the one program
          paid them once a request and every segment program would pay
          them again (12 ms each at SD3-medium's size); ``seg`` takes
          ``cast``'s answer as its last argument instead, and the
          request pays them once as before. None where nothing is
          converted — and ``seg`` given ``()`` converts for itself, which
          is what a run of ONE segment wants: nothing to share, and no
          4 GiB to allocate beside two resident models.

        None carries a host callback, so all persist in the compile
        cache and launch in ~2 ms."""
        from .pipeline import cached_build, mesh_cache_key
        from .progress import DenoiserTap
        from .samplers import (carry_structure, extract_output, make_program,
                               run_segment)

        def build():
            sigmas = sigmas_flow(spec.steps, spec.shift)
            ds = self.vae.config.downscale
            lat_hw = (spec.height // ds, spec.width // ds)
            B = spec.per_device_batch
            x_shape = (B,) + lat_hw + (self.dit.config.in_channels,)
            latent = P(axis, *(None,) * (len(x_shape) - 1))
            carry_specs = tuple(
                latent if tuple(leaf.shape) == x_shape else P()
                for leaf in carry_structure(
                    spec.sampler, jax.ShapeDtypeStruct(x_shape, jnp.float32)))
            base_specs = (P(), P(), P(None, None, None), P(None, None))
            if spec.cfg != 1.0:
                base_specs += (P(None, None, None), P(None, None))
            weights = self._weights()
            like = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
            read_as = _read_as(
                self.dit.apply,
                jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                             weights["dit"]),
                like(*x_shape), like(B),
                like(B, 1, self.dit.config.context_dim),
                like(B, self.dit.config.pooled_dim), like(B))
            dit_def = jax.tree.structure(weights["dit"])

            def flow_cast_body(weights):
                # the weights' conversion, once a request: reads as
                # (unnamed) by design — no layer's work
                return tuple(
                    leaf.astype(to) for leaf, to in zip(
                        jax.tree.leaves(weights["dit"]), read_as)
                    if to is not None)

            def with_cast(weights, cast):
                """``weights`` with the converted leaves in their places:
                the held ones they replace are then arguments no program
                reads, which jit drops."""
                if not cast:
                    return weights
                cast = iter(cast)
                return {**weights, "dit": jax.tree.unflatten(dit_def, [
                    leaf if to is None else next(cast) for leaf, to in zip(
                        jax.tree.leaves(weights["dit"]), read_as)])}

            cast = (bind_weights(jax.jit(shard_map(
                flow_cast_body, mesh=mesh, in_specs=(P(),), out_specs=P())),
                weights, name="flow_cast", mesh=mesh)
                if any(to is not None for to in read_as) else None)

            def program(weights, key, context, pooled, *uncond):
                k = participant_key(key, axis)
                den, x = self._build_sampling(
                    k, context, pooled, spec, B, lat_hw, weights=weights,
                    uncond_context=uncond[0] if uncond else None,
                    uncond_pooled=uncond[1] if uncond else None)
                tap = DenoiserTap(den)
                return make_program(spec.sampler, tap, sigmas, key=k), x, tap

            def flow_prep_body(weights, *args):
                prog, x, _ = program(weights, *args)
                return prog.init(x)

            prep = bind_weights(jax.jit(shard_map(
                flow_prep_body, mesh=mesh, in_specs=base_specs,
                out_specs=carry_specs)), weights, name="flow_prep",
                mesh=mesh)

            def make_seg(length: int):
                def flow_seg_body(weights, *args):
                    *args, start, carry, cast = args
                    prog, _, tap = program(with_cast(weights, cast), *args)
                    carry, (sigma, x0) = run_segment(
                        prog, tuple(carry), start, length, tap=tap)
                    return carry, sigma, x0

                return bind_weights(jax.jit(shard_map(
                    flow_seg_body, mesh=mesh,
                    in_specs=base_specs + (P(), carry_specs, P()),
                    out_specs=(carry_specs, P(), latent))), weights,
                    label="flow_dp", steps=length, mesh=mesh)

            def flow_fin_body(weights, carry):
                return self._decode_latent(
                    extract_output(spec.sampler, tuple(carry)), weights)

            fin = bind_weights(jax.jit(shard_map(
                flow_fin_body, mesh=mesh, in_specs=(P(), carry_specs),
                out_specs=P(axis, None, None, None))), weights,
                name="flow_fin", mesh=mesh)

            segs: dict = {}

            def seg(length: int):
                if length not in segs:
                    segs[length] = make_seg(length)
                return segs[length]

            return {"prep": prep, "seg": seg, "fin": fin, "cast": cast,
                    "n_steps": spec.steps}

        return cached_build(
            self, ("segments", mesh_cache_key(mesh), spec, axis), build,
            self._CACHE_MAX)

    def generate_segmented(self, mesh: Mesh, spec: FlowSpec, seed: int,
                           context: jax.Array, pooled: jax.Array,
                           uncond_context: Optional[jax.Array] = None,
                           uncond_pooled: Optional[jax.Array] = None,
                           on_step=None, should_stop=None) -> jax.Array:
        """:meth:`generate` as the serving lane runs it: prep → equal
        segments of at most ``CDT_PREEMPT_SEGMENT_STEPS`` steps → finish,
        bit-identical to the one program. After each segment
        ``on_step(sigma, x0, calls=, shard=)`` (``_ProgressScope.
        on_step``) is handed the segment's last x0 per dp shard, read
        from the program's outputs; before each but the first
        ``should_stop()`` is asked (``/distributed/interrupt``) and a
        true answer raises ``InterruptedError``, as the offloaded ladder
        does between steps."""
        from ..telemetry.spans import span
        from .progress import deliver_segment, segment_calls
        from .samplers import equal_segment_steps

        self._require_uncond(spec, uncond_context)
        fns = self.segment_fns(mesh, spec)
        n = fns["n_steps"]
        args = (jax.random.key(seed), context, pooled)
        if spec.cfg != 1.0:
            if uncond_pooled is None:
                uncond_pooled = jnp.zeros_like(pooled)
            args += (uncond_context, uncond_pooled)
        seg_steps = equal_segment_steps(
            n, constants.PREEMPT_SEGMENT_STEPS.get())
        carry = fns["prep"](*args)
        # converted once for the segments to share; one segment alone
        # converts inside its own program, as the one program did
        cast = fns["cast"]() if fns["cast"] and n > seg_steps else ()
        start = 0
        while start < n:
            with span("segment.boundary", step=start):
                if start and should_stop is not None and should_stop():
                    raise InterruptedError(
                        f"sampling interrupted at step {start}/{n}")
                length = min(seg_steps, n - start)
                seg = fns["seg"](length)
                at = jnp.int32(start)
            carry, sigma, previews = seg(*args, at, carry, cast)
            if on_step is not None:
                deliver_segment(on_step, sigma, previews, segment_calls(
                    spec.sampler, start, length, n))
            start += length
        # the last segment has been waited for: the converted weights go
        # before the decode's scratch comes (both do not fit beside two
        # resident models, PERF.md §6)
        for leaf in cast:
            leaf.delete()
        return fns["fin"](carry)

    @staticmethod
    def _require_uncond(spec: FlowSpec, uncond_context) -> None:
        if spec.cfg != 1.0 and uncond_context is None:
            raise ValueError(
                f"FlowSpec.cfg={spec.cfg} but no negative conditioning "
                "was provided — pass uncond_context/uncond_pooled (the "
                "FlowSampler node's 'negative' input). FLUX-dev distilled "
                "guidance wants cfg=1.0 with the 'guidance' field.")

    # --- mode 1c: host offload (model too large for one chip, no pod) ------

    def offload_executor(self, params=None,
                         resident_bytes: Optional[int] = None,
                         stream_dtype: Optional[str] = None):
        """Build-or-fetch the cached ``OffloadedFlux`` executor (resident
        upload + compiled programs — minutes at FLUX scale, so cached
        like every other mode; ``bench.py`` reads residency stats off the
        same instance the product path runs)."""
        from .offload import OffloadedFlux, normalize_stream_dtype
        from .pipeline import cached_build

        src = self.dit_params if params is None else params
        sd = normalize_stream_dtype(stream_dtype)
        return cached_build(
            self, ("offload", resident_bytes, sd, id(src)),
            lambda: OffloadedFlux(self.dit, src,
                                  resident_bytes=resident_bytes,
                                  stream_dtype=sd),
            self._CACHE_MAX)

    def generate_offloaded(self, spec: FlowSpec, seed: int,
                           context: jax.Array, pooled: jax.Array,
                           params=None,
                           resident_bytes: Optional[int] = None,
                           stream_dtype: Optional[str] = None,
                           on_step=None, progress_token=None,
                           should_stop=None) -> jax.Array:
        """ONE image on ONE device with weights beyond the HBM budget
        held host-side (``diffusion/offload.py``) — the single-chip
        answer to FLUX-12B's 24 GB of bf16 weights (CDT_OFFLOAD; dp×tp
        over a pod is the fast path when more chips exist). Under the
        default fp8 ``stream_dtype`` the quantized block set usually
        fits resident, nothing streams per step, and the WHOLE sigma
        ladder runs as one compiled program (in-trace progress via
        ``progress_token``); streamed executors fall back to the python
        ladder with host-side ``on_step``. ``"native"`` keeps exact
        dtypes. ``params`` may be a host-numpy tree (the usual case: a
        full-size init can't live on device)."""
        from .offload import sample_euler_py

        if spec.per_device_batch != 1 or context.shape[0] != 1:
            raise ValueError(
                "offloaded generation is single-image (batch 1): the "
                "streamed weight window serves one latent at a time")
        if spec.cfg != 1.0:
            raise ValueError(
                "true CFG (spec.cfg != 1.0) is not wired through the "
                "offload executor — use cfg=1.0 with FLUX distilled "
                "'guidance', or run the dp/sp paths")
        from .offload import ladder_mode

        if ladder_mode() == "step" and spec.sampler != "euler":
            # fail BEFORE the minutes-long quantize/upload — this half
            # of the euler-only rule needs no executor to decide
            raise ValueError(
                "the per-step offloaded ladder supports euler only "
                f"(got {spec.sampler!r}); fully-resident executors "
                "with CDT_OFFLOAD_LADDER=jit run every sampler")
        off = self.offload_executor(params, resident_bytes, stream_dtype)
        sigmas = sigmas_flow(spec.steps, spec.shift)
        ds = self.vae.config.downscale
        lat_h, lat_w = spec.height // ds, spec.width // ds
        # same key derivation as dp shard 0, so offloaded == sharded run
        # (noise AND the sampler's ancestral draws)
        key = jax.random.fold_in(jax.random.key(seed), 0)
        x = jax.random.normal(
            key, (1, lat_h, lat_w, self.dit.config.in_channels),
            jnp.float32)
        if off.stacked and ladder_mode() == "jit":
            # the in-trace ladder supports EVERY registered sampler
            g = jnp.full((context.shape[0],), float(spec.guidance))
            x0 = off.sample_resident(
                x, sigmas, context, pooled, g, sampler=spec.sampler,
                key=key, progress_token=progress_token)
        else:
            # per-step loop: streamed executors, or CDT_OFFLOAD_LADDER=
            # step (interruptible serving) — resident executors still
            # run one fused program per forward. Euler-only: the python
            # ladder implements just the euler update.
            if spec.sampler != "euler":
                raise ValueError(
                    "the per-step offloaded ladder supports euler only "
                    f"(got {spec.sampler!r}); fully-resident executors "
                    "with CDT_OFFLOAD_LADDER=jit run every sampler")
            den = off.denoiser(context, pooled, spec.guidance)
            x0 = sample_euler_py(den, jax.device_put(x, off.device),
                                 sigmas, on_step=on_step,
                                 should_stop=should_stop)
        images = self.vae.decode(x0)
        return jnp.clip(images / 2.0 + 0.5, 0.0, 1.0)

    # --- mode 1b: dp×tp GSPMD (models too large for one chip) --------------

    def generate_tp_fn(self, mesh: Mesh, spec: FlowSpec,
                       dp_axis: str = constants.AXIS_DATA,
                       tp_axis: str = constants.AXIS_TENSOR):
        """Batch over ``dp`` AND weights over ``tp`` in one jit: parameters
        are placed with Megatron-style column/row rules
        (``parallel/tensor.py``) and GSPMD propagates the layouts +
        inserts the all-reduces. This is how FLUX-scale (12B) models run
        on 16 GB chips — a capability with no reference analogue (its
        workers each need the whole model in VRAM, README.md:186-189)."""
        from ..parallel.tensor import (DIT_TP_RULES, require_tp_match,
                                       shard_params, tp_fanout_call)

        if spec.cfg != 1.0:
            raise ValueError(
                "true CFG (spec.cfg != 1.0) is not wired through tp "
                "mode — use cfg=1.0 with FLUX distilled 'guidance', or "
                "run the dp/sp paths")
        sigmas = sigmas_flow(spec.steps, spec.shift)
        ds = self.vae.config.downscale
        lat_h, lat_w = spec.height // ds, spec.width // ds
        c = self.dit.config.in_channels
        B = mesh.shape[dp_axis] * spec.per_device_batch
        require_tp_match(self.dit_params, mesh, DIT_TP_RULES, tp_axis, "dit")
        # tp-placed params are passed as ARGUMENTS (committed sharded
        # arrays) — closure capture would serialize the full weight set
        # into the lowered module
        params = shard_params(self.dit_params, mesh, DIT_TP_RULES, tp_axis)
        vae_dec = self.vae.dec_params

        def run(params, vae_dec, keys, context, pooled):
            noise = jax.vmap(
                lambda k: jax.random.normal(k, (lat_h, lat_w, c), jnp.float32)
            )(keys)
            bc = lambda a: jnp.broadcast_to(a, (B,) + a.shape[1:])

            def denoise(x, sigma):
                t = jnp.broadcast_to(sigma, (B,))
                g = jnp.full((B,), spec.guidance)
                v = self.dit.apply(params, x, t, bc(context), bc(pooled), g)
                return x - sigma * v

            x0 = sample(spec.sampler, denoise, noise, sigmas, key=keys[0])
            images = self.vae.decode(x0, params=vae_dec)
            return jnp.clip(images / 2.0 + 0.5, 0.0, 1.0)

        return tp_fanout_call(jax.jit(run), (params, vae_dec), mesh,
                              dp_axis, B)

    # --- mode 2: sp single-image sharding ----------------------------------

    def generate_sp_fn(self, mesh: Mesh, spec: FlowSpec,
                       axis: str = constants.AXIS_SEQUENCE):
        """One image, latent rows sharded over ``axis``; ring attention
        inside every DiT block. Noise is drawn from the SAME key on the
        full latent then row-sliced per shard, so the sharded run is
        bit-comparable to a single-chip run of the same seed."""
        n_sh = mesh.shape[axis]
        ds = self.vae.config.downscale
        lat_h, lat_w = spec.height // ds, spec.width // ds
        p = self.dit.config.patch_size
        if (lat_h // p) % n_sh:
            raise ValueError(
                f"latent rows/patch ({lat_h}/{p}) must divide over {n_sh} shards")
        sigmas = sigmas_flow(spec.steps, spec.shift)
        rows_per = lat_h // n_sh
        use_cfg = spec.cfg != 1.0

        def per_shard(weights, key, context, pooled, uncond_context=None,
                      uncond_pooled=None):
            idx = jax.lax.axis_index(axis)
            c = self.dit.config.in_channels
            full_noise = jax.random.normal(key, (1, lat_h, lat_w, c), jnp.float32)
            x = jax.lax.dynamic_slice_in_dim(full_noise, idx * rows_per,
                                             rows_per, axis=1)
            den = self._denoiser(context, pooled, spec.guidance, sp_axis=axis,
                                 weights=weights, cfg=spec.cfg,
                                 uncond_context=uncond_context,
                                 uncond_pooled=uncond_pooled)
            x0 = sample(spec.sampler, den, x, sigmas, key=key)
            return x0

        in_specs = (P(), P(), P(None, None, None), P(None, None))
        if use_cfg:
            in_specs += (P(None, None, None), P(None, None))
        f = shard_map(
            per_shard, mesh=mesh,
            in_specs=in_specs,
            out_specs=P(None, axis, None, None),
            check_vma=False,
        )

        def run(weights, key, context, pooled, *uncond):
            latents = f(weights, key, context, pooled, *uncond)
            images = self.vae.decode(latents, params=weights["vae_dec"])
            return jnp.clip(images / 2.0 + 0.5, 0.0, 1.0)

        jitted = jax.jit(run)
        weights = self._weights()

        return bind_weights(jitted, weights, label="flow_sp",
                            steps=spec.steps, mesh=mesh)

    def generate_sp(self, mesh: Mesh, spec: FlowSpec, seed: int,
                    context: jax.Array, pooled: jax.Array,
                    uncond_context: Optional[jax.Array] = None,
                    uncond_pooled: Optional[jax.Array] = None) -> jax.Array:
        self._require_uncond(spec, uncond_context)
        fn = self._cached_fn(mesh, spec, mode="sp")
        args = [jax.random.key(seed), context, pooled]
        if spec.cfg != 1.0:
            if uncond_pooled is None:
                uncond_pooled = jnp.zeros_like(pooled)
            args += [uncond_context, uncond_pooled]
        return fn(*args)
