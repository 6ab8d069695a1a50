"""Built-in node set.

Two groups:

1. **Parity nodes** — the reference's 8 distributed node classes
   (``nodes/__init__.py:14-22``) with the same names and contracts:
   DistributedCollector, DistributedSeed, DistributedValue,
   DistributedModelName, ImageBatchDivider, AudioBatchDivider,
   DistributedEmptyImage, UltimateSDUpscaleDistributed.

2. **Substrate nodes** — the minimum ComfyUI-core surface reference
   workflows assume (checkpoint loading, text encode, sampling, VAE,
   save/preview, primitives). The reference free-rides on ComfyUI for
   these; a standalone framework supplies them. The TPU twist: sampling
   nodes execute the *whole* distributed program (shard_map over the mesh
   in executor context) rather than single-device ops.

Graph value conventions: IMAGE = float32 [B,H,W,C] in [0,1];
AUDIO = {"waveform": [B,C,S], "sample_rate": int}; CONDITIONING =
{"context": [1,N,D], "pooled": [1,P]}; MODEL = ModelBundle; LATENT =
{"samples": [B,h,w,c]}.
"""

from __future__ import annotations

import concurrent.futures
import contextvars
import functools
import json
import os
from pathlib import Path
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.exceptions import ValidationError
from ..utils.logging import debug_log, log
from .node import NODE_REGISTRY, NodeDef, register_node


def _chunk_bounds(total: int, parts: int) -> list[tuple[int, int]]:
    """Contiguous chunk bounds, sizes differing by ≤1, larger chunks first
    (reference ``_chunk_bounds``, ``nodes/utilities.py:7-20``)."""
    parts = max(1, min(parts, total)) if total > 0 else 1
    base, extra = divmod(total, parts)
    bounds, start = [], 0
    for i in range(parts):
        size = base + (1 if i < extra else 0)
        bounds.append((start, start + size))
        start += size
    return bounds


# --------------------------------------------------------------------------
# Parity nodes
# --------------------------------------------------------------------------


@register_node("DistributedSeed")
class DistributedSeed(NodeDef):
    """Master passes ``seed`` through; worker N yields ``seed + N + 1``
    (reference ``nodes/utilities.py:52-75``). The sharded pipeline uses
    fold_in internally; this node carries the *visible* seed contract for
    graph-level fan-out across hosts."""

    INPUTS = {"seed": "INT"}
    HIDDEN = {"is_worker": "BOOLEAN", "worker_id": "STRING", "worker_index": "INT"}
    RETURNS = ("INT",)

    def execute(self, seed: int, is_worker: bool = False, worker_id: str = "",
                worker_index: int = 0, **_):
        if not is_worker:
            return (int(seed),)
        return (int(seed) + int(worker_index) + 1,)


@register_node("DistributedValue")
class DistributedValue(NodeDef):
    """Per-worker override with typed coercion and default fallback
    (reference ``nodes/utilities.py:86-162``): ``worker_values`` is a JSON
    map of 1-indexed worker number → value."""

    INPUTS = {"default_value": "*"}
    OPTIONAL = {"worker_values": "STRING", "value_type": "STRING"}
    HIDDEN = {"is_worker": "BOOLEAN", "worker_id": "STRING", "worker_index": "INT"}
    RETURNS = ("*",)

    _COERCERS = {
        "INT": lambda v: int(float(v)),
        "FLOAT": float,
        "STRING": str,
        "COMBO": str,
    }

    def _coerce(self, value: Any, value_type: str) -> Any:
        fn = self._COERCERS.get(value_type.upper())
        if fn is None:
            return value
        try:
            return fn(value)
        except (TypeError, ValueError):
            raise ValidationError(
                f"cannot coerce {value!r} to {value_type}", field="worker_values"
            )

    def execute(self, default_value, worker_values: str = "", value_type: str = "",
                is_worker: bool = False, worker_id: str = "", worker_index: int = 0,
                **_):
        if not is_worker or not worker_values:
            return (default_value,)
        try:
            mapping = json.loads(worker_values)
        except json.JSONDecodeError:
            return (default_value,)
        key = str(int(worker_index) + 1)   # 1-indexed per reference
        if key not in mapping:
            return (default_value,)
        vtype = value_type or mapping.get("_type", "")
        return (self._coerce(mapping[key], vtype) if vtype else mapping[key],)


@register_node("DistributedModelName")
class DistributedModelName(NodeDef):
    """OUTPUT_NODE passing model names through as strings so delegate-mode
    workers can load models the master lacks (reference
    ``nodes/utilities.py:164-224``)."""

    INPUTS = {"model_name": "*"}
    HIDDEN = {"is_worker": "BOOLEAN", "worker_id": "STRING"}
    RETURNS = ("STRING",)
    OUTPUT_NODE = True

    def execute(self, model_name, **_):
        return (str(model_name),)


@register_node("ImageBatchDivider")
class ImageBatchDivider(NodeDef):
    """Split an IMAGE batch into up to 10 contiguous chunks (reference
    ``nodes/utilities.py:235-268``); chunks beyond the batch repeat the
    empty image."""

    INPUTS = {"images": "IMAGE", "divide_by": "INT"}
    RETURNS = tuple(["IMAGE"] * 10)

    def execute(self, images, divide_by: int = 2, **_):
        divide_by = max(1, min(int(divide_by), 10))
        arr = jnp.asarray(images)
        bounds = _chunk_bounds(arr.shape[0], divide_by)
        chunks = [arr[s:e] for s, e in bounds]
        empty = arr[:0]
        while len(chunks) < 10:
            chunks.append(empty)
        return tuple(chunks)


@register_node("AudioBatchDivider")
class AudioBatchDivider(NodeDef):
    """Split AUDIO along the samples dim (reference
    ``nodes/utilities.py:271-329``)."""

    INPUTS = {"audio": "AUDIO", "divide_by": "INT"}
    RETURNS = tuple(["AUDIO"] * 10)

    def execute(self, audio, divide_by: int = 2, **_):
        divide_by = max(1, min(int(divide_by), 10))
        wf = np.asarray(audio["waveform"])
        sr = int(audio.get("sample_rate", 44100))
        bounds = _chunk_bounds(wf.shape[-1], divide_by)
        chunks = [
            {"waveform": wf[..., s:e], "sample_rate": sr} for s, e in bounds
        ]
        empty = {"waveform": wf[..., :0], "sample_rate": sr}
        while len(chunks) < 10:
            chunks.append(empty)
        return tuple(chunks)


@register_node("ImageFromBatch")
class ImageFromBatch(NodeDef):
    """Slice [batch_index : batch_index+length] out of an IMAGE batch
    (ComfyUI-core node the reference's video-upscale workflow assumes —
    ``/root/reference/workflows/distributed-upscale-video.json``; index
    and length clamp to the batch like the original)."""

    INPUTS = {"image": "IMAGE", "batch_index": "INT", "length": "INT"}
    RETURNS = ("IMAGE",)

    def execute(self, image, batch_index: int, length: int, **_):
        arr = jnp.asarray(image)
        start = min(max(int(batch_index), 0), max(arr.shape[0] - 1, 0))
        count = min(max(int(length), 1), arr.shape[0] - start)
        return (arr[start:start + count],)


@register_node("SolidMask")
class SolidMask(NodeDef):
    """Constant-value mask (ComfyUI's SolidMask): the building block for
    inpaint regions and USDU spatial conditioning."""

    INPUTS = {"value": "FLOAT", "width": "INT", "height": "INT"}
    RETURNS = ("MASK",)

    def execute(self, value: float = 1.0, width: int = 64,
                height: int = 64, **_):
        import numpy as np

        return (np.full((1, int(height), int(width)),
                        float(value), np.float32),)


@register_node("DistributedEmptyImage")
class DistributedEmptyImage(NodeDef):
    """0-batch IMAGE placeholder for delegate-only masters (reference
    ``nodes/utilities.py:332-354``)."""

    INPUTS = {"height": "INT", "width": "INT"}
    OPTIONAL = {"channels": "INT"}
    RETURNS = ("IMAGE",)

    def execute(self, height: int = 64, width: int = 64, channels: int = 3, **_):
        return (jnp.zeros((0, int(height), int(width), int(channels)), jnp.float32),)


@register_node("DistributedCollector")
class DistributedCollector(NodeDef):
    """Result gather point (reference ``nodes/collector.py``).

    On-pod, the "gather" already happened inside the SPMD program (the
    sharded output array), so locally this node is identity. Across hosts
    the executor context provides a ``collector_bridge`` (cluster layer):
    worker role pushes its batch to the master; master role drains and
    concatenates master-first (``nodes/collector.py:252-295``). With
    ``pass_through`` (downstream of USDU) it is always identity
    (``nodes/collector.py:121-124``).
    """

    INPUTS = {"images": "IMAGE"}
    OPTIONAL = {"audio": "AUDIO"}
    HIDDEN = {
        "multi_job_id": "STRING", "is_worker": "BOOLEAN", "worker_id": "STRING",
        "master_url": "STRING", "enabled_worker_ids": "*",
        "delegate_only": "BOOLEAN", "pass_through": "BOOLEAN",
        "collector_bridge": "*",
    }
    RETURNS = ("IMAGE", "AUDIO")

    def execute(self, images, audio=None, multi_job_id: str = "",
                is_worker: bool = False, worker_id: str = "",
                master_url: str = "", enabled_worker_ids=(),
                delegate_only: bool = False, pass_through: bool = False,
                collector_bridge=None, **_):
        if pass_through or not multi_job_id or collector_bridge is None:
            return (images, audio)
        if is_worker:
            collector_bridge.send(multi_job_id, worker_id, images, audio,
                                  master_url)
            return (images, audio)
        images, audio = collector_bridge.collect(
            multi_job_id, images, audio,
            enabled_worker_ids=tuple(enabled_worker_ids),
            delegate_only=delegate_only,
        )
        return (images, audio)


@register_node("UltimateSDUpscaleDistributed")
class UltimateSDUpscaleDistributed(NodeDef):
    """Tile-sharded upscale (reference ``nodes/distributed_upscale.py``).

    Mode selection collapses on TPU: static/dynamic/single-gpu pull-queues
    (``:230-267``) become one SPMD program over however many chips the
    executor's mesh has; the video 4n+1 batch rule (``:131-142``) is a
    padding rule applied by the video divider, not a constraint here.
    """

    INPUTS = {
        "image": "IMAGE", "model": "MODEL",
        "positive": "CONDITIONING", "negative": "CONDITIONING",
        "seed": "INT", "steps": "INT", "denoise": "FLOAT",
        "upscale_by": "FLOAT",
    }
    OPTIONAL = {
        "tile_width": "INT", "tile_height": "INT", "tile_padding": "INT",
        "cfg": "FLOAT", "sampler_name": "STRING", "scheduler": "STRING",
        "spatial_cond": "MASK", "dynamic_threshold": "INT",
    }
    HIDDEN = {
        "mesh": "*", "multi_job_id": "STRING", "is_worker": "BOOLEAN",
        "worker_id": "STRING", "master_url": "STRING",
        "enabled_worker_ids": "*", "delegate_only": "BOOLEAN",
        "tile_farm": "*",
    }
    RETURNS = ("IMAGE",)

    def execute(self, image, model, positive, negative, seed: int, steps: int,
                denoise: float, upscale_by: float, tile_width: int = 512,
                tile_height: int = 512, tile_padding: int = 32,
                cfg: float = 5.0, sampler_name: str = "euler",
                scheduler: str = "karras", spatial_cond=None,
                dynamic_threshold: int = 8, mesh=None,
                multi_job_id: str = "", is_worker: bool = False,
                worker_id: str = "", master_url: str = "",
                enabled_worker_ids=(), tile_farm=None, **_):
        from ..parallel.mesh import build_mesh
        from ..tiles.engine import TileUpscaler, UpscaleSpec

        if mesh is None:
            mesh = build_mesh({"dp": len(jax.devices())})
        spec = UpscaleSpec(
            scale=float(upscale_by), tile_w=int(tile_width), tile_h=int(tile_height),
            padding=int(tile_padding), steps=int(steps), denoise=float(denoise),
            sampler=sampler_name, scheduler=scheduler, guidance_scale=float(cfg),
        )
        # ControlNet rides the positive conditioning; hints are cropped
        # per tile inside the SPMD program (reference crop_cond +
        # crop_model_patch semantics, SURVEY §7 hard-part #3)
        control = positive.get("control") if isinstance(positive, dict) else None
        pipeline = model.pipeline
        control_hint = None
        if control:
            pipeline = pipeline.with_control(control["model"],
                                             control.get("strength", 1.0))
            # hints arrive 4-D (normalized by ControlNetApply)
            control_hint = jnp.asarray(control["hint"], jnp.float32)
        upscaler = TileUpscaler(pipeline)
        adm = model.pipeline.unet.config.adm_in_channels
        y = uy = None
        if adm:
            y = _adm_from_cond(positive, adm)
            uy = _adm_from_cond(negative, adm)

        # cross-host farm engages when orchestration assigned a job id and
        # remote worker hosts participate (reference mode selection,
        # nodes/distributed_upscale.py:230-267; on-pod SPMD otherwise)
        farm_active = (tile_farm is not None and multi_job_id
                       and (is_worker or enabled_worker_ids))
        smap = None
        if spatial_cond is not None:
            # MASK convention [B,H,W] → [B,H,W,1]; cropped per tile inside
            # the engine (reference crop_cond, usdu_utils.py:506)
            smap = jnp.asarray(spatial_cond, jnp.float32)
            if smap.ndim == 3:
                smap = smap[..., None]
        if not farm_active:
            out = upscaler.upscale(
                mesh, jnp.asarray(image), spec, int(seed),
                positive["context"], negative["context"], y, uy,
                spatial_cond=smap, control_hint=control_hint,
            )
            return (out,)
        if control_hint is not None:
            log("USDU farm mode: ControlNet hints apply to locally "
                "processed work only; cross-host STATIC tile tasks run "
                "without control this round")

        images = jnp.asarray(image)

        # dynamic (per-image) mode for large batches — reference
        # upscale/modes/dynamic.py: the pull queue holds IMAGE indices and
        # full processed images travel back, not tiles. Here each task is
        # one image run through the on-pod SPMD tile program; global image
        # index seeds the noise so assignment/requeue stays invisible.
        if images.shape[0] >= max(2, int(dynamic_threshold)):
            def process_images(start: int, end: int) -> np.ndarray:
                done = []
                for i in range(start, end):
                    ch = control_hint
                    if ch is not None and ch.shape[0] == images.shape[0]:
                        ch = ch[i:i + 1]
                    done.append(np.asarray(upscaler.upscale(
                        mesh, images[i:i + 1], spec, int(seed) + i,
                        positive["context"], negative["context"], y, uy,
                        spatial_cond=None if smap is None else smap[i:i + 1],
                        control_hint=ch,
                    )))
                return np.concatenate(done, axis=0)

            from ..cluster.tile_farm import assemble_tiles

            if is_worker:
                from ..ops.resize import upscale_image

                tile_farm.worker_run(multi_job_id, worker_id, master_url,
                                     process_images)
                return (upscale_image(images, spec.scale,
                                      spec.resize_method),)
            from ..utils import constants as _c

            results = tile_farm.master_run(
                multi_job_id, images.shape[0], process_images, chunk=1,
                journal_dir=_c.TILE_JOURNAL_DIR or None,
                journal_key=_journal_key(images, spec, seed, 0, 1,
                                         images.shape[0])
                if _c.TILE_JOURNAL_DIR else None)

            def _plain_resize(start: int, end: int) -> np.ndarray:
                # degraded fill for dead-lettered images: plain resize,
                # no diffusion — one poison image costs one unrefined
                # frame, not the job
                from ..ops.resize import upscale_image

                return np.asarray(upscale_image(
                    images[start:end], spec.scale, spec.resize_method),
                    np.float32)

            full = assemble_tiles(results, images.shape[0], 1,
                                  fallback_fn=_plain_resize)
            return (jnp.asarray(full),)

        outs = []
        for b in range(images.shape[0]):
            plan = upscaler.range_plan(
                mesh, images[b], spec, int(seed),
                positive["context"], negative["context"], y, uy,
                spatial_cond=None if smap is None else smap[b],
            )
            job_id = (f"{multi_job_id}_b{b}" if images.shape[0] > 1
                      else multi_job_id)
            if is_worker:
                from ..ops.resize import upscale_image

                tile_farm.worker_run(job_id, worker_id, master_url,
                                     plan.run_range)
                # master owns the composite; the worker returns a size-
                # correct plain resize so its downstream graph stays
                # shape-consistent (reference worker role,
                # nodes/distributed_upscale.py:164)
                outs.append(upscale_image(images[b][None], spec.scale,
                                          spec.resize_method)[0])
                continue
            from ..cluster.tile_farm import assemble_tiles

            from ..utils import constants as _c

            results = tile_farm.master_run(
                job_id, plan.num_tiles, plan.run_range, chunk=plan.chunk,
                journal_dir=_c.TILE_JOURNAL_DIR or None,
                journal_key=_journal_key(images[b], spec, seed, b,
                                         plan.chunk, plan.num_tiles)
                if _c.TILE_JOURNAL_DIR else None)
            tiles = assemble_tiles(results, plan.num_tiles, plan.chunk,
                                   fallback_fn=plan.source_range)
            outs.append(upscaler.composite(tiles, plan))
        return (jnp.stack([jnp.asarray(o) for o in outs], axis=0),)


def _journal_key(images, spec, seed: int, index: int = 0,
                 chunk: int = 1, total: int = 0) -> str:
    """Stable crash-resume key: a re-submitted workflow gets a fresh
    execution job id, so the journal is keyed by job CONTENT (input
    pixels + spec + seed) — plus the task topology (chunk/total): a
    restart on a different chip count must NOT restore payloads whose
    arrays cover different tile ranges."""
    import hashlib

    h = hashlib.sha1()
    h.update(np.ascontiguousarray(np.asarray(images, np.float32)).tobytes())
    h.update(repr((spec, int(seed), int(index), int(chunk),
                   int(total))).encode())
    return f"usdu_{h.hexdigest()[:20]}"


def _stop_cb(interrupt_event):
    """should_stop callable for the offloaded python ladders — ONE
    definition for every offload-capable sampler node."""
    return interrupt_event.is_set if interrupt_event is not None else None


class _ProgressScope:
    """Progress lifecycle shared by the sampler nodes: allocates a token
    on entry; ``complete(out)`` blocks on the result before exit marks
    the run done — anything else marks it failed, freezing progress where
    it stopped instead of reporting 100%.

    Two ways feed the tracker. The SERVED lanes (``TPUTxt2Img``'s
    preemptible lane, ``TPUFlowTxt2Img`` in ``dp`` mode) and the
    offloaded python ladders report host-side through ``on_step``: their
    programs carry no host callback, and a segment's last x0 is read from
    its outputs (``diffusion/progress.deliver_segment``). Every other
    compiled run takes ``traced`` as its ``progress_token`` — the token
    with the tracker's event stride — and streams through
    ``jax.debug.callback``; ``complete`` then also drains the pending
    callbacks (``block_until_ready`` alone does not flush them)."""

    @property
    def traced(self):
        return (None if self.token is None
                else self.tracker.traced_token(self.token))

    def on_step(self, sigma: float, x0, calls: int = 1,
                shard: int = 0) -> None:
        if self.token is not None:
            self.tracker.report(self.token, sigma, x0, shard=shard,
                                calls=calls)

    def __init__(self, tracker, prompt_id: str, total_calls: int):
        self.tracker, self.prompt_id = tracker, prompt_id
        self.token = (tracker.start(prompt_id, total_calls)
                      if tracker is not None and prompt_id else None)
        self._ok = False

    def complete(self, out, callbacks: bool = True) -> None:
        """``callbacks=False`` where the run's programs carry none: there
        is nothing for an ``effects_barrier`` to drain."""
        if self.token is not None:
            jax.block_until_ready(out)
            if callbacks:
                jax.effects_barrier()
        self._ok = True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        if self.token is not None:
            self.tracker.finish(self.prompt_id, failed=not self._ok)
        return False


def _adm_from_cond(cond: dict, adm_channels: int) -> jax.Array:
    """Build the ADM vector from pooled conditioning, zero-padded/truncated
    to the UNet's expected width (full SDXL micro-conds via
    ``diffusion.pipeline.sdxl_adm`` when sizes are known)."""
    pooled = cond.get("pooled")
    if pooled is None:
        return jnp.zeros((1, adm_channels), jnp.float32)
    pooled = jnp.asarray(pooled)
    pad = adm_channels - pooled.shape[-1]
    if pad > 0:
        return jnp.pad(pooled, ((0, 0), (0, pad)))
    return pooled[:, :adm_channels]


# --------------------------------------------------------------------------
# Substrate nodes (ComfyUI-core surface the reference assumes)
# --------------------------------------------------------------------------


def _resolve_model_file(env_var: str, subdir: str, name: str):
    """Shared weight-file resolution for the model-loader nodes:
    ``$<env_var>`` (or ``$CDT_CHECKPOINT_ROOT/<subdir>``) + ``name`` with
    ``.safetensors`` appended unless present. Returns (path_or_None,
    root, source_key) where ``source_key`` identifies the weight SOURCE
    (path + mtime for files) so loader caches invalidate when the file
    appears or changes."""
    import os

    from ..utils import constants

    ckpt_root = constants.CHECKPOINT_ROOT.get()
    root = constants.knob(env_var).get() or (
        os.path.join(ckpt_root, subdir) if ckpt_root else "")
    if not root:
        return None, "", None
    fname = name if name.endswith(".safetensors") else f"{name}.safetensors"
    path = Path(root) / fname
    if path.is_file():
        return path, root, ("file", str(path), path.stat().st_mtime_ns)
    return None, root, None


_UPSCALER_PRESETS = {
    "tiny-x2": lambda cfg_mod: cfg_mod.UpscalerConfig.tiny(scale=2),
    "tiny-x4": lambda cfg_mod: cfg_mod.UpscalerConfig.tiny(scale=4),
    "esrgan-x4": lambda cfg_mod: cfg_mod.UpscalerConfig.esrgan_x4(),
    "realesrgan-x2": lambda cfg_mod: cfg_mod.UpscalerConfig.realesrgan_x2(),
}
_upscaler_cache: dict[str, Any] = {}


@register_node("UpscaleModelLoader")
class UpscaleModelLoader(NodeDef):
    """ESRGAN-family model loader (ComfyUI-core surface the reference's
    upscale workflows assume: ``UpscaleModelLoader`` →
    ``ImageUpscaleWithModel`` feeding USDU's input,
    ``workflows/distributed-upscale.json``). ``model_name`` is either a
    published RRDBNet ``.safetensors`` under ``CDT_UPSCALE_MODEL_DIR``
    (falling back to ``CDT_CHECKPOINT_ROOT/upscalers``) — converted on
    load — or an architecture preset name (random-init, for tests and
    architecture work)."""

    INPUTS = {"model_name": "STRING"}
    RETURNS = ("UPSCALE_MODEL",)

    def execute(self, model_name: str, **_):
        name = str(model_name)
        candidate, root, source = _resolve_model_file(
            "CDT_UPSCALE_MODEL_DIR", "upscalers", name)
        # cache entries are keyed by their weight SOURCE: a checkpoint
        # dropped in after a random-init fallback (or replaced on disk)
        # must win on the next load, not be shadowed until restart
        if source is None and name in _UPSCALER_PRESETS:
            source = ("preset", name)
        if source is None:
            raise ValidationError(
                f"unknown upscale model {name!r}: no checkpoint under "
                f"{root or '$CDT_UPSCALE_MODEL_DIR'} and not one of "
                f"{sorted(_UPSCALER_PRESETS)}", field="model_name")
        cached = _upscaler_cache.get(name)
        if cached is not None and cached[0] == source:
            return (cached[1],)
        if source[0] == "file":
            from ..models.convert import load_upscaler_checkpoint

            bundle = load_upscaler_checkpoint(candidate)
        else:
            from ..models import upscaler as upscaler_mod

            cfg = _UPSCALER_PRESETS[name](upscaler_mod)
            bundle = upscaler_mod.init_upscaler(cfg, jax.random.key(0))
            bundle.name = name
            log(f"upscaler {name!r}: no checkpoint found — random init")
        _upscaler_cache[name] = (source, bundle)
        return (bundle,)


@register_node("ImageUpscaleWithModel")
class ImageUpscaleWithModel(NodeDef):
    """Tile-sharded learned upscale: the tile batch shards over the mesh's
    dp axis in one SPMD program (TPU redesign of ComfyUI's single-GPU
    tiled torch loop the reference free-rides on)."""

    INPUTS = {"upscale_model": "UPSCALE_MODEL", "image": "IMAGE"}
    OPTIONAL = {"tile": "INT", "tile_padding": "INT"}
    HIDDEN = {"mesh": "*"}
    RETURNS = ("IMAGE",)

    def execute(self, upscale_model, image, tile: int = 256,
                tile_padding: int = 16, mesh=None, **_):
        from ..parallel.mesh import build_mesh
        from ..tiles.model_upscale import tiled_model_upscale

        if mesh is None:
            mesh = build_mesh({"dp": len(jax.devices())})
        images = jnp.asarray(image, jnp.float32)
        if images.ndim == 3:
            images = images[None]
        tile = min(int(tile), images.shape[1], images.shape[2])
        out = tiled_model_upscale(mesh, upscale_model, images,
                                  tile=tile, padding=int(tile_padding))
        return (np.asarray(out),)


_controlnet_cache: dict[str, Any] = {}


@register_node("ControlNetLoader")
class ControlNetLoader(NodeDef):
    """ControlNet loader (ComfyUI-core surface; the reference's USDU
    crops control hints per tile, ``utils/usdu_utils.py:506``).
    ``control_net_name`` is a published ``.safetensors`` under
    ``CDT_CONTROLNET_DIR`` (or ``CDT_CHECKPOINT_ROOT/controlnet``) — the
    base architecture (sd15/sdxl) is detected from the checkpoint — or a
    preset name (``tiny``/``sd15``/``sdxl``, random init)."""

    INPUTS = {"control_net_name": "STRING"}
    RETURNS = ("CONTROL_NET",)

    _PRESETS = ("tiny", "sd15", "sdxl")

    def execute(self, control_net_name: str, **_):
        from ..models.unet import UNetConfig

        name = str(control_net_name)
        candidate, root, source = _resolve_model_file(
            "CDT_CONTROLNET_DIR", "controlnet", name)
        if source is None and name in self._PRESETS:
            source = ("preset", name)
        if source is None:
            raise ValidationError(
                f"unknown control net {name!r}: no checkpoint under "
                f"{root or '$CDT_CONTROLNET_DIR'} and not one of "
                f"{self._PRESETS}", field="control_net_name")
        cached = _controlnet_cache.get(name)
        if cached is not None and cached[0] == source:
            return (cached[1],)

        from ..models.controlnet import ControlNet, ControlNetBundle, \
            init_controlnet

        if source[0] == "file":
            from ..models.convert import convert_controlnet, load_safetensors

            sd = load_safetensors(candidate)
            # base architecture from the checkpoint itself
            if "control_model.label_emb.0.0.weight" in sd:
                cfg = UNetConfig.sdxl()
            else:
                cfg = UNetConfig.sd15()
            params = convert_controlnet(sd, self._template(cfg), cfg)
            bundle = ControlNetBundle(ControlNet(cfg), params,
                                      name=candidate.stem)
            log(f"converted controlnet {candidate} ({cfg.context_dim}-ctx)")
        else:
            cfg = {"tiny": UNetConfig.tiny, "sd15": UNetConfig.sd15,
                   "sdxl": UNetConfig.sdxl}[name]()
            hw = (8, 8) if name == "tiny" else (32, 32)
            bundle = init_controlnet(cfg, jax.random.key(0), sample_shape=(
                *hw, cfg.in_channels))
            bundle.name = name
            log(f"controlnet {name!r}: no checkpoint found — random init")
        if len(_controlnet_cache) >= 4:
            _controlnet_cache.pop(next(iter(_controlnet_cache)))
        _controlnet_cache[name] = (source, bundle)
        return (bundle,)

    @staticmethod
    def _template(cfg):
        """Shape-only template via eval_shape — the converter checks leaf
        shapes, so a full (GB-scale) random init would be pure waste."""
        from ..models.controlnet import ControlNet

        model = ControlNet(cfg)
        h, w = 8, 8
        return jax.eval_shape(
            model.init, jax.random.key(0),
            jnp.zeros((1, h, w, cfg.in_channels), jnp.float32),
            jnp.zeros((1,), jnp.float32),
            jnp.zeros((1, 8, cfg.context_dim), jnp.float32),
            (jnp.zeros((1, cfg.adm_in_channels), jnp.float32)
             if cfg.adm_in_channels else None),
            jnp.zeros((1, h * 8, w * 8, 3), jnp.float32))


@register_node("ControlNetApply")
class ControlNetApply(NodeDef):
    """Attach a control hint to a conditioning (ComfyUI semantics): the
    sampler nodes read ``conditioning["control"]`` and thread the hint
    through every denoise step. Under CFG the control conditions both
    passes (A1111 convention)."""

    INPUTS = {"conditioning": "CONDITIONING", "control_net": "CONTROL_NET",
              "image": "IMAGE"}
    OPTIONAL = {"strength": "FLOAT"}
    RETURNS = ("CONDITIONING",)

    def execute(self, conditioning, control_net, image,
                strength: float = 1.0, **_):
        hint = np.asarray(image, np.float32)
        if hint.ndim == 3:
            hint = hint[None]
        return ({**conditioning,
                 "control": {"model": control_net, "hint": hint,
                             "strength": float(strength)}},)


def _control_from_cond(pipeline, cond: dict, height: int, width: int):
    """Activate the conditioning's ControlNet on a pipeline clone and
    shape the hint for the stem: the published hint stem downscales by 8,
    so the hint target is latent-res × 8 (equal to the image size for
    SD-family VAEs; differs only for toy test VAEs). Returns
    (pipeline, hint)."""
    control = cond.get("control") if isinstance(cond, dict) else None
    if not control:
        return pipeline, None
    # ControlNetApply normalizes hints to 4-D at the producer side
    hint = jnp.asarray(control["hint"], jnp.float32)
    ds = pipeline.vae.config.downscale
    target = (height // ds * 8, width // ds * 8)
    if hint.shape[1:3] != target:
        hint = jax.image.resize(
            hint, (hint.shape[0], *target, hint.shape[-1]),
            method="bilinear")
    return (pipeline.with_control(control["model"],
                                  control.get("strength", 1.0)), hint)


@register_node("LoraLoader")
class LoraLoader(NodeDef):
    """Merge a kohya-format LoRA into copies of the model/clip (ComfyUI
    core ``LoraLoader`` surface; the reference free-rides on it). The
    registry's shared bundle is never mutated — patched params live in a
    shallow pipeline clone with a fresh compile cache. ``lora_name``
    resolves under ``CDT_LORA_DIR`` (or ``CDT_CHECKPOINT_ROOT/loras``)."""

    INPUTS = {"model": "MODEL", "clip": "CLIP", "lora_name": "STRING"}
    OPTIONAL = {"strength_model": "FLOAT", "strength_clip": "FLOAT"}
    RETURNS = ("MODEL", "CLIP")

    _cache: dict = {}

    def execute(self, model, clip, lora_name: str,
                strength_model: float = 1.0, strength_clip: float = 1.0,
                **_):
        from ..models.lora import apply_lora, load_lora_file

        if not strength_model and not strength_clip:
            return (model, clip)
        name = str(lora_name)
        path, root, source = _resolve_model_file("CDT_LORA_DIR", "loras",
                                                 name)
        if source is None:
            raise ValidationError(
                f"LoRA {name!r} not found under "
                f"{root or '$CDT_LORA_DIR'}", field="lora_name")
        # merge + compile are expensive; memoize per (base model, weight
        # source, strengths). The cached entry pins the base bundle, so
        # identity comparison is safe (ids can't recycle while cached).
        key = (name, source, float(strength_model), float(strength_clip))
        cached = self._cache.get(key)
        if (cached is not None and cached[0] is model
                and cached[1] is clip):
            return cached[2]
        patched, conditioner = apply_lora(
            model, load_lora_file(path),
            strength_model=float(strength_model),
            strength_clip=float(strength_clip), name=name)
        result = (patched, conditioner if conditioner is not None else clip)
        if len(self._cache) >= 4:
            self._cache.pop(next(iter(self._cache)))
        self._cache[key] = (model, clip, result)
        return result


@register_node("ImageScale")
class ImageScale(NodeDef):
    """Plain device-side resize (ComfyUI-core surface the reference's
    workflows interleave between model stages). Accepts ComfyUI's
    ``upscale_method`` input name and method vocabulary; width/height 0
    derives that dimension keeping aspect (ComfyUI convention)."""

    INPUTS = {"image": "IMAGE", "width": "INT", "height": "INT"}
    OPTIONAL = {"method": "STRING", "upscale_method": "STRING",
                "crop": "STRING"}
    RETURNS = ("IMAGE",)

    def execute(self, image, width: int, height: int,
                method: str = "lanczos3", upscale_method: str = "",
                crop: str = "disabled", **_):
        from ..ops.resize import normalize_method, resize_to

        try:
            method = normalize_method(upscale_method or method)
        except ValueError as e:
            raise ValidationError(str(e), field="upscale_method")
        if crop not in ("disabled", "center"):
            raise ValidationError(
                f"unknown crop mode {crop!r}; have disabled|center",
                field="crop")
        images = jnp.asarray(image, jnp.float32)
        if images.ndim == 3:
            images = images[None]
        _, H, W, _ = images.shape
        width, height = int(width), int(height)
        if width < 0 or height < 0:
            raise ValidationError(
                "width/height must be >= 0 (0 keeps aspect)", field="width")
        if width == 0 and height == 0:
            raise ValidationError("width and height cannot both be 0",
                                  field="width")
        if width == 0:
            width = max(1, round(W * height / H))
        if height == 0:
            height = max(1, round(H * width / W))
        if crop == "center" and (H * width != W * height):
            # center-crop the source to the target aspect before resizing
            # (ComfyUI-core ImageScale crop="center" semantics)
            if W * height > H * width:            # too wide
                new_w = max(1, round(H * width / height))
                x0 = (W - new_w) // 2
                images = images[:, :, x0:x0 + new_w, :]
            else:                                  # too tall
                new_h = max(1, round(W * height / width))
                y0 = (H - new_h) // 2
                images = images[:, y0:y0 + new_h, :, :]
        return (resize_to(images, height, width, method),)


@register_node("ImageScaleBy")
class ImageScaleBy(NodeDef):
    INPUTS = {"image": "IMAGE", "scale_by": "FLOAT"}
    OPTIONAL = {"method": "STRING", "upscale_method": "STRING"}
    RETURNS = ("IMAGE",)

    def execute(self, image, scale_by: float, method: str = "lanczos3",
                upscale_method: str = "", **_):
        from ..ops.resize import normalize_method, upscale_image

        try:
            method = normalize_method(upscale_method or method)
        except ValueError as e:
            raise ValidationError(str(e), field="upscale_method")
        if float(scale_by) <= 0:
            raise ValidationError("scale_by must be > 0", field="scale_by")
        images = jnp.asarray(image, jnp.float32)
        if images.ndim == 3:
            images = images[None]
        return (upscale_image(images, float(scale_by), method),)


@register_node("CheckpointLoader")
class CheckpointLoader(NodeDef):
    INPUTS = {"ckpt_name": "STRING"}
    HIDDEN = {"model_registry": "*"}
    RETURNS = ("MODEL", "CLIP", "VAE")

    def execute(self, ckpt_name: str, model_registry=None, **_):
        if model_registry is None:
            from ..models.registry import ModelRegistry
            model_registry = ModelRegistry()
        from ..models.registry import PRESETS

        preset = PRESETS.get(str(ckpt_name))
        if preset is not None and preset.kind == "llm":
            raise ValidationError(
                f"{ckpt_name!r} is a language model: load it with "
                "LLMLoader, not CheckpointLoader", field="ckpt_name")
        bundle = model_registry.get(ckpt_name)
        return (bundle, bundle.text_encoder, bundle.pipeline.vae)


@register_node("LLMLoader")
class LLMLoader(NodeDef):
    """A language-model preset from the registry (``kind == "llm"``), held
    resident beside the image models: ``LLM`` is its bundle."""

    INPUTS = {"llm_name": "STRING"}
    HIDDEN = {"model_registry": "*"}
    RETURNS = ("LLM",)

    def execute(self, llm_name: str, model_registry=None, **_):
        from ..models.registry import PRESETS, ModelRegistry

        preset = PRESETS.get(str(llm_name))
        if preset is not None and preset.kind != "llm":
            raise ValidationError(
                f"{llm_name!r} is a {preset.kind} model: load it with "
                "CheckpointLoader, not LLMLoader", field="llm_name")
        if model_registry is None:
            model_registry = ModelRegistry()
        return (model_registry.get(llm_name),)


# the rewriter's fixed instruction; cycled to fill the prompt to its length
REWRITE_PREAMBLE = (
    "you are a prompt engineer for an image model . think step by step "
    "about the subject , the composition , the lighting , the lens and "
    "the style the user most likely wants , then write one long detailed "
    "caption that keeps every thing the user asked for and adds concrete "
    "visual detail . do not add text , logos or watermarks . user prompt :")


def rewrite_prompt_array(text: str, prompt_tokens: int,
                         vocab: int) -> np.ndarray:
    """Exactly ``prompt_tokens`` ids over ``[0, vocab)``, an int32 array
    (what the prefill program takes: no list of the prompt's length is
    ever walked): the fixed preamble (cycled) and then the user's words,
    at most an eighth of the prompt. A stand-in hash tokenizer, as
    ``models/text.py``'s: the model's own tokenizer is not here."""
    from ..models.text import _stable_hash_token

    user = [_stable_hash_token(w, vocab)
            for w in str(text).lower().split()[:max(1, prompt_tokens // 8)]]
    lead = np.resize(_preamble_ids(vocab), prompt_tokens - len(user))
    return np.concatenate([lead, np.asarray(user, np.int32)])


def rewrite_prompt_ids(text: str, prompt_tokens: int, vocab: int) -> list:
    """``rewrite_prompt_array``'s ids as a list, for the parity tools."""
    return rewrite_prompt_array(text, prompt_tokens, vocab).tolist()


@functools.lru_cache(maxsize=8)
def _preamble_ids(vocab: int) -> np.ndarray:
    """The preamble's words hashed once a vocabulary: a 32 k-token prompt
    cycles these ids, it does not hash 32 k words a request."""
    from ..models.text import _stable_hash_token

    ids = np.array([_stable_hash_token(w, vocab)
                    for w in REWRITE_PREAMBLE.split()], np.int32)
    ids.setflags(write=False)       # cached: every request reads this one
    return ids


@register_node("TPUPromptRewrite")
class TPUPromptRewrite(NodeDef):
    """Rewrite a prompt with a language model ahead of ``CLIPTextEncode``:
    ``prompt_tokens`` in, exactly ``new_tokens`` sampled (no stop token),
    as words. Two programs a call (``diffusion/pipeline_llm.py``); fails
    on a non-finite logit or an id outside the model's vocabulary slice.
    A model counts what it has through hooks of its config: ``attended_keys``
    answers (query, key) pairs a head by ``(kind of layer, phase)`` — and a
    model that attends to NO key (a cache of states alone) the positions
    FOLDED into its states, tokens × layers, under ``layers="retention"``:
    linear in the tokens where every other label's count is pairs."""

    INPUTS = {"llm": "LLM", "text": "STRING", "seed": "INT"}
    OPTIONAL = {"prompt_tokens": "INT", "new_tokens": "INT",
                "temperature": "FLOAT"}
    RETURNS = ("STRING",)

    def execute(self, llm, text: str, seed: int, prompt_tokens: int = 512,
                new_tokens: int = 1024, temperature: float = 0.7, **_):
        from ..telemetry import enabled as _tm_enabled
        from ..telemetry import metrics as _tm
        from ..telemetry.spans import span

        if getattr(llm, "kind", None) != "llm":
            raise ValidationError("TPUPromptRewrite needs an LLM (wire "
                                  "LLMLoader's output)", field="llm")
        cfg = llm.pipeline.config
        prompt_tokens, new_tokens = int(prompt_tokens), int(new_tokens)
        if prompt_tokens < cfg.min_prompt_tokens or new_tokens < 1:
            raise ValidationError(
                f"prompt_tokens {prompt_tokens} / new_tokens {new_tokens}: "
                "too few", field="prompt_tokens")
        with span("llm.tokenize", tokens=prompt_tokens):
            ids = rewrite_prompt_array(text, prompt_tokens, cfg.vocab_size)
        with _pinned(llm):
            out = llm.pipeline.generate(ids, new_tokens, int(seed),
                                        float(temperature))
        if _tm_enabled():
            for kind, size in out["cache_bytes"].items():
                _tm.LLM_CACHE_BYTES.labels(layers=kind).set(float(size))
            _tm.LLM_CACHE_POSITIONS.set(float(prompt_tokens + new_tokens))
            _tm.LLM_PREFILL_CHUNKS.inc(out["prefill_chunks"])
            phases = (("prefill", prompt_tokens), ("decode", new_tokens))
            for phase, tokens in phases:
                _tm.LLM_TOKENS.labels(phase=phase).inc(tokens)
                _tm.LLM_STREAM_MIX.labels(phase=phase).inc(
                    tokens * cfg.stream_mixes_per_token)
                # a model counts what it has: state-space layers, experts
                scanned = tokens * getattr(cfg, "scan_layers_per_token", 0)
                if scanned:
                    _tm.LLM_SCAN_TOKENS.labels(phase=phase).inc(scanned)
            pairs = getattr(cfg, "attended_keys", None)
            if pairs is not None:     # pairs by kind — or positions folded
                for (kind, phase), n in pairs(prompt_tokens,
                                              new_tokens).items():
                    _tm.LLM_ATTN_KEYS.labels(layers=kind, phase=phase).inc(n)
            columns = getattr(cfg, "select_columns", None)
            if columns is not None:   # an exact selection a query
                for kind, n in columns(prompt_tokens, new_tokens).items():
                    _tm.LLM_SELECT_COLUMNS.labels(kind=kind).inc(n)
            blocks = getattr(cfg, "selected_blocks", None)
            if blocks is not None:    # attention over a selection of blocks
                for kind, n in blocks(prompt_tokens, new_tokens).items():
                    _tm.LLM_SELECT_BLOCKS.labels(kind=kind).inc(n)
                for kind, n in cfg.scored_slot_tiles(prompt_tokens,
                                                     new_tokens).items():
                    _tm.LLM_SELECT_SLOT_TILES.labels(kind=kind).inc(n)
                from ..ops.block_select_attention import STEP_FETCHES
                for fetch, n in zip(STEP_FETCHES, out["sparse_steps"]):
                    _tm.LLM_SPARSE_STEPS.labels(fetch=fetch).inc(int(n))
            if cfg.moe_layers:
                _tm.LLM_EXPERT_ROWS.labels(form=out["prefill_form"]).inc(
                    out["rows_prefill"])
                _tm.LLM_EXPERT_ROWS.labels(form="token").inc(
                    int(out["held_decode"].sum()))
                for phase, tokens in phases:
                    held = int(out[f"held_{phase}"].sum())
                    # on an identity expert: neither held nor absent
                    zero = int(out[f"zero_{phase}"].sum())
                    _tm.LLM_EXPERT_SLOTS.labels(where="held",
                                                phase=phase).inc(held)
                    if cfg.routing.zero_experts:
                        _tm.LLM_EXPERT_SLOTS.labels(where="zero",
                                                    phase=phase).inc(zero)
                    _tm.LLM_EXPERT_SLOTS.labels(
                        where="absent", phase=phase).inc(
                            tokens * cfg.routed_slots_per_token - held
                            - zero)
        new_ids = out["ids"]
        if not out["finite"]:
            raise RuntimeError("the language model produced a non-finite "
                               "logit")
        if new_ids.min() < 0 or new_ids.max() >= cfg.vocab_size:
            raise RuntimeError(
                f"the language model drew an id outside its slice of "
                f"{cfg.vocab_size} rows")
        with span("llm.render", tokens=new_tokens):
            words = " ".join(f"t{int(i)}" for i in new_ids)
        return (words,)


class _ShiftedModel:
    """MODEL proxy carrying a sampling-shift override; every other
    attribute forwards to the wrapped bundle (the ComfyUI patched-model
    clone pattern, minus torch model cloning)."""

    def __init__(self, base, shift: float):
        self._base = base
        self.sampling_shift = float(shift)

    def __getattr__(self, name):
        return getattr(self._base, name)


@register_node("ModelSamplingSD3")
class ModelSamplingSD3(NodeDef):
    """Sigma-shift control for flow models (ComfyUI-core node used by the
    reference's video workflow, ``distributed-upscale-video.json``):
    returns a MODEL whose default flow shift is overridden — the flow
    ladder becomes σ' = shift·σ / (1 + (shift−1)·σ). Sampler nodes
    consult it whenever the graph does not wire an explicit shift."""

    INPUTS = {"model": "MODEL", "shift": "FLOAT"}
    RETURNS = ("MODEL",)

    def execute(self, model, shift: float, **_):
        return (_ShiftedModel(model, shift),)


@register_node("CLIPTextEncode")
class CLIPTextEncode(NodeDef):
    INPUTS = {"text": "STRING", "clip": "CLIP"}
    HIDDEN = {"content_cache": "*"}
    RETURNS = ("CONDITIONING",)

    def execute(self, text: str, clip, content_cache=None, **_):
        # text-encode through the fleet conditioning cache when the
        # controller carries one (cluster/cache): identical prompts —
        # and the negative prompt nearly every request shares — encode
        # once, fleet-wide. Falls through to a plain encode for
        # unidentified encoders or CDT_CACHE=0.
        from ..cluster.cache.conditioning import cached_encode

        ctx, pooled = cached_encode(content_cache, clip, [str(text)])
        return ({"context": ctx, "pooled": pooled},)


@register_node("EmptyLatentImage")
class EmptyLatentImage(NodeDef):
    INPUTS = {"width": "INT", "height": "INT"}
    OPTIONAL = {"batch_size": "INT", "ckpt_name": "STRING"}
    RETURNS = ("LATENT",)

    def execute(self, width: int, height: int, batch_size: int = 1,
                ckpt_name: str = "", **_):
        # latent geometry follows the model preset (flux/wan latents are
        # 16-channel; the tiny test VAE downscales 2×, not 8×); SD-family
        # 8×/4ch is the default for preset-less graphs
        downscale, channels = 8, 4
        if ckpt_name:
            from ..models.registry import PRESETS

            preset = PRESETS.get(str(ckpt_name))
            if preset is not None:
                downscale = preset.vae.downscale
                channels = preset.vae.latent_channels
        return ({"samples": jnp.zeros(
                    (int(batch_size), int(height) // downscale,
                     int(width) // downscale, channels), jnp.float32),
                 "height": int(height), "width": int(width)},)


def _pinned(model):
    """Residency pin for the duration of a generate call: with
    ``CDT_HBM_BUDGET_GB`` set, a concurrent acquire (warmup thread,
    another model's request) must never evict THIS bundle mid-program
    (``cluster/residency.pinned_bundle``; no-op without a planner)."""
    from ..cluster.residency import pinned_bundle

    return pinned_bundle(model)


def _observe_shape(pipeline: str, model, height: int, width: int,
                   steps: int, batch: int = 1, frames: int = 0) -> None:
    """Feed the shape catalog (``cluster/shape_catalog.py``) from the
    request path so the NEXT restart warms the programs this fleet
    actually serves. Never fatal, and cheap after first sight."""
    from ..cluster.shape_catalog import observe

    name = getattr(getattr(model, "preset", None), "name", None)
    if name:
        observe(pipeline, name, height, width, steps, batch=batch,
                frames=frames)


@register_node("TPUTxt2Img")
class TPUTxt2Img(NodeDef):
    """The distributed sampler node: runs the whole sharded generation
    (per-shard seeds + sampling + decode + gather) as one SPMD program —
    the TPU equivalent of the reference's entire dispatch/collect cycle
    for ``distributed-txt2img.json``."""

    INPUTS = {
        "model": "MODEL", "positive": "CONDITIONING", "negative": "CONDITIONING",
        "seed": "INT", "steps": "INT", "cfg": "FLOAT",
        "width": "INT", "height": "INT",
    }
    OPTIONAL = {
        "sampler_name": "STRING", "scheduler": "STRING", "batch_per_device": "INT",
    }
    HIDDEN = {"mesh": "*", "prompt_id": "STRING", "progress_tracker": "*",
              "preemption": "*"}
    RETURNS = ("IMAGE",)

    def execute(self, model, positive, negative, seed: int, steps: int,
                cfg: float, width: int, height: int,
                sampler_name: str = "euler", scheduler: str = "karras",
                batch_per_device: int = 1, mesh=None, prompt_id: str = "",
                progress_tracker=None, preemption=None, **_):
        from ..diffusion.pipeline import GenerationSpec
        from ..parallel.mesh import build_mesh

        if mesh is None:
            mesh = build_mesh({"dp": len(jax.devices())})
        spec = GenerationSpec(
            height=int(height), width=int(width), steps=int(steps),
            sampler=sampler_name, scheduler=scheduler,
            guidance_scale=float(cfg), per_device_batch=int(batch_per_device),
        )
        _observe_shape("txt2img", model, spec.height, spec.width,
                       spec.steps, batch=spec.per_device_batch)
        adm = model.pipeline.unet.config.adm_in_channels
        y = _adm_from_cond(positive, adm) if adm else None
        uy = _adm_from_cond(negative, adm) if adm else None
        pipeline, hint = _control_from_cond(model.pipeline, positive,
                                            spec.height, spec.width)
        if preemption is not None and hint is None:
            # serving lane (cluster/preemption.py): resumable K-step
            # segments, preempt checks at segment boundaries, optional
            # checkpoint restore. Bit-identical to the monolithic path;
            # the segment programs carry no host callback and the
            # progress stream is fed from their outputs, a preview a
            # segment. (ControlNet graphs keep the monolithic path:
            # per-request hints are not threaded through the segment
            # programs.)
            with _pinned(model):
                return (self._execute_preemptible(
                    pipeline, mesh, spec, int(seed), positive, negative,
                    y, uy, preemption, progress_tracker, prompt_id),)
        from ..diffusion.progress import total_calls

        with _pinned(model), \
                _ProgressScope(progress_tracker, prompt_id,
                               total_calls(sampler_name, spec.steps)) as ps:
            images = pipeline.generate(
                mesh, spec, int(seed), positive["context"],
                negative["context"], y, uy, hint=hint,
                progress_token=ps.traced,
            )
            ps.complete(images)
        return (images,)

    def _execute_preemptible(self, pipeline, mesh, spec, seed,
                             positive, negative, y, uy, token,
                             progress_tracker, prompt_id):
        from ..diffusion.checkpoint import PreemptedError
        from ..diffusion.progress import total_calls

        # identity (incl. the conditioning digest) is validated inside
        # generate_preemptible; a mismatch raises CheckpointRestoreError
        # toward the runtime's bounded resume-retry machinery
        token.resume_consumed = token.resume is not None
        with _ProgressScope(progress_tracker, prompt_id,
                            total_calls(spec.sampler, spec.steps)) as ps:
            result = pipeline.generate_preemptible(
                mesh, spec, seed, positive["context"],
                negative["context"], y, uy,
                segment_steps=token.segment_steps,
                should_preempt=token.should_preempt, resume=token.resume,
                on_step=ps.on_step,
            )
            if "checkpoint" in result:
                # scope exit freezes the progress bar where it stopped
                # (preempted ≠ failed-to-0; resume re-registers a fresh
                # token under the same prompt_id)
                raise PreemptedError(result["checkpoint"],
                                     result["reason"])
            images = result["images"]
            ps.complete(images, callbacks=False)
        return images


@register_node("TPUImg2Img")
class TPUImg2Img(NodeDef):
    """Distributed img2img: every chip produces its own seed-varied edit
    of the (replicated) source batch in one SPMD program — the img2img
    analogue of the reference's seed-offset fan-out. ``denoise`` sets the
    partial sigma-ladder fraction (k-diffusion convention, like the
    reference's KSampler denoise)."""

    INPUTS = {
        "model": "MODEL", "image": "IMAGE",
        "positive": "CONDITIONING", "negative": "CONDITIONING",
        "seed": "INT", "steps": "INT", "cfg": "FLOAT", "denoise": "FLOAT",
    }
    OPTIONAL = {"sampler_name": "STRING", "scheduler": "STRING"}
    HIDDEN = {"mesh": "*"}
    RETURNS = ("IMAGE",)

    def execute(self, model, image, positive, negative, seed: int,
                steps: int, cfg: float, denoise: float,
                sampler_name: str = "euler", scheduler: str = "karras",
                mesh=None, **_):
        mesh, images, spec, y, uy, pipeline, hint = _i2i_setup(
            model, image, positive, negative, steps, cfg, denoise,
            sampler_name, scheduler, mesh)
        out = pipeline.img2img(
            mesh, spec, int(seed), images,
            positive["context"], negative["context"], y, uy, hint=hint,
        )
        return (out,)


def _i2i_setup(model, image, positive, negative, steps, cfg, denoise,
               sampler_name, scheduler, mesh):
    """Shared img2img/inpaint node prelude: mesh fallback, image batch
    coercion, spec construction, ADM + ControlNet extraction."""
    from ..diffusion.pipeline import GenerationSpec
    from ..parallel.mesh import build_mesh

    if mesh is None:
        mesh = build_mesh({"dp": len(jax.devices())})
    images = jnp.asarray(image, jnp.float32)
    if images.ndim == 3:
        images = images[None]
    B, H, W, _ = images.shape
    spec = GenerationSpec(
        height=int(H), width=int(W), steps=int(steps),
        sampler=sampler_name, scheduler=scheduler,
        guidance_scale=float(cfg), per_device_batch=B,
        denoise=float(denoise),
    )
    adm = model.pipeline.unet.config.adm_in_channels
    y = _adm_from_cond(positive, adm) if adm else None
    uy = _adm_from_cond(negative, adm) if adm else None
    pipeline, hint = _control_from_cond(model.pipeline, positive, H, W)
    return mesh, images, spec, y, uy, pipeline, hint


@register_node("TPUInpaint")
class TPUInpaint(NodeDef):
    """Distributed inpainting: img2img with a repaint mask (1 = repaint,
    0 = keep). ComfyUI KSamplerX0Inpaint semantics on every model call:
    the sampler input is recomposited with the source latent re-noised
    at the current sigma and the denoised estimate is pinned to the
    source (``diffusion/pipeline.inpaint_denoiser``), so unmasked
    regions track the reference trajectory — ancestral/SDE samplers
    included; each chip produces its own seed-varied repaint."""

    INPUTS = {
        "model": "MODEL", "image": "IMAGE", "mask": "MASK",
        "positive": "CONDITIONING", "negative": "CONDITIONING",
        "seed": "INT", "steps": "INT", "cfg": "FLOAT", "denoise": "FLOAT",
    }
    OPTIONAL = {"sampler_name": "STRING", "scheduler": "STRING"}
    HIDDEN = {"mesh": "*"}
    RETURNS = ("IMAGE",)

    def execute(self, model, image, mask, positive, negative, seed: int,
                steps: int, cfg: float, denoise: float,
                sampler_name: str = "euler", scheduler: str = "karras",
                mesh=None, **_):
        mesh, images, spec, y, uy, pipeline, hint = _i2i_setup(
            model, image, positive, negative, steps, cfg, denoise,
            sampler_name, scheduler, mesh)
        B, H, W, _ = images.shape
        m = jnp.asarray(mask, jnp.float32)
        if m.ndim == 2:
            m = m[None]
        if m.ndim == 3:
            m = m[..., None]
        if m.shape[-1] > 1:      # an IMAGE wired as mask: take channel 0
            m = m[..., :1]
        if m.shape[0] != B:
            m = jnp.broadcast_to(m, (B,) + m.shape[1:])
        if m.shape[1:3] != (H, W):
            m = jax.image.resize(m, (B, H, W, 1), method="bilinear")
        # both composites assume a convex blend — out-of-range masks
        # would EXTRAPOLATE pixels/latents outside [0,1]
        m = jnp.clip(m, 0.0, 1.0)
        out = pipeline.img2img(
            mesh, spec, int(seed), images,
            positive["context"], negative["context"], y, uy, hint=hint,
            mask=m,
        )
        return (out,)


@register_node("TPUFlowTxt2Img")
class TPUFlowTxt2Img(NodeDef):
    """Sharded rectified-flow sampler (FLUX-class DiT bundles).

    ``mode="dp"`` fans seeds over chips; ``mode="sp"`` shards ONE image's
    tokens over chips with ring attention (single-image latency scaling —
    beyond the reference's capability census, SURVEY §2.10)."""

    INPUTS = {
        "model": "MODEL", "positive": "CONDITIONING",
        "seed": "INT", "steps": "INT", "width": "INT", "height": "INT",
    }
    OPTIONAL = {
        "negative": "CONDITIONING", "cfg": "FLOAT",
        "guidance": "FLOAT", "shift": "FLOAT", "mode": "STRING",
        "batch_per_device": "INT",
    }
    HIDDEN = {"mesh": "*", "prompt_id": "STRING", "progress_tracker": "*",
              "interrupt_event": "*"}
    RETURNS = ("IMAGE",)

    def execute(self, model, positive, seed: int, steps: int, width: int,
                height: int, negative=None, cfg: float = 1.0,
                guidance: float = 3.5, shift=None,
                mode: str = "dp", batch_per_device: int = 1, mesh=None,
                prompt_id: str = "", progress_tracker=None,
                interrupt_event=None, **_):
        from ..diffusion.pipeline_flow import FlowSpec
        from ..parallel.mesh import build_mesh
        from ..utils.exceptions import ValidationError

        if mesh is None:
            mesh = build_mesh({"dp": len(jax.devices())})
        # unwired shift falls back to a ModelSamplingSD3 override on the
        # model, then the FLUX-convention default
        if shift is None:
            shift = getattr(model, "sampling_shift", 3.0)
        spec = FlowSpec(height=int(height), width=int(width), steps=int(steps),
                        shift=float(shift), guidance=float(guidance),
                        cfg=float(cfg),
                        per_device_batch=int(batch_per_device))
        if mode == "dp":
            _observe_shape("flow_dp", model, spec.height, spec.width,
                           spec.steps, batch=spec.per_device_batch)
        ctx = positive["context"]
        pooled = positive.get("pooled")
        if pooled is None:
            pooled = jnp.zeros((1, model.pipeline.dit.config.pooled_dim))
        # true CFG (SD3-family): the 'negative' conditioning rides along;
        # asking for cfg != 1.0 without it is a loud error, never a
        # silent unguided sample
        uncond_ctx = uncond_pooled = None
        if negative is not None:
            uncond_ctx = negative["context"]
            uncond_pooled = negative.get("pooled")
        if spec.cfg != 1.0 and uncond_ctx is None:
            raise ValidationError(
                f"cfg={spec.cfg} needs the 'negative' conditioning input "
                "(true CFG); FLUX-dev distilled guidance uses cfg=1.0 "
                "with 'guidance'")
        from ..diffusion.offload import offload_enabled

        if mode == "offload" or (mode == "dp" and offload_enabled()):
            # CDT_OFFLOAD=1 (or mode="offload"): full-size single-chip
            # execution with quantized-resident/streamed blocks — how
            # FLUX-12B runs without a pod (docs/deployment.md §5).
            # Progress: fully-resident runs stream in-trace via
            # ps.traced; streamed runs report host-side via ps.on_step.
            from ..diffusion.progress import total_calls

            with _pinned(model), \
                    _ProgressScope(progress_tracker, prompt_id,
                                   total_calls(spec.sampler,
                                               spec.steps)) as ps:
                images = model.pipeline.generate_offloaded(
                    spec, int(seed), ctx, pooled, on_step=ps.on_step,
                    progress_token=ps.traced,
                    should_stop=_stop_cb(interrupt_event))
                ps.complete(images)
        elif mode == "sp":
            from jax.sharding import Mesh

            axes = dict(mesh.shape)
            if "sp" not in axes:   # re-lay the same devices as an sp mesh
                mesh = build_mesh({"sp": mesh.devices.size},
                                  list(mesh.devices.flat))
            # sp mode: single-image token sharding. Progress streaming is
            # intentionally dp-only for now — each sp shard holds a row
            # BLOCK, so a per-shard preview would be a partial strip; the
            # tracker would need cross-shard assembly to be meaningful.
            with _pinned(model):
                images = model.pipeline.generate_sp(
                    mesh, spec, int(seed), ctx, pooled,
                    uncond_context=uncond_ctx,
                    uncond_pooled=uncond_pooled)
        else:
            from ..diffusion.progress import total_calls

            with _pinned(model), \
                    _ProgressScope(progress_tracker, prompt_id,
                                   total_calls(spec.sampler,
                                               spec.steps)) as ps:
                # the serving lane: callback-free segment programs, the
                # stream fed from their outputs (diffusion/progress.py)
                images = model.pipeline.generate_segmented(
                    mesh, spec, int(seed), ctx, pooled,
                    uncond_context=uncond_ctx,
                    uncond_pooled=uncond_pooled, on_step=ps.on_step,
                    should_stop=_stop_cb(interrupt_event))
                ps.complete(images, callbacks=False)
        return (images,)


def _video_pooled_default(model, positive):
    """Shared video-node prologue: real-WAN configs have no pooled-vector
    input (the model ignores it); any width satisfies the signature."""
    pooled = positive.get("pooled")
    if pooled is None:
        pooled = jnp.zeros(
            (1, getattr(model.pipeline.dit.config, "pooled_dim", 768)))
    return pooled


def _flatten_video_batch(videos):
    """[B,F,H,W,3] → IMAGE batch [B·F,H,W,3] (ImageBatchDivider splits it
    back per video/chunk — reference workflow parity)."""
    B, F = videos.shape[:2]
    return videos.reshape((B * F,) + videos.shape[2:])


@register_node("TPUTxt2Video")
class TPUTxt2Video(NodeDef):
    """Sharded WAN-class t2v sampler (reference parity: the WAN t2v/i2v
    workflows, SURVEY §2.9, which the reference runs job-per-worker).

    ``mode="dp"``: each chip samples a full seed-varied video (the
    reference's whole dispatch/collect cycle as one SPMD program).
    ``mode="sp"``: ONE video's frame blocks shard over chips with joint
    ring attention spanning the full spatio-temporal sequence — single-
    video latency scaling the reference cannot express (SURVEY §5.7).
    Frame count pads to 4n+1 (``nodes/distributed_upscale.py:131-142``'s
    rule, applied as padding not a constraint)."""

    INPUTS = {
        "model": "MODEL", "positive": "CONDITIONING",
        "seed": "INT", "frames": "INT", "steps": "INT",
        "width": "INT", "height": "INT",
    }
    OPTIONAL = {"cfg": "FLOAT", "shift": "FLOAT", "mode": "STRING"}
    HIDDEN = {"mesh": "*", "prompt_id": "STRING", "progress_tracker": "*",
              "interrupt_event": "*"}
    RETURNS = ("IMAGE",)

    def execute(self, model, positive, seed: int, frames: int, steps: int,
                width: int, height: int, cfg: float = 1.0,
                shift=None, mode: str = "dp", mesh=None,
                prompt_id: str = "", progress_tracker=None,
                interrupt_event=None, **_):
        from ..diffusion.pipeline_video import VideoSpec
        from ..diffusion.progress import total_calls
        from ..parallel.mesh import build_mesh

        if mesh is None:
            mesh = build_mesh({"dp": len(jax.devices())})
        if shift is None:   # ModelSamplingSD3 override, then WAN default
            shift = getattr(model, "sampling_shift", 3.0)
        spec = VideoSpec(frames=int(frames), height=int(height),
                         width=int(width), steps=int(steps),
                         shift=float(shift), guidance_scale=float(cfg))
        if mode == "dp":
            _observe_shape("video_dp", model, spec.height, spec.width,
                           spec.steps, frames=spec.frames)
        ctx = positive["context"]
        pooled = _video_pooled_default(model, positive)
        key = jax.random.key(int(seed))
        # t2v is the longest-running job type — stream per-step progress
        # and previews exactly like the image samplers do
        from ..diffusion.offload import offload_enabled

        with _pinned(model), \
                _ProgressScope(progress_tracker, prompt_id,
                               total_calls(spec.sampler,
                                           spec.steps)) as ps:
            if mode == "offload" or (mode == "dp" and offload_enabled()):
                # full-size single-chip execution with quantized expert
                # residency + dual-expert HBM swap — how WAN-14B runs
                # without a pod (diffusion/offload.OffloadedWan).
                # Progress: in-trace via ps.traced when resident,
                # host-side via ps.on_step when streaming.
                videos = model.pipeline.generate_offloaded(
                    spec, int(seed), ctx, on_step=ps.on_step,
                    progress_token=ps.traced,
                    should_stop=_stop_cb(interrupt_event))
            elif mode == "sp":
                if "sp" not in mesh.shape:
                    mesh = build_mesh({"sp": mesh.devices.size},
                                      list(mesh.devices.flat))
                videos = model.pipeline.generate_frames(
                    mesh, spec, int(seed), ctx, pooled,
                    progress_token=ps.traced)
            else:
                videos = model.pipeline.generate(mesh, spec, int(seed),
                                                 ctx, pooled,
                                                 progress_token=ps.traced)
            ps.complete(videos)
        return (_flatten_video_batch(videos),)


@register_node("TPUImg2Video")
class TPUImg2Video(NodeDef):
    """Sharded WAN-class i2v sampler: the start image conditions every
    sample via causal-VAE latent concat (WAN-2.2 style — no CLIP-vision
    branch), seeds fan out over ``dp`` (reference parity: the WAN i2v
    workflow, SURVEY §2.9, run job-per-worker there)."""

    INPUTS = {
        "model": "MODEL", "positive": "CONDITIONING", "image": "IMAGE",
        "seed": "INT", "frames": "INT", "steps": "INT",
    }
    OPTIONAL = {"cfg": "FLOAT", "shift": "FLOAT", "mode": "STRING"}
    HIDDEN = {"mesh": "*", "prompt_id": "STRING", "progress_tracker": "*",
              "interrupt_event": "*"}
    RETURNS = ("IMAGE",)

    def execute(self, model, positive, image, seed: int, frames: int,
                steps: int, cfg: float = 1.0, shift=None,
                mode: str = "dp", mesh=None, prompt_id: str = "",
                progress_tracker=None, interrupt_event=None, **_):
        from ..diffusion.pipeline_video import VideoSpec
        from ..diffusion.progress import total_calls
        from ..parallel.mesh import build_mesh
        from ..utils.exceptions import ValidationError

        image = jnp.asarray(image)
        if image.ndim == 3:
            image = image[None]
        din = model.pipeline.dit.config.in_channels
        dout = getattr(model.pipeline.dit.config, "out_channels", din)
        if din == dout:
            raise ValidationError(
                f"model {model.preset.name!r} is a t2v architecture "
                "(in_channels == out_channels) — i2v needs a preset with "
                "latent-concat conditioning channels, e.g. 'wan-i2v'")
        if mesh is None:
            mesh = build_mesh({"dp": len(jax.devices())})
        H, W = int(image.shape[1]), int(image.shape[2])
        if shift is None:   # ModelSamplingSD3 override, then WAN default
            shift = getattr(model, "sampling_shift", 3.0)
        spec = VideoSpec(frames=int(frames), height=H, width=W,
                         steps=int(steps), shift=float(shift),
                         guidance_scale=float(cfg))
        ctx = positive["context"]
        pooled = _video_pooled_default(model, positive)
        from ..diffusion.offload import offload_enabled

        with _pinned(model), \
                _ProgressScope(progress_tracker, prompt_id,
                               total_calls(spec.sampler,
                                           spec.steps)) as ps:
            if mode == "offload" or (mode == "dp" and offload_enabled()):
                videos = model.pipeline.generate_offloaded_i2v(
                    spec, int(seed), image[:1], ctx, on_step=ps.on_step,
                    progress_token=ps.traced,
                    should_stop=_stop_cb(interrupt_event))
            elif mode == "sp":
                if "sp" not in mesh.shape:
                    mesh = build_mesh({"sp": mesh.devices.size},
                                      list(mesh.devices.flat))
                videos = model.pipeline.generate_i2v_frames(
                    mesh, spec, int(seed), image[:1], ctx, pooled,
                    progress_token=ps.traced)
            else:
                videos = model.pipeline.generate_i2v(
                    mesh, spec, int(seed), image[:1], ctx, pooled,
                    progress_token=ps.traced)
            ps.complete(videos)
        return (_flatten_video_batch(videos),)


@register_node("VAEEncode")
class VAEEncode(NodeDef):
    INPUTS = {"pixels": "IMAGE", "vae": "VAE"}
    RETURNS = ("LATENT",)

    def execute(self, pixels, vae, **_):
        return ({"samples": vae.encode(jnp.asarray(pixels) * 2.0 - 1.0)},)


@register_node("VAEDecode")
class VAEDecode(NodeDef):
    INPUTS = {"samples": "LATENT", "vae": "VAE"}
    RETURNS = ("IMAGE",)

    def execute(self, samples, vae, **_):
        out = vae.decode(samples["samples"])
        return (jnp.clip(out / 2.0 + 0.5, 0.0, 1.0),)


# a saved batch's images, one worker each (SaveImage). The executor starts
# a thread only when a task finds none idle and its threads never retire,
# so the cap is also what one large batch leaves behind for good
_SAVE_POOL = concurrent.futures.ThreadPoolExecutor(
    max_workers=min(os.cpu_count() or 1, 8), thread_name_prefix="cdt-save")


@register_node("SaveImage")
class SaveImage(NodeDef):
    """Writes a batch as ``<prefix>_<index>.png``. The images share
    nothing, so each of several is fetched, quantised, encoded and written
    by a worker of ``_SAVE_POOL``, side by side; one image takes the same
    steps on the calling thread. The batch as a whole never comes to the
    host: of a device array a worker copies the one addressable shard
    that holds its image (a fan-out's four images leave their four chips
    at once), and no device program is launched for it."""

    INPUTS = {"images": "IMAGE"}
    OPTIONAL = {"filename_prefix": "STRING"}
    HIDDEN = {"output_dir": "STRING"}
    RETURNS = ()
    OUTPUT_NODE = True

    def execute(self, images, filename_prefix: str = "output",
                output_dir: str = "", **_):
        from ..telemetry import enabled as _tm_enabled
        from ..telemetry import metrics as _tm
        from ..telemetry.spans import span
        from ..utils.image import encode_png, to_uint8

        out_dir = Path(output_dir or "output")
        out_dir.mkdir(parents=True, exist_ok=True)
        if not hasattr(images, "shape"):
            images = np.asarray(images)
        if len(images.shape) != 4:
            # one [H,W,C] image gains its axis, anything else is refused
            images = to_uint8(images)
        n = images.shape[0]
        # image index -> (the addressable shard that holds it whole, the
        # shard's first row); None: host rows, indexed where they are
        holders = None
        if isinstance(images, jax.Array):
            holders = {}
            for shard in images.addressable_shards:
                if shard.data.shape[1:] == images.shape[1:]:
                    first, stop, _ = shard.index[0].indices(n)
                    for i in range(first, stop):
                        holders.setdefault(i, (shard, first))
            if len(holders) < n:
                # an image split over chips has no shard to copy: one gather
                images, holders = np.asarray(images), None
        pooled = n > 1

        def fetch(i: int) -> np.ndarray:
            if holders is None:
                return images[i]
            shard, first = holders[i]
            # the shard's buffer is copied and indexed HERE: shard.data[j]
            # would compile and run a slice program for every image
            with span("image.fetch", bytes=images.nbytes // n):
                return np.asarray(shard.data)[i - first]

        def save(i: int) -> str:
            p = out_dir / f"{filename_prefix}_{i:05d}.png"
            arr = to_uint8(fetch(i))
            with span("image.encode_png"):
                data = encode_png(arr[0])
            with span("image.write", bytes=len(data)):
                p.write_bytes(data)
            return str(p)

        if pooled:
            # each task under a copy of this thread's context: its spans
            # are children of node.SaveImage. Every task is waited for,
            # then the lowest failing index raises, as the loop's would
            tasks = [_SAVE_POOL.submit(contextvars.copy_context().run, save, i)
                     for i in range(n)]
            concurrent.futures.wait(tasks)
            paths = [t.result() for t in tasks]
        else:
            paths = [save(i) for i in range(n)]
        if _tm_enabled():
            _tm.IMAGE_SAVE_IMAGES.labels(
                mode="pooled" if pooled else "inline").inc(len(paths))
        log(f"saved {len(paths)} images to {out_dir}")
        return ()


@register_node("PreviewImage")
class PreviewImage(NodeDef):
    INPUTS = {"images": "IMAGE"}
    RETURNS = ()
    OUTPUT_NODE = True

    def execute(self, images, **_):
        debug_log(f"preview: batch of {np.asarray(images).shape[0]}")
        return ()


@register_node("LoadImage")
class LoadImage(NodeDef):
    INPUTS = {"image": "STRING"}
    HIDDEN = {"input_dir": "STRING"}
    RETURNS = ("IMAGE",)

    def execute(self, image: str, input_dir: str = "", **_):
        from ..utils.image import decode_png

        path = Path(input_dir or "input") / image
        if not path.exists():
            raise ValidationError(f"image file not found: {path}", field="image")
        return (jnp.asarray(decode_png(path.read_bytes()))[None],)


@register_node("LoadAudio")
class LoadAudio(NodeDef):
    """WAV file → AUDIO dict ``{"waveform": [1,C,S], "sample_rate"}``.

    The reference free-rides on ComfyUI's LoadAudio for the file edge and
    only ships the transport envelope (``utils/audio_payload.py``); here
    the stdlib WAV codec closes the loop so audio workflows are drivable
    end-to-end (media sync already handles ``.wav`` inputs)."""

    INPUTS = {"audio": "STRING"}
    HIDDEN = {"input_dir": "STRING"}
    RETURNS = ("AUDIO",)

    def execute(self, audio: str, input_dir: str = "", **_):
        from ..utils.audio_payload import wav_decode

        path = Path(input_dir or "input") / audio
        if not path.exists():
            raise ValidationError(f"audio file not found: {path}",
                                  field="audio")
        return (wav_decode(path.read_bytes()),)


@register_node("SaveAudio")
class SaveAudio(NodeDef):
    """AUDIO → one 16-bit PCM WAV per batch element (ComfyUI SaveAudio
    parity via the stdlib codec)."""

    INPUTS = {"audio": "AUDIO"}
    OPTIONAL = {"filename_prefix": "STRING"}
    HIDDEN = {"output_dir": "STRING"}
    RETURNS = ()
    OUTPUT_NODE = True

    def execute(self, audio, filename_prefix: str = "audio",
                output_dir: str = "", **_):
        from ..utils.audio_payload import wav_bytes

        wf = np.asarray(audio["waveform"])
        if wf.ndim == 2:               # tolerate [C,S]
            wf = wf[None]
        sr = int(audio.get("sample_rate", 44100))
        out_dir = Path(output_dir or "output")
        out_dir.mkdir(parents=True, exist_ok=True)
        paths = []
        for i in range(wf.shape[0]):
            p = out_dir / f"{filename_prefix}_{i:05d}.wav"
            p.write_bytes(wav_bytes(wf[i], sr))
            paths.append(str(p))
        log(f"saved {len(paths)} audio clips to {out_dir}")
        return ()


@register_node("LoadVideo")
class LoadVideo(NodeDef):
    """Video container → IMAGE frame batch + AUDIO + fps + frame count.

    Reference-ecosystem parity: the ``VHS_LoadVideo`` node type its video
    workflows assume (``/root/reference/workflows/
    distributed-upscale-video.json``; the reference itself free-rides on
    VideoHelperSuite for the file edge). Frame-selection knobs (cap /
    skip / stride) mirror that surface. Containers: mp4/webm via OpenCV,
    plus this framework's MJPG+PCM AVI with a truly muxed audio track
    (``utils/video_io.py`` — no ffmpeg exists in this environment)."""

    INPUTS = {"video": "STRING"}
    OPTIONAL = {"frame_load_cap": "INT", "skip_first_frames": "INT",
                "select_every_nth": "INT"}
    HIDDEN = {"input_dir": "STRING"}
    RETURNS = ("IMAGE", "AUDIO", "FLOAT", "INT")

    def execute(self, video: str, frame_load_cap: int = 0,
                skip_first_frames: int = 0, select_every_nth: int = 1,
                input_dir: str = "", **_):
        from ..utils.video_io import load_video

        path = Path(input_dir or "input") / video
        if not path.exists():
            raise ValidationError(f"video file not found: {path}",
                                  field="video")
        clip = load_video(path, frame_load_cap=int(frame_load_cap),
                          skip_first_frames=int(skip_first_frames),
                          select_every_nth=int(select_every_nth))
        # audio-less containers emit a valid zero-length AUDIO dict so
        # any downstream AUDIO consumer (SaveAudio, dividers) degrades
        # to a no-op instead of crashing on None
        audio = clip["audio"] or {
            "waveform": np.zeros((1, 1, 0), np.float32),
            "sample_rate": 44100,
        }
        return (jnp.asarray(clip["frames"]), audio,
                float(clip["fps"]), int(clip["frame_count"]))


@register_node("SaveVideo")
class SaveVideo(NodeDef):
    """IMAGE frame batch (+ optional AUDIO) → playable video container.

    Reference-ecosystem parity: the ``VHS_VideoCombine`` surface (frame
    rate, format, audio mux, filename prefix). Formats: ``avi`` writes
    MJPG+PCM with the audio track genuinely muxed (pure-Python RIFF
    muxer); ``mp4``/``webm`` write via OpenCV with audio as a sidecar
    ``.wav`` that ``LoadVideo`` re-attaches — a documented divergence
    from the reference's ffmpeg mux (no ffmpeg in this image). Returns
    the container path for downstream chaining."""

    INPUTS = {"images": "IMAGE", "frame_rate": "FLOAT"}
    OPTIONAL = {"audio": "AUDIO", "format": "STRING",
                "filename_prefix": "STRING", "quality": "INT"}
    HIDDEN = {"output_dir": "STRING"}
    RETURNS = ("STRING",)
    OUTPUT_NODE = True

    _FORMATS = ("mp4", "webm", "avi")

    def execute(self, images, frame_rate: float = 8.0, audio=None,
                format: str = "mp4", filename_prefix: str = "video",
                quality: int = 95, output_dir: str = "", **_):
        from ..utils.video_io import save_video

        # tolerate VHS-style format strings ("video/h264-mp4")
        fmt = str(format).lower()
        fmt = next((f for f in self._FORMATS if f in fmt), fmt)
        if fmt not in self._FORMATS:
            raise ValidationError(
                f"unsupported video format {format!r} "
                f"(supported: {list(self._FORMATS)})", field="format")
        out_dir = Path(output_dir or "output")
        out_dir.mkdir(parents=True, exist_ok=True)
        # uniqueness must cover the audio-sidecar namespace too: all
        # formats share "<stem>.wav", so a free .webm slot whose .wav is
        # taken by an earlier .mp4 save would silently clobber that
        # video's audio
        i = 0
        while True:
            stem = out_dir / f"{filename_prefix}_{i:05d}.{fmt}"
            if not stem.exists() and not stem.with_suffix(".wav").exists():
                break
            i += 1
        written = save_video(stem, images, fps=float(frame_rate),
                             audio=audio, quality=int(quality))
        log(f"saved video {written[0]}"
            + (f" (+ sidecar {written[1]})" if len(written) > 1 else ""))
        return (written[0],)


# Drop-in aliases so reference workflow JSON naming the VideoHelperSuite
# node types executes unchanged (distributed-upscale-video.json uses
# VHS_LoadVideo / VHS_VideoCombine; extra VHS-only inputs are tolerated
# by the executor's forward-compat rule).
NODE_REGISTRY["VHS_LoadVideo"] = LoadVideo
NODE_REGISTRY["VHS_VideoCombine"] = SaveVideo


@register_node("PrimitiveInt")
class PrimitiveInt(NodeDef):
    INPUTS = {"value": "INT"}
    RETURNS = ("INT",)

    def execute(self, value, **_):
        return (int(value),)


@register_node("PrimitiveFloat")
class PrimitiveFloat(NodeDef):
    INPUTS = {"value": "FLOAT"}
    RETURNS = ("FLOAT",)

    def execute(self, value, **_):
        return (float(value),)


@register_node("PrimitiveString")
class PrimitiveString(NodeDef):
    INPUTS = {"value": "STRING"}
    RETURNS = ("STRING",)

    def execute(self, value, **_):
        return (str(value),)
