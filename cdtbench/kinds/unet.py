"""UNet presets (SDXL, SD1.5): the model call of one denoise step, and the
segment program ``serve`` runs for a ``TPUTxt2Img`` graph."""

from __future__ import annotations


def step_call(config: dict, lat_h: int, lat_w: int, batch: int):
    """``(fn, args)``: one UNet call at ``batch`` with abstract weights,
    built from the sizes in the configuration's own file."""
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models.unet import UNetConfig, init_unet

    sizes = {k: tuple(v) if isinstance(v, list) else v
             for k, v in config["unet"].items()}
    cfg = UNetConfig(**sizes)
    ctx_len = int(config["context_len"])
    model, params = init_unet(
        cfg, jax.random.key(0), sample_shape=(lat_h, lat_w, cfg.in_channels),
        context_len=ctx_len, abstract=True)
    f32 = jnp.float32
    args = (jax.ShapeDtypeStruct((batch, lat_h, lat_w, cfg.in_channels), f32),
            jax.ShapeDtypeStruct((batch,), f32),
            jax.ShapeDtypeStruct((batch, ctx_len, cfg.context_dim), f32),
            jax.ShapeDtypeStruct((batch, cfg.adm_in_channels), f32))
    return model.apply, (params, *args)


def denoise_program(cell, preset, mesh, rep, vae, common):
    """The 8/10-step segment program with the progress token."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from comfyui_distributed_tpu.diffusion.pipeline import (GenerationSpec,
                                                            Txt2ImgPipeline)
    from comfyui_distributed_tpu.models.unet import init_unet

    sampler = cell.sampler_inputs
    model, params = init_unet(
        preset.unet, jax.random.key(0),
        sample_shape=(*preset.sample_hw, preset.unet.in_channels),
        context_len=preset.text.max_len, abstract=True,
        param_dtype=preset.param_dtype)
    pipe = Txt2ImgPipeline(model, params, vae)
    spec = GenerationSpec(
        height=sampler["height"], width=sampler["width"],
        steps=sampler["steps"],
        sampler=sampler.get("sampler_name", "euler"),
        scheduler=sampler.get("scheduler", "karras"),
        guidance_scale=float(cell.cfg),
        per_device_batch=int(sampler.get("batch_per_device", 1)))
    fns = pipe.preemptible_fns(mesh, spec)
    segment = int(cell.config.get("serve_env", {}).get(
        "CDT_PREEMPT_SEGMENT_STEPS", 8))
    length = min(segment, fns["n_steps"])
    fn = fns["seg"](length, True)
    y = jax.ShapeDtypeStruct((1, preset.unet.adm_in_channels), jnp.float32,
                             sharding=rep)
    carry = tuple(
        jax.ShapeDtypeStruct(
            s, jnp.float32,
            sharding=NamedSharding(mesh, P("dp")) if len(s) == 4 else rep)
        for s in fns["carry_shapes"])
    start = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    ctx, key, token = common["ctx"], common["key"], common["token"]
    return fn, (key, ctx, ctx, y, y, start, carry, token), \
        f"txt2img_seg, {length} steps"
