"""DiT presets (SD3, FLUX): the model call of one denoise step, and the
monolithic flow program (denoise + decode) ``serve`` runs for a
``TPUFlowTxt2Img`` graph."""

from __future__ import annotations


def step_call(config: dict, lat_h: int, lat_w: int, batch: int):
    """``(fn, args)``: one DiT call at ``batch`` with abstract weights,
    built from the sizes in the configuration's own file."""
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models.dit import DiTConfig, init_dit

    cfg = DiTConfig(**config["dit"])
    ctx_len = int(config["context_len"])
    model, params = init_dit(cfg, jax.random.key(0), sample_hw=(lat_h, lat_w),
                             context_len=ctx_len, abstract=True)
    f32 = jnp.float32
    args = (jax.ShapeDtypeStruct((batch, lat_h, lat_w, cfg.in_channels), f32),
            jax.ShapeDtypeStruct((batch,), f32),
            jax.ShapeDtypeStruct((batch, ctx_len, cfg.context_dim), f32),
            jax.ShapeDtypeStruct((batch, cfg.pooled_dim), f32))
    return model.apply, (params, *args)


def denoise_program(cell, preset, mesh, rep, vae, common):
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.diffusion.pipeline_flow import (FlowPipeline,
                                                                 FlowSpec)
    from comfyui_distributed_tpu.models.dit import init_dit

    sampler = cell.sampler_inputs
    model, params = init_dit(
        preset.dit, jax.random.key(0), sample_hw=preset.sample_hw,
        context_len=preset.text.max_len, abstract=True,
        param_dtype=preset.param_dtype)
    pipe = FlowPipeline(model, params, vae)
    spec = FlowSpec(height=sampler["height"], width=sampler["width"],
                    steps=sampler["steps"],
                    shift=float(sampler.get("shift", 3.0)),
                    guidance=float(sampler.get("guidance", 3.5)),
                    cfg=float(cell.cfg),
                    per_device_batch=int(sampler.get("batch_per_device", 1)))
    fn = pipe.generate_fn(mesh, spec, progress=True)
    pooled = jax.ShapeDtypeStruct((1, preset.dit.pooled_dim), jnp.float32,
                                  sharding=rep)
    ctx, key, token = common["ctx"], common["key"], common["token"]
    args = (key, ctx, pooled)
    if spec.cfg != 1.0:
        args += (ctx, pooled)
    return fn, args + (token,), f"flow_dp, {sampler['steps']} steps + decode"
