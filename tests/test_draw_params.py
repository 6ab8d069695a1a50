"""Random weights a leaf at a time (``models/draw.py``): every leaf of every
family bit for bit what ``jax.jit`` of the module's whole ``init`` gave — the
old path, kept here as the oracle — and as many programs as there are
distinct (initialiser, shape, dtype), however deep the model."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu import telemetry
from comfyui_distributed_tpu.models import draw
from comfyui_distributed_tpu.models.clip import CLIPTextConfig, CLIPTextModel
from comfyui_distributed_tpu.models.controlnet import init_controlnet
from comfyui_distributed_tpu.models.dit import DiTConfig, init_dit
from comfyui_distributed_tpu.models.t5 import T5Config, T5Model
from comfyui_distributed_tpu.models.text import TextEncoder, TextEncoderConfig
from comfyui_distributed_tpu.models.unet import UNetConfig, init_unet
from comfyui_distributed_tpu.models.upscaler import (UpscalerConfig,
                                                     init_upscaler)
from comfyui_distributed_tpu.models.vae import AutoencoderKL, VAEConfig
from comfyui_distributed_tpu.models.video_dit import (VideoDiTConfig,
                                                      init_video_dit)
from comfyui_distributed_tpu.models.wan import WanConfig, init_wan
from comfyui_distributed_tpu.models.wan_vae import WanVAE3D, WanVAEConfig
from comfyui_distributed_tpu.telemetry import metrics as tm

KEY = jax.random.key(7)


def _jitted_init(module, rng, *args, param_dtype=None, abstract=False):
    """The initialiser as it was: ONE program, the cast inside it."""
    init = lambda *a: draw.cast_float(module.init(*a), param_dtype)  # noqa: E731
    if abstract:
        return jax.eval_shape(init, rng, *args)
    return jax.jit(init)(rng, *args)


def _unet(**changes):
    config = dataclasses.replace(UNetConfig.tiny(), **changes)
    assert config.adm_in_channels and any(config.transformer_depth)
    return init_unet(config, KEY, sample_shape=(8, 8, 4), context_len=8)


# every site of the package that makes random weights for a flax module,
# called as the package calls it (a VAE is two modules: two draws)
FAMILIES = {
    "unet": _unet,
    "unet-remat": lambda: _unet(remat=True),
    "dit": lambda: init_dit(DiTConfig.tiny(), KEY, sample_hw=(8, 8),
                            context_len=8),
    "vae": lambda: AutoencoderKL(VAEConfig.tiny()).init(KEY,
                                                        image_hw=(16, 16)),
    "clip": lambda: CLIPTextModel(CLIPTextConfig.tiny()).init(KEY),
    "text": lambda: TextEncoder(TextEncoderConfig.tiny()).init(KEY),
    "t5": lambda: T5Model(T5Config.tiny()).init(KEY),
    "wan": lambda: init_wan(WanConfig.tiny(), KEY),
    "video-dit": lambda: init_video_dit(VideoDiTConfig.tiny(), KEY),
    "controlnet": lambda: init_controlnet(
        UNetConfig.tiny(), KEY, sample_shape=(8, 8, 4), context_len=8),
    "upscaler": lambda: init_upscaler(UpscalerConfig.tiny(), KEY,
                                      sample_hw=(8, 8)),
    "wan-vae": lambda: WanVAE3D(WanVAEConfig.tiny()).init(KEY,
                                                          image_hw=(16, 16)),
}


def _draws_of(build, monkeypatch):
    """(module, rng, example arguments) of each ``draw_params`` call that
    ``build`` makes, answered with shapes alone."""
    calls = []

    def spy(module, rng, *args, **kwargs):
        calls.append((module, rng, args))
        return draw_params(module, rng, *args, **{**kwargs, "abstract": True})

    draw_params = draw.draw_params
    monkeypatch.setattr(draw, "draw_params", spy)
    build()
    monkeypatch.setattr(draw, "draw_params", draw_params)
    return calls


@pytest.mark.parametrize("param_dtype", [None, "bfloat16"])
@pytest.mark.parametrize("family", FAMILIES)
def test_every_leaf_is_the_jitted_inits(family, param_dtype, monkeypatch):
    calls = _draws_of(FAMILIES[family], monkeypatch)
    assert calls
    for module, rng, args in calls:
        got = draw.draw_params(module, rng, *args, param_dtype=param_dtype)
        want = _jitted_init(module, rng, *args, param_dtype=param_dtype)
        assert (jax.tree_util.tree_structure(got)
                == jax.tree_util.tree_structure(want))
        for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree_util.tree_leaves(want)):
            where = f"{family}: {jax.tree_util.keystr(path)}"
            assert (g.dtype, g.shape) == (w.dtype, w.shape), where
            assert np.array_equal(np.asarray(g.astype(jnp.float32)),
                                  np.asarray(w.astype(jnp.float32))), where
        floats = {leaf.dtype for leaf in jax.tree_util.tree_leaves(got)
                  if jnp.issubdtype(leaf.dtype, jnp.floating)}
        assert floats == {jnp.dtype(param_dtype or jnp.float32)}
        assert (draw.draw_params(module, rng, *args, param_dtype=param_dtype,
                                 abstract=True)
                == _jitted_init(module, rng, *args, param_dtype=param_dtype,
                                abstract=True))


def _count(metric) -> float:
    return sum(snap["value"] for _, snap in metric.series())


def test_programs_are_the_distinct_leaves_not_the_depth():
    """A UNet of three blocks a level draws twice the leaves of one with one
    block a level through the SAME number of programs: one a distinct
    (parameter's initialiser, shape, dtype), counted where the bundle's
    line reads it."""
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    try:
        read = {}
        for blocks in (1, 3):
            leaves, programs = (_count(tm.WEIGHTS_DRAWN_LEAVES),
                                _count(tm.WEIGHTS_DRAW_PROGRAMS))
            _, params = _unet(num_res_blocks=blocks)
            flat = jax.tree_util.tree_leaves_with_path(params)
            # in this UNet a parameter's name says its initialiser:
            # kernel lecun_normal, bias zeros, scale ones
            distinct = {(path[-1].key, leaf.shape, leaf.dtype)
                        for path, leaf in flat}
            read[blocks] = (_count(tm.WEIGHTS_DRAWN_LEAVES) - leaves,
                            _count(tm.WEIGHTS_DRAW_PROGRAMS) - programs)
            assert read[blocks] == (len(flat), len(distinct))
    finally:
        telemetry.set_enabled(was)
    assert read[3][0] > 1.8 * read[1][0]
    assert read[3][1] == read[1][1] < read[1][0] / 5


def test_another_threads_scopes_are_not_watched():
    """While one thread's abstract pass watches ``Scope.param``, a flax
    ``init`` on another thread goes through untouched and unrecorded."""
    import threading

    import flax.linen as nn

    leaves, other = {}, {}
    dense = nn.Dense(3)

    def elsewhere():
        other["params"] = dense.init(KEY, jnp.zeros((1, 2)))

    with draw._watched_params(leaves):
        thread = threading.Thread(target=elsewhere)
        thread.start()
        thread.join(timeout=60)
        assert not thread.is_alive()
        jax.eval_shape(nn.Dense(5).init, KEY, jnp.zeros((1, 2)))
    assert sorted(leaves) == [("params", "bias"), ("params", "kernel")]
    assert leaves[("params", "kernel")].args[0] == (2, 5)
    assert other["params"]["params"]["kernel"].shape == (2, 3)
