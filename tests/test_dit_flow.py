"""DiT / flow pipeline tests, incl. the SP-vs-single-chip equivalence that
anchors the sequence-parallel design."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.diffusion.pipeline_flow import FlowPipeline, FlowSpec
from comfyui_distributed_tpu.models.dit import (
    DiTConfig,
    init_dit,
    patchify,
    unpatchify,
)
from comfyui_distributed_tpu.models.vae import AutoencoderKL, VAEConfig
from comfyui_distributed_tpu.parallel import build_mesh

pytestmark = pytest.mark.slow  # compile-heavy: builds/jits real model stacks


def test_patchify_roundtrip():
    x = jax.random.normal(jax.random.key(0), (2, 8, 12, 5))
    toks = patchify(x, 2)
    assert toks.shape == (2, 4 * 6, 4 * 5)
    back = unpatchify(toks, (8, 12), 2, 5)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_dit_tiny_forward():
    cfg = DiTConfig.tiny()
    model, params = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                             context_len=6)
    x = jnp.ones((2, 8, 8, cfg.in_channels))
    out = model.apply(params, x, jnp.array([0.5, 0.9]),
                      jnp.ones((2, 6, cfg.context_dim)),
                      jnp.ones((2, cfg.pooled_dim)))
    assert out.shape == (2, 8, 8, cfg.in_channels)
    assert np.isfinite(np.asarray(out)).all()


def test_flux_config_shape():
    cfg = DiTConfig.flux()
    assert cfg.hidden == 3072 and cfg.heads == 24
    assert cfg.depth_double == 19 and cfg.depth_single == 38
    assert cfg.in_channels == 16
    assert cfg.head_dim == 128


def test_sd3_config_shapes():
    m, l = DiTConfig.sd3_medium(), DiTConfig.sd35_large()
    assert m.hidden == 1536 and m.depth_double == 24 and m.depth_single == 0
    assert l.hidden == 2432 and l.depth_double == 38 and l.depth_single == 0
    assert not m.qk_norm and l.qk_norm
    for cfg in (m, l):
        assert cfg.pos_embed == "learned" and cfg.pos_embed_max_size == 192
        assert not cfg.guidance_embed
        assert cfg.context_dim == 4096 and cfg.pooled_dim == 2048
        assert cfg.head_dim == 64


def test_sd3_tiny_forward_and_param_shape():
    cfg = DiTConfig.sd3_tiny()
    model, params = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                             context_len=6)
    # no qk-norm scales, a learned table, no single blocks
    flat = jax.tree_util.tree_leaves_with_path(params)
    paths = {"/".join(str(k.key) for k in p if hasattr(k, "key"))
             for p, _ in flat}
    assert not any("q_scale" in p for p in paths)
    assert not any("single_" in p for p in paths)
    assert any(p.endswith("pos_emb") for p in paths)
    out = model.apply(params, jnp.ones((2, 8, 8, cfg.in_channels)),
                      jnp.array([0.5, 0.9]),
                      jnp.ones((2, 6, cfg.context_dim)),
                      jnp.ones((2, cfg.pooled_dim)))
    assert out.shape == (2, 8, 8, cfg.in_channels)
    assert np.isfinite(np.asarray(out)).all()


def test_sd3_rejects_oversized_grid():
    cfg = DiTConfig.sd3_tiny()          # 12×12 learned table
    model, params = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                             context_len=6)
    with pytest.raises(ValueError, match="learned position"):
        model.apply(params, jnp.ones((1, 32, 32, cfg.in_channels)),
                    jnp.array([0.5]), jnp.ones((1, 6, cfg.context_dim)),
                    jnp.ones((1, cfg.pooled_dim)))


def test_sd3_sp_matches_single_chip():
    """The learned-table row slicing under sp must reproduce the
    single-chip crop exactly (same discipline as the sincos/rope tests)."""
    cfg = DiTConfig.tiny(pos_embed="learned", pos_embed_max_size=12,
                         depth_single=0, qk_norm=False, dtype="float32")
    model, params = init_dit(cfg, jax.random.key(0), sample_hw=(16, 16),
                             context_len=6)
    vae = AutoencoderKL(VAEConfig.tiny(dtype="float32")).init(
        jax.random.key(1), image_hw=(32, 32))
    pipe = FlowPipeline(model, params, vae)
    ctx, pooled = _cond(cfg)
    spec = FlowSpec(height=32, width=32, steps=2, shift=1.0)
    sp_out = np.asarray(pipe.generate_sp(build_mesh({"sp": 4}), spec, seed=7,
                                         context=ctx, pooled=pooled))
    single = np.asarray(pipe.generate_sp(build_mesh({"sp": 1}), spec, seed=7,
                                         context=ctx, pooled=pooled))
    assert sp_out.shape == (1, 32, 32, 3)
    np.testing.assert_allclose(sp_out, single, rtol=2e-4, atol=2e-4)


@pytest.fixture(scope="module")
def flow_stack():
    cfg = DiTConfig.tiny(attn_backend="dense")
    model, params = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                             context_len=6)
    vae = AutoencoderKL(VAEConfig.tiny(dtype="float32")).init(
        jax.random.key(1), image_hw=(16, 16))
    # tiny VAE has latent_channels=4 == DiT in_channels
    return FlowPipeline(model, params, vae)


def _cond(cfg):
    return (jnp.ones((1, 6, cfg.context_dim)) * 0.1,
            jnp.ones((1, cfg.pooled_dim)) * 0.2)


def test_flow_dp_fanout(flow_stack):
    mesh = build_mesh({"dp": 8})
    spec = FlowSpec(height=16, width=16, steps=2, shift=1.0)
    ctx, pooled = _cond(flow_stack.dit.config)
    imgs = flow_stack.generate(mesh, spec, seed=0, context=ctx, pooled=pooled)
    imgs = np.asarray(imgs)
    assert imgs.shape == (8, 16, 16, 3)
    # distinct seeds per shard
    assert len({imgs[i].tobytes() for i in range(8)}) == 8


def test_flow_sp_matches_single_chip():
    """Row-sharded ring-attention generation must equal the single-chip
    result for the same seed (exactness of the SP decomposition)."""
    cfg = DiTConfig.tiny()
    # float32 end-to-end for bit comparability
    cfg = DiTConfig(patch_size=2, in_channels=4, hidden=64, depth_double=2,
                    depth_single=2, heads=4, context_dim=32, pooled_dim=16,
                    dtype="float32")
    model, params = init_dit(cfg, jax.random.key(0), sample_hw=(16, 16),
                             context_len=6)
    vae = AutoencoderKL(VAEConfig.tiny(dtype="float32")).init(
        jax.random.key(1), image_hw=(32, 32))
    pipe = FlowPipeline(model, params, vae)
    ctx, pooled = _cond(cfg)
    spec = FlowSpec(height=32, width=32, steps=2, shift=1.0)

    sp_out = np.asarray(pipe.generate_sp(build_mesh({"sp": 4}), spec, seed=7,
                                         context=ctx, pooled=pooled))
    single = np.asarray(pipe.generate_sp(build_mesh({"sp": 1}), spec, seed=7,
                                         context=ctx, pooled=pooled))
    assert sp_out.shape == (1, 32, 32, 3)
    np.testing.assert_allclose(sp_out, single, rtol=2e-4, atol=2e-4)


class TestFlowTrueCfg:
    """spec.cfg != 1.0 (SD3-family true CFG): uncond conditioning threads
    through generate/generate_sp, and missing it fails LOUDLY instead of
    silently sampling unguided (the r05 dead-plumbing fix)."""

    def test_missing_uncond_raises(self, flow_stack):
        mesh = build_mesh({"dp": 2})
        spec = FlowSpec(height=16, width=16, steps=2, shift=1.0, cfg=4.0)
        ctx, pooled = _cond(flow_stack.dit.config)
        with pytest.raises(ValueError, match="negative conditioning"):
            flow_stack.generate(mesh, spec, seed=0, context=ctx,
                                pooled=pooled)
        with pytest.raises(ValueError, match="negative conditioning"):
            flow_stack.generate_sp(build_mesh({"sp": 2}), spec, seed=0,
                                   context=ctx, pooled=pooled)

    def test_cfg_changes_the_sample(self):
        # random DiT init zero-inits the modulation/output projections, so
        # the context path is numerically dead — perturb every leaf to
        # give the conditioning real influence before testing guidance
        cfg = DiTConfig.tiny(attn_backend="dense")
        model, params = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                                 context_len=6)
        leaves, treedef = jax.tree_util.tree_flatten(params)
        keys = jax.random.split(jax.random.key(9), len(leaves))
        params = jax.tree_util.tree_unflatten(treedef, [
            l + 0.05 * jax.random.normal(k, l.shape, l.dtype)
            for l, k in zip(leaves, keys)])
        vae = AutoencoderKL(VAEConfig.tiny(dtype="float32")).init(
            jax.random.key(1), image_hw=(16, 16))
        pipe = FlowPipeline(model, params, vae)
        mesh = build_mesh({"dp": 2})
        ctx, pooled = _cond(cfg)
        unc = jnp.zeros_like(ctx)
        base = FlowSpec(height=16, width=16, steps=2, shift=1.0)
        plain = np.asarray(pipe.generate(
            mesh, base, seed=3, context=ctx, pooled=pooled))
        guided = np.asarray(pipe.generate(
            mesh, FlowSpec(height=16, width=16, steps=2, shift=1.0,
                           cfg=4.0),
            seed=3, context=ctx, pooled=pooled,
            uncond_context=unc, uncond_pooled=jnp.zeros_like(pooled)))
        assert guided.shape == plain.shape
        assert not np.allclose(guided, plain)
        # cfg with uncond == cond degenerates to the plain sample:
        # out = uncond + s·(cond − uncond) = cond
        degen = np.asarray(pipe.generate(
            mesh, FlowSpec(height=16, width=16, steps=2, shift=1.0,
                           cfg=4.0),
            seed=3, context=ctx, pooled=pooled,
            uncond_context=ctx, uncond_pooled=pooled))
        np.testing.assert_allclose(degen, plain, rtol=1e-5, atol=1e-5)

    def test_sp_cfg_matches_single_chip(self):
        cfg = DiTConfig(patch_size=2, in_channels=4, hidden=64,
                        depth_double=2, depth_single=2, heads=4,
                        context_dim=32, pooled_dim=16, dtype="float32")
        model, params = init_dit(cfg, jax.random.key(0),
                                 sample_hw=(16, 16), context_len=6)
        vae = AutoencoderKL(VAEConfig.tiny(dtype="float32")).init(
            jax.random.key(1), image_hw=(32, 32))
        pipe = FlowPipeline(model, params, vae)
        ctx, pooled = _cond(cfg)
        unc = jnp.zeros_like(ctx)
        spec = FlowSpec(height=32, width=32, steps=2, shift=1.0, cfg=3.0)
        sp_out = np.asarray(pipe.generate_sp(
            build_mesh({"sp": 4}), spec, seed=7, context=ctx,
            pooled=pooled, uncond_context=unc))
        single = np.asarray(pipe.generate_sp(
            build_mesh({"sp": 1}), spec, seed=7, context=ctx,
            pooled=pooled, uncond_context=unc))
        np.testing.assert_allclose(sp_out, single, rtol=2e-4, atol=2e-4)

    def test_offload_and_tp_reject_cfg(self, flow_stack):
        spec = FlowSpec(height=16, width=16, steps=2, cfg=2.0)
        ctx, pooled = _cond(flow_stack.dit.config)
        with pytest.raises(ValueError, match="not wired"):
            flow_stack.generate_offloaded(spec, 0, ctx, pooled)
        with pytest.raises(ValueError, match="not wired"):
            flow_stack.generate_tp_fn(build_mesh({"dp": 4, "tp": 2}), spec)


def test_flow_sp_rejects_indivisible():
    cfg = DiTConfig.tiny()
    model, params = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                             context_len=6)
    vae = AutoencoderKL(VAEConfig.tiny()).init(jax.random.key(1),
                                               image_hw=(16, 16))
    pipe = FlowPipeline(model, params, vae)
    with pytest.raises(ValueError, match="divide"):
        pipe.generate_sp_fn(build_mesh({"sp": 8}),
                            FlowSpec(height=16, width=16, steps=1))


def test_flow_dp_tp_gspmd(flow_stack):
    """dp×tp 2-D mesh: 4 seed-parallel images with weights sharded over 2
    chips each."""
    mesh = build_mesh({"dp": 4, "tp": 2})
    spec = FlowSpec(height=16, width=16, steps=2, shift=1.0)
    ctx, pooled = _cond(flow_stack.dit.config)
    fn = flow_stack.generate_tp_fn(mesh, spec)
    imgs = np.asarray(fn(jax.random.key(0), ctx, pooled))
    assert imgs.shape == (4, 16, 16, 3)
    assert np.isfinite(imgs).all()
    assert len({imgs[i].tobytes() for i in range(4)}) == 4


class TestRope:
    """FLUX-style 3-axis rotary positions (pos_embed='rope')."""

    def test_apply_rope_preserves_norm_and_moves_positions(self):
        from comfyui_distributed_tpu.models.dit import (
            apply_rope, image_ids, rope_freqs)

        ids = image_ids(4, 4)
        pe = rope_freqs(ids, (4, 6, 6), 10000.0)
        x = jax.random.normal(jax.random.key(0), (1, 16, 2, 16))
        out = np.asarray(apply_rope(x, pe))
        # rotation preserves per-pair norms
        np.testing.assert_allclose(
            np.linalg.norm(out, axis=-1), np.linalg.norm(np.asarray(x), axis=-1),
            rtol=1e-5)
        # token at (0,0) has zero angles → unrotated
        np.testing.assert_allclose(out[:, 0], np.asarray(x[:, 0]), rtol=1e-6)
        # distinct positions rotate differently
        assert not np.allclose(out[:, 5], np.asarray(x[:, 5]))

    def test_rope_forward_and_flux_axes(self):
        cfg = DiTConfig.tiny(pos_embed="rope")
        assert sum(cfg.axes_dim) == cfg.head_dim
        assert DiTConfig.flux().axes_dim == (16, 56, 56)
        assert sum(DiTConfig.flux().axes_dim) == DiTConfig.flux().head_dim
        model, params = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                                 context_len=6)
        out = model.apply(params, jnp.ones((1, 8, 8, 4)), jnp.ones((1,)) * 0.5,
                          jnp.ones((1, 6, 32)), jnp.ones((1, 16)))
        assert out.shape == (1, 8, 8, 4)
        assert np.isfinite(np.asarray(out)).all()

    def test_rope_sp_matches_single_chip(self):
        """Sharded rows with offset RoPE ids must reproduce the unsharded
        rotation exactly — the sp decomposition holds under rope too."""
        cfg = DiTConfig(patch_size=2, in_channels=4, hidden=64,
                        depth_double=2, depth_single=2, heads=4,
                        context_dim=32, pooled_dim=16, dtype="float32",
                        pos_embed="rope")
        model, params = init_dit(cfg, jax.random.key(0), sample_hw=(16, 16),
                                 context_len=6)
        vae = AutoencoderKL(VAEConfig.tiny(dtype="float32")).init(
            jax.random.key(1), image_hw=(32, 32))
        pipe = FlowPipeline(model, params, vae)
        ctx, pooled = _cond(cfg)
        spec = FlowSpec(height=32, width=32, steps=2, shift=1.0)
        sp_out = np.asarray(pipe.generate_sp(build_mesh({"sp": 4}), spec,
                                             seed=7, context=ctx, pooled=pooled))
        single = np.asarray(pipe.generate_sp(build_mesh({"sp": 1}), spec,
                                             seed=7, context=ctx, pooled=pooled))
        np.testing.assert_allclose(sp_out, single, rtol=2e-4, atol=2e-4)


# --- joint blocks through the two-segment attention entry (PR 41) -----------


def _awake(params, seed=7):
    """Every zero-initialised leaf (``img_out``, the adaLN modulations)
    made non-zero: a DiT whose output SEES its blocks."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        0.05 * jax.random.normal(k, l.shape, l.dtype)
        if not np.asarray(l).any() else l for k, l in zip(keys, leaves)])


@pytest.mark.parametrize("name", ["sd3_tiny", "tiny_rope_qknorm"])
def test_joint_blocks_on_the_packed_tier_match_the_xla_arm(name, monkeypatch):
    """The forward through ``ops.attention.joint_attention`` with the
    dispatcher on the ``packed`` tier (forced, as on a TPU; the Pallas
    interpreter here) against the ``xla`` arm, with weights that let the
    output see the blocks: SD3's class hands the kernel bare ``qkv``
    products, the rope + qk-norm class q and k as buffers of their own."""
    import dataclasses

    from comfyui_distributed_tpu.ops import attention as attn
    from comfyui_distributed_tpu.utils.flops import estimate_flops

    if name == "sd3_tiny":
        cfg = dataclasses.replace(DiTConfig.sd3_tiny(), hidden=128, heads=2,
                                  pos_embed_max_size=32)
    else:
        cfg = DiTConfig.tiny(pos_embed="rope", hidden=128, heads=2,
                             depth_single=0)
    assert cfg.head_dim == 64
    monkeypatch.delenv("CDT_FLASH_ATTENTION", raising=False)
    model, params = init_dit(cfg, jax.random.key(0), sample_hw=(64, 64),
                             context_len=13)
    params = _awake(params)
    keys = jax.random.split(jax.random.key(1), 3)
    args = (jax.random.normal(keys[0], (2, 64, 64, cfg.in_channels)),
            jnp.array([0.3, 0.8]),
            jax.random.normal(keys[1], (2, 13, cfg.context_dim)),
            jax.random.normal(keys[2], (2, cfg.pooled_dim)))

    attn.reset_selections()
    xla_out = model.apply(params, *args)
    xla_flops = estimate_flops(model.apply, params, *args)
    assert attn.selection_summary() == ""
    assert float(jnp.std(xla_out)) > 1e-3      # the blocks are seen

    monkeypatch.setenv("CDT_FLASH_ATTENTION", "1")
    packed_out = model.apply(params, *args)
    # 13 text rows + 32 × 32 image rows, bf16: one 16-row text q tile
    assert attn.selection_summary() == (
        "h2.d64.q2048.kv2048.bf16=packed:512+16/1024+128:k-resident")
    scale = float(jnp.abs(xla_out).max())
    np.testing.assert_allclose(np.asarray(packed_out), np.asarray(xla_out),
                               atol=2e-2 * scale, rtol=2e-2)
    assert estimate_flops(model.apply, params, *args) == xla_flops
    # one step's operations as PR 40's tree counted them
    assert xla_flops == {"sd3_tiny": 3852642304.0,
                         "tiny_rope_qknorm": 3852642304.0}[name]
