"""Host-offloaded FLUX execution (diffusion/offload.py): block streaming
must be numerically invisible — the offloaded forward equals DiT.apply,
the python euler ladder equals the scan sampler, and the end-to-end
offloaded generate equals the dp pipeline on one device."""

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from comfyui_distributed_tpu.diffusion.offload import (
    OffloadedFlux,
    materialize_host_params,
    offload_enabled,
    resident_budget_bytes,
    sample_euler_py,
    tree_bytes,
)
from comfyui_distributed_tpu.models.dit import DiTConfig, init_dit

pytestmark = pytest.mark.slow  # compile-heavy: builds/jits real model stacks


def _stack(pos_embed="rope"):
    cfg = DiTConfig.tiny(pos_embed=pos_embed)
    model, params = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                             context_len=6)
    x = jax.random.normal(jax.random.key(1), (2, 8, 8, cfg.in_channels))
    t = jnp.array([0.7, 0.3])
    ctx = jax.random.normal(jax.random.key(2), (2, 6, cfg.context_dim))
    pooled = jax.random.normal(jax.random.key(3), (2, cfg.pooled_dim))
    return cfg, model, params, x, t, ctx, pooled


class TestFlatBlocks:
    """r04: streamed blocks are flattened to one contiguous buffer per
    dtype (one device_put per block instead of ~20 — the fixed cost of
    each put dominated the stream). The layout must round-trip exactly."""

    def test_roundtrip_uniform_dtype(self):
        from comfyui_distributed_tpu.diffusion.offload import (
            _flatten_block, _unflatten_block)

        blk = {"attn": {"kernel": np.arange(12, dtype=np.float32)
                        .reshape(3, 4),
                        "bias": np.ones(4, np.float32)},
               "norm": {"scale": np.full((3,), 2.0, np.float32)}}
        bufs, treedef, metas = _flatten_block(blk)
        assert set(bufs) == {"float32"}
        assert bufs["float32"].shape == (12 + 4 + 3,)
        out = jax.tree_util.tree_map(
            np.asarray, _unflatten_block(
                {k: jnp.asarray(v) for k, v in bufs.items()},
                treedef, metas))
        jax.tree_util.tree_map(np.testing.assert_array_equal, blk, out)

    def test_roundtrip_mixed_dtypes_and_scalars(self):
        from comfyui_distributed_tpu.diffusion.offload import (
            _flatten_block, _unflatten_block)

        blk = {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
               "h": jnp.arange(4, dtype=jnp.bfloat16).reshape(2, 2),
               "step": np.int32(7)}                 # scalar leaf
        bufs, treedef, metas = _flatten_block(blk)
        assert set(bufs) == {"float32", "bfloat16", "int32"}
        out = _unflatten_block(
            {k: jnp.asarray(v) for k, v in bufs.items()}, treedef, metas)
        np.testing.assert_array_equal(np.asarray(out["w"]), blk["w"])
        np.testing.assert_array_equal(np.asarray(out["h"]),
                                      np.asarray(blk["h"]))
        assert np.asarray(out["step"]).item() == 7
        assert np.asarray(out["step"]).shape == ()

    def test_unflatten_traces_inside_jit(self):
        """The block programs unflatten in-trace — static offsets must
        trace cleanly and produce the same numbers under jit."""
        from comfyui_distributed_tpu.diffusion.offload import (
            _flatten_block, _unflatten_block)

        blk = {"a": np.random.randn(4, 5).astype(np.float32),
               "b": np.random.randn(5).astype(np.float32)}
        bufs, treedef, metas = _flatten_block(blk)

        @jax.jit
        def apply(bufs, x):
            p = _unflatten_block(bufs, treedef, metas)
            return x @ p["a"] + p["b"]

        x = np.random.randn(2, 4).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(apply(bufs, x)), x @ blk["a"] + blk["b"],
            rtol=1e-6)


class TestForwardEquivalence:
    @pytest.mark.parametrize("pos_embed", ["rope", "sincos"])
    @pytest.mark.parametrize("resident_bytes", [0, 1 << 40])
    def test_matches_monolithic_apply(self, pos_embed, resident_bytes):
        """All-streamed (0) and all-resident (huge — which engages the
        single scanned program, ``off.stacked``) partitions both equal
        the single-program DiT forward under exact ``native`` dtypes."""
        cfg, model, params, x, t, ctx, pooled = _stack(pos_embed)
        g = jnp.array([3.5, 3.5]) if cfg.guidance_embed else None
        want = np.asarray(model.apply(params, x, t, ctx, pooled, g))
        off = OffloadedFlux(model, params, resident_bytes=resident_bytes,
                            stream_dtype="native")
        if resident_bytes:
            assert off.stacked and not off.streamed and not off.resident
        else:
            assert off.streamed and not off.stacked
        got = np.asarray(off.forward(x, t, ctx, pooled, g))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_partial_residency_matches(self):
        """A budget that fits only SOME blocks: prefix resident, suffix
        streamed, same numbers."""
        cfg, model, params, x, t, ctx, pooled = _stack()
        inner = params["params"]
        one_block = tree_bytes(inner["double_0"])
        glue = tree_bytes({k: v for k, v in inner.items()
                           if not k.startswith(("double_", "single_"))})
        off = OffloadedFlux(model, params,
                            resident_bytes=glue + one_block * 2 + 64,
                            stream_dtype="native")
        assert 0 < len(off.resident) < len(off.block_order)
        assert set(off.resident) | set(off.streamed) == set(off.block_order)
        g = jnp.array([3.5, 3.5])
        want = np.asarray(model.apply(params, x, t, ctx, pooled, g))
        np.testing.assert_allclose(
            np.asarray(off.forward(x, t, ctx, pooled, g)), want,
            rtol=2e-5, atol=2e-5)

    def test_host_numpy_params_accepted(self):
        """The real offload scenario: params arrive as host numpy (a
        full-size init can't live on device)."""
        cfg, model, params, x, t, ctx, pooled = _stack()
        host = jax.tree_util.tree_map(np.asarray, params)
        off = OffloadedFlux(model, host, resident_bytes=0,
                            stream_dtype="native")
        g = jnp.array([3.5, 3.5])
        want = np.asarray(model.apply(params, x, t, ctx, pooled, g))
        np.testing.assert_allclose(
            np.asarray(off.forward(x, t, ctx, pooled, g)), want,
            rtol=2e-5, atol=2e-5)


class TestFp8Quantization:
    """r04: fp8(e4m3) weights-only quantization with per-output-channel
    absmax scales — the optimization that makes a 12B FLUX fit RESIDENT
    in one 16 GB chip (zero bytes streamed per step). Mirrors the
    reference ecosystem's standard fp8 low-VRAM FLUX practice."""

    def test_kernel_roundtrip_error_bounded(self):
        from comfyui_distributed_tpu.diffusion.offload import (
            _flatten_block, _unflatten_block)

        rng = np.random.default_rng(0)
        w = (rng.standard_normal((128, 256)) * 0.02).astype(np.float32)
        blk = {"kernel": w}
        bufs, treedef, metas = _flatten_block(blk, quantize=True)
        assert "float8_e4m3fn" in bufs and "scale" in bufs
        assert bufs["scale"].shape == (256,)       # per output channel
        out = np.asarray(jax.jit(
            lambda b: _unflatten_block(b, treedef, metas)["kernel"])(
            {k: jnp.asarray(v) for k, v in bufs.items()}))
        # e4m3 error model: ≤ half-ulp relative (1/16) in the normal
        # range, plus half a subnormal step (2^-10 × column scale)
        # absolute for weights tiny relative to their column absmax
        scale = np.max(np.abs(w), axis=0) / 448.0
        bound = np.abs(w) / 16.0 + (2.0 ** -10) * scale[None, :] + 1e-12
        assert np.all(np.abs(out - w) <= bound)
        rel = np.abs(out - w) / np.maximum(np.abs(w), 1e-8)
        assert float(np.mean(rel)) < 0.03

    def test_small_leaves_stay_exact(self):
        """Biases / norms / qk-scales are not worth quantizing and must
        round-trip bit-exact."""
        from comfyui_distributed_tpu.diffusion.offload import (
            _flatten_block, _unflatten_block)

        blk = {"kernel": np.random.randn(128, 64).astype(np.float32),
               "bias": np.random.randn(64).astype(np.float32),
               "scale1d": np.random.randn(16).astype(np.float32)}
        bufs, treedef, metas = _flatten_block(blk, quantize=True)
        out = jax.tree_util.tree_map(np.asarray, jax.jit(
            lambda b: _unflatten_block(b, treedef, metas))(
            {k: jnp.asarray(v) for k, v in bufs.items()}))
        np.testing.assert_array_equal(out["bias"], blk["bias"])
        np.testing.assert_array_equal(out["scale1d"], blk["scale1d"])
        assert not np.array_equal(out["kernel"], blk["kernel"])  # lossy

    def test_zero_column_safe(self):
        from comfyui_distributed_tpu.diffusion.offload import (
            _flatten_block, _unflatten_block)

        w = np.random.randn(64, 64).astype(np.float32)
        w[:, 7] = 0.0
        bufs, treedef, metas = _flatten_block({"k": w}, quantize=True)
        out = np.asarray(_unflatten_block(
            {k: jnp.asarray(v) for k, v in bufs.items()}, treedef,
            metas)["k"])
        assert np.all(np.isfinite(out))
        np.testing.assert_array_equal(out[:, 7], 0.0)

    def test_quantized_bytes_roughly_halved(self):
        cfg, model, params, *_ = _stack()
        from comfyui_distributed_tpu.diffusion.offload import \
            _flatten_block

        blk = jax.tree_util.tree_map(
            lambda a: np.asarray(a, ml_dtypes.bfloat16)
            if np.asarray(a).dtype == np.float32 else np.asarray(a),
            params["params"]["double_0"])
        full = tree_bytes(blk)
        bufs, _, _ = _flatten_block(blk, quantize=True)
        assert tree_bytes(bufs) < 0.62 * full

    def test_fp8_forward_close_to_exact(self):
        """End-to-end fp8 (fully-resident scan path) vs the monolithic
        bf16 forward on random-normal weights: quantization noise
        averages over the contraction — a few percent relative L2."""
        cfg = DiTConfig.tiny(pos_embed="rope")
        _, abstract = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                               context_len=6, abstract=True)
        from comfyui_distributed_tpu.diffusion.offload import \
            materialize_host_params

        from comfyui_distributed_tpu.models.dit import DiT
        model = DiT(cfg)
        params = materialize_host_params(abstract, seed=3)
        x = jax.random.normal(jax.random.key(1), (1, 8, 8, cfg.in_channels))
        t = jnp.array([0.5])
        ctx = jax.random.normal(jax.random.key(2), (1, 6, cfg.context_dim))
        pooled = jax.random.normal(jax.random.key(3), (1, cfg.pooled_dim))
        g = jnp.array([3.5])
        want = np.asarray(model.apply(params, x, t, ctx, pooled, g),
                          np.float32)
        off = OffloadedFlux(model, params, resident_bytes=1 << 40,
                            stream_dtype="float8_e4m3fn")
        assert off.stacked and not off.streamed
        got = np.asarray(off.forward(x, t, ctx, pooled, g), np.float32)
        rel_l2 = (np.linalg.norm(got - want)
                  / max(np.linalg.norm(want), 1e-9))
        assert rel_l2 < 0.05, rel_l2

    def test_fp8_streaming_loop_matches_fp8_resident(self):
        """Budget-constrained fp8 (per-block streaming loop) must equal
        the fully-resident scan path bit-for-bit: same quantized buffers,
        same block programs."""
        cfg = DiTConfig.tiny(pos_embed="sincos")
        _, abstract = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                               context_len=6, abstract=True)
        from comfyui_distributed_tpu.diffusion.offload import \
            materialize_host_params

        from comfyui_distributed_tpu.models.dit import DiT
        model = DiT(cfg)
        params = materialize_host_params(abstract, seed=4)
        x = jax.random.normal(jax.random.key(1), (1, 8, 8, cfg.in_channels))
        t = jnp.array([0.5])
        ctx = jax.random.normal(jax.random.key(2), (1, 6, cfg.context_dim))
        pooled = jax.random.normal(jax.random.key(3), (1, cfg.pooled_dim))
        g = jnp.array([3.5])
        res = OffloadedFlux(model, params, resident_bytes=1 << 40,
                            stream_dtype="float8_e4m3fn")
        strm = OffloadedFlux(model, params, resident_bytes=0,
                             stream_dtype="float8_e4m3fn")
        assert strm.streamed and not strm.stacked
        a = np.asarray(res.forward(x, t, ctx, pooled, g), np.float32)
        b = np.asarray(strm.forward(x, t, ctx, pooled, g), np.float32)
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)

    def test_fp8_trajectory_image_quality_flux(self):
        """END-TO-END fp8 quality pin (r04 VERDICT weak #6: the ~0.1%
        per-matmul bound was never propagated to an image-level metric):
        a full tiny-FLUX sampling trajectory with fp8 weights vs the
        exact trajectory, compared as IMAGES.

        Two ladders isolate the two effects: ``stream_dtype="native"``
        runs the same offload block programs with EXACT weights (the
        restructure itself must be image-identical to numerical noise),
        then fp8 adds only quantization, whose accumulated image error
        is pinned by PSNR."""
        from comfyui_distributed_tpu.diffusion.pipeline_flow import (
            FlowPipeline, FlowSpec)
        from comfyui_distributed_tpu.models.vae import (AutoencoderKL,
                                                        VAEConfig)
        from comfyui_distributed_tpu.parallel import build_mesh

        cfg = DiTConfig.tiny(pos_embed="rope")
        model, params = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                                 context_len=6)
        vae = AutoencoderKL(VAEConfig.tiny(dtype="float32")).init(
            jax.random.key(1), image_hw=(16, 16))
        pipe = FlowPipeline(model, params, vae)
        spec = FlowSpec(height=16, width=16, steps=8)
        ctx = jax.random.normal(jax.random.key(2), (1, 6, cfg.context_dim))
        pooled = jax.random.normal(jax.random.key(3), (1, cfg.pooled_dim))

        exact = np.asarray(pipe.generate(build_mesh({"dp": 1}), spec, 7,
                                         ctx, pooled), np.float32)
        native = np.asarray(pipe.generate_offloaded(
            spec, 7, ctx, pooled, resident_bytes=1 << 40,
            stream_dtype="native"), np.float32)
        fp8 = np.asarray(pipe.generate_offloaded(
            spec, 7, ctx, pooled, resident_bytes=1 << 40,
            stream_dtype="float8_e4m3fn"), np.float32)
        assert exact.shape == native.shape == fp8.shape

        # the block-program restructure alone: image-identical
        np.testing.assert_allclose(native, exact, atol=2e-3)
        # fp8 quantization, accumulated through the whole trajectory +
        # VAE decode, measured at the image level
        mse = float(np.mean((fp8 - exact) ** 2))
        psnr = 10.0 * np.log10(1.0 / max(mse, 1e-12))
        assert psnr > 25.0, f"fp8 trajectory PSNR {psnr:.1f} dB"
        assert float(np.abs(fp8 - exact).max()) < 0.25

    def test_fp8_trajectory_image_quality_wan(self):
        """Same end-to-end pin for the WAN offload path (video frames):
        fp8 expert residency must not visibly corrupt the clip."""
        from comfyui_distributed_tpu.diffusion.pipeline_video import (
            VideoPipeline, VideoSpec)
        from comfyui_distributed_tpu.models.wan import WanConfig, init_wan
        from comfyui_distributed_tpu.models.wan_vae import (WanVAE3D,
                                                            WanVAEConfig)
        from comfyui_distributed_tpu.parallel import build_mesh

        cfg = WanConfig.tiny()
        model, params = init_wan(cfg, jax.random.key(0),
                                 sample_fhw=(3, 8, 8), context_len=6)
        vae = WanVAE3D(WanVAEConfig.tiny()).init(jax.random.key(1),
                                                 frames=5,
                                                 image_hw=(16, 16))
        pipe = VideoPipeline(model, params, vae)
        spec = VideoSpec(frames=5, height=16, width=16, steps=4)
        ctx = jax.random.normal(jax.random.key(2), (1, 6, cfg.text_dim))
        pooled = jnp.zeros((1, 16))

        exact = np.asarray(pipe.generate(build_mesh({"dp": 1}), spec, 9,
                                         ctx, pooled), np.float32)
        fp8 = np.asarray(pipe.generate_offloaded(
            spec, 9, ctx, resident_bytes=1 << 40,
            stream_dtype="float8_e4m3fn"), np.float32)
        assert fp8.shape == exact.shape
        mse = float(np.mean((fp8 - exact) ** 2))
        psnr = 10.0 * np.log10(1.0 / max(mse, 1e-12))
        assert psnr > 25.0, f"fp8 WAN trajectory PSNR {psnr:.1f} dB"

    def test_executor_prefers_flash_attention(self):
        """The offload executor's block programs must request the pallas
        flash kernel regardless of the seq-length gate: with the fp8 set
        resident, XLA attention OOM'd at compile on the chip (r04,
        16.89 GB vs 15.75 HBM)."""
        cfg, model, params, *_ = _stack()
        off = OffloadedFlux(model, params, resident_bytes=1 << 40)
        assert off.cfg.attn_backend == "flash"

    def test_plan_matches_build(self):
        """``plan_offload`` (shapes-only, what bench.py's RAM guard uses)
        must agree with the executor actually built."""
        from comfyui_distributed_tpu.diffusion.offload import plan_offload

        cfg, model, params, *_ = _stack()
        for budget in (0, 1 << 40):
            for sd in ("native", "float8_e4m3fn"):
                plan = plan_offload(params, budget, sd)
                off = OffloadedFlux(model, params, resident_bytes=budget,
                                    stream_dtype=sd)
                assert plan["fully_resident"] == bool(off.stacked)
                assert set(plan["streamed"]) == set(off.streamed)
                assert plan["resident_bytes"] == off.resident_bytes
                if off.streamed:
                    assert plan["streamed_bytes"] == tree_bytes(
                        off.streamed)

    def test_env_knob_and_bad_value(self, monkeypatch):
        from comfyui_distributed_tpu.diffusion.offload import \
            stream_dtype_default

        monkeypatch.delenv("CDT_OFFLOAD_STREAM_DTYPE", raising=False)
        assert stream_dtype_default() == "float8_e4m3fn"
        monkeypatch.setenv("CDT_OFFLOAD_STREAM_DTYPE", "native")
        assert stream_dtype_default() == "native"
        cfg, model, params, *_ = _stack()
        with pytest.raises(ValueError, match="STREAM_DTYPE"):
            OffloadedFlux(model, params, resident_bytes=0,
                          stream_dtype="int4")


class TestQuantCache:
    """r04: CDT_OFFLOAD_CACHE_DIR persists quantized flat blocks —
    quantizing 12B params costs ~5 single-core minutes per process
    start; a warm cache cuts the build to a disk read."""

    def _params(self):
        cfg = DiTConfig.tiny(pos_embed="rope")
        from comfyui_distributed_tpu.diffusion.offload import \
            materialize_host_params
        from comfyui_distributed_tpu.models.dit import DiT
        _, abstract = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                               context_len=6, abstract=True)
        return DiT(cfg), materialize_host_params(abstract, seed=7)

    def _inputs(self, cfg):
        return (jax.random.normal(jax.random.key(1),
                                  (1, 8, 8, cfg.in_channels)),
                jnp.array([0.5]),
                jax.random.normal(jax.random.key(2),
                                  (1, 6, cfg.context_dim)),
                jax.random.normal(jax.random.key(3), (1, cfg.pooled_dim)),
                jnp.array([3.5]))

    def test_cold_build_writes_warm_build_loads(self, tmp_path,
                                                monkeypatch):
        import comfyui_distributed_tpu.diffusion.offload as off_mod

        monkeypatch.setenv("CDT_OFFLOAD_CACHE_DIR", str(tmp_path))
        model, params = self._params()
        off_cold = OffloadedFlux(model, params, resident_bytes=1 << 40,
                                 stream_dtype="float8_e4m3fn")
        # files live in a fingerprint-named subdir: concurrent builds of
        # DIFFERENT checkpoints in one shared dir can't cross-validate
        assert list(tmp_path.glob("*/manifest.json"))
        assert list(tmp_path.glob("*/double_0.*.npy"))

        calls = []
        real = off_mod._flatten_block
        monkeypatch.setattr(off_mod, "_flatten_block",
                            lambda *a, **k: calls.append(1) or real(*a, **k))
        off_warm = OffloadedFlux(model, params, resident_bytes=1 << 40,
                                 stream_dtype="float8_e4m3fn")
        assert not calls, "warm build must not re-quantize"
        x, t, ctx, pooled, g = self._inputs(model.config)
        np.testing.assert_array_equal(
            np.asarray(off_cold.forward(x, t, ctx, pooled, g)),
            np.asarray(off_warm.forward(x, t, ctx, pooled, g)))

    def test_stale_fingerprint_requantizes(self, tmp_path, monkeypatch):
        monkeypatch.setenv("CDT_OFFLOAD_CACHE_DIR", str(tmp_path))
        model, params = self._params()
        OffloadedFlux(model, params, resident_bytes=1 << 40,
                      stream_dtype="float8_e4m3fn")
        # different weights, same shapes → fingerprint must differ and
        # the stale cache must be ignored (correct output, no crash)
        _, params2 = self._params()
        p2 = jax.tree_util.tree_map(lambda a: a * 1.5
                                    if a.ndim >= 2 else a, params2)
        off2 = OffloadedFlux(model, p2, resident_bytes=1 << 40,
                             stream_dtype="float8_e4m3fn")
        x, t, ctx, pooled, g = self._inputs(model.config)
        want = np.asarray(model.apply(p2, x, t, ctx, pooled, g), np.float32)
        got = np.asarray(off2.forward(x, t, ctx, pooled, g), np.float32)
        rel = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-9)
        assert rel < 0.05, rel

    def test_corrupt_entry_falls_back(self, tmp_path, monkeypatch):
        monkeypatch.setenv("CDT_OFFLOAD_CACHE_DIR", str(tmp_path))
        model, params = self._params()
        off1 = OffloadedFlux(model, params, resident_bytes=1 << 40,
                             stream_dtype="float8_e4m3fn")
        for p in tmp_path.glob("*/single_1.*.npy"):
            p.write_bytes(b"garbage")
        off2 = OffloadedFlux(model, params, resident_bytes=1 << 40,
                             stream_dtype="float8_e4m3fn")
        x, t, ctx, pooled, g = self._inputs(model.config)
        np.testing.assert_array_equal(
            np.asarray(off1.forward(x, t, ctx, pooled, g)),
            np.asarray(off2.forward(x, t, ctx, pooled, g)))

    def test_garbled_manifest_shapes_never_fatal(self, tmp_path,
                                                 monkeypatch):
        """Valid-JSON-wrong-shape manifests (a list; metas rows that
        aren't 5-tuples) must degrade to re-quantizing, not crash the
        build (the 'never fatal' contract)."""
        monkeypatch.setenv("CDT_OFFLOAD_CACHE_DIR", str(tmp_path))
        model, params = self._params()
        off1 = OffloadedFlux(model, params, resident_bytes=1 << 40,
                             stream_dtype="float8_e4m3fn")
        (manifest,) = tmp_path.glob("*/manifest.json")
        fp = manifest.parent.name
        for garbage in ("[1, 2]",
                        '{"fingerprint": "%s", "metas": {"double": [1]}}'
                        % fp):
            manifest.write_text(garbage)
            off2 = OffloadedFlux(model, params, resident_bytes=1 << 40,
                                 stream_dtype="float8_e4m3fn")
            x, t, ctx, pooled, g = self._inputs(model.config)
            np.testing.assert_array_equal(
                np.asarray(off1.forward(x, t, ctx, pooled, g)),
                np.asarray(off2.forward(x, t, ctx, pooled, g)))

    def test_unwritable_cache_dir_never_fatal(self, tmp_path,
                                              monkeypatch):
        ro = tmp_path / "ro"
        ro.mkdir()
        ro.chmod(0o500)                      # no write permission
        monkeypatch.setenv("CDT_OFFLOAD_CACHE_DIR", str(ro / "cache"))
        model, params = self._params()
        try:
            off = OffloadedFlux(model, params, resident_bytes=1 << 40,
                                stream_dtype="float8_e4m3fn")
            assert off.stacked                # built fine, just uncached
        finally:
            ro.chmod(0o700)

    def test_no_cache_without_env(self, tmp_path, monkeypatch):
        monkeypatch.delenv("CDT_OFFLOAD_CACHE_DIR", raising=False)
        model, params = self._params()
        OffloadedFlux(model, params, resident_bytes=1 << 40,
                      stream_dtype="float8_e4m3fn")
        assert not list(tmp_path.iterdir())


class TestOffloadedWan:
    """r04: the WAN-side executor over the shared block-store substrate
    — how 14B video experts (28 GB bf16) run on one 16 GB chip."""

    def _stack(self):
        from comfyui_distributed_tpu.models.wan import (WanConfig,
                                                        WanModel, init_wan)

        cfg = WanConfig.tiny()
        model, params = init_wan(cfg, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (1, 4, 8, 8,
                                                  cfg.in_channels))
        t = jnp.array([0.6])
        ctx = jax.random.normal(jax.random.key(2), (1, 5, cfg.text_dim))
        return cfg, model, params, x, t, ctx

    @pytest.mark.parametrize("resident_bytes", [0, 1 << 40])
    def test_matches_monolithic_apply(self, resident_bytes):
        from comfyui_distributed_tpu.diffusion.offload import OffloadedWan

        cfg, model, params, x, t, ctx = self._stack()
        want = np.asarray(model.apply(params, x, t, ctx))
        off = OffloadedWan(model, params, resident_bytes=resident_bytes,
                           stream_dtype="native")
        if resident_bytes:
            assert off.stacked and not off.streamed
        else:
            assert off.streamed and not off.stacked
        got = np.asarray(off.forward(x, t, ctx))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_fp8_close_and_scan_equals_loop(self):
        from comfyui_distributed_tpu.diffusion.offload import (
            OffloadedWan, materialize_host_params)
        from comfyui_distributed_tpu.models.wan import (WanConfig,
                                                        WanModel, init_wan)

        cfg = WanConfig.tiny()
        model, _ = init_wan(cfg, jax.random.key(0))
        abstract = jax.eval_shape(
            lambda: init_wan(cfg, jax.random.key(0))[1])
        params = materialize_host_params(abstract, seed=9)
        x = jax.random.normal(jax.random.key(1), (1, 4, 8, 8,
                                                  cfg.in_channels))
        t = jnp.array([0.6])
        ctx = jax.random.normal(jax.random.key(2), (1, 5, cfg.text_dim))
        want = np.asarray(model.apply(params, x, t, ctx), np.float32)
        res = OffloadedWan(model, params, resident_bytes=1 << 40,
                           stream_dtype="float8_e4m3fn")
        strm = OffloadedWan(model, params, resident_bytes=0,
                            stream_dtype="float8_e4m3fn")
        a = np.asarray(res.forward(x, t, ctx), np.float32)
        b = np.asarray(strm.forward(x, t, ctx), np.float32)
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
        rel = np.linalg.norm(a - want) / max(np.linalg.norm(want), 1e-9)
        assert rel < 0.05, rel

    def test_cfg_denoiser_matches_batched_formula(self):
        from comfyui_distributed_tpu.diffusion.offload import OffloadedWan

        cfg, model, params, x, t, ctx = self._stack()
        off = OffloadedWan(model, params, resident_bytes=1 << 40,
                           stream_dtype="native")
        g = 4.5
        den = off.denoiser(ctx, guidance_scale=g)
        got = np.asarray(den(x, jnp.float32(0.6)))
        # the batched-concat formula of VideoPipeline._denoiser
        x2 = jnp.concatenate([x, x], axis=0)
        ctx2 = jnp.concatenate([ctx, jnp.zeros_like(ctx)], axis=0)
        t2 = jnp.full((2,), 0.6)
        v2 = model.apply(params, x2, t2, ctx2)
        out2 = x2 - 0.6 * v2
        cond, uncond = np.split(np.asarray(out2), 2, axis=0)
        want = uncond + g * (cond - uncond)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_release_frees_device_buffers(self):
        from comfyui_distributed_tpu.diffusion.offload import OffloadedWan

        cfg, model, params, x, t, ctx = self._stack()
        off = OffloadedWan(model, params, resident_bytes=1 << 40,
                           stream_dtype="native")
        assert off.stacked
        off.release()
        assert not off.stacked and not off.resident


class TestFullScalePlans:
    """Abstract-tree placement plans at the REAL published sizes — no
    materialization (`jax.eval_shape`), so these run in seconds and pin
    the single-chip claims numerically."""

    def test_flux_12b_fp8_fully_resident_at_default_budget(self):
        from comfyui_distributed_tpu.diffusion.offload import plan_offload

        cfg = DiTConfig.flux()
        _, abstract = init_dit(cfg, jax.random.key(0),
                               sample_hw=(128, 128), context_len=512,
                               abstract=True, param_dtype=jnp.bfloat16)
        plan = plan_offload(abstract, int(13 * (1 << 30)),
                            "float8_e4m3fn")
        assert plan["fully_resident"], plan["streamed"]
        assert 11e9 < plan["resident_bytes"] < 13 * (1 << 30)

    def test_wan_14b_fp8_mostly_resident_on_one_chip(self):
        """A 14B WAN expert is 28 GB bf16 (~2x one chip's HBM); fp8 it
        is ~14 GB — a 13.5 GB budget holds ≥90% resident with <2.5 GB
        streaming per step. This is the numeric basis of the 'WAN-14B
        on ONE chip' capability (OffloadedWan)."""
        from comfyui_distributed_tpu.diffusion.offload import (
            _WAN_GLUE_KEYS, plan_offload, tree_bytes)
        from comfyui_distributed_tpu.models.wan import WanConfig, init_wan

        cfg = WanConfig.wan_14b()
        _, abstract = init_wan(cfg, jax.random.key(0),
                               sample_fhw=(9, 60, 104), context_len=512,
                               abstract=True, param_dtype=jnp.bfloat16)
        total = tree_bytes(abstract["params"]
                           if "params" in abstract else abstract)
        assert total > 26e9                      # really 14B-scale bf16
        plan = plan_offload(abstract, int(13.5 * (1 << 30)),
                            "float8_e4m3fn", block_prefixes=("block",),
                            glue_keys=_WAN_GLUE_KEYS)
        assert len(plan["order"]) == cfg.num_layers
        frac = plan["resident_bytes"] / (plan["resident_bytes"]
                                         + plan["streamed_bytes"])
        assert frac > 0.90, frac
        assert plan["streamed_bytes"] < 2.5e9, plan["streamed_bytes"]


class TestGenerateOffloadedVideo:
    """r04: VideoPipeline.generate_offloaded — WAN-14B-class video on
    one chip, including the dual-expert HBM swap."""

    def _pipes(self):
        from comfyui_distributed_tpu.models.wan import WanConfig, init_wan
        from comfyui_distributed_tpu.models.vae import (AutoencoderKL,
                                                        VAEConfig)

        cfg = WanConfig.tiny()
        model, hi = init_wan(cfg, jax.random.key(0), sample_fhw=(5, 8, 8),
                             context_len=6)
        _, lo = init_wan(cfg, jax.random.key(99), sample_fhw=(5, 8, 8),
                         context_len=6)
        vae = AutoencoderKL(VAEConfig.tiny(dtype="float32")).init(
            jax.random.key(1), image_hw=(16, 16))
        ctx = jnp.ones((1, 6, cfg.text_dim)) * 0.1
        pooled = jnp.ones((1, 16)) * 0.2
        return model, hi, lo, vae, ctx, pooled

    def test_single_expert_equals_dp_on_one_device(self):
        from comfyui_distributed_tpu.diffusion.pipeline_video import (
            VideoPipeline, VideoSpec)
        from comfyui_distributed_tpu.parallel import build_mesh

        model, hi, lo, vae, ctx, pooled = self._pipes()
        pipe = VideoPipeline(model, hi, vae)
        spec = VideoSpec(frames=5, height=16, width=16, steps=3,
                         shift=1.0)
        want = np.asarray(pipe.generate(build_mesh({"dp": 1}), spec, 4,
                                        ctx, pooled))
        got = np.asarray(pipe.generate_offloaded(
            spec, 4, ctx, stream_dtype="native"))
        np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)

    def test_moe_swap_equals_dp_and_evicts_high(self):
        from comfyui_distributed_tpu.diffusion.pipeline_video import (
            VideoPipeline, VideoSpec)
        from comfyui_distributed_tpu.parallel import build_mesh

        model, hi, lo, vae, ctx, pooled = self._pipes()
        pipe = VideoPipeline(model, hi, vae, dit_params_low=lo,
                             expert_boundary=0.875)
        spec = VideoSpec(frames=5, height=16, width=16, steps=8,
                         shift=1.0)
        from comfyui_distributed_tpu.diffusion.schedules import sigmas_flow
        split = pipe._expert_split(sigmas_flow(8, 1.0))
        assert 0 < split < 8          # the swap path actually runs
        want = np.asarray(pipe.generate(build_mesh({"dp": 1}), spec, 7,
                                        ctx, pooled))
        got = np.asarray(pipe.generate_offloaded(
            spec, 7, ctx, stream_dtype="native"))
        np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)
        # high expert released + evicted; low stays cached for the next
        # video
        kinds = {k[1] for k in pipe._fn_cache if k[0] == "offload"}
        assert kinds == {"low"}

    @pytest.mark.parametrize("resident_bytes", [0, None])
    def test_i2v_offloaded_equals_dp_on_one_device(self, resident_bytes):
        """0 → streamed python ladder (inp_fn path); None (default
        budget, tiny model fully resident) → the one-jit resident ladder
        with traced y/mask. Both must match dp."""
        from comfyui_distributed_tpu.diffusion.pipeline_video import \
            VideoSpec
        from comfyui_distributed_tpu.models.registry import ModelRegistry
        from comfyui_distributed_tpu.parallel import build_mesh

        bundle = ModelRegistry().get("wan-i2v-tiny")
        pipe = bundle.pipeline
        spec = VideoSpec(frames=5, height=16, width=16, steps=2,
                         shift=1.0)
        ctx, pooled = bundle.text_encoder.encode(["animate"])
        img = jnp.ones((1, 16, 16, 3)) * 0.3
        want = np.asarray(pipe.generate_i2v(build_mesh({"dp": 1}), spec,
                                            6, img, ctx, pooled))
        got = np.asarray(pipe.generate_offloaded_i2v(
            spec, 6, img, ctx, stream_dtype="native",
            resident_bytes=resident_bytes))
        np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)

    @pytest.mark.parametrize("resident_bytes", [0, None])
    def test_cfg_offloaded_equals_dp(self, resident_bytes):
        """guidance_scale > 1 exercises the CFG branch of BOTH offload
        ladders (in-trace cond/uncond for resident, sequential python
        for streamed) against the dp batched-CFG path."""
        from comfyui_distributed_tpu.diffusion.pipeline_video import (
            VideoPipeline, VideoSpec)
        from comfyui_distributed_tpu.parallel import build_mesh

        model, hi, lo, vae, ctx, pooled = self._pipes()
        pipe = VideoPipeline(model, hi, vae)
        spec = VideoSpec(frames=5, height=16, width=16, steps=2,
                         shift=1.0, guidance_scale=4.0)
        want = np.asarray(pipe.generate(build_mesh({"dp": 1}), spec, 9,
                                        ctx, pooled))
        got = np.asarray(pipe.generate_offloaded(
            spec, 9, ctx, stream_dtype="native",
            resident_bytes=resident_bytes))
        np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)

    def test_non_euler_and_batch_guards(self):
        from comfyui_distributed_tpu.diffusion.pipeline_video import (
            VideoPipeline, VideoSpec)

        model, hi, lo, vae, ctx, pooled = self._pipes()
        pipe = VideoPipeline(model, hi, vae)
        # streamed (per-step) ladder: euler-only
        with pytest.raises(ValueError, match="euler only"):
            pipe.generate_offloaded(
                VideoSpec(frames=5, height=16, width=16,
                          sampler="dpmpp_2m"), 0, ctx, resident_bytes=0)
        with pytest.raises(ValueError, match="batch 1"):
            pipe.generate_offloaded(
                VideoSpec(frames=5, height=16, width=16), 0,
                jnp.zeros((2, 6, model.config.text_dim)))

    def test_resident_video_sampler_equals_dp(self):
        """A non-euler sampler through the resident video ladder matches
        dp — the capability the euler-only python loop lacks."""
        from comfyui_distributed_tpu.diffusion.pipeline_video import (
            VideoPipeline, VideoSpec)
        from comfyui_distributed_tpu.parallel import build_mesh

        model, hi, lo, vae, ctx, pooled = self._pipes()
        pipe = VideoPipeline(model, hi, vae)
        spec = VideoSpec(frames=5, height=16, width=16, steps=3,
                         shift=1.0, sampler="dpmpp_2m")
        want = np.asarray(pipe.generate(build_mesh({"dp": 1}), spec, 13,
                                        ctx, pooled))
        got = np.asarray(pipe.generate_offloaded(
            spec, 13, ctx, stream_dtype="native"))
        np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


class TestInterruptAndLadderMode:
    """r04: offloaded sampling honors /distributed/interrupt between
    steps (CDT_OFFLOAD_LADDER=step keeps fully-resident runs on the
    interruptible per-step loop; 'jit' — the default — trades that for
    a single compiled ladder)."""

    def test_should_stop_raises_between_steps(self):
        from comfyui_distributed_tpu.diffusion import sigmas_flow

        calls = []

        def den(x, s):
            calls.append(1)
            return x * 0.5

        x = jnp.ones((1, 4, 4, 2))
        with pytest.raises(InterruptedError, match="interrupted at step"):
            sample_euler_py(den, x, sigmas_flow(6, 1.0),
                            should_stop=lambda: len(calls) >= 2)
        assert len(calls) == 2          # stopped before the third step

    def test_ladder_mode_env(self, monkeypatch):
        from comfyui_distributed_tpu.diffusion.offload import ladder_mode

        monkeypatch.delenv("CDT_OFFLOAD_LADDER", raising=False)
        assert ladder_mode() == "jit"
        monkeypatch.setenv("CDT_OFFLOAD_LADDER", "step")
        assert ladder_mode() == "step"
        monkeypatch.setenv("CDT_OFFLOAD_LADDER", "bogus")
        with pytest.raises(ValueError, match="LADDER"):
            ladder_mode()

    def test_step_mode_resident_still_equals_dp(self, monkeypatch):
        """CDT_OFFLOAD_LADDER=step on a fully-resident executor runs the
        python loop over the fused forward — same numbers as dp."""
        from comfyui_distributed_tpu.diffusion.pipeline_flow import (
            FlowPipeline, FlowSpec)
        from comfyui_distributed_tpu.models.vae import (AutoencoderKL,
                                                        VAEConfig)
        from comfyui_distributed_tpu.parallel import build_mesh

        monkeypatch.setenv("CDT_OFFLOAD_LADDER", "step")
        cfg = DiTConfig.tiny(pos_embed="rope")
        model, params = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                                 context_len=6)
        vae = AutoencoderKL(VAEConfig.tiny(dtype="float32")).init(
            jax.random.key(1), image_hw=(16, 16))
        pipe = FlowPipeline(model, params, vae)
        ctx = jnp.ones((1, 6, cfg.context_dim)) * 0.1
        pooled = jnp.ones((1, cfg.pooled_dim)) * 0.2
        spec = FlowSpec(height=16, width=16, steps=3)
        want = np.asarray(pipe.generate(build_mesh({"dp": 1}), spec, 5,
                                        ctx, pooled))
        got = np.asarray(pipe.generate_offloaded(
            spec, 5, ctx, pooled, resident_bytes=1 << 40,
            stream_dtype="native"))
        np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)

    def test_node_interrupt_mid_offload(self, tmp_config, monkeypatch):
        """A set interrupt_event + step-mode ladder aborts the offloaded
        node with InterruptedError (the executor surfaces it like its
        own between-node check)."""
        import threading

        from comfyui_distributed_tpu.graph.node import get_node
        from comfyui_distributed_tpu.models.registry import (PRESETS,
                                                             ModelBundle)

        monkeypatch.setenv("CDT_OFFLOAD_LADDER", "step")
        monkeypatch.delenv("CDT_OFFLOAD", raising=False)
        ev = threading.Event()
        ev.set()
        bundle = ModelBundle(PRESETS["flux-tiny"])
        ctx, pooled = bundle.text_encoder.encode(["stop me"])
        with pytest.raises(InterruptedError):
            get_node("TPUFlowTxt2Img")().execute(
                bundle, {"context": ctx, "pooled": pooled},
                seed=1, steps=3, width=16, height=16, mode="offload",
                interrupt_event=ev)


class TestEulerLadder:
    def test_matches_scan_sampler(self):
        from comfyui_distributed_tpu.diffusion import sample, sigmas_flow

        sigmas = sigmas_flow(6, shift=1.0)
        x = jax.random.normal(jax.random.key(0), (1, 4, 4, 2))
        den = lambda xx, s: xx * 0.6
        want = np.asarray(sample("euler", den, x, sigmas))
        got = np.asarray(sample_euler_py(den, x, sigmas))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


class TestGenerateOffloaded:
    @pytest.mark.parametrize("resident_bytes", [0, 1 << 40])
    def test_equals_dp_generate_on_one_device(self, resident_bytes):
        """resident_bytes=0 → streamed python ladder; huge → the
        fully-resident ONE-JIT ladder (sample_euler_resident). Both must
        equal the dp path on one device."""
        from comfyui_distributed_tpu.diffusion.pipeline_flow import (
            FlowPipeline, FlowSpec)
        from comfyui_distributed_tpu.models.vae import (AutoencoderKL,
                                                        VAEConfig)
        from comfyui_distributed_tpu.parallel import build_mesh

        cfg = DiTConfig.tiny(pos_embed="rope")
        model, params = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                                 context_len=6)
        vae = AutoencoderKL(VAEConfig.tiny(dtype="float32")).init(
            jax.random.key(1), image_hw=(16, 16))
        pipe = FlowPipeline(model, params, vae)
        ctx = jnp.ones((1, 6, cfg.context_dim)) * 0.1
        pooled = jnp.ones((1, cfg.pooled_dim)) * 0.2
        spec = FlowSpec(height=16, width=16, steps=3)
        want = np.asarray(pipe.generate(build_mesh({"dp": 1}), spec, 5,
                                        ctx, pooled))
        off = pipe.offload_executor(resident_bytes=resident_bytes,
                                    stream_dtype="native")
        assert bool(off.stacked) == bool(resident_bytes)
        got = np.asarray(pipe.generate_offloaded(
            spec, 5, ctx, pooled, resident_bytes=resident_bytes,
            stream_dtype="native"))
        np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)

    def test_non_euler_streamed_raises_resident_works(self):
        """The per-step python ladder is euler-only; the fully-resident
        in-trace ladder runs EVERY registered sampler."""
        from comfyui_distributed_tpu.diffusion.pipeline_flow import (
            FlowPipeline, FlowSpec)
        from comfyui_distributed_tpu.models.vae import (AutoencoderKL,
                                                        VAEConfig)

        cfg = DiTConfig.tiny()
        model, params = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                                 context_len=6)
        vae = AutoencoderKL(VAEConfig.tiny()).init(jax.random.key(1),
                                                   image_hw=(16, 16))
        pipe = FlowPipeline(model, params, vae)
        ctx = jnp.zeros((1, 6, cfg.context_dim))
        pooled = jnp.zeros((1, cfg.pooled_dim))
        spec = FlowSpec(height=16, width=16, steps=2, sampler="heun")
        with pytest.raises(ValueError, match="euler only"):
            pipe.generate_offloaded(spec, 0, ctx, pooled,
                                    resident_bytes=0)
        out = pipe.generate_offloaded(spec, 0, ctx, pooled,
                                      resident_bytes=1 << 40)
        assert np.asarray(out).shape == (1, 16, 16, 3)

    @pytest.mark.parametrize("sampler", ["dpmpp_2m", "euler_ancestral"])
    def test_resident_ladder_samplers_equal_dp(self, sampler):
        """Non-euler samplers through the resident jit ladder must match
        the dp path — including ancestral ones (the ladder threads the
        SAME fold_in(key, 0) the dp shard-0 uses for its noise draws)."""
        from comfyui_distributed_tpu.diffusion.pipeline_flow import (
            FlowPipeline, FlowSpec)
        from comfyui_distributed_tpu.models.vae import (AutoencoderKL,
                                                        VAEConfig)
        from comfyui_distributed_tpu.parallel import build_mesh

        cfg = DiTConfig.tiny(pos_embed="rope")
        model, params = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                                 context_len=6)
        vae = AutoencoderKL(VAEConfig.tiny(dtype="float32")).init(
            jax.random.key(1), image_hw=(16, 16))
        pipe = FlowPipeline(model, params, vae)
        ctx = jnp.ones((1, 6, cfg.context_dim)) * 0.1
        pooled = jnp.ones((1, cfg.pooled_dim)) * 0.2
        spec = FlowSpec(height=16, width=16, steps=3, sampler=sampler)
        want = np.asarray(pipe.generate(build_mesh({"dp": 1}), spec, 11,
                                        ctx, pooled))
        got = np.asarray(pipe.generate_offloaded(
            spec, 11, ctx, pooled, resident_bytes=1 << 40,
            stream_dtype="native"))
        np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


class TestPlumbing:
    def test_materialize_host_params_shapes(self):
        cfg = DiTConfig.tiny()
        _, abstract = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                               context_len=6, abstract=True)
        host = materialize_host_params(abstract, seed=1)
        a_leaves = jax.tree_util.tree_leaves(abstract)
        h_leaves = jax.tree_util.tree_leaves(host)
        assert all(h.shape == a.shape and h.dtype == a.dtype
                   for h, a in zip(h_leaves, a_leaves))
        assert all(isinstance(h, np.ndarray) for h in h_leaves)

    def test_knobs(self, monkeypatch):
        monkeypatch.delenv("CDT_OFFLOAD", raising=False)
        assert not offload_enabled()
        monkeypatch.setenv("CDT_OFFLOAD", "1")
        assert offload_enabled()
        monkeypatch.setenv("CDT_OFFLOAD_RESIDENT_GB", "2.5")
        assert resident_budget_bytes() == int(2.5 * (1 << 30))


class TestNodeAndCaching:
    def test_executor_cached_across_calls(self):
        """generate_offloaded must reuse the streamed executor (resident
        upload + 4 compiled programs) — rebuilding per image costs
        minutes at FLUX scale."""
        from comfyui_distributed_tpu.diffusion.pipeline_flow import (
            FlowPipeline, FlowSpec)
        from comfyui_distributed_tpu.models.vae import (AutoencoderKL,
                                                        VAEConfig)

        cfg = DiTConfig.tiny(pos_embed="rope")
        model, params = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                                 context_len=6)
        vae = AutoencoderKL(VAEConfig.tiny()).init(jax.random.key(1),
                                                   image_hw=(16, 16))
        pipe = FlowPipeline(model, params, vae)
        ctx = jnp.zeros((1, 6, cfg.context_dim))
        pooled = jnp.zeros((1, cfg.pooled_dim))
        spec = FlowSpec(height=16, width=16, steps=2)
        pipe.generate_offloaded(spec, 0, ctx, pooled, resident_bytes=0)
        first = pipe.offload_executor(resident_bytes=0)
        assert len(pipe._fn_cache) == 1
        pipe.generate_offloaded(spec, 1, ctx, pooled, resident_bytes=0)
        assert pipe.offload_executor(resident_bytes=0) is first
        assert len(pipe._fn_cache) == 1

    def test_batch_gt_one_raises(self):
        from comfyui_distributed_tpu.diffusion.pipeline_flow import (
            FlowPipeline, FlowSpec)
        from comfyui_distributed_tpu.models.vae import (AutoencoderKL,
                                                        VAEConfig)

        cfg = DiTConfig.tiny()
        model, params = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                                 context_len=6)
        vae = AutoencoderKL(VAEConfig.tiny()).init(jax.random.key(1),
                                                   image_hw=(16, 16))
        pipe = FlowPipeline(model, params, vae)
        with pytest.raises(ValueError, match="batch 1"):
            pipe.generate_offloaded(
                FlowSpec(height=16, width=16, per_device_batch=2), 0,
                jnp.zeros((1, 6, cfg.context_dim)),
                jnp.zeros((1, cfg.pooled_dim)))

    def test_offload_mode_reports_progress(self, tmp_config, monkeypatch):
        """The offloaded python ladder must feed the SAME per-step
        progress machinery the compiled samplers drive (VERDICT-style
        parity: t2v/flux offload jobs are the longest-running work —
        0/N-until-done progress is a regression)."""
        from comfyui_distributed_tpu.cluster.progress import \
            ProgressTracker
        from comfyui_distributed_tpu.graph.node import get_node
        from comfyui_distributed_tpu.models.registry import (PRESETS,
                                                             ModelBundle)

        monkeypatch.delenv("CDT_OFFLOAD", raising=False)
        tracker = ProgressTracker()
        bundle = ModelBundle(PRESETS["flux-tiny"])
        ctx, pooled = bundle.text_encoder.encode(["progress"])
        (img,) = get_node("TPUFlowTxt2Img")().execute(
            bundle, {"context": ctx, "pooled": pooled},
            seed=1, steps=3, width=16, height=16, mode="offload",
            prompt_id="pp1", progress_tracker=tracker)
        snap = tracker.snapshot("pp1")
        assert snap is not None and snap["done"] and not snap["failed"]
        assert snap["step"] == 3
        assert tracker.preview_png("pp1") is not None

    def test_video_node_offload_mode(self, tmp_config, monkeypatch):
        """mode='offload' routes TPUTxt2Video through OffloadedWan."""
        from comfyui_distributed_tpu.graph.node import get_node
        from comfyui_distributed_tpu.models.registry import ModelRegistry

        monkeypatch.delenv("CDT_OFFLOAD", raising=False)
        bundle = ModelRegistry().get("wan-tiny-3d")
        ctx, pooled = bundle.text_encoder.encode(["offload clip"])
        (images,) = get_node("TPUTxt2Video")().execute(
            bundle, {"context": ctx, "pooled": pooled},
            seed=3, frames=5, steps=1, width=16, height=16,
            mode="offload")
        assert np.asarray(images).shape == (5, 16, 16, 3)

    def test_node_offload_mode(self, tmp_config, monkeypatch):
        """mode='offload' (or CDT_OFFLOAD=1 with dp) routes the flow node
        through the streamed executor."""
        from comfyui_distributed_tpu.graph.node import get_node
        from comfyui_distributed_tpu.models.registry import (PRESETS,
                                                             ModelBundle)

        monkeypatch.delenv("CDT_OFFLOAD", raising=False)
        bundle = ModelBundle(PRESETS["flux-tiny"])
        node = get_node("TPUFlowTxt2Img")()
        ctx, pooled = bundle.text_encoder.encode(["offload"])
        (img,) = node.execute(bundle, {"context": ctx, "pooled": pooled},
                              seed=1, steps=2, width=16, height=16,
                              mode="offload")
        assert np.asarray(img).shape == (1, 16, 16, 3)
