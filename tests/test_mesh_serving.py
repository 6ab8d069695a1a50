"""Executed mesh serving tier (ISSUE 13, docs/parallelism.md).

Tier-1 evidence that the multi-chip strategies EXECUTE on the virtual
8-device mesh — not merely validate:

- overlap-scheduled collectives (``parallel/overlap.py``): the per-block
  ppermute ring decompositions of reduce-scatter / all-gather /
  all-reduce match their fused counterparts, deterministically; the
  opt-in int8 wire tier stays inside its documented error bound and the
  default stays bit-exact;
- sp and dp×tp execute against a single-device reference of the same
  seed fold-in (f32 stacks, the repo's 2e-4 sharding tolerance; the
  txt2img dp fan-out and kill-switch paths are asserted bit-identical);
- the kernel selection resolves PER-SHARD geometries under
  ``tp_shard_scope``;
- the chaos-marked mesh-drain event: a worker drains mid mesh-tier
  batched job with bit-identical completion, zero dead-letters, and no
  breaker opening.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from comfyui_distributed_tpu.parallel import build_mesh
from comfyui_distributed_tpu.parallel import overlap
from jax import shard_map

MESH8 = {"x": 8}


def _smap(body, mesh, in_specs, out_specs):
    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# overlap-scheduled collectives
# ---------------------------------------------------------------------------


class TestOverlapCollectives:
    def _mesh(self):
        return build_mesh(MESH8)

    def test_reduce_scatter_matches_psum_scatter(self):
        mesh = self._mesh()
        x = jax.random.normal(jax.random.key(0), (8, 16, 24))

        got = _smap(lambda a: overlap.reduce_scatter_ring(a, "x", dim=0),
                    mesh, (P(None, None, None),), P("x", None, None))(x)
        want = _smap(lambda a: jax.lax.psum(a, "x"),
                     mesh, (P(None, None, None),),
                     P(None, None, None))(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

    def test_all_gather_ring_is_bit_exact(self):
        mesh = self._mesh()
        x = jax.random.normal(jax.random.key(1), (8, 4, 6))
        got = _smap(lambda a: overlap.all_gather_ring(a, "x", dim=0),
                    mesh, (P("x", None, None),), P(None, None, None))(x)
        # gathering moves bytes, never recomputes them — exact
        np.testing.assert_array_equal(np.asarray(got), np.asarray(x))

    def test_all_reduce_deterministic_and_close_to_psum(self):
        mesh = self._mesh()
        x = jax.random.normal(jax.random.key(2), (8, 8, 8))
        f = _smap(lambda a: overlap.all_reduce(a, "x"),
                  mesh, (P(None, None, None),), P(None, None, None))
        a, b = np.asarray(jax.jit(f)(x)), np.asarray(jax.jit(f)(x))
        # fixed ring order ⇒ run-to-run deterministic (bitwise)
        np.testing.assert_array_equal(a, b)
        want = _smap(lambda a: jax.lax.psum(a, "x"),
                     mesh, (P(None, None, None),),
                     P(None, None, None))(x)
        np.testing.assert_allclose(a, np.asarray(want), rtol=1e-5,
                                   atol=1e-5)

    def test_all_reduce_falls_back_without_divisible_dim(self):
        mesh = self._mesh()
        x = jax.random.normal(jax.random.key(3), (3, 5))  # nothing /8
        got = _smap(lambda a: overlap.all_reduce(a, "x"),
                    mesh, (P(None, None),), P(None, None))(x)
        np.testing.assert_allclose(np.asarray(got), 8 * np.asarray(x),
                                   rtol=1e-5)

    def test_quantized_all_reduce_within_documented_bound(self):
        mesh = self._mesh()
        x = jax.random.normal(jax.random.key(4), (8, 16))
        got = _smap(lambda a: overlap.all_reduce(a, "x", quant="int8"),
                    mesh, (P(None, None),), P(None, None))(x)
        want = 8 * np.asarray(x)
        err = np.abs(np.asarray(got) - want).max()
        # RS compounds ≤ n-1 rounds on partials + 1 gather round
        bound = overlap.quant_error_bound(float(np.abs(want).max()),
                                          hops=8)
        assert 0 < err < bound, (err, bound)

    def test_quant_default_off_is_bit_exact(self, monkeypatch):
        monkeypatch.delenv("CDT_COLLECTIVE_QUANT", raising=False)
        assert overlap.collective_quant_mode() == "none"
        mesh = self._mesh()
        x = jax.random.normal(jax.random.key(5), (8, 8))
        f = _smap(lambda a: overlap.all_reduce(a, "x"),
                  mesh, (P(None, None),), P(None, None))
        g = _smap(lambda a: overlap.all_reduce(a, "x", quant=None),
                  mesh, (P(None, None),), P(None, None))
        np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(g(x)))

    def test_wire_roundtrip_bound(self):
        x = jax.random.normal(jax.random.key(6), (64,)) * 5.0
        q, s = overlap.wire_quantize(x)
        back = overlap.wire_dequantize(q, s)
        absmax = float(jnp.max(jnp.abs(x)))
        assert float(jnp.max(jnp.abs(back - x))) <= \
            overlap.quant_error_bound(absmax) + 1e-7
        # all-zero payload is exact
        qz, sz = overlap.wire_quantize(jnp.zeros((4,)))
        np.testing.assert_array_equal(
            np.asarray(overlap.wire_dequantize(qz, sz)), np.zeros((4,)))


class TestQuantizedRingAttention:
    def _qkv(self, B=1, N=64, H=2, D=16):
        ks = jax.random.split(jax.random.key(7), 3)
        return tuple(jax.random.normal(k, (B, N, H, D)) for k in ks)

    @staticmethod
    def _dense(q, k, v):
        D = q.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (D ** 0.5)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    def test_int8_ring_bounded_and_default_exact(self, monkeypatch):
        from comfyui_distributed_tpu.ops.attention import ring_attention

        mesh = build_mesh({"sp": 8})
        q, k, v = self._qkv()
        want = np.asarray(self._dense(q, k, v))
        specs = (P(None, "sp"),) * 3

        monkeypatch.delenv("CDT_COLLECTIVE_QUANT", raising=False)
        exact = _smap(lambda *a: ring_attention(*a, "sp"), mesh, specs,
                      P(None, "sp"))(q, k, v)
        np.testing.assert_allclose(np.asarray(exact), want, rtol=2e-5,
                                   atol=2e-5)

        monkeypatch.setenv("CDT_COLLECTIVE_QUANT", "int8")
        got = _smap(lambda *a: ring_attention(*a, "sp"), mesh, specs,
                    P(None, "sp"))(q, k, v)
        err = np.abs(np.asarray(got) - want).max()
        # one quantization round per K/V payload; softmax keeps the
        # value-side error at the same order as the wire error
        assert 0 < err < 0.1, err


# ---------------------------------------------------------------------------
# executed sp / dp×tp vs single-device reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def flow32():
    from comfyui_distributed_tpu.diffusion.pipeline_flow import FlowPipeline
    from comfyui_distributed_tpu.models.dit import DiTConfig, init_dit
    from comfyui_distributed_tpu.models.vae import AutoencoderKL, VAEConfig

    cfg = dataclasses.replace(DiTConfig.tiny(pos_embed="rope"),
                              dtype="float32")
    dit, params = init_dit(cfg, jax.random.key(3), sample_hw=(8, 8),
                           context_len=16)
    vae = AutoencoderKL(VAEConfig.tiny(dtype="float32")).init(
        jax.random.key(1), image_hw=(16, 16))
    return FlowPipeline(dit, params, vae)


@pytest.fixture(scope="module")
def cond16():
    from comfyui_distributed_tpu.models.text import (TextEncoder,
                                                     TextEncoderConfig)

    enc = TextEncoder(TextEncoderConfig.tiny()).init(jax.random.key(2))
    ctx, _ = enc.encode(["mesh tier"])
    unc, _ = enc.encode([""])
    return ctx, unc


class TestExecutedMeshStrategies:
    def test_sp_executes_against_single_device_reference(self, flow32,
                                                         cond16):
        from comfyui_distributed_tpu.diffusion.pipeline_flow import FlowSpec

        ctx, _ = cond16
        pooled = jnp.zeros((1, flow32.dit.config.pooled_dim))
        spec = FlowSpec(height=32, width=16, steps=2)
        sharded = flow32.generate_sp_fn(build_mesh({"sp": 8}), spec)(
            jax.random.key(5), ctx, pooled)
        single = flow32.generate_sp_fn(
            build_mesh({"sp": 1}, devices=jax.devices()[:1]), spec)(
            jax.random.key(5), ctx, pooled)
        assert sharded.shape == (1, 32, 16, 3)
        np.testing.assert_allclose(np.asarray(sharded),
                                   np.asarray(single),
                                   rtol=2e-4, atol=2e-4)

    def test_dp_tp_executes_against_single_device_reference(self, flow32,
                                                            cond16):
        from comfyui_distributed_tpu.diffusion.pipeline_flow import FlowSpec

        ctx, _ = cond16
        pooled = jnp.zeros((1, flow32.dit.config.pooled_dim))
        spec = FlowSpec(height=16, width=16, steps=2)
        out = flow32.generate_tp_fn(build_mesh({"dp": 4, "tp": 2}),
                                    spec)(jax.random.key(4), ctx, pooled)
        assert out.shape[0] == 4
        # the single-device reference runs the SAME program semantics
        # (same fold-in of 4 per-sample keys) on one chip
        ref = flow32.generate_tp_fn(
            build_mesh({"dp": 1, "tp": 1}, devices=jax.devices()[:1]),
            dataclasses.replace(spec, per_device_batch=4))(
            jax.random.key(4), ctx, pooled)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


@pytest.fixture(scope="module")
def unet32():
    from comfyui_distributed_tpu.diffusion.pipeline import Txt2ImgPipeline
    from comfyui_distributed_tpu.models.unet import UNetConfig, init_unet
    from comfyui_distributed_tpu.models.vae import AutoencoderKL, VAEConfig

    model, params = init_unet(UNetConfig.tiny(dtype="float32"),
                              jax.random.key(0), sample_shape=(8, 8, 4),
                              context_len=16)
    vae = AutoencoderKL(VAEConfig.tiny(dtype="float32")).init(
        jax.random.key(1), image_hw=(16, 16))
    return Txt2ImgPipeline(model, params, vae)


class TestMeshTierMicrobatch:
    def _spec(self):
        from comfyui_distributed_tpu.diffusion.pipeline import \
            GenerationSpec

        return GenerationSpec(height=16, width=16, steps=2,
                              guidance_scale=2.0)

    def test_tp_microbatch_tracks_solo_on_same_mesh(self, unet32, cond16):
        ctx, unc = cond16
        spec = self._spec()
        mesh = build_mesh({"dp": 4, "tp": 2})
        solo = [np.asarray(unet32.generate(mesh, spec, s, ctx, unc))
                for s in (11, 22)]
        outs = unet32.generate_microbatch(mesh, spec, [11, 22],
                                          [ctx, ctx], [unc, unc])
        for got, want in zip(outs, solo):
            assert got.shape == want.shape == (4, 16, 16, 3)
            np.testing.assert_allclose(np.asarray(got), want,
                                       rtol=2e-4, atol=2e-4)

    def test_mesh_tier_kill_switch_restores_bit_identity(self, unet32,
                                                         cond16,
                                                         monkeypatch):
        ctx, unc = cond16
        spec = self._spec()
        mesh = build_mesh({"dp": 4, "tp": 2})
        solo = np.asarray(unet32.generate(mesh, spec, 31, ctx, unc))
        monkeypatch.setenv("CDT_MESH_TIER", "0")
        outs = unet32.generate_microbatch(mesh, spec, [31, 32],
                                          [ctx, ctx], [unc, unc])
        # replicated-weights fan-out: the PR 6 bit-identity contract
        np.testing.assert_array_equal(np.asarray(outs[0]), solo)

    def test_dp_microbatch_stays_bit_identical(self, unet32, cond16):
        ctx, unc = cond16
        spec = self._spec()
        mesh = build_mesh({"dp": 8})
        solo = np.asarray(unet32.generate(mesh, spec, 7, ctx, unc))
        outs = unet32.generate_microbatch(mesh, spec, [7, 8],
                                          [ctx, ctx], [unc, unc])
        np.testing.assert_array_equal(np.asarray(outs[0]), solo)


# ---------------------------------------------------------------------------
# mesh-aware kernel selection
# ---------------------------------------------------------------------------


class TestMeshAwareKernelSelection:
    def test_geometry_shard(self):
        from comfyui_distributed_tpu.ops.kernel_choice import GeometryKey

        g = GeometryKey.from_shape(12, 128, 14040, 14040)
        assert g.shard(2).num_heads == 6
        assert g.shard(2).key_str() == "h6.d128.q16384.kv16384.bf16"
        # indivisible head counts don't shard (rules replicate there too)
        assert g.shard(5) is g
        assert g.shard(1) is g

    def test_select_kernel_resolves_per_shard_geometry(self, monkeypatch):
        import types

        from comfyui_distributed_tpu.ops import attention

        monkeypatch.delenv("CDT_FLASH_ATTENTION", raising=False)
        monkeypatch.setattr(attention.jax, "devices",
                            lambda *a: [types.SimpleNamespace(platform="tpu")])
        attention.reset_selections()
        # SDXL's 64² site: 10 heads × 64 are five 128-lane groups (packed);
        # a tp=2 shard runs 5 heads, which are not — the policy is asked
        # with the shard's own head count and answers for IT
        with attention.tp_shard_scope(2):
            shard = attention.select_kernel(4096, 4096, 10, 64)
        assert shard.tier == "xla"
        assert "h5.d64.q4096.kv4096.bf16=xla" in attention.selection_summary()
        # without the scope the same site resolves the FULL-H geometry —
        # what a tp-sharded site must not see
        full = attention.select_kernel(4096, 4096, 10, 64)
        assert (full.tier, full.block_q, full.block_k) == \
            ("packed", 512, 4096)


# ---------------------------------------------------------------------------
# placement planning + residency + warmup keys
# ---------------------------------------------------------------------------


class TestPlacementPlanning:
    def test_tp_forced_by_weight_pressure(self):
        from comfyui_distributed_tpu.parallel import serving

        plan = serving.plan_placement(8, batch=4,
                                      param_bytes=24_000_000_000,
                                      budget_bytes=13_000_000_000)
        assert plan.strategy == "dp_tp" and plan.tp == 2
        assert plan.mesh_shape == {"dp": 4, "tp": 2}

    def test_sp_for_single_image_latency(self):
        from comfyui_distributed_tpu.parallel import serving

        plan = serving.plan_placement(8, batch=1, supports_sp=True)
        assert plan.strategy == "sp"
        assert plan.mesh_shape == {"sp": 8}

    def test_kill_switch_and_single_device(self, monkeypatch):
        from comfyui_distributed_tpu.parallel import serving

        assert serving.plan_placement(1, batch=1).strategy == "dp"
        monkeypatch.setenv("CDT_MESH_TIER", "0")
        plan = serving.plan_placement(8, batch=1, supports_sp=True)
        assert plan.strategy == "dp"

    def test_pinned_tp_clamps_to_factorable(self, monkeypatch):
        from comfyui_distributed_tpu.parallel import serving

        monkeypatch.setenv("CDT_MESH_TP", "4")
        plan = serving.plan_placement(8, batch=2)
        assert plan.strategy == "dp_tp" and plan.tp == 4
        assert serving.derive_tp(2) == 2  # clamped to device count


class TestTpShardResidency:
    def test_tp_shard_bytes_divides_only_rule_matched(self):
        from comfyui_distributed_tpu.cluster.residency import \
            tp_shard_bytes
        from comfyui_distributed_tpu.models.dit import (DiTConfig,
                                                        init_dit)
        from comfyui_distributed_tpu.parallel.tensor import (
            DIT_TP_RULES, tp_sharding_summary)

        _, params = init_dit(DiTConfig.tiny(), jax.random.key(0),
                             sample_hw=(8, 8), context_len=16)
        mesh = build_mesh({"tp": 2})
        summary = tp_sharding_summary(params, mesh, DIT_TP_RULES, "tp")
        got = tp_shard_bytes(params, DIT_TP_RULES, 2)
        want = (summary["sharded_bytes"] // 2
                + summary["replicated_bytes"])
        assert got == want
        assert got < summary["sharded_bytes"] + summary["replicated_bytes"]

    def test_bundle_bytes_tp_granularity(self):
        from comfyui_distributed_tpu.cluster.residency import bundle_bytes
        from comfyui_distributed_tpu.models.registry import ModelRegistry

        bundle = ModelRegistry().get("flux-tiny")
        whole = bundle_bytes(bundle)
        per_chip = bundle_bytes(bundle, tp_shards=2)
        assert per_chip < whole


class TestMeshTierWarmupKeys:
    def test_flow_entries_grow_sp_and_tp_variants(self, monkeypatch):
        from comfyui_distributed_tpu.cluster.shape_catalog import \
            ProgramKey
        from comfyui_distributed_tpu.diffusion.warmup import \
            mesh_tier_keys

        monkeypatch.setenv("CDT_MESH_TP", "2")
        keys = [ProgramKey("flow_dp", "flux-tiny", 32, 32, 2),
                ProgramKey("txt2img", "tiny", 32, 32, 2)]
        tier = mesh_tier_keys(keys, build_mesh({"dp": 8}))
        by_pipe = {k.pipeline: k for k in tier}
        assert set(by_pipe) == {"flow_sp", "flow_tp"}
        assert dict(by_pipe["flow_tp"].mesh) == {"dp": 4, "tp": 2}
        assert dict(by_pipe["flow_sp"].mesh)["sp"] >= 2

    def test_kill_switch_empties_tier(self, monkeypatch):
        from comfyui_distributed_tpu.cluster.shape_catalog import \
            ProgramKey
        from comfyui_distributed_tpu.diffusion.warmup import \
            mesh_tier_keys

        monkeypatch.setenv("CDT_MESH_TIER", "0")
        keys = [ProgramKey("flow_dp", "flux-tiny", 32, 32, 2)]
        assert mesh_tier_keys(keys, build_mesh({"dp": 8})) == []


@pytest.mark.slow
def test_warmup_compiles_mesh_tier_programs(monkeypatch, tmp_path):
    """The AOT pass lowers + compiles flow_sp and flow_tp catalog
    programs (the mesh tier is hot from boot, not first-request)."""
    from comfyui_distributed_tpu.cluster.shape_catalog import ProgramKey
    from comfyui_distributed_tpu.diffusion.warmup import (mesh_tier_keys,
                                                          run_warmup)
    from comfyui_distributed_tpu.models.registry import ModelRegistry

    monkeypatch.setenv("CDT_MESH_TP", "2")
    mesh = build_mesh({"dp": 8})
    keys = [ProgramKey("flow_dp", "flux-tiny", 32, 32, 2)]
    keys += mesh_tier_keys(keys, mesh)
    report = run_warmup(ModelRegistry(), mesh, keys,
                        models=["flux-tiny"])
    outcomes = {e.key.pipeline: e.outcome for e in report}
    assert outcomes["flow_sp"] in ("compiled", "cache_hit"), report
    assert outcomes["flow_tp"] in ("compiled", "cache_hit"), report


# ---------------------------------------------------------------------------
# virtual-device bootstrap
# ---------------------------------------------------------------------------


class TestVirtualDevices:
    def test_noop_when_unset(self, monkeypatch):
        from comfyui_distributed_tpu.parallel.bootstrap import \
            ensure_virtual_devices

        monkeypatch.delenv("CDT_VIRTUAL_DEVICES", raising=False)
        assert ensure_virtual_devices() is None

    def test_already_configured_flags_short_circuit(self, monkeypatch):
        from comfyui_distributed_tpu.parallel.bootstrap import \
            ensure_virtual_devices

        # conftest already set the force flag for this process
        monkeypatch.setenv("CDT_VIRTUAL_DEVICES", "8")
        assert ensure_virtual_devices() == 8

    def test_conflicting_existing_flag_fails_loudly(self, monkeypatch):
        from comfyui_distributed_tpu.parallel.bootstrap import \
            ensure_virtual_devices

        monkeypatch.setenv(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        with pytest.raises(RuntimeError, match="conflicts"):
            ensure_virtual_devices(16)

    def test_fails_loudly_after_jax_import(self, monkeypatch):
        from comfyui_distributed_tpu.parallel.bootstrap import \
            ensure_virtual_devices

        monkeypatch.setenv("XLA_FLAGS", "")
        monkeypatch.setenv("CDT_VIRTUAL_DEVICES", "4")
        with pytest.raises(RuntimeError, match="already imported"):
            ensure_virtual_devices()

    def test_rejects_degenerate_count(self, monkeypatch):
        from comfyui_distributed_tpu.parallel.bootstrap import \
            ensure_virtual_devices

        monkeypatch.setenv("XLA_FLAGS", "")
        with pytest.raises(ValueError, match="at least 2"):
            ensure_virtual_devices(1)


# ---------------------------------------------------------------------------
# chaos: drain mid mesh-tier batched job
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestChaosMeshDrain:
    """ISSUE 13 chaos stage: a worker drains MID mesh-tier batched job
    (each tile executes the dp×tp microbatched program) — the run must
    complete bit-identical to the uninterrupted reference with zero
    dead-letters and no breaker opening (a drain is intentional)."""

    TOTAL = 8

    @pytest.fixture()
    def mesh_proc(self, unet32, cond16):
        ctx, unc = cond16
        from comfyui_distributed_tpu.diffusion.pipeline import \
            GenerationSpec

        spec = GenerationSpec(height=16, width=16, steps=2,
                              guidance_scale=2.0)
        mesh = build_mesh({"dp": 4, "tp": 2})

        def proc(start, end):
            out = []
            for i in range(start, end):
                # the mesh-tier batched program, keyed on the GLOBAL
                # tile index — identical bits wherever it runs
                imgs = unet32.generate_microbatch(
                    mesh, spec, [100 + i, 200 + i], [ctx, ctx],
                    [unc, unc])
                out.append(np.asarray(imgs[0][0]))
            return np.stack(out)

        # warm the program so the drain lands mid-RUN, not mid-compile
        proc(0, 1)
        return proc

    def test_mesh_drain_is_lossless_and_bit_identical(self, tmp_config,
                                                      mesh_proc):
        from comfyui_distributed_tpu.cluster.elastic.states import (
            ACTIVE, DECOMMISSIONED, DRAIN)
        from comfyui_distributed_tpu.cluster.job_store import JobStore
        from comfyui_distributed_tpu.cluster.resilience import BREAKERS
        from comfyui_distributed_tpu.cluster.tile_farm import (
            TileFarm, assemble_tiles)

        async def reference():
            farm = TileFarm(JobStore(), asyncio.get_running_loop())
            res = await farm.master_run_async(
                "mesh-ref", total=self.TOTAL, process_fn=mesh_proc,
                chunk=1, heartbeat_interval=0.2)
            return assemble_tiles(res, self.TOTAL, 1)

        ref = asyncio.run(reference())

        async def chaotic():
            from aiohttp.test_utils import TestClient, TestServer

            from comfyui_distributed_tpu.api.app import create_app
            from comfyui_distributed_tpu.cluster.controller import \
                Controller

            DRAIN.reset()
            controller = Controller()
            client = TestClient(TestServer(create_app(controller)))
            await client.start_server()
            try:
                base = f"http://127.0.0.1:{client.port}"
                loop = asyncio.get_running_loop()
                master = asyncio.create_task(
                    controller.tile_farm.master_run_async(
                        "mesh-job", total=self.TOTAL,
                        process_fn=mesh_proc, chunk=1,
                        heartbeat_interval=0.2, worker_timeout=30.0))
                await asyncio.sleep(0.05)

                # w1 pulls and HOLDS mesh-tier work, then drains: the
                # deadline handback must return its tiles to the queue
                held = []
                for _ in range(2):
                    async with client.session.post(
                            f"{base}/distributed/request_image",
                            json={"job_id": "*",
                                  "worker_id": "w1"}) as r:
                        t = (await r.json())["task"]
                        if t:
                            held.append(t["task_id"])
                assert held
                w0 = asyncio.create_task(
                    TileFarm(JobStore(), loop).worker_steal_run_async(
                        "w0", base, lambda jid: mesh_proc,
                        idle_polls=3, idle_interval=0.1))
                async with client.session.post(
                        f"{base}/distributed/worker/w1/drain",
                        json={"deadline_s": 0.2,
                              "stop_process": False}) as r:
                    assert r.status == 200
                await controller.elastic.coordinator.wait("w1")

                res = await master
                await w0
                out = assemble_tiles(res, self.TOTAL, 1)
                status = await controller.store.job_status("mesh-job")
                assert status["dead_letter"] in ([], None)
                assert all(s == "closed"
                           for s in BREAKERS.states().values()), \
                    BREAKERS.states()
                assert DRAIN.state("w1") == DECOMMISSIONED
                assert DRAIN.state("w0") == ACTIVE
                return out
            finally:
                await client.close()

        out = asyncio.run(chaotic())
        np.testing.assert_array_equal(out, ref)
