"""Multi-model HBM residency planner (cluster/residency.py): LRU/priority
eviction under a synthetic budget, deterministic evict/re-upload cycles,
registry integration, device release on eviction, and per-request LoRA
hot-patching that never evicts the base bundle."""

import types

import pytest

from comfyui_distributed_tpu.cluster.residency import (BundleResidency,
                                                       ResidencyError,
                                                       ResidencyPlanner,
                                                       bundle_bytes)
from comfyui_distributed_tpu.models.registry import ModelRegistry


class TestPlannerPolicy:
    def test_lru_eviction_order(self):
        evicted = []
        p = ResidencyPlanner(100, on_evict=evicted.append)
        p.acquire("a", 40)
        p.acquire("b", 40)
        p.touch("a")                      # b is now least-recently-used
        assert p.acquire("c", 40) == ["b"]
        assert evicted == ["b"]
        assert p.resident_bytes() == 80

    def test_priority_outranks_recency(self):
        p = ResidencyPlanner(100)
        p.acquire("hi", 40, priority=1)
        p.acquire("lo", 40, priority=0)
        p.touch("lo")                     # recent but LOW priority
        assert p.acquire("new", 40) == ["lo"]

    def test_multi_victim_eviction(self):
        p = ResidencyPlanner(100)
        p.acquire("a", 30)
        p.acquire("b", 30)
        p.acquire("c", 30)
        assert p.acquire("big", 70) == ["a", "b"]
        assert p.resident() == ["c", "big"]

    def test_plan_is_a_dry_run(self):
        p = ResidencyPlanner(100)
        p.acquire("a", 60)
        assert p.plan("b", 60) == ["a"]
        assert p.resident() == ["a"]      # nothing applied

    def test_reacquire_touches_instead_of_duplicating(self):
        p = ResidencyPlanner(100)
        p.acquire("a", 40)
        p.acquire("b", 40)
        p.acquire("a", 40)                # refresh
        assert p.acquire("c", 40) == ["b"]

    def test_over_budget_model_rejected(self):
        p = ResidencyPlanner(100)
        with pytest.raises(ResidencyError, match="never be resident"):
            p.acquire("whale", 101)

    def test_pinned_never_evicted(self):
        p = ResidencyPlanner(100)
        p.acquire("a", 60)
        p.acquire("b", 40)
        with p.pinned("a"):
            with pytest.raises(ResidencyError, match="pinned"):
                p.acquire("c", 70)        # only a's eviction could fit c
            assert "a" in p.resident()
        # unpinned, the same acquire succeeds: a and b both go
        assert p.acquire("c", 70) == ["a", "b"]
        assert p.resident() == ["c"]

    def test_release_manual_and_pinned_guard(self):
        evicted = []
        p = ResidencyPlanner(100, on_evict=evicted.append)
        p.acquire("a", 40)
        with p.pinned("a"):
            with pytest.raises(ResidencyError):
                p.release("a")
        assert p.release("a") is True
        assert evicted == ["a"]
        assert p.release("a") is False

    def test_unlimited_budget_never_evicts(self):
        p = ResidencyPlanner(0)
        for i in range(10):
            assert p.acquire(f"m{i}", 10 ** 12) == []
        assert len(p.resident()) == 10

    def test_deterministic_swap_cycle(self):
        """The acceptance shape: two bundles under a one-bundle budget
        evict and re-acquire deterministically — A,B,A,B always swaps
        the other one out."""
        log = []
        p = ResidencyPlanner(50, on_evict=log.append)
        p.acquire("A", 40)
        assert p.acquire("B", 40) == ["A"]
        assert p.acquire("A", 40) == ["B"]
        assert p.acquire("B", 40) == ["A"]
        assert log == ["A", "B", "A"]


class _FakeLeaf:
    def __init__(self):
        self.deleted = False

    def delete(self):
        self.deleted = True


class TestRegistryIntegration:
    def test_budget_evicts_lru_bundle(self, monkeypatch):
        base = ModelRegistry()
        nb = bundle_bytes(base.get("tiny"))
        reg = ModelRegistry(hbm_budget_bytes=int(nb * 1.5))
        reg.get("tiny")
        reg.get("flux-tiny")              # must displace tiny
        assert "tiny" not in reg._cache
        assert reg.residency.planner.resident() == ["flux-tiny"]
        # deterministic re-upload: coming back displaces the other one
        reg.get("tiny")
        assert "flux-tiny" not in reg._cache
        assert reg.residency.planner.resident() == ["tiny"]

    def test_two_models_servable_under_budget(self):
        """Both bundles fit → repeated alternation never evicts."""
        base = ModelRegistry()
        nb = bundle_bytes(base.get("tiny")) \
            + bundle_bytes(base.get("flux-tiny"))
        reg = ModelRegistry(hbm_budget_bytes=int(nb * 1.2))
        for _ in range(3):
            reg.get("tiny")
            reg.get("flux-tiny")
        assert sorted(reg._cache) == ["flux-tiny", "tiny"]
        assert sorted(reg.residency.planner.resident()) == \
            ["flux-tiny", "tiny"]

    def test_env_budget_attaches_planner(self, monkeypatch):
        monkeypatch.setenv("CDT_HBM_BUDGET_GB", "2")
        assert ModelRegistry().residency is not None
        monkeypatch.setenv("CDT_HBM_BUDGET_GB", "0")
        assert ModelRegistry().residency is None

    def test_unplaceable_bundle_not_cached(self):
        """A bundle the budget can never hold must not squat in the
        registry cache after the rejection (it would be permanently
        over budget and unevictable)."""
        reg = ModelRegistry(hbm_budget_bytes=1)    # nothing fits
        with pytest.raises(ResidencyError, match="never be resident"):
            reg.get("tiny")
        assert "tiny" not in reg._cache
        # and the failure is repeatable, not sticky
        with pytest.raises(ResidencyError):
            reg.get("tiny")

    def test_pinned_bundle_guards_generate(self):
        from comfyui_distributed_tpu.cluster.residency import \
            pinned_bundle

        base = ModelRegistry()
        nb = bundle_bytes(base.get("tiny"))
        reg = ModelRegistry(hbm_budget_bytes=int(nb * 1.5))
        bundle = reg.get("tiny")
        with pinned_bundle(bundle):
            assert reg.residency.planner._entries["tiny"].pins == 1
            # a concurrent acquire cannot evict the executing bundle
            with pytest.raises(ResidencyError, match="pinned"):
                reg.get("flux-tiny")
        assert reg.residency.planner._entries["tiny"].pins == 0
        # no planner attached → transparent no-op
        with pinned_bundle(base.get("tiny")):
            pass

    def test_release_device_frees_offload_executors(self):
        reg = ModelRegistry()
        bundle = reg.get("tiny")
        leaf = _FakeLeaf()
        fake_exec = types.SimpleNamespace(
            stacked={"double": {"f32": [leaf]}}, resident={}, glue=None)
        bundle.pipeline._fn_cache = {("offload", None): fake_exec,
                                     ("other",): object()}
        bundle.release_device()
        assert leaf.deleted
        assert bundle.pipeline._fn_cache == {}

    def test_release_device_drops_served_programs_and_placed_copies(self):
        """An evicted bundle keeps no bound program and no copy of its
        weights on a mesh's other chips; acquired again it places once
        more (PR 31)."""
        import gc
        import weakref

        import jax

        from comfyui_distributed_tpu.diffusion.pipeline import GenerationSpec
        from comfyui_distributed_tpu.parallel import build_mesh
        from comfyui_distributed_tpu.telemetry import metrics as tm

        placed = tm.WEIGHT_PLACEMENT.labels(outcome="placed")
        bundle = ModelRegistry().get("tiny")
        pipe = bundle.pipeline
        mesh = build_mesh({"dp": 4}, devices=jax.devices()[:4])
        spec = GenerationSpec(height=16, width=16, steps=2)
        start = placed.value
        fns = pipe.preemptible_fns(mesh, spec)
        fns["seg"](2)
        # a cache release_device did not clear before; shares the VAE's leaves
        pipe._dec_cache = {"decode": pipe.decode_fn(mesh, 1)}
        assert placed.value == start + 1
        watched = [weakref.ref(leaf)
                   for leaf in jax.tree.leaves(fns["prep"].weights)]
        assert all(len(ref().devices()) == 4 for ref in watched)
        del fns
        bundle.release_device()
        gc.collect()
        assert not any(cache for name, cache in vars(pipe).items()
                       if name.endswith(("_cache", "_clones")))
        assert not hasattr(pipe, "_preempt_cache")
        assert all(ref() is None for ref in watched)
        pipe.preemptible_fns(mesh, spec)
        assert placed.value == start + 2


class TestLoRAHotPatch:
    def test_request_pins_base_and_patches_a_clone(self):
        base = ModelRegistry()
        nb = bundle_bytes(base.get("tiny"))
        reg = ModelRegistry(hbm_budget_bytes=int(nb * 1.5))
        res = reg.residency
        with res.request("tiny", lora_sd={}) as patched:
            bundle = reg._cache["tiny"]
            assert patched is not bundle            # copy-on-write clone
            assert patched.pipeline is not bundle.pipeline
            # the patch shares base leaves, so the planner must NOT see
            # a second registration
            assert res.planner.resident() == ["tiny"]
            assert res.planner._entries["tiny"].pins == 1
        assert res.planner._entries["tiny"].pins == 0

    def test_concurrent_acquire_cannot_evict_patched_base(self):
        base = ModelRegistry()
        nb = bundle_bytes(base.get("tiny"))
        reg = ModelRegistry(hbm_budget_bytes=int(nb * 1.5))
        with reg.residency.request("tiny", lora_sd={}):
            # another model arrives mid-request; evicting the pinned
            # base is the bug this guards against
            with pytest.raises(ResidencyError, match="pinned"):
                reg.get("flux-tiny")
            assert "tiny" in reg._cache
            assert reg.residency.planner.resident() == ["tiny"]
        # after the request drains, the swap proceeds normally
        reg.get("flux-tiny")
        assert reg.residency.planner.resident() == ["flux-tiny"]

    @staticmethod
    def _walk(params, path):
        node = params["params"]
        for part in path.split("/"):
            if not isinstance(node, dict) or part not in node:
                return None
            node = node[part]
        return node

    def test_real_lora_delta_applies_per_request(self):
        """A real (tiny) kohya LoRA merges into the request clone and
        leaves the registry's base weights untouched."""
        import numpy as np

        from comfyui_distributed_tpu.models.lora import unet_records

        reg = ModelRegistry()
        bundle = reg.get("tiny")
        cfg = bundle.preset.unet
        linear_proj = not (cfg.context_dim == 768
                           and cfg.adm_in_channels == 0)
        recs = unet_records(cfg, linear_proj=linear_proj)
        # first recorded 2-D (Linear) target → synthesize a kohya pair
        # with the matching torch geometry: down [r, in], up [out, r]
        target = next(
            ((src, dst) for src, dst, _ in recs
             if src.endswith(".weight")
             and getattr(self._walk(bundle.pipeline.unet_params, dst),
                         "ndim", 0) == 2), None)
        assert target is not None
        src_key, path = target
        leaf = self._walk(bundle.pipeline.unet_params, path)
        n_in, n_out = leaf.shape          # flax kernel [in, out]
        rng = np.random.RandomState(0)
        lkey = "lora_unet_" + src_key[
            len("model.diffusion_model."):-len(".weight")].replace(".", "_")
        sd = {f"{lkey}.lora_down.weight":
                  rng.randn(4, n_in).astype(np.float32) * 0.1,
              f"{lkey}.lora_up.weight":
                  rng.randn(n_out, 4).astype(np.float32) * 0.1}

        res = BundleResidency(reg, budget_bytes=0)
        res.planner = ResidencyPlanner(10 ** 15)
        res.planner.acquire("tiny", 1)
        before = np.asarray(leaf).copy()
        with res.request("tiny", lora_sd=sd) as patched:
            pl = self._walk(patched.pipeline.unet_params, path)
            assert not np.allclose(np.asarray(pl), before)   # patched
        # registry base untouched, during and after
        bl = self._walk(bundle.pipeline.unet_params, path)
        np.testing.assert_array_equal(np.asarray(bl), before)
