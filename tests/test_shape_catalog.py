"""Shape-catalog registry (cluster/shape_catalog.py): key round-trips,
dedup, persistence + cross-process merge, workflow seeding, and the
runtime observation hook — the inventory the AOT warmup pass walks."""

import json

import pytest

from comfyui_distributed_tpu.cluster import shape_catalog as sc
from comfyui_distributed_tpu.cluster.shape_catalog import (
    ProgramKey, ShapeCatalog, keys_from_prompt)


class TestProgramKey:
    def test_round_trip(self):
        k = ProgramKey("video_dp", "wan", 480, 832, 20, frames=33,
                       mesh=(("dp", 8),))
        assert ProgramKey.from_dict(k.to_dict()) == k

    def test_json_serializable(self):
        k = ProgramKey("txt2img", "sdxl", 1024, 1024, 30)
        assert ProgramKey.from_dict(
            json.loads(json.dumps(k.to_dict()))) == k

    def test_unknown_pipeline_rejected(self):
        with pytest.raises(ValueError, match="pipeline"):
            ProgramKey("nope", "sdxl", 64, 64, 2)

    def test_hashable_dedup(self):
        a = ProgramKey("txt2img", "tiny", 32, 32, 2)
        b = ProgramKey("txt2img", "tiny", 32, 32, 2)
        assert len({a, b}) == 1


class TestCatalogPersistence:
    def test_save_load_round_trip(self, tmp_path):
        path = tmp_path / "cat.json"
        cat = ShapeCatalog(path)
        cat.add(ProgramKey("txt2img", "tiny", 32, 32, 2))
        cat.add(ProgramKey("flow_dp", "flux-tiny", 64, 64, 4))
        assert cat.save()

        cat2 = ShapeCatalog(path)
        assert sorted(cat2.entries()) == sorted(cat.entries())

    def test_add_dedups(self, tmp_path):
        cat = ShapeCatalog(tmp_path / "cat.json")
        k = ProgramKey("txt2img", "tiny", 32, 32, 2)
        assert cat.add(k) is True
        assert cat.add(k) is False
        assert len(cat) == 1

    def test_merge_across_instances(self, tmp_path):
        """Two writers sharing one file union rather than clobber —
        master and warmup CLI may both persist."""
        path = tmp_path / "cat.json"
        a = ShapeCatalog(path)
        b = ShapeCatalog(path)
        a.add(ProgramKey("txt2img", "tiny", 32, 32, 2))
        a.save()
        b.add(ProgramKey("flow_dp", "flux-tiny", 64, 64, 4))
        b.save()            # merge-write: must keep a's entry too
        merged = ShapeCatalog(path)
        assert len(merged) == 2

    def test_garbled_file_degrades_to_empty(self, tmp_path):
        path = tmp_path / "cat.json"
        path.write_text("{not json")
        cat = ShapeCatalog(path)
        assert len(cat) == 0
        # and stays writable
        cat.add(ProgramKey("txt2img", "tiny", 32, 32, 2))
        assert cat.save() and len(ShapeCatalog(path)) == 1

    def test_malformed_entries_skipped(self, tmp_path):
        path = tmp_path / "cat.json"
        good = ProgramKey("txt2img", "tiny", 32, 32, 2).to_dict()
        path.write_text(json.dumps(
            {"version": 1,
             "entries": [good, {"pipeline": "txt2img"}, 42]}))
        cat = ShapeCatalog(path)
        assert cat.entries() == [ProgramKey.from_dict(good)]


class TestWorkflowSeeding:
    def test_repo_workflows_seed(self, tmp_path):
        cat = ShapeCatalog(tmp_path / "cat.json")
        added = cat.seed_from_workflows("workflows")
        keys = cat.entries()
        assert added == len(keys) > 0
        # the shipped catalog's static shapes, model names resolved
        # through the CheckpointLoader link
        assert ProgramKey("txt2img", "sdxl", 1024, 1024, 30) in cat
        assert ProgramKey("flow_dp", "flux", 1024, 1024, 28) in cat
        assert any(k.pipeline == "video_dp" and k.model == "wan"
                   and k.frames > 0 for k in keys)

    def test_seeding_idempotent(self, tmp_path):
        cat = ShapeCatalog(tmp_path / "cat.json")
        first = cat.seed_from_workflows("workflows")
        assert first > 0
        assert cat.seed_from_workflows("workflows") == 0

    def test_linked_geometry_skipped(self):
        # steps rides a link → not statically derivable → no key
        prompt = {
            "1": {"class_type": "CheckpointLoader",
                  "inputs": {"ckpt_name": "tiny"}},
            "2": {"class_type": "TPUTxt2Img",
                  "inputs": {"model": ["1", 0], "steps": ["9", 0],
                             "width": 64, "height": 64}},
        }
        assert keys_from_prompt(prompt) == []

    def test_unlinked_model_skipped(self):
        prompt = {"2": {"class_type": "TPUTxt2Img",
                        "inputs": {"model": ["7", 0], "steps": 2,
                                   "width": 64, "height": 64}}}
        assert keys_from_prompt(prompt) == []

    def test_missing_dir_is_empty(self, tmp_path):
        cat = ShapeCatalog(tmp_path / "cat.json")
        assert cat.seed_from_workflows(tmp_path / "nope") == 0


class TestRuntimeObservation:
    @pytest.fixture(autouse=True)
    def _isolated_default(self, tmp_path, monkeypatch):
        monkeypatch.setenv("CDT_SHAPE_CATALOG",
                           str(tmp_path / "observed.json"))
        sc.reset_default_catalog()
        yield
        sc.reset_default_catalog()

    def test_observe_persists_new_key(self, tmp_path):
        sc.observe("txt2img", "tiny", 32, 32, 2)
        on_disk = ShapeCatalog(tmp_path / "observed.json")
        assert ProgramKey("txt2img", "tiny", 32, 32, 2) in on_disk

    def test_observe_disabled_by_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("CDT_SHAPE_OBSERVE", "0")
        sc.observe("txt2img", "tiny", 32, 32, 2)
        assert not (tmp_path / "observed.json").exists()

    def test_observe_never_raises(self, monkeypatch):
        monkeypatch.setenv("CDT_SHAPE_CATALOG", "/proc/denied/cat.json")
        sc.reset_default_catalog()
        sc.observe("txt2img", "tiny", 32, 32, 2)   # must not raise

    def test_observation_capped(self, tmp_path, monkeypatch):
        monkeypatch.setenv("CDT_SHAPE_CATALOG_MAX", "2")
        sc.observe("txt2img", "tiny", 32, 32, 1)
        sc.observe("txt2img", "tiny", 32, 32, 2)
        sc.observe("txt2img", "tiny", 32, 32, 3)   # over cap → dropped
        on_disk = ShapeCatalog(tmp_path / "observed.json")
        assert len(on_disk) == 2
        assert ProgramKey("txt2img", "tiny", 32, 32, 3) not in on_disk

    def test_default_path_lives_next_to_xla_cache(self, monkeypatch):
        monkeypatch.delenv("CDT_SHAPE_CATALOG", raising=False)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/cache")
        assert str(sc.default_catalog_path()) == \
            "/some/cache/shape_catalog.json"
