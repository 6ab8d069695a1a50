"""A cell is three data files found by name; the shipped cells assemble;
the sizes in the configuration files are the registry's."""

import dataclasses
import json

import pytest

import numpy as np
from PIL import Image

from cdtbench import flops, golden, readers, workload


def _write(path, obj):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


def test_a_cell_is_assembled_from_three_data_files(tmp_path):
    here = tmp_path / "bench"
    _write(tmp_path / "BENCHMARK.json", {
        "run_seconds": 5,
        "workloads": [{"name": "m.mix", "config": "m", "traffic": "mix",
                       "chips": 4}],
        "end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["other"]}],
        "per_layer": [{"name": "c", "workloads": ["m.mix"]}]})
    _write(here / "configs" / "m.json",
           {"preset": "big", "rehearsal_preset": "small"})
    _write(here / "traffic" / "mix.json", {
        "loop": "closed", "graph": "g",
        "overrides": {"5": {"steps": 4}, "8": {"default_value": 1.0}},
        "rehearsal_overrides": {"5": {"width": 64}},
        "nodes": {"checkpoint": ["1", "ckpt_name"], "seed": ["4", "seed"],
                  "prompt": ["2", "text"],
                  "save_prefix": ["7", "filename_prefix"], "sampler": "5"}})
    _write(here / "workflows" / "g.json", {
        "_meta": {"title": "t"},
        "1": {"class_type": "CheckpointLoader", "inputs": {"ckpt_name": "x"}},
        "2": {"class_type": "CLIPTextEncode", "inputs": {"text": "t"}},
        "4": {"class_type": "DistributedSeed", "inputs": {"seed": 7}},
        "8": {"class_type": "DistributedValue",
              "inputs": {"default_value": 6.0}},
        "5": {"class_type": "TPUTxt2Img", "inputs": {
            "model": ["1", 0], "seed": ["4", 0], "steps": 30,
            "cfg": ["8", 0], "width": 1024, "height": 512}},
        "7": {"class_type": "SaveImage", "inputs": {"filename_prefix": "p"}}})
    cell = workload.assemble("m.mix", root=tmp_path, here=here)
    assert (cell.preset, cell.chips, cell.steps, cell.cfg) == ("big", 4, 4, 1.0)
    assert cell.image_hw == (512, 1024) and cell.images_per_request == 4
    assert "_meta" not in cell.graph
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["a"]
    assert [m["name"] for m in cell.metrics("per_layer")] == ["c"]
    graph = cell.request_graph(99, "a prompt", "w00001")
    assert graph["4"]["inputs"]["seed"] == 99
    assert graph["2"]["inputs"]["text"] == "a prompt"
    assert graph["7"]["inputs"]["filename_prefix"] == "w00001"
    assert graph["1"]["inputs"]["ckpt_name"] == "big"
    assert cell.graph["4"]["inputs"]["seed"] == 7       # the base is kept
    small = workload.assemble("m.mix", rehearsal=True, root=tmp_path,
                              here=here)
    assert small.preset == "small" and small.image_hw == (512, 64)
    with pytest.raises(KeyError):
        workload.assemble("m.nothing", root=tmp_path, here=here)


def test_every_shipped_cell_assembles_and_its_metrics_have_readers():
    bench = workload.load_json(workload.ROOT / "BENCHMARK.json")
    for entry in bench["workloads"]:
        cell = workload.assemble(entry["name"])
        assert cell.image_hw == (1024, 1024)
        assert {m["name"] for m in cell.metrics("end_to_end")} >= {
            "setup_s", "request_p50_s", "images_per_s"}
        for metric in cell.metrics("per_layer"):
            spec = readers.spec_of(metric["name"])
            assert spec["unit"] == metric["unit"], metric["name"]
            assert spec["reader"] == "python" or spec["reader"] in \
                readers.READERS
    for config in bench["configs"]:
        held = workload.load_json(workload.ROOT / config["file"])
        assert held["source"] == config["source"]
        assert held["reduced"] == config["reduced"]


@pytest.mark.parametrize("name", ["sdxl-base", "sd3-medium"])
def test_configuration_sizes_are_the_registry_presets(name):
    from comfyui_distributed_tpu.models.registry import PRESETS

    held = workload.load_json(workload.HERE / "configs" / f"{name}.json")
    preset = PRESETS[held["preset"]]
    for part in ("unet", "dit", "vae"):
        if part not in held:
            continue
        registry = dataclasses.asdict(getattr(preset, part))
        for key, value in held[part].items():
            got = registry[key]
            assert (list(got) if isinstance(got, tuple) else got) == value, \
                (part, key)
    assert held["context_len"] == preset.text.max_len
    assert held["kind"] == preset.kind


def _cells():
    bench = workload.load_json(workload.ROOT / "BENCHMARK.json")
    return [w["name"] for w in bench["workloads"]]


@pytest.mark.parametrize("name", _cells())
def test_pinned_step_operations_are_the_walk_of_the_model_call(name):
    """A run reads the count from the configuration's file alone; here,
    off the run, it is held to the jaxpr walk of the program's model."""
    cell = workload.assemble(name)
    height, width = cell.image_hw
    assert cell.step_flops == flops.step_flops(cell.config, height, width,
                                               cell.step_batch)


@pytest.mark.parametrize("name", _cells())
def test_every_shipped_cell_has_its_golden(name):
    spec = golden.spec_of(name)
    assert spec["request"]["seed"] > 0 and spec["request"]["prompt"]
    assert 0 < spec["max_mean_abs_levels"] <= 8
    cell = workload.assemble(name)
    height, width = cell.image_hw
    image = np.asarray(Image.open(golden.HERE / f"{name}.png"))
    assert image.shape == (height // spec["stride"], width // spec["stride"], 3)
    assert image.dtype == np.uint8 and image.min() < image.max()


def test_golden_check_passes_rounding_noise_and_refuses_a_changed_image(
        tmp_path):
    here, out = tmp_path / "goldens", tmp_path / "out"
    here.mkdir(), out.mkdir()
    said = []
    rng = np.random.default_rng(0)
    image = rng.integers(0, 256, (64, 64, 3), dtype=np.uint8)
    assert "missing" in golden.check("c.m", image, out, said.append, here)[0]
    (here / "c.m.json").write_text(json.dumps(
        {"request": {"seed": 1, "prompt": "p"}, "stride": 4,
         "max_mean_abs_levels": 2.0}))
    assert "no golden image" in golden.check("c.m", image, out, said.append,
                                             here)[0]
    (out / golden.CANDIDATE).rename(here / "c.m.png")     # how one records
    assert golden.check("c.m", image, out, said.append, here) == []
    assert "mean |diff| 0.0000" in said[-1]
    noisy = np.clip(image.astype(int) + rng.integers(-1, 2, image.shape),
                    0, 255).astype(np.uint8)
    assert golden.check("c.m", noisy, out, said.append, here) == []
    other = rng.integers(0, 256, (64, 64, 3), dtype=np.uint8)
    assert "mathematics changed" in golden.check("c.m", other, out,
                                                 said.append, here)[0]
    assert "against served" in golden.check(
        "c.m", other[:32], out, said.append, here)[0]
