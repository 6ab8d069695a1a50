"""Node registry / executor / builtin-node tests (parity model: reference
node unit tests — dividers, value coercion, seed offsets)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.graph import (
    GraphExecutor,
    NODE_REGISTRY,
    validate_prompt,
)
from comfyui_distributed_tpu.graph.executor import topo_order
from comfyui_distributed_tpu.graph.nodes_builtin import _chunk_bounds
from comfyui_distributed_tpu.utils.exceptions import ValidationError


REFERENCE_PARITY_NODES = [
    "DistributedCollector", "DistributedSeed", "DistributedValue",
    "DistributedModelName", "ImageBatchDivider", "AudioBatchDivider",
    "DistributedEmptyImage", "UltimateSDUpscaleDistributed",
]


def test_all_reference_nodes_registered():
    for name in REFERENCE_PARITY_NODES:
        assert name in NODE_REGISTRY, name


class TestValidation:
    def test_valid_prompt(self):
        p = {"1": {"class_type": "PrimitiveInt", "inputs": {"value": 3}}}
        assert validate_prompt(p) == []

    def test_unknown_class(self):
        p = {"1": {"class_type": "Nope", "inputs": {}}}
        errs = validate_prompt(p)
        assert len(errs) == 1 and "unknown node class" in errs[0].message

    def test_missing_required_input(self):
        p = {"1": {"class_type": "PrimitiveInt", "inputs": {}}}
        errs = validate_prompt(p)
        assert any("missing required input" in e.message for e in errs)

    def test_dangling_link(self):
        p = {"1": {"class_type": "PrimitiveInt", "inputs": {"value": ["9", 0]}}}
        errs = validate_prompt(p)
        assert any("missing node" in e.message for e in errs)

    def test_bad_output_index(self):
        p = {
            "1": {"class_type": "PrimitiveInt", "inputs": {"value": 1}},
            "2": {"class_type": "PrimitiveInt", "inputs": {"value": ["1", 5]}},
        }
        errs = validate_prompt(p)
        assert any("output 5" in e.message for e in errs)

    def test_cycle_detected(self):
        p = {
            "a": {"class_type": "PrimitiveInt", "inputs": {"value": ["b", 0]}},
            "b": {"class_type": "PrimitiveInt", "inputs": {"value": ["a", 0]}},
        }
        errs = validate_prompt(p)
        assert any("cycle" in e.message for e in errs)

    def test_empty_prompt(self):
        assert validate_prompt({})[0].message.startswith("prompt must be")


class TestExecutor:
    def test_chain_execution(self):
        p = {
            "1": {"class_type": "PrimitiveInt", "inputs": {"value": 41}},
            "2": {"class_type": "DistributedSeed", "inputs": {"seed": ["1", 0]}},
        }
        out = GraphExecutor().execute(p)
        assert out["2"] == (41,)

    def test_hidden_context_injection(self):
        p = {"1": {"class_type": "DistributedSeed",
                   "inputs": {"seed": 10}}}
        ex = GraphExecutor({"is_worker": True, "worker_index": 2})
        assert ex.execute(p)["1"] == (13,)   # 10 + 2 + 1

    def test_explicit_input_beats_context(self):
        p = {"1": {"class_type": "DistributedSeed",
                   "inputs": {"seed": 10, "is_worker": False}}}
        ex = GraphExecutor({"is_worker": True, "worker_index": 2})
        assert ex.execute(p)["1"] == (10,)

    def test_invalid_raises(self):
        with pytest.raises(ValidationError):
            GraphExecutor().execute({"1": {"class_type": "Nope"}})

    def test_topo_order_dependencies_first(self):
        p = {
            "c": {"class_type": "PrimitiveInt", "inputs": {"value": ["b", 0]}},
            "b": {"class_type": "PrimitiveInt", "inputs": {"value": ["a", 0]}},
            "a": {"class_type": "PrimitiveInt", "inputs": {"value": 1}},
        }
        order = topo_order(p)
        assert order.index("a") < order.index("b") < order.index("c")


class TestChunkBounds:
    def test_even_split(self):
        assert _chunk_bounds(8, 4) == [(0, 2), (2, 4), (4, 6), (6, 8)]

    def test_uneven_split_front_loaded(self):
        assert _chunk_bounds(7, 3) == [(0, 3), (3, 5), (5, 7)]

    def test_more_parts_than_items(self):
        assert _chunk_bounds(2, 5) == [(0, 1), (1, 2)]

    def test_zero_items(self):
        assert _chunk_bounds(0, 3) == [(0, 0)]


class TestDividers:
    def test_image_divider(self):
        node = NODE_REGISTRY["ImageBatchDivider"]()
        imgs = jnp.arange(10)[:, None, None, None] * jnp.ones((10, 2, 2, 3))
        outs = node.execute(images=imgs, divide_by=3)
        assert len(outs) == 10
        assert [o.shape[0] for o in outs[:3]] == [4, 3, 3]
        assert all(o.shape[0] == 0 for o in outs[3:])
        # concatenation restores the batch
        np.testing.assert_array_equal(
            np.asarray(jnp.concatenate(outs[:3])), np.asarray(imgs))

    def test_audio_divider(self):
        node = NODE_REGISTRY["AudioBatchDivider"]()
        audio = {"waveform": np.arange(100, dtype=np.float32).reshape(1, 1, 100),
                 "sample_rate": 16000}
        outs = node.execute(audio=audio, divide_by=4)
        assert [o["waveform"].shape[-1] for o in outs[:4]] == [25, 25, 25, 25]
        assert all(o["sample_rate"] == 16000 for o in outs[:4])
        recon = np.concatenate([o["waveform"] for o in outs[:4]], axis=-1)
        np.testing.assert_array_equal(recon, audio["waveform"])


class TestEcosystemNodes:
    def test_image_from_batch_slices(self):
        node = NODE_REGISTRY["ImageFromBatch"]()
        imgs = jnp.arange(6)[:, None, None, None] * jnp.ones((6, 2, 2, 3))
        out = node.execute(image=imgs, batch_index=2, length=3)[0]
        np.testing.assert_array_equal(np.asarray(out), np.asarray(imgs[2:5]))

    def test_image_from_batch_clamps(self):
        node = NODE_REGISTRY["ImageFromBatch"]()
        imgs = jnp.ones((4, 2, 2, 3))
        assert node.execute(image=imgs, batch_index=10,
                            length=5)[0].shape[0] == 1   # index→last, len→1
        assert node.execute(image=imgs, batch_index=2,
                            length=99)[0].shape[0] == 2  # len clamps to rest

    def test_model_sampling_sd3_overrides_shift(self):
        import types

        node = NODE_REGISTRY["ModelSamplingSD3"]()
        base = types.SimpleNamespace(pipeline="p", preset="x")
        wrapped = node.execute(model=base, shift=7.5)[0]
        assert wrapped.sampling_shift == 7.5
        assert wrapped.pipeline == "p" and wrapped.preset == "x"  # forwards

    def test_flow_node_uses_model_shift_when_unwired(self):
        """TPUFlowTxt2Img with no wired shift consults the
        ModelSamplingSD3 override; a wired shift wins."""
        import types

        seen = {}

        class FakePipe:
            def generate_segmented(self, mesh, spec, seed, ctx, pooled,
                                   **kw):
                seen["shift"] = spec.shift
                return jnp.zeros((1, 4, 4, 3))

        base = types.SimpleNamespace(pipeline=FakePipe())
        wrapped = NODE_REGISTRY["ModelSamplingSD3"]().execute(
            model=base, shift=5.5)[0]
        cond = {"context": jnp.zeros((1, 2, 8)),
                "pooled": jnp.zeros((1, 8))}
        node = NODE_REGISTRY["TPUFlowTxt2Img"]()
        node.execute(model=wrapped, positive=cond, seed=0, steps=1,
                     width=8, height=8)
        assert seen["shift"] == 5.5
        node.execute(model=wrapped, positive=cond, seed=0, steps=1,
                     width=8, height=8, shift=2.0)
        assert seen["shift"] == 2.0


class TestDistributedValue:
    def _run(self, **kw):
        return NODE_REGISTRY["DistributedValue"]().execute(**kw)[0]

    def test_master_gets_default(self):
        assert self._run(default_value=5, worker_values='{"1": 9}',
                         is_worker=False) == 5

    def test_worker_override_with_coercion(self):
        v = self._run(default_value=5, worker_values='{"1": "9", "_type": "INT"}',
                      is_worker=True, worker_index=0)
        assert v == 9 and isinstance(v, int)

    def test_worker_fallback_when_absent(self):
        assert self._run(default_value=5, worker_values='{"2": 9}',
                         is_worker=True, worker_index=0) == 5

    def test_bad_json_falls_back(self):
        assert self._run(default_value="d", worker_values="{oops",
                         is_worker=True, worker_index=0) == "d"

    def test_float_coercion(self):
        v = self._run(default_value=0.0, worker_values='{"2": "1.5"}',
                      value_type="FLOAT", is_worker=True, worker_index=1)
        assert v == 1.5

    def test_uncoercible_raises(self):
        with pytest.raises(ValidationError):
            self._run(default_value=0, worker_values='{"1": "abc"}',
                      value_type="INT", is_worker=True, worker_index=0)


class TestCollectorAndEmpty:
    def test_collector_identity_without_bridge(self):
        node = NODE_REGISTRY["DistributedCollector"]()
        imgs = jnp.ones((2, 4, 4, 3))
        out_imgs, out_audio = node.execute(images=imgs, multi_job_id="j1")
        assert out_imgs is imgs and out_audio is None

    def test_collector_pass_through(self):
        node = NODE_REGISTRY["DistributedCollector"]()

        class Boom:
            def send(self, *a, **k): raise AssertionError("must not send")
            def collect(self, *a, **k): raise AssertionError("must not collect")

        imgs = jnp.ones((1, 2, 2, 3))
        out, _ = node.execute(images=imgs, multi_job_id="j", pass_through=True,
                              collector_bridge=Boom())
        assert out is imgs

    def test_empty_image_zero_batch(self):
        node = NODE_REGISTRY["DistributedEmptyImage"]()
        (img,) = node.execute(height=32, width=16)
        assert img.shape == (0, 32, 16, 3)


def test_end_to_end_tiny_workflow():
    """Full graph execution: loader → clip → sharded txt2img → collector."""
    from comfyui_distributed_tpu.models.registry import ModelRegistry
    from comfyui_distributed_tpu.parallel import build_mesh

    p = {
        "1": {"class_type": "CheckpointLoader", "inputs": {"ckpt_name": "tiny"}},
        "2": {"class_type": "CLIPTextEncode", "inputs": {"text": "cat", "clip": ["1", 1]}},
        "3": {"class_type": "CLIPTextEncode", "inputs": {"text": "", "clip": ["1", 1]}},
        "4": {"class_type": "TPUTxt2Img", "inputs": {
            "model": ["1", 0], "positive": ["2", 0], "negative": ["3", 0],
            "seed": 3, "steps": 2, "cfg": 1.0, "width": 16, "height": 16}},
        "5": {"class_type": "DistributedCollector", "inputs": {"images": ["4", 0]}},
    }
    ex = GraphExecutor({
        "model_registry": ModelRegistry(),
        "mesh": build_mesh({"dp": 8}),
    })
    out = ex.execute(p)
    images = out["5"][0]
    assert images.shape == (8, 16, 16, 3)


class TestImageScaleNodes:
    def test_image_scale(self):
        import numpy as np

        from comfyui_distributed_tpu.graph.node import get_node

        img = np.random.RandomState(0).rand(2, 8, 8, 3).astype("float32")
        (out,) = get_node("ImageScale")().execute(img, width=16, height=12)
        assert np.asarray(out).shape == (2, 12, 16, 3)
        assert np.asarray(out).min() >= 0.0 and np.asarray(out).max() <= 1.0

    def test_image_scale_by(self):
        import numpy as np

        from comfyui_distributed_tpu.graph.node import get_node

        img = np.random.RandomState(1).rand(1, 8, 8, 3).astype("float32")
        (out,) = get_node("ImageScaleBy")().execute(img, scale_by=2.0)
        assert np.asarray(out).shape == (1, 16, 16, 3)

    def test_image_scale_bad_method(self):
        import numpy as np
        import pytest as _pytest

        from comfyui_distributed_tpu.graph.node import get_node
        from comfyui_distributed_tpu.utils.exceptions import ValidationError

        img = np.zeros((1, 8, 8, 3), "float32")
        with _pytest.raises(ValidationError):
            get_node("ImageScale")().execute(img, width=4, height=4,
                                             method="nope")

    def test_comfy_method_vocabulary_and_keep_aspect(self):
        import numpy as np

        from comfyui_distributed_tpu.graph.node import get_node

        img = np.random.RandomState(2).rand(1, 8, 16, 3).astype("float32")
        # ComfyUI input name + vocabulary
        (out,) = get_node("ImageScale")().execute(
            img, width=32, height=0, upscale_method="bicubic")
        assert np.asarray(out).shape == (1, 16, 32, 3)  # aspect kept
        (out2,) = get_node("ImageScaleBy")().execute(
            img, scale_by=2.0, upscale_method="nearest-exact")
        assert np.asarray(out2).shape == (1, 16, 32, 3)


def test_nodes_doc_covers_registry():
    """docs/nodes.md must mention every registered node (drift guard)."""
    from pathlib import Path

    from comfyui_distributed_tpu.graph import nodes_builtin  # noqa: F401
    from comfyui_distributed_tpu.graph.node import NODE_REGISTRY

    doc = (Path(__file__).resolve().parent.parent
           / "docs" / "nodes.md").read_text()
    missing = [n for n in NODE_REGISTRY if f"`{n}`" not in doc]
    assert not missing, f"docs/nodes.md missing nodes: {missing}"


def test_center_crop_and_negative_rejection():
    import numpy as np
    import pytest as _pytest

    from comfyui_distributed_tpu.graph.node import get_node
    from comfyui_distributed_tpu.utils.exceptions import ValidationError

    node = get_node("ImageScale")()
    img = np.random.RandomState(3).rand(1, 8, 16, 3).astype("float32")
    # center crop to square: wide source loses equal margins
    (out,) = node.execute(img, width=8, height=8, crop="center")
    assert np.asarray(out).shape == (1, 8, 8, 3)
    with _pytest.raises(ValidationError):
        node.execute(img, width=-4, height=8)
    with _pytest.raises(ValidationError):
        node.execute(img, width=8, height=8, crop="nope")
    with _pytest.raises(ValidationError):
        get_node("ImageScaleBy")().execute(img, scale_by=-1.0)
