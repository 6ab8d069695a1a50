"""A batch's images are saved side by side (ISSUE 33), each from the
chip that holds it (ISSUE 38).

``SaveImage`` gives every image of several to a worker of its module's
pool: fetch, quantise, encode, write. Of a device array a worker copies
the one addressable shard that holds its image; the batch as a whole never
comes to the host and no device program runs for it. Held here to the
loop it replaces (kept below as the reference): the same file names in
the same order with the same bytes, whatever the batch is made of; one
image never leaves the calling thread; a failing image surfaces as the
loop's would, after the others have finished; the spans of every image
hang under the caller's span; two calls at once share the pool without
waiting on each other for ever.
"""

import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from comfyui_distributed_tpu import telemetry
from comfyui_distributed_tpu.graph import nodes_builtin
from comfyui_distributed_tpu.graph.nodes_builtin import SaveImage
from comfyui_distributed_tpu.utils import image as image_mod
from comfyui_distributed_tpu.utils.exceptions import ValidationError
from comfyui_distributed_tpu.utils.image import encode_png, to_uint8

H, W = 24, 40


def serial_loop(images, out_dir: Path, prefix: str) -> list[Path]:
    """``SaveImage.execute`` as it was before the pool: the reference."""
    arr = to_uint8(images)
    paths = []
    for i in range(arr.shape[0]):
        p = out_dir / f"{prefix}_{i:05d}.png"
        p.write_bytes(encode_png(arr[i]))
        paths.append(p)
    return paths


def pixels(n: int, dtype: str) -> np.ndarray:
    rng = np.random.default_rng(33 + n)
    if dtype == "uint8":
        return rng.integers(0, 256, (n, H, W, 3), dtype=np.uint8)
    # past both ends of [0,1], and on the .5 levels where rounding decides
    x = rng.random((n, H, W, 3), dtype=np.float32) * 1.3 - 0.15
    x[:, 0, :, 0] = (np.arange(W, dtype=np.float32) + 0.5) / 255.0
    return x


def placed(x: np.ndarray, how: str):
    """The batch as a node may hand it over."""
    if how == "numpy":
        return x
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if how == "one_device":
        return jax.device_put(x, jax.devices()[0])
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))     # a fan-out's output
    spec = {"dp_rows": P("dp", None, None, None),
            "replicated": P(),
            # every image split over the chips: no shard holds one whole
            "split_height": P(None, "dp", None, None)}[how]
    return jax.device_put(x, NamedSharding(mesh, spec))


def on_device(how: str) -> bool:
    return how != "numpy" and how != "single_hwc"


@pytest.fixture
def counted():
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    telemetry.SPAN_STORE.reset()

    def saved() -> dict:
        snapshot = telemetry.REGISTRY.snapshot()
        by_mode = {s["labels"]["mode"]: s["value"] for s in snapshot.get(
            "cdt_image_save_images_total", {}).get("series", [])}
        return {"pooled": by_mode.get("pooled", 0),
                "inline": by_mode.get("inline", 0)}

    yield saved
    telemetry.SPAN_STORE.reset()
    telemetry.set_enabled(was)


class TestBytesEqualTheSerialLoop:
    @pytest.mark.parametrize("dtype", ["float32", "uint8"])
    @pytest.mark.parametrize("how,n", [
        ("numpy", 4), ("dp_rows", 4), ("dp_rows", 8), ("one_device", 4),
        ("numpy", 3), ("replicated", 4), ("replicated", 3),
        ("split_height", 4)])
    def test_names_order_and_bytes(self, tmp_path, counted, how, n, dtype):
        x = pixels(n, dtype)
        images = placed(x, how)
        (tmp_path / "ref").mkdir()
        want = serial_loop(x, tmp_path / "ref", "img")
        before = counted()
        assert SaveImage().execute(images, filename_prefix="img",
                                   output_dir=str(tmp_path / "out")) == ()
        got = sorted((tmp_path / "out").iterdir())
        assert [p.name for p in got] == [p.name for p in want]
        for g, w in zip(got, want):
            assert g.read_bytes() == w.read_bytes(), g.name
        after = counted()
        assert after["pooled"] - before["pooled"] == n
        assert after["inline"] == before["inline"]


class TestOneImageStaysOnTheCallingThread:
    @pytest.mark.parametrize("how", ["numpy", "one_device", "dp_rows_1",
                                     "single_hwc"])
    def test_inline(self, tmp_path, counted, monkeypatch, how):
        x = pixels(1, "float32")
        images = {"single_hwc": lambda: x[0],
                  "dp_rows_1": lambda: placed(pixels(4, "float32"),
                                              "dp_rows")[:1]}.get(
            how, lambda: placed(x, how))()
        want = serial_loop(np.asarray(images), tmp_path, "ref")[0]

        def no_pool(*a, **k):
            raise AssertionError("a batch of one went to the pool")

        monkeypatch.setattr(nodes_builtin._SAVE_POOL, "submit", no_pool)
        threads = []
        real = image_mod.encode_png

        def seen(*a, **k):
            threads.append(threading.get_ident())
            return real(*a, **k)

        monkeypatch.setattr(image_mod, "encode_png", seen)
        before = counted()
        with telemetry.span("node.SaveImage", trace_id="exec_one"):
            SaveImage().execute(images, filename_prefix="one",
                                output_dir=str(tmp_path))
        assert (tmp_path / "one_00000.png").read_bytes() == want.read_bytes()
        assert threads == [threading.get_ident()]
        after = counted()
        assert (after["inline"] - before["inline"],
                after["pooled"] - before["pooled"]) == (1, 0)
        names = sorted(s["name"] for s in telemetry.SPAN_STORE.spans("exec_one"))
        assert names == ["image.encode_png"] + (
            ["image.fetch"] if on_device(how) else []) + [
            "image.write", "node.SaveImage"]


class TestAFailingImage:
    @pytest.mark.parametrize("blocked,first", [((2,), 2), ((3, 1), 1),
                                               ((0,), 0)])
    def test_surfaces_after_the_others_finished(self, tmp_path, counted,
                                                monkeypatch, blocked, first):
        x = pixels(4, "float32")
        for i in blocked:                   # a directory where a file goes
            (tmp_path / f"bad_{i:05d}.png").mkdir()
        running = []
        encode, quantise = image_mod.encode_png, image_mod.to_uint8

        def counted_encode(*a, **k):
            running.append(1)
            try:
                return encode(*a, **k)
            finally:
                running.pop()

        def late_quantise(images):
            # the images that will be written start after the failing
            # ones have failed: an abandoned task would still be running
            if images.dtype == np.float32 and not any(
                    np.array_equal(images, x[i]) for i in blocked):
                time.sleep(0.1)
            return quantise(images)

        monkeypatch.setattr(image_mod, "encode_png", counted_encode)
        monkeypatch.setattr(image_mod, "to_uint8", late_quantise)
        before = counted()
        with pytest.raises(IsADirectoryError) as raised:
            SaveImage().execute(placed(x, "dp_rows"), filename_prefix="bad",
                                output_dir=str(tmp_path))
        # the lowest failing index, as the loop would have raised it
        assert f"bad_{first:05d}.png" in str(raised.value)
        # the others were waited for, not abandoned: nothing still runs
        assert running == []
        (tmp_path / "ref").mkdir()
        want = serial_loop(x, tmp_path / "ref", "bad")
        for i in set(range(4)) - set(blocked):
            assert (tmp_path / f"bad_{i:05d}.png").read_bytes() \
                == want[i].read_bytes()
        assert counted() == before          # nothing counted as saved
        # and the pool still serves the next batch
        SaveImage().execute(x, filename_prefix="next",
                            output_dir=str(tmp_path))
        assert len(list(tmp_path.glob("next_*.png"))) == 4


class TestSpansOfEveryImage:
    @pytest.mark.parametrize("how", ["dp_rows", "numpy"])
    def test_both_phases_of_an_image_under_the_callers_span(
            self, tmp_path, counted, how):
        events = []

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                events.append((self.name, threading.get_ident()))

            def __exit__(self, *exc):
                pass

        telemetry.set_annotator(Annotation)
        try:
            with telemetry.span("node.SaveImage", trace_id="exec_four",
                                node_id="7") as (_, node_id):
                SaveImage().execute(placed(pixels(4, "float32"), how),
                                    output_dir=str(tmp_path))
        finally:
            telemetry.set_annotator(None)
        spans = telemetry.SPAN_STORE.spans("exec_four")
        by_name: dict = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        phases = ["image.encode_png", "image.write"] + (
            ["image.fetch"] if on_device(how) else [])
        assert {k: len(v) for k, v in by_name.items()} == {
            "node.SaveImage": 1, **{name: 4 for name in phases}}
        for name in phases:
            assert {s["parent_id"] for s in by_name[name]} == {node_id}, name
        assert all(int(s["attrs"]["bytes"]) > 0 for s in by_name["image.write"])
        # mirrored for the profiler on the workers' own threads
        mirrored = [(n, t) for n, t in events if n.startswith("cdt.image.")]
        assert len(mirrored) == 4 * len(phases)
        assert threading.get_ident() not in {t for _, t in mirrored}


COMPILES = []          # every backend compile of this process, once listened for


@pytest.fixture(scope="module")
def compiles():
    import jax.monitoring

    def on_duration(event: str, seconds: float, **_):
        if event.endswith("backend_compile_duration"):
            COMPILES.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return COMPILES


def host_copies(monkeypatch, on_copy=lambda: None) -> list:
    """The jax arrays ``SaveImage``'s module brings to the host from now
    on, in order: every such copy there goes through ``np.asarray``."""
    import jax

    real, asked = np.asarray, []

    def seen(a, *args, **kwargs):
        if isinstance(a, jax.Array):
            asked.append(a)
            on_copy()
        return real(a, *args, **kwargs)

    monkeypatch.setattr(nodes_builtin.np, "asarray", seen)
    return asked


class TestEachWorkerFetchesItsOwnShard:
    """ISSUE 38: a device batch leaves its chips shard by shard, each on
    the worker that saves the image; nothing gathers the whole."""

    @pytest.mark.parametrize("dtype", ["float32", "uint8"])
    @pytest.mark.parametrize("how,n,shards_read", [
        ("dp_rows", 4, 4), ("dp_rows", 8, 4), ("replicated", 4, 1),
        ("one_device", 4, 1)])
    def test_no_whole_batch_copy_and_no_device_program(
            self, tmp_path, counted, monkeypatch, compiles, how, n,
            shards_read, dtype):
        x = pixels(n, dtype)[:, :H - 1, :W - 3]   # a shape no other test has
        images = placed(x, how)
        whole = [s.data for s in images.addressable_shards
                 if s.data.shape == images.shape]
        (tmp_path / "ref").mkdir()
        want = serial_loop(x, tmp_path / "ref", "img")
        asked = host_copies(monkeypatch)
        compiled_before = len(compiles)
        with telemetry.span("node.SaveImage", trace_id="exec_fetch") as (
                _, node_id):
            SaveImage().execute(images, filename_prefix="img",
                                output_dir=str(tmp_path / "out"))
        ran = compiles[compiled_before:]
        monkeypatch.undo()
        # indexing a device array eagerly would have compiled a slice
        assert ran == []
        # only shards were copied: the batch itself never (a one-device or
        # replicated batch IS one shard: its buffer, not a gather)
        assert all(any(a is s.data for s in images.addressable_shards)
                   for a in asked)
        assert not any(a is images for a in asked)
        assert len({id(a) for a in asked}) == shards_read
        assert len(asked) == n
        if not whole:
            assert {a.shape[0] for a in asked} == {n // 4}
        fetches = [s for s in telemetry.SPAN_STORE.spans("exec_fetch")
                   if s["name"] == "image.fetch"]
        assert len(fetches) == n
        assert {s["parent_id"] for s in fetches} == {node_id}
        assert {int(s["attrs"]["bytes"]) for s in fetches} == {x[0].nbytes}
        got = sorted((tmp_path / "out").iterdir())
        assert [p.read_bytes() for p in got] == [p.read_bytes() for p in want]

    def test_the_fetches_of_a_fan_out_run_on_four_threads(
            self, tmp_path, counted, monkeypatch):
        if nodes_builtin._SAVE_POOL._max_workers < 4:
            pytest.skip("the pool of this host has fewer than four workers")
        # four workers inside their fetch at once: each waits for the others
        inside = threading.Barrier(4, timeout=30)
        threads = []

        def arrived():
            threads.append(threading.get_ident())
            inside.wait()

        host_copies(monkeypatch, on_copy=arrived)
        SaveImage().execute(placed(pixels(4, "float32"), "dp_rows"),
                            output_dir=str(tmp_path))
        monkeypatch.undo()
        assert len(set(threads)) == 4
        assert threading.get_ident() not in threads

    def test_an_image_split_over_chips_is_gathered_once(
            self, tmp_path, counted, monkeypatch):
        asked = host_copies(monkeypatch)
        images = placed(pixels(4, "float32"), "split_height")
        with telemetry.span("node.SaveImage", trace_id="exec_split"):
            SaveImage().execute(images, output_dir=str(tmp_path))
        monkeypatch.undo()
        assert len(asked) == 1 and asked[0] is images
        assert "image.fetch" not in {
            s["name"] for s in telemetry.SPAN_STORE.spans("exec_split")}


class TestTwoCallsAtOnce:
    def test_share_the_pool_without_deadlock(self, tmp_path):
        # more images than the pool has workers, from more callers than
        # cores, switching threads often
        n = nodes_builtin._SAVE_POOL._max_workers + 3
        x = pixels(n, "float32")
        (tmp_path / "ref").mkdir()
        want = [p.read_bytes() for p in serial_loop(x, tmp_path / "ref", "c")]
        errors = []

        def call(k):
            try:
                for r in range(3):
                    SaveImage().execute(
                        x, filename_prefix="c",
                        output_dir=str(tmp_path / f"t{k}_{r}"))
            except BaseException as e:      # noqa: BLE001 - reported below
                errors.append(e)

        was = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            callers = [threading.Thread(target=call, args=(k,))
                       for k in range(4)]
            for t in callers:
                t.start()
            for t in callers:
                t.join(timeout=120)
            assert not [t for t in callers if t.is_alive()]
        finally:
            sys.setswitchinterval(was)
        assert errors == []
        for k in range(4):
            for r in range(3):
                got = sorted((tmp_path / f"t{k}_{r}").iterdir())
                assert [p.read_bytes() for p in got] == want


class TestWhatIsNotAnImage:
    @pytest.mark.parametrize("shape", [(H, W), (2, 2, H, W, 3), ()])
    def test_is_refused_before_anything_is_written(self, tmp_path, shape):
        with pytest.raises(ValidationError, match="image batch"):
            SaveImage().execute(np.zeros(shape, np.float32),
                                output_dir=str(tmp_path))
        assert list(tmp_path.iterdir()) == []

    def test_an_empty_batch_writes_nothing(self, tmp_path, counted):
        before = counted()
        assert SaveImage().execute(np.zeros((0, H, W, 3), np.float32),
                                   output_dir=str(tmp_path)) == ()
        assert list(tmp_path.iterdir()) == []
        assert counted() == before
