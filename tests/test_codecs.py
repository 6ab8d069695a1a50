"""Image/audio codec tests (parity: reference utils/image.py +
utils/audio_payload.py validation behavior)."""

import io

import numpy as np
import pytest
from PIL import Image

from comfyui_distributed_tpu.utils import audio_payload, image
from comfyui_distributed_tpu.utils.exceptions import ValidationError


def test_png_roundtrip_exact_uint8():
    rng = np.random.default_rng(0)
    img = rng.random((8, 6, 3)).astype(np.float32)
    decoded = image.decode_png(image.encode_png(img))
    assert decoded.shape == (8, 6, 3)
    # PNG is lossless over the uint8 quantization
    np.testing.assert_array_equal(image.to_uint8(decoded), image.to_uint8(img))


def _pixels(height, width, channels, dtype):
    rng = np.random.default_rng(height * 31 + width * 7 + channels)
    if dtype == "uint8":
        return rng.integers(0, 256, (height, width, channels), dtype=np.uint8)
    # past both ends of [0,1]: the quantiser clips before the framing
    return rng.random((height, width, channels), dtype=np.float32) * 1.3 - 0.15


class TestFramedPng:
    """``encode_png`` frames the file itself (ISSUE 38): signature, IHDR,
    one IDAT of filter-0 rows, IEND. Held to PIL on the reading side."""

    @pytest.mark.parametrize("dtype", ["float32", "uint8"])
    @pytest.mark.parametrize("size", [(5, 7), (6, 8), (1, 1), (33, 2)])
    @pytest.mark.parametrize("channels,mode", [(1, "L"), (2, "LA"),
                                               (3, "RGB"), (4, "RGBA")])
    def test_pil_reads_back_the_input(self, channels, mode, size, dtype):
        x = _pixels(*size, channels, dtype)
        want = image.to_uint8(x)[0]
        data = image.encode_png(x)
        Image.open(io.BytesIO(data)).verify()       # every chunk's CRC
        opened = Image.open(io.BytesIO(data))
        assert (opened.mode, opened.size) == (mode, size[::-1])
        got = np.asarray(opened).reshape(want.shape)
        np.testing.assert_array_equal(got, want)
        # and the pixels PIL's own level-0 file of the same array holds
        # (the encoder this one replaced)
        buf = io.BytesIO()
        Image.fromarray(want[..., 0] if channels == 1 else want).save(
            buf, format="PNG", compress_level=0)
        np.testing.assert_array_equal(
            got, np.asarray(Image.open(buf)).reshape(want.shape))

    @pytest.mark.parametrize("level", [1, 6, 9])
    @pytest.mark.parametrize("channels", [1, 3, 4])
    def test_a_level_is_zlibs_argument(self, channels, level):
        x = np.broadcast_to(np.arange(64, dtype=np.uint8)[None, :, None],
                            (48, 64, channels))
        stored, deflated = image.encode_png(x), image.encode_png(x, level)
        assert len(deflated) < len(stored)
        for data in (stored, deflated):
            Image.open(io.BytesIO(data)).verify()
            np.testing.assert_array_equal(
                np.asarray(Image.open(io.BytesIO(data))).reshape(x.shape), x)

    def test_the_file_is_four_chunks_and_its_rows(self):
        x = _pixels(6, 8, 3, "uint8")
        data = image.encode_png(x)
        assert data[:8] == b"\x89PNG\r\n\x1a\n"
        kinds, at = [], 8
        while at < len(data):
            size = int.from_bytes(data[at:at + 4], "big")
            kinds.append(data[at + 4:at + 8])
            at += 12 + size
        assert kinds == [b"IHDR", b"IDAT", b"IEND"] and at == len(data)
        # stored, not deflated: the file is the pixels plus a fixed frame
        assert len(data) - x.size < 100

    @pytest.mark.parametrize("shape", [(4, 4, 5), (4, 4, 0), (2, 2, 4, 4, 3),
                                       (4,), ()])
    def test_what_is_no_image_is_refused(self, shape):
        with pytest.raises(ValidationError):
            image.encode_png(np.zeros(shape, np.float32))

    def test_a_batch_gives_its_first_image(self):
        x = _pixels(4, 4, 3, "uint8")
        assert image.encode_png(np.stack([x, 255 - x])) == image.encode_png(x)

    def test_the_encode_side_does_not_import_pil(self):
        import ast
        import inspect
        tree = ast.parse(inspect.getsource(image))
        importers = {fn.name for fn in ast.walk(tree)
                     if isinstance(fn, ast.FunctionDef)
                     for node in ast.walk(fn)
                     if isinstance(node, (ast.Import, ast.ImportFrom))
                     and "PIL" in ast.dump(node)}
        assert importers == {"decode_png"}
        assert not any(isinstance(node, (ast.Import, ast.ImportFrom))
                       and "PIL" in ast.dump(node) for node in tree.body)


def test_b64_roundtrip_and_invalid():
    img = np.zeros((4, 4, 3), np.float32)
    s = image.encode_image_b64(img)
    out = image.decode_image_b64(s)
    assert out.shape == (4, 4, 3)
    with pytest.raises(ValidationError):
        image.decode_image_b64("!!!notbase64!!!")


def test_to_uint8_shape_validation():
    with pytest.raises(ValidationError):
        image.to_uint8(np.zeros((2, 2)))


def test_audio_roundtrip():
    wf = np.random.default_rng(1).standard_normal((1, 2, 100)).astype(np.float32)
    env = audio_payload.encode_audio({"waveform": wf, "sample_rate": 22050})
    back = audio_payload.decode_audio(env)
    np.testing.assert_array_equal(back["waveform"], wf)
    assert back["sample_rate"] == 22050


@pytest.mark.parametrize("mutate", [
    lambda e: e.pop("data"),
    lambda e: e.pop("shape"),
    lambda e: e.update(shape=[1, 2]),
    lambda e: e.update(dtype="float64"),
    lambda e: e.update(data=e["data"][:-8]),
])
def test_audio_envelope_validation(mutate):
    wf = np.zeros((1, 1, 10), np.float32)
    env = audio_payload.encode_audio({"waveform": wf, "sample_rate": 8000})
    mutate(env)
    with pytest.raises(ValidationError):
        audio_payload.decode_audio(env)


def test_audio_cap_enforced(monkeypatch):
    monkeypatch.setattr(audio_payload.constants, "MAX_AUDIO_PAYLOAD_BYTES", 16)
    wf = np.zeros((1, 1, 100), np.float32)
    with pytest.raises(ValidationError):
        audio_payload.encode_audio({"waveform": wf, "sample_rate": 8000})


class TestWavCodec:
    """Stdlib WAV file codec (LoadAudio/SaveAudio nodes)."""

    def test_roundtrip_stereo(self):
        from comfyui_distributed_tpu.utils.audio_payload import (wav_bytes,
                                                                 wav_decode)

        t = np.linspace(0, 1, 4410, dtype=np.float32)
        clip = np.stack([np.sin(t * 440), np.cos(t * 440)]) * 0.7
        out = wav_decode(wav_bytes(clip, 22050))
        assert out["sample_rate"] == 22050
        assert out["waveform"].shape == (1, 2, 4410)
        np.testing.assert_allclose(out["waveform"][0], clip, atol=2e-4)

    def test_mono_1d_accepted(self):
        from comfyui_distributed_tpu.utils.audio_payload import (wav_bytes,
                                                                 wav_decode)

        clip = np.zeros((100,), np.float32)
        out = wav_decode(wav_bytes(clip, 8000))
        assert out["waveform"].shape == (1, 1, 100)

    def test_clipping_bounded(self):
        from comfyui_distributed_tpu.utils.audio_payload import (wav_bytes,
                                                                 wav_decode)

        clip = np.full((1, 10), 3.0, np.float32)   # out of range → clipped
        out = wav_decode(wav_bytes(clip, 8000))
        assert np.all(out["waveform"] <= 1.0)

    def test_invalid_wav_raises(self):
        from comfyui_distributed_tpu.utils.audio_payload import wav_decode
        from comfyui_distributed_tpu.utils.exceptions import ValidationError

        with pytest.raises(ValidationError, match="invalid WAV"):
            wav_decode(b"not a wav file")

    def test_bad_shape_raises(self):
        from comfyui_distributed_tpu.utils.audio_payload import wav_bytes
        from comfyui_distributed_tpu.utils.exceptions import ValidationError

        with pytest.raises(ValidationError, match="C,S"):
            wav_bytes(np.zeros((1, 2, 3), np.float32), 8000)
