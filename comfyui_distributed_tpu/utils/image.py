"""Image tensor ↔ PNG codecs — control-plane edge only.

Parity: reference ``utils/image.py:8-24`` (tensor[B,H,W,C] ↔ PIL) and the
base64-PNG envelope of the collector protocol (``nodes/collector.py:152-174``,
``api/job_routes.py:104-132``). In this framework these codecs are used ONLY
at the UI/cross-pod edge — on-pod results stay device arrays (SURVEY §7
translation table) — which is precisely the reference's "single biggest
overhead" eliminated (SURVEY §3 hot-loop note).
"""

from __future__ import annotations

import base64
import io
import struct
import zlib

import numpy as np

from .exceptions import ValidationError


def to_uint8(images) -> np.ndarray:
    """[B,H,W,C] float [0,1] (or uint8) → uint8, contiguous."""
    arr = np.asarray(images)
    if arr.ndim == 3:
        arr = arr[None]
    if arr.ndim != 4:
        raise ValidationError(f"expected [B,H,W,C] image batch, got shape {arr.shape}")
    if arr.dtype != np.uint8:
        arr = (np.clip(arr.astype(np.float32), 0.0, 1.0) * 255.0).round().astype(np.uint8)
    return np.ascontiguousarray(arr)


def from_uint8(arr: np.ndarray) -> np.ndarray:
    """uint8 [B,H,W,C] → float32 [0,1]."""
    return arr.astype(np.float32) / 255.0


_PNG_SIGNATURE = b"\x89PNG\r\n\x1a\n"
# channels -> PNG colour type at bit depth 8: grey, grey + alpha, RGB, RGBA
_PNG_COLOUR_TYPE = {1: 0, 2: 4, 3: 2, 4: 6}


def _png_chunk(kind: bytes, body: bytes) -> bytes:
    return (struct.pack(">I", len(body)) + kind + body
            + struct.pack(">I", zlib.crc32(body, zlib.crc32(kind))))


def encode_png(image: np.ndarray, compress_level: int = 0) -> bytes:
    """One [H,W,C] image → PNG bytes (compress_level 0 for speed, matching
    ``nodes/collector.py:156``). The file is framed here: the pixels as
    filter-0 rows in ONE ``IDAT``, deflated by zlib at ``compress_level``
    (0 stores them), so they are copied once and never read by a filter
    heuristic; any PNG reader decodes the input bit for bit."""
    arr = to_uint8(image)[0]
    height, width, channels = arr.shape
    if channels not in _PNG_COLOUR_TYPE:
        raise ValidationError(
            f"a PNG holds 1 to 4 channels, got {channels} (shape {arr.shape})")
    rows = np.zeros((height, 1 + width * channels), np.uint8)   # filter byte 0
    rows[:, 1:] = arr.reshape(height, width * channels)
    header = struct.pack(">IIBBBBB", width, height, 8,
                         _PNG_COLOUR_TYPE[channels], 0, 0, 0)
    return b"".join((
        _PNG_SIGNATURE, _png_chunk(b"IHDR", header),
        _png_chunk(b"IDAT", zlib.compress(rows, compress_level)),
        _png_chunk(b"IEND", b"")))


def decode_png(data: bytes) -> np.ndarray:
    """PNG bytes → float32 [H,W,C] in [0,1]."""
    from PIL import Image

    img = Image.open(io.BytesIO(data))
    img = img.convert("RGB") if img.mode not in ("RGB", "RGBA") else img
    return np.asarray(img, dtype=np.float32) / 255.0


def encode_image_b64(image: np.ndarray, compress_level: int = 0) -> str:
    return base64.b64encode(encode_png(image, compress_level)).decode("ascii")


def decode_image_b64(data: str) -> np.ndarray:
    try:
        raw = base64.b64decode(data)
    except Exception as e:
        raise ValidationError(f"invalid base64 image payload: {e}") from e
    return decode_png(raw)
