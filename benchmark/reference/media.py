"""Plain references for what the media path leaves behind: the
thumbnail a file should have (PIL, full-size decode, Triangle filter,
EXIF orientation applied after the resize, upstream's target size) and
the embedding an image should have (the `patchpool-v1` forward pass in
float64 NumPy, weights drawn by the recipe the configuration states).
Nothing here imports the program or reads anything it made.

The controls compute a reference one notch below what the
configuration states: `embed_forward(control=True)` rounds the matmul
operands to float8 (e4m3) instead of bfloat16; the thumbnail's control
(EXIF orientation left out) is the caller's, who passes orientation 1.
"""

from __future__ import annotations

import io
import math

import numpy as np

IMAGE_SIZE = 32
PATCH = 4
HIDDEN = 128
EMBED_DIM = 128


def scale_dimensions(w: int, h: int, target_px: int) -> tuple[int, int]:
    """Aspect-preserving (w, h) with w*h near target_px; never upscales
    (upstream crates/images/src/lib.rs `scale_dimensions`)."""
    if w * h <= target_px:
        return w, h
    ratio = math.sqrt(target_px / (w * h))
    return max(1, round(w * ratio)), max(1, round(h * ratio))


def orient(arr: np.ndarray, orientation: int) -> np.ndarray:
    """EXIF orientation 1-8 applied to an HxWxC array."""
    if orientation == 2:
        return arr[:, ::-1]
    if orientation == 3:
        return arr[::-1, ::-1]
    if orientation == 4:
        return arr[::-1]
    if orientation == 5:
        return np.transpose(arr, (1, 0, 2))
    if orientation == 6:
        return np.transpose(arr[::-1], (1, 0, 2))
    if orientation == 7:
        return np.transpose(arr[::-1, ::-1], (1, 0, 2))
    if orientation == 8:
        return np.transpose(arr[:, ::-1], (1, 0, 2))
    return arr


def thumbnail_size(w: int, h: int, orientation: int,
                   target_px: int) -> tuple[int, int]:
    """(w, h) of the stored thumbnail."""
    tw, th = scale_dimensions(w, h, target_px)
    return (th, tw) if orientation >= 5 else (tw, th)


def decode_rgba(path: str):
    from PIL import Image

    with Image.open(path) as im:
        orientation = int(im.getexif().get(0x0112, 1) or 1)
        return im.convert("RGBA"), orientation


def thumbnail_pixels(rgba, orientation: int, target_px: int) -> np.ndarray:
    """The RGB pixels the thumbnail should show, before webp."""
    from PIL import Image

    tw, th = scale_dimensions(*rgba.size, target_px)
    small = rgba.resize((tw, th), Image.BILINEAR)
    return np.ascontiguousarray(orient(np.asarray(small), orientation)[..., :3])


def encode_webp(rgb: np.ndarray, quality: int) -> bytes:
    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(rgb, "RGB").save(buf, "WEBP", quality=quality)
    return buf.getvalue()


def thumbnail_gap(webp: bytes, want_rgb: np.ndarray) -> float:
    """Mean |difference| of 255 between a stored thumbnail and the
    reference pixels; 255, as wrong as pixels can be, when the sizes
    differ."""
    from PIL import Image

    with Image.open(io.BytesIO(webp)) as im:
        got = np.asarray(im.convert("RGB"))
    if got.shape != want_rgb.shape:
        return 255.0
    return float(np.abs(got.astype(np.int16) - want_rgb.astype(np.int16)).mean())


def embed_params() -> dict[str, np.ndarray]:
    rng = np.random.Generator(np.random.PCG64(0))
    feat = (IMAGE_SIZE // PATCH) ** 2 * 3
    return {
        "w1": rng.standard_normal((feat, HIDDEN)).astype(np.float32)
        * np.float32(1.0 / np.sqrt(feat)),
        "b1": np.zeros((HIDDEN,), np.float32),
        "w2": rng.standard_normal((HIDDEN, EMBED_DIM)).astype(np.float32)
        * np.float32(1.0 / np.sqrt(HIDDEN)),
        "b2": np.zeros((EMBED_DIM,), np.float32),
    }


def embed_plane(rgba) -> np.ndarray:
    """The embedder's input: RGB at 32x32 by PIL's default resize, in
    [0, 1]; no EXIF orientation is applied (the model sees the stored
    pixels)."""
    img = rgba.convert("RGB").resize((IMAGE_SIZE, IMAGE_SIZE))
    return np.asarray(img, np.float32) / 255.0


def embed_forward(planes: np.ndarray, control: bool = False) -> np.ndarray:
    """[B, 32, 32, 3] → [B, 128] in float64; `control` rounds the
    matmul operands to float8 e4m3, the step below the bfloat16 operands
    the configuration states."""
    import ml_dtypes

    def operand(a):
        a = np.asarray(a, np.float32)
        if control:
            a = a.astype(ml_dtypes.float8_e4m3fn).astype(np.float32)
        return a.astype(np.float64)

    p = embed_params()
    b = planes.shape[0]
    g = IMAGE_SIZE // PATCH
    x = np.asarray(planes, np.float32).reshape(b, g, PATCH, g, PATCH, 3)
    x = x.mean(axis=(2, 4)).reshape(b, g * g * 3)
    h = np.tanh(operand(x) @ operand(p["w1"]) + p["b1"])
    return operand(h) @ operand(p["w2"]) + p["b2"]


def embed_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest |difference| between an embedding and its reference."""
    return float(np.abs(np.asarray(got, np.float64) - want).max())
