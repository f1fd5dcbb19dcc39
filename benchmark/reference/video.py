"""Plain reference for what a clip is owed: the thumbnail (the frame a
tenth of the way in, bound to 256 px, film strips at its sides) and the
facts of its `media_data` row. Nothing here imports the program or reads
anything it made; OpenCV decodes, NumPy does the arithmetic in float64.

Where this departs from upstream (crates/ffmpeg), and why it may:

- upstream seeks to a tenth of the duration and decodes from the key
  frame the container's index gives; here the frames are counted, one
  by one, to exactly `int(0.1 * frames)`. The two differ by up to one
  key-frame interval, so the clips are built with no cut between them
  (`generators/clip_roll.py:shots_apart`) and a small moving patch is
  all that differs.
- upstream scales inside an ffmpeg filter graph (its default bicubic
  swscale); here a triangle filter widened by the scale factor, the
  antialiased downscale the configuration states for every thumbnail.
- upstream draws a film-strip bitmap chosen by the frame's width; any
  rendering of strips leaves the outermost columns dark, and those are
  what `strips_present` looks at. The compared region leaves out the
  widest strip either draws (`strip_mask`). Upstream's widths are
  written from memory: the configuration lists them under `assumed`.
"""

from __future__ import annotations

import io

import numpy as np

MAX_DIM = 256  # upstream thumbnail/process.rs:470, sd_ffmpeg size
MARK = 0.1  # upstream movie_decoder.rs seeks a tenth of the way in
#: columns at either side that every strip rendering darkens
STRIP_EDGE = 3
#: what a strip leaves of the picture under it, at most
STRIP_DARKER = 0.5
#: an edge this dark already cannot show whether a strip lies over it
STRIP_FLOOR = 16.0


def mark_frame(frames: int) -> int:
    return int(MARK * frames)


def frame_at(path: str, index: int) -> np.ndarray:
    """Frame `index` of the clip as HxWx3 RGB uint8, by decoding from
    the first frame on: no seek, so no index or key frame is trusted."""
    import cv2

    cap = cv2.VideoCapture(path)
    try:
        if not cap.isOpened():
            raise ValueError(f"OpenCV cannot open {path}")
        for _ in range(index):
            if not cap.grab():
                raise ValueError(f"{path} ends before frame {index}")
        ok, bgr = cap.read()
        if not ok:
            raise ValueError(f"{path} has no frame {index}")
    finally:
        cap.release()
    return np.ascontiguousarray(bgr[:, :, ::-1])


def thumbnail_size(w: int, h: int, max_dim: int = MAX_DIM) -> tuple[int, int]:
    """(w, h) of the stored thumbnail: the longer side bound to
    `max_dim`, aspect kept, rounded; never upscaled."""
    if max(w, h) <= max_dim:
        return w, h
    ratio = max_dim / max(w, h)
    return max(1, round(w * ratio)), max(1, round(h * ratio))


def _triangle_weights(n_in: int, n_out: int) -> np.ndarray:
    """[n_out, n_in] rows of a triangle filter whose support is the
    scale factor (an antialiased downscale), normalised over the input
    samples that exist."""
    scale = n_in / n_out
    support = max(scale, 1.0)
    centres = (np.arange(n_out) + 0.5) * scale - 0.5
    x = (np.arange(n_in)[None, :] - centres[:, None]) / support
    w = np.clip(1.0 - np.abs(x), 0.0, None)
    return w / w.sum(axis=1, keepdims=True)


def downscale(rgb: np.ndarray, tw: int, th: int) -> np.ndarray:
    """HxWx3 uint8 → th x tw x 3 uint8, float64 in between."""
    h, w = rgb.shape[:2]
    x = _triangle_weights(h, th) @ rgb.astype(np.float64).reshape(h, w * 3)
    x = np.ascontiguousarray(x.reshape(th, w, 3).transpose(1, 0, 2))
    x = _triangle_weights(w, tw) @ x.reshape(w, th * 3)
    x = x.reshape(tw, th, 3).transpose(1, 0, 2)
    return np.clip(np.rint(x), 0, 255).astype(np.uint8)


def thumbnail_pixels(path: str, frames: int, index: int | None = None,
                     max_dim: int = MAX_DIM) -> np.ndarray:
    """The RGB pixels a clip's thumbnail should show under its strips,
    before webp. `index` and `max_dim` are the controls': another frame
    than the mark's, another bound than upstream's."""
    rgb = frame_at(path, mark_frame(frames) if index is None else index)
    h, w = rgb.shape[:2]
    return downscale(rgb, *thumbnail_size(w, h, max_dim))


def strip_mask(w: int, h: int) -> np.ndarray:
    """[h, w] True where pixels are compared: everything but an eighth
    of the width at either side, wider than the strips of upstream (16
    px of a thumbnail 193 to 384 px wide) and of the program (a tenth)."""
    keep = np.zeros((h, w), bool)
    side = -(-w // 8)
    keep[:, side:w - side] = True
    return keep


def strips_present(got_rgb: np.ndarray, want_rgb: np.ndarray) -> bool:
    """Whether both sides of a stored thumbnail carry a strip: their
    outermost columns are much darker than the frame's there."""
    for edge in (slice(0, STRIP_EDGE), slice(-STRIP_EDGE, None)):
        want = float(want_rgb[:, edge].mean())
        got = float(got_rgb[:, edge].mean())
        if want > STRIP_FLOOR and got > STRIP_DARKER * want:
            return False
    return True


def decode_webp(webp: bytes) -> tuple[str, np.ndarray]:
    from PIL import Image

    with Image.open(io.BytesIO(webp)) as im:
        return im.format, np.asarray(im.convert("RGB"))


def encode_webp(rgb: np.ndarray, quality: int) -> bytes:
    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(rgb, "RGB").save(buf, "WEBP", quality=quality)
    return buf.getvalue()


def frame_gap(got_rgb: np.ndarray, want_rgb: np.ndarray) -> float:
    """Mean |difference| of 255 between a stored thumbnail and the
    reference pixels, strips left out; 255, as wrong as pixels can be,
    when the sizes differ."""
    if got_rgb.shape != want_rgb.shape:
        return 255.0
    keep = strip_mask(want_rgb.shape[1], want_rgb.shape[0])
    diff = np.abs(got_rgb.astype(np.int16) - want_rgb.astype(np.int16))
    return float(diff[keep].mean())


def facts(video: dict) -> dict:
    """What the clip's `media_data` row should say, from the manifest's
    plan of the clip alone: no decoder is asked."""
    return {"width": video["w"], "height": video["h"], "fps": video["fps"],
            "frames": video["frames"],
            "duration_s": video["frames"] / video["fps"]}
