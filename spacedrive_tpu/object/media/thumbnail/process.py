"""Thumbnail generation pipeline: CPU decode → TPU batch resize → webp.

Parity: ref:core/src/object/media/thumbnail/process.rs:394-473
(`generate_image_thumbnail` / `generate_video_thumbnail`) and
ref:crates/ffmpeg/src/movie_decoder.rs (video: preferred stream, seek
~10%, decode one frame, rotation-aware scale).

The TPU-first difference from the reference: decode stays on host
threads, but *all* resampling runs as batched `scale_and_translate`
device calls (spacedrive_tpu/ops/thumbnail_jax.py) — one compiled
program per size bucket instead of a per-image CPU resize pool.
"""

from __future__ import annotations

import io
import logging
import math
import os
import time
from dataclasses import dataclass
from typing import Any, Callable

import numpy as np

from ....ops import thumbnail_jax as tj
from ....telemetry import metrics as _tm
from ....telemetry import span

logger = logging.getLogger(__name__)

WEBP_QUALITY = 30  # ref:process.rs:440
from ..images import MAXIMUM_FILE_SIZE as MAX_FILE_SIZE  # ref:consts.rs:9

#: the longest side the device path takes whole (`benchmark/warm.py`
#: reads it by this name)
MAX_DIM = tj.MAX_SIDE
#: decoded frames one chunk of the thumbnailer holds on the host: the
#: bytes its widest device call takes (`chunk_len` cuts a chunk there)
CHUNK_FRAME_BYTES = tj.CALL_CANVAS_BYTES


def shrink_to_max_dim(arr: "np.ndarray") -> "np.ndarray":
    """Every frame on its way to the resize passes here and is counted:
    `whole` as the decoder handed it on, or, with a side over MAX_DIM,
    `thinned` to every `step`-th row and column so that the largest
    canvas holds it (unfiltered: what a panorama of 30,000 pixels still
    pays). Whether the reference rejects a picture over some size
    (crates/images/src/consts.rs:33, cited from memory) cannot be
    checked here: no copy of upstream is at hand."""
    h, w = arr.shape[:2]
    if max(h, w) > MAX_DIM:
        step = math.ceil(max(h, w) / MAX_DIM)
        arr = np.ascontiguousarray(arr[::step, ::step])
        _tm.THUMB_FRAMES.inc(path="thinned")
    else:
        _tm.THUMB_FRAMES.inc(path="whole")
    return arr

# Decodable subsets of the taxonomy (the taxonomy stays the single
# source of truth, ref:crates/file-ext; the reference fans out to the
# `image` crate / libheif / resvg / pdfium by extension,
# ref:crates/images/src/handler.rs:18-60 — here PIL takes the generic
# formats, HEIF goes through the ctypes binding over the system's
# libheif (`images.decode_heif`) and is listed only where that library
# loads, SVG and PDF ride their own renderers below).
from ....files.extensions import all_extensions as _all_extensions

_PIL_DECODABLE = {
    "jpg", "jpeg", "png", "gif", "bmp", "tiff", "tif", "webp", "ico",
    "apng",
}
_CV2_DECODABLE = {
    "mp4", "mov", "avi", "mkv", "webm", "m4v", "mpg", "mpeg", "mpe",
    "wmv", "flv", "3gp", "ogv", "mts", "m2ts", "m2v", "ts", "vob", "qt",
}
from ..images import HEIF_EXTENSIONS, draft_jpeg, format_image, heif_available
from ..svg import svg_available

IMAGE_EXTENSIONS = tuple(
    e for e in _all_extensions("Image") if e in _PIL_DECODABLE
) + (tuple(e for e in _all_extensions("Image") if e in HEIF_EXTENSIONS)
     if heif_available() else ())
# The native libav frontend (preferred, probed lazily at first decode
# so imports never trigger a compile) handles the full video taxonomy;
# exotic containers degrade to a per-file error on cv2-only hosts.
VIDEO_EXTENSIONS = tuple(_all_extensions("Video"))
# Document/vector formats (ref:crates/images/src/handler.rs:18-60 fans
# out to resvg + pdfium; here: librsvg via ctypes + the bundled PDF
# reader in ../pdf.py). The extension sets live in ..images — the
# single dispatch — gated here by renderer availability.
from ..images import PDF_EXTENSIONS as _PDF_EXTS
from ..images import SVG_EXTENSIONS as _SVG_EXTS

SVG_EXTENSIONS = tuple(sorted(_SVG_EXTS)) if svg_available() else ()
PDF_EXTENSIONS = tuple(sorted(_PDF_EXTS))
DOC_EXTENSIONS = PDF_EXTENSIONS + SVG_EXTENSIONS
VIDEO_SEEK_FRACTION = 0.1  # ref:movie_decoder.rs seeks ~10% in


class ThumbError(Exception):
    pass


# What a caller may hang on a still image's decode: called on the
# decoding thread with the frame as decoded (RGB or RGBA, before
# `shrink_to_max_dim`, EXIF orientation not applied: the PIL image
# where PIL decoded it, so the caller reads the decoder's own buffer,
# the uint8 array where libheif did) and the DCT scale it was decoded
# at. Video and document frames are not offered.
FrameTap = Callable[[Any, int], None]


@dataclass
class Decoded:
    """One decoded frame ready for the device batch."""
    # uint8, HxWx3 RGB or HxWx4 RGBA where there is alpha; None once the
    # device stage has resized it (the encode stage needs the rest only)
    array: np.ndarray | None
    target: tuple[int, int]  # (th, tw) scaled dims
    orientation: int = 1
    is_video: bool = False  # film-strip overlay on finish


def can_generate(extension: str | None) -> bool:
    e = (extension or "").lower()
    return e in IMAGE_EXTENSIONS or e in VIDEO_EXTENSIONS or \
        e in DOC_EXTENSIONS


def is_video(extension: str | None) -> bool:
    return (extension or "").lower() in VIDEO_EXTENSIONS


def decode_image(path: str, tap: FrameTap | None = None) -> Decoded:
    """Decode a still image to RGB, or to RGBA where the file has an
    alpha band (or a palette's transparency), reading EXIF orientation.

    Uses JPEG draft-mode DCT scaling so huge photos decode near the
    target size instead of full-res (the decode-side analogue of the
    reference's resize-after-full-decode; output parity is held by the
    device resample, which always produces `scale_dimensions` dims).
    The request is `images.draft_jpeg`'s, the one every decode of a
    JPEG makes, and `tap` sees the frame it gave, before the array is
    made and `shrink_to_max_dim` thins it.
    """
    from PIL import Image

    if os.path.getsize(path) > MAX_FILE_SIZE:
        raise ThumbError(f"file over {MAX_FILE_SIZE} bytes: {path}")
    with Image.open(path) as img:
        w0, h0 = img.size
        tw, th = tj.scale_dimensions(w0, h0)
        orientation = 1
        try:
            orientation = int(img.getexif().get(0x0112, 1) or 1)
        except Exception:
            pass
        scale = draft_jpeg(img)  # smallest DCT scale ≥ target
        img = img.convert("RGBA" if img.has_transparency_data else "RGB")
        if tap is not None:
            tap(img, scale)
        arr = np.asarray(img)
    arr = shrink_to_max_dim(arr)
    h, w = arr.shape[:2]
    if min(h, w) < 1:
        raise ThumbError(f"empty image: {path}")
    return Decoded(array=arr, target=(th, tw), orientation=orientation)


def host_resize_reason(d: Decoded) -> str | None:
    """Why a frame cannot take the batched device path and resizes on
    the host: `aspect` for a target beyond the output canvases (over
    16:1), `size` for a frame no canvas holds; None for every other."""
    if tj.out_canvas_for(*d.target) is None:
        return "aspect"
    if tj.bucket_for(*d.array.shape[:2]) is None:
        return "size"
    return None


def needs_cpu_fallback(d: Decoded) -> bool:
    """Whether the frame resizes on the host (`host_resize_reason`)."""
    return host_resize_reason(d) is not None


def decodes_full_size(extension: str | None) -> bool:
    """Whether `decode` hands on the file's every pixel: a still that is
    no JPEG (a JPEG decodes in draft mode, near its target), no clip's
    frame and no document's render (both thinned or capped to MAX_DIM
    and under)."""
    e = (extension or "").lower()
    return e in IMAGE_EXTENSIONS and e not in ("jpg", "jpeg")


def frame_bytes(path: str, extension: str | None) -> int:
    """nbytes of the frame `decode` will hand on, from the file's header
    alone; 0 where `decodes_full_size` says the decoder bounds it, and
    for a file whose header cannot be read (its decode says why)."""
    if not decodes_full_size(extension):
        return 0
    try:
        if (extension or "").lower() in HEIF_EXTENSIONS:
            from ..images import heif_frame_shape

            h, w, channels = heif_frame_shape(path)
        else:
            from PIL import Image

            with Image.open(path) as img:
                w, h = img.size
                channels = 4 if img.has_transparency_data else 3
    except Exception:
        return 0
    return h * w * channels


def chunk_len(entries: list[tuple[str, str, str]]) -> int:
    """How many of `entries` ((cas_id, path, extension), in order) one
    chunk of the thumbnailer takes: all of them, unless the frames their
    decodes hand on pass CHUNK_FRAME_BYTES before the last (32 decoded
    48 MP photos are 4.7 GB); then those before the one that passes it,
    and always one."""
    held = 0
    for n, (_cas_id, path, ext) in enumerate(entries):
        held += frame_bytes(path, ext)
        if held > CHUNK_FRAME_BYTES and n:
            return n
    return len(entries)


def _video_frame_native(path: str) -> tuple[np.ndarray, int, bool]:
    from ....native import video_frame

    try:
        return video_frame(path, seek_fraction=VIDEO_SEEK_FRACTION)
    except ValueError as exc:
        raise ThumbError(str(exc))


def _video_frame_cv2(path: str) -> np.ndarray:
    """The frame exactly ~10% in, BGR as cv2 hands it on."""
    try:
        import cv2
    except Exception as e:  # pragma: no cover
        raise ThumbError(f"video decode unavailable: {e}")
    cap = cv2.VideoCapture(path)
    try:
        if not cap.isOpened():
            raise ThumbError(f"cannot open video: {path}")
        frames = cap.get(cv2.CAP_PROP_FRAME_COUNT) or 0
        if frames > 0:
            cap.set(cv2.CAP_PROP_POS_FRAMES, int(frames * VIDEO_SEEK_FRACTION))
        ok, frame = cap.read()
        if not ok:
            # fall back to the first frame (seek can fail near EOF)
            cap.set(cv2.CAP_PROP_POS_FRAMES, 0)
            ok, frame = cap.read()
        if not ok or frame is None:
            raise ThumbError(f"no decodable frame: {path}")
    finally:
        cap.release()
    return frame


def decode_video_frame(path: str) -> Decoded:
    """Grab one frame ~10% into the video through the native FFmpeg
    frontend (native/movie_decoder.c — preferred stream with
    embedded-cover preference, ~10% seek, display-matrix rotation;
    ref:movie_decoder.rs:32-629, cover check :352), with cv2 as the
    fallback when libav isn't present. The frame is RGB (RGBA only
    where libav found alpha in the decoded format: a PNG cover), so a
    clip rides the device resize as three planes in one call. Target
    dims bound the max dimension to 256 (ref:process.rs:470)."""
    from ....native import video_available

    by_libav = video_available()
    rotation, is_cover = 0, False
    try:
        with span("video.frame") as grab:
            if by_libav:
                arr, rotation, is_cover = _video_frame_native(path)
            else:
                arr = _video_frame_cv2(path)
    except ThumbError:
        _tm.THUMB_VIDEO_FRAMES.inc(
            decoder="native" if by_libav else "cv2", result="error")
        raise
    t0 = time.perf_counter()
    if by_libav:
        if rotation % 360 and rotation % 90 == 0:
            # display matrix says rotate clockwise by `rotation`; only
            # right-angle rotations are meaningful for a raster thumb
            arr = np.ascontiguousarray(
                np.rot90(arr, k=(-rotation // 90) % 4)
            )
        arr = shrink_to_max_dim(arr)
    else:
        arr = np.ascontiguousarray(
            shrink_to_max_dim(arr[:, :, ::-1]))  # BGR → RGB
    h, w = arr.shape[:2]
    tw, th = tj.video_dimensions(w, h)
    _tm.THUMB_VIDEO_SECONDS.inc(grab.duration, part="frame")
    _tm.THUMB_VIDEO_SECONDS.inc(time.perf_counter() - t0, part="orient")
    _tm.THUMB_VIDEO_FRAMES.inc(
        decoder="native" if by_libav else "cv2", result="ok")
    _tm.THUMB_VIDEO_BYTES.inc(arr.nbytes)
    # embedded cover art is album art, not footage: no film strip
    return Decoded(array=arr, target=(th, tw), is_video=not is_cover)


def decode_heif_image(path: str, extension: str,
                      tap: FrameTap | None = None) -> Decoded:
    """HEIC/HEIF/AVIF through the libheif dispatch (ref:crates/images
    HEIF handler), at the picture's full size (libheif scales nothing
    on its way out) and with the channels the file holds: RGB for a
    photo, so it rides the device resize as three planes in one call,
    RGBA only where the handle reports an alpha channel. Orientation
    is baked in by libheif's transforms (the container's `irot`/`imir`),
    so the EXIF tag, which says the same, is not applied again. A file
    libheif does not take is a ThumbError: it costs its own thumbnail,
    never its batch's."""
    try:
        with span("heif.decode") as call:
            arr = format_image(path, extension)
    except Exception as exc:
        _tm.THUMB_HEIF_FRAMES.inc(result="error")
        raise ThumbError(f"heif decode failed ({path}): {exc}")
    _tm.THUMB_HEIF_SECONDS.inc(call.duration, part="decode")
    if tap is not None:
        t0 = time.perf_counter()
        tap(arr, 1)
        _tm.THUMB_HEIF_SECONDS.inc(time.perf_counter() - t0, part="plane")
    arr = shrink_to_max_dim(arr)
    h, w = arr.shape[:2]
    tw, th = tj.scale_dimensions(w, h)
    _tm.THUMB_HEIF_FRAMES.inc(result="ok")
    _tm.THUMB_HEIF_BYTES.inc(arr.nbytes)
    return Decoded(array=arr, target=(th, tw))


def decode_document(path: str, extension: str) -> Decoded:
    """SVG (ref:svg.rs:14-21, render cap 512²) and PDF first page
    (ref:pdf.rs:82-83) through the format_image dispatch; every
    failure becomes ThumbError so one bad document never aborts the
    surrounding batch."""
    try:
        arr = format_image(path, extension)
    except Exception as exc:
        raise ThumbError(f"document decode failed ({path}): {exc}")
    arr = shrink_to_max_dim(arr)
    h, w = arr.shape[:2]
    tw, th = tj.scale_dimensions(w, h)
    return Decoded(array=arr, target=(th, tw))


def decode(path: str, extension: str | None,
           tap: FrameTap | None = None) -> Decoded:
    ext = (extension or "").lower()
    if is_video(extension):
        return decode_video_frame(path)
    if ext in HEIF_EXTENSIONS:
        return decode_heif_image(path, extension, tap)
    if ext in SVG_EXTENSIONS or ext in PDF_EXTENSIONS:
        return decode_document(path, ext)
    return decode_image(path, tap)


def encode_webp(arr: np.ndarray, quality: int = WEBP_QUALITY) -> bytes:
    """RGB or RGBA uint8 (by the array's channels: an image with
    transparency keeps it) → webp bytes at the reference's quality 30
    (ref:process.rs:431-440)."""
    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, "WEBP", quality=quality)
    return buf.getvalue()


def apply_film_strip(arr: np.ndarray) -> np.ndarray:
    """Sprocket-hole side strips marking video thumbs
    (ref:crates/ffmpeg/src/film_strip.rs draws the same overlay)."""
    t0 = time.perf_counter()
    arr = arr.copy()
    h, w = arr.shape[:2]
    strip = max(4, min(w // 10, 20))
    hole_h = max(2, strip // 2)
    hole_w = max(2, strip // 2)
    pitch = hole_h * 3
    for x0, x1 in ((0, strip), (w - strip, w)):
        arr[:, x0:x1, :3] = (arr[:, x0:x1, :3] * 0.2).astype(np.uint8)
        cx0 = x0 + (strip - hole_w) // 2
        for y in range((pitch - hole_h) // 2, h - hole_h, pitch):
            arr[y : y + hole_h, cx0 : cx0 + hole_w, :3] = 235
    _tm.THUMB_VIDEO_SECONDS.inc(time.perf_counter() - t0, part="overlay")
    return arr


def finish(decoded: Decoded, resized: np.ndarray) -> bytes:
    """Orientation-correct the device output, overlay, and encode."""
    arr = tj.apply_orientation(resized, decoded.orientation)
    if decoded.is_video:
        arr = apply_film_strip(arr)
    return encode_webp(np.ascontiguousarray(arr))


def resize_decoded(batch: list[Decoded]) -> list[np.ndarray]:
    """One (or few, per bucket) device calls for a whole decoded batch."""
    return tj.resize_batch([d.array for d in batch], [d.target for d in batch])


def resize_cpu(d: Decoded, reason: str = "no_device") -> bytes:
    """Pure-CPU fallback path (extreme aspect ratios / no device): PIL
    resize with the same Triangle filter + quality. Counted by `reason`
    (`sd_thumbnail_host_resize_total`)."""
    from PIL import Image

    _tm.THUMB_HOST_RESIZE.inc(reason=reason)
    th, tw = d.target
    img = Image.fromarray(d.array).resize((tw, th), Image.BILINEAR)
    arr = tj.apply_orientation(np.asarray(img), d.orientation)
    if d.is_video:
        arr = apply_film_strip(arr)
    return encode_webp(np.ascontiguousarray(arr))


def generate_one_cpu(path: str, extension: str | None) -> bytes:
    return resize_cpu(decode(path, extension))
