"""Image decode/convert dispatch by extension.

Parity: ref:crates/images/src/handler.rs:18-60 — `format_image` routes
by extension to Generic (the `image` crate → here PIL), HEIF
(libheif-rs/libheif-sys → here a ctypes binding over the system
libheif, the same C library), SVG (resvg) and PDF (pdfium) handlers;
max-size guards ref:crates/images/src/consts.rs:9,33,39. SVG/PDF
raise `UnsupportedImage` when no rasterizer is present in the image —
the dispatch stays, the handler is gated (the reference gates the same
way via cargo features).
"""

from __future__ import annotations

import contextlib
import ctypes
import ctypes.util
import os
from typing import Optional

import numpy as np

MAXIMUM_FILE_SIZE = 192 * 1024 * 1024  # ref:consts.rs:9
SVG_RENDER_SIZE = 512  # ref:consts.rs:33 (SVG render cap 512²)
PDF_RENDER_WIDTH = 1024  # ref:consts.rs:39
# A side of the frame a JPEG is decoded to is never asked under this:
# 8 source pixels per pixel of the embedder's 32 x 32 plane
# (8 * models/embedder.IMAGE_SIZE; tests/test_decode_once.py holds the
# two together), so one decoded frame serves thumbnail and plane.
PLANE_SOURCE_SIDE = 256

HEIF_EXTENSIONS = {"heif", "heifs", "heic", "heics", "avif", "avci", "avcs"}
SVG_EXTENSIONS = {"svg", "svgz"}
PDF_EXTENSIONS = {"pdf"}


class ImageHandlerError(Exception):
    pass


class UnsupportedImage(ImageHandlerError):
    pass


# --- libheif ctypes binding (ref:crates/images HEIF handler) -------------


class _HeifError(ctypes.Structure):
    _fields_ = [
        ("code", ctypes.c_int),
        ("subcode", ctypes.c_int),
        ("message", ctypes.c_char_p),
    ]


_HEIF_COLORSPACE_RGB = 1
_HEIF_CHROMA_INTERLEAVED_RGB = 10
_HEIF_CHROMA_INTERLEAVED_RGBA = 11
_HEIF_CHANNEL_INTERLEAVED = 10

_heif: ctypes.CDLL | None = None


def _load_heif() -> ctypes.CDLL | None:
    global _heif
    if _heif is not None:
        return _heif
    name = ctypes.util.find_library("heif") or "libheif.so.1"
    try:
        lib = ctypes.CDLL(name)
    except OSError:
        return None
    lib.heif_context_alloc.restype = ctypes.c_void_p
    lib.heif_context_read_from_file.restype = _HeifError
    lib.heif_context_read_from_file.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p,
    ]
    lib.heif_context_get_primary_image_handle.restype = _HeifError
    lib.heif_context_get_primary_image_handle.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
    ]
    lib.heif_decode_image.restype = _HeifError
    lib.heif_decode_image.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
        ctypes.c_int, ctypes.c_int, ctypes.c_void_p,
    ]
    lib.heif_image_handle_get_width.restype = ctypes.c_int
    lib.heif_image_handle_get_width.argtypes = [ctypes.c_void_p]
    lib.heif_image_handle_get_height.restype = ctypes.c_int
    lib.heif_image_handle_get_height.argtypes = [ctypes.c_void_p]
    lib.heif_image_handle_has_alpha_channel.restype = ctypes.c_int
    lib.heif_image_handle_has_alpha_channel.argtypes = [ctypes.c_void_p]
    lib.heif_image_get_plane_readonly.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.heif_image_get_plane_readonly.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
    ]
    lib.heif_image_release.argtypes = [ctypes.c_void_p]
    lib.heif_image_handle_release.argtypes = [ctypes.c_void_p]
    # the container's own facts and metadata blocks (`heif_container`)
    for name in ("heif_image_handle_get_ispe_width",
                 "heif_image_handle_get_ispe_height"):
        getattr(lib, name).restype = ctypes.c_int
        getattr(lib, name).argtypes = [ctypes.c_void_p]
    lib.heif_image_handle_get_list_of_metadata_block_IDs.restype = ctypes.c_int
    lib.heif_image_handle_get_list_of_metadata_block_IDs.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_uint32), ctypes.c_int,
    ]
    lib.heif_image_handle_get_metadata_size.restype = ctypes.c_size_t
    lib.heif_image_handle_get_metadata_size.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32,
    ]
    lib.heif_image_handle_get_metadata.restype = _HeifError
    lib.heif_image_handle_get_metadata.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, ctypes.c_void_p,
    ]
    lib.heif_context_free.argtypes = [ctypes.c_void_p]
    _heif = lib
    return lib


def heif_available() -> bool:
    return _load_heif() is not None


def _heif_check(err: _HeifError, stage: str) -> None:
    if err.code != 0:
        msg = err.message.decode() if err.message else "?"
        raise ImageHandlerError(f"libheif {stage}: {msg} (code {err.code})")


@contextlib.contextmanager
def _heif_primary(path: str):
    """Open a HEIF container and lend (lib, handle of its primary
    image); both are released when the caller is done."""
    lib = _load_heif()
    if lib is None:
        raise UnsupportedImage("libheif not available")
    ctx = lib.heif_context_alloc()
    if not ctx:
        raise ImageHandlerError("heif_context_alloc failed")
    handle = ctypes.c_void_p()
    try:
        _heif_check(
            lib.heif_context_read_from_file(ctx, os.fsencode(path), None), "read"
        )
        _heif_check(
            lib.heif_context_get_primary_image_handle(
                ctx, ctypes.byref(handle)
            ),
            "primary handle",
        )
        yield lib, handle
    finally:
        if handle:
            lib.heif_image_handle_release(handle)
        lib.heif_context_free(ctx)


def heif_container(path: str) -> tuple[tuple[int, int], bytes | None]:
    """What a HEIC/HEIF/AVIF container says of its primary image
    without decoding it: → ((width, height), EXIF). The size is the
    item's `ispe` (the handle's own, transforms applied, where there is
    none). The EXIF block is the first metadata item of the type `Exif`:
    its first four bytes are the big-endian offset from their end to
    the TIFF header (ISO 23008-12 A.2.1; a phone's `Exif\\0\\0`
    prefix makes it 6), and what is handed on starts at that header, as
    `PIL.Image.Exif.load` takes it; None where the item is absent or
    too short to hold a header."""
    with _heif_primary(path) as (lib, handle):
        size = (lib.heif_image_handle_get_ispe_width(handle),
                lib.heif_image_handle_get_ispe_height(handle))
        if min(size) <= 0:
            size = (lib.heif_image_handle_get_width(handle),
                    lib.heif_image_handle_get_height(handle))
        block_id = ctypes.c_uint32()
        if lib.heif_image_handle_get_list_of_metadata_block_IDs(
                handle, b"Exif", ctypes.byref(block_id), 1) != 1:
            return size, None
        n = lib.heif_image_handle_get_metadata_size(handle, block_id)
        buf = ctypes.create_string_buffer(n)
        _heif_check(
            lib.heif_image_handle_get_metadata(handle, block_id, buf), "exif"
        )
        raw = buf.raw
        start = 4 + int.from_bytes(raw[:4], "big")
        return size, raw[start:] if n >= 4 and start + 8 <= n else None


def heif_frame_shape(path: str) -> tuple[int, int, int]:
    """(h, w, channels) of the array `decode_heif` will hand on, from
    the container alone: the handle's size, transforms applied, and
    whether it reports an alpha channel. Nothing is decoded."""
    with _heif_primary(path) as (lib, handle):
        return (lib.heif_image_handle_get_height(handle),
                lib.heif_image_handle_get_width(handle),
                4 if lib.heif_image_handle_has_alpha_channel(handle) else 3)


def decode_heif(path: str) -> np.ndarray:
    """HEIC/HEIF/AVIF → uint8 via the system libheif (the same C
    library the reference links, ref:crates/images/Cargo.toml:13,32),
    with the channels the file holds: a tight [h, w, 3] RGB array where
    the handle reports no alpha channel (a camera's photo), [h, w, 4]
    RGBA where it reports one (a sticker, a cut-out). The resize, the
    webp and the embedder's plane all go by the array's channels, so a
    photo pays for no constant fourth byte downstream."""
    with _heif_primary(path) as (lib, handle):
        if lib.heif_image_handle_has_alpha_channel(handle):
            chroma, channels = _HEIF_CHROMA_INTERLEAVED_RGBA, 4
        else:
            chroma, channels = _HEIF_CHROMA_INTERLEAVED_RGB, 3
        img = ctypes.c_void_p()
        try:
            _heif_check(
                lib.heif_decode_image(
                    handle,
                    ctypes.byref(img),
                    _HEIF_COLORSPACE_RGB,
                    chroma,
                    None,
                ),
                "decode",
            )
            width = lib.heif_image_handle_get_width(handle)
            height = lib.heif_image_handle_get_height(handle)
            stride = ctypes.c_int()
            plane = lib.heif_image_get_plane_readonly(
                img, _HEIF_CHANNEL_INTERLEAVED, ctypes.byref(stride)
            )
            if not plane:
                raise ImageHandlerError("heif: no interleaved plane")
            buf = np.ctypeslib.as_array(plane, shape=(height, stride.value))
            return buf[:, : width * channels].reshape(
                height, width, channels).copy()
        finally:
            if img:
                lib.heif_image_release(img)


# --- generic + dispatch ---------------------------------------------------


def decode_generic(path: str) -> np.ndarray:
    from PIL import Image

    with Image.open(path) as im:
        return np.asarray(im.convert("RGBA"))


def decode_svg(path: str) -> np.ndarray:
    """SVG/SVGZ via librsvg (ref:handler.rs SVG → resvg). Gzip payloads
    are expanded under the same size cap as the on-disk file."""
    from . import svg as svg_mod

    if not svg_mod.svg_available():
        raise UnsupportedImage(
            "no SVG rasterizer (librsvg unavailable; reference: resvg)"
        )
    with open(path, "rb") as f:
        data = f.read(MAXIMUM_FILE_SIZE + 1)
    if len(data) > MAXIMUM_FILE_SIZE:
        raise ImageHandlerError(f"file over {MAXIMUM_FILE_SIZE} bytes")
    if data[:2] == b"\x1f\x8b":  # svgz
        import gzip
        import io as _io

        try:
            with gzip.GzipFile(fileobj=_io.BytesIO(data)) as gz:
                data = gz.read(MAXIMUM_FILE_SIZE + 1)
        except Exception as exc:
            raise ImageHandlerError(f"svgz decompress failed: {exc}") from exc
        if len(data) > MAXIMUM_FILE_SIZE:
            raise ImageHandlerError("svgz expands past the size cap")
    try:
        return svg_mod.render_svg(data)
    except ImageHandlerError:
        raise
    except Exception as exc:
        raise ImageHandlerError(f"svg render failed: {exc}") from exc


def decode_pdf(path: str) -> np.ndarray:
    """PDF first page (ref:handler.rs PDF → pdfium) via ../pdf.py."""
    from . import pdf as pdf_mod

    try:
        return pdf_mod.render_pdf(path)
    except ImageHandlerError:
        raise
    except Exception as exc:
        raise ImageHandlerError(f"pdf render failed: {exc}") from exc


def format_image(path: str, extension: str | None = None) -> np.ndarray:
    """Decode any supported still image/document to uint8, [h, w, 4]
    RGBA (PIL's formats, SVG, PDF, a HEIF with an alpha channel) or
    [h, w, 3] RGB (a HEIF without one: `decode_heif`)
    (ref:handler.rs:18-60 `format_image` — the single dispatch)."""
    if os.path.getsize(path) > MAXIMUM_FILE_SIZE:
        raise ImageHandlerError(f"file over {MAXIMUM_FILE_SIZE} bytes")
    ext = (extension or os.path.splitext(path)[1].lstrip(".")).lower()
    if ext in HEIF_EXTENSIONS:
        return decode_heif(path)
    if ext in SVG_EXTENSIONS:
        return decode_svg(path)
    if ext in PDF_EXTENSIONS:
        return decode_pdf(path)
    return decode_generic(path)


def draft_jpeg(img) -> int:
    """The one DCT-scale request every decode of a JPEG makes, the
    thumbnailer's and the embedder's alike, so both hold the same
    frame whoever opened the file: the smallest of 1/1, 1/2, 1/4, 1/8
    that still covers the thumbnail's target (`scale_dimensions`) and
    leaves `PLANE_SOURCE_SIDE` pixels a side for the embedder's plane.
    `img` is a just-opened PIL image; → the scale applied (1 for what
    is not a JPEG, or too small to scale)."""
    if img.format != "JPEG":
        return 1
    from ...ops.thumbnail_jax import scale_dimensions

    width, height = img.size
    tw, th = scale_dimensions(width, height)
    img.draft("RGB", (max(tw, PLANE_SOURCE_SIDE), max(th, PLANE_SOURCE_SIDE)))
    # draft leaves ceil(side / scale); with 256 px or more a side left,
    # the ratio rounds to the scale itself
    return round(width / img.size[0])
