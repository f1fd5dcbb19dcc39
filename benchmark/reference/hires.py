"""Plain reference for what a photo of `photolib_hires` is owed beyond
what `reference/heic.py` already says of a HEIC: its thumbnail is the
triangle filter over the *whole* displayed picture, 24, 49 or 59 million
pixels of it, and nothing that takes every second row and column on the
way may pass for that. Nothing here imports the program or reads
anything it made.

The filter is `reference/video.py`'s (float64, support the scale factor,
normalised over the samples that exist), computed in blocks of rows so
that a 59 MP picture never stands in float64 as a whole (1.4 GB): a block
of rows is widened, weighed and added to the [th, w·3] sum.

The band. Every photo carries, across its middle, a band of one-pixel
line pairs of luma: grey columns alternately `BAND_DARK` and `BAND_LIGHT`
in the band's left half, rows in its right half (a photographed screen,
fabric, a railing). The filter's support at these scales is 14 to 30
pixels, so the whole filter gives the band's mean, mid-grey; an even
stride before the filter keeps every second line, the dark ones alone or
the light ones alone (which, goes by the turn and the picture's height),
and gives `BAND_DARK` or `BAND_LIGHT`, 80 of 255 off the mean either
way. `detail_gap` reads the band alone, a margin inside its edge.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import media
from benchmark.reference.video import _triangle_weights

#: the band's two greys, and what the whole filter makes of them
BAND_DARK, BAND_LIGHT = 48, 208
BAND_MEAN = (BAND_DARK + BAND_LIGHT) // 2
#: thumbnail pixels left out inside the band's edge: the filter's support
#: is under one output pixel either side, webp's blocks smear a little more
BAND_MARGIN = 3
#: rows of the picture widened to float64 at a time
BLOCK_ROWS = 256


def band_box(w: int, h: int) -> tuple[int, int, int, int]:
    """(r0, r1, c0, c1) of the band in a stored w x h picture: the middle
    quarter of its rows, three quarters of its columns, each bound even,
    so that the dark lines are the even rows and columns."""
    def even(x: int) -> int:
        return x - x % 2

    return (even(3 * h // 8), even(5 * h // 8), even(w // 8),
            even(7 * w // 8))


def draw_band(rgb: np.ndarray) -> np.ndarray:
    """The stored picture with the band of line pairs written over it
    (in place; → the same array)."""
    h, w = rgb.shape[:2]
    r0, r1, c0, c1 = band_box(w, h)
    mid = c0 + (c1 - c0) // 2
    mid -= (mid - c0) % 2
    rgb[r0:r1, c0:mid:2] = BAND_DARK
    rgb[r0:r1, c0 + 1:mid:2] = BAND_LIGHT
    rgb[r0:r1:2, mid:c1] = BAND_DARK
    rgb[r0 + 1:r1:2, mid:c1] = BAND_LIGHT
    return rgb


def downscale(rgb: np.ndarray, tw: int, th: int,
              block: int = BLOCK_ROWS) -> np.ndarray:
    """HxWx3 uint8 → th x tw x 3 uint8 by `reference/video.py`'s filter,
    float64 in between, the rows in blocks."""
    h, w = rgb.shape[:2]
    down = _triangle_weights(h, th)
    rows = np.zeros((th, w * 3), np.float64)
    for lo in range(0, h, block):
        part = rgb[lo:lo + block]
        rows += down[:, lo:lo + len(part)] @ part.reshape(
            len(part), w * 3).astype(np.float64)
    x = np.ascontiguousarray(rows.reshape(th, w, 3).transpose(1, 0, 2))
    x = _triangle_weights(w, tw) @ x.reshape(w, th * 3)
    x = x.reshape(tw, th, 3).transpose(1, 0, 2)
    return np.clip(np.rint(x), 0, 255).astype(np.uint8)


def thumbnail_pixels(rgb: np.ndarray, orientation: int, target_px: int,
                     stride: int = 1) -> np.ndarray:
    """The RGB pixels the thumbnail should show, before webp: the stored
    picture turned once as the container says, through the filter, at the
    size the whole picture is owed. `stride` is the control's: every
    `stride`-th row and column of the displayed picture before the
    filter, as a host that thins a frame to fit a canvas hands it on."""
    shown = np.ascontiguousarray(media.orient(rgb, orientation))
    h, w = shown.shape[:2]
    tw, th = media.scale_dimensions(w, h, target_px)
    if stride > 1:
        shown = np.ascontiguousarray(shown[::stride, ::stride])
    return downscale(shown, tw, th)


def band_in_thumbnail(w: int, h: int, orientation: int,
                      target_px: int) -> tuple[slice, slice]:
    """Where the band of a stored w x h picture lies in its thumbnail:
    the band's box turned as the picture is, scaled, `BAND_MARGIN`
    pixels in from each edge."""
    r0, r1, c0, c1 = band_box(w, h)
    mask = np.zeros((h, w, 1), bool)
    mask[r0:r1, c0:c1] = True
    shown = media.orient(mask, orientation)[..., 0]
    rows, cols = np.flatnonzero(shown.any(1)), np.flatnonzero(shown.any(0))
    sh, sw = shown.shape
    tw, th = media.scale_dimensions(sw, sh, target_px)

    def inside(lo: int, hi: int, scale: float) -> slice:
        a = int(np.ceil(lo * scale)) + BAND_MARGIN
        b = int(np.floor((hi + 1) * scale)) - BAND_MARGIN
        return slice(a, max(a, b))

    return (inside(rows[0], rows[-1], th / sh),
            inside(cols[0], cols[-1], tw / sw))


def detail_gap(got_rgb: np.ndarray, want_rgb: np.ndarray,
               band: tuple[slice, slice]) -> float:
    """Mean |difference| of 255 over the band alone; 255, as wrong as
    pixels can be, when the sizes differ or the band is empty."""
    if got_rgb.shape != want_rgb.shape:
        return 255.0
    got, want = got_rgb[band], want_rgb[band]
    if not got.size:
        return 255.0
    return float(np.abs(got.astype(np.int16) - want.astype(np.int16)).mean())
