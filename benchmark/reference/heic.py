"""Plain reference for what a HEIC photo is owed: the thumbnail (the
picture as displayed, a float64 triangle filter down to upstream's
target) and the embedding (`reference/media.py`'s forward on the plane
made as it makes one), and the facts of its `media_data` row. Nothing
here imports the program or reads anything it made.

The picture is the one the generator drew from the entry's seed, before
the encoder: this machine has one HEVC decoder (libheif's, the one the
program links), so there is no second decoder to hold it against, as
there was no second frame-exact clip decoder for `reference/video.py`.
What HEVC at the configuration's quality costs is therefore part of
every sound reading (`kinds/heic.py` has the numbers).

Orientation. A phone stores the sensor's rows and says how to turn them
twice: as the container's `irot`/`imir` properties, which a reader of
HEIF has to apply, and as the EXIF tag, which a reader of HEIF must not
apply again. `displayed` is the picture after that one turn; thumbnail
and embedding are both of the displayed picture (the embedder sees what
the decoder hands on, as it sees a JPEG's stored pixels).
"""

from __future__ import annotations

import datetime

import numpy as np

from benchmark.reference import media
from benchmark.reference.video import downscale

# EXIF tags the generator writes and the `media_data` row is held to
TAG_MAKE, TAG_MODEL, TAG_ORIENTATION = 0x010F, 0x0110, 0x0112
TAG_EXIF_IFD, TAG_GPS_IFD = 0x8769, 0x8825
TAG_DATE_ORIGINAL, TAG_PIXEL_X, TAG_PIXEL_Y = 0x9003, 0xA002, 0xA003


def displayed(rgb: np.ndarray, orientation: int) -> np.ndarray:
    """The stored rows turned as the container says, once."""
    return np.ascontiguousarray(media.orient(rgb, orientation))


#: (w, h) of the stored thumbnail of a w x h sensor picture
thumbnail_size = media.thumbnail_size


def thumbnail_pixels(rgb: np.ndarray, orientation: int,
                     target_px: int) -> np.ndarray:
    """The RGB pixels the thumbnail should show, before webp: the
    displayed picture through a float64 triangle filter whose support
    is the scale factor. `orientation` is the control's too: 1 for a
    picture left as the sensor stored it."""
    shown = displayed(rgb, orientation)
    h, w = shown.shape[:2]
    return downscale(shown, *media.scale_dimensions(w, h, target_px))


def mirrored(rgb: np.ndarray) -> np.ndarray:
    """The control's picture: `imir` applied where none was written."""
    return np.ascontiguousarray(rgb[:, ::-1])


def embedding(rgb: np.ndarray, orientation: int,
              control: bool = False) -> np.ndarray:
    """The float64 forward on the plane of the displayed picture;
    `control` rounds the matmul operands one notch below bfloat16."""
    from PIL import Image

    plane = media.embed_plane(Image.fromarray(displayed(rgb, orientation)))
    return media.embed_forward(plane[None], control)[0]


def date_taken(stamp: int) -> str:
    """Seconds since 2017-09-19 (iOS 11) → EXIF's DateTimeOriginal."""
    at = datetime.datetime(2017, 9, 19) + datetime.timedelta(seconds=stamp)
    return at.strftime("%Y:%m:%d %H:%M:%S")


def facts(photo: dict) -> dict:
    """What the photo's `media_data` row should say, from the
    manifest's plan alone: resolution as stored (the sensor's, which
    the EXIF pixel dimensions repeat), the date, the camera, the
    orientation tag, and the position where one was written."""
    position = photo.get("position")
    return {"resolution": [photo["w"], photo["h"]],
            "date_taken": date_taken(photo["taken"]),
            "make": photo["make"], "model": photo["model"],
            "orientation": photo["orientation"],
            "gps": position and [position["lat"]["value"],
                                 position["lon"]["value"]]}


def facts_wrong(photo: dict, resolution, media_date, camera,
                location) -> bool:
    """Whether a `media_data` row (its four blobs unpacked) departs
    from `facts`: resolution, date, make, model and orientation
    exactly; latitude and longitude, where written, to a thousandth of
    a second of arc (they are stored as rationals)."""
    want = facts(photo)
    try:
        if (list(resolution) != want["resolution"]
                or media_date != want["date_taken"]
                or camera["device_make"] != want["make"]
                or camera["device_model"] != want["model"]
                or camera["orientation"] != want["orientation"]):
            return True
        if want["gps"] is None:
            return location is not None
        return (location is None
                or abs(location["latitude"] - want["gps"][0]) > 3e-7
                or abs(location["longitude"] - want["gps"][1]) > 3e-7)
    except (TypeError, KeyError, IndexError):
        return True
