"""EXIF / media metadata extraction.

Parity: ref:crates/media-metadata/src/image/mod.rs:27-47
(ImageMetadata{resolution, date_taken, location, camera_data, artist,
description, copyright, exif_version}) and orientation handling
(image/orientation.rs) — extracted with PIL instead of kamadak-exif.
"""

from __future__ import annotations

import datetime as _dt
import logging
import os
from dataclasses import asdict, dataclass, field
from typing import Any

import msgpack

logger = logging.getLogger(__name__)

# EXIF orientation values 1-8 (the TPU resize pipeline turns these into
# transpose/flip ops on the batch, ref:crates/media-metadata/src/image/
# orientation.rs)
ORIENTATION_NORMAL = 1


@dataclass
class MediaLocation:
    latitude: float
    longitude: float
    altitude: float | None = None
    direction: float | None = None

    def plus_code(self) -> str:
        """Open Location Code of this position (parity with the
        reference's pluscodes module, ref:crates/media-metadata/src/
        image/geographic/pluscodes.rs)."""
        return encode_plus_code(self.latitude, self.longitude)


@dataclass
class CameraData:
    device_make: str | None = None
    device_model: str | None = None
    focal_length: float | None = None
    shutter_speed: str | None = None
    iso: int | None = None
    aperture: float | None = None
    flash: bool | None = None
    lens_make: str | None = None
    lens_model: str | None = None
    orientation: int = ORIENTATION_NORMAL


@dataclass
class ImageMetadata:
    resolution: tuple[int, int] = (0, 0)
    date_taken: str | None = None
    epoch_time: int | None = None
    location: MediaLocation | None = None
    camera_data: CameraData = field(default_factory=CameraData)
    artist: str | None = None
    description: str | None = None
    copyright: str | None = None
    exif_version: str | None = None

    @classmethod
    def from_path(cls, path: str | os.PathLike) -> "ImageMetadata | None":
        """PIL opens the file and hands on its EXIF; a HEIF container
        (HEIC, HEIF, AVIF), which PIL does not open, is read through
        libheif (`images.heif_container`: the size and the EXIF item,
        no decode). ref: the media-data extractor takes HEIF, HEIC and
        AVIF as it takes JPEG."""
        try:
            from PIL import ExifTags, Image

            from .images import HEIF_EXTENSIONS, heif_container

            ext = os.path.splitext(os.fspath(path))[1].lstrip(".").lower()
            if ext in HEIF_EXTENSIONS:
                size, block = heif_container(os.fspath(path))
                meta = cls(resolution=size)
                if block is None:
                    return meta
                exif = Image.Exif()
                exif.load(block)
                meta._read_exif(exif)
                # the EXIF's pixel dimensions are the sensor's; the
                # container's size may be the stored or the displayed
                # picture's, by who wrote it (libheif 1.15 writes a
                # quarter-turned item's `ispe` as displayed)
                sub = exif.get_ifd(ExifTags.IFD.Exif)
                w = sub.get(ExifTags.Base.ExifImageWidth)
                h = sub.get(ExifTags.Base.ExifImageHeight)
                if isinstance(w, int) and isinstance(h, int) and min(w, h) > 0:
                    meta.resolution = (w, h)
                return meta
            with Image.open(path) as im:
                meta = cls(resolution=(im.width, im.height))
                exif = im.getexif()
                if exif:
                    meta._read_exif(exif)
                return meta
        except Exception as e:  # noqa: BLE001 - any decode failure = no metadata
            logger.debug("exif extraction failed for %s: %s", path, e)
            return None

    def _read_exif(self, exif) -> None:
        """Fill the fields from a `PIL.Image.Exif`, wherever it came
        from (a file PIL opened, a HEIF container's item)."""
        from PIL import ExifTags

        tags = {ExifTags.TAGS.get(k, k): v for k, v in exif.items()}
        ifd = {}
        try:
            raw_ifd = exif.get_ifd(ExifTags.IFD.Exif)
            ifd = {ExifTags.TAGS.get(k, k): v for k, v in raw_ifd.items()}
        except Exception:  # noqa: BLE001
            pass

        dt = ifd.get("DateTimeOriginal") or tags.get("DateTime")
        if isinstance(dt, str):
            self.date_taken = dt
            try:
                parsed = _dt.datetime.strptime(dt, "%Y:%m:%d %H:%M:%S")
                self.epoch_time = int(parsed.timestamp())
            except ValueError:
                pass
        self.artist = _s(tags.get("Artist"))
        self.description = _s(tags.get("ImageDescription"))
        self.copyright = _s(tags.get("Copyright"))
        ev = ifd.get("ExifVersion")
        if isinstance(ev, bytes):
            self.exif_version = ev.decode("ascii", "ignore")
        cam = self.camera_data
        cam.device_make = _s(tags.get("Make"))
        cam.device_model = _s(tags.get("Model"))
        cam.orientation = int(tags.get("Orientation") or ORIENTATION_NORMAL)
        cam.lens_make = _s(ifd.get("LensMake"))
        cam.lens_model = _s(ifd.get("LensModel"))
        fl = ifd.get("FocalLength")
        cam.focal_length = float(fl) if fl is not None else None
        ap = ifd.get("FNumber")
        cam.aperture = float(ap) if ap is not None else None
        iso = ifd.get("ISOSpeedRatings")
        cam.iso = int(iso) if isinstance(iso, (int, float)) else None
        fl_ = ifd.get("Flash")
        cam.flash = bool(int(fl_) & 1) if isinstance(fl_, (int, float)) else None

        self.location = _gps(exif)

    # --- persistence into media_data (ref:schema.prisma:281-310) ---

    def to_row(self, object_id: int) -> dict[str, Any]:
        return {
            "resolution": msgpack.packb(list(self.resolution)),
            "media_date": msgpack.packb(self.date_taken),
            "media_location": (
                msgpack.packb(asdict(self.location)) if self.location else None
            ),
            "camera_data": msgpack.packb(asdict(self.camera_data)),
            "artist": self.artist,
            "description": self.description,
            "copyright": self.copyright,
            "exif_version": self.exif_version,
            "epoch_time": self.epoch_time,
            "object_id": object_id,
        }


@dataclass
class VideoMetadata:
    """ref:crates/media-metadata/src/video.rs (the reference ships a
    stub; this extracts real stream facts via the cv2/ffmpeg decoder)."""

    resolution: tuple[int, int] = (0, 0)
    duration_seconds: float | None = None
    fps: float | None = None
    frame_count: int | None = None
    codec: str | None = None

    @classmethod
    def from_path(cls, path: str | os.PathLike) -> "VideoMetadata | None":
        # native FFmpeg probe first (real codec names + container
        # duration, ref:crates/ffmpeg); cv2 as fallback
        try:
            from ...native import video_meta

            meta = video_meta(os.fspath(path))
        except Exception:
            meta = None
        if meta is not None and meta["width"] and meta["height"]:
            return cls(
                resolution=(meta["width"], meta["height"]),
                duration_seconds=meta["duration_seconds"] or None,
                fps=meta["fps"] or None,
                frame_count=meta["frame_count"] or None,
                codec=meta["codec"] or None,
            )
        try:
            import cv2
        except Exception:
            return None
        cap = cv2.VideoCapture(os.fspath(path))
        try:
            if not cap.isOpened():
                return None
            w = int(cap.get(cv2.CAP_PROP_FRAME_WIDTH) or 0)
            h = int(cap.get(cv2.CAP_PROP_FRAME_HEIGHT) or 0)
            fps = float(cap.get(cv2.CAP_PROP_FPS) or 0) or None
            frames = int(cap.get(cv2.CAP_PROP_FRAME_COUNT) or 0) or None
            fourcc = int(cap.get(cv2.CAP_PROP_FOURCC) or 0)
            codec = (
                "".join(chr((fourcc >> (8 * i)) & 0xFF) for i in range(4)).strip()
                or None
                if fourcc
                else None
            )
            duration = (frames / fps) if frames and fps else None
            if not (w and h):
                return None
            return cls(
                resolution=(w, h),
                duration_seconds=duration,
                fps=fps,
                frame_count=frames,
                codec=codec,
            )
        finally:
            cap.release()

    def to_row(self, object_id: int) -> dict[str, Any]:
        """media_data row (resolution blob shared with images; the
        video facts ride the camera_data blob slot as a typed dict)."""
        return {
            "resolution": msgpack.packb(list(self.resolution)),
            "camera_data": msgpack.packb(
                {
                    "video": True,
                    "duration_seconds": self.duration_seconds,
                    "fps": self.fps,
                    "frame_count": self.frame_count,
                    "codec": self.codec,
                }
            ),
            "object_id": object_id,
        }


def _s(v: Any) -> str | None:
    return str(v).strip("\x00 ").strip() if v is not None else None


def _gps(exif) -> MediaLocation | None:
    try:
        from PIL import ExifTags

        gps_raw = exif.get_ifd(ExifTags.IFD.GPSInfo)
        if not gps_raw:
            return None
        gps = {ExifTags.GPSTAGS.get(k, k): v for k, v in gps_raw.items()}
        lat = _dms(gps.get("GPSLatitude"), gps.get("GPSLatitudeRef", "N"))
        lon = _dms(gps.get("GPSLongitude"), gps.get("GPSLongitudeRef", "E"))
        if lat is None or lon is None:
            return None
        alt = gps.get("GPSAltitude")
        return MediaLocation(
            latitude=lat, longitude=lon,
            altitude=float(alt) if alt is not None else None,
        )
    except Exception:  # noqa: BLE001
        return None


def _dms(value, ref: str) -> float | None:
    if not value or len(value) != 3:
        return None
    deg = float(value[0]) + float(value[1]) / 60 + float(value[2]) / 3600
    if ref in ("S", "W"):
        deg = -deg
    return deg


# --- Open Location Code (plus codes), parity with
# ref:crates/media-metadata/src/image/geographic/pluscodes.rs ---

_OLC_ALPHABET = "23456789CFGHJMPQRVWX"


def encode_plus_code(lat: float, lon: float, code_length: int = 10) -> str:
    lat = min(90.0, max(-90.0, lat)) + 90.0
    lon = ((lon + 180.0) % 360.0)
    code = ""
    lat_res, lon_res = 400.0, 400.0
    for i in range(code_length // 2):
        lat_res /= 20.0
        lon_res /= 20.0
        code += _OLC_ALPHABET[min(19, int(lat / lat_res))]
        lat -= int(lat / lat_res) * lat_res
        code += _OLC_ALPHABET[min(19, int(lon / lon_res))]
        lon -= int(lon / lon_res) * lon_res
        if i == 3:
            code += "+"
    if "+" not in code:
        code += "+"
    return code
