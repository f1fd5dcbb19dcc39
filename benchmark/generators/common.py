"""What every location generator shares: the manifest's entry, seeded
file content in the ranges a cas_id reads (holes elsewhere), seeded
image content, and the writer that puts a manifest on disk.

A manifest is a list of entries; an entry is a dict with
  rel      path below the location's root, "/"-separated
  size     bytes (plain files; images learn theirs when written)
  content  [seed, serial] the bytes are drawn from: two entries with the
           same size and content are exact duplicates
  image    absent for plain files, else {"w", "h", "orientation",
           "format": "jpg"|"png", "blocky": bool}
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.reference import cas_layout

#: writer threads for images (PIL and NumPy release the GIL)
IMAGE_THREADS = 6


def write_plain(path: str, size: int, content: list[int]) -> None:
    rng = np.random.default_rng(content)
    with open(path, "wb") as f:
        f.truncate(size)
        for off, ln in cas_layout.ranges(size):
            f.seek(off)
            f.write(rng.bytes(ln))


def image_pixels(content: list[int], w: int, h: int, blocky: bool):
    """A seeded low-resolution colour field blown up to (w, h), as
    chip_smoke.py's `_photo_pixels` makes it: bicubic for photos, flat
    blocks for screenshots."""
    from PIL import Image

    rng = np.random.default_rng(content)
    gw, gh = max(2, w // 252), max(2, h // 252)
    field = rng.integers(0, 256, (gh, gw, 3), dtype=np.uint8)
    return Image.fromarray(field).resize(
        (w, h), Image.NEAREST if blocky else Image.BICUBIC)


def write_image(path: str, content: list[int], image: dict) -> None:
    from PIL import Image

    img = image_pixels(content, image["w"], image["h"], image["blocky"])
    if image["format"] == "png":
        img.save(path, "PNG", compress_level=1)
    else:
        exif = Image.Exif()
        exif[0x0112] = image["orientation"]
        img.save(path, "JPEG", quality=88, exif=exif)


def write_manifest(root: str, manifest: list[dict]) -> None:
    """Put every entry on disk; images learn their size."""
    for rel_dir in sorted({os.path.dirname(e["rel"]) for e in manifest}):
        os.makedirs(os.path.join(root, rel_dir), exist_ok=True)
    images = [e for e in manifest if e.get("image")]
    with ThreadPoolExecutor(IMAGE_THREADS) as pool:
        futures = [pool.submit(write_image, os.path.join(root, e["rel"]),
                               e["content"], e["image"]) for e in images]
        for e in manifest:
            if not e.get("image"):
                write_plain(os.path.join(root, e["rel"]), e["size"],
                            e["content"])
        for f in futures:
            f.result()
    for e in images:
        e["size"] = os.path.getsize(os.path.join(root, e["rel"]))


def seed_words(seed: int, *more: int) -> list[int]:
    """A seed of any size up to 2**63 as words NumPy's generator takes."""
    return [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, *more]
