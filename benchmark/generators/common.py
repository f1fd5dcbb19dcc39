"""What every location generator shares: the manifest's entry, seeded
file content in the ranges a cas_id reads (holes elsewhere), seeded
image content, and the writer that puts a manifest on disk.

A manifest is a list of entries; an entry is a dict with
  rel      path below the location's root, "/"-separated
  size     bytes (plain files; images learn theirs when written)
  content  [seed, serial] the bytes are drawn from: two entries with the
           same size and content are exact duplicates
  image    absent, else {"w", "h", "orientation", "format": "jpg"|"png",
           "blocky": bool}: a JPEG or PNG the harness writes and holds
           to a thumbnail and an embedding
  kind     absent, else the name of a kind of file the configuration
           lists under "kinds" and a module `<path>/kinds/<name>.py`
           brings (benchmark/README.md): its `write(path, entry)` puts
           the file on disk from `content` and the entry's own keys,
           and the entry learns `size` from disk, as an image does

A *plain* file has neither `image` nor `kind`: seeded bytes in the
ranges a cas_id reads, holes elsewhere. Only plain files are rewritten,
added and deleted by a traffic mix. An entry whose kind has no `write`
is written as its `image` says, or else as a plain file is.
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


def is_plain(entry: dict) -> bool:
    return not entry.get("image") and not entry.get("kind")


def entries_of(manifest: list[dict], kind: str) -> list[dict]:
    return [e for e in manifest if e.get("kind") == kind]


def kind_writer(entry: dict, kinds: dict | None):
    """The `write` of the entry's kind, or None where the entry has no
    kind or its kind writes nothing of its own. A kind the configuration
    does not list ends the run."""
    name = entry.get("kind")
    if name is None:
        return None
    if name not in (kinds or {}):
        raise SystemExit(
            f"benchmark: {entry['rel']} has the kind {name!r}, which the "
            f"configuration does not list under \"kinds\" "
            f"({sorted(kinds or {})})")
    return getattr(kinds[name], "write", None)


def read_from_disk(entry: dict, kinds: dict | None) -> bool:
    """Whether the file's bytes are known only once it is written (an
    image, a kind's own format) and not from `size` and `content`."""
    return bool(entry.get("image")) or kind_writer(entry, kinds) is not None


def write_manifest(root: str, manifest: list[dict],
                   kinds: dict | None = None) -> None:
    """Put every entry on disk; images and the files a kind (name →
    module) writes learn their size."""
    for rel_dir in sorted({os.path.dirname(e["rel"]) for e in manifest}):
        os.makedirs(os.path.join(root, rel_dir), exist_ok=True)

    def job(e: dict):
        path = os.path.join(root, e["rel"])
        own = kind_writer(e, kinds)
        if own is not None:
            return own, path, e
        if e.get("image"):
            return write_image, path, e["content"], e["image"]
        return None

    jobs = [(e, job(e)) for e in manifest]
    with ThreadPoolExecutor(IMAGE_THREADS) as pool:
        futures = [pool.submit(*j) for _e, j in jobs if j is not None]
        for e, j in jobs:
            if j is None:
                write_plain(os.path.join(root, e["rel"]), e["size"],
                            e["content"])
        for f in futures:
            f.result()
    for e, j in jobs:
        if j is not None:
            e["size"] = os.path.getsize(os.path.join(root, e["rel"]))


def seed_words(seed: int, *more: int) -> list[int]:
    """A seed of any size up to 2**63 as words NumPy's generator takes."""
    return [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, *more]
