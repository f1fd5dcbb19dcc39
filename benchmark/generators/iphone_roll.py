"""`iphone_roll`: an iPhone's camera roll as the phone writes it, from the
parameters of a configuration file (benchmark/configs/photolib_heic.json
names the sources): HEIC photos at the sensor's size, and one PNG
screenshot for every `photos_per_screenshot` photos. The count, the
pictures and the screenshots are `camera_roll`'s, so `photolib` and this
are one roll in the two codecs a phone has written.

A photo is an entry of the kind `heic` (`kinds/heic.py` writes it and
holds the program to its thumbnail, embedding and `media_data` row). Its
plan is what the phone would put into the container: the sensor's size,
how the picture is turned (the container's `irot`/`imir` and the EXIF
tag say the same), the encoder's settings, and the EXIF block's fields.
Which photo is turned and which carries a position goes by its index,
the same for every seed; the seed draws the pixels, the camera, the
dates and the positions.
"""

from __future__ import annotations

import numpy as np

from benchmark.generators.common import seed_words


def _position(rng) -> dict:
    """A seeded position as EXIF holds it: degrees, minutes and
    hundredths of a second, with the hemisphere beside them."""
    out = {}
    for axis, span, refs in (("lat", 60, "NS"), ("lon", 180, "EW")):
        dms = [int(rng.integers(0, span)), int(rng.integers(0, 60)),
               int(rng.integers(0, 6000))]
        ref = refs[int(rng.integers(0, 2))]
        value = dms[0] + dms[1] / 60 + dms[2] / 100 / 3600
        out[axis] = {"dms": dms, "ref": ref,
                     "value": -value if ref in "SW" else value}
    return out


def plan(config: dict, seed: int, scale: float = 1.0) -> list[dict]:
    n = max(2, int(config["photos"] * scale))
    photo, shot = config["photo"], config["screenshot"]
    every = config["photos_per_screenshot"] + 1
    rng = np.random.default_rng(seed_words(seed, 0x68656963))
    model = photo["models"][int(rng.integers(0, len(photo["models"])))]
    # a roll runs forward in time: a first shot, then a few minutes to
    # a few days between one photo and the next
    taken = int(rng.integers(0, 5 * 365 * 86400))
    orientations = photo["exif_orientations"]
    manifest, k = [], 0  # k: photos so far
    for i in range(n):
        folder = f"DCIM/{100 + i // 1000}APPLE"
        taken += int(rng.integers(60, 3 * 86400))
        position = _position(rng)
        if i % every == every - 1:
            manifest.append({
                "rel": f"{folder}/IMG_{i:04d}.{shot['extension']}",
                "size": 0, "content": seed_words(seed, i),
                "image": {"w": shot["width"], "h": shot["height"],
                          "format": shot["format"], "orientation": 1,
                          "blocky": True},
            })
            continue
        # turns and positions go by the photo's own count: by the
        # file's, every eighth place would be a screenshot's
        with_gps = k % photo["gps_every"] == 0
        turn = orientations[k % len(orientations)]
        k += 1
        manifest.append({
            "rel": f"{folder}/IMG_{i:04d}.{photo['extension']}",
            "size": 0, "content": seed_words(seed, i), "kind": "heic",
            "heic": {
                "w": photo["width"], "h": photo["height"],
                "orientation": turn,
                "compression": photo["compression"],
                "quality": photo["quality"], "preset": photo["preset"],
                "make": photo["make"], "model": model, "taken": taken,
                "position": position if with_gps else None,
            },
        })
    return manifest


def new_entry(config: dict, rng, manifest: list[dict], serial: int,
              seed: int) -> dict:
    raise NotImplementedError(
        "iphone_roll has no traffic that adds files yet")
