"""`iphone15_roll`: the roll of an iPhone 15 or later, from the
parameters of a configuration file (benchmark/configs/photolib_hires.json
names the sources): the main camera's 24 MP default and 48 MP HEICs, the
other lenses' 12 MP ones, a panorama, and one PNG screenshot for every
`photos_per_screenshot` photos. The count, the screenshots, the names,
the dates and the positions are `iphone_roll`'s, so `photolib_heic` and
this are one roll on two generations of phone.

A photo is an entry of the kind `hires` (`kinds/hires.py` writes it and
holds the program to its thumbnail, embedding and `media_data` row). Its
class (sensor size and turn) goes by the photo's own count k modulo the
length of the configuration's `photo.roll`, the same for every seed; the
seed draws the pixels, the camera, the dates and the positions.
"""

from __future__ import annotations

import datetime

import numpy as np

from benchmark.generators.common import seed_words
from benchmark.generators.iphone_roll import _position

#: `taken` counts seconds from 2017-09-19 (`reference/heic.py:date_taken`);
#: this roll starts after the iPhone 15 went on sale
FIRST_DAY = (datetime.date(2023, 9, 22) - datetime.date(2017, 9, 19)).days


def plan(config: dict, seed: int, scale: float = 1.0) -> list[dict]:
    n = max(2, int(config["photos"] * scale))
    photo, shot = config["photo"], config["screenshot"]
    every = config["photos_per_screenshot"] + 1
    rng = np.random.default_rng(seed_words(seed, 0x68697265))
    model = photo["models"][int(rng.integers(0, len(photo["models"])))]
    taken = FIRST_DAY * 86400 + int(rng.integers(0, 365 * 86400))
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
        # class, turn and position go by the photo's own count: by the
        # file's, every eighth place would be a screenshot's
        name, turn = photo["roll"][k % len(photo["roll"])]
        with_gps = k % photo["gps_every"] == 0
        k += 1
        size = photo["classes"][name]
        manifest.append({
            "rel": f"{folder}/IMG_{i:04d}.{photo['extension']}",
            "size": 0, "content": seed_words(seed, i), "kind": "hires",
            "hires": {
                "class": name, "w": size["width"], "h": size["height"],
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
        "iphone15_roll has no traffic that adds files yet")
