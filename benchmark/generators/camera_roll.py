"""`camera_roll`: a phone's camera roll, from the parameters of a
configuration file (benchmark/configs/photolib.json names the sources):
JPEG photos at the sensor's size with EXIF orientations, and one PNG
screenshot for every `photos_per_screenshot` photos.
"""

from __future__ import annotations

from benchmark.generators.common import seed_words


def plan(config: dict, seed: int, scale: float = 1.0) -> list[dict]:
    n = max(2, int(config["photos"] * scale))
    photo, shot = config["photo"], config["screenshot"]
    every = config["photos_per_screenshot"] + 1
    manifest = []
    for i in range(n):
        is_shot = i % every == every - 1
        c = shot if is_shot else photo
        orientations = c.get("exif_orientations", [1])
        manifest.append({
            "rel": (f"DCIM/{100 + i // 1000}APPLE/IMG_{i:04d}."
                    f"{c['format']}"),
            "size": 0, "content": seed_words(seed, i),
            "image": {"w": c["width"], "h": c["height"],
                      "format": c["format"],
                      "orientation": orientations[i % len(orientations)],
                      "blocky": c["format"] == "png"},
        })
    return manifest


def new_entry(config: dict, rng, manifest: list[dict], serial: int,
              seed: int) -> dict:
    raise NotImplementedError(
        "camera_roll has no traffic that adds files yet")
