"""`raw_shoot`: a photographer's shoot added to a library, from the
parameters of a configuration file (benchmark/configs/photolib_raw.json
names the sources): RAW frames as a camera's card holds them, plain
files of tens of megabytes that no thumbnailer decodes, and a few
exported JPEG selects beside them.

Every frame is over 100 KiB, so every frame's cas_id message is the
sampled layout whatever the frame's size. The set of sizes is the
configuration's (`shape_seed`); a run's seed gives every frame its bytes
and deals the sizes out in another order (see `plan`).
"""

from __future__ import annotations

import numpy as np

from benchmark.generators.common import seed_words


def plan(config: dict, seed: int, scale: float = 1.0) -> list[dict]:
    """The manifest for (config, seed): `frames` plain entries, then
    `exports` image entries. The sizes are drawn from `shape_seed` and
    are the same set for every seed; `seed` deals them out and fills the
    bytes, so that no two frames share content. `scale` shrinks both
    counts for the warm-up location and the tests, never for a timed
    pass."""
    shape = np.random.default_rng(
        seed_words(config.get("shape_seed", 0), 0x73686170))
    rng = np.random.default_rng(seed_words(seed, 0x72617773))
    frame, export = config["frame"], config["export"]
    n_frames = max(2, int(config["frames"] * scale))
    n_exports = max(1, int(config["exports"] * scale))
    sizes = shape.integers(frame["min_bytes"], frame["max_bytes"] + 1,
                           n_frames)
    dealt = rng.permutation(n_frames)  # frame i gets sizes[dealt[i]]
    manifest: list[dict] = [{
        "rel": (f"DCIM/{100 + i // 1000}CANON/IMG_{i:04d}."
                f"{frame['extension']}"),
        "size": int(sizes[int(dealt[i])]),
        "content": seed_words(seed, i),
    } for i in range(n_frames)]
    for j in range(n_exports):
        manifest.append({
            "rel": f"exports/IMG_{j:04d}.{export['format']}",
            "size": 0, "content": seed_words(seed, 1 << 30 | j),
            "image": {"w": export["width"], "h": export["height"],
                      "format": export["format"],
                      "orientation": export["orientation"],
                      "blocky": False},
        })
    return manifest


def new_entry(config: dict, rng, manifest: list[dict], serial: int,
              seed: int) -> dict:
    raise NotImplementedError(
        "raw_shoot has no traffic that adds files yet")
