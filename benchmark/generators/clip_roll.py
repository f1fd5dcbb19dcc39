"""`clip_roll`: the videos of a phone's DCIM folder, from the parameters
of a configuration file (benchmark/configs/photolib_video.json names the
sources): clips at the camera's frame size and rate, one in
`portrait_every` written turned, and one JPEG still (the `photo` class
of `photolib`) for every `clips_per_still` clips.

A clip is an entry of the kind `video` (`kinds/video.py` writes it and
holds the program to its thumbnail and facts). Its plan is a frame
count and the frames at which its shots are cut. The cuts are laid so
that frame 0, the frame a tenth of the way in and the middle frame are
three different pictures whichever decoder takes the tenth: upstream
seeks back to the key frame at or before the mark, OpenCV counts to the
exact frame, and `shots_apart` says what the plan owes both.

The set of durations is the configuration's (`shape_seed`); a run's
seed deals them out, places the cuts and draws every shot's pixels.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.generators.common import seed_words
from benchmark.reference.video import mark_frame


def shot_of(video: dict, frame: int) -> int:
    """Which shot of the clip a frame shows: 0 before the first cut."""
    return sum(1 for cut in video["cuts"] if frame >= cut)


def shots_apart(video: dict) -> bool:
    """The shot rule. The first cut falls on or before the last regular
    key frame before the mark; every frame from there to the exact
    frame (the regular key frame, one the encoder puts at the cut, the
    exact frame itself) shows one shot; neither frame 0 nor the middle
    frame shows it."""
    mark = mark_frame(video["frames"])
    key = mark - mark % video["key_interval"]
    first_cut = video["cuts"][0]
    taken = {shot_of(video, f) for f in range(first_cut, mark + 1)}
    return (first_cut <= key and len(taken) == 1
            and shot_of(video, 0) not in taken
            and shot_of(video, video["frames"] // 2) not in taken)


def _clip(config: dict, rng, frames: int, portrait: bool) -> dict:
    clip = config["clip"]
    w, h = clip["width"], clip["height"]
    mark, middle = mark_frame(frames), frames // 2
    # a short lead-in, cut before the first regular key frame; two more
    # cuts after the mark and before the middle
    lead = int(rng.integers(4, clip["key_interval"] - 1))
    later = sorted(int(c) for c in rng.choice(
        np.arange(mark + 2, middle), 2, replace=False))
    return {"w": h if portrait else w, "h": w if portrait else h,
            "fps": clip["fps"], "frames": frames, "codec": clip["codec"],
            "key_interval": clip["key_interval"], "cuts": [lead, *later]}


def plan(config: dict, seed: int, scale: float = 1.0) -> list[dict]:
    """The manifest for (config, seed): `clips` entries of the kind
    `video`, then the stills. The durations are drawn from `shape_seed`
    and are the same set for every seed; `seed` deals them out. `scale`
    shrinks the counts for the warm-up location and the tests, never
    for a timed pass."""
    clip, photo = config["clip"], config["photo"]
    shape = np.random.default_rng(
        seed_words(config.get("shape_seed", 0), 0x636C6970))
    rng = np.random.default_rng(seed_words(seed, 0x766964))
    n_clips = max(2, int(config["clips"] * scale))
    n_stills = max(1, n_clips // config["clips_per_still"])
    d = clip["duration_s"]
    seconds = np.clip(shape.lognormal(math.log(d["median"]), d["sigma"],
                                      n_clips), d["min"], d["max"])
    dealt = rng.permutation(n_clips)  # clip i gets seconds[dealt[i]]
    manifest: list[dict] = []
    for i in range(n_clips):
        frames = int(round(float(seconds[int(dealt[i])]) * clip["fps"]))
        manifest.append({
            "rel": f"DCIM/{100 + i // 1000}MEDIA/VID_{i:04d}."
                   f"{clip['extension']}",
            "size": 0, "content": seed_words(seed, i), "kind": "video",
            "video": _clip(config, rng, frames,
                           i % clip["portrait_every"]
                           == clip["portrait_every"] - 1),
        })
    orientations = photo.get("exif_orientations", [1])
    for j in range(n_stills):
        manifest.append({
            "rel": f"DCIM/100MEDIA/IMG_{j:04d}.{photo['format']}",
            "size": 0, "content": seed_words(seed, 1 << 30 | j),
            "image": {"w": photo["width"], "h": photo["height"],
                      "format": photo["format"],
                      "orientation": orientations[j % len(orientations)],
                      "blocky": False},
        })
    return manifest


def new_entry(config: dict, rng, manifest: list[dict], serial: int,
              seed: int) -> dict:
    raise NotImplementedError(
        "clip_roll has no traffic that adds files yet")
