"""The kind `video`: a clip of `generators/clip_roll.py`. `write` puts
it on disk as MPEG-4 part 2 in `.mp4` (OpenCV's writer; the
configuration says why not H.264), `programs` names the resize programs
its frames reach, `compare` holds every timed pass to what upstream owes
a clip (`reference/video.py`) and `control` gives that comparison's
upper readings.

A clip's pixels: every shot is a seeded low-frequency colour field (as
`generators/common.py:image_pixels` makes a photo's) with a small
square that moves a few pixels a frame, so that two frames of one shot
differ a little and two shots differ a lot.
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
from collections import Counter

import numpy as np

from benchmark.generators.common import image_pixels, seed_words
from benchmark.reference import video as ref

#: mean |difference| of 255 between a stored thumbnail and the
#: reference's, strips left out, worst clip of the sample. PERF.md §2
#: has the readings on both sides: sound runs (another decoder's frame
#: of the same shot, through the device resize and webp at quality 30),
#: and the controls (frame 0, the middle frame: another shot).
FRAME_GAP_LIMIT = 12.0
#: clips compared pixel by pixel in each pass
SAMPLE = 8
#: object.kind of a video (upstream crates/file-ext ObjectKind::Video)
KIND_VIDEO = 7

# --- write -----------------------------------------------------------------


def frames_bgr(entry: dict):
    """The clip's frames in order, HxWx3 BGR uint8 as OpenCV's writer
    takes them. One buffer is handed out again and again."""
    v = entry["video"]
    w, h = v["w"], v["h"]
    side = max(4, min(w, h) // 16)
    starts = [0, *v["cuts"], v["frames"]]
    for shot, (lo, hi) in enumerate(zip(starts, starts[1:])):
        rng = np.random.default_rng([*entry["content"], shot])
        field = np.asarray(image_pixels([*entry["content"], shot], w, h,
                                        False))[:, :, ::-1]
        colour = rng.integers(0, 256, 3, dtype=np.uint8)
        x, y = int(rng.integers(0, w - side)), int(rng.integers(0, h - side))
        dx, dy = (int(d) for d in rng.integers(2, 6, 2))
        frame = np.ascontiguousarray(field)
        for _ in range(lo, hi):
            frame[y:y + side, x:x + side] = field[y:y + side, x:x + side]
            x, y = (x + dx) % (w - side), (y + dy) % (h - side)
            frame[y:y + side, x:x + side] = colour
            yield frame


def write(path: str, entry: dict) -> None:
    import cv2

    v = entry["video"]
    writer = cv2.VideoWriter(path, cv2.VideoWriter_fourcc(*v["codec"]),
                             float(v["fps"]), (v["w"], v["h"]))
    if not writer.isOpened():
        raise SystemExit(f"benchmark: OpenCV's VideoWriter does not open "
                         f"the codec {v['codec']!r} for {path}")
    try:
        for frame in frames_bgr(entry):
            writer.write(frame)
    finally:
        writer.release()


# --- programs --------------------------------------------------------------

_PROBE = """
import sys
from spacedrive_tpu import native
for _ in range(12):
    native.video_frame(sys.argv[1])
    [bytearray(1 << 20) for _ in range(8)]
"""


def decoder_is_sound(clip: str, root: str) -> bool:
    """Whether the program's libav frontend takes this clip's frames
    without writing past their rows, tried in a child process that
    imports the frontend alone (no JAX, so it never asks for the chip):
    swscale writes whole blocks of 16 pixels, a row 1080 wide ends
    inside one, and a frontend that hands it a tight buffer corrupts its
    own heap (the program before PR 32). Such a process aborts a few
    clips on or hands on garbage; the child dies in its stead."""
    done = subprocess.run([sys.executable, "-c", _PROBE, clip], cwd=root,
                          capture_output=True, timeout=120)
    return done.returncode == 0


#: one clip program at a time: the widest holds gigabytes of canvases
#: on the host and on the device, and the hash programs warm beside it
_one_at_a_time = threading.Lock()


def programs(entries: list[dict], location: str, n_dev: int) -> list[tuple]:
    """The resize programs a pass can dispatch for these clips: the
    canvas bucket of the frame the program's own decode hands on (with
    the channels it hands on: an alpha plane is a program of its own),
    at every batch pad the thumbnailer can form for as many clips, from
    the program's tables as `warm.media_programs` derives the stills'."""
    import spacedrive_tpu
    from spacedrive_tpu import native
    from spacedrive_tpu.object.media.thumbnail import process
    from spacedrive_tpu.ops import thumbnail_jax as tj
    from spacedrive_tpu.parallel import autotune

    from benchmark import warm

    def shape(e):
        return e["video"]["w"], e["video"]["h"]

    clips_of = Counter(shape(e) for e in entries)
    first = {shape(e): e for e in reversed(entries)}
    by_libav = native.video_available()
    print("benchmark: the program takes a clip's frame through "
          + ("libav (native/movie_decoder.c)" if by_libav else "cv2"),
          file=sys.stderr, flush=True)
    if by_libav:
        root = os.path.dirname(os.path.dirname(spacedrive_tpu.__file__))
        for e in first.values():
            if not decoder_is_sound(os.path.join(location, e["rel"]), root):
                raise SystemExit(
                    f"benchmark: the program's libav frontend does not "
                    f"survive {e['video']['w']} x {e['video']['h']} frames "
                    f"(a child process that decoded {e['rel']} twelve times "
                    "died); this cell cannot run on it")
    per_bucket: Counter = Counter()
    frames: dict[tuple, tuple] = {}
    for e in first.values():
        decoded = process.decode(os.path.join(location, e["rel"]),
                                 e["rel"].rsplit(".", 1)[-1])
        bucket = tj.bucket_for(*decoded.array.shape[:2])
        per_bucket[bucket] += clips_of[shape(e)]
        frames.setdefault(bucket, (np.zeros_like(decoded.array),
                                   decoded.target))
    cap = autotune.THUMB_DEVICE_BATCH * n_dev * int(autotune.SCALE_MAX)

    def resize(bucket, pad):
        frame, target = frames[bucket]
        with _one_at_a_time:
            tj.resize_batch([frame] * pad, [target] * pad)

    return [(pad * frames[b][0].nbytes >> 20,
             f"video_resize_{b[0]}x{b[1]}x{frames[b][0].shape[2]}_pad{pad}",
             lambda b=b, pad=pad: resize(b, pad))
            for b, n in sorted(per_bucket.items())
            for pad in warm._pow2_pads(n, cap)]


# --- compare ---------------------------------------------------------------


def sample_of(entries: list[dict], seed: int) -> list[dict]:
    """At most SAMPLE clips drawn from the seed, one of every frame
    shape first."""
    rng = np.random.default_rng(seed_words(seed, 0x76696473))
    order = [entries[int(i)] for i in rng.permutation(len(entries))]
    picked, shapes = [], set()
    for e in order:
        shape = (e["video"]["w"], e["video"]["h"])
        if shape not in shapes:
            shapes.add(shape)
            picked.append(e)
    picked += [e for e in order if e not in picked]
    return picked[:SAMPLE]


#: (location, rel) → the reference's pixels: a location is compared
#: once for every pass of a run, and its files do not change
_references: dict[tuple[str, str], np.ndarray] = {}


def reference_pixels(location: str, entry: dict) -> np.ndarray:
    key = (location, entry["rel"])
    if key not in _references:
        _references[key] = ref.thumbnail_pixels(
            os.path.join(location, entry["rel"]), entry["video"]["frames"])
    return _references[key]


def _facts_wrong(video: dict, resolution, camera) -> bool:
    """Whether a `media_data` row departs from the manifest's plan of
    the clip: the frame size exactly, the rate to a hundredth, count
    and duration to one frame."""
    want = ref.facts(video)
    try:
        return (list(resolution) != [want["width"], want["height"]]
                or not camera.get("video")
                or abs(camera["fps"] - want["fps"]) > 0.01 * want["fps"]
                or abs(camera["frame_count"] - want["frames"]) > 1
                or abs(camera["duration_seconds"] - want["duration_s"])
                > 1.0 / want["fps"])
    except (TypeError, KeyError):
        return True


def compare(c, state: dict) -> set[str]:
    """One data directory against what every clip of the location is
    owed; → the clips that lack it."""
    import msgpack

    db, rows, stored = state["db"], state["rows"], state["stored"]
    entries, want_cas = state["entries"], state["want_cas"]
    kinds = dict(db.execute("SELECT id, kind FROM object"))
    media = {oid: (res, cam) for oid, res, cam in db.execute(
        "SELECT object_id, resolution, camera_data FROM media_data")}
    embedded = {oid for (oid,) in db.execute(
        "SELECT object_id FROM object_embedding")}
    sample = {e["rel"] for e in sample_of(entries, state["seed"])}
    missing, wrong_size, no_strip = set(), set(), set()
    wrong_kind, no_media, wrong_facts, has_vector = set(), set(), set(), set()
    for e in entries:
        rel, v = e["rel"], e["video"]
        row = rows.get(rel)
        oid = None if row is None else row["object_id"]
        if kinds.get(oid) != KIND_VIDEO:
            wrong_kind.add(rel)
        if oid in embedded:
            has_vector.add(rel)
        if oid not in media:
            no_media.add(rel)
        else:
            res, cam = media[oid]
            if res is None or cam is None or _facts_wrong(
                    v, msgpack.unpackb(res), msgpack.unpackb(cam)):
                wrong_facts.add(rel)
        thumb = stored.get(want_cas[rel] + ".webp")
        if thumb is None:
            missing.add(rel)
            continue
        with open(thumb, "rb") as f:
            fmt, got = ref.decode_webp(f.read())
        tw, th = ref.thumbnail_size(v["w"], v["h"])
        if fmt != "WEBP" or got.shape[:2] != (th, tw):
            wrong_size.add(rel)
        elif rel in sample:
            want = reference_pixels(state["location"], e)
            c.worst("video_frame_gap", ref.frame_gap(got, want),
                    FRAME_GAP_LIMIT)
            if not ref.strips_present(got, want):
                no_strip.add(rel)
    c.add("video_thumbnail_missing", len(missing), 0)
    c.add("video_thumbnail_wrong_size", len(wrong_size), 0)
    c.add("video_strip_missing", len(no_strip), 0)
    c.add("video_kind_wrong", len(wrong_kind), 0)
    c.add("video_media_data_missing", len(no_media), 0)
    c.add("video_facts_wrong", len(wrong_facts), 0)
    c.add("video_embedded", len(has_vector), 0)
    return (missing | wrong_size | no_strip | wrong_kind | no_media
            | wrong_facts | has_vector)


# --- control ---------------------------------------------------------------


def control(config: dict, entries: list[dict], location: str,
            seed: int) -> dict:
    """The reference in the program's place with the guarantee broken,
    read by `compare`'s own arithmetic: the thumbnail of frame 0 (no
    seek) and of the middle frame, through webp at the stated quality,
    each clip's gap to the reference's thumbnail, the smallest of the
    sample (every wrong clip has to show); the thumbnail without its
    strips; the thumbnail bound to 512 px."""
    quality = config["upstream"]["video_thumbnail"]["webp_quality"]
    sample = sample_of(entries, seed)
    gaps: dict[str, list[float]] = {"frame0": [], "middle": [], "sound": []}
    no_strip = wrong_size = 0

    def through_webp(rgb):
        return ref.decode_webp(ref.encode_webp(rgb, quality))[1]

    for e in sample:
        path, v = os.path.join(location, e["rel"]), e["video"]
        want = reference_pixels(location, e)
        gaps["sound"].append(ref.frame_gap(through_webp(want), want))
        gaps["frame0"].append(ref.frame_gap(through_webp(
            ref.thumbnail_pixels(path, v["frames"], index=0)), want))
        gaps["middle"].append(ref.frame_gap(through_webp(
            ref.thumbnail_pixels(path, v["frames"],
                                 index=v["frames"] // 2)), want))
        no_strip += not ref.strips_present(through_webp(want), want)
        wrong_size += (ref.thumbnail_size(v["w"], v["h"], 512)
                       != ref.thumbnail_size(v["w"], v["h"]))
    return {
        "video_frame_gap_frame0": [min(gaps["frame0"]), FRAME_GAP_LIMIT],
        "video_frame_gap_middle": [min(gaps["middle"]), FRAME_GAP_LIMIT],
        "video_frame_gap_codec_alone": max(gaps["sound"]),
        "video_strip_missing": [no_strip, 0],
        "video_thumbnail_wrong_size": [wrong_size, 0],
    }
