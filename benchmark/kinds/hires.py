"""The kind `hires`: a photo of `generators/iphone15_roll.py`, a HEIC of
24, 48 or 12 MP or a panorama. `write` puts it on disk through
`kinds/heic.py`'s writer (one HEVC item in a HEIF container, the
container's `irot`/`imir`, an EXIF block) with a band of one-pixel line
pairs drawn across the picture; `programs` derives every resize program a
pass can dispatch for these frames from the program's own tables and
byte bounds; `compare` holds every timed pass to what `kinds/heic.py`
holds a HEIC to and, beyond that, to the triangle filter over the whole
picture (`reference/hires.py`); `control` gives the upper readings.

The numbers compared carry names of their own (`hires_*`): the limits
here were set from this configuration's readings, not `photolib_heic`'s.
"""

from __future__ import annotations

import importlib.util
import os
import sys
import threading
from collections import Counter

import numpy as np

from benchmark.generators.common import image_pixels, seed_words
from benchmark.reference import heic as ref
from benchmark.reference import hires as whole
from benchmark.reference import media
from benchmark.reference.video import decode_webp

#: mean |difference| of 255 between a stored thumbnail and the
#: reference's, worst photo of the sample. PERF.md §2 has the readings
#: on both sides: sound runs 2.6-3.0 (webp at quality 30 alone costs
#: 2.6-3.0 on these pictures), the controls (the picture mirrored, the
#: picture not turned) 55 or more.
PIXEL_GAP_LIMIT = 10.0
#: mean |difference| of 255 over the band of line pairs alone, worst photo
#: of the sample. Sound runs read under 2: the whole filter gives the
#: band's mean, and webp at quality 30 costs a flat grey little. Upper
#: readings: 80, the distance from the mean to either line's grey, both
#: for the control (the picture thinned by two or four before the filter)
#: and for a host that thins libheif's frame (HEVC at quality 80 hands
#: the lines back at 48.0 and 208.0, as drawn). PERF.md §2 has them.
DETAIL_GAP_LIMIT = 8.0
#: largest |difference| between a stored embedding and the float64
#: forward on the picture before the encoder: `kinds/heic.py`'s limit,
#: bfloat16 operands below it, float8 above.
EMBED_GAP_LIMIT = 0.03
#: photos compared pixel by pixel and vector by vector in each pass
SAMPLE = 8
#: a frame with a side over this many pixels was thinned by the program
#: before ISSUE 38; the control thins the same frames
THINNED_OVER = 4096


def _private_copy_of_the_heic_kind():
    """`kinds/heic.py` loaded a second time under a name of its own: its
    `write` draws the picture through its module's `picture`, and this
    copy's is replaced below by the one with the band. The module every
    other importer sees is not touched."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "heic.py")
    spec = importlib.util.spec_from_file_location("bench_hires_writer", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_heic = _private_copy_of_the_heic_kind()
can_write, KIND_IMAGE = _heic.can_write, _heic.KIND_IMAGE

# --- write -----------------------------------------------------------------


def picture(entry: dict) -> np.ndarray:
    """The sensor's picture, HxWx3 RGB uint8, before the encoder:
    `generators/common.py:image_pixels`' field from the entry's seed,
    the band of line pairs over it."""
    photo = entry["hires"]
    field = np.array(image_pixels(entry["content"], photo["w"], photo["h"],
                                  False))
    return whole.draw_band(field)


def _as_heic(entry: dict) -> dict:
    return {**entry, "heic": entry["hires"]}


_heic.picture = picture  # of an entry that `_as_heic` made: it has both


def write(path: str, entry: dict) -> None:
    _heic.write(path, _as_heic(entry))


# --- programs --------------------------------------------------------------

#: one program at a time: the widest holds gigabytes of canvases on the
#: host and on the device, and the hash programs warm beside it
_one_at_a_time = threading.Lock()


def _shape(e: dict) -> tuple:
    return e["hires"]["w"], e["hires"]["h"], e["hires"]["orientation"]


def displayed_size(photo: dict) -> tuple[int, int]:
    """(h, w) of the picture as displayed."""
    return ((photo["w"], photo["h"]) if photo["orientation"] >= 5
            else (photo["h"], photo["w"]))


def require_whole_frames(decoded, photo: dict, path: str) -> None:
    """End the run in set-up on a program that does not take this photo
    whole on the device: every pass of it would be incorrect (a thinned
    frame fails `hires_detail_gap`, a still resized on the host goes
    uncounted), and a cell it cannot run fails soon and by itself."""
    from spacedrive_tpu.object.media.thumbnail import process

    if decoded.array.shape[:2] != displayed_size(photo):
        raise SystemExit(
            f"benchmark: the program's decode hands on {path} as "
            f"{decoded.array.shape[:2]}, not the {displayed_size(photo)} "
            "libheif decoded: it thins the frame on the host, so this cell "
            "cannot run on it")
    if process.needs_cpu_fallback(decoded):
        raise SystemExit(
            f"benchmark: the program resizes {path} (target "
            f"{decoded.target}) on the host, not on the device, so this "
            "cell cannot run on it")


def programs(entries: list[dict], location: str, n_dev: int) -> list[tuple]:
    """The device programs a pass can dispatch for these photos. A
    program is (input canvas, output canvas, planes, batch pad); the
    first three are those of the frame the program's own decode hands on
    (`bucket_for`, `out_canvas_for`), one photo of every class decoded
    to see. The pads: photos of one canvas meet in one device call at
    most as many as there are, as `call_rows` lets into a call, as the
    thumbnailer's chunk holds in rows, and as `CHUNK_FRAME_BYTES` holds
    of each class's frames; every power of two up to that is warmed.
    And the embed pads the location's images form together."""
    from spacedrive_tpu.object.media.thumbnail import process
    from spacedrive_tpu.ops import thumbnail_jax as tj
    from spacedrive_tpu.parallel import autotune

    from benchmark import warm

    _heic.require_media_data(os.path.join(location, entries[0]["rel"]))
    photos_of = Counter(_shape(e) for e in entries)
    scale_max = int(autotune.SCALE_MAX)
    chunk_rows = autotune.THUMB_DEVICE_BATCH * n_dev * scale_max
    reach: Counter = Counter()  # program → photos that can meet in a call
    frames: dict[tuple, tuple] = {}
    for e in {_shape(e): e for e in reversed(entries)}.values():
        path = os.path.join(location, e["rel"])
        decoded = process.decode(path, e["rel"].rsplit(".", 1)[-1])
        require_whole_frames(decoded, e["hires"], path)
        frame = decoded.array
        key = (tj.bucket_for(*frame.shape[:2]),
               tj.out_canvas_for(*decoded.target), frame.shape[2])
        reach[key] += min(photos_of[_shape(e)],
                          max(1, process.CHUNK_FRAME_BYTES // frame.nbytes))
        # a turned photo is the harder frame: it transposes in
        if key not in frames or frame.shape[0] > frames[key][0].shape[0]:
            frames[key] = (np.zeros_like(frame), decoded.target)
    print("benchmark: a photo's frame reaches the resize as "
          + ", ".join(f"{f.shape} in {b} out {o}"
                      for (b, o, _c), (f, _t) in frames.items()),
          file=sys.stderr, flush=True)

    def resize(key, pad):
        frame, target = frames[key]
        with _one_at_a_time:
            tj.resize_batch([frame] * pad, [target] * pad)

    def embed(pad):
        from spacedrive_tpu.models import embedder
        from spacedrive_tpu.ops import embed_jax

        embed_jax.embed_batch(np.zeros(
            (pad, embedder.IMAGE_SIZE, embedder.IMAGE_SIZE, 3), np.float32))

    images = sum(name.rsplit(".", 1)[-1].lower() in process.IMAGE_EXTENSIONS
                 for _d, _dirs, names in os.walk(location) for name in names)
    own = []
    for key, n in sorted(reach.items()):
        (bh, bw), (oh, ow), planes = key
        most = min(n, chunk_rows, tj.call_rows(bh, bw, min(planes, 3)))
        own += [(pad * bh * bw * planes >> 20,
                 f"hires_resize_{bh}x{bw}x{planes}_out{oh}x{ow}_pad{pad}",
                 lambda key=key, pad=pad: resize(key, pad))
                for pad in warm._pow2_pads(most, most)]
    own += [(0, f"hires_embed_pad{pad}", lambda pad=pad: embed(pad))
            for pad in warm._pow2_pads(
                images, autotune.EMBED_DEVICE_BATCH * n_dev * scale_max)]
    return own


# --- compare ---------------------------------------------------------------


def sample_of(entries: list[dict], seed: int) -> list[dict]:
    """At most SAMPLE photos drawn from the seed, one of every class
    (sensor size and turn) first."""
    rng = np.random.default_rng(seed_words(seed, 0x68697265))
    order = [entries[int(i)] for i in rng.permutation(len(entries))]
    picked, seen = [], set()
    for e in order:
        if _shape(e) not in seen:
            seen.add(_shape(e))
            picked.append(e)
    picked += [e for e in order if e not in picked]
    return picked[:SAMPLE]


#: (content, class) → the reference's pixels, band and vector: a location
#: is compared once for every pass of a run, and its pictures do not change
_references: dict[tuple, dict] = {}


def reference_of(entry: dict, target_px: int) -> dict:
    photo = entry["hires"]
    key = (*entry["content"], *_shape(entry), target_px)
    if key not in _references:
        rgb = picture(entry)
        _references[key] = {
            "pixels": whole.thumbnail_pixels(rgb, photo["orientation"],
                                             target_px),
            "band": whole.band_in_thumbnail(photo["w"], photo["h"],
                                            photo["orientation"], target_px),
            "vector": ref.embedding(rgb, photo["orientation"])}
    return _references[key]


def compare(c, state: dict) -> set[str]:
    """One data directory against what every photo of the location is
    owed; → the photos that lack it."""
    import msgpack
    from PIL import Image

    db, rows, stored = state["db"], state["rows"], state["stored"]
    entries, want_cas = state["entries"], state["want_cas"]
    target = state["config"]["upstream"]["thumbnail"]["target_px"]
    kinds = dict(db.execute("SELECT id, kind FROM object"))
    data = {oid: blobs for oid, *blobs in db.execute(
        "SELECT object_id, resolution, media_date, camera_data, "
        "media_location FROM media_data")}
    vectors = dict(db.execute(
        "SELECT object_id, vector FROM object_embedding"))
    sample = {e["rel"] for e in sample_of(entries, state["seed"])}
    missing, wrong_size, wrong_kind = set(), set(), set()
    no_data, wrong_facts, no_vector = set(), set(), set()

    def unpacked(blob):
        return None if blob is None else msgpack.unpackb(blob)

    for e in entries:
        rel, photo = e["rel"], e["hires"]
        row = rows.get(rel)
        oid = None if row is None else row["object_id"]
        if kinds.get(oid) != KIND_IMAGE:
            wrong_kind.add(rel)
        if oid not in data:
            no_data.add(rel)
        elif ref.facts_wrong(photo, *(unpacked(b) for b in data[oid])):
            wrong_facts.add(rel)
        blob = vectors.get(oid)
        if blob is None or len(blob) != 4 * media.EMBED_DIM or \
                not np.isfinite(np.frombuffer(blob, "<f4")).all():
            no_vector.add(rel)
        thumb = stored.get(want_cas[rel] + ".webp")
        if thumb is None:
            missing.add(rel)
            continue
        with Image.open(thumb) as t:
            sized = t.format == "WEBP" and t.size == ref.thumbnail_size(
                photo["w"], photo["h"], photo["orientation"], target)
        if not sized:
            wrong_size.add(rel)
        elif rel in sample:
            want = reference_of(e, target)
            with open(thumb, "rb") as f:
                got = decode_webp(f.read())[1]
            c.worst("hires_pixel_gap", whole.detail_gap(
                got, want["pixels"], (slice(None), slice(None))),
                PIXEL_GAP_LIMIT)
            c.worst("hires_detail_gap", whole.detail_gap(
                got, want["pixels"], want["band"]), DETAIL_GAP_LIMIT)
            if rel not in no_vector:
                c.worst("hires_embedding_gap", media.embed_gap(
                    np.frombuffer(blob, "<f4"), want["vector"]),
                    EMBED_GAP_LIMIT)
    c.add("hires_thumbnail_missing", len(missing), 0)
    c.add("hires_thumbnail_wrong_size", len(wrong_size), 0)
    c.add("hires_kind_wrong", len(wrong_kind), 0)
    c.add("hires_media_data_missing", len(no_data), 0)
    c.add("hires_facts_wrong", len(wrong_facts), 0)
    c.add("hires_embedding_missing", len(no_vector), 0)
    return (missing | wrong_size | wrong_kind | no_data | wrong_facts
            | no_vector)


# --- control ---------------------------------------------------------------


def stride_before(photo: dict) -> int:
    """The stride a host takes through a frame to fit it under
    `THINNED_OVER` pixels a side (1: the frame fits as it is)."""
    return -(-max(photo["w"], photo["h"]) // THINNED_OVER)


def control(config: dict, entries: list[dict], location: str,
            seed: int) -> dict:
    """The reference in the program's place with the guarantee broken,
    read by `compare`'s own arithmetic, each photo's gap to the
    reference's thumbnail through webp at the stated quality. Thinned:
    every second (a panorama's every fourth) row and column of the
    picture before the filter, the band's gap, the smallest of the
    sampled photos over 4096 a side (every thinned photo has to show).
    Mirrored and not turned, as `kinds/heic.py`'s: the smallest of the
    sample; a photo turned by a quarter has another shape and fails the
    exact size check, so `not_turned` reads the half-turned ones. The
    embedding with float8 (e4m3) matmul operands where the configuration
    states bfloat16, the largest of the sample. What webp alone costs:
    the reference through webp, whole and over the band."""
    upstream = config["upstream"]["thumbnail"]
    target, quality = upstream["target_px"], upstream["webp_quality"]
    gaps: dict[str, list[float]] = {
        "thinned": [], "not_turned": [], "mirrored": [], "fp8": [],
        "sound": [], "sound_band": []}
    wrong_size = 0
    everywhere = (slice(None), slice(None))
    for e in sample_of(entries, seed):
        photo, rgb = e["hires"], picture(e)
        want = reference_of(e, target)

        def through_webp(pixels, band=everywhere):
            return whole.detail_gap(
                decode_webp(media.encode_webp(pixels, quality))[1],
                want["pixels"], band)

        if stride_before(photo) > 1:
            gaps["thinned"].append(through_webp(
                whole.thumbnail_pixels(rgb, photo["orientation"], target,
                                       stride_before(photo)), want["band"]))
        if photo["orientation"] in (2, 3, 4):
            gaps["not_turned"].append(through_webp(
                whole.thumbnail_pixels(rgb, 1, target)))
        elif photo["orientation"] != 1:
            wrong_size += (
                ref.thumbnail_size(photo["w"], photo["h"], 1, target)
                != ref.thumbnail_size(photo["w"], photo["h"],
                                      photo["orientation"], target))
        gaps["mirrored"].append(through_webp(whole.thumbnail_pixels(
            ref.mirrored(rgb), photo["orientation"], target)))
        gaps["fp8"].append(media.embed_gap(
            ref.embedding(rgb, photo["orientation"], True), want["vector"]))
        gaps["sound"].append(through_webp(want["pixels"]))
        gaps["sound_band"].append(through_webp(want["pixels"], want["band"]))
    out = {
        "hires_pixel_gap_mirrored": [min(gaps["mirrored"]), PIXEL_GAP_LIMIT],
        "hires_embedding_gap_fp8": [max(gaps["fp8"]), EMBED_GAP_LIMIT],
        "hires_thumbnail_wrong_size": [wrong_size, 0],
    }
    for name, limit in (("thinned", DETAIL_GAP_LIMIT),
                        ("not_turned", PIXEL_GAP_LIMIT)):
        if gaps[name]:  # the sample holds one of every class first
            gap = "detail" if name == "thinned" else "pixel"
            out[f"hires_{gap}_gap_{name}"] = [min(gaps[name]), limit]
    out["hires_pixel_gap_webp_alone"] = max(gaps["sound"])
    out["hires_detail_gap_webp_alone"] = max(gaps["sound_band"])
    return out
