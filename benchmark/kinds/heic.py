"""The kind `heic`: a photo of `generators/iphone_roll.py`. `write` puts
it on disk as one HEVC image item in a HEIF container, through the
system's libheif and its x265 plugin by ctypes, with the container's
`irot`/`imir` and an EXIF block as a phone writes them; `programs` names
the resize and embed programs its frames reach; `compare` holds every
timed pass to what a HEIC is owed (`reference/heic.py`) and `control`
gives that comparison's upper readings.

The binding here is the writer's own: it shares no line with the
program's reader (`spacedrive_tpu/object/media/images.py`), only the C
library, which is also the only HEVC decoder on the machine.
"""

from __future__ import annotations

import ctypes
import ctypes.util
import os
import sys
import threading
from collections import Counter

import numpy as np

from benchmark.generators.common import image_pixels, seed_words
from benchmark.reference import heic as ref
from benchmark.reference import media

#: mean |difference| of 255 between a stored thumbnail and the
#: reference's, worst photo of the sample. PERF.md §2 has the readings on
#: both sides: sound runs (HEVC at quality 80, ≈0.9 of 255 before the
#: resize, then the device resize and webp at quality 30, 2-3 more), and
#: the controls (the picture not turned; the picture mirrored).
PIXEL_GAP_LIMIT = 10.0
#: largest |difference| between a stored embedding and the float64
#: forward on the picture before the encoder. Sound runs: bfloat16
#: operands on the chip, and HEVC's loss averaged over the 126 x 94
#: pixels that make one of the plane's; the control: float8 operands.
EMBED_GAP_LIMIT = 0.03
#: photos compared pixel by pixel and vector by vector in each pass
SAMPLE = 8
#: object.kind of an image (upstream crates/file-ext ObjectKind::Image)
KIND_IMAGE = 5

# --- write -----------------------------------------------------------------

_COMPRESSION = {"hevc": 1, "av1": 4}  # heif_compression_format
_COLORSPACE_RGB, _CHROMA_RGB, _CHANNEL_INTERLEAVED = 1, 10, 10


class _Error(ctypes.Structure):
    _fields_ = [("code", ctypes.c_int), ("subcode", ctypes.c_int),
                ("message", ctypes.c_char_p)]


class _EncodingOptions(ctypes.Structure):
    """`struct heif_encoding_options` up to its version 5 (libheif
    1.14): `heif_encoding_options_alloc` fills the defaults, and a
    library whose struct is older says so in `version`."""
    _fields_ = [("version", ctypes.c_uint8),
                ("save_alpha_channel", ctypes.c_uint8),
                ("macos_workaround", ctypes.c_uint8),
                ("save_two_colr_boxes", ctypes.c_uint8),
                ("output_nclx_profile", ctypes.c_void_p),
                ("macos_workaround_no_nclx", ctypes.c_uint8),
                ("image_orientation", ctypes.c_int)]


_lib = None
_lib_lock = threading.Lock()


def _libheif():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        try:
            lib = ctypes.CDLL(ctypes.util.find_library("heif")
                              or "libheif.so.1")
        except OSError:
            return None
        p, i, s = ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p
        pp = ctypes.POINTER(ctypes.c_void_p)
        for name, restype, argtypes in (
                ("heif_context_alloc", p, []),
                ("heif_context_free", None, [p]),
                ("heif_context_get_encoder_for_format", _Error, [p, i, pp]),
                ("heif_encoder_release", None, [p]),
                ("heif_encoder_set_lossy_quality", _Error, [p, i]),
                ("heif_encoder_set_parameter_string", _Error, [p, s, s]),
                ("heif_image_create", _Error, [i, i, i, i, pp]),
                ("heif_image_add_plane", _Error, [p, i, i, i, i]),
                ("heif_image_get_plane", ctypes.POINTER(ctypes.c_uint8),
                 [p, i, ctypes.POINTER(ctypes.c_int)]),
                ("heif_image_release", None, [p]),
                ("heif_encoding_options_alloc",
                 ctypes.POINTER(_EncodingOptions), []),
                ("heif_encoding_options_free", None,
                 [ctypes.POINTER(_EncodingOptions)]),
                ("heif_context_encode_image", _Error,
                 [p, p, p, ctypes.POINTER(_EncodingOptions), pp]),
                ("heif_context_add_exif_metadata", _Error, [p, p, s, i]),
                ("heif_image_handle_release", None, [p]),
                ("heif_context_write_to_file", _Error, [p, s]),
                ("heif_have_encoder_for_format", i, [i])):
            fn = getattr(lib, name)
            fn.restype, fn.argtypes = restype, argtypes
        _lib = lib
        return lib


def can_write(compression: str = "hevc") -> bool:
    """Whether this machine's libheif has an encoder for the codec."""
    lib = _libheif()
    return lib is not None and bool(
        lib.heif_have_encoder_for_format(_COMPRESSION[compression]))


def picture(entry: dict) -> np.ndarray:
    """The sensor's picture, HxWx3 RGB uint8, before the encoder: what
    `generators/common.py:image_pixels` draws from the entry's seed, the
    same field a JPEG of `photolib` holds."""
    h = entry["heic"]
    return np.asarray(image_pixels(entry["content"], h["w"], h["h"], False))


def exif_block(photo: dict) -> bytes:
    """The EXIF block a phone puts into the container, as PIL writes
    one: `Exif\\0\\0`, then a TIFF with IFD0 (make, model, orientation),
    the Exif IFD (the date taken, the sensor's pixel dimensions) and,
    where the photo has a position, the GPS IFD."""
    from PIL import Image

    want = ref.facts(photo)
    exif = Image.Exif()
    exif[ref.TAG_MAKE] = want["make"]
    exif[ref.TAG_MODEL] = want["model"]
    exif[ref.TAG_ORIENTATION] = want["orientation"]
    sub = exif.get_ifd(ref.TAG_EXIF_IFD)
    sub[ref.TAG_DATE_ORIGINAL] = want["date_taken"]
    sub[ref.TAG_PIXEL_X], sub[ref.TAG_PIXEL_Y] = want["resolution"]
    if photo.get("position"):
        from PIL.TiffImagePlugin import IFDRational

        gps = exif.get_ifd(ref.TAG_GPS_IFD)
        for tag, axis in ((1, "lat"), (3, "lon")):
            d, m, s = photo["position"][axis]["dms"]
            gps[tag] = photo["position"][axis]["ref"]
            gps[tag + 1] = (IFDRational(d), IFDRational(m),
                            IFDRational(s, 100))
    return exif.tobytes()


def write(path: str, entry: dict) -> None:
    photo = entry["heic"]
    lib = _libheif()
    if lib is None or not can_write(photo["compression"]):
        raise SystemExit(f"benchmark: libheif has no {photo['compression']} "
                         f"encoder on this machine: {path} cannot be written")

    def check(err: _Error, what: str) -> None:
        if err.code:
            raise SystemExit(f"benchmark: libheif {what} for {path}: "
                             f"{(err.message or b'?').decode()}")

    rgb = picture(entry)
    h, w = rgb.shape[:2]
    ctx = lib.heif_context_alloc()
    encoder, image, handle = (ctypes.c_void_p() for _ in range(3))
    options = None
    try:
        check(lib.heif_context_get_encoder_for_format(
            ctx, _COMPRESSION[photo["compression"]], ctypes.byref(encoder)),
            "encoder")
        check(lib.heif_encoder_set_lossy_quality(encoder, photo["quality"]),
              "quality")
        if photo.get("preset"):
            # without it libheif runs x265 at `slow`, 10 s a photo
            check(lib.heif_encoder_set_parameter_string(
                encoder, b"preset", photo["preset"].encode()), "preset")
        check(lib.heif_image_create(w, h, _COLORSPACE_RGB, _CHROMA_RGB,
                                    ctypes.byref(image)), "image")
        check(lib.heif_image_add_plane(image, _CHANNEL_INTERLEAVED, w, h, 8),
              "plane")
        stride = ctypes.c_int()
        plane = lib.heif_image_get_plane(image, _CHANNEL_INTERLEAVED,
                                         ctypes.byref(stride))
        rows = np.ctypeslib.as_array(plane, shape=(h, stride.value))
        rows[:, :w * 3] = rgb.reshape(h, w * 3)
        options = lib.heif_encoding_options_alloc()
        if photo["orientation"] != 1:
            if options.contents.version < 5:
                raise SystemExit(
                    "benchmark: this libheif's heif_encoding_options has "
                    f"version {options.contents.version}, under the 5 that "
                    "brings image_orientation: a turned photo cannot be "
                    "written as the container's irot/imir")
            # the rows are the sensor's; libheif writes the irot/imir
            # that turn them upright, as the EXIF tag of this value says
            options.contents.image_orientation = photo["orientation"]
        check(lib.heif_context_encode_image(ctx, image, encoder, options,
                                            ctypes.byref(handle)), "encode")
        if photo.get("exif", True):
            block = exif_block(photo)
            check(lib.heif_context_add_exif_metadata(
                ctx, handle, block, len(block)), "exif")
        check(lib.heif_context_write_to_file(ctx, os.fsencode(path)), "write")
    finally:
        if options is not None:
            lib.heif_encoding_options_free(options)
        if handle:
            lib.heif_image_handle_release(handle)
        if image:
            lib.heif_image_release(image)
        if encoder:
            lib.heif_encoder_release(encoder)
        lib.heif_context_free(ctx)


# --- programs --------------------------------------------------------------

#: one program at a time: the widest holds gigabytes of canvases on the
#: host and on the device, and the hash programs warm beside it
_one_at_a_time = threading.Lock()


def require_media_data(photo: str) -> None:
    """End the run in set-up on a program that reads nothing out of a
    HEIF container (the program before ISSUE 34 opens the file with PIL,
    which cannot): every pass of it would lack every HEIC's `media_data`
    row, and a cell it cannot run fails soon and by itself."""
    from spacedrive_tpu.object.media.media_data import ImageMetadata

    if ImageMetadata.from_path(photo) is None:
        raise SystemExit(
            f"benchmark: the program's ImageMetadata.from_path reads nothing "
            f"out of {photo}: it gives a HEIC no media_data row, so this "
            "cell cannot run on it")


def programs(entries: list[dict], location: str, n_dev: int) -> list[tuple]:
    """The device programs a pass can dispatch for these photos: the
    resize at the canvas bucket of the frame the program's own decode
    hands on, with the channels it hands on (an alpha plane is a program
    of its own, run beside the colour planes'), at every batch pad the
    thumbnailer can form for as many photos; and the embed pads the
    location's images form together, the photos among them, which
    `warm.media_programs` counts without them."""
    from spacedrive_tpu.object.media.thumbnail import process
    from spacedrive_tpu.ops import thumbnail_jax as tj
    from spacedrive_tpu.parallel import autotune

    from benchmark import warm

    def shape(e):
        return e["heic"]["w"], e["heic"]["h"], e["heic"]["orientation"]

    require_media_data(os.path.join(location, entries[0]["rel"]))
    photos_of = Counter(shape(e) for e in entries)
    per_bucket: Counter = Counter()
    frames: dict[tuple, tuple] = {}
    for e in {shape(e): e for e in reversed(entries)}.values():
        decoded = process.decode(os.path.join(location, e["rel"]),
                                 e["rel"].rsplit(".", 1)[-1])
        bucket = tj.bucket_for(*decoded.array.shape[:2])
        per_bucket[bucket] += photos_of[shape(e)]
        # a turned photo is the harder frame: it transposes in
        if bucket not in frames or decoded.array.shape[0] > \
                frames[bucket][0].shape[0]:
            frames[bucket] = (np.zeros_like(decoded.array), decoded.target)
    print("benchmark: a HEIC's frame reaches the resize as "
          + ", ".join(f"{f.shape} in {b}" for b, (f, _t) in frames.items()),
          file=sys.stderr, flush=True)

    def resize(bucket, pad):
        frame, target = frames[bucket]
        with _one_at_a_time:
            tj.resize_batch([frame] * pad, [target] * pad)

    def embed(pad):
        from spacedrive_tpu.models import embedder
        from spacedrive_tpu.ops import embed_jax

        embed_jax.embed_batch(np.zeros(
            (pad, embedder.IMAGE_SIZE, embedder.IMAGE_SIZE, 3), np.float32))

    scale_max = int(autotune.SCALE_MAX)
    images = sum(name.rsplit(".", 1)[-1].lower() in process.IMAGE_EXTENSIONS
                 for _d, _dirs, names in os.walk(location) for name in names)
    own = [(pad * frames[b][0].nbytes >> 20,
            f"heic_resize_{b[0]}x{b[1]}x{frames[b][0].shape[2]}_pad{pad}",
            lambda b=b, pad=pad: resize(b, pad))
           for b, n in sorted(per_bucket.items())
           for pad in warm._pow2_pads(
               n, autotune.THUMB_DEVICE_BATCH * n_dev * scale_max)]
    own += [(0, f"heic_embed_pad{pad}", lambda pad=pad: embed(pad))
            for pad in warm._pow2_pads(
                images, autotune.EMBED_DEVICE_BATCH * n_dev * scale_max)]
    return own


# --- compare ---------------------------------------------------------------


def sample_of(entries: list[dict], seed: int) -> list[dict]:
    """At most SAMPLE photos drawn from the seed, one of every
    orientation first."""
    rng = np.random.default_rng(seed_words(seed, 0x68656963))
    order = [entries[int(i)] for i in rng.permutation(len(entries))]
    picked, seen = [], set()
    for e in order:
        if e["heic"]["orientation"] not in seen:
            seen.add(e["heic"]["orientation"])
            picked.append(e)
    picked += [e for e in order if e not in picked]
    return picked[:SAMPLE]


#: (content, orientation) → the reference's pixels and vector: a
#: location is compared once for every pass of a run, and its pictures
#: do not change
_references: dict[tuple, dict] = {}


def reference_of(entry: dict, target_px: int) -> dict:
    photo = entry["heic"]
    key = (*entry["content"], photo["w"], photo["h"], photo["orientation"],
           target_px)
    if key not in _references:
        rgb = picture(entry)
        _references[key] = {
            "pixels": ref.thumbnail_pixels(rgb, photo["orientation"],
                                           target_px),
            "vector": ref.embedding(rgb, photo["orientation"])}
    return _references[key]


def compare(c, state: dict) -> set[str]:
    """One data directory against what every HEIC of the location is
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
        rel, photo = e["rel"], e["heic"]
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
                c.worst("heic_pixel_gap", media.thumbnail_gap(
                    f.read(), want["pixels"]), PIXEL_GAP_LIMIT)
            if rel not in no_vector:
                c.worst("heic_embedding_gap", media.embed_gap(
                    np.frombuffer(blob, "<f4"), want["vector"]),
                    EMBED_GAP_LIMIT)
    c.add("heic_thumbnail_missing", len(missing), 0)
    c.add("heic_thumbnail_wrong_size", len(wrong_size), 0)
    c.add("heic_kind_wrong", len(wrong_kind), 0)
    c.add("heic_media_data_missing", len(no_data), 0)
    c.add("heic_facts_wrong", len(wrong_facts), 0)
    c.add("heic_embedding_missing", len(no_vector), 0)
    return (missing | wrong_size | wrong_kind | no_data | wrong_facts
            | no_vector)


# --- control ---------------------------------------------------------------


def control(config: dict, entries: list[dict], location: str,
            seed: int) -> dict:
    """The reference in the program's place with the guarantee broken,
    read by `compare`'s own arithmetic. The thumbnail of the picture as
    the sensor stored it (the container's turn left out) and of the
    picture mirrored (a turn applied that was not written), each through
    webp at the stated quality, each photo's gap to the reference's
    thumbnail, the smallest of the sample (every wrong photo has to
    show; a photo turned by a quarter has another shape and fails the
    exact size check, so `not_turned` reads the others). The embedding
    with float8 (e4m3) matmul operands where the configuration states
    bfloat16, the largest of the sample, as `embedding_gap`'s control.
    What webp alone costs: the reference through webp."""
    upstream = config["upstream"]["thumbnail"]
    target, quality = upstream["target_px"], upstream["webp_quality"]
    gaps: dict[str, list[float]] = {
        "not_turned": [], "mirrored": [], "sound": [], "fp8": []}
    wrong_size = 0
    for e in sample_of(entries, seed):
        photo, rgb = e["heic"], picture(e)
        want = reference_of(e, target)

        def through_webp(pixels):
            return media.thumbnail_gap(media.encode_webp(pixels, quality),
                                       want["pixels"])

        if photo["orientation"] in (2, 3, 4):
            gaps["not_turned"].append(through_webp(
                ref.thumbnail_pixels(rgb, 1, target)))
        elif photo["orientation"] != 1:
            wrong_size += (
                ref.thumbnail_size(photo["w"], photo["h"], 1, target)
                != ref.thumbnail_size(photo["w"], photo["h"],
                                      photo["orientation"], target))
        gaps["mirrored"].append(through_webp(ref.thumbnail_pixels(
            ref.mirrored(rgb), photo["orientation"], target)))
        gaps["fp8"].append(media.embed_gap(
            ref.embedding(rgb, photo["orientation"], True), want["vector"]))
        gaps["sound"].append(through_webp(want["pixels"]))
    out = {
        "heic_pixel_gap_mirrored": [min(gaps["mirrored"]), PIXEL_GAP_LIMIT],
        "heic_embedding_gap_fp8": [max(gaps["fp8"]), EMBED_GAP_LIMIT],
        "heic_thumbnail_wrong_size": [wrong_size, 0],
    }
    if gaps["not_turned"]:
        out["heic_pixel_gap_not_turned"] = [min(gaps["not_turned"]),
                                            PIXEL_GAP_LIMIT]
    out["heic_pixel_gap_webp_alone"] = max(gaps["sound"])
    return out
