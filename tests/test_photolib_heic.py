"""`photolib.heic` at a small size (ISSUE 34): an iPhone's roll as the
phone writes it, HEICs of 640 x 480 written by `benchmark/kinds/heic.py`
(HEVC in HEIF through libheif, the container's `irot`, an EXIF block)
through the program's own path (`process.decode` by libheif, the device
resize, `finish`, `ImageMetadata` from the container, a whole
`cli.index_location`) and held against `benchmark/reference/heic.py`,
which imports nothing of the program. Since ISSUE 35 the frame follows
the file: RGB, three planes in one dispatch, for a camera's photo, and
RGBA with the alpha dispatch only where the handle reports an alpha
channel."""

import ctypes

import asyncio
import io
import json
import os

import numpy as np
import pytest

from benchmark import check, harness
from benchmark.generators import common, iphone_roll
from benchmark.reference import heic as ref
from benchmark.reference import media as ref_media
from spacedrive_tpu import telemetry
from spacedrive_tpu.object.media import images
from spacedrive_tpu.object.media.media_data import ImageMetadata
from spacedrive_tpu.object.media.thumbnail import process
from spacedrive_tpu.telemetry import metrics as tm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3000000019  # over 32 signed bits
TARGET = 262144


def tiny_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "photolib_heic.json")) as f:
        config = json.load(f)
    config["photos"] = 8
    config["photos_per_screenshot"] = 3
    config["photo"].update(width=640, height=480,
                           exif_orientations=[1, 6, 3, 8])
    config["screenshot"].update(width=234, height=506)
    return config


@pytest.fixture(scope="module")
def kind():
    mod = harness.Bench(ROOT).kinds(tiny_config())["heic"]
    if not (images.heif_available() and mod.can_write("hevc")):
        pytest.skip("libheif has no HEVC encoder on this machine")
    return mod


@pytest.fixture(scope="module")
def location(tmp_path_factory, kind):
    """The tiny location on disk: (path, manifest, photos)."""
    root = str(tmp_path_factory.mktemp("roll") / "location")
    os.makedirs(root)
    manifest = iphone_roll.plan(tiny_config(), SEED)
    common.write_manifest(root, manifest, {"heic": kind})
    return root, manifest, common.entries_of(manifest, "heic")


def _photo(location, orientation) -> tuple[str, dict]:
    """(path, entry) of the location's first photo turned so."""
    root, _manifest, photos = location
    e = next(p for p in photos if p["heic"]["orientation"] == orientation)
    return os.path.join(root, e["rel"]), e


# --- one photo through the thumbnailer's stages ------------------------------


@pytest.mark.parametrize("orientation", [1, 6], ids=["640x480", "turned"])
def test_thumbnail_is_within_the_references_gap(location, kind, orientation):
    """A 640 x 480 HEIC and one the container turns to 480 x 640 through
    `process.decode` → `resize_batch` → `finish`: a tight RGB frame (a
    camera's photo has no alpha channel), the colour dispatch alone, the
    picture as displayed, once."""
    path, e = _photo(location, orientation)
    alpha_before = tm.THUMB_RESIZE_IMAGES.value(alpha="1")
    plain_before = tm.THUMB_RESIZE_IMAGES.value(alpha="0")
    frames_before = tm.THUMB_HEIF_FRAMES.value(result="ok")
    tapped = []
    d = process.decode(path, "HEIC", lambda frame, scale: tapped.append(
        (frame.shape, scale)))
    w, h = (480, 640) if orientation == 6 else (640, 480)
    assert d.array.shape == (h, w, 3) and d.array.dtype == np.uint8
    assert d.array.strides == (w * 3, 3, 1)  # one copy into its canvas
    assert d.orientation == 1 and not d.is_video  # EXIF is not applied again
    assert tapped == [((h, w, 3), 1)]
    tw, th = ref.thumbnail_size(640, 480, orientation, TARGET)
    assert d.target == (th, tw)
    webp = process.finish(d, process.resize_decoded([d])[0])
    assert tm.THUMB_RESIZE_IMAGES.value(alpha="0") == plain_before + 1
    assert tm.THUMB_RESIZE_IMAGES.value(alpha="1") == alpha_before
    from PIL import Image

    assert Image.open(io.BytesIO(webp)).mode == "RGB"
    assert tm.THUMB_HEIF_FRAMES.value(result="ok") == frames_before + 1
    rgb = kind.picture(e)
    want = ref.thumbnail_pixels(rgb, orientation, TARGET)
    assert want.shape == (th, tw, 3)
    assert ref_media.thumbnail_gap(webp, want) < kind.PIXEL_GAP_LIMIT
    # neither the picture as the sensor stored it nor a mirrored one
    # would pass for it
    assert ref_media.thumbnail_gap(webp, ref.thumbnail_pixels(
        ref.mirrored(rgb), orientation, TARGET)) > 2 * kind.PIXEL_GAP_LIMIT
    if orientation == 6:
        assert ref_media.thumbnail_gap(
            webp, ref.thumbnail_pixels(rgb, 1, TARGET)) == 255.0


def test_a_half_turn_is_applied_once(location, kind):
    path, e = _photo(location, 3)
    d = process.decode(path, "heic")
    webp = process.finish(d, process.resize_decoded([d])[0])
    rgb = kind.picture(e)
    assert ref_media.thumbnail_gap(
        webp, ref.thumbnail_pixels(rgb, 3, TARGET)) < kind.PIXEL_GAP_LIMIT
    assert ref_media.thumbnail_gap(
        webp, ref.thumbnail_pixels(rgb, 1, TARGET)) > 2 * kind.PIXEL_GAP_LIMIT


# --- the frame's channels follow the file (ISSUE 35) -------------------------


def _says_it_has_alpha(monkeypatch):
    """Every handle reports an alpha channel, so `decode_heif` asks
    libheif for `heif_chroma_interleaved_RGBA` as it did for every file
    before ISSUE 35; libheif fills the plane a photo lacks with 255."""
    monkeypatch.setattr(images._load_heif(),
                        "heif_image_handle_has_alpha_channel",
                        lambda handle: 1)


@pytest.mark.parametrize("orientation", [1, 6], ids=["640x480", "turned"])
def test_the_rgb_frame_is_the_rgba_frames_colour(location, monkeypatch,
                                                 orientation):
    path, _e = _photo(location, orientation)
    rgb = images.decode_heif(path)
    _says_it_has_alpha(monkeypatch)
    rgba = images.decode_heif(path)
    h, w = (640, 480) if orientation == 6 else (480, 640)
    assert rgb.shape == (h, w, 3) and rgba.shape == (h, w, 4)
    assert rgb.flags.c_contiguous and (rgba[..., 3] == 255).all()
    assert np.array_equal(rgb, rgba[..., :3])


@pytest.mark.parametrize("orientation", [1, 6], ids=["640x480", "turned"])
def test_three_planes_give_the_thumbnail_and_the_plane_four_gave(
        location, orientation):
    """The stored colour and the embedder's plane are what they were
    when the frame carried a plane of 255s: byte for byte."""
    from spacedrive_tpu.models import embedder
    from spacedrive_tpu.ops import thumbnail_jax as tj

    path, _e = _photo(location, orientation)
    rgb = images.decode_heif(path)
    rgba = np.concatenate(
        [rgb, np.full((*rgb.shape[:2], 1), 255, np.uint8)], axis=-1)
    tw, th = tj.scale_dimensions(rgb.shape[1], rgb.shape[0])
    small, = tj.resize_batch([rgb], [(th, tw)])
    small_a, = tj.resize_batch([rgba], [(th, tw)])
    assert small.shape == (th, tw, 3) and small_a.shape == (th, tw, 4)
    assert np.array_equal(small, small_a[..., :3])
    assert (small_a[..., 3] == 255).all()
    assert np.array_equal(embedder.plane_from_frame(rgb),
                          embedder.plane_from_frame(rgba))


def _write_with_alpha(kind, path: str, rgba: np.ndarray) -> bool:
    """`rgba` as one HEVC item with its alpha as an auxiliary image,
    through the kind's own binding of the writer's side of libheif;
    False where this machine's encoder takes no alpha plane."""
    lib = kind._libheif()
    h, w = rgba.shape[:2]
    ctx = lib.heif_context_alloc()
    encoder, image, handle = (ctypes.c_void_p() for _ in range(3))
    try:
        steps = (
            lambda: lib.heif_context_get_encoder_for_format(
                ctx, 1, ctypes.byref(encoder)),
            lambda: lib.heif_encoder_set_lossy_quality(encoder, 80),
            lambda: lib.heif_encoder_set_parameter_string(
                encoder, b"preset", b"ultrafast"),
            # heif_colorspace_RGB, heif_chroma_interleaved_RGBA
            lambda: lib.heif_image_create(w, h, 1, 11, ctypes.byref(image)),
            lambda: lib.heif_image_add_plane(image, 10, w, h, 8))
        if any(step().code for step in steps):
            return False
        stride = ctypes.c_int()
        plane = lib.heif_image_get_plane(image, 10, ctypes.byref(stride))
        np.ctypeslib.as_array(plane, shape=(h, stride.value))[:, :w * 4] = \
            rgba.reshape(h, w * 4)
        return not (
            lib.heif_context_encode_image(
                ctx, image, encoder, None, ctypes.byref(handle)).code
            or lib.heif_context_write_to_file(ctx, os.fsencode(path)).code)
    finally:
        if handle:
            lib.heif_image_handle_release(handle)
        if image:
            lib.heif_image_release(image)
        if encoder:
            lib.heif_encoder_release(encoder)
        lib.heif_context_free(ctx)


def _is_rgba_with_the_alpha_dispatch(path: str, h: int, w: int):
    """→ (frame, webp): four channels from the decode, through the
    alpha dispatch, to the stored bytes."""
    alpha_before = tm.THUMB_RESIZE_IMAGES.value(alpha="1")
    plain_before = tm.THUMB_RESIZE_IMAGES.value(alpha="0")
    tapped = []
    d = process.decode(path, "heic",
                       lambda frame, scale: tapped.append(frame.shape))
    assert d.array.shape == (h, w, 4) and d.array.dtype == np.uint8
    assert tapped == [(h, w, 4)]
    webp = process.finish(d, process.resize_decoded([d])[0])
    assert tm.THUMB_RESIZE_IMAGES.value(alpha="1") == alpha_before + 1
    assert tm.THUMB_RESIZE_IMAGES.value(alpha="0") == plain_before
    return d.array, webp


def test_a_file_with_an_alpha_channel_keeps_it(kind, tmp_path):
    """A cut-out: the lower half opaque, the upper half clear."""
    rgba = np.empty((480, 640, 4), np.uint8)
    rgba[..., :3] = kind.picture(
        {"heic": {"w": 640, "h": 480}, "content": [11, 35]})
    rgba[..., 3] = 255
    rgba[:240, :, 3] = 0
    path = str(tmp_path / "cutout.heic")
    if not _write_with_alpha(kind, path, rgba):
        pytest.skip("this libheif's encoder takes no alpha plane")
    from PIL import Image

    frame, webp = _is_rgba_with_the_alpha_dispatch(path, 480, 640)
    assert (frame[:232, :, 3] < 16).all() and (frame[248:, :, 3] > 239).all()
    stored = Image.open(io.BytesIO(webp))
    assert stored.mode == "RGBA"
    stored = np.asarray(stored)
    th = stored.shape[0]
    assert (stored[:th // 2 - 8, :, 3] < 16).all()
    assert (stored[th // 2 + 8:, :, 3] > 239).all()
    assert np.abs(frame[248:, :, :3].astype(np.int16)
                  - rgba[248:, :, :3]).mean() < 3


def test_a_handle_that_reports_alpha_takes_the_alpha_dispatch(
        location, monkeypatch):
    """The flag alone decides: a photo whose handle says it has an
    alpha channel goes the way every HEIC went before ISSUE 35, and
    what is stored is the same bytes either way: libwebp writes no
    alpha chunk for a plane of 255s."""
    path, _e = _photo(location, 6)
    d = process.decode(path, "heic")
    plain = process.finish(d, process.resize_decoded([d])[0])
    _says_it_has_alpha(monkeypatch)
    frame, webp = _is_rgba_with_the_alpha_dispatch(path, 640, 480)
    assert (frame[..., 3] == 255).all()
    assert webp == plain


def test_the_decoder_hands_on_the_displayed_picture(location, kind):
    """What `benchmark/tests/test_heic_kind_cpu.py::
    test_written_photos_are_what_the_plan_says` holds of the program's
    decode, with the channels the decode hands on now (that case pins
    four and waits for a `benchmark` PR: tests/test_benchmark_suite.py)."""
    root, _manifest, photos = location
    for e in photos:
        path = os.path.join(root, e["rel"])
        assert e["size"] == os.path.getsize(path) > 0
        with open(path, "rb") as f:
            head = f.read(4096)
        assert head[4:12] == b"ftypheic" and b"hvcC" in head
        # the turn is the container's own
        assert (b"irot" in head) == (e["heic"]["orientation"] != 1)
        shown = images.decode_heif(path)
        want = ref.displayed(kind.picture(e), e["heic"]["orientation"])
        assert shown.shape == want.shape and shown.dtype == np.uint8
        assert np.abs(shown.astype(np.int16) - want).mean() < 3


# --- the container's EXIF ----------------------------------------------------


def test_exif_comes_back_field_for_field(location):
    root, _manifest, photos = location
    assert {p["heic"]["orientation"] for p in photos} == {1, 3, 6, 8}
    assert {p["heic"]["position"] is None for p in photos} == {True, False}
    for e in photos:
        want = ref.facts(e["heic"])
        meta = ImageMetadata.from_path(os.path.join(root, e["rel"]))
        assert list(meta.resolution) == want["resolution"] == [640, 480]
        assert meta.date_taken == want["date_taken"]
        assert meta.epoch_time is not None
        assert meta.camera_data.device_make == want["make"] == "Apple"
        assert meta.camera_data.device_model == want["model"]
        assert meta.camera_data.orientation == want["orientation"]
        if want["gps"] is None:
            assert meta.location is None
        else:
            assert meta.location.latitude == pytest.approx(want["gps"][0],
                                                           abs=1e-9)
            assert meta.location.longitude == pytest.approx(want["gps"][1],
                                                            abs=1e-9)
        # and the row's blobs are what the kind's comparison unpacks
        import msgpack

        row = meta.to_row(1)
        assert not ref.facts_wrong(
            e["heic"], msgpack.unpackb(row["resolution"]),
            msgpack.unpackb(row["media_date"]),
            msgpack.unpackb(row["camera_data"]),
            None if row["media_location"] is None
            else msgpack.unpackb(row["media_location"]))


def test_the_block_starts_at_the_tiff_header_whatever_the_offset(location):
    """A phone's item is `Exif\\0\\0` then TIFF, offset 6; libheif's own
    writer finds the header and says so in the first four bytes."""
    root, _manifest, photos = location
    size, block = images.heif_container(os.path.join(root, photos[0]["rel"]))
    assert size == (640, 480) and block[:4] in (b"MM\x00*", b"II*\x00")


def test_a_file_without_an_exif_block_gives_the_resolution_alone(
        tmp_path, kind):
    e = iphone_roll.plan(tiny_config(), 7)[0]
    e["heic"]["exif"] = False
    path = str(tmp_path / "bare.heic")
    kind.write(path, e)
    assert images.heif_container(path) == ((640, 480), None)
    meta = ImageMetadata.from_path(path)
    assert meta == ImageMetadata(resolution=(640, 480))
    assert meta.date_taken is None and meta.camera_data.device_make is None


def test_heif_extensions_are_extracted_only_where_libheif_loads(monkeypatch):
    from spacedrive_tpu.object.media import job

    assert images.heif_available()
    for ext in ("heic", "heif", "avif"):
        assert ext in job.EXIF_EXTENSIONS and ext in job.MEDIA_DATA_EXTENSIONS
        assert process.can_generate(ext) and process.can_generate(ext.upper())
    assert job.EXIF_EXTENSIONS[:5] == ("jpg", "jpeg", "png", "tiff", "webp")


# --- a torn file -------------------------------------------------------------


def test_a_truncated_file_is_a_thumb_error_and_no_row(location, tmp_path):
    root, _manifest, photos = location
    with open(os.path.join(root, photos[0]["rel"]), "rb") as f:
        whole = f.read()
    torn = tmp_path / "torn.HEIC"
    torn.write_bytes(whole[:len(whole) // 2])
    before = tm.THUMB_HEIF_FRAMES.value(result="error")
    with pytest.raises(process.ThumbError):
        process.decode(str(torn), "HEIC")
    assert tm.THUMB_HEIF_FRAMES.value(result="error") == before + 1
    assert ImageMetadata.from_path(str(torn)) is None


async def test_a_torn_file_costs_its_own_thumbnail_and_the_batch_goes_on(
        location, tmp_path):
    from spacedrive_tpu.object.media.thumbnail.actor import Thumbnailer

    root, _manifest, photos = location
    good = [os.path.join(root, e["rel"]) for e in photos[:2]]
    torn = tmp_path / "torn.HEIC"
    with open(good[0], "rb") as f:
        torn.write_bytes(f.read()[:4000])
    actor = Thumbnailer(str(tmp_path / "thumbs"), use_device=True)
    batch = actor.new_indexed_thumbnails_batch(
        "lib", [("a" * 16, good[0]), ("b" * 16, str(torn)),
                ("c" * 16, good[1])])
    await actor.wait_batch(batch)
    await actor.shutdown()
    assert (actor.generated, actor.errors) == (2, 1)
    assert actor.store.exists("lib", "a" * 16)
    assert actor.store.exists("lib", "c" * 16)
    assert not actor.store.exists("lib", "b" * 16)


# --- one index pass of the tiny location ------------------------------------


async def _index(data_dir: str, root: str, backend: str) -> dict:
    from spacedrive_tpu import cli
    from spacedrive_tpu.node import Node

    node = Node(data_dir, use_device=backend == "tpu")
    node.config.config.p2p.enabled = False
    await node.start()
    try:
        return await cli.index_location(node, root, "roll", backend)
    finally:
        await node.shutdown()


@pytest.fixture(scope="module", params=["tpu", "cpu"])
def indexed(request, tmp_path_factory, location, kind):
    """Index the location once on each backend, read the library back
    with sqlite3 and the kind's own `compare`."""
    from spacedrive_tpu.parallel import autotune

    root, manifest, photos = location
    data_dir = str(tmp_path_factory.mktemp(f"roll_node_{request.param}"))
    autotune.reset()
    # a fresh registry: `sd_span_seconds` holds 64 series, and a path first
    # seen after those a worker's earlier test files left folds into
    # `__overflow__` (PERF.md §7), which the cases below would read as absent
    telemetry.reset()
    before = harness.flat_counters()
    summary = asyncio.run(_index(data_dir, root, request.param))
    counters = {k: v - before.get(k, 0.0)
                for k, v in harness.flat_counters().items()}
    want = check.reference_cas(root, manifest, {"heic": kind})
    compared = check.Compared()
    db = check.library_db(data_dir)
    try:
        rows = {check._rel(r): r for r in db.execute(
            "SELECT materialized_path, name, extension, cas_id, object_id, "
            "pub_id FROM file_path WHERE is_dir = 0")}
        bad = kind.compare(compared.scoped("heic"), {
            "data_dir": data_dir, "location": root, "entries": photos,
            "rows": rows, "want_cas": want,
            "stored": check._stored_thumbnails(data_dir),
            "config": tiny_config(), "seed": SEED, "db": db})
        kinds = dict(db.execute("SELECT id, kind FROM object").fetchall())
        embedded = {r[0] for r in db.execute(
            "SELECT object_id FROM object_embedding")}
        media = {r[0] for r in db.execute("SELECT object_id FROM media_data")}
        rows = {rel: dict(r) for rel, r in rows.items()}
    finally:
        db.close()
    return {"backend": request.param, "manifest": manifest, "photos": photos,
            "summary": summary, "counters": counters, "rows": rows,
            "want": want, "bad": bad, "compared": compared, "kinds": kinds,
            "embedded": embedded, "media": media,
            "thumbs": {n for n in check._stored_thumbnails(data_dir)
                       if n.endswith(".webp")}}


def test_every_file_has_a_row_a_cas_id_and_an_object(indexed):
    # names in capitals, as a phone writes them, come back as written
    assert set(indexed["rows"]) == {e["rel"] for e in indexed["manifest"]}
    assert all(rel.endswith((".HEIC", ".PNG")) for rel in indexed["rows"])
    assert {rel: r["cas_id"] for rel, r in indexed["rows"].items()} \
        == indexed["want"]
    objects = [r["object_id"] for r in indexed["rows"].values()]
    assert None not in objects and len(set(objects)) == 8


def test_a_heic_is_an_image_with_thumbnail_embedding_and_media_data(indexed):
    from spacedrive_tpu.files.kind import ObjectKind

    assert len(indexed["photos"]) == 6
    for e in indexed["photos"]:
        r = indexed["rows"][e["rel"]]
        assert indexed["kinds"][r["object_id"]] == int(ObjectKind.Image) == 5
        assert r["cas_id"] + ".webp" in indexed["thumbs"]
        assert r["object_id"] in indexed["embedded"]
        assert r["object_id"] in indexed["media"]
    assert len(indexed["thumbs"]) == 8


def test_the_kinds_compare_holds_the_pass(indexed, kind):
    numbers = indexed["compared"].numbers
    assert indexed["bad"] == set() and indexed["compared"].correct
    assert {k for k, (v, _lim) in numbers.items() if v} \
        == {"heic_pixel_gap", "heic_embedding_gap"}
    assert 0 < numbers["heic_pixel_gap"][0] < kind.PIXEL_GAP_LIMIT
    assert 0 < numbers["heic_embedding_gap"][0] < kind.EMBED_GAP_LIMIT
    assert len(numbers) == 8


def test_the_jobs_complete(indexed):
    s = indexed["summary"]
    assert s["jobs"] == {"indexer": "COMPLETED", "file_identifier": "COMPLETED",
                         "media_processor": "COMPLETED"}
    assert s["jobs_failed"] == 0 and s["files"] == 8
    assert s["thumbnail_errors"] == 0 and s["thumbnails"] == 8
    if indexed["backend"] == "tpu":
        assert s["thumbnail_cpu_fallbacks"] == 0
        assert s["cas_backend_fallbacks"] == 0 and s["ladder_level"] == 0


def test_the_new_counters_move(indexed):
    c = indexed["counters"]
    assert c["sd_thumbnail_heif_frames_total{result=ok}"] == 6
    assert not c.get("sd_thumbnail_heif_frames_total{result=error}")
    assert c["sd_thumbnail_heif_bytes_total"] == 6 * 640 * 480 * 3
    assert c["sd_thumbnail_heif_seconds{part=decode}"] > 0
    assert c["sd_thumbnail_heif_seconds{part=plane}"] > 0
    assert c["sd_media_extract_seconds{kind=heif}.count"] == 6
    assert c["sd_media_extract_seconds{kind=heif}.sum"] > 0
    assert c["sd_media_extract_seconds{kind=image}.count"] == 2
    # every plane came from the frame the thumbnailer decoded
    assert c["sd_embed_planes_total{source=shared}"] == 8
    assert not c.get("sd_embed_planes_total{source=own}")
    spans = {k for k, v in c.items() if k.startswith("sd_span_seconds{") and v}
    assert "sd_span_seconds{stage=thumbnail.decode.heif.decode}.count" in spans
    assert "sd_span_seconds{stage=media.extract.heif}.count" in spans
    if indexed["backend"] == "tpu":
        # every HEIC went as three colour planes, none with an alpha
        # plane beside them
        assert c["sd_thumbnail_resize_images_total{alpha=0}"] >= 6
        assert not c.get("sd_thumbnail_resize_images_total{alpha=1}")
    # the readers the benchmark adds print a number from these
    bench = harness.Bench(ROOT)
    ctx = {"counters": c}
    assert bench.reader("heif_frame_bytes_per_image")(ctx) == 640 * 480 * 3
    for name in ("heif_decode_ms_per_image", "heif_plane_ms_per_image",
                 "heif_exif_ms_per_image"):
        assert bench.reader(name)(ctx) > 0
    # and nothing from a program without them
    for name in ("heif_decode_ms_per_image", "heif_plane_ms_per_image",
                 "heif_frame_bytes_per_image", "heif_exif_ms_per_image"):
        assert bench.reader(name)({"counters": {}}) is None


# --- the embedding's reference ----------------------------------------------


def test_the_embedding_is_of_the_displayed_picture(location, kind):
    """The tap's plane is made from the frame libheif hands on, the
    container's turn applied; the reference's from the picture before
    the encoder, turned the same way."""
    from spacedrive_tpu.models import embedder

    path, e = _photo(location, 6)
    frame = images.decode_heif(path)
    plane = embedder.input_plane(embedder.plane_from_frame(frame))
    want = ref.embedding(kind.picture(e), 6)
    got = ref_media.embed_forward(plane[None])[0]
    assert ref_media.embed_gap(got, want) < kind.EMBED_GAP_LIMIT / 3
    unturned = ref.embedding(kind.picture(e), 1)
    assert ref_media.embed_gap(got, unturned) > ref_media.embed_gap(got, want)
