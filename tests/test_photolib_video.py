"""`photolib.video` at a small size (ISSUE 32): a phone's clips added as
a location, 8 seeded clips of 320 x 180 (two of them portrait, 180 wide:
no multiple of 16, as 1080 is) and a still, through the program's own
path (`decode_video_frame` by libav and by cv2, the device resize,
`finish`, `VideoMetadata`, a whole `cli.index_location`) and held
against `benchmark/reference/video.py`, which imports nothing of the
program."""

import asyncio
import io
import json
import os

import numpy as np
import pytest

from benchmark import check, harness
from benchmark.generators import clip_roll, common
from benchmark.reference import video as ref
from spacedrive_tpu import native, telemetry
from spacedrive_tpu.object.media.thumbnail import process

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3000000019  # over 32 signed bits
CLIPS = 8

needs_libav = pytest.mark.skipif(
    not native.video_available(), reason="libav is absent: cv2 decodes")


def tiny_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "photolib_video.json")) as f:
        config = json.load(f)
    config["clips"] = CLIPS
    config["clip"].update(width=320, height=180)
    config["photo"].update(width=640, height=480)
    return config


@pytest.fixture(scope="module")
def kind():
    return harness.Bench(ROOT).kinds(tiny_config())["video"]


@pytest.fixture(scope="module")
def location(tmp_path_factory, kind):
    """The tiny location on disk: (path, manifest, clips)."""
    root = str(tmp_path_factory.mktemp("clips") / "location")
    os.makedirs(root)
    manifest = clip_roll.plan(tiny_config(), SEED)
    common.write_manifest(root, manifest, {"video": kind})
    return root, manifest, common.entries_of(manifest, "video")


def use_decoder(monkeypatch, decoder: str) -> None:
    if decoder == "cv2":
        monkeypatch.setattr(native, "video_available", lambda: False)
    elif not native.video_available():
        pytest.skip("libav is absent: cv2 decodes")


def thumbnail_of(path: str) -> tuple[process.Decoded, np.ndarray]:
    """A clip through the thumbnailer's three stages, the device resize
    among them; → (what `decode` handed on, the stored pixels)."""
    from PIL import Image

    d = process.decode(path, "mp4")
    webp = process.finish(d, process.resize_decoded([d])[0])
    fmt, got = ref.decode_webp(webp)
    assert fmt == "WEBP"
    with Image.open(io.BytesIO(webp)) as im:
        assert im.mode == "RGB"  # footage has no alpha: no band is stored
    return d, got


# --- one clip through the thumbnailer's stages -------------------------------


@pytest.mark.parametrize("decoder", ["native", "cv2"])
def test_thumbnail_is_of_the_marks_shot_at_upstreams_size(
        location, kind, monkeypatch, decoder):
    use_decoder(monkeypatch, decoder)
    root, _manifest, clips = location
    assert {(e["video"]["w"], e["video"]["h"]) for e in clips} \
        == {(320, 180), (180, 320)}
    for e in clips:
        v, path = e["video"], os.path.join(root, e["rel"])
        d, got = thumbnail_of(path)
        # RGB from either decoder, the 180-wide portrait (no multiple
        # of 16: swscale's padded rows at 3 bytes a pixel) included
        assert d.is_video and d.array.shape == (v["h"], v["w"], 3)
        assert d.array.flags.c_contiguous
        tw, th = ref.thumbnail_size(v["w"], v["h"])
        assert d.target == (th, tw) and got.shape == (th, tw, 3)
        assert max(tw, th) == 256
        want = ref.thumbnail_pixels(path, v["frames"])
        assert ref.frame_gap(got, want) < kind.FRAME_GAP_LIMIT, e["rel"]
        assert ref.strips_present(got, want)
        assert not ref.strips_present(want, want)
        # neither frame 0 nor the middle frame would pass for it
        for index in (0, v["frames"] // 2):
            other = ref.thumbnail_pixels(path, v["frames"], index=index)
            assert ref.frame_gap(got, other) > 2 * kind.FRAME_GAP_LIMIT


@needs_libav
def test_the_two_decoders_take_frames_of_one_shot(location, monkeypatch):
    """cv2 hands on the exact frame a tenth in; libav the key frame at or
    before it, which the encoder put at the first cut or a whole number
    of key intervals later: one of the frames the shot rule covers, bit
    for bit as the reference decodes it."""
    root, _manifest, clips = location
    for e in clips[:4]:
        v, path = e["video"], os.path.join(root, e["rel"])
        mark = ref.mark_frame(v["frames"])
        by_native = process.decode_video_frame(path).array
        with monkeypatch.context() as m:
            m.setattr(native, "video_available", lambda: False)
            by_cv2 = process.decode_video_frame(path).array
        assert by_native.shape == by_cv2.shape == (v["h"], v["w"], 3)
        assert np.array_equal(by_cv2, ref.frame_at(path, mark))
        taken = [f for f in range(v["cuts"][0], mark + 1)
                 if np.array_equal(by_native, ref.frame_at(path, f))]
        assert len(taken) == 1, e["rel"]
        assert (taken[0] - v["cuts"][0]) % v["key_interval"] == 0 \
            or taken[0] % v["key_interval"] == 0
        assert mark - taken[0] < v["key_interval"]
        gap = np.abs(by_native.astype(np.int16) - by_cv2).mean()
        assert gap < 3, e["rel"]


def test_a_clips_programs_are_named_by_the_channels_its_decode_hands_on(
        location, kind):
    """`benchmark/kinds/video.py:programs` takes bucket and planes from
    the program's own decode: three since ISSUE 33, so the clips' warm-up
    is one program a pad and no one-plane program beside it.
    (`benchmark/tests/test_video_kind_cpu.py` pins `x4`, PR 32's frame;
    `tests/test_benchmark_suite.py` says why that case is left out.)"""
    from spacedrive_tpu.ops import thumbnail_jax as tj

    root, _manifest, clips = location
    bh, bw = tj.bucket_for(180, 320)
    own = kind.programs(clips, root, 1)
    assert [name for _w, name, _fn in own] == [
        f"video_resize_{bh}x{bw}x3_pad{pad}" for pad in (1, 2, 4, 8)]
    own[0][2]()  # runs one to its end


@needs_libav
def test_a_row_that_ends_inside_a_block_is_decoded_in_bounds(location, kind):
    """swscale writes whole 16-pixel blocks: the frontend gives it padded
    rows (before PR 32 a 180- or 1080-wide frame corrupted the heap and
    the process died a few clips on). A child process decodes the
    portrait clip twelve times and has to live."""
    root, _manifest, clips = location
    portrait = next(e for e in clips if e["video"]["w"] % 16)
    assert kind.decoder_is_sound(os.path.join(root, portrait["rel"]), ROOT)


@pytest.mark.parametrize("decoder", ["native", "cv2"])
def test_video_metadata_is_the_manifests(location, monkeypatch, decoder):
    from spacedrive_tpu.object.media.media_data import VideoMetadata

    if decoder == "cv2":
        monkeypatch.setattr(native, "video_meta", lambda path: None)
    elif not native.video_available():
        pytest.skip("libav is absent: cv2 probes")
    root, _manifest, clips = location
    for e in clips:
        want = ref.facts(e["video"])
        meta = VideoMetadata.from_path(os.path.join(root, e["rel"]))
        assert meta.resolution == (want["width"], want["height"])
        assert meta.fps == pytest.approx(want["fps"])
        assert meta.frame_count == want["frames"]
        assert meta.duration_seconds == pytest.approx(
            want["duration_s"], abs=1 / want["fps"])
        assert meta.codec in ("mpeg4", "FMP4", "mp4v")


# --- one index pass of the tiny location ------------------------------------


async def _index(data_dir: str, root: str) -> dict:
    from spacedrive_tpu import cli
    from spacedrive_tpu.node import Node

    node = Node(data_dir, use_device=True)
    node.config.config.p2p.enabled = False
    await node.start()
    try:
        return await cli.index_location(node, root, "clips", "tpu")
    finally:
        await node.shutdown()


@pytest.fixture(scope="module")
def indexed(tmp_path_factory, location, kind):
    """Index the location once, read the library back with sqlite3 and
    the kind's own `compare`."""
    from spacedrive_tpu.parallel import autotune

    root, manifest, clips = location
    data_dir = str(tmp_path_factory.mktemp("clips_node"))
    autotune.reset()
    # a fresh registry: `sd_span_seconds` holds 64 series, and a path first
    # seen after those a worker's earlier test files left folds into
    # `__overflow__` (PERF.md §7), which the cases below would read as absent
    telemetry.reset()
    before = harness.flat_counters()
    summary = asyncio.run(_index(data_dir, root))
    counters = {k: v - before.get(k, 0.0)
                for k, v in harness.flat_counters().items()}
    want = check.reference_cas(root, manifest, {"video": kind})
    compared = check.Compared()
    db = check.library_db(data_dir)
    try:
        rows = {check._rel(r): r for r in db.execute(
            "SELECT materialized_path, name, extension, cas_id, object_id, "
            "pub_id FROM file_path WHERE is_dir = 0")}
        bad = kind.compare(compared.scoped("video"), {
            "data_dir": data_dir, "location": root, "entries": clips,
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
    return {"manifest": manifest, "clips": clips, "summary": summary,
            "counters": counters, "rows": rows, "want": want, "bad": bad,
            "compared": compared, "kinds": kinds, "embedded": embedded,
            "media": media,
            "thumbs": {n for n in check._stored_thumbnails(data_dir)
                       if n.endswith(".webp")}}


def test_every_file_has_a_row_a_cas_id_and_an_object(indexed):
    assert set(indexed["rows"]) == {e["rel"] for e in indexed["manifest"]}
    assert {rel: r["cas_id"] for rel, r in indexed["rows"].items()} \
        == indexed["want"]
    objects = [r["object_id"] for r in indexed["rows"].values()]
    assert None not in objects and len(set(objects)) == CLIPS + 1


def test_a_clip_is_a_video_object_with_a_thumbnail_and_its_facts(indexed):
    from spacedrive_tpu.files.kind import ObjectKind

    for e in indexed["clips"]:
        r = indexed["rows"][e["rel"]]
        assert indexed["kinds"][r["object_id"]] == int(ObjectKind.Video) == 7
        assert r["cas_id"] + ".webp" in indexed["thumbs"]
        assert r["object_id"] in indexed["media"]


def test_no_clip_is_embedded_and_the_still_is(indexed):
    clips = {e["rel"] for e in indexed["clips"]}
    for rel, r in indexed["rows"].items():
        assert (r["object_id"] in indexed["embedded"]) == (rel not in clips)
    assert len(indexed["thumbs"]) == CLIPS + 1


def test_the_kinds_compare_holds_the_pass(indexed, kind):
    numbers = indexed["compared"].numbers
    assert indexed["bad"] == set() and indexed["compared"].correct
    assert {k for k, (v, _lim) in numbers.items() if v} == {"video_frame_gap"}
    assert 0 < numbers["video_frame_gap"][0] < kind.FRAME_GAP_LIMIT
    assert len(numbers) == 8


def test_the_jobs_complete_on_the_device_path(indexed):
    s = indexed["summary"]
    assert s["jobs"] == {"indexer": "COMPLETED", "file_identifier": "COMPLETED",
                         "media_processor": "COMPLETED"}
    assert s["jobs_failed"] == 0 and s["files"] == CLIPS + 1
    assert s["thumbnail_errors"] == 0 and s["thumbnail_cpu_fallbacks"] == 0
    assert s["cas_backend_fallbacks"] == 0 and s["ladder_level"] == 0
    assert s["thumbnails"] == CLIPS + 1


def test_the_new_counters_move(indexed):
    c = indexed["counters"]
    decoder = "native" if native.video_available() else "cv2"
    assert c[f"sd_thumbnail_video_frames_total{{decoder={decoder},"
             "result=ok}"] == CLIPS
    assert c["sd_thumbnail_video_bytes_total"] == CLIPS * 320 * 180 * 3
    # every clip went as three planes in the colour call, none beside it
    assert c["sd_thumbnail_resize_images_total{alpha=0}"] == CLIPS + 1
    assert not c.get("sd_thumbnail_resize_images_total{alpha=1}")
    for part in ("frame", "orient", "overlay"):
        assert c[f"sd_thumbnail_video_seconds{{part={part}}}"] > 0
    assert c["sd_media_extract_seconds{kind=video}.count"] == CLIPS
    assert c["sd_media_extract_seconds{kind=image}.count"] == 1
    assert c["sd_media_extract_seconds{kind=video}.sum"] > 0
    spans = {k for k, v in c.items() if k.startswith("sd_span_seconds{") and v}
    assert "sd_span_seconds{stage=thumbnail.decode.video.frame}.count" in spans
    assert "sd_span_seconds{stage=media.extract.video}.count" in spans
    # the readers the benchmark adds print a number from these
    bench = harness.Bench(ROOT)
    ctx = {"counters": c}
    assert bench.reader("thumb_rgb_share")(ctx) == 100.0
    assert bench.reader("video_native_share")(ctx) == \
        (100.0 if decoder == "native" else 0.0)
    assert bench.reader("video_frame_bytes_per_clip")(ctx) == 320 * 180 * 3
    for name in ("video_frame_ms_per_clip", "video_overlay_ms_per_clip",
                 "video_probe_ms_per_clip"):
        assert bench.reader(name)(ctx) > 0
    # and nothing from a program without them
    for name in ("video_native_share", "video_frame_bytes_per_clip",
                 "video_frame_ms_per_clip", "video_overlay_ms_per_clip",
                 "video_probe_ms_per_clip"):
        assert bench.reader(name)({"counters": {}}) is None


def test_an_undecodable_clip_is_counted_and_costs_no_other(tmp_path):
    from spacedrive_tpu.telemetry import metrics as tm

    decoder = "native" if native.video_available() else "cv2"
    before = tm.THUMB_VIDEO_FRAMES.value(decoder=decoder, result="error")
    bad = tmp_path / "torn.mp4"
    bad.write_bytes(b"\x00\x00\x00\x18ftypisom" + b"\x00" * 64)
    with pytest.raises(process.ThumbError):
        process.decode_video_frame(str(bad))
    assert tm.THUMB_VIDEO_FRAMES.value(decoder=decoder, result="error") \
        == before + 1
