"""The device path for stills over 4096 on a side and over 4:1 (ISSUE 38),
at sizes a test run can hold: frames that are long and narrow, so that
they take the canvases above the square rungs and cost little. A frame
reaches `resize_batch` whole and comes back as a plain float64 triangle
filter over every pixel gives it; a band of one-pixel line pairs comes
out mid-grey, and the same band thinned by a stride does not; targets
between 4:1 and 16:1 ride the second output canvas, either way up; a
chunk over the byte bound goes as several calls and gives the same bytes;
the host's chunk is cut where its decoded frames would pass the bound;
whatever the host still does to a frame is counted."""

import io

import numpy as np
import pytest
from PIL import Image

from spacedrive_tpu import telemetry
from spacedrive_tpu.object.media.thumbnail import Thumbnailer, process
from spacedrive_tpu.ops import thumbnail_jax as tj
from spacedrive_tpu.telemetry.events import RESILIENCE_EVENTS

RNG = np.random.default_rng(38)
#: mean |difference| of 255 a device thumbnail may stand off the float64
#: filter: float32 weights and sums and one rounding to uint8 read under
#: 0.3; a frame thinned by two reads 2 or more on these pictures
FILTER_GAP = 0.5


def _triangle(n_in: int, n_out: int) -> np.ndarray:
    """[n_out, n_in] rows of a triangle filter whose support is the scale
    factor, normalised over the input samples that exist."""
    scale = n_in / n_out
    centres = (np.arange(n_out) + 0.5) * scale - 0.5
    x = (np.arange(n_in)[None, :] - centres[:, None]) / max(scale, 1.0)
    w = np.clip(1.0 - np.abs(x), 0.0, None)
    return w / w.sum(axis=1, keepdims=True)


def downscale(rgb: np.ndarray, th: int, tw: int) -> np.ndarray:
    """The plain reference: float64, every pixel, rounded once."""
    x = np.einsum("oh,hwc->owc", _triangle(rgb.shape[0], th),
                  rgb.astype(np.float64))
    x = np.einsum("pw,owc->opc", _triangle(rgb.shape[1], tw), x)
    return np.clip(np.rint(x), 0, 255).astype(np.uint8)


def _field(h: int, w: int) -> np.ndarray:
    """A smooth seeded colour field with fine noise on it."""
    coarse = RNG.integers(0, 256, (max(2, h // 40), max(2, w // 40), 3),
                          dtype=np.uint8)
    img = np.asarray(Image.fromarray(coarse).resize((w, h), Image.BICUBIC),
                     np.int16)
    img = img + RNG.integers(-20, 21, (h, w, 3), dtype=np.int16)
    return np.clip(img, 0, 255).astype(np.uint8)


def _target(img: np.ndarray) -> tuple[int, int]:
    tw, th = tj.scale_dimensions(img.shape[1], img.shape[0])
    return th, tw


def _gap(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.abs(a.astype(np.int16) - b.astype(np.int16)).mean())


def _counter(name: str, **labels) -> float:
    return telemetry.counter_value(name, **labels)


# --- the rungs above 4096 ----------------------------------------------------


@pytest.mark.parametrize("h,w,bucket", [
    (4284, 5712, (4608, 6144)),    # the 24 MP default, 86 % of its canvas
    (5712, 4284, (4608, 6144)),    # turned: transposes in
    (6048, 8064, (6144, 8192)),    # 48 MP
    (3628, 16382, (4096, 16384)),  # a panorama
    (6336, 9504, (9216, 12288)),   # a 61 MP camera's 3:2 frame
    (16384, 16384, (16384, 16384)),
    (3024, 4032, (4096, 4096)),    # the rungs up to 4096 are as they were
    (2532, 1170, (2048, 4096)),
    (1080, 1920, (2048, 2048)),
    (16385, 100, None),
])
def test_a_frame_takes_the_smallest_canvas_that_holds_it(h, w, bucket):
    assert tj.bucket_for(h, w) == bucket
    if bucket is not None:
        assert max(h, w) <= bucket[1] and min(h, w) <= bucket[0]
        assert process.MAX_DIM == tj.MAX_SIDE == 16384


@pytest.mark.parametrize("h,w", [(4200, 280), (280, 4200), (530, 8200)],
                         ids=["over_4096_portrait", "over_4096_landscape",
                              "over_8192_landscape"])
def test_a_long_side_reaches_the_filter_whole(h, w):
    """No stride stands before the resize: the decode hands on the PNG's
    every pixel, the device's thumbnail is the float64 filter's."""
    src = _field(h, w)
    buf = io.BytesIO()
    Image.fromarray(src).save(buf, "PNG", compress_level=1)
    path_bytes = buf.getvalue()
    whole = _counter("sd_thumbnail_frames_total", path="whole")
    with Image.open(io.BytesIO(path_bytes)) as img:
        arr = process.shrink_to_max_dim(np.asarray(img.convert("RGB")))
    assert arr.shape == src.shape and np.array_equal(arr, src)
    assert _counter("sd_thumbnail_frames_total", path="whole") == whole + 1
    d = process.Decoded(array=arr, target=_target(arr))
    assert not process.needs_cpu_fallback(d)
    assert tj.bucket_for(h, w)[1] > 4096
    out = process.resize_decoded([d])[0]
    assert out.shape == (*d.target, 3)
    assert _gap(out, downscale(src, *d.target)) < FILTER_GAP


def test_beyond_the_widest_canvas_a_frame_is_thinned_and_counted():
    thinned = _counter("sd_thumbnail_frames_total", path="thinned")
    arr = np.zeros((8, 2 * process.MAX_DIM + 2, 3), np.uint8)
    out = process.shrink_to_max_dim(arr)
    assert out.shape == (3, (2 * process.MAX_DIM + 2 + 2) // 3, 3)
    assert max(out.shape[:2]) <= process.MAX_DIM
    assert _counter("sd_thumbnail_frames_total", path="thinned") \
        == thinned + 1


# --- the band of line pairs ---------------------------------------------------


def _banded(h: int, w: int) -> tuple[np.ndarray, tuple[slice, slice]]:
    """A mid-grey picture with a band of one-pixel line pairs across its
    middle: alternate dark and light columns in the band's left half,
    rows in its right half. → (picture, the band)."""
    img = np.full((h, w, 3), 128, np.uint8)
    rows, cols = slice(h // 4, 3 * h // 4), slice(w // 8, 7 * w // 8)
    mid = w // 2
    img[rows, cols.start:mid][:, 0::2] = 32
    img[rows, cols.start:mid][:, 1::2] = 224
    img[rows, mid:cols.stop][0::2] = 32
    img[rows, mid:cols.stop][1::2] = 224
    return img, (rows, cols)


def test_line_pairs_come_out_mid_grey_and_a_stride_does_not():
    src, (rows, cols) = _banded(320, 4800)
    th, tw = _target(src)
    band = (slice(rows.start * th // 320 + 1, rows.stop * th // 320 - 1),
            slice(cols.start * tw // 4800 + 1, cols.stop * tw // 4800 - 1))
    assert band[0].stop - band[0].start >= 5

    def off_grey(frame: np.ndarray) -> float:
        out = tj.resize_batch([frame], [(th, tw)])[0]
        return float(np.abs(out[band].astype(np.int16) - 128).mean())

    # the whole filter averages every pair, (32 + 224) / 2, to the ripple
    # a triangle 4.8 pixels wide leaves of a 2-pixel period (2.9 here)
    assert off_grey(src) < 8.0
    # every second row and column keeps the dark lines alone (or the
    # light): what `arr[::step, ::step]` did to a frame over 4096
    thinned = np.ascontiguousarray(src[::2, ::2])
    assert off_grey(thinned) > 60.0


# --- the second output canvas -------------------------------------------------


@pytest.mark.parametrize("h,w", [(400, 1800), (1800, 400), (150, 1800),
                                 (1800, 150), (128, 2048)],
                         ids=["4.5_landscape", "4.5_portrait", "12_landscape",
                              "12_portrait", "16_landscape"])
def test_between_four_and_sixteen_to_one_resizes_on_the_device(h, w):
    src = _field(h, w)
    d = process.Decoded(array=src, target=_target(src))
    th, tw = d.target
    assert max(th, tw) > tj.OUT_CANVAS  # the first canvas does not hold it
    assert tj.out_canvas_for(th, tw) == tj.OUT_CANVAS_WIDE_HW
    assert not process.needs_cpu_fallback(d)
    calls = _counter("sd_thumbnail_device_calls_total",
                     bucket="{}x{}".format(*tj.bucket_for(h, w)),
                     out="{}x{}".format(*tj.OUT_CANVAS_WIDE_HW))
    host = _counter("sd_thumbnail_host_resize_total", reason="aspect")
    out = process.resize_decoded([d])[0]
    assert out.shape == (th, tw, 3)  # the exact `scale_dimensions` size
    assert _gap(out, downscale(src, th, tw)) < FILTER_GAP
    assert _counter("sd_thumbnail_device_calls_total",
                    bucket="{}x{}".format(*tj.bucket_for(h, w)),
                    out="{}x{}".format(*tj.OUT_CANVAS_WIDE_HW)) == calls + 1
    assert _counter("sd_thumbnail_host_resize_total", reason="aspect") == host


def test_the_two_output_canvases_share_a_bucket_in_two_calls():
    photo, pano = _field(1000, 1900), _field(300, 2000)
    targets = [_target(photo), _target(pano)]
    assert tj.bucket_for(1000, 1900) == tj.bucket_for(300, 2000)
    assert tj.out_canvas_for(*targets[0]) != tj.out_canvas_for(*targets[1])
    outs = tj.resize_batch([photo, pano], targets)
    for src, out, t in zip((photo, pano), outs, targets):
        assert out.shape == (*t, 3)
        assert _gap(out, downscale(src, *t)) < FILTER_GAP


# --- the byte bound -----------------------------------------------------------


def test_the_calls_formed_before_the_bound_are_under_it():
    assert tj.CALL_CANVAS_BYTES == 32 * 4096 * 4096 * 3
    assert tj.call_rows(4096, 4096, 3) == 32    # photolib.heic's call
    assert tj.call_rows(2048, 2048, 3) == 128   # photolib.video's warm-up
    assert tj.call_rows(4608, 6144, 3) == 16
    assert tj.call_rows(6144, 8192, 3) == 8
    assert tj.call_rows(16384, 16384, 3) == 2
    assert tj.call_rows(16384, 16384, 4) == 1   # always one


def test_a_chunk_over_the_bound_splits_and_gives_the_same_bytes(monkeypatch):
    images = [_field(300, 500) for _ in range(5)] + [_field(500, 300)]
    rgba = np.concatenate(
        [images[0], RNG.integers(0, 256, (300, 500, 1), dtype=np.uint8)], -1)
    images.append(rgba)
    targets = [_target(img) for img in images]
    calls = lambda: _counter("sd_thumbnail_device_calls_total",  # noqa: E731
                             bucket="512x512", out="512x1024")
    before = calls()
    one = tj.resize_batch(images, targets)
    assert calls() == before + 2  # the colour planes, the one alpha plane
    # two canvases of (512, 512) × 3 a call: 7 images go as 2 + 2 + 2 + 1
    monkeypatch.setattr(tj, "CALL_CANVAS_BYTES", 2 * 512 * 512 * 3)
    assert tj.call_rows(512, 512, 3) == 2
    before = calls()
    split = tj.resize_batch(images, targets)
    assert calls() == before + 4 + 1
    for a, b in zip(one, split):
        assert a.shape == b.shape and np.array_equal(a, b)


def test_a_hosts_chunk_is_cut_where_its_frames_pass_the_bound(
        tmp_path, monkeypatch):
    """`chunk_len` reads the headers of stills that decode at full size:
    PNGs here, 300 x 200 x 3 = 180,000 bytes a frame (one with alpha
    240,000); a JPEG decodes in draft mode and costs no look."""
    entries = []
    for i in range(6):
        path = str(tmp_path / f"{i}.png")
        Image.fromarray(_field(200, 300)).save(path)
        entries.append((f"cas{i}", path, "png"))
    jpg = str(tmp_path / "photo.jpg")
    Image.fromarray(_field(200, 300)).save(jpg)
    assert process.frame_bytes(entries[0][1], "png") == 180_000
    assert process.frame_bytes(jpg, "jpg") == 0
    assert process.frame_bytes(str(tmp_path / "gone.png"), "png") == 0
    rgba = str(tmp_path / "a.png")
    Image.fromarray(np.dstack([_field(200, 300),
                               np.full((200, 300), 7, np.uint8)])).save(rgba)
    assert process.frame_bytes(rgba, "png") == 240_000
    assert process.CHUNK_FRAME_BYTES == tj.CALL_CANVAS_BYTES
    assert process.chunk_len(entries) == 6
    monkeypatch.setattr(process, "CHUNK_FRAME_BYTES", 400_000)
    assert process.chunk_len(entries) == 2
    assert process.chunk_len([("j", jpg, "jpg")] * 9 + entries) == 11
    monkeypatch.setattr(process, "CHUNK_FRAME_BYTES", 1)
    assert process.chunk_len(entries) == 1  # always one


@pytest.mark.asyncio
async def test_a_batch_over_the_bound_goes_in_chunks_and_all_is_stored(
        tmp_path, monkeypatch):
    monkeypatch.setattr(process, "CHUNK_FRAME_BYTES", 400_000)
    entries = []
    for i in range(5):
        path = str(tmp_path / f"{i}.png")
        Image.fromarray(_field(200, 300)).save(path)
        entries.append((f"c0ffee00000000{i:02d}", path, "png"))
    th = Thumbnailer(tmp_path / "data")
    fills = lambda: telemetry.REGISTRY.snapshot()[  # noqa: E731
        "sd_thumbnail_batch_fill_ratio"]["series"][0]["count"]
    before = fills()
    try:
        assert th.new_indexed_thumbnails_batch("lib", entries) > 0
        await th.wait_library_batch("lib")
        assert th.generated == 5 and th.errors == 0
        assert fills() == before + 3  # chunks of 2, 2 and 1
        for cas, _path, _ext in entries:
            with Image.open(th.store.path_for("lib", cas)) as im:
                assert im.format == "WEBP" and im.size == (300, 200)
    finally:
        await th.shutdown()


# --- what the host still does is counted --------------------------------------


@pytest.mark.asyncio
async def test_a_still_resized_on_the_host_is_counted_and_reported(tmp_path):
    """Beyond 16:1 the old path stays: PIL on a host thread. A node that
    uses the device says so, once a still: the counter by its reason and
    a `thumbnail_cpu_fallback` event, which `cli.device_report` counts
    and the benchmark's `device_fallbacks` holds to 0."""
    path = str(tmp_path / "ribbon.png")
    Image.fromarray(_field(100, 4000)).save(path)
    host = _counter("sd_thumbnail_host_resize_total", reason="aspect")
    events = sum(e["type"] == "thumbnail_cpu_fallback"
                 for e in RESILIENCE_EVENTS.snapshot())
    th = Thumbnailer(tmp_path / "data")
    try:
        cas = "c0ffee0000000038"
        assert th.new_indexed_thumbnails_batch("lib", [(cas, path, "png")]) > 0
        await th.wait_library_batch("lib")
        assert th.generated == 1 and th.errors == 0
        with Image.open(th.store.path_for("lib", cas)) as im:
            assert im.size == tj.scale_dimensions(4000, 100)
    finally:
        await th.shutdown()
    assert _counter("sd_thumbnail_host_resize_total", reason="aspect") \
        == host + 1
    mine = [e for e in RESILIENCE_EVENTS.snapshot()
            if e["type"] == "thumbnail_cpu_fallback"]
    assert len(mine) == events + 1 and mine[-1]["fields"]["reason"] == "aspect"
