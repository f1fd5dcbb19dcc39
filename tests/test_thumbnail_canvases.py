"""The canvases of the thumbnailer's device stage (ISSUE 28): three
colour planes where the image has no alpha, an alpha plane beside them
where it has, one landscape 512 × 1024 output canvas, a kept staging
buffer (since ISSUE 39 one arena for every bucket call); what `pack`
writes into it (ISSUE 33): a frame once, the margin the filter reads, a
portrait as it stands where its canvas takes it; and the contract
`benchmark/warm.py` holds the stage to."""

import contextlib
import io
import sys
import threading

import numpy as np
import pytest
from PIL import Image

from spacedrive_tpu.object.media.thumbnail import Thumbnailer, process
from spacedrive_tpu.ops import thumbnail_jax as tj
from spacedrive_tpu.telemetry import metrics as tm

RNG = np.random.default_rng(28)


def _photo(h: int, w: int, channels: int = 4) -> np.ndarray:
    """A gradient that says which way is up (red grows to the right,
    green downwards) under some noise; alpha is a gradient of its own."""
    y, x = np.mgrid[0:h, 0:w]
    img = np.stack([x * 255 // w, y * 255 // h, (x + y) % 256,
                    255 - (x * 255 // w)], -1).astype(np.int16)
    img[..., :3] += RNG.integers(-12, 13, (h, w, 3), dtype=np.int16)
    return np.clip(img, 0, 255).astype(np.uint8)[..., :channels]


def _targets(images):
    out = []
    for img in images:
        tw, th = tj.scale_dimensions(img.shape[1], img.shape[0])
        out.append((th, tw))
    return out


# (a) the colour planes do not depend on whether an alpha plane rides along


@pytest.mark.parametrize("shapes", [
    [(600, 900)],               # a square bucket
    [(400, 900)],               # a landscape half bucket
    [(900, 600)],               # a portrait, transposed into a square bucket
    [(200, 150), (700, 1000)],  # two buckets in one call
], ids=["square", "half", "portrait_in_square", "two_buckets"])
def test_rgb_in_gives_the_colour_planes_rgba_in_gives(shapes):
    rgba = [_photo(h, w) for h, w in shapes]
    rgb = [np.ascontiguousarray(img[..., :3]) for img in rgba]
    targets = _targets(rgba)
    out4 = tj.resize_batch(rgba, targets)
    out3 = tj.resize_batch(rgb, targets)
    for a, b, t in zip(out4, out3, targets):
        assert a.shape == (*t, 4) and b.shape == (*t, 3)
        # equal expected; XLA may tile a 3-wide minor dimension otherwise
        assert np.abs(a[..., :3].astype(int) - b.astype(int)).max() <= 1


# (b) every aspect the device path takes fits the one output canvas


@pytest.mark.parametrize("h,w", [
    (512, 2048), (600, 1800), (720, 1280), (800, 1200), (1000, 1000),
    (1200, 800), (1280, 720), (1800, 600), (2048, 512),
], ids=lambda v: str(v))
def test_aspects_up_to_four_fit_the_canvas_the_right_way_up(h, w):
    img = _photo(h, w, 3)
    (th, tw), = _targets([img])
    d = process.Decoded(array=img, target=(th, tw))
    assert not process.needs_cpu_fallback(d)
    oh, ow = tj.OUT_CANVAS_HW
    assert min(th, tw) <= oh and max(th, tw) <= ow
    out = process.resize_decoded([d])[0]
    assert out.shape == (th, tw, 3)
    # red still grows to the right and green downwards
    assert out[:, -8:, 0].mean() > out[:, :8, 0].mean() + 100
    assert out[-8:, :, 1].mean() > out[:8, :, 1].mean() + 100
    ref = np.asarray(Image.fromarray(img).resize((tw, th), Image.BILINEAR))
    assert np.abs(out.astype(int) - ref.astype(int)).mean() < 1.5


@pytest.mark.parametrize("h,w", [(250, 4100), (4100, 250)])
def test_beyond_sixteen_to_one_still_resizes_on_the_host(h, w):
    """The limit stood at 4:1 until ISSUE 38 (tests/test_photolib_hires.py
    holds what lies between); beyond 16:1 the host's path stays, counted."""
    img = _photo(h, w, 3)
    d = process.Decoded(array=img, target=_targets([img])[0])
    assert process.needs_cpu_fallback(d)
    assert process.host_resize_reason(d) == "aspect"
    with pytest.raises(ValueError, match="exceeds the output canvas"):
        process.resize_decoded([d])
    counted = tm.THUMB_HOST_RESIZE.value(reason="aspect")
    with Image.open(io.BytesIO(process.resize_cpu(d, "aspect"))) as im:
        assert im.size == (d.target[1], d.target[0])
    assert tm.THUMB_HOST_RESIZE.value(reason="aspect") == counted + 1


# (c) what is stored: transparency is kept, and only where there is some


@pytest.mark.parametrize("ext,mode", [("png", "RGBA"), ("jpg", "RGB")])
@pytest.mark.asyncio
async def test_stored_webp_keeps_alpha_where_the_file_has_it(
        tmp_path, ext, mode):
    src = _photo(600, 900)
    path = str(tmp_path / f"src.{ext}")
    if mode == "RGBA":
        Image.fromarray(src).save(path)
    else:
        Image.fromarray(src[..., :3]).save(path, quality=92)
    th = Thumbnailer(tmp_path / "data")
    try:
        cas = "c0ffee0000000028"
        assert th.new_indexed_thumbnails_batch("lib", [(cas, path, ext)]) > 0
        await th.wait_library_batch("lib")
        assert th.generated == 1 and th.errors == 0
        with Image.open(th.store.path_for("lib", cas)) as im:
            assert im.format == "WEBP" and im.mode == mode
            got = np.asarray(im)
    finally:
        await th.shutdown()
    tw, t_h = tj.scale_dimensions(900, 600)
    assert got.shape == (t_h, tw, len(mode))
    want = np.asarray(Image.fromarray(src[..., :len(mode)]).resize(
        (tw, t_h), Image.BILINEAR))
    if mode == "RGBA":
        gap = np.abs(got[..., 3].astype(int) - want[..., 3].astype(int))
        assert gap.mean() <= 3
        assert got[..., 3].min() < 16 and got[..., 3].max() > 240


def test_decode_hands_over_alpha_only_where_the_file_has_it(tmp_path):
    src = _photo(64, 96)
    cases = {"rgba.png": (src, 4), "rgb.png": (src[..., :3], 3),
             "rgb.jpg": (src[..., :3], 3)}
    for name, (arr, channels) in cases.items():
        path = str(tmp_path / name)
        Image.fromarray(arr).save(path)
        assert process.decode_image(path).array.shape == (64, 96, channels)
    # a palette image with a transparent index has alpha too
    pal = Image.fromarray(src[..., :3]).convert("P")
    pal.save(str(tmp_path / "pal.png"), transparency=0)
    assert process.decode_image(
        str(tmp_path / "pal.png")).array.shape == (64, 96, 4)


# (d) the kept staging arena: one flat buffer for every bucket call


@pytest.fixture
def arena(monkeypatch):
    """No arena yet: what other tests of this process left there does
    not decide what is kept here (monkeypatch puts theirs back)."""
    monkeypatch.setattr(tj, "_arena", None)


def _staging_counts() -> dict:
    return {r: tm.THUMB_STAGING.value(result=r) for r in ("kept", "mapped")}


def _arena_canvas(bh: int, bw: int, planes: int, j: int = 0) -> np.ndarray:
    """Canvas j of a (bh, bw, planes) call as the arena holds it."""
    n = bh * bw * planes
    return tj._arena[j * n:(j + 1) * n].reshape(bh, bw, planes)


def test_a_call_of_another_bucket_and_planes_is_lent_the_same_bytes(arena):
    bright = [np.full((700, 1000, 3), 255, np.uint8) for _ in range(4)]
    start = _staging_counts()
    tj.resize_batch(bright, _targets(bright))
    assert _staging_counts() == {"kept": start["kept"],
                                 "mapped": start["mapped"] + 1}
    kept = tj._arena
    assert kept.size == 4 * 1024 * 1024 * 3
    # another bucket, a colour and an alpha call: both lent the arena
    small = [_photo(200, 300)]
    tj.resize_batch(small, _targets(small))
    assert _staging_counts() == {"kept": start["kept"] + 2,
                                 "mapped": start["mapped"] + 1}
    assert tj._arena is kept
    with tj._staging_canvas(3, 256, 512, 1) as canvas:
        assert canvas.shape == (3, 256, 512, 1)
        assert np.shares_memory(canvas, kept)
        assert tj._arena is None  # lent
    assert tj._arena is kept
    assert _staging_counts()["kept"] == start["kept"] + 3


def test_second_call_through_the_arena_returns_nothing_of_the_first(arena):
    bright = [np.full((700, 1000, 3), 255, np.uint8) for _ in range(4)]
    tj.resize_batch(bright, _targets(bright))
    kept = tj._arena
    assert (_arena_canvas(1024, 1024, 3, 3)[:700, :1000] == 255).all()
    kept[...] = 255  # the most a call could have left there
    dark = [np.zeros((300, 400, 3), np.uint8)]  # (512, 512): a smaller bucket
    out = tj.resize_batch(dark, _targets(dark))[0]
    assert out.shape == (*_targets(dark)[0], 3)
    assert (out == 0).all()
    # the same arena, lent again, and never the result
    assert tj._arena is kept
    assert not np.shares_memory(out, kept)


def test_the_arena_grows_to_the_largest_call_and_no_further(
        arena, monkeypatch):
    start = _staging_counts()
    with tj._staging_canvas(2, 256, 256, 3):
        pass
    first = tj._arena
    assert first.size == 2 * 256 * 256 * 3
    with tj._staging_canvas(4, 512, 512, 1) as canvas:  # larger: mapped
        assert not np.shares_memory(canvas, first)
    grown = tj._arena
    assert grown.size == 4 * 512 * 512
    with tj._staging_canvas(1, 256, 1024, 3) as canvas:  # fits: kept
        assert np.shares_memory(canvas, grown)
    assert tj._arena is grown
    assert _staging_counts() == {"kept": start["kept"] + 1,
                                 "mapped": start["mapped"] + 2}
    # a call over the bound maps its own canvas and leaves the arena
    monkeypatch.setattr(tj, "CALL_CANVAS_BYTES", 6 * 512 * 512)
    with tj._staging_canvas(8, 512, 512, 1) as canvas:
        assert canvas.shape == (8, 512, 512, 1)
        assert not np.shares_memory(canvas, grown)
        assert tj._arena is grown
    assert tj._arena is grown
    assert _staging_counts() == {"kept": start["kept"] + 1,
                                 "mapped": start["mapped"] + 3}


def test_a_failed_call_does_not_put_its_buffer_back(arena):
    with pytest.raises(RuntimeError):
        with tj._staging_canvas(2, 256, 256, 3):
            raise RuntimeError("the call failed: a transfer may still read")
    assert tj._arena is None
    with tj._staging_canvas(2, 256, 256, 3):
        pass
    kept = tj._arena
    with pytest.raises(RuntimeError):
        with tj._staging_canvas(1, 256, 256, 3) as canvas:
            assert np.shares_memory(canvas, kept)
            raise RuntimeError("the call failed with the arena lent")
    assert tj._arena is None


def test_two_calls_at_once_one_is_lent_the_arena_one_maps(arena):
    with tj._staging_canvas(2, 256, 256, 3):
        pass
    kept = tj._arena
    start = _staging_counts()
    both = threading.Barrier(2, timeout=30)
    canvases: list[np.ndarray] = []

    def call(planes: int) -> None:
        with tj._staging_canvas(2, 256, 256, planes) as canvas:
            canvases.append(canvas)
            both.wait()  # the two canvases are lent at the same time

    threads = [threading.Thread(target=call, args=(p,)) for p in (3, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert _staging_counts() == {"kept": start["kept"] + 1,
                                 "mapped": start["mapped"] + 1}
    a, b = canvases
    assert not np.shares_memory(a, b)
    assert sum(np.shares_memory(c, kept) for c in canvases) == 1
    # of the two taken back the larger is kept
    assert tj._arena.size == kept.size


def test_two_threads_resizing_at_once_get_a_canvas_each(arena):
    images = {0: [np.full((300, 400, 3), 40, np.uint8)] * 3,
              1: [np.full((280, 500, 3), 200, np.uint8)] * 2}
    failures: list[str] = []

    def work(k: int) -> None:
        value = int(images[k][0][0, 0, 0])
        for _ in range(6):
            for out in tj.resize_batch(images[k], _targets(images[k])):
                if not (out == value).all():
                    failures.append(f"thread {k}: pixels of the other")

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k % 2,))
                   for k in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not failures
    # the largest call's canvases, (512, 512) × 3 at pad 4
    assert tj._arena.size == 4 * 512 * 512 * 3


@pytest.mark.parametrize("seed", [39, 3900000011, 2147484731])
def test_results_do_not_depend_on_what_the_arena_held(arena, seed):
    """A mixed batch (RGB and RGBA, portrait and landscape, three
    buckets) gives the same bytes from an empty arena, one poisoned
    with 255 and one a larger call of another shape left."""
    rng = np.random.default_rng(seed)
    images = []
    # (256, 256), (512, 512), (512, 1024): a landscape and a portrait
    # each, the last transposed in; the channels fixed, so the programs
    for side, lo, channels in ((256, 100, (3, 4)), (512, 260, (4, 3)),
                               (1024, 520, (3, 4))):
        for k in range(2):
            a = int(rng.integers(lo, side + 1))
            b = int(rng.integers(lo // 2 if side == 1024 else lo,
                                 min(side, 512) + 1))
            h, w = (b, a) if k == 0 else (a, b)
            images.append(_photo(h, w, channels[k]))
    images.append(_photo(330, 500, 4))
    targets = [(max(1, round(th * s)), max(1, round(tw * s)))
               for (th, tw), s in zip(_targets(images),
                                      rng.uniform(0.3, 1.0, len(images)))]
    want = tj.resize_batch(images, targets)
    tj._arena = np.full(4 << 20, 255, np.uint8)
    poisoned = tj.resize_batch(images, targets)
    tj._arena = None
    larger = [_noise((700, 1000, 4))] * 4  # (1024, 1024) and its alpha
    tj.resize_batch(larger, _targets(larger))
    assert tj._arena.size == 4 * 1024 * 1024 * 3
    left = tj.resize_batch(images, targets)
    for a, b, c, t in zip(want, poisoned, left, targets):
        assert a.shape == (*t, a.shape[2])
        assert np.array_equal(a, b) and np.array_equal(a, c)


# (e) what `pack` writes: the frame and the filter's margin are enough


def _fill_edges(h: int, w: int, bh: int, bw: int, img: np.ndarray):
    """The whole canvas as the program before ISSUE 33 filled it."""
    return np.pad(img, ((0, bh - h), (0, bw - w), (0, 0)), mode="edge")


def _noise(shape):
    return np.random.default_rng(33).integers(0, 256, shape, dtype=np.uint8)


FILLS = {"ff": lambda shape: np.full(shape, 0xFF, np.uint8),
         "00": lambda shape: np.zeros(shape, np.uint8),
         "noise": _noise}


@pytest.mark.parametrize("scale", [0.1, 0.23, 0.5, 0.77, 1.0])
@pytest.mark.parametrize("channels", [3, 4])
@pytest.mark.parametrize("bucket,shapes", [
    ((256, 256), [(131, 233), (250, 256)]),   # landscape in a square
    ((256, 256), [(233, 131), (256, 250)]),   # portrait, as it stands
    ((512, 1024), [(300, 900), (505, 1021)]),  # landscape in a half
    ((512, 1024), [(900, 300), (1021, 505)]),  # portrait, transposed in
], ids=["square_landscape", "square_portrait", "half_landscape",
        "half_portrait"])
def test_nothing_unwritten_in_a_canvas_reaches_a_result(
        arena, monkeypatch, bucket, shapes, channels, scale):
    """Whatever the canvas held before the call (0xFF, 0x00, noise), the
    result is byte for byte what a canvas filled with replicated edges
    to its last byte gives: the margin is all the filter reads."""
    bh, bw = bucket
    images = [_photo(h, w, channels) for h, w in shapes]
    targets = [(max(1, round(h * scale)), max(1, round(w * scale)))
               for h, w in shapes]
    for h, w in shapes:
        assert tj.bucket_for(h, w) == bucket
    results = {}
    for name, fill in FILLS.items():
        tj._arena = fill((2 * bh * bw * 3,))
        results[name] = tj.resize_batch(images, targets)
    # the canvas before ISSUE 33: every byte an edge of the image it holds
    edges = {}
    as_packed = [img if (h <= bh and th <= tj.OUT_CANVAS_HW[0])
                 else np.transpose(img, (1, 0, 2))
                 for img, (h, _w), (th, _tw) in zip(images, shapes, targets)]
    for planes, chans in ((3, slice(0, 3)), (1, slice(3, 4))):
        if planes == 1 and channels == 3:
            continue
        edges[planes] = np.stack([
            _fill_edges(*a.shape[:2], bh, bw, a[..., chans])
            for a in as_packed])

    @contextlib.contextmanager
    def edge_canvas(bpad, _bh, _bw, planes):
        yield edges[planes][:bpad]

    with monkeypatch.context() as m:
        m.setattr(tj, "_staging_canvas", edge_canvas)
        results["edges"] = tj.resize_batch(images, targets)
    for name, outs in results.items():
        for out, want, t in zip(outs, results["edges"], targets):
            assert out.shape == (*t, channels)
            assert np.array_equal(out, want), name


@pytest.mark.parametrize("h,w,target", [
    (240, 135, (256, 144)),     # as a clip's frame: 1920 x 1080 by 8
    (233, 131, (58, 33)), (256, 250, (201, 196)), (200, 64, (100, 32)),
], ids=lambda v: str(v))
def test_a_portrait_as_it_stands_is_the_flipped_path_within_one(
        h, w, target):
    """Left as it stands, the two separable passes run in the other
    order: a byte differs by 1 where a float sum lands on .5."""
    th, tw = min(target[0], h), min(target[1], w)
    img = _photo(h, w, 3)
    stands = tj.resize_batch([img], [(th, tw)])[0]
    # the same pixels on the path a landscape takes, turned back
    flipped = np.transpose(tj.resize_batch(
        [np.ascontiguousarray(np.transpose(img, (1, 0, 2)))],
        [(tw, th)])[0], (1, 0, 2))
    assert stands.shape == flipped.shape == (th, tw, 3)
    gap = np.abs(stands.astype(int) - flipped.astype(int))
    assert gap.max() <= 1
    assert (gap != 0).mean() < 0.01, int((gap != 0).sum())


def test_a_portrait_transposes_in_only_where_it_has_to(arena):
    """Which canvas rows a frame filled says which way it went in."""
    clip = _photo(240, 135, 3)      # fits (256, 256) and its target fits
    photo = _photo(700, 600, 3)     # fits (1024, 1024); target 553 high
    tj.resize_batch([clip], [(128, 72)])
    assert np.array_equal(_arena_canvas(256, 256, 3)[:240, :135], clip)
    out = tj.resize_batch([photo], _targets([photo]))[0]
    assert _targets([photo])[0][0] > tj.OUT_CANVAS_HW[0]
    assert np.array_equal(_arena_canvas(1024, 1024, 3)[:600, :700],
                          np.transpose(photo, (1, 0, 2)))
    assert out.shape == (*_targets([photo])[0], 3)


# (f) how a frame goes in: a portrait turned in through a blocked transpose


def _pack_counts() -> dict:
    return {c: tm.THUMB_PACK_FRAMES.value(copy=c)
            for c in ("rows", "transposed", "planes")}


def _ticked(start: dict, **ticks) -> dict:
    return {c: n + ticks.get(c, 0) for c, n in start.items()}


def _turned(h: int, w: int, planes: int, seed: int = 42):
    """A decoded portrait of h × w as `resize_batch` places it: the
    transposed view of its contiguous frame, and its scale."""
    rng = np.random.default_rng(seed)
    frame = np.frombuffer(rng.bytes(h * w * planes), np.uint8).reshape(
        h, w, planes)
    tw, th = tj.scale_dimensions(w, h)
    return np.transpose(frame, (1, 0, 2)), (tw / w, th / h)


def _plane_loop(monkeypatch, canvas, img, scale) -> None:
    """`_pack_one` as the program before the blocked transpose packed."""
    with monkeypatch.context() as m:
        m.setattr(tj, "_transpose_in", lambda _dst, _img: False)
        tj._pack_one(canvas, img, scale)


@pytest.mark.parametrize("planes", [1, 3, 4])
@pytest.mark.parametrize("h,w,bucket", [
    (4032, 3024, (4096, 4096)),   # 12 MP, `photolib.heic` and `.hires`
    (4031, 3021, (4096, 4096)),
    (5712, 4284, (4608, 6144)),   # 24 MP, `photolib.hires`
    (5709, 4283, (4608, 6144)),
    (8064, 6048, (6144, 8192)),   # 48 MP, `photolib.hires`
    (8063, 6045, (6144, 8192)),
    (2532, 1170, (2048, 4096)),   # a screenshot, `photolib.cold`
    (2531, 1169, (2048, 4096)),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_a_turned_frame_transposes_in_byte_equal_to_the_plane_loop(
        arena, monkeypatch, h, w, bucket, planes):
    """Frame and margin, and every byte of the canvas round them that
    an earlier call left in the arena, are what the plane loop writes."""
    assert tj.bucket_for(h, w) == bucket
    img, scale = _turned(h, w, planes)
    bh, bw = bucket
    rng = np.random.default_rng(h * w)
    tj._arena = np.frombuffer(bytearray(rng.bytes(bh * bw * planes)),
                              np.uint8)
    want = tj._arena.reshape(bh, bw, planes).copy()
    _plane_loop(monkeypatch, want, img, scale)
    start = _pack_counts()
    with tj._staging_canvas(1, bh, bw, planes) as canvas:
        tj._pack_one(canvas[0], img, scale)
        assert np.array_equal(canvas[0], want)
    assert _pack_counts() == _ticked(start, transposed=1)


class _CopyingCv2:
    """cv2 whose transpose hands back a new array and leaves `dst`."""

    error = RuntimeError

    @staticmethod
    def transpose(src, dst=None):
        return np.ascontiguousarray(np.transpose(src, (1, 0, 2)))


@pytest.mark.parametrize("fallback", ["no_cv2", "dst_not_contiguous",
                                      "dst_not_written"])
def test_a_transpose_that_cannot_write_the_canvas_takes_the_plane_loop(
        monkeypatch, fallback):
    img, scale = _turned(700, 300, 3)
    bh, bw = tj.bucket_for(700, 300)
    # a canvas whose pixels lie 6 bytes apart: no cv::Mat can be it
    wide = np.full((bh, bw, 6), 0x5A, np.uint8)
    canvas = (wide[..., ::2] if fallback == "dst_not_contiguous"
              else np.full((bh, bw, 3), 0x5A, np.uint8))
    want = np.full((bh, bw, 3), 0x5A, np.uint8)
    _plane_loop(monkeypatch, want, img, scale)
    if fallback == "no_cv2":
        monkeypatch.setitem(sys.modules, "cv2", None)  # import fails
    elif fallback == "dst_not_written":
        monkeypatch.setitem(sys.modules, "cv2", _CopyingCv2)
    start = _pack_counts()
    tj._pack_one(canvas, img, scale)
    assert _pack_counts() == _ticked(start, planes=1)
    assert np.array_equal(canvas, want)


def test_every_frame_packed_ticks_once_by_the_copy_it_took(arena):
    images = [np.ascontiguousarray(a) for a in (
        _photo(400, 900, 3),   # landscape: one copy of its rows
        _photo(240, 135, 3),   # a clip's portrait, as it stands: rows
        _photo(900, 600, 3),   # a photo's portrait, turned in
        _photo(700, 600, 4),   # RGBA, turned in: colour and alpha planes
    )]
    # turned in, the three planes of a frame whose pixels are 4 bytes
    images.append(_photo(500, 300, 3))
    targets = _targets(images)
    targets[1] = (128, 72)
    start = _pack_counts()
    outs = tj.resize_batch(images, targets)
    assert _pack_counts() == _ticked(start, rows=2, transposed=1, planes=3)
    for out, img, t in zip(outs, images, targets):
        assert out.shape == (*t, img.shape[2])


# the warm-up contract (benchmark/warm.py:75-90, 131-135): warm.py runs ONE
# call per (bucket, pad), a 4-channel array of zeros, and the benchmark holds
# a timed pass to zero compile requests. So that call has to reach every
# program an RGB photo or an RGBA image of that bucket and pad reaches.

_compile_requests: list[str] = []


def _count_compile_requests() -> None:
    import jax.monitoring as monitoring

    if not _compile_requests:
        _compile_requests.append("listening")
        monitoring.register_event_duration_secs_listener(
            lambda event, _secs, **_kw: _compile_requests.append(event)
            if event == "/jax/core/compile/backend_compile_duration" else None)


@pytest.mark.parametrize("bucket,pad", [
    ((256, 256), 2), ((512, 1024), 1), ((1024, 1024), 4),
], ids=["256sq_pad2", "half1024_pad1", "1024sq_pad4"])
def test_warm_call_reaches_every_program_a_pass_dispatches(bucket, pad):
    _count_compile_requests()
    bh, bw = bucket
    # the literal call of benchmark/warm.py:resize_one
    tj.resize_batch([np.zeros((bh, bw, 4), np.uint8)] * pad,
                    [(min(bh, tj.OUT_CANVAS) // 2,
                      min(bw, tj.OUT_CANVAS) // 2)] * pad)
    before = len(_compile_requests)
    h, w = bh - 7, bw - 3
    assert tj.bucket_for(h, w) == bucket
    for channels in (3, 4):
        for shape in ((h, w), (w, h)):
            images = [_photo(*shape, channels)] * pad
            outs = tj.resize_batch(images, _targets(images))
            assert outs[0].shape[2] == channels
    assert len(_compile_requests) == before
