"""`embedder.decode_image` (ISSUE 26, ISSUE 30): a JPEG is decoded at
the DCT scale `images.draft_jpeg` asks for (the thumbnailer's target,
never under 8 source pixels per pixel of the 32×32 plane a side, so
one frame serves both), everything PIL opens goes straight to RGB, and
what is not DCT-scaled gives the plane the old path gave, bit for bit.
The old path (`format_image` → RGBA array → `fromarray` → RGB → resize)
is restated here as the reference."""

import os

import numpy as np
import pytest

from spacedrive_tpu.models import embedder
from spacedrive_tpu.telemetry import counter_value


def _field(seed: int, w: int, h: int, noisy: bool = False):
    """A seeded low-resolution colour field blown up to (w, h) with
    bicubic, as the benchmark's generator makes a photo; `noisy` adds
    pixel noise and hard edges, what a real photo has and the smooth
    field lacks."""
    from PIL import Image

    rng = np.random.default_rng(seed)
    grid = rng.integers(0, 256, (max(2, h // 252), max(2, w // 252), 3),
                        dtype=np.uint8)
    img = Image.fromarray(grid).resize((w, h), Image.BICUBIC)
    if not noisy:
        return img
    arr = np.asarray(img).astype(np.int16)
    arr += rng.integers(-24, 25, arr.shape, dtype=np.int16)
    for _ in range(12):  # rectangles with hard edges
        x, y = int(rng.integers(0, w - 8)), int(rng.integers(0, h - 8))
        dx, dy = int(rng.integers(8, w // 3)), int(rng.integers(8, h // 3))
        arr[y:y + dy, x:x + dx] = rng.integers(0, 256, 3)
    return Image.fromarray(np.clip(arr, 0, 255).astype(np.uint8))


def _old_plane(path: str, size: int = embedder.IMAGE_SIZE):
    from PIL import Image

    from spacedrive_tpu.object.media.images import format_image

    img = Image.fromarray(format_image(path)).convert("RGB").resize((size, size))
    return np.asarray(img, np.float32) / 255.0


def _full_size_plane(path: str, size: int = embedder.IMAGE_SIZE):
    from PIL import Image

    with Image.open(path) as im:
        img = im.convert("RGB").resize((size, size))
    return np.asarray(img, np.float32) / 255.0


def _scales() -> dict[str, float]:
    return {s: counter_value("sd_embed_decode_total", scale=s)
            for s in ("1", "2", "4", "8")}


def _decode_counting(path: str):
    """(plane, the one scale label this decode incremented or None)."""
    before = _scales()
    plane = embedder.decode_image(path)
    grew = [s for s, v in _scales().items() if v != before[s]]
    assert len(grew) <= 1
    if grew:
        assert _scales()[grew[0]] == before[grew[0]] + 1
    return plane, (grew[0] if grew else None)


# --- (a) a DCT-scaled decode stays inside the vector's tolerance -----------


@pytest.mark.parametrize("noisy", [False, True], ids=["smooth", "noisy"])
@pytest.mark.parametrize("w,h,scale", [(4032, 3024, "4"), (2016, 1512, "2")])
def test_scaled_jpeg_vector_close_to_full_size(tmp_path, w, h, scale, noisy):
    path = str(tmp_path / "photo.jpg")
    _field(7, w, h, noisy).save(path, "JPEG", quality=88)
    plane, label = _decode_counting(path)
    assert label == scale
    assert plane.shape == (32, 32, 3) and plane.dtype == np.float32
    p = embedder.params()
    both = np.asarray(embedder.forward(
        p, np.stack([plane, _full_size_plane(path)])))
    assert np.abs(both[0] - both[1]).max() <= 0.01


# --- (b) what is not DCT-scaled is the old plane, bit for bit --------------


def _save_small_jpeg(img, path):
    img.save(path, "JPEG", quality=88)


def _save_png_rgb(img, path):
    img.save(path, "PNG")


def _save_png_rgba(img, path):
    img = img.copy()
    img.putalpha(img.convert("L").rotate(180))
    img.save(path, "PNG")


def _save_png_p_transparent(img, path):
    img.quantize(64).save(path, "PNG", transparency=3)


def _save_png_l(img, path):
    img.convert("L").save(path, "PNG")


def _save_png_la(img, path):
    grey = img.convert("L")
    grey.putalpha(grey.rotate(180))
    assert grey.mode == "LA"
    grey.save(path, "PNG")


def _save_webp(img, path):
    img.save(path, "WEBP", quality=80)


def _save_gif(img, path):
    img.save(path, "GIF")


UNSCALED = {
    "jpeg_500x375": ("small.jpg", _save_small_jpeg, "RGB"),
    "png_rgb": ("rgb.png", _save_png_rgb, "RGB"),
    "png_rgba": ("rgba.png", _save_png_rgba, "RGBA"),
    "png_p_transparent": ("p.png", _save_png_p_transparent, "P"),
    "png_l": ("l.png", _save_png_l, "L"),
    "png_la": ("la.png", _save_png_la, "LA"),
    "webp": ("rgb.webp", _save_webp, "RGB"),
    "gif": ("rgb.gif", _save_gif, "P"),
}


@pytest.mark.parametrize("case", sorted(UNSCALED))
def test_unscaled_plane_bit_identical_to_old_path(tmp_path, case):
    from PIL import Image

    name, save, mode = UNSCALED[case]
    path = str(tmp_path / name)
    save(_field(11, 500, 375, noisy=True), path)
    with Image.open(path) as im:
        assert im.mode == mode
    plane, label = _decode_counting(path)
    assert label == "1"
    assert np.array_equal(plane, _old_plane(path))


# --- (c) modes, broken files, EXIF ----------------------------------------


@pytest.mark.parametrize("mode", ["L", "CMYK"])
@pytest.mark.parametrize("w,h,scale", [(500, 375, "1"), (1024, 768, "1")])
def test_grey_and_cmyk_jpegs_give_an_rgb_plane(tmp_path, mode, w, h, scale):
    path = str(tmp_path / "photo.jpg")
    _field(13, w, h).convert(mode).save(path, "JPEG", quality=88)
    plane, label = _decode_counting(path)
    assert label == scale
    assert plane.shape == (32, 32, 3) and plane.dtype == np.float32
    assert plane.min() >= 0.0 and plane.max() <= 1.0
    if scale == "1":
        assert np.array_equal(plane, _old_plane(path))


def _truncated_jpeg(path):
    _field(17, 1024, 768).save(path, "JPEG", quality=88)
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 3)


def _text_file(path):
    with open(path, "w") as f:
        f.write("not an image\n" * 40)


def _missing(path):
    pass


@pytest.mark.parametrize("make", [_truncated_jpeg, _text_file, _missing],
                         ids=["truncated", "text", "missing"])
def test_undecodable_gives_none_and_counts_nothing(tmp_path, make):
    path = str(tmp_path / "broken.jpg")
    make(path)
    plane, label = _decode_counting(path)
    assert plane is None and label is None


def test_file_over_the_size_guard_gives_none(tmp_path, monkeypatch):
    from spacedrive_tpu.object.media import images

    path = str(tmp_path / "small.png")
    _field(19, 64, 48).save(path, "PNG")
    assert embedder.decode_image(path) is not None
    monkeypatch.setattr(images, "MAXIMUM_FILE_SIZE", os.path.getsize(path) - 1)
    assert embedder.decode_image(path) is None


@pytest.mark.parametrize("w,h", [(500, 375), (2016, 1512)])
def test_exif_orientation_changes_nothing(tmp_path, w, h):
    """The model sees the stored pixels (the reference's `embed_plane`)."""
    from PIL import Image

    img = _field(23, w, h)
    planes = []
    for orientation in (1, 6):
        path = str(tmp_path / f"o{orientation}.jpg")
        exif = Image.Exif()
        exif[0x0112] = orientation
        img.save(path, "JPEG", quality=88, exif=exif)
        planes.append(embedder.decode_image(path))
    assert np.array_equal(planes[0], planes[1])


@pytest.mark.parametrize("mode", ["RGBA", "RGB"])
def test_handler_formats_keep_format_image(tmp_path, monkeypatch, mode):
    """HEIF, SVG and PDF do not come from PIL: `format_image` decodes
    them, and its array, RGBA or the three channels a HEIF without an
    alpha channel comes as, goes through the same RGB resize."""
    from spacedrive_tpu.object.media import images

    frame = np.asarray(_field(29, 96, 64).convert(mode))
    assert frame.shape == (64, 96, len(mode))
    seen = []

    def fake_format_image(path, extension=None):
        seen.append(path)
        return frame

    monkeypatch.setattr(images, "format_image", fake_format_image)
    # the same plane whichever way the frame came
    want = embedder.input_plane(np.asarray(
        _field(29, 96, 64).convert("RGB").resize((32, 32))))
    for ext in ("heic", "svg", "pdf"):
        path = str(tmp_path / f"doc.{ext}")
        with open(path, "wb") as f:
            f.write(b"opaque")
        plane, label = _decode_counting(path)
        assert label == "1" and plane.shape == (32, 32, 3)
        assert np.array_equal(plane, want)
    assert len(seen) == 3


# --- (d) the media job's leg, the mesh stage's leg and the thumbnailer's tap
# give the same plane ---------------------------------------------------------


def test_stage_embed_decode_bytes_equal_inline_planes(tmp_path):
    from spacedrive_tpu.object.media.job import MediaProcessorJob
    from spacedrive_tpu.object.media.thumbnail import process

    paths = []
    for name, (w, h), fmt in [("a.jpg", (4032, 3024), "JPEG"),
                              ("b.jpg", (500, 375), "JPEG"),
                              ("c.png", (390, 844), "PNG")]:
        paths.append(str(tmp_path / name))
        _field(31, w, h).save(paths[-1], fmt)
    paths.append(str(tmp_path / "d.jpg"))
    _text_file(paths[-1])
    # the media job's embed step decodes what the holder lacks; the
    # mesh embed stage (location/indexer/stages.py) decodes each pending
    # path with `embedder.decode_image`
    job_planes = MediaProcessorJob._decode_for_embed(None, paths)
    stage_planes = [embedder.decode_image(p) for p in paths]
    planes = [None if p is None else p.tobytes() for p in job_planes]
    assert [None if p is None else p.tobytes() for p in stage_planes] \
        == planes
    assert planes[-1] is None
    assert all(len(b) == 32 * 32 * 3 * 4 for b in planes[:-1])

    # the third side: the plane made from the frame the thumbnailer
    # decoded (what the media job's holder keeps, as uint8)
    tapped = []
    for path in paths[:-1]:
        process.decode_image(
            path, lambda frame, scale: tapped.append(
                embedder.plane_from_frame(frame, scale)))
    assert [embedder.input_plane(p).tobytes() for p in tapped] \
        == planes[:-1]
    with pytest.raises(Exception):
        process.decode_image(paths[-1], lambda frame, scale: tapped.append(0))
    assert len(tapped) == 3
