"""Media subsystem: image decode dispatch (incl. native libheif),
video thumbnails, labeler actor with resume, end-to-end labels.

Parity targets: ref:crates/images (handler dispatch), crates/ffmpeg
(movie_decoder), crates/ai (image_labeler actor).
"""

import asyncio
import os

import numpy as np
import pytest

from spacedrive_tpu.object.media.images import (

    format_image,
    heif_available,
)
from spacedrive_tpu.object.media.thumbnail import process


def _jpeg(path, size=(320, 240), color=(200, 60, 30)):
    from PIL import Image

    Image.new("RGB", size, color).save(path)


# --- decode dispatch ------------------------------------------------------


def test_format_image_generic(tmp_path):
    p = tmp_path / "a.jpg"
    _jpeg(p)
    arr = format_image(str(p))
    assert arr.shape == (240, 320, 4) and arr.dtype == np.uint8
    assert arr[0, 0, 0] > 150  # red-ish


def test_format_image_dispatches_svg_pdf(tmp_path):
    """SVG/PDF route through the single format_image dispatch (no
    longer gated out; ref:handler.rs:18-60). Undecodable payloads fail
    with the handler error, not an arbitrary exception."""
    from spacedrive_tpu.object.media.images import ImageHandlerError
    from spacedrive_tpu.object.media.svg import svg_available

    if svg_available():
        (tmp_path / "x.svg").write_text(
            '<svg xmlns="http://www.w3.org/2000/svg" width="10" height="10">'
            '<rect width="10" height="10" fill="blue"/></svg>'
        )
        arr = format_image(str(tmp_path / "x.svg"))
        assert arr.shape[-1] == 4 and arr.shape[0] > 0
    (tmp_path / "x.pdf").write_bytes(b"%PDF-1.4")  # no page tree
    with pytest.raises(ImageHandlerError):
        format_image(str(tmp_path / "x.pdf"))


@pytest.mark.skipif(not heif_available(), reason="libheif unavailable")
def test_heif_binding_loads():
    # without a HEIF encoder we can't make a fixture; assert the binding
    # wires and errors cleanly on a non-HEIF payload
    from spacedrive_tpu.object.media.images import ImageHandlerError, decode_heif

    with pytest.raises(ImageHandlerError):
        decode_heif("/dev/null")


def test_video_thumbnail_via_cv2(tmp_path, monkeypatch):
    cv2 = pytest.importorskip("cv2")
    # pin to the cv2 fallback: with libav present decode_video_frame
    # would short-circuit into the native frontend
    import spacedrive_tpu.native as native

    monkeypatch.setattr(native, "video_available", lambda: False)
    path = str(tmp_path / "clip.mp4")
    w, h = 128, 96
    vw = cv2.VideoWriter(path, cv2.VideoWriter_fourcc(*"mp4v"), 10, (w, h))
    assert vw.isOpened()
    for i in range(30):
        # bright frames so the film-strip darkening is measurable
        frame = np.full((h, w, 3), 180 + (i % 40), np.uint8)
        vw.write(frame)
    vw.release()
    d = process.decode_video_frame(path)
    assert d.array.shape[2] == 3 and d.array.shape[0] > 0  # RGB, no 255 plane
    webp = process.generate_one_cpu(path, "mp4")
    assert webp[:4] == b"RIFF" and webp[8:12] == b"WEBP"

    # film-strip overlay marks video thumbs (crates/ffmpeg film_strip.rs)
    import io as _io

    from PIL import Image

    frame = np.asarray(Image.open(_io.BytesIO(webp)).convert("RGB"))
    fh, fw = frame.shape[:2]
    strip = max(4, min(fw // 10, 20))
    assert frame[:, :strip].mean() < frame[:, strip:-strip].mean() * 0.75

    # stream facts (media-metadata video parity, via the same decoder)
    from spacedrive_tpu.object.media.media_data import VideoMetadata

    meta = VideoMetadata.from_path(path)
    assert meta is not None
    assert meta.resolution == (w, h)
    assert meta.fps and abs(meta.fps - 10) < 0.5
    assert meta.frame_count == 30
    assert meta.duration_seconds and abs(meta.duration_seconds - 3.0) < 0.3
    row = meta.to_row(object_id=1)
    import msgpack

    facts = msgpack.unpackb(row["camera_data"])
    assert facts["video"] is True and facts["codec"]


# --- native FFmpeg frontend parity (crates/ffmpeg movie_decoder.rs) -------


def _write_clip(path, w=128, h=96, frames=30, fps=10, asym=False):
    cv2 = pytest.importorskip("cv2")
    vw = cv2.VideoWriter(str(path), cv2.VideoWriter_fourcc(*"mp4v"), fps, (w, h))
    assert vw.isOpened()
    for i in range(frames):
        frame = np.zeros((h, w, 3), np.uint8)
        if asym:
            frame[: h // 4, :, 2] = 240  # bright-red top band (BGR)
            frame[h // 4:, :, 1] = 60
        else:
            frame[:, :, 2] = 10 + i * 8
        vw.write(frame)
    vw.release()


def _patch_tkhd_rotation(data: bytes, deg: int) -> bytes:
    """Rewrite the mp4 tkhd display matrix (how real phones mark
    portrait video)."""
    import struct

    i = data.find(b"tkhd")
    assert i > 4
    version = data[i + 4]
    moff = i + 4 + (40 if version == 0 else 52)
    fixed = lambda v: struct.pack(">i", int(v * 65536))  # noqa: E731
    f30 = lambda v: struct.pack(">i", int(v * (1 << 30)))  # noqa: E731
    assert deg == 90
    matrix = (fixed(0) + fixed(1) + f30(0) + fixed(-1) + fixed(0) + f30(0)
              + fixed(0) + fixed(0) + f30(1))
    return data[:moff] + matrix + data[moff + 36:]


@pytest.mark.skipif(
    not __import__("spacedrive_tpu.native", fromlist=["x"]).video_available(),
    reason="libav unavailable",
)
def test_native_video_rotation_applied(tmp_path):
    """A 90°-rotated clip (tkhd display matrix) decodes with swapped
    dimensions and the content rotated (ref:movie_decoder.rs rotation-
    aware filter graph)."""
    src = tmp_path / "plain.mp4"
    _write_clip(src, asym=True)
    rotated = tmp_path / "rot90.mp4"
    rotated.write_bytes(_patch_tkhd_rotation(src.read_bytes(), 90))

    d_plain = process.decode_video_frame(str(src))
    assert d_plain.array.shape[:2] == (96, 128)
    # red band at the top of the unrotated frame
    assert d_plain.array[:10, :, 0].mean() > 150

    d_rot = process.decode_video_frame(str(rotated))
    assert d_rot.array.shape[:2] == (128, 96)  # portrait now
    # after clockwise rotation the top band lands on the right edge
    assert d_rot.array[:, -10:, 0].mean() > 150
    assert d_rot.array[:, :10, 0].mean() < 100


@pytest.mark.skipif(
    not __import__("spacedrive_tpu.native", fromlist=["x"]).video_available(),
    reason="libav unavailable",
)
@pytest.mark.parametrize("fmt,mode,channels", [
    ("JPEG", "RGB", 3), ("PNG", "RGBA", 4)], ids=["jpeg", "png_with_alpha"])
def test_native_embedded_cover_preference(tmp_path, fmt, mode, channels):
    """A media file with attached cover art thumbnails from the cover,
    not a decoded frame (ref:movie_decoder.rs:352); the frontend hands
    on alpha only where the decoded format has it."""
    import io
    import struct

    from PIL import Image

    from spacedrive_tpu import native

    jpg = io.BytesIO()
    Image.new(mode, (64, 48), (250, 200, 10, 77)[:len(mode)]).save(jpg, fmt)
    jpeg = jpg.getvalue()
    apic = (b"\x00" + f"image/{fmt.lower()}".encode() + b"\x00" + b"\x03"
            + b"cover\x00" + jpeg)

    def synchsafe(n):
        return bytes([(n >> 21) & 0x7F, (n >> 14) & 0x7F,
                      (n >> 7) & 0x7F, n & 0x7F])

    tag_body = b"APIC" + struct.pack(">I", len(apic)) + b"\x00\x00" + apic
    id3 = b"ID3\x03\x00\x00" + synchsafe(len(tag_body)) + tag_body
    mp3_frame = b"\xff\xfb\x90\x00" + b"\x00" * 413  # MPEG1 L3 128k/44.1k
    p = tmp_path / "song.mp3"
    p.write_bytes(id3 + mp3_frame * 30)

    arr, rotation, is_cover = native.video_frame(str(p))
    assert is_cover and rotation == 0
    assert arr.shape == (48, 64, channels)
    assert arr[10, 10, 0] > 200 and arr[10, 10, 2] < 80  # the yellow art
    if channels == 4:
        assert (arr[..., 3] == 77).all()
        d = process.decode_video_frame(str(p))
        assert d.array.shape == (48, 64, 4) and not d.is_video


@pytest.mark.skipif(
    not __import__("spacedrive_tpu.native", fromlist=["x"]).video_available(),
    reason="libav unavailable",
)
def test_native_video_meta(tmp_path):
    src = tmp_path / "m.mp4"
    _write_clip(src)
    from spacedrive_tpu import native

    meta = native.video_meta(str(src))
    assert meta["width"] == 128 and meta["height"] == 96
    assert abs(meta["fps"] - 10) < 0.5
    assert meta["frame_count"] == 30
    assert meta["codec"] == "mpeg4"
    assert abs(meta["duration_seconds"] - 3.0) < 0.3
    with pytest.raises(ValueError):
        native.video_meta("/dev/null")


# --- labeler actor --------------------------------------------------------


def _provision_ckpt(labeler_dir, image_size=64):
    """Write a small (untrained but provisioned) checkpoint artifact:
    the actor's gate is artifact presence, matching the reference's
    downloaded-model gate (ref:crates/ai yolov8.rs:45-88). Pipeline
    tests run with threshold=0.0 so emitted labels don't depend on
    the weights being meaningful."""
    import jax

    from spacedrive_tpu.models import checkpoint
    from spacedrive_tpu.models import labeler as labeler_model

    widths, depths = (8, 8, 8, 8, 8), (1, 1, 1, 1)
    model = labeler_model.LabelerNet(num_classes=4, widths=widths, depths=depths)
    with jax.default_device(jax.devices("cpu")[0]):
        params = labeler_model.init_params(
            jax.random.key(0), image_size=image_size, model=model
        )
    checkpoint.save(
        os.path.join(labeler_dir, "weights.npz"), params,
        classes=["cat", "dog", "car", "tree"],
        image_size=image_size, widths=widths, depths=depths,
    )


def test_labeler_actor_writes_labels(tmp_path):
    async def run():
        from spacedrive_tpu.db.database import LibraryDb
        from spacedrive_tpu.models.labeler_actor import ImageLabeler

        class FakeLib:
            id = "11111111-1111-1111-1111-111111111111"
            db = LibraryDb(None, memory=True)

        lib = FakeLib()
        oid = lib.db.insert("object", pub_id=os.urandom(16), kind=5)
        img = tmp_path / "cat.jpg"
        _jpeg(img, size=(64, 64))
        _provision_ckpt(str(tmp_path / "labeler"))
        labeler = ImageLabeler(
            str(tmp_path / "labeler"), use_device=False, image_size=64,
            threshold=0.0,  # accept everything → labels exist
        )
        batch_id = labeler.new_batch(
            lib, [{"file_path_id": 1, "object_id": oid, "path": str(img)}]
        )
        assert batch_id != 0
        await asyncio.wait_for(labeler.wait_batch(batch_id), 120)
        assert labeler.labeled == 1
        n_links = lib.db.count("label_on_object")
        assert n_links > 0 and lib.db.count("label") == n_links
        await labeler.shutdown()

    asyncio.run(run())


def test_labeler_resume_file(tmp_path):
    async def run():
        from spacedrive_tpu.db.database import LibraryDb
        from spacedrive_tpu.models.labeler_actor import RESUME_FILE, ImageLabeler

        class FakeLib:
            id = "22222222-2222-2222-2222-222222222222"
            db = LibraryDb(None, memory=True)

        lib = FakeLib()
        oid = lib.db.insert("object", pub_id=os.urandom(16), kind=5)
        img = tmp_path / "dog.jpg"
        _jpeg(img, size=(64, 64))
        data_dir = str(tmp_path / "labeler")
        _provision_ckpt(data_dir)

        # queue a batch but never start an event loop worker for it:
        # shutdown persists it to to_resume_batches.bin
        labeler = ImageLabeler(data_dir, use_device=False, image_size=64)
        labeler._stopped = True  # prevent the worker from grabbing it
        labeler.new_batch(
            lib, [{"file_path_id": 1, "object_id": oid, "path": str(img)}]
        )
        await labeler.shutdown()
        assert os.path.exists(os.path.join(data_dir, RESUME_FILE))

        # a fresh actor + re-registered library resumes and completes it
        labeler2 = ImageLabeler(
            data_dir, use_device=False, image_size=64, threshold=0.0
        )
        labeler2.register_library(lib)
        for _ in range(600):
            if labeler2.labeled >= 1:
                break
            await asyncio.sleep(0.1)
        assert labeler2.labeled == 1
        assert lib.db.count("label_on_object") > 0
        await labeler2.shutdown()

    asyncio.run(run())


# --- end-to-end through the media job ------------------------------------


def test_media_job_labels_end_to_end(tmp_path):
    async def run():
        from spacedrive_tpu.location.locations import LocationCreateArgs, scan_location
        from spacedrive_tpu.node import Node

        corpus = tmp_path / "corpus"
        corpus.mkdir()
        for i in range(3):
            _jpeg(corpus / f"photo{i}.jpg", size=(100, 80), color=(i * 50, 90, 120))
        node = Node(str(tmp_path / "node"), use_device=False)
        node.config.config.p2p.enabled = False
        _provision_ckpt(node.image_labeler.data_dir)
        node.image_labeler.threshold = 0.0  # emit all classes
        node.image_labeler.image_size = 64
        await node.start()
        lib = await node.create_library("pics")
        loc = LocationCreateArgs(path=str(corpus)).create(lib)
        await scan_location(lib, loc, node.jobs)
        await node.jobs.wait_idle()
        try:
            assert node.image_labeler.labeled == 3
            assert lib.db.count("label_on_object") > 0
            # labels are queryable through the API
            labels = await node.router.exec(
                node, "labels.list", library_id=str(lib.id)
            )
            assert labels["nodes"]
        finally:
            await node.shutdown()

    asyncio.run(run())
