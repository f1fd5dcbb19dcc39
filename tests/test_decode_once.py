"""Decode once (ISSUE 30): the embedder's 32 x 32 plane is made from
the frame the thumbnailer decoded, on the thumbnailer's decode worker,
and the media job's embed step decodes only what no plane arrived for.

- (a) one definition of the plane: the tap's plane is
  `embedder.decode_image`'s, bit for bit, whatever the file;
- (b) a cold media job embeds every image from a shared plane and its
  vectors equal those of a pass that had to decode for itself;
- (c) no sink, a restarted actor and the pooled software path fall back;
- (d) copies share a plane, vouched rows get none, the holder is empty
  after the job however it ended;
- (e) the thumbnailer's own arrays and stored webp bytes are the
  parent commit's (golden hashes taken on 44a5960).
"""

import asyncio
import hashlib
import os
import shutil

import numpy as np
import pytest

from spacedrive_tpu.telemetry import counter_value
from test_embed_decode import _field

# --- the files -------------------------------------------------------------


def _jpeg(w, h, mode="RGB", seed=41):
    def make(path):
        _field(seed, w, h, noisy=True).convert(mode).save(
            path, "JPEG", quality=88)
    return make


def _png_rgb(path):
    _field(43, 500, 375, noisy=True).save(path, "PNG")


def _png_rgba(path):
    img = _field(47, 500, 375, noisy=True)
    img.putalpha(img.convert("L").rotate(180))
    img.save(path, "PNG")


def _png_p_transparent(path):
    _field(53, 500, 375, noisy=True).quantize(64).save(
        path, "PNG", transparency=3)


def _png_over_max_dim(path):
    _field(59, 4200, 300).save(path, "PNG")


def _jpeg_oriented(path):
    from PIL import Image

    exif = Image.Exif()
    exif[0x0112] = 6
    _field(61, 2016, 1512, noisy=True).save(
        path, "JPEG", quality=88, exif=exif)


FILES = {
    "jpeg_2016x1512": ("photo.jpg", _jpeg(2016, 1512)),
    "jpeg_400x300": ("small.jpg", _jpeg(400, 300)),
    "jpeg_grey": ("grey.jpg", _jpeg(1024, 768, "L")),
    "jpeg_cmyk": ("cmyk.jpg", _jpeg(1024, 768, "CMYK")),
    "jpeg_exif6": ("turned.jpg", _jpeg_oriented),
    "png_rgb": ("rgb.png", _png_rgb),
    "png_rgba": ("rgba.png", _png_rgba),
    "png_p_transparent": ("p.png", _png_p_transparent),
    "png_over_4096": ("wide.png", _png_over_max_dim),
}
# the DCT scale `images.draft_jpeg` gives each (the plane's counter label)
SCALES = {"jpeg_2016x1512": 2, "jpeg_exif6": 2}


def _write(case, root) -> str:
    name, make = FILES[case]
    path = os.path.join(str(root), name)
    make(path)
    return path


def _planes(source=None) -> float:
    if source is None:
        return sum(counter_value("sd_embed_decode_total", scale=s)
                   for s in ("1", "2", "4", "8"))
    return counter_value("sd_embed_planes_total", source=source)


# --- (a) the tap's plane is decode_image's ---------------------------------


def test_draft_floor_is_eight_source_pixels_a_plane_pixel():
    from spacedrive_tpu.models import embedder
    from spacedrive_tpu.object.media import images

    assert images.PLANE_SOURCE_SIDE == 8 * embedder.IMAGE_SIZE


@pytest.mark.parametrize("case", sorted(FILES))
def test_tap_plane_is_decode_image_plane(tmp_path, case):
    from spacedrive_tpu.models import embedder
    from spacedrive_tpu.object.media.thumbnail import process

    path = _write(case, tmp_path)
    seen = []
    decoded = process.decode(
        path, os.path.splitext(path)[1][1:],
        lambda frame, scale: seen.append((frame, scale)))
    (frame, scale), = seen
    assert scale == SCALES.get(case, 1)
    assert frame.mode in ("RGB", "RGBA")
    if case == "png_over_4096":
        # since ISSUE 38 no stride thins it: the frame the tap saw is the
        # frame the resize gets ((150, 2100) of it before)
        assert frame.size == (4200, 300)
        assert decoded.array.shape[:2] == (300, 4200)
    assert np.array_equal(decoded.array, np.asarray(frame))

    before = _planes()
    shared = embedder.plane_from_frame(frame, scale)
    assert shared.dtype == np.uint8 and shared.shape == (32, 32, 3)
    own = embedder.decode_image(path)
    assert _planes() == before + 2  # one count a plane, whoever made it
    assert own.dtype == np.float32
    assert np.array_equal(embedder.input_plane(shared), own)


def test_heif_frames_are_offered_document_and_video_frames_are_not(
        tmp_path, monkeypatch):
    from spacedrive_tpu.models import embedder
    from spacedrive_tpu.object.media import images
    from spacedrive_tpu.object.media.thumbnail import process

    rgba = np.asarray(_field(67, 640, 480).convert("RGBA"))
    monkeypatch.setattr(process, "format_image", lambda p, e=None: rgba)
    monkeypatch.setattr(images, "format_image", lambda p, e=None: rgba)
    monkeypatch.setattr(process, "SVG_EXTENSIONS", ("svg",))
    path = str(tmp_path / "doc.heic")
    with open(path, "wb") as f:
        f.write(b"opaque")
    seen = []
    tap = lambda frame, scale: seen.append((frame, scale))  # noqa: E731
    process.decode(path, "heic", tap)
    (frame, scale), = seen
    assert scale == 1
    assert np.array_equal(
        embedder.input_plane(embedder.plane_from_frame(frame, scale)),
        embedder.decode_image(path))
    process.decode(path, "svg", tap)
    process.decode(path, "pdf", tap)
    assert len(seen) == 1


def test_a_failing_sink_costs_the_plane_not_the_thumbnail(tmp_path):
    from spacedrive_tpu.object.media.thumbnail import Thumbnailer

    path = _write("png_rgb", tmp_path)

    def sink(cas_id, frame, scale):
        raise RuntimeError("guest fault")

    async def main():
        thumbs = Thumbnailer(str(tmp_path / "data"))
        try:
            bid = thumbs.new_indexed_thumbnails_batch(
                "lib", [("c" * 16, path)], sink=sink)
            await asyncio.wait_for(thumbs.wait_batch(bid), 120)
            assert thumbs.store.exists("lib", "c" * 16)
            assert thumbs.errors == 0
        finally:
            await thumbs.shutdown()

    asyncio.run(main())


# --- the media job harness -------------------------------------------------


def _location(root, cases=tuple(sorted(FILES))) -> str:
    corpus = os.path.join(str(root), "corpus")
    os.makedirs(corpus)
    for case in cases:
        _write(case, corpus)
    return corpus


async def _pipeline(tmp_path, use_device=True):
    """(node, library, mgr): a stub node with a real thumbnailer, the
    test_semantic_search pattern."""
    from spacedrive_tpu.jobs import JobManager
    from spacedrive_tpu.node import Libraries
    from spacedrive_tpu.object.media.thumbnail import Thumbnailer
    from spacedrive_tpu.tasks import TaskSystem

    class _Node:
        pass

    node = _Node()
    node.thumbnailer = Thumbnailer(
        str(tmp_path / "data"), use_device=use_device)
    node.image_labeler = None
    libs = Libraries(str(tmp_path / "data"), node=node)
    return node, libs.create("decode-once"), JobManager(TaskSystem(2))


async def _identify(library, mgr, corpus) -> dict:
    """location → indexer → identifier, and no media job yet."""
    from spacedrive_tpu.jobs.manager import JobBuilder
    from spacedrive_tpu.location.indexer.job import IndexerJob
    from spacedrive_tpu.location.locations import LocationCreateArgs
    from spacedrive_tpu.object.file_identifier.job import FileIdentifierJob

    loc = library.db.find_one("location", path=corpus)
    if loc is None:
        loc = LocationCreateArgs(path=corpus).create(library)
    init = {"location_id": loc["id"]}
    job_id = await JobBuilder(IndexerJob(dict(init))).queue_next(
        FileIdentifierJob({**init, "backend": "cpu"})).spawn(mgr, library)
    await mgr.wait(job_id)
    await mgr.wait_idle()
    return loc


async def _media(library, mgr, loc):
    """Run one MediaProcessorJob to its end; → (job, its holders)."""
    from spacedrive_tpu.jobs.manager import JobBuilder
    from spacedrive_tpu.object.media import job as media_job

    holders = []
    real = media_job._EmbedPlanes

    class Recorded(real):
        def __init__(self, cas_ids):
            super().__init__(cas_ids)
            holders.append(self)

    media_job._EmbedPlanes = Recorded
    try:
        job = media_job.MediaProcessorJob(
            {"location_id": loc["id"], "backend": "cpu"})
        await JobBuilder(job).spawn(mgr, library)
        await asyncio.wait_for(mgr.wait(job.id), 300)
        await mgr.wait_idle()
    finally:
        media_job._EmbedPlanes = real
    return job, holders


def _vectors(library) -> dict[str, bytes]:
    return {
        f"{r['name']}.{r['extension']}": bytes(r["vector"])
        for r in library.db.query(
            "SELECT fp.name, fp.extension, e.vector FROM file_path fp "
            "JOIN object_embedding e ON e.object_id = fp.object_id")
    }


def _job_status(library, job) -> int:
    return library.db.query_one(
        "SELECT status FROM job WHERE id = ?", (job.id.bytes,))["status"]


COMPLETED = 2


# --- (b) a cold pass shares every plane; the vectors are the same bits -----


async def test_cold_job_embeds_from_shared_planes_same_vectors_as_own(
        tmp_path, monkeypatch):
    from spacedrive_tpu.models import embedder

    corpus = _location(tmp_path)
    names = sorted(name for name, _ in FILES.values())

    # the pass that has to decode for itself: thumbnails stored (and
    # vouched) by a pass without the embedder, vectors made afterwards
    node, library, mgr = await _pipeline(tmp_path / "own")
    try:
        loc = await _identify(library, mgr, corpus)
        monkeypatch.setenv("SD_EMBED", "0")
        await _media(library, mgr, loc)
        assert _vectors(library) == {}
        monkeypatch.delenv("SD_EMBED")
        shared0, own0 = _planes("shared"), _planes("own")
        job, holders = await _media(library, mgr, loc)
        assert _job_status(library, job) == COMPLETED
        assert holders == []  # no thumbnail left to make: no sink
        assert _planes("shared") == shared0
        assert _planes("own") == own0 + len(names)
        own_vectors = _vectors(library)
        assert sorted(own_vectors) == names
    finally:
        await node.thumbnailer.shutdown()

    # the cold pass: every plane from the thumbnailer's frame
    calls = []
    real = embedder.decode_image
    monkeypatch.setattr(embedder, "decode_image",
                        lambda p: calls.append(p) or real(p))
    node, library, mgr = await _pipeline(tmp_path / "shared")
    try:
        loc = await _identify(library, mgr, corpus)
        shared0, own0, made0 = _planes("shared"), _planes("own"), _planes()
        job, (holder,) = await _media(library, mgr, loc)
        assert _job_status(library, job) == COMPLETED
        assert _planes("shared") == shared0 + len(names)
        assert _planes("own") == own0
        assert _planes() == made0 + len(names)
        assert calls == []
        assert len(holder) == 0 and job._planes is None
        assert job.run_metadata["embeddings_written"] == len(names)
        for name in names:  # every webp landed and was vouched as before
            row = library.db.find_one(
                "file_path", name=os.path.splitext(name)[0])
            assert node.thumbnailer.store.exists(
                str(library.id), row["cas_id"])
        assert _vectors(library) == own_vectors
    finally:
        await node.thumbnailer.shutdown()


# --- (c) what offers no plane falls back to the job's own decode -----------


def _without_sink(node):
    real = node.thumbnailer.new_indexed_thumbnails_batch
    node.thumbnailer.new_indexed_thumbnails_batch = (
        lambda lib, entries, background=False, sink=None:
        real(lib, entries, background))


def _restarting_actor(node):
    """The process dies after the batch is queued and persisted, before
    the worker decodes a file; the next actor reloads the batch from
    `thumbs_to_process.bin`."""
    from spacedrive_tpu.object.media.thumbnail import Thumbnailer

    old = node.thumbnailer
    real = old.new_indexed_thumbnails_batch

    def enqueue(lib, entries, background=False, sink=None):
        assert sink is not None
        old._kick = lambda: None  # the worker never starts
        assert real(lib, entries, background, sink)
        fresh = Thumbnailer(old.data_dir)
        node.thumbnailer = fresh
        (batch,) = fresh._bg
        assert batch.sink is None and len(batch.entries) == len(entries)
        return batch.id

    old.new_indexed_thumbnails_batch = enqueue


@pytest.mark.parametrize("arrange", [_without_sink, _restarting_actor],
                         ids=["no_sink", "restarted_actor"])
async def test_no_plane_offered_every_vector_through_own(tmp_path, arrange):
    cases = ("jpeg_2016x1512", "jpeg_400x300", "png_rgba")
    corpus = _location(tmp_path, cases)
    node, library, mgr = await _pipeline(tmp_path)
    try:
        loc = await _identify(library, mgr, corpus)
        arrange(node)
        shared0, own0 = _planes("shared"), _planes("own")
        job, (holder,) = await _media(library, mgr, loc)
        assert _job_status(library, job) == COMPLETED
        assert _planes("shared") == shared0
        assert _planes("own") == own0 + len(cases)
        assert len(_vectors(library)) == len(cases)
        assert len(holder) == 0
        for row in library.db.query(
                "SELECT cas_id FROM file_path WHERE is_dir = 0"):
            assert node.thumbnailer.store.exists(
                str(library.id), row["cas_id"])
    finally:
        await node.thumbnailer.shutdown()


async def test_pooled_software_path_offers_nothing(tmp_path, monkeypatch):
    from spacedrive_tpu.parallel import procpool

    cases = ("jpeg_2016x1512", "jpeg_400x300", "png_rgba")
    corpus = _location(tmp_path, cases)
    monkeypatch.setenv("SD_PROCS", "2")
    assert procpool.POOL.start()
    try:
        procpool.POOL.warm()
        node, library, mgr = await _pipeline(tmp_path, use_device=False)
        try:
            loc = await _identify(library, mgr, corpus)
            shared0, own0 = _planes("shared"), _planes("own")
            job, (holder,) = await _media(library, mgr, loc)
            assert _job_status(library, job) == COMPLETED
            assert _planes("shared") == shared0
            assert _planes("own") == own0 + len(cases)
            assert len(_vectors(library)) == len(cases)
            assert len(holder) == 0
        finally:
            await node.thumbnailer.shutdown()
    finally:
        while procpool.POOL.running():
            procpool.POOL.stop()


# --- (d) the holder ---------------------------------------------------------


def test_holder_keeps_one_plane_a_cas_id_for_wanted_rows_only():
    from spacedrive_tpu.object.media.job import _EmbedPlanes

    frame = np.asarray(_field(71, 320, 240))
    holder = _EmbedPlanes(["a", "a", "b"])
    made0 = _planes()
    holder.offer("c", frame, 1)  # not a row the job embeds
    assert len(holder) == 0 and _planes() == made0
    holder.offer("a", frame, 1)
    holder.offer("a", frame, 1)  # the copy's frame: the plane is there
    assert len(holder) == 1 and _planes() == made0 + 1
    first, second = holder.take("a"), holder.take("a")
    assert first is second and first.nbytes == 3072
    assert len(holder) == 0  # dropped with its last row
    assert holder.take("a") is None and holder.take("b") is None
    holder.offer("b", frame, 1)  # its row is past: nothing is kept
    assert len(holder) == 0
    holder = _EmbedPlanes(["a"])
    holder.offer("a", frame, 1)
    holder.close()
    assert len(holder) == 0 and holder.take("a") is None
    holder.offer("a", frame, 1)  # a decode that outlived the job
    assert len(holder) == 0


async def test_copies_share_a_plane_and_vouched_rows_get_none(tmp_path):
    corpus = _location(tmp_path, ("jpeg_400x300", "png_rgb"))
    shutil.copy(os.path.join(corpus, "rgb.png"),
                os.path.join(corpus, "rgb_copy.png"))
    node, library, mgr = await _pipeline(tmp_path)
    thumbnailer, node.thumbnailer = node.thumbnailer, None
    try:
        # no thumbnailer: three vectors through the job's own decode,
        # vouched; no thumbnail stored or vouched
        loc = await _identify(library, mgr, corpus)
        job, holders = await _media(library, mgr, loc)
        assert holders == [] and len(_vectors(library)) == 3

        # the thumbnail batch now holds three rows whose vectors the
        # journal vouches, and a new photo with a copy of its own
        _write("jpeg_2016x1512", corpus)
        shutil.copy(os.path.join(corpus, "photo.jpg"),
                    os.path.join(corpus, "photo_copy.jpg"))
        node.thumbnailer = thumbnailer
        loc = await _identify(library, mgr, corpus)
        shared0, own0, made0 = _planes("shared"), _planes("own"), _planes()
        job, (holder,) = await _media(library, mgr, loc)
        assert _job_status(library, job) == COMPLETED
        assert thumbnailer.generated == 5  # every row was decoded
        assert _planes() == made0 + 1  # one plane: the two new rows' cas_id
        assert _planes("shared") == shared0 + 2
        assert _planes("own") == own0
        vectors = _vectors(library)
        assert len(vectors) == 5
        assert vectors["photo.jpg"] == vectors["photo_copy.jpg"]
        assert len(holder) == 0
    finally:
        await thumbnailer.shutdown()


async def test_holder_empty_after_a_failed_embed_step(tmp_path, monkeypatch):
    from spacedrive_tpu.ops import embed_jax

    corpus = _location(tmp_path, ("jpeg_400x300", "png_rgb"))
    node, library, mgr = await _pipeline(tmp_path)
    try:
        loc = await _identify(library, mgr, corpus)

        def broken(images, **kwargs):
            raise RuntimeError("forward failed")

        monkeypatch.setattr(embed_jax, "embed_batch", broken)
        job, (holder,) = await _media(library, mgr, loc)
        assert _job_status(library, job) != COMPLETED
        assert _vectors(library) == {}
        assert len(holder) == 0 and job._planes is None
    finally:
        await node.thumbnailer.shutdown()


async def test_holder_empty_after_cancel(tmp_path, monkeypatch):
    from spacedrive_tpu.object.media.job import MediaProcessorJob

    corpus = _location(tmp_path, ("jpeg_400x300", "png_rgb"))
    node, library, mgr = await _pipeline(tmp_path)
    try:
        loc = await _identify(library, mgr, corpus)
        held = []
        real = MediaProcessorJob._wait_thumbnails

        async def wait_then_cancel(self, ctx, step):
            result = await real(self, ctx, step)
            held.append(len(self._planes))
            await mgr.cancel(self.id)  # honoured before the embed step
            return result

        monkeypatch.setattr(
            MediaProcessorJob, "_wait_thumbnails", wait_then_cancel)
        job, (holder,) = await _media(library, mgr, loc)
        assert held == [2]  # both planes were waiting for the embed step
        assert _job_status(library, job) != COMPLETED
        assert _vectors(library) == {}
        assert len(holder) == 0 and job._planes is None
    finally:
        await node.thumbnailer.shutdown()


# --- (e) the thumbnailer's own output is the parent's ----------------------


def _sha(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:24]


async def _thumbnailer_fingerprints(root, sink=None) -> dict:
    """case → (array shape, target, orientation, sha of `Decoded.array`,
    sha of the stored webp). Written against what the parent commit
    has, so the same function took the goldens there."""
    from spacedrive_tpu.object.media.thumbnail import Thumbnailer, process

    out = {}
    thumbs = Thumbnailer(os.path.join(str(root), "data"))
    try:
        entries = []
        for case in sorted(FILES):
            path = _write(case, root)
            d = process.decode(path, os.path.splitext(path)[1][1:])
            out[case] = [list(d.array.shape), list(d.target), d.orientation,
                         _sha(d.array.tobytes())]
            entries.append((case, path))
        kwargs = {} if sink is None else {"sink": sink}
        bid = thumbs.new_indexed_thumbnails_batch("lib", entries, **kwargs)
        await asyncio.wait_for(thumbs.wait_batch(bid), 300)
        for case in sorted(FILES):
            with open(thumbs.store.path_for("lib", case), "rb") as f:
                out[case].append(_sha(f.read()))
    finally:
        await thumbs.shutdown()
    return out


# taken on the parent commit (44a5960) by this file's
# `_thumbnailer_fingerprints`, on the CPU mesh tests/conftest.py forces
GOLDEN = {
    "jpeg_2016x1512": [[756, 1008, 3], [443, 591], 1,
        "94dfb1a51f6a39b96816652f", "245e44022be25ba7ec27a144"],
    "jpeg_400x300": [[300, 400, 3], [300, 400], 1,
        "d6d7208a759a52ec9d187126", "75e0d0fa87f7a1d170544554"],
    "jpeg_cmyk": [[768, 1024, 3], [443, 591], 1,
        "cfda6589b5caa104f46cbe64", "bd4814ff86f184efa74d96d0"],
    "jpeg_exif6": [[756, 1008, 3], [443, 591], 6,
        "d5446d325abdf63810055443", "e266088c0ebfd92c61775907"],
    "jpeg_grey": [[768, 1024, 3], [443, 591], 1,
        "71ba0c8a4466d8af453b2dd3", "399be017a7801f683021b6a1"],
    # since ISSUE 38 the frame goes whole, through the device's second
    # output canvas: on the parent `[::2, ::2]` made it (150, 2100) and
    # PIL resized that on a host thread ("70ce320a…", "5c9a1476…")
    "png_over_4096": [[300, 4200, 3], [137, 1916], 1,
        "b098d8fefd2705e0083e4476", "fab0f5626cb34abbb03d6a7f"],
    "png_p_transparent": [[375, 500, 4], [375, 500], 1,
        "3a0bb018b8fb55859ccaa231", "07540687b16effb429b1e0b8"],
    "png_rgb": [[375, 500, 3], [375, 500], 1,
        "fdc6779d7e2181feac4c47af", "388a456a78feef2a3a6fcd7f"],
    "png_rgba": [[375, 500, 4], [375, 500], 1,
        "a6bee450a421df0aba7aa31d", "8340bd2cc9c09b6ae5e8d1b6"],
}


@pytest.fixture(scope="module")
def fingerprints(tmp_path_factory):
    offered = []
    got = asyncio.run(_thumbnailer_fingerprints(
        tmp_path_factory.mktemp("golden"),
        sink=lambda cas_id, frame, scale: offered.append(cas_id)))
    assert sorted(offered) == sorted(FILES)
    return got


@pytest.mark.parametrize("case", sorted(FILES))
def test_thumbnailer_array_and_webp_are_the_parents(fingerprints, case):
    assert fingerprints[case] == GOLDEN[case]


# --- the benchmark's reader of the hit share -------------------------------


def _reader():
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "metrics", "embed_shared_decode_share.py")
    spec = importlib.util.spec_from_file_location("_share_reader", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize("counters,want", [
    ({"sd_embed_planes_total{source=shared}": 1152.0}, 100.0),
    ({"sd_embed_planes_total{source=shared}": 27.0,
      "sd_embed_planes_total{source=own}": 9.0}, 75.0),
    ({"sd_embed_planes_total{source=own}": 36.0}, 0.0),
    # the parent has no such counter, a rescan embeds nothing: no line
    ({"sd_embed_decode_total{scale=8}": 128.0}, None),
    ({"sd_embed_planes_total{source=shared}": 0.0}, None),
    ({}, None),
], ids=["all_shared", "mixed", "all_own", "parent", "no_image", "empty"])
def test_embed_shared_decode_share_reader(counters, want):
    assert _reader()({"counters": counters}) == want


def test_embed_shared_decode_share_is_declared_with_its_cells():
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    entry = dict(declared["embed_shared_decode_share"])
    # as PR 30 listed them; a later cell that embeds appends itself
    assert entry.pop("workloads")[:3] == [
        "photolib.cold", "photolib.raw", "homedir.cold"]
    assert entry == {
        "name": "embed_shared_decode_share", "unit": "%",
        "better": "higher", "source": "program_counter",
        "layer": "media host", "moves": "pass_rate"}
