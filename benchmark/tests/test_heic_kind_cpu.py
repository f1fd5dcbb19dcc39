"""`photolib.heic` on the CPU: the generator's plan (the same shape for
every seed), what `kinds/heic.py` writes (HEVC in HEIF, the container's
`irot`, an EXIF block), the programs it names, a tiny whole run through
the harness that is correct, runs with the guarantee broken that are
not, and every control incorrect. The fixture is this file's own, as
`test_video_kind_cpu.py`'s is: photos of 640 x 480, turned as the full
configuration turns them."""

import json
import os

import numpy as np
import pytest

from benchmark import control, harness
from benchmark.generators import iphone_roll
from benchmark.generators.common import entries_of, write_manifest
from benchmark.kinds import heic as kind
from benchmark.reference import heic as ref
from benchmark.tests.conftest import ROOT, cpu_stamp

SEED = 2147483999
BIG = 3000000019  # more than 32 signed bits hold

pytestmark = pytest.mark.skipif(
    not kind.can_write("hevc"),
    reason="this machine's libheif has no HEVC encoder")


def full_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "photolib_heic.json")) as f:
        return json.load(f)


def tiny_config() -> dict:
    """The configuration at a size a test run can hold: 8 files, 6 of
    them HEICs of 640 x 480 (orientations 1, 6, 3, 8 among them) and 2
    small screenshots."""
    config = full_config()
    config["photos"] = 8
    config["photos_per_screenshot"] = 3
    config["photo"].update(width=640, height=480,
                           exif_orientations=[1, 6, 3, 8])
    config["screenshot"].update(width=234, height=506)
    return config


@pytest.fixture()
def heic_root(tmp_path):
    """A checkout's worth of benchmark files that holds this one
    configuration, tiny, and its cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"] = [c for c in doc["configs"]
                      if c["name"] == "photolib_heic"]
    doc["workloads"] = [w for w in doc["workloads"]
                        if w["name"] == "photolib.heic"]
    doc["configs"][0]["file"] = "tiny_photolib_heic.json"
    with open(tmp_path / "tiny_photolib_heic.json", "w") as f:
        json.dump(tiny_config(), f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(doc, f)
    os.symlink(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    return str(tmp_path)


def run(heic_root, tmp_path):
    return harness.run_cell("photolib.heic", SEED, 1.0, False,
                            root=heic_root, require=cpu_stamp,
                            work=str(tmp_path / "work"))


def failing(result) -> set:
    return {k for k, (v, lim) in result["compared"].items() if v > lim}


def written(tmp_path, config=None, seed=SEED):
    config = config or tiny_config()
    kinds = harness.Bench(ROOT).kinds(config)
    location = str(tmp_path / "location")
    os.makedirs(location)
    manifest = iphone_roll.plan(config, seed)
    write_manifest(location, manifest, kinds)
    return config, location, manifest, kinds


# --- the generator's plan ---------------------------------------------------


@pytest.mark.parametrize("config", [full_config(), tiny_config()],
                         ids=["full", "tiny"])
def test_plan_has_the_same_shape_for_every_seed(config):
    a, b = iphone_roll.plan(config, 1), iphone_roll.plan(config, BIG)
    assert iphone_roll.plan(config, BIG) == b
    assert [e["rel"] for e in a] == [e["rel"] for e in b]
    assert [e.get("image") for e in a] == [e.get("image") for e in b]
    shape = ("w", "h", "orientation", "compression", "quality", "preset",
             "make")
    pa, pb = ([e["heic"] for e in entries_of(m, "heic")] for m in (a, b))
    assert [[p[k] for k in shape] for p in pa] \
        == [[p[k] for k in shape] for p in pb]
    assert [p["position"] is None for p in pa] \
        == [p["position"] is None for p in pb]
    assert all(x["content"] != y["content"] for x, y in zip(a, b))
    assert [p["taken"] for p in pa] != [p["taken"] for p in pb]
    for photos in (pa, pb):  # a roll runs forward in time
        taken = [p["taken"] for p in photos]
        assert taken == sorted(taken)


def test_full_plan_is_the_deployment():
    manifest = iphone_roll.plan(full_config(), BIG)
    photos = entries_of(manifest, "heic")
    shots = [e for e in manifest if e.get("image")]
    assert (len(manifest), len(photos), len(shots)) == (36, 32, 4)
    assert all(e["rel"].startswith("DCIM/100APPLE/IMG_") for e in manifest)
    assert all(e["rel"].endswith(".HEIC") and not e.get("image")
               for e in photos)
    assert all((e["heic"]["w"], e["heic"]["h"]) == (4032, 3024)
               and e["heic"]["quality"] == 80
               and e["heic"]["compression"] == "hevc" for e in photos)
    turned = [e["heic"]["orientation"] for e in photos]
    assert sorted(set(turned)) == [1, 3, 6, 8]
    assert sum(o != 1 for o in turned) == 12  # three of eight
    assert 0 < sum(e["heic"]["position"] is not None for e in photos) < 32
    assert all(e["rel"].endswith(".PNG") and (e["image"]["w"], e["image"]["h"])
               == (1170, 2532) for e in shots)
    # the thumbnail a 12 MP photo is owed
    assert ref.thumbnail_size(4032, 3024, 1, 262144) == (591, 443)
    assert ref.thumbnail_size(4032, 3024, 6, 262144) == (443, 591)


# --- what is written --------------------------------------------------------


def _boxes(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read(4096)


def test_written_photos_are_what_the_plan_says(tmp_path):
    from PIL import Image

    from spacedrive_tpu.object.media import images

    _config, location, manifest, _kinds = written(tmp_path)
    for e in entries_of(manifest, "heic"):
        photo, path = e["heic"], os.path.join(location, e["rel"])
        assert e["size"] == os.path.getsize(path) > 0
        head = _boxes(path)
        assert head[4:12] == b"ftypheic" and b"hvcC" in head
        # the turn is the container's: an irot box where the photo is
        # turned, none where it is not
        assert (b"irot" in head) == (photo["orientation"] != 1)
        _size, block = images.heif_container(path)
        exif = Image.Exif()
        exif.load(block)
        assert exif[ref.TAG_MAKE] == "Apple"
        assert exif[ref.TAG_ORIENTATION] == photo["orientation"]
        sub = exif.get_ifd(ref.TAG_EXIF_IFD)
        assert sub[ref.TAG_DATE_ORIGINAL] == ref.date_taken(photo["taken"])
        assert (sub[ref.TAG_PIXEL_X], sub[ref.TAG_PIXEL_Y]) == (640, 480)
        assert bool(exif.get_ifd(ref.TAG_GPS_IFD)) \
            == (photo["position"] is not None)
        # the one decoder of the machine hands on the displayed picture
        shown = images.decode_heif(path)
        want = ref.displayed(kind.picture(e), photo["orientation"])
        assert shown.shape == (*want.shape[:2], 4)
        assert np.abs(shown[..., :3].astype(np.int16) - want).mean() < 3


def test_same_seed_same_bytes(tmp_path):
    config = tiny_config()
    for d in ("a", "b"):
        os.makedirs(tmp_path / d)
        write_manifest(str(tmp_path / d), iphone_roll.plan(config, 5)[:3],
                       harness.Bench(ROOT).kinds(config))
    for e in iphone_roll.plan(config, 5)[:3]:
        with open(tmp_path / "a" / e["rel"], "rb") as f, \
                open(tmp_path / "b" / e["rel"], "rb") as g:
            assert f.read() == g.read()


def test_a_codec_libheif_cannot_encode_ends_set_up(tmp_path, monkeypatch):
    config = tiny_config()
    monkeypatch.setattr(kind, "can_write", lambda compression="hevc": False)
    os.makedirs(tmp_path / "location")
    with pytest.raises(SystemExit, match="no hevc encoder"):
        kind.write(str(tmp_path / "location" / "x.HEIC"),
                   iphone_roll.plan(config, 5)[0])


# --- programs ---------------------------------------------------------------


def test_programs_are_named_from_the_programs_own_tables(tmp_path):
    """Bucket and channels are those of the frame the program's own
    decode hands on, pads and caps the autotuner's: a later decode that
    hands on RGB renames the programs without an edit here."""
    from spacedrive_tpu.object.media.thumbnail import process
    from spacedrive_tpu.ops import thumbnail_jax as tj
    from spacedrive_tpu.parallel import autotune

    from benchmark import warm

    _config, location, manifest, kinds = written(tmp_path)
    photos = entries_of(manifest, "heic")
    own = kinds["heic"].programs(photos, location, 1)
    frame = process.decode(os.path.join(location, photos[0]["rel"]),
                           "HEIC").array
    bh, bw = tj.bucket_for(*frame.shape[:2])
    cap = int(autotune.SCALE_MAX)
    names = [name for _w, name, _fn in own]
    assert names == [
        f"heic_resize_{bh}x{bw}x{frame.shape[2]}_pad{pad}"
        for pad in warm._pow2_pads(
            len(photos), autotune.THUMB_DEVICE_BATCH * cap)] + [
        f"heic_embed_pad{pad}" for pad in warm._pow2_pads(
            len(manifest), autotune.EMBED_DEVICE_BATCH * cap)]
    for _w, _name, fn in (own[0], own[-1]):
        fn()  # runs one to its end
    # the full-size frame, either way up, reaches the canvas no other
    # cell dispatches
    assert tj.bucket_for(3024, 4032) == tj.bucket_for(4032, 3024) \
        == (4096, 4096)


def test_a_program_that_reads_no_container_ends_set_up(tmp_path, monkeypatch):
    """The program before ISSUE 34 gives a HEIC no `media_data` row: the
    kind's probe ends the run in set-up, by itself and soon, before a
    program is warmed."""
    from spacedrive_tpu.object.media import media_data

    _config, location, manifest, kinds = written(tmp_path)
    photos = entries_of(manifest, "heic")
    kinds["heic"].require_media_data(os.path.join(location, photos[0]["rel"]))
    monkeypatch.setattr(media_data.ImageMetadata, "from_path",
                        classmethod(lambda cls, path: None))
    with pytest.raises(SystemExit, match="no media_data row"):
        kinds["heic"].programs(photos, location, 1)


# --- the whole run and the controls -----------------------------------------


def test_sound_run_is_correct(heic_root, tmp_path):
    r = run(heic_root, tmp_path)
    assert r["correct"] is True, failing(r)
    assert r["failed"] == 0 and r["attempted"] >= 8
    assert set(r["metrics"]) == {"pass_rate", "setup_s"}
    own = {k for k in r["compared"] if k.startswith("heic_")}
    assert own == {
        "heic_thumbnail_missing", "heic_thumbnail_wrong_size",
        "heic_kind_wrong", "heic_media_data_missing", "heic_facts_wrong",
        "heic_embedding_missing", "heic_pixel_gap", "heic_embedding_gap"}
    gaps = {"heic_pixel_gap", "heic_embedding_gap"}
    assert all(r["compared"][k][0] == 0 for k in own - gaps)
    assert 0 < r["compared"]["heic_pixel_gap"][0] < kind.PIXEL_GAP_LIMIT
    assert 0 < r["compared"]["heic_embedding_gap"][0] < kind.EMBED_GAP_LIMIT


def test_a_turn_applied_twice_is_not_correct(heic_root, tmp_path, monkeypatch):
    """The program turns the frame by the EXIF tag after libheif has
    turned it by the container's `irot`."""
    from spacedrive_tpu.object.media import images
    from spacedrive_tpu.object.media.thumbnail import process

    real = process.decode_heif_image

    def twice(path, extension, tap=None):
        d = real(path, extension, tap)
        _size, block = images.heif_container(path)
        from PIL import Image

        exif = Image.Exif()
        exif.load(block)
        d.orientation = int(exif.get(ref.TAG_ORIENTATION, 1))
        return d

    monkeypatch.setattr(process, "decode_heif_image", twice)
    r = run(heic_root, tmp_path)
    assert r["correct"] is False
    # a half turn twice is the picture upside down at the right size; a
    # quarter turn twice is the wrong size
    assert failing(r) == {"heic_pixel_gap", "heic_thumbnail_wrong_size"}


def test_no_media_data_is_not_correct(heic_root, tmp_path, monkeypatch):
    """The program before ISSUE 34: no HEIF extension among those the
    media job extracts from."""
    from spacedrive_tpu.object.media import job

    plain = tuple(e for e in job.MEDIA_DATA_EXTENSIONS
                  if e not in job.HEIF_EXTENSIONS)
    monkeypatch.setattr(job, "MEDIA_DATA_EXTENSIONS", plain)
    r = run(heic_root, tmp_path)
    assert r["correct"] is False
    assert failing(r) == {"heic_media_data_missing"} and r["failed"] > 0


def test_wrong_facts_are_not_correct(heic_root, tmp_path, monkeypatch):
    """A reader that takes the EXIF tag for upright whatever it says."""
    from spacedrive_tpu.object.media import media_data

    real = media_data.ImageMetadata._read_exif

    def upright(self, exif):
        real(self, exif)
        self.camera_data.orientation = 1

    monkeypatch.setattr(media_data.ImageMetadata, "_read_exif", upright)
    r = run(heic_root, tmp_path)
    assert r["correct"] is False and failing(r) == {"heic_facts_wrong"}


@pytest.mark.parametrize("seed", [5, SEED, BIG])
def test_controls_fail(tmp_path, seed):
    bench = harness.Bench(ROOT)
    config = tiny_config()
    r = control.readings(config, bench.generator(config), seed, str(tmp_path),
                         bench.kinds(config))
    fails = control.not_correct(r)
    # `thumbnail_pixel_gap`'s own control leaves out an EXIF orientation
    # the location's screenshots do not carry: the turn it would break is
    # the HEICs', held by the kind's controls
    for name in ("heic_pixel_gap_not_turned", "heic_pixel_gap_mirrored",
                 "heic_embedding_gap_fp8", "heic_thumbnail_wrong_size",
                 "embedding_gap"):
        assert fails[name], r
    # webp alone stays inside the limit, or sound runs could not
    assert r["heic_pixel_gap_webp_alone"] < kind.PIXEL_GAP_LIMIT
