"""`photolib.hires` on the CPU: the generator's plan (classes by the
photo's count modulo 8, the same for every seed), what `kinds/hires.py`
writes and what the program's decode makes of it, the programs it names,
`compare` on a tiny whole run that is correct and on one with the frame
thinned again that is not, every control over its limit, and the five
readers on made-up `ctx`. The fixture is this file's own: photos of 1.1
to 3.5 MP, the same classes and turns, each large enough that the filter
still averages a line pair (a scale under one half)."""

import json
import os

import numpy as np
import pytest

from benchmark import control, harness
from benchmark.generators import iphone15_roll
from benchmark.generators.common import entries_of, write_manifest
from benchmark.kinds import hires as kind
from benchmark.reference import heic as ref
from benchmark.reference import hires as whole
from benchmark.reference import media
from benchmark.tests.conftest import ROOT, cpu_stamp

SEED = 2147484213
BIG = 3800000021  # more than 32 signed bits hold
TARGET = 262144
#: the tiny classes; a side over TINY_THINNED stands for one over 4096
TINY = {"main_24mp": (1440, 1080), "lens_12mp": (1200, 900),
        "main_48mp": (1600, 1200), "panorama": (4000, 886)}
TINY_THINNED = 1250

pytestmark = pytest.mark.skipif(
    not kind.can_write("hevc"),
    reason="this machine's libheif has no HEVC encoder")


def full_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "photolib_hires.json")) as f:
        return json.load(f)


def tiny_config() -> dict:
    """9 files: one photo of every class and turn of the roll, 8 in all,
    at the sizes of TINY, and a small screenshot."""
    config = full_config()
    config["photos"] = 9
    config["photos_per_screenshot"] = 8
    for name, (w, h) in TINY.items():
        config["photo"]["classes"][name] = {"width": w, "height": h}
    config["screenshot"].update(width=234, height=506)
    return config


@pytest.fixture()
def tiny_thinning(monkeypatch):
    monkeypatch.setattr(kind, "THINNED_OVER", TINY_THINNED)


@pytest.fixture()
def hires_root(tmp_path):
    """A checkout's worth of benchmark files that holds this one
    configuration, tiny, and its cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"] = [c for c in doc["configs"]
                      if c["name"] == "photolib_hires"]
    doc["workloads"] = [w for w in doc["workloads"]
                        if w["name"] == "photolib.hires"]
    doc["configs"][0]["file"] = "tiny_photolib_hires.json"
    with open(tmp_path / "tiny_photolib_hires.json", "w") as f:
        json.dump(tiny_config(), f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(doc, f)
    os.symlink(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    return str(tmp_path)


def run(hires_root, tmp_path):
    return harness.run_cell("photolib.hires", SEED, 1.0, False,
                            root=hires_root, require=cpu_stamp,
                            work=str(tmp_path / "work"))


def failing(result) -> set:
    return {k for k, (v, lim) in result["compared"].items() if v > lim}


def written(tmp_path, config=None, seed=SEED, keep=None):
    config = config or tiny_config()
    kinds = harness.Bench(ROOT).kinds(config)
    location = str(tmp_path / "location")
    os.makedirs(location)
    manifest = iphone15_roll.plan(config, seed)
    if keep is not None:
        manifest = [e for e in manifest if keep(e)]
    write_manifest(location, manifest, kinds)
    return config, location, manifest, kinds


# --- the generator's plan ---------------------------------------------------


def test_full_plan_is_the_deployment():
    manifest = iphone15_roll.plan(full_config(), BIG)
    photos = entries_of(manifest, "hires")
    shots = [e for e in manifest if e.get("image")]
    assert (len(manifest), len(photos), len(shots)) == (36, 32, 4)
    assert all(e["rel"].startswith("DCIM/100APPLE/IMG_") for e in manifest)
    assert all(e["rel"].endswith(".HEIC") and not e.get("image")
               for e in photos)
    assert [i for i, e in enumerate(manifest) if e.get("image")] \
        == [7, 15, 23, 31]  # every eighth file, as photolib's
    by_count = [(e["hires"]["w"], e["hires"]["h"], e["hires"]["orientation"])
                for e in photos]
    assert by_count[:8] == [
        (5712, 4284, 1), (5712, 4284, 6), (5712, 4284, 1), (4032, 3024, 1),
        (5712, 4284, 1), (8064, 6048, 3), (4032, 3024, 8), (16382, 3628, 1)]
    assert by_count == by_count[:8] * 4  # by the photo's own count mod 8
    sizes = [s[:2] for s in by_count]
    assert [sizes.count(s) for s in ((5712, 4284), (4032, 3024),
                                     (8064, 6048), (16382, 3628))] \
        == [16, 8, 4, 4]
    assert sum(o != 1 for _w, _h, o in by_count) == 12  # three of eight
    assert sum(w * h * 3 for w, h, _o in by_count) == 2_765_656_032
    assert all(e["hires"]["quality"] == 80 and e["hires"]["preset"]
               == "ultrafast" and e["hires"]["compression"] == "hevc"
               and e["hires"]["model"].startswith("iPhone 1") for e in photos)
    assert 0 < sum(e["hires"]["position"] is not None for e in photos) < 32
    assert all(e["rel"].endswith(".PNG") and (e["image"]["w"], e["image"]["h"])
               == (1170, 2532) for e in shots)
    # the thumbnails the classes are owed
    assert ref.thumbnail_size(5712, 4284, 1, TARGET) == (591, 443)
    assert ref.thumbnail_size(5712, 4284, 6, TARGET) == (443, 591)
    assert ref.thumbnail_size(8064, 6048, 3, TARGET) == (591, 443)
    assert ref.thumbnail_size(16382, 3628, 1, TARGET) == (1088, 241)
    # a roll of this phone starts after it went on sale
    assert min(ref.date_taken(e["hires"]["taken"]) for e in photos) \
        > "2023:09:22"


@pytest.mark.parametrize("config", [full_config(), tiny_config()],
                         ids=["full", "tiny"])
def test_plan_has_the_same_shape_for_every_seed(config):
    a, b = iphone15_roll.plan(config, 1), iphone15_roll.plan(config, BIG)
    assert iphone15_roll.plan(config, BIG) == b
    assert [e["rel"] for e in a] == [e["rel"] for e in b]
    assert [e.get("image") for e in a] == [e.get("image") for e in b]
    shape = ("class", "w", "h", "orientation", "compression", "quality",
             "preset", "make")
    pa, pb = ([e["hires"] for e in entries_of(m, "hires")] for m in (a, b))
    assert [[p[k] for k in shape] for p in pa] \
        == [[p[k] for k in shape] for p in pb]
    assert all(x["content"] != y["content"] for x, y in zip(a, b))
    for photos in (pa, pb):  # a roll runs forward in time
        taken = [p["taken"] for p in photos]
        assert taken == sorted(taken)


def test_the_sample_holds_one_photo_of_every_class_first():
    photos = entries_of(iphone15_roll.plan(full_config(), BIG), "hires")
    for seed in (1, SEED, BIG):
        sample = kind.sample_of(photos, seed)
        assert len(sample) == kind.SAMPLE
        assert {kind._shape(e) for e in sample[:6]} \
            == {kind._shape(e) for e in photos}


# --- what is written, and what the program's decode makes of it -------------


def test_written_photos_are_what_the_plan_says(tmp_path):
    from PIL import Image

    from spacedrive_tpu.object.media import images
    from spacedrive_tpu.object.media.thumbnail import process

    _config, location, manifest, _kinds = written(tmp_path)
    photos = entries_of(manifest, "hires")
    assert len(photos) == 8
    for e in photos:
        photo, path = e["hires"], os.path.join(location, e["rel"])
        assert e["size"] == os.path.getsize(path) > 0
        with open(path, "rb") as f:
            head = f.read(4096)
        assert head[4:12] == b"ftypheic" and b"hvcC" in head
        assert (b"irot" in head) == (photo["orientation"] != 1)
        _size, block = images.heif_container(path)
        exif = Image.Exif()
        exif.load(block)
        assert exif[ref.TAG_MODEL] == photo["model"]
        assert exif[ref.TAG_ORIENTATION] == photo["orientation"]
        sub = exif.get_ifd(ref.TAG_EXIF_IFD)
        assert (sub[ref.TAG_PIXEL_X], sub[ref.TAG_PIXEL_Y]) \
            == TINY[photo["class"]]
        # the program's own decode hands on the displayed picture, whole,
        # with the band's lines as they were drawn
        d = process.decode(path, "HEIC")
        want = ref.displayed(kind.picture(e), photo["orientation"])
        assert d.array.shape == want.shape
        assert d.target == ref.thumbnail_size(
            photo["w"], photo["h"], photo["orientation"], TARGET)[::-1]
        assert np.abs(d.array.astype(np.int16) - want).mean() < 3
        kind.require_whole_frames(d, photo, path)
    # the band: dark even columns in its left half, dark even rows in
    # its right half, the mean between them
    e = photos[0]
    rgb, (w, h) = kind.picture(e), TINY["main_24mp"]
    r0, r1, c0, c1 = whole.band_box(w, h)
    assert (r0 % 2, c0 % 2) == (0, 0)
    assert (rgb[r0:r1, c0:c0 + 200:2] == whole.BAND_DARK).all()
    assert (rgb[r0:r1, c0 + 1:c0 + 200:2] == whole.BAND_LIGHT).all()
    assert (rgb[r0:r1:2, c1 - 200:c1] == whole.BAND_DARK).all()
    assert (rgb[r0 + 1:r1:2, c1 - 200:c1] == whole.BAND_LIGHT).all()
    assert whole.BAND_MEAN == 128
    field = np.asarray(harness.load_module(os.path.join(
        ROOT, "benchmark", "generators", "common.py")).image_pixels(
        e["content"], w, h, False))
    assert np.array_equal(rgb[:r0], field[:r0])  # photolib's pixels elsewhere


def test_blocked_downscale_is_the_plain_filter():
    from benchmark.reference.video import downscale

    rgb = np.random.default_rng(38).integers(0, 256, (700, 523, 3),
                                             dtype=np.uint8)
    assert np.array_equal(whole.downscale(rgb, 131, 175, block=64),
                          downscale(rgb, 131, 175))
    assert np.array_equal(whole.downscale(rgb, 131, 175, block=4096),
                          downscale(rgb, 131, 175))


@pytest.mark.parametrize("orientation", [1, 3, 6, 8])
def test_band_in_thumbnail_follows_the_turn(orientation):
    w, h = 1440, 1080
    rgb = whole.draw_band(np.full((h, w, 3), 7, np.uint8))
    pixels = whole.thumbnail_pixels(rgb, orientation, TARGET)
    rows, cols = whole.band_in_thumbnail(w, h, orientation, TARGET)
    assert rows.stop - rows.start > 20 and cols.stop - cols.start > 20
    # inside: the band's mean (to the ripple a filter 4.9 pixels wide
    # leaves of a 2-pixel period); the picture's 7 nowhere
    inside = np.abs(pixels[rows, cols].astype(int) - whole.BAND_MEAN)
    assert inside.mean() < 4 and inside.max() < 40
    outside = np.ones(pixels.shape[:2], bool)
    grown = (slice(rows.start - 2 * whole.BAND_MARGIN,
                   rows.stop + 2 * whole.BAND_MARGIN),
             slice(cols.start - 2 * whole.BAND_MARGIN,
                   cols.stop + 2 * whole.BAND_MARGIN))
    outside[grown] = False
    assert (pixels[outside] == 7).all()
    # thinned by two before the filter: one of the two greys, not the mean
    thin = whole.thumbnail_pixels(rgb, orientation, TARGET, stride=2)
    assert thin.shape == pixels.shape
    assert whole.detail_gap(thin, pixels, (rows, cols)) > 70


# --- programs ---------------------------------------------------------------


def test_programs_are_named_from_the_programs_own_tables(tmp_path):
    from spacedrive_tpu.ops import thumbnail_jax as tj

    _config, location, manifest, kinds = written(tmp_path)
    photos = entries_of(manifest, "hires")
    own = kinds["hires"].programs(photos, location, 1)
    names = [name for _w, name, _fn in own]
    std = "x".join(map(str, tj.OUT_CANVAS_HW))
    wide = "x".join(map(str, tj.OUT_CANVAS_WIDE_HW))
    # 24 MP (4) and 48 MP (1) share (2048, 2048); 12 MP (2) fits the
    # half canvas; the panorama alone takes the second output canvas
    assert [n for n in names if "resize" in n] == [
        f"hires_resize_1024x2048x3_out{std}_pad1",
        f"hires_resize_1024x2048x3_out{std}_pad2",
        f"hires_resize_2048x2048x3_out{std}_pad1",
        f"hires_resize_2048x2048x3_out{std}_pad2",
        f"hires_resize_2048x2048x3_out{std}_pad4",
        f"hires_resize_2048x2048x3_out{std}_pad8",
        f"hires_resize_2048x4096x3_out{wide}_pad1"]
    assert names[-1].startswith("hires_embed_pad")
    own[6][2]()  # the panorama's program runs to its end


def test_full_size_programs_follow_the_byte_bounds():
    """At the configuration's own sizes, from the program's tables alone
    (nothing is decoded or run): the canvas of every class, and how many
    photos of one canvas a call can hold."""
    from spacedrive_tpu.object.media.thumbnail import process
    from spacedrive_tpu.ops import thumbnail_jax as tj

    assert process.CHUNK_FRAME_BYTES == tj.CALL_CANVAS_BYTES == 3 << 29
    want = {(5712, 4284): ((4608, 6144), tj.OUT_CANVAS_HW, 16),
            (4032, 3024): ((4096, 4096), tj.OUT_CANVAS_HW, 32),
            (8064, 6048): ((6144, 8192), tj.OUT_CANVAS_HW, 8),
            (16382, 3628): ((4096, 16384), tj.OUT_CANVAS_WIDE_HW, 8)}
    for (w, h), (bucket, out, rows) in want.items():
        assert tj.bucket_for(h, w) == tj.bucket_for(w, h) == bucket
        tw, th = ref.thumbnail_size(w, h, 1, TARGET)
        assert tj.out_canvas_for(th, tw) == out
        assert tj.call_rows(*bucket, 3) == rows
        # a chunk's frames of this class: more than a call's canvases
        assert process.CHUNK_FRAME_BYTES // (w * h * 3) >= 8


@pytest.mark.parametrize("break_it,match", [
    ("thin", "thins the frame on the host"),
    ("host", "on the host, not on the device")])
def test_a_program_that_does_not_take_the_frame_whole_ends_set_up(
        tmp_path, monkeypatch, break_it, match):
    """The program before ISSUE 38: a frame over 4096 a side thinned by
    a stride, a panorama's target resized by PIL on a host thread."""
    from spacedrive_tpu.object.media.thumbnail import process

    _config, location, manifest, kinds = written(
        tmp_path, keep=lambda e: e.get("hires", {}).get("class")
        == "panorama")
    if break_it == "thin":
        real = process.decode

        def thinned(path, ext, tap=None):
            d = real(path, ext, tap)
            d.array = np.ascontiguousarray(d.array[::4, ::4])
            return d

        monkeypatch.setattr(process, "decode", thinned)
    else:
        monkeypatch.setattr(process, "needs_cpu_fallback", lambda d: True)
    with pytest.raises(SystemExit, match=match):
        kinds["hires"].programs(entries_of(manifest, "hires"), location, 1)


# --- the whole run and the controls -----------------------------------------


def test_sound_run_is_correct(hires_root, tmp_path):
    r = run(hires_root, tmp_path)
    assert r["correct"] is True, failing(r)
    assert r["failed"] == 0 and r["attempted"] >= 9
    assert set(r["metrics"]) == {"pass_rate", "setup_s"}
    own = {k for k in r["compared"] if k.startswith("hires_")}
    assert own == {
        "hires_thumbnail_missing", "hires_thumbnail_wrong_size",
        "hires_kind_wrong", "hires_media_data_missing", "hires_facts_wrong",
        "hires_embedding_missing", "hires_pixel_gap", "hires_detail_gap",
        "hires_embedding_gap"}
    gaps = {"hires_pixel_gap", "hires_detail_gap", "hires_embedding_gap"}
    assert all(r["compared"][k][0] == 0 for k in own - gaps)
    assert 0 < r["compared"]["hires_pixel_gap"][0] < kind.PIXEL_GAP_LIMIT
    assert 0 < r["compared"]["hires_detail_gap"][0] < kind.DETAIL_GAP_LIMIT
    assert 0 < r["compared"]["hires_embedding_gap"][0] < kind.EMBED_GAP_LIMIT
    assert r["compared"]["device_fallbacks"] == [0, 0]


def test_a_frame_thinned_on_the_host_is_not_correct(hires_root, tmp_path,
                                                    monkeypatch):
    """The program before ISSUE 38, put back: every second row and
    column of a frame over the line, then the filter. The kind's look in
    set-up is switched off so that the passes run and `compare` sees
    what they stored."""
    from spacedrive_tpu.object.media.thumbnail import process

    def thin(arr):
        step = -(-max(arr.shape[:2]) // TINY_THINNED)
        return np.ascontiguousarray(arr[::step, ::step])

    monkeypatch.setattr(process, "shrink_to_max_dim", thin)
    monkeypatch.setattr(kind, "require_whole_frames", lambda *a: None)
    monkeypatch.setattr(
        harness.Bench, "kinds", lambda self, config: {"hires": kind})
    r = run(hires_root, tmp_path)
    assert r["correct"] is False
    assert "hires_detail_gap" in failing(r)
    assert 40 < r["compared"]["hires_detail_gap"][0] < 255
    # at this size the thinned panorama (1000 x 222) is under the target
    # and keeps its size, so it alone is the wrong size too (and reads
    # 255 as a picture); the configuration's (4096 x 907) is not
    assert failing(r) <= {"hires_detail_gap", "hires_thumbnail_wrong_size",
                          "hires_pixel_gap"}
    assert r["compared"]["hires_thumbnail_wrong_size"][0] \
        == len(r["pass_cycle_s"])


@pytest.mark.parametrize("seed", [SEED, BIG])
def test_controls_fail(tmp_path, tiny_thinning, seed):
    bench = harness.Bench(ROOT)
    config = tiny_config()
    r = control.readings(config, bench.generator(config), seed, str(tmp_path),
                         {"hires": kind})
    fails = control.not_correct(r)
    for name in ("hires_detail_gap_thinned", "hires_pixel_gap_mirrored",
                 "hires_pixel_gap_not_turned", "hires_embedding_gap_fp8",
                 "hires_thumbnail_wrong_size", "embedding_gap"):
        assert fails[name], r
    assert r["hires_detail_gap_thinned"][0] > 60
    # webp alone stays inside the limits, or sound runs could not
    assert r["hires_pixel_gap_webp_alone"] < kind.PIXEL_GAP_LIMIT / 2
    assert r["hires_detail_gap_webp_alone"] < kind.DETAIL_GAP_LIMIT / 2


# --- the five readers -------------------------------------------------------


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(ROOT)


def _passes(n, generated=36):
    return [{"summary": {"thumbnailer_generated": generated}}] * n


FRAMES = {"sd_thumbnail_frames_total{path=whole}": 180.0}
PARENT = {"sd_thumbnail_pack_bytes_total": 5.0e9,
          "sd_thumbnail_staging_total{result=mapped}": 12.0}


@pytest.mark.parametrize("name,counters,want", [
    ("thumb_thinned_share", FRAMES, 0.0),
    ("thumb_thinned_share",
     {**FRAMES, "sd_thumbnail_frames_total{path=thinned}": 20.0}, 10.0),
    ("thumb_thinned_share", PARENT, None),
    ("thumb_host_resize_share", FRAMES, 0.0),
    ("thumb_host_resize_share",
     {**FRAMES, "sd_thumbnail_host_resize_total{reason=aspect}": 16.0,
      "sd_thumbnail_host_resize_total{reason=device_failed}": 2.0}, 10.0),
    ("thumb_host_resize_share", PARENT, None),
    ("thumb_calls_per_pass",
     {"sd_thumbnail_device_calls_total{bucket=4608x6144,out=512x1024}": 10.0,
      "sd_thumbnail_device_calls_total{bucket=4096x16384,out=256x2048}": 5.0},
     3.0),
    ("thumb_calls_per_pass", PARENT, None),
    ("thumb_canvas_fill_share",
     {**PARENT, "sd_thumbnail_canvas_bytes_total{bucket=4608x6144}": 6.0e9,
      "sd_thumbnail_canvas_bytes_total{bucket=4096x4096}": 4.0e9}, 50.0),
    ("thumb_canvas_fill_share", PARENT, None),
], ids=["whole", "a_tenth_thinned", "thinned_parent", "on_the_chip",
        "a_tenth_on_the_host", "host_parent", "calls", "calls_parent",
        "fill", "fill_parent"])
def test_counter_readers(bench, name, counters, want):
    got = bench.reader(name)({"counters": counters, "passes": _passes(5)})
    assert got == (None if want is None else pytest.approx(want))


def test_resize_roofline_counts_the_bytes_from_the_configuration(bench):
    reader = harness.load_module(bench.find("metrics", "resize_roofline.py"))
    config = full_config()
    frames = 2_765_656_032 + 4 * 1170 * 2532 * 3
    thumbs = 3 * (28 * 591 * 443 + 4 * 1088 * 241 + 4 * 348 * 753)
    assert reader.needed_bytes(config) == frames + thumbs
    ctx = {"config": config, "passes": _passes(5),
           "peaks": {"hbm_bytes_per_s": 819e9},
           "trace": {"kernels": {"resize": {"seconds": 0.5,
                                            "dispatches": 40}}}}
    share = reader.read(ctx)
    assert share == pytest.approx(
        100 * 5 * (frames + thumbs) / 819e9 / 0.5)
    assert 0 < share < 100
    # nothing to read: no trace, no resize program in it, no peaks
    assert reader.read({**ctx, "trace": None}) is None
    assert reader.read({**ctx, "trace": {"kernels": {}}}) is None
    assert reader.read({**ctx, "peaks": None}) is None
    assert reader.read({**ctx, "config": {"files": 2000}}) is None


def test_declared_with_the_cell_and_found_by_name(bench):
    declared = {m["name"]: m for m in bench.doc["per_layer"]}
    mine = ["thumb_thinned_share", "thumb_host_resize_share",
            "thumb_calls_per_pass", "thumb_canvas_fill_share",
            "resize_roofline"]
    assert [m["name"] for m in bench.doc["per_layer"]][-5:] == mine
    for name in mine:
        assert declared[name]["workloads"] == ["photolib.hires"]
        assert declared[name]["moves"] == "pass_rate"
        assert os.path.isfile(bench.find("metrics", name + ".py"))
    assert declared["resize_roofline"]["source"] == "device_trace"
    assert declared["resize_roofline"]["layer"] == "kernels"
    # the cell reports every metric `photolib.heic` reports, and its own;
    # but for `node_start_stop_s`, whose reader finds nothing to read in
    # any cell since PR 36 (PERF.md §7): a list names the cells in which
    # a reader finds something
    heic = {m["name"] for m in bench.metrics_for("photolib.heic", "per_layer")}
    hires = {m["name"] for m in bench.metrics_for("photolib.hires",
                                                  "per_layer")}
    assert hires == (heic - {"node_start_stop_s"}) | set(mine)
    for m in bench.doc["per_layer"]:
        if "photolib.hires" in m["workloads"] and m["name"] not in mine:
            assert m["workloads"][-1] == "photolib.hires"  # appended
    assert bench.doc["workloads"][-1]["name"] == "photolib.hires"
    assert bench.doc["workloads"][-1]["chips"] == 1
    assert bench.doc["configs"][-1]["reduced"] == ["photos"]
    assert len(bench.doc["configs"][-1]["source"]) <= 200


def test_the_lists_pr36_pinned_are_as_they_were_with_this_cell_appended(bench):
    """Holds what `test_index_path_readers.py::
    test_declared_with_its_cells_and_found_by_name` held for the four of
    PR 36's thirteen that every media cell reports (it pins their
    `workloads` whole, so tier-1 leaves those four cases out since this
    cell): each entry as PR 36 declared it, `photolib.hires` after it."""
    from benchmark.tests.test_index_path_readers import DECLARED

    declared = {m["name"]: m for m in bench.doc["per_layer"]}
    grown = []
    for name, (unit, better, source, layer, cells) in DECLARED.items():
        with_mine = cells + ["photolib.hires"] if "photolib.heic" in cells \
            else cells
        assert declared[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "pass_rate", "workloads": with_mine}
        if with_mine != cells:
            grown.append(name)
    assert grown == ["db_commit_us_per_file", "db_changes_per_file",
                     "db_reads_per_file", "db_read_us_per_file"]
