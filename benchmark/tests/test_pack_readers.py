"""`thumb_pack_bytes_per_image` and `thumb_staging_kept_share` (PR 33) on
a hand-made `ctx`: the value each computes from its counter, None (never
an error) on a program without it, as PR 33's parent is, and how each is
declared."""

import os

import pytest

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = {"photolib.cold", "photolib.raw", "homedir.cold", "photolib.video"}
PASSES = [{"files": 108, "summary": {"thumbnailer_generated": 108}},
          {"files": 108, "summary": {"thumbnailer_generated": 108}}]
#: what the parent's thumbnailer counts: the families PR 28 brought
PARENT = {"sd_thumbnail_device_seconds{part=pack}": 10.6,
          "sd_thumbnail_device_bytes_total{dir=h2d}": 4.2e9,
          "sd_thumbnail_resize_images_total{alpha=0}": 24.0,
          "sd_thumbnail_resize_images_total{alpha=1}": 192.0}


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(ROOT)


def ctx_with(counters, passes=PASSES):
    return {"passes": passes, "counters": counters}


@pytest.mark.parametrize("counters,want", [
    ({"sd_thumbnail_pack_bytes_total": 216 * 6_000_000.0}, 6_000_000.0),
    ({**PARENT, "sd_thumbnail_pack_bytes_total": 432.0}, 2.0),
], ids=["clips", "beside_the_parents_families"])
def test_pack_bytes_per_thumbnail(bench, counters, want):
    read = bench.reader("thumb_pack_bytes_per_image")
    assert read(ctx_with(counters)) == pytest.approx(want)


@pytest.mark.parametrize("counters,want", [
    ({"sd_thumbnail_staging_total{result=kept}": 64.0}, 100.0),
    ({"sd_thumbnail_staging_total{result=kept}": 57.0,
      "sd_thumbnail_staging_total{result=mapped}": 3.0}, 95.0),
    ({**PARENT, "sd_thumbnail_staging_total{result=mapped}": 8.0}, 0.0),
], ids=["all_kept", "some_mapped", "none_kept"])
def test_share_of_calls_that_found_their_canvas(bench, counters, want):
    read = bench.reader("thumb_staging_kept_share")
    assert read(ctx_with(counters)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["thumb_pack_bytes_per_image",
                                  "thumb_staging_kept_share"])
@pytest.mark.parametrize("counters,passes", [
    ({}, PASSES), (PARENT, PASSES),
    ({"sd_thumbnail_pack_bytes_total": 0.0,
      "sd_thumbnail_staging_total{result=kept}": 0.0}, PASSES),
    ({"sd_thumbnail_pack_bytes_total": 0.0}, []),
], ids=["empty", "parent", "no_call_in_window", "no_pass"])
def test_nothing_to_read_gives_none(bench, name, counters, passes):
    assert bench.reader(name)(ctx_with(counters, passes)) is None


@pytest.mark.parametrize("name,unit,better", [
    ("thumb_pack_bytes_per_image", "bytes/image", "lower"),
    ("thumb_staging_kept_share", "%", "higher")])
def test_declared_with_its_cells(bench, name, unit, better):
    """Found by name, not by place: in the cells `thumb_pack_ms_per_image`
    lists at the least, as the stage they read is the one it times."""
    declared = {m["name"]: m for m in bench.doc["per_layer"]}
    m = dict(declared[name])
    assert CELLS <= set(m.pop("workloads"))
    assert CELLS <= set(declared["thumb_pack_ms_per_image"]["workloads"])
    assert m == {"name": name, "unit": unit, "better": better,
                 "source": "program_counter", "layer": "media host",
                 "moves": "pass_rate"}
    for cell in CELLS:
        assert name in [x["name"] for x in
                        bench.metrics_for(cell, "per_layer")]
