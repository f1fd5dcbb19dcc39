"""`embed_scaled_decode_share` on a hand-made `ctx`: the share it
computes from `sd_embed_decode_total`, and None (never an error) on a
program without that counter, as PR 26's parent is."""

import os

import pytest

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "embed_scaled_decode_share"

PHOTOLIB = {"sd_embed_decode_total{scale=8}": 128.0,
            "sd_embed_decode_total{scale=1}": 16.0}
HOMEDIR = {"sd_embed_decode_total{scale=8}": 35.0,
           "sd_embed_decode_total{scale=2}": 14.0,
           "sd_embed_decode_total{scale=1}": 91.0}
PARENT = {"sd_embed_stage_seconds{stage=decode}.sum": 6.0,
          "sd_embed_files_total{result=embedded}": 20.0}


@pytest.fixture(scope="module")
def read():
    return harness.Bench(ROOT).reader(NAME)


@pytest.mark.parametrize("counters,want", [
    (PHOTOLIB, 100.0 * 32 / 36),
    (HOMEDIR, 35.0),
    ({"sd_embed_decode_total{scale=1}": 4.0}, 0.0),
    ({**PARENT, "sd_embed_decode_total{scale=4}": 3.0}, 100.0),
], ids=["photolib", "homedir", "none_scaled", "all_scaled"])
def test_share_of_images_decoded_at_a_scale_above_one(read, counters, want):
    assert read({"counters": counters}) == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    {}, PARENT, {"sd_embed_decode_total{scale=8}": 0.0},
], ids=["empty", "parent", "no_image_in_window"])
def test_nothing_to_read_gives_none(read, counters):
    assert read({"counters": counters}) is None


def test_declared_with_its_cells():
    """Found by name, not by place, and in the cells PR 26 read it in at
    the least: a later PR appends after it and lists more cells."""
    declared = {m["name"]: m for m in harness.Bench(ROOT).doc["per_layer"]}
    m = dict(declared[NAME])
    assert {"photolib.cold", "homedir.cold"} <= set(m.pop("workloads"))
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "media host",
                 "moves": "pass_rate"}
