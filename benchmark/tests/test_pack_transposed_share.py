"""`thumb_pack_transposed_share`: of the frames `pack` wrote without one
copy of their rows, the share that went in through the blocked transpose
(`sd_thumbnail_pack_frames_total{copy}`). Its reader on made-up `ctx`,
and its entry: last in `per_layer`, on the four cells whose roll holds a
portrait turned into its canvas. The two cases of
`test_labeler_decode_work.py` that pin the end of `per_layer` to that
file's entry (tier-1 leaves them out since this one) run here as they
stand on the declaration with this entry taken off the end, after it is
shown to be the only change."""

import copy
import json
import os

import pytest

from benchmark import harness
from benchmark.tests import test_labeler_decode_work as decode_work
from benchmark.tests.conftest import ROOT

NAME = "thumb_pack_transposed_share"
CELLS = ["photolib.cold", "photolib.heic", "photolib.hires",
         "photolib.labeled"]
FAMILY = "sd_thumbnail_pack_frames_total"
#: what a program before the counter counts of `pack`
PARENT = {"sd_thumbnail_device_seconds{part=pack}": 15.9,
          "sd_thumbnail_pack_bytes_total": 4.7e9,
          "sd_thumbnail_staging_total{result=kept}": 28.0}


def frames(**by_copy) -> dict:
    return {f"{FAMILY}{{copy={c}}}": float(n) for c, n in by_copy.items()}


@pytest.mark.parametrize("counters,want", [
    (frames(rows=280, transposed=80, planes=0), 100.0),
    (frames(rows=280, transposed=0, planes=80), 0.0),
    ({**PARENT, **frames(rows=24, transposed=8)}, 100.0),
    (frames(transposed=3, planes=1), 75.0),
], ids=["all_transposed", "all_planes", "beside_older_families", "mixed"])
def test_reader(counters, want):
    read = harness.Bench(ROOT).reader(NAME)
    assert read({"counters": counters}) == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    {}, PARENT, frames(rows=108), frames(rows=0, transposed=0, planes=0),
], ids=["empty", "parent", "every_frame_one_copy", "no_frame_in_window"])
def test_nothing_against_the_grain_gives_none(counters):
    read = harness.Bench(ROOT).reader(NAME)
    assert read({"counters": counters}) is None


def declared() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def without_this_entry(doc: dict) -> dict:
    doc = copy.deepcopy(doc)
    assert doc["per_layer"].pop()["name"] == NAME
    return doc


def test_declared_last_on_its_cells_and_found_by_name():
    doc = declared()
    bench = harness.Bench(ROOT)
    assert doc["per_layer"][-1] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "media host",
        "moves": "pass_rate", "workloads": CELLS}
    assert os.path.isfile(bench.find("metrics", NAME + ".py"))
    assert set(CELLS) <= {w["name"] for w in doc["workloads"]}
    for cell in {w["name"] for w in doc["workloads"]}:
        listed = NAME in [m["name"] for m in bench.metrics_for(cell, "per_layer")]
        assert listed == (cell in CELLS)


@pytest.mark.parametrize("case,inner", [
    ("test_declared_last_on_the_labeled_cell_and_found_by_name", None),
    ("test_what_the_labeled_cell_pinned_holds_but_for_this_entry",
     "test_declared_with_the_cell_and_found_by_name"),
    ("test_what_the_labeled_cell_pinned_holds_but_for_this_entry",
     "test_what_pr38_pinned_is_as_it_was_with_this_cell_appended"),
])
def test_what_the_entry_before_pinned_holds_but_for_this_entry(
        case, inner, monkeypatch):
    doc = declared()
    before = without_this_entry(doc)
    assert {k: v for k, v in doc.items() if k != "per_layer"} == \
        {k: v for k, v in before.items() if k != "per_layer"}
    assert before["per_layer"] == doc["per_layer"][:-1]
    load = harness.Bench.__init__

    def load_before(self, root=harness.ROOT):
        load(self, root)
        self.doc = without_this_entry(self.doc)

    monkeypatch.setattr(harness.Bench, "__init__", load_before)
    monkeypatch.setattr(decode_work, "declared",
                        lambda: without_this_entry(declared()))
    run = getattr(decode_work, case)
    if inner is None:
        run()
    else:
        run(inner, monkeypatch)
