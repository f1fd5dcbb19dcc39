"""`fetch_fd_identity_share` (PR 37) on a hand-made `ctx`: the share it
computes from `sd_identifier_identity_total{source}`, None (never 0,
never an error) on a program without that counter, as PR 37's parent
is, and how it is declared."""

import os

import pytest

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "fetch_fd_identity_share"
INDEX = ["homedir.cold", "homedir.rescan", "photolib.raw"]

#: what the parent's program counts of the row loop: its five stages
PARENT = {"sd_identifier_stage_seconds{stage=stat}.sum": 0.5,
          "sd_identifier_stage_seconds{stage=stat}.count": 4.0,
          "sd_identifier_stage_seconds{stage=journal}.sum": 0.5,
          "sd_identifier_messages_total{layout=whole}": 1900.0}


def identities(by_fd, by_path):
    return {"sd_identifier_identity_total{source=descriptor}": float(by_fd),
            "sd_identifier_identity_total{source=path}": float(by_path)}


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(ROOT)


@pytest.mark.parametrize("counters,want", [
    (identities(1996, 4), 99.8),            # a cold pass: four empty files
    (identities(2080, 0), 100.0),           # every file sampled
    (identities(5, 10), 100.0 / 3),         # a rescan: five new, ten rewritten
    (identities(0, 7), 0.0),                # the journal knew every file
    ({**PARENT, **identities(3, 1)}, 75.0),
    ({"sd_identifier_identity_total{source=descriptor}": 2.0}, 100.0),
], ids=["cold", "sampled", "rescan", "all_known", "beside_the_stages",
        "one_series"])
def test_share_of_identities_taken_from_the_descriptor(bench, counters, want):
    assert bench.reader(NAME)({"counters": counters}) == pytest.approx(want)


@pytest.mark.parametrize("counters", [{}, PARENT, identities(0, 0)],
                         ids=["empty", "parent", "no_identity_in_window"])
def test_nothing_to_read_gives_none(bench, counters):
    assert bench.reader(NAME)({"counters": counters}) is None


def test_declared_with_its_cells_and_found_by_name(bench):
    declared = {m["name"]: m for m in bench.doc["per_layer"]}
    assert declared[NAME] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "feeder",
        "moves": "pass_rate", "workloads": INDEX}
    assert os.path.isfile(bench.find("metrics", NAME + ".py"))
    # the cells that report `fetch_stat_us_per_file`, and no other
    assert declared["fetch_stat_us_per_file"]["workloads"] == INDEX
    for cell in {w["name"] for w in bench.doc["workloads"]}:
        listed = NAME in [m["name"] for m in bench.metrics_for(cell, "per_layer")]
        assert listed == (cell in INDEX)


def test_appended_and_nothing_before_it_moved(bench):
    """Holds what `test_index_path_readers.py::
    test_the_thirteen_are_appended_and_nothing_before_them_moved` held
    (it pins the list's end, so tier-1 leaves it out since this entry):
    PR 36's thirteen where they were, this one after them."""
    from benchmark.tests.test_index_path_readers import DECLARED

    names = [m["name"] for m in bench.doc["per_layer"]]
    assert names[53:66] == list(DECLARED)
    assert names[52] == "heif_exif_ms_per_image"
    assert names.index(NAME) == 66
    assert len(names) == len(set(names))
