"""The trace reduction on a recorded profile: `data/rescan.xplane.pb` is
the `--trace 1` run of `homedir.rescan`, seed 204, on one TPU v5 lite
(PR 24, chip call 1), stripped to the device plane's op and module
events and the window annotation. The stripped file reduces to the same
numbers as the 19 MB original did."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
JOBS = [("indexer", 0.6, 3.9), ("file_identifier", 3.9, 4.8),
        ("media_processor", 4.8, 4.83)]


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(HERE, "..", "kernels.json")) as f:
        kernels = json.load(f)
    return tr.reduce_file(os.path.join(HERE, "data", "rescan.xplane.pb"),
                          JOBS, kernels)


def test_recorded_trace_reduces_to_known_numbers(reduced):
    assert reduced["chips"] == 1
    assert reduced["window_s"] == pytest.approx(23.770628311, abs=1e-6)
    assert reduced["busy_s"] == pytest.approx(0.002523307, abs=1e-8)
    assert reduced["modules"] == {"jit_impl": [34, pytest.approx(0.002537814, abs=1e-8)]}
    assert reduced["kernels"]["hash"]["dispatches"] == 34
    assert reduced["kernels"]["resize"] == {"dispatches": 0, "seconds": 0}
    name, seconds = reduced["breakdown"]["device_ops"][0]
    assert name.startswith("%run.1 = u32[8,2048]") and len(name) <= tr.NAME_CHARS
    assert seconds == pytest.approx(0.000253802, abs=1e-8)
    assert len(reduced["breakdown"]["device_ops"]) == 10


def test_idle_gaps_go_to_the_job_that_covers_them(reduced):
    idle = dict(map(tuple, reduced["breakdown"]["idle_gaps"]))
    assert idle["indexer"] == pytest.approx(3.3, abs=1e-6)      # no device work
    assert idle["file_identifier"] == pytest.approx(0.899458546, abs=1e-6)
    assert idle["between_jobs"] == pytest.approx(19.538646458, abs=1e-6)
    total = sum(v for k, v in idle.items() if "." not in k)
    assert total == pytest.approx(reduced["window_s"] - reduced["busy_s"], abs=1e-4)
    assert len(reduced["breakdown"]["idle_gaps"]) <= 10


def test_interval_arithmetic():
    assert tr.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.merged([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    out = tr.attribute_gaps([(0.0, 10.0)], [("a", 1.0, 4.0), ("b", 6.0, 7.0)])
    assert out["a"] == [3.0, 3.0] and out["b"] == [1.0, 1.0]
    assert out["between_jobs"] == [6.0, 3.0]


def test_a_trace_without_a_tpu_plane_is_refused():
    with pytest.raises(ValueError):
        tr.reduce_planes([("/host:CPU", [("", [("x", 0, 10)])])], [], {})
